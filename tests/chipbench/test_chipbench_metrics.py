"""Each per-layer metric's operations and bytes against hand counts at
small shapes, and each reader against a synthetic traced window."""
import dataclasses

import numpy as np
import pytest

from chipbench import bench
from chipbench import trace as tr
from chipbench.metrics_context import Context

PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
#: the architecture module of the Yi configurations
LLAMA = bench.architecture(bench.config("yi-34b-l4"))


@dataclasses.dataclass
class Cfg:
    d_model: int = 8
    num_heads: int = 4
    num_kv_heads: int = 2
    head_dim: int = 2
    d_ff: int = 16
    vocab_size: int = 10
    num_layers: int = 3


@dataclasses.dataclass
class Sent:
    prompt: np.ndarray
    n_pre: int


@dataclasses.dataclass
class Clients:
    sent: dict
    token_log: list
    steps: list


def ctx(**kw):
    base = dict(trace=tr.Trace([], [], 1), window_ns=(0.0, 1e9),
                window_s=1.0, cfg=Cfg(), arch=LLAMA, peaks=PEAKS,
                restore_calls=[], attend_calls=[],
                clients=Clients({}, [], []), t0=0.0, t_stop=1.0, compiles=0)
    base.update(kw)
    return Context(**base)


def test_kv_restore_bytes_and_flops_by_hand():
    m = bench.metric_reader("kv_restore_roofline")
    # 3 tokens x 2 heads x 4 dims: 24 uint8 read, 2 float32 scales,
    # 3 int32 slots, 24 bf16 rows written
    assert m.call_bytes(3, 2, 4, 2) == 24 + 8 + 12 + 48
    assert m.call_flops(3, 2, 4) == 48


def test_kv_restore_share_from_bytes_over_kernel_time():
    m = bench.metric_reader("kv_restore_roofline")
    t = tr.Trace([tr.Event("%custom-call.1 = custom-call()", 0.0, 4e9)], [], 1,
                 [tr.Event("jit_kv_restore_pallas(7)", 0.0, 5e9)])
    calls = [((3, 2, 4), 2, 1, 4)]  # 92 bytes -> 9.2 s at 10 B/s
    assert m.read(ctx(trace=t, restore_calls=calls)) == \
        pytest.approx(100 * 9.2 / 4.0)
    # nothing to read: no calls, or no kernel in the trace
    assert m.read(ctx(trace=t)) is None
    assert m.read(ctx(restore_calls=calls)) is None


def test_paged_attention_flops_and_bytes_by_hand():
    m = bench.metric_reader("paged_attention_roofline")
    lens = np.array([3, 5])
    assert m.call_flops(4, 2, lens) == 4 * 4 * 2 * 8
    # q and out: 2 x 2 seqs x 4 heads x 2 dims x 2 bytes; K and V rows:
    # 2 x 8 x 2 heads x 2 dims x 2 bytes; context lens: 8
    assert m.call_bytes(4, 2, 2, 2, lens) == 64 + 128 + 8
    t = tr.Trace([tr.Event("%custom-call.3 = custom-call()", 0.0, 1e9)], [], 1,
                 [tr.Event("jit_paged_attention_pallas(8)", 0.0, 2e9)])
    calls = [((2, 4, 2), (9, 4, 2, 2), 2, lens)]
    got = m.read(ctx(trace=t, attend_calls=calls))
    assert got == pytest.approx(100 * max(128 / 100.0, 200 / 10.0) / 1.0)


def test_step_mfu_counts_prefill_and_decode_by_hand():
    m = bench.metric_reader("step_mfu")
    a = LLAMA
    cfg = Cfg()
    # per token per layer: q,k,v 2*8*(4+4)*2, out 2*4*2*8, mlp 2*3*8*16
    per_layer = 2 * (8 * 8 * 2 + 4 * 2 * 8 + 3 * 8 * 16)
    assert a.layer_matmul_flops(cfg) == per_layer
    assert a.attention_flops(cfg, 7) == 4 * 4 * 2 * 7
    head = 2 * 8 * 10
    # a 2-token suffix over a 5-token prefix: contexts 6 and 7
    assert a.prefill_flops(cfg, 5, 2) == \
        3 * (2 * per_layer + 4 * 4 * 2 * (6 + 7)) + head
    assert a.decode_flops(cfg, 9) == 3 * (per_layer + 4 * 4 * 2 * 9) + head
    sent = {0: Sent(np.zeros(7, np.int64), 5)}
    log = [(0.1, 0, 0), (0.2, 0, 1), (5.0, 0, 2)]  # the last is outside
    got = m.read(ctx(clients=Clients(sent, log, []), window_s=2.0))
    want = a.prefill_flops(cfg, 5, 2) + a.decode_flops(cfg, 8)
    assert got == pytest.approx(100 * want / (2.0 * 100.0))
    assert m.read(ctx()) is None


def test_step_mfu_counts_the_whole_prompt_where_nothing_is_reused():
    m = bench.metric_reader("step_mfu")
    cfg = Cfg()
    per_layer = LLAMA.layer_matmul_flops(cfg)
    # 3 prompt tokens computed from position 0: contexts 1, 2 and 3
    assert LLAMA.prefill_flops(cfg, 0, 3) == \
        3 * (3 * per_layer + 4 * 4 * 2 * (1 + 2 + 3)) + 2 * 8 * 10
    sent = {4: Sent(np.zeros(3, np.int64), 0)}
    got = m.read(ctx(clients=Clients(sent, [(0.5, 4, 0)], [])))
    assert got == pytest.approx(
        100 * LLAMA.prefill_flops(cfg, 0, 3) / (1.0 * 100.0))


def test_step_mfu_asks_the_cells_architecture():
    """The count is the architecture module's: a module of another
    architecture, given as ``ctx.arch``, sets it."""
    class Toy:
        @staticmethod
        def prefill_flops(cfg, n_pre, s):
            return 1000 * s + n_pre

        @staticmethod
        def decode_flops(cfg, context):
            return 10 * context

    m = bench.metric_reader("step_mfu")
    sent = {0: Sent(np.zeros(7, np.int64), 5)}
    log = [(0.1, 0, 0), (0.2, 0, 1)]
    got = m.read(ctx(clients=Clients(sent, log, []), arch=Toy))
    assert got == pytest.approx(100 * (2005 + 80) / (1.0 * 100.0))


def test_decode_batch_idle_share_and_compiles():
    mb = bench.metric_reader("decode_batch_mean")
    steps = [(0.1, 0), (0.2, 2), (0.3, 4), (2.0, 1)]
    assert mb.read(ctx(clients=Clients({}, [], steps))) == 3.0
    assert mb.read(ctx()) is None
    mi = bench.metric_reader("device_idle_share")
    t = tr.Trace([tr.Event("a", -5e8, 6e8), tr.Event("b", 5e8, 1e8)], [], 1)
    assert mi.read(ctx(trace=t)) == pytest.approx(100 * (1 - 0.2))
    assert mi.read(ctx()) is None
    assert bench.metric_reader("window_compiles").read(ctx(compiles=2)) == 2
