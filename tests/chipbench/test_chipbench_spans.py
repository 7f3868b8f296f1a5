"""The readers of the program's own spans (`chipbench/spans.py` and the
metrics that use it): window clipping, self time, the pairing of spans
with the harness's records, on synthetic traces; then a traced tiny run
on the CPU, in which every one of them reads a number and the
restore and attention spans match the harness's kernel calls one for
one."""
import dataclasses

import numpy as np
import pytest

from chipbench import bench, run, spans
from chipbench import trace as tr
from chipbench.metrics_context import Context
from repro.cluster.costmodel import CHIPS

PEAKS = bench.peaks("TPU v5 lite")
NEW = ("codec_mb_s", "h2d_mb_s", "restore_dispatches_per_ktok",
       "suffix_prefill_ms", "kv_wait_p50_s", "decode_step_ms",
       "page_write_share")


@dataclasses.dataclass
class Cfg:
    num_layers: int = 2


@dataclasses.dataclass
class Sent:
    t_send: float


@dataclasses.dataclass
class Clients:
    sent: dict
    token_log: list


def ev(name, start, dur):
    return tr.Event(name, float(start), float(dur))


def ctx(host=(), window=(100.0, 1100.0), restore_calls=(), sent=None,
        token_log=(), t0=10.0):
    """A traced window of 1000 ns on the trace's clock that opened at
    host time ``t0``."""
    return Context(trace=tr.Trace([], list(host), 0), window_ns=window,
                   window_s=1e-6, cfg=Cfg(), arch=None, peaks=PEAKS,
                   restore_calls=list(restore_calls), attend_calls=[],
                   clients=Clients(sent or {}, list(token_log)), t0=t0,
                   t_stop=t0 + 1e-6, compiles=0)


def read(name, c):
    return bench.metric_reader(name).read(c)


def test_named_keeps_whole_spans_inside_the_window_in_order():
    host = [ev("kvf.step", 900, 100), ev("kvf.step", 50, 100),
            ev("kvf.step", 1050, 100), ev("kvf.step", 100, 10),
            ev("other", 300, 10)]
    got = spans.named(ctx(host), "kvf.step")
    assert [(e.start_ns, e.dur_ns) for e in got] == [(100, 10), (900, 100)]
    assert spans.named(ctx(host, window=None), "kvf.step") == []


def test_inside_and_self_time():
    parents = [ev("p", 100, 100), ev("p", 300, 100)]
    kids = [ev("c", 110, 20), ev("c", 190, 20), ev("c", 250, 10),
            ev("c", 300, 100)]
    assert [e.start_ns for e in spans.inside(kids, parents)] == [110, 300]
    # children overlap each other and run past the span's end
    s = ev("s", 0, 100)
    kids = [ev("c", 10, 20), ev("c", 20, 20), ev("c", 90, 50),
            ev("c", 200, 5)]
    assert spans.self_ns(s, kids) == 100 - 30 - 10
    assert spans.self_ns(s, []) == 100


def test_suffix_prefill_self_time_leaves_out_its_waits():
    host = [ev("kvf.prefill.suffix", 100, 300),
            ev("kvf.prefill.await", 120, 100),
            ev("kvf.prefill.await", 250, 50),
            ev("kvf.prefill.suffix", 500, 100),
            ev("kvf.prefill.await", 510, 10),
            # a suffix prefill the window cuts is not counted
            ev("kvf.prefill.suffix", 1000, 200)]
    assert read("suffix_prefill_ms", ctx(host)) == \
        pytest.approx((150 + 90) / 2 / 1e6)
    assert read("suffix_prefill_ms", ctx()) is None


def test_full_prefill_self_time_leaves_out_its_page_writes():
    host = [ev("kvf.prefill.full", 100, 400),
            ev("kvf.cache.write", 300, 50), ev("kvf.cache.write", 400, 60),
            ev("kvf.prefill.full", 600, 100),
            # a decode step's write, outside any prefill
            ev("kvf.cache.write", 800, 20),
            # a full prefill the window cuts is not counted
            ev("kvf.prefill.full", 1050, 100)]
    assert read("full_prefill_ms", ctx(host)) == \
        pytest.approx((290 + 100) / 2 / 1e6)
    assert read("full_prefill_ms", ctx()) is None
    # the restore path's suffix prefills are not full prefills
    assert read("full_prefill_ms",
                ctx([ev("kvf.prefill.suffix", 100, 300)])) is None


def test_calls_forward_further_kernel_arguments_untouched():
    """A kernel that takes more than the recorded arguments (a window,
    a layer kind) is called with all of them, and still recorded."""
    seen = []

    class Cache:
        def _restore(self, *a, **k):
            seen.append(("restore", a[4:], k))
            return "r"

        def _attend(self, *a, **k):
            seen.append(("attend", a[5:], k))
            return "a"

    cache, calls = Cache(), run.Calls(on=True)
    calls.wrap(cache)
    pages = np.zeros((2, 4), np.float16)
    toks = np.zeros((3, 2, 4), np.uint8)
    assert cache._restore(pages, toks, np.zeros(2, np.float32),
                          np.zeros(3, np.int32), 7, kind="k") == "r"
    q = np.zeros((1, 4, 8), np.float16)
    kp = np.zeros((5, 16, 2, 8), np.float16)
    assert cache._attend(q, kp, kp, None, np.array([9]), window=512) == "a"
    assert cache._attend(q, kp, kp, None, np.array([9])) == "a"
    assert seen == [("restore", (7,), {"kind": "k"}),
                    ("attend", (), {"window": 512}), ("attend", (), {})]
    assert calls.restore == [((3, 2, 4), 2, 1, 4)]
    assert [c[:3] for c in calls.attend] == \
        [((1, 4, 8), (5, 16, 2, 8), 2)] * 2


def test_decode_step_median_and_page_write_share():
    host = [ev("kvf.decode.step", 100, 40), ev("kvf.decode.step", 200, 10),
            ev("kvf.decode.step", 300, 20), ev("kvf.decode.step", 1090, 20),
            ev("kvf.cache.write", 210, 5), ev("kvf.cache.write", 310, 45),
            ev("kvf.cache.write", 60, 50)]
    assert read("decode_step_ms", ctx(host)) == pytest.approx(20e-6)
    assert read("page_write_share", ctx(host)) == pytest.approx(5.0)
    assert read("decode_step_ms", ctx()) is None
    assert read("page_write_share", ctx()) is None


def restore_window(n_calls=3):
    """Two chunks: three frames of one layer's restore, each with its
    h2d copy; 4 tokens, 2 heads of 8 dims per call."""
    host = [ev("kvf.restore.chunk", 100, 300),
            ev("kvf.codec.frame", 110, 40),  # parse
            ev("kvf.codec.frame", 160, 60),
            ev("kvf.restore.h2d", 230, 10), ev("kvf.cache.restore", 250, 30),
            ev("kvf.codec.frame", 290, 20),
            ev("kvf.restore.h2d", 320, 30), ev("kvf.cache.restore", 360, 30),
            ev("kvf.restore.chunk", 500, 100),
            ev("kvf.codec.frame", 510, 30),
            ev("kvf.restore.h2d", 550, 20), ev("kvf.cache.restore", 570, 20),
            # a frame outside any chunk is not the restore path's
            ev("kvf.codec.frame", 700, 50)]
    calls = [((4, 2, 8), 2, 1, 4)] * n_calls
    return host, calls


def test_restore_readers_pair_spans_with_the_kernel_calls():
    host, calls = restore_window()
    c = ctx(host, restore_calls=calls)
    # 3 calls x 64 uint8 bytes over 150 ns of codec time
    assert read("codec_mb_s", c) == pytest.approx(192 / 1e6 / 150e-9)
    # and their 2 float32 scales each, over 60 ns of copies
    assert read("h2d_mb_s", c) == pytest.approx(3 * 72 / 1e6 / 60e-9)
    # 12 rows of 2 layers x K and V: 3 prefix tokens, 3 dispatches
    assert read("restore_dispatches_per_ktok", c) == pytest.approx(1000.0)


@pytest.mark.parametrize("calls", [0, 2, 4])
def test_restore_readers_read_nothing_unless_spans_and_calls_agree(calls):
    host, recorded = restore_window(calls)
    for name in ("codec_mb_s", "h2d_mb_s", "restore_dispatches_per_ktok"):
        assert read(name, ctx(host, restore_calls=recorded)) is None


def test_kv_wait_pairs_prefills_with_first_tokens_in_order():
    # window opens at host time 10.0 and trace time 100 ns
    host = [ev("kvf.prefill.suffix", 200, 50),   # rid 5, starts 10+1e-7
            ev("kvf.prefill.full", 400, 50),     # rid 6: no fetch
            ev("kvf.prefill.suffix", 600, 50),   # rid 7
            ev("kvf.prefill.suffix", 800, 50)]   # rid 4, sent before
    sent = {5: Sent(10.0 + 0.2e-7), 6: Sent(10.0), 7: Sent(10.0 + 1e-7),
            4: Sent(9.0), 3: Sent(9.0)}
    log = [(10.0 + 2e-7, 5, 0), (10.0 + 2.5e-7, 5, 1),
           (10.0 + 4e-7, 6, 0), (10.0 + 6e-7, 7, 0), (10.0 + 8e-7, 4, 0),
           (9.5, 3, 0)]  # a first token before the window: not paired
    got = read("kv_wait_p50_s", ctx(host, sent=sent, token_log=log))
    assert got == pytest.approx(np.median([1e-7 - 0.2e-7, 5e-7 - 1e-7]))
    # a prefill whose first token the harness did not see: no pairing
    assert read("kv_wait_p50_s",
                ctx(host, sent=sent, token_log=log[:-2])) is None
    assert read("kv_wait_p50_s", ctx(sent=sent, token_log=log)) is None


def test_no_program_span_reads_nothing():
    """A program that opens none of the spans gives no number and no
    error."""
    host = [ev("chipbench.window", 100, 1000), ev("chipbench.step", 200, 10)]
    _, calls = restore_window()
    for name in NEW:
        assert read(name, ctx(host, restore_calls=calls)) is None


@pytest.fixture(scope="module")
def cell():
    conf = dict(bench.config("yi-34b-l4"), name="tiny", hidden_size=64,
                intermediate_size=128, num_attention_heads=8,
                num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                vocab_size=256)
    mix = dict(bench.traffic("doc-qa"), documents=[32, 48],
               question_tokens=8, answer_tokens=6, clients=2,
               requests_per_client=64, check_requests=3, grace_seconds=60)
    per_layer = [m["name"] for m in bench.benchmark()["per_layer"]]
    return run.Cell("tiny", conf, mix, {}, per_layer)


def prefill_rids(path, window):
    """The ``rid`` argument of each prefill span inside ``window``, in
    order, read from the profile itself."""
    import jax
    lo, hi = window
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name == tr.HOST_PLANE \
                    and line.name.startswith(tr.HOST_LINE):
                out += [(ev.start_ns, dict(ev.stats)["rid"])
                        for ev in line.events
                        if ev.name.startswith("kvf.prefill.")
                        and ev.name != "kvf.prefill.await"
                        and lo <= ev.start_ns
                        and ev.start_ns + ev.duration_ns <= hi]
    return [rid for _, rid in sorted(out)]


def test_traced_run_reads_every_span_metric_and_matches_kernel_calls(cell):
    served = run.serve(cell, 2**33 + 21, 2.0, True, chip=CHIPS["tpu-v5e"])
    try:
        path = tr.find_xplane(served.log_dir)
        t = tr.load(path)
        win, = [e for e in t.host if e.name == "chipbench.window"]
        c = ctx(t.host, window=(win.start_ns, win.end_ns))
        assert len(spans.named(c, "kvf.cache.restore")) == \
            len(served.calls.restore) > 0
        assert len(spans.named(c, "kvf.cache.attend")) == \
            len(served.calls.attend) > 0
        # kv_wait_p50_s pairs prefills with first tokens by order: the
        # spans' own rids come in that order
        firsts = [rid for tm, rid, i in sorted(served.clients.token_log)
                  if i == 0 and served.t0 <= tm <= served.t_stop]
        assert prefill_rids(path, (win.start_ns, win.end_ns)) == firsts
        assert firsts
        values, _, _ = run.per_layer(served, "TPU v5 lite", PEAKS)
    finally:
        served.release()
    for name in NEW:
        assert np.isfinite(values[name]) and values[name] > 0, name
    assert values["page_write_share"] < 100
    assert values["kv_wait_p50_s"] < served.t_stop - served.t0
