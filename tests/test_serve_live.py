"""The live entry point (`repro.launch.serve.serve_live`), the checks that
`chip_smoke.py` runs on its result, and the launcher's guards: the
device decides the cost model, the simulator refuses a chip it has no
decode table for, and the compile cache stays where it is put.

Serving runs here on the CPU with interpreted kernels and a model cut
in width as well as depth; `chip_smoke.py` runs the same path on a TPU
at Yi-34B's published widths.
"""
import dataclasses
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.costmodel import CHIPS, chip_for_device
from repro.configs import get_config
from repro.launch import serve
from repro.serving import verify

ROOT = pathlib.Path(__file__).resolve().parents[1]


#: Yi-34B's head geometry (56/8) at a CPU-sized width and depth
TINY_YI = dataclasses.replace(get_config("yi-34b"), num_layers=2,
                              d_model=256, num_heads=56, num_kv_heads=8,
                              head_dim=32, d_ff=512, vocab_size=512)


def serve_tiny(on_logits):
    return serve.serve_live(TINY_YI, prefix_len=64, suffix_len=16,
                            new_tokens=3, plain_len=24,
                            chip=CHIPS["tpu-v5e"], on_logits=on_logits)


@pytest.fixture(scope="module")
def tiny_logits():
    return verify.LogitsRecorder()


@pytest.fixture(scope="module")
def tiny_run(tiny_logits):
    return serve_tiny(tiny_logits)


def test_serve_live_serves_reuse_and_plain_requests(tiny_run, tiny_logits):
    eng = tiny_run.engine
    assert len(tiny_run.reuse) == 3
    for r in tiny_run.reuse:
        assert r.storage_hit == "full" and r.fetch_done is not None
        assert r.reuse_tokens == 64
    assert tiny_run.plain.reuse_tokens == 0
    for r in tiny_run.reuse + [tiny_run.plain]:
        assert len(eng.outputs[r.rid]) == 3 == len(tiny_logits[r.rid])
        assert tiny_logits[r.rid][0].shape == (512,)
        # the recorded logits are the ones the tokens were taken from
        assert [int(lg.argmax()) for lg in tiny_logits[r.rid]] == \
            eng.outputs[r.rid]
    assert not hasattr(eng, "logits")  # the engine itself keeps none
    assert eng.cache.k_pages.dtype == jnp.bfloat16
    assert set(tiny_run.phase_seconds) == {
        "init_weights", "donor_prefill", "encode_register", "serve"}


def test_served_kernels_match_ref_and_run_interpreted_off_tpu(tiny_run):
    k = verify.check_kernels(tiny_run)
    assert k["kv_restore_max_abs_err"] == 0.0
    assert k["paged_attention_max_abs_err"] <= k["paged_attention_atol"]
    # on the CPU the wrappers pick the interpreter, which lowers to
    # plain HLO; chip_smoke.py requires the Mosaic call on the TPU
    assert not k["kv_restore_mosaic"] and not k["paged_attention_mosaic"]


def test_reuse_logits_match_full_prefill(tiny_run, tiny_logits):
    errs = verify.check_reuse_logits(tiny_run, tiny_logits)
    assert set(errs) == {r.rid for r in tiny_run.reuse + [tiny_run.plain]}
    assert errs[tiny_run.plain.rid] == 0.0  # no reuse: same computation
    tol = verify.kv_int8_logit_tolerance(2, jnp.bfloat16)
    assert all(0.0 < errs[r.rid] <= tol for r in tiny_run.reuse)


def test_check_logits_rejects_unrelated_logits(tiny_run, tiny_logits):
    """The budget is far from what a wrong prefix gives."""
    a, b = tiny_run.reuse[:2]
    tol = verify.kv_int8_logit_tolerance(2, jnp.bfloat16)
    with pytest.raises(AssertionError, match="relative L2"):
        verify.check_logits(tiny_logits[a.rid][0], tiny_logits[b.rid][0],
                            tol, "unrelated")


def test_shard_tolerance_rejects_one_device_heads_fault(
        tiny_run, tiny_logits, monkeypatch):
    """One device's share of heads lost in one layer's decode attention
    (2 of 8 KV heads, as on a 1x4 mesh) lands outside the sharded
    engine's budget, while the sound run repeats itself exactly."""
    from repro.paged.cache import PagedKVCache

    attend = PagedKVCache.attend
    quarter = TINY_YI.num_heads // 4

    def faulty(self, layer, q, *args):
        out = attend(self, layer, q, *args)
        return out.at[:, quarter:2 * quarter].set(0) if layer == 0 else out

    tol = verify.shard_logit_tolerance(2, jnp.bfloat16)
    sound = verify.LogitsRecorder()
    again = serve_tiny(sound)
    monkeypatch.setattr(PagedKVCache, "attend", faulty)
    bad = verify.LogitsRecorder()
    broken = serve_tiny(bad)
    for r in tiny_run.reuse + [tiny_run.plain]:
        err, steps = verify.check_streams(
            sound[r.rid], again.engine.outputs[r.rid], tiny_logits[r.rid],
            tiny_run.engine.outputs[r.rid], 0.0, "sound")
        assert err == 0.0 and steps == 3
        # step 0 comes from prefill, which the fault does not touch
        verify.check_logits(bad[r.rid][0], tiny_logits[r.rid][0], 0.0,
                            "first token")
        with pytest.raises(AssertionError, match="step 1"):
            verify.check_streams(bad[r.rid], broken.engine.outputs[r.rid],
                                 tiny_logits[r.rid],
                                 tiny_run.engine.outputs[r.rid], tol,
                                 "one device's heads zeroed")


def test_check_streams_stops_at_token_divergence():
    want = [np.array([1.0, 0.0]), np.array([0.0, 1.0]),
            np.array([5.0, 5.0])]
    got = [w + 1e-3 for w in want[:2]] + [np.array([-9.0, 9.0])]
    err, steps = verify.check_streams(got, [0, 1, 0], want, [0, 0, 0],
                                      0.01, "x")
    assert steps == 2 and err <= 0.01  # step 2 follows different tokens
    with pytest.raises(AssertionError, match="x step 2"):
        verify.check_streams(got, [0, 0, 0], want, [0, 0, 0], 0.01, "x")
    with pytest.raises(AssertionError, match="non-finite"):
        verify.check_logits([np.nan, 1.0], [1.0, 1.0], 1.0, "x")


def test_int8_tolerance_adds_over_layers_and_dtype():
    f32 = verify.kv_int8_logit_tolerance(4, jnp.float32)
    bf16 = verify.kv_int8_logit_tolerance(4, jnp.bfloat16)
    assert f32 == pytest.approx(4 * 2 / 127, rel=1e-5)
    assert bf16 == pytest.approx(4 * (2 / 127 + 2 ** -7))
    assert verify.kv_int8_logit_tolerance(2, jnp.float32) == \
        pytest.approx(f32 / 2)


def test_shard_tolerance_is_rounding_only():
    """Two dtype eps per layer, with no int8 term: both sides restore
    the same frames."""
    assert verify.shard_logit_tolerance(4, jnp.bfloat16) == 4 * 2 * 2 ** -7
    assert verify.shard_logit_tolerance(2, jnp.float32) == 2 * 2 * 2 ** -23
    assert verify.shard_logit_tolerance(4, jnp.bfloat16) < \
        verify.kv_int8_logit_tolerance(4, jnp.bfloat16)


def test_cost_model_comes_from_the_device_kind():
    assert chip_for_device("TPU v5 lite") is CHIPS["tpu-v5e"]
    with pytest.raises(ValueError, match="no cost model"):
        chip_for_device("cpu")


def test_serve_live_refuses_an_unknown_device():
    """No chip given and a device with no cost model: an error, not a
    stand-in chip."""
    cfg = serve.cut_layers(get_config("yi-34b"), 1)
    with pytest.raises(ValueError, match="no cost model"):
        serve.serve_live(cfg)


def test_cut_layers_keeps_every_width():
    full = get_config("yi-34b")
    cut = serve.cut_layers(full, 4)
    assert cut.num_layers == 4
    assert dataclasses.replace(cut, num_layers=60) == full
    with pytest.raises(ValueError):
        serve.cut_layers(full, 61)


def test_live_refuses_chip(monkeypatch, capsys):
    """--live models the device it runs on; --chip cannot relabel it."""
    monkeypatch.setattr(sys, "argv", ["serve", "--live", "--chip", "h20"])
    with pytest.raises(SystemExit) as exc:
        serve.main()
    assert exc.value.code == 2
    assert "--chip applies to --simulate only" in capsys.readouterr().err


def test_simulate_refuses_tpu_v5e(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["serve", "--simulate", "--chip",
                                      "tpu-v5e"])
    with pytest.raises(SystemExit, match="no TPU v5e decode table"):
        serve.main()


def test_compile_cache_env_wins(monkeypatch):
    import jax
    from repro.launch.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_ignored_dir(monkeypatch):
    import jax
    from repro.launch.compile_cache import CACHE_DIR, use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(CACHE_DIR) == \
            jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert CACHE_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{CACHE_DIR.name}/" in ignored


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_chip_smoke_fails_without_tpu(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "chip_smoke.py", *argv],
                         cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=120)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "no TPU" in res.stderr


def test_chip_smoke_help_touches_no_device():
    # JAX_PLATFORMS names a backend that does not exist: any device
    # lookup would fail, so a clean --help proves none happened
    env = dict(os.environ, JAX_PLATFORMS="nonexistent")
    res = subprocess.run([sys.executable, "chip_smoke.py", "--help"],
                         cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=60)
    assert res.returncode == 0 and "--chips" in res.stdout
