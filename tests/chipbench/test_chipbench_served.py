"""A whole run of the harness on the CPU at a tiny size, past the look
for a chip: a sound run is correct, and a run whose served path is
broken underneath is not. Also the float8 control, which the limit must
separate from the program. Both cells' traffic: documents stored and
reused (doc-qa), and the same prompts with nothing stored (doc-qa-fresh).

The model keeps a Yi-like head geometry at widths the CPU and the
Pallas interpreter can hold; the chip runs the same code at the
published widths.
"""
import dataclasses

import jax
import pytest

from chipbench import bench, run
from repro.cluster.costmodel import CHIPS
from repro.paged.cache import PagedKVCache
from repro.serving import paged_model

PEAKS = bench.peaks("TPU v5 lite")
TINY = dict(bench.config("yi-34b-l4"), name="tiny", hidden_size=64,
            intermediate_size=128, num_attention_heads=8,
            num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
            vocab_size=256)


def tiny_cell(workload):
    """``workload`` at a tiny size: its mix with two contexts of 32 and
    48 tokens, 8-token questions and 6-token answers, 2 clients."""
    wl = bench.workload(bench.benchmark(), workload)
    mix = bench.traffic(wl["traffic"])
    mix = dict(mix, question_tokens=8, answer_tokens=6, clients=2,
               requests_per_client=64, check_requests=3, grace_seconds=60)
    mix["documents" if "documents" in mix else "contexts"] = [32, 48]
    # the sound tiny program reads 0 (its greedy tokens are the
    # reference's); a broken one reads whole logits off
    limits = {"widest_logit_gap": {"limit": 0.05}}
    per_layer = [m["name"]
                 for m in bench.metrics_for(bench.benchmark(), workload, True)]
    return run.Cell("tiny", TINY, mix, limits, per_layer)


@pytest.fixture(scope="module")
def cell():
    c = tiny_cell("yi34b-doc-qa")
    # every per-layer reader, the restore path's and the others
    return dataclasses.replace(
        c, per_layer=[m["name"] for m in bench.benchmark()["per_layer"]])


@pytest.fixture(scope="module")
def fresh():
    return tiny_cell("yi34b-fresh-control")


def go(cell, seed, trace=False):
    return run.run_cell(cell, seed, 1.0, trace, devices=jax.devices(),
                        chip=CHIPS["tpu-v5e"], peaks=PEAKS)


def test_sound_run_is_correct_and_reports_every_end_to_end_metric(cell):
    out = go(cell, 2**33 + 11)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {
        "ttft_p50_s", "ttft_p95_s", "itl_p95_ms", "output_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "check"
    assert out["check"]["checked_requests"]["value"] >= 1


def test_traced_run_reports_what_it_can_read(cell):
    out = go(cell, 12, trace=True)
    assert out["correct"] is True, out["check"]
    m = out["metrics"]
    assert m["window_compiles"]["value"] == 0
    assert m["decode_batch_mean"]["value"] >= 1
    assert 0 < m["step_mfu"]["value"] < 100
    # the CPU has no device plane: no kernel, no idle share to read
    assert "kv_restore_roofline" not in m and "device_idle_share" not in m
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["window_s"] > 0


def test_restore_that_leaves_the_pages_unchanged_is_caught(cell,
                                                           monkeypatch):
    monkeypatch.setattr(PagedKVCache, "restore_tokens",
                        lambda self, *a, **k: None)
    out = go(cell, 13)
    assert out["correct"] is False
    assert out["check"]["widest_logit_gap"]["value"] > 0.05


def test_token_altered_where_it_is_produced_is_caught(cell, monkeypatch):
    decode = paged_model.decode_paged

    def altered(*a, **k):
        return jax.numpy.roll(decode(*a, **k), 1, axis=-1)

    monkeypatch.setattr(paged_model, "decode_paged", altered)
    out = go(cell, 14)
    assert out["correct"] is False
    assert out["check"]["widest_logit_gap"]["value"] > 0.05


def test_float8_control_fails_the_limit_the_program_passes(cell):
    """The control at this size, on fixed seeded prompts and tokens (so
    no engine timing picks the sample): its widest gap is over twice the
    tiny cell's limit, which the sound program meets above."""
    import numpy as np

    arch = bench.architecture(cell.conf)
    params = arch.init_weights(arch.model_config(cell.conf), 1)
    rng = np.random.default_rng(1)
    served = [(rng.integers(0, 256, 40), 32, rng.integers(0, 256, 16))
              for _ in range(4)]
    ref = bench.reference(cell.conf["reference"])
    ctl = ref.served_gaps(params, cell.conf, served, 64, control=True)
    assert [g.shape for g in ctl] == [(16,)] * 4
    limit = cell.limits["widest_logit_gap"]["limit"]
    assert max(float(g.max()) for g in ctl) > 2 * limit


def control_in_the_programs_place(cell, workload, monkeypatch):
    """A whole run whose judged tokens are those the float8 control puts
    first at each served position (the control in the program's place),
    held to the limit committed for the chip cell ``workload``. The
    model is wider than the fixture's, so that its logits spread as a
    real model's do: at hidden 64 the control reads 0.04, at 256 0.2
    (the sound program there 0.007). The window holds the 8 requests
    that 8 clients send as it opens and no more than a few others
    (0.01 s), whatever the host's speed: the control's widest gap is a
    maximum over the checked tokens, and over the fewer requests that a
    loaded host finished in a 2-s window it fell under the limit."""
    check = run.check
    monkeypatch.setattr(run, "check",
                        lambda *a, **k: check(*a, **dict(k, control=True)))
    committed = bench.limits(workload)
    wider = dataclasses.replace(
        cell, limits=committed,
        conf=dict(cell.conf, hidden_size=256, intermediate_size=512,
                  head_dim=32, vocab_size=1024),
        mix=dict(cell.mix, clients=8, check_requests=8))
    out = run.run_cell(wider, 15, 0.01, False, devices=jax.devices(),
                       chip=CHIPS["tpu-v5e"], peaks=PEAKS)
    assert out["check"]["checked_requests"]["value"] == 8
    return out, committed["widest_logit_gap"]["limit"]


def test_float8_control_in_the_programs_place_is_not_correct(cell,
                                                             monkeypatch):
    out, limit = control_in_the_programs_place(cell, "yi34b-doc-qa",
                                               monkeypatch)
    assert out["check"]["widest_logit_gap"]["limit"] == limit
    assert out["check"]["widest_logit_gap"]["value"] > limit
    assert out["correct"] is False


def test_fresh_cell_stores_nothing_and_restores_nothing(fresh, monkeypatch):
    """With reuse off no document is stored, no donor prefill runs, no
    ``kv_restore`` is called, every request is prefilled whole
    (``n_pre`` 0), and the check samples a request of the longest
    prompt."""
    counts = {"donor": 0, "restore": 0}
    donor, restore = paged_model.donor_prefix_kv, PagedKVCache.restore_tokens

    def count(key, fn):
        def counted(*a, **k):
            counts[key] += 1
            return fn(*a, **k)
        return counted

    monkeypatch.setattr(paged_model, "donor_prefix_kv",
                        count("donor", donor))
    monkeypatch.setattr(PagedKVCache, "restore_tokens",
                        count("restore", restore))
    served = run.serve(fresh, 2**33 + 31, 1.0, True, chip=CHIPS["tpu-v5e"])
    try:
        store = served.clients.eng.store
        assert not store.catalog
        assert counts == {"donor": 0, "restore": 0}
        assert served.calls.restore == [] and served.calls.attend
        assert served.sent and served.failed == 0
        assert all(s.n_pre == 0 for s in served.clients.sent.values())
        longest = max(len(s.prompt) for s in served.sent)
        for seed in range(8):
            pick = run.sample_for_check(served.clients, served.t0,
                                        served.t_end, 1, seed)
            assert [len(s.prompt) for s in pick] == [longest]
        values, _, _ = run.per_layer(served, "TPU v5 lite", PEAKS)
    finally:
        served.release()
    assert values["full_prefill_ms"] > 0
    assert "suffix_prefill_ms" not in values
    assert "kv_wait_p50_s" not in values and "codec_mb_s" not in values


def test_sound_run_of_the_fresh_cell_is_correct(fresh):
    out = go(fresh, 2**33 + 32)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {
        "ttft_p50_s", "ttft_p95_s", "itl_p95_ms", "output_tok_s", "setup_s"}
    assert out["check"]["checked_requests"]["value"] >= 1


def test_token_altered_in_the_fresh_cell_is_caught(fresh, monkeypatch):
    decode = paged_model.decode_paged

    def altered(*a, **k):
        return jax.numpy.roll(decode(*a, **k), 1, axis=-1)

    monkeypatch.setattr(paged_model, "decode_paged", altered)
    out = go(fresh, 2**33 + 33)
    assert out["correct"] is False
    assert out["check"]["widest_logit_gap"]["value"] > 0.05


def test_full_prefill_that_leaves_the_pages_unwritten_is_caught(
        fresh, monkeypatch):
    """The prompt's K and V reach the pages as zeros: every decode step
    attends over a prompt that is not there."""
    write = PagedKVCache.write_prefill

    def unwritten(self, layer, seq_id, k, v, *a, **kw):
        return write(self, layer, seq_id, k * 0, v * 0, *a, **kw)

    monkeypatch.setattr(PagedKVCache, "write_prefill", unwritten)
    out = go(fresh, 2**33 + 34)
    assert out["correct"] is False
    assert out["check"]["widest_logit_gap"]["value"] > 0.05


def test_float8_control_in_the_fresh_cells_place_is_not_correct(
        fresh, monkeypatch):
    out, limit = control_in_the_programs_place(
        fresh, "yi34b-fresh-control", monkeypatch)
    assert out["check"]["widest_logit_gap"]["limit"] == limit
    assert out["check"]["widest_logit_gap"]["value"] > limit
    assert out["correct"] is False
