"""End-to-end live integration: real model, real codec, real paged memory.

Covers the paper's "lossless accuracy" property at system level: a request
whose prefix KV is fetched+restored from the remote store must produce the
same generations as full prefill (up to the shared int8 quantization step).

Tiny-model fixtures (tiny_cfg / tiny_params / donor_kv / registered_store)
come from conftest.py.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.cluster.storage import KVStore
from repro.models import transformer as tf
from repro.serving import paged_model
from repro.serving.engine import LiveEngine
from repro.paged.cache import PagedKVCache


#: prompt lengths of a decode batch, so the new tokens sit at distinct
#: positions; at 8 rows a page the first one opens its sequence's third
#: page
PROMPT_LENS = (16, 21, 11, 30)
PAGE = 8
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _prefilled(cfg, params, lens):
    """A paged cache holding a prefilled random prompt of each length
    (sequence ids 0..), the prompts, their prefill logits and the next
    token of each."""
    rng = np.random.default_rng(0)
    cache = PagedKVCache(cfg, n_pages=64, page_size=PAGE,
                         dtype=params["embed"].dtype)
    prompts, prefill_logits = [], []
    for sid, n in enumerate(lens):
        tokens = rng.integers(0, cfg.vocab_size, n)
        cache.add_seq(sid, n + PAGE)
        logits, kvs = paged_model.prefill_collect_kv(
            params, cfg, jnp.asarray(tokens[None]))
        for layer, (k, v) in enumerate(kvs):
            cache.write_prefill(layer, sid, k[0], v[0])
        prompts.append(tokens)
        prefill_logits.append(np.asarray(logits[0]))
    nxt = [int(np.argmax(lg)) for lg in prefill_logits]
    return cache, prompts, prefill_logits, nxt


def _decode(cfg, params, cache, nxt, lens):
    return paged_model.decode_paged(
        params, cfg, jnp.asarray(nxt, jnp.int32),
        jnp.asarray(lens, jnp.int32), cache, list(range(len(lens))))


@functools.lru_cache(maxsize=None)
def _model(arch):
    """A reduced config of ``arch`` and its float32 parameters."""
    import jax
    from repro.configs import get_config, reduce_config
    cfg = reduce_config(get_config(arch))
    return cfg, tf.init_params(cfg, jax.random.PRNGKey(0))


# lwm-7b: the tiny model of the other tests; qwen1.5-110b: q/k/v biases;
# deepseek-moe-16b: a dense first layer kept apart from the stacked MoE
# layers, so the step picks weights from both layouts
@pytest.mark.parametrize("arch,batch", [
    ("lwm-7b", 1), ("lwm-7b", 2), ("lwm-7b", 3), ("lwm-7b", 4),
    ("qwen1.5-110b", 2), ("deepseek-moe-16b", 2)])
def test_paged_decode_matches_dense_decode(arch, batch):
    """Paged decode path == dense-cache decode path on the same model:
    each sequence of a paged decode batch against the dense decode of
    that sequence alone (the dense step takes one scalar position)."""
    cfg, params = _model(arch)
    lens = PROMPT_LENS[:batch]
    cache, prompts, prefill_logits, nxt = _prefilled(cfg, params, lens)
    lp = np.asarray(_decode(cfg, params, cache, nxt, lens))
    for b, tokens in enumerate(prompts):
        dense_cache = tf.init_cache(cfg, 1, 32)
        logits_d, dense_cache = tf.prefill(params, cfg,
                                           tokens=jnp.asarray(tokens[None]),
                                           cache=dense_cache)
        np.testing.assert_allclose(prefill_logits[b],
                                   np.asarray(logits_d[0, 0]), rtol=2e-4,
                                   atol=2e-4)
        ld, _ = tf.decode_step(params, cfg, jnp.asarray([nxt[b]]),
                               jnp.int32(lens[b]), dense_cache)
        np.testing.assert_allclose(lp[b], np.asarray(ld[0]), rtol=3e-4,
                                   atol=3e-4)


def test_second_decode_step_compiles_nothing(tiny_cfg, tiny_params):
    """The step's programs are keyed on shapes, never on positions, tokens
    or the layer: the next step of the same batch compiles nothing."""
    import jax
    lens = PROMPT_LENS
    cache, _, _, nxt = _prefilled(tiny_cfg, tiny_params, lens)
    logits = np.asarray(_decode(tiny_cfg, tiny_params, cache, nxt, lens))
    compiles = []

    def on_event(name, secs, **kw):
        if name == COMPILE_EVENT:
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        jax.block_until_ready(_decode(
            tiny_cfg, tiny_params, cache, list(np.argmax(logits, -1)),
            [n + 1 for n in lens]))
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert compiles == []


def test_decode_step_reads_nothing_back_to_the_host(tiny_cfg, tiny_params,
                                                   monkeypatch):
    """No device-to-host read in a step, its compiles included. The
    transfer guard refuses one on an accelerator; on the CPU an array's
    host copy is no transfer, so a read of any array's value is refused
    as well."""
    import jax
    from jax._src import array
    lens = PROMPT_LENS
    cache, _, _, nxt = _prefilled(tiny_cfg, tiny_params, lens)

    def refuse(self):
        raise AssertionError(f"host read of a {self.shape} array")

    with monkeypatch.context() as m:
        m.setattr(array.ArrayImpl, "_value", property(refuse))
        with jax.transfer_guard_device_to_host("disallow"):
            logits = _decode(tiny_cfg, tiny_params, cache, nxt, lens)
    assert np.isfinite(np.asarray(logits)).all()


@pytest.mark.slow
@pytest.mark.parametrize("policy", ["kvfetcher", "fetch_agnostic"])
def test_engine_reuse_matches_full_prefill(policy, tiny_cfg, tiny_params,
                                           registered_store):
    CFG, PARAMS = tiny_cfg, tiny_params
    rng = np.random.default_rng(1)
    prefix_tokens = rng.integers(0, CFG.vocab_size, 48)
    suffix_tokens = rng.integers(0, CFG.vocab_size, 8)
    full = np.concatenate([prefix_tokens, suffix_tokens])

    store, key = registered_store(prefix_tokens)

    # engine A: no reuse
    eng_a = LiveEngine(PARAMS, CFG, KVStore(), policy=policy)
    ra = eng_a.submit(full, max_new_tokens=4)
    eng_a.run()
    # engine B: prefix fetched from the store
    eng_b = LiveEngine(PARAMS, CFG, store, policy=policy)
    rb = eng_b.submit(full, reuse_prefix=key, reuse_tokens=48,
                      max_new_tokens=4)
    eng_b.run()

    assert ra.t_first_token is not None and rb.t_first_token is not None
    assert eng_b.stats.restored_tokens == 48 * 2  # k and v
    assert eng_b.stats.fetched_bytes > 0
    # "lossless" at the system level: identical generations
    assert eng_a.outputs[ra.rid] == eng_b.outputs[rb.rid]
    # frame-wise restoration buffer stays tiny (paper Fig. 24)
    assert eng_b.stats.restore_buffer_high_water < 1_000_000


@pytest.mark.slow
def test_engine_mixed_batch_no_interference(tiny_cfg, tiny_params,
                                            registered_store):
    """A fetching request must not delay non-reuse requests (kvfetcher)."""
    CFG, PARAMS = tiny_cfg, tiny_params
    rng = np.random.default_rng(2)
    prefix_tokens = rng.integers(0, CFG.vocab_size, 32)
    store, key = registered_store(prefix_tokens)
    eng = LiveEngine(PARAMS, CFG, store, policy="kvfetcher", max_running=4)
    rng2 = np.random.default_rng(3)
    r_fetch = eng.submit(np.concatenate([prefix_tokens,
                                         rng2.integers(0, CFG.vocab_size,
                                                       4)]),
                         reuse_prefix=key, reuse_tokens=32,
                         max_new_tokens=2)
    r_plain = eng.submit(rng2.integers(0, CFG.vocab_size, 16),
                         max_new_tokens=2)
    eng.run()
    assert r_plain.t_first_token is not None
    assert r_fetch.t_first_token is not None
    assert len(eng.finished) == 2
