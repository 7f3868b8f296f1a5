"""Compiles for a described TPU v5e host (v5e:2x2, no chip attached).

What interpret mode cannot show: that the served path's Pallas kernels
compile for the TPU at published KV widths (tiling, VMEM limits, casts
Mosaic lacks), and that the KV-head-sharded restore and decode
attention split the page array across four chips without gathering it.
Nothing here runs; the TPU compiler only accepts or refuses.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file. Where it cannot be described, these tests skip.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import kernels
from repro.configs import get_config
from repro.kernels.kv_restore.kv_restore import kv_restore_pallas
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_pallas,
)
from repro.paged.cache import sharded_attention, sharded_restore

ARCHS = ("yi-34b", "lwm-7b")  # GQA 56/8 and MHA 32/32, head_dim 128
DTYPES = (jnp.float32, jnp.bfloat16)
PAGE, N_PAGES, FRAME = 16, 512, 75  # page rows, pages, tokens per frame
BATCH, CTX = 4, 2112  # decode batch, context (2048 prefix + 64 suffix)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    """1x4 ("data", "model") mesh over the described chips."""
    devs = np.asarray(topo.devices[:4]).reshape(1, 4)
    return Mesh(devs, ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _widths(arch):
    cfg = get_config(arch)
    return cfg.num_heads, cfg.num_kv_heads, cfg.head_dim


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("arch", ARCHS)
def test_kv_restore_compiles_for_v5e(arch, dtype, one_chip,
                                     no_compile_cache):
    _, K, hd = _widths(arch)
    args = (_sds((N_PAGES * PAGE, K, hd), dtype, one_chip),
            _sds((FRAME, K, hd), jnp.uint8, one_chip),
            _sds((K,), jnp.float32, one_chip),
            _sds((FRAME,), jnp.int32, one_chip))
    fn = jax.jit(lambda *a: kv_restore_pallas(*a, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_attention_compiles_for_v5e(arch, dtype, one_chip,
                                          no_compile_cache):
    H, K, hd = _widths(arch)
    bps = -(-CTX // PAGE)
    args = (_sds((BATCH, H, hd), dtype, one_chip),
            _sds((N_PAGES, PAGE, K, hd), dtype, one_chip),
            _sds((N_PAGES, PAGE, K, hd), dtype, one_chip),
            _sds((BATCH, bps), jnp.int32, one_chip),
            _sds((BATCH,), jnp.int32, one_chip))
    fn = jax.jit(lambda *a: paged_attention_pallas(*a, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def _sharded_restore_args(mesh, K, hd):
    heads = NamedSharding(mesh, P(None, "model", None))
    return (_sds((N_PAGES * PAGE, K, hd), jnp.bfloat16, heads),
            _sds((FRAME, K, hd), jnp.uint8, heads),
            _sds((K,), jnp.float32, NamedSharding(mesh, P("model"))),
            _sds((FRAME,), jnp.int32, NamedSharding(mesh, P())))


def _sharded_attention_args(mesh, H, K, hd):
    pages = NamedSharding(mesh, P(None, None, "model", None))
    rep = NamedSharding(mesh, P())
    bps = -(-CTX // PAGE)
    return (_sds((BATCH, H, hd), jnp.bfloat16,
                 NamedSharding(mesh, P(None, "model", None))),
            _sds((N_PAGES, PAGE, K, hd), jnp.bfloat16, pages),
            _sds((N_PAGES, PAGE, K, hd), jnp.bfloat16, pages),
            _sds((BATCH, bps), jnp.int32, rep),
            _sds((BATCH,), jnp.int32, rep))


@pytest.mark.parametrize("op", ["restore", "attention"])
def test_head_sharded_kernels_keep_pages_split(op, mesh, monkeypatch,
                                               no_compile_cache):
    """Yi-34B's 8 KV heads at 2 per chip: each chip runs the Mosaic
    kernel on its own heads, and the program holds no all-gather."""
    # this backend is the CPU, so the wrappers would pick the
    # interpreter; compile what the chip would run
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    H, K, hd = _widths("yi-34b")
    if op == "restore":
        fn = sharded_restore(mesh, "model")
        args = _sharded_restore_args(mesh, K, hd)
    else:
        fn = sharded_attention(mesh, "model")
        args = _sharded_attention_args(mesh, H, K, hd)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


# -- the compiled decode step at Yi-34B widths -------------------------------

DECODE_PAGES = 537  # the benchmark's pool: 4 requests of 134 pages, and one
#: both page arrays (operands 0 and 1) are the outputs' buffers
ALIAS = ("input_output_alias={ {0}: (0, {}, may-alias), "
         "{1}: (1, {}, may-alias) }")


def _yi34b_step_args(sharding):
    """Yi-34B cut to 4 layers, bf16 weights placed by ``sharding``: the
    config, abstract parameters and the step's operands at ``BATCH``."""
    import dataclasses
    import functools

    from repro.models import transformer as tf
    cfg = dataclasses.replace(get_config("yi-34b"), num_layers=4)
    shapes = jax.eval_shape(functools.partial(tf.init_params, cfg,
                                              dtype=jnp.bfloat16),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), shapes)
    H, hd = cfg.num_heads, cfg.head_dim
    ops = {"tokens": _sds((BATCH,), jnp.int32, sharding),
           "x": _sds((BATCH, 1, cfg.d_model), jnp.bfloat16, sharding),
           "out": _sds((BATCH, H, hd), jnp.bfloat16, sharding),
           "bt": _sds((BATCH, -(-CTX // PAGE)), jnp.int32, sharding),
           "rows": _sds((BATCH, cfg.num_kv_heads, hd), jnp.bfloat16,
                        sharding)}
    return cfg, params, ops


def _pages(cfg, sharding):
    return _sds((cfg.num_layers, DECODE_PAGES, PAGE, cfg.num_kv_heads,
                 cfg.head_dim), jnp.bfloat16, sharding)


def test_decode_step_compiles_for_v5e_writing_pages_in_place(
        one_chip, no_compile_cache):
    """Every program of `decode_paged` but the Pallas kernel (compiled
    above) compiles for one v5e at Yi-34B widths, batch 4; the row
    write's page operands are its outputs' buffers."""
    from repro.paged import cache as cache_mod
    from repro.serving import paged_model as pm
    cfg, params, ops = _yi34b_step_args(one_chip)
    pages = _pages(cfg, one_chip)
    lp, idx = pm._layer_ref(params, cfg, 1)
    assert idx == 1  # the stacked layers: one program for all four
    toks = ops["tokens"]
    pm._decode_inputs.lower(params["embed"], toks, toks).compile()
    pm._attn_in.lower(lp, idx, ops["x"], toks, cfg=cfg).compile()
    pm._attn_out.lower(lp, idx, ops["x"], ops["out"], cfg=cfg).compile()
    head = {"final_norm": params["final_norm"],
            "lm_head": params["lm_head"]}
    pm._head.lower(head, ops["x"], cfg=cfg).compile()
    cache_mod._layer_pages.lower(pages, pages, 1).compile()
    text = cache_mod._write_decode.lower(
        pages, pages, 1, ops["bt"], toks, ops["rows"],
        ops["rows"]).compile().as_text()
    assert ALIAS in text


def test_decode_write_keeps_head_sharded_pages_split(mesh, no_compile_cache):
    """Pages split by KV head over four chips (2 of Yi-34B's 8 a chip):
    the donated row write and the layer slice hold no all-gather, and
    both hand the pages back split as they came in."""
    from repro.paged import cache as cache_mod
    split = NamedSharding(mesh, P(None, None, None, "model", None))
    cfg, _, ops = _yi34b_step_args(NamedSharding(mesh, P()))
    pages = _pages(cfg, split)
    write = cache_mod.page_writer(split).lower(
        pages, pages, 1, ops["bt"], ops["tokens"], ops["rows"],
        ops["rows"]).compile()
    text = write.as_text()
    assert "all-gather" not in text and ALIAS in text
    for s in write.output_shardings:
        assert s.is_equivalent_to(split, 5)
    sliced = cache_mod._layer_pages.lower(pages, pages, 1).compile()
    assert "all-gather" not in sliced.as_text()
    layer = NamedSharding(mesh, P(None, None, "model", None))
    for s in sliced.output_shardings:
        assert s.is_equivalent_to(layer, 4)
