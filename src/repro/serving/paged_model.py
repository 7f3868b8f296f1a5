"""Paged-cache model paths for the live serving engine (dense GQA archs —
the paper's model class: LWM/Yi/Llama families).

``prefill_collect_kv`` runs the prompt and hands back per-layer K/V so the
engine can scatter them into pages; ``decode_paged`` runs one token per
sequence with per-sequence positions (continuous batching) using the
Pallas paged-attention kernel. The decode step is a fixed set of
compiled programs, keyed on the batch's shapes only; prefill runs
eagerly.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models.common import apply_rope, rms_norm
from repro.models.transformer import lm_logits
from repro.paged.cache import PagedKVCache


def _layer_ref(params, cfg: ModelConfig, i: int) -> Tuple[dict, Optional[int]]:
    """Layer ``i`` as (parameters, index): a ``prefix``/``rest`` layer's
    own dict and None, or the stacked ``cycles`` entry of its place in
    the pattern and its cycle, so one compiled program, given the index
    as a traced scalar, serves every layer of the stack."""
    n_prefix = len(params["prefix"])
    if i < n_prefix:
        return params["prefix"][i], None
    j = i - n_prefix
    cl = len(cfg.layer_pattern)
    n_cycles = 0 if params["cycles"] is None else jax.tree.leaves(
        params["cycles"])[0].shape[0]
    if j < n_cycles * cl:
        return params["cycles"][f"l{j % cl}"], j // cl
    return params["rest"][j - n_cycles * cl], None


def _pick(lp, idx):
    return lp if idx is None else jax.tree.map(lambda x: x[idx], lp)


def _layer_params(params, cfg: ModelConfig, i: int) -> dict:
    return _pick(*_layer_ref(params, cfg, i))


def _qkv(p, h, cfg, positions):
    q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mlp_out(lp, h2, cfg):
    if "moe" in lp:
        out, _ = moe_mod.apply_moe(lp["moe"], h2, cfg)
        return out
    return mlp_mod.apply_mlp(lp["mlp"], h2, cfg.mlp_kind)


def prefill_collect_kv(params, cfg: ModelConfig, tokens: jax.Array
                       ) -> Tuple[jax.Array, List[Tuple[jax.Array,
                                                        jax.Array]]]:
    """tokens [b, s] -> (last-pos logits [b, V], [(k, v)] per layer).

    Full causal attention over the prompt (dense arch assumption).
    """
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    x = params["embed"][tokens]
    kvs = []
    from repro.models.attention import attend
    for i in range(cfg.num_layers):
        lp = _layer_params(params, cfg, i)
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(lp["attn"], h, cfg, positions)
        kvs.append((k, v))
        out = attend(q, k, v, positions, positions, causal=True,
                     window=cfg.sliding_window)
        x = x + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + _mlp_out(lp, h2, cfg)
    return lm_logits(params, cfg, x[:, -1:, :])[:, 0], kvs


def donor_prefix_kv(params, cfg: ModelConfig,
                    tokens) -> Tuple[np.ndarray, np.ndarray]:
    """Run the donor prefill and stack per-layer K/V into the
    [T, L, K, hd] arrays `KVStore.register_prefix` expects."""
    tokens = np.asarray(tokens)
    _, kvs = prefill_collect_kv(params, cfg, jnp.asarray(tokens[None]))
    kv_k = np.stack([np.asarray(k[0]) for k, _ in kvs], axis=1)
    kv_v = np.stack([np.asarray(v[0]) for _, v in kvs], axis=1)
    return kv_k, kv_v


@jax.jit
def _decode_inputs(embed, tokens, positions):
    """The step's residual stream [b, 1, d] and context lengths [b]."""
    return embed[tokens][:, None, :], positions + 1


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attn_in(lp, idx, x, positions, cfg: ModelConfig):
    """One layer's norm, q/k/v projections and rope for x [b, 1, d]:
    q [b, H, hd], k and v [b, K, hd]."""
    lp = _pick(lp, idx)
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    q, k, v = _qkv(lp["attn"], h, cfg, positions[:, None])
    return q[:, 0], k[:, 0], v[:, 0]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attn_out(lp, idx, x, out, cfg: ModelConfig):
    """The rest of one layer: output projection of the attention
    ``out`` [b, H, hd], residual, norm, MLP (or MoE) and residual."""
    lp = _pick(lp, idx)
    x = x + jnp.einsum("bhk,hkd->bd", out, lp["attn"]["wo"])[:, None]
    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    return x + _mlp_out(lp, h2, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _head(head, x, cfg: ModelConfig):
    return lm_logits(head, cfg, x)[:, 0]


def decode_paged(params, cfg: ModelConfig, tokens: jax.Array,
                 positions: jax.Array, cache: PagedKVCache,
                 seq_ids: List[int]) -> jax.Array:
    """One decode step for a batch of sequences at distinct positions.

    tokens [b] int32; positions [b] int32 (index of the new token).
    Per layer: q/k/v, one donated write of the batch's new K/V rows
    into the pages, the Pallas kernel over the paged cache, and the
    rest of the layer, each compiled. Nothing is read back to the host.
    Returns logits [b, V].
    """
    bt = jnp.asarray(cache.block_table_array(seq_ids), jnp.int32)
    x, context_lens = _decode_inputs(params["embed"], tokens, positions)
    for i in range(cfg.num_layers):
        lp, idx = _layer_ref(params, cfg, i)
        q, k, v = _attn_in(lp, idx, x, positions, cfg=cfg)
        cache.write_decode_rows(i, bt, positions, k, v)
        out = cache.attend(i, q, bt, context_lens)
        x = _attn_out(lp, idx, x, out, cfg=cfg)
    w = "embed" if cfg.tie_embeddings else "lm_head"
    return _head({"final_norm": params["final_norm"], w: params[w]}, x,
                 cfg=cfg)
