"""Paged-cache model paths for the live serving engine (dense GQA archs —
the paper's model class: LWM/Yi/Llama families).

``prefill_collect_kv`` runs the prompt and hands back per-layer K/V so the
engine can scatter them into pages; ``decode_paged`` runs one token per
sequence with per-sequence positions (continuous batching) using the
Pallas paged-attention kernel.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models.common import apply_rope, rms_norm
from repro.models.transformer import lm_logits
from repro.paged.cache import PagedKVCache


def _layer_params(params, cfg: ModelConfig, i: int) -> dict:
    n_prefix = len(params["prefix"])
    if i < n_prefix:
        return params["prefix"][i]
    j = i - n_prefix
    cl = len(cfg.layer_pattern)
    n_cycles = 0 if params["cycles"] is None else jax.tree.leaves(
        params["cycles"])[0].shape[0]
    if j < n_cycles * cl:
        cyc = jax.tree.map(lambda x: x[j // cl], params["cycles"])
        return cyc[f"l{j % cl}"]
    return params["rest"][j - n_cycles * cl]


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


def decode_paged(params, cfg: ModelConfig, tokens: jax.Array,
                 positions: jax.Array, cache: PagedKVCache,
                 seq_ids: List[int]) -> jax.Array:
    """One decode step for a batch of sequences at distinct positions.

    tokens [b] int32; positions [b] int32 (index of the new token).
    Writes the new token's K/V into the pages, then attends over the
    paged cache with the Pallas kernel. Returns logits [b, V].
    """
    b = tokens.shape[0]
    x = params["embed"][tokens][:, None, :]  # [b, 1, d]
    pos2 = positions[:, None]
    bt = jnp.asarray(cache.block_table_array(seq_ids), jnp.int32)
    context_lens = positions + 1
    for i in range(cfg.num_layers):
        lp = _layer_params(params, cfg, i)
        h = rms_norm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(lp["attn"], h, cfg, pos2)
        for bi, sid in enumerate(seq_ids):
            cache.write_decode_token(i, sid, int(positions[bi]),
                                     k[bi, 0], v[bi, 0])
        out = cache.attend(i, q[:, 0], bt, context_lens)
        x = x + jnp.einsum("bhk,hkd->bd", out, lp["attn"]["wo"])[:, None]
        h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
        x = x + _mlp_out(lp, h2, cfg)
    return lm_logits(params, cfg, x)[:, 0]
