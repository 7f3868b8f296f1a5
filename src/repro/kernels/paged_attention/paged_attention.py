"""Pallas TPU kernel: GQA decode attention over a paged KV cache
(flash-decoding style online softmax, one page per grid step).

TPU mapping: block tables are scalar-prefetch operands so each grid step's
K/V BlockSpec index_map aims DMA at the right physical page — HBM->VMEM
traffic is exactly one (page_size, K, hd) tile per step. The online-softmax
running state (m, l, acc) lives in VMEM scratch and persists across the
sequential page-axis grid iterations of the same batch row. MXU work is the
[H, hd] x [hd, ps] logits matmul and the [H, ps] x [ps, hd] value matmul;
head_dim and page_size should be multiples of the 128-lane tiling for full
MXU utilization (all production configs here satisfy that).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, out_ref,
            m_scr, l_scr, acc_scr, *, page_size: int, pages_per_seq: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)  # [H, hd]
    k = k_ref[0].astype(jnp.float32)  # [ps, K, hd]
    v = v_ref[0].astype(jnp.float32)
    H, hd = q.shape
    ps, K, _ = k.shape
    g = H // K

    qg = q.reshape(K, g, hd)
    logits = jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)  # [K, g, ps]
    logits = logits / jnp.sqrt(jnp.float32(hd))
    pos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
    logits = jnp.where(pos < lens_ref[b], logits, NEG_INF)
    logits = logits.reshape(H, ps)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)  # [H, ps]
    l_new = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.reshape(K, g, ps), v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32).reshape(H, hd)
    acc_new = acc_scr[...] * alpha + pv
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(j == pages_per_seq - 1)
    def _finish():
        out_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)
                      ).astype(out_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, block_tables, context_lens,
                           *, interpret: bool):
    B, H, hd = q.shape
    P, ps, K, _ = k_pages.shape
    bps = block_tables.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (block_tables flat, context_lens)
        grid=(B, bps),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, j, t, ln: (b, 0, 0)),
            pl.BlockSpec((1, ps, K, hd),
                         lambda b, j, t, ln: (t[b, j], 0, 0, 0)),
            pl.BlockSpec((1, ps, K, hd),
                         lambda b, j, t, ln: (t[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, j, t, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, page_size=ps, pages_per_seq=bps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )
    return fn(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
              q, k_pages, v_pages)
