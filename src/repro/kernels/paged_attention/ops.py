"""Jitted public wrapper for paged GQA decode attention."""
from __future__ import annotations

import jax

from repro import kernels
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_pallas,
)

_attend = jax.jit(paged_attention_pallas, static_argnames=("interpret",))


def paged_attention(q, k_pages, v_pages, block_tables, context_lens):
    """Decode-time attention of one query token per sequence over a paged
    KV cache.

    q            [B, H, hd]
    k/v_pages    [P, page_size, K, hd]
    block_tables [B, pages_per_seq] int32 (physical page per logical page)
    context_lens [B] int32
    """
    return _attend(q, k_pages, v_pages, block_tables, context_lens,
                   interpret=kernels.interpret_mode())
