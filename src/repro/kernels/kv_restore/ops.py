"""Jitted public wrapper for the fused KV restoration op."""
from __future__ import annotations

import jax

from repro import kernels
from repro.kernels.kv_restore.kv_restore import kv_restore_pallas

_restore = jax.jit(kv_restore_pallas, static_argnames=("interpret",))


def kv_restore(pages, q_tokens, scales, slots):
    """Dequantize decoded uint8 KV tokens and scatter them into paged rows.

    pages    [R, H, D] float  (paged KV memory rows)
    q_tokens [n, H, D] uint8  (one decoded frame's tokens, one layer/kind)
    scales   [H] float32      (per-head dequant scales)
    slots    [n] int32        (destination rows; -1 drops the token)
    """
    return _restore(pages, q_tokens, scales, slots,
                    interpret=kernels.interpret_mode())
