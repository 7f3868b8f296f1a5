"""Pallas TPU kernel: inter-frame (token-delta) predictive transform.

Encode side of the KV codec's hot loop: residual = frame_f - frame_{f-1}
(mod 256) followed by the zigzag sign-interleave, tiled (block_h, block_w)
over each frame so a grid step touches exactly two VMEM tiles (current +
reference). Pure VPU element-wise work; tiles are chosen 8x128-aligned.

The decode-side inverse is per-frame (frame-wise restoration consumes one
frame at a time), so it is exposed as a (prev, residual) -> frame kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _zigzag(r):
    r32 = r.astype(jnp.int32)
    z = jnp.where(r32 < 128, 2 * r32, 2 * (256 - r32) - 1)
    return z.astype(jnp.uint8)


def _unzigzag(z):
    z32 = z.astype(jnp.int32)
    r = jnp.where(z32 % 2 == 0, z32 // 2, 256 - (z32 + 1) // 2)
    return r.astype(jnp.uint8)


def _encode_kernel(cur_ref, prev_ref, out_ref):
    f = pl.program_id(0)
    cur = cur_ref[...]
    prev = jnp.where(f > 0, prev_ref[...], jnp.zeros_like(cur))
    out_ref[...] = _zigzag(cur - prev)


def token_delta_encode_pallas(video, *, block=(8, 128),
                              interpret: bool):
    """video [F, H, W] uint8 -> zigzag residuals [F, H, W] uint8."""
    F, H, W = video.shape
    bh = min(block[0], H)
    bw = min(block[1], W)
    grid = (F, -(-H // bh), -(-W // bw))
    fn = pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, bw), lambda f, i, j: (f, i, j)),
            # reference frame: previous f (clamped at 0; masked in-kernel)
            pl.BlockSpec((1, bh, bw),
                         lambda f, i, j: (jnp.maximum(f - 1, 0), i, j)),
        ],
        out_specs=pl.BlockSpec((1, bh, bw), lambda f, i, j: (f, i, j)),
        out_shape=jax.ShapeDtypeStruct(video.shape, jnp.uint8),
        interpret=interpret,
    )
    return fn(video, video)


def _decode_kernel(prev_ref, zres_ref, out_ref):
    out_ref[...] = prev_ref[...] + _unzigzag(zres_ref[...])


def token_delta_decode_frame_pallas(prev_frame, zres, *, block=(8, 128),
                                    interpret: bool):
    """prev [H, W] u8, zres [H, W] u8 -> reconstructed frame u8."""
    H, W = zres.shape
    bh = min(block[0], H)
    bw = min(block[1], W)
    grid = (-(-H // bh), -(-W // bw))
    fn = pl.pallas_call(
        _decode_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
                  pl.BlockSpec((bh, bw), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(zres.shape, jnp.uint8),
        interpret=interpret,
    )
    return fn(prev_frame, zres)
