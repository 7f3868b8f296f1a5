"""Jitted public wrappers for the token-delta transform."""
from __future__ import annotations

import jax

from repro import kernels
from repro.kernels.token_delta.token_delta import (
    token_delta_decode_frame_pallas, token_delta_encode_pallas,
)

_encode = jax.jit(token_delta_encode_pallas, static_argnames=("interpret",))
_decode_frame = jax.jit(token_delta_decode_frame_pallas,
                        static_argnames=("interpret",))


def token_delta_encode(video):
    return _encode(video, interpret=kernels.interpret_mode())


def token_delta_decode_frame(prev_frame, zres):
    return _decode_frame(prev_frame, zres,
                         interpret=kernels.interpret_mode())
