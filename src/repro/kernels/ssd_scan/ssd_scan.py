"""Pallas TPU kernel: Mamba2 chunked SSD scan (state-space duality).

Grid (batch, heads, chunks); the chunk axis is innermost so each (b, h)
pair walks its chunks sequentially with the running [hd, S] state in VMEM
scratch — the inter-chunk recurrence never touches HBM. Per chunk the work
is three MXU matmuls (C.B^T scores, (L*scores).X intra-chunk, decayed-state
outer products) on [Q, S]/[Q, hd] tiles; Q=128 aligns the matmul dims with
the MXU and keeps the VMEM working set to a few tiles:
  Q*(hd + 2S + Q) + hd*S floats  ~= 0.3 MB at Q=128, hd=64, S=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, b_ref, c_ref, y_ref, st_ref, state_scr,
            *, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, :, 0].astype(jnp.float32)  # [Q, hd]
    a = a_ref[0, :, 0].astype(jnp.float32)  # [Q]
    Bm = b_ref[0, :, 0].astype(jnp.float32)  # [Q, S]
    Cm = c_ref[0, :, 0].astype(jnp.float32)  # [Q, S]
    Q = x.shape[0]

    acs = jnp.cumsum(a)  # [Q]
    # intra-chunk decay matrix L[i, j] = exp(acs[i] - acs[j]) for i >= j
    dif = acs[:, None] - acs[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(tri, jnp.exp(dif), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y_diag = jax.lax.dot_general(L * scores, x, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    state = state_scr[...]  # [hd, S]
    # inter-chunk contribution: y_off = (C * exp(acs)) @ state^T
    Cd = Cm * jnp.exp(acs)[:, None]
    y_off = jax.lax.dot_general(Cd, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, :, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: state' = exp(acs[-1]) * state + X^T @ (decay_to_end * B)
    decay_to_end = jnp.exp(acs[-1] - acs)  # [Q]
    Bd = Bm * decay_to_end[:, None]
    upd = jax.lax.dot_general(x, Bd, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    new_state = jnp.exp(acs[-1]) * state + upd
    state_scr[...] = new_state
    st_ref[0, 0] = new_state.astype(st_ref.dtype)


def ssd_scan_pallas(xdt, a_log, Bm, Cm, *, chunk: int = 128,
                    interpret: bool):
    """Shapes as ssd_scan_ref; s must be a multiple of `chunk` (the ops
    wrapper pads). G must divide nh (B/C broadcast per head group)."""
    b, s, nh, hd = xdt.shape
    G, S = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, s)
    assert s % Q == 0
    nc = s // Q
    hpg = nh // G

    grid = (b, nh, nc)
    fn = pl.pallas_call(
        functools.partial(_kernel, n_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, Q, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1, Q, 1, S),
                         lambda bi, hi, ci: (bi, ci, hi // hpg, 0)),
            pl.BlockSpec((1, Q, 1, S),
                         lambda bi, hi, ci: (bi, ci, hi // hpg, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, 1, hd), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, hd, S), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, nh, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, nh, hd, S), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, S), jnp.float32)],
        interpret=interpret,
    )
    y, st = fn(xdt, a_log, Bm, Cm)
    return y, st
