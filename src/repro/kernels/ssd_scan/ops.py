"""Jitted public wrapper for the chunked SSD scan."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import kernels
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_pallas


def ssd_scan(xdt, a_log, Bm, Cm, *, chunk: int = 128):
    """Mamba2 chunked SSD scan.

    xdt [b,s,nh,hd] (x pre-multiplied by dt), a_log [b,s,nh] (dt*A),
    Bm/Cm [b,s,G,S]. Returns (y [b,s,nh,hd] f32, final_state [b,nh,hd,S]).
    """
    return _scan(xdt, a_log, Bm, Cm, chunk=chunk,
                 interpret=kernels.interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan(xdt, a_log, Bm, Cm, *, chunk: int, interpret: bool):
    b, s = xdt.shape[:2]
    Q = min(chunk, s)
    pad = (-s) % Q
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a_log = jnp.pad(a_log, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    y, st = ssd_scan_pallas(xdt, a_log, Bm, Cm, chunk=Q,
                            interpret=interpret)
    return y[:, :s], st
