"""Kernel-level microbench: Pallas kernel vs pure-jnp ref, host clock.
`us_per_call` is the kernel's wall time and `derived` the ref op's. Off
a TPU the kernel runs in the Pallas interpreter, so there it measures
Python emulation, not the kernel."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, timeit
from repro.kernels.kv_restore.ops import kv_restore
from repro.kernels.kv_restore.ref import kv_restore_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.token_delta.ops import token_delta_encode
from repro.kernels.token_delta.ref import token_delta_encode_ref


def run() -> List[Row]:
    rows: List[Row] = []
    rng = np.random.default_rng(0)

    # kv_restore
    R, H, D, n = 512, 8, 128, 64
    pages = jnp.asarray(rng.standard_normal((R, H, D)), jnp.float32)
    q = jnp.asarray(rng.integers(0, 256, (n, H, D)), jnp.uint8)
    sc = jnp.asarray(rng.random(H) + 0.1, jnp.float32)
    slots = jnp.asarray(rng.choice(R, n, replace=False), jnp.int32)
    uk = timeit(kv_restore, pages, q, sc, slots)
    ur = timeit(jax.jit(kv_restore_ref), pages, q, sc, slots)
    rows.append(("kernel.kv_restore.pallas_vs_ref", uk, ur))

    # paged_attention
    B, Hh, K, hd, ps, P, bps = 4, 16, 4, 128, 16, 64, 8
    qq = jnp.asarray(rng.standard_normal((B, Hh, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, ps, K, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, ps, K, hd)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, P, (B, bps)), jnp.int32)
    cl = jnp.asarray(rng.integers(1, bps * ps, (B,)), jnp.int32)
    uk = timeit(paged_attention, qq, kp, vp, bt, cl)
    ur = timeit(jax.jit(paged_attention_ref), qq, kp, vp, bt, cl)
    rows.append(("kernel.paged_attention.pallas_vs_ref", uk, ur))

    # token_delta
    video = jnp.asarray(rng.integers(0, 256, (8, 128, 512)), jnp.uint8)
    uk = timeit(token_delta_encode, video)
    ur = timeit(jax.jit(token_delta_encode_ref), video)
    rows.append(("kernel.token_delta.pallas_vs_ref", uk, ur))

    # ssd_scan
    b, s, nh, hd2, G, S = 1, 256, 4, 32, 1, 16
    xdt = jnp.asarray(rng.standard_normal((b, s, nh, hd2)) * .3, jnp.float32)
    al = jnp.asarray(-np.abs(rng.standard_normal((b, s, nh))) * .1,
                     jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, s, G, S)) * .3, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, s, G, S)) * .3, jnp.float32)
    uk = timeit(ssd_scan, xdt, al, Bm, Cm, chunk=64)
    ur = timeit(jax.jit(ssd_scan_ref, static_argnames=("chunk",)), xdt, al,
                Bm, Cm, chunk=64)
    rows.append(("kernel.ssd_scan.pallas_vs_ref", uk, ur))
    return rows
