"""Pallas TPU kernel: fused dequantize + scatter of decoded KV tokens into
paged KV memory (the ``Sparse_frame_KV_transfer`` operator, §3.3.2/§4).

Design for TPU: the destination row of each token block is data-dependent
(slot mapping), so the slot array is a *scalar-prefetch* operand — the
output BlockSpec's index_map reads it to aim each grid step's (1, H, D)
VMEM tile at the right page row. The dequant (uint8 -> (x-128)*scale) runs
on the VPU over the tile; the MXU is untouched, and VMEM footprint is a
single token tile per step — this is why restoration memory stays in the
tens-of-MB range (Fig. 24) instead of chunk-sized buffers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QOFF = 128.0


def _kernel(safe_ref, orig_ref, q_ref, scale_ref, pages_in_ref,
            pages_out_ref):
    i = pl.program_id(0)
    q = q_ref[...]  # [1, H, D] uint8
    # Mosaic has no uint8 -> float32 cast; widen through int32 (exact)
    deq = (q.astype(jnp.int32).astype(jnp.float32) - QOFF) \
        * scale_ref[...][None, :, None]
    # dropped tokens (original slot < 0) keep the old page row
    keep = orig_ref[i] >= 0
    old = pages_in_ref[...]
    pages_out_ref[...] = jnp.where(keep, deq.astype(old.dtype), old)


def kv_restore_pallas(pages, q_tokens, scales, slots, *,
                      interpret: bool):
    """pages [R, H, D]; q_tokens [n, H, D] u8; scales [H]; slots [n] i32."""
    n, H, D = q_tokens.shape
    slots = slots.astype(jnp.int32)
    safe = jnp.where(slots >= 0, slots, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # (clamped slots for index_map, originals)
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda i, safe, orig: (i, 0, 0)),
            pl.BlockSpec((H,), lambda i, safe, orig: (0,)),
            pl.BlockSpec((1, H, D), lambda i, safe, orig: (safe[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D),
                               lambda i, safe, orig: (safe[i], 0, 0)),
    )
    fn = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={4: 0},  # pages operand aliases the output
        interpret=interpret,
    )
    return fn(safe, slots, q_tokens, scales, pages)
