"""Paged KV cache (vLLM-style) for dense-attention models.

Device state: k_pages / v_pages [L, P, page_size, K, hd]; host state: the
allocator + per-sequence block tables. Writes happen through
  - ``write_prefill``: bulk scatter of freshly computed K/V,
  - ``write_decode_rows``: a decode step's new rows of one layer, written
    in place into the donated page arrays, and
  - ``restore_tokens``: the frame-wise fused dequant+scatter kernel
    (repro.kernels.kv_restore), i.e. the paper's Sparse_frame_KV_transfer.
Decode reads go through ``attend`` (repro.kernels.paged_attention).

``shard(mesh)`` spreads the KV heads over the mesh's "model" axis. Both
kernels then run under ``shard_map``: each device restores and attends
over its own heads, and the page arrays are never gathered; the decode
write hands them back in the layout they came in.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.kernels.kv_restore.ops import kv_restore
from repro.kernels.paged_attention.ops import paged_attention
from repro.paged.allocator import PageAllocator


# Both use check_vma=False: a pallas_call's out_shape carries no per-axis
# variance, which shard_map's check would demand.
def sharded_restore(mesh, axis: str):
    """``kv_restore`` over rows [R, K, hd] whose heads are split on
    ``axis``: each device dequantizes and scatters its own heads."""
    heads = P(None, axis, None)
    return jax.jit(jax.shard_map(
        kv_restore, mesh=mesh, in_specs=(heads, heads, P(axis), P()),
        out_specs=heads, check_vma=False))


def sharded_attention(mesh, axis: str):
    """``paged_attention`` with query heads and KV heads split on
    ``axis``. Query head h reads KV head h // (H // K), so contiguous
    query blocks line up with contiguous KV-head blocks."""
    pages = P(None, None, axis, None)
    return jax.jit(jax.shard_map(
        paged_attention, mesh=mesh,
        in_specs=(P(None, axis, None), pages, pages, P(), P()),
        out_specs=P(None, axis, None), check_vma=False))


def _write_rows(k_pages, v_pages, layer, block_tables, positions, k, v):
    """Row ``positions[b]`` of sequence b, found through its block table
    on the device, takes k[b] / v[b] [K, hd] in ``layer``."""
    ps = k_pages.shape[2]
    page = block_tables[jnp.arange(positions.shape[0]), positions // ps]
    row = positions % ps
    return (k_pages.at[layer, page, row].set(k.astype(k_pages.dtype)),
            v_pages.at[layer, page, row].set(v.astype(v_pages.dtype)))


def page_writer(sharding=None):
    """``_write_rows`` compiled with both page arrays donated, so the
    rows are written in place; ``sharding``, where given, is the page
    arrays' layout, which the updated arrays keep."""
    kw = {} if sharding is None else {"out_shardings": (sharding, sharding)}
    return jax.jit(_write_rows, donate_argnums=(0, 1), **kw)


_write_decode = page_writer()


@jax.jit
def _layer_pages(k_pages, v_pages, layer):
    return k_pages[layer], v_pages[layer]


@dataclasses.dataclass
class SeqInfo:
    seq_id: int
    block_table: List[int]
    context_len: int = 0


class PagedKVCache:
    def __init__(self, cfg: ModelConfig, n_pages: int, page_size: int = 16,
                 dtype=jnp.float32):
        self.cfg = cfg
        self.page_size = page_size
        self.n_pages = n_pages
        L = cfg.num_layers
        K, hd = cfg.num_kv_heads, cfg.head_dim
        shape = (L, n_pages, page_size, K, hd)
        self.k_pages = jnp.zeros(shape, dtype)
        self.v_pages = jnp.zeros(shape, dtype)
        self.alloc = PageAllocator(n_pages)
        self.seqs: Dict[int, SeqInfo] = {}
        self._restore = kv_restore
        self._attend = paged_attention
        self._write = _write_decode

    def shard(self, mesh) -> None:
        """Lay the page arrays out over ``mesh``: KV heads on the
        "model" axis where they divide it (DEFAULT_RULES), everything
        else replicated, so tiny models on small meshes stay valid."""
        from repro.sharding import rules
        with rules.activate(mesh):
            spec = rules.logical_to_pspec(
                ("layers", None, None, "kv_heads", None),
                self.k_pages.shape, mesh)
        ns = NamedSharding(mesh, spec)
        self.k_pages = jax.device_put(self.k_pages, ns)
        self.v_pages = jax.device_put(self.v_pages, ns)
        self._write = page_writer(ns)
        axis = spec[3]
        if axis is not None:
            self._restore = sharded_restore(mesh, axis)
            self._attend = sharded_attention(mesh, axis)

    # -- sequence lifecycle ------------------------------------------------
    def add_seq(self, seq_id: int, n_tokens: int) -> SeqInfo:
        n = -(-n_tokens // self.page_size)
        pages = self.alloc.allocate(seq_id, n)
        info = SeqInfo(seq_id, pages, 0)
        self.seqs[seq_id] = info
        return info

    def ensure_capacity(self, seq_id: int, n_tokens: int) -> None:
        info = self.seqs[seq_id]
        need = -(-n_tokens // self.page_size)
        if need > len(info.block_table):
            info.block_table.extend(
                self.alloc.extend(seq_id, need - len(info.block_table)))

    def free_seq(self, seq_id: int) -> None:
        self.alloc.release(seq_id)
        self.seqs.pop(seq_id, None)

    # -- slot math -----------------------------------------------------------
    def slots_for(self, seq_id: int, positions: np.ndarray) -> np.ndarray:
        """Logical token positions -> physical page rows (flat)."""
        info = self.seqs[seq_id]
        bt = np.asarray(info.block_table)
        return bt[positions // self.page_size] * self.page_size + \
            positions % self.page_size

    def block_table_array(self, seq_ids: List[int],
                          max_pages: Optional[int] = None) -> np.ndarray:
        mp = max_pages or max(len(self.seqs[s].block_table)
                              for s in seq_ids)
        out = np.zeros((len(seq_ids), mp), np.int32)
        for i, s in enumerate(seq_ids):
            bt = self.seqs[s].block_table
            out[i, :len(bt)] = bt
        return out

    # -- device writes -------------------------------------------------------
    def write_prefill(self, layer: int, seq_id: int, k: jax.Array,
                      v: jax.Array, start_pos: int = 0) -> None:
        """k/v [s, K, hd] computed by a prefill pass."""
        with jax.profiler.TraceAnnotation("kvf.cache.write"):
            s = k.shape[0]
            positions = np.arange(start_pos, start_pos + s)
            slots = jnp.asarray(self.slots_for(seq_id, positions),
                                jnp.int32)
            ps = self.page_size
            L, P = self.k_pages.shape[:2]
            flat_k = self.k_pages[layer].reshape(P * ps,
                                                 *self.k_pages.shape[3:])
            flat_v = self.v_pages[layer].reshape(P * ps,
                                                 *self.v_pages.shape[3:])
            flat_k = flat_k.at[slots].set(k.astype(flat_k.dtype))
            flat_v = flat_v.at[slots].set(v.astype(flat_v.dtype))
            self.k_pages = self.k_pages.at[layer].set(
                flat_k.reshape(self.k_pages.shape[1:]))
            self.v_pages = self.v_pages.at[layer].set(
                flat_v.reshape(self.v_pages.shape[1:]))

    def write_decode_rows(self, layer: int, block_tables: jax.Array,
                          positions: jax.Array, k: jax.Array,
                          v: jax.Array) -> None:
        """One decode step's new rows of ``layer``, one per sequence:
        k/v [B, K, hd] at ``positions`` [B], with rows found through
        ``block_tables`` [B, pages] on the device."""
        with jax.profiler.TraceAnnotation("kvf.cache.write"):
            self.k_pages, self.v_pages = self._write(
                self.k_pages, self.v_pages, layer, block_tables, positions,
                k, v)

    def restore_tokens(self, layer: int, kind: str, seq_id: int,
                       token_ids: np.ndarray, q_tokens: jax.Array,
                       scales: jax.Array) -> None:
        """Frame-wise restoration: decoded uint8 tokens -> page rows.

        q_tokens [n, K, hd] uint8 (one layer, one frame); scales [K].
        """
        with jax.profiler.TraceAnnotation("kvf.cache.restore"):
            slots = jnp.asarray(
                self.slots_for(seq_id, np.asarray(token_ids)), jnp.int32)
            ps = self.page_size
            P = self.n_pages
            pages = self.k_pages if kind == "k" else self.v_pages
            flat = pages[layer].reshape(P * ps, *pages.shape[3:])
            flat = self._restore(flat, q_tokens, scales, slots)
            updated = pages.at[layer].set(flat.reshape(pages.shape[1:]))
            if kind == "k":
                self.k_pages = updated
            else:
                self.v_pages = updated

    # -- device reads --------------------------------------------------------
    def attend(self, layer: int, q: jax.Array, block_tables: jax.Array,
               context_lens: jax.Array) -> jax.Array:
        """Decode attention of q [B, H, hd] over ``layer``'s pages."""
        with jax.profiler.TraceAnnotation("kvf.cache.attend"):
            k, v = _layer_pages(self.k_pages, self.v_pages, layer)
            return self._attend(q, k, v, block_tables, context_lens)

    def gpu_bytes(self) -> int:
        return self.k_pages.nbytes + self.v_pages.nbytes
