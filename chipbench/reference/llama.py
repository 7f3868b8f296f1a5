"""Plain reference of a dense Llama-architecture model serving a stored
prefix, in float32 at the highest matmul precision.

Written from the published architecture, not from the program: token
embedding, then per layer RMSNorm -> grouped-query attention with
rotary embedding (half rotation) -> residual -> RMSNorm -> SwiGLU MLP ->
residual, then a final RMSNorm and an untied output head.

Serving a stored prefix has one lossy step by the system's own
specification: the prefix's K and V (K after the rotary embedding) are
kept as int8 per (layer, K or V, KV head), symmetric around zero with
the scale absmax / 127 over the prefix's tokens and the head's dims, and
rounded half to even. The prefix itself runs as a plain prefill with
exact K and V; each position after it attends over the dequantized
prefix and the exact K and V of the positions after it. Nothing else
is approximated. Where nothing is stored (``n_pre`` 0) it is the exact
recompute of the whole prompt.

Weights are the benchmark's own arrays (``chipbench.model``): matrices
in the layout ``wq [L, d, H, hd]``, ``wk``/``wv [L, d, K, hd]``,
``wo [L, H, hd, d]``, ``wi [L, d, 2, f]`` (gate, up), MLP ``wo [L, f, d]``,
``lm_head [d, V]``; norm gains stored as offsets from 1. Each layer is
upcast to float32 inside its own call, so only one layer's float32
copy exists at a time.

``control=True`` runs the same reference computed in float8 (e4m3),
the precision step below the configuration's bfloat16, as float8
inference does it: every weight matrix rounded with one scale per
matrix, and every activation that enters a weight matrix rounded with
one scale per token. Attention itself stays in float32.
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _f8(x, axis=None):
    """x rounded to float8 e4m3 with one scale per slice along ``axis``
    (None: one scale for the whole array)."""
    keep = axis is not None
    scale = jnp.maximum(jnp.abs(x).max(axis=axis, keepdims=keep),
                        1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _weight(w, control: bool):
    w = w.astype(jnp.float32)
    return _f8(w) if control else w


def _act(x, control: bool):
    """An activation [T, ...] entering a weight matrix."""
    if not control:
        return x
    flat = x.reshape(x.shape[0], -1)
    return _f8(flat, axis=1).reshape(x.shape)


def _norm(x, gain_offset, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + gain_offset.astype(jnp.float32))


def _rope(x, pos, theta):
    """x [T, n, hd]; rotate the first half against the second."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _int8_roundtrip(x, in_prefix):
    """Per-head symmetric int8 of x [T, K, hd] over the prefix rows."""
    m = jnp.where(in_prefix[:, None, None], jnp.abs(x), 0.0).max(axis=(0, 2))
    scale = jnp.maximum(m, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / scale[None, :, None]), -127, 127)
    return jnp.where(in_prefix[:, None, None], q * scale[None, :, None], x)


def _attention(q, k, v, pos):
    """Causal attention of q [T, H, hd] over k, v [T, K, hd]; query head
    h reads KV head h // (H // K)."""
    T, H, hd = q.shape
    K = k.shape[1]
    qg = q.reshape(T, K, H // K, hd).transpose(1, 2, 0, 3)  # [K, g, T, hd]
    causal = pos[None, :] <= pos[:, None]

    def head(args):
        qh, kh, vh = args  # [g, T, hd], [T, hd], [T, hd]
        s = jnp.einsum("gqd,sd->gqs", qh, kh, precision=HI) / np.sqrt(hd)
        s = jnp.where(causal[None], s, -jnp.inf)
        return jnp.einsum("gqs,sd->gqd", jax.nn.softmax(s, axis=-1), vh,
                          precision=HI)

    out = jax.lax.map(head, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(T, H, hd)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "control"))
def _layer(x, w, i, n_pre, *, eps, theta, control):
    lw = jax.tree.map(lambda a: a[i], w)
    T = x.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    in_prefix = pos < n_pre
    h = _act(_norm(x, lw["ln1"], eps), control)
    a = lw["attn"]
    q = jnp.einsum("td,dhk->thk", h, _weight(a["wq"], control), precision=HI)
    k = jnp.einsum("td,dhk->thk", h, _weight(a["wk"], control), precision=HI)
    v = jnp.einsum("td,dhk->thk", h, _weight(a["wv"], control), precision=HI)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    exact = _attention(q, k, v, pos)
    stored = _attention(q, _int8_roundtrip(k, in_prefix),
                        _int8_roundtrip(v, in_prefix), pos)
    att = jnp.where(in_prefix[:, None, None], exact, stored)
    x = x + jnp.einsum("thk,hkd->td", _act(att, control),
                       _weight(a["wo"], control), precision=HI)
    h = _act(_norm(x, lw["ln2"], eps), control)
    gu = jnp.einsum("td,dcf->tcf", h, _weight(lw["mlp"]["wi"], control),
                    precision=HI)
    m = _act(jax.nn.silu(gu[:, 0]) * gu[:, 1], control)
    return x + jnp.einsum("tf,fd->td", m, _weight(lw["mlp"]["wo"], control),
                          precision=HI)


@functools.partial(jax.jit, static_argnames=("control",))
def _embed(table, tokens, *, control):
    return _weight(table, control)[tokens] if control else \
        table[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(x, rows, gain_offset, lm_head, *, eps, control):
    h = _act(_norm(x[rows], gain_offset, eps), control)
    return jnp.einsum("td,dv->tv", h, _weight(lm_head, control),
                      precision=HI)


def logits(params, conf: dict, tokens: np.ndarray, n_pre: int,
           rows: np.ndarray, *, control: bool = False) -> jax.Array:
    """float32 logits [len(rows), V] at positions ``rows`` of
    ``tokens`` (padded at the end as the caller likes; causal attention
    keeps padding out of every earlier position), whose first ``n_pre``
    tokens are a stored prefix."""
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    w = params["cycles"]["l0"]
    x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32),
               control=control)
    n = jnp.int32(n_pre)
    for i in range(conf["num_hidden_layers"]):
        x = _layer(x, w, jnp.int32(i), n, eps=eps, theta=theta,
                   control=control)
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["lm_head"], eps=eps, control=control)


def served_gaps(params, conf: dict,
                served: Sequence[Tuple[np.ndarray, int, Sequence[int]]],
                pad_to: int, *, control: bool = False) -> List[np.ndarray]:
    """For each (prompt, n_pre, served tokens): per served token, how far
    its reference logit lies below the reference's best at that
    position. With ``control`` the tokens are not the served ones but
    those the float8 control puts first at each position, judged by the
    same float32 reference."""
    out = []
    for prompt, n_pre, toks in served:
        toks = np.asarray(toks, np.int64)
        seq = np.concatenate([prompt, toks[:-1]])
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} > pad_to {pad_to}")
        seq = np.pad(seq, (0, pad_to - len(seq)))
        rows = len(prompt) - 1 + np.arange(len(toks))
        ref = logits(params, conf, seq, n_pre, rows)
        if control:
            toks = np.asarray(jnp.argmax(
                logits(params, conf, seq, n_pre, rows, control=True),
                axis=-1))
        ref = np.asarray(ref)
        out.append(ref.max(axis=-1) - ref[np.arange(len(toks)), toks])
    return out
