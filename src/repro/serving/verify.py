"""Logit comparisons for the served path.

Prefix reuse is lossy in one place only: the per-(layer, head) int8
quantization of the prefix KV (`repro.core.quantization`). Everything
after it (layout, prediction, rANS, restore) is bit-exact. A reused
request's logits therefore differ from a full prefill of the same
prompt by what that quantization and the compute dtype let through, and
are compared on logits with a tolerance derived from both; sampled
tokens of random-weight models are tie-dominated and prove nothing.
An engine whose KV heads are split over devices is held to a tighter
budget against the same engine on one device: only the rounding of the
reductions the split reorders stands between them.

`LiveEngine` keeps no logits; a `LogitsRecorder` passed as its
``on_logits`` collects them for these checks.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: one int8 quantization step relative to its (layer, head) absmax
INT8_STEP = 1.0 / 127.0


def kv_int8_logit_tolerance(n_layers: int, dtype) -> float:
    """Relative L2 budget for logits over int8-restored prefix KV
    against a full prefill of the same prompt.

    Rounding moves each restored K and V element by at most half a step
    (absmax / 254) of its (layer, head). A V error passes linearly into
    the attention output; a K error moves the attention scores and,
    through the softmax, the weights on every V row. Each is budgeted
    one full step relative to the layer's KV scale, so two steps per
    layer. A layer's perturbation reaches the logits once, so the
    budget adds over the ``n_layers`` restored layers. The two paths
    also evaluate in a different order (suffix prefill over restored
    pages vs. one full prefill), which costs the dtype's rounding,
    ``eps``, per layer. At the budget's edge a bug is far away: logits
    of an unrelated prompt sit at a relative error near 1.
    """
    eps = float(jnp.finfo(dtype).eps)
    return n_layers * (2 * INT8_STEP + eps)


def shard_logit_tolerance(n_layers: int, dtype) -> float:
    """Relative L2 budget for logits of an engine whose KV heads are
    split over devices against the same requests on one device.

    Both restore the same int8 frames and run the same ops on the same
    weights, so quantization cancels. What the split can change is the
    order of the reductions it partitions: the sum over heads in the
    attention output projection becomes per-device partial sums and a
    cross-device add. Reordering a float32 accumulation moves it by a
    few float32 ulps, which flips its rounding to ``dtype`` by at most
    one ``eps`` of the result. Each layer rounds two such sums into the
    residual stream (attention output, MLP output), so two ``eps`` per
    layer, added over ``n_layers``. Losing one device's share of heads
    in one layer lands far outside it
    (``test_shard_tolerance_rejects_one_device_heads_fault``).
    """
    return n_layers * 2 * float(jnp.finfo(dtype).eps)


class LogitsRecorder(dict):
    """`LiveEngine(on_logits=)` sink owned by the caller: rid -> the
    float32 logits [V] behind each token, in order."""

    def __call__(self, req, logits) -> None:
        self.setdefault(req.rid, []).append(logits)


def rel_l2(got, want) -> float:
    """||got - want|| / ||want|| over float32 copies."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def check_logits(got, want, tol: float, what: str) -> float:
    """Relative L2 error of ``got`` against ``want``; raises
    AssertionError naming ``what`` when it exceeds ``tol`` or either
    side is not finite."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{what}: shape {got.shape} != {want.shape}")
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise AssertionError(f"{what}: non-finite logits")
    err = rel_l2(got, want)
    if not err <= tol:
        raise AssertionError(
            f"{what}: relative L2 error {err:.3e} > tolerance {tol:.3e}")
    return err


def check_streams(got_logits, got_tokens, want_logits, want_tokens,
                  tol: float, what: str) -> tuple:
    """Compare two generations step by step on logits. Step i is
    compared while both token streams agree on tokens 0..i-1 (after a
    divergence the inputs differ); step 0 is always compared.
    Returns (max relative L2 error, steps compared)."""
    worst, n = 0.0, 0
    for i, (g, w) in enumerate(zip(got_logits, want_logits)):
        if i and list(got_tokens[:i]) != list(want_tokens[:i]):
            break
        worst = max(worst, check_logits(g, w, tol, f"{what} step {i}"))
        n += 1
    if n == 0:
        raise AssertionError(f"{what}: no logits to compare")
    return worst, n


# ---------------------------------------------------------------------------
# checks of a `repro.launch.serve.serve_live` run
# ---------------------------------------------------------------------------

def check_kernels(run) -> dict:
    """Run both kernels on inputs from the served path and compare them
    with their ``ref.py`` oracles; report whether each wrapper lowers to
    a Mosaic kernel (``tpu_custom_call``) rather than interpreted HLO.

    ``kv_restore`` restores the first decoded frame of the registered
    prefix into the engine's layer page rows; ``paged_attention`` runs
    one decode step of seeded queries over the engine's pages."""
    import jax

    from repro.core.chunks import prefix_key
    from repro.core.codec import KVCodec
    from repro.core.layout import IntraLayout
    from repro.kernels.kv_restore.ops import kv_restore
    from repro.kernels.kv_restore.ref import kv_restore_ref
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref

    cfg, cache = run.cfg, run.engine.cache
    K, hd, ps, P = (cfg.num_kv_heads, cfg.head_dim, cache.page_size,
                    cache.n_pages)
    dtype = cache.k_pages.dtype
    u = float(jnp.finfo(dtype).eps) / 2  # unit roundoff of the pages
    out = {}

    man = run.cluster.catalog[prefix_key(run.prefix)].manifest
    ref0 = man.refs[0]
    codec = KVCodec(K, hd, IntraLayout(K, hd, *man.layout))
    toks, qt = next(codec.iter_decode_frames(
        man.blobs[(ref0.chunk_id, run.engine.resolution)]))
    layer = ref0.layers[0]
    n = min(len(toks), P * ps)  # one frame's tokens into distinct rows
    args = (cache.k_pages[layer].reshape(P * ps, K, hd),
            jnp.asarray(qt[:n, 0]),
            jnp.asarray(man.scales[ref0.kind][layer]),
            jnp.arange(n, dtype=jnp.int32))
    got = np.asarray(kv_restore(*args), np.float32)
    want = np.asarray(kv_restore_ref(*args), np.float32)
    # both compute (q - 128) * scale in float32 and round once to the
    # page dtype: equal up to that one rounding
    np.testing.assert_allclose(got, want, rtol=2 * u, atol=0,
                               err_msg="kv_restore vs kv_restore_ref")
    out["kv_restore_max_abs_err"] = float(np.abs(got - want).max())
    out["kv_restore_mosaic"] = "tpu_custom_call" in jax.jit(
        kv_restore).lower(*args).as_text()

    B = len(run.reuse)
    ctx = len(run.engine.prompts[run.reuse[0].rid])
    bps = -(-ctx // ps)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, cfg.num_heads, hd)), dtype)
    bt = jnp.asarray(np.arange(B * bps).reshape(B, bps) % P, jnp.int32)
    lens = jnp.asarray([ctx - i for i in range(B)], jnp.int32)
    args = (q, cache.k_pages[0], cache.v_pages[0], bt, lens)
    got = np.asarray(paged_attention(*args), np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(paged_attention_ref(*args), np.float32)
    vmax = float(np.abs(np.asarray(cache.v_pages[0][bt],
                                   np.float32)).max())
    # the kernel may round the softmax weights to the page dtype on the
    # MXU (u), both sides round their output (u each), and the online
    # softmax rescales its float32 accumulator once per page: all scale
    # with the largest |v| the weights average over
    u32 = float(jnp.finfo(jnp.float32).eps) / 2
    atol = (3 * u + bps * u32) * vmax
    np.testing.assert_allclose(got, want, rtol=0, atol=atol,
                               err_msg="paged_attention vs "
                                       "paged_attention_ref")
    out["paged_attention_max_abs_err"] = float(np.abs(got - want).max())
    out["paged_attention_atol"] = atol
    out["paged_attention_mosaic"] = "tpu_custom_call" in jax.jit(
        paged_attention).lower(*args).as_text()
    return out


def check_reuse_logits(run, logits: LogitsRecorder) -> dict:
    """Each request's first-token logits (recorded in ``logits``)
    against a full prefill of its prompt, within
    `kv_int8_logit_tolerance`. Returns rid -> error."""
    from repro.serving import paged_model

    eng = run.engine
    tol = kv_int8_logit_tolerance(run.cfg.num_layers,
                                  run.params["embed"].dtype)
    errs = {}
    for r in run.reuse + [run.plain]:
        want, _ = paged_model.prefill_collect_kv(
            run.params, run.cfg, jnp.asarray(eng.prompts[r.rid][None]))
        errs[r.rid] = check_logits(logits[r.rid][0], want[0], tol,
                                   f"rid {r.rid} first token vs full "
                                   "prefill")
    return errs
