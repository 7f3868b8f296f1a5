"""Per-kernel validation: Pallas (interpreted on this backend) vs pure-jnp
ref oracle, with hypothesis shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.kernels.kv_restore.ops import kv_restore
from repro.kernels.kv_restore.ref import kv_restore_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.token_delta.ops import (
    token_delta_decode_frame, token_delta_encode,
)
from repro.kernels.token_delta.ref import token_delta_encode_ref
from repro.core.prediction import ZIGZAG, UNZIGZAG


# ---------------------------------------------------------------------------
# kv_restore
# ---------------------------------------------------------------------------

@given(st.integers(1, 4), st.sampled_from([(2, 8), (4, 16), (8, 128)]),
       st.sampled_from([jnp.float32, jnp.bfloat16]),
       st.integers(0, 5))
@settings(max_examples=25, deadline=None)
def test_kv_restore_matches_ref(n, hd_shape, dtype, seed):
    H, D = hd_shape
    rng = np.random.default_rng(seed)
    R = 12
    pages = jnp.asarray(rng.standard_normal((R, H, D)), dtype)
    q = jnp.asarray(rng.integers(0, 256, (n, H, D)), jnp.uint8)
    scales = jnp.asarray(rng.random(H) + 0.05, jnp.float32)
    # distinct slots in rows >= 1; one optional dropped token
    slots = rng.choice(np.arange(1, R), size=n, replace=False)
    if n > 1 and seed % 2:
        slots[-1] = -1
    slots = jnp.asarray(slots, jnp.int32)
    a = kv_restore(pages, q, scales, slots)
    b = kv_restore_ref(pages, q, scales, slots)
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# paged_attention
# ---------------------------------------------------------------------------

@given(st.sampled_from([(8, 2, 16), (8, 8, 32), (4, 1, 128), (16, 4, 64)]),
       st.sampled_from([4, 8, 16]),
       st.integers(0, 4))
@settings(max_examples=20, deadline=None)
def test_paged_attention_matches_ref(hkd, ps, seed):
    H, K, hd = hkd
    rng = np.random.default_rng(seed)
    B, P, bps = 2, 9, 3
    q = jnp.asarray(rng.standard_normal((B, H, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, ps, K, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, ps, K, hd)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, P, (B, bps)), jnp.int32)
    cl = jnp.asarray(rng.integers(1, bps * ps + 1, (B,)), jnp.int32)
    a = paged_attention(q, kp, vp, bt, cl)
    b = paged_attention_ref(q, kp, vp, bt, cl)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5,
                               atol=3e-5)


def test_paged_attention_matches_dense_attention():
    """Paged result == plain attention over the logically ordered KV."""
    rng = np.random.default_rng(0)
    B, H, K, hd, ps, bps = 2, 4, 2, 16, 4, 4
    S = ps * bps
    k = rng.standard_normal((B, S, K, hd)).astype(np.float32)
    v = rng.standard_normal((B, S, K, hd)).astype(np.float32)
    q = rng.standard_normal((B, H, hd)).astype(np.float32)
    cl = np.array([S, S - 3], np.int32)
    # scatter into pages: seq b uses pages [b*bps .. b*bps+bps)
    P = B * bps
    kp = k.reshape(B * bps, ps, K, hd)
    vp = v.reshape(B * bps, ps, K, hd)
    bt = np.arange(P, dtype=np.int32).reshape(B, bps)
    out = paged_attention(jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                          jnp.asarray(bt), jnp.asarray(cl))
    # dense reference
    g = H // K
    qg = q.reshape(B, K, g, hd)
    logits = np.einsum("bkgd,bskd->bkgs", qg, k) / np.sqrt(hd)
    mask = np.arange(S)[None] < cl[:, None]
    logits = np.where(mask[:, None, None], logits, -1e30)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    expect = np.einsum("bkgs,bskd->bkgd", w, v).reshape(B, H, hd)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# token_delta
# ---------------------------------------------------------------------------

@given(st.integers(1, 5), st.sampled_from([(8, 128), (16, 256), (5, 77)]),
       st.integers(0, 3))
@settings(max_examples=20, deadline=None)
def test_token_delta_encode_matches_ref(F, hw, seed):
    H, W = hw
    rng = np.random.default_rng(seed)
    video = jnp.asarray(rng.integers(0, 256, (F, H, W)), jnp.uint8)
    a = token_delta_encode(video)
    b = token_delta_encode_ref(video)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@given(st.sampled_from([(8, 128), (3, 50)]), st.integers(0, 3))
@settings(max_examples=10, deadline=None)
def test_token_delta_roundtrip(hw, seed):
    H, W = hw
    rng = np.random.default_rng(seed)
    video = jnp.asarray(rng.integers(0, 256, (4, H, W)), jnp.uint8)
    zres = token_delta_encode(video)
    prev = jnp.zeros((H, W), jnp.uint8)
    for f in range(4):
        frame = token_delta_decode_frame(prev, zres[f])
        assert np.array_equal(np.asarray(frame), np.asarray(video[f]))
        prev = frame


def test_zigzag_kernel_matches_lut():
    from repro.kernels.token_delta.token_delta import _unzigzag, _zigzag
    allb = jnp.arange(256, dtype=jnp.uint8)
    assert np.array_equal(np.asarray(_zigzag(allb)), ZIGZAG)
    assert np.array_equal(np.asarray(_unzigzag(allb)), UNZIGZAG)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------

@given(st.sampled_from([(1, 32, 2, 8, 1, 4), (2, 64, 4, 16, 2, 8),
                        (1, 100, 2, 8, 1, 4)]),
       st.integers(0, 3))
@settings(max_examples=12, deadline=None)
def test_ssd_scan_matches_ref(shape, seed):
    b, s, nh, hd, G, S = shape
    rng = np.random.default_rng(seed)
    xdt = jnp.asarray(rng.standard_normal((b, s, nh, hd)) * 0.3, jnp.float32)
    a_log = jnp.asarray(-np.abs(rng.standard_normal((b, s, nh))) * 0.1,
                        jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((b, s, G, S)) * 0.3, jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((b, s, G, S)) * 0.3, jnp.float32)
    y_k, st_k = ssd_scan(xdt, a_log, Bm, Cm, chunk=32)
    y_r, st_r = ssd_scan_ref(xdt, a_log, Bm, Cm, chunk=32)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_r),
                               rtol=2e-4, atol=2e-4)
