"""Per-kernel allclose sweeps: Pallas (interpret=True) vs the jnp oracle,
across shapes and dtypes, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref)
from repro.kernels.flash_attention import attention, attention_ref
from repro.kernels.flash_attention.ref import attention_xla
from repro.kernels.mamba2_ssd import ssd, ssd_scan_ref
from repro.kernels.mamba2_ssd.ref import ssd_decode_ref
from repro.kernels.rwkv6_wkv import wkv6, wkv6_scan_ref
from repro.kernels.rwkv6_wkv.ref import wkv6_decode_ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- flash attention --

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,skv,hq,hkv,d,causal,window", [
    (1, 64, 64, 4, 4, 32, True, 0),       # MHA causal
    (2, 80, 80, 6, 2, 64, True, 0),       # GQA, non-multiple seq
    (2, 48, 48, 4, 1, 128, True, 0),      # MQA, big head
    (1, 64, 64, 4, 2, 32, False, 0),      # bidirectional
    (2, 96, 96, 4, 2, 32, True, 24),      # sliding window
    (1, 33, 33, 2, 2, 16, True, 0),       # odd seq
])
def test_flash_attention_sweep(b, sq, skv, hq, hkv, d, causal, window,
                               dtype):
    q = jnp.asarray(RNG.normal(size=(b, sq, hq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), dtype)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    pal = attention(q, k, v, causal=causal, window=window,
                    impl="pallas_interpret", block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_q_offset():
    """Chunked prefill: q block continuing an existing kv timeline."""
    q = jnp.asarray(RNG.normal(size=(1, 16, 2, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 48, 2, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 48, 2, 32)), jnp.float32)
    ref = attention_ref(q, k, v, causal=True, q_offset=32)
    pal = attention(q, k, v, causal=True, q_offset=32,
                    impl="pallas_interpret", block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_kv_start_masks_left_pad_on_all_impls():
    """Per-row kv_start (ragged-batch left padding): XLA and Pallas paths
    must agree with the oracle, and an explicit slice of the unpadded
    problem must agree with the masked padded one."""
    b, s, hq, hkv, d = 3, 64, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    kv_start = jnp.asarray([0, 17, 40], jnp.int32)
    ref = attention_ref(q, k, v, causal=True, kv_start=kv_start)
    for impl, kw in (("xla", {}), ("pallas_interpret",
                                   {"block_q": 32, "block_k": 32})):
        out = attention(q, k, v, causal=True, kv_start=kv_start,
                        impl=impl, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=impl)
    # row 2: the masked suffix must equal attention over the suffix alone
    st_ = 40
    solo = attention_ref(q[2:, st_:], k[2:, st_:], v[2:, st_:], causal=True)
    np.testing.assert_allclose(np.asarray(ref[2, st_:]),
                               np.asarray(solo[0]), rtol=2e-5, atol=2e-5)


def test_flash_attention_fully_masked_rows_finite():
    """kv_start == Skv (a filler row): output must be finite on every
    impl, never NaN from an all-masked softmax row."""
    b, s, hq, hkv, d = 2, 32, 2, 2, 16
    q = jnp.asarray(RNG.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    kv_start = jnp.asarray([s, 5], jnp.int32)     # row 0 fully masked
    for impl, kw in (("ref", {}), ("xla", {}),
                     ("pallas_interpret", {"block_q": 16, "block_k": 16})):
        out = attention(q, k, v, causal=True, kv_start=kv_start,
                        impl=impl, **kw)
        assert bool(jnp.all(jnp.isfinite(out))), impl


@pytest.mark.parametrize("impl", ["pallas", "pallas_interpret"])
def test_flash_attention_pallas_refuses_kv_len(impl):
    """The Pallas prefill kernel has no kv_len bound: asking for one raises
    instead of quietly running the oracle under the kernel's name."""
    q = jnp.zeros((1, 16, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="kv_len"):
        attention(q, q, q, kv_len=jnp.asarray([9], jnp.int32), impl=impl)


def test_decode_attention_kv_start_matches_unpadded():
    """Decode over a cache with left-pad junk below kv_start must equal
    decode over the compacted cache, ref and Pallas."""
    b, skv, hq, hkv, d = 2, 64, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), jnp.float32)
    kv_start = jnp.asarray([0, 24], jnp.int32)
    kv_len = jnp.asarray([50, 64], jnp.int32)
    ref = decode_attention_ref(q, k, v, kv_len, kv_start=kv_start)
    pal = decode_attention(q, k, v, kv_len, kv_start=kv_start,
                           impl="pallas_interpret", block_k=128)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # row 1 vs the compacted (junk removed) cache
    solo = decode_attention_ref(q[1:], k[1:, 24:], v[1:, 24:],
                                jnp.asarray([40], jnp.int32))
    np.testing.assert_allclose(np.asarray(ref[1]), np.asarray(solo[0]),
                               rtol=2e-5, atol=2e-5)


def test_attention_xla_chunked_matches_oracle():
    q = jnp.asarray(RNG.normal(size=(2, 100, 6, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 100, 3, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 100, 3, 32)), jnp.float32)
    for causal, window in [(True, 0), (False, 0), (True, 13)]:
        ref = attention_ref(q, k, v, causal=causal, window=window)
        out = attention_xla(q, k, v, causal=causal, window=window,
                            block_q=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@settings(max_examples=15, deadline=None)
@given(sq=st.integers(8, 70), hkv=st.sampled_from([1, 2, 3]),
       g=st.sampled_from([1, 2, 4]), d=st.sampled_from([16, 32]))
def test_flash_attention_hypothesis(sq, hkv, g, d):
    q = jnp.asarray(RNG.normal(size=(1, sq, hkv * g, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, sq, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, sq, hkv, d)), jnp.float32)
    ref = attention_ref(q, k, v, causal=True)
    pal = attention(q, k, v, causal=True, impl="pallas_interpret",
                    block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), rtol=3e-5,
                               atol=3e-5)


# ------------------------------------------------------ decode attention --

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,skv,hq,hkv,d,window", [
    (2, 300, 4, 2, 64, 0),
    (1, 128, 8, 1, 32, 0),        # MQA
    (3, 257, 6, 6, 32, 0),        # MHA odd cache
    (2, 300, 4, 2, 64, 64),       # windowed
])
def test_decode_attention_sweep(b, skv, hq, hkv, d, window, dtype):
    q = jnp.asarray(RNG.normal(size=(b, hq, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, skv, hkv, d)), dtype)
    kv_len = jnp.asarray(RNG.integers(window + 1 if window else 1, skv + 1,
                                      size=(b,)), jnp.int32)
    ref = decode_attention_ref(q, k, v, kv_len, window=window)
    pal = decode_attention(q, k, v, kv_len, window=window,
                           impl="pallas_interpret", block_k=128)
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_decode_matches_full_attention_last_row():
    """decode(q_last | cache) == full-causal attention's last row."""
    b, s, hq, hkv, d = 2, 40, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, d)), jnp.float32)
    full = attention_ref(q, k, v, causal=True)
    dec = decode_attention_ref(q[:, -1], k, v,
                               jnp.full((b,), s, jnp.int32))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]),
                               rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------- SSD ----

@pytest.mark.parametrize("impl", ["chunked", "pallas_interpret"])
@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 96, 4, 8, 2, 16, 32),
    (1, 64, 2, 16, 1, 8, 16),
    (2, 100, 4, 8, 1, 16, 32),      # needs padding
])
def test_ssd_sweep(b, s, h, p, g, n, chunk, impl):
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    y0, h0 = ssd_scan_ref(x, dt, A, Bm, Cm)
    y1, h1 = ssd(x, dt, A, Bm, Cm, chunk=chunk, impl=impl)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h0), rtol=2e-4,
                               atol=2e-4)


def test_ssd_initial_state_and_decode_consistency():
    """Chunked scan with h0 == continuing the sequence; decode == 1-step."""
    b, s, h, p, g, n = 1, 64, 2, 8, 1, 8
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    yf, hf = ssd_scan_ref(x, dt, A, Bm, Cm)
    # split at 32: scan first half, then chunked-with-state second half
    y1, h1 = ssd_scan_ref(x[:, :32], dt[:, :32], A, Bm[:, :32], Cm[:, :32])
    y2, h2 = ssd(x[:, 32:], dt[:, 32:], A, Bm[:, 32:], Cm[:, 32:],
                 h0=h1, chunk=16, impl="chunked")
    np.testing.assert_allclose(np.asarray(y2), np.asarray(yf[:, 32:]),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(hf), rtol=2e-4,
                               atol=2e-4)
    # single-token decode continues exactly
    y3, h3 = ssd_decode_ref(x[:, 32, :, :], dt[:, 32], A, Bm[:, 32],
                            Cm[:, 32], h1)
    np.testing.assert_allclose(np.asarray(y3), np.asarray(yf[:, 32]),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(4, 80), chunk=st.sampled_from([8, 16, 32]),
       h=st.sampled_from([1, 2, 4]))
def test_ssd_hypothesis(s, chunk, h):
    b, p, g, n = 1, 4, 1, 4
    x = jnp.asarray(RNG.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.3, size=(b, s, h)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 2.0, size=(h,)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(b, s, g, n)), jnp.float32)
    y0, h0 = ssd_scan_ref(x, dt, A, Bm, Cm)
    y1, h1 = ssd(x, dt, A, Bm, Cm, chunk=chunk, impl="chunked")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=3e-4,
                               atol=3e-4)


# ---------------------------------------------------------------- WKV6 ----

@pytest.mark.parametrize("impl", ["chunked", "pallas_interpret"])
@pytest.mark.parametrize("b,s,h,k,chunk", [
    (2, 96, 4, 8, 32),
    (1, 64, 2, 16, 16),
    (2, 70, 4, 8, 32),             # needs padding
])
def test_wkv6_sweep(b, s, h, k, chunk, impl):
    r = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    kk = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    logw = jnp.asarray(-RNG.uniform(0.01, 1.0, size=(b, s, h, k)),
                       jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, k)), jnp.float32)
    o0, s0 = wkv6_scan_ref(r, kk, v, logw, u)
    o1, s1 = wkv6(r, kk, v, logw, u, chunk=chunk, impl=impl)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), rtol=5e-4,
                               atol=5e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), rtol=5e-4,
                               atol=5e-4)


def test_wkv6_decode_consistency():
    b, s, h, k = 1, 33, 2, 8
    r = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    kk = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    logw = jnp.asarray(-RNG.uniform(0.01, 1.0, size=(b, s, h, k)),
                       jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, k)), jnp.float32)
    of, sf = wkv6_scan_ref(r, kk, v, logw, u)
    o1, s1 = wkv6(r[:, :-1], kk[:, :-1], v[:, :-1], logw[:, :-1], u,
                  chunk=8, impl="chunked")
    o2, s2 = wkv6_decode_ref(r[:, -1], kk[:, -1], v[:, -1], logw[:, -1],
                             u, s1)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(of[:, -1]),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sf), rtol=5e-4,
                               atol=5e-4)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(4, 70), chunk=st.sampled_from([8, 16, 32]))
def test_wkv6_hypothesis(s, chunk):
    b, h, k = 1, 2, 4
    r = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    kk = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, h, k)), jnp.float32)
    logw = jnp.asarray(-RNG.uniform(0.01, 2.0, size=(b, s, h, k)),
                       jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, k)), jnp.float32)
    o0, s0 = wkv6_scan_ref(r, kk, v, logw, u)
    o1, s1 = wkv6(r, kk, v, logw, u, chunk=chunk, impl="chunked")
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), rtol=1e-3,
                               atol=1e-3)
