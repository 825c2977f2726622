"""GQA/MQA/MHA attention block with KV-cache, RoPE / M-RoPE, windows.

Three execution modes, all from the same params:
  * full  — training / prefill: flash kernel (TPU) or jnp oracle (CPU)
  * prefill — full + returns the populated KV cache
  * decode — one token against a cache (flash-decode kernel or oracle)
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.decode_attention import decode_attention, decode_attention_paged
from ..kernels.flash_attention import attention
from ..sharding import shard
from .layers import apply_mrope, apply_rope, dense_init


def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int,
              head_dim: int, dtype, *, bias: bool = False,
              stack: tuple[int, ...] = ()):
    ks = jax.random.split(key, 4)
    pre = stack
    ps = ("layers",) * len(stack)
    p, s = {}, {}
    # fan-in is the whole contracted width (d_model in, Hq·D out), not the
    # heads axis dense_init would take from these 3-D shapes
    s_in, s_out = d_model ** -0.5, (n_heads * head_dim) ** -0.5
    p["wq"], s["wq"] = dense_init(ks[0], (*pre, d_model, n_heads, head_dim),
                                  (*ps, "embed", "heads", "head_dim"), dtype,
                                  scale=s_in)
    p["wk"], s["wk"] = dense_init(ks[1], (*pre, d_model, n_kv_heads, head_dim),
                                  (*ps, "embed", "kv_heads", "head_dim"), dtype,
                                  scale=s_in)
    p["wv"], s["wv"] = dense_init(ks[2], (*pre, d_model, n_kv_heads, head_dim),
                                  (*ps, "embed", "kv_heads", "head_dim"), dtype,
                                  scale=s_in)
    p["wo"], s["wo"] = dense_init(ks[3], (*pre, n_heads, head_dim, d_model),
                                  (*ps, "heads", "head_dim", "embed"), dtype,
                                  scale=s_out)
    if bias:
        for nm, hs, ax in (("bq", n_heads, "heads"),
                           ("bk", n_kv_heads, "kv_heads"),
                           ("bv", n_kv_heads, "kv_heads")):
            p[nm] = jnp.zeros((*pre, hs, head_dim), dtype)
            s[nm] = (*ps, ax, "head_dim")
    return p, s


def _project(p, x, positions, *, rope_theta, mrope_sections, pos3d):
    """x (B,S,d) -> q (B,S,Hq,D), k/v (B,S,Hkv,D), rotary applied."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if rope_theta:
        if mrope_sections:
            q = apply_mrope(q, pos3d, rope_theta, mrope_sections)
            k = apply_mrope(k, pos3d, rope_theta, mrope_sections)
        else:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)
    q = shard(q, "act_batch", "act_seq", "act_heads", None)
    k = shard(k, "act_batch", "act_seq", "act_kv_heads", None)
    v = shard(v, "act_batch", "act_seq", "act_kv_heads", None)
    return q, k, v


def attn_full(p, x, positions, *, causal=True, window=0, rope_theta=0.0,
              mrope_sections=(), pos3d=None, impl="ref", kv_x=None,
              kv_start=None, return_kv=False) -> Any:
    """Training / prefill attention.  kv_x: cross-attention source.
    kv_start (B,): per-row left-pad count — pad keys are masked out."""
    if kv_x is None:
        q, k, v = _project(p, x, positions, rope_theta=rope_theta,
                           mrope_sections=mrope_sections, pos3d=pos3d)
    else:  # cross-attn: q from x, k/v from encoder output (no rope)
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"])
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        causal = False
    o = attention(q, k, v, causal=causal, window=window, impl=impl,
                  kv_start=kv_start)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    out = shard(out, "act_batch", "act_seq", "act_embed")
    if return_kv:
        return out, (k, v)
    return out


def quantize_kv(x):
    """Per-(token, head) symmetric int8.  x (..., D) -> (q int8, scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def attn_decode(p, x, cache_k, cache_v, idx, *, window=0, rope_theta=0.0,
                mrope_sections=(), pos3d=None, impl="ref",
                update_cache=True, cache_ks=None, cache_vs=None,
                kv_start=None):
    """One-token attention.  x (B,1,d); cache_k/v (B,Smax,Hkv,D); idx scalar
    position of the new token.  With int8-quantized caches, cache_ks/vs are
    the (B,Smax,Hkv) scale planes (updated and returned alongside).
    kv_start (B,): per-row first valid cache slot — positions below it are
    left-pad junk from a ragged prefill; it also offsets RoPE so the new
    token's rotary position counts real tokens, not buffer slots.
    Returns (out, cache_k, cache_v[, cache_ks, cache_vs])."""
    b = x.shape[0]
    quant = cache_ks is not None
    positions = jnp.full((b, 1), idx, jnp.int32)
    if kv_start is not None:
        positions = positions - kv_start[:, None].astype(jnp.int32)
    q, k, v = _project(p, x, positions, rope_theta=rope_theta,
                       mrope_sections=mrope_sections, pos3d=pos3d)
    if update_cache:
        if quant:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            cache_k = jax.lax.dynamic_update_slice(cache_k, kq,
                                                   (0, idx, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(cache_v, vq,
                                                   (0, idx, 0, 0))
            cache_ks = jax.lax.dynamic_update_slice(cache_ks, ks,
                                                    (0, idx, 0))
            cache_vs = jax.lax.dynamic_update_slice(cache_vs, vs,
                                                    (0, idx, 0))
        else:
            cache_k = jax.lax.dynamic_update_slice(
                cache_k, k.astype(cache_k.dtype), (0, idx, 0, 0))
            cache_v = jax.lax.dynamic_update_slice(
                cache_v, v.astype(cache_v.dtype), (0, idx, 0, 0))
    kv_len = jnp.full((b,), idx + 1, jnp.int32)
    if quant:
        kd = dequantize_kv(cache_k, cache_ks, q.dtype)
        vd = dequantize_kv(cache_v, cache_vs, q.dtype)
    else:
        kd, vd = cache_k, cache_v
    o = decode_attention(q[:, 0], kd, vd, kv_len, window=window, impl=impl,
                         kv_start=kv_start)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    if quant:
        return out, cache_k, cache_v, cache_ks, cache_vs
    return out, cache_k, cache_v


# -------------------------------------------------- paged (block-table) ----
# Paged rows are RIGHT-dense: row content occupies logical columns
# [0, len), kv_start is always 0, and RoPE position == logical column —
# the per-row block table maps logical columns to physical pool blocks.
# Both facts together are what make paged decode bit-identical to the
# left-padded solo path: the per-token q/k values are equal (same RoPE
# positions), and the masked-softmax reductions are placement/width
# invariant as long as every gathered width stays a power of two.

def _pool_write(pool, layer, table, start, kv, n):
    """Write one run of new K or V per row into layer ``layer`` of the
    stacked pool (L,NB,Hkv,BS,D); returns the pool.

    table (R,T) int32 block tables; start (R,) the logical column where
    each row's run begins; kv (R,C,Hkv,D) the runs, of which each row's
    first ``n`` (R,) are real.  Every block a run can touch is gathered
    whole, the run's real tokens are selected into it, and the blocks are
    scattered back whole: a scatter whose window is a whole (Hkv,BS,D)
    block keeps the pool in the kernel's layout, so XLA updates the
    carried pool in place.  (Scattering single tokens, an (Hkv,D) window,
    has XLA lay the pool out with Hkv minor to BS, and copy it back whole
    for the kernel every layer.)  Rows with n == 0 and columns past the
    table touch only the trash block 0."""
    r, c = kv.shape[:2]
    _, _, hkv, bs, d = pool.shape
    t = table.shape[1]
    nt = min(t, (c + bs - 2) // bs + 1)       # blocks a run of c can span
    lb = start[:, None] // bs + jnp.arange(nt)                  # (R,nt)
    ids = jnp.take_along_axis(table, jnp.minimum(lb, t - 1), axis=1)
    ids = jnp.where((lb < t) & (n[:, None] > 0), ids, 0)
    j = jnp.arange(nt * bs) - (start % bs)[:, None]     # run index per column
    real = ((j >= 0) & (j < n[:, None])).reshape(r, nt, 1, bs, 1)
    vals = kv[jnp.arange(r)[:, None], jnp.clip(j, 0, c - 1)]  # R,nt·BS,Hkv,D
    vals = jnp.swapaxes(vals.reshape(r, nt, bs, hkv, d), 2, 3)
    blocks = jnp.where(real, vals.astype(pool.dtype), pool[layer, ids])
    return pool.at[layer, ids].set(blocks)


def attn_decode_paged(p, x, pool_k, pool_v, layer, table, lens, live, *,
                      window=0, rope_theta=0.0, impl="ref"):
    """One-token attention of layer ``layer`` against the stacked pool.

    x (B,1,d); pool_k/pool_v (L,NB,Hkv,BS,D), every layer's blocks;
    layer an int or int32 scalar; table (B,T) int32; lens (B,) tokens
    already resident per row; live (B,) bool.

    The new token is written at logical column ``lens`` — physically
    ``pool[layer, table[b, lens // BS], :, lens % BS]`` — into the pool
    the caller carries, which the kernel then reads in place: nothing of
    pool size is sliced or copied.  Dead rows write nothing (their kv_len
    is 0, so the kernel's l == 0 guard zeroes the whole row).
    Returns (out, pool_k, pool_v)."""
    lens = lens.astype(jnp.int32)
    positions = lens[:, None]                       # right-dense: pos == len
    q, k, v = _project(p, x, positions, rope_theta=rope_theta,
                       mrope_sections=(), pos3d=None)
    n = live.astype(jnp.int32)                      # one token per live row
    pool_k = _pool_write(pool_k, layer, table, lens, k, n)
    pool_v = _pool_write(pool_v, layer, table, lens, v, n)
    kv_len = jnp.where(live, lens + 1, 0).astype(jnp.int32)
    o = decode_attention_paged(q[:, 0], pool_k, pool_v, table, kv_len, layer,
                               window=window, impl=impl)
    out = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    return out, pool_k, pool_v


def attn_prefill_paged(p, x, pool_k, pool_v, layer, table, m, n_real, *,
                       window=0, rope_theta=0.0, impl="ref"):
    """One chunk of continued prefill of layer ``layer`` against the
    stacked pool (B == 1).

    x (1,C,d) — the chunk's embeddings, real tokens in [0, n_real), the
    rest right-pad; ``m`` is how many tokens of this row the pool already
    holds, so chunk token j is logical column m + j.  K/V for real chunk
    positions are written into the row's table-mapped blocks of ``layer``
    (pad positions are not written); attention runs q_offset = m against
    the row's T blocks of that layer gathered into a contiguous view,
    masked to kv_len = m + n_real.  Chaining calls with growing ``m``
    reproduces a monolithic prefill bit-for-bit.
    Returns (out (1,C,d-model), pool_k, pool_v)."""
    _, c, _ = x.shape
    _, _, hkv, bs, d = pool_k.shape
    t = table.shape[1]
    j = jnp.arange(c)
    positions = (m + j)[None, :]
    q, k, v = _project(p, x, positions, rope_theta=rope_theta,
                       mrope_sections=(), pos3d=None)
    start, n = jnp.reshape(m, (1,)), jnp.reshape(n_real, (1,))
    pool_k = _pool_write(pool_k, layer, table, start, k, n)
    pool_v = _pool_write(pool_v, layer, table, start, v, n)

    def view(pool):                          # (T,Hkv,BS,D) -> (1,T·BS,Hkv,D)
        return jnp.swapaxes(pool[layer, table[0]], 1, 2).reshape(
            1, t * bs, hkv, d).astype(q.dtype)

    kv_len = jnp.full((1,), m + n_real, jnp.int32)
    # The Pallas prefill kernel has no kv_len bound, so a chunk attends
    # over its gathered view on the query-chunked jnp path on every
    # platform — the same code the CPU invariance tests pin.
    o = attention(q, view(pool_k), view(pool_v), causal=True, window=window,
                  q_offset=m, kv_len=kv_len,
                  impl="ref" if impl == "ref" else "xla")
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, pool_k, pool_v
