"""Pallas TPU flash attention (tiled, causal/windowed, GQA).

Layout: q (B, Hq, Sq, D); k, v (B, Hkv, Skv, D).  Grid (B, Hq, Sq/bq,
Skv/bk) — the kv-block dim is minor-most, so it iterates sequentially on TPU
and the running softmax state (acc, m, l) lives in VMEM scratch across kv
blocks.  Fully-masked kv blocks are skipped with ``pl.when`` (causal upper
triangle and out-of-window lower band), so the causal pass does ~half the
work — the roofline win the paper's tiling (32x32 -> 16x16, Fig 1) chases.

Block sizes default to 128 (MXU-aligned); D is kept whole per block
(64..256 for the assigned archs — fits VMEM comfortably:
3 * 128 * 256 * 4 B < 0.5 MiB working set per operand).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
# Running max and sum live lane-replicated in (rows, LANES) f32 scratch, the
# TPU flash layout: Mosaic lays out no 1-D vector of rows.
LANES = 128


def _lanes(x, n):
    """Widen a lane-replicated (R, LANES) value to (R, n) by slicing or
    tiling whole vregs."""
    if n <= LANES:
        return x[:, :n]
    assert n % LANES == 0, f"tile width {n} is not a multiple of {LANES}"
    return jnp.tile(x, (1, n // LANES))


def softmax_init(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def softmax_update(s, mask, v, acc_ref, m_ref, l_ref):
    """One online-softmax step over a tile: scores ``s`` (R, bk) f32, its
    validity ``mask``, values ``v`` (bk, D) f32.  Shared by the prefill and
    both decode kernels."""
    bk, d = v.shape
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]               # (R, LANES)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_s = _lanes(m_new, bk)
    p = jnp.exp(s - m_s)
    # a row with NO valid col so far (m_new == NEG_INF: a pad query sharing
    # a live tile with real rows, or kv_start >= kv_len) must contribute 0,
    # not exp(NEG_INF - NEG_INF) = 1 per col — keeps l at 0 so the finish
    # step zeroes it, matching the ref path
    p = jnp.where(m_s > 0.5 * NEG_INF, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = (acc_ref[...] * _lanes(alpha, d)
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))


def softmax_finish(acc_ref, l_ref, o_ref):
    l = _lanes(l_ref[...], acc_ref.shape[1])
    l = jnp.where(l == 0.0, 1.0, l)         # fully-masked rows -> 0 output
    o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _fa_kernel(start_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
               *, scale, causal, window, q_offset, block_q, block_k,
               kv_blocks, kv_valid):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    kv_start = start_ref[pl.program_id(0)]  # left-pad count for this row

    @pl.when(ik == 0)
    def _init():
        softmax_init(acc_ref, m_ref, l_ref)

    # absolute coordinates of this tile
    row0 = iq * block_q + q_offset          # first absolute q position
    col0 = ik * block_k

    # tile-level skip: causal upper triangle / sliding-window lower band /
    # left-pad prefix tiles
    live = col0 < kv_valid                  # beyond valid kv (padding) tile
    live &= col0 + block_k > kv_start       # tile fully inside the left pad
    if causal:
        live &= col0 <= row0 + block_q - 1
    if window:
        live &= col0 + block_k - 1 > row0 - window

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = (cols < kv_valid) & (cols >= kv_start)
        if causal:
            mask &= cols <= rows
        if window:
            mask &= cols > rows - window
        softmax_update(s, mask, v, acc_ref, m_ref, l_ref)

    @pl.when(ik == kv_blocks - 1)
    def _fin():
        softmax_finish(acc_ref, l_ref, o_ref)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "scale",
                              "block_q", "block_k", "interpret"))
def flash_attention_bhsd(q, k, v, kv_start=None, *, causal=True, window=0,
                         q_offset=0, scale=None, block_q=128, block_k=128,
                         interpret=False):
    """q (B,Hq,Sq,D); k,v (B,Hkv,Skv,D) — Skv/Sq already padded by ops.py.

    ``q_offset``: absolute position of q[0] on the kv timeline.
    ``kv_start`` (B,) int32: per-row left-pad count — kv positions before it
    are masked out (ragged-batch prefill).  None = no padding.  It rides in
    as a scalar-prefetch operand (whole array in SMEM).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0 and sq % block_q == 0 and skv % block_k == 0
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    kv_blocks = skv // block_k
    if kv_start is None:
        kv_start = jnp.zeros((b,), jnp.int32)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        kv_blocks=kv_blocks, kv_valid=skv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, sq // block_q, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h, i, j, st: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, st: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, i, j, st: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h, i, j, st: (b_, h, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        interpret=interpret,
    )(kv_start.astype(jnp.int32), q, k, v)
