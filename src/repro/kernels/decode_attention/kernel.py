"""Pallas TPU flash-decode: one query token vs. a long KV cache.

Decode is memory-bound (roofline: stream the whole cache at ~2 bytes/FLOP),
so the kernel's job is to stream K/V through VMEM exactly once while all G
query heads of a kv group ride along — GQA turns the dot into a (G, bk)
matmul, amortizing the K/V read across the group (the TPU adaptation of
GPU flash-decode, where warps split the cache instead).

Layout: q (B, Hkv, G, D); k, v (B, Hkv, Skv, D); (kv_start, kv_len) as a
(B, 2) int32 bounds plane, a scalar-prefetch operand in SMEM — start masks
left-pad cache slots from ragged prefill, len bounds the live suffix.
Grid (B, Hkv, Skv/bk) — kv dim minor-most/sequential; running softmax state
in VMEM scratch (the prefill kernel's lane-replicated layout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..flash_attention.kernel import (LANES, softmax_finish, softmax_init,
                                      softmax_update)


def _tile_live(col0, width, kv_start, kv_len, window):
    live = col0 < kv_len
    live &= col0 + width > kv_start          # tile fully inside the left pad
    if window:
        live &= col0 + width > kv_len - window
    return live


def _decode_tile(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *, scale, col0,
                 kv_start, kv_len, window):
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G,bk)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (cols < kv_len) & (cols >= kv_start)
    if window:
        mask &= cols >= kv_len - window
    softmax_update(s, mask, v, acc_ref, m_ref, l_ref)


def _dec_kernel(bounds_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                l_ref, *, scale, window, block_k, kv_blocks):
    b_ = pl.program_id(0)
    ik = pl.program_id(2)
    kv_start = bounds_ref[b_, 0]             # first valid slot (left pad end)
    kv_len = bounds_ref[b_, 1]

    @pl.when(ik == 0)
    def _init():
        softmax_init(acc_ref, m_ref, l_ref)

    col0 = ik * block_k

    @pl.when(_tile_live(col0, block_k, kv_start, kv_len, window))
    def _body():
        _decode_tile(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale=scale,
                     col0=col0, kv_start=kv_start, kv_len=kv_len,
                     window=window)

    @pl.when(ik == kv_blocks - 1)
    def _fin():
        softmax_finish(acc_ref, l_ref, o_ref)


def _scratch(g, d):
    return [pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32),
            pltpu.VMEM((g, LANES), jnp.float32)]


def _bounds(b, kv_len, kv_start):
    if kv_start is None:
        kv_start = jnp.zeros((b,), jnp.int32)
    return jnp.stack([kv_start.astype(jnp.int32),
                      kv_len.astype(jnp.int32)], axis=1)      # (B, 2)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "block_k", "interpret"))
def flash_decode_bhgd(q, k, v, kv_len, kv_start=None, *, window=0, scale=None,
                      block_k=256, interpret=False):
    """q (B,Hkv,G,D); k,v (B,Hkv,Skv,D); kv_len/kv_start (B,) ->
    (B,Hkv,G,D).  kv_start masks left-pad cache slots (None = 0)."""
    b, hkv, g, d = q.shape
    _, _, skv, _ = k.shape
    # Ragged tail: pad K/V with zeros up to a block_k multiple instead of
    # asserting divisibility.  The kv_len column mask already excludes the
    # pad columns from the softmax; zero-padding (not garbage) keeps the
    # masked p·v products finite on hardware.
    tail = (-skv) % block_k
    if tail:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tail), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tail), (0, 0)))
        skv += tail
    scale = scale if scale is not None else d ** -0.5
    kv_blocks = skv // block_k

    kernel = functools.partial(_dec_kernel, scale=scale, window=window,
                               block_k=block_k, kv_blocks=kv_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,          # bounds — SMEM, whole array
        grid=(b, hkv, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h, j, bnd: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, j, bnd: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h, j, bnd: (b_, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, h, j, bnd: (b_, h, 0, 0)),
        scratch_shapes=_scratch(g, d),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(_bounds(b, kv_len, kv_start), q, k, v)


def _dec_paged_kernel(layer_ref, table_ref, bounds_ref, q_ref, k_ref, v_ref,
                      o_ref, acc_ref, m_ref, l_ref, *, scale, window,
                      block_size, table_width):
    """Block-table flash-decode body: grid dim 2 walks the row's table.

    Identical running-softmax math to `_dec_kernel`; the only change is
    that tile j holds *logical* columns [j·bs, (j+1)·bs) gathered from
    physical pool block `table[b, j]` of layer `layer` by the BlockSpec
    index_map — dead table entries point at block 0 and are masked out by
    kv_len anyway."""
    del layer_ref                        # read by the index_maps only
    b_ = pl.program_id(0)
    ik = pl.program_id(2)
    kv_start = bounds_ref[b_, 0]
    kv_len = bounds_ref[b_, 1]

    @pl.when(ik == 0)
    def _init():
        softmax_init(acc_ref, m_ref, l_ref)

    col0 = ik * block_size

    @pl.when(_tile_live(col0, block_size, kv_start, kv_len, window))
    def _body():
        _decode_tile(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, scale=scale,
                     col0=col0, kv_start=kv_start, kv_len=kv_len,
                     window=window)

    @pl.when(ik == table_width - 1)
    def _fin():
        softmax_finish(acc_ref, l_ref, o_ref)


@functools.partial(
    jax.jit, static_argnames=("window", "scale", "interpret"))
def flash_decode_paged_bhgd(q, k_pool, v_pool, layer, table, kv_len,
                            kv_start=None, *, window=0, scale=None,
                            interpret=False):
    """Paged flash-decode: q (B,Hkv,G,D); k_pool/v_pool (L,NB,Hkv,BS,D),
    every layer's pool stacked; ``layer`` int32 scalar; table (B,T) int32
    of pool block ids -> (B,Hkv,G,D).

    Row b's logical cache column c of layer l lives at pool[l, table[b, c
    // BS], :, c % BS].  The layer and the table ride in as scalar-prefetch
    operands so the K/V BlockSpec index_maps gather physical blocks of one
    layer straight out of the stacked pool per grid step: nothing of pool
    size is sliced or copied to feed the kernel.  The tile size IS the
    block size, so masking is byte-for-byte the contiguous kernel's.
    Unused table entries should be 0 (the reserved trash block) — they are
    masked by kv_len but must still be valid ids."""
    b, hkv, g, d = q.shape
    _, _, _, bs, _ = k_pool.shape
    t = table.shape[1]
    scale = scale if scale is not None else d ** -0.5

    def kv_map(b_, h, j, layer_ref, table_ref, bounds_ref):
        return (layer_ref[0], table_ref[b_, j], h, 0, 0)

    def q_map(b_, h, j, layer_ref, table_ref, bounds_ref):
        return (b_, h, 0, 0)

    kernel = functools.partial(_dec_paged_kernel, scale=scale, window=window,
                               block_size=bs, table_width=t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, table, bounds: SMEM, index_maps
        grid=(b, hkv, t),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), q_map),
            pl.BlockSpec((None, 1, 1, bs, d), kv_map),
            pl.BlockSpec((None, 1, 1, bs, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_map),
        scratch_shapes=_scratch(g, d),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), table.astype(jnp.int32),
      _bounds(b, kv_len, kv_start), q, k_pool, v_pool)
