"""Public decode-attention ops: GQA grouping, padding, impl dispatch.

Two entry points:
- ``decode_attention``      — contiguous (B, Skv, Hkv, D) cache.
- ``decode_attention_paged``— one layer of the stacked block pool
  (L, NB, Hkv, BS, D) addressed through a per-row int32 block table
  (B, T); the jnp path gathers the layer's table into a contiguous view
  (bit-identical by construction), the Pallas path walks the table in
  SMEM via scalar prefetch and reads the stacked pool in place.
"""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import flash_decode_bhgd, flash_decode_paged_bhgd
from .ref import decode_attention_ref


def decode_attention(q, k, v, kv_len, *, window: int = 0,
                     scale: float | None = None, kv_start=None,
                     impl: str = "ref", block_k: int = 256):
    """q (B,Hq,D); k,v (B,Skv,Hkv,D); kv_len (B,) -> (B,Hq,D).

    ``kv_start`` (B,) int32 masks cache slots below it — the left-pad
    prefix a ragged prefill left in the cache (None = no padding).
    """
    if impl in ("ref", "xla"):
        # the jnp decode path is already linear-memory (scores (B,Hq,Skv))
        return decode_attention_ref(q, k, v, kv_len, window=window,
                                    scale=scale, kv_start=kv_start)
    interpret = impl == "pallas_interpret"
    b, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    bk = min(block_k, 128 * -(-skv // 128))      # whole 128-lane tiles

    qg = q.reshape(b, hkv, g, d)
    kt = jnp.swapaxes(k, 1, 2)                       # (B,Hkv,Skv,D)
    vt = jnp.swapaxes(v, 1, 2)
    # ragged Skv is padded to a block multiple inside flash_decode_bhgd

    out = flash_decode_bhgd(qg, kt, vt, kv_len, kv_start, window=window,
                            scale=scale, block_k=bk, interpret=interpret)
    return out.reshape(b, hq, d)


def decode_attention_paged(q, k_pool, v_pool, table, kv_len, layer=0, *,
                           window: int = 0, scale: float | None = None,
                           kv_start=None, impl: str = "ref"):
    """Paged decode attention over layer ``layer`` of a stacked pool.

    q (B,Hq,D); k_pool/v_pool (L,NB,Hkv,BS,D); table (B,T) int32 of pool
    block ids; kv_len (B,); layer an int or int32 scalar -> (B,Hq,D).
    Row b's logical column c is pool[layer, table[b, c // BS], :, c % BS];
    entries past the row's length should be 0 (the reserved trash block)
    so every gather stays in bounds.
    """
    b, hq, d = q.shape
    _, _, hkv, bs, _ = k_pool.shape
    if impl in ("ref", "xla"):
        t = table.shape[1]
        # gather the layer's table into the contiguous layout and defer to
        # the ref: table indexing is pure gather, so this IS the semantics
        # the Pallas path must reproduce bit-for-bit.
        def view(pool):              # (B,T,Hkv,BS,D) -> (B,T·BS,Hkv,D)
            return jnp.swapaxes(pool[layer, table], 2, 3).reshape(
                b, t * bs, hkv, d)
        return decode_attention_ref(q, view(k_pool), view(v_pool), kv_len,
                                    window=window, scale=scale,
                                    kv_start=kv_start)
    interpret = impl == "pallas_interpret"
    g = hq // hkv
    out = flash_decode_paged_bhgd(q.reshape(b, hkv, g, d), k_pool, v_pool,
                                  layer, table, kv_len, kv_start,
                                  window=window, scale=scale,
                                  interpret=interpret)
    return out.reshape(b, hq, d)
