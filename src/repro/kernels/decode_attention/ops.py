"""Public decode-attention ops: GQA grouping, padding, impl dispatch.

Two entry points:
- ``decode_attention``      — contiguous (B, Skv, Hkv, D) cache.
- ``decode_attention_paged``— block-pool cache (NB, BS, Hkv, D) addressed
  through a per-row int32 block table (B, T); the jnp path gathers the
  table into a contiguous view (bit-identical by construction), the
  Pallas path walks the table in SMEM via scalar prefetch.
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


def decode_attention_paged(q, k_pool, v_pool, table, kv_len, *,
                           window: int = 0, scale: float | None = None,
                           kv_start=None, impl: str = "ref"):
    """Paged decode attention.

    q (B,Hq,D); k_pool/v_pool (NB,BS,Hkv,D); table (B,T) int32 of pool
    block ids; kv_len (B,) -> (B,Hq,D).  Row b's logical column c is
    pool[table[b, c // BS], c % BS]; entries past the row's length should
    be 0 (the reserved trash block) so every gather stays in bounds.
    """
    b, hq, d = q.shape
    nb, bs, hkv, _ = k_pool.shape
    if impl in ("ref", "xla"):
        t = table.shape[1]
        # gather the table into the contiguous layout and defer to the ref:
        # table indexing is pure gather, so this IS the semantics the
        # Pallas path must reproduce bit-for-bit.
        kc = k_pool[table].reshape(b, t * bs, hkv, d)
        vc = v_pool[table].reshape(b, t * bs, hkv, d)
        return decode_attention_ref(q, kc, vc, kv_len, window=window,
                                    scale=scale, kv_start=kv_start)
    interpret = impl == "pallas_interpret"
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    kp = jnp.swapaxes(k_pool, 1, 2)                  # (NB,Hkv,BS,D)
    vp = jnp.swapaxes(v_pool, 1, 2)
    out = flash_decode_paged_bhgd(qg, kp, vp, table, kv_len, kv_start,
                                  window=window, scale=scale,
                                  interpret=interpret)
    return out.reshape(b, hq, d)
