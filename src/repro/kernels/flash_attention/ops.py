"""Public attention op: layout handling, padding, impl dispatch.

``attention(q, k, v)`` takes the model-native layout (B, S, H, D) and
dispatches to the Pallas kernel (TPU target; ``interpret=True`` executes the
kernel body on CPU) or the pure-jnp oracle in ``ref.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bhsd
from .ref import attention_ref, attention_xla


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              q_offset: int = 0, scale: float | None = None,
              kv_len=None, kv_start=None, impl: str = "ref",
              block_q: int = 128, block_k: int = 128):
    """q (B,Sq,Hq,D); k,v (B,Skv,Hkv,D) -> (B,Sq,Hq,D).

    ``kv_start`` (B,) int32: per-row left-pad count — kv positions < start
    are masked out on every impl (ragged-batch prefill).  A fully masked
    row (start == Skv) yields finite output, never NaN.

    impl: "ref" (jnp oracle) | "xla" (query-chunked jnp) | "pallas" (TPU) |
    "pallas_interpret" (CPU execution of the kernel body, used by the
    allclose test sweeps).  ``kv_len`` (B,) bounds the valid keys per row
    on the jnp impls only; the Pallas impls raise on it.
    """
    if impl == "ref":
        return attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale, kv_len=kv_len,
                             kv_start=kv_start)
    if impl == "xla":
        return attention_xla(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, scale=scale, kv_len=kv_len,
                             kv_start=kv_start)
    if kv_len is not None:
        raise ValueError(
            f"attention impl={impl!r} takes no kv_len: the Pallas prefill "
            "kernel bounds keys by Skv only — pick impl='ref' or 'xla' at "
            "the call site")

    interpret = impl == "pallas_interpret"
    b, sq, hq, d = q.shape
    bq = min(block_q, max(16, sq))
    bk = min(block_k, max(16, k.shape[1]))

    qt = jnp.swapaxes(q, 1, 2)                    # (B,Hq,Sq,D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    qt, sq0 = _pad_to(qt, 2, bq)
    kt, _ = _pad_to(kt, 2, bk)
    vt, _ = _pad_to(vt, 2, bk)

    out = flash_attention_bhsd(qt, kt, vt, kv_start, causal=causal,
                               window=window, q_offset=q_offset, scale=scale,
                               block_q=bq, block_k=bk, interpret=interpret)
    out = out[:, :, :sq0]
    return jnp.swapaxes(out, 1, 2)
