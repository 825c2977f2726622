"""Operations and bytes that a decode step must do, from the configuration.

The counts come from the configuration's family (``references.family``):
its published shapes and the live lengths, never what an implementation
pads to.  A kernel that reads dead tiles or a step that copies its pool does
more work than this, and shows it as a lower share of the peak.
"""
from __future__ import annotations

import json
from collections import Counter
from pathlib import Path

import references

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error, not a
    default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


def matmul_params(cfg: dict) -> int:
    """Weights every token multiplies by."""
    return references.family(cfg).matmul_flops_per_token(cfg) // 2


def step_weight_bytes(cfg: dict, rows: float) -> float:
    """Bytes of weights a decode step of ``rows`` live rows must read."""
    return references.family(cfg).step_weight_bytes(cfg, rows)


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of keys and values that one cached token holds, all layers."""
    return references.family(cfg).attention_work(cfg, 1)[1]


def paged_kernel_work(cfg: dict, kv_lens: dict) -> tuple[int, int]:
    """(flops, bytes) that the paged decode-attention kernel must do, over
    all layers, for query tokens at the live key counts ``kv_lens``
    ({live length: query tokens})."""
    work = references.family(cfg).attention_work
    flops = nbytes = 0
    for kv_len, n in kv_lens.items():
        f, b = work(cfg, kv_len)
        flops += n * f
        nbytes += n * b
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def decode_work(cfg: dict, records, t0: float, t1: float) -> dict:
    """Useful work of the decode steps that produced the tokens delivered
    in [t0, t1): the token at index j >= 1 of a request came out of a step
    that read prompt + j keys (index 0 comes out of prefill).  ``attention``
    is the paged kernel's (flops, bytes) for them."""
    kv_lens: Counter = Counter()
    for r in records:
        base = len(r.prompt)
        for j, s in enumerate(r.stamps):
            if j and t0 <= s < t1:
                kv_lens[base + j] += 1
    tokens = sum(kv_lens.values())
    attention = paged_kernel_work(cfg, kv_lens)
    return {"tokens": tokens,
            "kv_tokens": sum(n * c for n, c in kv_lens.items()),
            "attention": attention,
            "flops": references.family(cfg).matmul_flops_per_token(cfg)
            * tokens + attention[0]}
