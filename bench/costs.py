"""Operations and bytes that a decode step must do, from the configuration.

Counted from the model's published shapes and the live lengths, never from
what an implementation pads to: a kernel that reads dead tiles or a step
that copies its pool does more work than this, and shows it as a lower
share of the peak.  All weights and KV are bfloat16 (2 bytes).
"""
from __future__ import annotations

import json
from pathlib import Path

BYTES = 2
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


def _dims(cfg: dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"],
            cfg.get("head_dim", d // h), cfg["intermediate_size"],
            cfg["vocab_size"])


def matmul_params(cfg: dict) -> int:
    """Weights every token multiplies by: the layers' projections and the
    output head (the embedding lookup is a gather, not a multiply)."""
    L, d, h, hkv, dh, f, v = _dims(cfg)
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    return L * per_layer + v * d


def kv_bytes_per_token(cfg: dict) -> int:
    L, _, _, hkv, dh, _, _ = _dims(cfg)
    return L * 2 * hkv * dh * BYTES


def attention_flops(cfg: dict, kv_len: int) -> int:
    """Scores and weighted sum of one query token over ``kv_len`` keys, all
    layers."""
    L, _, h, _, dh, _, _ = _dims(cfg)
    return L * 4 * h * dh * kv_len


def paged_kernel_work(cfg: dict, kv_tokens: int) -> tuple[int, int]:
    """(flops, bytes) that the paged decode-attention kernel must do, over
    all layers, for query tokens whose live key counts sum to
    ``kv_tokens``."""
    return attention_flops(cfg, kv_tokens), kv_tokens * kv_bytes_per_token(
        cfg)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def decode_work(cfg: dict, records, t0: float, t1: float) -> dict:
    """Useful work of the decode steps that produced the tokens delivered
    in [t0, t1): the token at index j >= 1 of a request came out of a step
    that read prompt + j keys per layer (index 0 comes out of prefill)."""
    tokens = kv = 0
    for r in records:
        base = len(r.prompt)
        for j, s in enumerate(r.stamps):
            if j and t0 <= s < t1:
                tokens += 1
                kv += base + j
    return {"tokens": tokens, "kv_tokens": kv,
            "flops": 2 * matmul_params(cfg) * tokens
            + attention_flops(cfg, 1) * kv}
