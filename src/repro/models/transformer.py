"""Decoder-only LM stack (dense / MoE / VLM) with scan-over-layers.

Three lowered entry points from one parameter tree — the LM-side analogue of
Cppless's alternative entry points (one source, several compiled programs):

  forward  (train)    tokens/embeds -> logits (B, S, V)
  prefill             tokens/embeds -> last-token logits (B, V) + KV cache
  decode              one token + cache -> logits (B, V) + updated cache
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..sharding import shard
from .attention import (attn_decode, attn_decode_paged, attn_full, attn_init,
                        attn_prefill_paged)
from .layers import (embed_apply, embed_init, mlp_apply, mlp_init,
                     ragged_positions, rms_norm)
from .moe import moe_apply, moe_init
from .stacking import scan_layers


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def lm_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 8)
    dt = _dtype(cfg)
    L = cfg.n_layers
    p, s = {}, {}
    p["embed"], s["embed"] = embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt)

    lp, ls = {}, {}
    lp["ln1"] = jnp.zeros((L, cfg.d_model), dt)
    ls["ln1"] = ("layers", "embed")
    lp["ln2"] = jnp.zeros((L, cfg.d_model), dt)
    ls["ln2"] = ("layers", "embed")
    lp["attn"], ls["attn"] = attn_init(
        ks[1], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dt,
        bias=cfg.qkv_bias, stack=(L,))
    if cfg.moe.n_experts:
        lp["moe"], ls["moe"] = moe_init(
            ks[2], cfg.d_model, cfg.d_ff, cfg.moe.n_experts, cfg.act, dt,
            stack=(L,))
    else:
        lp["mlp"], ls["mlp"] = mlp_init(
            ks[2], cfg.d_model, cfg.d_ff, cfg.act, dt, stack=(L,))
    p["layers"], s["layers"] = lp, ls

    p["final_norm"] = jnp.zeros((cfg.d_model,), dt)
    s["final_norm"] = ("embed",)
    if not cfg.tie_embeddings:
        p["unembed"], s["unembed"] = embed_init(
            ks[3], cfg.vocab_size, cfg.d_model, dt)
    return p, s


def _embed_in(p, cfg, tokens, embeds):
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = embed_apply(p["embed"], tokens).astype(
            jnp.dtype(cfg.compute_dtype))
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return shard(x, "act_batch", "act_seq", "act_embed")


def _logits(p, cfg, x):
    x = rms_norm(x, p["final_norm"], cfg.rms_eps)
    table = p["embed"] if cfg.tie_embeddings else p["unembed"]
    # fp32 logits come straight out of the f32 accumulator: rounding them to
    # the param dtype first ties near-equal greedy candidates
    out = jnp.einsum("...d,vd->...v", x, table,
                     preferred_element_type=jnp.float32 if cfg.logits_fp32
                     else None)
    out = shard(out, "act_batch", "act_seq", "act_vocab") if out.ndim == 3 \
        else shard(out, "act_batch", "act_vocab")
    return out


def _ffn(lp, cfg: ModelConfig, h, dropless: bool = False):
    """Dense MLP or MoE; returns (y, (aux, zloss, drop)).

    ``dropless`` (the prefill/decode entry points): expert capacity covers
    the worst case, so a token's routing never depends on what else is in
    the batch — capacity dropping is a training-throughput trade, and it
    would make serving batch-composition-DEPENDENT.
    """
    if cfg.moe.n_experts:
        y, m = moe_apply(
            lp["moe"], h, n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
            capacity_factor=(0.0 if dropless else cfg.moe.capacity_factor),
            act=cfg.act,
            impl=("ep_a2a" if cfg.moe.impl == "ep" else "replicated"))
        return y, (m["moe_aux"], m["moe_zloss"], m["moe_drop"])
    y = mlp_apply(lp["mlp"], h, cfg.act)
    y = shard(y, "act_batch", "act_seq", "act_embed")
    return y, (jnp.float32(0), jnp.float32(0), jnp.float32(0))


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat == "dots_saveable" else None)
    return jax.checkpoint(fn, policy=policy)


def lm_forward(p, cfg: ModelConfig, tokens=None, embeds=None, pos3d=None,
               attn_impl: str = "ref", lengths=None):
    """Training forward: full logits (B, S, V) + moe metrics."""
    x = _embed_in(p, cfg, tokens, embeds)
    b, s_len = x.shape[:2]
    positions, kv_start = ragged_positions(lengths, b, s_len)

    def body(carry, lp):
        x, aux = carry
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        h = attn_full(lp["attn"], h, positions, causal=True,
                      window=cfg.window, rope_theta=cfg.rope_theta,
                      mrope_sections=cfg.mrope_sections, pos3d=pos3d,
                      impl=attn_impl, kv_start=kv_start)
        x = x + h
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        h, m = _ffn(lp, cfg, h)
        x = x + h
        return (x, tuple(a + mm for a, mm in zip(aux, m))), None

    zero = (jnp.float32(0),) * 3
    (x, aux), _ = scan_layers(_remat(cfg, body), (x, zero), p["layers"],
                              use_scan=cfg.scan_layers)
    metrics = {"moe_aux": aux[0] / cfg.n_layers,
               "moe_zloss": aux[1] / cfg.n_layers,
               "moe_drop": aux[2] / cfg.n_layers}
    return _logits(p, cfg, x), metrics


def lm_prefill(p, cfg: ModelConfig, tokens=None, embeds=None, pos3d=None,
               attn_impl: str = "ref", lengths=None):
    """Prefill: last-token logits + populated KV cache.

    ``lengths`` (B,) int32: real-token count per left-padded row.  Pad keys
    are masked out of every attention layer and RoPE positions count real
    tokens, so a prompt's logits (and its cache suffix) are identical
    whatever ragged company it was packed with.  The cache records each
    row's first valid slot under ``"start"`` for the decode path.
    """
    x = _embed_in(p, cfg, tokens, embeds)
    b, s_len = x.shape[:2]
    positions, kv_start = ragged_positions(lengths, b, s_len)
    cdt = jnp.dtype(cfg.param_dtype)

    def body(x, lp):
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        h, (k, v) = attn_full(lp["attn"], h, positions, causal=True,
                              window=cfg.window, rope_theta=cfg.rope_theta,
                              mrope_sections=cfg.mrope_sections, pos3d=pos3d,
                              impl=attn_impl, kv_start=kv_start,
                              return_kv=True)
        x = x + h
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        h, _ = _ffn(lp, cfg, h, dropless=True)
        if cfg.kv_quant == "int8":
            from .attention import quantize_kv
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            return x + h, (kq, vq, ks, vs)
        return x + h, (k.astype(cdt), v.astype(cdt))

    x, caches = scan_layers(body, x, p["layers"], use_scan=cfg.scan_layers)
    logits = _logits(p, cfg, x[:, -1])
    start = (jnp.zeros((b,), jnp.int32) if kv_start is None else kv_start)
    if cfg.kv_quant == "int8":
        ck, cv, cks, cvs = caches
        cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                 "idx": jnp.int32(s_len), "start": start}
    else:
        ck, cv = caches
        cache = {"k": ck, "v": cv, "idx": jnp.int32(s_len), "start": start}
    return logits, cache


def lm_init_cache(cfg: ModelConfig, batch: int, cap: int,
                  filled: int | None = None, start=None):
    """Abstract/zero cache of capacity ``cap``; idx = filled (default cap-1,
    i.e. the decode_32k cell: a full cache, new token in the last slot).
    ``start`` (B,) int32: per-row first valid slot (left-pad count from a
    ragged prefill); default 0 = fully dense rows."""
    cdt = jnp.dtype(cfg.param_dtype)
    shp = (cfg.n_layers, batch, cap, cfg.n_kv_heads, cfg.head_dim)
    idx = cap - 1 if filled is None else filled
    if start is None:
        start = jnp.zeros((batch,), jnp.int32)
    if cfg.kv_quant == "int8":
        return {"k": jnp.zeros(shp, jnp.int8), "v": jnp.zeros(shp, jnp.int8),
                "k_scale": jnp.zeros(shp[:-1], jnp.float32),
                "v_scale": jnp.zeros(shp[:-1], jnp.float32),
                "idx": jnp.int32(idx), "start": start}
    return {"k": jnp.zeros(shp, cdt), "v": jnp.zeros(shp, cdt),
            "idx": jnp.int32(idx), "start": start}


def lm_decode(p, cfg: ModelConfig, cache, tokens, pos3d=None,
              attn_impl: str = "ref"):
    """One decode step.  tokens (B, 1) -> logits (B, V), updated cache."""
    x = _embed_in(p, cfg, tokens, None)
    idx = cache["idx"]
    start = cache.get("start")               # (B,) left-pad counts, or None
    if cfg.mrope_sections and pos3d is None:
        b = tokens.shape[0]
        rel = (jnp.full((b,), idx, jnp.int32) if start is None
               else idx - start.astype(jnp.int32))
        pos3d = jnp.broadcast_to(rel[None, :, None], (3, b, 1))

    quant = cfg.kv_quant == "int8"

    def body(x, xs):
        if quant:
            lp, ck, cv, cks, cvs = xs
        else:
            lp, ck, cv = xs
            cks = cvs = None
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        out = attn_decode(lp["attn"], h, ck, cv, idx,
                          window=cfg.window, rope_theta=cfg.rope_theta,
                          mrope_sections=cfg.mrope_sections,
                          pos3d=pos3d, impl=attn_impl,
                          cache_ks=cks, cache_vs=cvs, kv_start=start)
        h, ck, cv = out[:3]
        x = x + h
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        h, _ = _ffn(lp, cfg, h, dropless=True)
        if quant:
            return x + h, (ck, cv, out[3], out[4])
        return x + h, (ck, cv)

    carry = {} if start is None else {"start": start}
    if quant:
        xs = (p["layers"], cache["k"], cache["v"], cache["k_scale"],
              cache["v_scale"])
        x, (ck, cv, cks, cvs) = scan_layers(body, x, xs,
                                            use_scan=cfg.scan_layers)
        logits = _logits(p, cfg, x[:, -1])
        return logits, {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs,
                        "idx": idx + 1, **carry}
    x, (ck, cv) = scan_layers(body, x,
                              (p["layers"], cache["k"], cache["v"]),
                              use_scan=cfg.scan_layers)
    logits = _logits(p, cfg, x[:, -1])
    return logits, {"k": ck, "v": cv, "idx": idx + 1, **carry}


# -------------------------------------------------- paged (block-table) ----

def lm_decode_paged(p, cfg: ModelConfig, pool_k, pool_v, table, lens, live,
                    tokens, attn_impl: str = "ref"):
    """One decode step against a shared block pool.

    tokens (B,1); pool_k/pool_v (L,NB,Hkv,BS,D); table (B,T) int32;
    lens (B,) resident tokens per row; live (B,) bool.  Each live row's
    new K/V lands at logical column ``lens[b]`` through its table.  The
    stacked pools are the layer loop's carry — each
    layer writes its token into them and attends over them in place, so
    no layer's pool is sliced out or stacked back.  Returns (logits (B,V),
    pool_k, pool_v) — per-row lens/table bookkeeping is the host's job
    (block refcounts live there).
    """
    x = _embed_in(p, cfg, tokens, None)

    def body(carry, xs):
        x, pk, pv = carry
        lp, layer = xs
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        h, pk, pv = attn_decode_paged(lp["attn"], h, pk, pv, layer, table,
                                      lens, live, window=cfg.window,
                                      rope_theta=cfg.rope_theta,
                                      impl=attn_impl)
        x = x + h
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        h, _ = _ffn(lp, cfg, h, dropless=True)
        return (x + h, pk, pv), None

    # the layer index rides beside each layer's params: a traced int32
    # under lax.scan, a plain integer when the loop is unrolled
    xs = (p["layers"], np.arange(cfg.n_layers, dtype=np.int32))
    (x, pk, pv), _ = scan_layers(body, (x, pool_k, pool_v), xs,
                                 use_scan=cfg.scan_layers)
    logits = _logits(p, cfg, x[:, -1])
    return logits, pk, pv


def lm_prefill_paged_chunk(p, cfg: ModelConfig, tokens, pool_k, pool_v,
                           table, m, n_real, attn_impl: str = "ref"):
    """One chunk of continued prefill for a single row (B == 1).

    tokens (1,C) right-padded, n_real real; ``m`` tokens of the row are
    already resident in the pool, so this chunk covers logical columns
    [m, m + n_real).  The stacked pools are the layer loop's carry, as in
    :func:`lm_decode_paged`.  Returns (last-real-token logits (1,V),
    pools).  Chaining chunks with growing m reproduces a monolithic
    prefill's logits and cache bit-for-bit — that is the chunked-prefill
    contract the invariance matrix pins.
    """
    x = _embed_in(p, cfg, tokens, None)

    def body(carry, xs):
        x, pk, pv = carry
        lp, layer = xs
        h = rms_norm(x, lp["ln1"], cfg.rms_eps)
        h, pk, pv = attn_prefill_paged(lp["attn"], h, pk, pv, layer, table,
                                       m, n_real, window=cfg.window,
                                       rope_theta=cfg.rope_theta,
                                       impl=attn_impl)
        x = x + h
        h = rms_norm(x, lp["ln2"], cfg.rms_eps)
        h, _ = _ffn(lp, cfg, h, dropless=True)
        return (x + h, pk, pv), None

    xs = (p["layers"], np.arange(cfg.n_layers, dtype=np.int32))
    (x, pk, pv), _ = scan_layers(body, (x, pool_k, pool_v), xs,
                                 use_scan=cfg.scan_layers)
    last = jax.lax.dynamic_slice(x, (0, n_real - 1, 0), (1, 1, x.shape[-1]))
    logits = _logits(p, cfg, last[:, 0])
    return logits, pk, pv
