"""Plain reference of a dense decoder-only transformer (Llama / Qwen2 family).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no cache,
no batching, no kernels, one sequence at a time, layer by layer under a scan
so that only one layer is held in float32.  It follows the published
architecture: RMSNorm before attention and MLP, rotary position embedding
(rotate-half, base ``rope_theta``), grouped-query attention (query head h
reads key/value head h // (H / Hkv)), optional q/k/v bias, SwiGLU MLP,
final RMSNorm, logits against the (tied or separate) embedding table.  The
weights are the benchmark's own (``bench/weights.py``); RMSNorm scales are
stored as offsets from 1.

``forward(..., fp8=True)`` is the correctness control: the same mathematics
with every matrix-multiply operand rounded to float8 e4m3 (per-tensor scale,
amax / 448), the precision a faster path would be tempted to drop to from
the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F8_MAX = 448.0


def _q8(x):
    """Round to float8 e4m3 with one per-tensor scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, theta):
    """x (S, heads, D): rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=8)
def _forward_fn(dims: tuple, fp8: bool):
    (n_heads, n_kv, eps, theta, tied) = dims

    def layer(x, lp):
        lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
        a, m = lp["attn"], lp["mlp"]
        s = x.shape[0]
        h = _rms(x, lp["ln1"], eps)
        q = _mm("sd,dhk->shk", h, a["wq"], fp8)
        k = _mm("sd,dhk->shk", h, a["wk"], fp8)
        v = _mm("sd,dhk->shk", h, a["wv"], fp8)
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        g = n_heads // n_kv
        d = q.shape[-1]
        qg = q.reshape(s, n_kv, g, d)
        scores = _mm("sngd,tnd->ngst", qg, k, fp8) * d ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        o = _mm("ngst,tnd->sngd", p, v, fp8).reshape(s, n_heads, d)
        x = x + _mm("shk,hkd->sd", o, a["wo"], fp8)
        h = _rms(x, lp["ln2"], eps)
        up = _mm("sd,df->sf", h, m["wi"], fp8)
        gate = _mm("sd,df->sf", h, m["wg"], fp8)
        x = x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, m["wo"], fp8)
        return x, None

    def forward(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            x, _ = jax.lax.scan(layer, x, params["layers"])
            x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
            table = params["embed"] if tied else params["unembed"]
            return _mm("sd,vd->sv", x, table.astype(jnp.float32), fp8)

    return jax.jit(forward)


def forward(params, cfg: dict, tokens, *, fp8: bool = False):
    """Logits (S, V) in float32 for one sequence ``tokens`` (S,)."""
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            bool(cfg.get("tie_word_embeddings")))
    return _forward_fn(dims, fp8)(params, tokens)
