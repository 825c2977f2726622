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

The weight tree (``shapes``) is the layout the program's dense family
serves from:

    embed (V, d); final_norm (d,); unembed (V, d) unless tied
    layers/ln1, ln2 (L, d)            RMSNorm scale is 1 + the stored value
    layers/attn/wq (L, d, H, D)  wk, wv (L, d, Hkv, D)  wo (L, H, D, d)
    layers/attn/bq (L, H, D)  bk, bv (L, Hkv, D)        with attention_bias
    layers/mlp/wi, wg (L, d, F)  wo (L, F, d)    out = (silu(x wg) * x wi) wo

with the scales of ``bench/weights.py``.  Operations and bytes are counted
from the published shapes and live lengths, never from what an
implementation pads to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from weights import LOGIT_SPREAD, NORM_STD

F8_MAX = 448.0


def sizes(cfg: dict) -> dict:
    """The widths the layout needs, read from a configuration file."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"L": cfg["num_hidden_layers"], "d": d, "H": h,
            "Hkv": cfg["num_key_value_heads"],
            "D": cfg.get("head_dim", d // h), "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"]}


def shapes(cfg: dict) -> dict:
    """{path: (shape, std)} of every leaf, in the program's layout."""
    s = sizes(cfg)
    L, d, H, Hkv, D, F, V = (s[k] for k in ("L", "d", "H", "Hkv", "D", "F",
                                            "V"))
    out = {
        "embed": ((V, d), LOGIT_SPREAD * d ** -0.5),
        "final_norm": ((d,), NORM_STD),
        "layers/ln1": ((L, d), NORM_STD),
        "layers/ln2": ((L, d), NORM_STD),
        "layers/attn/wq": ((L, d, H, D), d ** -0.5),
        "layers/attn/wk": ((L, d, Hkv, D), d ** -0.5),
        "layers/attn/wv": ((L, d, Hkv, D), d ** -0.5),
        "layers/attn/wo": ((L, H, D, d), (H * D) ** -0.5),
        "layers/mlp/wi": ((L, d, F), d ** -0.5),
        "layers/mlp/wg": ((L, d, F), d ** -0.5),
        "layers/mlp/wo": ((L, F, d), F ** -0.5),
    }
    if cfg.get("attention_bias"):
        out["layers/attn/bq"] = ((L, H, D), NORM_STD)
        out["layers/attn/bk"] = ((L, Hkv, D), NORM_STD)
        out["layers/attn/bv"] = ((L, Hkv, D), NORM_STD)
    if not cfg.get("tie_word_embeddings"):
        out["unembed"] = ((V, d), LOGIT_SPREAD * d ** -0.5)
    return out


def program_fields(cfg: dict) -> dict:
    """Every size and constant the file states, as the program's
    ``ModelConfig`` fields."""
    return dict(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=sizes(cfg)["D"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg.get("attention_bias")),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"],
        window=0)


def matmul_flops_per_token(cfg: dict) -> int:
    """Twice the weights every token multiplies by: the layers' projections
    and the output head (the embedding lookup is a gather, not a
    multiply)."""
    s = sizes(cfg)
    d, h, hkv, dh, f = s["d"], s["H"], s["Hkv"], s["D"], s["F"]
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    return 2 * (s["L"] * per_layer + s["V"] * d)


def step_weight_bytes(cfg: dict, rows) -> int:
    """Every weight a token multiplies by, read once a step whatever the
    rows."""
    return matmul_flops_per_token(cfg) // 2 * _itemsize(cfg)


def attention_work(cfg: dict, kv_len: int) -> tuple[int, int]:
    """(flops, bytes) of one query token over ``kv_len`` keys, all layers:
    scores and weighted sum, and every key and value read once."""
    s = sizes(cfg)
    flops = s["L"] * 4 * s["H"] * s["D"] * kv_len
    return flops, kv_len * s["L"] * 2 * s["Hkv"] * s["D"] * _itemsize(cfg)


def _itemsize(cfg: dict) -> int:
    """Bytes of one weight or cached K/V element: the program serves both in
    ``torch_dtype``."""
    return jnp.dtype(cfg["torch_dtype"]).itemsize


def _q8(x):
    """Round to float8 e4m3 with one per-tensor scale, back to float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(eq, a, b, fp8):
    """einsum in float32; with ``fp8`` both operands rounded to float8."""
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, theta):
    """x (S, heads, D): rotate-half rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, a, n_heads, n_kv, theta, fp8):
    """Causal grouped-query self-attention of the normed input h (S, d)
    with one layer's weights ``a``; the output projection's result (S, d)."""
    s = h.shape[0]
    q = mm("sd,dhk->shk", h, a["wq"], fp8)
    k = mm("sd,dhk->shk", h, a["wk"], fp8)
    v = mm("sd,dhk->shk", h, a["wv"], fp8)
    if "bq" in a:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = rope(q, theta), rope(k, theta)
    g = n_heads // n_kv
    d = q.shape[-1]
    qg = q.reshape(s, n_kv, g, d)
    scores = mm("sngd,tnd->ngst", qg, k, fp8) * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    o = mm("ngst,tnd->sngd", p, v, fp8).reshape(s, n_heads, d)
    return mm("shk,hkd->sd", o, a["wo"], fp8)


def decoder(layer, eps, tied, fp8):
    """The jitted forward(params, tokens) of a decoder: the embedding,
    ``layer(x, lp)`` scanned over the stacked layers with each layer's
    weights in float32, the final RMSNorm and the logits."""
    def body(x, lp):
        lp = jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), lp)
        return layer(x, lp), None

    def forward(params, tokens):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            x, _ = jax.lax.scan(body, x, params["layers"])
            x = rms(x, params["final_norm"].astype(jnp.float32), eps)
            table = params["embed"] if tied else params["unembed"]
            return mm("sd,vd->sv", x, table.astype(jnp.float32), fp8)

    return jax.jit(forward)


@functools.lru_cache(maxsize=8)
def _forward_fn(dims: tuple, fp8: bool):
    (n_heads, n_kv, eps, theta, tied) = dims

    def layer(x, lp):
        x = x + attention(rms(x, lp["ln1"], eps), lp["attn"], n_heads,
                          n_kv, theta, fp8)
        m = lp["mlp"]
        h = rms(x, lp["ln2"], eps)
        up = mm("sd,df->sf", h, m["wi"], fp8)
        gate = mm("sd,df->sf", h, m["wg"], fp8)
        return x + mm("sf,fd->sd", jax.nn.silu(gate) * up, m["wo"], fp8)

    return decoder(layer, eps, tied, fp8)


def forward(params, cfg: dict, tokens, *, fp8: bool = False):
    """Logits (S, V) in float32 for one sequence ``tokens`` (S,)."""
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
            bool(cfg.get("tie_word_embeddings")))
    return _forward_fn(dims, fp8)(params, tokens)
