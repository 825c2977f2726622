"""Plain reference of a decoder whose every MLP is a sparse mixture of
experts, for the tests' second family (``data/tiny_moe.json``).

Each layer: RMSNorm, the dense family's grouped-query attention
(``references/dense_decoder.py``), RMSNorm, then a router (a softmax over
``num_experts`` logits, the top ``num_experts_per_tok`` kept and, with
``norm_topk_prob``, renormalised to sum to 1) and every expert's SwiGLU
MLP, summed with the kept gates (0 for the rest).  Float32 at ``highest``
precision; ``fp8=True`` rounds every matrix-multiply operand, the
router's included, to float8 e4m3.  The configuration keys follow the
published MoE configs (``num_experts``, ``moe_intermediate_size``,
``layer_types``, ``mlp_layer_types``, ``rope_parameters`` keyed by layer
type); every layer must be ``full_attention`` with ``default`` RoPE and a
``sparse`` MLP, and anything else is refused.

The weight tree is the program's ``moe`` family's: the dense family's
attention, norms and tables, and

    layers/moe/router (L, d, E)   wi, wg (L, E, d, F)   wo (L, E, F, d)

The tests load this module as ``references.moe_decoder``: a family joins
the benchmark by a module of its own and no edit to the harness.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from references import dense_decoder as dense


def _layers(cfg: dict) -> int:
    """The layer count, once every layer is of the kind this module
    computes."""
    L = cfg["num_hidden_layers"]
    kinds = (tuple(cfg["layer_types"]), tuple(cfg["mlp_layer_types"]))
    if kinds != (("full_attention",) * L, ("sparse",) * L):
        raise ValueError(f"moe_decoder computes full attention and sparse "
                         f"MLPs in every layer, not {kinds}")
    return L


def _theta(cfg: dict) -> float:
    rope = cfg["rope_parameters"]["full_attention"]
    if rope["rope_type"] != "default":
        raise ValueError(f"moe_decoder has no {rope['rope_type']!r} RoPE")
    return float(rope["rope_theta"])


def _widths(cfg: dict):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (_layers(cfg), d, h, cfg["num_key_value_heads"],
            cfg.get("head_dim", d // h), cfg["num_experts"],
            cfg["num_experts_per_tok"], cfg["moe_intermediate_size"],
            cfg["vocab_size"])


def shapes(cfg: dict) -> dict:
    """{path: (shape, std)} of every leaf, in the program's layout."""
    L, d, _, _, _, E, _, F, _ = _widths(cfg)
    out = {p: v for p, v in dense.shapes(cfg).items()
           if not p.startswith("layers/mlp/")}
    out["layers/moe/router"] = ((L, d, E), d ** -0.5)
    out["layers/moe/wi"] = ((L, E, d, F), d ** -0.5)
    out["layers/moe/wg"] = ((L, E, d, F), d ** -0.5)
    out["layers/moe/wo"] = ((L, E, F, d), F ** -0.5)
    return out


def program_fields(cfg: dict) -> dict:
    L, d, h, hkv, dh, E, k, F, V = _widths(cfg)
    return dict(
        n_layers=L, d_model=d, n_heads=h, n_kv_heads=hkv, head_dim=dh,
        d_ff=F, vocab_size=V, rope_theta=_theta(cfg),
        rms_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        qkv_bias=bool(cfg.get("attention_bias")),
        param_dtype=cfg["torch_dtype"], compute_dtype=cfg["torch_dtype"],
        window=0, moe={"n_experts": E, "top_k": k})


def _weights(cfg: dict, experts: float) -> float:
    """Weights of the layers (attention, router and ``experts`` experts
    each) and the output head."""
    L, d, h, hkv, dh, E, _, F, V = _widths(cfg)
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + d * E \
        + experts * 3 * d * F
    return L * per_layer + V * d


def matmul_flops_per_token(cfg: dict) -> int:
    """Twice the weights a token multiplies by: its top-k experts'."""
    return 2 * _weights(cfg, cfg["num_experts_per_tok"])


def step_weight_bytes(cfg: dict, rows: float) -> float:
    """A step of ``rows`` tokens must read the experts that any of them is
    routed to: E (1 - (1 - k/E)^rows) under uniform routing."""
    E, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    touched = E * (1.0 - (1.0 - k / E) ** rows)
    return _weights(cfg, touched) * jnp.dtype(cfg["torch_dtype"]).itemsize


def attention_work(cfg: dict, kv_len: int) -> tuple[int, int]:
    """(flops, bytes) of one query token over ``kv_len`` keys, every layer
    full attention."""
    L, _, h, hkv, dh, _, _, _, _ = _widths(cfg)
    size = jnp.dtype(cfg["torch_dtype"]).itemsize
    return L * 4 * h * dh * kv_len, kv_len * L * 2 * hkv * dh * size


@functools.lru_cache(maxsize=8)
def _forward_fn(dims: tuple, fp8: bool):
    (n_heads, n_kv, eps, theta, top_k, norm_topk, tied) = dims

    def layer(x, lp):
        x = x + dense.attention(dense.rms(x, lp["ln1"], eps), lp["attn"],
                                n_heads, n_kv, theta, fp8)
        m = lp["moe"]
        h = dense.rms(x, lp["ln2"], eps)
        probs = jax.nn.softmax(dense.mm("sd,de->se", h, m["router"], fp8),
                               -1)
        top, ids = jax.lax.top_k(probs, top_k)
        if norm_topk:
            top = top / jnp.sum(top, -1, keepdims=True)
        rows = jnp.arange(h.shape[0])[:, None]
        gates = jnp.zeros_like(probs).at[rows, ids].set(top)
        up = dense.mm("sd,edf->sef", h, m["wi"], fp8)
        gate = dense.mm("sd,edf->sef", h, m["wg"], fp8)
        y = dense.mm("sef,efd->sed", jax.nn.silu(gate) * up, m["wo"], fp8)
        return x + jnp.einsum("se,sed->sd", gates, y)

    return dense.decoder(layer, eps, tied, fp8)


def forward(params, cfg: dict, tokens, *, fp8: bool = False):
    """Logits (S, V) in float32 for one sequence ``tokens`` (S,)."""
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            float(cfg["rms_norm_eps"]), _theta(cfg),
            cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"]),
            bool(cfg.get("tie_word_embeddings")))
    _layers(cfg)
    return _forward_fn(dims, fp8)(params, tokens)
