"""Random weights for a dense decoder configuration, made from the seed.

The benchmark makes the weights itself, so that the plain reference and the
program read the same numbers and the reference takes nothing the program
made.  The tree has the layout the program's dense family serves from:

    embed (V, d); final_norm (d,); unembed (V, d) unless tied
    layers/ln1, ln2 (L, d)            RMSNorm scale is 1 + the stored value
    layers/attn/wq (L, d, H, D)  wk, wv (L, d, Hkv, D)  wo (L, H, D, d)
    layers/attn/bq (L, H, D)  bk, bv (L, Hkv, D)        with attention_bias
    layers/mlp/wi, wg (L, d, F)  wo (L, F, d)    out = (silu(x wg) * x wi) wo

Scales: every projection is N(0, 1/fan_in) over its whole contracted width,
so attention stays soft; norms and biases N(0, 0.1**2); the embedding and
output tables N(0, (3 / sqrt(d))**2), so the logits spread by about 3, as a
trained model's do.  A table of N(0, 1) would put the input token's own
row far above every other logit of a tied model, and greedy decoding would
copy its input whatever attention computed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NORM_STD = 0.1
LOGIT_SPREAD = 3.0


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


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits (the upper half folded in)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for part in head:
            node = node.setdefault(part, {})
        node[last] = leaf
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


@functools.lru_cache(maxsize=8)
def _maker(spec: tuple, dtype: str):
    def make(key):
        flat = {}
        for i, (path, shape, std) in enumerate(spec):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            flat[path] = (x * std).astype(dtype)
        return nest(flat)
    return jax.jit(make)


def make(cfg: dict, seed: int):
    """All weights, on the default device, in ``torch_dtype``, in one jitted
    call."""
    spec = tuple((p, shape, std) for p, (shape, std)
                 in sorted(shapes(cfg).items()))
    return _maker(spec, cfg["torch_dtype"])(seed_key(seed))


def check_layout(params, expected) -> None:
    """Refuse to serve when the program's parameter tree (``expected``,
    abstract shapes) differs from the one made here."""
    got = {p: (tuple(x.shape), str(x.dtype))
           for p, x in flatten(params).items()}
    want = {p: (tuple(x.shape), str(x.dtype))
            for p, x in flatten(expected).items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"the program's parameter layout differs from the "
                         f"benchmark's: {diff[:6]}")
