"""Random weights for a configuration, made from the seed.

The benchmark makes the weights itself, so that the plain reference and the
program read the same numbers and the reference takes nothing the program
made.  The tree and each leaf's scale come from the configuration's family
(``references.family(cfg).shapes``), in the layout the program serves from.

Scales: every projection is N(0, 1/fan_in) over its whole contracted width,
so attention stays soft; norms and biases N(0, NORM_STD**2); the embedding
and output tables N(0, (LOGIT_SPREAD / sqrt(d))**2), so the logits spread by
about 3, as a trained model's do.  A table of N(0, 1) would put the input
token's own row far above every other logit of a tied model, and greedy
decoding would copy its input whatever attention computed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import references

NORM_STD = 0.1
LOGIT_SPREAD = 3.0


def shapes(cfg: dict) -> dict:
    """{path: (shape, std)} of every leaf, in the program's layout."""
    return references.family(cfg).shapes(cfg)


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
