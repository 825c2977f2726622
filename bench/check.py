"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished, the longest and seven drawn from the seed, is
run through the plain reference (``bench/references/<reference>.py``) over
each prompt followed by its served tokens.  For each served token the gap
is how far its reference logit lies below the reference's best logit at
that position; greedy decoding served the best token, so a sound program
reads only rounding there.  The widest gap over the sample is held to the
cell's limit (``bench/limits/<cell>.json``).

The control (``control=True``, never in a benchmark run) puts the plain
reference computed in float8 in the program's place: at each position of
the same sequences its first choice is scored as the served token, through
the same row and limit, and the run must come out not correct.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE_REQUESTS = 8      # the longest finished request and 7 drawn
MIN_TOKENS = 256         # served tokens a sample must hold


def sample(recs, seed: int) -> list:
    """The longest finished request and SAMPLE_REQUESTS - 1 others drawn
    from the seed."""
    done = sorted((r for r in recs if r.tokens),
                  key=lambda r: (r.client, r.index))
    if not done:
        return []
    first = max(range(len(done)), key=lambda i: len(done[i].tokens))
    order = [first] + [int(i) for i in np.random.default_rng(
        [seed, 3]).permutation(len(done)) if i != first]
    return [done[i] for i in order[:SAMPLE_REQUESTS]]


@functools.lru_cache(maxsize=8)
def _gap_fns(reference: str, config_key: tuple):
    ref = importlib.import_module(f"references.{reference}")
    config = dict(config_key)

    def gap(logits, targets):
        got = jnp.take_along_axis(logits, jnp.maximum(targets, 0)[:, None],
                                  -1)[:, 0]
        return jnp.max(logits, -1) - got

    @jax.jit
    def served(params, tokens, targets):
        return gap(ref.forward(params, config, tokens), targets)

    @jax.jit
    def with_control(params, tokens, targets):
        logits = ref.forward(params, config, tokens)
        low = ref.forward(params, config, tokens, fp8=True)
        return gap(logits, targets), gap(logits, jnp.argmax(low, -1))

    return served, with_control


def gaps(params, config: dict, capacity: int, recs, control: bool) -> dict:
    """Widest reference-logit gap of the served tokens over ``recs`` and,
    with ``control``, of the float8 reference's first choices at the same
    positions."""
    key = tuple(sorted((k, v) for k, v in config.items()
                       if not isinstance(v, (dict, list))))
    served_fn, control_fn = _gap_fns(config["reference"], key)
    worst, worst_ctl, n = 0.0, 0.0, 0
    for r in recs:
        seq = list(r.prompt) + list(r.tokens[:-1])
        lo, hi = len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens)
        tokens = np.zeros(capacity, np.int32)
        tokens[:len(seq)] = seq
        targets = np.full(capacity, -1, np.int32)
        targets[lo:hi] = r.tokens
        if control:
            g, c = (np.asarray(x)[lo:hi]
                    for x in control_fn(params, tokens, targets))
            worst_ctl = max(worst_ctl, float(c.max()))
        else:
            g = np.asarray(served_fn(params, tokens, targets))[lo:hi]
        worst = max(worst, float(g.max()))
        n += hi - lo
    return {"served": worst, "control": worst_ctl if control else None,
            "tokens": n, "requests": len(recs)}


def _row(value, limit, ok) -> dict:
    return {"value": value, "limit": limit, "ok": bool(ok)}


def judge(params, cell, recs, run: dict, seed: int,
          control: bool = False) -> tuple[dict, float]:
    """Every number compared, beside its limit; and the program's own widest
    gap, which with ``control`` is not the one compared."""
    picked = sample(recs, seed)
    g = gaps(params, cell.config, cell.capacity, picked, control)
    gap = g["control"] if control else g["served"]
    limit = cell.limits["greedy_gap"]["limit"]
    return {
        "greedy_gap_max": _row(gap, limit, gap <= limit),
        "checked_tokens_min": _row(g["tokens"], MIN_TOKENS,
                                   g["tokens"] >= MIN_TOKENS),
        "failed_requests_max": _row(len(run["errors"]), 0,
                                    not run["errors"]),
        "wave_fallbacks_max": _row(run["final"]["wave_fallbacks"], 0,
                                   run["final"]["wave_fallbacks"] == 0),
        "iteration_level": _row(int(run["mode"] == "iteration"), 1,
                                run["mode"] == "iteration"),
    }, g["served"]
