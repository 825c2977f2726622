"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests the run finished, the longest and seven drawn from the seed, is
run through the plain reference (``bench/references/<reference>.py``) over
each prompt followed by its served tokens.  For each served token the gap
is how far its reference logit lies below the reference's best logit at
that position; greedy decoding served the best token, so a sound program
reads only rounding there.  The widest gap over the sample is held to the
cell's limit (``bench/limits/<cell>.json``), or, where the limit names a
``quantile``, that quantile of the sample's per-token gaps: a family whose
router picks its top-k experts from near-equal scores serves a few tokens
after a choice that rounding flipped, and those read wide gaps in a sound
run (PERF.md, section 6, PR 16).

The control (``control=True``, never in a benchmark run) puts the plain
reference computed in float8 in the program's place: at each position of
the same sequences its first choice is scored as the served token, through
the same row and limit, and the run must come out not correct.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

import references

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
def _gap_fns(config_key: str):
    config = json.loads(config_key)
    ref = references.family(config)

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


def gaps(params, config: dict, capacity: int, recs, control: bool,
         quantile: float = 1.0) -> dict:
    """The ``quantile`` (1: the widest) of the reference-logit gaps of the
    served tokens over ``recs`` and, with ``control``, of the float8
    reference's first choices at the same positions."""
    served_fn, control_fn = _gap_fns(json.dumps(config, sort_keys=True))
    served, ctl, n = [], [], 0
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
            ctl.append(c)
        else:
            g = np.asarray(served_fn(params, tokens, targets))[lo:hi]
        served.append(g)
        n += hi - lo

    def q(parts):
        return float(np.quantile(np.concatenate(parts), quantile)) \
            if parts else 0.0

    return {"served": q(served), "control": q(ctl) if control else None,
            "tokens": n, "requests": len(recs)}


def gap_row(limits: dict) -> tuple[str, float]:
    """The name of the gap's row and the quantile it reads: the widest gap
    unless the cell's limit names a quantile."""
    q = float(limits["greedy_gap"].get("quantile", 1.0))
    return ("greedy_gap_max" if q == 1 else f"greedy_gap_p{100 * q:g}"), q


def _row(value, limit, ok) -> dict:
    return {"value": value, "limit": limit, "ok": bool(ok)}


def judge(params, cell, recs, run: dict, seed: int,
          control: bool = False) -> tuple[dict, float]:
    """Every number compared, beside its limit; and the program's own gap,
    which with ``control`` is not the one compared."""
    picked = sample(recs, seed)
    row, quantile = gap_row(cell.limits)
    g = gaps(params, cell.config, cell.capacity, picked, control, quantile)
    gap = g["control"] if control else g["served"]
    limit = cell.limits["greedy_gap"]["limit"]
    return {
        row: _row(gap, limit, gap <= limit),
        "checked_tokens_min": _row(g["tokens"], MIN_TOKENS,
                                   g["tokens"] >= MIN_TOKENS),
        "failed_requests_max": _row(len(run["errors"]), 0,
                                    not run["errors"]),
        "wave_fallbacks_max": _row(run["final"]["wave_fallbacks"], 0,
                                   run["final"]["wave_fallbacks"] == 0),
        "iteration_level": _row(int(run["mode"] == "iteration"), 1,
                                run["mode"] == "iteration"),
    }, g["served"]
