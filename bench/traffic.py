"""Closed-loop traffic from a mix file (``bench/traffic/<mix>.json``).

A mix file gives distributions, never requests:

    {"ramp_s": 30, "stagger_s": 10, "min_levels": 16, "reserve": 64,
     "prompt": {"dist": "lognormal", "mean": 161, "sigma": 1.0, "min": 4},
     "output": {"dist": "loguniform", "min": 256, "max": "capacity"},
     "prefix": {"count": 4, "tokens": 512}}        # optional shared prefixes

The loop is closed: one client per arena row sends its next request the
moment the previous one completes.  The lengths are a fixed multiset,
the same for every seed: N = clients x ceil(min_levels / clients) levels,
level i at the quantile (i + 0.5) / N of each distribution; prompt level i
is paired with output level (i * step) mod N for a fixed step coprime to N,
so long prompts do not all meet long outputs.  The levels are dealt in N /
clients rounds, round r holding every (N / clients)-th level from r, so each
round is a stratified sample of the whole mix.  At their j-th request all
clients are in the same round.  Which client takes which pair of a round is
drawn once, the same for every seed: the tails depend on which requests
meet in the queues, so an order that changed with the seed would change
the work.  So does the round the clients start from.  The seed draws the
token ids.

``"capacity"`` stands for the per-row capacity of the configuration; every
pair is clipped so that prompt + output + ``reserve`` fits it, output first.
With ``prefix``, each prompt is one of ``count`` shared prefixes of
``tokens`` tokens followed by the drawn prompt
as its suffix; within each round the prefixes take turns.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Pair:
    prompt: int          # prompt tokens, shared prefix included
    output: int          # tokens to generate
    prefix: int = -1     # index of the shared prefix, -1 for none


def _bound(v, capacity: int) -> float:
    return float(capacity if v == "capacity" else v)


def quantile(spec: dict, u: float, capacity: int) -> int:
    """The ``u``-quantile of a length distribution, as a whole number."""
    lo = _bound(spec.get("min", 1), capacity)
    kind = spec["dist"]
    if kind == "lognormal":
        # parameterised by its mean, as the sources report it
        sigma = float(spec["sigma"])
        mu = math.log(float(spec["mean"])) - sigma * sigma / 2
        x = math.exp(mu + sigma * NormalDist().inv_cdf(u))
    elif kind == "loguniform":
        hi = _bound(spec["max"], capacity)
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return max(int(lo), int(round(x)))


def _step(n: int) -> int:
    """A fixed multiplier coprime to n near n / golden ratio."""
    s = max(1, int(round(n * 0.618)))
    while math.gcd(s, n) != 1:
        s += 1
    return s % n or 1


def levels(mix: dict, clients: int, capacity: int) -> list[Pair]:
    """The fixed multiset of (prompt, output) pairs, in level order."""
    m = -(-int(mix.get("min_levels", 1)) // clients)
    n = clients * m
    step = _step(n)
    room = capacity - int(mix.get("reserve", 0))
    pre = mix.get("prefix")
    pairs = []
    for i in range(n):
        p = quantile(mix["prompt"], (i + 0.5) / n, capacity)
        o = quantile(mix["output"], ((i * step) % n + 0.5) / n, capacity)
        k = -1
        if pre:
            k = (i // m) % int(pre["count"])
            p += int(pre["tokens"])
        p = min(p, room - 1)
        o = max(1, min(o, room - p))
        pairs.append(Pair(p, o, k))
    return pairs


class Plan:
    """Per-client request sequences for one run, from the seed."""

    def __init__(self, mix: dict, rows: int, capacity: int, vocab: int,
                 seed: int):
        self.mix = mix
        self.clients = rows
        self.pairs = levels(mix, self.clients, capacity)
        self.rounds = len(self.pairs) // self.clients
        self.vocab = vocab
        self.seed = seed
        rng = np.random.default_rng([0, 0])
        self.start_round = int(rng.integers(self.rounds))
        self.deal = [rng.permutation(self.clients) for _ in range(self.rounds)]
        pre = mix.get("prefix")
        self.prefixes = ([self._tokens([seed, 1, k], int(pre["tokens"]))
                          for k in range(int(pre["count"]))] if pre else [])

    def _tokens(self, key, n: int) -> list[int]:
        return [int(t) for t in
                np.random.default_rng(key).integers(1, self.vocab, n)]

    def pair(self, client: int, j: int) -> Pair:
        r = (self.start_round + j) % self.rounds
        return self.pairs[r + self.rounds * int(self.deal[r][client])]

    def request(self, client: int, j: int) -> tuple[list[int], int]:
        """(prompt token ids, tokens to generate) of a client's j-th
        request."""
        pr = self.pair(client, j)
        if pr.prefix < 0:
            return self._tokens([self.seed, 2, client, j], pr.prompt), \
                pr.output
        head = self.prefixes[pr.prefix]
        tail = self._tokens([self.seed, 2, client, j], pr.prompt - len(head))
        return head + tail, pr.output

    def start_s(self, client: int) -> float:
        """When a client sends its first request, seconds after the start:
        spread evenly over ``stagger_s``."""
        return float(self.mix.get("stagger_s", 0.0)) * client / self.clients

    def max_prompt(self) -> int:
        return max(p.prompt for p in self.pairs)
