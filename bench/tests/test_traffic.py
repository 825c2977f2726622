import json
from collections import Counter
from pathlib import Path

import pytest

import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def _multiset(plan, rounds):
    return Counter(plan.pair(c, j) for c in range(plan.clients)
                   for j in range(rounds))


@pytest.mark.parametrize("mix,rows,capacity", [("chat", 14, 2048),
                                               ("decode", 3, 1024)])
def test_same_lengths_in_the_same_order_for_every_seed(mix, rows, capacity):
    spec = json.loads((MIXES / f"{mix}.json").read_text())
    plans = [traffic.Plan(spec, rows, capacity, 1000, seed)
             for seed in (0, 1, 2**31 + 5, 2**33 + 7)]
    sets = [_multiset(p, p.rounds) for p in plans]
    assert all(s == sets[0] for s in sets[1:])
    assert sum(sets[0].values()) == len(plans[0].pairs)
    # nor the order: which requests meet is part of the work
    orders = {tuple(p.pair(c, j) for c in range(p.clients)
                    for j in range(p.rounds)) for p in plans}
    assert len(orders) == 1


@pytest.mark.parametrize("mix,rows,capacity", [("chat", 14, 2048),
                                               ("decode", 3, 1024)])
def test_every_request_fits_the_row(mix, rows, capacity):
    spec = json.loads((MIXES / f"{mix}.json").read_text())
    for pair in traffic.levels(spec, rows, capacity):
        assert pair.prompt >= 1 and pair.output >= 1
        assert pair.prompt + pair.output + spec["reserve"] <= capacity


def test_each_round_is_a_stratified_sample():
    dist = {"dist": "loguniform", "min": 1, "max": 10000}
    spec = {"min_levels": 16, "reserve": 0,
            "prompt": dist, "output": dist}
    plan = traffic.Plan(spec, 4, 1 << 20, 100, 3)
    assert plan.rounds == 4
    quarter = [traffic.quantile(dist, q, 0) for q in (0.25, 0.5, 0.75)]
    for j in range(plan.rounds):
        for side in ("prompt", "output"):
            got = sorted(getattr(plan.pair(c, j), side) for c in range(4))
            # one level from each quarter of the distribution
            assert [sum(x > b for b in quarter) for x in got] == [0, 1, 2, 3]


def test_lengths_follow_the_stated_means():
    spec = json.loads((MIXES / "chat.json").read_text())
    # the distribution holds the source's means ...
    pairs = traffic.levels({**spec, "min_levels": 2000}, 1, 1 << 20)
    mean_in = sum(p.prompt for p in pairs) / len(pairs)
    mean_out = sum(p.output for p in pairs) / len(pairs)
    assert abs(mean_in - 161) < 8 and abs(mean_out - 338) < 15
    # ... and the cell's own 28 levels, which stop at the 98.2 % quantile,
    # hold a little less: what the benchmark's documentation reports
    pairs = traffic.levels(spec, 14, 2048)
    assert len(pairs) == 28
    assert sum(p.prompt for p in pairs) == 4343      # mean 155.1
    assert sum(p.output for p in pairs) == 9121      # mean 325.75
    assert max(p.prompt for p in pairs) == 798
    assert max(p.output for p in pairs) == 1674


def test_requests_are_reproducible_from_the_seed():
    spec = json.loads((MIXES / "chat.json").read_text())
    a = traffic.Plan(spec, 14, 2048, 49152, 2**32 + 11)
    b = traffic.Plan(spec, 14, 2048, 49152, 2**32 + 11)
    assert a.request(3, 2) == b.request(3, 2)
    prompt, n = a.request(3, 2)
    assert len(prompt) == a.pair(3, 2).prompt and n == a.pair(3, 2).output
    assert all(1 <= t < 49152 for t in prompt)


def test_shared_prefixes_head_the_prompts():
    spec = {"min_levels": 8, "reserve": 0,
            "prompt": {"dist": "loguniform", "min": 1, "max": 50},
            "output": {"dist": "loguniform", "min": 1, "max": 50},
            "prefix": {"count": 2, "tokens": 32}}
    plan = traffic.Plan(spec, 4, 1024, 500, 9)
    heads = {tuple(plan.request(c, 0)[0][:32]) for c in range(4)}
    assert heads == {tuple(p) for p in plan.prefixes}
    assert all(plan.pair(c, 0).prompt > 32 for c in range(4))
