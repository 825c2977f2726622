"""A whole run on the CPU at test size, the chip check skipped: sound, it
is correct; with the timed path broken underneath, it is not."""
import json
from pathlib import Path

import pytest

import harness

DATA = Path(__file__).resolve().parent / "data"


def tiny_cell():
    return harness.Cell(
        "tiny", json.loads((DATA / "tiny.json").read_text()),
        json.loads((DATA / "tiny_mix.json").read_text()),
        json.loads((DATA / "tiny_limits.json").read_text()), 1, {},
        ["ttft_p90_ms", "tpot_p90_ms", "output_tok_s"])


def _broken(kind):
    """A paged decode program that breaks one guarantee."""
    from repro.runtime import engine
    real = engine._paged_decode_fn

    def make(cfg, k):
        step = real(cfg, k)

        def run(params, pool_k, pool_v, table, lens, live, last):
            pk, pv, nxt, toks = step(params, pool_k, pool_v, table, lens,
                                     live, last)
            if kind == "state_unchanged":       # the K/V written is lost
                return pool_k, pool_v, nxt, toks
            if kind == "token_altered":
                bump = lambda t: (t + 1) % cfg.vocab_size  # noqa: E731
                return pk, pv, bump(nxt), bump(toks)
            half = toks.shape[0] // 2           # half the rows left out
            return pk, pv, nxt.at[half:].set(cfg.pad_id), \
                toks.at[half:].set(cfg.pad_id)
        return run
    return make


@pytest.fixture(autouse=True)
def _own_cache(tmp_path, monkeypatch):
    """Keep the test's CPU programs out of the checkout's cache."""
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")


def _run(seed):
    return harness.run_cell(tiny_cell(), seed, 2.0, False, allow_cpu=True,
                            log=lambda *a: None)


def test_sound_run_is_correct():
    doc = _run(2**33 + 1)
    assert doc["correct"], doc["checks"]
    assert doc["failed"] == 0 and doc["attempted"] > 0
    assert set(doc["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                   "output_tok_s", "setup_s"}
    assert doc["checks"]["checked_tokens_min"]["ok"]


@pytest.mark.parametrize("kind", ["state_unchanged", "token_altered",
                                  "half_the_rows"])
def test_broken_decode_is_not_correct(monkeypatch, kind):
    from repro.runtime import engine
    monkeypatch.setattr(engine, "_paged_decode_fn", _broken(kind))
    doc = _run(2**33 + 2)
    assert not doc["correct"]
    gap = doc["checks"]["greedy_gap_max"]
    assert not gap["ok"] and gap["value"] > gap["limit"]
