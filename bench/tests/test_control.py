"""The correctness check's control at test size: the plain reference in
float8 e4m3, put in the program's place, makes the harness's own verdict
come out not correct on every seed, while the program's own served tokens
on the same requests stay under the limit."""
import pytest

import harness
from test_faults import tiny_cell


@pytest.fixture(autouse=True)
def _own_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 2**40 + 13])
def test_float8_control_fails_where_the_program_passes(seed):
    doc = harness.run_cell(tiny_cell(), seed, 2.0, False, allow_cpu=True,
                           control=True, log=lambda *a: None)
    assert doc["correct"] is False, doc["checks"]
    gap = doc["checks"]["greedy_gap_max"]
    assert not gap["ok"] and gap["value"] > gap["limit"]
    assert doc["served_gap"] <= gap["limit"]
    others = {k: v for k, v in doc["checks"].items() if k != "greedy_gap_max"}
    assert all(v["ok"] for v in others.values()), others
