"""Every per-layer reader, on the recorded chip trace excerpt."""
import json
from pathlib import Path

import pytest

import costs
import harness
from test_devtrace import _recorded

BENCH = Path(__file__).resolve().parents[1]


class _Rec:
    def __init__(self, prompt, t_admit, stamps):
        self.prompt, self.t_admit, self.stamps = [1] * prompt, t_admit, stamps
        self.ttft_s = stamps[0] - t_admit


def _window(trace):
    cfg = json.loads((BENCH / "configs" / "smollm-360m.json").read_text())
    t0, t1 = 0.0, trace.window_s
    # the excerpt holds five layers of one decode step: one row decodes a
    # token inside it, another prefills
    recs = [_Rec(300, -1.0, [-0.5, t1 * 0.5]),
            _Rec(500, t0, [t1 * 0.9, t1 * 1.5])]
    counters = {"decode_chunks": 4, "occupancy_sum": 10, "prefix_hits": 1,
                "prefix_misses": 3}
    return harness.Window(t0, t1, trace, counters, recs, cfg,
                          costs.peaks_for("TPU v5 lite"), 0)


def _names():
    doc = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    return [m["name"] for m in doc["per_layer"]]


@pytest.mark.parametrize("name", _names())
def test_reader_reads_the_recorded_trace(name):
    value = harness.load_reader(name)(_window(_recorded()))
    assert value is not None and value >= 0
    if name.split(".")[0].endswith(("_pct", "_roofline")):
        assert value <= 100


@pytest.mark.parametrize("name", _names())
def test_reader_without_a_trace_reads_nothing_from_it(name):
    win = _window(_recorded())
    win.trace = None
    win.counters = {}
    value = harness.load_reader(name)(win)
    assert value is None or name.startswith("window_compiles")


def test_tpot_counts_every_token_of_a_quantum_at_its_share():
    # first token at 1.0; a quantum of 4 tokens 0.4 s later, one of 2
    # tokens 0.1 s after that (outside the window); a request admitted
    # earlier delivers 3 tokens at 1.2
    recs = [_Rec(10, 0.9, [1.0, 1.4, 1.4, 1.4, 1.4, 2.5, 2.5]),
            _Rec(10, 0.0, [0.5, 1.2, 1.2, 1.2])]
    got = sorted(harness.token_times(recs, 0.8, 2.0))
    assert got == pytest.approx([0.1] * 4 + [0.7 / 3] * 3)
    e2e = harness.end_to_end(recs, 0.8, 2.0)
    assert e2e["tpot_p90_ms"][2] == 7
    assert e2e["output_tok_s"][2] == 8


def test_stalls_name_the_longest_gap_between_deliveries():
    recs = [_Rec(10, 0.0, [0.1, 0.3, 0.3, 1.3]),
            _Rec(10, 0.0, [0.2, 0.3005, 0.5])]
    line = harness.stalls(recs, 0.0, 2.0)
    assert line.startswith("delivery gaps 4 ")
    assert "longest 800.0ms@1.3s 200.0ms@0.5s" in line


def test_a_deployment_on_another_arena_is_refused():
    from test_faults import tiny_cell
    cell = tiny_cell()
    with pytest.raises(ValueError, match="paged arena only"):
        harness.Cell(cell.name, {**cell.config, "deployment": {
            **cell.deployment, "arena": "slot"}}, cell.mix, cell.limits,
            1, {}, [])
