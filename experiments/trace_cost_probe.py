"""What the program's spans cost, and where a benchmark cell's device idle
time goes.

  python experiments/trace_cost_probe.py --sites
  python experiments/trace_cost_probe.py --cell smollm-360m.chat \
      --seed 7 [--seconds 51] [--out experiments/results/trace_cost]

``--sites`` times one span site with both sinks off (tracing off, no
profiler session), as the engine loop opens them, in ns on this host's
CPU, and the ``gc.callbacks`` hook on a collection.

``--cell`` needs the cell's chips.  In one process it sets the cell up as
``bench/run.py`` does, then serves two closed-loop windows of the cell's
traffic from one seed: the first with no profiler session, the second
captured.  For each it prints ``output_tok_s`` and the median gap between
deliveries, the cost of capturing.  Of the captured window it prints every
per-layer metric the cell reports, the device idle time by innermost
program span (``bench/spans.py``) and the ten longest idle stretches
named so, and writes ``<out>/<cell>.excerpt.json``: the device
operations, modules and program spans around the idle stretch that
follows one decode program in the middle of the window.
"""
from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def site_cost(n: int = 200_000) -> dict:
    """ns per span site and per collection hook, both sinks off."""
    from repro.obs import trace as obs_trace
    tracer = obs_trace.TRACER
    assert not tracer.enabled and obs_trace._capturing() is None

    def loop_site():                     # EngineLoop._span, root unsampled
        with tracer.span("engine.schedule", None, profile=True):
            pass

    def worker_site():                   # WorkerPool._execute, untraced
        sp = tracer.span("worker.entry", None, profile=True, function="f")
        sp.set("cold_start", False)
        sp.finish()

    def sync_site():                     # engine.sync
        with tracer.span("engine.sync", profile=True, what="tokens"):
            pass

    def empty():
        pass

    out = {}
    for name, fn in (("empty", empty), ("loop_site", loop_site),
                     ("worker_site", worker_site), ("sync_site", sync_site)):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        out[name] = (time.perf_counter() - t0) / n * 1e9
    for name in ("loop_site", "worker_site", "sync_site"):
        out[name + "_net"] = out[name] - out["empty"]
    t0 = time.perf_counter()
    for _ in range(n // 10):
        obs_trace._on_gc("start", {"generation": 0})
        obs_trace._on_gc("stop", {"generation": 0, "collected": 0})
    out["gc_hook_pair"] = (time.perf_counter() - t0) / (n // 10) * 1e9
    out["calls"] = tracer.calls
    return out


def median_gap_ms(recs, t0: float, t1: float) -> float:
    """Median gap between successive deliveries to any client in the
    window, as ``harness.stalls`` counts them."""
    stamps: list[float] = []
    for t in sorted({t for r in recs for t in r.stamps if t0 <= t < t1}):
        if not stamps or t - stamps[-1] > 0.002:
            stamps.append(t)
    gaps = sorted((b - a) * 1000 for a, b in zip(stamps, stamps[1:]))
    return gaps[len(gaps) // 2] if gaps else float("nan")


def excerpt(trace, spans_mod) -> dict:
    """The idle stretch in which the middle decode quantum's last
    device-to-host read ends, with the device operations on both sides of
    it and every program span over it."""
    import numpy as np
    quanta = spans_mod.decode_quanta(trace)
    _, _, syncs = quanta[len(quanta) // 2]
    t = max(s + d for _, s, d in syncs)
    a, b = spans_mod.idle_stretches(trace)
    i = min(int(np.searchsorted(b, t)), len(b) - 1)
    lo, hi = int(a[i]) - 200_000, int(b[i]) + 200_000
    return {"ops": [list(o) for o in trace.ops
                    if o[1] < hi and o[1] + o[2] > lo],
            "modules": [list(m) for m in trace.modules
                        if m[1] < hi and m[1] + m[2] > lo],
            "host": [list(h) for h in spans_mod.program(trace)
                     if h[1] < hi and h[1] + h[2] > lo],
            "stretch": [lo, hi]}


def run_cell(name: str, seed: int, seconds: float, out: Path) -> dict:
    import jax
    import harness
    import spans
    import traffic
    import weights
    from repro.cloud import Session
    from repro.runtime.server import LMServer

    cell = harness.load_cell(name)
    device = harness.device_info(cell.chips)
    peaks = harness.costs.peaks_for(device["kind"])
    harness.enable_cache()
    params = weights.make(cell.config, seed)
    jax.block_until_ready(params)
    dep = cell.deployment
    plan = traffic.Plan(cell.mix, int(dep["rows"]), cell.capacity,
                        cell.config["vocab_size"], seed)
    sess = Session("threads")
    server = LMServer(harness.program_config(cell.config), params,
                      session=sess)
    doc = {"cell": name, "seed": seed, "device": device, "runs": {}}
    try:
        harness.warm_up(server, cell, plan)
        for mode in ("off", "captured"):
            with tempfile.TemporaryDirectory(prefix="probe-trace-") as tdir:
                run = asyncio.run(harness.drive(
                    server, cell, plan, seconds,
                    tdir if mode == "captured" else None))
                reduced = devtrace_reduce(tdir, run) \
                    if mode == "captured" else None
            recs, t0, t1 = run["records"], run["t0"], run["t1"]
            e2e = harness.end_to_end(recs, t0, t1)
            row = {"output_tok_s": e2e["output_tok_s"][0],
                   "tpot_p90_ms": e2e["tpot_p90_ms"][0],
                   "median_gap_ms": median_gap_ms(recs, t0, t1),
                   "errors": len(run["errors"])}
            if reduced is not None:
                c0, c1 = run["c0"], run["c1"]
                win = harness.Window(run["trace_t0"], run["trace_t1"],
                                     reduced, {k: c1[k] - c0[k] for k in c1},
                                     recs, cell.config, peaks, 0)
                row["metrics"] = {k: v["value"] for k, v in
                                  harness.per_layer(cell, win).items()}
                row["window_s"] = reduced.window_s
                row["busy_s"] = reduced.busy_s
                idle = spans.idle_by_span(reduced)
                row["idle_by_span_s"] = dict(sorted(
                    idle.items(), key=lambda kv: -kv[1]))
                row["longest_idle"] = longest(reduced, spans)
                out.mkdir(parents=True, exist_ok=True)
                (out / f"{name}.excerpt.json").write_text(
                    json.dumps(excerpt(reduced, spans)))
            doc["runs"][mode] = row
            print(json.dumps({"cell": name, "mode": mode, **row}),
                  flush=True)
            gc.collect()
    finally:
        server.close()
        sess.close()
    return doc


def devtrace_reduce(tdir, run):
    import devtrace
    return devtrace.reduce(tdir, run["trace_t1"] - run["trace_t0"])


def longest(trace, spans_mod, n: int = 10) -> list:
    """The ``n`` longest idle stretches with the innermost program span
    over their middle."""
    import numpy as np
    a, b = spans_mod.idle_stretches(trace)
    order = np.argsort(a - b, kind="stable")[:n]
    prog = spans_mod.program(trace)
    out = []
    for i in order:
        mid = (int(a[i]) + int(b[i])) // 2
        over = [e for e in prog if e[1] <= mid < e[1] + e[2]]
        name = max(over, key=lambda e: e[1])[0] if over else \
            spans_mod.NO_SPAN
        out.append([name, (int(b[i]) - int(a[i])) / 1e9])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sites", action="store_true")
    ap.add_argument("--cell")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", default=str(ROOT / "experiments" /
                                         "results" / "trace_cost"))
    args = ap.parse_args()
    if args.sites:
        print(json.dumps(site_cost()))
    if args.cell:
        doc = run_cell(args.cell, args.seed, args.seconds, Path(args.out))
        off, on = doc["runs"]["off"], doc["runs"]["captured"]
        print(json.dumps({"cell": args.cell, "seed": args.seed,
                          "tok_s_ratio": on["output_tok_s"]
                          / off["output_tok_s"],
                          "gap_ratio": on["median_gap_ms"]
                          / off["median_gap_ms"],
                          "named_idle_share": 1 - on["idle_by_span_s"].get(
                              "no_span", 0) / max(1e-12, sum(
                                  on["idle_by_span_s"].values()))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
