"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload smollm-360m.chat --seconds 10 \
        --seeds 11 12 13

For each seed, in this one process, a run of the cell at its own load
with the control in the program's place: the plain reference computed in
float8, whose first choice at each position of the sampled requests is
judged as the served token by the harness's own check.  Each run must come
out not correct; the line also gives the same gap of what the program
itself served on those requests, a sound run's reading.  Benchmark runs
never compute the control.  One JSON line per seed; the exit code is 1 if
any control run came out correct.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import harness
    cell = harness.load_cell(args.workload)
    row, _ = harness.check.gap_row(cell.limits)
    t = T_START
    caught = True
    for seed in args.seeds:
        doc = harness.run_cell(cell, seed, args.seconds, False, t_start=t,
                               control=True)
        caught &= not doc["correct"]
        print(json.dumps({
            "seed": seed, "control_correct": doc["correct"],
            "control_gap": doc["checks"][row]["value"],
            "served_gap": doc["served_gap"], "checks": doc["checks"],
            "metrics": doc["metrics"]}), flush=True)
        t = time.perf_counter()
    return 0 if caught else 1


if __name__ == "__main__":
    raise SystemExit(main())
