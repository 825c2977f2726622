"""Run one benchmark cell and print its result as the last stdout line.

    python3 bench/run.py --workload smollm-360m.chat --seed 7 --seconds 30 \
        --trace 0

Set-up (weights from the seed, the program's set-up, every program the
window can call compiled or loaded) counts as ``setup_s``; then the cell's
closed-loop traffic runs through a ramp and a window of ``--seconds``, the
requests still in flight are served to their end, and a sample of what was
served is held to the plain reference.  ``--trace 1`` profiles the window
and reports the cell's per-layer metrics instead of its end-to-end ones.
Without the chips the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

# libtpu logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import harness
        cell = harness.load_cell(args.workload)
        doc = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    except ImportError as e:
        print(f"bench: cannot import the system under test: {e}",
              file=sys.stderr)
        return 1
    for name, row in doc["checks"].items():
        print(f"check {name} {row['value']} limit {row['limit']} "
              f"{'ok' if row['ok'] else 'FAIL'}", file=sys.stderr)
    print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
