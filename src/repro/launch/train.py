"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --steps 200 --batch 8 --seq 128 [--smoke] [--ckpt DIR] [--fail-at 60]

Uses the real config by default (with the production mesh when more than
one device is available) or the reduced smoke config for CPU runs.
"""
from __future__ import annotations

import argparse
import json

from ..configs import get_config, get_smoke
from ..runtime.trainer import train
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject simulated preemptions at these steps")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)

    def on_step(step, metrics):
        if step % args.log_every == 0:
            print(f"step {step:5d}  loss {float(metrics['loss']):.4f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)

    report = train(cfg, steps=args.steps, global_batch=args.batch,
                   seq_len=args.seq, ckpt_dir=args.ckpt,
                   ckpt_every=args.ckpt_every, peak_lr=args.lr,
                   fail_at=set(args.fail_at), on_step=on_step)
    print(json.dumps({
        "arch": cfg.name, "steps_run": report.steps_run,
        "restarts": report.restarts, "restored_from": report.restored_from,
        "first_loss": report.losses[0] if report.losses else None,
        "final_loss": report.final_loss,
        "mean_step_s": (sum(report.step_times_s[1:])
                        / max(1, len(report.step_times_s) - 1)),
    }, indent=1))


if __name__ == "__main__":
    main()
