"""Serving launcher: batched requests through a serverless cloud session.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --requests 16 --max-new 8 \
      [--backend threads|inline|sim-aws|processes|http|http-aio] \
      [--mode waves|continuous]

``--backend`` switches the execution backend and ``--mode`` the scheduler
without touching any serving code — the single-source property the
session API guarantees.  ``waves`` is the fixed fork-join client
(``LMServer.serve``); ``continuous`` drives the same pack/unpack core
through the asyncio :class:`~repro.serving.batcher.ContinuousBatcher`
(slot-based admission, decode-length bucketing).  The
``processes``/``http``/``http-aio`` backends run generation in real worker
processes behind the wire protocol; params deploy once to the
content-addressed artifact store and payloads carry the reference.

``--fleet N`` serves through the :class:`~repro.fleet.FleetRouter`
instead: N engine-loop members, each pinned to its own worker, with
prefix-aware routing (``--fleet-policy prefix|p2c|random``), optional
prefill/decode disaggregation (``--fleet-disaggregate``), and elastic
scale-up/drain (``--fleet-elastic``).

On a TPU host this process initialises the params and so holds the chip;
worker processes could not get it.  The launcher therefore refuses the
backends that spawn them there (``CHILD_PROCESS_BACKENDS``).
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from ..cloud import Session, available_backends
from ..configs import get_config, get_smoke
from ..models import build_model
from ..runtime.server import LMServer, Request
from .compile_cache import enable_compile_cache

# backends whose workers run in child processes of this one
CHILD_PROCESS_BACKENDS = ("processes", "http", "http-aio")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--wave", type=int, default=8,
                    help="wave size (waves) / max batch (continuous)")
    ap.add_argument("--backend", default="threads",
                    choices=available_backends())
    ap.add_argument("--mode", default="waves",
                    choices=("waves", "continuous"))
    ap.add_argument("--slots", type=int, default=2,
                    help="continuous mode: in-flight decode batches/arenas")
    ap.add_argument("--max-wait-ms", type=float, default=10.0,
                    help="continuous mode: batch-fill wait")
    ap.add_argument("--iteration", default="auto",
                    choices=("auto", "on", "off"),
                    help="continuous mode: iteration-level scheduling "
                         "(worker-resident KV arena; auto = when the "
                         "backend supports resident state)")
    ap.add_argument("--quantum", type=int, default=8,
                    help="iteration mode: decode steps per chunk")
    ap.add_argument("--prefix-tokens", type=int, default=1 << 16,
                    help="iteration mode: prompt-prefix cache budget "
                         "(tokens; 0 disables)")
    ap.add_argument("--paged", default="off", choices=("on", "off"),
                    help="iteration mode: paged KV arena (block-table "
                         "attention, radix prefix sharing, chunked "
                         "prefill)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged mode: tokens per KV block")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve through a FleetRouter with N members "
                         "(overrides --mode)")
    ap.add_argument("--fleet-policy", default="prefix",
                    choices=("prefix", "p2c", "random", "radix"))
    ap.add_argument("--fleet-elastic", default="off", choices=("on", "off"),
                    help="start at --fleet-min members, grow under backlog, "
                         "drain on sustained low occupancy")
    ap.add_argument("--fleet-min", type=int, default=1)
    ap.add_argument("--fleet-disaggregate", default="off",
                    choices=("on", "off"),
                    help="split members into prefill and decode roles; "
                         "prefilled rows migrate over CONTROL frames")
    ap.add_argument("--fleet-prefill", type=int, default=1,
                    help="disaggregated mode: prefill member count")
    args = ap.parse_args()
    if args.backend in CHILD_PROCESS_BACKENDS \
            and jax.default_backend() == "tpu":
        ap.error(f"--backend {args.backend} runs workers in child processes, "
                 "and on a TPU host this process holds the chip: use "
                 "--backend threads or inline")
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    session = Session(args.backend)
    server = LMServer(cfg, params, session=session, max_new=args.max_new)

    rng = np.random.default_rng(0)
    reqs = [Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                             args.prompt_len)),
                    max_new=args.max_new)
            for _ in range(args.requests)]
    t0 = time.perf_counter()
    fleet_summary = None
    if args.fleet > 0:
        from ..fleet import run_fleet
        comps, fleet_summary = run_fleet(
            server, reqs, concurrency=args.requests,
            n_members=args.fleet, policy=args.fleet_policy,
            elastic=args.fleet_elastic == "on", min_members=args.fleet_min,
            disaggregate=args.fleet_disaggregate == "on",
            prefill_members=args.fleet_prefill,
            max_batch=args.wave, quantum=args.quantum,
            prompt_cap=max(8, args.prompt_len),
            prefix_tokens=args.prefix_tokens,
            paged=args.paged == "on", block_size=args.block_size,
            return_stats=True)
    elif args.mode == "continuous":
        from ..serving import run_continuous
        iteration = {"auto": None, "on": True, "off": False}[args.iteration]
        comps = run_continuous(server, reqs, concurrency=args.requests,
                               max_batch=args.wave, slots=args.slots,
                               max_wait_ms=args.max_wait_ms,
                               iteration_level=iteration,
                               quantum=args.quantum,
                               prompt_cap=max(8, args.prompt_len),
                               prefix_tokens=args.prefix_tokens,
                               paged=args.paged == "on",
                               block_size=args.block_size)
    else:
        comps = server.serve(reqs, wave_size=args.wave)
    wall = time.perf_counter() - t0
    doc = {
        "arch": cfg.name, "backend": args.backend,
        "mode": f"fleet-{args.fleet}" if args.fleet > 0 else args.mode,
        "requests": len(comps),
        "wall_s": round(wall, 3),
        "tokens_generated": sum(len(c.tokens) for c in comps),
        "cost": server.cost_report.summary(),
        "sample": comps[0].tokens,
    }
    if fleet_summary is not None:
        doc["fleet"] = fleet_summary
        doc["workers"] = session.stats()
    print(json.dumps(doc, indent=1))
    server.close()
    session.close()


if __name__ == "__main__":
    main()
