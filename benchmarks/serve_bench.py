"""Serving load generator: waves vs batch-level vs iteration-level.

  PYTHONPATH=src python -m benchmarks.serve_bench \
      [--backend threads|processes|http|...] [--requests 48] \
      [--concurrency 32] [--open-rate 0] [--prefix-shared 0.5] \
      [--json BENCH_serving.json]

Closed loop (default): ``--concurrency`` clients each keep one request
outstanding until ``--requests`` total have completed — the paper's
fork-join client turned into sustained traffic.  Open loop
(``--open-rate`` req/s): Poisson arrivals, latency includes queueing the
way a real client sees it.

Three schedulers over the *same* model entry points:

* ``waves``            — ``LMServer.serve``: fixed fork-join partition
                         into ``--wave``-sized batches.
* ``continuous-batch`` — ``ContinuousBatcher`` pinned to the PR 4
                         batch-level path (``iteration_level=False``):
                         slot admission *between* batches, every batch
                         re-runs prefill.
* ``continuous``       — the ISSUE 5 iteration-level path where the
                         backend supports worker-resident state: KV cache
                         arenas live on the workers, admission every
                         ``--quantum`` decode steps, eviction at
                         ``max_new`` without batch-tail wait, and a
                         worker-resident prompt-prefix cache that lets
                         repeated prompts skip prefill entirely.
* ``continuous-paged`` — the ISSUE 7 paged twin of ``continuous``
                         (``--paged on`` adds it): each arena is a
                         refcounted block pool with per-row block tables,
                         prompts sharing a prefix share physical blocks
                         through a worker-resident radix index (partial
                         hits skip prefill for the matched head), and
                         long prompts chunk-prefill instead of falling
                         back to solo waves.  The scheduler summary
                         reports pool occupancy peaks (live tokens,
                         allocated blocks, radix-shared blocks) and the
                         JSON gains paged-vs-slot A/B numbers.

Requests are *long-tail mixed* on both axes (decode ~3/4 short at
``max_new/8``; prompts ~3/4 short at ``prompt_len/4``), and
``--prefix-shared`` replaces that fraction of prompts with one shared
system prompt of length ``--prompt-len`` — the workload where prefix
reuse shows up.  Reported per mode: throughput, completion-latency
percentiles, **TTFT** percentiles (time to first token — batch-level
schedulers have no token stream, so their TTFT *is* the completion
latency) and **TPOT** (time per output token after the first).

``--fleet N`` adds fleet mode (ISSUE 6): N engine-loop members on N
affinity-pinned workers behind the prefix-aware ``FleetRouter``, plus two
A/B baselines — ``fleet-random`` (same fleet, uniform-random placement)
and ``single`` (ONE worker carrying the same total arena slots).  The
JSON gains ``fleet_speedup_vs_single`` and
``ttft_p50_prefix_vs_random_ms``, per-member served/migration counts,
routing and scale-event logs, and per-worker busy-time shares from
``Session.stats()``.  ``--fleet-disaggregate on`` splits prefill/decode
roles; ``--fleet-elastic on`` (default) starts at ``--fleet-min`` and
scales on backlog/occupancy.

``--chaos kill-member`` (ISSUE 10) runs the chaos drill instead of the
regular modes: a seeded :class:`~repro.runtime.sandbox.ChaosPlan` is
armed after warmup and the transport client SIGKILLs one fleet member's
worker mid-decode.  The run asserts nothing itself — it *records*
everything (chaos events, per-row recovery, retry timestamps, recovered
vs untouched latency percentiles) into the ``repro.serve_chaos/v1``
document that CI's chaos smoke step asserts on.  Other kinds:
``drop-conn``, ``stall``, ``expire-lease``.

``--json`` writes the machine-readable ``repro.serve_bench/v2`` schema
(see ``make_result``); CI's serving smoke steps run tiny instances on
every push.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import random
import sys
import time

import numpy as np


# ------------------------------------------------------------- workload ----

def make_requests(cfg, n: int, prompt_len: int, max_new: int, seed: int = 0,
                  prefix_shared: float = 0.0, prefix_suffixes: int = 0):
    """Long-tail request mix on BOTH axes: ~3/4 short, ~1/4 long, for the
    prompt length and (independently) the decode length; ``prefix_shared``
    of the requests instead carry one identical shared prompt (the
    system-prompt pattern the prefix cache exists for).  With
    ``prefix_suffixes > 0`` the shared requests carry the shared *system
    prefix* (3/4 of ``prompt_len``) plus one of that many user suffixes —
    the fleet-routing workload, where the router's prefix key is the
    system prefix (``shared_prefix_len``) rather than the whole prompt."""
    from repro.runtime.server import Request
    rng = np.random.default_rng(seed)
    short_new = max(1, max_new // 8)
    short_prompt = max(1, prompt_len // 4)
    shared = list(rng.integers(1, cfg.vocab_size, prompt_len))
    head = shared[:shared_prefix_len(prompt_len)]
    tails = [list(rng.integers(1, cfg.vocab_size,
                               max(1, prompt_len - len(head))))
             for _ in range(max(0, prefix_suffixes))]
    out = []
    for _ in range(n):
        if prefix_shared > 0 and rng.random() < prefix_shared:
            prompt = (head + tails[int(rng.integers(len(tails)))]
                      if tails else list(shared))
        else:
            prompt = list(rng.integers(
                1, cfg.vocab_size,
                (short_prompt if rng.random() < 0.75 else prompt_len)))
        out.append(Request(
            prompt=prompt,
            max_new=(short_new if rng.random() < 0.75 else max_new)))
    return out


def shared_prefix_len(prompt_len: int) -> int:
    """Length of the shared system prefix in the suffix-pool workload —
    the router's content-hash key covers exactly this many tokens."""
    return max(1, (3 * prompt_len) // 4)


def make_server(backend: str, arch: str, max_new: int, os_threads: int,
                chaos=None):
    import jax
    from repro.cloud import Session
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.runtime.server import LMServer

    cfg = get_smoke(arch)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    session = Session(backend, os_threads=os_threads, chaos=chaos)
    server = LMServer(cfg, params, session=session, max_new=max_new)
    return cfg, session, server


def warmup(server, cfg, max_new: int, prompt_len: int, batch: int) -> None:
    """Pay every decode bucket's AOT compile at the *real* packed shapes
    (batch/prompt shape buckets, short AND long prompt buckets — the
    long-tail mix produces both) before timing anything."""
    from repro.runtime.server import Request, decode_bucket, shape_bucket
    for plen in sorted({shape_bucket(max(1, prompt_len // 4)),
                        shape_bucket(prompt_len)}):
        prompt = list(range(1, plen + 1))
        for b in sorted({decode_bucket(max(1, max_new // 8)),
                         decode_bucket(max_new)}):
            server.serve_wave([Request(prompt=prompt, max_new=b)] * batch)


def warmup_iteration(server, cfg, max_new: int, prompt_len: int, wave: int,
                     slots: int, **batcher_kwargs) -> None:
    """Untimed pass through the iteration-level scheduler itself: pays the
    engine entry points' jit compiles (prefill per prompt-width bucket,
    decode per chunk-length bucket) on the same affinity-pinned workers
    the timed run will use — the engine analogue of ``warmup``."""
    from repro.runtime.server import Request, shape_bucket
    from repro.serving import run_continuous
    plens = sorted({shape_bucket(max(1, prompt_len // 4)),
                    shape_bucket(prompt_len)})
    prompt_of = {plen: list(range(1, plen + 1)) for plen in plens}
    if batcher_kwargs.get("paged"):
        # chunked prefill splits a prompt wherever the per-call budget
        # lands, so ANY pow2 chunk-width bucket up to the longest prompt
        # can occur mid-run — compile them all here, or a budget split
        # would pay a fresh jit inside the measured window.  Widest
        # first (the first admission of a call always gets its full
        # width) and with a distinct token head per width, so neither a
        # budget split nor a radix prefix hit shrinks the first chunk of
        # a group below its bucket
        plens, w = [], 1
        while w <= shape_bucket(prompt_len):
            plens.append(w)
            w *= 2
        plens.reverse()
        prompt_of = {plen: list(range(plen, 2 * plen)) for plen in plens}
    reqs = []
    for plen in plens:
        for new in sorted({max(1, max_new // 8), max_new}):
            reqs.extend([Request(prompt=list(prompt_of[plen]),
                                 max_new=new)] * wave)
    run_continuous(server, reqs, concurrency=wave * slots, max_batch=wave,
                   slots=slots, iteration_level=True, **batcher_kwargs)


def warmup_fleet(server, cfg, max_new: int, prompt_len: int, wave: int,
                 n_members: int, **fleet_kwargs) -> None:
    """Untimed non-elastic fleet pass: spawns all ``n_members`` so every
    member's worker pays its engine jit compiles (prefill and decode per
    shape bucket, per role) before the timed — possibly elastic — run
    lands on the same affinity indices warm."""
    from repro.fleet import run_fleet
    from repro.runtime.server import Request, shape_bucket
    reqs = []
    for plen in sorted({shape_bucket(max(1, prompt_len // 4)),
                        shape_bucket(prompt_len)}):
        for new in sorted({max(1, max_new // 8), max_new}):
            reqs.extend([Request(prompt=list(range(1, plen + 1)),
                                 max_new=new)] * wave)
    fleet_kwargs.setdefault("max_batch", wave)
    run_fleet(server, reqs, concurrency=wave * n_members,
              n_members=n_members, elastic=False, **fleet_kwargs)


def worker_utilization(session) -> dict:
    """Per-worker cold/warm and busy-time evidence (satellite: sandbox
    counters surfaced through ``Session.stats()``).  Busy seconds include
    warmup — shares across workers are the meaningful number."""
    try:
        st = session.stats()
    except Exception as e:       # pragma: no cover - backend without stats
        return {"error": repr(e)}
    busy = {str(i): round(w.get("sandboxes", {}).get("busy_s", 0.0), 3)
            for i, w in st.get("workers", {}).items() if isinstance(w, dict)}
    total = sum(busy.values())
    return {"n_workers": st.get("n_workers"),
            "cold_starts": st.get("cold_starts"),
            "warm_hits": st.get("warm_hits"),
            "busy_s": round(st.get("busy_s", 0.0), 3),
            "per_worker_busy_s": busy,
            "per_worker_busy_share": {
                i: round(b / total, 3) for i, b in busy.items()} if total
            else {}}


def percentiles(lats_ms: list[float], prefix: str = "") -> dict:
    a = np.asarray(lats_ms, dtype=np.float64)
    return {f"{prefix}p50_ms": float(np.percentile(a, 50)),
            f"{prefix}p95_ms": float(np.percentile(a, 95)),
            f"{prefix}p99_ms": float(np.percentile(a, 99)),
            f"{prefix}mean_ms": float(a.mean())}


def summarize(lats_ms: list[float], wall_s: float, n_requests: int,
              tokens: int, ttfts_ms: list[float] | None = None,
              tpots_ms: list[float] | None = None) -> dict:
    out = {"requests": n_requests, "wall_s": round(wall_s, 3),
           "throughput_rps": round(n_requests / wall_s, 3),
           "tokens_per_s": round(tokens / wall_s, 3)}
    out.update({k: round(v, 2) for k, v in percentiles(lats_ms).items()})
    if ttfts_ms:
        out.update({k: round(v, 2)
                    for k, v in percentiles(ttfts_ms, "ttft_").items()})
    if tpots_ms:
        out.update({k: round(v, 3)
                    for k, v in percentiles(tpots_ms, "tpot_").items()})
    return out


def _token_metrics(comps, lats_ms):
    """Client-side TTFT/TPOT from the scheduler's per-token stamps.

    Iteration-level completions carry ``token_times_ms`` — stamped ONCE at
    each decode-chunk reply by the batcher — so TTFT is ``times[0]``
    (equal to ``ttft_ms`` by construction; asserted) and TPOT is the
    measured inter-token spread ``(times[-1] - times[0]) / (n - 1)``
    instead of being re-derived from the completion latency.  The derived
    TPOT can only over-estimate (latency includes the post-decode join),
    which the assert pins down.  Batch-level completions have no token
    stream: TTFT falls back to the completion latency and TPOT to the old
    derivation — the honest numbers for a scheduler whose whole batch
    joins at once."""
    ttfts, tpots = [], []
    for comp, lat in zip(comps, lats_ms):
        times = comp.token_times_ms
        if times:
            assert comp.ttft_ms is None or times[0] == comp.ttft_ms, \
                (times[0], comp.ttft_ms)
            ttfts.append(times[0])
            if len(times) > 1:
                tpot = (times[-1] - times[0]) / (len(times) - 1)
                derived = max(0.0, lat - times[0]) / (len(times) - 1)
                assert tpot <= derived + 1e-6, (tpot, derived)
                tpots.append(tpot)
            continue
        ttft = comp.ttft_ms if comp.ttft_ms is not None else lat
        ttfts.append(ttft)
        n = len(comp.tokens)
        if n > 1:
            tpots.append(max(0.0, lat - ttft) / (n - 1))
    return ttfts, tpots


# ----------------------------------------------------------- sync waves ----

def bench_waves(server, requests, *, wave_size: int, slots: int) -> dict:
    """Fixed fork-join: all requests present at t0, ``wave_size`` batches,
    ``slots`` waves in flight; a request's client-observed latency is its
    wave's completion time (the whole wave joins before anyone unpacks)."""
    waves = [requests[i:i + wave_size]
             for i in range(0, len(requests), wave_size)]
    t0 = time.perf_counter()
    futs, done_at = [], [0.0] * len(waves)

    def settle(i):
        futs[i].result()
        done_at[i] = time.perf_counter() - t0

    for i, w in enumerate(waves):
        if i >= slots:
            settle(i - slots)              # free the oldest payload
        futs.append(server.submit_wave(w, min_rows=wave_size))
    for i in range(max(0, len(waves) - slots), len(waves)):
        settle(i)
    comps = []
    for w, f in zip(waves, futs):
        comps.extend(server.unpack_wave(w, f))
    wall = time.perf_counter() - t0
    lats = [done_at[i // wave_size] * 1000.0 for i in range(len(requests))]
    tokens = sum(len(c.tokens) for c in comps)
    ttfts, tpots = _token_metrics(comps, lats)
    return summarize(lats, wall, len(requests), tokens, ttfts, tpots)


# ----------------------------------------------------- async continuous ----

def bench_continuous(server, requests, *, concurrency: int, max_batch: int,
                     slots: int, max_wait_ms: float,
                     open_rate: float = 0.0, seed: int = 0,
                     **batcher_kwargs) -> dict:
    """Closed loop (``open_rate==0``): ``concurrency`` clients back to
    back.  Open loop: Poisson arrivals at ``open_rate`` req/s, latency
    measured from *arrival* (queueing included).  ``batcher_kwargs``
    select the granularity (``iteration_level`` etc.)."""
    from repro.serving import ContinuousBatcher

    lats_ms: list[float] = []
    comps_out: list = []
    tokens = 0

    async def go():
        nonlocal tokens
        loop = asyncio.get_running_loop()
        sem = asyncio.Semaphore(max(1, concurrency))
        rng = np.random.default_rng(seed)
        arrivals = None
        if open_rate > 0:
            gaps = rng.exponential(1.0 / open_rate, size=len(requests))
            arrivals = np.cumsum(gaps)

        async with ContinuousBatcher(server, max_batch=max_batch,
                                     slots=slots, max_wait_ms=max_wait_ms,
                                     **batcher_kwargs) as batcher:
            t0 = loop.time()

            async def one(i, r):
                nonlocal tokens
                t_issue = None
                if arrivals is not None:
                    await asyncio.sleep(max(0.0, arrivals[i]
                                            - (loop.time() - t0)))
                    t_issue = loop.time()   # open loop: latency from ARRIVAL
                async with sem:
                    if t_issue is None:     # closed loop: from the client's turn
                        t_issue = loop.time()
                    comp = await batcher.submit(r)
                    lats_ms.append((loop.time() - t_issue) * 1000.0)
                    comps_out.append(comp)
                    tokens += len(comp.tokens)

            await asyncio.gather(*[one(i, r) for i, r in enumerate(requests)])
            wall = loop.time() - t0
            return wall, batcher.stats.summary()

    wall, sched = asyncio.run(go())
    ttfts, tpots = _token_metrics(comps_out, lats_ms)
    out = summarize(lats_ms, wall, len(requests), tokens, ttfts, tpots)
    out["scheduler"] = sched
    return out


# -------------------------------------------------------------- fleet ----

def bench_fleet(server, requests, *, concurrency: int, open_rate: float = 0.0,
                seed: int = 0, **fleet_kwargs) -> dict:
    """Same client loops as :func:`bench_continuous`, but requests go
    through a :class:`~repro.fleet.FleetRouter` — N members, each with its
    own worker-resident arena, placed by the configured routing policy."""
    from repro.fleet import FleetRouter

    lats_ms: list[float] = []
    comps_out: list = []
    tokens = 0

    async def go():
        nonlocal tokens
        loop = asyncio.get_running_loop()
        sem = asyncio.Semaphore(max(1, concurrency))
        rng = np.random.default_rng(seed)
        arrivals = None
        if open_rate > 0:
            gaps = rng.exponential(1.0 / open_rate, size=len(requests))
            arrivals = np.cumsum(gaps)

        async with FleetRouter(server, **fleet_kwargs) as fleet:
            t0 = loop.time()

            async def one(i, r):
                nonlocal tokens
                t_issue = None
                if arrivals is not None:
                    await asyncio.sleep(max(0.0, arrivals[i]
                                            - (loop.time() - t0)))
                    t_issue = loop.time()   # open loop: latency from ARRIVAL
                async with sem:
                    if t_issue is None:
                        t_issue = loop.time()
                    comp = await fleet.submit(r)
                    lats_ms.append((loop.time() - t_issue) * 1000.0)
                    comps_out.append(comp)
                    tokens += len(comp.tokens)

            await asyncio.gather(*[one(i, r) for i, r in enumerate(requests)])
            wall = loop.time() - t0
            return wall, fleet.summary()

    wall, fleet_summary = asyncio.run(go())
    ttfts, tpots = _token_metrics(comps_out, lats_ms)
    out = summarize(lats_ms, wall, len(requests), tokens, ttfts, tpots)
    out["fleet"] = fleet_summary
    return out


# ------------------------------------------------------------------ run ----

MODES = ("waves", "continuous-batch", "continuous", "continuous-paged",
         "fleet")


def make_result(config: dict, results: dict) -> dict:
    """The ``--json`` document — stable schema for CI and plots."""
    # the A/B readings are meaningless without knowing how many cores the
    # fleet's workers shared — a 1-core host serializes the whole fleet
    doc = {"schema": "repro.serve_bench/v2",
           "config": dict(config, host_cpus=os.cpu_count()),
           "results": results}
    w = results.get("waves")
    cb = results.get("continuous-batch")
    c = results.get("continuous")
    if w and c:
        doc["speedup_continuous_vs_waves"] = round(
            c["throughput_rps"] / max(w["throughput_rps"], 1e-9), 3)
    if cb and c:
        # the ISSUE 5 acceptance number: iteration-level vs the PR 4
        # batch-level continuous baseline, same workload, same backend
        doc["speedup_iteration_vs_batch"] = round(
            c["throughput_rps"] / max(cb["throughput_rps"], 1e-9), 3)
        doc["ttft_p50_iteration_vs_batch_ms"] = [
            c.get("ttft_p50_ms"), cb.get("ttft_p50_ms")]
    cp = results.get("continuous-paged")
    if cp and c:
        # the ISSUE 7 acceptance pair: paged block-pool arena vs the slot
        # arena, same workload, same backend — plus the occupancy evidence
        # that shared prefixes really shared physical blocks
        doc["speedup_paged_vs_slot"] = round(
            cp["throughput_rps"] / max(c["throughput_rps"], 1e-9), 3)
        doc["ttft_p50_paged_vs_slot_ms"] = [
            cp.get("ttft_p50_ms"), c.get("ttft_p50_ms")]
        sched = cp.get("scheduler", {})
        doc["paged_occupancy_peaks"] = {
            k: sched.get(f"{k}_peak") for k in
            ("live_tokens", "allocated_blocks", "shared_blocks")}
    fl = results.get("fleet")
    fr = results.get("fleet-random")
    sg = results.get("single")
    if fl and sg:
        # the ISSUE 6 acceptance number: N members on N workers vs ONE
        # worker carrying the same total arena slots, same workload
        doc["fleet_speedup_vs_single"] = round(
            fl["throughput_rps"] / max(sg["throughput_rps"], 1e-9), 3)
    if fl and fr:
        # prefix-aware vs uniform-random placement, same fleet shape:
        # routed repeats skip prefill on the owning worker → lower TTFT
        doc["ttft_p50_prefix_vs_random_ms"] = [
            fl.get("ttft_p50_ms"), fr.get("ttft_p50_ms")]
    return doc


def run(backend: str = "threads", arch: str = "smollm-360m", *,
        requests: int = 64, concurrency: int = 32, prompt_len: int = 16,
        max_new: int = 32, wave: int = 8, slots: int = 4,
        max_wait_ms: float = 10.0, open_rate: float = 0.0,
        prefix_shared: float = 0.0, prefix_suffixes: int = 0,
        quantum: int = 8, prefix_tokens: int = 1 << 16,
        block_size: int = 16, os_threads: int = 8,
        modes=("waves", "continuous"),
        fleet: dict | None = None, seed: int = 0) -> dict:
    results: dict = {}
    config = {"backend": backend, "arch": arch, "requests": requests,
              "concurrency": concurrency, "prompt_len": prompt_len,
              "max_new": max_new, "wave_size": wave, "slots": slots,
              "max_wait_ms": max_wait_ms, "open_rate": open_rate,
              "prefix_shared": prefix_shared,
              "prefix_suffixes": prefix_suffixes, "quantum": quantum,
              "block_size": block_size}
    if "fleet" in modes:
        fleet = dict(fleet or {})
        fleet.setdefault("n", 3)
        fleet.setdefault("policy", "prefix")
        fleet.setdefault("elastic", True)
        fleet.setdefault("min", 1)
        fleet.setdefault("disaggregate", False)
        fleet.setdefault("prefill", 1)
        fleet.setdefault("paged", False)
        fleet.setdefault(
            "prefix_len",
            shared_prefix_len(prompt_len) if prefix_suffixes else None)
        config["fleet"] = dict(fleet)

    if "waves" in modes:
        cfg, session, server = make_server(backend, arch, max_new, os_threads)
        try:
            reqs = make_requests(cfg, requests, prompt_len, max_new, seed,
                                 prefix_shared, prefix_suffixes)
            warmup(server, cfg, max_new, prompt_len, wave)
            results["waves"] = bench_waves(server, reqs, wave_size=wave,
                                           slots=slots)
            results["waves"]["cost"] = session.cost.summary()
        finally:
            server.close()
            session.close()

    for mode in ("continuous-batch", "continuous", "continuous-paged"):
        if mode not in modes:
            continue
        # the async stack's client half: on the plain http backend swap in
        # the multiplexed asyncio client (same worker model, no thread per
        # in-flight request) — that pairing IS the async-serving story
        cont_backend = "http-aio" if backend == "http" else backend
        cfg, session, server = make_server(cont_backend, arch, max_new,
                                           os_threads)
        try:
            reqs = make_requests(cfg, requests, prompt_len, max_new, seed,
                                 prefix_shared, prefix_suffixes)
            warmup(server, cfg, max_new, prompt_len, wave)
            if mode == "continuous-batch":
                kwargs = {"iteration_level": False}
            else:
                kwargs = {"quantum": quantum,
                          "prompt_cap": max(prompt_len, 8),
                          "prefix_tokens": prefix_tokens}
                if mode == "continuous-paged":
                    kwargs.update(paged=True, block_size=block_size)
            if mode != "continuous-batch":
                warmup_iteration(server, cfg, max_new, prompt_len, wave,
                                 slots, **{k: v for k, v in kwargs.items()
                                           if k != "iteration_level"})
            results[mode] = bench_continuous(
                server, reqs, concurrency=concurrency, max_batch=wave,
                slots=slots, max_wait_ms=max_wait_ms, open_rate=open_rate,
                seed=seed, **kwargs)
            results[mode]["backend"] = cont_backend
            results[mode]["cost"] = session.cost.summary()
        finally:
            server.close()
            session.close()

    if "fleet" in modes:
        n = fleet["n"]
        common = dict(prefix_len=fleet["prefix_len"],
                      disaggregate=fleet["disaggregate"],
                      prefill_members=fleet["prefill"], max_batch=wave,
                      quantum=quantum, prompt_cap=max(prompt_len, 8),
                      prefix_tokens=prefix_tokens,
                      paged=fleet["paged"], block_size=block_size)
        # the A/B pair: the configured policy vs uniform-random placement
        # on an identical fleet — isolates what routing (not parallelism)
        # buys.  The elastic run is the one that records scale events.
        for key, policy, elastic in (
                ("fleet", fleet["policy"], fleet["elastic"]),
                ("fleet-random", "random", False)):
            # the router provisions workers as members spawn — start at 1
            cfg, session, server = make_server(backend, arch, max_new, 1)
            try:
                reqs = make_requests(cfg, requests, prompt_len, max_new,
                                     seed, prefix_shared, prefix_suffixes)
                warmup(server, cfg, max_new, prompt_len, wave)
                warmup_fleet(server, cfg, max_new, prompt_len, wave, n,
                             policy=policy, seed=seed, **common)
                results[key] = bench_fleet(
                    server, reqs, concurrency=concurrency, n_members=n,
                    policy=policy, elastic=elastic,
                    min_members=fleet["min"], open_rate=open_rate,
                    seed=seed, **common)
                results[key]["backend"] = backend
                results[key]["cost"] = session.cost.summary()
                results[key]["workers"] = worker_utilization(session)
            finally:
                server.close()
                session.close()
        # single-worker baseline at EQUAL TOTAL SLOTS: the same n arenas ×
        # wave rows, all affinity-pinned onto one worker
        cfg, session, server = make_server(backend, arch, max_new, 1)
        try:
            reqs = make_requests(cfg, requests, prompt_len, max_new, seed,
                                 prefix_shared, prefix_suffixes)
            warmup(server, cfg, max_new, prompt_len, wave)
            kwargs = dict(quantum=quantum, prompt_cap=max(prompt_len, 8),
                          prefix_tokens=prefix_tokens)
            warmup_iteration(server, cfg, max_new, prompt_len, wave, n,
                             **kwargs)
            results["single"] = bench_continuous(
                server, reqs, concurrency=concurrency, max_batch=wave,
                slots=n, max_wait_ms=max_wait_ms, open_rate=open_rate,
                seed=seed, iteration_level=True, **kwargs)
            results["single"]["backend"] = backend
            results["single"]["cost"] = session.cost.summary()
            results["single"]["workers"] = worker_utilization(session)
        finally:
            server.close()
            session.close()

    return make_result(config, results)


# ------------------------------------------------------------- chaos ----

CHAOS_KINDS = ("kill-member", "drop-conn", "stall", "expire-lease")


def make_chaos_plan(kind: str, *, seed: int, n_slots: int,
                    after: int | None = None):
    """One seeded ChaosPlan per CLI kind — same (slot, Nth-invoke)
    derivation for every kind so seeds compare across failure modes."""
    from repro.runtime.sandbox import ChaosEvent, ChaosPlan
    if kind == "kill-member":
        return ChaosPlan.kill_member(seed=seed, n_slots=n_slots, after=after)
    rng = random.Random(seed * 1_000_003 + 17)
    slot = rng.randrange(max(1, n_slots))
    fire = after if after is not None else 3 + rng.randrange(3)
    if kind == "drop-conn":
        ev = ChaosEvent("drop", slot=slot, after=fire)
    elif kind == "stall":
        ev = ChaosEvent("stall", slot=slot, after=fire, stall_s=0.25)
    elif kind == "expire-lease":
        ev = ChaosEvent("expire", slot=slot, after=fire)
    else:
        raise ValueError(f"unknown chaos kind {kind!r} "
                         f"(one of {CHAOS_KINDS})")
    return ChaosPlan([ev], seed=seed)


def run_chaos(backend: str = "processes", arch: str = "smollm-360m", *,
              kind: str = "kill-member", requests: int = 12,
              concurrency: int = 8, prompt_len: int = 16, max_new: int = 16,
              wave: int = 4, quantum: int = 4, prefix_tokens: int = 1 << 16,
              n_members: int = 2, after: int | None = None,
              seed: int = 7) -> dict:
    """The chaos drill: a non-elastic fleet of ``n_members`` on a real
    transport, one seeded failure injected mid-run, everything recorded.

    The contract under test: a killed worker is *added latency*, not a
    client-visible error — the victim's live rows replay (prompt +
    generated-so-far) on a surviving member and finish bit-identical,
    while the dispatcher's backoff policy spaces the retries and the
    transport lazily respawns the dead worker.  ``all_served`` and the
    event counts in the returned document are what CI asserts."""
    from repro.fleet import FleetRouter

    plan = make_chaos_plan(kind, seed=seed, n_slots=n_members, after=after)
    cfg, session, server = make_server(backend, arch, max_new, 1, chaos=plan)
    try:
        reqs = make_requests(cfg, requests, prompt_len, max_new, seed)
        common = dict(max_batch=wave, quantum=quantum,
                      prompt_cap=max(prompt_len, 8),
                      prefix_tokens=prefix_tokens)
        warmup(server, cfg, max_new, prompt_len, wave)
        warmup_fleet(server, cfg, max_new, prompt_len, wave, n_members,
                     policy="prefix", seed=seed, **common)
        plan.arm()                      # warmup traffic cost no chaos budget

        lats_ms: list[float] = []
        comps: list = []
        errors: list[str] = []

        async def go():
            loop = asyncio.get_running_loop()
            sem = asyncio.Semaphore(max(1, concurrency))
            async with FleetRouter(server, n_members=n_members,
                                   policy="prefix", elastic=False,
                                   seed=seed, **common) as fleet:
                t0 = loop.time()

                async def one(r):
                    async with sem:
                        t_issue = loop.time()
                        try:
                            comp = await fleet.submit(r)
                        except Exception as e:   # the drill records, CI asserts
                            errors.append(repr(e))
                            return
                        lats_ms.append((loop.time() - t_issue) * 1000.0)
                        comps.append(comp)

                await asyncio.gather(*[one(r) for r in reqs])
                return loop.time() - t0, fleet.summary()

        wall, fleet_summary = asyncio.run(go())
        retry_log = [dict(e) for e in session.retry_log]
        try:
            respawns = session.stats().get("respawns")
        except Exception:
            respawns = None
    finally:
        server.close()
        session.close()

    recovered = [(c, l) for c, l in zip(comps, lats_ms)
                 if getattr(c, "recovered", False)]
    untouched = [(c, l) for c, l in zip(comps, lats_ms)
                 if not getattr(c, "recovered", False)]
    # per-row receipts next to the transport's worker.* events — one
    # row.recovered per completion that survived a failover
    row_events = [{"action": "row.recovered", "tokens": len(c.tokens)}
                  for c, _ in recovered]
    counts = plan.counts()
    counts["row.recovered"] = len(row_events)
    tokens = sum(len(c.tokens) for c in comps)
    ttfts, tpots = _token_metrics(comps, lats_ms)
    result = summarize(lats_ms, wall, len(comps), tokens, ttfts, tpots)
    recovery: dict = {
        "recovered_rows": fleet_summary["batcher"].get("recovered_rows", 0),
        "fleet_recoveries": fleet_summary.get("recoveries", 0),
        "n_recovered": len(recovered), "n_untouched": len(untouched)}
    if recovered:
        recovery["recovered_latency"] = {
            k: round(v, 2)
            for k, v in percentiles([l for _, l in recovered]).items()}
    if untouched:
        recovery["untouched_latency"] = {
            k: round(v, 2)
            for k, v in percentiles([l for _, l in untouched]).items()}
    return {
        "schema": "repro.serve_chaos/v1",
        "config": {"backend": backend, "arch": arch, "requests": requests,
                   "concurrency": concurrency, "prompt_len": prompt_len,
                   "max_new": max_new, "wave_size": wave, "quantum": quantum,
                   "n_members": n_members, "chaos": kind, "seed": seed,
                   "host_cpus": os.cpu_count()},
        "plan": [{"kind": e.kind, "slot": e.slot, "after": e.after}
                 for e in plan.events],
        "events": plan.log() + row_events,
        "counts": counts,
        "all_served": not errors and len(comps) == len(reqs),
        "client_errors": errors,
        "worker_respawns": respawns,
        "result": result,
        "recovery": recovery,
        "retry_log": retry_log,
        "fleet": fleet_summary,
    }


def main(argv=None):
    from repro.cloud import available_backends
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", default="threads",
                    choices=available_backends())
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--concurrency", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--wave", type=int, default=8,
                    help="wave size / continuous max_batch / arena rows")
    ap.add_argument("--slots", type=int, default=4,
                    help="in-flight batches (batch modes) / arenas (iteration)")
    ap.add_argument("--max-wait-ms", type=float, default=10.0)
    ap.add_argument("--open-rate", type=float, default=0.0,
                    help="req/s Poisson arrivals (0 = closed loop)")
    ap.add_argument("--prefix-shared", type=float, default=0.0,
                    help="fraction of requests carrying one shared prompt "
                         "(prefix-cache workload)")
    ap.add_argument("--prefix-suffixes", type=int, default=0,
                    help="shared requests carry the shared SYSTEM PREFIX "
                         "plus one of this many user suffixes (0 = whole "
                         "prompt identical)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="run fleet mode with N members (adds the fleet / "
                         "fleet-random / single results and A/B numbers)")
    ap.add_argument("--fleet-policy", default="prefix",
                    choices=("prefix", "p2c", "random", "radix"))
    ap.add_argument("--fleet-elastic", default="on", choices=("on", "off"),
                    help="elastic pool: start at --fleet-min, grow under "
                         "backlog, drain on low occupancy")
    ap.add_argument("--fleet-min", type=int, default=1)
    ap.add_argument("--fleet-disaggregate", default="off",
                    choices=("on", "off"),
                    help="split members into prefill/decode roles with row "
                         "migration over CONTROL frames")
    ap.add_argument("--fleet-prefill", type=int, default=1,
                    help="prefill members in disaggregated mode")
    ap.add_argument("--quantum", type=int, default=8,
                    help="iteration mode: decode steps per chunk")
    ap.add_argument("--prefix-tokens", type=int, default=1 << 16,
                    help="iteration mode: prefix-cache budget (0 disables)")
    ap.add_argument("--paged", default="off", choices=("on", "off"),
                    help="add the continuous-paged mode (block-pool KV "
                         "arena with radix prefix sharing, ISSUE 7)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged mode: KV block granularity (pow2-rounded)")
    ap.add_argument("--chaos", default="off",
                    choices=("off",) + CHAOS_KINDS,
                    help="run the seeded chaos drill instead of the normal "
                         "modes (writes repro.serve_chaos/v1)")
    ap.add_argument("--chaos-after", type=int, default=None,
                    help="fire on the Nth armed invocation of the victim "
                         "slot (default: seed-derived)")
    ap.add_argument("--chaos-members", type=int, default=2,
                    help="fleet size for the chaos drill")
    ap.add_argument("--os-threads", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--modes", default="waves,continuous",
                    help=f"comma list from {MODES}")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="write the repro.serve_bench/v2 document here")
    ap.add_argument("--trace", dest="trace_path", default=None,
                    help="record request spans and write Chrome-trace JSON "
                         "here (open in chrome://tracing or Perfetto)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    help="fraction of requests to trace (default 1.0 when "
                         "--trace is given, else 0 = off)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.trace_path or args.trace_sample is not None:
        from repro.obs import trace as obs_trace
        obs_trace.configure(sample=(args.trace_sample
                                    if args.trace_sample is not None
                                    else 1.0))

    if args.chaos != "off":
        doc = run_chaos(args.backend, args.arch, kind=args.chaos,
                        requests=args.requests, concurrency=args.concurrency,
                        prompt_len=args.prompt_len, max_new=args.max_new,
                        wave=args.wave, quantum=args.quantum,
                        prefix_tokens=args.prefix_tokens,
                        n_members=args.chaos_members,
                        after=args.chaos_after, seed=args.seed)
        text = json.dumps(doc, indent=1)
        print(text)
        if args.json_path:
            with open(args.json_path, "w") as f:
                f.write(text + "\n")
        return

    modes = tuple(m for m in args.modes.split(",") if m)
    if args.paged == "on" and "continuous-paged" not in modes:
        modes = modes + ("continuous-paged",)
    fleet = None
    if args.fleet > 0:
        if "fleet" not in modes:
            modes = modes + ("fleet",)
        fleet = {"n": args.fleet, "policy": args.fleet_policy,
                 "elastic": args.fleet_elastic == "on",
                 "min": args.fleet_min,
                 "disaggregate": args.fleet_disaggregate == "on",
                 "prefill": args.fleet_prefill,
                 "paged": args.paged == "on"}
    doc = run(args.backend, args.arch, requests=args.requests,
              concurrency=args.concurrency, prompt_len=args.prompt_len,
              max_new=args.max_new, wave=args.wave, slots=args.slots,
              max_wait_ms=args.max_wait_ms, open_rate=args.open_rate,
              prefix_shared=args.prefix_shared,
              prefix_suffixes=args.prefix_suffixes, quantum=args.quantum,
              prefix_tokens=args.prefix_tokens, block_size=args.block_size,
              os_threads=args.os_threads, modes=modes, fleet=fleet,
              seed=args.seed)
    text = json.dumps(doc, indent=1)
    print(text)
    if args.json_path:
        with open(args.json_path, "w") as f:
            f.write(text + "\n")
    if args.trace_path:
        from repro.obs import trace as obs_trace
        n = obs_trace.TRACER.dump(args.trace_path)
        print(f"trace: {n} span events -> {args.trace_path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
