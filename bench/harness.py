"""One run of one benchmark cell: set-up, closed-loop traffic, metrics, check.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything it
needs is found by name: ``configs[].file`` (the model and its deployment),
``bench/traffic/<traffic>.json`` (the mix), ``bench/limits/<cell>.json``
(the correctness limits) and ``bench/metrics/<metric>.py`` (one reader per
per-layer metric).  The system under test is the program's
``ContinuousBatcher`` on the ``threads`` backend over the paged arena, in
this one process, with every knob the deployment does not choose at the
program's default.
"""
from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"

import check  # noqa: E402  (bench/ is on sys.path: run.py's directory)
import costs  # noqa: E402
import devtrace  # noqa: E402
import references  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    config: dict          # the configuration file
    mix: dict             # the traffic file
    limits: dict          # the correctness limits
    chips: int
    metrics: dict         # per_layer metrics that this cell reports
    end_to_end: list      # names of the end-to-end metrics it reports

    def __post_init__(self):
        arena = self.deployment.get("arena")
        if arena != "paged":
            raise ValueError(f"{self.name}: arena {arena!r} in the deployment;"
                             " the harness serves the paged arena only")

    @property
    def deployment(self) -> dict:
        return self.config["deployment"]

    @property
    def capacity(self) -> int:
        return int(self.config["max_position_embeddings"])


def load_cell(name: str, doc: dict | None = None) -> Cell:
    doc = doc if doc is not None else load_json(ROOT / "BENCHMARK.json")
    wl = next((w for w in doc["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in doc["configs"] if c["name"] == wl["config"])
    mine = [m for m in doc["end_to_end"]
            if name in m.get("workloads", [name])]
    e2e = {m["name"] for m in mine}
    layer = {m["name"]: m for m in doc["per_layer"]
             if name in m.get("workloads", [name]) and m["moves"] in e2e}
    return Cell(name=name, config=load_json(ROOT / conf["file"]),
                mix=load_json(BENCH / "traffic" / f"{wl['traffic']}.json"),
                limits=load_json(BENCH / "limits" / f"{name}.json"),
                chips=int(wl["chips"]), metrics=layer,
                end_to_end=[m["name"] for m in mine])


def program_config(config: dict):
    """The program's ModelConfig for a configuration file: its registry
    entry with the fields that the configuration's family reads from the
    file put in (a nested group replaced field by field)."""
    from repro.configs import get_config
    base = get_config(config["program_model"])
    fields = references.family(config).program_fields(config)
    return base.replace(**{
        k: dataclasses.replace(getattr(base, k), **v)
        if isinstance(v, dict) else v for k, v in fields.items()})


# ------------------------------------------------------ compile events --

class CompileLog:
    """Backend compiles and persistent-cache loads, stamped on the monotonic
    clock (the event loop's).  JAX listeners cannot be removed, so one log
    per process."""
    _instance = None

    def __init__(self):
        import jax
        self.events: list[tuple[float, str]] = []
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    @classmethod
    def get(cls) -> "CompileLog":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.events.append((time.monotonic(), "cache_load"))

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), "compile"))

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 <= t < t1)


# ------------------------------------------------------------ set-up --

def enable_cache() -> None:
    """Persistent compilation cache at a fixed path inside the checkout,
    every program written to it however quickly it compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pow2_upto(n: int) -> list[int]:
    out, w = [], 1
    while w < n:
        out.append(w)
        w *= 2
    return out + [w]


def warm_up(server, cell: Cell, plan: traffic.Plan) -> dict:
    """Compile (or load) every program the window can call, through a
    throw-away engine of the cell's arena shape: the chunked-prefill widths
    that the budget and the mix's longest prompt can reach, and every decode
    chunk length up to the quantum.  Derived from the mix's ranges and the
    engine's own budget and quantum, not from the requests drawn."""
    from repro.runtime.engine import EngineClient
    dep = cell.deployment
    eng = EngineClient(server, rows=int(dep["rows"]), paged=True,
                       cap=cell.capacity, block_size=int(dep["block_size"]))
    try:
        if not eng.paged:
            raise RuntimeError("the program refused the paged arena for "
                               f"{cell.config['name']}")
        budget = eng.prefill_budget if eng.prefill_budget > 0 \
            else plan.max_prompt()
        widths = pow2_upto(min(budget, plan.max_prompt()))
        ks = pow2_upto(eng.quantum)
        rng = np.random.default_rng(12345)
        free: tuple = ()
        for w in reversed(widths):
            prompt = [int(t) for t in rng.integers(1, plan.vocab, w)]
            fut, _ = eng.submit_admit([(0, prompt)], True, free)
            eng.observe_paged_prefill(fut.result())
            free = (0,)
        for k in reversed(ks):
            eng.observe(eng.submit_step(k).result())
        return {"prefill_widths": widths, "decode_ks": ks,
                "prefill_budget": eng.prefill_budget,
                "quantum": eng.quantum}
    finally:
        eng.close()


# ------------------------------------------------------------ traffic --

@dataclass
class Record:
    client: int
    index: int
    prompt: list
    t_send: float
    t_admit: float
    tokens: list
    stamps: list          # per token, on the event loop's clock

    @property
    def ttft_s(self) -> float:
        return self.stamps[0] - self.t_send


def _record(c, j, prompt, t_send, t_done, comp) -> Record:
    # The batcher stamps tokens from admission; admission is recovered on
    # the same clock from the completion's latency (a client resumes a few
    # microseconds after the batcher resolved it, which this counts as wait).
    if not comp.token_times_ms:
        raise RuntimeError("the batcher returned no per-token stamps")
    t_admit = t_done - comp.latency_ms / 1000.0
    return Record(c, j, prompt, t_send, t_admit, list(comp.tokens),
                  [t_admit + ms / 1000.0 for ms in comp.token_times_ms])


def _counters(stats) -> dict:
    return {k: getattr(stats, k) for k in (
        "requests", "decode_chunks", "occupancy_sum", "prefix_hits",
        "prefix_misses", "wave_fallbacks")}


async def drive(server, cell: Cell, plan: traffic.Plan, seconds: float,
                trace_dir: str | None) -> dict:
    """Closed loop: each client sends its next request when the previous one
    completes, until the window closes; then every request in flight is
    served to its end (its tokens inside the window count)."""
    from repro.runtime.server import Request
    from repro.serving import ContinuousBatcher
    import jax
    dep = cell.deployment
    loop = asyncio.get_running_loop()
    recs: list[Record] = []
    errors: list[str] = []
    attempted = 0
    out: dict = {}
    async with ContinuousBatcher(
            server, max_batch=int(dep["rows"]), slots=1, paged=True,
            block_size=int(dep["block_size"]),
            arena_cap=cell.capacity) as batcher:
        t_begin = loop.time()
        t0 = t_begin + float(cell.mix["ramp_s"])
        t1 = t0 + seconds

        async def client(c: int) -> None:
            nonlocal attempted
            await asyncio.sleep(plan.start_s(c))
            j = 0
            while loop.time() < t1:
                prompt, n = plan.request(c, j)
                t_send = loop.time()
                attempted += 1
                try:
                    comp = await batcher.submit(
                        Request(prompt=prompt, max_new=n))
                except Exception as e:          # counted, and the run fails
                    errors.append(f"{type(e).__name__}: {e}")
                    return
                recs.append(_record(c, j, prompt, t_send, loop.time(),
                                    comp))
                j += 1

        async def window() -> None:
            await asyncio.sleep(max(0.0, t0 - loop.time()))
            out["c0"] = _counters(batcher.stats)
            if trace_dir:
                jax.profiler.start_trace(trace_dir)
                out["trace_t0"] = loop.time()
            await asyncio.sleep(max(0.0, t1 - loop.time()))
            out["c1"] = _counters(batcher.stats)
            if trace_dir:
                out["trace_t1"] = loop.time()
                jax.profiler.stop_trace()

        await asyncio.gather(window(), *(client(c)
                                         for c in range(plan.clients)))
        out["mode"] = batcher.stats.mode
        out["final"] = _counters(batcher.stats)
    out.update(records=recs, errors=errors, attempted=attempted, t0=t0,
               t1=t1)
    return out


# ------------------------------------------------------------ metrics --

def p90(values) -> float:
    if not values:
        raise RuntimeError("no request in the window to take a tail from")
    return float(np.percentile(np.asarray(values, np.float64), 90))


def deliveries(r: Record):
    """A request's deliveries after its first token: (stamp, tokens, gap
    since its previous delivery).  A decode quantum delivers several
    tokens under one stamp."""
    s, i = r.stamps, 1
    while i < len(s):
        j = i
        while j < len(s) and s[j] == s[i]:
            j += 1
        yield s[i], j - i, s[i] - s[i - 1]
        i = j


def token_times(recs: list[Record], t0: float, t1: float) -> list[float]:
    """For every token after a request's first that reached its client
    inside the window, the time it took: the gap since that request's
    previous delivery over the tokens the delivery brought."""
    out = []
    for r in recs:
        for t, n, gap in deliveries(r):
            if t0 <= t < t1:
                out.extend([gap / n] * n)
    return out


def end_to_end(recs: list[Record], t0: float, t1: float) -> dict:
    """TTFT over requests whose first token came inside the window, timed
    from the send; TPOT over every token after a request's first delivered
    inside it; the rate over every token delivered inside it."""
    ttft = [r.ttft_s * 1000 for r in recs if t0 <= r.stamps[0] < t1]
    tpot = [g * 1000 for g in token_times(recs, t0, t1)]
    toks = sum(1 for r in recs for s in r.stamps if t0 <= s < t1)
    return {"ttft_p90_ms": (p90(ttft), "ms", len(ttft)),
            "tpot_p90_ms": (p90(tpot), "ms", len(tpot)),
            "output_tok_s": (toks / (t1 - t0), "tokens/s", toks)}


def stalls(recs: list[Record], t0: float, t1: float, top: int = 3) -> str:
    """Gaps between successive deliveries to any client inside the window
    (a decode quantum's or a first token's): their median and the longest,
    with when each ended, in seconds into the window.  A run that reads slow
    shows here whether one stall or every step took the time."""
    stamps: list[float] = []
    # one quantum stamps its rows alike, up to each request's admission
    # clock, which the harness recovers to some microseconds
    for t in sorted({t for r in recs for t in r.stamps if t0 <= t < t1}):
        if not stamps or t - stamps[-1] > 0.002:
            stamps.append(t)
    gaps = sorted(((b - a) * 1000, b - t0)
                  for a, b in zip(stamps, stamps[1:]))
    if not gaps:
        return "delivery gaps 0"
    longest = " ".join(f"{g:.1f}ms@{t:.1f}s" for g, t in gaps[::-1][:top])
    return (f"delivery gaps {len(gaps)} median {gaps[len(gaps) // 2][0]:.2f}"
            f"ms longest {longest}")


@dataclass
class Window:
    """What a per-layer reader may read: the reduced device trace, the
    program's counters over the traced window, the requests, the sizes."""
    t0: float
    t1: float
    trace: "devtrace.Reduced | None"
    counters: dict
    records: list
    config: dict
    peaks: dict
    compiles: int


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: Cell, win: Window) -> dict:
    out = {}
    for name, meta in cell.metrics.items():
        value = load_reader(name)(win)
        if value is not None:
            out[name] = {"value": float(value), "unit": meta["unit"]}
    return out


# ---------------------------------------------------------------- run --

def device_info(chips: int, allow_cpu: bool = False) -> dict:
    import jax
    devs = jax.devices()
    if not allow_cpu and (devs[0].platform == "cpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} accelerator chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(cell: Cell, seed: int, seconds: float, trace_on: bool, *,
             t_start: float | None = None, allow_cpu: bool = False,
             control: bool = False, log=None) -> dict:
    """One run; returns the result document (the last stdout line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    import jax
    device = device_info(cell.chips, allow_cpu)
    peaks = costs.peaks_for(device["kind"]) if not allow_cpu else {}
    enable_cache()
    sys.path.insert(0, str(ROOT / "src"))
    from repro.cloud import Session
    from repro.models import build_model
    from repro.runtime.server import LMServer

    clog = CompileLog.get()
    cfg = program_config(cell.config)
    params = weights.make(cell.config, seed)
    jax.block_until_ready(params)
    weights.check_layout(params, jax.eval_shape(
        lambda: build_model(cfg).init(jax.random.PRNGKey(0))[0]))
    dep = cell.deployment
    plan = traffic.Plan(cell.mix, int(dep["rows"]), cell.capacity,
                        cell.config["vocab_size"], seed)
    sess = Session("threads")
    server = LMServer(cfg, params, session=sess)
    try:
        warm = warm_up(server, cell, plan)
        setup_s = time.perf_counter() - t_start
        log(f"setup_s {setup_s:.3f} warmed {warm}")
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            run = asyncio.run(drive(server, cell, plan, seconds,
                                    tdir if trace_on else None))
            reduced = devtrace.reduce(
                tdir, run["trace_t1"] - run["trace_t0"]) if trace_on \
                else None
        device["memory_peak_bytes"] = memory_peak()
    finally:
        server.close()
        sess.close()
    recs, t0, t1 = run["records"], run["t0"], run["t1"]
    compiles = clog.count(t0, t1)
    log(f"requests {len(recs)} attempted {run['attempted']} errors "
        f"{len(run['errors'])} window_compiles {compiles} "
        f"mode {run['mode']} counters {run['final']}")
    for e in run["errors"][:5]:
        log(f"request error: {e}")

    checks, served_gap = check.judge(params, cell, recs, run, seed,
                                     control=control)
    del params
    doc = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": run["attempted"], "failed": len(run["errors"])}
    if control:
        doc["served_gap"] = served_gap
    if trace_on:
        c0, c1 = run["c0"], run["c1"]
        win = Window(run["trace_t0"], run["trace_t1"], reduced,
                     {k: c1[k] - c0[k] for k in c1}, recs, cell.config,
                     peaks, compiles)
        doc["metrics"] = per_layer(cell, win)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        doc["device"] = device
        doc["breakdown"] = reduced.breakdown()
    else:
        e2e = end_to_end(recs, t0, t1)
        log("samples " + " ".join(f"{k} {v[2]}" for k, v in e2e.items()))
        per_token = np.asarray(token_times(recs, t0, t1)) * 1000
        log("tpot_ms at p50/p80/p90/p95/p99 " + " ".join(
            f"{v:.3f}" for v in np.percentile(per_token, [50, 80, 90, 95,
                                                          99])))
        log(stalls(recs, t0, t1))
        doc["metrics"] = {k: {"value": v[0], "unit": v[1]}
                          for k, v in e2e.items() if k in cell.end_to_end}
        doc["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
        doc["device"] = device
    doc["checks"] = checks
    return doc
