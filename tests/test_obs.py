"""Observability plane (ISSUE 8): trace context on the wire, span
stitching across the process boundary, metrics registry math, exporters,
the JAX profiler sink, and the tracing-off overhead guard."""
import asyncio
import gc
import glob
import json
import os

import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serialization import wire


@pytest.fixture(autouse=True)
def _clean_tracer():
    """The process tracer is shared state — every test starts and ends
    hard-off with an empty ring."""
    t = obs_trace.TRACER
    t.configure(enabled=False, sample=0.0)
    t.reset()
    yield
    t.configure(enabled=False, sample=0.0)
    t.reset()


# Module-level tasks: shippable to worker processes by reference.
def task_double(x):
    return x * 2


def task_exit(x):
    import os
    os._exit(13)               # sandbox loss: no goodbye on the wire


# ------------------------------------------------------------------ wire ----

def test_wire_trace_roundtrip():
    ctx = {"tid": "t1", "sid": "s1", "t0": 12.5}
    frame = wire.encode_invoke("fn", b"p", task_id=1, attempt=1, trace=ctx)
    msg = wire.decode(frame)
    assert msg.trace == ctx

    spans = [{"name": "worker.entry", "tid": "t1", "sid": "w1",
              "parent": "s1", "t0": 12.5, "dur": 0.01, "proc": "worker"}]
    reply = wire.decode(wire.encode_result(b"b", stats={}, server_s=0.1,
                                           spans=spans))
    assert reply.spans == spans
    err = wire.decode(wire.encode_error(etype="ValueError", retryable=False,
                                        message="boom", spans=spans))
    assert err.spans == spans


def test_wire_trace_is_additive():
    """Untraced frames carry no trace/spans header fields at all (an old
    worker never sees the key), and decoding an old-style frame without
    them fills the defaults."""
    frame = wire.encode_invoke("fn", b"p", task_id=1, attempt=1)
    assert b'"trace"' not in frame
    assert wire.decode(frame).trace is None
    reply = wire.encode_result(b"b", stats={}, server_s=0.1)
    assert b'"spans"' not in reply
    assert wire.decode(reply).spans == []


# ------------------------------------------------------------- stitching ----

def test_span_stitching_across_processes():
    """One traced request through the real ``processes`` backend: the
    worker-side spans come back on the reply envelope and parent under the
    client's submit span — one tree spanning two pids."""
    from repro.cloud import Session
    obs_trace.configure(sample=1.0)
    with Session("processes", os_threads=1) as sess:
        f = sess.function(task_double, jax_traceable=False)
        assert f.submit(3).result() == 6
    spans = obs_trace.TRACER.spans()
    by_name = {s.name: s for s in spans}
    root = by_name["client.submit"]
    assert root.parent_id is None and root.proc == "client"
    assert {"client.transport", "worker.decode", "worker.entry"} \
        <= set(by_name)
    for name in ("worker.decode", "worker.compile", "worker.entry"):
        s = by_name[name]
        assert s.proc == "worker"
        assert s.trace_id == root.trace_id
        assert s.parent_id == root.span_id
        assert s.pid != root.pid          # genuinely crossed a process
    assert by_name["client.transport"].parent_id == root.span_id
    assert by_name["worker.entry"].attrs.get("cold_start") is True


def test_worker_error_context_on_failing_span():
    """A crashed worker's epitaph (exit detail) lands on the transport
    span, and the submit span records the failure type."""
    from repro.cloud import Session
    obs_trace.configure(sample=1.0)
    with Session("processes", os_threads=1) as sess:
        f = sess.function(task_exit, jax_traceable=False)
        with pytest.raises(Exception):
            f.submit(1).result()
    errs = [s for s in obs_trace.TRACER.spans() if s.status == "error"]
    assert errs, "a failing request must produce error-status spans"
    transport = [s for s in errs if s.name == "client.transport"]
    assert transport and "error.type" in transport[0].attrs
    assert "error.detail" in transport[0].attrs


# --------------------------------------------------------------- metrics ----

def test_histogram_bucket_math():
    h = obs_metrics.Histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 50.0, 500.0, 1000.0):
        h.observe(v)
    s = h.series()
    # le semantics: a value equal to a bound counts in that bound's bucket
    assert s["counts"] == [2, 1, 1, 2]
    assert s["count"] == 6
    assert s["sum"] == pytest.approx(1556.5)
    assert h.cumulative() == [2, 3, 4, 6]


def test_registry_merge_and_labels():
    a, b = obs_metrics.Registry(), obs_metrics.Registry()
    a.counter("c").inc(2, k="x")
    b.counter("c").inc(3, k="x")
    b.counter("c").inc(1, k="y")
    a.gauge("g").set(4)
    b.gauge("g").set(5)
    a.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    b.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
    b.histogram("other", buckets=(9.0,)).observe(1.0)
    a.merge(b.snapshot())
    assert a.counter("c").value(k="x") == 5
    assert a.counter("c").value(k="y") == 1
    assert a.gauge("g").value() == 9       # summed: fleet total of a gauge
    assert a.histogram("h", buckets=(1.0, 2.0)).series()["counts"] \
        == [1, 1, 0]
    assert a.get("other") is not None      # unknown names are created


def test_prometheus_exposition():
    reg = obs_metrics.Registry()
    reg.counter("reqs", "requests handled").inc(3, backend="x")
    reg.histogram("lat_ms", buckets=(1.0, 10.0)).observe(0.5)
    text = reg.render()
    assert "# HELP reqs requests handled" in text
    assert "# TYPE reqs counter" in text
    assert 'reqs{backend="x"} 3' in text
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="+Inf"} 1' in text
    assert "lat_ms_sum 0.5" in text
    assert "lat_ms_count 1" in text


def test_session_stats_carries_metrics():
    from repro.cloud import Session
    with Session("threads", os_threads=2) as sess:
        f = sess.function(task_double, jax_traceable=False)
        assert f.submit(5).result() == 10
        m = sess.stats()["metrics"]
    assert m["sandbox_cold_starts_total"]["type"] == "counter"
    assert sum(m["sandbox_cold_starts_total"]["values"].values()) >= 1
    assert sum(m["entry_busy_seconds_total"]["values"].values()) > 0


# --------------------------------------------------------------- sampler ----

def test_sampler_seeded_determinism():
    a = obs_trace.Sampler(0.5, seed=7)
    b = obs_trace.Sampler(0.5, seed=7)
    seq = [a.decide() for _ in range(64)]
    assert seq == [b.decide() for _ in range(64)]
    assert any(seq) and not all(seq)
    assert all(obs_trace.Sampler(1.0, seed=1).decide() for _ in range(8))
    assert not any(obs_trace.Sampler(0.0, seed=1).decide()
                   for _ in range(8))


# -------------------------------------------------------------- exporter ----

def test_chrome_export_schema(tmp_path):
    obs_trace.configure(sample=1.0)
    root = obs_trace.TRACER.start_trace("client.submit", function="f")
    child = obs_trace.TRACER.span("client.transport", root.ctx, slot=0)
    child.finish()
    root.finish()
    path = tmp_path / "trace.json"
    n = obs_trace.dump_trace(str(path))
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert n == len(events) == 2
    for ev in events:
        assert ev["ph"] == "X"
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(ev)
    sub = next(e for e in events if e["name"] == "client.submit")
    tra = next(e for e in events if e["name"] == "client.transport")
    assert tra["args"]["parent_span_id"] == sub["args"]["span_id"]
    assert tra["args"]["trace_id"] == sub["args"]["trace_id"]
    assert sub["args"]["parent_span_id"] is None


# --------------------------------------------------------- overhead guard ----

def test_disabled_tracing_makes_no_instrumentation_calls():
    """The hard off-switch: with tracing off every site returns before
    counting as an engagement — ``calls`` stays 0 end to end."""
    from repro.cloud import Session
    t = obs_trace.TRACER
    assert not t.enabled and t.calls == 0
    assert t.start_trace("x") is obs_trace.NOOP
    assert t.span("x") is obs_trace.NOOP
    t.span_at("x", obs_trace.SpanContext("t", "s"), 0.0, 0.0)
    t.ingest([{"name": "x"}])
    assert t.calls == 0 and t.spans() == []

    with Session("threads", os_threads=2) as sess:
        f = sess.function(task_double, jax_traceable=False)
        assert f.submit(4).result() == 8
    assert t.calls == 0 and t.spans() == []


# ------------------------------------------------------ the profiler sink ----

PROGRAM_SPANS = {"engine.schedule", "engine.idle", "engine.prefill_chunk",
                 "engine.decode_quantum", "worker.entry", "engine.sync",
                 "py.gc"}


@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    from repro.configs import get_smoke
    from repro.models import build_model
    cfg = get_smoke("smollm-360m")
    params, _ = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


def _paged_serve(cfg, params, during=None, paged=True):
    """A ``threads`` paged serve: five prompts (two repeated, so the radix
    index serves part of them), then after a pause, in which the loop
    idles, one more.  ``during(start)`` brackets the second round.
    ``paged=False`` serves the same from the slot arena and its prefix
    store."""
    import numpy as np
    from repro.cloud import Session
    from repro.runtime.server import LMServer, Request
    from repro.serving import ContinuousBatcher
    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in (20, 40, 7)]
    prompts += [list(prompts[0]), list(prompts[1])]
    reqs = [Request(prompt=p, max_new=m)
            for p, m in zip(prompts, (12, 9, 16, 5, 12))]

    async def go(b):
        comps = list(await asyncio.gather(*[b.submit(r) for r in reqs]))
        await asyncio.sleep(0.05)
        comps.append(await b.submit(Request(prompt=prompts[2], max_new=4)))
        return comps

    with Session("threads", os_threads=2) as sess:
        server = LMServer(cfg, params, session=sess, max_new=16)

        async def main():
            async with ContinuousBatcher(server, max_batch=3, slots=1,
                                         quantum=4, paged=paged,
                                         block_size=4, prefill_budget=8,
                                         arena_cap=64 if paged else None
                                         ) as b:
                await go(b)                      # compiles every program
                if during:
                    during(True)
                comps = await go(b)
                gc.collect()
                if during:
                    during(False)
                return comps, b.stats

        comps, stats = asyncio.run(main())
        server.close(prune=False)
    return reqs, comps, stats


@pytest.fixture
def counted(monkeypatch):
    """Every TraceAnnotation the sink creates, by name."""
    from jax.profiler import TraceAnnotation
    made = []

    class Counting(TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(obs_trace, "_ANNOTATION", Counting)
    return made


def test_no_sink_on_makes_no_annotation_and_no_call(tiny_lm, counted):
    """Neither the ring nor a profiler session: a whole paged serve creates
    no annotation and engages the tracer nowhere."""
    _paged_serve(*tiny_lm)
    gc.collect()
    assert counted == []
    assert obs_trace.TRACER.calls == 0 and obs_trace.TRACER.spans() == []


def test_profiler_alone_lands_every_span_in_the_trace(tiny_lm, counted,
                                                      tmp_path):
    """Tracing off, a profiler session capturing: the program's spans are
    host events of the trace, attributes in their metadata, and the ring
    sees nothing."""
    import jax

    def during(start):
        if start:
            jax.profiler.start_trace(str(tmp_path))
        else:
            jax.profiler.stop_trace()

    _paged_serve(*tiny_lm, during=during)
    assert obs_trace.TRACER.calls == 0 and obs_trace.TRACER.spans() == []
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    found: dict = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in PROGRAM_SPANS:
                    found.setdefault(e.name, dict(e.stats))
    assert set(found) == PROGRAM_SPANS
    assert set(counted) == PROGRAM_SPANS
    assert {"k", "rows"} <= set(found["engine.decode_quantum"])
    assert {"prompt_tokens", "padded_tokens", "prefix_tokens"} \
        <= set(found["engine.prefill_chunk"])
    assert {"generation", "collected"} <= set(found["py.gc"])


def test_ring_and_profiler_share_one_span(counted, tmp_path):
    """With both sinks on, one handle feeds both: a ring span and a
    profiler annotation of the same name and attributes."""
    import jax
    obs_trace.configure(sample=1.0)
    root = obs_trace.TRACER.start_trace("engine.loop")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.TRACER.span("engine.sync", root.ctx, profile=True,
                                   what="tokens") as sp:
            sp.set("rows", 3)
        unprofiled = obs_trace.TRACER.span("client.submit", root.ctx)
        unprofiled.finish()
    finally:
        jax.profiler.stop_trace()
    root.finish()
    assert counted == ["engine.sync"]
    ring = {s.name: s for s in obs_trace.TRACER.spans()}
    assert ring["engine.sync"].attrs == {"what": "tokens", "rows": 3}
    assert ring["engine.sync"].parent_id == root.ctx.span_id


def test_gc_hook_takes_no_tracer_lock(counted, tmp_path):
    """A collection can interrupt a thread that holds the tracer's locks.
    With both sinks on and a context bound, the ``py.gc`` hook must take
    neither lock, and records into the profiler alone."""
    import threading
    import jax
    obs_trace.configure(sample=1.0)
    root = obs_trace.TRACER.start_trace("engine.loop")
    calls = obs_trace.TRACER.calls

    def collection():
        obs_trace._on_gc("start", {"generation": 2})
        obs_trace._on_gc("stop", {"generation": 2, "collected": 5,
                                  "uncollectable": 0})

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs_trace.TRACER._lock, obs_trace._id_lock:
            t = threading.Thread(target=obs_trace.bound(root.ctx, collection),
                                 daemon=True)
            t.start()
            t.join(10)
            blocked = t.is_alive()
    finally:
        jax.profiler.stop_trace()
    t.join()
    assert not blocked
    assert counted == ["py.gc"]
    assert obs_trace.TRACER.calls == calls and obs_trace.TRACER.spans() == []


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "slot"])
def test_prompt_tokens_and_queue_wait_are_counted(tiny_lm, paged):
    """Every admitted prompt token is either prefilled or served from the
    radix index (or the slot arena's prefix store), and both are counted;
    the queue wait and TTFT start at submit, so the TTFT histogram sums at
    least the admission-based ``Completion.ttft_ms``."""
    reg = obs_metrics.REGISTRY
    wait0 = reg.histogram("serve_queue_wait_ms").series()["count"]
    ttft0 = reg.histogram("serve_ttft_ms").series()["sum"]
    reqs, comps, stats = _paged_serve(*tiny_lm, paged=paged)
    served = 2 * sum(len(r.prompt) for r in reqs) + 2 * len(reqs[2].prompt)
    s = stats.summary()
    assert s["prefill_tokens"] + s["prefix_tokens"] == served
    assert s["prefix_tokens"] > 0
    assert reg.histogram("serve_queue_wait_ms").series()["count"] \
        - wait0 == 2 * (len(reqs) + 1)
    assert reg.histogram("serve_ttft_ms").series()["sum"] - ttft0 \
        >= sum(c.ttft_ms for c in comps)
