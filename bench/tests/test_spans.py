"""The program's spans read from a profiler capture: a tiny paged serve on
the CPU, and a recorded chip excerpt whose innermost-span arithmetic is
worked out by hand."""
import asyncio
import gc
import glob
import json
import math
import os
from pathlib import Path

import pytest

import devtrace
import harness
import spans

DATA = Path(__file__).resolve().parent / "data"
READERS = ("dispatch_ms.tpot", "engine_host_ms.tpot", "schedule_ms.tok_s",
           "idle_dispatch_pct.tok_s", "gc_pause_pct.tok_s")
NAMES = ("engine.schedule", "engine.idle", "engine.prefill_chunk",
         "engine.decode_quantum", "worker.entry", "engine.sync", "py.gc")


def _cpu_reduce(trace_dir: str, window_s: float) -> devtrace.Reduced:
    """``devtrace.reduce`` wants an accelerator plane; on the CPU the
    programs run on the host's own XLA threads, so their operations stand
    for the device's and every other host event is kept as it would be."""
    import jax
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    ops, host = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [e for e in devtrace._events(line)
                      if not e[0].startswith(devtrace._HOST_NOISE)]
            if line.name.startswith("tf_XLA"):
                ops += [e for e in events if not e[0].startswith("end: ")]
            else:
                host += events
    return devtrace.Reduced(window_s, ops, [], host)


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """A tiny paged ContinuousBatcher on the threads backend, its second
    round captured: five requests, a pause the loop idles through, one
    more, and a collection."""
    import time

    import jax
    import numpy as np
    from repro.cloud import Session
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.runtime.server import LMServer, Request
    from repro.serving import ContinuousBatcher

    cfg = get_smoke("smollm-360m")
    params, _ = build_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=[int(t) for t in rng.integers(1, cfg.vocab_size,
                                                         n)], max_new=m)
            for n, m in ((20, 12), (40, 9), (7, 16), (20, 5), (33, 12))]
    tdir = str(tmp_path_factory.mktemp("trace"))

    async def serve(b):
        await asyncio.gather(*[b.submit(r) for r in reqs])
        await asyncio.sleep(0.05)
        await b.submit(reqs[0])

    with Session("threads", os_threads=2) as sess:
        server = LMServer(cfg, params, session=sess, max_new=16)

        async def main():
            async with ContinuousBatcher(server, max_batch=3, slots=1,
                                         quantum=4, paged=True,
                                         block_size=4, prefill_budget=8,
                                         arena_cap=64) as b:
                await serve(b)
                jax.profiler.start_trace(tdir)
                t0 = time.perf_counter()
                await serve(b)
                gc.collect()
                t1 = time.perf_counter()
                jax.profiler.stop_trace()
                return t1 - t0

        window = asyncio.run(main())
        server.close(prune=False)
    return _cpu_reduce(tdir, window)


def _window(trace):
    cfg = json.loads((DATA / "tiny.json").read_text())
    return harness.Window(0.0, trace.window_s, trace, {}, [], cfg, {}, 0)


def test_every_span_reaches_the_trace(captured):
    found = {e[0] for e in spans.program(captured)}
    assert set(NAMES) <= found


def test_spans_nest_across_threads(captured):
    """The worker thread's entry lies inside the loop thread's decode
    quantum, and each device-to-host read inside an entry."""
    quanta = spans.named(captured, "engine.decode_quantum")
    entries = spans.named(captured, "worker.entry")
    assert quanta and all(spans.inside(q, entries) for q in quanta)
    for s in spans.named(captured, "engine.sync"):
        assert any(spans.inside(e, [s]) for e in entries)
    # no schedule span encloses an engine call
    calls = [e for e in spans.program(captured)
             if e[0] in spans.ENGINE_CALLS]
    for s in spans.named(captured, "engine.schedule"):
        assert not spans.inside(s, calls)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_a_finite_number(captured, name):
    value = harness.load_reader(name)(_window(captured))
    assert value is not None and math.isfinite(value) and value >= 0
    if name.split(".")[0].endswith("_pct"):
        assert value <= 100


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_spans(captured, name):
    """A program without spans, as before they existed: nothing to read,
    and nothing raised."""
    bare = devtrace.Reduced(captured.window_s, list(captured.ops), [],
                            [e for e in captured.host
                             if not e[0].startswith(spans.PROGRAM)])
    assert harness.load_reader(name)(_window(bare)) is None


def test_idle_time_goes_to_the_innermost_span():
    """Hand-made: a call (0-100) holds an entry (10-60) holding a read
    (40-55); the loop's schedule runs 100-120, a collection 105-108 on
    another thread; the device works 0-20, 50-52 and 110-130."""
    ms = 1_000_000
    host = [("engine.decode_quantum", 0, 100 * ms),
            ("worker.entry", 10 * ms, 50 * ms),
            ("engine.sync", 40 * ms, 15 * ms),
            ("engine.schedule", 100 * ms, 20 * ms),
            ("py.gc", 105 * ms, 3 * ms),
            ("np.asarray(jax.Array)", 41 * ms, 14 * ms)]
    ops = [("fusion.1", 0, 20 * ms), ("fusion.2", 50 * ms, 2 * ms),
           ("fusion.3", 110 * ms, 20 * ms)]
    idle = spans.idle_by_span(devtrace.Reduced(0.2, ops, [], host))
    # idle 20-50: entry 20-40, read 40-50; idle 52-110: read 52-55,
    # entry 55-60, call 60-100, schedule 100-105 and 108-110, collection
    # 105-108
    assert idle == pytest.approx({
        "worker.entry": 0.025, "engine.sync": 0.013,
        "engine.decode_quantum": 0.040, "engine.schedule": 0.007,
        "py.gc": 0.003, spans.NO_SPAN: 0.0})


def _excerpt(cell):
    doc = json.loads((DATA / "spans_excerpt.json").read_text())["cells"][cell]
    lo, hi = doc["stretch"]
    return devtrace.Reduced(
        (hi - lo) / 1e9, [tuple(o) for o in doc["ops"]],
        [tuple(m) for m in doc["modules"]],
        [tuple(h) for h in doc["host"]]), lo


def test_recorded_chat_gap_by_innermost_span():
    """smollm-360m.chat on the chip: the decode program ends 0.2 ms into
    the excerpt and the next program (a prefill chunk) starts at
    34.198971 ms.  By hand, in ms from the excerpt's start: the token read
    runs to 3.620432; its entry packs the reply to 4.603732; the hop back
    ends the quantum at 5.267412; the schedule runs 5.275242-5.409422; the
    chunk call opens at 5.416712 and its entry at 31.548782, with a
    collection 17.100942-17.364482 between (the entry also holds 16 ns of
    gaps between the chunk program's first operations)."""
    trace, lo = _excerpt("smollm-360m.chat")
    idle = {k: v * 1e3 for k, v in spans.idle_by_span(trace).items()}
    assert idle == pytest.approx({
        "engine.sync": 3.620432 - 0.2,
        "worker.entry": (4.603732 - 3.620432) + (34.198971 - 31.548782)
        + 0.000016,
        "engine.decode_quantum": 5.267412 - 4.603732,
        "engine.schedule": 5.409422 - 5.275242,
        "engine.prefill_chunk": (17.100942 - 5.416712)
        + (31.548782 - 17.364482),
        "py.gc": 17.364482 - 17.100942,
        spans.NO_SPAN: (5.275242 - 5.267412) + (5.416712 - 5.409422)},
        abs=1e-6)
    # the whole gap is one stretch, and the invocation layer holds most
    a, b = spans.idle_stretches(trace)
    assert (int(b[0]) - int(a[0])) / 1e6 == pytest.approx(
        34.198971 - 0.2, abs=1e-6)


def test_recorded_decode_gap_by_innermost_span():
    """qwen1.5-4b.decode on the chip: decode program to decode program,
    idle 0.2-7.30279 ms.  By hand: the read to 2.453866 (with a 2 ns gap
    inside it at 0.199643), the entry's reply to 2.835206, the hop back
    to 3.245796, the schedule 3.282706-3.401536, the next quantum's hop
    3.409206-4.238746, then its entry until the program starts."""
    trace, _ = _excerpt("qwen1.5-4b.decode")
    idle = {k: v * 1e3 for k, v in spans.idle_by_span(trace).items()}
    assert idle == pytest.approx({
        "engine.sync": 2.453866 - 0.2 + 0.000002,
        "worker.entry": (2.835206 - 2.453866) + (7.30279 - 4.238746),
        "engine.decode_quantum": (3.245796 - 2.835206)
        + (4.238746 - 3.409206),
        "engine.schedule": 3.401536 - 3.282706,
        spans.NO_SPAN: (3.282706 - 3.245796) + (3.409206 - 3.401536)},
        abs=1e-6)


def test_recorded_excerpt_through_the_readers():
    """The chat excerpt holds one whole decode quantum: the readers read
    it as a one-pass window."""
    trace, _ = _excerpt("smollm-360m.chat")
    win = _window(trace)
    q = spans.named(trace, "engine.decode_quantum")[0]
    (e, syncs), = [(c[1], c[2]) for c in spans.decode_quanta(trace)]
    assert harness.load_reader("dispatch_ms.tpot")(win) == pytest.approx(
        (q[2] - e[2]) / 1e6)
    assert harness.load_reader("engine_host_ms.tpot")(win) \
        == pytest.approx((e[2] - syncs[0][2]) / 1e6)
    assert harness.load_reader("idle_dispatch_pct.tok_s")(win) \
        == pytest.approx(100 * (0.66368 + 25.86853) / 1e3 / trace.window_s,
                         rel=1e-5)
