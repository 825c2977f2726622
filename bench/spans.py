"""The program's own spans in a reduced device trace.

The serving stack opens a span at each host phase of an ``EngineLoop``
pass (``repro.obs.trace``: ``engine.schedule``, the engine calls,
``worker.entry``, ``engine.sync``, ``engine.idle``, ``py.gc``); while the
JAX profiler captures, each one is a host event on the device trace's
clock.  ``devtrace.reduce`` keeps its name, start and duration, not its
thread, so spans pair by containment across threads.  Device idle time is
given, instant by instant, to the innermost program span: the
latest-started one still open on any thread.  A trace of a program
without these spans has none of them, and every reader built on this
module then reads nothing.
"""
from __future__ import annotations

import numpy as np

# the program's span names start so; JAX's own host events do not
PROGRAM = ("engine.", "worker.", "py.")
# one engine call each, from before its dispatch to after its reply is
# folded into the loop's mirrors
ENGINE_CALLS = ("engine.prefill", "engine.prefill_chunk",
                "engine.decode_quantum", "engine.migrate_in",
                "engine.migrate_out")
NO_SPAN = "no_span"


def program(trace) -> list:
    """(name, start_ns, dur_ns) of every program span, by start."""
    return sorted((e for e in trace.host if e[0].startswith(PROGRAM)),
                  key=lambda e: e[1])


def named(trace, name: str) -> list:
    return [e for e in program(trace) if e[0] == name]


def inside(outer: tuple, spans: list) -> list:
    """The spans of ``spans`` that lie within ``outer``."""
    _, s, d = outer
    return [e for e in spans if e[1] >= s and e[1] + e[2] <= s + d]


def seconds(spans: list) -> float:
    return sum(d for _, _, d in spans) / 1e9


def decode_quanta(trace) -> list:
    """Per decode quantum: (the quantum's span, the worker entry that ran
    it, the device-to-host reads inside that entry).  The entry is the
    longest ``worker.entry`` inside the quantum; a quantum without one is
    left out."""
    entries = named(trace, "worker.entry")
    syncs = named(trace, "engine.sync")
    out = []
    for q in named(trace, "engine.decode_quantum"):
        mine = inside(q, entries)
        if mine:
            e = max(mine, key=lambda x: x[2])
            out.append((q, e, inside(e, syncs)))
    return out


def idle_stretches(trace):
    """Start and end (ns) of each stretch between device operations in
    which no operation ran."""
    if not trace.ops:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    s = np.fromiter((e[1] for e in trace.ops), np.int64, len(trace.ops))
    d = np.fromiter((e[2] for e in trace.ops), np.int64, len(trace.ops))
    order = np.argsort(s, kind="stable")
    s, ends = s[order], np.maximum.accumulate((s + d)[order])
    gap = s[1:] > ends[:-1]
    return ends[:-1][gap], s[1:][gap]


def idle_by_span(trace) -> dict:
    """Device idle time (s) between the first and the last device
    operation, by the innermost program span open over it; ``no_span``
    holds the idle time that no program span covers."""
    a, b = idle_stretches(trace)
    cum = np.concatenate(([0], np.cumsum(b - a)))

    def idle_before(t: int) -> int:
        i = int(np.searchsorted(a, t, side="right")) - 1
        if i < 0:
            return 0
        return int(cum[i]) + min(t, int(b[i])) - int(a[i])

    spans = program(trace)
    bounds = sorted({t for _, s, d in spans for t in (s, s + d)})
    out: dict = {}
    open_: list = []              # (start, end, name), latest start last
    k = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while k < len(spans) and spans[k][1] <= t0:
            n, s, d = spans[k]
            open_.append((s, s + d, n))
            k += 1
        open_ = [x for x in open_ if x[1] > t0]
        idle = idle_before(t1) - idle_before(t0)
        if idle and open_:
            name = max(open_, key=lambda x: x[0])[2]
            out[name] = out.get(name, 0) + idle
    total = int(cum[-1])
    named_ns = sum(out.values())
    res = {n: v / 1e9 for n, v in out.items()}
    res[NO_SPAN] = (total - named_ns) / 1e9
    return res
