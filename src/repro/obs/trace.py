"""Distributed spans for the serving stack — mint, propagate, collect, export.

A *trace* is one request's tree of timed spans across the client/worker
boundary; a :class:`SpanContext` (``trace_id``, ``span_id``) names a node
in it.  The context is minted client-side at dispatch (sampling decides
whether this request records at all), rides the wire envelope as an
additive header field, and worker-side spans come back attached to the
RESULT/ERROR envelope — no separate export channel, no clock sync beyond
both processes stamping wall-clock epoch seconds.

Hot-path contract: every instrumentation site first checks
``TRACER.enabled`` (one attribute load); with tracing off (the default)
nothing else runs and :attr:`Tracer.calls` stays 0 — the overhead guard
in ``tests/test_obs.py`` pins this.  Sampled-out traces cost one sampler
roll at the root and nothing per child (children of an unsampled root get
the no-op handle).

A second sink, the JAX profiler: a site opened with ``profile=True`` also
records as a ``jax.profiler.TraceAnnotation`` whenever a profiler session
is capturing, sampled or not, so the span lands on the device trace's
clock beside the device operations.  Off, that costs one ``is_enabled()`` call per site.
JAX is never imported here: the sink looks for ``jax.profiler`` among the
loaded modules, so ``repro.obs`` stays importable without JAX.

Export is Chrome-trace JSON (:func:`export_chrome` / :func:`dump_trace`):
load the file in ``chrome://tracing`` or Perfetto.  Span linkage
(``trace_id`` / ``span_id`` / ``parent_span_id``) rides in each event's
``args`` so tools — and CI — can rebuild the tree exactly.
"""
from __future__ import annotations

import gc
import json
import os
import random
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Iterable, Mapping

__all__ = ["Sampler", "Span", "SpanContext", "Tracer", "TRACER",
           "RemoteSpans", "bound", "configure", "current", "dump_trace",
           "enabled", "export_chrome"]


@dataclass(frozen=True)
class SpanContext:
    """The wire-portable name of one span: enough to parent children under
    it from any process.  ``t_start`` (epoch s) lets the receiving side
    derive queue-wait spans without carrying a separate timestamp."""
    trace_id: str
    span_id: str
    t_start: float = 0.0

    def to_wire(self) -> dict:
        return {"tid": self.trace_id, "sid": self.span_id,
                "t0": round(self.t_start, 6)}

    @classmethod
    def from_wire(cls, d: Mapping[str, Any] | None) -> "SpanContext | None":
        if not d or "tid" not in d or "sid" not in d:
            return None
        return cls(trace_id=str(d["tid"]), span_id=str(d["sid"]),
                   t_start=float(d.get("t0", 0.0)))


@dataclass
class Span:
    """One finished span (the ring buffer element)."""
    name: str
    trace_id: str
    span_id: str
    parent_id: str | None
    t_start: float                 # epoch seconds (cross-process timebase)
    dur_s: float
    pid: int
    proc: str                      # "client" | "worker"
    thread: str
    status: str = "ok"
    attrs: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "tid": self.trace_id,
                "sid": self.span_id, "parent": self.parent_id,
                "t0": self.t_start, "dur": self.dur_s, "pid": self.pid,
                "proc": self.proc, "thread": self.thread,
                "status": self.status, "attrs": self.attrs}

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Span":
        return cls(name=str(d.get("name", "?")),
                   trace_id=str(d.get("tid", "")),
                   span_id=str(d.get("sid", "")),
                   parent_id=d.get("parent"),
                   t_start=float(d.get("t0", 0.0)),
                   dur_s=float(d.get("dur", 0.0)),
                   pid=int(d.get("pid", 0)),
                   proc=str(d.get("proc", "client")),
                   thread=str(d.get("thread", "")),
                   status=str(d.get("status", "ok")),
                   attrs=dict(d.get("attrs", {})))


class Sampler:
    """Seeded head-based sampler: one roll per trace root.  Deterministic —
    two samplers with the same seed admit the same decision sequence
    (``tests/test_obs.py`` pins this), so a benchmark re-run traces the
    same requests."""

    def __init__(self, sample: float = 0.0, seed: int = 0):
        self.sample = float(sample)
        self.seed = seed
        self._rng = random.Random(seed)
        self._lock = threading.Lock()

    def decide(self) -> bool:
        if self.sample >= 1.0:
            return True
        if self.sample <= 0.0:
            return False
        with self._lock:
            return self._rng.random() < self.sample


class _NoopHandle:
    """The disabled/unsampled span: every operation is a no-op and the
    handle is falsy, so ``if sp:`` guards optional attribute work."""
    __slots__ = ()
    ctx = None

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set(self, *a, **kw) -> None:
        pass

    def finish(self, status: str = "ok") -> None:
        pass

    def __bool__(self) -> bool:
        return False


NOOP = _NoopHandle()


# ------------------------------------------------------- profiler sink --

_ANNOTATION = None      # jax.profiler.TraceAnnotation, once JAX is loaded


def _capturing():
    """The ``TraceAnnotation`` class while a JAX profiler session is
    capturing, else None.  Only looks JAX up among the loaded modules (a
    module still importing has no ``TraceAnnotation`` yet)."""
    global _ANNOTATION
    ann = _ANNOTATION
    if ann is None:
        ann = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                      None)
        if ann is None:
            return None
        _ANNOTATION = ann
    return ann if ann.is_enabled() else None


def _open(annotation, name: str, attrs: dict):
    if annotation is None:
        return None
    ann = annotation(name, **attrs)
    ann.__enter__()
    return ann


def _close(ann, status: str) -> None:
    if ann is not None:
        if status != "ok":
            ann.set_metadata(status=status)
        ann.__exit__(None, None, None)


class _ProfiledHandle:
    """A span that reaches the profiler only (tracing off, no sampled
    parent, or ``py.gc``): falsy like NOOP, so nothing parents under it in
    the ring.  Finish it on the thread that opened it."""
    __slots__ = ("_ann",)
    ctx = None

    def __init__(self, annotation, name: str, attrs: dict):
        self._ann = _open(annotation, name, attrs)

    def __enter__(self):
        return self

    def __exit__(self, etype, err, tb) -> None:
        if etype is not None:
            self.set("error.type", etype.__name__)
        self.finish("ok" if etype is None else "error")

    def set(self, key: str, value) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**{key: value})

    def finish(self, status: str = "ok") -> None:
        ann, self._ann = self._ann, None
        _close(ann, status)

    def __bool__(self) -> bool:
        return False


def _profiled(name: str, **attrs):
    """A span for the profiler sink alone: a ``TraceAnnotation`` while a
    profiler session is capturing, else :data:`NOOP`."""
    ann = _capturing()
    return NOOP if ann is None else _ProfiledHandle(ann, name, attrs)


class _SpanHandle:
    """A live span: context manager or manually ``finish()``-ed (exactly
    once).  ``set`` adds attributes; an exception leaving the ``with``
    marks status=error and records the exception type/message."""

    __slots__ = ("_sink", "name", "ctx", "parent_id", "_t0_perf", "attrs",
                 "_proc", "_done", "_ann")

    def __init__(self, sink, name: str, ctx: SpanContext,
                 parent_id: str | None, proc: str, attrs: dict,
                 annotation=None):
        self._sink = sink
        self.name = name
        self.ctx = ctx
        self.parent_id = parent_id
        self.attrs = attrs
        self._proc = proc
        self._ann = _open(annotation, name, attrs)
        self._t0_perf = time.perf_counter()
        self._done = False

    def set(self, key: str, value) -> None:
        self.attrs[key] = value
        if self._ann is not None:
            self._ann.set_metadata(**{key: value})

    def finish(self, status: str = "ok") -> None:
        if self._done:
            return
        self._done = True
        _close(self._ann, status)
        self._sink(Span(
            name=self.name, trace_id=self.ctx.trace_id,
            span_id=self.ctx.span_id, parent_id=self.parent_id,
            t_start=self.ctx.t_start,
            dur_s=time.perf_counter() - self._t0_perf,
            pid=os.getpid(), proc=self._proc,
            thread=threading.current_thread().name,
            status=status, attrs=self.attrs))

    def __enter__(self) -> "_SpanHandle":
        return self

    def __exit__(self, etype, err, tb) -> None:
        if etype is not None:
            self.attrs.setdefault("error.type", etype.__name__)
            self.attrs.setdefault("error.message", str(err))
            self.finish("error")
        else:
            self.finish()

    def __bool__(self) -> bool:
        return True


_ids = random.Random()          # span/trace id minting (uniqueness only)
_id_lock = threading.Lock()


def _new_id(bits: int = 64) -> str:
    with _id_lock:
        return f"{_ids.getrandbits(bits):0{bits // 4}x}"


class Tracer:
    """Span factory + in-memory ring-buffer collector.

    ``enabled`` is the hard off-switch; ``sampler`` decides per trace
    root.  ``calls`` counts real instrumentation engagements (handles
    created / spans ingested) — the disabled-overhead guard asserts it
    stays 0 with tracing off.
    """

    def __init__(self, *, enabled: bool = False, sample: float = 0.0,
                 seed: int = 0, ring: int = 65536, proc: str = "client"):
        self.enabled = bool(enabled)
        self.sampler = Sampler(sample, seed)
        self.proc = proc
        self.calls = 0
        self._lock = threading.Lock()
        self._ring: deque[Span] = deque(maxlen=max(1, ring))
        self._local = threading.local()

    # ----------------------------------------------------------- configure
    def configure(self, *, enabled: bool | None = None,
                  sample: float | None = None, seed: int | None = None,
                  ring: int | None = None) -> None:
        if sample is not None or seed is not None:
            self.sampler = Sampler(
                self.sampler.sample if sample is None else sample,
                self.sampler.seed if seed is None else seed)
        if enabled is not None:
            self.enabled = bool(enabled)
        elif sample is not None:
            # setting a positive sample IS the opt-in; sample=0 hard-disables
            self.enabled = sample > 0.0
        if ring is not None:
            with self._lock:
                self._ring = deque(self._ring, maxlen=max(1, ring))

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()
        self.calls = 0

    # ----------------------------------------------------- context plumbing
    def current(self) -> SpanContext | None:
        return getattr(self._local, "ctx", None)

    def set_current(self, ctx: SpanContext | None):
        prev = getattr(self._local, "ctx", None)
        self._local.ctx = ctx
        return prev

    # ------------------------------------------------------------ spanning
    def start_trace(self, name: str, **attrs):
        """Mint a trace root — the sampling decision happens here; children
        of an unsampled root are no-ops all the way down."""
        if not self.enabled or not self.sampler.decide():
            return NOOP
        self.calls += 1
        ctx = SpanContext(_new_id(64), _new_id(64), time.time())
        return _SpanHandle(self._record, name, ctx, None, self.proc, attrs)

    def span(self, name: str, parent: SpanContext | None = None, *,
             profile: bool = False, **attrs):
        """A child span under ``parent`` (or the thread's current context).
        No parent → no ring span: orphan spans cannot stitch into any tree.
        ``profile=True`` also records it in a capturing JAX profiler
        session, whether or not the ring records it."""
        if not self.enabled:
            return _profiled(name, **attrs) if profile else NOOP
        if parent is None:
            parent = self.current()
            if parent is None:
                return _profiled(name, **attrs) if profile else NOOP
        self.calls += 1
        ctx = SpanContext(parent.trace_id, _new_id(64), time.time())
        return _SpanHandle(self._record, name, ctx, parent.span_id,
                           self.proc, attrs,
                           _capturing() if profile else None)

    def span_at(self, name: str, parent: SpanContext, t_start: float,
                dur_s: float, status: str = "ok", **attrs) -> None:
        """Record an already-elapsed interval (e.g. queue wait derived from
        the context's mint time) as a finished span."""
        if not self.enabled:
            return
        self.calls += 1
        self._record(Span(
            name=name, trace_id=parent.trace_id, span_id=_new_id(64),
            parent_id=parent.span_id, t_start=t_start, dur_s=dur_s,
            pid=os.getpid(), proc=self.proc,
            thread=threading.current_thread().name, status=status,
            attrs=attrs))

    # ------------------------------------------------------------- collect
    def _record(self, span: Span) -> None:
        with self._lock:
            self._ring.append(span)

    def ingest(self, span_dicts: Iterable[Mapping]) -> None:
        """Adopt spans another process recorded (worker spans riding the
        RESULT envelope) into this collector's ring."""
        if not self.enabled or not span_dicts:
            return
        self.calls += 1
        with self._lock:
            for d in span_dicts:
                try:
                    self._ring.append(Span.from_dict(d))
                except (TypeError, ValueError):
                    continue           # a malformed span must not kill a reply

    def spans(self) -> list[Span]:
        with self._lock:
            return list(self._ring)

    # -------------------------------------------------------------- export
    def export_chrome(self) -> dict:
        return export_chrome(self.spans())

    def dump(self, path: str) -> int:
        """Write Chrome-trace JSON; returns the number of events written."""
        doc = self.export_chrome()
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        return len(doc["traceEvents"])


class RemoteSpans:
    """Worker-side span batch for ONE request.

    The worker records spans only when the incoming envelope carries a
    trace context (the client already made the sampling decision), and the
    finished spans ship back on the reply envelope — the worker keeps
    nothing.  ``span(name)`` parents under the client's context by
    default; pass ``parent=`` (a handle's ``.ctx``) to nest deeper.
    """

    def __init__(self, wire_ctx: Mapping[str, Any] | None,
                 proc: str = "worker"):
        self.ctx = SpanContext.from_wire(wire_ctx)
        self.proc = proc
        self._spans: list[Span] = []

    def __bool__(self) -> bool:
        return self.ctx is not None

    def span(self, name: str, parent: SpanContext | None = None, **attrs):
        if self.ctx is None:
            return NOOP
        parent = parent or self.ctx
        ctx = SpanContext(parent.trace_id, _new_id(64), time.time())
        return _SpanHandle(self._spans.append, name, ctx, parent.span_id,
                           self.proc, attrs)

    def span_at(self, name: str, t_start: float, dur_s: float,
                **attrs) -> None:
        if self.ctx is None:
            return
        self._spans.append(Span(
            name=name, trace_id=self.ctx.trace_id, span_id=_new_id(64),
            parent_id=self.ctx.span_id, t_start=t_start, dur_s=dur_s,
            pid=os.getpid(), proc=self.proc,
            thread=threading.current_thread().name, attrs=attrs))

    def dicts(self) -> list[dict]:
        return [s.to_dict() for s in self._spans]


# ----------------------------------------------------------------- export --

def export_chrome(spans: Iterable[Span]) -> dict:
    """Chrome-trace/Perfetto JSON: complete ('X') events, microsecond
    timestamps on the shared epoch timebase; span linkage in ``args``."""
    events = []
    for s in spans:
        events.append({
            "name": s.name, "cat": s.proc, "ph": "X",
            "ts": s.t_start * 1e6, "dur": max(0.0, s.dur_s) * 1e6,
            "pid": s.pid, "tid": abs(hash(s.thread)) % (1 << 31),
            "args": {"trace_id": s.trace_id, "span_id": s.span_id,
                     "parent_span_id": s.parent_id, "proc": s.proc,
                     "thread": s.thread, "status": s.status, **s.attrs}})
    return {"displayTimeUnit": "ms", "traceEvents": events}


# ------------------------------------------------------- module-level API --

#: the process tracer — sessions configure it, exporters read it
TRACER = Tracer()


def configure(**kwargs) -> None:
    """``obs.configure(sample=1.0)`` / ``obs.configure(enabled=False)`` —
    see :meth:`Tracer.configure`."""
    TRACER.configure(**kwargs)


def enabled() -> bool:
    return TRACER.enabled


def current() -> SpanContext | None:
    return TRACER.current()


def dump_trace(path: str) -> int:
    return TRACER.dump(path)


# ------------------------------------------------------------ gc pauses --

_gc_span = NOOP


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: each collection becomes a ``py.gc`` span on
    the thread it interrupted (generation and objects collected as
    attributes).  It reaches the profiler sink alone and takes no Python
    lock: a collection can interrupt a thread that holds the tracer's own
    locks.  Returns at once when no profiler session is capturing."""
    global _gc_span
    if phase == "start":
        _gc_span = _profiled("py.gc", generation=info["generation"])
    elif _gc_span is not NOOP:
        sp, _gc_span = _gc_span, NOOP
        sp.set("collected", info["collected"])
        sp.finish()


gc.callbacks.append(_on_gc)           # once per process: at first import


class bound:
    """Bind a span context to a callable for cross-thread propagation:
    ``executor.submit(bound(ctx, fn), *args)`` makes ``fn`` (and anything
    it dispatches) parent under ``ctx`` even on another thread."""

    __slots__ = ("ctx", "fn")

    def __init__(self, ctx: SpanContext | None, fn):
        self.ctx = ctx
        self.fn = fn

    def __call__(self, *args, **kwargs):
        if self.ctx is None:
            return self.fn(*args, **kwargs)
        prev = TRACER.set_current(self.ctx)
        try:
            return self.fn(*args, **kwargs)
        finally:
            TRACER.set_current(prev)
