"""``repro.obs`` — request tracing and the metrics plane (ISSUE 8).

Two small, dependency-free subsystems that make every hop of an
invocation observable:

* :mod:`repro.obs.trace` — distributed spans.  A ``(trace_id, span_id)``
  context is minted client-side at dispatch, rides the wire envelope as
  an additive header field (old workers ignore it), and worker-side spans
  ship back on the RESULT/ERROR envelope so one request's client spans
  (submit → queue → transport) and worker spans (decode, cold compile,
  entry) stitch into a single tree.  Export is Chrome-trace JSON
  (``chrome://tracing`` / Perfetto) via :func:`dump_trace`.
* :mod:`repro.obs.metrics` — process-local counters / gauges /
  fixed-bucket histograms with Prometheus text exposition.  These replace
  the ad-hoc stats dicts that used to live in ``runtime/sandbox.py`` and
  are aggregated worker→client over the existing ``host_stats`` CONTROL
  verb (see ``Session.stats()['metrics']``).

The tracing hot path honors a hard off-switch: with tracing disabled
(the default — ``sample=0``), every instrumentation site is one
attribute load and a falsy check, plus one ``is_enabled()`` call at the
sites that also feed a capturing JAX profiler session (the engine loop's
phases, ``worker.entry``, ``engine.sync``, ``py.gc``), and the tracer's
``calls`` counter stays at zero (guarded by ``tests/test_obs.py``).
Metrics are always on — they are the same counters the sandbox host
always kept, just uniform.
"""
from __future__ import annotations

from . import metrics, trace
from .metrics import REGISTRY, Counter, Gauge, Histogram, Registry
from .trace import (RemoteSpans, Sampler, Span, SpanContext, Tracer,
                    TRACER, bound, configure, current, dump_trace, enabled,
                    export_chrome)

__all__ = [
    "metrics", "trace",
    "REGISTRY", "Counter", "Gauge", "Histogram", "Registry",
    "RemoteSpans", "Sampler", "Span", "SpanContext", "Tracer", "TRACER",
    "bound", "configure", "current", "dump_trace", "enabled",
    "export_chrome",
]
