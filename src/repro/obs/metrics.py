"""Process-local metrics registry: counters, gauges, fixed-bucket histograms.

One :class:`Registry` holds named metrics; every metric supports optional
labels (``counter.inc(function="f")``).  The design goals, in order:

* **lock-cheap** — one ``threading.Lock`` per metric, taken only around a
  dict/list increment; no global lock on the hot path;
* **mergeable** — :meth:`Registry.snapshot` produces a plain-JSON dict and
  :meth:`Registry.merge` folds another process's snapshot in (counters and
  histogram series sum, gauges sum — across workers a summed gauge is the
  fleet total).  This is how worker metrics travel to the client over the
  existing ``host_stats`` CONTROL verb without a new wire kind;
* **renderable** — :func:`render` emits Prometheus text exposition
  (``GET /metrics`` on the http worker host serves it).

The module-level :data:`REGISTRY` is the process default (transport and
scheduler metrics); components that need per-instance scoping (one
``SandboxHost`` per backend/test) own a private ``Registry`` and surface
it through their ``stats()``.
"""
from __future__ import annotations

import bisect
import threading
from typing import Iterable, Mapping

# fixed default buckets: milliseconds-flavored, covering sub-ms transport
# hops up to multi-second cold compiles
DEFAULT_BUCKETS_MS = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0,
                      500.0, 1000.0, 2000.0, 5000.0, 10000.0)


def _label_key(labels: Mapping[str, object]) -> str:
    """Canonical label encoding — doubles as the Prometheus label body."""
    if not labels:
        return ""
    return ",".join(f'{k}="{_escape(str(v))}"'
                    for k, v in sorted(labels.items()))


def _escape(v: str) -> str:
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()


class Counter(_Metric):
    """Monotonic counter, optionally labeled."""
    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._values: dict[str, float] = {}

    def inc(self, amount: float = 1.0, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    @property
    def total(self) -> float:
        with self._lock:
            return sum(self._values.values())

    def snapshot(self) -> dict:
        with self._lock:
            return {"type": self.kind, "help": self.help,
                    "values": dict(self._values)}


class Gauge(Counter):
    """Settable value (queue depths, live instances).  ``merge`` sums
    gauges across snapshots — the fleet-wide total of a per-worker gauge."""
    kind = "gauge"

    def set(self, value: float, **labels) -> None:
        with self._lock:
            self._values[_label_key(labels)] = float(value)

    def dec(self, amount: float = 1.0, **labels) -> None:
        self.inc(-amount, **labels)


class Histogram(_Metric):
    """Fixed-bucket histogram: cumulative bucket counts + sum + count,
    Prometheus-shaped.  Bucket bounds are frozen at construction, so two
    processes' series always merge bucket-for-bucket."""
    kind = "histogram"

    def __init__(self, name: str, help: str = "",
                 buckets: Iterable[float] = DEFAULT_BUCKETS_MS):
        super().__init__(name, help)
        self.buckets = tuple(sorted(float(b) for b in buckets))
        if not self.buckets:
            raise ValueError("a histogram needs at least one bucket bound")
        # per label-set: [per-bucket counts..., overflow], sum, count
        self._series: dict[str, dict] = {}

    def _slot(self, key: str) -> dict:
        s = self._series.get(key)
        if s is None:
            s = self._series[key] = {"counts": [0] * (len(self.buckets) + 1),
                                     "sum": 0.0, "count": 0}
        return s

    def observe(self, value: float, **labels) -> None:
        idx = bisect.bisect_left(self.buckets, value)
        key = _label_key(labels)
        with self._lock:
            s = self._slot(key)
            s["counts"][idx] += 1
            s["sum"] += value
            s["count"] += 1

    def series(self, **labels) -> dict:
        with self._lock:
            s = self._slot(_label_key(labels))
            return {"counts": list(s["counts"]), "sum": s["sum"],
                    "count": s["count"]}

    def cumulative(self, **labels) -> list[int]:
        """Per-bound cumulative counts (… plus the +Inf total last)."""
        s = self.series(**labels)
        out, acc = [], 0
        for c in s["counts"]:
            acc += c
            out.append(acc)
        return out

    def snapshot(self) -> dict:
        with self._lock:
            return {"type": self.kind, "help": self.help,
                    "buckets": list(self.buckets),
                    "series": {k: {"counts": list(s["counts"]),
                                   "sum": s["sum"], "count": s["count"]}
                               for k, s in self._series.items()}}


class Registry:
    """Named metrics, get-or-create: calling ``registry.counter(name)``
    twice returns the same object (modules register at import or first
    use without coordination)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: dict[str, _Metric] = {}

    def _get(self, cls, name: str, help: str, **kwargs) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help, **kwargs)
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} already registered as "
                                f"{m.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)      # type: ignore[return-value]

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)        # type: ignore[return-value]

    def histogram(self, name: str, help: str = "",
                  buckets: Iterable[float] = DEFAULT_BUCKETS_MS) -> Histogram:
        return self._get(Histogram, name, help,    # type: ignore[return-value]
                         buckets=buckets)

    def get(self, name: str) -> _Metric | None:
        with self._lock:
            return self._metrics.get(name)

    # ----------------------------------------------------------- aggregate
    def snapshot(self) -> dict:
        """Plain-JSON view of every metric — what rides ``host_stats``."""
        with self._lock:
            metrics = list(self._metrics.values())
        return {m.name: m.snapshot() for m in metrics}

    def merge(self, snap: Mapping[str, Mapping] | None) -> None:
        """Fold another process's :meth:`snapshot` into this registry —
        counters/gauges/histogram series sum elementwise.  Unknown metric
        names are created; bucket-bound mismatches skip that metric rather
        than corrupt the series."""
        if not snap:
            return
        for name, m in snap.items():
            kind = m.get("type")
            if kind == "counter" or kind == "gauge":
                cls = Gauge if kind == "gauge" else Counter
                dst = self._get(cls, name, m.get("help", ""))
                with dst._lock:
                    for key, v in m.get("values", {}).items():
                        dst._values[key] = dst._values.get(key, 0.0) + v
            elif kind == "histogram":
                buckets = tuple(float(b) for b in m.get("buckets", ()))
                try:
                    dst = self._get(Histogram, name, m.get("help", ""),
                                    buckets=buckets or DEFAULT_BUCKETS_MS)
                except TypeError:
                    continue
                if dst.buckets != buckets:
                    continue
                with dst._lock:
                    for key, s in m.get("series", {}).items():
                        d = dst._slot(key)
                        counts = s.get("counts", [])
                        if len(counts) != len(d["counts"]):
                            continue
                        d["counts"] = [a + b
                                       for a, b in zip(d["counts"], counts)]
                        d["sum"] += s.get("sum", 0.0)
                        d["count"] += s.get("count", 0)

    def render(self) -> str:
        return render_snapshot(self.snapshot())


def render(registries: Iterable[Registry]) -> str:
    """Prometheus text exposition over several registries merged (the http
    worker serves its sandbox host's registry plus the process default)."""
    merged = Registry()
    for r in registries:
        merged.merge(r.snapshot())
    return merged.render()


def render_snapshot(snap: Mapping[str, Mapping]) -> str:
    """Prometheus text exposition (version 0.0.4) from a snapshot dict."""
    lines: list[str] = []
    for name in sorted(snap):
        m = snap[name]
        kind = m.get("type", "untyped")
        if m.get("help"):
            lines.append(f"# HELP {name} {m['help']}")
        lines.append(f"# TYPE {name} {kind}")
        if kind in ("counter", "gauge"):
            values = m.get("values", {}) or {"": 0.0}
            for key in sorted(values):
                label = f"{{{key}}}" if key else ""
                lines.append(f"{name}{label} {_fmt(values[key])}")
        elif kind == "histogram":
            bounds = m.get("buckets", [])
            series = m.get("series", {}) or {"": {"counts": [0] * (
                len(bounds) + 1), "sum": 0.0, "count": 0}}
            for key in sorted(series):
                s = series[key]
                acc = 0
                for bound, c in zip(list(bounds) + ["+Inf"], s["counts"]):
                    acc += c
                    le = bound if bound == "+Inf" else _fmt(bound)
                    label = f'{key},le="{le}"' if key else f'le="{le}"'
                    lines.append(f"{name}_bucket{{{label}}} {acc}")
                label = f"{{{key}}}" if key else ""
                lines.append(f"{name}_sum{label} {_fmt(s['sum'])}")
                lines.append(f"{name}_count{label} {s['count']}")
    return "\n".join(lines) + "\n"


def _fmt(v: float) -> str:
    f = float(v)
    return str(int(f)) if f == int(f) else repr(f)


#: process-default registry — transport, scheduler, and worker-host metrics
REGISTRY = Registry()
