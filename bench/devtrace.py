"""Reduce a JAX profiler trace to what the per-layer metrics read.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.  Of
it this keeps, for the first accelerator plane, the device operations
(``XLA Ops``, named by their HLO instruction, ``copy.115``) and programs
(``XLA Modules``, named by the jitted function, ``jit_run``), and of the
host planes the events that name what the host was doing while the device
sat idle.  Busy
time is the union of the operations' intervals; an idle gap is a stretch
between two of them, named by the longest host event that covers most of
it.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# control-flow operations enclose the operations they run
_CONTAINERS = ("while", "conditional", "call")
# host events too generic to say what the host was doing
_HOST_NOISE = ("ThreadpoolListener", "ThunkExecutor", "$", "ExecuteOnDevice")


@dataclass
class Reduced:
    window_s: float
    ops: list                      # (name, start_ns, dur_ns), device ops
    modules: list                  # (name, start_ns, dur_ns), device programs
    host: list = field(default_factory=list)   # (name, start_ns, dur_ns)

    def __post_init__(self):
        self.ops.sort(key=lambda e: e[1])
        self.modules.sort(key=lambda e: e[1])
        self._busy = _union([(s, s + d) for _, s, d in self.ops])

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self._busy) / 1e9

    def op_time_s(self, prefix: str) -> float:
        return sum(d for n, _, d in self.ops if n.startswith(prefix)) / 1e9

    def op_count(self, prefix: str) -> int:
        return sum(1 for n, _, _ in self.ops if n.startswith(prefix))

    def modules_with(self, prefix: str) -> list:
        """Programs that ran at least one operation named ``prefix...``."""
        hits = sorted(s for n, s, _ in self.ops if n.startswith(prefix))
        out, i = [], 0
        for m in self.modules:
            _, s, d = m
            while i < len(hits) and hits[i] < s:
                i += 1
            if i < len(hits) and hits[i] < s + d:
                out.append(m)
        return out

    def gaps(self, n: int = 10) -> list:
        """(host event name, seconds) of the ``n`` longest idle stretches
        between device operations, longest first."""
        spans = sorted(((b - a, a, b) for (_, a), (b, _)
                        in zip(self._busy, self._busy[1:]) if b > a),
                       reverse=True)[:n]
        return [(self._host_at(a, b), d / 1e9) for d, a, b in spans]

    def _host_at(self, a: int, b: int) -> str:
        best, cover = "no_host_event", 0
        for n, s, d in self.host:
            c = min(b, s + d) - max(a, s)
            if c > cover or (c == cover and c > 0 and d < self._dur(best)):
                best, cover = n, c
        return best

    def _dur(self, name: str) -> int:
        return min((d for n, _, d in self.host if n == name), default=0)

    def breakdown(self) -> dict:
        """The ten operations that took most device time (control flow,
        which encloses others, left out) and the ten longest idle gaps."""
        per = defaultdict(int)
        for n, _, d in self.ops:
            if not n.startswith(_CONTAINERS):
                per[n] += d
        top = sorted(per.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, d / 1e9] for n, d in top],
                "idle_gaps": [[n, s] for n, s in self.gaps()]}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def op_name(text: str) -> str:
    """``%copy.115 = bf16[...] copy(...)`` -> ``copy.115``: the trace names
    an operation by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def module_name(text: str) -> str:
    """``jit_run(1534...)`` -> ``jit_run``: the program, not its build."""
    return text.split("(", 1)[0]


def _events(line, name=lambda n: n):
    return [(name(e.name), int(e.start_ns), int(e.duration_ns))
            for e in line.events]


def reduce(trace_dir: str, window_s: float | None = None) -> Reduced:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(files)}")
    data = jax.profiler.ProfileData.from_file(files[0])
    ops, modules, host = [], [], []
    device = None
    for plane in data.planes:
        if plane.name.startswith("/device:") and device is None \
                and "CPU" not in plane.name:
            device = plane.name
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line, op_name)
                elif line.name == MODULES_LINE:
                    modules = _events(line, module_name)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if not e[0].startswith(_HOST_NOISE)]
    if device is None or not ops:
        raise RuntimeError("the trace holds no device operations")
    if window_s is None:
        lo = min(s for _, s, _ in ops)
        hi = max(s + d for _, s, d in ops)
        window_s = (hi - lo) / 1e9
    return Reduced(window_s, ops, modules, host)
