"""Sandbox lifecycle — the transport-agnostic half of every worker runtime.

A FaaS *sandbox* is an execution slot with state the platform (not the
task) manages: it is provisioned cold, reused warm per function, billed per
invocation, and may be lost at any time.  This module owns exactly that
bookkeeping — cold/warm accounting, elastic drain, deterministic fault
injection — around an opaque entry callable
``entry(payload: bytes) -> (bytes, stats)``.

It deliberately knows nothing about *where* the entry runs: the in-process
backends hand it ``Bridge.entry`` directly, the ``processes``/``http``
transports hand it a proxy that ships the payload across a pipe or socket,
and the worker-side :class:`~repro.runtime.worker_host.WorkerHost` uses the
same host to account for the sandboxes living inside one worker process.
That single seam is what makes backends swappable above it
(``dispatch.backends``) and transports swappable below it.
"""
from __future__ import annotations

import random
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Tuple

from ..obs import metrics as obs_metrics


class WorkerCrash(RuntimeError):
    """Sandbox failure (node loss / worker death) — retried by the dispatcher."""


@dataclass
class WorkerInstance:
    worker_id: int
    function_name: str
    invocations: int = 0
    created_at: float = field(default_factory=time.time)
    busy_s: float = 0.0                # real wall time spent inside entries

    @property
    def is_cold(self) -> bool:
        return self.invocations == 0


@dataclass
class FaultPlan:
    """Deterministic fault/straggler injection for tests and benchmarks."""
    failure_rate: float = 0.0          # P(sandbox crash) per invocation
    straggler_rate: float = 0.0        # P(task straggles)
    straggler_factor: float = 8.0      # straggler duration multiplier
    straggler_sleep_s: float = 0.0     # real extra sleep for stragglers
    seed: int = 0

    def roll(self, task_id: int, attempt: int) -> tuple[bool, bool]:
        rng = random.Random(self.seed * 1_000_003 + task_id * 1009 + attempt)
        fail = rng.random() < self.failure_rate
        straggle = rng.random() < self.straggler_rate
        return fail, straggle


@dataclass(frozen=True)
class ChaosEvent:
    """One injected failure: fire ``kind`` on the ``after``-th invocation
    routed to worker slot ``slot`` (counted from :meth:`ChaosPlan.arm`)."""
    kind: str                          # "kill" | "stall" | "drop" | "expire"
    slot: int                          # worker slot index the event targets
    after: int = 3                     # fire on the Nth armed invoke there
    stall_s: float = 0.0               # client-side stall duration ("stall")

    KINDS = ("kill", "stall", "drop", "expire")

    def __post_init__(self):
        if self.kind not in self.KINDS:
            raise ValueError(f"unknown chaos kind {self.kind!r} "
                             f"(one of {self.KINDS})")


class ChaosPlan:
    """Seeded, deterministic cross-process fault injection (ISSUE 10).

    Where :class:`FaultPlan` simulates sandbox loss *inside* the executing
    process, a ChaosPlan is executed for real by the transport client
    against live worker subprocesses: ``kill`` SIGKILLs the slot's worker
    mid-decode, ``drop`` injects a connection loss (exercising the
    ConnectionError→WorkerCrash normalization), ``stall`` sleeps the
    dispatch thread long enough to threaten a state lease (the heartbeat's
    reason to exist), and ``expire`` force-expires the worker's state
    leases via the CONTROL ``chaos`` verb.  Every event is pinned to a
    (slot, Nth-invoke) coordinate, so a given seed replays the identical
    failure schedule run after run.

    The plan starts DISARMED so warmup traffic doesn't consume the invoke
    budget; ``arm()`` resets the counters and starts counting.  Everything
    that fires (and every respawn the transport observes afterwards) is
    appended to a thread-safe event log — ``log()`` is the evidence the
    chaos bench and CI asserts read.
    """

    def __init__(self, events: list[ChaosEvent] | tuple[ChaosEvent, ...] = (),
                 *, seed: int = 0):
        self.events = tuple(events)
        self.seed = seed
        self._lock = threading.Lock()
        self._counts: dict[int, int] = {}
        self._fired: set[int] = set()
        self._log: list[dict] = []
        self._armed = False
        self._t0 = time.monotonic()

    @classmethod
    def kill_member(cls, *, seed: int = 0, n_slots: int = 2,
                    after: int | None = None) -> "ChaosPlan":
        """The canonical chaos schedule: SIGKILL one fleet member's worker
        mid-decode.  Slot and firing point derive from the seed alone, so
        ``--chaos kill-member --seed 7`` is one reproducible failure."""
        rng = random.Random(seed * 1_000_003 + 17)
        slot = rng.randrange(max(1, n_slots))
        if after is None:
            after = 3 + rng.randrange(3)       # past prefill, into decode
        return cls([ChaosEvent("kill", slot=slot, after=after)], seed=seed)

    @property
    def armed(self) -> bool:
        return self._armed

    def arm(self) -> None:
        """Start counting invocations (reset counters; keep the log)."""
        with self._lock:
            self._armed = True
            self._counts.clear()
            self._fired.clear()

    def on_invoke(self, slot: int) -> list[ChaosEvent]:
        """Advance the slot's invoke counter; return events due NOW."""
        if not self._armed:
            return []
        with self._lock:
            n = self._counts.get(slot, 0) + 1
            self._counts[slot] = n
            due = []
            for i, ev in enumerate(self.events):
                if i not in self._fired and ev.slot == slot and ev.after == n:
                    self._fired.add(i)
                    due.append(ev)
            return due

    def record(self, action: str, *, slot: int | None = None,
               **extra) -> None:
        """Append one event to the chaos log (``worker.killed``,
        ``worker.respawned``, ``conn.dropped``, ``lease.expired``, ...)."""
        entry = {"t": round(time.monotonic() - self._t0, 6),
                 "action": action}
        if slot is not None:
            entry["slot"] = slot
        entry.update(extra)
        with self._lock:
            self._log.append(entry)

    def log(self) -> list[dict]:
        with self._lock:
            return [dict(e) for e in self._log]

    def counts(self) -> dict[str, int]:
        """Per-action tallies of the log — the cheap CI assertion surface."""
        out: dict[str, int] = {}
        for e in self.log():
            out[e["action"]] = out.get(e["action"], 0) + 1
        return out


@dataclass
class SandboxInvocation:
    """What one trip through a sandbox produced (feeds InvocationRecord)."""
    blob: bytes
    stats: Any                         # EntryStats-shaped (attribute access)
    worker_id: int
    cold_start: bool
    server_s: float


class SandboxHost:
    """Cold/warm sandbox pool + fault injection around entry callables.

    Thread-safe; one host stands in for one fleet (client side) or for the
    sandboxes inside one worker process (worker side).  ``worker_id_base``
    keeps ids globally unique when several processes each run a host.
    """

    def __init__(self, fault_plan: FaultPlan | None = None, *,
                 worker_id_base: int = 0):
        self.fault_plan = fault_plan or FaultPlan()
        self._warm: dict[str, list[WorkerInstance]] = {}
        self._next_worker_id = worker_id_base
        self._live_instances = 0
        self._lock = threading.Lock()
        # fleet observability: cold/warm and busy-time accounting lives in
        # a PRIVATE metrics registry (several hosts per process in tests),
        # labeled by function — this registry replaced the ad-hoc
        # _cold_starts/_warm_hits/_busy_s/_per_fn dicts that used to live
        # here.  stats() keeps the legacy shape; the worker host's
        # /metrics and host_stats serve the registry directly.
        self.metrics = obs_metrics.Registry()
        self._m_cold = self.metrics.counter(
            "sandbox_cold_starts_total", "sandboxes provisioned cold")
        self._m_warm = self.metrics.counter(
            "sandbox_warm_hits_total", "invocations served by a warm sandbox")
        self._m_busy = self.metrics.counter(
            "entry_busy_seconds_total", "wall time inside entry callables")
        self._fn_names: set[str] = set()

    # ----------------------------------------------------------- lifecycle
    def acquire(self, function_name: str) -> Tuple[WorkerInstance, bool]:
        """A sandbox for one invocation: warm if available, else cold."""
        with self._lock:
            self._fn_names.add(function_name)
            warm = self._warm.setdefault(function_name, [])
            if warm:
                self._m_warm.inc(function=function_name)
                return warm.pop(), False
            self._next_worker_id += 1
            self._live_instances += 1
            self._m_cold.inc(function=function_name)
            return WorkerInstance(self._next_worker_id, function_name), True

    def release(self, inst: WorkerInstance) -> None:
        with self._lock:
            self._warm.setdefault(inst.function_name, []).append(inst)

    def discard(self, inst: WorkerInstance) -> None:
        """A crashed sandbox is never reused."""
        with self._lock:
            self._live_instances -= 1

    def drain(self, function_name: str | None = None) -> int:
        """Scale-in: drop warm sandboxes (next invocations pay cold starts)."""
        with self._lock:
            if function_name is None:
                n = sum(len(v) for v in self._warm.values())
                self._warm.clear()
            else:
                n = len(self._warm.pop(function_name, []))
            self._live_instances -= n
            return n

    @property
    def live_instances(self) -> int:
        with self._lock:
            return self._live_instances

    def warm_count(self, function_name: str | None = None) -> int:
        with self._lock:
            if function_name is None:
                return sum(len(v) for v in self._warm.values())
            return len(self._warm.get(function_name, []))

    def stats(self) -> dict:
        """Cold/warm and busy-time accounting, totals plus a per-function
        breakdown — what the fleet controller and ``Session.stats()`` read
        instead of scraping logs.  The shape predates the metrics registry
        and is preserved exactly; the numbers now come FROM the registry."""
        with self._lock:
            names = sorted(self._fn_names)
            live = self._live_instances
            warm = sum(len(v) for v in self._warm.values())
        return {"cold_starts": int(self._m_cold.total),
                "warm_hits": int(self._m_warm.total),
                "busy_s": self._m_busy.total,
                "live_instances": live,
                "warm_count": warm,
                "functions": {
                    name: {"cold_starts": int(self._m_cold.value(function=name)),
                           "warm_hits": int(self._m_warm.value(function=name)),
                           "busy_s": self._m_busy.value(function=name)}
                    for name in names}}

    # ------------------------------------------------------------- invoke
    def invoke(self, entry: Callable[[bytes], tuple], function_name: str,
               payload: bytes, *, task_id: int = 0,
               attempt: int = 1) -> SandboxInvocation:
        """One billed trip through a sandbox.

        Rolls the fault plan (an injected failure raises
        :class:`WorkerCrash` and burns the sandbox), times the entry call as
        the billable server duration, applies straggler inflation, and
        returns blob + stats + sandbox metadata.  User-code exceptions
        propagate unchanged — error policy belongs to the caller.
        """
        fail, straggle = self.fault_plan.roll(task_id, attempt)
        inst, cold = self.acquire(function_name)
        if fail:
            self.discard(inst)
            crash = WorkerCrash(
                f"sandbox {inst.worker_id} lost (task {task_id} "
                f"attempt {attempt})")
            self._stamp(crash, inst, cold)
            raise crash
        t0 = time.perf_counter()
        try:
            # stats come back with the blob: concurrent entries of the same
            # bridge must not read each other's accounting (shared-attr race)
            blob, stats = entry(payload)
            server_s = time.perf_counter() - t0
        except BaseException as e:
            self.discard(inst)       # errored sandbox is not re-warmed
            self._stamp(e, inst, cold)
            raise
        finally:
            # busy time is real wall clock inside the entry (straggler
            # inflation is billing, not occupancy), per slot and per host
            elapsed = time.perf_counter() - t0
            inst.busy_s += elapsed
            self._m_busy.inc(elapsed, function=function_name)
        if straggle:
            if self.fault_plan.straggler_sleep_s:
                time.sleep(self.fault_plan.straggler_sleep_s)
            server_s *= self.fault_plan.straggler_factor
        inst.invocations += 1
        self.release(inst)
        return SandboxInvocation(blob=blob, stats=stats,
                                 worker_id=inst.worker_id, cold_start=cold,
                                 server_s=server_s)

    @staticmethod
    def _stamp(err: BaseException, inst: WorkerInstance, cold: bool) -> None:
        """Failure records must still say which sandbox burned: ride the
        accounting on the exception (some exception types reject attrs)."""
        try:
            err.sandbox_worker_id = inst.worker_id     # type: ignore[attr-defined]
            err.sandbox_cold_start = cold              # type: ignore[attr-defined]
        except Exception:
            pass
