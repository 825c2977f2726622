"""Elastic worker pool — the in-process execution backend for the FaaS fleet.

Real execution, simulated fleet: invocations run on a bounded set of OS
threads, while sandbox lifecycle (cold/warm accounting, fault injection,
billing stats) lives in the reusable :class:`repro.runtime.sandbox.SandboxHost`
— the same host the out-of-process transports (``processes``/``http``) and
the worker-side :class:`~repro.runtime.worker_host.WorkerHost` use.  The
serverless execution contract is enforced: a task sees only its payload
bytes (``Bridge.entry``), is stateless, and may be killed and retried at
any time.
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass

from ..runtime.sandbox import (FaultPlan, SandboxHost, WorkerCrash,
                               WorkerInstance)
from .futures import Invocation, InvocationRecord

__all__ = ["BackendCapabilities", "FaultPlan", "WorkerCrash",
           "WorkerInstance", "WorkerPool", "fill_record"]


@dataclass(frozen=True)
class BackendCapabilities:
    """What an execution backend can do — policy layers branch on these
    instead of isinstance checks (see ``dispatch.backends.Backend``)."""
    concurrent: bool = True        # real OS-thread parallelism
    warm_reuse: bool = True        # sandbox cold/warm bookkeeping
    fault_injection: bool = False  # honors a FaultPlan
    models_latency: bool = False   # fills InvocationRecord.modeled_latency_ms
    measures_latency: bool = False # modeled_latency_ms is a *measurement*
    cross_process: bool = False    # payloads cross a process/socket boundary
    # worker-resident state (ISSUE 5): entries in repro.runtime.state
    # survive between invocations and FunctionConfig.affinity pinning is
    # honored (trivially, for in-process backends) — iteration-level
    # serving requires this; backends without it get the wave fallback
    resident_state: bool = False


def fill_record(rec: InvocationRecord, *, stats, server_s: float,
                worker_id: int, cold_start: bool, result_bytes: int) -> None:
    """Copy one completed entry's accounting into an invocation record —
    shared by every transport so records look identical across backends."""
    rec.worker_id = worker_id
    rec.cold_start = cold_start
    rec.server_s = server_s
    rec.result_bytes = result_bytes
    if isinstance(stats, dict):
        rec.deserialize_s = stats.get("deserialize_s", 0.0)
        rec.compute_s = stats.get("compute_s", 0.0)
        rec.serialize_s = stats.get("serialize_s", 0.0)
    else:
        rec.deserialize_s = stats.deserialize_s
        rec.compute_s = stats.compute_s
        rec.serialize_s = stats.serialize_s


class WorkerPool:
    """Elastic pool executing ``Invocation``s on OS threads.

    ``max_concurrency`` models the account's function-concurrency limit
    (paper: 1000); ``os_threads`` bounds real parallelism in this container.
    Sandboxes scale out on demand (cold start) and are reused warm, per
    function name — matching FaaS semantics — via the ``SandboxHost``.

    ``WorkerPool`` is the ``"threads"`` backend of the registry in
    ``dispatch.backends``; subclasses there reuse its sandbox model with
    different execution strategies (inline, simulated-AWS).
    """

    capabilities = BackendCapabilities(concurrent=True, warm_reuse=True,
                                       fault_injection=True,
                                       resident_state=True)

    def __init__(self, max_concurrency: int = 1000, os_threads: int = 16,
                 fault_plan: FaultPlan | None = None):
        self.max_concurrency = max_concurrency
        self.sandboxes = SandboxHost(fault_plan)
        self._queue: "queue.Queue[Invocation | None]" = queue.Queue()
        self._lock = threading.Lock()
        self._threads: list[threading.Thread] = []
        self._stop = False
        self._resize(os_threads)

    @property
    def fault_plan(self) -> FaultPlan:
        return self.sandboxes.fault_plan

    # ------------------------------------------------------------- elastic
    def _resize(self, n: int) -> None:
        while len(self._threads) < n:
            t = threading.Thread(target=self._run, daemon=True)
            t.start()
            self._threads.append(t)

    def scale_to(self, os_threads: int) -> None:
        """Elastic scale-out of real executors (scale-in is cooperative)."""
        self._resize(os_threads)

    def drain_warm(self, function_name: str | None = None) -> int:
        """Scale-in: drop warm sandboxes (next invocations pay cold starts)."""
        return self.sandboxes.drain(function_name)

    def stats(self) -> dict:
        """Fleet observability: the pool's cold/warm and busy accounting
        plus the (process-local) resident-state registry — the in-process
        shape of ``_TransportBackend.stats()``."""
        from ..runtime import state
        s = dict(self.sandboxes.stats())
        st = state.stats()
        return {"n_workers": max(1, len(self._threads)), "spawned": 1,
                "workers": {0: {"sandboxes": s, "state": st}},
                "cold_starts": s["cold_starts"], "warm_hits": s["warm_hits"],
                "busy_s": s["busy_s"], "state_handles": st["count"]}

    # ------------------------------------------------------------ dispatch
    def submit(self, inv: Invocation) -> None:
        self._queue.put(inv)

    @property
    def queue_depth(self) -> int:
        """Invocations accepted but not yet started (admission control)."""
        return self._queue.qsize()

    def shutdown(self) -> None:
        self._stop = True
        for _ in self._threads:
            self._queue.put(None)

    # ------------------------------------------------------------- worker
    def _run(self) -> None:
        while not self._stop:
            inv = self._queue.get()
            if inv is None:
                return
            if inv.future.done():       # hedged sibling already won
                self._skipped(inv)
                continue
            try:
                self._execute(inv)
            except BaseException as e:  # executor bug must not kill the thread
                inv.future.set_error(e)

    # Subclass hooks (see dispatch.backends): called for every invocation
    # that is dropped unexecuted / right before its completion is delivered.
    def _skipped(self, inv: Invocation) -> None:
        pass

    def _post_execute(self, inv: Invocation, rec: InvocationRecord,
                      ok: bool) -> None:
        pass

    def _execute(self, inv: Invocation) -> None:
        bridge = inv.deployed.bridge
        rec = InvocationRecord(
            task_id=inv.task_id, function_name=bridge.name,
            attempts=inv.attempt, hedged=inv.is_hedge,
            payload_bytes=len(inv.payload),
            memory_gb=bridge.config.memory_gb)

        def finish(ok: bool, value, record: InvocationRecord) -> None:
            self._post_execute(inv, record, ok)
            if inv.on_complete is not None:
                inv.on_complete(inv, ok, value, record)
            elif ok:
                inv.future.set_result(value, record)
            else:
                inv.future.set_error(value, record)

        # the in-process analogue of the worker-side entry span: same name
        # ("worker.entry"), same parent (the request's submit span), so a
        # trace looks the same whether the entry ran in a thread or a child
        # process.  It reaches a capturing JAX profiler for every
        # invocation, sampled or not, and is the current context of the
        # spans the entry opens.
        from ..obs import trace as obs_trace
        espan = obs_trace.TRACER.span("worker.entry", inv.trace,
                                      profile=True, function=bridge.name)
        try:
            done = obs_trace.bound(espan.ctx, self.sandboxes.invoke)(
                bridge.entry, bridge.name, inv.payload,
                task_id=inv.task_id, attempt=inv.attempt)
            fill_record(rec, stats=done.stats, server_s=done.server_s,
                        worker_id=done.worker_id, cold_start=done.cold_start,
                        result_bytes=len(done.blob))
            espan.set("cold_start", done.cold_start)
            espan.set("worker_id", done.worker_id)
            espan.finish()
            finish(True, bridge.unpack_result(done.blob), rec)
        except WorkerCrash as e:
            self._stamp_failure(rec, e)
            espan.set("error.type", type(e).__name__)
            espan.finish("error")
            finish(False, e, rec)          # dispatcher decides on retry
        except BaseException as e:         # user-code error: no retry
            self._stamp_failure(rec, e)
            rec.server_s = 0.0
            espan.set("error.type", type(e).__name__)
            espan.set("error.message", str(e))
            espan.finish("error")
            finish(False, e, rec)

    @staticmethod
    def _stamp_failure(rec: InvocationRecord, e: BaseException) -> None:
        # the sandbox host rode its accounting on the exception: crash and
        # error records still identify the (cold?) sandbox that burned
        rec.worker_id = getattr(e, "sandbox_worker_id", rec.worker_id)
        rec.cold_start = getattr(e, "sandbox_cold_start", rec.cold_start)
