"""Out-of-process transports: real ``processes`` and ``http`` backends.

PR 1 made backends pluggable but every one of them executed in the caller's
process — simulation.  These two ship the payload bytes across a real
boundary to a :class:`~repro.runtime.worker_host.WorkerHost` speaking the
versioned wire protocol:

* ``ProcessesBackend`` — one worker subprocess per slot (the worker-host
  CLI in ``--stdio`` mode), framed envelopes over stdin/stdout.  GIL-free:
  compute runs in the children; client threads only block on IO.  Workers
  rebuild bridges from the manifest on first use (a real cold start, AOT
  compile included) and reuse them warm across invocations.
* ``HttpBackend`` — the paper's actual client model: a separately-spawned
  ``http.server`` worker process plus a pool of persistent (keep-alive)
  HTTP/1.1 connections.  Every record's ``modeled_latency_ms`` is the
  *measured* client-observed roundtrip, flagged ``latency_measured`` —
  the field stops being a model and becomes a measurement.

Failure contract (the dead-worker satellite): a worker that dies
mid-request surfaces as a retryable ``WorkerCrash`` carrying whatever
traceback text the worker managed to send (EOF/connection loss synthesizes
one), the worker slot is respawned, and the dispatcher's ordinary retry
policy takes it from there — never a hung future.
"""
from __future__ import annotations

import http.client
import os
import struct
import subprocess
import sys
import tempfile
import threading
import time
import queue as queue_mod
from typing import Any

from ..core.deploy import Deployment
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..runtime.sandbox import ChaosPlan, WorkerCrash
from ..serialization import wire
from .futures import Invocation, InvocationRecord
from .workers import BackendCapabilities, fill_record

# client-side transport metrics (process-default registry; the worker-side
# twins ride back through host_stats and merge in ``stats()``)
_M_REQS = obs_metrics.REGISTRY.counter(
    "client_requests_total", "invocations sent over a real transport")
_M_CRASH = obs_metrics.REGISTRY.counter(
    "client_worker_crashes_total", "transport-level worker losses")
_M_QDEPTH = obs_metrics.REGISTRY.gauge(
    "client_queue_depth", "invocations waiting for a dispatch thread")


def _deliver(inv: Invocation, ok: bool, value: Any,
             rec: InvocationRecord) -> None:
    if inv.on_complete is not None:
        inv.on_complete(inv, ok, value, rec)
    elif ok:
        inv.future.set_result(value, rec)
    else:
        inv.future.set_error(value, rec)


def _worker_crash(message: str, traceback_text: str = "") -> WorkerCrash:
    e = WorkerCrash(message)
    e.remote_traceback = traceback_text        # type: ignore[attr-defined]
    return e


class _TransportBackend:
    """Shared client half: manifest persistence, dispatch threads, reply
    handling, measured-latency stamping.  Subclasses own the byte transport
    (``_request``) and worker lifecycle (``_spawn_slot`` / ``_close_slot``)."""

    capabilities = BackendCapabilities(concurrent=True, warm_reuse=True,
                                       measures_latency=True,
                                       cross_process=True,
                                       resident_state=True)

    def __init__(self, *, deployment: Deployment | None = None,
                 manifest_path: str | None = None, n_workers: int = 2,
                 chaos: ChaosPlan | None = None):
        if deployment is not None:
            self._manifest_path = self._persist_manifest(deployment)
        elif manifest_path is not None:
            self._manifest_path = manifest_path
            self._owns_manifest = False
        else:
            raise ValueError(
                f"{type(self).__name__} needs the client deployment (or an "
                "explicit manifest_path): workers rebuild bridges from the "
                "manifest")
        self._queue: "queue_mod.Queue[Invocation | None]" = queue_mod.Queue()
        self._threads: list[threading.Thread] = []
        self._slots: dict[int, Any] = {}
        self._lock = threading.Lock()
        self._started = False
        self._stop = False
        self._n_workers = max(1, n_workers)
        # affinity pinning (ISSUE 5): an affinity key maps to one slot
        # index, frozen at first use (scale_to growing n_workers must not
        # re-home resident state), served by a dedicated dispatch thread
        # per pinned slot.  Pinned and anonymous traffic may share a slot
        # — the per-slot lock already serializes the byte transport.
        self._affinity_slots: dict[int, int] = {}
        self._affinity_queues: dict[int, "queue_mod.Queue"] = {}
        self._affinity_threads: list[threading.Thread] = []
        # chaos injection (ISSUE 10): the seeded plan this client executes
        # for real — kill/stall/drop/expire against live worker slots.
        # ``_burned`` remembers slots discarded after a transport loss so
        # the lazy respawn in ``_slot_for`` is observable as an event.
        self.chaos = chaos
        self._burned: set[int] = set()
        self._respawn_count = 0

    def _persist_manifest(self, deployment: Deployment) -> str:
        """Workers share the client's manifest through the filesystem —
        ``Manifest.add`` re-saves on every deploy, workers reload on miss."""
        m = deployment.manifest
        if m.path is None:
            fd, path = tempfile.mkstemp(prefix="repro-manifest-",
                                        suffix=".json")
            os.close(fd)
            m.path = path
            self._owns_manifest = True
        else:
            self._owns_manifest = False
        m.save(m.path)
        return m.path

    # ------------------------------------------------------------ backend
    def submit(self, inv: Invocation) -> None:
        self._ensure_started()
        cfg = inv.config or inv.deployed.config
        affinity = getattr(cfg, "affinity", None)
        if affinity is None:
            self._queue.put(inv)
        else:
            self._affinity_queue(affinity).put(inv)

    def _affinity_slot(self, affinity: int) -> int:
        with self._lock:
            idx = self._affinity_slots.get(affinity)
            if idx is None:
                idx = affinity % self._n_workers
                self._affinity_slots[affinity] = idx
            return idx

    def _affinity_queue(self, affinity: int) -> "queue_mod.Queue":
        idx = self._affinity_slot(affinity)
        with self._lock:
            q = self._affinity_queues.get(idx)
            if q is None:
                q = queue_mod.Queue()
                self._affinity_queues[idx] = q
                t = threading.Thread(target=self._serve_queue,
                                     args=(idx, q), daemon=True)
                t.start()
                self._affinity_threads.append(t)
            return q

    def state_control(self, affinity: int, op: str, body: bytes = b"",
                      **data: Any) -> dict:
        """One CONTROL round-trip to the worker an affinity key pins —
        the client surface for state-lease management (ISSUE 5) and arena
        row migration (ISSUE 6).  A reply that carries a body (row
        extraction) surfaces it under the ``"_body"`` key.

        Transport-level connection loss here is normalized into a retryable
        :class:`WorkerCrash` with the usual exit-code/stderr-tail context
        (the dead-``url=``-worker satellite) — a raw ``ConnectionError``
        or socket error must never leak past the transport, so spawned and
        external workers share ONE recovery path."""
        idx = self._affinity_slot(affinity)
        slot = self._slot_for(idx)
        try:
            raw = self._request(slot, wire.encode_control(op, body=body,
                                                          **data))
        except Exception as e:
            detail = self._discard_slot(idx, e)
            _M_CRASH.inc(backend=type(self).__name__)
            raise _worker_crash(
                f"worker {idx} connection lost during control {op!r}: "
                f"{detail}") from e
        reply = wire.decode(raw)
        if isinstance(reply, wire.ErrorReply):
            raise wire.to_exception(reply)
        if not isinstance(reply, wire.ControlRequest):
            raise wire.WireProtocolError(
                f"unexpected control reply {type(reply).__name__}")
        out = dict(reply.data)
        if reply.body:
            out["_body"] = reply.body
        return out

    def _slot_control(self, slot, op: str, **data: Any) -> dict:
        """Best-effort CONTROL round-trip to one spawned slot (stats and
        scale-in probes; a dead worker just reports nothing).  Connection
        loss normalizes to :class:`WorkerCrash` like every other transport
        failure — callers catching ``Exception`` see no behavior change,
        callers that re-raise surface a retryable crash, not a socket
        error."""
        try:
            raw = self._request(slot, wire.encode_control(op, **data))
        except Exception as e:
            detail = self._slot_epitaph(slot) or (
                type(e).__name__ if not str(e) else str(e))
            raise _worker_crash(
                f"worker connection lost during control {op!r}: "
                f"{detail}") from e
        msg = wire.decode(raw)
        if isinstance(msg, wire.ErrorReply):
            raise wire.to_exception(msg)
        if not isinstance(msg, wire.ControlRequest):
            raise wire.WireProtocolError(
                f"unexpected control reply {type(msg).__name__}")
        return msg.data

    @property
    def queue_depth(self) -> int:
        return self._queue.qsize() + sum(
            q.qsize() for q in self._affinity_queues.values())

    def stats(self) -> dict:
        """Fleet observability: per-worker sandbox/state accounting, one
        ``host_stats`` CONTROL round-trip per *spawned* slot (an unspawned
        slot has no process, hence nothing resident)."""
        with self._lock:
            slots = dict(self._slots)
            n = self._n_workers
            pinned = dict(self._affinity_slots)
        workers: dict[int, dict] = {}
        totals = {"cold_starts": 0, "warm_hits": 0, "busy_s": 0.0,
                  "state_handles": 0}
        _M_QDEPTH.set(self.queue_depth)
        merged = obs_metrics.Registry()
        merged.merge(obs_metrics.REGISTRY.snapshot())
        for idx, slot in sorted(slots.items()):
            if slot is None:
                continue
            try:
                d = self._slot_control(slot, "host_stats")
            except Exception as e:
                workers[idx] = {"error": str(e) or type(e).__name__}
                continue
            workers[idx] = d
            merged.merge(d.get("metrics"))
            sb = d.get("sandboxes", {})
            totals["cold_starts"] += int(sb.get("cold_starts", 0))
            totals["warm_hits"] += int(sb.get("warm_hits", 0))
            totals["busy_s"] += float(sb.get("busy_s", 0.0))
            totals["state_handles"] += int(d.get("state", {}).get("count", 0))
        return {"n_workers": n, "spawned": len(workers),
                "respawns": self._respawn_count,
                "affinity_slots": pinned, "workers": workers,
                "metrics": merged.snapshot(), **totals}

    def scale_to(self, os_threads: int) -> None:
        n = max(1, int(os_threads))
        with self._lock:
            cur = self._n_workers
        if n >= cur:
            with self._lock:
                self._n_workers = max(self._n_workers, n)
            if self._started:
                self._ensure_started(force_resize=True)
            return
        # ---- scale-in (ISSUE 6): slots above the new fleet size may hold
        # affinity-pinned resident state.  Re-homing a frozen affinity
        # would hand its next invocation a blank arena mid-serve, so this
        # REFUSES while any doomed slot holds a live state lease — callers
        # drain the fleet member (or release the lease) first.
        with self._lock:
            doomed = {aff: idx for aff, idx in self._affinity_slots.items()
                      if idx >= n}
            doomed_slots = sorted(set(doomed.values()))
            slot_objs = {idx: self._slots.get(idx) for idx in doomed_slots}
        stranded = []
        for idx in doomed_slots:
            slot = slot_objs.get(idx)
            if slot is None:
                continue               # never spawned: nothing resident
            try:
                st = self._slot_control(slot, "state_stats")
            except Exception:
                continue               # dead worker holds nothing
            if int(st.get("count", 0)):
                stranded.append((idx, list(st.get("handles", []))))
        if stranded:
            detail = "; ".join(
                f"worker {idx} holds {', '.join(h[:12] for h in hs)}"
                for idx, hs in stranded)
            raise RuntimeError(
                f"scale_to({n}) would strand live state leases on pinned "
                f"workers ({detail}): drain those engines or release their "
                "handles first — refusing to silently re-home resident "
                "arenas")
        closing = []
        with self._lock:
            self._n_workers = n
            for aff in list(doomed):
                # safe to re-home: the pin re-freezes at aff % n next use
                self._affinity_slots.pop(aff, None)
            for idx in [i for i in list(self._slots) if i >= n]:
                slot = self._slots.pop(idx)
                if slot is not None:
                    closing.append(slot)
            for idx in [i for i in list(self._affinity_queues) if i >= n]:
                self._affinity_queues.pop(idx).put(None)   # retire its thread
        for slot in closing:
            try:
                self._close_slot(slot)
            except Exception:
                pass

    def drain_warm(self, function_name: str | None = None) -> int:
        """Drop warm sandboxes in every live worker (control roundtrip);
        ``function_name`` (the mangled bridge name) scopes the drain, as on
        the in-process pool."""
        total = 0
        with self._lock:
            slots = list(self._slots.items())
        frame = wire.encode_control("drain", function=function_name)
        for idx, slot in slots:
            if slot is None:
                continue
            try:
                msg = wire.decode(self._request(slot, frame))
                if isinstance(msg, wire.ControlRequest):
                    total += int(msg.data.get("count", 0))
            except Exception:
                pass                       # a dead worker has nothing warm
        return total

    def shutdown(self) -> None:
        self._stop = True
        for _ in self._threads:
            self._queue.put(None)
        with self._lock:
            aqueues = list(self._affinity_queues.values())
        for q in aqueues:
            q.put(None)
        with self._lock:
            slots, self._slots = dict(self._slots), {}
        for slot in slots.values():
            if slot is not None:
                try:
                    self._close_slot(slot)
                except Exception:
                    pass
        if getattr(self, "_owns_manifest", False):
            try:
                os.unlink(self._manifest_path)
            except OSError:
                pass

    # ----------------------------------------------------------- dispatch
    def _ensure_started(self, force_resize: bool = False) -> None:
        with self._lock:
            if self._started and not force_resize:
                return
            self._started = True
            while len(self._threads) < self._n_workers:
                idx = len(self._threads)
                t = threading.Thread(target=self._serve, args=(idx,),
                                     daemon=True)
                t.start()
                self._threads.append(t)

    def _slot_for(self, idx: int):
        with self._lock:
            slot = self._slots.get(idx)
        if slot is None:
            slot = self._spawn_slot(idx)
            with self._lock:
                self._slots[idx] = slot
                respawn = idx in self._burned
                self._burned.discard(idx)
                if respawn:
                    self._respawn_count += 1
            if respawn:
                # a slot burned by a crash (or a chaos kill) coming back:
                # worker death was added latency, and here is the receipt
                if self.chaos is not None:
                    self.chaos.record("worker.respawned", slot=idx)
        return slot

    def _serve(self, idx: int) -> None:
        self._serve_queue(idx, self._queue)

    def _serve_queue(self, idx: int,
                     queue: "queue_mod.Queue[Invocation | None]") -> None:
        while not self._stop:
            inv = queue.get()
            if inv is None:
                return
            if inv.future.done():          # hedged sibling already won
                continue
            try:
                self._execute(idx, inv)
            except BaseException as e:     # transport bug must not hang futures
                inv.future.set_error(e)

    def _execute(self, idx: int, inv: Invocation) -> None:
        # anonymous dispatch threads above a scaled-in fleet size share the
        # low slots instead of resurrecting retired workers (pinned traffic
        # re-froze its mapping below n in scale_to)
        idx %= max(1, self._n_workers)
        bridge = inv.deployed.bridge
        rec = InvocationRecord(
            task_id=inv.task_id, function_name=bridge.name,
            attempts=inv.attempt, hedged=inv.is_hedge,
            payload_bytes=len(inv.payload),
            memory_gb=bridge.config.memory_gb)
        label = type(self).__name__
        _M_REQS.inc(backend=label)
        ctx = inv.trace
        request = wire.encode_invoke(
            bridge.name, inv.payload, task_id=inv.task_id,
            attempt=inv.attempt,
            trace=ctx.to_wire() if ctx is not None else None,
            deadline=inv.deadline)
        tracer = obs_trace.TRACER
        if ctx is not None and ctx.t_start:
            # queue wait = context mint (dispatch) → this thread picking
            # the invocation up; derived, not measured, so it costs nothing
            # on the submit path
            tracer.span_at("client.queue", ctx, ctx.t_start,
                           max(0.0, time.time() - ctx.t_start), slot=idx)
        tspan = (tracer.span("client.transport", ctx, slot=idx,
                             backend=label)
                 if ctx is not None else obs_trace.NOOP)
        try:
            slot = self._slot_for(idx)
            if self.chaos is not None:
                self._inject_chaos(idx, slot)
            t0 = time.perf_counter()
            reply = self._request(slot, request)
            reply = self._serve_missing_artifacts(slot, request, reply)
            measured_ms = (time.perf_counter() - t0) * 1000.0
        except Exception as e:
            # transport loss: burn the slot, surface a retryable crash
            detail = self._discard_slot(idx, e)
            _M_CRASH.inc(backend=label)
            tspan.set("error.type", type(e).__name__)
            tspan.set("error.detail", detail[:2000])
            tspan.finish("error")
            _deliver(inv, False,
                     _worker_crash(f"worker {idx} died mid-request "
                                   f"(task {inv.task_id}): {detail}"), rec)
            return
        rec.modeled_latency_ms = measured_ms
        rec.latency_measured = True
        tspan.set("bytes_out", len(request))
        tspan.set("bytes_in", len(reply))
        tspan.finish()
        self._complete(inv, reply, rec)

    def _serve_missing_artifacts(self, slot, request: bytes,
                                 reply: bytes) -> bytes:
        """Artifact remote fetch (ROADMAP): a worker that cannot resolve an
        ``ArtifactRef`` (no shared filesystem) answers ``ArtifactMissing``;
        the client pushes the blob over the wire (CONTROL ``artifact_put``)
        and replays the invocation.  Bounded by distinct shas, so a worker
        that keeps losing blobs cannot loop the client forever."""
        from ..serialization.artifacts import export_artifact_blob
        served: set[str] = set()
        while True:
            miss = wire.decode_artifact_missing(reply)
            if miss is None:
                return reply
            sha, path = miss
            if sha in served:
                return reply               # pushed already and still missing
            blob = export_artifact_blob(sha, path)
            if blob is None:
                return reply               # client doesn't have it either
            ack = wire.decode(self._request(
                slot, wire.encode_control("artifact_put", body=blob,
                                          sha=sha)))
            if not (isinstance(ack, wire.ControlRequest)
                    and ack.data.get("ok")):
                return reply
            served.add(sha)
            reply = self._request(slot, request)

    def _complete(self, inv: Invocation, reply: bytes,
                  rec: InvocationRecord) -> None:
        bridge = inv.deployed.bridge
        try:
            msg = wire.decode(reply)
        except wire.WireProtocolError as e:
            _deliver(inv, False,
                     _worker_crash(f"undecodable worker reply: {e}"), rec)
            return
        # worker-side spans ride the reply envelope (RESULT and ERROR both):
        # adopt them into the client collector so the tree stitches
        spans = getattr(msg, "spans", None)
        if spans:
            obs_trace.TRACER.ingest(spans)
        if isinstance(msg, wire.ErrorReply):
            if msg.retryable:
                _deliver(inv, False, _worker_crash(
                    f"{msg.etype}: {msg.message}", msg.traceback), rec)
            else:
                exc = wire.to_exception(msg)
                # user-code failure: append the deploy-time shippability
                # diagnostic that predicts it (NameError under the fresh-
                # globals contract, unserializable capture, ...) as a
                # "likely cause" hint on the remote traceback / span attrs
                try:
                    from ..analysis import attach_failure_hint
                    attach_failure_hint(exc, inv.deployed)
                except Exception:
                    pass
                _deliver(inv, False, exc, rec)
            return
        if not isinstance(msg, wire.ResultReply):
            _deliver(inv, False, _worker_crash(
                f"unexpected reply frame {type(msg).__name__}"), rec)
            return
        try:
            value = bridge.unpack_result(msg.blob)
        except Exception as e:
            _deliver(inv, False, wire.WireProtocolError(
                f"result blob deserialization failed: {e}"), rec)
            return
        fill_record(rec, stats=msg.stats, server_s=msg.server_s,
                    worker_id=msg.worker_id, cold_start=msg.cold_start,
                    result_bytes=len(msg.blob))
        _deliver(inv, True, value, rec)

    def _inject_chaos(self, idx: int, slot) -> None:
        """Execute the chaos events due on this slot's Nth invocation.

        ``kill`` and ``drop`` make THIS invocation fail (the kill lands
        before the request bytes go out, so the in-flight decode dies with
        the worker — the WorkerCrash/EOF path, then lazy respawn);
        ``stall`` wedges the dispatch thread (a client-side straggle long
        enough to threaten a state lease — what the heartbeat defends
        against); ``expire`` force-expires the worker's leases via the
        CONTROL ``chaos`` verb, then lets the invocation proceed into the
        state-lost KeyError."""
        for ev in self.chaos.on_invoke(idx):
            if ev.kind == "kill":
                self.chaos.record("worker.killed", slot=idx)
                self._chaos_kill(idx, slot)
            elif ev.kind == "drop":
                self.chaos.record("conn.dropped", slot=idx)
                raise ConnectionError(
                    f"chaos: connection to worker {idx} dropped")
            elif ev.kind == "stall":
                self.chaos.record("entry.stalled", slot=idx,
                                  stall_s=ev.stall_s)
                time.sleep(ev.stall_s)
            elif ev.kind == "expire":
                try:
                    out = self._slot_control(slot, "chaos",
                                             action="expire_leases")
                    self.chaos.record("lease.expired", slot=idx,
                                      handles=out.get("expired", []))
                except Exception:
                    self.chaos.record("lease.expired", slot=idx, handles=[])

    def _chaos_kill(self, idx: int, slot) -> None:
        """Hard-kill the slot's worker.  Default: the CONTROL ``die`` verb
        (``os._exit``, no reply) — the only lever for workers we did not
        spawn; subclasses with a subprocess handle SIGKILL it directly."""
        try:
            self._request(slot, wire.encode_control("chaos", action="die"))
        except Exception:
            pass                   # death mid-reply is the expected outcome

    def _discard_slot(self, idx: int, err: Exception) -> str:
        with self._lock:
            slot = self._slots.pop(idx, None)
            self._burned.add(idx)
        detail = type(err).__name__ if not str(err) else str(err)
        if slot is not None:
            try:
                detail = self._slot_epitaph(slot) or detail
            finally:
                try:
                    self._close_slot(slot)
                except Exception:
                    pass
        return detail

    # -- subclass surface ----------------------------------------------------
    def _spawn_slot(self, idx: int):
        raise NotImplementedError

    def _request(self, slot, data: bytes) -> bytes:
        raise NotImplementedError

    def _close_slot(self, slot) -> None:
        raise NotImplementedError

    def _slot_epitaph(self, slot) -> str | None:
        """Best-effort post-mortem (exit code, stderr tail) for crash messages."""
        return None


# ---------------------------------------------------------------- processes

def _worker_env() -> dict[str, str]:
    """Child env: the client's import path on PYTHONPATH (the worker must
    resolve the same package tree the client deployed from — the analogue
    of building the worker image alongside the client binary), everything
    else inherited (JAX_PLATFORMS etc. must match the client's)."""
    import repro
    # repro may be a namespace package (no __init__.py): use __path__
    pkg_dir = os.path.abspath(list(repro.__path__)[0])
    src_dir = os.path.dirname(pkg_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [src_dir, *(p for p in sys.path if p)])
    return env


class _ProcSlot:
    def __init__(self, proc: subprocess.Popen, stderr_path: str):
        self.proc = proc
        self.stderr_path = stderr_path
        self.lock = threading.Lock()       # drain vs dispatch interleaving


class ProcessesBackend(_TransportBackend):
    """Worker-subprocess fleet — GIL-free python tasks, warm reuse.

    Each slot is one worker-host CLI child in ``--stdio`` mode; requests
    are ``u32 length``-prefixed wire frames.  A separate OS process per
    sandbox means the payload genuinely crosses a process boundary — the
    worker shares nothing with the client but the manifest file.

    Fleet size defaults to ``min(os_threads, cpu_count)`` — more python
    workers than cores cannot add parallelism — and ``n_workers=`` takes
    it anywhere.  Slots spawn lazily, one per concurrently-busy dispatch
    thread, so an idle session never pays for a full fleet.
    """

    def __init__(self, *, deployment: Deployment | None = None,
                 manifest_path: str | None = None, os_threads: int = 16,
                 n_workers: int | None = None,
                 chaos: ChaosPlan | None = None, **_):
        if n_workers is None:
            n_workers = max(1, min(os_threads, os.cpu_count() or 1))
        super().__init__(deployment=deployment, manifest_path=manifest_path,
                         n_workers=n_workers, chaos=chaos)

    def _spawn_slot(self, idx: int) -> _ProcSlot:
        fd, stderr_path = tempfile.mkstemp(prefix="repro-worker-",
                                           suffix=".log")
        proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "repro.runtime.worker_host",
             "--manifest", self._manifest_path, "--stdio",
             "--worker-id-base", str((idx + 1) * 1_000_000)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, stderr=fd,
            env=_worker_env())
        os.close(fd)
        return _ProcSlot(proc, stderr_path)

    def _request(self, slot: _ProcSlot, data: bytes) -> bytes:
        with slot.lock:
            assert slot.proc.stdin is not None and slot.proc.stdout is not None
            slot.proc.stdin.write(struct.pack("<I", len(data)) + data)
            slot.proc.stdin.flush()
            header = slot.proc.stdout.read(4)
            if len(header) < 4:
                raise EOFError("worker closed the pipe")
            (n,) = struct.unpack("<I", header)
            reply = slot.proc.stdout.read(n)
            if len(reply) < n:
                raise EOFError("worker died mid-reply")
            return reply

    def _close_slot(self, slot: _ProcSlot) -> None:
        try:
            if slot.proc.stdin is not None:
                slot.proc.stdin.close()    # EOF: worker loop exits cleanly
            slot.proc.wait(timeout=5)
        except Exception:
            slot.proc.kill()
        try:
            os.unlink(slot.stderr_path)
        except OSError:
            pass

    def _chaos_kill(self, idx: int, slot: _ProcSlot) -> None:
        # SIGKILL from the client side: the worker gets no chance to flush
        # a reply or clean up — the hardest failure the transport can see
        slot.proc.kill()
        try:
            slot.proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            pass

    def _slot_epitaph(self, slot: _ProcSlot) -> str | None:
        try:
            code = slot.proc.wait(timeout=1)
        except subprocess.TimeoutExpired:
            return None
        tail = ""
        try:
            with open(slot.stderr_path, "r", errors="replace") as f:
                tail = f.read()[-2000:].strip()
        except OSError:
            pass
        msg = f"worker process exited (code {code}) mid-request"
        return f"{msg}; stderr tail:\n{tail}" if tail else msg


# --------------------------------------------------------------------- http

def _parse_worker_url(url: str) -> tuple[str, int]:
    """``http://host:port[/...]``, ``host:port``, or ``http://host`` →
    (host, port).  The stdlib transport speaks plain HTTP only."""
    from urllib.parse import urlsplit
    u = urlsplit(url if "//" in url else "//" + url)
    if u.scheme not in ("", "http"):
        raise ValueError(f"worker url {url!r}: only plain http is supported "
                         "by the stdlib transport")
    if not u.hostname:
        raise ValueError(f"worker url {url!r} has no hostname")
    return u.hostname, u.port or 80


class _HttpSlot:
    def __init__(self, host: str, port: int):
        self.host = host
        self.port = port
        self.conn: http.client.HTTPConnection | None = None
        self.lock = threading.Lock()


class HttpBackend(_TransportBackend):
    """The paper's client model: payloads POSTed to a separately-deployed
    worker over pooled keep-alive connections; latency is *measured*."""

    def __init__(self, *, deployment: Deployment | None = None,
                 manifest_path: str | None = None, os_threads: int = 16,
                 url: str | None = None, n_connections: int | None = None,
                 spawn_timeout_s: float = 180.0,
                 chaos: ChaosPlan | None = None, **_):
        if n_connections is None:
            n_connections = max(1, min(os_threads, 8))
        if url is not None and manifest_path is None and deployment is None:
            manifest_path = "<external>"   # worker owns its own manifest
        super().__init__(deployment=deployment, manifest_path=manifest_path,
                         n_workers=n_connections, chaos=chaos)
        self._url = url
        self._spawn_timeout_s = spawn_timeout_s
        self._proc: subprocess.Popen | None = None
        self._addr: tuple[str, int] | None = None
        self._proc_lock = threading.Lock()

    # one worker process serves every connection slot
    def _ensure_worker(self) -> tuple[str, int]:
        with self._proc_lock:
            if self._addr is not None and (
                    self._proc is None or self._proc.poll() is None):
                return self._addr
            if self._url is not None:
                self._addr = _parse_worker_url(self._url)
                return self._addr
            self._addr = self._spawn_worker()
            return self._addr

    def _spawn_worker(self) -> tuple[str, int]:
        from ..runtime.worker_host import READY_MARKER
        self._proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "repro.runtime.worker_host",
             "--manifest", self._manifest_path],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=_worker_env(), text=True)
        proc = self._proc
        assert proc.stdout is not None
        # readline() has no timeout of its own: scrape stdout from a helper
        # thread so a worker that hangs *before* printing the READY line
        # (stalled import, wedged manifest read) still trips the deadline
        # instead of blocking every dispatch thread behind _proc_lock.
        lines: "queue_mod.Queue[str | None]" = queue_mod.Queue()

        def scrape():
            for line in iter(proc.stdout.readline, ""):
                lines.put(line)
            lines.put(None)                # EOF: the worker exited

        threading.Thread(target=scrape, daemon=True).start()
        deadline = time.monotonic() + self._spawn_timeout_s
        while True:
            try:
                line = lines.get(timeout=max(0.1,
                                             deadline - time.monotonic()))
            except queue_mod.Empty:
                proc.kill()
                raise TimeoutError(
                    f"http worker not ready within {self._spawn_timeout_s}s"
                ) from None
            if line is None:
                raise WorkerCrash(f"http worker exited during startup "
                                  f"(code {proc.wait()})")
            if line.startswith(READY_MARKER):
                port = int(line.strip().rsplit("port=", 1)[1])
                return ("127.0.0.1", port)

    # ------------------------------------------------------------- slots
    def _spawn_slot(self, idx: int) -> _HttpSlot:
        host, port = self._ensure_worker()
        return _HttpSlot(host, port)

    def _request(self, slot: _HttpSlot, data: bytes) -> bytes:
        with slot.lock:
            if slot.conn is None:
                slot.conn = http.client.HTTPConnection(
                    slot.host, slot.port, timeout=600)
            try:
                slot.conn.request(
                    "POST", "/invoke", body=data,
                    headers={"Content-Type": "application/octet-stream"})
                resp = slot.conn.getresponse()
                body = resp.read()
            except Exception:
                try:
                    slot.conn.close()
                finally:
                    slot.conn = None
                raise
            if resp.status != 200:
                raise WorkerCrash(f"worker HTTP {resp.status}")
            return body

    def _close_slot(self, slot: _HttpSlot) -> None:
        if slot.conn is not None:
            slot.conn.close()

    def _slot_epitaph(self, slot: _HttpSlot) -> str | None:
        with self._proc_lock:
            if self._proc is not None and self._proc.poll() is not None:
                code = self._proc.poll()
                self._addr = None          # force respawn on next slot
                return f"http worker exited (code {code})"
        return None

    def shutdown(self) -> None:
        super().shutdown()
        with self._proc_lock:
            if self._proc is not None and self._proc.poll() is None:
                self._proc.terminate()
                try:
                    self._proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    self._proc.kill()
            self._proc = None
