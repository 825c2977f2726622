"""Worker host — the separately-deployed entry point (paper §3.3, Fig 5).

This is the *server* half of the real transports: a fresh process that
knows nothing about the client except the deployment manifest.  It rebuilds
bridges on demand (thaw the shipped code, AOT-compile against the first
invocation's payload — a genuine cold start), accounts sandboxes with the
same :class:`~repro.runtime.sandbox.SandboxHost` the in-process backends
use, and speaks only the versioned wire protocol
(:mod:`repro.serialization.wire`).

Two front-ends share one :class:`WorkerHost`, both reachable through the
CLI (``python -m repro.runtime.worker_host --manifest m.json``):

* ``stdio_main(...)`` / ``--stdio``  — length-prefixed wire frames on
  stdin/stdout, one subprocess per sandbox slot (``processes`` backend);
* ``serve_http(...)`` / ``--port``   — stdlib ``http.server`` POST /invoke
  endpoint (``http`` backend, the paper's client model); deployable
  standalone anywhere the package tree exists.

Error contract (the wire's, exactly): user-code exceptions become
non-retryable ``ERROR`` envelopes carrying the original traceback text;
anything that escapes the handler is sent as a *retryable* ``ERROR`` (best
effort) before the process dies, so the client surfaces a retryable
invocation error instead of a hung future.
"""
from __future__ import annotations

import argparse
import os
import sys
import threading
import time
import traceback

from ..core.codeship import thaw_function
from ..core.function import RemoteFunction
from ..core.manifest import Manifest, ManifestEntry
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..serialization import (ArtifactMissingError, deserialize,
                             import_artifact_blob, wire)
from .sandbox import SandboxHost

# worker-side request metrics (process-default registry; per-function
# entry accounting lives in the sandbox host's private registry — both are
# merged into the host_stats reply and the /metrics exposition)
_M_REQS = obs_metrics.REGISTRY.counter(
    "worker_requests_total", "INVOKE frames handled")
_M_INFLIGHT = obs_metrics.REGISTRY.gauge(
    "worker_inflight", "INVOKE frames currently executing")
_M_EXPIRED = obs_metrics.REGISTRY.counter(
    "worker_deadline_rejections_total",
    "INVOKE frames rejected because their deadline had already passed")
# eagerly registered so every /metrics exposition carries the serving
# histograms' bucket layout even before (or without) the batcher running
# in this process — the client-side batcher observes into the same names,
# and the fleet merge requires exact bucket agreement
obs_metrics.REGISTRY.histogram(
    "serve_ttft_ms", "time to first token (ms)")
obs_metrics.REGISTRY.histogram(
    "serve_tpot_ms", "per-token decode latency (ms)")


class WorkerHost:
    """Manifest-driven bridge cache + wire-protocol request handler."""

    def __init__(self, manifest_path: str, *, worker_id_base: int | None = None):
        self.manifest_path = manifest_path
        self.manifest = Manifest(manifest_path)
        self._bridges: dict[str, object] = {}
        self._build_locks: dict[str, threading.Lock] = {}
        self._lock = threading.Lock()
        base = (os.getpid() % 100_000) * 1_000 \
            if worker_id_base is None else worker_id_base
        self.sandboxes = SandboxHost(worker_id_base=base)

    # ------------------------------------------------------------ bridges
    def _entry_for(self, name: str) -> ManifestEntry:
        if name not in self.manifest.entries:
            try:
                # the client deploys continuously; reload before giving up
                self.manifest.load(self.manifest_path)
            except OSError:
                pass                   # nothing deployed yet
        try:
            return self.manifest.get(name)
        except KeyError:
            raise LookupError(
                f"function {name!r} not in manifest {self.manifest_path!r}"
            ) from None

    def _build_bridge(self, entry: ManifestEntry, example_payload: bytes):
        """Rebuild a bridge from the manifest — the worker-side deploy.

        AOT specialization needs example arguments; the first invocation's
        payload provides them (and pays the compile, i.e. the cold start).
        """
        from ..core.bridge import (NOT_TRACEABLE, Bridge, make_executor_aot,
                                   make_executor_generic)
        fn = thaw_function(entry.code)
        rf = RemoteFunction(fn, name=entry.human_name, config=entry.config,
                            jax_traceable=(entry.kind == "aot_xla"))
        args, kwargs, captures = deserialize(example_payload)
        kind = "generic_worker"
        if rf.jax_traceable:
            try:
                executor = make_executor_aot(rf, args, kwargs, captures)
                kind = "aot_xla"
            except NOT_TRACEABLE:
                executor = make_executor_generic(rf)
        else:
            executor = make_executor_generic(rf)
        return Bridge(name=entry.name, config=entry.config,
                      executor=executor, kind=kind)

    def get_bridge(self, name: str, example_payload: bytes):
        with self._lock:
            bridge = self._bridges.get(name)
            if bridge is not None:
                return bridge
            build_lock = self._build_locks.setdefault(name, threading.Lock())
        # per-name build lock: concurrent first invocations of one function
        # must not each pay the AOT compile (multi-second for real models)
        with build_lock:
            with self._lock:
                bridge = self._bridges.get(name)
                if bridge is not None:
                    return bridge
            entry = self._entry_for(name)
            bridge = self._build_bridge(entry, example_payload)
            with self._lock:
                self._bridges[name] = bridge
            return bridge

    # ------------------------------------------------------------ handler
    def handle(self, data: bytes) -> bytes:
        """One request → one reply, both wire frames.  Never raises on user
        or protocol errors — those become ``ERROR`` envelopes; only a host
        bug escapes (and the transport loops turn it into a retryable
        error before dying)."""
        t_recv = time.time()
        t0 = time.perf_counter()
        try:
            msg = wire.decode(data)
        except wire.WireProtocolError as e:
            return wire.encode_error(e, retryable=False)
        if isinstance(msg, wire.ControlRequest):
            return self._handle_control(msg)
        if not isinstance(msg, wire.InvokeRequest):
            return wire.encode_error(
                etype="WireProtocolError", retryable=False,
                message=f"unexpected frame {type(msg).__name__} on a worker")
        # deadline propagation (ISSUE 10): already-expired work is rejected
        # BEFORE any bridge build or entry call — the worker does not burn
        # compute on a result no client is waiting for.  Non-retryable by
        # design (a retry cannot un-expire it); TimeoutError is a builtin,
        # so the client reconstructs the exact type.
        if msg.deadline is not None and t_recv > msg.deadline:
            _M_EXPIRED.inc(function=msg.function)
            return wire.encode_error(
                etype="TimeoutError", retryable=False,
                message=(f"deadline exceeded before execution: task "
                         f"{msg.task_id} arrived {t_recv - msg.deadline:.3f}s "
                         "past its deadline"))
        # worker-side spans exist only when the client sampled this request
        # (the trace header field IS the sampling decision crossing the
        # wire); they ship back on the reply envelope — the worker keeps
        # nothing and needs no tracing config of its own
        spans = obs_trace.RemoteSpans(msg.trace)
        if spans:
            spans.span_at("worker.decode", t_recv,
                          time.perf_counter() - t0, bytes=len(data))
        _M_REQS.inc(function=msg.function)
        _M_INFLIGHT.inc()
        try:
            with self._lock:
                first_use = msg.function not in self._bridges
            cspan = (spans.span("worker.compile", function=msg.function)
                     if first_use else obs_trace.NOOP)
            with cspan:
                bridge = self.get_bridge(msg.function, msg.payload)
            with spans.span("worker.entry", function=msg.function) as espan:
                done = self.sandboxes.invoke(
                    bridge.entry, msg.function, msg.payload,
                    task_id=msg.task_id, attempt=msg.attempt)
                espan.set("cold_start", done.cold_start)
                espan.set("worker_id", done.worker_id)
        except ArtifactMissingError as e:  # no shared fs: ask for a push
            return wire.encode_artifact_missing(e.sha, e.path)
        except Exception as e:             # user code / lookup / deserialize
            return wire.encode_error(
                e, traceback_text=traceback.format_exc(), retryable=False,
                spans=spans.dicts() or None)
        finally:
            _M_INFLIGHT.dec()
        s = done.stats
        return wire.encode_result(
            done.blob,
            stats={"deserialize_s": s.deserialize_s, "compute_s": s.compute_s,
                   "serialize_s": s.serialize_s},
            server_s=done.server_s, cold_start=done.cold_start,
            worker_id=done.worker_id, spans=spans.dicts() or None)

    def _handle_control(self, msg: wire.ControlRequest) -> bytes:
        if msg.op == "ping":
            return wire.encode_control("pong", pid=os.getpid(),
                                       functions=len(self._bridges))
        if msg.op == "drain":
            name = msg.data.get("function")
            with self._lock:
                if name is None:
                    self._bridges.clear()
                else:
                    self._bridges.pop(name, None)
            return wire.encode_control("drained",
                                       count=self.sandboxes.drain(name))
        if msg.op in ("state_lease", "state_renew", "state_release",
                      "state_stats"):
            # worker-resident serving state (ISSUE 5): lease renewal and
            # release for cache arenas, TTL-reclaimed so a dead client
            # cannot pin worker memory
            from . import state
            try:
                return wire.encode_control(msg.op, **state.control(
                    msg.op, msg.data))
            except Exception as e:
                return wire.encode_error(e, retryable=False)
        if msg.op in ("state_extract_rows", "state_insert_rows"):
            # arena row migration (ISSUE 6): ship finished prefill rows out
            # of / into this worker's resident arenas as CONTROL bodies —
            # the disaggregated prefill→decode hand-off.  Lazy engine
            # import: only workers already running engine entry points
            # (jax loaded) ever receive these.
            from .engine import migration_control
            try:
                reply, body = migration_control(msg.op, msg.data, msg.body)
                return wire.encode_control(msg.op, body=body, **reply)
            except Exception as e:
                return wire.encode_error(e, retryable=False)
        if msg.op == "host_stats":
            # fleet observability (ISSUE 6): this worker's cold/warm and
            # busy-time accounting plus its resident-state leases, one
            # round-trip — what Session.stats() aggregates across slots.
            # ``metrics`` (ISSUE 8) is the uniform registry snapshot the
            # client merges fleet-wide.
            from . import state
            return wire.encode_control(
                "host_stats", pid=os.getpid(), functions=len(self._bridges),
                sandboxes=self.sandboxes.stats(), state=state.stats(),
                metrics=self.metrics_snapshot())
        if msg.op == "chaos":
            # worker-side chaos execution (ISSUE 10): the client's ChaosPlan
            # reaches across the process boundary through this verb —
            # ``expire_leases`` backdates every resident state lease (the
            # next engine call surfaces state-lost), ``stall`` wedges this
            # worker for a bit (straggler), ``die`` hard-exits without a
            # reply (the SIGKILL analogue for transports that cannot signal
            # the process directly, e.g. an external url= http worker).
            from . import state
            action = msg.data.get("action")
            if action == "expire_leases":
                expired = state.expire_all(msg.data.get("handles"))
                return wire.encode_control("chaos", ok=True, expired=expired)
            if action == "stall":
                time.sleep(float(msg.data.get("stall_s", 0.0)))
                return wire.encode_control("chaos", ok=True)
            if action == "die":
                os._exit(int(msg.data.get("code", 9)))
            return wire.encode_error(
                etype="ValueError", retryable=False,
                message=f"unknown chaos action {action!r}")
        if msg.op == "artifact_put":
            # remote artifact fetch: the client pushes a blob this worker
            # reported missing; deposit it in the local store and ack
            try:
                path = import_artifact_blob(msg.data["sha"], msg.body)
                return wire.encode_control("artifact_put", ok=True,
                                           path=path)
            except Exception as e:
                return wire.encode_error(e, retryable=False)
        return wire.encode_error(etype="WireProtocolError", retryable=False,
                                 message=f"unknown control op {msg.op!r}")

    def metrics_snapshot(self) -> dict:
        """This worker's full metrics view: the process-default registry
        (request/control counters) merged with the sandbox host's private
        registry (per-function cold/warm/busy) — what rides ``host_stats``
        and backs the http front-end's ``GET /metrics``."""
        merged = obs_metrics.Registry()
        merged.merge(obs_metrics.REGISTRY.snapshot())
        merged.merge(self.sandboxes.metrics.snapshot())
        return merged.snapshot()


# ------------------------------------------------------ processes front-end

def stdio_main(manifest_path: str, worker_id_base: int | None = None) -> None:
    """Framed-stdio loop for one ``processes``-backend worker subprocess.

    Frames are ``u32 length | wire envelope`` on stdin/stdout — the same
    envelopes as HTTP bodies, just a different byte carrier.  BaseExceptions
    that escape the handler (host bug, SystemExit from user code) are
    reported as *retryable* errors with the original traceback — then the
    process exits and the client-side transport respawns a replacement.  A
    hard death (``os._exit``, SIGKILL) sends nothing; the client sees EOF
    and synthesizes the retryable error from the exit code and stderr tail.
    """
    import struct

    inp = sys.stdin.buffer
    out = sys.stdout.buffer
    sys.stdout = sys.stderr        # stray prints must not corrupt framing

    def send(reply: bytes) -> None:
        out.write(struct.pack("<I", len(reply)))
        out.write(reply)
        out.flush()

    host = WorkerHost(manifest_path, worker_id_base=worker_id_base)
    while True:
        header = inp.read(4)
        if len(header) < 4:
            return                 # client closed the pipe: clean shutdown
        (n,) = struct.unpack("<I", header)
        data = inp.read(n)
        if len(data) < n:
            return
        try:
            reply = host.handle(data)
        except BaseException:
            try:
                send(wire.encode_error(
                    etype="WorkerCrash", retryable=True,
                    message="worker died mid-request",
                    traceback_text=traceback.format_exc()))
            except Exception:
                pass
            raise
        try:
            send(reply)
        except (BrokenPipeError, OSError):
            return


# ------------------------------------------------------------ http front-end

READY_MARKER = "WORKER_HOST_READY"


def serve_http(manifest_path: str, *, host: str = "127.0.0.1", port: int = 0,
               announce=None):
    """Serve the wire protocol over stdlib HTTP (POST /invoke).

    Returns the live ``ThreadingHTTPServer`` (caller drives
    ``serve_forever``); ``announce(port)`` fires once the socket is bound —
    the CLI prints the ready line from it so a parent process can scrape
    the chosen port.
    """
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    worker = WorkerHost(manifest_path)

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"      # keep-alive: the pooled client

        def do_POST(self):                 # noqa: N802 (stdlib casing)
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            try:
                reply = worker.handle(body)
            except BaseException:
                reply = wire.encode_error(
                    etype="WorkerCrash", retryable=True,
                    message="worker died mid-request",
                    traceback_text=traceback.format_exc())
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(reply)))
            self.end_headers()
            self.wfile.write(reply)

        def do_GET(self):                  # noqa: N802 (stdlib casing)
            # Prometheus scrape endpoint — text exposition of this worker's
            # merged metrics (request counters + per-function sandbox
            # accounting).  Anything else is 404.
            if self.path.split("?", 1)[0] != "/metrics":
                self.send_response(404)
                self.send_header("Content-Length", "0")
                self.end_headers()
                return
            text = obs_metrics.render_snapshot(
                worker.metrics_snapshot()).encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(text)))
            self.end_headers()
            self.wfile.write(text)

        def log_message(self, *a):         # quiet: latency is measured, not logged
            pass

    class Server(ThreadingHTTPServer):
        daemon_threads = True              # a hung handler never pins exit

    server = Server((host, port), Handler)
    server.worker = worker                 # introspection for in-test workers
    if announce is not None:
        announce(server.server_address[1])
    return server


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Serverless worker host: serve a deployment manifest "
                    "over the wire protocol (framed stdio or HTTP).")
    ap.add_argument("--manifest", required=True)
    ap.add_argument("--stdio", action="store_true",
                    help="speak length-prefixed wire frames on stdin/stdout "
                         "(the `processes` transport)")
    ap.add_argument("--worker-id-base", type=int, default=None)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 picks a free port (announced on stdout)")
    args = ap.parse_args(argv)

    if args.stdio:
        stdio_main(args.manifest, args.worker_id_base)
        return

    def announce(port: int) -> None:
        print(f"{READY_MARKER} port={port}", flush=True)

    server = serve_http(args.manifest, host=args.host, port=args.port,
                        announce=announce)
    # After the READY line stdout belongs to the parent's scraper, which
    # stops reading: user-code prints must go to stderr or they would fill
    # the unread pipe and wedge every handler thread mid-request.
    sys.stdout = sys.stderr
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()


if __name__ == "__main__":
    main()
