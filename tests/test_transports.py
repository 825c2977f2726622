"""Transport-agnostic worker runtime (ISSUE 2): wire protocol, code
shipping, the worker host, and the real `processes`/`http` backends —
including the dead-worker and wire-deserialize error paths."""
import os
import threading
import time

import jax.numpy as jnp
import pytest

from repro.cloud import Session
from repro.core import (Deployment, RemoteFunction, freeze_function,
                        thaw_function)
from repro.core.codeship import CodeShipError
from repro.dispatch import HttpBackend, WorkerCrash
from repro.runtime.sandbox import FaultPlan, SandboxHost
from repro.runtime.worker_host import WorkerHost, serve_http
from repro.serialization import serialize, wire


# Module-level task functions: shippable to worker processes by reference
# (the test module rides to workers on the propagated import path).

def task_square_sum(x):
    import jax.numpy as jnp
    return jnp.sum(x * x)


def task_raise(x):
    raise ValueError(f"bad input {x}")


def task_hard_exit(x):
    os._exit(13)               # sandbox loss: no goodbye on the wire


def task_base_exception(x):
    raise SystemExit(3)        # escapes the handler: retryable + traceback


# ------------------------------------------------------------------ wire ----

def test_wire_invoke_roundtrip():
    frame = wire.encode_invoke("fn_abc", b"\x00payload", task_id=7, attempt=2)
    msg = wire.decode(frame)
    assert isinstance(msg, wire.InvokeRequest)
    assert (msg.function, msg.payload, msg.task_id, msg.attempt) == \
        ("fn_abc", b"\x00payload", 7, 2)


def test_wire_result_roundtrip():
    frame = wire.encode_result(b"blob", stats={"compute_s": 0.5},
                               server_s=0.7, cold_start=True, worker_id=42)
    msg = wire.decode(frame)
    assert isinstance(msg, wire.ResultReply)
    assert msg.blob == b"blob" and msg.worker_id == 42 and msg.cold_start
    assert msg.stats["compute_s"] == 0.5 and msg.server_s == 0.7


def test_wire_error_roundtrip_and_reconstruction():
    try:
        raise ValueError("kaboom")
    except ValueError as e:
        frame = wire.encode_error(e, traceback_text="Traceback ... kaboom")
    msg = wire.decode(frame)
    assert isinstance(msg, wire.ErrorReply) and not msg.retryable
    exc = wire.to_exception(msg)
    assert isinstance(exc, ValueError) and str(exc) == "kaboom"
    assert "kaboom" in exc.remote_traceback


def test_wire_unknown_exception_type_becomes_remote_task_error():
    msg = wire.decode(wire.encode_error(etype="WeirdCustomError",
                                        message="m", retryable=False))
    exc = wire.to_exception(msg)
    assert isinstance(exc, wire.RemoteTaskError)
    assert "WeirdCustomError" in str(exc)


def test_wire_malformed_frames_raise():
    good = wire.encode_invoke("f", b"x")
    for bad in (b"", b"shrt", b"XXXX" + good[4:],           # magic
                good[:4] + b"\xff\xff" + good[6:],          # version
                good[:11],                                  # truncated header
                good[:6] + bytes([99]) + good[7:]):         # unknown kind
        with pytest.raises(wire.WireProtocolError):
            wire.decode(bad)


# -------------------------------------------------------------- codeship ----

def test_freeze_importable_function_ships_by_reference():
    frozen = freeze_function(task_square_sum)
    assert frozen["kind"] == "ref"
    assert thaw_function(frozen) is task_square_sum


def test_freeze_closure_ships_code_with_payload_slots():
    scale = 3.0                      # data capture: travels in payloads
    fn = lambda x: scale * x         # noqa: E731
    frozen = freeze_function(fn)
    assert frozen["kind"] == "code"
    assert frozen["freevars"] == {"scale": None}
    thawed = thaw_function(frozen)
    from repro.core import rebind
    assert rebind(thawed, {"scale": 5.0})(2.0) == 10.0


def test_freeze_callable_capture_travels_with_artifact():
    def helper(x):
        return x + 1

    fn = lambda x: helper(x) * 2     # noqa: E731
    thawed = thaw_function(freeze_function(fn))
    assert thawed(3) == 8            # helper code came along


def test_freeze_main_module_gets_fresh_globals():
    def script_fn(x):
        import math
        return math.sqrt(x)

    script_fn.__module__ = "__main__"
    script_fn.__qualname__ = "script_fn"
    thawed = thaw_function(freeze_function(script_fn))
    assert thawed(16.0) == 4.0


def test_thaw_missing_artifact_raises():
    with pytest.raises(CodeShipError):
        thaw_function(None)


# ----------------------------------------------------------- worker host ----

@pytest.fixture
def manifest_deployment(tmp_path):
    path = str(tmp_path / "manifest.json")
    return path, Deployment(manifest_path=path)


def _pack_invoke(dep, fn, *args, jax_traceable=True):
    deployed = dep.deploy(RemoteFunction(fn, jax_traceable=jax_traceable),
                          *args)
    payload = deployed.bridge.pack(args, {}, {})
    return deployed, wire.encode_invoke(deployed.name, payload, task_id=1)


def test_worker_host_rebuilds_bridge_from_manifest(manifest_deployment):
    path, dep = manifest_deployment
    deployed, frame = _pack_invoke(dep, task_square_sum, jnp.ones(4))
    host = WorkerHost(path)          # fresh host: only the manifest in common
    msg = wire.decode(host.handle(frame))
    assert isinstance(msg, wire.ResultReply), msg
    assert msg.cold_start and msg.server_s > 0
    assert float(deployed.bridge.unpack_result(msg.blob)) == 4.0
    # warm on the second hit
    msg2 = wire.decode(host.handle(frame))
    assert isinstance(msg2, wire.ResultReply) and not msg2.cold_start


def test_worker_host_user_error_keeps_traceback(manifest_deployment):
    path, dep = manifest_deployment
    _, frame = _pack_invoke(dep, task_raise, 2, jax_traceable=False)
    msg = wire.decode(WorkerHost(path).handle(frame))
    assert isinstance(msg, wire.ErrorReply) and not msg.retryable
    assert msg.etype == "ValueError" and "bad input 2" in msg.message
    assert "task_raise" in msg.traceback


def test_worker_host_unknown_function_is_visible_error(tmp_path):
    host = WorkerHost(str(tmp_path / "missing.json"))
    msg = wire.decode(host.handle(wire.encode_invoke("ghost", b"")))
    assert isinstance(msg, wire.ErrorReply)
    assert "ghost" in msg.message and not msg.retryable


def test_worker_host_malformed_request_is_visible_error(tmp_path):
    host = WorkerHost(str(tmp_path / "missing.json"))
    msg = wire.decode(host.handle(b"not a frame at all"))
    assert isinstance(msg, wire.ErrorReply) and not msg.retryable


def test_worker_host_control_ping_and_drain(manifest_deployment):
    path, dep = manifest_deployment
    _, frame = _pack_invoke(dep, task_square_sum, jnp.ones(2))
    host = WorkerHost(path)
    pong = wire.decode(host.handle(wire.encode_control("ping")))
    assert isinstance(pong, wire.ControlRequest) and pong.op == "pong"
    host.handle(frame)
    drained = wire.decode(host.handle(wire.encode_control("drain")))
    assert drained.op == "drained" and drained.data["count"] == 1
    # post-drain invocations pay the cold start again
    msg = wire.decode(host.handle(frame))
    assert isinstance(msg, wire.ResultReply) and msg.cold_start


# ------------------------------------------------------------ sandbox host --

def test_sandbox_host_cold_warm_drain_accounting():
    host = SandboxHost()
    entry = lambda payload: (payload, type("S", (), {   # noqa: E731
        "deserialize_s": 0.0, "compute_s": 0.0, "serialize_s": 0.0})())
    first = host.invoke(entry, "f", b"x")
    second = host.invoke(entry, "f", b"x")
    assert first.cold_start and not second.cold_start
    assert first.worker_id == second.worker_id          # warm reuse
    assert host.drain() == 1
    assert host.invoke(entry, "f", b"x").cold_start     # drained → cold


def test_sandbox_host_fault_injection_burns_sandbox():
    host = SandboxHost(FaultPlan(failure_rate=1.0, seed=1))
    with pytest.raises(WorkerCrash):
        host.invoke(lambda p: (p, None), "f", b"x", task_id=0, attempt=1)
    assert host.live_instances == 0


# ------------------------------------------- processes backend error paths --

@pytest.fixture(scope="module")
def proc_session():
    with Session("processes", os_threads=1) as sess:
        yield sess


def test_processes_user_error_surfaces_with_remote_traceback(proc_session):
    f = proc_session.function(task_raise, jax_traceable=False)
    with pytest.raises(ValueError, match="bad input 2") as ei:
        f.submit(2).result(timeout=300)
    assert "task_raise" in ei.value.remote_traceback


def test_processes_dead_worker_is_retryable_not_hung(proc_session):
    """The satellite regression: a worker that dies mid-request must surface
    as a retryable invocation error (WorkerCrash), never a hung future."""
    f = proc_session.function(task_hard_exit, jax_traceable=False,
                              max_retries=0)
    t0 = time.monotonic()
    with pytest.raises(WorkerCrash, match="died mid-request"):
        f.submit(0).result(timeout=300)
    assert time.monotonic() - t0 < 120
    # the slot respawns: the session keeps serving afterwards
    g = proc_session.function(task_square_sum, name="after_crash")
    assert float(g.submit(jnp.ones(3)).result(timeout=300)) == 3.0


def test_processes_base_exception_carries_original_traceback(proc_session):
    f = proc_session.function(task_base_exception, jax_traceable=False,
                              max_retries=0)
    with pytest.raises(WorkerCrash) as ei:
        f.submit(0).result(timeout=300)
    assert "SystemExit" in getattr(ei.value, "remote_traceback", "")


def test_processes_dead_worker_retry_can_succeed():
    """A crash on attempt 1 is retried on a fresh worker and succeeds."""
    with Session("processes", os_threads=1) as sess:
        marker = os.path.join(os.path.dirname(__file__), "..",
                              f".crash-once-{os.getpid()}")
        f = sess.function(task_crash_once, jax_traceable=False, max_retries=2)
        try:
            assert f.submit(marker).result(timeout=300) == "survived"
        finally:
            if os.path.exists(marker):
                os.unlink(marker)


def task_crash_once(marker):
    if not os.path.exists(marker):
        open(marker, "w").close()
        os._exit(1)            # first attempt: die mid-request
    return "survived"


# ------------------------------------------------- http: in-test worker -----

def test_http_backend_against_in_test_worker(tmp_path):
    """The paper's client model with the worker under test control: an
    in-process http.server thread serving the same manifest the session
    deploys into; records must carry *measured* latency."""
    path = str(tmp_path / "manifest.json")
    dep = Deployment(manifest_path=path)
    server = serve_http(path, port=0)
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        backend = HttpBackend(url=f"http://127.0.0.1:{port}",
                              manifest_path=path, os_threads=2)
        with Session(backend, deployment=dep) as sess:
            f = sess.function(task_square_sum, name="http_ssq", memory_mb=512)
            out = [float(v) for v in f.map([(jnp.ones(4) * i,)
                                            for i in range(4)])]
            assert out == [0.0, 4.0, 16.0, 36.0]
            assert all(r.latency_measured for r in sess.records)
            assert all(r.modeled_latency_ms > 0 for r in sess.records)
            assert any(r.cold_start for r in sess.records)
            assert sess.cost.invocations == 4
        backend.shutdown()
    finally:
        server.shutdown()
        server.server_close()


def test_http_worker_gone_is_retryable_error(tmp_path):
    """A vanished fleet (connection refused) surfaces as a retryable
    WorkerCrash, never a hung future."""
    path = str(tmp_path / "manifest.json")
    dep = Deployment(manifest_path=path)
    # grab a port that nothing listens on
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead_port = s.getsockname()[1]
    s.close()
    backend = HttpBackend(url=f"http://127.0.0.1:{dead_port}",
                          manifest_path=path, os_threads=1)
    with Session(backend, deployment=dep) as sess:
        f = sess.function(task_square_sum, name="gone_ssq", max_retries=0)
        t0 = time.monotonic()
        with pytest.raises(WorkerCrash):
            f.submit(jnp.ones(2)).result(timeout=300)
        assert time.monotonic() - t0 < 120
    backend.shutdown()


# ------------------------------- worker-resident state + affinity (ISSUE 5) --

def task_pid(x):
    return os.getpid()


def task_state_note(handle, value):
    from repro.runtime import state
    data = state.lease(handle, ttl_s=60.0, make=dict)
    data["value"] = value
    return sorted(state.stats()["handles"])


def task_state_read(handle):
    from repro.runtime import state
    return state.get(handle)["value"]


def test_wire_control_frame_carries_body():
    frame = wire.encode_control("artifact_put", body=b"\x00blob", sha="abc")
    msg = wire.decode(frame)
    assert isinstance(msg, wire.ControlRequest)
    assert msg.op == "artifact_put"
    assert msg.data == {"sha": "abc"} and msg.body == b"\x00blob"


def test_affinity_pins_invocations_to_one_worker():
    """Invocations sharing an affinity key land on one worker process
    across calls (the resident-state prerequisite); the pin survives
    interleaved anonymous traffic on the same backend."""
    with Session("processes", os_threads=2) as sess:
        pinned = sess.function(task_pid, name="pid_pinned",
                               jax_traceable=False, affinity=0)
        free = sess.function(task_pid, name="pid_free", jax_traceable=False)
        pids = [pinned.submit(i).result(timeout=300) for i in range(4)]
        free.submit(0).result(timeout=300)
        pids.append(pinned.submit(9).result(timeout=300))
        assert len(set(pids)) == 1


def test_state_survives_across_invocations_and_control_release():
    """A lease written by one invocation is readable by the next (same
    affinity ⇒ same worker), visible to state_stats, and gone after the
    CONTROL state_release — the wire half of the state-lease op."""
    with Session("processes", os_threads=2) as sess:
        note = sess.function(task_state_note, jax_traceable=False, affinity=3)
        read = sess.function(task_state_read, jax_traceable=False, affinity=3)
        handles = note.submit("h-trans", 42).result(timeout=300)
        assert "h-trans" in handles
        assert read.submit("h-trans").result(timeout=300) == 42
        stats = sess.backend.state_control(3, "state_stats")
        assert "h-trans" in stats["handles"]
        out = sess.backend.state_control(3, "state_release",
                                         handle="h-trans")
        assert out["released"] is True
        with pytest.raises(KeyError, match="state handle"):
            read.submit("h-trans").result(timeout=300)


def task_artifact_sum(tree):
    import numpy as np
    return float(np.sum(tree["a"]))


def test_artifact_missing_on_worker_is_fetched_from_client():
    """Remote artifact fetch (ROADMAP satellite): the store file vanishes
    before a fresh worker resolves the ref — the worker reports
    ArtifactMissing, the client pushes the blob over a CONTROL frame, the
    invocation replays and succeeds, and the blob is re-deposited."""
    import numpy as np

    from repro.serialization import put_artifact, release_artifact

    value = {"a": np.arange(7, dtype=np.float32)}
    ref = put_artifact(value)
    try:
        os.unlink(ref.path)            # no shared file: only the client
        assert not os.path.exists(ref.path)  # has it (in-memory cache)
        with Session("processes", os_threads=1) as sess:
            f = sess.function(task_artifact_sum, jax_traceable=False)
            assert f.submit(ref).result(timeout=300) == 21.0
            # warm path: resolved from the worker's process cache now
            assert f.submit(ref).result(timeout=300) == 21.0
        assert os.path.exists(ref.path)      # fetched blob was deposited
    finally:
        release_artifact(ref)
        if os.path.exists(ref.path):
            os.unlink(ref.path)
