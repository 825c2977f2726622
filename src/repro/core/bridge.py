"""Bridge classes — the alternative entry points (paper §3.1–§3.2, Fig 4).

A Cppless bridge connects a user function object to a separately-compiled
entry point: the cloud side deserializes the payload, reconstructs the
function object, runs it, and serializes the result.  Here the "separate
compilation path" is JAX AOT (``jit(...).lower(avals).compile()``) against the
*target* device topology, and ``entry(payload: bytes) -> bytes`` is the
executable surface a worker sandbox sees — nothing else crosses the wire.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax

from ..serialization import deserialize, serialize
from .config import FunctionConfig
from .function import RemoteFunction, rebind, reflect_captures


@dataclass
class EntryStats:
    """Per-invocation server-side accounting (drives GB-s billing)."""
    deserialize_s: float = 0.0
    compute_s: float = 0.0
    serialize_s: float = 0.0

    @property
    def total_s(self) -> float:
        return self.deserialize_s + self.compute_s + self.serialize_s


@dataclass
class Bridge:
    """A deployed alternative entry point."""
    name: str
    config: FunctionConfig
    # executor(args, kwargs, captures) -> result; already specialized/compiled.
    executor: Callable[..., Any]
    kind: str = "aot_xla"  # or "generic_worker" for non-traceable tasks
    # Last-completed stats, best-effort observability only: one bridge may be
    # entered concurrently (warm sandboxes of the same function), so per-
    # invocation accounting must use the stats *returned* by ``entry``.
    last_stats: EntryStats = field(default_factory=EntryStats)

    def pack(self, args: tuple, kwargs: dict, captures: dict) -> bytes:
        return serialize((args, kwargs, captures), format=self.config.serializer)

    def entry(self, payload: bytes) -> tuple[bytes, EntryStats]:
        """The remote main(): bytes in, (bytes, stats) out (paper Fig 4).

        Stats are returned (not only stored) so concurrent invocations of
        the same deployed function cannot corrupt each other's accounting.
        """
        stats = EntryStats()
        t0 = time.perf_counter()
        args, kwargs, captures = deserialize(payload)
        t1 = time.perf_counter()
        out = self.executor(args, kwargs, captures)
        out = jax.block_until_ready(out)
        t2 = time.perf_counter()
        blob = serialize(out, format=self.config.serializer)
        t3 = time.perf_counter()
        stats.deserialize_s, stats.compute_s, stats.serialize_s = (
            t1 - t0, t2 - t1, t3 - t2)
        self.last_stats = stats
        return blob, stats

    def unpack_result(self, blob: bytes) -> Any:
        return deserialize(blob, format=self.config.serializer)


_STATIC_TYPES = (bool, int, float, str, bytes)

# What tracing raises when a task is plain Python rather than JAX code: such
# a task deploys to the generic worker.  Every other error of the AOT path —
# a bug in the task, a lowering or compiler refusal — propagates.
NOT_TRACEABLE = (jax.errors.ConcretizationTypeError,
                 jax.errors.TracerArrayConversionError,
                 jax.errors.TracerIntegerConversionError)


def _is_static_capture(v: Any) -> bool:
    """Compile-time constant vs. dynamic payload input.

    Python scalars and any *hashable* structured value (frozen dataclasses
    like ``ModelConfig``, tuples of scalars) are template-parameter-like:
    their values determine shapes/control flow, so they bake into the
    traced jaxpr.  Arrays (jax/numpy, including numpy scalars) stay
    dynamic — they are the data the payload exists to carry.
    """
    if isinstance(v, _STATIC_TYPES):
        return True
    import numpy as np
    if isinstance(v, (np.ndarray, np.generic)) or \
            type(v).__module__.startswith("jax"):
        return False
    try:
        hash(v)
    except TypeError:
        return False
    return True


def make_executor_aot(rf: RemoteFunction, args: tuple, kwargs: dict,
                      captures: dict) -> Callable:
    """AOT path: lower+compile once against abstract payloads.

    The compile happens at *deploy* time (ahead of any invocation) — the
    defining property of Cppless's alternative entry points vs. runtime
    code shipping (Lithops).

    Python-scalar and hashable structured captures (frozen dataclasses
    like ``ModelConfig``) are **compile-time constants** (the analogue of
    Cppless's template parameters): they are rebound into the closure
    BEFORE tracing, so `range(n)`/`arange(tile)`/`build_model(cfg)`-style
    uses stay static.  Leaving them as traced inputs would raise on any
    shape-determining use and silently demote the function to the eager
    generic worker — measured ~250x slower on the raytracer tiles, ~60x
    on the LM serve task.  Array captures remain dynamic payload inputs.
    Changed static values change the traced jaxpr, hence the stable name,
    hence deploy a new entry point — the correct Cppless semantics.
    """
    # example payloads may carry ArtifactRefs in place of large constants;
    # specialization needs the real arrays (shapes drive the lowering)
    from ..serialization import resolve_artifacts
    args = resolve_artifacts(args)
    kwargs = resolve_artifacts(kwargs)
    captures = resolve_artifacts(captures)

    static = {k: v for k, v in captures.items() if _is_static_capture(v)}
    dynamic = {k: v for k, v in captures.items() if k not in static}
    base_fn = rebind(rf.fn, static) if static else rf.fn

    def with_payload(args_, kwargs_, dyn_):
        fn = rebind(base_fn, dyn_) if dyn_ else base_fn
        return fn(*args_, **kwargs_)

    try:
        lowered = jax.jit(with_payload).lower(args, kwargs, dynamic)
    except TypeError as e:      # e.g. a str return value: not JAX code
        e.add_note(f"deploying {rf.human_name!r} as JAX code failed; a task "
                   f"that is not JAX code is bound with jax_traceable=False")
        raise
    compiled = lowered.compile()
    dyn_keys = tuple(dynamic)

    def executor(args_, kwargs_, captures_):
        dyn = {k: captures_[k] for k in dyn_keys}
        return compiled(args_, kwargs_, dyn)

    executor.lowered = lowered
    executor.compiled = compiled
    return executor


def make_executor_generic(rf: RemoteFunction) -> Callable:
    """Generic-worker path for non-jax tasks (numpy / pure python).

    Mirrors the Lithops model the paper contrasts with: the worker rebinds
    captures and runs the python callable directly.
    """
    def executor(args_, kwargs_, captures_):
        fn = rebind(rf.fn, captures_) if captures_ else rf.fn
        return fn(*args_, **kwargs_)

    return executor
