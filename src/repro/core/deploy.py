"""Deployment: turn RemoteFunctions into invocable cloud artifacts.

The Cppless flow (paper Fig 5): compile alternative entry points → emit
manifest → deployment tool creates/updates cloud functions, *only if a code
change is detected*.  Our flow: specialize the function on abstract payloads,
AOT lower+compile (the separate compilation path), register the Bridge under
its content-addressed stable name, and record it in the manifest.  A repeat
deploy of an unchanged function is a cache hit — no recompilation.
"""
from __future__ import annotations

import json
import threading
import time
import warnings
import weakref
from dataclasses import dataclass
from typing import Any, Callable

import jax

from .bridge import (NOT_TRACEABLE, Bridge, make_executor_aot,
                     make_executor_generic)
from .codeship import freeze_function
from .config import DEFAULT_CONFIG, FunctionConfig
from .function import RemoteFunction, data_captures
from .manifest import Manifest, ManifestEntry


@dataclass
class DeployedFunction:
    name: str
    bridge: Bridge
    remote_fn: RemoteFunction
    entry_args: tuple          # example (args, kwargs, captures) for shape ref
    compile_s: float = 0.0
    # Deploy-time shippability diagnostics (repro.analysis).  A tuple —
    # possibly empty — once analysis ran; None if the analyzer itself
    # failed, in which case the failure-hint path re-analyzes on demand.
    diagnostics: tuple | None = ()

    @property
    def config(self) -> FunctionConfig:
        return self.bridge.config


class Deployment:
    """Artifact store + manifest; the `aws_lambda_serverless_target` analogue."""

    def __init__(self, manifest_path: str | None = None):
        self.manifest = Manifest(manifest_path)
        self._functions: dict[str, DeployedFunction] = {}
        self.compile_count = 0   # observability: redeploy-on-change works
        self.cache_hits = 0
        # async serving submits from executor threads: concurrent deploys
        # of the same function must compile once, not race the cache
        self._lock = threading.RLock()
        # Shippability analysis (repro.analysis) runs on every cache-miss
        # deploy.  strict_analysis upgrades error-severity findings to an
        # AnalysisError *before* anything ships; the dispatcher flips
        # analysis_cross_process off for in-process backends so RF101
        # (fresh-globals NameError) reports as info, not error.
        self.strict_analysis = False
        self.analysis_cross_process = True
        self._warned: set[str] = set()
        # dispatch-path fast cache: content identity (stable_name) traces
        # the function, which costs ~100 ms for a real serve task — per
        # SUBMIT.  Repeat dispatches hit this shape/value key instead and
        # never re-trace; anything the AOT path would bake differently
        # (arg shapes/dtypes, scalar values, static captures, billing
        # config) is part of the key, so a fast hit is always the same
        # entry point the slow path would have chosen.
        self._fast_cache: dict[Any, DeployedFunction] = {}

    # ------------------------------------------------------------------ api
    def deploy(self, fn: Callable | RemoteFunction, *example_args: Any,
               config: FunctionConfig | None = None,
               **example_kwargs: Any) -> DeployedFunction:
        rf = fn if isinstance(fn, RemoteFunction) else RemoteFunction(fn)
        cfg = config or rf.config
        captures = data_captures(rf.fn)
        payload = (example_args, example_kwargs, captures)

        key = self._fast_key(rf, cfg, example_args, example_kwargs)
        if key is not None:
            with self._lock:
                hit = self._fast_cache.get(key)
                # the key carries id(fn): guard against a dead function
                # object's id being reused by different code
                if hit is not None and hit[0]() is rf.fn:
                    self.cache_hits += 1
                    return hit[1]

        # Artifact/billing config is part of the function's type (Cppless:
        # compile-time template metadata), so it salts the deployed name:
        # same code with different memory/serializer is a *different* cloud
        # function — this is what makes `.options()` overrides take effect.
        # Pure client policy (timeout, retries, hedging) travels with each
        # invocation instead, so overriding it never forces a redeploy.
        cfg_d = cfg.to_json()
        salt = json.dumps({k: cfg_d[k] for k in
                           ("memory_mb", "ephemeral_mb", "serializer")},
                          sort_keys=True)
        name = rf.stable_name(*example_args, salt=salt, **example_kwargs)
        with self._lock:
            deployed = self._deploy_locked(rf, cfg, payload, name)
            if key is not None:
                # bounded: scalar arg values are part of the key, so an
                # argument sweep would otherwise grow this forever
                while len(self._fast_cache) >= 4096:
                    self._fast_cache.pop(next(iter(self._fast_cache)))
                self._fast_cache[key] = (weakref.ref(rf.fn), deployed)
            return deployed

    def _fast_key(self, rf: RemoteFunction, cfg: FunctionConfig,
                  args: tuple, kwargs: dict):
        """Hashable dispatch-cache key, or ``None`` to use the slow path.

        Components mirror exactly what changes the deployed entry point:
        the function object, artifact/billing config (the name salt), arg
        *shapes* (arrays trace shape-generically) and scalar arg values,
        plus non-callable capture values — static captures bake into the
        jaxpr, array captures contribute shape.  ``ArtifactRef`` leaves key
        by content hash, so a repeat params pointer never loads the value.
        """
        try:
            import jax

            from ..serialization.artifacts import ArtifactRef

            weakref.ref(rf.fn)     # non-weakrefable callable → slow path

            def leaf_sig(v: Any):
                if isinstance(v, ArtifactRef):
                    return ("artifact", v.sha)
                if hasattr(v, "shape") and hasattr(v, "dtype"):
                    return ("array", tuple(v.shape), str(v.dtype))
                return ("value", type(v).__name__, v)

            leaves, treedef = jax.tree_util.tree_flatten(
                (args, kwargs), is_leaf=lambda x: isinstance(x, ArtifactRef))
            caps = (data_captures(rf.fn) if rf.fn.__closure__ else {})
            key = (id(rf.fn), rf.human_name, rf.jax_traceable,
                   cfg.memory_mb, cfg.ephemeral_mb, cfg.serializer,
                   treedef, tuple(leaf_sig(v) for v in leaves),
                   tuple((k, leaf_sig(v)) for k, v in sorted(caps.items())))
            hash(key)                  # unhashable component → slow path
            return key
        except Exception:
            return None

    def _deploy_locked(self, rf: RemoteFunction, cfg: FunctionConfig,
                       payload: tuple, name: str) -> DeployedFunction:
        if name in self._functions:
            self.cache_hits += 1          # unchanged code → no redeploy
            return self._functions[name]

        # Compile-time validation before anything ships (Cppless: the LLVM
        # extension rejects un-extractable lambdas at build time).  Strict
        # mode raises here — before AOT compile, before the manifest entry.
        diagnostics = self._analyze(rf, cfg, name)

        t0 = time.perf_counter()
        kind = "generic_worker"
        if rf.jax_traceable:
            try:
                executor = make_executor_aot(rf, *payload)
                kind = "aot_xla"
            except NOT_TRACEABLE:
                executor = make_executor_generic(rf)
        else:
            executor = make_executor_generic(rf)
        compile_s = time.perf_counter() - t0
        self.compile_count += 1

        bridge = Bridge(name=name, config=cfg, executor=executor, kind=kind)
        deployed = DeployedFunction(name=name, bridge=bridge, remote_fn=rf,
                                    entry_args=payload, compile_s=compile_s,
                                    diagnostics=diagnostics)
        self._functions[name] = deployed

        in_avals, out_avals = self._aval_strings(rf, payload, kind, executor)
        try:
            code = freeze_function(rf.fn)
        except Exception:
            code = None        # local-only function: in-process backends fine
        self.manifest.add(ManifestEntry(
            name=name, human_name=rf.human_name, kind=kind, config=cfg,
            in_avals=in_avals, out_avals=out_avals, artifact=name, code=code))
        return deployed

    def _analyze(self, rf: RemoteFunction, cfg: FunctionConfig,
                 name: str) -> tuple | None:
        """Run the shippability pass; gate on strictness; warn once.

        Returns the diagnostic tuple stored on the DeployedFunction (used
        later by the transport failure-hint path), or ``None`` if the
        analyzer itself crashed — analysis must never take down a deploy
        except through its own strict-mode contract.
        """
        from ..analysis import (AnalysisError, ShippabilityWarning,
                                analyze_function)
        try:
            diags = tuple(analyze_function(
                rf.fn, name=rf.human_name,
                cross_process=self.analysis_cross_process))
        except AnalysisError:
            raise
        except Exception:
            return None
        errors = [d for d in diags if d.severity == "error"]
        if errors and (cfg.strict or self.strict_analysis):
            raise AnalysisError(rf.human_name, errors)
        loud = [d for d in diags if d.severity in ("error", "warning")]
        if loud and name not in self._warned:
            self._warned.add(name)
            lines = "\n".join("  " + d.format() for d in loud)
            warnings.warn(
                f"shippability analysis of {rf.human_name!r} found "
                f"{len(loud)} issue(s):\n{lines}",
                ShippabilityWarning, stacklevel=4)
        return diags

    def get(self, name: str) -> DeployedFunction:
        return self._functions[name]

    @property
    def functions(self) -> tuple[DeployedFunction, ...]:
        """Every function deployed so far."""
        with self._lock:
            return tuple(self._functions.values())

    def __contains__(self, name: str) -> bool:
        return name in self._functions

    # -------------------------------------------------------------- helpers
    @staticmethod
    def _aval_strings(rf, payload, kind, executor):
        if kind != "aot_xla":
            return [], []
        try:
            lowered = executor.lowered
            in_avals = [str(a) for a in jax.tree_util.tree_leaves(
                jax.eval_shape(lambda *p: p, *payload))]
            out_info = lowered.out_info
            out_avals = [f"{v.shape}:{v.dtype}"
                         for v in jax.tree_util.tree_leaves(out_info)]
            return in_avals, out_avals
        except Exception:
            return [], []
