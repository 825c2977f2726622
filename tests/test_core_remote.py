"""Core remote-function layer tests (paper §3–§4)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (Deployment, FunctionConfig, RemoteFunction,
                        data_captures, rebind, reflect_captures, remote,
                        stable_name)
from repro.core.naming import canonicalize_jaxpr_text, mangle


# ------------------------------------------------------------- reflection ---

def make_closure(n, scale):
    def task(x):
        return jnp.sum(x * scale) + n
    return task


def test_reflect_captures_reads_cells():
    t = make_closure(7, 2.0)
    caps = reflect_captures(t)
    assert caps == {"n": 7, "scale": 2.0}


def test_rebind_replaces_captures():
    t = make_closure(7, 2.0)
    t2 = rebind(t, {"n": 100, "scale": 1.0})
    x = jnp.ones(3)
    assert float(t2(x)) == pytest.approx(103.0)
    # original untouched (value semantics, like serialized C++ captures)
    assert float(t(x)) == pytest.approx(13.0)


def test_rebind_partial_keeps_code_captures():
    def helper(x):
        return x * 3

    def outer():
        h = helper

        def task(x):
            return h(x) + k
        k = 5
        return task

    t = outer()
    caps = data_captures(t)
    assert set(caps) == {"k"}          # helper is a code capture, not data
    t2 = rebind(t, {"k": 10})
    assert float(t2(jnp.float32(2))) == pytest.approx(16.0)


# ------------------------------------------------------------------ naming --

def test_stable_name_deterministic_across_instances():
    a = make_closure(7, 2.0)
    b = make_closure(7, 2.0)   # distinct closure objects, same code
    x = jnp.zeros((4,), jnp.float32)
    na = stable_name(a, x)
    nb = stable_name(b, x)
    assert na == nb
    assert na.startswith("_ZRF")


def test_stable_name_changes_with_code():
    x = jnp.zeros((4,), jnp.float32)
    n1 = stable_name(lambda v: jnp.sum(v), x, human_name="f")
    n2 = stable_name(lambda v: jnp.prod(v), x, human_name="f")
    assert n1 != n2


def test_stable_name_changes_with_shape():
    f = lambda v: jnp.sum(v)  # noqa: E731
    n1 = stable_name(f, jnp.zeros((4,), jnp.float32))
    n2 = stable_name(f, jnp.zeros((8,), jnp.float32))
    assert n1 != n2


def test_canonicalization_strips_incidental_detail():
    t1 = canonicalize_jaxpr_text("a:f32[4] <function f at 0xdeadbeef>  /tmp/x.py:12")
    t2 = canonicalize_jaxpr_text("a:f32[4] <function f at 0xcafebabe> /home/y.py:99")
    assert t1 == t2


def test_mangle_is_cloud_safe():
    n = mangle("my task!! με unicode", "ab" * 32)
    assert all(c.isalnum() or c == "_" for c in n)


# ------------------------------------------------------------- deployment ---

def test_deploy_and_invoke_roundtrip():
    dep = Deployment()
    n = 1000

    @remote
    def estimate(x):
        return jnp.mean(x) * n

    d = dep.deploy(estimate, jnp.arange(8, dtype=jnp.float32))
    payload = d.bridge.pack((jnp.arange(8, dtype=jnp.float32),), {},
                            data_captures(estimate.fn))
    blob, stats = d.bridge.entry(payload)
    out = d.bridge.unpack_result(blob)
    assert float(np.asarray(out)) == pytest.approx(3500.0)
    assert d.bridge.kind == "aot_xla"
    assert stats.total_s > 0
    assert d.bridge.last_stats.total_s == stats.total_s


def test_deploy_dedup_no_recompile():
    dep = Deployment()
    x = jnp.ones((16,), jnp.float32)

    def task(v):
        return v * 2

    dep.deploy(task, x)
    assert dep.compile_count == 1
    dep.deploy(task, x)                 # unchanged → cache hit
    assert dep.compile_count == 1
    assert dep.cache_hits == 1

    def task2(v):
        return v * 3                    # code change → redeploy

    dep.deploy(task2, x)
    assert dep.compile_count == 2


def test_deploy_generic_worker_fallback():
    """Non-jax python tasks run via the generic-worker path (Lithops-style)."""
    dep = Deployment()

    def pytask(n):
        return sum(i * i for i in range(n))

    rf = RemoteFunction(pytask, jax_traceable=False)
    d = dep.deploy(rf, 10)
    blob, _ = d.bridge.entry(d.bridge.pack((10,), {}, {}))
    assert d.bridge.unpack_result(blob) == 285
    assert d.bridge.kind == "generic_worker"


def pallas_task(q):
    from repro.kernels.flash_attention import attention
    return attention(q, q, q, impl="pallas")


def host_only_task(x):
    return float(x.sum())


def test_deploy_surfaces_lowering_errors():
    """A traceable task whose lowering fails — here a compiled (not
    interpreted) Pallas kernel on the CPU — raises at deploy; it is never
    demoted to the eager generic worker."""
    with pytest.raises(ValueError, match="interpret mode"):
        Deployment().deploy(pallas_task, jnp.ones((1, 16, 2, 16)))


def str_task(x):
    return "done"


def test_deploy_non_jax_result_needs_jax_traceable_false():
    """A default (traceable) task whose result JAX cannot hold fails at
    deploy and names the switch that ships it to the generic worker."""
    with pytest.raises(TypeError, match="not a valid JAX type") as info:
        Deployment().deploy(str_task, jnp.ones(3))
    assert any("'str_task'" in n and "jax_traceable=False" in n
               for n in info.value.__notes__)
    d = Deployment().deploy(RemoteFunction(str_task, jax_traceable=False),
                            jnp.ones(3))
    assert d.bridge.kind == "generic_worker"


def test_deploy_untraceable_task_ships_generic():
    """Only tracing errors (a host conversion of a tracer) mean 'not JAX
    code' and route the task to the generic worker."""
    d = Deployment().deploy(host_only_task, jnp.ones(3))
    assert d.bridge.kind == "generic_worker"
    blob, _ = d.bridge.entry(d.bridge.pack((jnp.ones(3),), {}, {}))
    assert d.bridge.unpack_result(blob) == 3.0


def test_manifest_persists(tmp_path):
    mpath = str(tmp_path / "manifest.json")
    dep = Deployment(manifest_path=mpath)
    cfg = FunctionConfig().with_memory(512).with_ephemeral_storage(64)
    dep.deploy(RemoteFunction(lambda x: x + 1, name="inc", config=cfg),
               jnp.zeros((4,)))
    dep2 = Deployment(manifest_path=mpath)      # fresh process analogue
    assert len(dep2.manifest) == 1
    (entry,) = dep2.manifest.entries.values()
    assert entry.human_name == "inc"
    assert entry.config.memory_mb == 512
    assert entry.config.ephemeral_mb == 64
    assert entry.kind == "aot_xla"


def test_entry_stats_are_per_invocation():
    """Concurrent entries of one bridge must not share accounting: stats
    travel with the return value, not through a mutable attribute."""
    import threading
    import time

    dep = Deployment()

    def sleepy(s):
        time.sleep(s)
        return s

    d = dep.deploy(RemoteFunction(sleepy, jax_traceable=False), 0.01)
    out = {}

    def call(s):
        _, stats = d.bridge.entry(d.bridge.pack((s,), {}, {}))
        out[s] = stats.compute_s

    ts = [threading.Thread(target=call, args=(s,)) for s in (0.05, 0.3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # the fast call must see its own ~0.05 s, not the slow sibling's ~0.3 s
    assert out[0.05] < 0.2 < out[0.3]


def test_config_fluent_api_matches_paper_listing():
    cfg = (FunctionConfig()
           .with_memory(512)
           .with_ephemeral_storage(64))
    assert cfg.memory_mb == 512 and cfg.ephemeral_mb == 64
    assert cfg.memory_gb == 0.5


def test_captures_travel_in_payload():
    dep = Deployment()
    scale = np.float32(4.0)

    def task(x):
        return x * scale

    d = dep.deploy(task, jnp.ones((4,), jnp.float32))
    # invoke with *different* capture values — payload carries state
    blob, _ = d.bridge.entry(
        d.bridge.pack((jnp.ones((4,), jnp.float32),), {},
                      {"scale": np.float32(9.0)}))
    out = d.bridge.unpack_result(blob)
    assert np.allclose(np.asarray(out), 9.0)
