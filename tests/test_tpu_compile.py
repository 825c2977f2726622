"""Ahead-of-time compiles of the served attention kernels for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described topology.
Interpret-mode tests cannot see what Mosaic refuses (block shapes, vector
layouts), so each kernel of the serving path is compiled here at the
published widths of smollm-360m (GQA 15/5, head_dim 64) and qwen1.5-4b
(MHA 20/20, head_dim 128), through the same ops the models call.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_paged)
from repro.kernels.flash_attention import attention

WIDTHS = {"smollm-360m": (15, 5, 64), "qwen1.5-4b": (20, 20, 128)}
BATCH, PROMPT, CACHE = 8, 256, 512
BLOCK, TABLE, LAYERS = 16, 32, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs go to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip of the described host, with the persistent compilation
    cache off: entries written for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _prefill(hq, hkv, d):
    def fn(q, k, v, start):
        return attention(q, k, v, causal=True, kv_start=start, impl="pallas")
    return fn, [(BATCH, PROMPT, hq, d), (BATCH, PROMPT, hkv, d),
                (BATCH, PROMPT, hkv, d), ((BATCH,), jnp.int32)]


def _decode(hq, hkv, d):
    def fn(q, k, v, kv_len, start):
        return decode_attention(q, k, v, kv_len, kv_start=start,
                                impl="pallas")
    return fn, [(BATCH, hq, d), (BATCH, CACHE, hkv, d), (BATCH, CACHE, hkv, d),
                ((BATCH,), jnp.int32), ((BATCH,), jnp.int32)]


def _paged(hq, hkv, d):
    nb = 1 + BATCH * TABLE

    def fn(q, k_pool, v_pool, table, kv_len, layer):
        return decode_attention_paged(q, k_pool, v_pool, table, kv_len,
                                      layer, impl="pallas")
    return fn, [(BATCH, hq, d), (LAYERS, nb, hkv, BLOCK, d),
                (LAYERS, nb, hkv, BLOCK, d), ((BATCH, TABLE), jnp.int32),
                ((BATCH,), jnp.int32), ((), jnp.int32)]


KERNELS = {"flash_attention": _prefill, "flash_decode": _decode,
           "flash_decode_paged": _paged}


@pytest.mark.parametrize("arch", tuple(WIDTHS))
@pytest.mark.parametrize("kernel", tuple(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, arch):
    fn, shapes = KERNELS[kernel](*WIDTHS[arch])
    args = [jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            if isinstance(s[0], tuple)
            else jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------ paged step programs, whole-pool moves --
# The engine's paged decode and chunk programs carry the stacked KV pool
# through their layer loop.  Compiled for the chip at each served width
# (2 layers, a few rows, the cells' block size and row capacity), no op
# inside a loop body may produce a buffer of the whole pool's size or of
# one layer of it — a slice, a stack, a relayout or a copy of the pool —
# except the in-place update of the carried pool itself.

POOL_CELLS = {"smollm-360m": 2048, "qwen1.5-4b": 1024}   # per-row capacity
POOL_ROWS, POOL_BLOCK, POOL_LAYERS, QUANTUM, CHUNK = 4, 64, 2, 8, 32
_PASS_THROUGH = ("parameter", "get-tuple-element", "bitcast")
_IN_PLACE = ("fusion", "scatter", "dynamic-update-slice")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\][^ ]* "
                    r"([\w\-]+)\((.*)")


def _elements(dims: str) -> int:
    return math.prod(int(d) for d in dims.split(",") if d)


def _loop_bodies(hlo: str) -> dict[str, list]:
    """{while-body computation: [(name, elements, opcode, rest of line)]}."""
    names = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    bodies, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^%?([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1) if head.group(1) in names else None
            if comp:
                bodies[comp] = []
            continue
        m = _INSTR.match(line) if comp else None
        if m:
            name, _, dims, op, rest = m.groups()
            bodies[comp].append((name, _elements(dims), op, rest))
    return bodies


def _pool_moves(hlo: str, pool: int, layer: int) -> list[str]:
    """Ops inside a loop body whose result holds the pool or one layer."""
    moves = []
    for comp, instrs in _loop_bodies(hlo).items():
        size = {n: e for n, e, _, _ in instrs}
        for name, n, op, rest in instrs:
            if n not in (pool, layer) or op in _PASS_THROUGH:
                continue
            big = [o for o in re.findall(r"%([\w.\-]+)", rest)
                   if size.get(o, 0) >= layer]
            if op in _IN_PLACE and n == pool and len(big) == 1:
                continue                 # the carried pool, updated in place
            moves.append(f"{comp}: {name} = {op}, {n} elements")
    return moves


def _paged_program(arch, program, one_chip):
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.api import paged_init_pool
    from repro.runtime import engine

    cfg = get_config(arch).replace(n_layers=POOL_LAYERS, attn_impl="pallas")
    table_w = POOL_CELLS[arch] // POOL_BLOCK
    blocks = 1 + POOL_ROWS * table_w

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def ints(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = placed(jax.eval_shape(
        lambda: build_model(cfg).init(jax.random.PRNGKey(0))[0]))
    pool = placed(jax.eval_shape(
        lambda: paged_init_pool(cfg, blocks, POOL_BLOCK)))
    if program == "decode":
        low = engine._paged_decode_fn(cfg, QUANTUM).lower(
            params, pool["k"], pool["v"], ints(POOL_ROWS, table_w),
            ints(POOL_ROWS), ints(POOL_ROWS, dtype=bool), ints(POOL_ROWS))
    else:
        low = engine._paged_chunk_fn(cfg, CHUNK).lower(
            params, pool["k"], pool["v"], ints(1, CHUNK), ints(1, table_w),
            ints(), ints())
    layer = math.prod(pool["k"].shape[1:])
    return low.compile().as_text(), math.prod(pool["k"].shape), layer


@pytest.mark.parametrize("arch", tuple(POOL_CELLS))
@pytest.mark.parametrize("program", ("decode", "chunk"))
def test_paged_programs_keep_the_pool_in_place(one_chip, arch, program):
    hlo, pool, layer = _paged_program(arch, program, one_chip)
    bodies = _loop_bodies(hlo)
    assert bodies, "the layer loop compiled away: nothing to check"
    assert not _pool_moves(hlo, pool, layer), _pool_moves(hlo, pool, layer)
    if program == "decode":            # the kernel runs inside the loop
        assert any("tpu_custom_call" in rest for instrs in bodies.values()
                   for *_, rest in instrs)
