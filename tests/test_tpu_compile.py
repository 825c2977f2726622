"""Ahead-of-time compiles of the served attention kernels for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described topology.
Interpret-mode tests cannot see what Mosaic refuses (block shapes, vector
layouts), so each kernel of the serving path is compiled here at the
published widths of smollm-360m (GQA 15/5, head_dim 64) and qwen1.5-4b
(MHA 20/20, head_dim 128), through the same ops the models call.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_paged)
from repro.kernels.flash_attention import attention

WIDTHS = {"smollm-360m": (15, 5, 64), "qwen1.5-4b": (20, 20, 128)}
BATCH, PROMPT, CACHE = 8, 256, 512
BLOCK, TABLE = 16, 32


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

    def fn(q, k_pool, v_pool, table, kv_len):
        return decode_attention_paged(q, k_pool, v_pool, table, kv_len,
                                      impl="pallas")
    return fn, [(BATCH, hq, d), (nb, BLOCK, hkv, d), (nb, BLOCK, hkv, d),
                ((BATCH, TABLE), jnp.int32), ((BATCH,), jnp.int32)]


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
