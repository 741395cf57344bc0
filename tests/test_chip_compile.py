"""The serving path's Pallas kernels, compiled (not interpreted) for a
described TPU v5e at real widths.

Interpret mode accepts kernels the chip's compiler refuses: blocks that
break the (8, 128) tiling rule, tiles that overflow VMEM, vector ops Mosaic
cannot lower. These AOT compiles need no chip. The topology is described
only inside the fixture, once a test of this file runs (never at import),
and the persistent compilation cache is off around the compiles: an entry
written here could not be read back without a chip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

D_MODEL, D_FF, VOCAB = 4096, 13440, 92416          # codeqwen1.5-7b
HEADS, HEAD_DIM = 32, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("k,n", [(D_MODEL, D_MODEL), (D_MODEL, D_FF),
                                 (D_FF, D_MODEL), (D_MODEL, VOCAB)],
                         ids=["qkvo", "gate_up", "down", "lm_head"])
@pytest.mark.parametrize("m", [8, 16, 32, 256],
                         ids=["decode_b8", "decode_b16", "chunk32", "chunk256"])
def test_cascade_matmul_compiles(one_chip, m, k, n):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = _compile(
        lambda x, p, s, b: ops.cascade_matmul(x, p, s, b, out_dtype=jnp.bfloat16,
                                              interpret=False),
        sds((m, k), jnp.bfloat16), sds((k // 2, n), jnp.uint8),
        sds((1, n), jnp.float32), sds((n,), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("t", [1024, 576])
def test_decode_attention_compiles(one_chip, t):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    b = 8
    text = _compile(
        lambda q, k, v, m: ops.decode_attention(q, k, v, m, interpret=False),
        sds((b, HEADS, HEAD_DIM), jnp.bfloat16),
        sds((b, t, HEADS, HEAD_DIM), jnp.bfloat16),
        sds((b, t, HEADS, HEAD_DIM), jnp.bfloat16), sds((b, t), jnp.bool_))
    assert "tpu_custom_call" in text


def test_ssd_decode_compiles(one_chip):
    """mamba2-370m: 32 heads of P=64, state N=128, one group."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    b, h, p, n = 8, 32, 64, 128
    text = _compile(
        lambda *a: ops.ssd_decode(*a, interpret=False),
        sds((b, 1, h, p), jnp.bfloat16), sds((b, 1, h), jnp.float32),
        sds((h,), jnp.float32), sds((b, 1, 1, n), jnp.bfloat16),
        sds((b, 1, 1, n), jnp.bfloat16), sds((h,), jnp.float32),
        sds((b, h, p, n), jnp.float32))
    assert "tpu_custom_call" in text
