"""Compile the device kernels with the TPU's compiler, for a v5e chip
that is described, not attached: what Mosaic or XLA:TPU refuses fails
here without a chip. Shapes are gpt2_345m's (16 heads, head dim 64,
seq 1024, d_model 1024) and a mega-batch scan of 128 candidates.

The topology is described only inside a fixture: one process at a time
may load the TPU library, and every test worker imports this file."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import megabatch_scan
from repro.kernels import rmsnorm as rn


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles_at_gpt2_width(one_chip, dtype):
    x = jax.ShapeDtypeStruct((16, 1024, 64), dtype, sharding=one_chip)
    f = functools.partial(fa.flash_attention_bh, causal=True,
                          interpret=False)
    assert "tpu_custom_call" in _compile(f, x, x, x)


def test_rmsnorm_compiles_at_gpt2_width(one_chip):
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((1024,), jnp.float32, sharding=one_chip)
    f = functools.partial(rn.rmsnorm, interpret=False)
    assert "tpu_custom_call" in _compile(f, x, scale)


def test_megabatch_scan_compiles(one_chip):
    t, k = 512, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ends0 = sds((t * k + 2,), jnp.float32)
    xs = (sds((t, k), jnp.int32), sds((t, k, 3), jnp.int32),
          sds((t, k, 3), jnp.float32), sds((t, k), jnp.float32))
    assert "while" in _compile(megabatch_scan.scan_program, ends0, xs)
