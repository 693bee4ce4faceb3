"""Compile the segment_combine kernel for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse (block
tiling, VMEM, Mosaic op support) — none of which interpret mode sees.
The topology is described inside a module fixture only, never while a
module is imported, so every xdist worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.segment_combine.kernel import segment_combine_blocks


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable cannot be read back from the
    # persistent cache without a chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_hlo(one_chip, shape, dtype, op, nb=128):
    vals = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct(shape[:2], jnp.int32, sharding=one_chip)
    fn = jax.jit(lambda v, i: segment_combine_blocks(v, i, op, nb,
                                                     interpret=False))
    return fn.lower(vals, idx).compile().as_text()


# n_blocks = 1001 is not a multiple of the 8-row tile: the wrapper pads
@pytest.mark.parametrize("eb", [8, 128, 512])
@pytest.mark.parametrize("op,dtype", [
    ("min", jnp.float32), ("max", jnp.float32), ("sum", jnp.float32),
    ("min", jnp.int32), ("max", jnp.int32), ("sum", jnp.int32)])
def test_scalar_kernel_compiles_for_v5e(one_chip, op, dtype, eb):
    assert "tpu_custom_call" in _compiled_hlo(one_chip, (1001, eb), dtype,
                                              op)


@pytest.mark.parametrize("op", ["sum", "min", "max"])
def test_vector_kernel_compiles_for_v5e(one_chip, op):
    assert "tpu_custom_call" in _compiled_hlo(one_chip, (77, 512, 32),
                                              jnp.float32, op)
