"""Kernels of the main path compiled for a described (not attached) TPU
v5e at real widths: what the chip's compiler refuses (a block off the
tiling, too much VMEM) fails here, at no chip time.  Nothing runs, so
nothing here is a result or a time.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU's library.  Keep every such test
in THIS file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_kernels


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_grouped_matmul_compiles_at_olmoe_widths(one_chip):
    """The three kernels (product, product with the stack transposed,
    gradient of the stack) for 64 experts of [2048, 1024] and 8 x 4096
    routed rows in the lowering's tiles: Mosaic takes the blocks and the
    VMEM they need."""
    from paddle_tpu.ops.moe_ops import ROW_TILE as tile
    d, h, experts = 2048, 1024, 64
    rows = (8 * 4096 // tile + experts) * tile

    def loss(lhs, rhs, tile_group, num_tiles):
        out = pallas_kernels._grouped(lhs, rhs, tile_group, num_tiles, False)
        return jnp.sum(out * out)

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        spec((rows, d)), spec((experts, d, h)),
        spec((rows // tile,), jnp.int32), spec((1,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    # operands, results and the forward's output; nothing of E x rows
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows * d * 3
