"""Kernels of the main path compiled for a described (not attached) TPU
v5e at real widths: what the chip's compiler refuses (a block off the
tiling, too much VMEM) fails here, at no chip time.  Nothing runs, so
nothing here is a result or a time.

The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU's library.  Keep every such test
in THIS file."""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import moe_ops, pallas_kernels


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


D, H, EXPERTS = 2048, 1024, 64               # OLMoE's experts
TILE = moe_ops.ROW_TILE
ROWS = (8 * 4096 // TILE + EXPERTS) * TILE    # 8 x 4096 routed rows, tiled


@pytest.fixture(scope="module")
def spec(one_chip):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return spec


@pytest.fixture(scope="module")
def layout(spec):
    """``tile_group`` and ``num_tiles``."""
    return spec((ROWS // TILE,), jnp.int32), spec((1,), jnp.int32)


def _kernels(text):
    return text.count('custom_call_target="tpu_custom_call"')


def test_grouped_matmul_compiles_at_olmoe_widths(spec, layout):
    """The three kernels (product, product with the stack transposed,
    gradient of the stack) for 64 experts of [2048, 1024] and 8 x 4096
    routed rows in the lowering's tiles: Mosaic takes the blocks and the
    VMEM they need."""
    def loss(lhs, rhs, tile_group, num_tiles):
        out = pallas_kernels._grouped(lhs, rhs, tile_group, num_tiles, False)
        return jnp.sum(out * out)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        spec((ROWS, D)), spec((EXPERTS, D, H)), *layout).compile()
    assert _kernels(compiled.as_text()) == 3
    # operands, results and the forward's output; nothing of E x rows
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * ROWS * D * 3


@pytest.mark.parametrize("pair,kernels,row_results,row_sums", [
    (True, 6, 2, 0), (False, 9, 3, 1)])
def test_gated_experts_take_six_kernels_and_no_sum_of_row_gradients(
        spec, layout, pair, kernels, row_results, row_sums):
    """The experts stage of a gated ``moe`` op at OLMoE's widths, gradient
    and all: gate and up as the pair (``_gated``) are three kernels, two
    weight blocks each inside ``GMM_VMEM_BYTES``, and with ``down``'s three
    the program holds six where two ``grouped_matmul`` calls made nine.  Of
    [rows, d] kernel results it holds ``down``'s output and ONE gradient of
    the rows, so no second one exists and nothing adds two of them."""
    def loss(lhs, w_gate, w_up, w_down, tile_group, num_tiles):
        def product(a, w):
            return pallas_kernels._grouped(a, w, tile_group, num_tiles, False)
        hidden = pallas_kernels._gated(
            lhs, w_gate, w_up, tile_group, num_tiles, jax.nn.silu, False
        ) if pair else jax.nn.silu(product(lhs, w_gate)) * product(lhs, w_up)
        return jnp.sum(product(hidden, w_down) ** 3)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        spec((ROWS, D)), spec((EXPERTS, D, H)), spec((EXPERTS, D, H)),
        spec((EXPERTS, H, D)), *layout).compile().as_text()
    wide = rf"= f32\[{ROWS},{D}\]\S* "
    assert (_kernels(text), len(re.findall(wide + r"custom-call\(", text)),
            len(re.findall(wide + r"add\(", text))
            ) == (kernels, row_results, row_sums)


@pytest.mark.parametrize("act", sorted(moe_ops._ACTS))
def test_gated_forward_takes_every_activation_in_its_epilogue(spec, layout,
                                                              act):
    """``act(gate) * up`` is the forward kernel's epilogue: Mosaic lowers
    each activation a ``moe`` op may name, at OLMoE's widths."""
    text = jax.jit(functools.partial(
        pallas_kernels._gated, act=moe_ops._ACTS[act], interpret=False)
    ).lower(spec((ROWS, D)), spec((EXPERTS, D, H)), spec((EXPERTS, D, H)),
            *layout).compile().as_text()
    assert _kernels(text) == 1
