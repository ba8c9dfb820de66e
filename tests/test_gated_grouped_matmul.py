"""``gated_grouped_matmul`` (ops/pallas_kernels.py), the gate and up stacks
of gated experts as one pass a direction, against what it replaced: two
``grouped_matmul`` calls and ``act(gate) * up``.  Interpreted here, where
both are exact, so the hidden rows and all three gradients are the same
bits: the pair sums the two gradients of ``rows`` in float32 as XLA's add
did, and accumulates each stack's gradient in the same order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kernels

TILE = 8
# rows of each group: none (it still owns one tile), several tiles, counts
# no tile divides; three tiles at the tail are not in use
COUNTS = (11, 0, 21, 3)
TILES = 10


def _layout():
    used = [max(-(-c // TILE), 1) for c in COUNTS]
    tile_group = np.repeat(np.arange(len(COUNTS)), used)
    tile_group = np.concatenate(
        [tile_group, np.full(TILES - len(tile_group), len(COUNTS) - 1)])
    return (jnp.asarray(tile_group, jnp.int32),
            jnp.asarray([sum(used)], jnp.int32))


def _two_calls(rows, w_gate, w_up, tile_group, num_tiles, act):
    gate = pallas_kernels.grouped_matmul(rows, w_gate, tile_group, num_tiles)
    up = pallas_kernels.grouped_matmul(rows, w_up, tile_group, num_tiles)
    return act(gate) * up


@pytest.mark.parametrize("act", ["silu", "relu", "gelu"])
def test_the_pair_is_the_two_products_bit_for_bit(act):
    rng = np.random.RandomState(3)
    d, h = 16, 24
    tile_group, num_tiles = _layout()
    assert int(num_tiles[0]) == 7 and TILES * TILE > sum(COUNTS)
    operands = tuple(jnp.asarray(rng.randn(*shape).astype("float32"))
                     for shape in ((TILES * TILE, d), (len(COUNTS), d, h),
                                   (len(COUNTS), d, h)))
    mix = jnp.asarray(rng.randn(TILES * TILE, h).astype("float32"))

    def both(fn):
        def loss(rows, w_gate, w_up):
            hidden = fn(rows, w_gate, w_up, tile_group, num_tiles,
                        getattr(jax.nn, act))
            return jnp.sum(hidden * mix), hidden
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(*operands)

    (_, hidden), grads = both(pallas_kernels.gated_grouped_matmul)
    (_, ref_hidden), refs = both(_two_calls)
    np.testing.assert_array_equal(hidden, ref_hidden)
    for name, got, ref in zip(("rows", "w_gate", "w_up"), grads, refs):
        np.testing.assert_array_equal(got, ref, err_msg=name)
    # rows of the tiles not in use read as zero and take no gradient; the
    # group without rows took one from its padding tile alone
    assert not np.any(hidden[7 * TILE:]) and not np.any(grads[0][7 * TILE:])
    assert np.any(grads[1][1]) and np.any(np.asarray(hidden[:7 * TILE]))
