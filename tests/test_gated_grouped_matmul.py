"""``gated_grouped_matmul`` (ops/pallas_kernels.py), the gate and up stacks
of gated experts as one pass a direction, against what it replaced: two
``grouped_matmul`` calls and ``act(gate) * up``; and its un-gated form (no
gate stack, the stack held either way) against ``act(grouped_matmul(...))``.
The activation is the forward kernel's epilogue and its derivative a kernel
over the tiles in use, where XLA's elementwise work and autodiff stood.
Interpreted here, where both are exact, so the hidden rows and every
gradient are the same bits: the pair sums the two gradients of ``rows`` in
float32 as XLA's add did, and accumulates each stack's gradient in the same
order.  The interpreter fills a result with NaN before the kernel runs, so a
tile that no grid step stored reads NaN."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe_ops, pallas_kernels

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


def _through_xla(rows, w_gate, w_up, tile_group, num_tiles, act,
                 transpose_rhs=False):
    """The products alone as kernels, the elementwise work and its
    derivative XLA's, over every row of the bound."""
    if w_gate is None:
        return act(pallas_kernels.grouped_matmul(
            rows, w_up, tile_group, num_tiles, transpose_rhs=transpose_rhs))
    gate = pallas_kernels.grouped_matmul(rows, w_gate, tile_group, num_tiles)
    up = pallas_kernels.grouped_matmul(rows, w_up, tile_group, num_tiles)
    return act(gate) * up


FORMS = {
    # name: (a gate stack, the up stack held [G, N, K])
    "pair": (True, False),
    "single": (False, False),
    "single-transposed": (False, True),
}


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("act", sorted(moe_ops._ACTS))
def test_the_pair_is_the_two_products_bit_for_bit(act, form):
    gated, transposed = FORMS[form]
    rng = np.random.RandomState(3)
    d, h = 16, 24
    tile_group, num_tiles = _layout()
    used = int(num_tiles[0]) * TILE
    assert used == 7 * TILE and TILES * TILE > sum(COUNTS)
    rows, w_gate, w_up = (
        jnp.asarray(rng.randn(*shape).astype("float32"))
        for shape in ((TILES * TILE, d), (len(COUNTS), d, h),
                      (len(COUNTS), d, h)))
    operands = {"rows": rows, "w_up": w_up.mT if transposed else w_up,
                **({"w_gate": w_gate} if gated else {})}
    mix = jnp.asarray(rng.randn(TILES * TILE, h).astype("float32"))

    def both(fn):
        def loss(w):
            hidden = fn(w["rows"], w.get("w_gate"), w["w_up"], tile_group,
                        num_tiles, moe_ops._ACTS[act],
                        transpose_rhs=transposed)
            return jnp.sum(hidden[:used] * mix[:used]), hidden
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(operands)

    (_, hidden), grads = both(pallas_kernels.gated_grouped_matmul)
    (_, ref_hidden), refs = both(_through_xla)
    # (NaN where neither wrote: the same rows)
    np.testing.assert_array_equal(hidden, ref_hidden)
    for name in operands:
        np.testing.assert_array_equal(grads[name], refs[name], err_msg=name)
    # rows of the tiles not in use are never written and take no gradient;
    # the group without rows took one from its padding tile alone
    for tiled in (hidden, grads["rows"]):
        assert np.isnan(tiled[used:]).all()
        assert np.isfinite(tiled[:used]).all()
    assert np.any(grads["w_up"][1]) and np.any(np.asarray(hidden[:used]))
