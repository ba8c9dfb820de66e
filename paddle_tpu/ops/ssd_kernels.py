"""``ssd_scan`` as a pair of Pallas kernels (forward and backward) on the
layout the model has: U and Out as [B, T, H*P], Bm and Cm as [B, T, G*N].

One grid step is one chunk of Q positions of one BLOCK of heads (whole
128-lane tiles: pairs of heads at P = 64); the grid is (B, head blocks,
chunks), the chunks last and in order, and the running state of the
block's heads, held transposed as S^T [N, heads * P], stays in a VMEM
scratch across them.  Held so, a step's products that involve the state are
ONE dense product each for all heads of the block:

    carried = Cm S^T               [Q, N] x [N, heads * P]
    own     = Bm^T (to_end o x)    [N, Q] x [Q, heads * P]

``Cm Bm^T`` [Q, Q] is computed once a step (the heads of a block share one
group), and per head M = (Cm Bm^T) o L with L[i, j] = exp(cs_i - cs_j) for
i >= j, y += M x: the two heads of a lane tile as two masked [Q, Q] x
[Q, 128] products, so that no 64-lane slice is cut.

The numerics are ``ops/ssd_ops.py``'s: every decay is the ``exp`` of a
DIFFERENCE of running sums <= 0, the triangle masked BEFORE the ``exp``.
The running sum cs is computed by XLA beside the kernels in float32 (a
cumulative sum, not a product) and handed in as columns [Q, heads] and rows
[heads, Q].  The large products are one bfloat16 pass with float32
accumulation, as the einsum form's are on the chip; everything else float32.

Backward: the same visit from the last chunk to the first with the state's
cotangent in the scratch.  Residuals are the op's inputs and the
chunk-boundary states S_before^T [B, T/Q, N, H*P]; L, M and M x are
recomputed in VMEM.  The reductions over a head's P lanes (the gradients of cs and delta)
are products with a 0 / 1 selection matrix of the value split into three
bfloat16 terms, which is the float32 sum.  Per head block the kernel writes
partial dBm and dCm that XLA sums over the blocks of a group.

Each ``pallas_call`` sits behind a ``jax.jit``ted function, so a module with
nine such layers traces it once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _mxu_dot, _mxu_operand

SSD_VMEM_BYTES = 32 << 20     # scoped VMEM a kernel may take
SSD_BLOCK_LANES = 512         # lanes (heads * P) of U a grid step holds
_LANES = 128
_F32 = jnp.float32

# contractions of ``_mxu_dot``: a b, a b^T, a^T b
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def heads_a_block(heads, groups, p):
    """How many heads one grid step holds: the most, up to
    ``SSD_BLOCK_LANES`` lanes, that are whole lane tiles and divide the heads
    of a group (a block lies inside one group); 0 where none does."""
    per = heads // groups
    for hb in range(min(per, SSD_BLOCK_LANES // p), 0, -1):
        if per % hb == 0 and (hb * p) % _LANES == 0:
            return hb
    return 0


def _blocks_fit(chunk, n, lanes):
    """Whether a step's blocks stand inside ``SSD_VMEM_BYTES``, counted for
    the backward (the larger): the streamed [Q, lanes] blocks u, dy, du
    and the [Q, N] blocks Bm, Cm, dBm, dCm in both pipeline buffers, the
    state's blocks, and the temporaries of a step (a dozen [Q, lanes]
    arrays, half a dozen [Q, Q])."""
    streamed = 2 * (3 * chunk * lanes + 4 * chunk * n) * 4
    state = 4 * n * lanes * 4
    temporaries = (12 * chunk * lanes + 6 * chunk * chunk) * 4
    return streamed + state + temporaries <= SSD_VMEM_BYTES


def ssd_scan_route(shapes, chunk, dtype, interpret=False):
    """Which lowering an ``ssd_scan`` of U ``shapes[0]`` [B, T, H, P] and Bm
    ``shapes[1]`` [B, T, G, N] takes: the kernels (``pallas`` on a TPU;
    ``interpret``, which only a test asks for) where P is a whole or half
    lane tile, N and the chunk are whole lane tiles, the heads of a group
    are whole head blocks, the blocks fit ``SSD_VMEM_BYTES`` and U is
    float32 or bfloat16; ``xla``, the einsum form, for every other shape
    and backend."""
    u_shape, bm_shape = shapes
    eligible = False
    if len(u_shape) == 4 and len(bm_shape) == 4:
        (_, t_len, heads, p), (groups, n) = u_shape, bm_shape[2:]
        hb = heads_a_block(heads, groups, p) \
            if p in (64, 128) and groups and heads % groups == 0 else 0
        eligible = (hb > 0 and n % _LANES == 0 and chunk % _LANES == 0
                    and t_len % chunk == 0
                    and dtype in (jnp.float32, jnp.bfloat16)
                    and _blocks_fit(chunk, n, hb * p))
    if eligible and interpret:
        return "interpret"
    if eligible and jax.default_backend() == "tpu":
        return "pallas"
    return "xla"


# ---------------------------------------------------------------------------
# what both kernels compute of a step
# ---------------------------------------------------------------------------
def _add_tile(tiles, t, tile):
    tiles[t] = tile if tiles[t] is None else tiles[t] + tile


def _joined(tiles):
    """Lane tiles side by side, [R, 128] each -> [R, 128 * tiles]."""
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=1)


def _expanded(cols, p):
    """[R, heads] -> [R, heads * P]: every head's column over its P lanes
    (lane broadcasts; the two heads of a tile by a select)."""
    rows, hb = cols.shape
    a_tile = _LANES // p
    first = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1) < p
    tiles = []
    for k in range(0, hb, a_tile):
        tile = jnp.broadcast_to(cols[:, k:k + 1], (rows, _LANES))
        if a_tile == 2:
            tile = jnp.where(first, tile, jnp.broadcast_to(
                cols[:, k + 1:k + 2], (rows, _LANES)))
        tiles.append(tile)
    return _joined(tiles)


def _decays(col_ref, p):
    """Of a step's columns (cs and delta, [Q, heads]): delta, exp(cs)
    (``from_start``) and exp(cs_last - cs) (``to_end``) over the lanes,
    [Q, heads * P], and exp(cs_last) (``whole``) [1, heads * P]."""
    cs, dt = col_ref[0], col_ref[1]
    last = cs[-1:, :]
    return (_expanded(dt, p), _expanded(jnp.exp(cs), p),
            _expanded(jnp.exp(last - cs), p), _expanded(jnp.exp(last), p))


def _heads_of(lanes, p):
    """(head, lane tile, mask of its lanes in the tile or None) for every
    head of a block."""
    a_tile = _LANES // p
    first = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) < p
    for k in range(lanes // p):
        yield (k, k // a_tile,
               None if a_tile == 1 else first if k % 2 == 0 else ~first)


def _within(col_ref, row_ref, k, causal):
    """L of head ``k``: exp(cs_i - cs_j) for i >= j, else 0; the triangle
    masked before the ``exp``."""
    cs = col_ref[0]
    return jnp.exp(jnp.where(
        causal, cs[:, k:k + 1] - row_ref[k:k + 1, :], -jnp.inf))


def _tile(x, t):
    return x[:, t * _LANES:(t + 1) * _LANES]


def _masked(x, mask):
    return x if mask is None else jnp.where(mask, x, 0.0)


def _causal(chunk):
    return lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _ssd_fwd_kernel(u_ref, bm_ref, cm_ref, col_ref, row_ref, d_ref, y_ref,
                    before_ref, state, *, p, interpret):
    dot = functools.partial(_mxu_dot, interpret=interpret)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    s = state[...]                                         # S^T [N, lanes]
    before_ref[...] = s
    u = u_ref[...].astype(_F32)
    bm, cm = bm_ref[...].astype(_F32), cm_ref[...].astype(_F32)
    chunk, lanes = u.shape
    dt, from_start, to_end, whole = _decays(col_ref, p)
    x = dt * u
    scores = dot(cm, bm, _NT)                              # [Q, Q]
    causal = _causal(chunk)
    tiles = [None] * (lanes // _LANES)
    for k, t, mask in _heads_of(lanes, p):
        m = scores * _within(col_ref, row_ref, k, causal)
        _add_tile(tiles, t, dot(m, _masked(_tile(x, t), mask), _NN))
    y = _joined(tiles) + from_start * dot(cm, s, _NN) + d_ref[...] * u
    y_ref[...] = y.astype(y_ref.dtype)
    state[...] = whole * s + dot(bm, to_end * x, _TN)


def _params(interpret):
    return {"interpret": True} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=SSD_VMEM_BYTES)}


def _operands(u, delta, a, bm, cm, d, chunk):
    """What XLA computes beside the kernels (``ssd.decay``): the operands
    flat as the model holds them, cs and delta as columns [B, blocks, 2, T,
    heads] and cs as rows [B, blocks, heads, T], D over the lanes."""
    b, t_len, heads, p = u.shape
    groups, n = bm.shape[2:]
    hb = heads_a_block(heads, groups, p)
    blocks = heads // hb
    with jax.named_scope("ssd.decay"):
        dt = delta.astype(_F32)
        cs = jnp.cumsum((dt * a.astype(_F32)).reshape(
            b, t_len // chunk, chunk, heads), axis=2).reshape(
                b, t_len, blocks, hb)
        cols = jnp.stack([jnp.moveaxis(cs, 2, 1), jnp.moveaxis(
            dt.reshape(b, t_len, blocks, hb), 2, 1)], axis=2)
        rows = jnp.transpose(cs, (0, 2, 3, 1))
        d_lanes = jnp.repeat(d.astype(_F32), p)[None]
    return (u.reshape(b, t_len, heads * p), bm.reshape(b, t_len, groups * n),
            cm.reshape(b, t_len, groups * n), cols, rows, d_lanes)


def _specs(u_shape, bm_shape, chunk, at):
    """(grid, block specs by kind) for the static shapes; ``at(c)`` is the
    chunk a grid step visits (the backward's run from the last)."""
    b, t_len, heads, p = u_shape
    groups, n = bm_shape[2:]
    hb = heads_a_block(heads, groups, p)
    lanes, a_group = hb * p, heads // groups // hb

    specs = {
        "wide": pl.BlockSpec((None, chunk, lanes),
                             lambda b, j, c: (b, at(c), j)),
        "bc": pl.BlockSpec((None, chunk, n),
                           lambda b, j, c: (b, at(c), j // a_group)),
        "bc_part": pl.BlockSpec((None, None, chunk, n),
                                lambda b, j, c: (b, j, at(c), 0)),
        "cols": pl.BlockSpec((None, None, 2, chunk, hb),
                             lambda b, j, c: (b, j, 0, at(c), 0)),
        "rows": pl.BlockSpec((None, None, hb, chunk),
                             lambda b, j, c: (b, j, 0, at(c))),
        "lane_row": pl.BlockSpec((1, lanes), lambda b, j, c: (0, j)),
        "state": pl.BlockSpec((None, None, n, lanes),
                              lambda b, j, c: (b, at(c), 0, j)),
        "chunk_rows": pl.BlockSpec((None, None, 2, lanes),
                                   lambda b, j, c: (b, at(c), 0, j)),
    }
    return (b, heads // hb, t_len // chunk), specs


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_fwd_call(u, delta, a, bm, cm, d, chunk, interpret):
    """y [B, T, H, P] and S_before^T [B, T / chunk, N, H * P] (float32)."""
    b, t_len, heads, p = u.shape
    n = bm.shape[3]
    grid, specs = _specs(u.shape, bm.shape, chunk, lambda c: c)
    hb = heads // grid[1]
    flat = _operands(u, delta, a, bm, cm, d, chunk)
    y, before = pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, p=p, interpret=interpret),
        out_shape=[jax.ShapeDtypeStruct((b, t_len, heads * p), u.dtype),
                   jax.ShapeDtypeStruct((b, grid[2], n, heads * p), _F32)],
        grid=grid,
        in_specs=[specs["wide"], specs["bc"], specs["bc"], specs["cols"],
                  specs["rows"], specs["lane_row"]],
        out_specs=[specs["wide"], specs["state"]],
        scratch_shapes=[pltpu.VMEM((n, hb * p), _F32)],
        **_params(interpret))(*flat)
    return y.reshape(u.shape), before


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _lane_sums(z, p, interpret):
    """[Q, heads * P] -> [Q, 128]: column k the sum over head k's P lanes,
    in float32: the product with the 0 / 1 selection of z split into three
    bfloat16 terms (8 + 8 + 8 bits of mantissa), each exact in its pass."""
    lanes = z.shape[1]
    select = (lax.broadcasted_iota(jnp.int32, (lanes, _LANES), 0) // p
              == lax.broadcasted_iota(jnp.int32, (lanes, _LANES), 1))
    if interpret:
        return lax.dot_general(z, select.astype(_F32), (_NN, ((), ())),
                               precision=lax.Precision.HIGHEST)
    select = select.astype(jnp.bfloat16)
    total = None
    for _ in range(3):
        term = z.astype(jnp.bfloat16)
        part = lax.dot_general(term, select, (_NN, ((), ())),
                               precision=lax.Precision.DEFAULT,
                               preferred_element_type=_F32)
        total = part if total is None else total + part
        z = z - term.astype(_F32)
    return total


def _as_multiplied(x, interpret):
    """``x`` as ``_mxu_dot`` multiplies it (compiled: rounded to bfloat16)."""
    return _mxu_operand(x, interpret).astype(_F32)


def _ssd_bwd_kernel(u_ref, g_ref, bm_ref, cm_ref, col_ref, row_ref, d_ref,
                    before_ref, du_ref, dbm_ref, dcm_ref, dcol_ref, drow_ref,
                    dstate, *, p, interpret):
    dot = functools.partial(_mxu_dot, interpret=interpret)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    ds = dstate[...]              # cotangent of the state this chunk LEAVES
    s = before_ref[...]
    u, g = u_ref[...].astype(_F32), g_ref[...].astype(_F32)
    bm, cm = bm_ref[...].astype(_F32), cm_ref[...].astype(_F32)
    chunk, lanes = u.shape
    hb = lanes // p
    d_lanes = d_ref[...]
    dt, from_start, to_end, whole = _decays(col_ref, p)
    x = dt * u
    scores = dot(cm, bm, _NT)
    causal = _causal(chunk)
    # dx: through the state the chunk leaves, then through M head by head
    dx_state = to_end * dot(bm, ds, _NN)                   # [Q, lanes]
    dx_tiles, y_tiles = ([None] * (lanes // _LANES) for _ in range(2))
    dscores = None
    for k, t, mask in _heads_of(lanes, p):
        within = _within(col_ref, row_ref, k, causal)
        m = scores * within
        gk, xk = (_masked(_tile(v, t), mask) for v in (g, x))
        _add_tile(dx_tiles, t, dot(m, gk, _TN))            # M^T dy
        _add_tile(y_tiles, t, dot(m, xk, _NN))             # M x, again
        dm = dot(gk, _tile(x, t), _NT) * within            # (dy x^T) o L
        dscores = dm if dscores is None else dscores + dm
    dx_within, y_within = _joined(dx_tiles), _joined(y_tiles)
    dx = dx_state + dx_within
    g_start = from_start * g
    dcm_ref[...] = dot(dscores, bm, _NN) + dot(g_start, s, _NT)
    dbm_ref[...] = dot(dscores, cm, _TN) + dot(to_end * x, ds, _NT)
    dstate[...] = whole * ds + dot(cm, g_start, _TN)
    du_ref[...] = (dt * dx + d_lanes * g).astype(du_ref.dtype)
    # d cs_i = sum_p dy y - x dx, y without D u: through L's rows and
    # exp(cs_i) of the carried part; through L's columns and to_end with the
    # other sign.  With W = dM o M, L's part is W's row sums less its column
    # sums, and the running sum's transpose then cancels every W[i, j] with
    # i and j on one side of a position (a head that decays fast is all
    # diagonal: what is left is a thousandth of either sum).  So the two have
    # to be sums of the SAME W: M x is computed again here (y less D u and
    # the carried part would be right to 2^-24 of u, not of M x) and both are
    # taken of the operands as the products rounded them.
    carried = from_start * dot(cm, s, _NN)
    dcol_ref[0] = _lane_sums(
        _as_multiplied(g, interpret) * y_within
        - _as_multiplied(x, interpret) * dx_within
        + g * carried - x * dx_state, p, interpret)[:, :hb]
    # d delta_i (through x) = sum_p dx u
    dcol_ref[1] = _lane_sums(dx * u, p, interpret)[:, :hb]
    # what cs_last gets besides (to_end and whole), and dD, a row a chunk
    drow_ref[0:1, :] = jnp.sum(x * dx_state, axis=0, keepdims=True) \
        + whole * jnp.sum(ds * s, axis=0, keepdims=True)
    drow_ref[1:2, :] = jnp.sum(g * u, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_bwd_call(u, delta, a, bm, cm, d, before, g, chunk, interpret):
    """The six gradients."""
    b, t_len, heads, p = u.shape
    groups, n = bm.shape[2:]
    nc = t_len // chunk
    grid, specs = _specs(u.shape, bm.shape, chunk, lambda c: nc - 1 - c)
    blocks = grid[1]
    hb = heads // blocks
    flat = _operands(u, delta, a, bm, cm, d, chunk)
    wide = (b, t_len, heads * p)
    du, dbm, dcm, dcol, drow = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, p=p, interpret=interpret),
        out_shape=[jax.ShapeDtypeStruct(wide, u.dtype),
                   jax.ShapeDtypeStruct((b, blocks, t_len, n), _F32),
                   jax.ShapeDtypeStruct((b, blocks, t_len, n), _F32),
                   jax.ShapeDtypeStruct((b, blocks, 2, t_len, hb), _F32),
                   jax.ShapeDtypeStruct((b, nc, 2, heads * p), _F32)],
        grid=grid,
        in_specs=[specs["wide"], specs["wide"], specs["bc"], specs["bc"],
                  specs["cols"], specs["rows"], specs["lane_row"],
                  specs["state"]],
        out_specs=[specs["wide"], specs["bc_part"], specs["bc_part"],
                   specs["cols"], specs["chunk_rows"]],
        scratch_shapes=[pltpu.VMEM((n, hb * p), _F32)],
        **_params(interpret))(
            flat[0], g.reshape(wide), *flat[1:], before)
    with jax.named_scope("ssd.decay"):
        dt, a32 = delta.astype(_F32), a.astype(_F32)
        # [B, blocks, T, hb] -> [B, nc, Q, H]
        by_chunk = [jnp.moveaxis(dcol[:, :, i], 1, 2).reshape(
            b, nc, chunk, heads) for i in (0, 1)]
        last = jnp.sum(drow.reshape(b, nc, 2, heads, p), axis=-1)
        dcs = by_chunk[0].at[:, :, -1].add(last[:, :, 0])
        # the running sum's transpose: a sum from each position to the end
        da = jnp.flip(jnp.cumsum(jnp.flip(dcs, 2), axis=2), 2).reshape(
            b, t_len, heads)
        d_delta = da * a32 + by_chunk[1].reshape(b, t_len, heads)
        d_a = jnp.sum(da * dt, axis=(0, 1))
        d_d = jnp.sum(last[:, :, 1], axis=(0, 1))

        def of_group(part):                # [B, blocks, T, N] -> [B, T, G, N]
            return jnp.moveaxis(jnp.sum(part.reshape(
                b, groups, blocks // groups, t_len, n), axis=2), 1, 2)
    return (du.reshape(u.shape), d_delta.astype(delta.dtype),
            d_a.astype(a.dtype), of_group(dbm).astype(bm.dtype),
            of_group(dcm).astype(cm.dtype), d_d.astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(u, delta, a, bm, cm, d, chunk, interpret):
    return _ssd_fwd_call(u, delta, a, bm, cm, d, chunk=chunk,
                         interpret=interpret)[0]


def _ssd_vjp_fwd(u, delta, a, bm, cm, d, chunk, interpret):
    y, before = _ssd_fwd_call(u, delta, a, bm, cm, d, chunk=chunk,
                              interpret=interpret)
    return y, (u, delta, a, bm, cm, d, before)


def _ssd_vjp_bwd(chunk, interpret, res, g):
    return _ssd_bwd_call(*res, g, chunk=chunk, interpret=interpret)


_ssd.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


def ssd_scan(u, delta, a, bm, cm, d, chunk, interpret=False):
    """y [B, T, H, P] of ``ops/ssd_ops.py``'s recurrence for the shapes
    ``ssd_scan_route`` gives the kernels; differentiable in all six."""
    return _ssd(u, delta, a, bm, cm, d, chunk, interpret)
