"""``gated_delta_scan`` as a pair of Pallas kernels (forward and backward) on
the layout the model has: q, k, g and Out as [B, T, H*K], v as [B, T, H*V].

One grid step is one chunk of C positions of one BLOCK of heads (whole lane
tiles); the grid is (B, head blocks, chunks), the chunks last and in order,
and every head's state, held transposed as S^T [V, K], stays in a VMEM
scratch across them.  A step does the whole of ``ops/delta_ops.py``'s chunk
in VMEM, a head at a time (gam the running sum of g inside the chunk, S0
the state before it, K+ = exp(gam) k, Q+ = exp(gam) scale q):

    A = strict_lower(K+ K-^T), P = lower(Q+ K-^T)   the decays inside the
        chunk: the diagonal sub-blocks of ``SUB`` positions ELEMENTWISE, an
        offset d = r - i at a time (k and gam rolled down by d rows, the
        pairs outside a sub-block masked BEFORE the ``exp``, summed over the
        key channels in float32); the rows of sub-block R >= 1 against every
        earlier position as a product through the position b before it,
        exp(gam_r - gam_b) exp(gam_b - gam_i), both factors <= 1
    T = (I + diag(beta) A)^-1     forward substitution on the VPU, float32
    [U | TK] = T diag(beta) [V | K+]
    W = U - TK S0;  S_C = exp(gam_C) S0 + (exp(gam_C - gam) k)^T W
    o = Q+ S0 + P W

Every decay is the ``exp`` of a difference of running sums <= 0; exp(-gam)
is never formed.  The running sum gam is computed in the kernel in float32
(``_running_sums``: a product with a 0 / 1 triangle of g split into three
bfloat16 terms, each exact), and so is the sum from each position to the
chunk's end that turns the gradient of gam into that of g; XLA beside the
kernels only moves beta to columns and back (``delta.decay``).  The large
products are one bfloat16 pass with float32 accumulation, as the XLA form's
are on the chip (``_mxu_dot``); the triangle's inverse, the elementwise sums
and the state are float32.

Backward: the same visit from the last chunk to the first with the state's
cotangent in the scratch.  Residuals are the op's inputs and the
chunk-boundary states S_before^T [B, T/C, H, V, K]; A, P, T, U, TK and W
are recomputed in VMEM.  The gradient of gam through A and P is, a pair of
positions at a time, the same term added at the row and taken off at the
column, which cancel in part over a chunk: both sides are taken of the
operands as the products multiplied them (the lesson of ``ssd_kernels``).

Each ``pallas_call`` sits behind a ``jax.jit``ted function, so a module with
several such layers traces it once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _mxu_dot, _mxu_operand

SUB = 16                      # positions of a diagonal sub-block
DELTA_VMEM_BYTES = 32 << 20   # scoped VMEM a kernel may take
DELTA_BLOCK_LANES = 1024      # lanes (heads * K) of q a grid step holds
_LANES = 128
_F32 = jnp.float32

# contractions of ``_mxu_dot``: a b, a b^T, a^T b
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def heads_a_block(heads, kd):
    """How many heads one grid step holds: the most, up to
    ``DELTA_BLOCK_LANES`` lanes of q, that divide the heads."""
    for hb in range(max(1, min(heads, DELTA_BLOCK_LANES // kd)), 0, -1):
        if heads % hb == 0:
            return hb
    return 1


def _blocks_fit(chunk, kd, vd, hb):
    """Whether a step's blocks stand inside ``DELTA_VMEM_BYTES``, counted
    for the backward (the larger): the streamed blocks q, k, g, dq, dk, dg
    ([C, hb K]), v, dO, dv ([C, hb V]) and beta, d beta in both pipeline
    buffers, the state's blocks and scratch, and a head's temporaries (some
    forty [C, K] arrays and twenty [C, C])."""
    streamed = 2 * (6 * chunk * hb * kd + 3 * chunk * hb * vd
                    + 2 * chunk * hb) * 4
    state = 3 * hb * vd * kd * 4
    temporaries = (40 * chunk * max(kd, vd) + 20 * chunk * chunk) * 4
    return streamed + state + temporaries <= DELTA_VMEM_BYTES


def gated_delta_scan_route(shapes, chunk, dtype, interpret=False):
    """Which lowering a ``gated_delta_scan`` of q ``shapes[0]`` [B, T, H, K]
    and v ``shapes[1]`` [B, T, H, V] takes: the kernels (``pallas`` on a
    TPU; ``interpret``, which only a test asks for) where K and V are whole
    lane tiles, the chunk is whole sub-blocks of ``SUB`` positions (and so
    of 8 sublanes), T is whole chunks, q is float32 or bfloat16 and the
    blocks fit ``DELTA_VMEM_BYTES``; ``xla``, ``delta_ops.delta_chunked``,
    for every other shape and backend.  A mesh is the op's to see."""
    q_shape, v_shape = shapes
    eligible = False
    if len(q_shape) == 4 and len(v_shape) == 4:
        (_, t_len, heads, kd), vd = q_shape, v_shape[3]
        eligible = (kd % _LANES == 0 and vd % _LANES == 0 and chunk > 0
                    and chunk % SUB == 0 and t_len % chunk == 0
                    and dtype in (jnp.float32, jnp.bfloat16)
                    and _blocks_fit(chunk, kd, vd, heads_a_block(heads, kd)))
    if eligible and interpret:
        return "interpret"
    if eligible and jax.default_backend() == "tpu":
        return "pallas"
    return "xla"


# ---------------------------------------------------------------------------
# what both kernels compute of a head's chunk
# ---------------------------------------------------------------------------
def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


def _running_sums(x, to_end):
    """Sums of ``x``'s rows over the chunk, float32: from its start to each
    row, or (``to_end``) from each row to its end.  A product with a 0 / 1
    triangle of ``x`` split into three bfloat16 terms, each exact in its
    pass (8 + 8 + 8 bits of mantissa)."""
    c = x.shape[0]
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    tri = (rows <= cols if to_end else rows >= cols).astype(jnp.bfloat16)
    total = None
    for _ in range(3):
        term = x.astype(jnp.bfloat16)
        part = lax.dot_general(tri, term, (_NN, ((), ())),
                               precision=lax.Precision.DEFAULT,
                               preferred_element_type=_F32)
        total = part if total is None else total + part
        x = x - term.astype(_F32)
    return total


def _offset(k, gam, d, sub_row):
    """k rolled down by d rows and exp(gam_r - gam_{r-d}) where r and r - d
    lie in one sub-block (``sub_row``: r's place in its sub-block), else 0
    (masked before the ``exp``)."""
    return (pltpu.roll(k, d, 0),
            jnp.exp(jnp.where(sub_row >= d, gam - pltpu.roll(gam, d, 0),
                              -jnp.inf)))


def _masks(c, kd):
    """(each row's place in its sub-block [C, K], r - i [C, C])."""
    return (_iota((c, kd), 0) % SUB,
            _iota((c, c), 0) - _iota((c, c), 1))


def _through(gam, r0):
    """Of the rows of the sub-block at r0 against every earlier position:
    (exp(gam_r - gam_b) [SUB, K], exp(gam_b - gam_i) for i < r0 else 0
    [C, K]), b = r0 - 1."""
    c = gam.shape[0]
    gb = gam[r0 - 1:r0, :]
    before = _iota((c, 1), 0) < r0
    return (jnp.exp(gam[r0:r0 + SUB, :] - gb),
            jnp.exp(jnp.where(before, gb - gam, -jnp.inf)))


def _lower_pair(qs, k, gam, dot):
    """A (strict) and P (inclusive) [C, C] of a head's chunk, and of each
    sub-block R >= 1 the product's operands (row factor, [k | qs] rows
    times it, k times the column factor, column factor)."""
    c = k.shape[0]
    sub_row, apart = _masks(c, k.shape[1])
    a = jnp.zeros((c, c), _F32)
    p = jnp.zeros((c, c), _F32)
    for d in range(SUB):       # the d-th diagonal, where nothing stands yet
        if d == 0:
            y = k
        else:
            k_d, e = _offset(k, gam, d, sub_row)
            y = k_d * e
            a = jnp.where(apart == d, jnp.sum(k * y, axis=1, keepdims=True),
                          a)
        p = jnp.where(apart == d, jnp.sum(qs * y, axis=1, keepdims=True), p)
    offs, a_rows, p_rows = [], [], []
    for r0 in range(SUB, c, SUB):
        rows, cols = _through(gam, r0)
        lhs = jnp.concatenate([k[r0:r0 + SUB] * rows,
                               qs[r0:r0 + SUB] * rows], axis=0)
        right = k * cols
        off = dot(lhs, right, _NT)                       # [2 SUB, C]
        a_rows.append(off[:SUB])
        p_rows.append(off[SUB:])
        offs.append((rows, lhs, right, cols))
    if offs:
        top = jnp.zeros((SUB, c), _F32)
        a = a + jnp.concatenate([top] + a_rows, axis=0)
        p = p + jnp.concatenate([top] + p_rows, axis=0)
    return a, p, offs


def _unit_lower_inverses(lbs):
    """(I + lb)^-1 of each ``lb`` [C, C] strictly lower triangular, by
    forward substitution in float32, column by column on tiles of 8 rows
    (row i is final when its column is taken, and only the tiles at and
    below it change).  The heads go side by side in a lane tile where C is
    less (two at C = 64), so a step's column reaches all of them in one lane
    permutation (``take_along_axis`` of a constant pattern)."""
    c = lbs[0].shape[0]
    side = max(1, _LANES // c)
    width = side * c
    lane = _iota((8, width), 1)
    eye = [(lane % c == _iota((8, width), 0) + 8 * j).astype(_F32)
           for j in range(c // 8)]
    packs = []
    for at in range(0, len(lbs), side):
        part = lbs[at:at + side]
        part = part + [jnp.zeros_like(lbs[0])] * (side - len(part))
        packed = part[0] if side == 1 else jnp.concatenate(part, axis=1)
        packs.append(([packed[8 * j:8 * j + 8] for j in range(c // 8)],
                      list(eye)))
    for i in range(c - 1):
        column = lane - lane % c + i                   # lane i of each head
        for lb_t, t in packs:
            row = t[i // 8][i % 8:i % 8 + 1, :]
            for j in range(i // 8, c // 8):
                t[j] = t[j] - jnp.take_along_axis(lb_t[j], column,
                                                  axis=1) * row
    invs = []
    for _, t in packs:
        whole = jnp.concatenate(t, axis=0)
        invs += [whole[:, h * c:(h + 1) * c] for h in range(side)]
    return invs[:len(lbs)]


def _chunks(heads, scale, dot):
    """What the forward computes of each head's chunk, for both kernels;
    ``heads`` holds (q, k, v, gam, beta, S0^T) a head."""
    out = []
    for q, k, v, gam, beta, s0t in heads:
        qs = q * scale
        eg = jnp.exp(gam)
        a, p, offs = _lower_pair(qs, k, gam, dot)
        out.append(dict(qs=qs, eg=eg, kp=eg * k, qp=eg * qs, a=a, p=p,
                        offs=offs))
    ts = _unit_lower_inverses([beta * f["a"] for f, (*_, beta, _s) in
                               zip(out, heads)])
    for f, t, (_, _, v, _, beta, s0t) in zip(out, ts, heads):
        vd = v.shape[1]
        x = dot(t, beta * jnp.concatenate([v, f["kp"]], axis=1), _NN)
        f.update(t=t, x=x, tk=x[:, vd:],
                 w=x[:, :vd] - dot(x[:, vd:], s0t, _NT))
    return out


def _tile(x, j, width):
    return x[:, j * width:(j + 1) * width]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, before_ref,
                state, *, scale, kd, vd, interpret):
    dot = functools.partial(_mxu_dot, interpret=interpret)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    refs = (q_ref, k_ref, v_ref, g_ref, beta_ref)
    heads = [_head(refs, j, kd, vd) + (state[j],)
             for j in range(state.shape[0])]
    for j, ((q, k, v, gam, beta, s0t), f) in enumerate(
            zip(heads, _chunks(heads, scale, dot))):
        before_ref[j] = s0t
        last = gam[-1:, :]
        k_end = jnp.exp(last - gam) * k
        o = dot(f["qp"], s0t, _NT) + dot(f["p"], f["w"], _NN)
        o_ref[:, j * vd:(j + 1) * vd] = o.astype(o_ref.dtype)
        state[j] = jnp.exp(last) * s0t + dot(f["w"], k_end, _TN)


def _head(refs, j, kd, vd):
    """q, k, v, gam (float32) and beta [C, 1] of head ``j`` of a step."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    q, k, g = (_tile(r[...], j, kd).astype(_F32)
               for r in (q_ref, k_ref, g_ref))
    return (q, k, _tile(v_ref[...], j, vd).astype(_F32),
            _running_sums(g, False), beta_ref[:, j:j + 1])


def _params(interpret):
    return {"interpret": True} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=DELTA_VMEM_BYTES)}


def _operands(q, k, v, g, beta):
    """The operands flat as the model holds them, beta as columns [B,
    blocks, T, heads a block] (``delta.decay``)."""
    b, t_len, heads, kd = q.shape
    vd = v.shape[3]
    hb = heads_a_block(heads, kd)
    with jax.named_scope("delta.decay"):
        cols = jnp.moveaxis(beta.astype(_F32).reshape(
            b, t_len, heads // hb, hb), 2, 1)
    return (q.reshape(b, t_len, heads * kd), k.reshape(b, t_len, heads * kd),
            v.reshape(b, t_len, heads * vd), g.reshape(b, t_len, heads * kd),
            cols)


def _specs(q_shape, vd, chunk, at):
    """(grid, block specs by kind) for the static shapes; ``at(c)`` is the
    chunk a grid step visits (the backward's run from the last)."""
    b, t_len, heads, kd = q_shape
    hb = heads_a_block(heads, kd)
    specs = {
        "qk": pl.BlockSpec((None, chunk, hb * kd),
                           lambda b, j, c: (b, at(c), j)),
        "v": pl.BlockSpec((None, chunk, hb * vd),
                          lambda b, j, c: (b, at(c), j)),
        "cols": pl.BlockSpec((None, None, chunk, hb),
                             lambda b, j, c: (b, j, at(c), 0)),
        "state": pl.BlockSpec((None, None, hb, vd, kd),
                              lambda b, j, c: (b, at(c), j, 0, 0)),
    }
    return (b, heads // hb, t_len // chunk), specs


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def _fwd_call(q, k, v, g, beta, scale, chunk, interpret):
    """o [B, T, H, V] and S_before^T [B, T / chunk, H, V, K] (float32)."""
    b, t_len, heads, kd = q.shape
    vd = v.shape[3]
    grid, specs = _specs(q.shape, vd, chunk, lambda c: c)
    hb = heads // grid[1]
    o, before = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, kd=kd, vd=vd,
                          interpret=interpret),
        out_shape=[jax.ShapeDtypeStruct((b, t_len, heads * vd), v.dtype),
                   jax.ShapeDtypeStruct((b, grid[2], heads, vd, kd), _F32)],
        grid=grid,
        in_specs=[specs["qk"], specs["qk"], specs["v"], specs["qk"],
                  specs["cols"]],
        out_specs=[specs["v"], specs["state"]],
        scratch_shapes=[pltpu.VMEM((hb, vd, kd), _F32)],
        **_params(interpret))(*_operands(q, k, v, g, beta))
    return o.reshape(b, t_len, heads, vd), before


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _as_multiplied(x, interpret):
    """``x`` as ``_mxu_dot`` multiplies it (compiled: rounded to bfloat16)."""
    return _mxu_operand(x, interpret).astype(_F32)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, before_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *,
                scale, kd, vd, interpret):
    dot = functools.partial(_mxu_dot, interpret=interpret)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    refs = (q_ref, k_ref, v_ref, g_ref, beta_ref)
    heads = [_head(refs, j, kd, vd) + (before_ref[j],)
             for j in range(dstate.shape[0])]
    for j, ((q, k, v, gam, beta, s0t), f) in enumerate(
            zip(heads, _chunks(heads, scale, dot))):
        ds1t = dstate[j]          # cotangent of the state this chunk LEAVES
        do = _tile(do_ref[...], j, vd).astype(_F32)
        qs, kp, qp, w, tk = f["qs"], f["kp"], f["qp"], f["w"], f["tk"]
        c = k.shape[0]
        last = gam[-1:, :]
        el = jnp.exp(last - gam)
        ke = el * k
        w_last = jnp.exp(last)
        # o = Q+ S0 + P W;  S_C = exp(gam_C) S0 + Ke^T W;  W = U - TK S0
        dw = dot(f["p"], do, _TN) + dot(ke, ds1t, _NT)
        dp = jnp.where(_iota((c, c), 0) >= _iota((c, c), 1),
                       dot(do, w, _NT), 0.0)
        dqp = dot(do, s0t, _NN)
        dke = dot(w, ds1t, _NN)
        dstate[j] = (dot(do, qp, _TN) + w_last * ds1t
                     - dot(dw, tk, _TN))
        d_last = (jnp.sum(ke * dke, axis=0, keepdims=True)
                  + w_last * jnp.sum(ds1t * s0t, axis=0, keepdims=True))
        # [U | TK] = T Y, Y = diag(beta) [V | K+], (I + diag(beta) A) T = I
        dy = dot(f["t"], jnp.concatenate([dw, -dot(dw, s0t, _NN)], axis=1),
                 _TN)
        dlb = jnp.where(_iota((c, c), 0) > _iota((c, c), 1),
                        -dot(dy, f["x"], _NT), 0.0)
        dbeta_ref[:, j:j + 1] = (
            jnp.sum(dy * jnp.concatenate([v, kp], axis=1), axis=1,
                    keepdims=True)
            + jnp.sum(dlb * f["a"], axis=1, keepdims=True))
        dv = beta * dy[:, :vd]
        dkp = beta * dy[:, vd:]
        da = beta * dlb
        dk = f["eg"] * dkp + el * dke
        dqs = f["eg"] * dqp
        dgam = kp * dkp + qp * dqp - ke * dke
        # A and P: the rows of a sub-block against the earlier positions,
        # through the position before it
        if f["offs"]:
            dk_rows, dq_rows, dgam_rows = ([jnp.zeros((SUB, kd), _F32)]
                                           for _ in range(3))
            for n, (rows, lhs, right, cols) in enumerate(f["offs"]):
                r0 = SUB * (n + 1)
                d_off = jnp.concatenate([da[r0:r0 + SUB], dp[r0:r0 + SUB]],
                                        axis=0)                  # [2 SUB, C]
                d_lhs = dot(d_off, right, _NN)
                d_right = dot(d_off, lhs, _TN)
                lhs_m = _as_multiplied(lhs, interpret)
                dk_rows.append(rows * d_lhs[:SUB])
                dq_rows.append(rows * d_lhs[SUB:])
                dgam_rows.append(lhs_m[:SUB] * d_lhs[:SUB]
                                 + lhs_m[SUB:] * d_lhs[SUB:])
                dk = dk + cols * d_right
                dgam = dgam - _as_multiplied(right, interpret) * d_right
            dk = dk + jnp.concatenate(dk_rows, axis=0)
            dqs = dqs + jnp.concatenate(dq_rows, axis=0)
            dgam = dgam + jnp.concatenate(dgam_rows, axis=0)
        # A and P inside the diagonal sub-blocks, an offset at a time: the
        # pair (r, r - d) adds the same term to gam_r and takes it off gam_i
        sub_row, apart = _masks(c, kd)
        for d in range(SUB):
            at = apart == d
            dp_d = jnp.sum(jnp.where(at, dp, 0.0), axis=1, keepdims=True)
            if d == 0:
                dk = dk + dp_d * qs
                dqs = dqs + dp_d * k
                continue
            da_d = jnp.sum(jnp.where(at, da, 0.0), axis=1, keepdims=True)
            k_d, e = _offset(k, gam, d, sub_row)
            y = k_d * e
            h = da_d * k + dp_d * qs
            z = y * h
            dk = dk + da_d * y + pltpu.roll(e * h, c - d, 0)
            dqs = dqs + dp_d * y
            dgam = dgam + z - pltpu.roll(z, c - d, 0)
        dgam = dgam + jnp.where(_iota((c, 1), 0) == c - 1, d_last, 0.0)
        dq_ref[:, j * kd:(j + 1) * kd] = (scale * dqs).astype(dq_ref.dtype)
        dk_ref[:, j * kd:(j + 1) * kd] = dk.astype(dk_ref.dtype)
        dv_ref[:, j * vd:(j + 1) * vd] = dv.astype(dv_ref.dtype)
        # gam is g's running sum: its transpose, a sum to the chunk's end
        dg_ref[:, j * kd:(j + 1) * kd] = _running_sums(
            dgam, True).astype(dg_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def _bwd_call(q, k, v, g, beta, before, do, scale, chunk, interpret):
    """The five gradients."""
    b, t_len, heads, kd = q.shape
    vd = v.shape[3]
    nc = t_len // chunk
    grid, specs = _specs(q.shape, vd, chunk, lambda c: nc - 1 - c)
    blocks = grid[1]
    hb = heads // blocks
    wide_k, wide_v = (b, t_len, heads * kd), (b, t_len, heads * vd)
    dq, dk, dv, dg, dcols = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, kd=kd, vd=vd,
                          interpret=interpret),
        out_shape=[jax.ShapeDtypeStruct(wide_k, q.dtype),
                   jax.ShapeDtypeStruct(wide_k, k.dtype),
                   jax.ShapeDtypeStruct(wide_v, v.dtype),
                   jax.ShapeDtypeStruct(wide_k, g.dtype),
                   jax.ShapeDtypeStruct((b, blocks, t_len, hb), _F32)],
        grid=grid,
        in_specs=[specs["qk"], specs["qk"], specs["v"], specs["qk"],
                  specs["cols"], specs["state"], specs["v"]],
        out_specs=[specs["qk"], specs["qk"], specs["v"], specs["qk"],
                   specs["cols"]],
        scratch_shapes=[pltpu.VMEM((hb, vd, kd), _F32)],
        **_params(interpret))(
            *_operands(q, k, v, g, beta), before, do.reshape(wide_v))
    with jax.named_scope("delta.decay"):
        d_beta = jnp.moveaxis(dcols, 1, 2).reshape(b, t_len, heads)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), d_beta.astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _delta(q, k, v, g, beta, scale, chunk, interpret):
    return _fwd_call(q, k, v, g, beta, scale=scale, chunk=chunk,
                     interpret=interpret)[0]


def _delta_vjp_fwd(q, k, v, g, beta, scale, chunk, interpret):
    o, before = _fwd_call(q, k, v, g, beta, scale=scale, chunk=chunk,
                          interpret=interpret)
    return o, (q, k, v, g, beta, before)


def _delta_vjp_bwd(scale, chunk, interpret, res, do):
    return _bwd_call(*res, do, scale=scale, chunk=chunk, interpret=interpret)


_delta.defvjp(_delta_vjp_fwd, _delta_vjp_bwd)


def gated_delta_scan(q, k, v, g, beta, scale, chunk, interpret=False):
    """o [B, T, H, V] of ``ops/delta_ops.py``'s recurrence for the shapes
    ``gated_delta_scan_route`` gives the kernels; differentiable in all
    five."""
    return _delta(q, k, v, g, beta, float(scale), int(chunk), interpret)

