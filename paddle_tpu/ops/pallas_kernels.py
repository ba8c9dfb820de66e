"""Pallas TPU kernels for hot ops.

The reference hand-wrote CUDA for its hot paths (paddle/cuda hl_*.cu — fused
LSTM, attention-ish matrix kernels).  The TPU-native analog is Pallas: this
module provides a fused flash-attention kernel (online-softmax, O(T) memory,
K/V streamed through VMEM) used by ``nets.scaled_dot_product_attention`` and
available to models directly.

Both directions are fused kernels.  The forward computes exact attention and
saves only the per-row logsumexp; the backward (FlashAttention-2 style)
recomputes block-local probabilities from (q, k, lse) inside Pallas kernels
— ONE that visits each score tile once and makes dq, dk and dv from it where
dk / dv of a whole sequence fit VMEM, else two (one accumulating dq over key
blocks, one accumulating dk/dv over query blocks), a rule on the static
shapes — so the [T, T] probability matrix is never materialized in either
direction and O(T) memory holds for *training*, not just inference.
On non-TPU backends the jnp reference runs instead (CPU tests exercise the
kernels in interpret mode).

``grouped_matmul`` is the product of rows sorted by group with each group's
own matrix (the experts of the dropless ``moe`` lowering), forward and both
gradients as kernels; ``gated_grouped_matmul`` runs the gate and up stacks of
gated experts through the same three kernels as a pair.

``rope_turn`` is the half-rotation of rotary positions as a lane rotation
in fast memory, one read and one write a direction.  ``short_conv`` is the
short causal depthwise convolution of a hybrid decoder, gated (two gates
and the taps) or not (taps, bias and SiLU), in one pass a direction.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import manual_axes
from ..core import compile_cache

NEG_INF = -1e30


def _sds(x, shape, dtype):
    """ShapeDtypeStruct inheriting ``x``'s varying-manual-axes type, so the
    kernels compose with the new shard_map's vma checker (ring attention
    calls them per device hop)."""
    vma = getattr(jax.typeof(x), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------
def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, block_k, num_k_blocks, causal, sm_scale, block_q):
    """Grid (bh, q_blocks, k_blocks), k innermost/sequential: K/V stream
    through VMEM one [block_k, D] tile at a time (O(T) memory), with the
    online-softmax running stats (m, l) and the output accumulator living in
    VMEM scratch across the k dimension.  Also emits the per-row logsumexp
    (the only residual the fused backward needs)."""
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q32 = q_ref[0].astype(jnp.float32) * sm_scale      # [bq, D]
        kblk = k_ref[0].astype(jnp.float32)                # [bk, D]
        vblk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q32, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        if causal:
            qpos = j * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    if causal:
        # blocks strictly above the diagonal contribute nothing — skip them
        pl.when(kb * block_k <= (j + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kb == num_k_blocks - 1)
    def _write():
        l_safe = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_safe)


def _kv_of(group):
    """Which K / V batch-head a Q batch-head reads: with ``group`` query
    heads to a K / V head (heads innermost in the leading dim), head i
    reads i // group; the block specs' index maps go there, so no repeated
    K or V is ever written.  Equal head counts: i itself."""
    return (lambda i: i) if group == 1 else (lambda i: i // group)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    """Returns (out, lse); lse is [BH, Tq, 1] float32.  ``k`` and ``v`` may
    hold fewer batch-heads than ``q``, a whole divisor (grouped-query
    heads)."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    Dv = v.shape[2]
    nk = Tk // block_k
    grid = (BH, Tq // block_q, nk)
    kv = _kv_of(BH // k.shape[0])
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_flash_kernel, block_k=block_k, num_k_blocks=nk,
                          causal=causal, sm_scale=sm_scale,
                          block_q=block_q),
        out_shape=[
            _sds(q, (BH, Tq, Dv), q.dtype),
            _sds(q, (BH, Tq, 1), jnp.float32),
        ],
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j, kb: (kv(i), kb, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda i, j, kb: (kv(i), kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2: recompute p from (q, k, lse) per block)
# ---------------------------------------------------------------------------
# Two routes, chosen by ``_one_pass_fits`` from the static shapes and the
# blocks alone and counted at trace time as
# ``route/flash_attention_bwd:{one_pass,two_pass}``:
#
# * ONE kernel (``_flash_bwd_one_pass_kernel``) that visits each (query block,
#   key block) tile once and makes dq, dk and dv from one recomputation of
#   the scores: five products, one exponential and one mask a tile.  dk and
#   dv of a K/V head's whole sequence stay in VMEM, so it runs where they fit
#   ``FLASH_BWD_VMEM_BYTES`` beside what else it holds (at 1024 x 1024 blocks
#   up to 9 216 keys of 64 or of 128 features; at 512 x 512, 13 824).
# * TWO kernels (``_flash_bwd_dq_kernel``, ``_flash_bwd_dkv_kernel``), each
#   recomputing the scores (seven products, two exponentials a tile) with
#   O(block) VMEM: every longer sequence, and blocks a row of lse cannot be
#   cut into.
#
# Every call asks for ``FLASH_BWD_VMEM_BYTES`` of scoped VMEM.  What Mosaic
# asks for the one kernel at 1024 x 1024 blocks, compiling for a v5e with
# every operand in HBM: 13.6 MiB at 2048 keys, 19.6 at 4096, 27.6 at 8192,
# 35.6 at 12 288 (26.6 inside Nemotron's step).  No more than 32 MiB: asked
# for 64 the same kernel ran 6 % slower at 8192 keys, alone and in a step.
# (on the v5e, the kernels alone, ms forward / two kernels / one, causal,
# 1024 x 1024 blocks: 32 query over 8 K/V heads of 64 at T 8192 4.9 / 15.8 /
# 9.0; 2 x 16 heads of 128 at T 4096 1.79 / 4.63 / 2.84; 16 heads 0.71 /
# 2.25 / 1.34.  With the tile not transposed the one kernel read 10.0, 3.13,
# 1.50; with queries innermost and dq resident 11.8, 3.27, 1.64.)
FLASH_BWD_VMEM_BYTES = 32 << 20    # scoped VMEM a backward kernel may take


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, block_q, block_k, num_k_blocks,
                         causal, sm_scale):
    """Grid (bh, q_blocks, k_blocks), k innermost: dq for one query block
    accumulates over streamed K/V blocks in a VMEM scratch."""
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        q32 = q_ref[0].astype(jnp.float32) * sm_scale      # [bq, D]
        kblk = k_ref[0].astype(jnp.float32)                # [bk, D]
        vblk = v_ref[0].astype(jnp.float32)                # [bk, Dv]
        do = do_ref[0].astype(jnp.float32)                 # [bq, Dv]
        lse = lse_ref[0]                                   # [bq, 1]
        delta = delta_ref[0]                               # [bq, 1]
        s = jax.lax.dot_general(
            q32, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        if causal:
            qpos = j * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse)                               # normalized probs
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = p * (dp - delta)
        acc_ref[...] += jax.lax.dot_general(
            ds, kblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, D]

    if causal:
        pl.when(kb * block_k <= (j + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kb == num_k_blocks - 1)
    def _write():
        dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, block_q,
                          block_k, num_q_blocks, causal, sm_scale, group=1):
    """Grid (K/V batch-heads, k_blocks, group * q_blocks), the last
    innermost: dk/dv for one key block accumulate in VMEM scratches over
    the streamed Q/dO blocks of every query head of the group, one head
    after the other."""
    kb = pl.program_id(1)
    step = pl.program_id(2)
    j = step if group == 1 else step % num_q_blocks

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        q32 = q_ref[0].astype(jnp.float32) * sm_scale      # [bq, D]
        kblk = k_ref[0].astype(jnp.float32)                # [bk, D]
        vblk = v_ref[0].astype(jnp.float32)                # [bk, Dv]
        do = do_ref[0].astype(jnp.float32)                 # [bq, Dv]
        lse = lse_ref[0]                                   # [bq, 1]
        delta = delta_ref[0]                               # [bq, 1]
        s = jax.lax.dot_general(
            q32, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        if causal:
            qpos = j * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse)                               # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, Dv]
        dp = jax.lax.dot_general(
            do, vblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, bk]
        ds = p * (dp - delta)
        dk_acc[...] += jax.lax.dot_general(
            ds, q32, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, D]

    if causal:
        # query blocks entirely above the diagonal see this key block masked
        pl.when((j + 1) * block_q - 1 >= kb * block_k)(_compute)
    else:
        _compute()

    @pl.when(step == group * num_q_blocks - 1)
    def _write():
        # q32 already carried sm_scale, so dk_acc is fully scaled
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_one_pass_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, dq_ref, dk_ref, dv_ref, dq_acc, *,
                               block_q, block_k, num_q_blocks, num_k_blocks,
                               causal, sm_scale):
    """Grid (K/V batch-heads, group * q_blocks, k_blocks), k innermost: one
    visit a (query block, key block) tile, all three gradients from one
    recomputation of the scores.  dq for one query block accumulates in a
    VMEM scratch over the streamed K/V blocks; dk and dv of the WHOLE
    sequence of one K/V head are the resident float32 output blocks, summed
    over the query blocks of every query head of the group in turn, and
    leave VMEM once a K/V head.

    The tile is held TRANSPOSED, [block_k, block_q] (lse and delta come as
    rows): dv += p^T do and dk += ds^T q are then plain products and dq +=
    ds k the one transposed contraction, where the [block_q, block_k]
    orientation of the two kernels above has two."""
    step = pl.program_id(1)
    kb = pl.program_id(2)
    j = step % num_q_blocks

    @pl.when((step == 0) & (kb == 0))
    def _init_kv():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(kb == 0)
    def _init_q():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        q32 = q_ref[0].astype(jnp.float32) * sm_scale      # [bq, D]
        kblk = k_ref[0].astype(jnp.float32)                # [bk, D]
        vblk = v_ref[0].astype(jnp.float32)                # [bk, Dv]
        do = do_ref[0].astype(jnp.float32)                 # [bq, Dv]
        lse = lse_ref[0]                                   # [1, bq]
        delta = delta_ref[0]                               # [1, bq]
        st = jax.lax.dot_general(
            kblk, q32, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, bq]
        if causal:
            kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, st.shape, 0)
            qpos = j * block_q + lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(kpos <= qpos, st, NEG_INF)
        pt = jnp.exp(st - lse)                             # normalized probs
        rows = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        dv_ref[0, rows, :] += jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, Dv]
        dpt = jax.lax.dot_general(
            vblk, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, bq]
        dst = pt * (dpt - delta)
        # q32 carries sm_scale, so dk is fully scaled
        dk_ref[0, rows, :] += jax.lax.dot_general(
            dst, q32, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, D]
        dq_acc[...] += jax.lax.dot_general(
            dst, kblk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, D]

    if causal:
        pl.when(kb * block_k <= (j + 1) * block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kb == num_k_blocks - 1)
    def _write():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _one_pass_fits(Tq, Tk, D, Dv, block_q, block_k):
    """Whether the one-pass backward takes this call, from the static shapes
    and the blocks alone: what Mosaic holds for it in VMEM (dk, dv of a K/V
    head's whole sequence and the blocks q, do, dq, lse, delta, k, v in both
    pipeline buffers; the dq accumulator; two float32 tiles) within
    ``FLASH_BWD_VMEM_BYTES``, and a row of lse it can cut into blocks."""
    d, dv = -(-D // 128) * 128, -(-Dv // 128) * 128    # whole lane tiles
    resident = 2 * Tk * (d + dv) * 4
    streamed = 2 * (block_q * (2 * d + dv + 2) + block_k * (d + dv)) * 4
    held = block_q * d * 4 + 2 * block_q * block_k * 4
    return (resident + streamed + held <= FLASH_BWD_VMEM_BYTES
            and (block_q % 128 == 0 or block_q == Tq))


def _flash_bwd_params(interpret, *semantics):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=FLASH_BWD_VMEM_BYTES)}


def _flash_bwd_one_pass(q, k, v, g, lse, delta, causal, sm_scale, block_q,
                        block_k, interpret):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    Dv = v.shape[2]
    nq = Tq // block_q
    nk = Tk // block_k
    group = BH // k.shape[0]

    def of_q(i, step, kb):           # the group's query heads in turn
        return (i * group + step // nq, step % nq, 0)

    def of_row(i, step, kb):
        return (i * group + step // nq, 0, step % nq)

    def of_k(i, step, kb):
        return (i, kb, 0)

    def whole(i, step, kb):
        return (i, 0, 0)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_one_pass_kernel, block_q=block_q,
                          block_k=block_k, num_q_blocks=nq, num_k_blocks=nk,
                          causal=causal, sm_scale=sm_scale),
        out_shape=[
            _sds(q, (BH, Tq, D), q.dtype),
            _sds(k, k.shape[:2] + (D,), jnp.float32),
            _sds(v, v.shape, jnp.float32),
        ],
        grid=(k.shape[0], group * nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), of_q),
            pl.BlockSpec((1, block_k, D), of_k),
            pl.BlockSpec((1, block_k, Dv), of_k),
            pl.BlockSpec((1, block_q, Dv), of_q),
            pl.BlockSpec((1, 1, block_q), of_row),
            pl.BlockSpec((1, 1, block_q), of_row),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), of_q),
            pl.BlockSpec((1, Tk, D), whole),
            pl.BlockSpec((1, Tk, Dv), whole),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        **_flash_bwd_params(interpret, "parallel", "arbitrary", "arbitrary"),
    )(q, k, v, g, lse.reshape(BH, 1, Tq), delta.reshape(BH, 1, Tq))
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_bwd_two_pass(q, k, v, g, lse, delta, causal, sm_scale, block_q,
                        block_k, interpret):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    Dv = v.shape[2]
    nq = Tq // block_q
    nk = Tk // block_k
    group = BH // k.shape[0]
    kv = _kv_of(group)
    kwargs = _flash_bwd_params(interpret, "parallel", "parallel", "arbitrary")

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, num_k_blocks=nk, causal=causal,
                          sm_scale=sm_scale),
        out_shape=_sds(q, (BH, Tq, D), q.dtype),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda i, j, kb: (kv(i), kb, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda i, j, kb: (kv(i), kb, 0)),
            pl.BlockSpec((1, block_q, Dv), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j, kb: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda i, j, kb: (i, j, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(q, k, v, g, lse, delta)

    if group == 1:
        def of_q(i, kb, j):
            return (i, j, 0)
    else:
        def of_q(i, kb, step):       # the group's query heads in turn
            return (i * group + step // nq, step % nq, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, num_q_blocks=nq, causal=causal,
                          sm_scale=sm_scale, group=group),
        out_shape=[
            _sds(k, k.shape[:2] + (D,), k.dtype),
            _sds(v, v.shape, v.dtype),
        ],
        grid=(k.shape[0], nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, D), of_q),
            pl.BlockSpec((1, block_k, D), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_q, Dv), of_q),
            pl.BlockSpec((1, block_q, 1), of_q),
            pl.BlockSpec((1, block_q, 1), of_q),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda i, kb, j: (i, kb, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda i, kb, j: (i, kb, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


def _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q, block_k,
               interpret, g_lse=None):
    """dq, dk, dv by the route ``_one_pass_fits`` gives the static shapes."""
    # delta_i = sum_d dO_i · O_i  (rescaling term of dsoftmax); O(T·Dv) work,
    # fused by XLA — not worth a kernel.  A cotangent on lse folds in here:
    # dL/ds_ij = p_ij (dp_ij - delta_i + g_lse_i), so delta_eff = delta -
    # g_lse and the kernels run unchanged.
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                # [BH, Tq, 1]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32).reshape(delta.shape)
    one_pass = _one_pass_fits(q.shape[1], k.shape[1], q.shape[2], v.shape[2],
                              block_q, block_k)
    compile_cache.stats().bump(
        "route/flash_attention_bwd:" + ("one_pass" if one_pass
                                        else "two_pass"))
    bwd = _flash_bwd_one_pass if one_pass else _flash_bwd_two_pass
    return bwd(q, k, v, g, lse, delta, causal, sm_scale, block_q, block_k,
               interpret)


def _reference_attention(q, k, v, causal, sm_scale):
    group = q.shape[0] // k.shape[0]
    if group > 1:                 # grouped-query heads: K, V head i // group
        k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", q * sm_scale, k)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                        interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, causal, sm_scale, block_q,
                      block_k, interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                      interpret)


def _flash_lse_vjp_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                       interpret):
    out, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_vjp_bwd(causal, sm_scale, block_q, block_k, interpret, res,
                       g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    return _flash_bwd(q, k, v, out, lse, g_out, causal, sm_scale, block_q,
                      block_k, interpret, g_lse=g_lse)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def flash_attention_with_lse(q, k, v, causal=False, sm_scale=None,
                             block_q=128, block_k=128, interpret=False):
    """Fused attention returning (out, lse [BH, Tq, 1]) — the streaming-
    softmax residual blockwise consumers (ring attention) merge across
    device hops.  q,k,v: [BH, T, D], block-divisible lengths.  Fully
    differentiable: an lse cotangent folds into the backward's delta."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    if q.shape[1] % bq or k.shape[1] % bk or (causal and
                                             q.shape[1] != k.shape[1]):
        raise ValueError(
            "flash_attention_with_lse needs block-divisible lengths "
            f"(got Tq={q.shape[1]}, Tk={k.shape[1]})")
    return _flash_lse(q, k, v, causal, sm_scale, bq, bk, interpret)


def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=128,
                    block_k=128, use_pallas=None, interpret=None):
    """Fused attention.  q,k,v: [B, T, H, D] (or [BH, T, D]).  K and V may
    have fewer heads than Q, a whole divisor (grouped-query heads): query
    head h reads K / V head h // (H / H_kv), through the kernels' index
    maps, and dK / dV sum over a group's query heads inside the backward
    kernel; counted as ``route/flash_attention:grouped``.

    The backward is one kernel that visits each (query block, key block)
    tile once where dK and dV of a K/V head's whole sequence fit VMEM
    beside a tile's temporaries (``_one_pass_fits``: from Tq, Tk, the
    feature sizes and the blocks alone; at 1024 x 1024 blocks up to 9 216
    keys of 64 or 128 features), and the two kernels that each
    recompute the scores for longer sequences; counted at trace time as
    ``route/flash_attention_bwd:{one_pass,two_pass}``.

    use_pallas=None auto-selects the Pallas kernel on TPU only; every other
    backend gets the exact jnp reference.  interpret=True (explicit, as the
    CPU tests do) runs the kernel through the Pallas interpreter instead.
    Which of the three ran is counted at trace time as
    ``route/flash_attention:{pallas,interpret,reference}`` in
    ``profiler.compile_stats()``.
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    squeeze_heads = q.ndim == 4
    if squeeze_heads:
        B, Tq_out, H, _ = q.shape

        def rs(x):
            b, t, h, d = x.shape
            return jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)

        q3, k3, v3 = rs(q), rs(k), rs(v)
    else:
        q3, k3, v3 = q, k, v
    if q3.shape[-1] != k3.shape[-1]:
        raise ValueError(
            f"flash_attention: q feature dim {q3.shape[-1]} != k feature "
            f"dim {k3.shape[-1]}")
    if k3.shape[0] != v3.shape[0] or q3.shape[0] % k3.shape[0]:
        raise ValueError(
            f"flash_attention: {k3.shape[0]} K and {v3.shape[0]} V "
            f"batch-heads do not divide Q's {q3.shape[0]}")
    if q3.shape[0] != k3.shape[0]:
        compile_cache.stats().bump("route/flash_attention:grouped")
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    interpret = bool(interpret)
    Tq, Tk = q3.shape[1], k3.shape[1]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    # ragged tail (kernel needs block-divisible lengths) or causal
    # cross-attention (kernel's diagonal offset assumes Tq==Tk) run the
    # exact jnp reference, as does every non-TPU backend
    if (use_pallas or interpret) and not (
            Tq % bq or Tk % bk or (causal and Tq != Tk)):
        compile_cache.stats().bump(
            "route/flash_attention:" + ("interpret" if interpret
                                        else "pallas"))
        out = _flash(q3, k3, v3, causal, sm_scale, bq, bk, interpret)
    else:
        compile_cache.stats().bump("route/flash_attention:reference")
        out = _reference_attention(q3, k3, v3, causal, sm_scale)
    if squeeze_heads:
        out = jnp.moveaxis(
            out.reshape(B, H, Tq_out, v.shape[-1]), 1, 2)
    return out


# ---------------------------------------------------------------------------
# grouped matmul (the expert products of the dropless ``moe`` lowering)
# ---------------------------------------------------------------------------
# Rows sorted by group, every group padded to whole row tiles (at least
# one), so a tile belongs to one group and no store is masked:
# ``tile_group[i]`` names tile i's group, ``num_tiles[0]`` the tiles in use
# (the length is the static worst case).  Past the count NOTHING is touched.
# The row axis of every grid ENDS at ``num_tiles[0]`` (a grid bound may be a
# value), so no grid step exists for a tile past it: an operand's tiles
# there are never read, a result's never written (as ``rows_from_tokens``
# leaves its own), and no elementwise work of XLA's stands between two
# kernels to read them: the experts' activation is the forward kernel's
# epilogue, and its derivative a kernel over the tiles in use (``_d_pre``).
# A product is a tiled matmul whose weight block, the whole contraction, is
# picked by ``tile_group`` and stays in VMEM over a group: one read from HBM.
#
# Every kernel takes a TUPLE of stacks that share the layout (the gate and
# up stacks of gated experts; one stack otherwise): a tile of rows is read
# and cast once for all of them, and the gradient of the rows is summed
# over the stacks before its one store.  A weight block is capped at
# ``GMM_BLOCK_ELEMS`` whatever the number of stacks: two stacks' blocks,
# double-buffered, are 32 MiB of ``GMM_VMEM_BYTES``.
#
# Float32 operands are rounded to bfloat16 at the MXU and accumulated in
# float32: one pass, what XLA's default precision does for every other
# product of a program on the TPU.  Interpreted (any other backend) the
# products are exact, as XLA's are there.
# (on the v5e, the dropless lowering of 8 x 8192 rows through 64 experts of
# [2048, 1024], forward and backward: 49.5 ms at these values and the
# lowering's row tiles of 128; 53.3 with blocks of 1 << 20, 49.5 with
# 1 << 22, 53.9 / 52.1 / 57.8 with tiles of 64 / 256 / 512; 75.2 with
# lax.ragged_dot in the kernels' place.  PERF.md section 6, PR 27.  With
# gate and up as a pair, PR 31: the ``moe`` op 47.6 -> 42.6 ms a step, its
# experts stage 28.7 -> 23.7 in six kernels for nine: of the pair's three,
# forward with ``act(gate) * up`` in its epilogue 4.64 ms (two single ones
# and the XLA fusion: 6.1), the rows' gradient 4.54 (two and XLA's add:
# 7.5), both stacks' gradients 5.02 (two: 5.6).  PERF.md section 6, PR 31.
# A share of 8 experts held, forward and backward, PR 41: at 28 of 392
# tiles in use and un-gated [1856, 2688] stacks the stage 8.94 -> 3.25 ms,
# at 68 of 264 and a [2048, 1792] pair 9.01 -> 5.83: of it the grids that
# end at the count 0.6 / 0.4 ms over grids of the bound whose steps past
# the count start no copy; the derivative in the prologue of the backward
# products instead of a kernel of its own 0.0 / 0.3 ms less, for 298 MB
# more where every expert is held.  PERF.md section 6, PR 41)
GMM_BLOCK_ELEMS = 1 << 21        # a weight / gradient block: 8 MiB of f32
GMM_VMEM_BYTES = 64 << 20        # scoped VMEM these kernels may take


def _largest_tile(n, cap):
    """The largest lane-aligned (128) divisor of ``n`` up to ``cap``; ``n``
    itself where it fits or has none."""
    if n <= cap:
        return n
    for t in range(cap - cap % 128, 0, -128):
        if n % t == 0:
            return t
    return n


def _mxu_operand(x, interpret):
    """A product's operand as the MXU takes it: compiled, float32 rounded
    to bfloat16; interpreted, as it is."""
    if not interpret and x.dtype == jnp.float32:
        return x.astype(jnp.bfloat16)
    return x


def _mxu_dot(a, b, contract, interpret):
    """The kernels' one product, float32 out.  Compiled: float32 operands
    rounded to bfloat16, one MXU pass whatever ``jax_default_matmul_precision``
    says (Mosaic has no multi-pass product of bfloat16 operands).
    Interpreted: as XLA computes it on that backend."""
    return jax.lax.dot_general(
        _mxu_operand(a, interpret), _mxu_operand(b, interpret),
        (contract, ((), ())),
        precision=None if interpret else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


def _tile_in_use(i, count):
    """Row-tile index for a block spec of a grid over the static bound
    (``rows_from_tokens``'): an unused tile (``i`` past the count) re-reads
    the last one in use, which costs no copy."""
    return jnp.minimum(i, count[0] - 1)


def _gmm_call_params(interpret, *semantics):
    if interpret:
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=GMM_VMEM_BYTES)}


def _activated(act, pre, up=None):
    """The experts' elementwise function of their first product(s):
    ``act(pre)``, gated ``act(pre) * up``."""
    return act(pre) if up is None else act(pre) * up


def _d_pre_kernel(g_ref, *refs, act):
    """``refs``: the tiles of the products before ``act``, then the blocks
    of their gradients."""
    pre_refs, out_refs = refs[:len(refs) // 2], refs[len(refs) // 2:]
    _, vjp = jax.vjp(functools.partial(_activated, act),
                     *(ref[...] for ref in pre_refs))
    for out_ref, d in zip(out_refs, vjp(g_ref[...])):
        out_ref[...] = d


def _d_pre(g, pre, tile_group, num_tiles, act, interpret):
    """The gradients of ``_activated(act, *pre)`` under the cotangent ``g``
    on the row tiles in use: ``jax.vjp`` of the function itself on a tile,
    so every elementwise ``act`` has its derivative here.  Rows past
    ``num_tiles`` are not written.
    (results written over ``pre``, ``input_output_aliases``: 298 MB MORE of
    temporaries at OLMoE's shape as XLA assigns them, not less)"""
    rows, n = g.shape
    tile = pl.BlockSpec((rows // tile_group.shape[0], n), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_d_pre_kernel, act=act),
        out_shape=[_sds(g, g.shape, g.dtype)] * len(pre),
        grid=(num_tiles[0],),
        in_specs=[tile] * (1 + len(pre)), out_specs=[tile] * len(pre),
        **_gmm_call_params(interpret, "parallel"),
    )(g, *pre)


def _gmm_kernel(group_ref, *refs, stacks, transpose_rhs, act, interpret):
    """``refs``: the row tile, the stacks' weight blocks, their outputs and,
    with ``act``, one more for ``_activated(act, *outputs)``.  With
    ``transpose_rhs``: the stacks' row tiles, their weight blocks, the ONE
    output that sums the stacks' products (and ``act`` of it)."""
    lhs_refs = refs[:stacks if transpose_rhs else 1]
    rhs_refs = refs[len(lhs_refs):len(lhs_refs) + stacks]
    out_refs = refs[len(lhs_refs) + stacks:]
    if transpose_rhs:
        outs = [functools.reduce(operator.add, (
            _mxu_dot(lhs_ref[...], rhs_ref[0], ((1,), (1,)), interpret)
            for lhs_ref, rhs_ref in zip(lhs_refs, rhs_refs))
        ).astype(out_refs[0].dtype)]
    else:
        lhs = _mxu_operand(lhs_refs[0][...], interpret)
        outs = [_mxu_dot(lhs, rhs_ref[0], ((1,), (0,)), interpret)
                .astype(out_refs[0].dtype) for rhs_ref in rhs_refs]
    if act is not None:              # of the values as stored, which the
        outs.append(_activated(act, *outs))            # backward reads
    for out_ref, out in zip(out_refs, outs):
        out_ref[...] = out.astype(out_ref.dtype)


def _gmm(lhs, rhs, tile_group, num_tiles, transpose_rhs, interpret,
         act=None):
    """``outs[s][r] = lhs[0][r] @ rhs[s][group of r's tile]`` for the stacks
    ``rhs`` (a tuple of [G, K, N]) and the one ``lhs`` (a 1-tuple).  With
    ``transpose_rhs`` the 1-tuple of ``sum_s lhs[s][r] @ rhs[s][..].T``
    (``rhs[s]`` [G, N, K], one ``lhs`` a stack).  With ``act`` one more
    result from the kernel's epilogue, ``_activated(act, *outs)``.  Grid
    (column blocks, row tiles in use), rows innermost, so a weight block
    changes only where the group does.  Rows past ``num_tiles`` are not
    written."""
    rows, k = lhs[0].shape
    tm = rows // tile_group.shape[0]
    n = rhs[0].shape[1] if transpose_rhs else rhs[0].shape[2]
    tn = _largest_tile(n, max(128, GMM_BLOCK_ELEMS // k))

    lhs_spec = pl.BlockSpec((tm, k), lambda j, i, group: (i, 0))
    rhs_spec = pl.BlockSpec(
        (1, tn, k), lambda j, i, group: (group[i], j, 0)
    ) if transpose_rhs else pl.BlockSpec(
        (1, k, tn), lambda j, i, group: (group[i], 0, j))
    outs = (1 if transpose_rhs else len(rhs)) + (act is not None)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, stacks=len(rhs),
                          transpose_rhs=transpose_rhs, act=act,
                          interpret=interpret),
        out_shape=[_sds(lhs[0], (rows, n), lhs[0].dtype)] * outs,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // tn, num_tiles[0]),
            in_specs=[lhs_spec] * len(lhs) + [rhs_spec] * len(rhs),
            out_specs=[pl.BlockSpec(
                (tm, tn), lambda j, i, group: (i, j))] * outs),
        **_gmm_call_params(interpret, "parallel", "arbitrary"),
    )(tile_group, *lhs, *rhs)


def _tgmm_kernel(group_ref, lhs_ref, *refs, interpret):
    """``refs``: the stacks' row tiles (cotangents), their output blocks."""
    stacks = len(refs) // 2
    i = pl.program_id(2)

    @pl.when(jnp.logical_or(
        i == 0, group_ref[i] != group_ref[jnp.maximum(i - 1, 0)]))
    def _init():
        for out_ref in refs[stacks:]:
            out_ref[...] = jnp.zeros_like(out_ref)

    lhs = _mxu_operand(lhs_ref[...], interpret)
    for rhs_ref, out_ref in zip(refs[:stacks], refs[stacks:]):
        out_ref[0] += _mxu_dot(lhs, rhs_ref[...], ((0,), (0,)),
                               interpret).astype(out_ref.dtype)


def _tgmm(lhs, rhs, tile_group, num_tiles, groups, interpret):
    """``outs[s][g] = lhs[rows of g].T @ rhs[s][rows of g]``: a [G, K, N] for
    each of the tuple ``rhs`` of [R, N], from the one [R, K].  Grid (K
    blocks, N blocks, row tiles in use), rows innermost: a group's output
    blocks stay in VMEM and accumulate over its tiles."""
    rows, k = lhs.shape
    n = rhs[0].shape[1]
    tm = rows // tile_group.shape[0]
    tk = _largest_tile(k, 1024)
    tn = _largest_tile(n, max(128, GMM_BLOCK_ELEMS // tk))

    return pl.pallas_call(
        functools.partial(_tgmm_kernel, interpret=interpret),
        out_shape=[_sds(lhs, (groups, k, n), lhs.dtype)] * len(rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(k // tk, n // tn, num_tiles[0]),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda a, b, i, group: (i, a))] + [
                pl.BlockSpec((tm, tn), lambda a, b, i, group: (i, b))
            ] * len(rhs),
            out_specs=[pl.BlockSpec(
                (1, tk, tn), lambda a, b, i, group:
                (group[i], a, b))] * len(rhs)),
        **_gmm_call_params(interpret, "parallel", "parallel", "arbitrary"),
    )(tile_group, lhs, *rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(lhs, rhs, tile_group, num_tiles, interpret, transpose_rhs=False):
    return _gmm((lhs,), (rhs,), tile_group, num_tiles, transpose_rhs,
                interpret)[0]


def _grouped_vjp_fwd(lhs, rhs, tile_group, num_tiles, interpret,
                     transpose_rhs=False):
    return (_gmm((lhs,), (rhs,), tile_group, num_tiles, transpose_rhs,
                 interpret)[0], (lhs, rhs, tile_group, num_tiles))


def _grouped_vjp_bwd(interpret, transpose_rhs, res, g):
    lhs, rhs, tile_group, num_tiles = res
    # (the stack's gradient in the stack's own orientation: g^T lhs where
    # the stack is read transposed, lhs^T g otherwise)
    a, b = (g, lhs) if transpose_rhs else (lhs, g)
    return (*_gmm((g,), (rhs,), tile_group, num_tiles, not transpose_rhs,
                  interpret),
            *_tgmm(a, (b,), tile_group, num_tiles, rhs.shape[0], interpret),
            None, None)


_grouped.defvjp(_grouped_vjp_fwd, _grouped_vjp_bwd)


def grouped_matmul(lhs, rhs, tile_group, num_tiles, transpose_rhs=False):
    """``out[r] = lhs[r] @ rhs[tile_group[r // tm]]`` for rows laid out in
    whole tiles per group (see above): ``lhs`` [R, K], ``rhs`` [G, K, N],
    ``tile_group`` int32 [R / tm] non-decreasing with every group present,
    ``num_tiles`` int32 [1]; the rows of the result past ``num_tiles`` are
    not written.  With ``transpose_rhs`` the stack is [G, N, K]
    and read transposed, ``lhs[r] @ rhs[..].T``: the same three kernels, in
    other places (a stack whose N is no whole lane tiles is held so, with K
    on the lanes: the device lays a [G, K, N] array out with K minor, a
    kernel reads row-major, and the stack, its gradient and the optimizer's
    moments would each be copied).  Differentiable in ``lhs`` and ``rhs``.
    The Pallas kernels everywhere: compiled on the TPU, interpreted on any
    other backend."""
    return _grouped(lhs, rhs, tile_group, num_tiles,
                    jax.default_backend() != "tpu", transpose_rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _gated(rows, w_gate, w_up, tile_group, num_tiles, act, interpret,
           transpose_rhs=False):
    return _gated_vjp_fwd(rows, w_gate, w_up, tile_group, num_tiles, act,
                          interpret, transpose_rhs)[0]


def _gated_vjp_fwd(rows, w_gate, w_up, tile_group, num_tiles, act,
                   interpret, transpose_rhs=False):
    """``w_gate`` None: un-gated experts, one stack (``transpose_rhs``: held
    [G, N, K]); the residuals are the products before ``act``."""
    stacks = (w_up,) if w_gate is None else (w_gate, w_up)
    *pre, hidden = _gmm((rows,), stacks, tile_group, num_tiles,
                        transpose_rhs, interpret, act)
    return hidden, (rows, stacks, tile_group, num_tiles, tuple(pre))


def _gated_vjp_bwd(act, interpret, transpose_rhs, res, g):
    rows, stacks, tile_group, num_tiles, pre = res
    d_pre = _d_pre(g, pre, tile_group, num_tiles, act, interpret)
    # (a stack's gradient in the stack's own orientation, as ``_grouped``'s)
    a, b = (d_pre[0], (rows,)) if transpose_rhs else (rows, d_pre)
    return (*_gmm(d_pre, stacks, tile_group, num_tiles, not transpose_rhs,
                  interpret),
            *([None] * (2 - len(stacks))),
            *_tgmm(a, b, tile_group, num_tiles, stacks[0].shape[0],
                   interpret),
            None, None)


_gated.defvjp(_gated_vjp_fwd, _gated_vjp_bwd)


def gated_grouped_matmul(rows, w_gate, w_up, tile_group, num_tiles, act,
                         transpose_rhs=False):
    """``act(rows @ w_gate[g]) * (rows @ w_up[g])`` in ``grouped_matmul``'s
    layout, gate and up as ONE pass a direction: a tile of ``rows`` is read
    once for both stacks, forward and in the stacks' gradients, and the
    gradient of ``rows`` is summed over the two stacks inside its kernel.
    ``w_gate`` None: un-gated experts, ``act(rows @ w_up[g])`` (with
    ``transpose_rhs`` the stack is [G, N, K], as ``grouped_matmul``'s).
    ``act``, an elementwise function, is the forward kernel's epilogue and
    its derivative a kernel over the tiles in use (``_d_pre``), so nothing
    of XLA's reads a row past ``num_tiles``.
    Differentiable in ``rows`` and the stacks."""
    if transpose_rhs and w_gate is not None:
        raise ValueError("gated_grouped_matmul: only an un-gated stack is "
                         "read transposed")
    return _gated(rows, w_gate, w_up, tile_group, num_tiles, act,
                  jax.default_backend() != "tpu", transpose_rhs)


# ---------------------------------------------------------------------------
# rope (the half-rotation of the ``rope`` lowering, ops/nn_ops.py)
# ---------------------------------------------------------------------------
# ``concat(-x[D/2:], x[:D/2])`` is ``roll(x, D/2)`` times a sign per lane,
# and the sign goes into the sine table.  XLA writes the two halves to HBM
# (a minor dimension of D/2 = 64 in tiles of 128 lanes: each half as large
# as ``x``) and reads them back, 3.5 times the bytes of one read and one
# write; here the rotation happens on the block in VMEM.  The kernel works on
# the head-major view [B, H, T, D], which is ``flash_attention``'s: the
# transposes around it are logical, and XLA's layout assignment gives them to
# the projection before and the attention kernels after, so no copy is left.
ROPE_ROWS = 256                  # positions a block
ROPE_BLOCK_BYTES = 2 << 20       # a block of X as float32: in and out,
#                                  double-buffered, stay under half of the
#                                  16 MiB of scoped VMEM


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                 # [heads, rows, D]
    turned = pltpu.roll(x, x.shape[2] // 2, axis=2)
    o_ref[...] = (x * cos_ref[...] + turned * sin_ref[...]).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rope_call(x, cos, sin, interpret):
    """``x * cos + roll(x, D/2) * sin`` for ``x`` [B, H, T, D] and float32
    tables [T, D]: ``x`` read once, the result written once.  Jitted, so a
    program that calls it many times lowers the kernel once."""
    b, h, t, d = x.shape
    heads = max(n for n in range(1, h + 1) if h % n == 0
                and n * ROPE_ROWS * d * 4 <= ROPE_BLOCK_BYTES)
    block = pl.BlockSpec((None, heads, ROPE_ROWS, d),
                         lambda i, j, k: (i, k, j, 0))
    table = pl.BlockSpec((ROPE_ROWS, d), lambda i, j, k: (j, 0))
    kwargs = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"))}
    # heads innermost: a table block is fetched once for all of them
    return pl.pallas_call(
        _rope_kernel, out_shape=_sds(x, x.shape, x.dtype),
        grid=(b, t // ROPE_ROWS, h // heads),
        in_specs=[block, table, table], out_specs=block,
        interpret=interpret, **kwargs)(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rope_turn(x, cos, sin, interpret):
    return _rope_call(x, cos, sin, interpret)


def _rope_turn_fwd(x, cos, sin, interpret):
    return _rope_call(x, cos, sin, interpret), (cos, sin)


def _rope_turn_bwd(interpret, res, g):
    # d/dx of x * cos + roll(x, D/2) * sin with a sine table whose halves
    # differ in sign only: the same kernel with that table negated.  The
    # tables are the only residuals; nothing of x is kept.
    cos, sin = res
    return _rope_call(g, cos, -sin, interpret), None, None


_rope_turn.defvjp(_rope_turn_fwd, _rope_turn_bwd)


def rope_route(shape, dtype, interpret=False):
    """Which lowering a ``rope`` of X [B, T, H, D] takes: the kernel
    (``pallas`` on a TPU; ``interpret``, which only a test asks for) where
    D is whole lane tiles (one head's rows fitting a block), T whole row
    blocks and X float32 or bfloat16; ``reference``, the op's formula
    through XLA, for every other shape and backend."""
    eligible = (len(shape) == 4 and shape[3] % 128 == 0
                and ROPE_ROWS * shape[3] * 4 <= ROPE_BLOCK_BYTES
                and shape[1] % ROPE_ROWS == 0
                and dtype in (jnp.float32, jnp.bfloat16))
    if eligible and interpret:
        return "interpret"
    if eligible and jax.default_backend() == "tpu":
        return "pallas"
    return "reference"


def rope_turn(x, cos, sin, interpret=False):
    """``x * cos + roll(x, D/2, axis=-1) * sin`` for ``x`` [B, T, H, D] that
    ``rope_route`` takes and float32 tables [T, D], in ``x``'s dtype, computed
    in float32.  Differentiable in ``x``."""
    out = _rope_turn(jnp.moveaxis(x, 2, 1), cos, sin, interpret)
    return jnp.moveaxis(out, 1, 2)


# ---------------------------------------------------------------------------
# short_conv (the gates, taps, bias and activation of the ``short_conv``
# lowering, ops/nn_ops.py)
# ---------------------------------------------------------------------------
# Filter [C, L]: c[t] = sum_j Filter[:, j] * v[t - (L-1) + j].  Gated: X [B,
# T, 3C] = [Bg | Cg | u], v = Bg * u, Out = Cg * c.  Ungated: X [B, T, C],
# v = X, Out = act(c + Bias), act SiLU or nothing.  ONE pair of kernels, told
# the two static facts ``gated`` and ``act``.  Through XLA the gated forward
# is two fusions (v goes to memory and comes back) and the backward five and
# the pads that put the three gradients side by side: 2.3 and 3.4 times the
# bytes of one pass.  Here a block is ``SHORT_CONV_ROWS`` positions of ALL the
# features, so the parts of X are lane-aligned slices of one block and dX is
# written as one array; a tap reads the block rotated along the positions in
# fast memory, its first rows patched from the 8 rows before the block (the
# backward's from the 8 rows after: a second and third view of the same
# arrays).  The cotangent of c at the rows after a block is one product where
# gated; ungated it wants the activation's slope there, so the filter runs
# over that halo too (the rows before ITS first are the block's last).  The
# filter's and the bias's gradients accumulate in blocks that stay resident
# over the whole grid.
SHORT_CONV_ROWS = 64             # positions a block: X, dX and the cotangent,
#                                  double-buffered, are 7 MiB at C = 2048
SHORT_CONV_LANES = 512           # features a pass inside a block, where that
#                                  divides C (else 256, else 128)
_HALO = 8                        # rows of a halo view (a sublane tile)


def _shifted(v, edge, shift, row, down):
    """``v`` moved ``shift`` rows down the positions (``down``: row t takes
    v[t - shift], the first rows from the last of ``edge``, the 8 rows
    before the block) or up (row t takes v[t + shift], the last rows from
    the first of ``edge``, the 8 rows after)."""
    rows = v.shape[0]
    out = pltpu.roll(v, shift if down else rows - shift, axis=0)
    for r in range(shift):
        at, src = (r, _HALO - shift + r) if down else (rows - shift + r, r)
        out = jnp.where(row == at, edge[src:src + 1], out)
    return out


def _lane_passes(channels):
    lanes = next(n for n in (SHORT_CONV_LANES, 256, 128) if channels % n == 0)
    return [(at, lanes) for at in range(0, channels, lanes)]


def _behind(v, edge, taps):
    """[v[t - s] for s < taps]: ``v`` and its copies moved down the
    positions, the rows before its first from the last of ``edge``."""
    row = lax.broadcasted_iota(jnp.int32, v.shape, 0)
    return [v] + [_shifted(v, edge, s, row, True) for s in range(1, taps)]


def _filtered(behind, w):
    """sum_s w[L-1-s] * v[t - s] from ``_behind``'s list."""
    taps = len(behind)
    acc = behind[0] * w[taps - 1:taps]
    for s in range(1, taps):
        acc = acc + behind[s] * w[taps - 1 - s:taps - s]
    return acc


def _act_and_slope(pre, act):
    """(act(pre), d act / d pre)."""
    if act is None:
        return pre, jnp.ones_like(pre)
    s = jax.nn.sigmoid(pre)
    return pre * s, s * (1.0 + pre * (1.0 - s))


def _part(ref, k, c, at, lanes):
    """Lanes ``at`` .. ``at + lanes`` of the ``k``-th of the C-wide parts of
    a block, in float32."""
    return ref[:, k * c + at:k * c + at + lanes].astype(jnp.float32)


def _filter_input(ref, c, at, lanes, gated):
    """v of the rows of a block or a halo: Bg * u, or X itself."""
    if gated:
        return _part(ref, 0, c, at, lanes) * _part(ref, 2, c, at, lanes)
    return _part(ref, 0, c, at, lanes)


def _short_conv_kernel(x_ref, before_ref, w_ref, *rest, taps, gated, act):
    """``rest``: the bias (ungated only), then Out."""
    o_ref = rest[-1]
    i = pl.program_id(1)
    c = o_ref.shape[1]
    for at, lanes in _lane_passes(c):
        w = w_ref[:, at:at + lanes].astype(jnp.float32)        # [L, lanes]
        before = jnp.where(
            i > 0, _filter_input(before_ref, c, at, lanes, gated), 0.0)
        acc = _filtered(_behind(_filter_input(x_ref, c, at, lanes, gated),
                                before, taps), w)
        if gated:
            out = _part(x_ref, 1, c, at, lanes) * acc
        else:
            out = _act_and_slope(acc + _part(rest[0], 0, c, at, lanes),
                                 act)[0]
        o_ref[:, at:at + lanes] = out.astype(o_ref.dtype)


def _short_conv_bwd_kernel(x_ref, before_ref, after_ref, g_ref, g_after_ref,
                           w_ref, *rest, taps, gated, act, num_blocks):
    """``rest``: the bias (ungated only), then dX, dFilter and (ungated
    only) dBias."""
    dx_ref, dw_ref = rest[-2:] if gated else rest[1:3]
    b, i = pl.program_id(0), pl.program_id(1)
    rows, c = g_ref.shape

    @pl.when(jnp.logical_and(b == 0, i == 0))
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if not gated:
            rest[3][...] = jnp.zeros_like(rest[3])

    for at, lanes in _lane_passes(c):
        row = lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
        w = w_ref[:, at:at + lanes].astype(jnp.float32)
        g = _part(g_ref, 0, c, at, lanes)
        g_after = _part(g_after_ref, 0, c, at, lanes)
        v = _filter_input(x_ref, c, at, lanes, gated)
        before = jnp.where(
            i > 0, _filter_input(before_ref, c, at, lanes, gated), 0.0)
        behind = _behind(v, before, taps)
        acc = _filtered(behind, w)
        # d_acc: the cotangent of the filter's sum, here and at the 8 rows
        # after the block
        if gated:
            d_acc = g * _part(x_ref, 1, c, at, lanes)
            d_after = g_after * _part(after_ref, 1, c, at, lanes)
        else:
            bias = _part(rest[0], 0, c, at, lanes)
            d_acc = g * _act_and_slope(acc + bias, act)[1]
            d_after = g_after * _act_and_slope(_filtered(_behind(
                _part(after_ref, 0, c, at, lanes), v[rows - _HALO:], taps),
                w) + bias, act)[1]
            rest[3][:, at:at + lanes] += jnp.sum(d_acc, axis=0, keepdims=True)
        d_after = jnp.where(i < num_blocks - 1, d_after, 0.0)
        d_v = d_acc * w[taps - 1:taps]
        for s in range(taps):
            tap = slice(taps - 1 - s, taps - s)
            if s:
                d_v = d_v + _shifted(d_acc, d_after, s, row, False) * w[tap]
            dw_ref[tap, at:at + lanes] += jnp.sum(d_acc * behind[s], axis=0,
                                                   keepdims=True)
        parts = (d_v * _part(x_ref, 2, c, at, lanes), g * acc,
                 d_v * _part(x_ref, 0, c, at, lanes)) if gated else (d_v,)
        for k, value in enumerate(parts):
            dx_ref[:, k * c + at:k * c + at + lanes] = value.astype(
                dx_ref.dtype)


def _short_conv_specs(x):
    """(grid, block of X, the 8 rows before it, the 8 rows after it, their
    likes for an array [B, T, C])."""
    b, t, _ = x.shape
    per, last = SHORT_CONV_ROWS // _HALO, t // _HALO - 1

    def views(width):
        return (pl.BlockSpec((None, SHORT_CONV_ROWS, width),
                             lambda b, i: (b, i, 0)),
                pl.BlockSpec((None, _HALO, width), lambda b, i:
                             (b, jnp.maximum(i * per - 1, 0), 0)),
                pl.BlockSpec((None, _HALO, width), lambda b, i:
                             (b, jnp.minimum((i + 1) * per, last), 0)))
    return (b, t // SHORT_CONV_ROWS), views


def _short_conv_params(interpret):
    return {"interpret": True} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))}


def _resident(rows, channels):
    return pl.BlockSpec((rows, channels), lambda b, i: (0, 0))


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _short_conv_call(x, w, bias, act, interpret):
    """(jitted, as ``_rope_call`` is: a module traces and lowers the kernel
    once however many layers call it; its body is 17 unrolled lane passes at
    4352 channels, a second of tracing a call)"""
    channels, taps = w.shape
    gated = bias is None
    grid, views = _short_conv_specs(x)
    block, before, _ = views(x.shape[2])
    more = () if gated else (bias[None],)
    return pl.pallas_call(
        functools.partial(_short_conv_kernel, taps=taps, gated=gated,
                          act=act),
        out_shape=_sds(x, x.shape[:2] + (channels,), x.dtype), grid=grid,
        in_specs=[block, before, _resident(taps, channels)]
        + [_resident(1, channels) for _ in more],
        out_specs=views(channels)[0],
        **_short_conv_params(interpret))(x, x, w.T, *more)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _short_conv_bwd_call(x, w, bias, g, act, interpret):
    channels, taps = w.shape
    gated = bias is None
    grid, views = _short_conv_specs(x)
    g_block, _, g_after = views(channels)
    more = () if gated else (bias[None],)
    dx, dw, *db = pl.pallas_call(
        functools.partial(_short_conv_bwd_kernel, taps=taps, gated=gated,
                          act=act, num_blocks=grid[1]),
        out_shape=[_sds(x, x.shape, x.dtype),
                   _sds(w, (taps, channels), jnp.float32)]
        + [_sds(m, m.shape, jnp.float32) for m in more],
        grid=grid,
        in_specs=[*views(x.shape[2]), g_block, g_after,
                  _resident(taps, channels)]
        + [_resident(1, channels) for _ in more],
        out_specs=[views(x.shape[2])[0], _resident(taps, channels)]
        + [_resident(1, channels) for _ in more],
        **_short_conv_params(interpret))(x, x, x, g, g, w.T, *more)
    return dx, dw.T.astype(w.dtype), \
        None if gated else db[0][0].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _short_conv(x, w, bias, act, interpret):
    return _short_conv_call(x, w, bias, act=act, interpret=interpret)


def _short_conv_fwd(x, w, bias, act, interpret):
    return _short_conv(x, w, bias, act, interpret), (x, w, bias)


def _short_conv_bwd(act, interpret, res, g):
    return _short_conv_bwd_call(*res, g, act=act, interpret=interpret)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def short_conv_route(shape, taps, dtype, interpret=False, gated=True,
                     act=None):
    """Which lowering a ``short_conv`` of X [B, T, 3C] (gated) or [B, T, C]
    (ungated) takes: the kernels (``pallas`` on a TPU; ``interpret``, which
    only a test asks for) where C is whole lane tiles, T whole blocks of
    ``SHORT_CONV_ROWS`` positions, the taps fit a halo, X is float32 or
    bfloat16 and the activation is SiLU or none; ``xla``, the op's formula,
    for every other shape and backend."""
    parts = 3 if gated else 1
    eligible = (len(shape) == 3 and shape[2] % (parts * 128) == 0
                and shape[1] % SHORT_CONV_ROWS == 0 and 1 <= taps <= _HALO
                and dtype in (jnp.float32, jnp.bfloat16)
                and act in (None, "silu") and not (gated and act))
    if eligible and interpret:
        return "interpret"
    if eligible and jax.default_backend() == "tpu":
        return "pallas"
    return "xla"


def short_conv(x, w, bias=None, act=None, interpret=False):
    """The short causal depthwise filter ``w`` [C, L] over ``x`` that
    ``short_conv_route`` takes.  ``bias`` None, the gated form: ``Cg *
    filter(Bg * u)`` for ``x`` [B, T, 3C] = [Bg | Cg | u].  ``bias`` [C], the
    ungated form: ``act(filter(x) + bias)`` for ``x`` [B, T, C], ``act`` None
    or ``"silu"``.  X read once and Out [B, T, C] written once; the backward
    reads X and the cotangent once and writes dX once.  In ``x``'s dtype,
    computed in float32.  Differentiable in ``x``, ``w`` and ``bias``."""
    return _short_conv(x, w, bias, act, interpret)


# ---------------------------------------------------------------------------
# op registration (layer: layers.flash_attention)
# ---------------------------------------------------------------------------
from ..core.registry import register_op, register_tunable  # noqa: E402

# Autotuner knob declaration (paddle_tpu.tuning), next to the kernel it
# tunes.  Replay is fingerprint-coherent by construction: the winning
# blocks land in the flash_attention OP ATTRS (layers.flash_attention
# resolves omitted block_q/block_k through tuned() under the autotune
# flag), so they are part of the Program content digest every compile-
# cache key hashes.  Search needs the chip: benchmark/longctx.py --sweep
# is the measurement driver.
register_tunable(
    "pallas/flash_attention", side="device",
    space={"block_q": (512, 1024, 2048), "block_k": (1024, 2048, 4096)},
    default={"block_q": 1024, "block_k": 1024},
    description="flash-attention Pallas tile shape: rows of Q per grid "
                "step and the K-stream slab; 2048-row tiles additionally "
                "need the scoped-VMEM limit raised "
                "(xla/scoped_vmem_limit_kib).",
    pending_hardware=True,
    decision_rule="flip the default only when the on-chip longctx sweep "
                  "shows >= 1.10x median ms/step over 1024x1024 at BOTH "
                  "32k and 64k tokens (paired-window discipline, "
                  "spread < gain)")

# Paged KV-cache gather for the decode slot pool (serving/decode.py):
# replace the contiguous [S, Tmax, D] slabs with fixed-size pages plus a
# per-slot page table, gathered into the attention tile by a Pallas
# kernel — the vLLM layout, removing the max-len * slots HBM reservation.
# On this CPU container the contiguous slabs are strictly better (the
# gather is pure overhead without HBM pressure), so the search is
# pre-registered pending hardware rather than fabricated here.
register_tunable(
    "pallas/paged_kv_gather", side="device",
    space={"page_size": (16, 32, 64, 128), "gather_block": (128, 256, 512)},
    default={"page_size": 64, "gather_block": 256},
    description="paged KV-cache layout for incremental decode: tokens "
                "per cache page and the rows-per-grid-step of the Pallas "
                "page-table gather feeding attention_with_cache.",
    pending_hardware=True,
    decision_rule="adopt paging only when the on-chip decode benchmark "
                  "shows >= 1.15x decode tokens/s over the contiguous "
                  "slabs at >= 50% slot occupancy with mixed-length "
                  "traces, OR the contiguous reservation exceeds 25% of "
                  "HBM at the serving config — below either bar the "
                  "gather is pure overhead and the slabs stay")


@register_op("flash_attention")
def _flash_attention_op(ctx, ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    causal = attrs.get("causal", False)
    # First-class sequence parallelism: under a ShardedExecutor whose mesh
    # has sp>1, eligible self-attention lowers to ring attention over the
    # sp axis (parallel/ring_attention.py) — K/V circulate on ICI, memory
    # O(T/sp) — instead of one device-global attention.  Eligibility is
    # checked statically; ineligible shapes (cross-attention, ragged T)
    # fall back to the GSPMD whole-array kernel.
    sp = ctx.mesh_axis_size("sp")
    if (sp > 1 and attrs.get("sequence_parallel", True)
            # inside a shard_map manual region (a pipeline stage body)
            # entering another shard_map with a concrete mesh is an error
            and not manual_axes()
            and q.ndim in (3, 4) and q.shape[1] == k.shape[1]
            and q.shape[1] % sp == 0
            # grouped-query heads run the device-global kernels
            and q.shape == k.shape):
        from ..parallel.ring_attention import ring_attention_sharded
        q4, k4, v4 = (x[:, :, None, :] if x.ndim == 3 else x
                      for x in (q, k, v))
        out = ring_attention_sharded(
            q4, k4, v4, ctx.mesh, causal=causal,
            block_q=attrs.get("block_q", 1024),
            block_k=attrs.get("block_k", 1024),
            interpret=attrs.get("interpret", False))
        return {"Out": out[:, :, 0, :] if q.ndim == 3 else out}
    return {"Out": flash_attention(
        q, k, v,
        causal=causal,
        block_q=attrs.get("block_q", 1024),   # swept best at 16k AND 32k
        block_k=attrs.get("block_k", 1024),
        interpret=attrs.get("interpret", False))}


# ---------------------------------------------------------------------------
# Static shape/dtype rule: flash_attention is shape-preserving on Q.
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import ShapeError, dim_ok, first  # noqa: E402
from ..core.registry import register_shape_fn  # noqa: E402


@register_shape_fn("flash_attention")
def _flash_attention_shape(op, ins, attrs):
    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    for name, o in (("K", k), ("V", v)):
        if q.shape is not None and o.shape is not None:
            if len(o.shape) != len(q.shape) or \
                    not dim_ok(q.shape[-1], o.shape[-1]):
                raise ShapeError(
                    f"flash_attention: Q {list(q.shape)} vs {name} "
                    f"{list(o.shape)} (rank or head dim mismatch)")
            # grouped-query heads: K and V heads divide Q's ([B, T, H, D]:
            # H; [BH, T, D]: the leading dim)
            at = 2 if len(q.shape) == 4 else 0
            if len(q.shape) in (3, 4) and q.shape[at] >= 0 and \
                    o.shape[at] > 0 and q.shape[at] % o.shape[at]:
                raise ShapeError(
                    f"flash_attention: {name}'s {o.shape[at]} heads do "
                    f"not divide Q's {q.shape[at]}")
    return {"Out": q}


# Sharding propagation: flash_attention is shape-preserving on Q (the
# kernel runs per-shard under shard_map; batch/head sharding rides along).
from ..analysis.shard_prop import shard_same_as  # noqa: E402
from ..core.registry import register_shard_fn  # noqa: E402

register_shard_fn("flash_attention")(shard_same_as("Q"))


# ---------------------------------------------------------------------------
# rows_from_tokens (the row movement of the dropless ``moe`` lowering,
# ops/moe_ops.py: its dispatch, and the transpose of its combine)
# ---------------------------------------------------------------------------
# ``tiled[r] = src[token[r]]`` in ``grouped_matmul``'s layout, a row tile a
# grid step, for the tiles in use alone: a step past ``num_tiles`` starts no
# copy and its output block is the last one in use, so nothing is written
# there and the rows past the count are NEVER WRITTEN (they hold whatever
# the buffer held: no reader may look there, and the grouped kernels do
# not).  A row of a [N, D] array is not something a DMA can take (eight rows
# interleave in a tile of the (8, 128) layout), so the source is viewed as
# [N, D / 128, 128]: a row is then D / 128 whole sublanes, contiguous, and
# lands in a [tm, D / 128, 128] scratch; the output block [tm, D] takes it
# 128 lanes at a time (a load with a sublane stride).  XLA's own gather
# would do for the rows, but a loop of it over the tiles in use leaves a
# while loop's result, which the TPU compiler holds twice where a kernel
# reads it (1.44 GB in LFM2's step; PERF.md section 6, PR 35).
ROW_LANES = 128


def _rows_kernel(token_ref, count_ref, *refs, tm, n, weighted):
    """``refs``: the source in HBM, the output tile, the scratch the rows
    land in, the DMA semaphore; ``weighted``: the rows' weights (in SMEM)
    first, the partner tile [tm, D] after the source, and a second output
    [1, 1, tm] for the dots."""
    if weighted:
        weight_ref, src_ref, partner_ref, out_ref, dots_ref, landed, sem = refs
    else:
        src_ref, out_ref, landed, sem = refs
    i = pl.program_id(0)

    @pl.when(i < count_ref[0])
    def _tile():
        base = i * tm

        def start(r, carry):
            t = token_ref[base + r]

            @pl.when(t < n)
            def _row():
                pltpu.make_async_copy(src_ref.at[t], landed.at[r],
                                      sem).start()

            @pl.when(t >= n)             # a padding row: zeros, as a
            def _padding():              # product reads it
                landed[r] = jnp.zeros(landed.shape[1:], landed.dtype)
            return carry

        lax.fori_loop(0, tm, start, 0)

        def wait(r, carry):
            @pl.when(token_ref[base + r] < n)
            def _row():
                pltpu.make_async_copy(src_ref.at[0], landed.at[r],
                                      sem).wait()
            return carry

        lax.fori_loop(0, tm, wait, 0)
        groups, lanes = landed.shape[1:]
        if weighted:
            dots = jnp.zeros((tm, 1), jnp.float32)
            for c in range(groups):      # of the rows as they came
                beside = partner_ref[:, c * lanes:(c + 1) * lanes]
                dots += jnp.sum(landed[:, c, :] * beside, axis=1,
                                keepdims=True)
            dots_ref[0] = dots.reshape(1, tm).astype(dots_ref.dtype)

            def scale(r, carry):         # a row is whole sublanes here
                landed[r] = landed[r] * weight_ref[base + r]
                return carry

            lax.fori_loop(0, tm, scale, 0)
        for c in range(groups):
            out_ref[:, c * lanes:(c + 1) * lanes] = landed[:, c, :]


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _rows_call(token, num_tiles, src, weight, partner, tm, interpret):
    """The ``pallas_call`` of ``rows_from_tokens`` (``weight`` and
    ``partner`` both given or both None).  Jitted, so a program whose
    layers call it at one shape lowers the kernel once."""
    n, d = src.shape
    rows = token.shape[0]
    weighted = weight is not None
    lanes = d if d % ROW_LANES else ROW_LANES

    def tile(i, token, count, *weight):
        return _tile_in_use(i, count), 0

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    out_shape = [_sds(src, (rows, d), src.dtype)]
    out_specs = [pl.BlockSpec((tm, d), tile)]
    if weighted:
        in_specs.append(pl.BlockSpec((tm, d), tile))
        out_shape.append(_sds(src, (rows // tm, 1, tm), src.dtype))
        out_specs.append(pl.BlockSpec(
            (1, 1, tm), lambda i, token, count, weight:
            (_tile_in_use(i, count), 0, 0)))
    out = pl.pallas_call(
        functools.partial(_rows_kernel, tm=tm, n=n, weighted=weighted),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + weighted, grid=(rows // tm,),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((tm, d // lanes, lanes), src.dtype),
                pltpu.SemaphoreType.DMA(())]),
        **({"interpret": True} if interpret else
           {"compiler_params": pltpu.CompilerParams(
               dimension_semantics=("arbitrary",))}),
    )(token, num_tiles, *([weight] if weighted else []),
      src.reshape(n, d // lanes, lanes), *([partner] if weighted else []))
    return (out[0], out[1].reshape(rows)) if weighted else out[0]


def rows_from_tokens(src, token, num_tiles, tm, weight=None, partner=None):
    """``tiled[r] = src[token[r]]`` for the rows of the ``num_tiles`` (int32
    [1]) row tiles of ``tm`` in use: ``src`` [N, D] (a D that is no multiple
    of 128 moves as one group of lanes), ``token`` int32 [R] (past the end,
    ``>= N``: a padding row, zeros).  The rows of the tiles past the count
    are never written.  With ``weight`` [R] and ``partner`` [R, D], of the
    same rows: ``(weight[r] * src[token[r]], <src[token[r]], partner[r]>)``,
    the second [R].  Not differentiable (``_dropless`` holds the
    transposes).  The Pallas kernel everywhere: compiled on the TPU,
    interpreted on any other backend."""
    return _rows_call(token, num_tiles, src, weight, partner, tm=tm,
                      interpret=jax.default_backend() != "tpu")
