"""Hand-written Pallas TPU kernels for the 1x1-conv hot path.

RESULTS.md's corrected roofline (round 5) identifies XLA's conv emitters
as the binding constraint on ResNet training: the 1x1-conv/gradient
shapes run at ~51 TFLOP/s against a 57-115 TFLOP/s bandwidth-corrected
ceiling.  The reference framework answered the same problem by hand-
writing its hot kernels (paddle/cuda/src/hl_cuda_matrix.cu); the
TPU-native analog is this module: an im2col-free dot-based kernel pair
for 1x1 convolutions.

A 1x1 conv IS a matmul over the pixel dimension — x [N,C,H,W] viewed as
[P, C] (P = N*H*W) against the filter [M, C] — so all three passes
(forward, dgrad, wgrad) are instances of ONE blocked Pallas matmul with
transpose options:

    forward:  out[P, M] = x[P, C]    @ w[M, C]^T
    dgrad:    dx[P, C]  = gout[P, M] @ w[M, C]
    wgrad:    dw[M, C]  = gout[P, M]^T @ x[P, C]     (K = P, streamed)

The wgrad is the worst measured shape (deep-K reduction over every
pixel); its kernel streams P through VMEM in ``block_k`` slabs with an
f32 accumulator resident in VMEM — the flash-kernel pattern
(``pallas_kernels._flash_kernel``) applied to convolution.  Fused
epilogues ride the streams for free (the data is already in VMEM):

* forward can emit per-channel sum/sum-of-squares partials (the
  batch-norm statistics reduction — saves BN's separate HBM pass over
  the conv output);
* wgrad can emit the per-channel gout sum (the bias/BN-beta gradient).

``pallas_matmul`` carries a custom VJP whose backward runs the same
kernels, so ``conv2d_1x1`` is fully differentiable end-to-end and the
executor's autodiff pass routes conv gradients through the hand-written
path automatically.  Everything here is opt-in behind the conv2d op's
``use_pallas`` attribute (``layers.conv2d(use_pallas=True)``) — see
``ops/nn_ops._conv2d`` for the routing and ``benchmark/conv_kernel.py``
for the per-op A/B against XLA's emitters.

On non-TPU backends the kernels run only under ``interpret=True`` (the
CPU tests); eligibility gating lives in ``conv1x1_eligible``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pallas_matmul", "conv2d_1x1", "conv2d_1x1_with_bn_stats",
           "conv2d_1x1_grad_fused", "conv1x1_eligible"]


# ---------------------------------------------------------------------------
# generic blocked matmul kernel (the one kernel all three conv passes use)
# ---------------------------------------------------------------------------
def _mm_kernel(a_ref, b_ref, *refs, nk, ta, tb, out_stats, a_colsum):
    """Grid (m_blocks, n_blocks, k_blocks), k innermost/sequential: the
    f32 accumulator lives in VMEM scratch across the K stream; operands
    feed the MXU in their native dtype (bf16 in, f32 accumulate).

    ``out_stats``: also emit per-N-column sum / sum-of-squares of the
    finished output block (per-M-block partials) — the fused BN-
    statistics epilogue for the forward conv.
    ``a_colsum``: also emit the column sums of logical-A (requires
    ``ta``; K-streamed in scratch) — the fused bias/BN-beta gradient
    epilogue for the wgrad, where A is gout.
    """
    outs = list(refs)
    o_ref = outs.pop(0)
    sum_ref = outs.pop(0) if out_stats else None
    sq_ref = outs.pop(0) if out_stats else None
    csum_ref = outs.pop(0) if a_colsum else None
    acc_ref = outs.pop(0)
    csum_acc = outs.pop(0) if a_colsum else None

    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    ca = 0 if ta else 1            # storage axis holding K
    cb = 1 if tb else 0
    acc_ref[...] += lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        preferred_element_type=jnp.float32)

    if a_colsum:
        # gout column sums: accumulate only on the first N sweep (every j
        # sees the same A blocks; one sweep suffices)
        @pl.when(jnp.logical_and(j == 0, kb == 0))
        def _cs_init():
            csum_acc[...] = jnp.zeros_like(csum_acc)

        @pl.when(j == 0)
        def _cs_acc():
            csum_acc[...] += jnp.sum(a.astype(jnp.float32), axis=0,
                                     keepdims=True)

    @pl.when(kb == nk - 1)
    def _write():
        out = acc_ref[...]
        o_ref[...] = out.astype(o_ref.dtype)
        if out_stats:
            sum_ref[0] = jnp.sum(out, axis=0, keepdims=True)
            sq_ref[0] = jnp.sum(out * out, axis=0, keepdims=True)
        if a_colsum:
            @pl.when(j == 0)
            def _cs_write():
                csum_ref[...] = csum_acc[...]


def _pick_block(dim: int, target: int):
    """Largest multiple of 128 <= target that divides ``dim`` (None when
    dim itself is not 128-divisible — the caller gates on that)."""
    b = min(target, dim)
    b -= b % 128
    while b >= 128:
        if dim % b == 0:
            return b
        b -= 128
    return None


def _mm(a, b, ta, tb, block_m, block_n, block_k, interpret,
        out_stats=False, a_colsum=False, out_dtype=None):
    M, K = (a.shape[1], a.shape[0]) if ta else (a.shape[0], a.shape[1])
    N = b.shape[0] if tb else b.shape[1]
    bm, bn, bk = (_pick_block(M, block_m), _pick_block(N, block_n),
                  _pick_block(K, block_k))
    if bm is None or bn is None or bk is None:
        raise ValueError(
            f"pallas_matmul needs 128-divisible dims, got M={M} N={N} K={K}")
    nm, nn, nk = M // bm, N // bn, K // bk
    out_dtype = out_dtype or a.dtype

    a_spec = pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)) if ta \
        else pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    b_spec = pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)) if tb \
        else pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    out_shape = [jax.ShapeDtypeStruct((M, N), out_dtype)]
    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))]
    if out_stats:
        # per-M-block partials of the per-column output sums; the caller
        # finishes the tiny [nm, 1, N] reduction (BN statistics).  The
        # unit middle dim keeps the block's last two dims (1, bn) legal
        # for Mosaic: a (1, bn) block over an (nm, N) array is rejected
        # (second-to-last must be a multiple of 8 or the whole dim).
        out_shape += [jax.ShapeDtypeStruct((nm, 1, N), jnp.float32)] * 2
        out_specs += [pl.BlockSpec((1, 1, bn),
                                   lambda i, j, k: (i, 0, j))] * 2
    if a_colsum:
        assert ta, "a_colsum epilogue is the wgrad (gout^T) path"
        out_shape.append(jax.ShapeDtypeStruct((1, M), jnp.float32))
        out_specs.append(pl.BlockSpec((1, bm), lambda i, j, k: (0, i)))
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    if a_colsum:
        scratch.append(pltpu.VMEM((1, bm), jnp.float32))

    kwargs = {}
    if not interpret:
        # The a_colsum epilogue writes csum_ref (mapped to block (0, i)
        # for EVERY j) only under pl.when(j == 0): if Mosaic partitioned a
        # "parallel" j across megacore, a core whose j-range excludes 0
        # would copy its uninitialized VMEM output block over the result.
        # Keep j sequential whenever the epilogue is on.
        nsem = "arbitrary" if a_colsum else "parallel"
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", nsem, "arbitrary"))
    res = pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk, ta=ta, tb=tb,
                          out_stats=out_stats, a_colsum=a_colsum),
        out_shape=out_shape,
        grid=(nm, nn, nk),
        in_specs=[a_spec, b_spec],
        out_specs=out_specs,
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(a, b)
    return res if (out_stats or a_colsum) else res[0]


# ---------------------------------------------------------------------------
# differentiable matmul: backward runs the same kernels (dgrad/wgrad)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def pallas_matmul(a, b, trans_a=False, trans_b=False, block_m=512,
                  block_n=512, block_k=1024, interpret=False):
    """O = A_logical @ B_logical with A stored transposed when
    ``trans_a`` (likewise B).  Differentiable: the VJP lowers da/db to
    the same blocked kernel, so the wgrad (db with K = the big pixel
    dimension) is the hand-written K-streaming gradient kernel."""
    return _mm(a, b, trans_a, trans_b, block_m, block_n, block_k, interpret)


def _pm_fwd(a, b, trans_a, trans_b, block_m, block_n, block_k, interpret):
    return _mm(a, b, trans_a, trans_b, block_m, block_n, block_k,
               interpret), (a, b)


def _pm_bwd(trans_a, trans_b, block_m, block_n, block_k, interpret, res, g):
    a, b = res
    ta, tb = trans_a, trans_b
    if not ta:      # da_storage [M, K] = g @ B_logical^T
        da = _mm(g, b, False, not tb, block_m, block_n, block_k, interpret)
    else:           # da_storage [K, M] = B_logical @ g^T
        da = _mm(b, g, tb, True, block_m, block_n, block_k, interpret)
    if not tb:      # db_storage [K, N] = A_logical^T @ g
        db = _mm(a, g, not ta, False, block_m, block_n, block_k, interpret)
    else:           # db_storage [N, K] = g^T @ A_logical  (the deep-K wgrad)
        db = _mm(g, a, True, ta, block_m, block_n, block_k, interpret)
    return da.astype(a.dtype), db.astype(b.dtype)


pallas_matmul.defvjp(_pm_fwd, _pm_bwd)


# ---------------------------------------------------------------------------
# 1x1 convolution on the matmul view
# ---------------------------------------------------------------------------
def _to_pixel_major(x):
    """[N, C, H, W] -> [N*H*W, C] (the im2col of a 1x1 filter is a
    reshape)."""
    N, C, H, W = x.shape
    return jnp.transpose(x.reshape(N, C, H * W), (0, 2, 1)).reshape(-1, C), \
        (N, H, W)


def _from_pixel_major(om, dims, M):
    N, H, W = dims
    return jnp.transpose(om.reshape(N, H * W, M), (0, 2, 1)) \
        .reshape(N, M, H, W)


def conv2d_1x1(x, w, strides=(1, 1), block_m=512, block_n=512,
               block_k=1024, interpret=False):
    """NCHW 1x1 convolution (pad 0, dil 1, groups 1) through the Pallas
    dot kernel; fully differentiable (strided input gradients scatter
    through the slice like any jnp op)."""
    sh, sw = int(strides[0]), int(strides[1])
    if (sh, sw) != (1, 1):
        x = x[:, :, ::sh, ::sw]
    xm, dims = _to_pixel_major(x)
    M = w.shape[0]
    wm = w.reshape(M, -1)
    om = pallas_matmul(xm, wm, False, True, block_m, block_n, block_k,
                       interpret)
    return _from_pixel_major(om, dims, M)


def conv2d_1x1_with_bn_stats(x, w, strides=(1, 1), block_m=512,
                             block_n=512, block_k=1024, interpret=False):
    """Forward 1x1 conv with the fused BN-statistics epilogue: returns
    (out [N,M,H,W], csum [M], csumsq [M]) where csum/csumsq are the
    per-out-channel sum and sum-of-squares over N,H,W — computed from
    the output blocks while they are still in VMEM, saving batch-norm's
    separate reduction pass over the conv output in HBM."""
    sh, sw = int(strides[0]), int(strides[1])
    if (sh, sw) != (1, 1):
        x = x[:, :, ::sh, ::sw]
    xm, dims = _to_pixel_major(x)
    M = w.shape[0]
    wm = w.reshape(M, -1)
    om, psum, psq = _mm(xm, wm, False, True, block_m, block_n, block_k,
                        interpret, out_stats=True)
    return (_from_pixel_major(om, dims, M),
            jnp.sum(psum, axis=(0, 1)), jnp.sum(psq, axis=(0, 1)))


def conv2d_1x1_grad_fused(x, w, gout, strides=(1, 1), block_m=512,
                          block_n=512, block_k=1024, interpret=False):
    """The hand-written 1x1-conv gradient pass: (dx, dw, dsum) from one
    dgrad kernel and one K-streaming wgrad kernel whose epilogue fuses
    dsum = sum_{N,H,W} gout (the bias / BN-beta gradient) into the gout
    stream.  ``gout`` is [N, M, OH, OW] in the conv's output geometry."""
    sh, sw = int(strides[0]), int(strides[1])
    xs = x[:, :, ::sh, ::sw] if (sh, sw) != (1, 1) else x
    xm, dims = _to_pixel_major(xs)
    gm, _ = _to_pixel_major(gout)
    M, C = w.shape[0], w.shape[1]
    wm = w.reshape(M, C)
    # dgrad: dx [P, C] = gout [P, M] @ w [M, C]
    dxm = _mm(gm, wm, False, False, block_m, block_n, block_k, interpret)
    dx = _from_pixel_major(dxm, dims, C)
    if (sh, sw) != (1, 1):
        dx = jnp.zeros(x.shape, x.dtype).at[:, :, ::sh, ::sw].set(dx)
    # wgrad (+ fused dsum): dw [M, C] = gout^T @ x, K = P streamed
    dw, dsum = _mm(gm, xm, True, False, block_m, block_n, block_k,
                   interpret, a_colsum=True)
    return dx, dw.reshape(w.shape).astype(w.dtype), dsum.reshape(M)


def conv1x1_eligible(x_shape, w_shape, strides, pads, dils, groups) -> bool:
    """Static routing gate for ``ops.nn_ops._conv2d``: the kernel covers
    1x1 / groups-1 / pad-0 / dil-1 convs whose matmul-view dims are
    128-divisible (MXU lane tiles; ResNet's 1x1 shapes qualify from the
    256-channel stages up — the 64-channel stage-1 blocks stay on XLA)."""
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    if tuple(w_shape[2:]) != (1, 1) or int(groups or 1) != 1:
        return False
    if tuple(pads) != (0, 0) or tuple(dils) != (1, 1):
        return False
    N, C, H, W = x_shape
    M = w_shape[0]
    sh, sw = int(strides[0]), int(strides[1])
    P = N * ((H - 1) // sh + 1) * ((W - 1) // sw + 1)
    return C % 128 == 0 and M % 128 == 0 and P % 128 == 0
