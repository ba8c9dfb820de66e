"""Math / elementwise / reduction / comparison op lowerings.

Covers the reference's math category (SURVEY §2.2: elementwise_op.h, mul_op,
matmul_op.cc, sum_op, scale_op, cast_op, clip_op, clip_by_norm_op, sign_op,
logical_op, compare_op, reduce_op.cc) as jnp/lax lowerings.  Gradients come
from jax.vjp — no *_grad ops exist.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op
from ..core.types import convert_dtype


# ---------------------------------------------------------------------------
# elementwise binary with fluid broadcast semantics
# (reference: elementwise_op.h trailing-axis broadcast: Y's shape must match a
# contiguous run of X's dims starting at `axis`)
# ---------------------------------------------------------------------------
def _bcast(x, y, axis: int):
    if x.shape == y.shape or axis in (-1, None):
        return x, y
    if y.ndim > x.ndim:
        raise ValueError(f"elementwise: y rank {y.ndim} > x rank {x.ndim}")
    trailing = x.ndim - axis - y.ndim
    if trailing < 0:
        raise ValueError(f"elementwise: bad axis {axis} for shapes "
                         f"{x.shape} {y.shape}")
    y = y.reshape(y.shape + (1,) * trailing)
    return x, y


def _elementwise(fn):
    def impl(ctx, ins, attrs):
        x, y = ins["X"][0], ins["Y"][0]
        x, y = _bcast(x, y, attrs.get("axis", -1))
        return {"Out": fn(x, y)}
    return impl


register_op("elementwise_add")(_elementwise(jnp.add))
register_op("elementwise_sub")(_elementwise(jnp.subtract))
register_op("elementwise_mul")(_elementwise(jnp.multiply))
register_op("elementwise_div")(_elementwise(jnp.divide))
register_op("elementwise_pow")(_elementwise(jnp.power))
register_op("elementwise_max")(_elementwise(jnp.maximum))
register_op("elementwise_min")(_elementwise(jnp.minimum))
register_op("elementwise_mod")(_elementwise(jnp.mod))


@register_op("mul")
def _mul(ctx, ins, attrs):
    """fluid mul_op (mul_op.cc): flatten x/y to 2-D then matmul — the FC
    primitive.  Kept batched + bf16-friendly so it lands on the MXU."""
    x, y = ins["X"][0], ins["Y"][0]
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    xs, ys = x.shape, y.shape
    x2 = x.reshape((_prod(xs[:xn]), _prod(xs[xn:])))
    y2 = y.reshape((_prod(ys[:yn]), _prod(ys[yn:])))
    out = jnp.matmul(x2, y2)
    return {"Out": out.reshape(xs[:xn] + ys[yn:])}


def _prod(t):
    # no int() cast: dims may be symbolic (jax.export shape polymorphism)
    p = 1
    for v in t:
        p *= v
    return p


@register_op("matmul")
def _matmul(ctx, ins, attrs):
    """matmul_op.cc semantics: optional transposes, batched stacks."""
    x, y = ins["X"][0], ins["Y"][0]
    if attrs.get("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if attrs.get("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    out = jnp.matmul(x, y)
    alpha = attrs.get("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": out}


@register_op("sum")
def _sum(ctx, ins, attrs):
    """sum_op: add N tensors (used to merge multi-consumer grads)."""
    xs = ins["X"]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": out}


@register_op("scale")
def _scale(ctx, ins, attrs):
    x = ins["X"][0]
    s = attrs.get("scale", 1.0)
    b = attrs.get("bias", 0.0)
    if attrs.get("bias_after_scale", True):
        return {"Out": x * s + b}
    return {"Out": (x + b) * s}


@register_op("minus")
def _minus(ctx, ins, attrs):
    return {"Out": ins["X"][0] - ins["Y"][0]}


@register_op("cast")
def _cast(ctx, ins, attrs):
    dt = convert_dtype(attrs.get("out_dtype", attrs.get("dtype", "float32")))
    return {"Out": ins["X"][0].astype(dt)}


@register_op("clip")
def _clip(ctx, ins, attrs):
    return {"Out": jnp.clip(ins["X"][0], attrs["min"], attrs["max"])}


@register_op("clip_by_norm")
def _clip_by_norm(ctx, ins, attrs):
    x = ins["X"][0]
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    scale = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12), 1.0)
    return {"Out": x * scale.astype(x.dtype)}


@register_op("sign")
def _sign(ctx, ins, attrs):
    return {"Out": jnp.sign(ins["X"][0])}


@register_op("pow")
def _pow(ctx, ins, attrs):
    return {"Out": jnp.power(ins["X"][0], attrs.get("factor", 1.0))}


# -- logical / comparison ----------------------------------------------------
def _logical(fn, unary=False):
    def impl(ctx, ins, attrs):
        if unary:
            return {"Out": fn(ins["X"][0].astype(bool))}
        return {"Out": fn(ins["X"][0].astype(bool), ins["Y"][0].astype(bool))}
    return impl


register_op("logical_and")(_logical(jnp.logical_and))
register_op("logical_or")(_logical(jnp.logical_or))
register_op("logical_xor")(_logical(jnp.logical_xor))
register_op("logical_not")(_logical(jnp.logical_not, unary=True))


def _compare(fn):
    def impl(ctx, ins, attrs):
        x, y = ins["X"][0], ins["Y"][0]
        x, y = _bcast(x, y, attrs.get("axis", -1))
        return {"Out": fn(x, y)}
    return impl


register_op("equal")(_compare(jnp.equal))
register_op("not_equal")(_compare(jnp.not_equal))
register_op("less_than")(_compare(jnp.less))
register_op("less_equal")(_compare(jnp.less_equal))
register_op("greater_than")(_compare(jnp.greater))
register_op("greater_equal")(_compare(jnp.greater_equal))


# -- reductions (reduce_op.cc: dim/keep_dim/reduce_all attrs) ---------------
def _reduce(fn):
    def impl(ctx, ins, attrs):
        x = ins["X"][0]
        if attrs.get("reduce_all", False):
            axis = None
        else:
            dim = attrs.get("dim", [0])
            axis = tuple(dim) if isinstance(dim, (list, tuple)) else (int(dim),)
            axis = tuple(d % x.ndim for d in axis)
        keep = attrs.get("keep_dim", False)
        return {"Out": fn(x, axis=axis, keepdims=keep)}
    return impl


register_op("reduce_sum")(_reduce(jnp.sum))
register_op("reduce_mean")(_reduce(jnp.mean))
register_op("reduce_max")(_reduce(jnp.max))
register_op("reduce_min")(_reduce(jnp.min))
register_op("reduce_prod")(_reduce(jnp.prod))


@register_op("mean")
def _mean(ctx, ins, attrs):
    """mean_op: full reduction to scalar (loss averaging)."""
    return {"Out": jnp.mean(ins["X"][0])}


@register_op("increment")
def _increment(ctx, ins, attrs):
    return {"Out": ins["X"][0] + jnp.asarray(attrs.get("step", 1.0),
                                             ins["X"][0].dtype)}


@register_op("abs_diff", "squared_difference")
def _sq_diff(ctx, ins, attrs):
    d = ins["X"][0] - ins["Y"][0]
    return {"Out": d * d}


@register_op("cumsum")
def _cumsum(ctx, ins, attrs):
    return {"Out": jnp.cumsum(ins["X"][0], axis=attrs.get("axis", -1))}


@register_op("isfinite")
def _isfinite(ctx, ins, attrs):
    return {"Out": jnp.all(jnp.isfinite(ins["X"][0]))}


@register_op("l2_normalize", "norm")
def _l2_normalize(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return {"Out": x / jnp.maximum(norm, eps)}


# ---------------------------------------------------------------------------
# v1 attention-support / CTR ops (gserver layers without fluid successors)
# ---------------------------------------------------------------------------
@register_op("conv_shift")
def _conv_shift(ctx, ins, attrs):
    """ConvShiftLayer.cpp: circular correlation (NTM attention shift).
    X [B, M], Y [B, N] (N odd) -> Out[b, i] = sum_j X[b, (i + j - N//2) % M]
    * Y[b, j]."""
    x, y = ins["X"][0], ins["Y"][0]
    B, M = x.shape
    N = y.shape[1]
    half = N // 2
    cols = []
    for j in range(N):
        cols.append(jnp.roll(x, half - j, axis=1) * y[:, j:j + 1])
    return {"Out": sum(cols)}


@register_op("interpolation")
def _interpolation(ctx, ins, attrs):
    """InterpolationLayer.cpp: out = w*X + (1-w)*Y with per-row w [B,1]."""
    w, x, y = ins["W"][0], ins["X"][0], ins["Y"][0]
    w = w.reshape((-1,) + (1,) * (x.ndim - 1))
    return {"Out": w * x + (1.0 - w) * y}


@register_op("outer_prod")
def _outer_prod(ctx, ins, attrs):
    """OuterProdLayer.cpp: per-row outer product, flattened [B, M*N]."""
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": jnp.einsum("bm,bn->bmn", x, y).reshape(x.shape[0], -1)}


@register_op("factorization_machine")
def _factorization_machine(ctx, ins, attrs):
    """FactorizationMachineLayer.cpp second-order term:
    0.5 * sum_k((X V)_k^2 - (X^2 V^2)_k) -> [B, 1]."""
    x, v = ins["X"][0], ins["V"][0]
    xv = x @ v
    x2v2 = (x * x) @ (v * v)
    return {"Out": 0.5 * jnp.sum(xv * xv - x2v2, axis=1, keepdims=True)}


@register_op("scale_sub_region")
def _scale_sub_region(ctx, ins, attrs):
    """ScaleSubRegionLayer.cpp: scale value inside per-sample [C,H,W]
    index boxes (Indices [B,6] = c1,c2,h1,h2,w1,w2, 1-based inclusive)."""
    x, idx = ins["X"][0], ins["Indices"][0].astype(jnp.int32)
    value = attrs.get("value", 1.0)
    B, C, H, W = x.shape
    c = jnp.arange(C)[None, :, None, None]
    h = jnp.arange(H)[None, None, :, None]
    w = jnp.arange(W)[None, None, None, :]
    i = idx.reshape(B, 6, 1, 1, 1)
    mask = ((c >= i[:, 0] - 1) & (c <= i[:, 1] - 1) &
            (h >= i[:, 2] - 1) & (h <= i[:, 3] - 1) &
            (w >= i[:, 4] - 1) & (w <= i[:, 5] - 1))
    return {"Out": jnp.where(mask, x * value, x)}


# ---------------------------------------------------------------------------
# Static shape/dtype rules (analysis.shape_infer) — the InferShape analogs
# of elementwise_op.h / mul_op.cc / matmul_op.cc / reduce_op.cc.
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import (ShapeError, VarInfo, dim_ok,  # noqa: E402
                                    elementwise, first, prod_dims,
                                    reduce_rule, same_as,
                                    shapes_compatible, unify_dim)
from ..core.registry import register_shape_fn  # noqa: E402

register_shape_fn(
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_pow", "elementwise_max",
    "elementwise_min", "elementwise_mod",
)(elementwise())
register_shape_fn(
    "equal", "not_equal", "less_than", "less_equal", "greater_than",
    "greater_equal",
)(elementwise(dtype="bool"))
register_shape_fn("logical_and", "logical_or", "logical_xor")(
    elementwise(dtype="bool"))
register_shape_fn("logical_not")(same_as("X", dtype="bool"))
register_shape_fn(
    "scale", "minus", "clip", "clip_by_norm", "sign", "pow", "increment",
    "cumsum", "l2_normalize", "norm", "interpolation", "scale_sub_region",
)(same_as("X"))
register_shape_fn("abs_diff", "squared_difference")(elementwise())
register_shape_fn("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
                  "reduce_prod")(reduce_rule())


@register_shape_fn("mul")
def _mul_shape(op, ins, attrs):
    """mul_op.cc InferShape: flatten to 2-D at the num_col_dims splits and
    check the contraction."""
    x, y = first(ins, "X"), first(ins, "Y")
    if x.shape is None or y.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    if not 0 < xn < len(x.shape) + 1 or not 0 < yn < len(y.shape) + 1:
        raise ShapeError(
            f"mul: num_col_dims ({xn}, {yn}) out of range for ranks "
            f"{len(x.shape)}, {len(y.shape)}")
    k1, k2 = prod_dims(x.shape[xn:]), prod_dims(y.shape[:yn])
    if not dim_ok(k1, k2):
        raise ShapeError(
            f"mul: contraction mismatch {list(x.shape)}[{xn}:] ({k1}) vs "
            f"{list(y.shape)}[:{yn}] ({k2})")
    return {"Out": VarInfo(x.shape[:xn] + y.shape[yn:], x.dtype)}


@register_shape_fn("matmul")
def _matmul_shape(op, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    if x.shape is None or y.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    xs, ys = list(x.shape), list(y.shape)
    if len(xs) < 1 or len(ys) < 1:
        raise ShapeError("matmul: operands must have rank >= 1")
    if attrs.get("transpose_X", False) and len(xs) > 1:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if attrs.get("transpose_Y", False) and len(ys) > 1:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if len(xs) == 1:
        xs = [1] + xs
    if len(ys) == 1:
        ys = ys + [1]
    if not dim_ok(xs[-1], ys[-2]):
        raise ShapeError(
            f"matmul: contraction mismatch {list(x.shape)} @ "
            f"{list(y.shape)} ({xs[-1]} vs {ys[-2]})")
    batch = []
    for i in range(2, max(len(xs), len(ys)))[::-1]:
        bx = xs[-i - 1] if i < len(xs) else None
        by = ys[-i - 1] if i < len(ys) else None
        if bx is not None and by is not None:
            if not (dim_ok(bx, by) or bx == 1 or by == 1):
                raise ShapeError(
                    f"matmul: batch dims mismatch {list(x.shape)} vs "
                    f"{list(y.shape)}")
            # broadcast with -1-safe semantics: a 1 yields the other
            # side verbatim (even if unknown); -1 never collapses to 1
            if bx == 1:
                batch.append(by)
            elif by == 1:
                batch.append(bx)
            else:
                batch.append(unify_dim(bx, by))
        else:
            batch.append(bx if bx is not None else by)
    shape = tuple(batch) + (xs[-2], ys[-1])
    if x.ndim == 1:
        shape = shape[:-2] + (shape[-1],)
    elif y.ndim == 1:
        shape = shape[:-1]
    return {"Out": VarInfo(shape, x.dtype)}


@register_shape_fn("sum")
def _sum_shape(op, ins, attrs):
    """sum_op: every input must carry the same shape."""
    xs = ins.get("X", [])
    out = xs[0] if xs else None
    for x in xs[1:]:
        if not shapes_compatible(out.shape, x.shape):
            raise ShapeError(
                f"sum: operand shapes differ: {list(out.shape)} vs "
                f"{list(x.shape)}")
    return {"Out": out}


@register_shape_fn("mean")
def _mean_shape(op, ins, attrs):
    x = first(ins, "X")
    return {"Out": x.with_shape(())}


@register_shape_fn("cast")
def _cast_shape(op, ins, attrs):
    x = first(ins, "X")
    return {"Out": x.with_dtype(
        attrs.get("out_dtype", attrs.get("dtype", "float32")))}


@register_shape_fn("isfinite")
def _isfinite_shape(op, ins, attrs):
    return {"Out": VarInfo((), "bool")}


@register_shape_fn("conv_shift")
def _conv_shift_shape(op, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    if x.shape is not None and y.shape is not None and \
            len(y.shape) == 2 and y.shape[1] >= 0 and y.shape[1] % 2 == 0:
        raise ShapeError(f"conv_shift: Y width must be odd, got "
                         f"{y.shape[1]}")
    return {"Out": x}


@register_shape_fn("outer_prod")
def _outer_prod_shape(op, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    if x.shape is None or y.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    if len(x.shape) != 2 or len(y.shape) != 2:
        raise ShapeError("outer_prod: X and Y must be rank-2")
    m, n = x.shape[1], y.shape[1]
    return {"Out": VarInfo((x.shape[0], -1 if m < 0 or n < 0 else m * n),
                           x.dtype)}


@register_shape_fn("factorization_machine")
def _fm_shape(op, ins, attrs):
    x, v = first(ins, "X"), first(ins, "V")
    if x.shape is not None and v.shape is not None and \
            not dim_ok(x.shape[-1], v.shape[0]):
        raise ShapeError(
            f"factorization_machine: X feature dim {x.shape[-1]} vs V rows "
            f"{v.shape[0]}")
    b = x.shape[0] if x.shape is not None else -1
    return {"Out": VarInfo((b, 1), x.dtype)}


# ---------------------------------------------------------------------------
# Sharding-propagation rules (analysis.shard_prop).  mul carries the
# Megatron contract: row dims follow X, col dims follow Y, and a sharded
# contraction must match on both sides (the row-parallel all-reduce).
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import (merge_specs,  # noqa: E402
                                   shard_elementwise, shard_matmul,
                                   shard_mul, shard_reduce,
                                   shard_replicated, shard_same_as)
from ..core.registry import register_shard_fn  # noqa: E402

register_shard_fn(
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_pow", "elementwise_max",
    "elementwise_min", "elementwise_mod", "equal", "not_equal",
    "less_than", "less_equal", "greater_than", "greater_equal",
    "logical_and", "logical_or", "logical_xor", "abs_diff",
    "squared_difference",
)(shard_elementwise())
register_shard_fn(
    "logical_not", "scale", "minus", "clip", "clip_by_norm", "sign",
    "pow", "increment", "cumsum", "l2_normalize", "norm",
    "interpolation", "scale_sub_region", "cast",
)(shard_same_as("X"))
register_shard_fn("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
                  "reduce_prod")(shard_reduce())
register_shard_fn("mul")(shard_mul())
register_shard_fn("matmul")(shard_matmul())
register_shard_fn("mean", "isfinite")(shard_replicated("Out"))


@register_shard_fn("sum")
def _sum_shard(op, ins, attrs):
    spec = None
    for x in ins.get("X", []):
        spec = merge_specs(spec, x.spec, "sum operands")
    return {} if spec is None else {"Out": spec}


# ---------------------------------------------------------------------------
# Row-wise rules (core.registry.register_rowwise).  The reductions, matmul
# (its Y may be batched) and clip_by_norm (a norm over every row) have none.
# ---------------------------------------------------------------------------
from ..core.registry import register_rowwise, rows_of_one_rank  # noqa: E402

register_rowwise("scale", "cast", "clip", "sign", "sum")(rows_of_one_rank)


@register_rowwise(
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_pow", "elementwise_max",
    "elementwise_min", "elementwise_mod")
def _elementwise_rowwise(attrs, ins):
    """X's rows against a Y of the same rank that has rows too, or against
    a Y without rows that ``_bcast`` lines up with X's trailing axes (a
    bias): axis 0 would line it up with the rows."""
    x, y = ins["X"][0], ins["Y"][0]
    if not x.rows:
        return False
    if y.rows:
        return len(y.shape) == len(x.shape)
    return len(y.shape) < len(x.shape) and attrs.get("axis", -1) != 0


@register_rowwise("mul")
def _mul_rowwise(attrs, ins):
    """The FC product: X flattened to [rows, K] times a weight."""
    return ins["X"][0].rows and not ins["Y"][0].rows \
        and attrs.get("x_num_col_dims", 1) == 1
