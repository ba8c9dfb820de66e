"""Tensor creation / data-movement op lowerings.

Reference category (SURVEY §2.2 Data/layout + I/O): reshape, transpose,
concat, split, pad, crop, expand, gather/scatter, multiplex, top_k,
fill_constant(_batch_size_like), fill_zeros_like, gaussian_random,
uniform_random, assign, one_hot, shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op
from ..core.types import convert_dtype


@register_op("feed", "fetch")
def _feed_fetch(ctx, ins, attrs):
    """Kept for program parity (feed_op.cc/fetch_op.cc); the executor feeds
    and fetches by name directly, so these are identity/no-ops."""
    if "X" in ins and ins["X"]:
        return {"Out": ins["X"][0]}
    return {}


@register_op("assign")
def _assign(ctx, ins, attrs):
    return {"Out": ins["X"][0]}


@register_op("shape")
def _shape(ctx, ins, attrs):
    return {"Out": jnp.asarray(ins["X"][0].shape, dtype=jnp.int64)}


@register_op("fill_constant")
def _fill_constant(ctx, ins, attrs):
    dt = convert_dtype(attrs.get("dtype", "float32"))
    shape = tuple(attrs.get("shape", []))
    return {"Out": jnp.full(shape, attrs.get("value", 0.0), dtype=dt)}


@register_op("fill_constant_batch_size_like")
def _fill_cbsl(ctx, ins, attrs):
    """Shape copied from Input except the batch dim (fill_constant_batch_
    size_like_op.cc) — used to seed decoder states."""
    ref = ins["Input"][0]
    dt = convert_dtype(attrs.get("dtype", "float32"))
    shape = list(attrs.get("shape", []))
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    return {"Out": jnp.full(tuple(shape), attrs.get("value", 0.0), dtype=dt)}


@register_op("fill_zeros_like")
def _fill_zeros_like(ctx, ins, attrs):
    return {"Out": jnp.zeros_like(ins["X"][0])}


@register_op("fill_any_like")
def _fill_any_like(ctx, ins, attrs):
    return {"Out": jnp.full_like(ins["X"][0], attrs.get("value", 0.0))}


@register_op("gaussian_random")
def _gaussian_random(ctx, ins, attrs):
    dt = convert_dtype(attrs.get("dtype", "float32"))
    shape = tuple(attrs.get("shape", []))
    mean = attrs.get("mean", 0.0)
    std = attrs.get("std", 1.0)
    return {"Out": mean + std * jax.random.normal(ctx.rng(), shape, dtype=dt)}


@register_op("uniform_random")
def _uniform_random(ctx, ins, attrs):
    dt = convert_dtype(attrs.get("dtype", "float32"))
    shape = tuple(attrs.get("shape", []))
    return {"Out": jax.random.uniform(ctx.rng(), shape, dtype=dt,
                                      minval=attrs.get("min", -1.0),
                                      maxval=attrs.get("max", 1.0))}


@register_op("truncated_gaussian_random")
def _truncated_gaussian_random(ctx, ins, attrs):
    dt = convert_dtype(attrs.get("dtype", "float32"))
    shape = tuple(attrs.get("shape", []))
    mean = attrs.get("mean", 0.0)
    std = attrs.get("std", 1.0)
    return {"Out": mean + std * jax.random.truncated_normal(
        ctx.rng(), -2.0, 2.0, shape, dtype=dt)}


@register_op("assign_value")
def _assign_value(ctx, ins, attrs):
    vals = attrs["values"]
    dt = convert_dtype(attrs.get("dtype", "float32"))
    arr = jnp.asarray(vals, dtype=dt)
    if "shape" in attrs and attrs["shape"]:
        arr = arr.reshape(tuple(attrs["shape"]))
    return {"Out": arr}


@register_op("reshape")
def _reshape(ctx, ins, attrs):
    x = ins["X"][0]
    shape = list(attrs["shape"])
    # fluid: 0 means copy input dim, -1 infers
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    out = x.reshape(tuple(shape))
    note = ctx.env.softmax_note(ctx.op.inputs["X"][0], x)
    if note is not None and x.shape[-1:] == out.shape[-1:]:
        # rows regrouped, classes untouched: still that softmax (Env.softmax_of)
        logits, row_scale = note
        ctx.env.note_softmax(
            ctx.op.outputs["Out"][0], out, logits.reshape(out.shape),
            None if row_scale is None
            else row_scale.reshape(out.shape[:-1] + (1,)))
    return {"Out": out}


@register_op("squeeze")
def _squeeze(ctx, ins, attrs):
    axes = attrs.get("axes", None)
    return {"Out": jnp.squeeze(ins["X"][0],
                               axis=tuple(axes) if axes else None)}


@register_op("unsqueeze")
def _unsqueeze(ctx, ins, attrs):
    return {"Out": jnp.expand_dims(ins["X"][0], tuple(attrs["axes"]))}


@register_op("transpose")
def _transpose(ctx, ins, attrs):
    return {"Out": jnp.transpose(ins["X"][0], tuple(attrs["axis"]))}


@register_op("concat")
def _concat(ctx, ins, attrs):
    return {"Out": jnp.concatenate(ins["X"], axis=attrs.get("axis", 0))}


@register_op("split")
def _split(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    sections = attrs.get("sections")
    if sections:
        idx = []
        acc = 0
        for s in sections[:-1]:
            acc += s
            idx.append(acc)
        parts = jnp.split(x, idx, axis=axis)
    else:
        parts = jnp.split(x, attrs["num"], axis=axis)
    return {"Out": list(parts)}


@register_op("pad")
def _pad(ctx, ins, attrs):
    """pad_op: paddings = [before0, after0, before1, after1, ...]"""
    x = ins["X"][0]
    p = attrs["paddings"]
    pads = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))}


@register_op("crop")
def _crop(ctx, ins, attrs):
    x = ins["X"][0]
    offsets = attrs["offsets"]
    shape = attrs["shape"]
    # a negative size is a symbolic dim (e.g. batch -1): keep to the end
    slices = tuple(slice(o, o + s if s >= 0 else None)
                   for o, s in zip(offsets, shape))
    return {"Out": x[slices]}


@register_op("expand")
def _expand(ctx, ins, attrs):
    """expand_op: tile each dim by expand_times."""
    return {"Out": jnp.tile(ins["X"][0], tuple(attrs["expand_times"]))}


@register_op("tile")
def _tile(ctx, ins, attrs):
    return {"Out": jnp.tile(ins["X"][0], tuple(attrs["repeat_times"]))}


@register_op("slice")
def _slice(ctx, ins, attrs):
    # fluid's slice_op names its input slot "Input"; accept both spellings
    x = ins.get("Input", ins.get("X"))[0]
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    sl = [slice(None)] * x.ndim
    for ax, st, en in zip(axes, starts, ends):
        sl[ax] = slice(st, en)
    return {"Out": x[tuple(sl)]}


@register_op("gather")
def _gather(ctx, ins, attrs):
    """gather_op: rows of X by Index (gather.h)."""
    x, idx = ins["X"][0], ins["Index"][0]
    return {"Out": jnp.take(x, idx.astype(jnp.int32),
                            axis=attrs.get("axis", 0))}


@register_op("scatter")
def _scatter(ctx, ins, attrs):
    """scatter_op: write Updates rows into X at Ids (scatter.h).
    overwrite=False accumulates (the SelectedRows-merge behavior)."""
    x, ids, upd = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    ids = ids.astype(jnp.int32).reshape(-1)
    if attrs.get("overwrite", True):
        return {"Out": x.at[ids].set(upd)}
    return {"Out": x.at[ids].add(upd)}


@register_op("multiplex")
def _multiplex(ctx, ins, attrs):
    """multiplex_op: per-row select among candidate tensors by Ids."""
    ids = ins["Ids"][0].astype(jnp.int32).reshape(-1)
    stack = jnp.stack(ins["X"], axis=0)  # [K, N, ...]
    return {"Out": stack[ids, jnp.arange(stack.shape[1])]}


@register_op("top_k")
def _top_k(ctx, ins, attrs):
    vals, idx = jax.lax.top_k(ins["X"][0], attrs["k"])
    return {"Out": vals, "Indices": idx.astype(jnp.int64)}


@register_op("sampling_id")
def _sampling_id(ctx, ins, attrs):
    """sampling_id_op (SamplingIdLayer.cpp): sample one id per row from
    the row's probability distribution; per-step PRNG key from ctx."""
    x = ins["X"][0]                  # [B, V] probabilities
    logp = jnp.log(jnp.clip(x.astype(jnp.float32), 1e-20, None))
    ids = jax.random.categorical(ctx.rng(), logp, axis=-1)
    return {"Out": ids.astype(jnp.int64)}


@register_op("argmax", "arg_max", "max_ids")
def _argmax(ctx, ins, attrs):
    return {"Out": jnp.argmax(ins["X"][0], axis=attrs.get("axis", -1))
            .astype(jnp.int64)}


@register_op("argsort")
def _argsort(ctx, ins, attrs):
    axis = attrs.get("axis", -1)
    x = ins["X"][0]
    idx = jnp.argsort(x, axis=axis, descending=attrs.get("descending", False))
    out = jnp.take_along_axis(x, idx, axis=axis)
    return {"Out": out, "Indices": idx.astype(jnp.int64)}


@register_op("one_hot")
def _one_hot(ctx, ins, attrs):
    x = ins["X"][0].astype(jnp.int32)
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = x.squeeze(-1)
    return {"Out": jax.nn.one_hot(x, attrs["depth"], dtype=jnp.float32)}


@register_op("range")
def _range(ctx, ins, attrs):
    return {"Out": jnp.arange(attrs["start"], attrs["end"],
                              attrs.get("step", 1),
                              dtype=convert_dtype(attrs.get("dtype", "int64")))}


@register_op("flatten")
def _flatten(ctx, ins, attrs):
    x = ins["X"][0]
    ax = attrs.get("axis", 1)
    lead = 1
    for s in x.shape[:ax]:
        lead *= s
    return {"Out": x.reshape((lead, -1))}


@register_op("stack")
def _stack(ctx, ins, attrs):
    return {"Out": jnp.stack(ins["X"], axis=attrs.get("axis", 0))}


@register_op("unstack")
def _unstack(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    return {"Y": [jnp.squeeze(p, axis)
                  for p in jnp.split(x, x.shape[axis], axis=axis)]}


@register_op("where", "select")
def _where(ctx, ins, attrs):
    return {"Out": jnp.where(ins["Condition"][0], ins["X"][0], ins["Y"][0])}


@register_op("is_empty")
def _is_empty(ctx, ins, attrs):
    """is_empty_op.cc — static under XLA (shapes are compile-time)."""
    return {"Out": jnp.asarray(ins["X"][0].size == 0)}


@register_op("shuffle")
def _shuffle(ctx, ins, attrs):
    x = ins["X"][0]
    perm = jax.random.permutation(ctx.rng(), x.shape[0])
    return {"Out": x[perm]}


@register_op("reverse")
def _reverse(ctx, ins, attrs):
    axes = attrs.get("axis", [0])
    if not isinstance(axes, (list, tuple)):
        axes = [axes]
    return {"Out": jnp.flip(ins["X"][0], axis=tuple(axes))}


# ---------------------------------------------------------------------------
# Static shape/dtype rules (analysis.shape_infer) — reshape/concat/split etc.
# InferShape analogs of the reference's data-movement ops.
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import (ShapeError, VarInfo,  # noqa: E402
                                    dim_ok, filled_from_attrs, first,
                                    numpy_broadcast, passthrough,
                                    prod_dims, same_as, shapes_compatible,
                                    squeeze_ids, unify_dim)
from ..core.registry import register_shape_fn  # noqa: E402

register_shape_fn("feed", "fetch")(passthrough("X"))
register_shape_fn("assign", "fill_zeros_like", "fill_any_like", "shuffle",
                  "reverse")(same_as("X"))
register_shape_fn("scatter")(same_as("X"))
register_shape_fn("fill_constant", "gaussian_random", "uniform_random",
                  "truncated_gaussian_random")(filled_from_attrs())
@register_shape_fn("where", "select")
def _where_shape(op, ins, attrs):
    # jnp.where broadcasts all three operands
    cond, x, y = first(ins, "Condition"), first(ins, "X"), first(ins, "Y")
    if x.shape is None or y.shape is None or cond.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    shape = numpy_broadcast(numpy_broadcast(cond.shape, x.shape,
                                            "where Condition/X"),
                            y.shape, "where X/Y")
    return {"Out": VarInfo(shape, x.dtype)}


@register_shape_fn("shape")
def _shape_shape(op, ins, attrs):
    x = first(ins, "X")
    nd = -1 if x.shape is None else len(x.shape)
    return {"Out": VarInfo((nd,), "int64")}


@register_shape_fn("fill_constant_batch_size_like")
def _fill_cbsl_shape(op, ins, attrs):
    ref = first(ins, "Input")
    shape = list(attrs.get("shape", []))
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    if not shape:
        return {"Out": VarInfo(None, attrs.get("dtype", "float32"))}
    if ref.shape is not None:
        if in_idx >= len(ref.shape) or out_idx >= len(shape):
            raise ShapeError(
                f"fill_constant_batch_size_like: dim idx ({in_idx}, "
                f"{out_idx}) out of range for {list(ref.shape)} -> {shape}")
        shape[out_idx] = ref.shape[in_idx]
    else:
        shape[out_idx] = -1
    return {"Out": VarInfo(shape, attrs.get("dtype", "float32"))}


@register_shape_fn("assign_value")
def _assign_value_shape(op, ins, attrs):
    import numpy as _np
    dt = attrs.get("dtype", "float32")
    if attrs.get("shape"):
        return {"Out": VarInfo(tuple(attrs["shape"]), dt)}
    vals = attrs.get("values")
    if vals is None:
        return {"Out": VarInfo(None, dt)}
    return {"Out": VarInfo(_np.shape(vals), dt)}


@register_shape_fn("reshape")
def _reshape_shape(op, ins, attrs):
    x = first(ins, "X")
    shape = list(attrs.get("shape", []))
    if x.shape is None or not shape:
        return {"Out": VarInfo(None, x.dtype)}
    for i, s in enumerate(shape):
        if s == 0:
            if i >= len(x.shape):
                raise ShapeError(
                    f"reshape: dim {i} copies input dim but input rank is "
                    f"{len(x.shape)}")
            shape[i] = x.shape[i]
    neg = [i for i, s in enumerate(shape) if s == -1]
    if len(neg) > 1:
        raise ShapeError(f"reshape: more than one -1 in {shape}")
    total = prod_dims(x.shape)
    known = prod_dims([s for i, s in enumerate(shape) if i not in neg])
    if total >= 0 and known >= 0:
        if neg:
            if known == 0 or total % known:
                raise ShapeError(
                    f"reshape: cannot infer -1: {list(x.shape)} "
                    f"({total} elems) -> {shape}")
            shape[neg[0]] = total // known
        elif known != total:
            raise ShapeError(
                f"reshape: element count mismatch: {list(x.shape)} "
                f"({total}) -> {shape} ({known})")
    elif neg:
        shape[neg[0]] = -1
    return {"Out": VarInfo(shape, x.dtype)}


@register_shape_fn("squeeze")
def _squeeze_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    axes = attrs.get("axes", None)
    nd = len(x.shape)
    if axes:
        axes = {a % nd for a in axes}
        for a in axes:
            if x.shape[a] not in (-1, 1):
                raise ShapeError(
                    f"squeeze: axis {a} has size {x.shape[a]} != 1 in "
                    f"{list(x.shape)}")
        shape = tuple(d for i, d in enumerate(x.shape) if i not in axes)
    else:
        shape = tuple(d for d in x.shape if d != 1)
    return {"Out": x.with_shape(shape)}


@register_shape_fn("unsqueeze")
def _unsqueeze_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    shape = list(x.shape)
    for a in sorted(attrs.get("axes", [])):
        shape.insert(a if a >= 0 else a + len(shape) + 1, 1)
    return {"Out": x.with_shape(shape)}


@register_shape_fn("transpose")
def _transpose_shape(op, ins, attrs):
    x = first(ins, "X")
    perm = attrs.get("axis")
    if x.shape is None or perm is None:
        return {"Out": x}
    if sorted(a % len(x.shape) for a in perm) != list(range(len(x.shape))):
        raise ShapeError(
            f"transpose: axis {list(perm)} is not a permutation of rank "
            f"{len(x.shape)}")
    return {"Out": x.with_shape(tuple(x.shape[a] for a in perm))}


@register_shape_fn("concat")
def _concat_shape(op, ins, attrs):
    xs = [v for v in ins.get("X", []) if v is not None]
    known = [v for v in xs if v.shape is not None]
    if not known:
        return {"Out": VarInfo(None, xs[0].dtype if xs else None)}
    nd = len(known[0].shape)
    axis = attrs.get("axis", 0) % nd
    shape = list(known[0].shape)
    for v in known[1:]:
        if len(v.shape) != nd:
            raise ShapeError(
                f"concat: rank mismatch {list(known[0].shape)} vs "
                f"{list(v.shape)}")
        for i in range(nd):
            if i != axis and not dim_ok(shape[i], v.shape[i]):
                raise ShapeError(
                    f"concat: non-axis dim {i} differs: "
                    f"{list(known[0].shape)} vs {list(v.shape)}")
            shape[i] = unify_dim(shape[i], v.shape[i]) if i != axis \
                else shape[i]
    if len(known) == len(xs):
        cat = 0
        for v in known:
            if v.shape[axis] < 0:
                cat = -1
                break
            cat += v.shape[axis]
    else:
        cat = -1
    shape[axis] = cat
    return {"Out": VarInfo(shape, known[0].dtype)}


@register_shape_fn("split")
def _split_shape(op, ins, attrs):
    x = first(ins, "X")
    names = op.outputs.get("Out", [])
    if x.shape is None:
        return {"Out": [VarInfo(None, x.dtype)] * len(names)}
    axis = attrs.get("axis", 0) % len(x.shape)
    sections = attrs.get("sections")
    if sections:
        if len(sections) != len(names):
            raise ShapeError(
                f"split: {len(sections)} sections for {len(names)} outputs")
        if x.shape[axis] >= 0 and sum(sections) != x.shape[axis]:
            raise ShapeError(
                f"split: sections {list(sections)} do not sum to dim "
                f"{x.shape[axis]}")
        return {"Out": [x.with_shape(x.shape[:axis] + (s,)
                                     + x.shape[axis + 1:])
                        for s in sections]}
    num = attrs.get("num", len(names))
    if x.shape[axis] >= 0 and num and x.shape[axis] % num:
        raise ShapeError(
            f"split: dim {x.shape[axis]} not divisible into {num} parts")
    part = -1 if x.shape[axis] < 0 else x.shape[axis] // num
    return {"Out": [x.with_shape(x.shape[:axis] + (part,)
                                 + x.shape[axis + 1:])] * len(names)}


@register_shape_fn("pad")
def _pad_shape(op, ins, attrs):
    x = first(ins, "X")
    p = attrs.get("paddings")
    if x.shape is None or p is None:
        return {"Out": x}
    if len(p) != 2 * len(x.shape):
        raise ShapeError(
            f"pad: {len(p)} padding entries for rank {len(x.shape)}")
    shape = tuple(d if d < 0 else d + p[2 * i] + p[2 * i + 1]
                  for i, d in enumerate(x.shape))
    return {"Out": x.with_shape(shape)}


@register_shape_fn("crop")
def _crop_shape(op, ins, attrs):
    x = first(ins, "X")
    shape = attrs.get("shape")
    if shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    offsets = attrs.get("offsets") or (0,) * len(shape)
    if x.shape is not None:
        # a negative size keeps to the end — the lowering slices x[o:],
        # so the dim is input minus offset (symbolic when input is)
        out = tuple(
            (x.shape[i] - offsets[i] if x.shape[i] >= 0 else -1)
            if s < 0 else s
            for i, s in enumerate(shape))
    else:
        out = tuple(s if s >= 0 else -1 for s in shape)
    return {"Out": VarInfo(out, x.dtype)}


@register_shape_fn("expand")
def _expand_shape(op, ins, attrs):
    return _tile_like(first(ins, "X"), attrs.get("expand_times"))


@register_shape_fn("tile")
def _tile_shape(op, ins, attrs):
    return _tile_like(first(ins, "X"), attrs.get("repeat_times"))


def _tile_like(x, times):
    if x.shape is None or times is None:
        return {"Out": VarInfo(None, x.dtype)}
    times = list(times)
    if len(times) < len(x.shape):
        times = [1] * (len(x.shape) - len(times)) + times
    shape = [1] * (len(times) - len(x.shape)) + list(x.shape)
    out = tuple(d if d < 0 else d * t for d, t in zip(shape, times))
    return {"Out": VarInfo(out, x.dtype)}


@register_shape_fn("slice")
def _slice_shape(op, ins, attrs):
    x = first(ins, "Input") if ins.get("Input") else first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    shape = list(x.shape)
    for ax, st, en in zip(attrs.get("axes", []), attrs.get("starts", []),
                          attrs.get("ends", [])):
        ax = ax % len(shape)
        d = shape[ax]
        if d < 0:
            continue
        lo = max(st + d, 0) if st < 0 else min(st, d)
        hi = max(en + d, 0) if en < 0 else min(en, d)
        shape[ax] = max(hi - lo, 0)
    return {"Out": x.with_shape(shape)}


@register_shape_fn("gather")
def _gather_shape(op, ins, attrs):
    x, idx = first(ins, "X"), first(ins, "Index")
    if x.shape is None or idx.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    axis = attrs.get("axis", 0) % len(x.shape)
    # NO [N,1]->[N] squeeze: the lowering is a plain jnp.take, so a 2-D
    # index really does produce (..., N, 1, ...) — the rule must describe
    # the runtime, not the reference's squeezing variant
    return {"Out": VarInfo(x.shape[:axis] + idx.shape
                           + x.shape[axis + 1:], x.dtype)}


@register_shape_fn("multiplex")
def _multiplex_shape(op, ins, attrs):
    x = first(ins, "X")
    return {"Out": x}


@register_shape_fn("top_k")
def _top_k_shape(op, ins, attrs):
    x = first(ins, "X")
    k = attrs.get("k", 1)
    if x.shape is None:
        return {"Out": x, "Indices": VarInfo(None, "int64")}
    if x.shape[-1] >= 0 and k > x.shape[-1]:
        raise ShapeError(f"top_k: k={k} > last dim {x.shape[-1]}")
    shape = x.shape[:-1] + (k,)
    return {"Out": x.with_shape(shape),
            "Indices": VarInfo(shape, "int64")}


@register_shape_fn("sampling_id")
def _sampling_id_shape(op, ins, attrs):
    x = first(ins, "X")
    b = x.shape[0] if x.shape is not None else -1
    return {"Out": VarInfo((b,), "int64")}


@register_shape_fn("argmax", "arg_max", "max_ids")
def _argmax_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": VarInfo(None, "int64")}
    axis = attrs.get("axis", -1) % len(x.shape)
    return {"Out": VarInfo(x.shape[:axis] + x.shape[axis + 1:], "int64")}


@register_shape_fn("argsort")
def _argsort_shape(op, ins, attrs):
    x = first(ins, "X")
    return {"Out": x, "Indices": VarInfo(x.shape, "int64")}


@register_shape_fn("one_hot")
def _one_hot_shape(op, ins, attrs):
    ids = first(ins, "X")
    s = squeeze_ids(ids)
    if s is None:
        return {"Out": VarInfo(None, "float32")}
    return {"Out": VarInfo(s + (attrs["depth"],), "float32")}


@register_shape_fn("range")
def _range_shape(op, ins, attrs):
    start, end = attrs.get("start"), attrs.get("end")
    step = attrs.get("step", 1)
    dt = attrs.get("dtype", "int64")
    try:
        n = max(0, int(-(-(end - start) // step)))
    except (TypeError, ZeroDivisionError):
        n = -1
    return {"Out": VarInfo((n,), dt)}


@register_shape_fn("flatten")
def _flatten_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    ax = attrs.get("axis", 1)
    return {"Out": x.with_shape((prod_dims(x.shape[:ax]),
                                 prod_dims(x.shape[ax:])))}


@register_shape_fn("stack")
def _stack_shape(op, ins, attrs):
    xs = [v for v in ins.get("X", []) if v is not None]
    base = next((v for v in xs if v.shape is not None), None)
    if base is None:
        return {"Out": VarInfo(None, xs[0].dtype if xs else None)}
    for v in xs:
        if not shapes_compatible(v.shape, base.shape):
            raise ShapeError(
                f"stack: operand shapes differ: {list(base.shape)} vs "
                f"{list(v.shape)}")
    axis = attrs.get("axis", 0)
    nd = len(base.shape) + 1
    axis = axis % nd
    shape = base.shape[:axis] + (len(xs),) + base.shape[axis:]
    return {"Out": VarInfo(shape, base.dtype)}


@register_shape_fn("unstack")
def _unstack_shape(op, ins, attrs):
    x = first(ins, "X")
    names = op.outputs.get("Y", [])
    if x.shape is None:
        return {"Y": [VarInfo(None, x.dtype)] * len(names)}
    axis = attrs.get("axis", 0) % len(x.shape)
    if x.shape[axis] >= 0 and len(names) not in (0, x.shape[axis]):
        raise ShapeError(
            f"unstack: {len(names)} outputs for dim {x.shape[axis]}")
    part = x.shape[:axis] + x.shape[axis + 1:]
    return {"Y": [x.with_shape(part)] * len(names)}


@register_shape_fn("is_empty")
def _is_empty_shape(op, ins, attrs):
    return {"Out": VarInfo((), "bool")}


# ---------------------------------------------------------------------------
# Sharding-propagation rules (analysis.shard_prop).  reshape keeps the
# batch sharding only when the batch dim survives the reshape; transpose
# permutes entries; concat/split replicate their concat axis (a sharded
# concat dim would interleave shards).
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import (first_in, merge_entry,  # noqa: E402
                                   shard_batch_only, shard_noop,
                                   shard_replicated, shard_same_as)
from ..core.registry import register_shard_fn  # noqa: E402

register_shard_fn("feed", "fetch", "assign", "fill_zeros_like",
                  "fill_any_like", "shuffle", "scatter", "reverse",
                  "lod_reset")(shard_same_as("X"))
register_shard_fn("fill_constant", "gaussian_random", "uniform_random",
                  "truncated_gaussian_random", "range", "assign_value",
                  "shape")(shard_replicated("Out"))
register_shard_fn("is_empty")(shard_noop())


@register_shard_fn("reshape")
def _reshape_shard(op, ins, attrs):
    x = first_in(ins, "X")
    if x.spec is None:
        return {}
    new_shape = list(attrs.get("shape", []))
    if not new_shape:
        return {}
    keep_batch = new_shape[0] in (-1, 0) or \
        (x.shape is not None and new_shape[0] == x.shape[0])
    return {"Out": ((x.entry(0),) if keep_batch else (None,))
            + (None,) * (len(new_shape) - 1)}


@register_shard_fn("transpose")
def _transpose_shard(op, ins, attrs):
    x = first_in(ins, "X")
    perm = attrs.get("axis")
    if x.spec is None or perm is None:
        return {}
    n = len(x.spec)
    return {"Out": tuple(x.entry(a % n) for a in perm)}


@register_shard_fn("concat")
def _concat_shard(op, ins, attrs):
    xs = ins.get("X", [])
    if not any(x.spec is not None for x in xs):
        return {}
    nd = next((x.ndim for x in xs if x.ndim is not None), None)
    if nd is None:
        return {}
    axis = attrs.get("axis", 0) % nd
    entries = []
    for i in range(nd):
        if i == axis:
            entries.append(None)
            continue
        e = None
        for x in xs:
            e = merge_entry(e, x.entry(i), f"concat operands dim {i}")
        entries.append(e)
    return {"Out": tuple(entries)}


@register_shard_fn("squeeze", "unsqueeze", "flatten")
def _rank_change_shard(op, ins, attrs):
    # conservatively keep only the batch-dim sharding (dim 0 survives all
    # three ops' lowerings for the axes>=1 cases the layers emit)
    x = first_in(ins, "X")
    if x.spec is None:
        return {}
    return {"Out": (x.entry(0),)}


# index/selection family: batch dim follows X/Ids, everything else
# replicates (indices are tiny; gather output layout is data-driven)
register_shard_fn("gather", "one_hot", "top_k", "argmax", "arg_max",
                  "argsort", "sampling_id", "max_ids")(
    shard_batch_only("X", fallbacks=("Ids",), also=("Indices",)))
