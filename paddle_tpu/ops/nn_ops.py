"""Neural-network op lowerings: conv, pool, normalization, softmax, dropout.

Reference category (SURVEY §2.2 NN): conv_op/conv_cudnn_op, conv_transpose,
pool_op/pool_cudnn, pool_with_index, batch_norm_op, softmax,
softmax_with_cross_entropy, cross_entropy, dropout, lrn, maxout, prelu (in
activation_ops).  cuDNN paths collapse into XLA convolutions, which tile onto
the MXU; data layout is NCHW for API parity.  Measured (ResNet-50 train step,
bs128 bf16, v5e): logical NCHW vs NHWC is within ~1% — XLA's layout
assignment re-tiles internally, so no NHWC rewrite is forced on users.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core import compile_cache
from ..core.registry import register_op
from . import pallas_kernels


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


def _conv2d_space_to_depth(x, w, pads):
    """Stride-2 small-channel conv (a ResNet/VGG-style stem) folded into a
    stride-1 conv over 2x2-space-to-depth input: 4x the MXU lane utilization
    when C_in is tiny (3 channels fill 3/128 lanes).  Exact — padded filter
    taps are zero.  Public MLPerf-era technique, not in the reference."""
    n, c, h, wd = x.shape
    o, _, kh, kw = w.shape
    kh2, kw2 = ((kh + 1) // 2) * 2, ((kw + 1) // 2) * 2
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, kh2 - kh), (0, kw2 - kw)))
    w2 = wp.reshape(o, c, kh2 // 2, 2, kw2 // 2, 2) \
           .transpose(0, 1, 3, 5, 2, 4).reshape(o, c * 4, kh2 // 2, kw2 // 2)
    xp = jnp.pad(x, ((0, 0), (0, 0), (pads[0], pads[0]),
                     (pads[1], pads[1])))
    hp, wp_ = h + 2 * pads[0], wd + 2 * pads[1]
    xs = xp.reshape(n, c, hp // 2, 2, wp_ // 2, 2) \
           .transpose(0, 1, 3, 5, 2, 4).reshape(n, c * 4, hp // 2, wp_ // 2)
    return lax.conv_general_dilated(
        xs, w2, (1, 1), [(0, 0), (0, 0)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


@register_op("conv2d", "depthwise_conv2d")
def _conv2d(ctx, ins, attrs):
    """conv_op.cc / conv_cudnn_op: Input [N,C,H,W], Filter [M,C/g,kh,kw]."""
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1) or 1)
    # the hand-written 1x1 Pallas path is the OP's choice (the use_pallas
    # attribute layers.conv2d writes): it lives in the Program, hence in
    # the content digest, and no executor or process setting selects it
    if attrs.get("use_pallas"):
        from . import pallas_conv
        interpret = bool(attrs.get("pallas_interpret", False))
        # single-device only: GSPMD treats pallas_call as opaque, so under
        # a >1-device mesh the routing would silently replicate the conv
        single = ctx.mesh is None or getattr(ctx.mesh, "size", 1) == 1
        if (single and (interpret or jax.default_backend() == "tpu")
                and pallas_conv.conv1x1_eligible(
                    x.shape, w.shape, strides, pads, dil, groups)):
            compile_cache.stats().bump(
                "route/conv2d_1x1:" + ("interpret" if interpret
                                       else "pallas"))
            return {"Output": pallas_conv.conv2d_1x1(
                x, w, strides, interpret=interpret)}
        # asked for, not taken (ineligible shape, mesh, or backend)
        compile_cache.stats().bump("route/conv2d_1x1:xla")
    if (strides == (2, 2) and dil == (1, 1) and groups == 1
            and x.shape[1] <= 4 and x.ndim == 4
            and (x.shape[2] + 2 * pads[0]) % 2 == 0
            and (x.shape[3] + 2 * pads[1]) % 2 == 0):
        return {"Output": _conv2d_space_to_depth(x, w, pads)}
    out = lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
    )
    return {"Output": out}


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    """conv_transpose_op: Filter layout [C_in, C_out/g, kh, kw] ('IOHW')."""
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    kh, kw = w.shape[2], w.shape[3]
    # transposed conv == lhs-dilated conv with flipped, transposed kernel
    out = lax.conv_general_dilated(
        x, jnp.flip(w, (2, 3)).swapaxes(0, 1),
        window_strides=(1, 1),
        padding=[(dil[0] * (kh - 1) - pads[0], dil[0] * (kh - 1) - pads[0]),
                 (dil[1] * (kw - 1) - pads[1], dil[1] * (kw - 1) - pads[1])],
        lhs_dilation=strides,
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return {"Output": out}


@register_op("conv3d")
def _conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1, 1]))
    pads = tuple(attrs.get("paddings", [0, 0, 0]))
    dil = tuple(attrs.get("dilations", [1, 1, 1]))
    out = lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(p, p) for p in pads], rhs_dilation=dil,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=int(attrs.get("groups", 1) or 1),
    )
    return {"Output": out}


def _pool2d_core(x, ptype, ksize, strides, pads, global_pooling, exclusive,
                 adaptive=False, ceil_mode=False):
    if global_pooling or adaptive and tuple(ksize) == (1, 1):
        axis = (2, 3)
        if ptype == "max":
            return jnp.max(x, axis=axis, keepdims=True)
        return jnp.mean(x, axis=axis, keepdims=True)
    ksize = _pair(ksize)
    strides = _pair(strides)
    pads = _pair(pads)
    window = (1, 1) + ksize
    ws = (1, 1) + strides
    extra = (0, 0)
    if ceil_mode:
        # v1 default (PoolLayer ceil): pad right/bottom so partial windows
        # produce an output element
        def _extra(size, k, p, s):
            rem = (size + 2 * p - k) % s
            return (s - rem) % s if rem else 0
        extra = (_extra(x.shape[2], ksize[0], pads[0], strides[0]),
                 _extra(x.shape[3], ksize[1], pads[1], strides[1]))
    padding = ((0, 0), (0, 0), (pads[0], pads[0] + extra[0]),
               (pads[1], pads[1] + extra[1]))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max,
                                 window, ws, padding)
    s = lax.reduce_window(x, 0.0, lax.add,
                          window, ws, padding)
    if exclusive and (pads[0] or pads[1] or extra[0] or extra[1]):
        ones = jnp.ones_like(x)
        cnt = lax.reduce_window(ones, 0.0, lax.add,
                                window, ws, padding)
        return s / cnt
    return s / (ksize[0] * ksize[1])


@register_op("pool2d")
def _pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    out = _pool2d_core(
        x, attrs.get("pooling_type", "max"), attrs.get("ksize", [2, 2]),
        attrs.get("strides", [1, 1]), attrs.get("paddings", [0, 0]),
        attrs.get("global_pooling", False), attrs.get("exclusive", True),
        ceil_mode=attrs.get("ceil_mode", False))
    return {"Out": out}


@register_op("pool3d")
def _pool3d(ctx, ins, attrs):
    """pool3d_op (pool_op.cc 3-D branch): NCDHW max/avg pooling."""
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False):
        ks = list(x.shape[2:])
        strides, pads = ks, [0, 0, 0]
    else:
        ks = list(attrs.get("ksize", [2, 2, 2]))
        strides = list(attrs.get("strides", ks))
        pads = list(attrs.get("paddings", [0, 0, 0]))
    window = (1, 1) + tuple(ks)
    stride = (1, 1) + tuple(strides)
    pad = ((0, 0), (0, 0)) + tuple((p, p) for p in pads)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, stride, pad)
    else:
        s = lax.reduce_window(x, 0.0, lax.add, window, stride, pad)
        ones = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, window,
                                 stride, pad)
        out = s / ones
    return {"Out": out}


@register_op("max_pool2d_with_index", "pool2d_with_index")
def _max_pool2d_with_index(ctx, ins, attrs):
    """pool_with_index_op: returns flat H*W indices of maxima (for unpool).
    Patch extraction keeps this one fused XLA computation."""
    x = ins["X"][0]
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", ksize))
    pads = _pair(attrs.get("paddings", [0, 0]))
    n, c, h, w = x.shape
    patches = lax.conv_general_dilated_patches(
        x, ksize, strides,
        [(pads[0], pads[0]), (pads[1], pads[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    oh, ow = patches.shape[2], patches.shape[3]
    patches = patches.reshape(n, c, ksize[0] * ksize[1], oh, ow)
    arg = jnp.argmax(patches, axis=2)
    out = jnp.max(patches, axis=2)
    # convert patch-local index to flat input H*W index
    ph, pw = arg // ksize[1], arg % ksize[1]
    base_h = (jnp.arange(oh) * strides[0] - pads[0])[None, None, :, None]
    base_w = (jnp.arange(ow) * strides[1] - pads[1])[None, None, None, :]
    idx = (base_h + ph) * w + (base_w + pw)
    return {"Out": out, "Mask": idx.astype(jnp.int64)}


@register_op("unpool")
def _unpool(ctx, ins, attrs):
    """unpool_op: scatter values back to positions given by Indices."""
    x, idx = ins["X"][0], ins["Indices"][0]
    n, c, oh, ow = x.shape
    uh, uw = attrs["unpool_size"] if "unpool_size" in attrs else (
        attrs["ksize"][0] * oh, attrs["ksize"][1] * ow)
    flat = jnp.zeros((n, c, uh * uw), x.dtype)
    out = flat.at[
        jnp.arange(n)[:, None, None],
        jnp.arange(c)[None, :, None],
        idx.reshape(n, c, -1).astype(jnp.int32),
    ].set(x.reshape(n, c, -1))
    return {"Out": out.reshape(n, c, uh, uw)}


@register_op("batch_norm")
def _batch_norm(ctx, ins, attrs):
    """batch_norm_op.cc: NCHW (or NC) input; train updates running stats.

    Outputs mirror the reference (Y, MeanOut, VarianceOut, SavedMean,
    SavedVariance) so optimizer/IO code can treat stats as persistables.
    """
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    # v1 use_global_stats tri-state (BatchNormBaseLayer.cpp): True forces
    # running stats even in training, False forces batch stats even at
    # PASS_TEST (the GAN configs rely on this); None keeps is_test routing.
    # Running stats still update only on training passes.
    use_global = attrs.get("use_global_stats")
    if use_global is None:
        use_global = is_test
    axes = tuple(i for i in range(x.ndim) if i != 1)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    if use_global:
        use_mean = mean.astype(jnp.float32)
        use_var = var.astype(jnp.float32)
        mean_out, var_out = mean, var
    else:
        # single-pass stats: mean and mean-of-squares with fp32 accumulation
        # (one read of x for both reductions; under AMP x is bf16 and the
        # fp32 accumulate keeps the stats honest).  Caveat: E[x^2]-E[x]^2
        # cancels catastrophically when |mean| >> std; the fp32 accumulate
        # and the clamp below bound the damage, and post-BN activations in
        # practice are near zero-mean, but a pathological input distribution
        # can lose stat precision vs the two-pass form.
        use_mean = jnp.mean(x, axis=axes, dtype=jnp.float32)
        m2 = jnp.mean(lax.square(x.astype(jnp.float32)), axis=axes)
        use_var = jnp.maximum(m2 - lax.square(use_mean), 0.0)
        use_mean_sg = lax.stop_gradient(use_mean)
        use_var_sg = lax.stop_gradient(use_var)
        if is_test:
            # batch stats forced by use_global_stats=False, but a test pass
            # never advances the moving averages
            mean_out, var_out = mean, var
        else:
            mean_out = (momentum * mean
                        + (1.0 - momentum) * use_mean_sg.astype(mean.dtype))
            var_out = (momentum * var
                       + (1.0 - momentum) * use_var_sg.astype(var.dtype))
    inv = lax.rsqrt(use_var + eps)
    # fold into a per-channel scale/shift so the big tensor gets ONE fused
    # multiply-add in its own dtype (no fp32 round trip through HBM)
    eff_scale = scale.astype(jnp.float32) * inv
    eff_shift = bias.astype(jnp.float32) - use_mean * eff_scale
    y = (x * eff_scale.reshape(bshape).astype(x.dtype)
         + eff_shift.reshape(bshape).astype(x.dtype))
    return {"Y": y, "MeanOut": mean_out, "VarianceOut": var_out,
            "SavedMean": use_mean, "SavedVariance": inv}


@register_op("layer_norm")
def _layer_norm(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    if "Scale" in ins and ins["Scale"]:
        shape = (1,) * begin + x.shape[begin:]
        y = y * ins["Scale"][0].reshape(shape)
    if "Bias" in ins and ins["Bias"]:
        shape = (1,) * begin + x.shape[begin:]
        y = y + ins["Bias"][0].reshape(shape)
    return {"Y": y, "Mean": mean.reshape(x.shape[:begin]),
            "Variance": var.reshape(x.shape[:begin])}


@register_op("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """y = x * rsqrt(mean(x^2, -1) + epsilon) * scale, the statistics in
    float32 whatever X's dtype; with ``groups`` G over each of the G equal
    parts of the last axis on its own."""
    x = ins["X"][0]
    groups = int(attrs.get("groups", 1))
    x32 = x.astype(jnp.float32)
    if groups != 1:
        x32 = x32.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    inv = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                    + attrs.get("epsilon", 1e-5))
    y = x32 * inv
    if groups != 1:
        y = y.reshape(x.shape)
    y = y * ins["Scale"][0].astype(jnp.float32)
    return {"Y": y.astype(x.dtype)}


@register_op("rope")
def _rope(ctx, ins, attrs):
    """Rotary positions on X [B, T, H, D] at positions 0..T-1, half-split
    pairing (feature i turns with feature i + D/2):
    angle[t, i] = t * theta^(-2i/D), i < D/2;
    out = x * cos + concat(-x[D/2:], x[:D/2]) * sin, in float32.

    The formula below runs where ``pallas_kernels.rope_route`` says
    ``reference``: every backend but the TPU, a D that is not whole lane
    tiles, a T off the kernel's row block, a mesh of more than one device
    (GSPMD would have to make the kernel's operand whole on each).  On the
    TPU the other shapes take ``pallas_kernels.rope_turn``: the same two
    products and one sum an element, the half-rotation done in fast memory
    (XLA writes both halves to HBM, lane-padded, and reads them back).
    Counted at trace time as ``route/rope:{pallas,interpret,reference}``."""
    x = ins["X"][0]
    t_len, dim = x.shape[1], x.shape[3]
    half = dim // 2
    inv_freq = float(attrs.get("theta", 10000.0)) ** (
        -2.0 * jnp.arange(half, dtype=jnp.float32) / dim)
    angle = jnp.arange(t_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)          # [T, D]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    single = ctx.mesh is None or getattr(ctx.mesh, "size", 1) == 1
    route = pallas_kernels.rope_route(x.shape, x.dtype) if single \
        else "reference"
    compile_cache.stats().bump("route/rope:" + route)
    if route != "reference":
        # concat(-x[D/2:], x[:D/2]) * sin = roll(x, D/2) * (sign * sin)
        signed = jnp.concatenate([-sin[:, :half], sin[:, half:]], axis=-1)
        return {"Out": pallas_kernels.rope_turn(
            x, cos, signed, interpret=route == "interpret")}
    x32 = x.astype(jnp.float32)
    turned = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return {"Out": (x32 * cos[None, :, None, :]
                    + turned * sin[None, :, None, :]).astype(x.dtype)}


@register_op("short_conv")
def _short_conv(ctx, ins, attrs):
    """The short causal depthwise convolution of a hybrid decoder, between
    its two projections.  Filter [C, L] is a depthwise causal filter of L
    taps: c[t] = sum_j Filter[:, j] * v[t - (L-1) + j], v before position 0
    is 0.  Two forms, by the attribute ``gated`` (default true):

    * gated (LFM2's ``conv`` layers): X [B, T, 3C] holds the gates and the
      input side by side, [Bg | Cg | u];  v = Bg * u,  Out = Cg * c;
    * ungated (a Mamba-2 layer's filter): X [B, T, C];  v = X,
      Out = act(c + Bias), with Bias [C] (optional) and ``activation``
      (``silu`` or none).

    ONE lowering for the gates, taps, bias and activation, computed in
    float32.  Where ``pallas_kernels.short_conv_route`` says so (a TPU, C
    whole lane tiles, T whole row blocks, one device) the Pallas kernels
    run, one pair for both forms: one read of X and one write of Out
    forward, one read of X and the cotangent and one write of dX backward.
    Everywhere else the formula below: tap j reads ``v`` shifted down by
    L-1-j positions (a slice of ``v`` behind as many zero rows), and XLA
    makes of the gated form two passes forward and five backward.  Counted
    at trace time as ``route/short_conv:{pallas,interpret,xla}``, both
    forms."""
    x, w = ins["X"][0], ins["Filter"][0]
    c, taps = w.shape
    gated = attrs.get("gated", True)
    act = attrs.get("activation")
    bias = ins["Bias"][0] if ins.get("Bias") else None
    if gated and (bias is not None or act):
        raise ValueError("short_conv: the gated form takes no bias and no "
                         "activation")
    if act not in (None, "silu"):
        raise ValueError(f"short_conv: activation {act!r} is neither "
                         f"'silu' nor None")
    single = ctx.mesh is None or getattr(ctx.mesh, "size", 1) == 1
    interpret = attrs.get("interpret", False)
    route = pallas_kernels.short_conv_route(
        x.shape, taps, x.dtype, interpret, gated, act) if single else "xla"
    compile_cache.stats().bump("route/short_conv:" + route)
    if route != "xla":
        if not gated and bias is None:
            bias = jnp.zeros((c,), w.dtype)
        return {"Out": pallas_kernels.short_conv(
            x, w, bias, act, interpret=route == "interpret")}
    x32, w32 = x.astype(jnp.float32), w.astype(jnp.float32)
    v = x32[..., :c] * x32[..., 2 * c:] if gated else x32
    acc = v * w32[:, taps - 1]
    for j in range(taps - 1):
        back = taps - 1 - j
        behind = jnp.concatenate(
            [jnp.zeros_like(v[:, :back]), v[:, :v.shape[1] - back]], axis=1)
        acc = acc + behind * w32[:, j]
    if gated:
        return {"Out": (x32[..., c:2 * c] * acc).astype(x.dtype)}
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return {"Out": (jax.nn.silu(acc) if act else acc).astype(x.dtype)}


_CE_EPS = 1e-8      # cross_entropy_op's clamp under the logarithm


def _label_rows(label, rank):
    """Hard labels as int32 with the class axis squeezed away."""
    lab = label.astype(jnp.int32)
    return lab.squeeze(-1) if lab.ndim == rank else lab


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _nll_and_lse(logits, lab, row_scale, clamp):
    return _nll_and_lse_fwd(logits, lab, row_scale, clamp)[0]


def _nll_and_lse_fwd(logits, lab, row_scale, clamp):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1,
                           keepdims=True)
    z_y = jnp.take_along_axis(logits, lab[..., None], axis=-1)
    loss, keep = lse - z_y.astype(jnp.float32), None
    if clamp:
        p_y = jnp.exp(-loss)
        if row_scale is not None:
            p_y = p_y * row_scale.astype(jnp.float32)
        loss, keep = -jnp.log(jnp.maximum(p_y, _CE_EPS)), p_y >= _CE_EPS
    return (loss.astype(logits.dtype), lse), (logits, lse, lab, keep)


def _nll_and_lse_bwd(clamp, res, cts):
    logits, lse, lab, keep = res
    g, g_lse = cts
    g = g.astype(jnp.float32)
    if clamp:
        g = jnp.where(keep, g, 0.0)
    classes = logits.shape[-1]
    lab = jnp.where(lab < 0, lab + classes, lab)      # as the gather reads it
    hit = lax.broadcasted_iota(jnp.int32, logits.shape,
                               logits.ndim - 1) == lab[..., None]
    p = jnp.exp(logits.astype(jnp.float32) - lse)
    dlogits = p * (g + g_lse) - jnp.where(hit, g, 0.0)
    return dlogits.astype(logits.dtype), None, None


_nll_and_lse.defvjp(_nll_and_lse_fwd, _nll_and_lse_bwd)


def _nll_from_logits(logits, label, row_scale=None, clamp=True):
    """``(loss, lse)``: the hard-label cross-entropy of
    ``softmax(logits, -1) * row_scale`` and the float32
    ``logsumexp(logits)`` beside it, from the logits alone.  With ``clamp``
    the loss is ``cross_entropy``'s, ``-log(max(p[label], 1e-8))`` with
    ``p[label] = exp(logits[label] - lse) * row_scale``; without it (and
    without a ``row_scale``) ``softmax_with_cross_entropy``'s, ``lse -
    logits[label]``.  Shape ``[..., 1]``.  Nothing of the size of the
    logits is written going forward, and the residuals are the logits
    (alive as their producer's output), ``lse``, the labels and one flag a
    row.  Going back, ``dlogits = (exp(logits - lse) - onehot(label)) * g``
    with the one-hot an ``iota`` comparison XLA fuses (the composition's
    gather transposes to a scatter-add into dense zeros), zero for a row
    the clamp caught, as the clamp's own gradient is; labels and
    ``row_scale`` get none.  Reductions run in float32 whatever the logits'
    dtype; the loss and ``dlogits`` come back in it."""
    return _nll_and_lse(logits, _label_rows(label, logits.ndim), row_scale,
                        clamp)


@register_op("softmax")
def _softmax(ctx, ins, attrs):
    x = ins["X"][0]
    out = jax.nn.softmax(x, axis=attrs.get("axis", -1))
    if attrs.get("axis", -1) % x.ndim == x.ndim - 1:
        ctx.env.note_softmax(ctx.op.outputs["Out"][0], out, x)
    return {"Out": out}


@register_op("log_softmax")
def _log_softmax(ctx, ins, attrs):
    return {"Out": jax.nn.log_softmax(ins["X"][0], axis=attrs.get("axis", -1))}


@register_op("cross_entropy")
def _cross_entropy(ctx, ins, attrs):
    """cross_entropy_op: X is probabilities [N, D]; hard or soft labels.
    Out is [N, 1] like the reference.  Where X is known to be a softmax
    (``Env.softmax_of``) and the labels are hard, the loss is computed
    from that softmax's logits and X is not read."""
    x, label = ins["X"][0], ins["Label"][0]
    soft = attrs.get("soft_label", False)
    note = None if soft else ctx.env.softmax_note(ctx.op.inputs["X"][0], x)
    if note is not None:
        compile_cache.stats().bump("route/cross_entropy:from_logits")
        return {"Y": _nll_from_logits(note[0], label, note[1])[0]}
    compile_cache.stats().bump("route/cross_entropy:probabilities")
    if soft:
        return {"Y": -jnp.sum(label * jnp.log(jnp.maximum(x, _CE_EPS)),
                              axis=-1, keepdims=True)}
    picked = jnp.take_along_axis(x, _label_rows(label, x.ndim)[..., None],
                                 axis=-1)
    return {"Y": -jnp.log(jnp.maximum(picked, _CE_EPS))}


@register_op("softmax_with_cross_entropy")
def _softmax_with_ce(ctx, ins, attrs):
    """Fused, numerically-stable logits->loss (softmax_with_cross_entropy_op).
    Hard labels share ``_nll_from_logits`` with ``cross_entropy``, without
    its clamp: a label whose probability is under 1e-8 keeps its loss and
    its gradient, as ``-log_softmax`` gives them."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    if attrs.get("soft_label", False):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return {"Softmax": jnp.exp(logp),
                "Loss": -jnp.sum(label * logp, axis=-1, keepdims=True)}
    compile_cache.stats().bump("route/cross_entropy:from_logits")
    loss, lse = _nll_from_logits(logits, label, clamp=False)
    return {"Softmax": jnp.exp(logits.astype(jnp.float32) - lse)
            .astype(logits.dtype), "Loss": loss}


@register_op("dropout")
def _dropout(ctx, ins, attrs):
    """dropout_op: reference semantics — train: x*mask; test: x*(1-p).
    'upscale_in_train' implementation also supported."""
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    upscale = attrs.get("dropout_implementation", "downgrade_in_infer") \
        == "upscale_in_train"
    if attrs.get("is_test", False) or ctx.is_test:
        out = x if upscale else x * (1.0 - p)
        return {"Out": out, "Mask": jnp.ones_like(x)}
    keep = jax.random.bernoulli(ctx.rng(), 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    out = x * mask
    if upscale:
        out = out / (1.0 - p)
    return {"Out": out, "Mask": mask}


@register_op("lrn")
def _lrn(ctx, ins, attrs):
    """lrn_op: cross-channel local response normalization (AlexNet)."""
    x = ins["X"][0]
    n = attrs.get("n", 5)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    k = attrs.get("k", 2.0)
    sq = jnp.square(x)
    half = n // 2
    pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    acc = sum(pad[:, i:i + x.shape[1]] for i in range(n))
    mid = k + alpha * acc
    return {"Out": x / jnp.power(mid, beta), "MidOut": mid}


@register_op("maxout")
def _maxout(ctx, ins, attrs):
    """maxout_op: max over groups of channels."""
    x = ins["X"][0]
    g = attrs["groups"]
    n, c, h, w = x.shape
    return {"Out": jnp.max(x.reshape(n, c // g, g, h, w), axis=2)}


@register_op("bilinear_interp")
def _bilinear_interp(ctx, ins, attrs):
    """v1 BilinearInterpLayer / interpolate: resize H,W bilinearly."""
    x = ins["X"][0]
    oh = attrs["out_h"]
    ow = attrs["out_w"]
    n, c = x.shape[0], x.shape[1]
    out = jax.image.resize(x, (n, c, oh, ow), method="bilinear")
    return {"Out": out}


@register_op("pad_constant_like")
def _pad_constant_like(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    pads = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    return {"Out": jnp.pad(y, pads, constant_values=attrs.get("pad_value", 0.0))}


@register_op("spp")
def _spp(ctx, ins, attrs):
    """spp_op: spatial pyramid pooling — concat of pyramid_height levels."""
    x = ins["X"][0]
    levels = attrs.get("pyramid_height", 3)
    ptype = attrs.get("pooling_type", "max")
    n, c, h, w = x.shape
    outs = []
    for lv in range(levels):
        bins = 2 ** lv
        kh, kw = -(-h // bins), -(-w // bins)
        sh, sw = kh, kw
        ph, pw = (kh * bins - h + 1) // 2, (kw * bins - w + 1) // 2
        o = _pool2d_core(x, ptype, (kh, kw), (sh, sw), (ph, pw), False, False)
        outs.append(o.reshape(n, -1))
    return {"Out": jnp.concatenate(outs, axis=1)}


@register_op("im2sequence", "block_expand")
def _im2sequence(ctx, ins, attrs):
    """block_expand (v1 BlockExpandLayer): image patches -> sequence."""
    x = ins["X"][0]
    kh, kw = _pair(attrs.get("kernels", attrs.get("block", [1, 1])))
    sh, sw = _pair(attrs.get("strides", [1, 1]))
    ph, pw = _pair(attrs.get("paddings", [0, 0]))
    patches = lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), [(ph, ph), (pw, pw)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    n, ckk, oh, ow = patches.shape
    out = patches.reshape(n, ckk, oh * ow).transpose(0, 2, 1)
    return {"Out": out}



# ---------------------------------------------------------------------------
# Static shape/dtype rules (analysis.shape_infer) — the InferShape analogs
# of conv_op.cc / pool_op.cc / batch_norm_op.cc etc.
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import (ShapeError, VarInfo,  # noqa: E402
                                    conv_out_dim, dim_ok, first, mirror,
                                    same_as)
from ..core.registry import register_shape_fn  # noqa: E402

register_shape_fn("softmax", "log_softmax")(same_as("X"))
register_shape_fn("pad_constant_like")(same_as("X"))


@register_shape_fn("conv2d", "depthwise_conv2d")
def _conv2d_shape(op, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    if x.shape is None or w.shape is None:
        return {"Output": VarInfo(None, x.dtype)}
    if len(x.shape) != 4 or len(w.shape) != 4:
        raise ShapeError(
            f"conv2d: Input/Filter must be rank-4, got {list(x.shape)} / "
            f"{list(w.shape)}")
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = int(attrs.get("groups", 1) or 1)
    n, c, h, wd = x.shape
    o, cg, kh, kw = w.shape
    if c >= 0 and cg >= 0 and c != cg * groups:
        raise ShapeError(
            f"conv2d: input channels {c} != Filter C/g {cg} * groups "
            f"{groups}")
    if o >= 0 and groups > 1 and o % groups:
        raise ShapeError(
            f"conv2d: output channels {o} not divisible by groups {groups}")
    oh = conv_out_dim(h, kh, pads[0], strides[0], dil[0])
    ow = conv_out_dim(wd, kw, pads[1], strides[1], dil[1])
    return {"Output": VarInfo((n, o, oh, ow), x.dtype)}


@register_shape_fn("conv2d_transpose")
def _conv2d_transpose_shape(op, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    if x.shape is None or w.shape is None:
        return {"Output": VarInfo(None, x.dtype)}
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    n, c, h, wd = x.shape
    ci, co, kh, kw = w.shape
    if c >= 0 and ci >= 0 and c != ci:
        raise ShapeError(
            f"conv2d_transpose: input channels {c} != Filter C_in {ci}")

    def _out(size, k, p, s, d):
        if size < 0:
            return -1
        return (size - 1) * s - 2 * p + d * (k - 1) + 1

    return {"Output": VarInfo(
        (n, co, _out(h, kh, pads[0], strides[0], dil[0]),
         _out(wd, kw, pads[1], strides[1], dil[1])), x.dtype)}


@register_shape_fn("conv3d")
def _conv3d_shape(op, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Filter")
    if x.shape is None or w.shape is None:
        return {"Output": VarInfo(None, x.dtype)}
    strides = tuple(attrs.get("strides", [1, 1, 1]))
    pads = tuple(attrs.get("paddings", [0, 0, 0]))
    dil = tuple(attrs.get("dilations", [1, 1, 1]))
    n, c = x.shape[0], x.shape[1]
    o = w.shape[0]
    dims = tuple(conv_out_dim(x.shape[2 + i], w.shape[2 + i], pads[i],
                              strides[i], dil[i]) for i in range(3))
    return {"Output": VarInfo((n, o) + dims, x.dtype)}


def _pool2d_out_shape(x, attrs):
    if attrs.get("global_pooling", False):
        return x.shape[:2] + (1, 1)
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    ceil = attrs.get("ceil_mode", False)
    return x.shape[:2] + (
        conv_out_dim(x.shape[2], ksize[0], pads[0], strides[0],
                     ceil_mode=ceil),
        conv_out_dim(x.shape[3], ksize[1], pads[1], strides[1],
                     ceil_mode=ceil))


@register_shape_fn("pool2d")
def _pool2d_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    if len(x.shape) != 4:
        raise ShapeError(f"pool2d: X must be rank-4, got {list(x.shape)}")
    return {"Out": x.with_shape(_pool2d_out_shape(x, attrs))}


@register_shape_fn("max_pool2d_with_index", "pool2d_with_index")
def _pool2d_with_index_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x, "Mask": VarInfo(None, "int64")}
    a = dict(attrs)
    a.setdefault("strides", a.get("ksize", [2, 2]))
    # the patch-extraction lowering always floors, unlike _pool2d_core
    a["ceil_mode"] = False
    shape = _pool2d_out_shape(x, a)
    return {"Out": x.with_shape(shape), "Mask": VarInfo(shape, "int64")}


@register_shape_fn("pool3d")
def _pool3d_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    if attrs.get("global_pooling", False):
        return {"Out": x.with_shape(x.shape[:2] + (1, 1, 1))}
    ks = list(attrs.get("ksize", [2, 2, 2]))
    strides = list(attrs.get("strides", ks))
    pads = list(attrs.get("paddings", [0, 0, 0]))
    dims = tuple(conv_out_dim(x.shape[2 + i], ks[i], pads[i], strides[i])
                 for i in range(3))
    return {"Out": x.with_shape(x.shape[:2] + dims)}


@register_shape_fn("unpool")
def _unpool_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    n, c, oh, ow = x.shape
    if "unpool_size" in attrs:
        uh, uw = attrs["unpool_size"]
    else:
        uh, uw = attrs["ksize"][0] * oh, attrs["ksize"][1] * ow
    return {"Out": x.with_shape((n, c, uh, uw))}


@register_shape_fn("batch_norm")
def _batch_norm_shape(op, ins, attrs):
    x = first(ins, "X")
    scale = first(ins, "Scale")
    if x.shape is not None and scale.shape is not None and \
            len(x.shape) >= 2 and not dim_ok(x.shape[1], scale.shape[-1]):
        raise ShapeError(
            f"batch_norm: channel dim {x.shape[1]} != Scale size "
            f"{scale.shape[-1]}")
    res = {"Y": x}
    res.update(mirror({"MeanOut": "Mean", "VarianceOut": "Variance",
                       "SavedMean": "Mean", "SavedVariance": "Variance"})(
        op, ins, attrs))
    return res


@register_shape_fn("layer_norm")
def _layer_norm_shape(op, ins, attrs):
    x = first(ins, "X")
    res = {"Y": x}
    if x.shape is not None:
        begin = attrs.get("begin_norm_axis", 1)
        stat = VarInfo(x.shape[:begin], x.dtype)
        res["Mean"] = stat
        res["Variance"] = stat
    return res


@register_shape_fn("rms_norm")
def _rms_norm_shape(op, ins, attrs):
    x, scale = first(ins, "X"), first(ins, "Scale")
    if x.shape is not None and scale.shape is not None and \
            not dim_ok(x.shape[-1], scale.shape[-1]):
        raise ShapeError(
            f"rms_norm: feature dim {x.shape[-1]} != Scale size "
            f"{scale.shape[-1]}")
    groups = int(attrs.get("groups", 1))
    if groups < 1 or (x.shape is not None and x.shape[-1] >= 0
                      and x.shape[-1] % groups):
        raise ShapeError(f"rms_norm: {x.shape[-1]} features are not "
                         f"{groups} equal groups")
    return {"Y": x}


@register_shape_fn("rope")
def _rope_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is not None and (len(x.shape) != 4 or (
            x.shape[-1] >= 0 and x.shape[-1] % 2)):
        raise ShapeError(
            f"rope: X {list(x.shape)} is not [B, T, H, D] with an even D")
    return {"Out": x}


@register_shape_fn("short_conv")
def _short_conv_shape(op, ins, attrs):
    x, w = first(ins, "X"), first(ins, "Filter")
    if x.shape is None:
        return {"Out": x}
    if not attrs.get("gated", True):
        bias = first(ins, "Bias")
        if len(x.shape) != 3:
            raise ShapeError(
                f"short_conv: X {list(x.shape)} is not [B, T, C] (the "
                f"ungated form)")
        if w.shape is not None and (len(w.shape) != 2 or not dim_ok(
                w.shape[0], x.shape[-1])):
            raise ShapeError(
                f"short_conv: Filter {list(w.shape)} is not [C, taps] for "
                f"X {list(x.shape)} = [B, T, C]")
        if ins.get("Bias") and bias.shape is not None and not (
                len(bias.shape) == 1 and dim_ok(bias.shape[0], x.shape[-1])):
            raise ShapeError(
                f"short_conv: Bias {list(bias.shape)} is not [C] for X "
                f"{list(x.shape)} = [B, T, C]")
        return {"Out": x}
    if ins.get("Bias") or attrs.get("activation"):
        raise ShapeError("short_conv: the gated form takes no Bias and no "
                         "activation")
    if len(x.shape) != 3 or (x.shape[-1] >= 0 and x.shape[-1] % 3):
        raise ShapeError(
            f"short_conv: X {list(x.shape)} is not [B, T, 3C] (the two "
            f"gates and the input side by side)")
    if w.shape is not None and (len(w.shape) != 2 or not dim_ok(
            3 * w.shape[0], x.shape[-1])):
        raise ShapeError(
            f"short_conv: Filter {list(w.shape)} is not [C, taps] for X "
            f"{list(x.shape)} = [B, T, 3C]")
    return {"Out": x.with_shape(
        x.shape[:-1] + (-1 if x.shape[-1] < 0 else x.shape[-1] // 3,))}


@register_shape_fn("cross_entropy")
def _cross_entropy_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Y": x}
    return {"Y": x.with_shape(x.shape[:-1] + (1,))}


@register_shape_fn("softmax_with_cross_entropy")
def _softmax_ce_shape(op, ins, attrs):
    logits, label = first(ins, "Logits"), first(ins, "Label")
    if logits.shape is None:
        return {"Softmax": logits, "Loss": VarInfo(None, logits.dtype)}
    if label.shape is not None and not attrs.get("soft_label", False):
        if not dim_ok(label.shape[0], logits.shape[0]):
            raise ShapeError(
                f"softmax_with_cross_entropy: batch mismatch Logits "
                f"{list(logits.shape)} vs Label {list(label.shape)}")
    return {"Softmax": logits,
            "Loss": logits.with_shape(logits.shape[:-1] + (1,))}


@register_shape_fn("dropout")
def _dropout_shape(op, ins, attrs):
    x = first(ins, "X")
    return {"Out": x, "Mask": x}


@register_shape_fn("lrn")
def _lrn_shape(op, ins, attrs):
    x = first(ins, "X")
    return {"Out": x, "MidOut": x}


@register_shape_fn("maxout")
def _maxout_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    g = attrs["groups"]
    n, c, h, w = x.shape
    if c >= 0 and c % g:
        raise ShapeError(f"maxout: channels {c} not divisible by groups {g}")
    return {"Out": x.with_shape((n, -1 if c < 0 else c // g, h, w))}


@register_shape_fn("bilinear_interp")
def _bilinear_interp_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    return {"Out": x.with_shape(x.shape[:2] + (attrs["out_h"],
                                               attrs["out_w"]))}


@register_shape_fn("spp")
def _spp_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    n, c = x.shape[0], x.shape[1]
    bins = sum(4 ** lv for lv in range(attrs.get("pyramid_height", 3)))
    return {"Out": x.with_shape((n, -1 if c < 0 else c * bins))}


@register_shape_fn("im2sequence", "block_expand")
def _im2sequence_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    kh, kw = _pair(attrs.get("kernels", attrs.get("block", [1, 1])))
    sh, sw = _pair(attrs.get("strides", [1, 1]))
    ph, pw = _pair(attrs.get("paddings", [0, 0]))
    n, c, h, wd = x.shape
    oh = conv_out_dim(h, kh, ph, sh)
    ow = conv_out_dim(wd, kw, pw, sw)
    t = -1 if oh < 0 or ow < 0 else oh * ow
    d = -1 if c < 0 else c * kh * kw
    return {"Out": x.with_shape((n, t, d))}


# ---------------------------------------------------------------------------
# Sharding-propagation rules (analysis.shard_prop): convs follow
# batch/output-channel sharding, normalizations and pointwise heads are
# shape-preserving, losses keep the batch dim only.
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import (shard_batch_only,  # noqa: E402
                                   shard_conv2d, shard_same_as)
from ..core.registry import register_shard_fn  # noqa: E402

register_shard_fn("conv2d", "depthwise_conv2d")(shard_conv2d())
register_shard_fn("softmax", "log_softmax", "lrn")(shard_same_as("X"))
register_shard_fn("dropout")(shard_same_as("X", also=("Mask",)))
register_shard_fn("cross_entropy")(shard_batch_only("X", out="Y"))


@register_shard_fn("pool2d", "pool3d", "max_pool2d_with_index",
                   "pool2d_with_index")
def _pool_shard(op, ins, attrs):
    from ..analysis.shard_prop import ShardConflict, first_in
    x = first_in(ins, "X")
    if x.spec is None:
        return {}
    if any(x.entry(i) for i in range(2, len(x.spec))):
        raise ShardConflict(
            "pooling input spatially sharded: halo exchange required")
    spec = (x.entry(0), x.entry(1)) + (None,) * (len(x.spec) - 2)
    res = {"Out": spec}
    if op.outputs.get("Mask"):
        res["Mask"] = spec
    return res


@register_shard_fn("batch_norm")
def _batch_norm_shard(op, ins, attrs):
    from ..analysis.shard_prop import first_in
    x = first_in(ins, "X")
    res = {}
    if x.spec is not None:
        res["Y"] = x.spec
    for out_slot, in_slot in (("MeanOut", "Mean"),
                              ("VarianceOut", "Variance"),
                              ("SavedMean", "Mean"),
                              ("SavedVariance", "Variance")):
        v = first_in(ins, in_slot)
        if op.outputs.get(out_slot) and v.spec is not None:
            res[out_slot] = v.spec
    return res


@register_shard_fn("layer_norm")
def _layer_norm_shard(op, ins, attrs):
    from ..analysis.shard_prop import first_in
    x = first_in(ins, "X")
    if x.spec is None:
        return {}
    begin = attrs.get("begin_norm_axis", 1)
    res = {"Y": x.spec}
    if op.outputs.get("Mean"):
        res["Mean"] = x.spec[:begin]
    if op.outputs.get("Variance"):
        res["Variance"] = x.spec[:begin]
    return res


# rms_norm and rope keep X's layout dim for dim (rope reads its position
# from the index along T, which a sharded T keeps global under GSPMD)
register_shard_fn("rms_norm")(shard_same_as("X", out="Y"))
register_shard_fn("rope")(shard_same_as("X"))


@register_shard_fn("short_conv")
def _short_conv_shard(op, ins, attrs):
    """Out keeps X's batch sharding.  The filter runs along T and the
    feature axis is cut in three inside the gated op (the kernels of
    either form take whole features), so a sharded T (it would need the
    L-1 rows before each shard) or feature axis is a conflict."""
    from ..analysis.shard_prop import ShardConflict, first_in
    x = first_in(ins, "X")
    if x.spec is None:
        return {}
    if x.entry(1) or x.entry(2):
        raise ShardConflict(
            "short_conv: X sharded along T or the features: the filter "
            "needs a halo along T, the split whole features")
    return {"Out": (x.entry(0), None, None)}


@register_shard_fn("softmax_with_cross_entropy")
def _softmax_ce_shard(op, ins, attrs):
    from ..analysis.shard_prop import first_in
    logits = first_in(ins, "Logits")
    if logits.spec is None:
        return {}
    return {"Softmax": logits.spec, "Loss": (logits.entry(0), None)}


# ---------------------------------------------------------------------------
# Row-wise rules (core.registry.register_rowwise): a softmax along any axis
# but the leading one, rms_norm.  batch_norm (statistics over the rows),
# dropout (a random draw) and rope (a row's position) have none.
# ---------------------------------------------------------------------------
from ..core.registry import register_rowwise  # noqa: E402


@register_rowwise("softmax", "log_softmax")
def _softmax_rowwise(attrs, ins):
    x = ins["X"][0]
    rank = len(x.shape)
    return x.rows and rank >= 2 and attrs.get("axis", -1) % rank != 0


@register_rowwise("rms_norm")
def _rms_norm_rowwise(attrs, ins):
    """The statistics run along the last axis alone, the scale has no rows
    (rope has no rule: a row's result depends on its position)."""
    x = ins["X"][0]
    return x.rows and len(x.shape) >= 2 and not ins["Scale"][0].rows
