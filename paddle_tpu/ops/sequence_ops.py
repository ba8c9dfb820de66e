"""Sequence op lowerings over the padded+lengths representation.

The reference stores variable-length sequences padding-free via LoD offsets
(lod_tensor.h:34-83; v1 Argument.sequenceStartPositions, Argument.h:84-90) and
reorders into time-major shrinking batches (SequenceToBatch.cpp,
lod_rank_table_op.cc).  XLA needs static shapes, so the TPU-native design is:

    value:  [B, T_max, ...] padded dense tensor
    length: [B] int32 companion (var ``name@LEN`` threaded by the executor)

Every sequence op masks by length.  This trades padding FLOPs for MXU-sized
static matmuls — the standard TPU bargain — and buckets in the data feeder
keep T_max tight (see paddle_tpu.reader).

Fused RNNs (``lstm``/``gru``, reference lstm_op.cc + math/lstm_compute,
gru_op) are lax.scan loops whose per-step math is batched matmul — XLA fuses
the gate nonlinearities; the recurrent matmul rides the MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op


def _mask(lens, T, dtype=jnp.float32):
    """[B,T] validity mask from lengths."""
    return (jnp.arange(T)[None, :] < lens[:, None]).astype(dtype)


def _in_lens(ctx, slot="X", idx=0):
    name = ctx.op.inputs[slot][idx]
    lens = ctx.get_len(name)
    return lens


def _seq_lens_or_full(ctx, x, slot="X"):
    lens = _in_lens(ctx, slot)
    if lens is None:
        lens = jnp.full((x.shape[0],), x.shape[1], dtype=jnp.int32)
    return lens


@register_op("sequence_pool")
def _sequence_pool(ctx, ins, attrs):
    """sequence_pool_op: AVERAGE/SUM/SQRT/MAX/LAST/FIRST over time.

    Nested input ([B, S, T, ...] with an @LEN2 companion): LAST returns the
    last valid token of the last valid subsequence; FIRST the first token of
    the first subsequence — the level-0 aggregation of the reference's
    nested LoD."""
    x = ins["X"][0]                      # [B, T, ...]
    lens = _seq_lens_or_full(ctx, x)
    lens2 = ctx.get_len2(ctx.op.inputs["X"][0])
    if lens2 is not None:
        ptype_n = attrs.get("pooltype",
                            attrs.get("pool_type", "AVERAGE")).upper()
        B = x.shape[0]
        b_idx = jnp.arange(B)
        if ptype_n == "LAST":
            last_s = jnp.maximum(lens - 1, 0)                # [B]
            il = jnp.take_along_axis(lens2, last_s[:, None],
                                     axis=1)[:, 0]           # [B]
            return {"Out": x[b_idx, last_s, jnp.maximum(il - 1, 0)]}
        if ptype_n == "FIRST":
            return {"Out": x[:, 0, 0]}
        raise NotImplementedError(
            f"sequence_pool {ptype_n} over nested sequences: only "
            f"LAST/FIRST are defined (matching last_seq/first_seq use)")
    ptype = attrs.get("pooltype", attrs.get("pool_type", "AVERAGE")).upper()
    if ptype == "AVG":                 # v1 AvgPooling spelling
        ptype = "AVERAGE"
    T = x.shape[1]
    m = _mask(lens, T, x.dtype).reshape((x.shape[0], T) + (1,) * (x.ndim - 2))
    if ptype == "SUM":
        out = jnp.sum(x * m, axis=1)
    elif ptype == "AVERAGE":
        out = jnp.sum(x * m, axis=1) / jnp.maximum(
            lens.astype(x.dtype), 1).reshape((-1,) + (1,) * (x.ndim - 2))
    elif ptype == "SQRT":
        out = jnp.sum(x * m, axis=1) / jnp.sqrt(jnp.maximum(
            lens.astype(x.dtype), 1)).reshape((-1,) + (1,) * (x.ndim - 2))
    elif ptype == "MAX":
        neg = jnp.asarray(-3.4e38, x.dtype)
        out = jnp.max(jnp.where(m > 0, x, neg), axis=1)
    elif ptype == "LAST":
        idx = jnp.maximum(lens - 1, 0)
        out = jnp.take_along_axis(
            x, idx.reshape((-1, 1) + (1,) * (x.ndim - 2)).astype(jnp.int32)
            .repeat(1, axis=1), axis=1).squeeze(1)
    elif ptype == "FIRST":
        out = x[:, 0]
    else:
        raise ValueError(f"unknown pooltype {ptype}")
    return {"Out": out}


@register_op("sequence_softmax")
def _sequence_softmax(ctx, ins, attrs):
    """softmax over the time dim, masked to each sequence's length."""
    x = ins["X"][0]                      # [B, T] or [B, T, 1]
    squeeze = x.ndim == 3 and x.shape[-1] == 1
    v = x.squeeze(-1) if squeeze else x
    lens = _seq_lens_or_full(ctx, v)
    m = _mask(lens, v.shape[1], jnp.bool_)
    z = jnp.where(m, v, -3.4e38)
    out = jax.nn.softmax(z, axis=1)
    out = out * m.astype(out.dtype)
    if squeeze:
        out = out[..., None]
    ctx.set_len(ctx.op.outputs["Out"][0], lens)
    return {"Out": out}


@register_op("sequence_expand", "sequence_expand_as")
def _sequence_expand(ctx, ins, attrs):
    """sequence_expand_op: broadcast one row per sequence along Y's time."""
    x, y = ins["X"][0], ins["Y"][0]
    lens = _seq_lens_or_full(ctx, y, slot="Y")
    T = y.shape[1]
    if x.ndim == y.ndim:                  # already time-major: tile-nothing
        out = x
    else:                                 # [B, D] -> [B, T, D]
        out = jnp.broadcast_to(x[:, None], (x.shape[0], T) + x.shape[1:])
    m = _mask(lens, T, out.dtype).reshape(
        (out.shape[0], T) + (1,) * (out.ndim - 2))
    out = out * m
    ctx.set_len(ctx.op.outputs["Out"][0], lens)
    return {"Out": out}


@register_op("sequence_concat")
def _sequence_concat(ctx, ins, attrs):
    """sequence_concat_op axis=0: concatenate per-sequence along time.

    out[i] = concat(x0[i, :l0_i], x1[i, :l1_i], ...) then re-padded.
    Built with a gather from the stacked inputs — one fused XLA gather.
    """
    xs = ins["X"]
    B = xs[0].shape[0]
    lens_list = []
    for i, nm in enumerate(ctx.op.inputs["X"]):
        l = ctx.get_len(nm)
        if l is None:
            l = jnp.full((B,), xs[i].shape[1], jnp.int32)
        lens_list.append(l)
    total = sum(lens_list)
    T_out = sum(x.shape[1] for x in xs)
    # For output position t of row b, find which source and source offset.
    starts = jnp.cumsum(jnp.stack([jnp.zeros_like(lens_list[0])] +
                                  lens_list[:-1]), axis=0)  # [K, B]
    tpos = jnp.arange(T_out)[None, :]                        # [1, T_out]
    src = jnp.zeros((B, T_out), jnp.int32)
    off = tpos.repeat(B, 0)
    for k in range(len(xs)):
        sel = tpos >= starts[k][:, None]
        src = jnp.where(sel, k, src)
        off = jnp.where(sel, tpos - starts[k][:, None], off)
    padded = jnp.stack([jnp.pad(x, [(0, 0), (0, T_out - x.shape[1])] +
                                [(0, 0)] * (x.ndim - 2)) for x in xs])  # [K,B,T_out,...]
    b_idx = jnp.arange(B)[:, None]
    out = padded[src, b_idx, jnp.clip(off, 0, T_out - 1)]
    m = _mask(total, T_out, out.dtype).reshape(
        (B, T_out) + (1,) * (out.ndim - 2))
    out = out * m
    ctx.set_len(ctx.op.outputs["Out"][0], total)
    return {"Out": out}


@register_op("sequence_context")
def _sequence_context(ctx, ins, attrs):
    """v1 ContextProjection without the matmul: [B,T,D] -> [B,T,ctx_len*D]
    concat of shifted timesteps (function/ContextProjectionOp.cpp).  With
    a PadW input ([begin_pad+end_pad, D], trainable), out-of-range
    positions read the learned boundary rows instead of zeros — the
    reference's trainable_padding path."""
    x = ins["X"][0]
    pad_w = ins.get("PadW", [None])[0]
    lens = _seq_lens_or_full(ctx, x)
    ctx_len = attrs.get("contextLength", 3)
    start = attrs.get("contextStart", -(ctx_len // 2))
    begin_pad = max(0, -start)
    B, T, D = x.shape
    m = _mask(lens, T, x.dtype)[..., None]
    xm = x * m
    t = jnp.arange(T)
    cols = []
    for j in range(ctx_len):
        shift = start + j
        src = t + shift                                   # [T]
        base = jnp.take(xm, jnp.clip(src, 0, T - 1), axis=1)  # [B,T,D]
        under = (src < 0)[None, :, None]
        over = (src[None, :] >= lens[:, None])[..., None]
        if pad_w is not None:
            total = pad_w.shape[0]
            u_idx = jnp.clip(begin_pad + src, 0, total - 1)
            u_rows = pad_w[u_idx][None, :, :].astype(x.dtype)
            o_idx = jnp.clip(begin_pad + (src[None, :] - lens[:, None]),
                             0, total - 1)
            o_rows = pad_w[o_idx].astype(x.dtype)
            col = jnp.where(under, u_rows, base)
            col = jnp.where(over, o_rows, col)
        else:
            col = jnp.where(under | over, jnp.zeros_like(base), base)
        cols.append(col)
    out = jnp.concatenate(cols, axis=-1) * m
    ctx.set_len(ctx.op.outputs["Out"][0], lens)
    return {"Out": out}


@register_op("sub_nested_seq")
def _sub_nested_seq(ctx, ins, attrs):
    """SubNestedSequenceLayer.cpp: select subsequences of a level-2
    sequence [B,S,T,...] by per-batch indices [B,K].  Invalid indices
    (<0, the kmax_seq_score pad, or >=S) contribute zero rows and are
    excluded from the output lengths, so downstream sequence ops mask
    them as padding."""
    x = ins["X"][0]
    sel = ins["Selection"][0].astype(jnp.int32)
    if sel.ndim == 1:
        sel = sel[:, None]
    S = x.shape[1]
    valid = (sel >= 0) & (sel < S)                      # [B, K]
    safe = jnp.clip(sel, 0, S - 1)
    idx = safe.reshape(safe.shape + (1,) * (x.ndim - 2))
    out = jnp.take_along_axis(x, idx, axis=1)
    vmask = valid.reshape(valid.shape + (1,) * (x.ndim - 2))
    out = out * vmask.astype(x.dtype)
    lens2 = ctx.get_len2(ctx.op.inputs["X"][0])
    if lens2 is not None:
        ctx.set_len2(ctx.op.outputs["Out"][0],
                     jnp.take_along_axis(lens2, safe, axis=1) *
                     valid.astype(lens2.dtype))
    ctx.set_len(ctx.op.outputs["Out"][0],
                jnp.sum(valid, axis=1).astype(jnp.int32))
    return {"Out": out}


@register_op("conv2d_dynamic_filter")
def _conv2d_dynamic_filter(ctx, ins, attrs):
    """v1 conv_operator: convolution whose FILTER is another layer's
    output (ConvOperator.cpp).  The filter layer yields one filter set
    PER SAMPLE ([B, O*I*kh*kw]); lowered as one grouped conv by folding
    the batch into channels (feature_group_count=B) — stays a single MXU
    conv instead of a python loop over samples."""
    x, w = ins["Input"][0], ins["Filter"][0]
    O, I, kh, kw = attrs["filter_shape"]
    strides = tuple(attrs.get("strides", [1, 1]))
    pads = tuple(attrs.get("paddings", [0, 0]))
    B = x.shape[0]
    w = w.astype(x.dtype)
    if w.ndim == 2 and w.shape[0] == B and w.size == B * O * I * kh * kw:
        # per-sample filters: x [B,C,H,W] -> [1,B*C,H,W], w -> [B*O,I,kh,kw]
        xg = x.reshape((1, B * x.shape[1]) + x.shape[2:])
        wg = w.reshape(B * O, I, kh, kw)
        out = jax.lax.conv_general_dilated(
            xg, wg, window_strides=strides,
            padding=[(p, p) for p in pads],
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=B)
        out = out.reshape((B, O) + out.shape[2:])
    else:
        out = jax.lax.conv_general_dilated(
            x, w.reshape(O, I, kh, kw), window_strides=strides,
            padding=[(p, p) for p in pads],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return {"Output": out}


@register_op("sequence_conv")
def _sequence_conv(ctx, ins, attrs):
    """sequence_conv_op: context-window projection along time
    (v1 ContextProjection, function/ContextProjection*).  Filter shape
    [ctx_len * D, M]."""
    x, w = ins["X"][0], ins["Filter"][0]
    lens = _seq_lens_or_full(ctx, x)
    stride = attrs.get("contextStride", 1)
    assert stride == 1, "sequence_conv supports stride 1 (as the reference)"
    ctx_len = attrs.get("contextLength", 3)
    start = attrs.get("contextStart", -(ctx_len // 2))
    B, T, D = x.shape
    m = _mask(lens, T, x.dtype)[..., None]
    xm = x * m
    cols = []
    for j in range(ctx_len):
        shift = start + j
        rolled = jnp.roll(xm, -shift, axis=1)
        # zero positions that rolled around
        t = jnp.arange(T)
        valid = (t + shift >= 0) & (t + shift < T)
        cols.append(rolled * valid[None, :, None].astype(x.dtype))
    ctxmat = jnp.concatenate(cols, axis=-1)          # [B, T, ctx_len*D]
    out = jnp.einsum("btd,dm->btm", ctxmat, w)
    out = out * m
    ctx.set_len(ctx.op.outputs["Out"][0], lens)
    return {"Out": out}


@register_op("sequence_slice")
def _sequence_slice(ctx, ins, attrs):
    """sequence_slice_op: per-sequence [offset, offset+length) gather."""
    x = ins["X"][0]
    offset = ins["Offset"][0].reshape(-1).astype(jnp.int32)
    length = ins["Length"][0].reshape(-1).astype(jnp.int32)
    B, T = x.shape[0], x.shape[1]
    tpos = jnp.arange(T)[None, :]
    idx = jnp.clip(offset[:, None] + tpos, 0, T - 1)
    out = jnp.take_along_axis(
        x, idx.reshape((B, T) + (1,) * (x.ndim - 2)), axis=1)
    m = _mask(length, T, x.dtype).reshape((B, T) + (1,) * (x.ndim - 2))
    out = out * m
    ctx.set_len(ctx.op.outputs["Out"][0], length)
    return {"Out": out}


def _masked_reverse(x, lens):
    """Reverse the first ``lens[b]`` steps of each row, padding stays put
    (reference sequence_reverse_op semantics)."""
    B, T = x.shape[0], x.shape[1]
    tpos = jnp.arange(T)[None, :]
    idx = jnp.where(tpos < lens[:, None], lens[:, None] - 1 - tpos, tpos)
    return jnp.take_along_axis(
        x, idx.reshape((B, T) + (1,) * (x.ndim - 2)).astype(jnp.int32), axis=1)


@register_op("sequence_reverse")
def _sequence_reverse(ctx, ins, attrs):
    x = ins["X"][0]
    lens = _seq_lens_or_full(ctx, x)
    out = _masked_reverse(x, lens)
    ctx.set_len(ctx.op.outputs["Y" if "Y" in ctx.op.outputs else "Out"][0], lens)
    return {("Y" if "Y" in ctx.op.outputs else "Out"): out}


@register_op("sequence_reshape")
def _sequence_reshape(ctx, ins, attrs):
    """sequence_reshape_op: change feature dim, scaling lengths."""
    x = ins["X"][0]
    new_dim = attrs["new_dim"]
    B, T, D = x.shape
    if (T * D) % new_dim:
        raise ValueError(
            f"sequence_reshape: T*D={T * D} not divisible by new_dim "
            f"{new_dim}")
    lens = _seq_lens_or_full(ctx, x)
    out = x.reshape(B, T * D // new_dim, new_dim)
    new_lens = (lens * D) // new_dim
    ctx.set_len(ctx.op.outputs["Out"][0], new_lens)
    return {"Out": out}


@register_op("sequence_pad")
def _sequence_pad(ctx, ins, attrs):
    """Identity in the padded representation (kept for API parity)."""
    x = ins["X"][0]
    lens = _seq_lens_or_full(ctx, x)
    return {"Out": x, "Length": lens}


@register_op("sequence_unpad")
def _sequence_unpad(ctx, ins, attrs):
    x = ins["X"][0]
    lens = ins["Length"][0] if "Length" in ins and ins["Length"] else \
        _seq_lens_or_full(ctx, x)
    ctx.set_len(ctx.op.outputs["Out"][0], lens.reshape(-1))
    return {"Out": x}


@register_op("lod_reset")
def _lod_reset(ctx, ins, attrs):
    x = ins["X"][0]
    if "Y" in ins and ins["Y"]:
        lens = ins["Y"][0].reshape(-1).astype(jnp.int32)
    else:
        target = attrs.get("target_lod", [])
        offs = jnp.asarray(target, jnp.int32)
        lens = offs[1:] - offs[:-1]
    ctx.set_len(ctx.op.outputs["Out"][0], lens)
    return {"Out": x}


@register_op("row_conv")
def _row_conv(ctx, ins, attrs):
    """row_conv_op: lookahead convolution (DeepSpeech2-style)."""
    x, w = ins["X"][0], ins["Filter"][0]   # x [B,T,D], w [future_ctx, D]
    lens = _seq_lens_or_full(ctx, x)
    T = x.shape[1]
    m = _mask(lens, T, x.dtype)[..., None]
    xm = x * m
    ctx_len = w.shape[0]
    out = jnp.zeros_like(x)
    for j in range(ctx_len):
        rolled = jnp.roll(xm, -j, axis=1)
        t = jnp.arange(T)
        valid = (t + j < T)[None, :, None].astype(x.dtype)
        out = out + rolled * valid * w[j][None, None, :]
    out = out * m
    ctx.set_len(ctx.op.outputs["Out"][0], lens)
    return {"Out": out}


@register_op("max_sequence_len")
def _max_sequence_len(ctx, ins, attrs):
    lens = _in_lens(ctx, "RankTable") if "RankTable" in ctx.op.inputs else \
        _in_lens(ctx, "X")
    if lens is None:
        x = next(iter(ins.values()))[0]
        return {"Out": jnp.asarray(x.shape[1], jnp.int64)}
    return {"Out": jnp.max(lens).astype(jnp.int64)}


# ---------------------------------------------------------------------------
# Fused recurrent ops (reference lstm_op.cc + math/lstm_compute;
# gru_op.cc + math/gru_compute; *_unit ops)
# Gate order: i, f, c(candidate), o for LSTM; u(update), r(reset), c for GRU.
# ---------------------------------------------------------------------------
_ACT = {
    "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh, "relu": jax.nn.relu,
    "identity": lambda x: x,
}


@register_op("lstm")
def _lstm(ctx, ins, attrs):
    """dynamic LSTM over [B,T,4H] pre-projected input; recurrent Weight
    [H,4H]; Bias [1,4H] (+[1,3H] peephole tail when use_peepholes)."""
    x = ins["Input"][0]
    w = ins["Weight"][0]
    bias = ins["Bias"][0].reshape(-1) if "Bias" in ins and ins["Bias"] else None
    lens = _seq_lens_or_full(ctx, x, slot="Input")
    B, T, H4 = x.shape
    H = H4 // 4
    use_peep = attrs.get("use_peepholes", False)
    gate_act = _ACT[attrs.get("gate_activation", "sigmoid")]
    cell_act = _ACT[attrs.get("cell_activation", "tanh")]
    cand_act = _ACT[attrs.get("candidate_activation", "tanh")]
    b_gate = None
    wi = wf = wo = None
    if bias is not None:
        b_gate = bias[:4 * H]
        if use_peep:
            peep = bias[4 * H:7 * H]
            wi, wf, wo = peep[:H], peep[H:2 * H], peep[2 * H:]
    h0 = ins["H0"][0] if "H0" in ins and ins["H0"] else jnp.zeros((B, H), x.dtype)
    c0 = ins["C0"][0] if "C0" in ins and ins["C0"] else jnp.zeros((B, H), x.dtype)
    is_reverse = attrs.get("is_reverse", False)
    if is_reverse:
        x = _masked_reverse(x, lens)
    xt_seq = jnp.swapaxes(x, 0, 1)              # [T, B, 4H]
    step_mask = _mask(lens, T, x.dtype).T       # [T, B]

    def step(carry, inp):
        h, c = carry
        xt, mt = inp
        gates = xt + h @ w
        if b_gate is not None:
            gates = gates + b_gate
        gi, gf, gc, go = jnp.split(gates, 4, axis=-1)
        if use_peep:
            gi = gi + c * wi
            gf = gf + c * wf
        i = gate_act(gi)
        f = gate_act(gf)
        cand = cand_act(gc)
        c_new = f * c + i * cand
        if use_peep:
            go = go + c_new * wo
        o = gate_act(go)
        h_new = o * cell_act(c_new)
        mt = mt[:, None]
        h_new = mt * h_new + (1 - mt) * h
        c_new = mt * c_new + (1 - mt) * c
        return (h_new, c_new), (h_new * mt, c_new * mt)

    (_, _), (hs, cs) = lax.scan(step, (h0, c0), (xt_seq, step_mask))
    hidden = jnp.swapaxes(hs, 0, 1)
    cell = jnp.swapaxes(cs, 0, 1)
    if is_reverse:
        hidden = _masked_reverse(hidden, lens)
        cell = _masked_reverse(cell, lens)
    for slot, val in (("Hidden", hidden), ("Cell", cell)):
        if slot in ctx.op.outputs and ctx.op.outputs[slot]:
            ctx.set_len(ctx.op.outputs[slot][0], lens)
    return {"Hidden": hidden, "Cell": cell}


@register_op("gru")
def _gru(ctx, ins, attrs):
    """dynamic GRU over [B,T,3H]; Weight [H,3H] laid out [u|r|c]."""
    x = ins["Input"][0]
    w = ins["Weight"][0]
    bias = ins["Bias"][0].reshape(-1) if "Bias" in ins and ins["Bias"] else None
    lens = _seq_lens_or_full(ctx, x, slot="Input")
    B, T, H3 = x.shape
    H = H3 // 3
    gate_act = _ACT[attrs.get("gate_activation", "sigmoid")]
    cand_act = _ACT[attrs.get("activation", "tanh")]
    w_ur = w[:, :2 * H]
    w_c = w[:, 2 * H:]
    h0 = ins["H0"][0] if "H0" in ins and ins["H0"] else jnp.zeros((B, H), x.dtype)
    is_reverse = attrs.get("is_reverse", False)
    if is_reverse:
        x = _masked_reverse(x, lens)
    xt_seq = jnp.swapaxes(x, 0, 1)
    step_mask = _mask(lens, T, x.dtype).T

    def step(h, inp):
        xt, mt = inp
        x_ur = xt[:, :2 * H]
        x_c = xt[:, 2 * H:]
        ur = x_ur + h @ w_ur
        if bias is not None:
            ur = ur + bias[:2 * H]
        u, r = jnp.split(gate_act(ur), 2, axis=-1)
        c = x_c + (r * h) @ w_c
        if bias is not None:
            c = c + bias[2 * H:]
        c = cand_act(c)
        h_new = u * h + (1.0 - u) * c
        mt = mt[:, None]
        h_new = mt * h_new + (1 - mt) * h
        return h_new, h_new * mt

    _, hs = lax.scan(step, h0, (xt_seq, step_mask))
    hidden = jnp.swapaxes(hs, 0, 1)
    if is_reverse:
        hidden = _masked_reverse(hidden, lens)
    if "Hidden" in ctx.op.outputs and ctx.op.outputs["Hidden"]:
        ctx.set_len(ctx.op.outputs["Hidden"][0], lens)
    return {"Hidden": hidden}


@register_op("lstm_unit")
def _lstm_unit(ctx, ins, attrs):
    """single LSTM step from pre-computed gates [B,4H] (lstm_unit_op)."""
    gates, c_prev = ins["X"][0], ins["C_prev"][0]
    forget_bias = attrs.get("forget_bias", 0.0)
    gi, gf, gc, go = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(gi)
    f = jax.nn.sigmoid(gf + forget_bias)
    o = jax.nn.sigmoid(go)
    c = f * c_prev + i * jnp.tanh(gc)
    h = o * jnp.tanh(c)
    return {"C": c, "H": h}


@register_op("gru_unit")
def _gru_unit(ctx, ins, attrs):
    """single GRU step (gru_unit_op): Input [B,3H], HiddenPrev [B,H],
    Weight [H,3H]."""
    x, h, w = ins["Input"][0], ins["HiddenPrev"][0], ins["Weight"][0]
    H = h.shape[-1]
    bias = ins["Bias"][0].reshape(-1) if "Bias" in ins and ins["Bias"] else None
    gate_act = _ACT[attrs.get("gate_activation", "sigmoid")]
    cand_act = _ACT[attrs.get("activation", "tanh")]
    ur = x[:, :2 * H] + h @ w[:, :2 * H]
    if bias is not None:
        ur = ur + bias[:2 * H]
    g = gate_act(ur)
    u, r = g[:, :H], g[:, H:]
    c = x[:, 2 * H:] + (r * h) @ w[:, 2 * H:]
    if bias is not None:
        c = c + bias[2 * H:]
    c = cand_act(c)
    h_new = u * h + (1.0 - u) * c
    return {"Hidden": h_new, "Gate": g, "ResetHiddenPrev": r * h}


@register_op("kmax_seq_score")
def _kmax_seq_score(ctx, ins, attrs):
    """KmaxSeqScoreLayer.cpp: indices of the top-k scores per sequence
    (padding positions masked out); -1 pads when a sequence is shorter
    than k."""
    x = ins["X"][0]                      # [B, T] or [B, T, 1]
    if x.ndim == 3:
        x = x[..., 0]
    k = int(attrs.get("beam_size", attrs.get("k", 1)))
    lens = _seq_lens_or_full(ctx, x)
    T = x.shape[1]
    neg = jnp.asarray(-3.4e38, x.dtype)
    masked = jnp.where(jnp.arange(T)[None, :] < lens[:, None], x, neg)
    k_eff = min(k, T)
    _, idx = jax.lax.top_k(masked, k_eff)
    valid = jnp.arange(k_eff)[None, :] < jnp.minimum(lens, k_eff)[:, None]
    out = jnp.where(valid, idx, -1)
    if k_eff < k:
        out = jnp.pad(out, ((0, 0), (0, k - k_eff)), constant_values=-1)
    return {"Out": out.astype(jnp.int64)}


# ---------------------------------------------------------------------------
# Static shape/dtype rules (analysis.shape_infer) over the padded+lengths
# representation — the InferShape analogs of sequence_*_op.cc and
# lstm_op.cc/gru_op.cc.
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import (ShapeError, VarInfo,  # noqa: E402
                                    conv_out_dim, dim_ok, first, same_as)
from ..core.registry import register_shape_fn  # noqa: E402

register_shape_fn("sequence_softmax", "sequence_slice", "sequence_unpad",
                  "lod_reset", "row_conv")(same_as("X"))


@register_shape_fn("sequence_pool")
def _sequence_pool_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None or len(x.shape) < 2:
        return {"Out": VarInfo(None, x.dtype)}
    # [B, T, ...] -> [B, ...]; the nested (lod-2) LAST/FIRST form drops two
    # dims, but lod levels are runtime metadata — stay at the common case
    # and let the declaration fill the gap when it disagrees in rank only.
    return {"Out": x.with_shape(x.shape[:1] + x.shape[2:])}


@register_shape_fn("sequence_expand", "sequence_expand_as")
def _sequence_expand_shape(op, ins, attrs):
    x, y = first(ins, "X"), first(ins, "Y")
    if x.shape is None:
        return {"Out": x}
    if y.shape is not None and len(y.shape) >= 2 and \
            len(x.shape) < len(y.shape):
        return {"Out": x.with_shape(x.shape[:1] + (y.shape[1],)
                                    + x.shape[1:])}
    return {"Out": x}


@register_shape_fn("sequence_concat")
def _sequence_concat_shape(op, ins, attrs):
    xs = [v for v in ins.get("X", []) if v is not None]
    known = [v for v in xs if v.shape is not None]
    if not known or len(known) != len(xs):
        return {"Out": VarInfo(None, xs[0].dtype if xs else None)}
    base = known[0]
    t = 0
    for v in known:
        if len(v.shape) != len(base.shape) or \
                not all(dim_ok(a, b) for a, b in
                        zip(v.shape[2:], base.shape[2:])):
            raise ShapeError(
                f"sequence_concat: feature dims differ: "
                f"{list(base.shape)} vs {list(v.shape)}")
        t = -1 if t < 0 or v.shape[1] < 0 else t + v.shape[1]
    return {"Out": base.with_shape(base.shape[:1] + (t,) + base.shape[2:])}


@register_shape_fn("sequence_context")
def _sequence_context_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    if len(x.shape) != 3:
        raise ShapeError(
            f"sequence_context: X must be [B, T, D], got {list(x.shape)}")
    b, t, d = x.shape
    ctx_len = attrs.get("contextLength", 3)
    return {"Out": x.with_shape((b, t, -1 if d < 0 else ctx_len * d))}


@register_shape_fn("sub_nested_seq")
def _sub_nested_seq_shape(op, ins, attrs):
    x, sel = first(ins, "X"), first(ins, "Selection")
    if x.shape is None or sel.shape is None:
        return {"Out": VarInfo(None, x.dtype)}
    k = sel.shape[1] if len(sel.shape) >= 2 else 1
    return {"Out": x.with_shape(x.shape[:1] + (k,) + x.shape[2:])}


@register_shape_fn("conv2d_dynamic_filter")
def _conv2d_dynamic_filter_shape(op, ins, attrs):
    x = first(ins, "Input")
    if x.shape is None:
        return {"Output": x}
    o, i, kh, kw = attrs["filter_shape"]
    strides = tuple(attrs.get("strides", [1, 1]))
    pads = tuple(attrs.get("paddings", [0, 0]))
    return {"Output": VarInfo(
        (x.shape[0], o, conv_out_dim(x.shape[2], kh, pads[0], strides[0]),
         conv_out_dim(x.shape[3], kw, pads[1], strides[1])), x.dtype)}


@register_shape_fn("sequence_conv")
def _sequence_conv_shape(op, ins, attrs):
    x, w = first(ins, "X"), first(ins, "Filter")
    if x.shape is None:
        return {"Out": x}
    if w.shape is not None and x.shape[-1] >= 0 and w.shape[0] >= 0:
        ctx_len = attrs.get("contextLength", 3)
        if w.shape[0] != ctx_len * x.shape[-1]:
            raise ShapeError(
                f"sequence_conv: Filter rows {w.shape[0]} != "
                f"contextLength {ctx_len} * D {x.shape[-1]}")
    m = w.shape[-1] if w.shape is not None else -1
    return {"Out": x.with_shape(x.shape[:-1] + (m,))}


@register_shape_fn("sequence_reverse")
def _sequence_reverse_shape(op, ins, attrs):
    x = first(ins, "X")
    out_slot = "Y" if op.outputs.get("Y") else "Out"
    return {out_slot: x}


@register_shape_fn("sequence_reshape")
def _sequence_reshape_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None:
        return {"Out": x}
    new_dim = attrs["new_dim"]
    b, t, d = x.shape
    if t >= 0 and d >= 0:
        if (t * d) % new_dim:
            raise ShapeError(
                f"sequence_reshape: T*D={t * d} not divisible by new_dim "
                f"{new_dim}")
        return {"Out": x.with_shape((b, t * d // new_dim, new_dim))}
    return {"Out": x.with_shape((b, -1, new_dim))}


@register_shape_fn("sequence_pad")
def _sequence_pad_shape(op, ins, attrs):
    x = first(ins, "X")
    b = x.shape[0] if x.shape is not None else -1
    return {"Out": x, "Length": VarInfo((b,), "int32")}


@register_shape_fn("max_sequence_len")
def _max_sequence_len_shape(op, ins, attrs):
    return {"Out": VarInfo((), "int64")}


@register_shape_fn("lstm")
def _lstm_shape(op, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Weight")
    if x.shape is None:
        return {"Hidden": x, "Cell": x}
    b, t, h4 = x.shape
    if h4 >= 0 and h4 % 4:
        raise ShapeError(f"lstm: input width {h4} is not 4*H")
    h = -1 if h4 < 0 else h4 // 4
    if w.shape is not None and h >= 0 and \
            (len(w.shape) != 2
             or not all(dim_ok(a, b)
                        for a, b in zip(w.shape, (h, h4)))):
        raise ShapeError(
            f"lstm: Weight {list(w.shape)} != [H, 4H] = [{h}, {h4}]")
    info = VarInfo((b, t, h), x.dtype)
    return {"Hidden": info, "Cell": info}


@register_shape_fn("gru")
def _gru_shape(op, ins, attrs):
    x, w = first(ins, "Input"), first(ins, "Weight")
    if x.shape is None:
        return {"Hidden": x}
    b, t, h3 = x.shape
    if h3 >= 0 and h3 % 3:
        raise ShapeError(f"gru: input width {h3} is not 3*H")
    h = -1 if h3 < 0 else h3 // 3
    if w.shape is not None and h >= 0 and \
            (len(w.shape) != 2
             or not all(dim_ok(a, b)
                        for a, b in zip(w.shape, (h, h3)))):
        raise ShapeError(
            f"gru: Weight {list(w.shape)} != [H, 3H] = [{h}, {h3}]")
    return {"Hidden": VarInfo((b, t, h), x.dtype)}


@register_shape_fn("lstm_unit")
def _lstm_unit_shape(op, ins, attrs):
    gates, c_prev = first(ins, "X"), first(ins, "C_prev")
    if gates.shape is not None and c_prev.shape is not None and \
            gates.shape[-1] >= 0 and c_prev.shape[-1] >= 0 and \
            gates.shape[-1] != 4 * c_prev.shape[-1]:
        raise ShapeError(
            f"lstm_unit: gates width {gates.shape[-1]} != 4 * H "
            f"{c_prev.shape[-1]}")
    return {"C": c_prev, "H": c_prev}


@register_shape_fn("gru_unit")
def _gru_unit_shape(op, ins, attrs):
    x, h = first(ins, "Input"), first(ins, "HiddenPrev")
    res = {"Hidden": h}
    if h.shape is not None and h.shape[-1] >= 0:
        res["Gate"] = h.with_shape(h.shape[:-1] + (2 * h.shape[-1],))
        res["ResetHiddenPrev"] = h
    return res


@register_shape_fn("kmax_seq_score")
def _kmax_seq_score_shape(op, ins, attrs):
    x = first(ins, "X")
    b = x.shape[0] if x.shape is not None else -1
    k = int(attrs.get("beam_size", attrs.get("k", 1)))
    return {"Out": VarInfo((b, k), "int64")}


# ---------------------------------------------------------------------------
# Sharding-propagation rules (analysis.shard_prop).  Recurrences keep the
# batch sharding; the lstm/gru gate dim follows the Weight's column split
# (the Megatron gate-parallel pattern — the col-split input projection and
# the recurrent weight shard the same axis).
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import (first_in, merge_entry,  # noqa: E402
                                   shard_noop, shard_same_as)
from ..core.registry import register_shard_fn  # noqa: E402

register_shard_fn("sequence_softmax")(shard_same_as("X"))
register_shard_fn("sequence_reverse")(shard_same_as("X", out="Y"))
register_shard_fn("sequence_unpad", "sequence_pad")(shard_noop())


@register_shard_fn("sequence_pool")
def _sequence_pool_shard(op, ins, attrs):
    x = first_in(ins, "X")
    if x.spec is None:
        return {}
    # [B, T, D] -> [B, D]: the time dim drops
    return {"Out": (x.entry(0),) + tuple(x.spec[2:])}


@register_shard_fn("lstm")
def _lstm_shard(op, ins, attrs):
    x, w = first_in(ins, "Input"), first_in(ins, "Weight")
    if x.spec is None and w.spec is None:
        return {}
    h_entry = merge_entry(x.entry(2), w.entry(1), "lstm gate dim")
    info = ((x.entry(0), x.entry(1), h_entry))
    return {"Hidden": info, "Cell": info}


@register_shard_fn("gru")
def _gru_shard(op, ins, attrs):
    x, w = first_in(ins, "Input"), first_in(ins, "Weight")
    if x.spec is None and w.spec is None:
        return {}
    h_entry = merge_entry(x.entry(2), w.entry(1), "gru gate dim")
    return {"Hidden": (x.entry(0), x.entry(1), h_entry)}
