"""granite-4.0-h-micro (IBM; ``model_type: granitemoehybrid``, no experts) cut
to one chip as stage 0 of a four-stage pipeline: the program under test
(``models.granite_hybrid_loss`` through the public layers API, Adam) and its
plain float32 reference.

``reference`` is written from the model's equations and shares no code with
``paddle_tpu``.  rms(x; g) = x * rsqrt(mean(x^2, -1) + eps) * g over the
last axis, eps 1e-5; no bias anywhere except the filter's.  For x [B, T,
2048]:

    x0     = 12 * Emb[ids]                              (embedding_multiplier)
    layer: h   = x + 0.22 * mixer_i(rms(x; g1))         (residual_multiplier)
           out = h + 0.22 * ffn(rms(h; g2))
    ffn(m):    [a | b] = split(m W_in, 2) (2 x 8192);  (silu(a) * b) W_out
    attention: q = n Wq (32 heads of 64), k = n Wk, v = n Wv (8 of 64); NO
           positional encoding; query head h reads K / V head h // 4; causal
           softmax of (q . k) * 0.015625 (attention_multiplier, NOT 1/8);
           mixer = o Wo
    mamba: [z | xBC | dt] = split(n W_in; 4096, 4352, 64)
           xBC = silu(c + b_conv),
               c[t] = sum_{j<4} w[:, j] * xBC_in[t - 3 + j]
           [u | Bm | Cm] = split(xBC; 4096, 128, 128); u as 64 heads of 64
           (P); Bm, Cm [T, 128] (N) shared by all heads (mamba_n_groups 1)
           delta[t, h] = softplus(dt[t, h] + dt_bias[h])
           A[h] = -exp(A_log[h])
           S[h, t] = exp(delta[t, h] A[h]) S[h, t-1]
                     + delta[t, h] * u[t, h, :] (x) Bm[t, :]     (S[-1] = 0)
           y[t, h, :] = S[h, t] Cm[t, :] + D[h] * u[t, h, :]
           g = y * silu(z)
           mixer = (g * rsqrt(mean(g^2 over all 4096) + eps) * g_norm) W_out
    logits = rms(x_L; g_f) Emb^T / 8  (logits_scaling; tied)
    loss   = mean_t -log softmax(logits)[label]

**The recurrence is a scan over POSITIONS**, one state update a position
(``_recurrence``): it shares neither code nor algorithm with the program's
chunked ``ssd_scan``.

**The cut.**  Published layers 0-9 of 40 (one whole period: mamba x5,
attention, mamba x4) and rows 0-12 543 of the tied table's 100 352; ids,
labels, logits and loss are over the slice.

Departures from a training recipe, all in the configuration's ``assumed``:
float32 for bfloat16 mixed precision, plain Adam, uniform random tokens and
labels, ten layers of 40, vectors set by the startup program.  Departures of
THIS code from the plainest form, to fit beside the program's live state
(12.35 GB of the chip's 16.9) and none changing a value: the chain rule is
applied LAYER BY LAYER (forward keeping each layer's input, then each
layer's ``jax.vjp`` in turn, last to first), so that the device holds one
layer's weights at a time where ``jax.grad`` of the whole loss would hold
all 3.1 GB and their gradients; the scan over positions is cut into blocks
of 64 positions under ``jax.checkpoint`` (its backward would else keep a
[64, 64, 128] state for each of 4096 positions, 8.6 GB); attention runs
query head by query head and the loss in blocks of rows; gradients are kept
for ``check_params`` only.
"""
from __future__ import annotations

import math

PREFIX = "granite"

# What the training step is held to, on ONE seeded sequence at the seeded
# weights (relative errors: |loss - ref| / |ref|, ||g - ref||_2 / ||ref||_2).
# The program's products run at the TPU's default precision (one bfloat16
# pass, float32 accumulation) on float32 weights and activations, the
# reference's at 'highest'.  Nothing here is discrete, so the check is
# ``drivers/train_scan.py``'s own; the cell runs through
# ``drivers/train_scan_fresh_start.py`` (9.3 GB of state cannot stand twice
# on the chip while the startup program runs again), which also hands
# ``reference`` a control that the check has to FAIL, through the SAME
# comparison (``--set control=...``).
#
# Measured on the chip (PR 38, PERF.md section 6; every run its own seed), in
# the order of ``grad_rel_tol`` below, in percent.
# SOUND runs (14: eight traced, six not): loss 2.0e-7 to 1.4e-6; table
# 1.30-1.40, in_proj 1.36-1.44, filter 1.34-1.45, out_proj 1.36-1.44, wq / wk
# 1.54-1.67, layer 9's feed-forward 1.48-1.60 (an aggregate of rounding over
# millions of elements: they hardly move from seed to seed); the three
# 64-vectors of layer 0, whose gradients ARE the scan's backward, move more:
# A_log 1.22-1.98, dt_bias 0.94-2.14, D 1.25-1.81.
# THE NEAREST PRECISION BELOW, bfloat16, read two ways, one seed each:
# (a) {'lower': 'all'}: the reference computed in bfloat16 throughout
#     (weights, every activation, the state of the recurrence; products
#     accumulate in float32): loss 2.0e-4; table 2.55, in_proj 2.62, filter
#     2.60, A_log 63.2, dt_bias 16.5, D 3.16, out_proj 2.62, wq / wk 2.95 /
#     2.97, feed-forward 2.92 / 2.88;
# (b) the PROGRAM itself under ``Executor(amp=True)`` (``compute_dtype``
#     bfloat16: its own path of that precision) against the float32
#     reference: loss 1.1e-4; table 2.30, in_proj 2.35, filter 2.34, A_log
#     3.32, dt_bias 2.18, D 2.36, out_proj 2.36, wq / wk 2.58, feed-forward
#     2.59 / 2.56 (and its losses do not fall: Adam under amp, PERF.md PR 22).
# Both NOT correct by the limits below, (a) through the loss, the eight
# matrices, A_log and dt_bias, (b) through the loss and the eight matrices.  The limits this
# cell first had (3.5-5 %, loss 1.5e-4) passed (b) outright and (a) in every
# matrix: they could not tell float32 from bfloat16 (REVIEW.md, PR 38).
# {'lower': 'weights'}, every matrix and the table of the reference rounded
# to bfloat16 and nothing else, reads 1.18-1.54, INSIDE the sound runs, and
# `correct`: the program's one-pass products round the same operands, so this
# is the program's own stated precision and no lower one.
# FAULTS, one seed each: the state not passed from chunk to chunk moves
# A_log to 21.8 and dt_bias to 4.6 and nothing else (most heads forget
# within a chunk; A_log's gradient is the slow heads'); D left out 106 to
# 5 600 and D itself infinite; the scale 1/8 for 1/64 wq / wk 95, the others
# 6.0-8.0; the residual multiplier left out 71-110 (a state left unchanged
# reads 100).  All four NOT correct.
# LIMITS.  The eight matrices, between the sound runs' largest reading and
# the NEARER lower-precision reading, (b)'s, at their geometric mean: table
# 1.8 (1.29 times 1.40; 0.78 of 2.30), in_proj / filter / out_proj 1.85 (1.28
# times 1.45; 0.79 of 2.34), wq / wk 2.1 (1.26 times 1.67; 0.81 of 2.58),
# feed-forward 2.05 (1.28 times 1.60; 0.80 of 2.56); the sound readings lie
# within 4 % of their mean, so a quarter more is room.  The three vectors
# swing by a factor of two between seeds and (b) lies inside that swing, so
# no limit can part (b) there; they are held against (a) and the faults:
# A_log 5 (2.5 times 1.98; 0.23 of the state fault's 21.8, 0.08 of (a)'s
# 63.2), dt_bias 4.5 (2.1 times 2.14; 0.27 of (a)'s 16.5; the state fault's
# 4.6 is held by A_log), D 4 (2.2 times 1.81; 0.6 of the scale fault's 6.8;
# (a)'s 3.16 passes it).  Loss 2e-5: 14 times the largest sound reading
# (1.4e-6), a fifth of (b)'s 1.1e-4, a tenth of (a)'s 2.0e-4 (the harness's
# accepted decoder cells hold 1.5e-4, which is ABOVE (b)).
CHECKS = (
    {"name": "train", "is_test": False, "loss_rel_tol": 2e-5,
     "grad_rel_tol": {"granite.embed": 0.018, "granite.l0.in_proj": 0.0185,
                      "granite.l0.conv": 0.0185, "granite.l0.A_log": 0.05,
                      "granite.l0.dt_bias": 0.045, "granite.l0.D": 0.04,
                      "granite.l0.out_proj": 0.0185, "granite.l5.wq": 0.021,
                      "granite.l5.wk": 0.021, "granite.l9.ffn_in": 0.0205,
                      "granite.l9.ffn_out": 0.0205}},
)


def _layers_run(sizes):
    """The mixer of each layer this chip runs: the published ``layer_types``
    (whole in the file) at ``layers_run``."""
    picked = sizes["layers_run"]
    if len(picked) != sizes["num_hidden_layers"]:
        raise ValueError("granite_4_0_h_micro: layers_run does not name "
                         "num_hidden_layers layers")
    return [sizes["layer_types"][at] for at in picked]


def build(mode, batch, sizes):
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    if mode != "train":
        raise ValueError("granite_4_0_h_micro: only 'train' is built "
                         "(serving waits for the recurrent and convolution "
                         "state beside the decode cache, ROADMAP B5)")
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    vocab, t_len = sizes["vocab_size"], sizes["seq_len"]
    ids = layers.data("ids", shape=[t_len], dtype="int64")
    lbl = layers.data("lbl", shape=[t_len], dtype="int64")
    loss = models.granite_hybrid_loss(
        ids, lbl, vocab, _layers_run(sizes),
        hidden_size=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        ffn_size=sizes["shared_intermediate_size"],
        mamba_heads=sizes["mamba_n_heads"],
        mamba_head_dim=sizes["mamba_d_head"],
        mamba_state=sizes["mamba_d_state"],
        mamba_groups=sizes["mamba_n_groups"],
        conv_taps=sizes["mamba_d_conv"], chunk=sizes["mamba_chunk_size"],
        norm_eps=sizes["rms_norm_eps"],
        embedding_multiplier=sizes["embedding_multiplier"],
        residual_multiplier=sizes["residual_multiplier"],
        attention_multiplier=sizes["attention_multiplier"],
        logits_scaling=sizes["logits_scaling"],
        time_step_min=sizes["time_step_min"],
        time_step_max=sizes["time_step_max"],
        recompute=sizes.get("recompute", False), prefix=PREFIX)
    pt.optimizer.Adam(sizes["optimizer"]["learning_rate"]).minimize(loss)
    feeds = {"ids": {"shape": [t_len], "dtype": "int64", "high": vocab},
             "lbl": {"shape": [t_len], "dtype": "int64", "high": vocab}}
    return {"main": pt.default_main_program(),
            "startup": pt.default_startup_program(),
            "feeds": feeds, "loss": loss.name,
            "amp": sizes["compute_dtype"] == "bfloat16",
            "items_per_example": t_len}


# ---------------------------------------------------------------------------
# operations and bytes, from the sizes
# ---------------------------------------------------------------------------
def _count(sizes, kind):
    return _layers_run(sizes).count(kind)


def _mamba_widths(sizes):
    """(inner = heads x head features, Bm + Cm features, heads)."""
    heads = sizes["mamba_n_heads"]
    return (heads * sizes["mamba_d_head"],
            2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"], heads)


def _ssd_macs(sizes):
    """Multiply-accumulates of ``ssd_scan``'s own products for one token of
    one layer, forward, in the chunked form at ``mamba_chunk_size`` Q: the
    chunk's scores Cm Bm^T once a group (Q N), and a head's Q P for the
    product inside the chunk, N P for the chunk's state and N P for what the
    earlier chunks hand on."""
    q, n, p = (sizes["mamba_chunk_size"], sizes["mamba_d_state"],
               sizes["mamba_d_head"])
    return sizes["mamba_n_groups"] * q * n \
        + sizes["mamba_n_heads"] * (q * p + 2 * n * p)


def flops_per_item(sizes, mode):
    """FLOPs the mathematics needs per token ON THIS CHIP, 2 per
    multiply-accumulate of every matrix product; training = 3x forward.  A
    ``mamba`` mixer: its two projections and ``ssd_scan``'s own products
    (``_ssd_macs``); the ``attention`` mixer: four projections (K and V at
    the 8 heads they have), causal scores and context at T/2 keys a query;
    the feed-forward of every layer; the head over the slice.  What a
    backward pass computes again is not counted (every layer is recomputed:
    a third more is executed).  The filter's taps, the gates, look-ups,
    norms, softmax and Adam are not counted."""
    d, t = sizes["hidden_size"], sizes["seq_len"]
    inner, bc, heads = _mamba_widths(sizes)
    kv = d * sizes["num_key_value_heads"] // sizes["num_attention_heads"]
    macs = (_count(sizes, "mamba") * (
                d * (2 * inner + bc + heads) + inner * d + _ssd_macs(sizes))
            + _count(sizes, "attention") * (
                2 * d * d + 2 * d * kv + 2 * (t / 2) * d)
            + sizes["num_hidden_layers"] * 3 * d
            * sizes["shared_intermediate_size"]
            + d * sizes["vocab_size"])
    return 2.0 * macs * (3 if mode == "train" else 1)


def short_conv_work(sizes, tokens):
    """(FLOPs, bytes) the UNGATED short convolutions of all the ``mamba``
    layers run need in a training step on ``tokens`` tokens, in float32, C =
    4352 channels of 4 taps.  Forward: X [N, C] read, Out [N, C] written.
    Backward: X and the cotangent read, dX written (the filter's and the
    bias's own gradients are [C, 4] and [C]: nothing).  A multiply-add a tap
    and about a dozen operations for SiLU an element, three times over for
    the backward.  The bytes bound it."""
    inner, bc, _ = _mamba_widths(sizes)
    c, n = inner + bc, tokens
    flops = 3 * (2.0 * sizes["mamba_d_conv"] + 12.0) * n * c
    bytes_ = 4.0 * n * c * (2 + 3)
    layers_ = _count(sizes, "mamba")
    return layers_ * flops, layers_ * bytes_


def ssd_scan_work(sizes, tokens):
    """(FLOPs, bytes) ``ssd_scan`` of all the ``mamba`` layers run needs in a
    training step on ``tokens`` tokens, in float32.  Forward: u [N, H P],
    delta [N, H], Bm and Cm [N, G N_state] read and y [N, H P] written, once.
    Backward: those four and the cotangent read, their four gradients
    written (A's and D's are [H]: nothing).  The FLOPs are the chunked
    form's own products (``_ssd_macs``), three times over.  What a
    recomputed layer executes again is not counted.  The bytes bound it."""
    inner, bc, heads = _mamba_widths(sizes)
    operands = inner + heads + bc
    bytes_ = 4.0 * tokens * ((operands + inner) + (operands + inner)
                             + operands)
    flops = 3 * 2.0 * _ssd_macs(sizes) * tokens
    layers_ = _count(sizes, "mamba")
    return layers_ * flops, layers_ * bytes_


def grouped_attention_work(sizes, sequences):
    """(FLOPs, bytes) causal grouped-query attention of the ``attention``
    layers run needs in a training step on ``sequences`` sequences: six
    products over half the T x T square at the 32 query heads (what a fused
    kernel recomputes is not counted); q, o, do, dq moved at 32 heads (q, o
    forward; q, o, do, dq backward) and k, v, dk, dv at the 8 they have
    (k, v forward; k, v, dk, dv backward), float32."""
    t, d = sizes["seq_len"], sizes["hidden_size"]
    kv = d * sizes["num_key_value_heads"] // sizes["num_attention_heads"]
    layers_ = _count(sizes, "attention")
    return (layers_ * 6 * 2.0 * sequences * (t * t / 2) * d,
            layers_ * 4.0 * sequences * t * (6 * d + 6 * kv))


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
MIXER_WEIGHTS = {
    "mamba": ("in_proj", "conv", "conv_bias", "dt_bias", "A_log", "D",
              "gate_norm", "out_proj"),
    "attention": ("wq", "wk", "wv", "wo")}
LAYER_WEIGHTS = ("mixer_norm", "ffn_norm", "ffn_in", "ffn_out")


def _rms(x, g, eps):
    import jax.numpy as jnp
    from jax import lax

    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _filter(x, w, bias):
    """silu(c + bias) for x [B, T, C], w [C, L]: tap j reads x[t - (L-1) + j],
    zeros before position 0."""
    import jax
    import jax.numpy as jnp

    taps, t_len = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j] * padded[:, j:j + t_len]
                           for j in range(taps)) + bias)


def _recurrence(u, delta, a, bm, cm, d, reset_every=None):
    """y [B, T, H, P] of S[t] = exp(delta[t] a) S[t-1] + delta[t] u[t] (x)
    bm[t], y[t] = S[t] cm[t] + d u[t], S[-1] = 0, ONE position a step; u
    [B, T, H, P], delta [B, T, H], a and d [H], bm and cm [B, T, G, N], head
    h reading group h // (H / G).  Blocks of positions are recomputed in the
    backward pass (``jax.checkpoint``): no value changes.  ``reset_every``
    (a fault, for a control): the state is dropped every so many
    positions."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t_len, heads, p = u.shape
    groups, n = bm.shape[2], bm.shape[3]
    per = heads // groups
    a, d = a.reshape(groups, per), d.reshape(groups, per)
    kept = jnp.ones((t_len,), u.dtype) if reset_every is None else (
        jnp.arange(t_len) % reset_every != 0).astype(u.dtype)

    def position(state, at):                     # state [B, G, per, P, N]
        ut, dt, bt, ct, keep = at
        state = (keep * jnp.exp(dt * a))[..., None, None] * state \
            + (dt[..., None] * ut)[..., None] * bt[:, :, None, None, :]
        return state, jnp.sum(state * ct[:, :, None, None, :], axis=-1) \
            + d[..., None] * ut

    block = math.gcd(t_len, 64)

    def first(x):                      # [B, T, ...] -> [T/b, b, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((t_len // block, block) + x.shape[1:])

    _, y = lax.scan(
        jax.checkpoint(lambda state, rows: lax.scan(position, state, rows)),
        jnp.zeros((b, groups, per, p, n), u.dtype),
        (first(u.reshape(b, t_len, groups, per, p)),
         first(delta.reshape(b, t_len, groups, per)), first(bm), first(cm),
         kept.reshape(t_len // block, block)))
    return jnp.moveaxis(y.reshape(t_len, b, heads, p), 0, 1)


def _attention(q, k, v, scale):
    """Causal softmax attention of q [B, T, H, d] over k, v [B, T, H_kv, d]
    at ``scale``, query head h reading K / V head h // (H / H_kv), one query
    head at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    t_len, heads = q.shape[1], q.shape[2]
    group = heads // k.shape[2]
    mask = jnp.tril(jnp.ones((t_len, t_len), bool))
    k_first, v_first = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)

    @jax.checkpoint
    def one_head(args):
        qh, h = args                                         # [B, T, d]
        kh, vh = k_first[h // group], v_first[h // group]
        s = jnp.einsum("btd,bsd->bts", qh, kh) * scale
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", p, vh)

    out = lax.map(one_head, (jnp.moveaxis(q, 2, 0), jnp.arange(heads)))
    return jnp.moveaxis(out, 0, 2)


def _mamba(n, w, sizes, fault):
    import jax
    import jax.numpy as jnp

    b, t_len, _ = n.shape
    inner, bc, heads = _mamba_widths(sizes)
    groups, state = sizes["mamba_n_groups"], sizes["mamba_d_state"]
    zxd = n @ w["in_proj"]
    z, dt = zxd[..., :inner], zxd[..., 2 * inner + bc:]
    xbc = _filter(zxd[..., inner:2 * inner + bc], w["conv"], w["conv_bias"])
    u = xbc[..., :inner].reshape(b, t_len, heads, sizes["mamba_d_head"])
    bm = xbc[..., inner:inner + bc // 2].reshape(b, t_len, groups, state)
    cm = xbc[..., inner + bc // 2:].reshape(b, t_len, groups, state)
    y = _recurrence(
        u, jax.nn.softplus(dt + w["dt_bias"]), -jnp.exp(w["A_log"]), bm, cm,
        jnp.zeros_like(w["D"]) if fault == "no_d" else w["D"],
        sizes["mamba_chunk_size"] if fault == "chunk_reset" else None)
    g = y.reshape(b, t_len, inner) * jax.nn.silu(z)
    return _rms(g, w["gate_norm"], sizes["rms_norm_eps"]) @ w["out_proj"]


def _layer(x, w, kind, sizes, fault=None):
    """One layer's output for x [B, T, D]; ``w`` the layer's weights by
    their short names."""
    import jax

    eps, r = sizes["rms_norm_eps"], sizes["residual_multiplier"]
    b, t_len, d = x.shape
    n = _rms(x, w["mixer_norm"], eps)
    if kind == "mamba":
        o = _mamba(n, w, sizes, fault)
    else:
        heads, kv_heads = (sizes["num_attention_heads"],
                           sizes["num_key_value_heads"])
        dh = d // heads
        o = _attention((n @ w["wq"]).reshape(b, t_len, heads, dh),
                       (n @ w["wk"]).reshape(b, t_len, kv_heads, dh),
                       (n @ w["wv"]).reshape(b, t_len, kv_heads, dh),
                       sizes["attention_multiplier"])
        o = o.reshape(b, t_len, d) @ w["wo"]
    h = x + r * o
    both = _rms(h, w["ffn_norm"], eps) @ w["ffn_in"]
    half = both.shape[-1] // 2
    return h + r * ((jax.nn.silu(both[..., :half]) * both[..., half:])
                    @ w["ffn_out"])


def _head_loss(x, table, final_norm, labels, sizes, rows=512):
    """mean_t -log softmax(rms(x_t) table^T / logits_scaling)[label_t] for x
    [B, T, D], in blocks of ``rows`` rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    x = _rms(x, final_norm, sizes["rms_norm_eps"]).reshape(-1, x.shape[-1])
    labels = labels.reshape(-1)
    n = x.shape[0]
    rows = math.gcd(rows, n)

    @jax.checkpoint
    def block(xl):
        xb, lb = xl
        logp = jax.nn.log_softmax(
            xb @ table.T / sizes["logits_scaling"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    return jnp.sum(lax.map(block, (x.reshape(n // rows, rows, -1),
                                   labels.reshape(n // rows, rows)))) / n


def _layer_names(kind):
    return LAYER_WEIGHTS + MIXER_WEIGHTS[kind]


def _parameter_names(sizes):
    return [f"{PREFIX}.embed", f"{PREFIX}.final_norm"] + [
        f"{PREFIX}.l{i}.{n}" for i, kind in enumerate(_layers_run(sizes))
        for n in _layer_names(kind)]


def _assumed_vectors(sizes):
    """{short name: the values the startup program is to give it}."""
    import numpy as np

    heads = sizes["mamba_n_heads"]
    step = np.exp(np.linspace(math.log(sizes["time_step_min"]),
                              math.log(sizes["time_step_max"]), heads))
    return {"A_log": np.log(np.arange(1, heads + 1)),
            "dt_bias": step + np.log(-np.expm1(-step)),
            "D": np.ones(heads), "conv_bias": np.zeros(
                sum(_mamba_widths(sizes)[:2]))}


def reference(mode, params, feeds, sizes, frozen_stats=False, control=None):
    """'loss': the training loss, forward only.  'train': (loss, {name:
    gradient} for ``sizes['check_params']``).  float32 throughout, matmul
    precision 'highest' (``frozen_stats`` changes nothing: there are no
    batch statistics).  ``params`` are host arrays (they also hold the
    optimizer's moments); a layer's weights are on the device while that
    layer runs, forward or backward, and no longer.

    The vectors that the startup program sets and the harness does not draw
    (``A_log``, ``dt_bias``, ``D``, the filter's bias) are held to what the
    configuration's ``assumed`` says of them.

    ``control`` (``drivers/train_scan_fresh_start.py``; never in a measured
    run) makes this a control that the check has to FAIL: {'lower': 'all'}
    computes everything here in bfloat16, weights, activations and the
    recurrence's state (the nearest precision below the float32 the
    configuration states); {'lower': 'weights'} only rounds every matrix and
    the table to bfloat16's 8 bits of mantissa (the operands of the program's
    own one-pass products: it does NOT fail, ``CHECKS``);
    {'fault': 'chunk_reset'} drops the state every ``mamba_chunk_size``
    positions (the state not passed from chunk to chunk), {'fault': 'no_d'}
    leaves D out, {'sizes': {...}} is laid over ``sizes`` (another attention
    scale, another residual multiplier)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    if mode not in ("train", "loss"):
        raise ValueError("granite_4_0_h_micro: only training has a "
                         "reference")
    control = control or {}
    kinds = _layers_run(sizes)
    assumed = _assumed_vectors(sizes)
    for i in (i for i, kind in enumerate(kinds) if kind == "mamba"):
        for short, want in assumed.items():
            got = np.asarray(params[f"{PREFIX}.l{i}.{short}"])
            if got.shape != want.shape or not np.allclose(got, want,
                                                          rtol=1e-5):
                raise ValueError(
                    f"{PREFIX}.l{i}.{short} is not what the startup "
                    f"program is to set it to: {got[:4]} for {want[:4]}")
    sizes = {**sizes, **control.get("sizes", {})}
    fault = control.get("fault")

    lower = control.get("lower")
    if lower not in (None, "weights", "all"):
        raise ValueError(f"granite_4_0_h_micro: no control lower={lower!r}")
    dtype = jnp.bfloat16 if lower == "all" else jnp.float32

    def put(name):
        value = jnp.asarray(params[name], jnp.float32)
        if lower == "weights" and value.ndim > 1:
            # (reduce_precision: XLA drops a convert there and back)
            value = lax.reduce_precision(value, 8, 7)
        return value.astype(dtype)

    def weights(i):
        return {n: put(f"{PREFIX}.l{i}.{n}") for n in _layer_names(kinds[i])}

    ids, labels = jnp.asarray(feeds["ids"]), jnp.asarray(feeds["lbl"])
    table, final_norm = put(f"{PREFIX}.embed"), put(f"{PREFIX}.final_norm")
    scale = sizes["embedding_multiplier"]
    run = {kind: jax.jit(lambda x, w, kind=kind:
                         _layer(x, w, kind, sizes, fault))
           for kind in set(kinds)}
    back = {kind: jax.jit(lambda x, w, ct, kind=kind: jax.vjp(
        lambda x, w: _layer(x, w, kind, sizes, fault), x, w)[1](ct))
        for kind in set(kinds)}
    def head(x, table, g):
        return _head_loss(x, table, g, labels, sizes)

    with jax.default_matmul_precision("highest"):
        x, inputs = scale * table[ids], []
        for i, kind in enumerate(kinds):
            inputs.append(x)
            x = run[kind](x, weights(i))
        if mode == "loss":
            return jax.jit(head)(x, table, final_norm)
        loss, (ct, d_table, d_final) = jax.jit(jax.value_and_grad(
            head, argnums=(0, 1, 2)))(x, table, final_norm)
        held = set(sizes["check_params"])
        grads = {f"{PREFIX}.final_norm": d_final} \
            if f"{PREFIX}.final_norm" in held else {}
        for i in reversed(range(len(kinds))):
            ct, d_w = back[kinds[i]](inputs.pop(), weights(i), ct)
            grads.update({f"{PREFIX}.l{i}.{n}": g for n, g in d_w.items()
                          if f"{PREFIX}.l{i}.{n}" in held})
        if f"{PREFIX}.embed" in held:
            # the tied table: the head's gradient and the look-up's
            grads[f"{PREFIX}.embed"] = d_table.at[ids.reshape(-1)].add(
                scale * ct.reshape(-1, ct.shape[-1]))
    return loss.astype(jnp.float32), {
        n: g.astype(jnp.float32) for n, g in grads.items()}
