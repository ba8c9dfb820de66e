"""Kimi-Linear-48B-A3B (``model_type: kimi_linear``) cut to one chip as rank
0 of thirty-two that share each layer: the program under test
(``models.kimi_linear_loss`` through the public layers API, Adam) and its
plain float32 reference.

``reference`` is written from the model's equations and shares no code with
``paddle_tpu``.  rms(x; g) = x * rsqrt(mean(x^2, -1) + eps) * g over the
last axis, eps 1e-5; unit(x) = x / max(||x||, 1e-6) over the last axis.
Every layer is ``x = x + mixer_i(rms(x; g_i))`` then ``x = x + ffn_i(rms(x;
h_i))``, the mixer picked by ``linear_attn_config``.  For x [B, T, 2304]:

    K  [q | k | v] = silu(conv(n W_qkv)), c[t] = sum_{j<4} w[:, j] * x[t-3+j]
       (no bias; 32 heads of 128 each); q, k = unit(q), unit(k) a head
       g = -exp(A_log[h]) * softplus(n W_fd W_fu + dt_bias)   (<= 0, [4096])
       beta = sigmoid(n W_b)                                  ([32])
       S[t] = (I - beta[t] k[t] k[t]^T) Diag(exp(g[t])) S[t-1]
              + beta[t] k[t] v[t]^T                 (S[-1] = 0, [128, 128])
       o[t] = S[t]^T q[t] / sqrt(128)
       mixer = (rms(o; g_o) a head * sigmoid(n W_gd W_gu + b_g)) W_o
    A  q = n W_q (32 heads of 192 = 128 + 64); [c | k_pe] = n W_kva
       [k_nope | v] = rms(c; g_c) W_kvb (32 heads of 128 + 128)
       k = [k_nope | k_pe] (k_pe's 64 features the same for every head, NOT
       rotated); causal softmax of (q . k) / sqrt(192) over v; mixer = o W_o
    ffn, layer 0: (silu(n W_gate) * n W_up) W_down             (9216)
    ffn, the others: s = sigmoid(n W_r)                (256 outputs, float32)
       chosen = the 8 largest of s + b   (b: no gradient, choice only)
       w = s[chosen] / (sum s[chosen] + 1e-6) * 2.446
       ffn = sum over the chosen e THAT THIS CHIP HOLDS of
             w_e * (silu(n Wg[e]) * n Wu[e]) Wd[e]              (1024)
           + (silu(n Sg) * n Su) Sd                             (shared, 1024)
    logits = rms(x_L; g_f) W_head  (untied);
    loss   = mean_t -log softmax(logits)[label]

**The recurrence is a scan over POSITIONS**, one state update a position
(``_delta_recurrence``): it shares neither code nor algorithm with the
program's chunked ``gated_delta_scan`` (no running sums, no triangular
solve).

**The share.**  This chip holds experts ``expert_parallel_rank * 8`` to + 7
of the router's 256 (``router_width``), the shared expert whole (every chip
of the deployment computes it on its own tokens), and 20 480 of each table's
163 840 rows.  Scores, choice and renormalisation run over all 256; what the
248 absent experts would add is left out here and in the program alike, and
that partial result goes on to the next layer (the guide's section 4).
Published layers 1-5, ``KKKAK``.

Departures from a training recipe, all in the configuration's ``assumed``:
float32 for bfloat16 mixed precision, plain Adam, uniform random tokens and
labels, five layers of 27, a fixed correction bias, vectors set by the
startup program.  Departures of THIS code from the plainest form, to fit
beside the program's live state (7.2 GB of the chip's 16.9) and none
changing a value: the chain rule is applied LAYER BY LAYER (forward keeping
each layer's input, then each layer's ``jax.vjp`` in turn, last to first),
so that the device holds one layer's weights at a time; the scan over
positions is cut into blocks of 64 positions under ``jax.checkpoint``;
attention runs query head by query head; the held experts are a sequential
loop, each applied to every token under its weight (0 where not chosen);
the head and the loss run in blocks of rows; gradients are kept for
``check_params`` only.
"""
from __future__ import annotations

import math

PREFIX = "kimi"

# What the training step is held to, on ONE seeded 8192-token sequence at the
# seeded weights (relative errors: |loss - ref| / |ref|, ||g - ref||_2 /
# ||ref||_2).  The program's products run at the TPU's default precision
# (one bfloat16 pass, float32 accumulation; the router's at HIGHEST) on
# float32 weights and activations, the reference's at 'highest'.  The choice
# of 8 of 256 is discontinuous, so the cell's check shows ``reference`` what
# the program's routers read (``build``'s ``check_fetches``; LFM2's hazard,
# ``configs/lfm2_8b_a1b.py``) and ``reference`` makes the CHOICE from it
# with its own router and bias; everything continuous stays its own, and
# ``router_input_rel_tol`` holds how far its own router inputs lie from the
# ones shown.
#
# Measured on the chip (PERF.md section 6; every run its own seed),
# in percent.  SOUND runs, eleven: every matrix 2.80-3.03 (an aggregate of
# one bfloat16 pass a product over the five layers: the same to 0.2 from
# seed to seed), A_log 1.87-4.31 (a 32-vector, each entry a sum over every
# position and channel of its head: it swings), dt_bias 2.84-2.97, the MLA
# layer's q_proj 3.20-3.33 and kv_b 2.53-2.69, router 2.97-3.40; router
# inputs 1.99-2.06 (0.98 at the first expert layer, +0.35 a layer after
# it: each KDA layer adds more than Nemotron's Mamba-2 layers, 0.41 at its
# first); loss 2.8e-6 to 2.7e-5.  Four of them solved each chunk's
# triangle by XLA's triangular_solve, seven (the tree as it is) by the
# blocked inverse: the same readings.
# THE NEAREST PRECISION BELOW, bfloat16: {'lower': 'all'}, the reference
# computed in bfloat16 throughout (weights, every activation, the scores,
# the recurrence's state), three seeds: every matrix 41-47, A_log
# 39.6-67.8, dt_bias 46.1-50.2, q_proj 36.9-37.4, kv_b 29.8-30.1, router
# 49.3-51.3; router inputs 30.1-30.6 (and 7 000 of 8 192 tokens a layer
# routed otherwise); loss 2.0e-5 to 2.2e-4.  NOT correct, by every gradient
# and the router inputs on every seed, by the loss on two of three.
# LIMITS, between the two with room on both sides: every matrix's gradient
# 6 (1.8 times the largest sound reading, 3.33; 0.2 of the least bfloat16
# one, 29.8), A_log's 8 (its sound readings swing by a factor of two from
# seed to seed: 1.9 times 4.31; 0.2 of 39.6), the router's 7 (2.1 times
# 3.40; 0.14 of 49.3: a token whose 8th and 9th s + b lie within the last
# bits moves a whole row); router inputs 4 (1.9 times 2.06; 0.13 of 30.1).
# The loss is a mean over 8 192 tokens whose errors mostly cancel, so its
# bfloat16 readings overlap the sound ones and no limit fails the control
# by the loss on every seed: 6e-5, 2.2 times the largest sound reading,
# fails it on the two seeds that read 1.31e-4 and 2.22e-4 (the accepted
# decoder cells' 1.5e-4 would fail it on one).
ROUTER_MARGINS = (0.0005, 0.001, 0.002)
CHECKS = (
    {"name": "train", "is_test": False, "loss_rel_tol": 6e-5,
     "router_input_rel_tol": 0.04,
     "grad_rel_tol": {"kimi.embed": 0.06, "kimi.l0.qkv": 0.06,
                      "kimi.l0.conv": 0.06, "kimi.l0.A_log": 0.08,
                      "kimi.l0.dt_bias": 0.06, "kimi.l0.f_up": 0.06,
                      "kimi.l0.b_proj": 0.06, "kimi.l0.g_up": 0.06,
                      "kimi.l0.mlp_up": 0.06, "kimi.l3.q_proj": 0.06,
                      "kimi.l3.kv_b": 0.06, "kimi.l1.router": 0.07,
                      "kimi.l1.experts_gate": 0.06,
                      "kimi.l1.experts_down": 0.06,
                      "kimi.l1.shared_up": 0.06}},
)


def _layers_run(sizes):
    """The mixer of each layer this chip runs, a character a layer (``K``
    KDA, ``A`` MLA): the published ``linear_attn_config`` (whole in the
    file, layers numbered from 1) at ``layers_run`` (from 0)."""
    picked = sizes["layers_run"]
    if len(picked) != sizes["num_hidden_layers"]:
        raise ValueError("kimi_linear_48b_a3b: layers_run does not name "
                         "num_hidden_layers layers")
    config = sizes["linear_attn_config"]
    kinds = {**{at - 1: "K" for at in config["kda_layers"]},
             **{at - 1: "A" for at in config["full_attn_layers"]}}
    return "".join(kinds[at] for at in picked)


def _dense_layers(sizes):
    """How many of the layers built lead with the dense feed-forward: those
    run whose published index is under ``first_k_dense_replace``."""
    return sum(at < sizes["first_k_dense_replace"]
               for at in sizes["layers_run"])


def _expert_offset(sizes):
    return sizes["expert_parallel_rank"] * sizes["num_experts"]


def _kda_widths(sizes):
    """(heads, head size, heads x head size) of a KDA layer."""
    config = sizes["linear_attn_config"]
    heads, head = config["num_heads"], config["head_dim"]
    return heads, head, heads * head


def _model_kwargs(sizes):
    heads, head, _ = _kda_widths(sizes)
    return dict(
        hidden_size=sizes["hidden_size"], dense_layers=_dense_layers(sizes),
        dense_width=sizes["intermediate_size"], kda_heads=heads,
        kda_head_dim=head,
        conv_taps=sizes["linear_attn_config"]["short_conv_kernel_size"],
        chunk=sizes["chunk_size"], mla_heads=sizes["num_attention_heads"],
        qk_nope_dim=sizes["qk_nope_head_dim"],
        qk_rope_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"], kv_lora_rank=sizes["kv_lora_rank"],
        num_experts=sizes["router_width"],
        experts_per_tok=sizes["num_experts_per_token"],
        expert_width=sizes["moe_intermediate_size"],
        shared_width=sizes["num_shared_experts"]
        * sizes["moe_intermediate_size"],
        renormalize=sizes["moe_renormalize"],
        routed_scale=sizes["routed_scaling_factor"],
        expert_bias_range=sizes["expert_bias_range"],
        experts_held=sizes["num_experts"],
        expert_offset=_expert_offset(sizes), norm_eps=sizes["rms_norm_eps"],
        time_step_min=sizes["time_step_min"],
        time_step_max=sizes["time_step_max"],
        recompute=sizes.get("recompute", False), prefix=PREFIX)


def build(mode, batch, sizes):
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    if mode != "train":
        raise ValueError("kimi_linear_48b_a3b: only 'train' is built "
                         "(serving waits for the latent cache and the delta "
                         "rule's state beside the decode cache, ROADMAP B)")
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    vocab, t_len = sizes["vocab_size"], sizes["seq_len"]
    ids = layers.data("ids", shape=[t_len], dtype="int64")
    lbl = layers.data("lbl", shape=[t_len], dtype="int64")
    loss = models.kimi_linear_loss(ids, lbl, vocab, _layers_run(sizes),
                                   **_model_kwargs(sizes))
    pt.optimizer.Adam(sizes["optimizer"]["learning_rate"]).minimize(loss)
    feeds = {"ids": {"shape": [t_len], "dtype": "int64", "high": vocab},
             "lbl": {"shape": [t_len], "dtype": "int64", "high": vocab}}
    main = pt.default_main_program()
    # what each expert layer's router read in the check's step, by the
    # layer its router parameter names ('l1': ...): ``reference`` is shown
    # them (``observed``)
    routers = {op.input("GateW")[0].split(".")[1]: op.input("X")[0]
               for block in main.blocks for op in block.ops
               if op.type == "moe"}
    return {"main": main,
            "startup": pt.default_startup_program(),
            "check_fetches": routers,
            "feeds": feeds, "loss": loss.name,
            "amp": sizes["compute_dtype"] == "bfloat16",
            "items_per_example": t_len}


# ---------------------------------------------------------------------------
# operations and bytes, from the sizes
# ---------------------------------------------------------------------------
def _count(sizes, kind):
    return _layers_run(sizes).count(kind)


def _held_share(sizes):
    """The part of a token's assignments that lands here when the router
    is balanced: experts held over the router's width."""
    return sizes["num_experts"] / sizes["router_width"]


def _delta_macs(sizes):
    """Multiply-accumulates of ``gated_delta_scan``'s own products for one
    token of one layer, forward, in the chunked form at ``chunk_size`` C: a
    head's C K for each of the two decayed C x C products (A and the
    output's; its diagonal sub-blocks elementwise, the rest a product),
    C (V + K) / 2 for the triangular solve, 3 K V for the chunk's hand-over
    (TK S0, the state's own update, Q+ S0) and C V for the product inside
    the chunk (K = V = the head size)."""
    heads, head, _ = _kda_widths(sizes)
    c = sizes["chunk_size"]
    return heads * (2 * c * head + c * head + 3 * head * head + c * head)


def flops_per_item(sizes, mode):
    """FLOPs the mathematics needs per token ON THIS CHIP, 2 per
    multiply-accumulate of every matrix product; training = 3x forward.  A
    ``K`` layer: its projections (q, k, v; the two low-rank gates and beta;
    the output) and ``gated_delta_scan``'s own products (``_delta_macs``);
    the ``A`` layer: its four projections, causal scores at 192 and context
    at 128 over T/2 keys a query; the dense layer's three products; an
    expert layer: the router at its whole width, the shared expert's three
    products and the BALANCED rows held, ``num_experts_per_token`` * 8 /
    256 = 0.25 routed experts a token of three products each (what the
    run's routing really holds: ``rows_held`` of ``reference``'s third
    result); the head over the slice.  What a backward pass computes again
    is not counted.  The filter's taps, the gates' elementwise work,
    look-ups, norms, softmax and Adam are not counted."""
    d, t = sizes["hidden_size"], sizes["seq_len"]
    heads, head, width = _kda_widths(sizes)
    mla_heads = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    v_dim, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    expert_layers = sizes["num_hidden_layers"] - _dense_layers(sizes)
    macs = (_count(sizes, "K") * (
                d * 3 * width + 2 * (d * head + head * width) + d * heads
                + width * d + _delta_macs(sizes))
            + _count(sizes, "A") * (
                d * mla_heads * qk + d * (rank + sizes["qk_rope_head_dim"])
                + rank * mla_heads * (sizes["qk_nope_head_dim"] + v_dim)
                + mla_heads * v_dim * d
                + (t / 2) * mla_heads * (qk + v_dim))
            + _dense_layers(sizes) * 3 * d * sizes["intermediate_size"]
            + expert_layers * (
                d * sizes["router_width"]
                + 3 * d * sizes["num_shared_experts"]
                * sizes["moe_intermediate_size"]
                + sizes["num_experts_per_token"] * _held_share(sizes)
                * 3 * d * sizes["moe_intermediate_size"])
            + d * sizes["vocab_size"])
    return 2.0 * macs * (3 if mode == "train" else 1)


def gated_delta_scan_work(sizes, tokens):
    """(FLOPs, bytes) ``gated_delta_scan`` of all the ``K`` layers run needs
    in a training step on ``tokens`` tokens, in float32, whatever implements
    it.  Forward: q, k, v, g [N, 4096] and beta [N, 32] read, o [N, 4096]
    written, once.  Backward: those five and the cotangent read, their five
    gradients written.  The FLOPs are the chunked form's own products
    (``_delta_macs``), three times over.  What a recomputed layer executes
    again is not counted.  The bytes bound it."""
    heads, _, width = _kda_widths(sizes)
    operands = 4 * width + heads
    bytes_ = 4.0 * tokens * ((operands + width) + (operands + width)
                             + operands)
    flops = 3 * 2.0 * _delta_macs(sizes) * tokens
    layers_ = _count(sizes, "K")
    return layers_ * flops, layers_ * bytes_


def short_conv_work(sizes, tokens):
    """(FLOPs, bytes) the UNGATED short convolutions of all the ``K`` layers
    run need in a training step on ``tokens`` tokens, in float32, C = 3 x
    4096 channels of 4 taps, no bias.  Forward: X [N, C] read, Out [N, C]
    written.  Backward: X and the cotangent read, dX written (the filter's
    own gradient is [C, 4]: nothing).  A multiply-add a tap and about a
    dozen operations for SiLU an element, three times over for the backward.
    The bytes bound it."""
    c, n = 3 * _kda_widths(sizes)[2], tokens
    taps = sizes["linear_attn_config"]["short_conv_kernel_size"]
    flops = 3 * (2.0 * taps + 12.0) * n * c
    bytes_ = 4.0 * n * c * (2 + 3)
    layers_ = _count(sizes, "K")
    return layers_ * flops, layers_ * bytes_


def grouped_attention_work(sizes, sequences):
    """(FLOPs, bytes) causal attention of the ``A`` layers run needs in a
    training step on ``sequences`` sequences, 32 query heads over 32 K / V
    heads: six products over half the T x T square, three at the query /
    key head size D = 192 (scores forward, dq and dk backward) and three at
    the value head size Dv = 128 (context forward, dp and dv backward; what
    a fused kernel recomputes is not counted); q, k, dq, dk moved at D (q,
    k read forward; q, k read and dq, dk written backward) and v, o, do, dv
    at Dv (v read, o written forward; v, o, do read and dv written
    backward), float32."""
    t, heads = sizes["seq_len"], sizes["num_attention_heads"]
    d = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    layers_ = _count(sizes, "A")
    return (layers_ * 3 * 2.0 * sequences * (t * t / 2) * heads * (d + dv),
            layers_ * 4.0 * sequences * t * heads * (6 * d + 6 * dv))


def expert_share_work(sizes, tokens):
    """(FLOPs, bytes) the grouped products of ALL the expert layers run need
    in a training step on ``tokens`` tokens for the BALANCED rows held
    (tokens * num_experts_per_token * 8 / 256) through the three stacks of
    the 8 SiLU-gated experts held: forward, gradient of the rows, gradient
    of the stack, nine products, each reading its two operands and writing
    its result once, in float32."""
    rows = tokens * sizes["num_experts_per_token"] * _held_share(sizes)
    d, h, e = (sizes["hidden_size"], sizes["moe_intermediate_size"],
               sizes["num_experts"])
    layers_ = sizes["num_hidden_layers"] - _dense_layers(sizes)
    return (layers_ * 9 * 2.0 * rows * d * h,
            layers_ * 9 * 4.0 * (rows * d + rows * h + e * d * h))


def moe_shared_work(sizes, tokens):
    """(FLOPs, bytes) the shared experts of ALL the expert layers run need
    in a training step on ``tokens`` tokens: three dense products 2304 x
    1024 over EVERY token forward (gate, up, down), six backward (each
    product's two gradients), nine in all, each reading its two operands
    and writing its result once, in float32.  What a recomputed layer
    executes again is not counted.  The FLOPs bound it."""
    d = sizes["hidden_size"]
    h = sizes["num_shared_experts"] * sizes["moe_intermediate_size"]
    layers_ = sizes["num_hidden_layers"] - _dense_layers(sizes)
    return (layers_ * 9 * 2.0 * tokens * d * h,
            layers_ * 9 * 4.0 * (tokens * d + tokens * h + d * h))


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
MIXER_WEIGHTS = {
    "K": ("qkv", "conv", "f_down", "f_up", "dt_bias", "A_log", "b_proj",
          "g_down", "g_up", "g_bias", "o_norm", "o_proj"),
    "A": ("q_proj", "kv_a", "kv_norm", "kv_b", "o_proj")}
FFN_WEIGHTS = {
    "dense": ("mlp_gate", "mlp_up", "mlp_down"),
    "experts": ("router", "expert_bias", "experts_gate", "experts_up",
                "experts_down", "shared_gate", "shared_up", "shared_down")}


def _rms(x, g, eps):
    import jax.numpy as jnp
    from jax import lax

    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _unit(x):
    import jax.numpy as jnp

    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


def _filter(x, w):
    """silu(c) for x [B, T, C], w [C, L]: tap j reads x[t - (L-1) + j],
    zeros before position 0; no bias."""
    import jax
    import jax.numpy as jnp

    taps, t_len = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[:, j] * padded[:, j:j + t_len]
                           for j in range(taps)))


def _delta_recurrence(q, k, v, g, beta, scale, fault):
    """o [B, T, H, V] of S[t] = (I - beta k k^T) Diag(exp(g)) S[t-1] + beta
    k v^T, o[t] = S[t]^T (scale q[t]), S[-1] = 0, ONE position a step; q, k,
    g [B, T, H, K], v [B, T, H, V], beta [B, T, H].  Blocks of positions are
    recomputed in the backward pass (``jax.checkpoint``): no value changes.
    ``fault`` 'no_correction' leaves beta k k^T out (a plain gated linear
    recurrence S[t] = Diag(exp(g)) S[t-1] + beta k v^T)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t_len, heads, kd = q.shape

    def position(state, at):                 # state [B, H, K, V]
        qt, kt, vt, gt, bt = at
        state = jnp.exp(gt)[..., None] * state
        seen = 0.0 if fault == "no_correction" else jnp.einsum(
            "bhk,bhkv->bhv", kt, state)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, scale * qt)

    block = math.gcd(t_len, 64)

    def first(x):                      # [B, T, ...] -> [T/b, b, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((t_len // block, block) + x.shape[1:])

    _, o = lax.scan(
        jax.checkpoint(lambda state, rows: lax.scan(position, state, rows)),
        jnp.zeros((b, heads, kd, v.shape[-1]), q.dtype),
        tuple(first(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((t_len,) + o.shape[2:]), 0, 1)


def _kda(n, w, sizes, fault):
    import jax
    import jax.numpy as jnp

    b, t_len, _ = n.shape
    heads, head, width = _kda_widths(sizes)

    def by_head(x):
        return x.reshape(b, t_len, heads, head)

    qkv = _filter(n @ w["qkv"], w["conv"])
    q, k = (_unit(by_head(qkv[..., at:at + width])) for at in (0, width))
    v = by_head(qkv[..., 2 * width:])
    g = by_head(jax.nn.softplus(n @ w["f_down"] @ w["f_up"] + w["dt_bias"])) \
        * -jnp.exp(w["A_log"])[:, None]
    if fault == "scalar_decay":         # one decay a head, for each channel's
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(n @ w["b_proj"])
    o = _delta_recurrence(q, k, v, g, beta, head ** -0.5, fault)
    gate = jax.nn.sigmoid(n @ w["g_down"] @ w["g_up"] + w["g_bias"])
    o = _rms(o, w["o_norm"], sizes["rms_norm_eps"]).reshape(b, t_len, width)
    return (o * gate) @ w["o_proj"]


def _attention(q, k, v):
    """Causal softmax attention of q, k [B, T, H, D] over v [B, T, H, Dv] at
    1/sqrt(D), one head at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    t_len, d = q.shape[1], q.shape[3]
    mask = jnp.tril(jnp.ones((t_len, t_len), bool))

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                                  # [B, T, ...]
        s = jnp.einsum("btd,bsd->bts", qh, kh) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", p, vh)

    out = lax.map(one_head, tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v)))
    return jnp.moveaxis(out, 0, 2)


def _mla(n, w, sizes):
    import jax.numpy as jnp

    b, t_len, _ = n.shape
    heads, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    v_dim = sizes["v_head_dim"]
    q = (n @ w["q_proj"]).reshape(b, t_len, heads, nope + rope)
    kv_a = n @ w["kv_a"]
    kv = (_rms(kv_a[..., :rank], w["kv_norm"], sizes["rms_norm_eps"])
          @ w["kv_b"]).reshape(b, t_len, heads, nope + v_dim)
    k_pe = jnp.broadcast_to(kv_a[..., None, rank:], (b, t_len, heads, rope))
    o = _attention(q, jnp.concatenate([kv[..., :nope], k_pe], axis=-1),
                   kv[..., nope:])
    return o.reshape(b, t_len, heads * v_dim) @ w["o_proj"]


def _gated(m, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def _experts(m, weight, w_gate, w_up, w_down):
    """sum over the experts HELD of weight[:, e] * (silu(m Wg[e]) * m Wu[e])
    Wd[e] for m [N, D] and weight [N, held] (the token's renormalised score
    where expert e is among its chosen, else 0): every held expert on every
    token, in turn."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.checkpoint
    def one_expert(acc, ws):
        wg, wu, wd, pe = ws
        return acc + pe[:, None].astype(m.dtype) * _gated(m, wg, wu, wd), None

    return lax.scan(one_expert, jnp.zeros_like(m),
                    (w_gate, w_up, w_down, weight.T))[0]


def _expert_ffn(n, w, sizes, routed_by):
    """(the expert layer's output [B, T, D], what its router saw).
    ``routed_by`` [B, T, D]: what the program's router read at this layer in
    the same step.  The CHOICE of experts (discrete, no gradient) is then
    made from it, with this code's own router and bias; scores, weights and
    everything continuous stay this code's own."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, t_len, d = n.shape
    top_k, held = sizes["num_experts_per_token"], sizes["num_experts"]

    def scores(rows):                                        # [N, 256]
        return jax.nn.sigmoid(rows @ w["router"])

    def choice(score):
        biased = lax.stop_gradient(score + w["expert_bias"])
        best = lax.top_k(biased, top_k + 1)[0]
        return (biased >= best[:, top_k - 1:top_k],          # [N, 256] 0/1
                best[:, top_k - 1] - best[:, top_k])

    n2 = n.reshape(b * t_len, d)
    score = scores(n2)
    chosen, gap = choice(score)
    saw = {f"under_{margin}": jnp.sum(gap < margin)
           for margin in ROUTER_MARGINS}
    if routed_by is not None:
        shown = lax.stop_gradient(routed_by.reshape(b * t_len, d))
        own, chosen = chosen, choice(scores(shown))[0]
        saw["tokens_routed_otherwise"] = jnp.sum(jnp.any(own != chosen, -1))
        saw["input_rel_err"] = (
            jnp.linalg.norm((shown - n2).astype(jnp.float32))
            / jnp.linalg.norm(n2.astype(jnp.float32)))
    weight = jnp.where(chosen, score, 0.0)
    if sizes["moe_renormalize"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    weight = weight * sizes["routed_scaling_factor"]
    here = slice(_expert_offset(sizes), _expert_offset(sizes) + held)
    y = _experts(n2, weight[:, here], w["experts_gate"], w["experts_up"],
                 w["experts_down"]) \
        + _gated(n2, w["shared_gate"], w["shared_up"], w["shared_down"])
    saw["rows_held"] = jnp.sum(chosen[:, here])
    return y.reshape(b, t_len, d), lax.stop_gradient(saw)


def _layer(x, w, kind, sizes, fault=None, routed_by=None):
    """(one layer's output, what its router saw: {} for the dense layer) for
    x [B, T, D]; ``kind`` the mixer's character, with ``+`` where the ffn is
    the dense MLP; ``w`` the layer's weights by their short names."""
    eps = sizes["rms_norm_eps"]
    n = _rms(x, w["attn_norm"], eps)
    x = x + (_kda(n, w, sizes, fault) if kind[0] == "K"
             else _mla(n, w, sizes))
    n, saw = _rms(x, w["ffn_norm"], eps), {}
    if kind.endswith("+"):
        o = _gated(n, w["mlp_gate"], w["mlp_up"], w["mlp_down"])
    else:
        o, saw = _expert_ffn(n, w, sizes, routed_by)
    return x + o, saw


def _head_loss(x, head, final_norm, labels, sizes, rows=512):
    """mean_t -log softmax(rms(x_t) head)[label_t] for x [B, T, D], in
    blocks of ``rows`` rows."""
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
        logp = jax.nn.log_softmax((xb @ head).astype(jnp.float32), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    return jnp.sum(lax.map(block, (x.reshape(n // rows, rows, -1),
                                   labels.reshape(n // rows, rows)))) / n


def _kinds(sizes):
    """A layer's kind: its mixer's character, ``+`` where its ffn is the
    dense MLP."""
    dense = _dense_layers(sizes)
    return [kind + ("+" if i < dense else "")
            for i, kind in enumerate(_layers_run(sizes))]


def _layer_names(kind):
    return ("attn_norm", "ffn_norm") + MIXER_WEIGHTS[kind[0]] \
        + FFN_WEIGHTS["dense" if kind.endswith("+") else "experts"]


def _parameter_names(sizes):
    return [f"{PREFIX}.embed", f"{PREFIX}.final_norm", f"{PREFIX}.head"] + [
        f"{PREFIX}.l{i}.{n}" for i, kind in enumerate(_kinds(sizes))
        for n in _layer_names(kind)]


def _assumed_vectors(sizes):
    """{short name: the values the startup program is to give a KDA
    layer's vector}."""
    import numpy as np

    heads, _, width = _kda_widths(sizes)
    step = np.exp(np.linspace(math.log(sizes["time_step_min"]),
                              math.log(sizes["time_step_max"]), width))
    return {"A_log": np.log(np.linspace(1.0, 16.0, heads)),
            "dt_bias": step + np.log(-np.expm1(-step))}


def _hold_startup_values(params, sizes):
    """The values that the startup program sets and the harness does not
    draw (``lib/weights.py`` draws matrices only) are held to what the
    configuration's ``assumed`` says of them."""
    import numpy as np

    assumed, limit = _assumed_vectors(sizes), sizes["expert_bias_range"]
    for i, kind in enumerate(_kinds(sizes)):
        if kind[0] == "K":
            for short, want in assumed.items():
                got = np.asarray(params[f"{PREFIX}.l{i}.{short}"])
                if got.shape != want.shape or not np.allclose(got, want,
                                                              rtol=1e-5):
                    raise ValueError(
                        f"{PREFIX}.l{i}.{short} is not what the startup "
                        f"program is to set it to: {got[:4]} for {want[:4]}")
        if not kind.endswith("+"):
            bias = np.asarray(params[f"{PREFIX}.l{i}.expert_bias"])
            # (all within the range, and spread: 8 values of a rehearsal's
            # router may lie within half of it)
            if not (np.max(np.abs(bias)) <= limit
                    and np.ptp(bias) >= limit / 4):
                raise ValueError(f"{PREFIX}.l{i}.expert_bias is not a draw "
                                 f"in +-{limit}: {bias[:4]}")


def reference(mode, params, feeds, sizes, frozen_stats=False, observed=None,
              control=None):
    """'loss': the training loss, forward only.  'train': (loss, {name:
    gradient} for ``sizes['check_params']``, what the routers saw).  float32
    throughout, matmul precision 'highest' (``frozen_stats`` changes
    nothing: there are no batch statistics).  ``params`` are host arrays
    (they also hold the optimizer's moments); a layer's weights are on the
    device while that layer runs, forward or backward, and no longer.

    ``observed`` {layer: [B, T, D]} (``build``'s ``check_fetches``, fetched
    from the program's own step): the choice of experts follows what the
    program's routers read (``_expert_ffn``).  The third result says, layer
    by layer, how far that lies from this code's own router input
    (``input_rel_err``, held by ``CHECKS``), how many tokens it routed
    otherwise, the tokens whose 8th / 9th s + b lie within each of
    ``ROUTER_MARGINS``, and the assignments that landed on the experts held
    (beside the balanced count and the static bound).

    ``control`` (``--set control=...``; never in a measured run) makes this
    a control that the check has to FAIL: {'lower': 'all'} computes
    everything here in bfloat16, weights, activations, scores and the
    recurrence's state (the nearest precision below the float32 the
    configuration states); {'fault': 'no_correction'} leaves the delta
    rule's correction beta k k^T out, {'fault': 'scalar_decay'} takes one
    decay a head (the mean of its channels') for the decay a key channel;
    {'sizes': {...}} is laid over ``sizes`` (another rank's offset, weights
    not renormalised)."""
    import jax
    import jax.numpy as jnp

    if mode not in ("train", "loss"):
        raise ValueError("kimi_linear_48b_a3b: only training has a "
                         "reference")
    control = control or {}
    kinds = _kinds(sizes)
    _hold_startup_values(params, sizes)
    sizes = {**sizes, **control.get("sizes", {})}
    fault, lower = control.get("fault"), control.get("lower")
    if lower not in (None, "all") or fault not in (None, "no_correction",
                                                   "scalar_decay"):
        raise ValueError(f"kimi_linear_48b_a3b: no control {control!r}")
    dtype = jnp.bfloat16 if lower == "all" else jnp.float32

    def put(name):
        return jnp.asarray(params[name], jnp.float32).astype(dtype)

    def weights(i):
        return {n: put(f"{PREFIX}.l{i}.{n}") for n in _layer_names(kinds[i])}

    def shown(i):
        if not observed or f"l{i}" not in observed:
            return None
        return jnp.asarray(observed[f"l{i}"], jnp.float32).astype(dtype)

    ids, labels = jnp.asarray(feeds["ids"]), jnp.asarray(feeds["lbl"])
    table, final_norm, head_w = (put(f"{PREFIX}.{n}")
                                 for n in ("embed", "final_norm", "head"))
    run = {kind: jax.jit(lambda x, w, routed_by, kind=kind:
                         _layer(x, w, kind, sizes, fault, routed_by))
           for kind in set(kinds)}
    back = {kind: jax.jit(lambda x, w, routed_by, ct, kind=kind: jax.vjp(
        lambda x, w: _layer(x, w, kind, sizes, fault, routed_by)[0],
        x, w)[1](ct)) for kind in set(kinds)}

    def head(x, head_w, g):
        return _head_loss(x, head_w, g, labels, sizes)

    with jax.default_matmul_precision("highest"):
        x, inputs, saw = table[ids], [], {}
        for i, kind in enumerate(kinds):
            inputs.append(x)
            x, seen = run[kind](x, weights(i), shown(i))
            if seen:
                saw[f"l{i}"] = seen
        if mode == "loss":
            return jax.jit(head)(x, head_w, final_norm)
        loss, (ct, d_head, d_final) = jax.jit(jax.value_and_grad(
            head, argnums=(0, 1, 2)))(x, head_w, final_norm)
        held = set(sizes["check_params"])
        grads = {name: g for name, g in (
            (f"{PREFIX}.head", d_head), (f"{PREFIX}.final_norm", d_final))
            if name in held}
        for i in reversed(range(len(kinds))):
            ct, d_w = back[kinds[i]](inputs.pop(), weights(i), shown(i), ct)
            grads.update({f"{PREFIX}.l{i}.{n}": g for n, g in d_w.items()
                          if f"{PREFIX}.l{i}.{n}" in held})
        if f"{PREFIX}.embed" in held:
            # the look-up's gradient: the head has its own matrix
            grads[f"{PREFIX}.embed"] = jnp.zeros_like(table).at[
                ids.reshape(-1)].add(ct.reshape(-1, ct.shape[-1]))
    tokens = int(ids.size)
    saw = {layer: {k: float(v) if k == "input_rel_err" else int(v)
                   for k, v in seen.items()} for layer, seen in saw.items()}
    saw["tokens"] = tokens
    saw["rows_balanced"] = tokens * sizes["num_experts_per_token"] \
        * _held_share(sizes)
    saw["rows_bound"] = tokens * sizes["num_experts_per_token"]
    return loss.astype(jnp.float32), {
        n: g.astype(jnp.float32) for n, g in grads.items()}, saw
