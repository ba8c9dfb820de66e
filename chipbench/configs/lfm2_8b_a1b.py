"""LFM2-8B-A1B (Liquid AI; ``model_type: lfm2_moe``) cut to one chip as rank
0 of four that share each layer: the program under test (``models.lfm2_loss``
through the public layers API, Adam) and its plain float32 reference.

``reference`` is written from the model's equations and shares no code with
``paddle_tpu``.  rms(x; g) = x * rsqrt(mean(x^2, -1) + eps) * g over the
last axis; no bias anywhere.  For x [B, T, 2048], layer i:

    h   = x + operator_i(rms(x; g_op))     operator_i by layer_types
    out = h + ffn_i(rms(h; g_ffn))         dense for the leading layers

    conv:  [Bg, Cg, u] = split(a W_in, 3);  v = Bg * u
           c[t] = sum_{j<3} w[:, j] * v[t - 2 + j]      (v[<0] = 0)
           operator = (Cg * c) W_out
    full_attention:  q = a Wq (32 heads of 64), k = a Wk, v = a Wv (8 of 64)
           q, k = rope(rms over EACH head's 64 features), angle[t, i] =
           t * theta^(-2i/64), u' = u cos + concat(-u[32:], u[:32]) sin
           query head h reads K / V head h // 4; causal softmax, scale 1/8
           operator = o Wo
    dense ffn:  (silu(m W1) * (m W3)) W2
    experts:    s = sigmoid(m W_r)                       (32 outputs)
           chosen = the 4 largest of s + b  (b: no gradient, choice only)
           g = s[chosen] / (sum s[chosen] + 1e-6) * routed_scaling_factor
           ffn = sum over the chosen e THAT THIS CHIP HOLDS of
                 g_e * (silu(m Wg[e]) * (m Wu[e])) Wd[e]
    logits = rms(x_L; g_f) Emb^T;   loss = mean_t -log softmax(logits)[label]

**The share.**  This chip holds experts ``expert_parallel_rank * 8`` to + 7
of the router's 32 (``router_width``) and 16 384 of the table's 65 536 rows.
Scores, choice and renormalisation run over all 32; what the 24 absent
experts would add is left out here and in the program alike, and that
partial result goes on to the next layer (the guide's section 4).

Departures from a training recipe, all in the configuration's ``assumed``:
float32 for bfloat16 mixed precision, plain Adam, uniform random tokens and
labels, five layers of 24, a fixed selection bias.  Departures of THIS code
from the plainest form, to fit beside the program's live state on the same
chip and none changing a value: every layer is recomputed in the backward
pass, the held experts are a sequential loop, each applied to every token
under its weight (0 where not chosen), attention runs query head by query
head and the loss in blocks of rows; gradients are taken for
``check_params`` only.
"""
from __future__ import annotations

PREFIX = "lfm2"

# What the training step is held to, on ONE seeded 8192-token sequence at the
# seeded weights (relative errors: |loss - ref| / |ref|, ||g - ref||_2 /
# ||ref||_2).  The program's products run at the TPU's default precision
# (one bfloat16 pass, float32 accumulation; the router's at HIGHEST), the
# reference's at 'highest'.
#
# The hazard is OLMoE's, four times over: the choice of 4 of 32 is
# discontinuous, a router's INPUT differs between the two sides by
# single-pass rounding (0.7-1.0 % here), and a token whose fourth and fifth
# s + b lie within that takes another expert on one side: 190-320 of 8192
# tokens in each of FOUR expert layers in a row.  Left alone, that read
# 14.6-25.2 % in every gradient on every seed (22 runs), the same as this
# reference against itself at one bfloat16 pass, and above every
# lower-precision control: a comparison that told nothing.  So the cell
# runs through ``drivers/train_scan_routed.py``: the check's step also
# fetches each router's input (``build``'s ``check_fetches``) and
# ``reference`` makes the CHOICE from what it is shown, with its own router,
# bias and weights; scores, weights and every gradient stay its own, and
# ``router_input_rel_tol`` holds how far its own router inputs lie from the
# ones shown.  A program that chose wrongly from what it read (no bias,
# another top-k) still parts from the reference: the reference never sees
# the program's choice.
#
# What is left: the program's router and this one round the SAME input
# differently in the last bits (products at 'highest' are six bfloat16
# passes), so a token whose fourth and fifth s + b lie within ~5e-6 still
# goes otherwise, 0 to 5 tokens of layer 1 a run.  Each costs the router's
# gradient 2.1 % in quadrature (1.8-1.9 % with none, 2.82 with one, 5.05
# the most seen) and the expert stacks' 0.8 %.
#
# Measured on the chip (PR 34, PERF.md section 6; every run its own seed).
# Sound runs (21): loss 3.9e-7 to 1.7e-5; gradients: table 1.33-1.86 %,
# in_proj 1.37-1.93, filter 1.35-1.96, wq / wk 1.93-2.91, router 1.79-5.05,
# expert stacks 1.48-2.89; router inputs 0.95-1.00 %.  Controls through this
# same comparison (``--set control=``), gradients in the order above: every
# matrix rounded to bfloat16, the routers among them (the nearest precision
# below float32 weights) 5.9 / 6.0 / 6.1 / 6.5 / 10.8 / 7.3 % and 3.96 % in
# the router inputs; the router's product in bfloat16 7.3 / 7.5 / 7.6 / 8.0
# / 13.3 / 9.1 % and 3.98 %: both NOT correct, by every gradient and by the
# router inputs.  The embedding table alone rounded to bfloat16 reads
# 1.6-2.4 % and 1.08 %, 1.1-1.2 times the sound run of its seed, and
# passes: the program's own products round the table to bfloat16, only the
# look-up differs, and no limit can part the two.  Faults: the bias not used
# in the choice 50-83 %, weights not renormalised 72-86 %, the second half
# of the tokens left out 56-71 %, rank 1's offset 113-140 % (a state left
# unchanged reads 100 %).  The program with every XLA product at 'highest'
# (the kernels keep their one pass) reads 0.48-0.61 %, no token of layer 1
# routed otherwise.
# Limits, each between its two readings near their geometric mean: leaves
# before the attention layer 3.5 % (1.8 times the largest sound reading,
# 0.6 of the smaller control's), layer 1's projections and stacks 4.5 %
# (1.5 times; 0.6-0.7), the router 7.5 % (1.5 times, twelve such tokens
# where five were the most; 0.7); router inputs 2 % (2 times; half); loss
# 1.5e-4, the harness's accepted cells' (OLMoE, Ouro), 9 times the largest
# seen: no control moves the loss, the faults read 1.1e-4 to 7.7e-4.
ROUTER_MARGINS = (0.0005, 0.001, 0.002)
CHECKS = (
    {"name": "train", "is_test": False, "loss_rel_tol": 1.5e-4,
     "router_input_rel_tol": 0.02,
     "grad_rel_tol": {"lfm2.embed": 0.035, "lfm2.l0.in_proj": 0.035,
                      "lfm2.l0.conv": 0.035, "lfm2.l1.wq": 0.045,
                      "lfm2.l1.wk": 0.045, "lfm2.l1.router": 0.075,
                      "lfm2.l1.experts_up": 0.045,
                      "lfm2.l1.experts_down": 0.045}},
)


def _layers_run(sizes):
    """[(operator, dense?)] of the layers this chip runs: the published
    ``layer_types`` (whole in the file) at ``layers_run``, the first
    ``num_dense_layers`` of them dense."""
    picked = sizes["layers_run"]
    if len(picked) != sizes["num_hidden_layers"]:
        raise ValueError("lfm2_8b_a1b: layers_run does not name "
                         "num_hidden_layers layers")
    return [(sizes["layer_types"][at], i < sizes["num_dense_layers"])
            for i, at in enumerate(picked)]


def _expert_offset(sizes):
    return sizes["expert_parallel_rank"] * sizes["num_experts"]


def build(mode, batch, sizes):
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    if mode != "train":
        raise ValueError("lfm2_8b_a1b: only 'train' is built (serving "
                         "waits for the decode cache and the convolution's "
                         "state, ROADMAP B5)")
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    vocab, t_len = sizes["vocab_size"], sizes["seq_len"]
    ids = layers.data("ids", shape=[t_len], dtype="int64")
    lbl = layers.data("lbl", shape=[t_len], dtype="int64")
    loss = models.lfm2_loss(
        ids, lbl, vocab, [kind for kind, _ in _layers_run(sizes)],
        hidden_size=sizes["hidden_size"],
        num_dense_layers=sizes["num_dense_layers"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        ffn_size=sizes["intermediate_size"],
        num_experts=sizes["router_width"],
        experts_per_tok=sizes["num_experts_per_tok"],
        expert_width=sizes["moe_intermediate_size"],
        conv_taps=sizes["conv_L_cache"], rope_theta=sizes["rope_theta"],
        norm_eps=sizes["norm_eps"], norm_topk_prob=sizes["norm_topk_prob"],
        routed_scale=sizes["routed_scaling_factor"],
        expert_bias_range=sizes["expert_bias_range"]
        if sizes["use_expert_bias"] else None,
        experts_held=sizes["num_experts"],
        expert_offset=_expert_offset(sizes),
        recompute=sizes.get("recompute", False), prefix=PREFIX)
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
def _count(sizes, kind=None, dense=None):
    return sum(1 for k, d in _layers_run(sizes)
               if kind in (None, k) and dense in (None, d))


def _held_share(sizes):
    """The part of a token's assignments that lands here when the router
    is balanced: experts held over the router's width."""
    return sizes["num_experts"] / sizes["router_width"]


def flops_per_item(sizes, mode):
    """FLOPs the mathematics needs per token ON THIS CHIP, 2 per
    multiply-accumulate of every matrix product; training = 3x forward.  A
    ``conv`` operator: its two projections; a ``full_attention`` one: four
    projections (K and V at the 8 heads they have), causal scores and
    context at T/2 keys a query; a dense feed-forward; an expert layer: the
    router at its whole width and the BALANCED rows held,
    ``num_experts_per_tok`` * 8 / 32 = one expert a token (what the run's
    routing really holds: ``rows_held`` of ``reference``'s third result); the head
    over the slice.  What a backward pass computes again is not counted.
    The filter's taps, the gates, look-ups, norms, rope, softmax and Adam
    are not counted."""
    d, t = sizes["hidden_size"], sizes["seq_len"]
    kv = d * sizes["num_key_value_heads"] // sizes["num_attention_heads"]
    macs = (_count(sizes, "conv") * (3 * d * d + d * d)
            + _count(sizes, "full_attention") * (
                2 * d * d + 2 * d * kv + 2 * (t / 2) * d)
            + _count(sizes, dense=True) * 3 * d * sizes["intermediate_size"]
            + _count(sizes, dense=False) * (
                d * sizes["router_width"]
                + sizes["num_experts_per_tok"] * _held_share(sizes)
                * 3 * d * sizes["moe_intermediate_size"])
            + d * sizes["vocab_size"])
    return 2.0 * macs * (3 if mode == "train" else 1)


def short_conv_work(sizes, tokens):
    """(FLOPs, bytes) the gated short convolutions of ALL the ``conv``
    layers run need in a training step on ``tokens`` tokens, in float32.
    Forward: X [N, 3C] read, Out [N, C] written; 2 gate products and 3
    multiply-adds an output element.  Backward: X and the cotangent [N, C]
    read, dX [N, 3C] written (the filter's own gradient is [C, 3]: nothing);
    about three times the forward's arithmetic.  The bytes bound it."""
    c, n = sizes["hidden_size"], tokens
    flops = 4 * 8.0 * n * c
    bytes_ = 4.0 * n * c * ((3 + 1) + (3 + 1 + 3))
    layers_ = _count(sizes, "conv")
    return layers_ * flops, layers_ * bytes_


def expert_share_work(sizes, tokens):
    """(FLOPs, bytes) the grouped products of ALL the expert layers run
    need in a training step on ``tokens`` tokens for the BALANCED rows held
    (tokens * num_experts_per_tok * 8 / 32) through the three stacks of the
    8 experts held: forward, gradient of the rows, gradient of the stack,
    nine products, each reading its two operands and writing its result
    once, in float32."""
    rows = tokens * sizes["num_experts_per_tok"] * _held_share(sizes)
    d, h, e = (sizes["hidden_size"], sizes["moe_intermediate_size"],
               sizes["num_experts"])
    layers_ = _count(sizes, dense=False)
    return (layers_ * 9 * 2.0 * rows * d * h,
            layers_ * 9 * 4.0 * (rows * d + rows * h + e * d * h))


def grouped_attention_work(sizes, sequences):
    """(FLOPs, bytes) causal grouped-query attention of ALL the
    ``full_attention`` layers run needs in a training step on ``sequences``
    sequences: six products over half the T x T square at the 32 query
    heads (what a fused kernel recomputes is not counted); q, o, do, dq
    moved at 32 heads (q, o forward; q, o, do, dq backward) and k, v, dk,
    dv at the 8 they have (k, v forward; k, v, dk, dv backward), float32."""
    t, d = sizes["seq_len"], sizes["hidden_size"]
    kv = d * sizes["num_key_value_heads"] // sizes["num_attention_heads"]
    layers_ = _count(sizes, "full_attention")
    return (layers_ * 6 * 2.0 * sequences * (t * t / 2) * d,
            layers_ * 4.0 * sequences * t * (6 * d + 6 * kv))


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def _rms(x, g, eps):
    import jax.numpy as jnp
    from jax import lax

    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(u, theta):
    """u [B, T, H, d]."""
    import jax.numpy as jnp

    t_len, d = u.shape[1], u.shape[3]
    angle = jnp.arange(t_len, dtype=jnp.float32)[:, None] * theta ** (
        -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-u[..., d // 2:], u[..., :d // 2]], axis=-1)
    return u * jnp.cos(angle) + turned * jnp.sin(angle)


def _attention(q, k, v):
    """Causal softmax attention of q [B, T, H, d] over k, v [B, T, H_kv, d],
    query head h reading K / V head h // (H / H_kv), one query head at a
    time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    t_len, heads, d = q.shape[1], q.shape[2], q.shape[3]
    group = heads // k.shape[2]
    mask = jnp.tril(jnp.ones((t_len, t_len), bool))
    k_first, v_first = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)

    @jax.checkpoint
    def one_head(args):
        qh, h = args                                         # [B, T, d]
        kh, vh = k_first[h // group], v_first[h // group]
        s = jnp.einsum("btd,bsd->bts", qh, kh) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", p, vh)

    out = lax.map(one_head, (jnp.moveaxis(q, 2, 0), jnp.arange(heads)))
    return jnp.moveaxis(out, 0, 2)


def _short_conv(x, w):
    """(Cg * causal depthwise filter of (Bg * u)) for x [B, T, 3C] =
    [Bg | Cg | u] and w [C, L]: tap j reads v[t - (L-1) + j]."""
    import jax.numpy as jnp

    c, taps = w.shape
    gate_in, gate_out, u = x[..., :c], x[..., c:2 * c], x[..., 2 * c:]
    v = gate_in * u
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    t_len = v.shape[1]
    conv = sum(w[:, j] * padded[:, j:j + t_len] for j in range(taps))
    return gate_out * conv


def _experts(m, weight, w_gate, w_up, w_down):
    """sum over the experts HELD of weight[:, e] * (silu(m Wg[e]) *
    (m Wu[e])) Wd[e] for m [N, D] and weight [N, held] (the token's
    renormalised score where expert e is among its chosen, else 0): every
    held expert on every token, in turn."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.checkpoint
    def one_expert(acc, ws):
        wg, wu, wd, pe = ws
        return acc + pe[:, None] * (
            (jax.nn.silu(m @ wg) * (m @ wu)) @ wd), None

    return lax.scan(one_expert, jnp.zeros_like(m),
                    (w_gate, w_up, w_down, weight.T))[0]


def _cross_entropy(x, table, labels, rows=512):
    """mean_t -log softmax(x_t table^T)[label_t] for x [N, D], in blocks of
    ``rows`` rows (the logits of all N at once are 0.5 GB here)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n = x.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"{n} rows are not whole blocks of {rows}")

    @jax.checkpoint
    def block(xl):
        xb, lb = xl
        logp = jax.nn.log_softmax(xb @ table.T, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, lb[:, None], axis=-1))

    return jnp.sum(lax.map(block, (x.reshape(n // rows, rows, -1),
                                   labels.reshape(n // rows, rows)))) / n


OPERATOR_WEIGHTS = {"conv": ("in_proj", "conv", "out_proj"),
                    "full_attention": ("wq", "wk", "wv", "wo", "q_norm",
                                       "k_norm")}
FFN_WEIGHTS = {True: ("w1", "w3", "w2"),
               False: ("router", "expert_bias", "experts_gate",
                       "experts_up", "experts_down")}


def _layer(x, w, kind, dense, sizes, lower=None, routed_by=None):
    """(one layer's output, what its router saw: {}, or scalars by name)
    for x [B, T, D]; ``w`` the layer's weights by their short names.
    ``routed_by`` [B, T, D]: what the program's router read at this layer in
    the same step.  The CHOICE of experts (discrete, no gradient) is then
    made from it, with this code's own router; scores, weights and
    everything continuous stay this code's own.  ``lower`` 'router': the
    router's products with bfloat16 operands (a control)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps, theta = sizes["norm_eps"], float(sizes["rope_theta"])
    b, t_len, d = x.shape
    a = _rms(x, w["operator_norm"], eps)
    if kind == "conv":
        o = _short_conv(a @ w["in_proj"], w["conv"]) @ w["out_proj"]
    else:
        heads, kv_heads = (sizes["num_attention_heads"],
                           sizes["num_key_value_heads"])
        dh = d // heads
        q = _rms((a @ w["wq"]).reshape(b, t_len, heads, dh), w["q_norm"], eps)
        k = _rms((a @ w["wk"]).reshape(b, t_len, kv_heads, dh), w["k_norm"],
                 eps)
        v = (a @ w["wv"]).reshape(b, t_len, kv_heads, dh)
        o = _attention(_rope(q, theta), _rope(k, theta), v)
        o = o.reshape(b, t_len, d) @ w["wo"]
    h = x + o
    m = _rms(h, w["ffn_norm"], eps)
    if dense:
        y = (jax.nn.silu(m @ w["w1"]) * (m @ w["w3"])) @ w["w2"]
        return h + y, {}
    top_k, held = sizes["num_experts_per_tok"], sizes["num_experts"]

    def scores(rows):                                        # [N, 32]
        if lower == "router":
            return jax.nn.sigmoid(jnp.dot(
                rows.astype(jnp.bfloat16), w["router"].astype(jnp.bfloat16),
                preferred_element_type=jnp.float32))
        return jax.nn.sigmoid(rows @ w["router"])

    def choice(score):
        biased = lax.stop_gradient(score)
        if sizes["use_expert_bias"]:
            biased = biased + lax.stop_gradient(w["expert_bias"])
        best = lax.top_k(biased, top_k + 1)[0]
        return (biased >= best[:, top_k - 1:top_k],          # [N, 32] 0/1
                best[:, top_k - 1] - best[:, top_k])

    m2 = m.reshape(b * t_len, d)
    score = scores(m2)
    chosen, gap = choice(score)
    saw = {f"under_{margin}": jnp.sum(gap < margin)
           for margin in ROUTER_MARGINS}
    if routed_by is not None:
        shown = lax.stop_gradient(routed_by.reshape(b * t_len, d))
        own, chosen = chosen, choice(scores(shown))[0]
        saw["tokens_routed_otherwise"] = jnp.sum(jnp.any(own != chosen, -1))
        saw["input_rel_err"] = jnp.linalg.norm(shown - m2) \
            / jnp.linalg.norm(m2)
    weight = jnp.where(chosen, score, 0.0)
    if sizes["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    weight = weight * sizes["routed_scaling_factor"]
    here = slice(_expert_offset(sizes), _expert_offset(sizes) + held)
    y = _experts(m2, weight[:, here], w["experts_gate"], w["experts_up"],
                 w["experts_down"])
    saw["rows_held"] = jnp.sum(chosen[:, here])
    return h + y.reshape(b, t_len, d), lax.stop_gradient(saw)


def _layer_names(kind, dense, sizes):
    names = ("operator_norm", "ffn_norm") + OPERATOR_WEIGHTS[kind] \
        + FFN_WEIGHTS[dense]
    return [n for n in names
            if n != "expert_bias" or sizes["use_expert_bias"]]


def _loss(p, feeds, sizes, lower=None, observed=None):
    """(loss, {layer: what its router saw}).  ``observed`` {layer: [B, T,
    D]}: the program's router inputs (``_layer``'s ``routed_by``).
    ``lower`` (a control): 'table' rounds the embedding table to bfloat16's
    8 bits of mantissa, 'weights' every matrix and table, 'router' the
    router's operands."""
    import jax
    from jax import lax

    ids, labels = feeds["ids"], feeds["lbl"]
    if lower in ("table", "weights"):
        # (reduce_precision: XLA drops a convert there and back)
        p = {k: lax.reduce_precision(v, 8, 7)
             if v.ndim > 1 and (lower == "weights" or k.endswith(".embed"))
             else v for k, v in p.items()}
    x = p[f"{PREFIX}.embed"][ids]                            # [B, T, D]
    saw = {}
    for i, (kind, dense) in enumerate(_layers_run(sizes)):
        w = {n: p[f"{PREFIX}.l{i}.{n}"]
             for n in _layer_names(kind, dense, sizes)}
        x, seen = jax.checkpoint(
            lambda x, w, routed_by, kind=kind, dense=dense:
            _layer(x, w, kind, dense, sizes, lower, routed_by))(
                x, w, (observed or {}).get(f"l{i}"))
        if seen:
            saw[f"l{i}"] = seen
    x = _rms(x, p[f"{PREFIX}.final_norm"], sizes["norm_eps"])
    ce = _cross_entropy(x.reshape(-1, x.shape[-1]), p[f"{PREFIX}.embed"],
                        labels.reshape(-1))
    return ce, saw


def _parameter_names(sizes):
    return [f"{PREFIX}.embed", f"{PREFIX}.final_norm"] + [
        f"{PREFIX}.l{i}.{n}"
        for i, (kind, dense) in enumerate(_layers_run(sizes))
        for n in _layer_names(kind, dense, sizes)]


def reference(mode, params, feeds, sizes, frozen_stats=False, observed=None,
              control=None):
    """'loss': the training loss, forward only.  'train': (loss, {name:
    gradient} for ``sizes['check_params']``, what the routers saw).
    float32 throughout, matmul precision 'highest' (``frozen_stats``
    changes nothing: there are no batch statistics).  Only the model's own
    parameters are put on the device: ``params`` also holds the optimizer's
    moments.

    ``observed`` {layer: [B, T, D]} (``build``'s ``check_fetches``, fetched
    from the program's own step): the choice of experts follows what the
    program's routers read (``_layer``).  The third result says, layer by
    layer, how far that lies from this code's own router input
    (``input_rel_err``, held by ``CHECKS``), how many tokens it routed
    otherwise, the tokens whose 4th / 5th s + b lie within each of
    ``ROUTER_MARGINS``, and the assignments that landed on the experts held
    (beside the balanced count and the static bound).

    ``control`` (``--set control=...``; never in a measured run) makes this
    a control that the check has to FAIL: {'lower': 'table' | 'weights' |
    'router'} (``_loss``), {'sizes': {...}} laid over ``sizes`` (a bias
    that is not used, weights not renormalised, another rank's offset),
    {'tokens': n} the loss over the first n tokens of each sequence."""
    import jax
    import jax.numpy as jnp

    if mode not in ("train", "loss"):
        raise ValueError("lfm2_8b_a1b: only training has a reference")
    control = control or {}
    params = {k: jnp.asarray(params[k], jnp.float32)
              for k in _parameter_names(sizes)}
    if sizes["use_expert_bias"]:
        # drawn by the program's startup initializer, not by the harness
        # (lib/weights.py draws matrices): hold it to what was asked for
        for name in (n for n in params if n.endswith(".expert_bias")):
            limit, b = sizes["expert_bias_range"], params[name]
            if not (float(jnp.max(jnp.abs(b))) <= limit
                    and float(jnp.ptp(b)) > limit):
                raise ValueError(f"{name} is not a draw in +-{limit}: {b}")
    sizes = {**sizes, **control.get("sizes", {})}
    feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    if observed is not None:
        observed = {k: jnp.asarray(v, jnp.float32)
                    for k, v in observed.items()}
    if "tokens" in control:
        feeds, observed = ({k: v[:, :control["tokens"]]
                            for k, v in part.items()}
                           for part in (feeds, observed or {}))
    lower = control.get("lower")
    with jax.default_matmul_precision("highest"):
        if mode == "loss":
            return jax.jit(lambda p, f: _loss(p, f, sizes, lower)[0])(
                params, feeds)
        wrt = {k: params.pop(k) for k in sizes["check_params"]}
        (loss, saw), grads = jax.jit(jax.value_and_grad(
            lambda wrt, rest, f, o: _loss({**rest, **wrt}, f, sizes, lower,
                                          o or None),
            has_aux=True))(wrt, params, feeds, observed or {})
    tokens = int(feeds["ids"].size)
    saw = {layer: {k: float(v) if k == "input_rel_err" else int(v)
                   for k, v in seen.items()} for layer, seen in saw.items()}
    saw["tokens"] = tokens
    saw["rows_balanced"] = tokens * sizes["num_experts_per_tok"] \
        * _held_share(sizes)
    saw["rows_bound"] = tokens * sizes["num_experts_per_tok"]
    return loss, grads, saw
