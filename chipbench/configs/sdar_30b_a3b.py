"""SDAR-30B-A3B (``model_type: sdar_moe``) trained by block diffusion, cut
to one chip of eight that share each layer, at a chip's load: the program
under test (``models.sdar_moe_loss`` through the public layers API, Adam) and its
plain float32 reference.

``reference`` is written from the model's equations and shares no code with
``paddle_tpu``.  rms(x; g) = x * rsqrt(mean(x^2, -1) + eps) * g over the
last axis, eps 1e-6.  For clean ids x0 [B, L], levels d [B, L / 4] and
draws u [B, L] (integers in [0, 1024)):

    t_b = (d_b + 1) / 1024;  m_i = [u_i <= d_{beta(i)}],  beta(i) = i // 4
    x_t = MASK where m_i, else x0 (MASK = the last row of the slice)
    h = [Emb[x_t] | Emb[x0]] along the positions: 2L of them, position
        j < L the noisy stream, j >= L the clean, p(j) = j mod L
    every layer: h = h + attn(rms(h; g_in)), then h = h + ffn(rms(h; g_post))
    attn  q = rope(rms(n W_q; g_q)) (32 heads of 128, the norm a head),
          k = rope(rms(n W_k; g_k)), v = n W_v (4 heads of 128 each, query
          head h reading K / V head h // 8); rope half-split at 1e6, at
          p(j); softmax of q . k / sqrt(128) over the keys the mask allows:
            noisy j -> noisy k: beta(k) = beta(j)
            noisy j -> clean k: beta(k) < beta(j)
            clean j -> clean k: beta(k) <= beta(j)
            clean j -> noisy k: never
          then o W_o
    ffn   p = softmax(n W_r) over the 16 experts' outputs in float32
          chosen = the 8 largest of p;  w = p[chosen] / (sum p[chosen] + 1e-6)
          ffn = sum over the chosen e of
                w_e * (silu(n Wg[e]) * n Wu[e]) Wd[e]                   (768)
    logits = rms(h[:, :L]; g_f) W_head  (untied; the noisy stream)
    loss   = (1 / (B L)) sum_i m_i / t_{beta(i)} * -log softmax(logits_i)[x0_i]

**The cut.**  This chip holds 16 experts, the 128 of a layer over
``expert_parallel_size`` (8) chips, under a router over those 16, the
attention whole, and 18 992 of each table's 151 936 rows: every row a layer
routes lands here, 2L x 8 = 131 072, 8 192 an expert, what a chip of the
eight receives from all eight when the published 128-wide router balances.
Under the 128-wide router this chip's rows follow where the random router
sends a sequence's tokens, which swings by a factor of four from seed to
seed (the configuration's ``assumed``).  Published layers 1-4.

Departures from a training recipe, all in the configuration's ``assumed``:
float32 for bfloat16 mixed precision, plain Adam, uniform random tokens, four
layers of 48, a router over 16 experts, no balance loss, a block of 4 and the linear schedule on a
grid of 1/1024.  Departures of THIS code from the plainest form, to fit
beside the program's live state (5.5 GB of the chip's 16.9) and none
changing a value: the chain rule is applied LAYER BY LAYER (forward keeping
each layer's input, then each layer's ``jax.vjp`` in turn, last to first);
attention runs a query head and a block of queries at a time; the
experts are a sequential loop, each applied to every token under its weight
(0 where not chosen); the head and the loss run in blocks of rows;
gradients are kept for ``check_params`` only.
"""
from __future__ import annotations

import math

PREFIX = "sdar"

# What the training step is held to, on ONE seeded 8192-token sequence at the
# seeded weights (relative errors: |loss - ref| / |ref|, ||g - ref||_2 /
# ||ref||_2).  The program's products run at the TPU's default precision
# (one bfloat16 pass, float32 accumulation; the router's at HIGHEST) on
# float32 weights and activations, the reference's at 'highest'.  The choice
# of 8 of 16 is discontinuous, so the cell's check shows ``reference`` what
# the program's routers read (``build``'s ``check_fetches``; LFM2's hazard,
# ``configs/lfm2_8b_a1b.py``) and ``reference`` makes the CHOICE from it
# with its own router; everything continuous stays its own, and
# ``router_input_rel_tol`` holds how far its own router inputs lie from the
# ones shown.
#
# Measured on the chip (PERF.md section 6; the cut of 16 experts
# under their own router; every run its own seed), in percent.  SOUND,
# nine seeds: q_proj 1.67-2.15, k_proj 1.70-2.08, q_norm 1.64-2.03, k_norm
# 1.61-1.99, the fourth layer's q_proj 1.63-1.98 (the queries and keys pass
# QK-norm, rope and a softmax over up to 8196 keys: the most rounding),
# embed 1.34-1.61, v_proj 1.15-1.43, o_proj 1.16-1.41, the experts' gate
# 1.42-1.60, up 1.34-1.50, down 1.34-1.49, head 0.78-0.92, router
# 0.85-2.30 (a token whose 8th and 9th logits lie within the last bits
# moves a whole row); router inputs 0.80-0.85; loss 1.2e-6 to 2.1e-5.
# THE NEAREST PRECISION BELOW, bfloat16: {'lower': 'all'}, the reference
# computed in bfloat16 throughout, three seeds: q_proj 3.42-3.61, k_proj
# 3.37-3.61, q_norm 2.99-3.58, k_norm 3.13-3.42, the fourth layer's q_proj
# 2.81-3.03, embed 2.57-2.75, v_proj 2.22-2.33, o_proj 2.11-2.27, gate
# 2.33-2.45, up 2.14-2.34, down 2.12-2.32, head 1.28-1.41, router
# 2.29-3.32; router inputs 1.49-1.58; loss 4.6e-6 to 3.0e-5.
# LIMITS, where the two readings part: the geometric mean of the largest
# sound reading and the least bfloat16 one, so the room is the same ratio
# both ways (1.18-1.26): NOT correct by every gradient held and the router
# inputs on every seed.  Where they do not part, nothing is held: the
# router's gradient (its sound readings reach 2.30, the bfloat16 ones start
# at 2.29) is fetched and not held.  The loss takes the limit of the
# accepted decoder cells, 1.5e-4 (7.2 times the largest sound reading;
# the bfloat16 readings lie among the sound ones: a mean over 8192 tokens
# whose errors cancel).
ROUTER_MARGINS = (0.0005, 0.001, 0.002)
CHECKS = (
    {"name": "train", "is_test": False, "loss_rel_tol": 1.5e-4,
     "router_input_rel_tol": 0.0113,
     "grad_rel_tol": {"sdar.embed": 0.0203, "sdar.l0.q_proj": 0.027,
                      "sdar.l0.k_proj": 0.0265, "sdar.l0.v_proj": 0.0178,
                      "sdar.l0.o_proj": 0.0173, "sdar.l0.q_norm": 0.0246,
                      "sdar.l0.k_norm": 0.025,
                      "sdar.l1.experts_gate": 0.0193,
                      "sdar.l1.experts_up": 0.0179,
                      "sdar.l1.experts_down": 0.0178,
                      "sdar.l3.q_proj": 0.0236, "sdar.head": 0.0109}},
)


def _mask_id(sizes):
    """The mask token: the last row of the vocabulary slice."""
    return sizes["vocab_size"] - 1


def _model_kwargs(sizes):
    return dict(
        num_layers=sizes["num_hidden_layers"],
        hidden_size=sizes["hidden_size"],
        num_heads=sizes["num_attention_heads"],
        num_kv_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], num_experts=sizes["num_experts"],
        experts_per_tok=sizes["num_experts_per_tok"],
        expert_width=sizes["moe_intermediate_size"],
        renormalize=sizes["norm_topk_prob"],
        rope_theta=float(sizes["rope_theta"]), rms_eps=sizes["rms_norm_eps"],
        block=sizes["block_size"], grid=sizes["noise_grid"],
        recompute=sizes.get("recompute", False), prefix=PREFIX)


def build(mode, batch, sizes):
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    if mode != "train":
        raise ValueError("sdar_30b_a3b: only 'train' is built (serving by "
                         "blocks waits for the decode engine, ROADMAP C2)")
    if sizes["noise_schedule"] != "linear":
        raise ValueError("sdar_30b_a3b: the loss is the linear schedule's")
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    vocab, t_len = sizes["vocab_size"], sizes["seq_len"]
    blocks, grid = t_len // sizes["block_size"], sizes["noise_grid"]
    ids = layers.data("ids", shape=[t_len], dtype="int64")
    level = layers.data("level", shape=[blocks], dtype="int64")
    draw = layers.data("draw", shape=[t_len], dtype="int64")
    loss = models.sdar_moe_loss(ids, level, draw, vocab,
                                **_model_kwargs(sizes))
    pt.optimizer.Adam(sizes["optimizer"]["learning_rate"]).minimize(loss)
    feeds = {"ids": {"shape": [t_len], "dtype": "int64",
                     "high": _mask_id(sizes)},
             "level": {"shape": [blocks], "dtype": "int64", "high": grid},
             "draw": {"shape": [t_len], "dtype": "int64", "high": grid}}
    main = pt.default_main_program()
    # what each expert layer's router read in the check's step, by the
    # layer its router parameter names ('l0': ...): ``reference`` is shown
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
def _mask_entries(sizes):
    """(query, key) entries the block-diffusion mask allows a head of one
    sequence: L^2 + L * block (a noisy query sees its own block's noisy keys
    and the clean ones before it, a clean query the clean ones up to its
    own block's end)."""
    t = sizes["seq_len"]
    return t * t + t * sizes["block_size"]


def flops_per_item(sizes, mode):
    """FLOPs the mathematics needs per DATA token ON THIS CHIP, 2 per
    multiply-accumulate of every matrix product; training = 3x forward.  Per
    layer, for BOTH streams' tokens: the four projections, the router and
    the ``num_experts_per_tok`` experts of a token, three products each; the
    attention: the mask's L^2 + L * 4 entries a head and sequence, two
    products of the head size each (scores, context); the head over the
    noisy stream's tokens alone.  What a backward pass computes again is
    not counted.  Look-ups, norms, rope, softmax, the noise and Adam are not
    counted."""
    d, t = sizes["hidden_size"], sizes["seq_len"]
    heads, kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    per_stream_token = (
        d * heads * hd + 2 * d * kv * hd + heads * hd * d
        + d * sizes["num_experts"]
        + sizes["num_experts_per_tok"] * 3 * d
        * sizes["moe_intermediate_size"])
    attention = 2 * heads * hd * _mask_entries(sizes) / t
    macs = (sizes["num_hidden_layers"] * (2 * per_stream_token + attention)
            + d * sizes["vocab_size"])
    return 2.0 * macs * (3 if mode == "train" else 1)


def block_diffusion_attention_work(sizes, tokens):
    """(FLOPs, bytes) ``block_diffusion_attention`` of every layer run needs
    in a training step on ``tokens`` data tokens (tokens / L sequences, 2L
    positions each), in float32, whatever implements it: the mask's L^2 + L
    * block entries a query head, two products of the head size each
    forward (4 * D FLOPs an entry a head) and five backward (dV, dP, dQ,
    dK and the scores again: 2.5 times the forward).  Forward: q, k, v read
    and o written once; backward: q, k, v, o and the cotangent read, dq, dk,
    dv written once (the rows' logsumexp and delta, one float a row and
    head, both ways).  What a recomputed layer executes again is not
    counted.  Computing the tiles the mask empties is not work: a kernel
    that did would read lower."""
    t, heads = sizes["seq_len"], sizes["num_attention_heads"]
    kv, hd = sizes["num_key_value_heads"], sizes["head_dim"]
    sequences, positions = tokens / t, 2 * t
    forward = 4.0 * hd * heads * _mask_entries(sizes) * sequences
    q_side, kv_side = positions * heads * hd, positions * kv * hd
    rows = positions * heads
    bytes_ = 4.0 * sequences * (
        (q_side + 2 * kv_side + q_side + rows)              # forward
        + (3 * q_side + 2 * kv_side + 2 * rows)             # read backward
        + (q_side + 2 * kv_side))                           # written
    layers_ = sizes["num_hidden_layers"]
    return layers_ * 3.5 * forward, layers_ * bytes_


def moe_experts_work(sizes, tokens):
    """(FLOPs, bytes) the grouped products of ONE expert layer need in a
    training step on ``tokens`` data tokens: both streams are routed, so 2
    * tokens * num_experts_per_tok rows through the three stacks of the 16
    SiLU-gated experts: forward, gradient of the rows, gradient of the
    stack, nine products, each reading its two operands and writing its
    result once, in float32.  What a recomputed layer executes again is not
    counted."""
    rows = 2 * tokens * sizes["num_experts_per_tok"]
    d, h, e = (sizes["hidden_size"], sizes["moe_intermediate_size"],
               sizes["num_experts"])
    return (9 * 2.0 * rows * d * h,
            9 * 4.0 * (rows * d + rows * h + e * d * h))


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
LAYER_WEIGHTS = ("input_norm", "q_proj", "k_proj", "v_proj", "q_norm",
                 "k_norm", "o_proj", "post_norm", "router", "experts_gate",
                 "experts_up", "experts_down")
FAULTS = ("no_reweight", "own_clean_block", "causal_in_block")


def _rms(x, g, eps):
    import jax.numpy as jnp
    from jax import lax

    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(u, positions, theta):
    """u [B, T, H, d] at ``positions`` [T]: angle[t, i] = p_t * theta^(-2i/d),
    i < d/2; u' = u cos(aa) + concat(-u[d/2:], u[:d/2]) sin(aa), aa =
    concat(angle, angle)."""
    import jax.numpy as jnp

    d = u.shape[3]
    angle = positions.astype(jnp.float32)[:, None] * theta ** (
        -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-u[..., d // 2:], u[..., :d // 2]], axis=-1)
    return u * jnp.cos(angle).astype(u.dtype) \
        + turned * jnp.sin(angle).astype(u.dtype)


def _allowed(queries, keys, t_len, block, fault):
    """[queries, keys] bool of the mask at query positions ``queries`` and
    key positions ``keys`` of the 2L joined ones, written as its four
    rules.  ``fault`` 'own_clean_block' lets a noisy query see the clean
    keys of its OWN block (the answer); 'causal_in_block' lets a noisy query
    see only the noisy keys of its block up to itself."""
    import jax.numpy as jnp

    q_noisy, k_noisy = (queries < t_len)[:, None], (keys < t_len)[None, :]
    qb, kb = ((queries % t_len) // block)[:, None], \
        ((keys % t_len) // block)[None, :]
    noisy_noisy = kb == qb
    if fault == "causal_in_block":
        noisy_noisy = noisy_noisy & (keys[None, :] <= queries[:, None])
    noisy_clean = kb <= qb if fault == "own_clean_block" else kb < qb
    return jnp.where(q_noisy, jnp.where(k_noisy, noisy_noisy, noisy_clean),
                     jnp.logical_not(k_noisy) & (kb <= qb))


def _attention(q, k, v, sizes, fault, rows=1024):
    """Softmax attention of q [B, 2L, H, D] over k, v [B, 2L, H_kv, D] at
    1/sqrt(D) under the block-diffusion mask, one query head and ``rows``
    queries at a time (the dense scores of a head are [2L, 2L])."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, positions, heads, d = q.shape
    group = heads // k.shape[2]
    t_len, block = sizes["seq_len"], sizes["block_size"]
    rows = math.gcd(rows, positions)
    keys = jnp.arange(positions)

    def one_head(args):
        qh, h = args                                       # [B, 2L, D]
        kh, vh = k[:, :, h // group], v[:, :, h // group]

        @jax.checkpoint
        def some_rows(at):
            qr = lax.dynamic_slice_in_dim(qh, at * rows, rows, axis=1)
            s = jnp.einsum("btd,bsd->bts", qr, kh) / math.sqrt(d)
            allowed = _allowed(at * rows + jnp.arange(rows), keys, t_len,
                               block, fault)
            p = jax.nn.softmax(jnp.where(allowed[None], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("bts,bsd->btd", p, vh)

        out = lax.map(some_rows, jnp.arange(positions // rows))
        return jnp.moveaxis(out, 0, 1).reshape(b, positions, vh.shape[-1])

    out = lax.map(one_head, (jnp.moveaxis(q, 2, 0), jnp.arange(heads)))
    return jnp.moveaxis(out, 0, 2)


def _gated(m, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(m @ w_gate) * (m @ w_up)) @ w_down


def _experts(m, weight, w_gate, w_up, w_down):
    """sum over the experts of weight[:, e] * (silu(m Wg[e]) * m Wu[e])
    Wd[e] for m [N, D] and weight [N, experts] (the token's renormalised
    probability where expert e is among its chosen, else 0): every expert
    on every token, in turn."""
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
    """(the expert layer's output [B, 2L, D], what its router saw).
    ``routed_by`` [B, 2L, D]: what the program's router read at this layer
    in the same step.  The CHOICE of experts (discrete, no gradient) is then
    made from it, with this code's own router; probabilities, weights and
    everything continuous stay this code's own."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, positions, d = n.shape
    top_k = sizes["num_experts_per_tok"]

    def logits(rows):                                        # [N, 16]
        return (rows @ w["router"]).astype(jnp.float32)

    def choice(logit):
        best = lax.top_k(lax.stop_gradient(logit), top_k + 1)[0]
        return (logit >= best[:, top_k - 1:top_k],           # [N, 16] 0/1
                best[:, top_k - 1] - best[:, top_k])

    n2 = n.reshape(b * positions, d)
    logit = logits(n2)
    chosen, gap = choice(logit)
    saw = {f"under_{margin}": jnp.sum(gap < margin)
           for margin in ROUTER_MARGINS}
    if routed_by is not None:
        shown = lax.stop_gradient(routed_by.reshape(b * positions, d))
        own, chosen = chosen, choice(logits(shown))[0]
        saw["tokens_routed_otherwise"] = jnp.sum(jnp.any(own != chosen, -1))
        saw["input_rel_err"] = (
            jnp.linalg.norm((shown - n2).astype(jnp.float32))
            / jnp.linalg.norm(n2.astype(jnp.float32)))
    weight = jnp.where(chosen, jax.nn.softmax(logit, axis=-1), 0.0)
    if sizes["norm_topk_prob"]:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    y = _experts(n2, weight.astype(n.dtype), w["experts_gate"],
                 w["experts_up"], w["experts_down"])
    return y.reshape(b, positions, d), lax.stop_gradient(saw)


def _layer(x, w, sizes, fault=None, routed_by=None):
    """(one layer's output, what its router saw) for x [B, 2L, D]; ``w`` the
    layer's weights by their short names."""
    import jax.numpy as jnp

    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    b, positions, _ = x.shape
    heads, kv, hd = (sizes["num_attention_heads"],
                     sizes["num_key_value_heads"], sizes["head_dim"])
    at = jnp.arange(positions) % sizes["seq_len"]           # p(j)
    n = _rms(x, w["input_norm"], eps)
    q = _rope(_rms((n @ w["q_proj"]).reshape(b, positions, heads, hd),
                   w["q_norm"], eps), at, theta)
    k = _rope(_rms((n @ w["k_proj"]).reshape(b, positions, kv, hd),
                   w["k_norm"], eps), at, theta)
    v = (n @ w["v_proj"]).reshape(b, positions, kv, hd)
    o = _attention(q, k, v, sizes, fault)
    x = x + o.reshape(b, positions, heads * hd) @ w["o_proj"]
    o, saw = _expert_ffn(_rms(x, w["post_norm"], eps), w, sizes, routed_by)
    return x + o, saw


def _noise(feeds, sizes):
    """(x_t [B, L], the loss weight [B, L]) from the clean ids, the levels
    and the draws: t_b = (d_b + 1) / grid, token i masked where u_i <=
    d_{i // block}."""
    import jax.numpy as jnp

    d = jnp.repeat(jnp.asarray(feeds["level"]), sizes["block_size"], axis=1)
    masked = jnp.asarray(feeds["draw"]) <= d
    t = (d + 1).astype(jnp.float32) / sizes["noise_grid"]
    ids = jnp.asarray(feeds["ids"])
    return (jnp.where(masked, _mask_id(sizes), ids),
            jnp.where(masked, 1.0 / t, 0.0))


def _head_loss(x, head, final_norm, labels, weight, sizes, rows=512):
    """(1 / N) sum_i weight_i * -log softmax(rms(x_i) head)[label_i] for the
    noisy stream x [B, L, D], in blocks of ``rows`` rows."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    x = _rms(x, final_norm, sizes["rms_norm_eps"]).reshape(-1, x.shape[-1])
    labels, weight = labels.reshape(-1), weight.reshape(-1)
    n = x.shape[0]
    rows = math.gcd(rows, n)

    @jax.checkpoint
    def block(xlw):
        xb, lb, wb = xlw
        logp = jax.nn.log_softmax((xb @ head).astype(jnp.float32), axis=-1)
        return -jnp.sum(wb * jnp.take_along_axis(logp, lb[:, None],
                                                 axis=-1)[:, 0])

    return jnp.sum(lax.map(block, (x.reshape(n // rows, rows, -1),
                                   labels.reshape(n // rows, rows),
                                   weight.reshape(n // rows, rows)))) / n


def _parameter_names(sizes):
    return [f"{PREFIX}.embed", f"{PREFIX}.final_norm", f"{PREFIX}.head"] + [
        f"{PREFIX}.l{i}.{n}" for i in range(sizes["num_hidden_layers"])
        for n in LAYER_WEIGHTS]


def reference(mode, params, feeds, sizes, frozen_stats=False, observed=None,
              control=None):
    """'loss': the training loss, forward only.  'train': (loss, {name:
    gradient} for ``sizes['check_params']``, what the routers saw).  float32
    throughout, matmul precision 'highest' (``frozen_stats`` changes
    nothing: there are no batch statistics).  ``params`` are host arrays
    (they also hold the optimizer's moments); a layer's weights are on the
    device while that layer runs, forward or backward, and no longer.

    ``observed`` {layer: [2B, L, D]} (``build``'s ``check_fetches``, fetched
    from the program's own step, a sequence's two streams side by side):
    the choice of experts follows what the program's routers read
    (``_expert_ffn``).  The third result says, layer by layer, how far that
    lies from this code's own router input (``input_rel_err``, held by
    ``CHECKS``), how many tokens it routed otherwise, the tokens whose 8th /
    9th router logits lie within each of ``ROUTER_MARGINS``; then the
    tokens routed, the rows they make and the tokens masked.

    ``control`` (``--set control=...``; never in a measured run) makes this
    a control that the check has to FAIL: {'lower': 'all'} computes
    everything here in bfloat16, weights, activations and scores (the
    nearest precision below the float32 the configuration states);
    {'fault': 'no_reweight'} weighs a masked token's loss 1 for 1 / t_b,
    {'fault': 'own_clean_block'} lets a noisy query see its own block's
    clean keys, {'fault': 'causal_in_block'} a noisy query only the noisy
    keys of its block up to itself; {'sizes': {...}} is laid over ``sizes``
    (another rope base, weights not renormalised)."""
    import jax
    import jax.numpy as jnp

    if mode not in ("train", "loss"):
        raise ValueError("sdar_30b_a3b: only training has a reference")
    control = control or {}
    sizes = {**sizes, **control.get("sizes", {})}
    fault, lower = control.get("fault"), control.get("lower")
    if lower not in (None, "all") or fault not in (None,) + FAULTS:
        raise ValueError(f"sdar_30b_a3b: no control {control!r}")
    dtype = jnp.bfloat16 if lower == "all" else jnp.float32
    layers_ = sizes["num_hidden_layers"]

    def put(name):
        return jnp.asarray(params[name], jnp.float32).astype(dtype)

    def weights(i):
        return {n: put(f"{PREFIX}.l{i}.{n}") for n in LAYER_WEIGHTS}

    def shown(i):
        if not observed or f"l{i}" not in observed:
            return None
        seen = jnp.asarray(observed[f"l{i}"], jnp.float32).astype(dtype)
        return seen.reshape(-1, 2 * seen.shape[1], seen.shape[2])

    noisy, loss_weight = _noise(feeds, sizes)
    if fault == "no_reweight":
        loss_weight = jnp.where(loss_weight > 0, 1.0, 0.0)
    ids = jnp.asarray(feeds["ids"])
    t_len = ids.shape[1]
    table, final_norm, head_w = (put(f"{PREFIX}.{n}")
                                 for n in ("embed", "final_norm", "head"))
    run = jax.jit(lambda x, w, routed_by: _layer(x, w, sizes, fault,
                                                 routed_by))
    back = jax.jit(lambda x, w, routed_by, ct: jax.vjp(
        lambda x, w: _layer(x, w, sizes, fault, routed_by)[0], x, w)[1](ct))

    def head(x, head_w, g):
        return _head_loss(x[:, :t_len], head_w, g, ids, loss_weight, sizes)

    with jax.default_matmul_precision("highest"):
        joined = jnp.concatenate([noisy, ids], axis=1)       # [B, 2L]
        x, inputs, saw = table[joined], [], {}
        for i in range(layers_):
            inputs.append(x)
            x, saw[f"l{i}"] = run(x, weights(i), shown(i))
        if mode == "loss":
            return jax.jit(head)(x, head_w, final_norm)
        loss, (ct, d_head, d_final) = jax.jit(jax.value_and_grad(
            head, argnums=(0, 1, 2)))(x, head_w, final_norm)
        held = set(sizes["check_params"])
        grads = {name: g for name, g in (
            (f"{PREFIX}.head", d_head), (f"{PREFIX}.final_norm", d_final))
            if name in held}
        for i in reversed(range(layers_)):
            ct, d_w = back(inputs.pop(), weights(i), shown(i), ct)
            grads.update({f"{PREFIX}.l{i}.{n}": g for n, g in d_w.items()
                          if f"{PREFIX}.l{i}.{n}" in held})
        if f"{PREFIX}.embed" in held:
            # the look-up's gradient, both streams: the head has its own
            grads[f"{PREFIX}.embed"] = jnp.zeros_like(table).at[
                joined.reshape(-1)].add(ct.reshape(-1, ct.shape[-1]))
    routed = int(joined.size)
    saw = {layer: {k: float(v) if k == "input_rel_err" else int(v)
                   for k, v in seen.items()} for layer, seen in saw.items()}
    saw["tokens"] = routed
    saw["tokens_masked"] = int(jnp.sum(noisy == _mask_id(sizes)))
    saw["rows"] = routed * sizes["num_experts_per_tok"]
    return loss.astype(jnp.float32), {
        n: g.astype(jnp.float32) for n, g in grads.items()}, saw
