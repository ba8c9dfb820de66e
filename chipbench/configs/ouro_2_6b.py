"""Ouro-2.6B (ByteDance, "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741; ``model_type: ouro``) cut to one chip by depth:
the program under test (``models.ouro_loss`` through the public layers API,
Adam) and its plain float32 reference.

``reference`` is written from the model's equations and shares no code with
``paddle_tpu``.  rms(x; g) = x * rsqrt(mean(x^2, -1) + eps) * g.  With L
layers, R = ``total_ut_steps`` passes and no bias in any projection:

    h(0) = Emb[ids]
    for t = 1..R:                      # the SAME L layers and weights
        u = h(t-1)
        for l = 1..L:
            a  = rms(u; g1_l);  q, k, v = a Wq_l, a Wk_l, a Wv_l
            rope on q and k, per head of size d: angle[s, i] = s * theta^(-2i/d),
                i < d/2;  x' = x cos(aa) + concat(-x[d/2:], x[:d/2]) sin(aa),
                aa = concat(angle, angle)
            u1 = u  + rms(softmax(q k^T / sqrt(d) + causal mask) v Wo_l; g2_l)
            m  = rms(u1; g3_l)
            u  = u1 + rms((silu(m Wg_l) * (m Wu_l)) Wd_l; g4_l)
        h(t) = rms(u; g_f)             # closes every pass, feeds the next
        CE(t) = -log softmax(h(t) W_head)[label]          (one head weight)
        lam(t) = sigmoid(h(t) w_gate + b_gate)            (one number a token)
    p(1) = lam(1);  p(t) = lam(t) prod_{j<t}(1 - lam(j)), t < R;
    p(R) = prod_{j<R}(1 - lam(j))
    loss = mean_tokens[ sum_t p(t) CE(t) + beta sum_t p(t) log p(t) ]

Departures from the published training recipe, all in the configuration's
``assumed``: float32 for bfloat16 mixed precision, plain Adam for AdamW,
uniform random tokens and labels, 8 layers of 48, the stage-I objective
with beta 0.05.  Departures of THIS code from the plainest form, all of
them to fit beside 7.35 GB of live program state and 1.5 GB of loaded
executables on the same chip and none changing a value: the passes are a
``lax.scan`` whose body holds the L layers once (it also keeps the compile
short), every pass and, inside it, every layer is recomputed in the
backward pass, attention runs head by head and the heads' cross-entropy in
blocks of rows, both recomputed likewise; gradients are taken for
``check_params`` only.  Bytes on the chip, compiled for a described v5e
before the first call: this reference's copy of the weights 2.45 GB, the
six gradients 0.87 (two of them 403 MB tables), temporaries 2.16 (3.76
with the passes' layer inputs kept), code 0.41: 5.89 beside 8.8 of 16.9.
"""
from __future__ import annotations

PREFIX = "ouro"

# What the training step is held to, on ONE seeded 4096-token sequence at
# the seeded weights (relative errors: |loss - ref| / |ref|,
# ||g - ref||_2 / ||ref||_2).  The program's products run at the TPU's
# default precision (one bfloat16 pass, float32 accumulation), the
# reference's at 'highest'.  There is no discontinuity here (no top-k, no
# routing): what separates the two is rounding alone, compounded by 32
# layer applications in a row.  Measured on the chip (PR 32, PERF.md
# section 6; 15 runs, each its own seed): loss 5.3e-7 to 1.8e-5; embedding
# 1.0-3.2 %, wq 1.6-3.0 %, w_down 1.1-3.5 %, the norm scale 1.0-3.4 %, the
# exit gate 0.7-2.2 %, head 1.0-2.1 % (the six move together from seed to
# seed, by a factor of three).  THIS reference with every product at one bfloat16 pass
# against itself at 'highest' reads the same (loss 1.4e-6 / 3.4e-6,
# gradients 0.8-1.9 %, two seeds), and with bfloat16 WEIGHTS AND
# ACTIVATIONS hardly more (loss 4.1e-6 / 9.2e-6, gradients 1.0-2.4 %):
# single-pass products are what any implementation of this model pays at a
# seeded start, and 16-bit storage adds a quarter to it.  So no bound can
# lie between the program's largest reading and the bfloat16 reading (the
# second is under the first), and the bounds are set as OLMoE's were.
# Gradients: 3.4 times the largest seen of any of them (3.53 %; fresh
# seeds read higher), said plainly: they hold the structure of each
# gradient (a dropped term, a transposed weight, a gradient that misses one
# pass's share of a shared weight are errors of a quarter and more) and do
# NOT tell float32 from bfloat16.  Loss: the bound of the
# harness's accepted decoder cell, 1.5e-4, 8 times the largest error seen
# and a thirtieth of the entropy term (beta * H(p) / loss = 0.05 * 1.0 /
# 10.8 = 4.6e-3 at a seeded start), so a dropped term of the loss fails;
# 16-bit activations do not (measured above, not the 1e-3 ISSUE 32 hoped
# for).  In the CPU rehearsal both sides are true float32 and agree to
# 1e-6.
CHECKS = (
    {"name": "train", "is_test": False, "loss_rel_tol": 1.5e-4,
     "grad_rel_tol": {"ouro.embed": 0.12, "ouro.l0.wq": 0.12,
                      "ouro.l0.w_down": 0.12, "ouro.l0.attn_out_norm": 0.12,
                      "ouro.exit_gate": 0.12, "ouro.head": 0.12}},
)
WINDOW_LOSS_REL_TOL = 1.5e-4


def build(mode, batch, sizes):
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    if mode != "train":
        raise ValueError("ouro_2_6b: only 'train' is built (serving with "
                         "early exit waits for the decode cache, ROADMAP B5)")
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    vocab, t_len = sizes["vocab_size"], sizes["seq_len"]
    ids = layers.data("ids", shape=[t_len], dtype="int64")
    lbl = layers.data("lbl", shape=[t_len], dtype="int64")
    loss, _ = models.ouro_loss(
        ids, lbl, vocab, hidden_size=sizes["hidden_size"],
        num_layers=sizes["num_hidden_layers"],
        num_heads=sizes["num_attention_heads"],
        ffn_size=sizes["intermediate_size"],
        total_ut_steps=sizes["total_ut_steps"],
        rope_theta=sizes["rope_theta"], rms_eps=sizes["rms_norm_eps"],
        prefix=PREFIX, beta=sizes["exit_entropy_beta"])
    pt.optimizer.Adam(sizes["optimizer"]["learning_rate"]).minimize(loss)
    feeds = {"ids": {"shape": [t_len], "dtype": "int64", "high": vocab},
             "lbl": {"shape": [t_len], "dtype": "int64", "high": vocab}}
    return {"main": pt.default_main_program(),
            "startup": pt.default_startup_program(),
            "feeds": feeds, "loss": loss.name,
            "amp": sizes["compute_dtype"] == "bfloat16",
            "items_per_example": t_len}


# ---------------------------------------------------------------------------
# operations, from the sizes
# ---------------------------------------------------------------------------
def flops_per_item(sizes, mode):
    """FLOPs the mathematics needs per token, 2 per multiply-accumulate of
    every matrix product; training = 3x forward.  ``total_ut_steps`` x
    ``num_hidden_layers`` layer applications (the four attention
    projections, causal scores and context at T/2 keys a query on average,
    the three feed-forward products) and ``total_ut_steps`` heads.  What
    the backward pass computes AGAIN is not counted: ``mfu`` is a share of
    the whole step that recomputation lowers.  Look-ups, norms, rope,
    softmax, the exit gate and Adam are not counted."""
    d, f, t = sizes["hidden_size"], sizes["intermediate_size"], sizes["seq_len"]
    per_layer = 4 * d * d + 3 * d * f + 2 * (t / 2) * d
    passes = sizes["total_ut_steps"]
    macs = passes * (sizes["num_hidden_layers"] * per_layer
                     + d * sizes["vocab_size"])
    return 2.0 * macs * (3 if mode == "train" else 1)


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------
def _rms(x, g, eps):
    import jax.numpy as jnp
    from jax import lax

    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [B, T, H, d]."""
    import jax.numpy as jnp

    t_len, d = x.shape[1], x.shape[3]
    angle = jnp.arange(t_len, dtype=jnp.float32)[:, None] * theta ** (
        -2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return (x * jnp.cos(angle) + turned * jnp.sin(angle)).astype(x.dtype)


def _attention(q, k, v):
    """Causal softmax attention, q k v [B, T, H, d], one head at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    t_len, d = q.shape[1], q.shape[3]
    mask = jnp.tril(jnp.ones((t_len, t_len), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv                                     # [B, T, d]
        s = jnp.einsum("btd,bsd->bts", qh, kh,
                       preferred_element_type=jnp.float32) / jnp.sqrt(
            jnp.float32(d))
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", p.astype(vh.dtype), vh)

    heads_first = tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v))
    return jnp.moveaxis(lax.map(one_head, heads_first), 0, 2)


def _layer(u, w, sizes):
    """One sandwich-normed layer on u [B, T, D]; ``w`` its ten weights."""
    import jax

    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    b, t_len, d = u.shape
    split = (b, t_len, sizes["num_attention_heads"],
             d // sizes["num_attention_heads"])
    a = _rms(u, w["input_norm"], eps)
    o = _attention(_rope((a @ w["wq"]).reshape(split), theta),
                   _rope((a @ w["wk"]).reshape(split), theta),
                   (a @ w["wv"]).reshape(split))
    u = u + _rms(o.reshape(b, t_len, d) @ w["wo"], w["attn_out_norm"], eps)
    m = _rms(u, w["ffn_in_norm"], eps)
    y = (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]
    return u + _rms(y, w["ffn_out_norm"], eps)


def _token_cross_entropy(x, w_head, labels, rows=512):
    """-log softmax(x_n W_head)[label_n] for every row of x [N, D], in
    float32, in blocks of ``rows`` rows (all N at once are 0.8 GB here)."""
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
        logp = jax.nn.log_softmax((xb @ w_head).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    return lax.map(block, (x.reshape(n // rows, rows, -1),
                           labels.reshape(n // rows, rows))).reshape(n)


LAYER_WEIGHTS = ("input_norm", "wq", "wk", "wv", "wo", "attn_out_norm",
                 "ffn_in_norm", "w_gate", "w_up", "w_down", "ffn_out_norm")


def _loss(p, feeds, sizes, dtype=None):
    """The training loss; ``dtype`` (for the reading that sets ``CHECKS``:
    what 16-bit weights and activations would give) casts the weights and
    with them every activation; norms, softmax and the loss stay float32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if dtype is not None:
        p = {k: v.astype(dtype) for k, v in p.items()}
    eps, beta = sizes["rms_norm_eps"], sizes["exit_entropy_beta"]
    passes, n_layers = sizes["total_ut_steps"], sizes["num_hidden_layers"]
    ids, labels = feeds["ids"], feeds["lbl"]
    layer_weights = [{n: p[f"{PREFIX}.l{i}.{n}"] for n in LAYER_WEIGHTS}
                     for i in range(n_layers)]
    one_layer = jax.checkpoint(lambda u, w: _layer(u, w, sizes))

    @jax.checkpoint
    def one_pass(h, _):
        u = h
        for w in layer_weights:
            u = one_layer(u, w)
        h = _rms(u, p[f"{PREFIX}.final_norm"], eps)
        return h, h

    _, hs = lax.scan(one_pass, p[f"{PREFIX}.embed"][ids], None, length=passes)
    hs = hs.reshape(passes, -1, hs.shape[-1])                # [R, N, D]
    ce = jnp.stack([_token_cross_entropy(hs[t], p[f"{PREFIX}.head"],
                                         labels.reshape(-1))
                    for t in range(passes)])                 # [R, N]
    lam = jax.nn.sigmoid(
        (hs[:-1] @ p[f"{PREFIX}.exit_gate"]).astype(jnp.float32)[..., 0]
        + p[f"{PREFIX}.exit_gate_bias"].astype(jnp.float32))  # [R-1, N]
    stay = jnp.cumprod(1.0 - lam, axis=0)       # prod_{j<=t} (1 - lam(j))
    exit_p = jnp.concatenate(
        [lam[:1], lam[1:] * stay[:-1], stay[-1:]], axis=0)   # [R, N]
    neg_entropy = exit_p * jnp.log(jnp.maximum(exit_p, 1e-30))
    return jnp.mean(jnp.sum(exit_p * ce + beta * neg_entropy, axis=0))


def _parameter_names(sizes):
    return [f"{PREFIX}.{n}" for n in ("embed", "final_norm", "head",
                                      "exit_gate", "exit_gate_bias")] + [
        f"{PREFIX}.l{i}.{n}" for i in range(sizes["num_hidden_layers"])
        for n in LAYER_WEIGHTS]


def reference(mode, params, feeds, sizes, frozen_stats=False):
    """'loss': the training loss, forward only.  'train': (loss, {name:
    gradient}) for ``sizes['check_params']``.  float32 throughout, matmul
    precision 'highest' (``frozen_stats`` changes nothing: there are no
    batch statistics).  Only the model's own parameters are put on the
    device: ``params`` also holds the optimizer's moments."""
    import jax
    import jax.numpy as jnp

    if mode not in ("train", "loss"):
        raise ValueError("ouro_2_6b: only training has a reference")
    params = {k: jnp.asarray(params[k], jnp.float32)
              for k in _parameter_names(sizes)}
    feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    with jax.default_matmul_precision("highest"):
        if mode == "loss":
            return jax.jit(lambda p, f: _loss(p, f, sizes))(params, feeds)
        wrt = {k: params.pop(k) for k in sizes["check_params"]}
        return jax.jit(jax.value_and_grad(
            lambda wrt, rest, f: _loss({**rest, **wrt}, f, sizes)))(
                wrt, params, feeds)
