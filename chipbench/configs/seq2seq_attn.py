"""GRU encoder/decoder with attention: the program under test (through the
public layers and models API) and its plain float32 reference.

``reference`` computes what ``models.seq2seq_attention`` declares — a
one-direction GRU encoder over the projected source embeddings, a decoder
GRU started from tanh(W h_last + b), dot-product attention of the decoder
state against a learned projection of the encoder states, a softmax over
the target dictionary at every step, teacher forcing, and the mean of
-log p(label) over all target positions — written from the equations, with
no code shared with ``paddle_tpu``.  Gate layout of every 3H block is
[update | reset | candidate]; h' = u*h + (1-u)*c.  Positions past a
sentence's length hold the encoder state and emit zeros, as a padded batch
has to; the loss averages over every position of the padded target.
"""
from __future__ import annotations

PREFIX = "s2s"

# What the training step is held to, on a seeded sample batch at the seeded
# weights (relative errors: |loss - ref| / |ref|, ||g - ref||_2 / ||ref||_2).
# The program computes in float32 with the TPU's default matmul precision
# (one bfloat16 pass, float32 accumulation); the reference at 'highest'.
# Measured on the chip (PR 22, 13 runs, each with its own seed): loss
# 2.8e-7 to 4.6e-7 (an average of 480 terms in float32); the three
# gradients 0.45 % to 0.51 % (60 recurrent steps of single-pass products).  The gradient bounds are three
# times that; the loss bound leaves room for another seed and is still a
# tenth of what bfloat16 activations would give, so an 8-bit or 16-bit
# format, or a dropped term of the loss or of a gradient, fails.
CHECKS = (
    {"name": "train", "is_test": False, "loss_rel_tol": 1e-4,
     "grad_rel_tol": {"s2s.src_emb": 0.015, "s2s.dec_gru_w": 0.015,
                      "s2s.dec_out_w": 0.015}},
)
WINDOW_LOSS_REL_TOL = 1e-4


def build(mode, batch, sizes):
    import paddle_tpu as pt
    from paddle_tpu import layers, models

    if mode != "train":
        raise ValueError("seq2seq_attn: only 'train' is built (beam search "
                         "serving is a later cell)")
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    sv, tv = sizes["src_vocab_size"], sizes["tgt_vocab_size"]
    src = layers.data("src", shape=[], dtype="int64", lod_level=1)
    tgt = layers.data("tgt", shape=[], dtype="int64", lod_level=1)
    lbl = layers.data("lbl", shape=[], dtype="int64", lod_level=1)
    probs = models.seq2seq_attention(
        src, tgt, sv, tv, emb_dim=sizes["embedding_dim"],
        hidden_dim=sizes["hidden_dim"], prefix=PREFIX)
    loss = layers.mean(layers.cross_entropy(
        layers.reshape(probs, [-1, tv]), layers.reshape(lbl, [-1, 1])))
    pt.optimizer.Adam(sizes["optimizer"]["learning_rate"]).minimize(loss)
    s_len, t_len = sizes["src_len"], sizes["tgt_len"]
    feeds = {
        "src": {"shape": [s_len], "dtype": "int64", "high": sv},
        "src@LEN": {"shape": [], "dtype": "int64", "fill": s_len},
        "tgt": {"shape": [t_len], "dtype": "int64", "high": tv},
        "tgt@LEN": {"shape": [], "dtype": "int64", "fill": t_len},
        "lbl": {"shape": [t_len], "dtype": "int64", "high": tv},
        "lbl@LEN": {"shape": [], "dtype": "int64", "fill": t_len},
    }
    return {"main": pt.default_main_program(),
            "startup": pt.default_startup_program(),
            "feeds": feeds, "output": probs.name, "loss": loss.name,
            "amp": sizes["compute_dtype"] == "bfloat16", "items_per_example": s_len + t_len}


def flops_per_item(sizes, mode):
    """FLOPs the mathematics needs per token (source + target), 2 per
    multiply-accumulate of every matrix product; training = 3x forward.
    Embedding look-ups, gates' elementwise work, softmax and Adam are not
    counted."""
    e, h = sizes["embedding_dim"], sizes["hidden_dim"]
    s, t, v = sizes["src_len"], sizes["tgt_len"], sizes["tgt_vocab_size"]
    per_src = e * 3 * h + h * 3 * h + h * h      # projection, GRU, att proj
    per_tgt = (2 * s * h                          # scores and context
               + e * 3 * h + h * 3 * h            # gates from token, context
               + h * 3 * h                        # GRU recurrence
               + h * v)                           # dictionary head
    macs = s * per_src + t * per_tgt + h * h      # + decoder start state
    return 2.0 * macs * (3 if mode == "train" else 1) / (s + t)


def _gru_step(x, h, w, b, hi):
    import jax
    import jax.numpy as jnp

    hd = h.shape[-1]
    ur = jax.nn.sigmoid(x[:, :2 * hd]
                        + jnp.dot(h, w[:, :2 * hd], precision=hi)
                        + b[:2 * hd])
    u, r = ur[:, :hd], ur[:, hd:]
    c = jnp.tanh(x[:, 2 * hd:]
                 + jnp.dot(r * h, w[:, 2 * hd:], precision=hi) + b[2 * hd:])
    return u * h + (1.0 - u) * c


def _loss(p, feeds):
    import jax
    import jax.numpy as jnp
    from jax import lax

    hi = lax.Precision.HIGHEST

    def w(name):
        return p[f"{PREFIX}.{name}"]

    src, tgt, lbl = feeds["src"], feeds["tgt"], feeds["lbl"]
    src_len = feeds["src@LEN"]
    b_sz, s_len = src.shape
    hd = w("enc_gru_w").shape[0]

    # encoder
    x = jnp.dot(w("src_emb")[src], w("enc_proj_w"), precision=hi) \
        + w("enc_proj_b")
    valid = (jnp.arange(s_len)[None, :] < src_len[:, None]) \
        .astype(jnp.float32)

    def enc_step(h, inp):
        xt, mt = inp
        h_new = _gru_step(xt, h, w("enc_gru_w"), w("enc_gru_b").reshape(-1),
                          hi)
        h_new = mt[:, None] * h_new + (1.0 - mt[:, None]) * h
        return h_new, h_new * mt[:, None]

    h_last, enc = lax.scan(enc_step, jnp.zeros((b_sz, hd), jnp.float32),
                           (jnp.swapaxes(x, 0, 1), valid.T))
    enc = jnp.swapaxes(enc, 0, 1)                                # [B,S,H]
    enc_proj = jnp.dot(enc, w("att_proj_w"), precision=hi)
    state0 = jnp.tanh(jnp.dot(h_last, w("dec_init_w"), precision=hi)
                      + w("dec_init_b"))

    # decoder, teacher forced
    def dec_step(state, tok_emb):
        scores = jnp.einsum("bsh,bh->bs", enc_proj, state, precision=hi)
        ctx = jnp.einsum("bs,bsh->bh", jax.nn.softmax(scores, axis=-1), enc,
                         precision=hi)
        gates = (jnp.dot(tok_emb, w("dec_gates_w_emb"), precision=hi)
                 + jnp.dot(ctx, w("dec_gates_w_ctx"), precision=hi)
                 + w("dec_gates_b"))
        state = _gru_step(gates, state, w("dec_gru_w"),
                          w("dec_gru_b").reshape(-1), hi)
        logits = jnp.dot(state, w("dec_out_w"), precision=hi) \
            + w("dec_out_b")
        return state, jax.nn.log_softmax(logits, axis=-1)

    _, logp = lax.scan(dec_step, state0,
                       jnp.swapaxes(w("tgt_emb")[tgt], 0, 1))
    logp = jnp.swapaxes(logp, 0, 1)                              # [B,T,V]
    return -jnp.mean(jnp.take_along_axis(logp, lbl[..., None], axis=-1))


def reference(mode, params, feeds, sizes, frozen_stats=False):
    """'loss': the training loss, forward only.  'train': (loss, {name:
    gradient}) for ``sizes['check_params']``.  float32 throughout, matmul
    precision 'highest' (``frozen_stats`` changes nothing: there are no
    batch statistics)."""
    import jax
    import jax.numpy as jnp

    if mode not in ("train", "loss"):
        raise ValueError("seq2seq_attn: only training has a reference")
    params = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    feeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    with jax.default_matmul_precision("highest"):
        if mode == "loss":
            return jax.jit(_loss)(params, feeds)
        wrt = {k: params[k] for k in sizes["check_params"]}
        rest = {k: v for k, v in params.items() if k not in wrt}
        return jax.jit(jax.value_and_grad(
            lambda wrt, rest, f: _loss({**rest, **wrt}, f)))(wrt, rest, feeds)
