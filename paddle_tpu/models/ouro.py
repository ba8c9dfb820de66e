"""Ouro: a looped language model (ByteDance, "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741; ``model_type: ouro``).

ONE stack of decoder layers is run ``total_ut_steps`` times with one set of
weights (``layers.Repeat``: the layers stand once in the Program).  A layer
is sandwich-normed: RMSNorm on the way into AND out of causal attention
(rotary positions, no QK-norm) and the SiLU-gated feed-forward, each with
a residual; no bias in any projection.  The final RMSNorm closes every
pass, and the next pass starts from its output.  After every pass the same
untied head gives logits and an exit gate (``Linear(hidden, 1)``, sigmoid)
gives the probability of stopping there.

Every layer, every pass, is a ``layers.recompute`` stretch: a backward pass
keeps each layer's input and computes the rest again, or the activations of
``total_ut_steps`` x ``num_layers`` layer applications would not fit beside
the optimizer's state at the published widths.
"""
from __future__ import annotations

from .. import layers
from ..param_attr import ParamAttr


def _p(prefix, name):
    return ParamAttr(name=f"{prefix}.{name}")


def _layer(x, hidden_size, num_heads, ffn_size, rope_theta, rms_eps, prefix):
    """x + norm(attention(norm(x))), then + norm(ffn(norm(.))), x [B, T, D]."""
    seq_len = x.shape[1]
    heads = [-1, seq_len, num_heads, hidden_size // num_heads]

    def proj(inp, name, size=hidden_size):
        return layers.fc(inp, size=size, num_flatten_dims=2,
                         param_attr=_p(prefix, name), bias_attr=False)

    a = layers.rms_norm(x, rms_eps, _p(prefix, "input_norm"))
    q = layers.rope(layers.reshape(proj(a, "wq"), heads), rope_theta)
    k = layers.rope(layers.reshape(proj(a, "wk"), heads), rope_theta)
    o = layers.flash_attention(q, k, layers.reshape(proj(a, "wv"), heads),
                               causal=True)
    o = proj(layers.reshape(o, [-1, seq_len, hidden_size]), "wo")
    x = layers.elementwise_add(
        x, layers.rms_norm(o, rms_eps, _p(prefix, "attn_out_norm")))

    m = layers.rms_norm(x, rms_eps, _p(prefix, "ffn_in_norm"))
    y = proj(layers.elementwise_mul(
        layers.silu(proj(m, "w_gate", ffn_size)), proj(m, "w_up", ffn_size)),
        "w_down")
    return layers.elementwise_add(
        x, layers.rms_norm(y, rms_eps, _p(prefix, "ffn_out_norm")))


def _hidden_states(ids, vocab_size, hidden_size, num_layers, num_heads,
                   ffn_size, total_ut_steps, rope_theta, rms_eps, prefix):
    """h(1)..h(R), each [B, T, hidden]: what every pass of the stack leaves."""
    h0 = layers.embedding(ids, size=[vocab_size, hidden_size],
                          param_attr=_p(prefix, "embed"))
    loop = layers.Repeat(total_ut_steps)
    with loop.block():
        h = u = loop.carry(h0)
        for i in range(num_layers):
            with layers.recompute():
                u = _layer(u, hidden_size, num_heads, ffn_size, rope_theta,
                           rms_eps, f"{prefix}.l{i}")
        closed = layers.rms_norm(u, rms_eps, _p(prefix, "final_norm"))
        loop.update(h, closed)
        loop.output(closed)
    hs = loop()                                   # [R, B, T, hidden]
    return [layers.squeeze(layers.slice(hs, [0], [t], [t + 1]), [0])
            for t in range(total_ut_steps)]


def _head(h_t, vocab_size, prefix):
    """The ONE head weight, whichever pass reads it."""
    return layers.fc(h_t, size=vocab_size, num_flatten_dims=2,
                     param_attr=_p(prefix, "head"), bias_attr=False)


def _exit_gate(h_t, prefix):
    """lam(t) [B, T, 1]: the probability of stopping after this pass."""
    return layers.fc(h_t, size=1, num_flatten_dims=2, act="sigmoid",
                     param_attr=_p(prefix, "exit_gate"),
                     bias_attr=_p(prefix, "exit_gate_bias"))


def ouro(ids, vocab_size, hidden_size=2048, num_layers=48, num_heads=16,
         ffn_size=5632, total_ut_steps=4, rope_theta=1e6, rms_eps=1e-6,
         prefix="ouro"):
    """``ids`` [B, T] int64 -> ``(logits, gates)``, two lists of
    ``total_ut_steps`` variables: after pass t the head's logits [B, T,
    vocab_size] (one head weight, read by every pass) and the exit gate's
    probability lam(t) [B, T, 1] of stopping at t."""
    hs = _hidden_states(ids, vocab_size, hidden_size, num_layers, num_heads,
                        ffn_size, total_ut_steps, rope_theta, rms_eps, prefix)
    return ([_head(h_t, vocab_size, prefix) for h_t in hs],
            [_exit_gate(h_t, prefix) for h_t in hs])


def exit_distribution(gates):
    """p(1..R), each [N, 1] and summing to 1 a token, from the gates
    lam(1..R-1) of every pass but the last, which is not read:
    p(1) = lam(1);  p(t) = lam(t) prod_{j<t}(1 - lam(j)) for t < R;
    p(R) = prod_{j<R}(1 - lam(j))."""
    if not gates:
        raise ValueError("an exit distribution needs at least two passes")
    stay, probs = None, []         # stay: prod_{j<t} (1 - lam(j))
    for lam in gates:
        lam = layers.reshape(lam, [-1, 1])
        probs.append(lam if stay is None
                     else layers.elementwise_mul(stay, lam))
        leave = layers.scale(lam, -1.0, 1.0)
        stay = leave if stay is None else layers.elementwise_mul(stay, leave)
    return probs + [stay]


def _exit_term(p, ce, beta):
    """p CE + beta p log p per token [N, 1], p kept away from 0 under the
    logarithm only (a gate at 0 or 1 gives a pass no weight at all)."""
    plogp = layers.elementwise_mul(p, layers.log(layers.clip(p, 1e-30, 1.0)))
    return layers.elementwise_add(layers.elementwise_mul(p, ce),
                                  layers.scale(plogp, beta))


def ouro_loss(ids, labels, vocab_size, hidden_size=2048, num_layers=48,
              num_heads=16, ffn_size=5632, total_ut_steps=4, rope_theta=1e6,
              rms_eps=1e-6, prefix="ouro", beta=0.05):
    """The paper's stage-I objective on ``ids``/``labels`` [B, T] int64:
    the expectation of the cross-entropy over the exit step less ``beta``
    times the entropy of the exit distribution, a mean over tokens:

        loss = mean_tokens[ sum_t p(t) CE(t) + beta sum_t p(t) log p(t) ]

    with CE(t) per token from pass t's logits and p from
    ``exit_distribution``; the model's keywords are ``ouro``'s.  Returns
    ``(loss, p)``.  A pass's head product and its cross-entropy are one
    ``recompute`` stretch: the [B*T, vocab_size] logits of
    ``total_ut_steps`` passes are not kept side by side."""
    hs = _hidden_states(ids, vocab_size, hidden_size, num_layers, num_heads,
                        ffn_size, total_ut_steps, rope_theta, rms_eps, prefix)
    probs = exit_distribution([_exit_gate(h_t, prefix) for h_t in hs[:-1]])
    lbl = layers.reshape(labels, [-1, 1])
    total = None
    for h_t, p in zip(hs, probs):
        with layers.recompute():
            ce = layers.softmax_with_cross_entropy(
                layers.reshape(_head(h_t, vocab_size, prefix),
                               [-1, vocab_size]), lbl)
        term = _exit_term(p, ce, beta)
        total = term if total is None else layers.elementwise_add(total, term)
    return layers.mean(total), probs
