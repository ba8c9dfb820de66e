"""Kimi Linear (Moonshot AI, Kimi-Linear-48B-A3B; ``model_type: kimi_linear``
in transformers; arXiv:2510.26692): a hybrid decoder of Kimi Delta Attention
(KDA) and latent attention (MLA) layers.  Every layer is
``x = x + mixer(RMSNorm(x))``, then ``x = x + ffn(RMSNorm(x))``; ``pattern``
picks each layer's mixer, a character a layer:

* ``K``, KDA (``kda_mixer``): ``q, k, v = silu(conv(n W_{q,k,v}))``, one
  depthwise causal filter of ``conv_taps`` taps WITHOUT a bias over the
  three (``layers.short_conv``, ungated); q and k L2-normalised a head;
  a decay a key channel, ``g = -exp(A_log[h]) softplus(n W_f_down W_f_up +
  dt_bias)`` (<= 0), and ``beta = sigmoid(n W_b)``; the gated delta rule
  through ``layers.gated_delta_scan`` at q scaled by head_dim^-1/2; the
  result RMS-normalised a head (one [head_dim] weight) and gated by
  ``sigmoid(n W_g_down W_g_up + b_g)``, then ``o_proj``;
* ``A``, MLA (``mla_mixer``): ``q = n W_q``, ``[c | k_pe] = n W_kv_a``,
  ``[k_nope | v] = RMSNorm(c) W_kv_b`` a head, ``k = [k_nope | k_pe]`` with
  k_pe's features shared by every head, NO rotation (``mla_use_nope``),
  causal softmax at (qk_nope + qk_rope)^-1/2 through ``layers.flash_attention``
  with a value head size of its own, then ``o_proj``.

The ffn of the first ``dense_layers`` layers built is a SiLU-gated MLP of
``dense_width``; every other layer's is the expert layer (``layers.moe``,
dropless): sigmoid scores over all ``num_experts`` in float32, the
``experts_per_tok`` largest of score + a correction bias (no gradient, for
the choice alone), the chosen scores renormalised and scaled by
``routed_scale``; an expert is SiLU-gated, ``(silu(x W_g) * x W_u) W_d`` at
``expert_width``, and a shared expert of the same form at ``shared_width``
takes every token.  ``experts_held`` / ``expert_offset`` build one chip's
share of an expert-parallel deployment.  A final RMSNorm and an untied head.

Two vectors of a KDA layer start at neither 0 nor 1, and the startup program
sets them (the published initialisation's ranges, made deterministic):
``A_log[h]`` the log of ``heads`` values evenly spaced from 1 to 16,
``dt_bias`` the inverse softplus of values log-spaced from ``time_step_min``
to ``time_step_max`` over the key channels.
"""
from __future__ import annotations

import contextlib

import numpy as np

from .. import layers
from ..initializer import UniformInitializer
from .hybrid_mixers import cut, dt_bias_start, p_, proj, vector

MIXERS = ("K", "A")


def a_log_start(heads):
    """log of ``heads`` values evenly spaced from 1 to 16 (fla's KDA draws
    them uniform in [1, 16])."""
    return np.log(np.linspace(1.0, 16.0, heads)).astype("float32")


def kda_mixer(n, hidden_size, heads, head_dim, conv_taps, chunk, norm_eps,
              time_step_min, time_step_max, prefix):
    seq_len, width = n.shape[1], heads * head_dim

    def by_head(x, features=head_dim):
        return layers.reshape(x, [-1, seq_len, heads, features])

    qkv = layers.short_conv(proj(n, 3 * width, prefix, "qkv"), conv_taps,
                            p_(prefix, "conv"), gated=False,
                            bias_attr=False, act="silu")
    q, k = (layers.l2_normalize(by_head(cut(qkv, at, at + width)), axis=-1,
                                epsilon=1e-6) for at in (0, width))
    v = by_head(cut(qkv, 2 * width, 3 * width))
    step = layers.softplus(layers.elementwise_add(
        proj(proj(n, head_dim, prefix, "f_down"), width, prefix, "f_up"),
        vector(prefix, "dt_bias",
               dt_bias_start(width, time_step_min, time_step_max)), axis=2))
    g = layers.elementwise_mul(by_head(step), layers.scale(layers.exp(
        vector(prefix, "A_log", a_log_start(heads))), -1.0), axis=2)
    beta = layers.sigmoid(proj(n, heads, prefix, "b_proj"))
    o = layers.gated_delta_scan(q, k, v, g, beta, head_dim ** -0.5,
                                chunk=chunk)
    gate = layers.sigmoid(layers.fc(
        proj(n, head_dim, prefix, "g_down"), size=width, num_flatten_dims=2,
        param_attr=p_(prefix, "g_up"), bias_attr=p_(prefix, "g_bias")))
    o = layers.reshape(layers.rms_norm(o, norm_eps, p_(prefix, "o_norm")),
                       [-1, seq_len, width])
    return proj(layers.elementwise_mul(o, gate), hidden_size, prefix,
                "o_proj")


def mla_mixer(n, hidden_size, heads, nope_dim, rope_dim, v_dim, kv_rank,
              norm_eps, prefix):
    seq_len = n.shape[1]

    def features(x, start, stop):
        return layers.slice(x, axes=[3], starts=[start], ends=[stop])

    q = layers.reshape(proj(n, heads * (nope_dim + rope_dim), prefix,
                            "q_proj"),
                       [-1, seq_len, heads, nope_dim + rope_dim])
    kv_a = proj(n, kv_rank + rope_dim, prefix, "kv_a")
    kv = layers.reshape(
        proj(layers.rms_norm(cut(kv_a, 0, kv_rank), norm_eps,
                             p_(prefix, "kv_norm")),
             heads * (nope_dim + v_dim), prefix, "kv_b"),
        [-1, seq_len, heads, nope_dim + v_dim])
    k_pe = layers.expand(layers.reshape(
        cut(kv_a, kv_rank, kv_rank + rope_dim), [-1, seq_len, 1, rope_dim]),
        [1, 1, heads, 1])
    k = layers.concat([features(kv, 0, nope_dim), k_pe], axis=3)
    # the kernels scale the scores by (nope + rope)^-1/2, the model's scale
    o = layers.flash_attention(q, k, features(kv, nope_dim, nope_dim + v_dim),
                               causal=True)
    return proj(layers.reshape(o, [-1, seq_len, heads * v_dim]), hidden_size,
                prefix, "o_proj")


def gated_mlp(n, hidden_size, width, prefix):
    return proj(layers.elementwise_mul(
        layers.silu(proj(n, width, prefix, "mlp_gate")),
        proj(n, width, prefix, "mlp_up")), hidden_size, prefix, "mlp_down")


def kimi_linear(ids, vocab_size, pattern, hidden_size=2304, dense_layers=1,
                dense_width=9216, kda_heads=32, kda_head_dim=128,
                conv_taps=4, chunk=64, mla_heads=32, qk_nope_dim=128,
                qk_rope_dim=64, v_head_dim=128, kv_lora_rank=512,
                num_experts=256, experts_per_tok=8, expert_width=1024,
                shared_width=1024, renormalize=True, routed_scale=2.446,
                expert_bias_range=0.0, experts_held=None, expert_offset=0,
                norm_eps=1e-5, time_step_min=0.001, time_step_max=0.1,
                recompute=False, prefix="kimi"):
    """``ids`` [B, T] int64 -> logits [B, T, vocab_size].  ``pattern`` names
    the mixer of every layer that is built, in order (the published model:
    27 characters, 20 ``K``, 7 ``A``).  ``expert_bias_range`` r draws every
    expert layer's correction bias once, uniform in [-r, r] (0: the zeros a
    training run starts from).  ``recompute``: True makes every layer two
    ``layers.recompute`` stretches (its mixer's and its ffn's), a list of
    layer indices those layers."""
    bad = sorted(set(pattern) - set(MIXERS))
    if bad:
        raise ValueError(f"kimi_linear: mixers {bad} are not of {MIXERS}")
    x = layers.embedding(ids, size=[vocab_size, hidden_size],
                         param_attr=p_(prefix, "embed"))
    for i, kind in enumerate(pattern):
        at = f"{prefix}.l{i}"
        again = recompute if isinstance(recompute, bool) else i in recompute

        def stretch():
            return layers.recompute() if again else contextlib.nullcontext()

        # a recomputed layer is two stretches, the mixer's and the ffn's:
        # the backward of one does not hold what the other computes again
        with stretch():
            n = layers.rms_norm(x, norm_eps, p_(at, "attn_norm"))
            if kind == "K":
                o = kda_mixer(n, hidden_size, kda_heads, kda_head_dim,
                              conv_taps, chunk, norm_eps, time_step_min,
                              time_step_max, at)
            else:
                o = mla_mixer(n, hidden_size, mla_heads, qk_nope_dim,
                              qk_rope_dim, v_head_dim, kv_lora_rank,
                              norm_eps, at)
            x = layers.elementwise_add(x, o)
        with stretch():
            n = layers.rms_norm(x, norm_eps, p_(at, "ffn_norm"))
            if i < dense_layers:
                o = gated_mlp(n, hidden_size, dense_width, at)
            else:
                o, _, _ = layers.moe(
                    n, num_experts, expert_width, top_k=experts_per_tok,
                    capacity_factor=None, act="silu", gated=True,
                    gate_attr=p_(at, "router"), param_attr=p_(at, "experts"),
                    scoring="sigmoid", select_bias_attr=p_(
                        at, "expert_bias", UniformInitializer(
                            -expert_bias_range, expert_bias_range)),
                    renormalize=renormalize, routed_scale=routed_scale,
                    experts_held=experts_held, expert_offset=expert_offset,
                    shared_hidden=shared_width, shared_attr=p_(at, "shared"))
            x = layers.elementwise_add(x, o)
    x = layers.rms_norm(x, norm_eps, p_(prefix, "final_norm"))
    return layers.fc(x, size=vocab_size, num_flatten_dims=2,
                     param_attr=p_(prefix, "head"), bias_attr=False)


def kimi_linear_loss(ids, labels, vocab_size, pattern, **model):
    """The mean token cross-entropy of ``kimi_linear(ids, ...)`` against
    ``labels`` [B, T] int64 (the config gives no auxiliary loss); ``model``
    are ``kimi_linear``'s keywords."""
    logits = kimi_linear(ids, vocab_size, pattern, **model)
    return layers.mean(layers.softmax_with_cross_entropy(
        layers.reshape(logits, [-1, vocab_size]),
        layers.reshape(labels, [-1, 1])))
