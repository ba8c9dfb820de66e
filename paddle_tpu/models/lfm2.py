"""LFM2-MoE: a hybrid decoder of gated short convolutions and grouped-query
attention over sparse experts (Liquid AI, LFM2-8B-A1B; ``model_type:
lfm2_moe`` in transformers).

Every layer is ``h = x + operator(RMSNorm(x)); out = h + ffn(RMSNorm(h))``
with no bias anywhere.  ``layer_types`` picks each layer's operator:

* ``conv``: ``[Bg, Cg, u] = split(in_proj(a), 3)``, a depthwise causal
  filter of ``conv_taps`` taps over ``Bg * u``, gated by ``Cg``, then
  ``out_proj`` (``layers.short_conv`` between two ``fc``);
* ``full_attention``: ``num_heads`` query heads over ``num_kv_heads`` key /
  value heads (query head h reads K / V head h // group), an RMSNorm over
  the features of EACH head of q and of k, rotary positions, causal.

The first ``num_dense_layers`` layers have a dense SiLU-gated feed-forward;
the others ``experts_per_tok`` of ``num_experts`` SiLU-gated experts chosen
by the largest of sigmoid(router) + a selection bias (no gradient, used for
the choice alone), weighted by the chosen experts' scores renormalised and
scaled by ``routed_scale``; no shared expert, no auxiliary loss.  A final
RMSNorm, and the head is the embedding table transposed (one parameter).

``experts_held`` / ``expert_offset`` build one chip's share of an
expert-parallel deployment (``layers.moe``): the router keeps its
``num_experts`` outputs, the stacks hold ``experts_held`` experts.
"""
from __future__ import annotations

import contextlib

from .. import layers
from ..initializer import UniformInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

OPERATORS = ("conv", "full_attention")


def _p(prefix, name):
    return ParamAttr(name=f"{prefix}.{name}")


def _proj(x, size, prefix, name):
    return layers.fc(x, size=size, num_flatten_dims=2,
                     param_attr=_p(prefix, name), bias_attr=False)


def _conv_operator(a, hidden_size, conv_taps, prefix):
    gated = layers.short_conv(_proj(a, 3 * hidden_size, prefix, "in_proj"),
                              conv_taps, _p(prefix, "conv"))
    return _proj(gated, hidden_size, prefix, "out_proj")


def _attention_operator(a, hidden_size, num_heads, num_kv_heads, rope_theta,
                        norm_eps, prefix):
    seq_len, head = a.shape[1], hidden_size // num_heads

    def heads(x, n):
        return layers.reshape(x, [-1, seq_len, n, head])

    q = heads(_proj(a, hidden_size, prefix, "wq"), num_heads)
    k = heads(_proj(a, num_kv_heads * head, prefix, "wk"), num_kv_heads)
    v = heads(_proj(a, num_kv_heads * head, prefix, "wv"), num_kv_heads)
    q = layers.rope(layers.rms_norm(q, norm_eps, _p(prefix, "q_norm")),
                    rope_theta)
    k = layers.rope(layers.rms_norm(k, norm_eps, _p(prefix, "k_norm")),
                    rope_theta)
    o = layers.flash_attention(q, k, v, causal=True)
    return _proj(layers.reshape(o, [-1, seq_len, hidden_size]), hidden_size,
                 prefix, "wo")


def _dense_ffn(m, hidden_size, ffn_size, prefix):
    return _proj(layers.elementwise_mul(
        layers.silu(_proj(m, ffn_size, prefix, "w1")),
        _proj(m, ffn_size, prefix, "w3")), hidden_size, prefix, "w2")


def lfm2(ids, vocab_size, layer_types, hidden_size=2048, num_dense_layers=2,
         num_heads=32, num_kv_heads=8, ffn_size=7168, num_experts=32,
         experts_per_tok=4, expert_width=1792, conv_taps=3, rope_theta=1e6,
         norm_eps=1e-5, norm_topk_prob=True, routed_scale=1.0,
         expert_bias_range=None, experts_held=None, expert_offset=0,
         recompute=False, prefix="lfm2"):
    """``ids`` [B, T] int64 -> logits [B, T, vocab_size].  ``layer_types``
    lists the operator of every layer that is built, in order (the
    published model: 24 entries); the first ``num_dense_layers`` of them
    get the dense feed-forward.  ``expert_bias_range``: None builds no
    selection bias (``use_expert_bias`` false); a number r draws it once,
    uniform in [-r, r] (0: the zeros a training run starts from).
    ``recompute``: True makes every layer a ``layers.recompute`` stretch,
    a list of layer indices those layers."""
    bad = [t for t in layer_types if t not in OPERATORS]
    if bad:
        raise ValueError(f"lfm2: layer types {bad} are not of {OPERATORS}")
    x = layers.embedding(ids, size=[vocab_size, hidden_size],
                         param_attr=_p(prefix, "embed"))
    for i, kind in enumerate(layer_types):
        at = f"{prefix}.l{i}"
        again = recompute if isinstance(recompute, bool) else i in recompute
        with layers.recompute() if again else contextlib.nullcontext():
            a = layers.rms_norm(x, norm_eps, _p(at, "operator_norm"))
            if kind == "conv":
                o = _conv_operator(a, hidden_size, conv_taps, at)
            else:
                o = _attention_operator(a, hidden_size, num_heads,
                                        num_kv_heads, rope_theta, norm_eps,
                                        at)
            h = layers.elementwise_add(x, o)
            m = layers.rms_norm(h, norm_eps, _p(at, "ffn_norm"))
            if i < num_dense_layers:
                y = _dense_ffn(m, hidden_size, ffn_size, at)
            else:
                bias = None
                if expert_bias_range is not None:
                    bias = ParamAttr(
                        name=f"{at}.expert_bias",
                        initializer=UniformInitializer(
                            -expert_bias_range, expert_bias_range))
                y, _, _ = layers.moe(
                    m, num_experts, expert_width, top_k=experts_per_tok,
                    capacity_factor=None, act="silu", gated=True,
                    gate_attr=_p(at, "router"), param_attr=_p(at, "experts"),
                    scoring="sigmoid", select_bias_attr=bias,
                    renormalize=norm_topk_prob, routed_scale=routed_scale,
                    experts_held=experts_held, expert_offset=expert_offset)
            x = layers.elementwise_add(h, y)
    x = layers.rms_norm(x, norm_eps, _p(prefix, "final_norm"))
    # the tied head: the embedding's own parameter, read transposed
    table = LayerHelper("lfm2_head").create_parameter(
        _p(prefix, "embed"), shape=[vocab_size, hidden_size], dtype=x.dtype)
    return layers.matmul(x, table, transpose_y=True)


def lfm2_loss(ids, labels, vocab_size, layer_types, **model):
    """The mean token cross-entropy of ``lfm2(ids, ...)`` against ``labels``
    [B, T] int64 (the config gives no auxiliary loss); ``model`` are
    ``lfm2``'s keywords."""
    logits = lfm2(ids, vocab_size, layer_types, **model)
    return layers.mean(layers.softmax_with_cross_entropy(
        layers.reshape(logits, [-1, vocab_size]),
        layers.reshape(labels, [-1, 1])))
