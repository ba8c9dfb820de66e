"""Granite 4.0-H: a hybrid decoder of Mamba-2 state-space layers and
grouped-query attention over one shared SiLU-gated feed-forward (IBM,
granite-4.0-h-micro; ``model_type: granitemoehybrid`` in transformers, here
without experts).

Every layer is ``h = x + r * mixer(RMSNorm(x)); out = h + r * ffn(RMSNorm(h))``
with r the ``residual_multiplier`` and no bias anywhere except the filter's.
``layer_types`` picks each layer's mixer:

* ``mamba``: ``[z | xBC | dt] = split(in_proj(n))``; a depthwise causal
  filter of ``conv_taps`` taps with a bias and SiLU over xBC
  (``layers.short_conv``, ungated); ``[u | Bm | Cm] = split(xBC)``, u as
  ``mamba_heads`` heads of ``mamba_head_dim``, Bm / Cm ``mamba_groups``
  groups of ``mamba_state``; ``delta = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the recurrence through ``layers.ssd_scan``; the
  result gated by ``silu(z)``, RMS-normalised over all its features, then
  ``out_proj``;
* ``attention``: ``num_heads`` query heads over ``num_kv_heads`` key / value
  heads, causal, NO positional encoding, the scores scaled by
  ``attention_multiplier`` (not 1/sqrt(head)).

The embedding is scaled by ``embedding_multiplier``, a final RMSNorm, and
the head is the embedding table transposed (one parameter), its logits
divided by ``logits_scaling``.

Three vectors of a ``mamba`` layer start at neither 0 nor 1, and the
startup program sets them (the published initialisation, made
deterministic): ``A_log[h] = log(h + 1)``, ``dt_bias`` the inverse softplus
of values log-spaced from ``time_step_min`` to ``time_step_max``, ``D`` 1.
"""
from __future__ import annotations

import contextlib
import math

import numpy as np

from .. import layers
from ..initializer import NumpyArrayInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

MIXERS = ("mamba", "attention")


def _p(prefix, name, initializer=None):
    return ParamAttr(name=f"{prefix}.{name}", initializer=initializer)


def _proj(x, size, prefix, name):
    return layers.fc(x, size=size, num_flatten_dims=2,
                     param_attr=_p(prefix, name), bias_attr=False)


def _vector(prefix, name, values):
    """A trainable [len(values)] parameter the startup program sets to
    ``values``."""
    values = np.asarray(values, "float32")
    return LayerHelper("granite_vector").create_parameter(
        _p(prefix, name, NumpyArrayInitializer(values)),
        shape=list(values.shape), dtype="float32")


def _cut(x, start, stop):
    """x[..., start:stop] of a [B, T, C] variable."""
    return layers.slice(x, axes=[2], starts=[start], ends=[stop])


def dt_bias_start(heads, time_step_min, time_step_max):
    """The inverse softplus of ``heads`` values log-spaced from
    ``time_step_min`` to ``time_step_max``: softplus(dt_bias) is the step."""
    step = np.exp(np.linspace(math.log(time_step_min),
                              math.log(time_step_max), heads))
    return (step + np.log(-np.expm1(-step))).astype("float32")


def _mamba_mixer(n, hidden_size, heads, head_dim, state, groups, conv_taps,
                 chunk, norm_eps, time_step_min, time_step_max, prefix):
    seq_len = n.shape[1]
    inner, bc = heads * head_dim, groups * state
    zxd = _proj(n, 2 * inner + 2 * bc + heads, prefix, "in_proj")
    z = _cut(zxd, 0, inner)
    xbc = layers.short_conv(
        _cut(zxd, inner, 2 * inner + 2 * bc), conv_taps, _p(prefix, "conv"),
        gated=False, bias_attr=_p(prefix, "conv_bias"), act="silu")
    u = layers.reshape(_cut(xbc, 0, inner), [-1, seq_len, heads, head_dim])
    bm = layers.reshape(_cut(xbc, inner, inner + bc),
                        [-1, seq_len, groups, state])
    cm = layers.reshape(_cut(xbc, inner + bc, inner + 2 * bc),
                        [-1, seq_len, groups, state])
    delta = layers.softplus(layers.elementwise_add(
        _cut(zxd, 2 * inner + 2 * bc, 2 * inner + 2 * bc + heads),
        _vector(prefix, "dt_bias",
                dt_bias_start(heads, time_step_min, time_step_max)), axis=2))
    a = layers.scale(layers.exp(_vector(
        prefix, "A_log", np.log(np.arange(1, heads + 1)))), -1.0)
    y = layers.ssd_scan(u, delta, a, bm, cm,
                        _vector(prefix, "D", np.ones(heads)), chunk=chunk)
    gated = layers.elementwise_mul(
        layers.reshape(y, [-1, seq_len, inner]), layers.silu(z))
    return _proj(layers.rms_norm(gated, norm_eps, _p(prefix, "gate_norm")),
                 hidden_size, prefix, "out_proj")


def _attention_mixer(n, hidden_size, num_heads, num_kv_heads, scale, prefix):
    seq_len, head = n.shape[1], hidden_size // num_heads

    def heads(x, count):
        return layers.reshape(x, [-1, seq_len, count, head])

    # flash_attention scales by 1/sqrt(head): the rest goes on q
    q = layers.scale(_proj(n, hidden_size, prefix, "wq"),
                     scale * math.sqrt(head))
    k = _proj(n, num_kv_heads * head, prefix, "wk")
    v = _proj(n, num_kv_heads * head, prefix, "wv")
    o = layers.flash_attention(heads(q, num_heads), heads(k, num_kv_heads),
                               heads(v, num_kv_heads), causal=True)
    return _proj(layers.reshape(o, [-1, seq_len, hidden_size]), hidden_size,
                 prefix, "wo")


def _ffn(m, hidden_size, ffn_size, prefix):
    both = _proj(m, 2 * ffn_size, prefix, "ffn_in")
    return _proj(layers.elementwise_mul(
        layers.silu(_cut(both, 0, ffn_size)),
        _cut(both, ffn_size, 2 * ffn_size)), hidden_size, prefix, "ffn_out")


def granite_hybrid(ids, vocab_size, layer_types, hidden_size=2048,
                   num_heads=32, num_kv_heads=8, ffn_size=8192,
                   mamba_heads=64, mamba_head_dim=64, mamba_state=128,
                   mamba_groups=1, conv_taps=4, chunk=256, norm_eps=1e-5,
                   embedding_multiplier=12.0, residual_multiplier=0.22,
                   attention_multiplier=0.015625, logits_scaling=8.0,
                   time_step_min=0.001, time_step_max=0.1, recompute=False,
                   prefix="granite"):
    """``ids`` [B, T] int64 -> logits [B, T, vocab_size].  ``layer_types``
    lists the mixer of every layer that is built, in order (the published
    model: 40 entries, nine ``mamba`` to one ``attention``).  ``recompute``:
    True makes every layer a ``layers.recompute`` stretch, a list of layer
    indices those layers."""
    bad = [t for t in layer_types if t not in MIXERS]
    if bad:
        raise ValueError(f"granite_hybrid: layer types {bad} are not of "
                         f"{MIXERS}")
    x = layers.scale(layers.embedding(
        ids, size=[vocab_size, hidden_size], param_attr=_p(prefix, "embed")),
        embedding_multiplier)
    for i, kind in enumerate(layer_types):
        at = f"{prefix}.l{i}"
        again = recompute if isinstance(recompute, bool) else i in recompute
        with layers.recompute() if again else contextlib.nullcontext():
            n = layers.rms_norm(x, norm_eps, _p(at, "mixer_norm"))
            if kind == "mamba":
                o = _mamba_mixer(n, hidden_size, mamba_heads, mamba_head_dim,
                                 mamba_state, mamba_groups, conv_taps, chunk,
                                 norm_eps, time_step_min, time_step_max, at)
            else:
                o = _attention_mixer(n, hidden_size, num_heads, num_kv_heads,
                                     attention_multiplier, at)
            h = layers.elementwise_add(
                x, layers.scale(o, residual_multiplier))
            m = layers.rms_norm(h, norm_eps, _p(at, "ffn_norm"))
            x = layers.elementwise_add(h, layers.scale(
                _ffn(m, hidden_size, ffn_size, at), residual_multiplier))
    x = layers.rms_norm(x, norm_eps, _p(prefix, "final_norm"))
    # the tied head: the embedding's own parameter, read transposed
    table = LayerHelper("granite_head").create_parameter(
        _p(prefix, "embed"), shape=[vocab_size, hidden_size], dtype=x.dtype)
    return layers.scale(layers.matmul(x, table, transpose_y=True),
                        1.0 / logits_scaling)


def granite_hybrid_loss(ids, labels, vocab_size, layer_types, **model):
    """The mean token cross-entropy of ``granite_hybrid(ids, ...)`` against
    ``labels`` [B, T] int64; ``model`` are ``granite_hybrid``'s keywords."""
    logits = granite_hybrid(ids, vocab_size, layer_types, **model)
    return layers.mean(layers.softmax_with_cross_entropy(
        layers.reshape(logits, [-1, vocab_size]),
        layers.reshape(labels, [-1, 1])))
