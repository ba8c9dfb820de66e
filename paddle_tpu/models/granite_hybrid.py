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

from .. import layers
from ..layer_helper import LayerHelper
from .hybrid_mixers import attention_mixer, cut, mamba_mixer, p_, proj

MIXERS = ("mamba", "attention")


def _ffn(m, hidden_size, ffn_size, prefix):
    both = proj(m, 2 * ffn_size, prefix, "ffn_in")
    return proj(layers.elementwise_mul(
        layers.silu(cut(both, 0, ffn_size)),
        cut(both, ffn_size, 2 * ffn_size)), hidden_size, prefix, "ffn_out")


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
        ids, size=[vocab_size, hidden_size], param_attr=p_(prefix, "embed")),
        embedding_multiplier)
    for i, kind in enumerate(layer_types):
        at = f"{prefix}.l{i}"
        again = recompute if isinstance(recompute, bool) else i in recompute
        with layers.recompute() if again else contextlib.nullcontext():
            n = layers.rms_norm(x, norm_eps, p_(at, "mixer_norm"))
            if kind == "mamba":
                o = mamba_mixer(n, hidden_size, mamba_heads, mamba_head_dim,
                                mamba_state, mamba_groups, conv_taps, chunk,
                                norm_eps, time_step_min, time_step_max, at)
            else:
                o = attention_mixer(n, hidden_size, num_heads, num_kv_heads,
                                    hidden_size // num_heads,
                                    attention_multiplier, at)
            h = layers.elementwise_add(
                x, layers.scale(o, residual_multiplier))
            m = layers.rms_norm(h, norm_eps, p_(at, "ffn_norm"))
            x = layers.elementwise_add(h, layers.scale(
                _ffn(m, hidden_size, ffn_size, at), residual_multiplier))
    x = layers.rms_norm(x, norm_eps, p_(prefix, "final_norm"))
    # the tied head: the embedding's own parameter, read transposed
    table = LayerHelper("granite_head").create_parameter(
        p_(prefix, "embed"), shape=[vocab_size, hidden_size], dtype=x.dtype)
    return layers.scale(layers.matmul(x, table, transpose_y=True),
                        1.0 / logits_scaling)


def granite_hybrid_loss(ids, labels, vocab_size, layer_types, **model):
    """The mean token cross-entropy of ``granite_hybrid(ids, ...)`` against
    ``labels`` [B, T] int64; ``model`` are ``granite_hybrid``'s keywords."""
    logits = granite_hybrid(ids, vocab_size, layer_types, **model)
    return layers.mean(layers.softmax_with_cross_entropy(
        layers.reshape(logits, [-1, vocab_size]),
        layers.reshape(labels, [-1, 1])))
