"""Nemotron-H with sparse experts: a hybrid decoder whose every layer is ONE
mixer, ``x = x + mixer(RMSNorm(x))`` (NVIDIA, Nemotron-3-Nano-30B-A3B;
``model_type: nemotron_h`` in transformers).  No feed-forward stands beside
a mixer, and no bias anywhere except the filter's.  ``pattern`` picks each
layer's mixer, a character a layer:

* ``M``, Mamba-2 (``models/hybrid_mixers.py mamba_mixer``): ``mamba_heads``
  heads of ``mamba_head_dim`` over ``mamba_groups`` groups of
  ``mamba_state`` (the heads of a group share its Bm and Cm), the gated
  result RMS-normalised over EACH GROUP's features on its own (one weight
  over all of them);
* ``*``, attention (``attention_mixer``): ``num_heads`` query heads of
  ``head_dim`` over ``num_kv_heads`` key / value heads, causal, scores times
  1/sqrt(head_dim), NO positional encoding;
* ``E``, experts (``layers.moe``, dropless): the scores are
  sigmoid(router) over all ``num_experts`` in float32, the ``experts_per_tok``
  are chosen by the largest of score + a correction bias (float32, no
  gradient, used for the choice alone), weighted by the chosen experts'
  scores renormalised and scaled by ``routed_scale``; a routed expert is
  UN-GATED, ``relu(x W_up)^2 W_down`` at ``expert_width``, and a shared
  expert of the same form at ``shared_width`` takes every token:
  ``out = sum_e w_e expert_e(n) + shared(n)``.

A final RMSNorm and an untied head.  ``experts_held`` / ``expert_offset``
build one chip's share of an expert-parallel deployment (``layers.moe``):
the router keeps its ``num_experts`` outputs, the stacks hold
``experts_held`` experts; the shared expert is whole on every chip.
"""
from __future__ import annotations

import contextlib
import math

from .. import layers
from ..initializer import UniformInitializer
from .hybrid_mixers import attention_mixer, mamba_mixer, p_

MIXERS = ("M", "*", "E")


def nemotron_h(ids, vocab_size, pattern, hidden_size=2688, num_heads=32,
               num_kv_heads=2, head_dim=128, mamba_heads=64,
               mamba_head_dim=64, mamba_state=128, mamba_groups=8,
               conv_taps=4, chunk=128, num_experts=128, experts_per_tok=6,
               expert_width=1856, shared_width=3712, norm_topk_prob=True,
               routed_scale=2.5, expert_bias_range=0.0, experts_held=None,
               expert_offset=0, norm_eps=1e-5, time_step_min=0.001,
               time_step_max=0.1, recompute=False, prefix="nemotron"):
    """``ids`` [B, T] int64 -> logits [B, T, vocab_size].  ``pattern`` names
    the mixer of every layer that is built, in order (the published model:
    52 characters, 23 ``M``, 23 ``E``, 6 ``*``).  ``expert_bias_range`` r
    draws every expert layer's correction bias once, uniform in [-r, r] (0:
    the zeros a training run starts from).  ``recompute``: True makes every
    layer a ``layers.recompute`` stretch, a list of layer indices those
    layers."""
    bad = sorted(set(pattern) - set(MIXERS))
    if bad:
        raise ValueError(f"nemotron_h: mixers {bad} are not of {MIXERS}")
    x = layers.embedding(ids, size=[vocab_size, hidden_size],
                         param_attr=p_(prefix, "embed"))
    for i, kind in enumerate(pattern):
        at = f"{prefix}.l{i}"
        again = recompute if isinstance(recompute, bool) else i in recompute
        with layers.recompute() if again else contextlib.nullcontext():
            n = layers.rms_norm(x, norm_eps, p_(at, "norm"))
            if kind == "M":
                o = mamba_mixer(n, hidden_size, mamba_heads, mamba_head_dim,
                                mamba_state, mamba_groups, conv_taps, chunk,
                                norm_eps, time_step_min, time_step_max, at,
                                norm_groups=mamba_groups)
            elif kind == "*":
                o = attention_mixer(n, hidden_size, num_heads, num_kv_heads,
                                    head_dim, 1.0 / math.sqrt(head_dim), at)
            else:
                o, _, _ = layers.moe(
                    n, num_experts, expert_width, top_k=experts_per_tok,
                    capacity_factor=None, act="relu2", gated=False,
                    gate_attr=p_(at, "router"), param_attr=p_(at, "experts"),
                    scoring="sigmoid", select_bias_attr=p_(
                        at, "expert_bias", UniformInitializer(
                            -expert_bias_range, expert_bias_range)),
                    renormalize=norm_topk_prob, routed_scale=routed_scale,
                    experts_held=experts_held, expert_offset=expert_offset,
                    shared_hidden=shared_width, shared_attr=p_(at, "shared"))
            x = layers.elementwise_add(x, o)
    x = layers.rms_norm(x, norm_eps, p_(prefix, "final_norm"))
    return layers.fc(x, size=vocab_size, num_flatten_dims=2,
                     param_attr=p_(prefix, "head"), bias_attr=False)


def nemotron_h_loss(ids, labels, vocab_size, pattern, **model):
    """The mean token cross-entropy of ``nemotron_h(ids, ...)`` against
    ``labels`` [B, T] int64 (the config gives no auxiliary loss); ``model``
    are ``nemotron_h``'s keywords."""
    logits = nemotron_h(ids, vocab_size, pattern, **model)
    return layers.mean(layers.softmax_with_cross_entropy(
        layers.reshape(logits, [-1, vocab_size]),
        layers.reshape(labels, [-1, 1])))
