"""What the hybrid state-space decoders share (``models/granite_hybrid.py``,
``models/nemotron_h.py``): a Mamba-2 mixer and a grouped-query attention
mixer WITHOUT positional encoding, each a function of the normed input
``n`` [B, T, D] that returns the mixer's output [B, T, D]; no bias anywhere
except the filter's.

* ``mamba_mixer``: ``[z | xBC | dt] = split(in_proj(n))``; a depthwise causal
  filter of ``conv_taps`` taps with a bias and SiLU over xBC
  (``layers.short_conv``, ungated); ``[u | Bm | Cm] = split(xBC)``, u as
  ``heads`` heads of ``head_dim``, Bm / Cm ``groups`` groups of ``state``
  (head h reads group h // (heads / groups)); ``delta = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the recurrence through
  ``layers.ssd_scan``; the result gated by ``silu(z)``, RMS-normalised over
  each of ``norm_groups`` equal parts of its features (1: over all of
  them), then ``out_proj``;
* ``attention_mixer``: ``num_heads`` query heads of ``head_dim`` over
  ``num_kv_heads`` key / value heads, causal, the scores scaled by
  ``scale``.

Three vectors of a Mamba-2 layer start at neither 0 nor 1, and the startup
program sets them (the published initialisation, made deterministic):
``A_log[h] = log(h + 1)``, ``dt_bias`` the inverse softplus of values
log-spaced from ``time_step_min`` to ``time_step_max``, ``D`` 1.
"""
from __future__ import annotations

import math

import numpy as np

from .. import layers
from ..initializer import NumpyArrayInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr


def p_(prefix, name, initializer=None):
    return ParamAttr(name=f"{prefix}.{name}", initializer=initializer)


def proj(x, size, prefix, name):
    return layers.fc(x, size=size, num_flatten_dims=2,
                     param_attr=p_(prefix, name), bias_attr=False)


def vector(prefix, name, values):
    """A trainable [len(values)] parameter the startup program sets to
    ``values``."""
    values = np.asarray(values, "float32")
    return LayerHelper("mamba_vector").create_parameter(
        p_(prefix, name, NumpyArrayInitializer(values)),
        shape=list(values.shape), dtype="float32")


def cut(x, start, stop):
    """x[..., start:stop] of a [B, T, C] variable."""
    return layers.slice(x, axes=[2], starts=[start], ends=[stop])


def dt_bias_start(heads, time_step_min, time_step_max):
    """The inverse softplus of ``heads`` values log-spaced from
    ``time_step_min`` to ``time_step_max``: softplus(dt_bias) is the step."""
    step = np.exp(np.linspace(math.log(time_step_min),
                              math.log(time_step_max), heads))
    return (step + np.log(-np.expm1(-step))).astype("float32")


def mamba_mixer(n, hidden_size, heads, head_dim, state, groups, conv_taps,
                chunk, norm_eps, time_step_min, time_step_max, prefix,
                norm_groups=1):
    seq_len = n.shape[1]
    inner, bc = heads * head_dim, groups * state
    zxd = proj(n, 2 * inner + 2 * bc + heads, prefix, "in_proj")
    z = cut(zxd, 0, inner)
    xbc = layers.short_conv(
        cut(zxd, inner, 2 * inner + 2 * bc), conv_taps, p_(prefix, "conv"),
        gated=False, bias_attr=p_(prefix, "conv_bias"), act="silu")
    u = layers.reshape(cut(xbc, 0, inner), [-1, seq_len, heads, head_dim])
    bm = layers.reshape(cut(xbc, inner, inner + bc),
                        [-1, seq_len, groups, state])
    cm = layers.reshape(cut(xbc, inner + bc, inner + 2 * bc),
                        [-1, seq_len, groups, state])
    delta = layers.softplus(layers.elementwise_add(
        cut(zxd, 2 * inner + 2 * bc, 2 * inner + 2 * bc + heads),
        vector(prefix, "dt_bias",
               dt_bias_start(heads, time_step_min, time_step_max)), axis=2))
    a = layers.scale(layers.exp(vector(
        prefix, "A_log", np.log(np.arange(1, heads + 1)))), -1.0)
    y = layers.ssd_scan(u, delta, a, bm, cm,
                        vector(prefix, "D", np.ones(heads)), chunk=chunk)
    gated = layers.elementwise_mul(
        layers.reshape(y, [-1, seq_len, inner]), layers.silu(z))
    return proj(layers.rms_norm(gated, norm_eps, p_(prefix, "gate_norm"),
                                groups=norm_groups),
                hidden_size, prefix, "out_proj")


def attention_mixer(n, hidden_size, num_heads, num_kv_heads, head_dim, scale,
                    prefix):
    seq_len, width = n.shape[1], num_heads * head_dim

    def heads(x, count):
        return layers.reshape(x, [-1, seq_len, count, head_dim])

    # flash_attention scales by 1/sqrt(head_dim): the rest goes on q
    q, on_q = proj(n, width, prefix, "wq"), scale * math.sqrt(head_dim)
    if on_q != 1.0:
        q = layers.scale(q, on_q)
    k = proj(n, num_kv_heads * head_dim, prefix, "wk")
    v = proj(n, num_kv_heads * head_dim, prefix, "wv")
    o = layers.flash_attention(heads(q, num_heads), heads(k, num_kv_heads),
                               heads(v, num_kv_heads), causal=True)
    return proj(layers.reshape(o, [-1, seq_len, width]), hidden_size, prefix,
                "wo")
