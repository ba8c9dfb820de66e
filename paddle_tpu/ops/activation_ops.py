"""Activation op lowerings.

The reference registers ~20 activations in one file (activation_op.h, and the
v1 registry activations/ActivationFunction.cpp).  All are trivially jnp/lax —
XLA fuses them into the producing matmul/conv, replacing the handwritten CUDA
elementwise kernels (hl_cpu_*/hl_cuda_*).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def _unary(fn):
    def impl(ctx, ins, attrs):
        return {"Out": fn(ins["X"][0], attrs)}
    return impl


def _simple(fn):
    return _unary(lambda x, attrs: fn(x))


register_op("sigmoid")(_simple(jax.nn.sigmoid))
register_op("logsigmoid")(_simple(jax.nn.log_sigmoid))
register_op("tanh")(_simple(jnp.tanh))
register_op("relu")(_simple(jax.nn.relu))
register_op("relu6")(_unary(lambda x, a: jnp.clip(x, 0.0, a.get("threshold", 6.0))))
register_op("abs")(_simple(jnp.abs))
register_op("sqrt")(_simple(jnp.sqrt))
register_op("rsqrt")(_simple(jax.lax.rsqrt))
register_op("square")(_simple(jnp.square))
register_op("exp")(_simple(jnp.exp))
register_op("log")(_simple(jnp.log))
register_op("floor")(_simple(jnp.floor))
register_op("ceil")(_simple(jnp.ceil))
register_op("round")(_simple(jnp.round))
register_op("reciprocal")(_simple(lambda x: 1.0 / x))
register_op("softsign")(_simple(jax.nn.soft_sign))
register_op("softplus", "softrelu")(_simple(jax.nn.softplus))
register_op("sin")(_simple(jnp.sin))
register_op("cos")(_simple(jnp.cos))
register_op("gelu")(_simple(jax.nn.gelu))
register_op("silu", "swish")(_simple(jax.nn.silu))


@register_op("brelu")
def _brelu(ctx, ins, attrs):
    """v1 brelu: clip(x, t_min, t_max) (ActivationFunction.cpp brelu)."""
    return {"Out": jnp.clip(ins["X"][0], attrs.get("t_min", 0.0),
                            attrs.get("t_max", 24.0))}


@register_op("leaky_relu")
def _leaky_relu(ctx, ins, attrs):
    return {"Out": jax.nn.leaky_relu(ins["X"][0],
                                     attrs.get("alpha", 0.02))}


@register_op("elu")
def _elu(ctx, ins, attrs):
    return {"Out": jax.nn.elu(ins["X"][0], attrs.get("alpha", 1.0))}


@register_op("stanh")
def _stanh(ctx, ins, attrs):
    """scaled tanh: b * tanh(a * x) (activation_op.h STanh)."""
    a = attrs.get("scale_a", 2.0 / 3.0)
    b = attrs.get("scale_b", 1.7159)
    return {"Out": b * jnp.tanh(a * ins["X"][0])}


@register_op("hard_shrink")
def _hard_shrink(ctx, ins, attrs):
    x = ins["X"][0]
    t = attrs.get("threshold", 0.5)
    return {"Out": jnp.where(jnp.abs(x) > t, x, jnp.zeros_like(x))}


@register_op("soft_shrink", "softshrink")
def _soft_shrink(ctx, ins, attrs):
    x = ins["X"][0]
    lam = attrs.get("lambda", 0.5)
    return {"Out": jnp.where(x > lam, x - lam,
                             jnp.where(x < -lam, x + lam, jnp.zeros_like(x)))}


@register_op("thresholded_relu")
def _thresholded_relu(ctx, ins, attrs):
    x = ins["X"][0]
    t = attrs.get("threshold", 1.0)
    return {"Out": jnp.where(x > t, x, jnp.zeros_like(x))}


@register_op("hard_sigmoid")
def _hard_sigmoid(ctx, ins, attrs):
    x = ins["X"][0]
    slope = attrs.get("slope", 0.2)
    offset = attrs.get("offset", 0.5)
    return {"Out": jnp.clip(x * slope + offset, 0.0, 1.0)}


@register_op("prelu")
def _prelu(ctx, ins, attrs):
    """prelu_op: learned negative slope — mode all (scalar), channel
    (alpha [C], x [N,C,...]) or element (alpha = x.shape[1:])."""
    x = ins["X"][0]
    alpha = ins["Alpha"][0]
    if alpha.size > 1:
        if alpha.ndim == x.ndim - 1:            # element mode
            alpha = alpha.reshape((1,) + alpha.shape)
        else:                                   # channel mode
            alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return {"Out": jnp.where(x >= 0, x, alpha * x)}


# ---------------------------------------------------------------------------
# Static shape/dtype rules (analysis.shape_infer): every activation maps
# X -> Out elementwise, so one same_as rule covers the whole file — the
# InferShape analog of activation_op.h's UnaryOpUnchangedInferShape.
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import same_as  # noqa: E402
from ..core.registry import register_shape_fn  # noqa: E402

register_shape_fn(
    "sigmoid", "logsigmoid", "tanh", "relu", "relu6", "abs", "sqrt",
    "rsqrt", "square", "exp", "log", "floor", "ceil", "round",
    "reciprocal", "softsign", "softplus", "softrelu", "sin", "cos",
    "gelu", "silu", "swish", "brelu", "leaky_relu", "elu", "stanh",
    "hard_shrink", "soft_shrink", "softshrink", "thresholded_relu",
    "hard_sigmoid", "prelu",
)(same_as("X"))

# ---------------------------------------------------------------------------
# Sharding-propagation rules (analysis.shard_prop): activations are
# elementwise, so outputs carry their input's per-dim sharding unchanged.
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import shard_same_as  # noqa: E402
from ..core.registry import register_shard_fn  # noqa: E402

register_shard_fn(
    "sigmoid", "logsigmoid", "tanh", "relu", "relu6", "abs", "sqrt",
    "rsqrt", "square", "exp", "log", "floor", "ceil", "round",
    "reciprocal", "softsign", "softplus", "softrelu", "sin", "cos",
    "gelu", "silu", "swish", "brelu", "leaky_relu", "elu", "stanh",
    "hard_shrink", "soft_shrink", "softshrink", "thresholded_relu",
    "hard_sigmoid", "prelu",
)(shard_same_as("X"))

# ---------------------------------------------------------------------------
# Row-wise rules (core.registry.register_rowwise): a unary elementwise op
# maps row i to row i.  prelu is left out: its Alpha is a second operand.
# ---------------------------------------------------------------------------
from ..core.registry import register_rowwise, rows_of_one_rank  # noqa: E402

register_rowwise(
    "sigmoid", "logsigmoid", "tanh", "relu", "relu6", "abs", "sqrt",
    "rsqrt", "square", "exp", "log", "floor", "ceil", "round",
    "reciprocal", "softsign", "softplus", "softrelu", "sin", "cos",
    "gelu", "silu", "swish", "brelu", "leaky_relu", "elu", "stanh",
    "hard_shrink", "soft_shrink", "softshrink", "thresholded_relu",
    "hard_sigmoid",
)(rows_of_one_rank)
