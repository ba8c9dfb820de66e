"""Optimizer op lowerings.

The reference implements optimizers *as graph ops* taking param/grad/moments
as inputs and producing updated outputs (SURVEY §2.2 "Optimizers (as ops!)":
sgd_op, momentum_op, adam_op.cc/.h, adamax_op, adagrad_op, adadelta_op,
decayed_adagrad_op, rmsprop_op, ftrl_op, proximal_gd_op,
proximal_adagrad_op).  We keep that design: updates are pure functions inside
the compiled step, so the whole train step (fwd+bwd+update) is ONE XLA
computation with donated parameter buffers — no per-parameter kernel launches.

All moments are persistable scope vars created by paddle_tpu.optimizer
(the fluid optimizer.py accumulator pattern)."""
from __future__ import annotations

import jax.numpy as jnp

from ..core.registry import register_op


def _lr(ins):
    return ins["LearningRate"][0].reshape(())


@register_op("sgd")
def _sgd(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    return {"ParamOut": p - _lr(ins) * g}


@register_op("momentum")
def _momentum(ctx, ins, attrs):
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    mu = attrs.get("mu", 0.9)
    lr = _lr(ins)
    v_out = mu * v + g
    if attrs.get("use_nesterov", False):
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    return {"ParamOut": p_out, "VelocityOut": v_out}


@register_op("adam")
def _adam(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m, v = ins["Moment1"][0], ins["Moment2"][0]
    b1p = ins["Beta1Pow"][0].reshape(())
    b2p = ins["Beta2Pow"][0].reshape(())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr = _lr(ins)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    out = {"Beta1PowOut": (b1p * b1).reshape(1),
           "Beta2PowOut": (b2p * b2).reshape(1)}
    if attrs.get("lazy_mode") and "Rows" in ins:
        # adam_op.cc lazy_mode: touch only the rows the batch looked up.
        # The dense grad row already sums duplicate ids, so per-row values
        # are identical across duplicates and .at[ids].set is idempotent;
        # untouched rows keep stale moments (reference sparse semantics).
        ids = jnp.concatenate([i.reshape(-1) for i in ins["Rows"]])
        g_r = g[ids]
        m_r = b1 * m[ids] + (1 - b1) * g_r
        v_r = b2 * v[ids] + (1 - b2) * jnp.square(g_r)
        p_r = p[ids] - lr_t * m_r / (jnp.sqrt(v_r) + eps)
        mode = "promise_in_bounds"
        out.update({
            "ParamOut": p.at[ids].set(p_r.astype(p.dtype), mode=mode),
            "Moment1Out": m.at[ids].set(m_r.astype(m.dtype), mode=mode),
            "Moment2Out": v.at[ids].set(v_r.astype(v.dtype), mode=mode)})
        return out
    m_out = b1 * m + (1 - b1) * g
    v_out = b2 * v + (1 - b2) * jnp.square(g)
    p_out = p - lr_t * m_out / (jnp.sqrt(v_out) + eps)
    out.update({"ParamOut": p_out, "Moment1Out": m_out,
                "Moment2Out": v_out})
    return out


@register_op("adamax")
def _adamax(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m, inf = ins["Moment"][0], ins["InfNorm"][0]
    b1p = ins["Beta1Pow"][0].reshape(())
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    lr = _lr(ins)
    m_out = b1 * m + (1 - b1) * g
    inf_out = jnp.maximum(b2 * inf, jnp.abs(g))
    p_out = p - (lr / (1 - b1p)) * m_out / (inf_out + eps)
    return {"ParamOut": p_out, "MomentOut": m_out, "InfNormOut": inf_out,
            "Beta1PowOut": (b1p * b1).reshape(1)}


@register_op("adagrad")
def _adagrad(ctx, ins, attrs):
    p, g, mom = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    eps = attrs.get("epsilon", 1e-6)
    m_out = mom + jnp.square(g)
    p_out = p - _lr(ins) * g / (jnp.sqrt(m_out) + eps)
    return {"ParamOut": p_out, "MomentOut": m_out}


@register_op("adadelta")
def _adadelta(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    avg_sq_g, avg_sq_u = ins["AvgSquaredGrad"][0], ins["AvgSquaredUpdate"][0]
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    g2 = rho * avg_sq_g + (1 - rho) * jnp.square(g)
    upd = -jnp.sqrt((avg_sq_u + eps) / (g2 + eps)) * g
    u2 = rho * avg_sq_u + (1 - rho) * jnp.square(upd)
    return {"ParamOut": p + upd, "AvgSquaredGradOut": g2,
            "AvgSquaredUpdateOut": u2}


@register_op("decayed_adagrad")
def _decayed_adagrad(ctx, ins, attrs):
    p, g, mom = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    m_out = decay * mom + (1 - decay) * jnp.square(g)
    p_out = p - _lr(ins) * g / (jnp.sqrt(m_out) + eps)
    return {"ParamOut": p_out, "MomentOut": m_out}


@register_op("rmsprop")
def _rmsprop(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    ms, mom = ins["MeanSquare"][0], ins["Moment"][0]
    rho = attrs.get("decay", 0.95)
    mu = attrs.get("momentum", 0.0)
    eps = attrs.get("epsilon", 1e-6)
    ms_out = rho * ms + (1 - rho) * jnp.square(g)
    mom_out = mu * mom + _lr(ins) * g / jnp.sqrt(ms_out + eps)
    return {"ParamOut": p - mom_out, "MomentOut": mom_out,
            "MeanSquareOut": ms_out}


@register_op("ftrl")
def _ftrl(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    sq_acc, lin_acc = ins["SquaredAccumulator"][0], ins["LinearAccumulator"][0]
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    lr = _lr(ins)
    new_sq = sq_acc + jnp.square(g)
    sigma = (jnp.power(new_sq, -power) - jnp.power(sq_acc, -power)) / lr
    new_lin = lin_acc + g - sigma * p
    denom = jnp.power(new_sq, -power) / lr + 2 * l2
    pre = jnp.clip(new_lin, -l1, l1) - new_lin
    p_out = pre / denom
    return {"ParamOut": p_out, "SquaredAccumOut": new_sq,
            "LinearAccumOut": new_lin}


@register_op("proximal_gd")
def _proximal_gd(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr = _lr(ins)
    prox = p - lr * g
    if l1 > 0:
        prox = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
    return {"ParamOut": prox / (1.0 + lr * l2)}


@register_op("proximal_adagrad")
def _proximal_adagrad(ctx, ins, attrs):
    p, g, mom = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr = _lr(ins)
    m_out = mom + jnp.square(g)
    eff_lr = lr / jnp.sqrt(m_out)
    prox = p - eff_lr * g
    if l1 > 0:
        prox = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - eff_lr * l1, 0.0)
    return {"ParamOut": prox / (1.0 + eff_lr * l2), "MomentOut": m_out}


# ---------------------------------------------------------------------------
# Static shape/dtype rules: every optimizer op mirrors its state inputs to
# the matching *Out slots (the reference's Param/Grad same-dims CHECKs in
# sgd_op.cc etc. become an explicit Param-vs-Grad shape check).
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import (ShapeError, first, mirror,  # noqa: E402
                                    shapes_compatible)
from ..core.registry import register_shape_fn  # noqa: E402


def _opt_rule(mapping):
    base = mirror(mapping)

    def rule(op, ins, attrs):
        p, g = first(ins, "Param"), first(ins, "Grad")
        if not shapes_compatible(p.shape, g.shape):
            raise ShapeError(
                f"Param {list(p.shape)} vs Grad {list(g.shape)} dims differ")
        return base(op, ins, attrs)

    return rule


register_shape_fn("sgd")(_opt_rule({"ParamOut": "Param"}))
register_shape_fn("momentum")(_opt_rule(
    {"ParamOut": "Param", "VelocityOut": "Velocity"}))
register_shape_fn("adam")(_opt_rule(
    {"ParamOut": "Param", "Moment1Out": "Moment1", "Moment2Out": "Moment2",
     "Beta1PowOut": "Beta1Pow", "Beta2PowOut": "Beta2Pow"}))
register_shape_fn("adamax")(_opt_rule(
    {"ParamOut": "Param", "MomentOut": "Moment", "InfNormOut": "InfNorm",
     "Beta1PowOut": "Beta1Pow"}))
register_shape_fn("adagrad", "decayed_adagrad", "proximal_adagrad")(
    _opt_rule({"ParamOut": "Param", "MomentOut": "Moment"}))
register_shape_fn("adadelta")(_opt_rule(
    {"ParamOut": "Param", "AvgSquaredGradOut": "AvgSquaredGrad",
     "AvgSquaredUpdateOut": "AvgSquaredUpdate"}))
register_shape_fn("rmsprop")(_opt_rule(
    {"ParamOut": "Param", "MomentOut": "Moment",
     "MeanSquareOut": "MeanSquare"}))
register_shape_fn("ftrl")(_opt_rule(
    {"ParamOut": "Param", "SquaredAccumOut": "SquaredAccumulator",
     "LinearAccumOut": "LinearAccumulator"}))
register_shape_fn("proximal_gd")(_opt_rule({"ParamOut": "Param"}))


# ---------------------------------------------------------------------------
# Sharding-propagation rules (analysis.shard_prop): every optimizer op
# keeps its state on the parameter's sharding (the dp-reduced gradient
# arrives in the param's layout; accumulators ride along), with the
# Param-vs-Grad merge surfacing layout mismatches as PT041.
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import shard_mirror  # noqa: E402
from ..core.registry import register_shard_fn  # noqa: E402

register_shard_fn("sgd", "proximal_gd")(shard_mirror(
    {"ParamOut": "Param"}, check_grad=True))
register_shard_fn("momentum")(shard_mirror(
    {"ParamOut": "Param", "VelocityOut": "Velocity"}, check_grad=True))
register_shard_fn("adam")(shard_mirror(
    {"ParamOut": "Param", "Moment1Out": "Moment1",
     "Moment2Out": "Moment2", "Beta1PowOut": "Beta1Pow",
     "Beta2PowOut": "Beta2Pow"}, check_grad=True))
register_shard_fn("adamax")(shard_mirror(
    {"ParamOut": "Param", "MomentOut": "Moment", "InfNormOut": "InfNorm",
     "Beta1PowOut": "Beta1Pow"}, check_grad=True))
register_shard_fn("adagrad", "decayed_adagrad", "proximal_adagrad")(
    shard_mirror({"ParamOut": "Param", "MomentOut": "Moment"},
                 check_grad=True))
register_shard_fn("adadelta")(shard_mirror(
    {"ParamOut": "Param", "AvgSquaredGradOut": "AvgSquaredGrad",
     "AvgSquaredUpdateOut": "AvgSquaredUpdate"}, check_grad=True))
register_shard_fn("rmsprop")(shard_mirror(
    {"ParamOut": "Param", "MomentOut": "Moment",
     "MeanSquareOut": "MeanSquare"}, check_grad=True))
register_shard_fn("ftrl")(shard_mirror(
    {"ParamOut": "Param", "SquaredAccumOut": "SquaredAccumulator",
     "LinearAccumOut": "LinearAccumulator"}, check_grad=True))
