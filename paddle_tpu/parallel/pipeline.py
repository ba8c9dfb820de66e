"""Pipeline parallelism over the 'pp' mesh axis — per-stage parameters,
GPipe microbatching, differentiable end to end.

The reference's nearest ancestor is ParallelNeuralNetwork.cpp: whole layers
pinned to devices with queue-pipelined activations (SURVEY §2.6 "Model
parallelism (v1)").  The TPU-native redesign:

* Stage parameters are STACKED on a leading [n_stages, ...] axis and sharded
  ``PartitionSpec('pp', ...)`` — each device physically holds only its own
  stage's weights (true model-memory scaling, not a replicated-weight
  scaffold).  Inside ``shard_map`` every device sees its [1, ...] slice.
* The forward is a lax.scan over (microbatches + stages - 1) ticks;
  activations hop stages with ppermute.  Every collective is differentiable,
  so ``jax.grad`` through the whole pipelined step yields per-stage gradients
  with the SAME 'pp' sharding — the backward pipeline falls out of autodiff
  rather than being hand-scheduled (contrast the reference's explicit
  backward activation queues).
* ``remat=True`` wraps each stage in jax.checkpoint: activation memory drops
  to O(microbatch) and the backward replays stage forwards — the GPipe
  recompute schedule.

Heterogeneous stacks (stages that cannot share one stacked pytree) can still
pipeline compute via ``switch_stage_fn`` (lax.switch on the stage index with
replicated params) — pipelined time, unsharded memory; a documented
tradeoff, with uniform stacked stages as the first-class path.
"""
from __future__ import annotations

import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P


__all__ = ["pipeline_forward", "pipeline_spmd_fn", "stack_stage_params",
           "place_stage_params", "make_pipeline_train_step",
           "switch_stage_fn"]


def stack_stage_params(*stages):
    """Stack S same-structure per-stage pytrees into one pytree whose leaves
    carry a leading [S, ...] stage axis (to be sharded P('pp', ...))."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *stages)


def place_stage_params(params, mesh, axis_name: str = "pp"):
    """device_put stacked stage params so the stage axis lives on ``pp``."""
    def put(x):
        spec = P(axis_name, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(mesh, spec))
    return jax.tree.map(put, params)


def pipeline_forward(stage_fn: Callable, stage_params, x_microbatches,
                     axis_name: str = "pp"):
    """GPipe forward inside shard_map.

    stage_fn(params, x) -> y: one stage's computation.  ``stage_params`` is
    THIS device's slice of the stacked params — leaves [1, ...] (shard_map
    over P('pp', ...)); the leading axis is squeezed before stage_fn sees
    it.  x_microbatches: [M, ...] stacked microbatches (stage 0 injects
    them).  Returns [M, ...] last-stage outputs, replicated over the axis.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    my = jax.tree.map(lambda x: x[0], stage_params)
    M = x_microbatches.shape[0]
    ticks = M + n - 1
    perm = [(i, (i + 1) % n) for i in range(n)]
    out_aval = jax.eval_shape(functools.partial(stage_fn, my),
                              x_microbatches[0])

    def tick(carry, t):
        buf, outs = carry
        # stage 0 injects microbatch t (if in range); others use what arrived
        inject = jnp.where(t < M, t, M - 1)
        x0 = x_microbatches[inject]
        x = jnp.where(idx == 0, x0, buf)
        y = stage_fn(my, x)
        # last stage records its result at slot t-(n-1)
        slot = t - (n - 1)
        valid = (idx == n - 1) & (slot >= 0)
        slot_c = jnp.clip(slot, 0, M - 1)
        cur = lax.dynamic_index_in_dim(outs, slot_c, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, y, cur), slot_c, 0)
        buf_next = lax.ppermute(y, axis_name, perm)
        return (buf_next, outs), None

    buf0 = jnp.zeros(out_aval.shape, out_aval.dtype)
    outs0 = jnp.zeros((M,) + buf0.shape, buf0.dtype)
    # carries become device-varying (ppermute / axis_index); mark the inits
    buf0 = lax.pcast(buf0, (axis_name,), to="varying")
    outs0 = lax.pcast(outs0, (axis_name,), to="varying")
    (_, outs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(ticks))
    # only the last stage holds real results; psum broadcasts them so the
    # output is replicated over pp (callers can use out_specs=P())
    return lax.psum(jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)),
                    axis_name)


def pipeline_spmd_fn(stage_fn: Callable, loss_fn: Callable, mesh,
                     num_microbatches: int, axis_name: str = "pp",
                     remat: bool = False):
    """Build loss(params, x, y) running the stacked-params GPipe pipeline
    under shard_map — differentiable, so jax.grad(loss) yields gradients
    sharded P('pp', ...) exactly like the params.

    stage_fn(stage_params, x) -> y;  loss_fn(last_stage_out, labels) ->
    scalar per microbatch.  x: [B, ...] global batch with
    B % num_microbatches == 0; labels likewise.
    """
    from ..compat import shard_map

    sfn = jax.checkpoint(stage_fn) if remat else stage_fn

    def per_device(params, x, y):
        M = num_microbatches
        xs = x.reshape((M, x.shape[0] // M) + x.shape[1:])
        ys = y.reshape((M, y.shape[0] // M) + y.shape[1:])
        outs = pipeline_forward(sfn, params, xs, axis_name)
        losses = jax.vmap(loss_fn)(outs, ys)
        return jnp.mean(losses)

    def loss(params, x, y):
        param_specs = jax.tree.map(
            lambda v: P(axis_name, *([None] * (v.ndim - 1))), params)
        f = shard_map(per_device, mesh=mesh,
                      in_specs=(param_specs, P(), P()), out_specs=P())
        return f(params, x, y)

    return loss


def make_pipeline_train_step(stage_fn: Callable, loss_fn: Callable, mesh,
                             num_microbatches: int, learning_rate: float,
                             momentum: float = 0.0, axis_name: str = "pp",
                             remat: bool = False):
    """jitted (params, velocity, x, y) -> (params', velocity', loss): GPipe
    training step with SGD(+momentum) on the pp-sharded stage params
    (updates are elementwise, so they preserve the 'pp' placement)."""
    loss = pipeline_spmd_fn(stage_fn, loss_fn, mesh, num_microbatches,
                            axis_name, remat=remat)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, velocity, x, y):
        lval, grads = jax.value_and_grad(loss)(params, x, y)
        velocity = jax.tree.map(lambda v, g: momentum * v + g, velocity,
                                grads)
        params = jax.tree.map(lambda p, v: p - learning_rate * v, params,
                              velocity)
        return params, velocity, lval

    return step


def switch_stage_fn(stage_fns: Sequence[Callable], params_tuple,
                    axis_name: str = "pp"):
    """Adapter for HETEROGENEOUS stages: returns stage_fn(_, x) that
    lax.switches on this device's stage index over ``stage_fns`` with the
    matching pytree from ``params_tuple`` (closed over, passed REPLICATED —
    compute is pipelined, memory is not sharded).  Inter-stage activations
    must share one shape/dtype."""
    def fn(_, x):
        idx = lax.axis_index(axis_name)
        branches = [functools.partial(lambda f, p, xx: f(p, xx), f, p)
                    for f, p in zip(stage_fns, params_tuple)]
        return lax.switch(idx, branches, x)
    return fn
