"""Async-SGD's TPU-native successor: local SGD (periodic parameter
averaging).

Reference capability: asyncSGD (pserver/ParameterServer2.h:468,
trainer/TrainerConfigHelper + async_lagged_grad_discard_ratio) let trainers
apply gradients WITHOUT a global barrier, tolerating staleness to keep slow
workers from stalling the fleet.  On a TPU mesh there is no parameter
server to be async *against* — the analogous capability is to decouple
replicas between syncs:

* each dp replica runs K local SGD steps on its own batch shard with NO
  collective (replica parameters drift, exactly like pserver-era staleness,
  but bounded by K);
* every K steps one pmean restores consensus (one collective per K steps
  instead of per step — the same comm-hiding asyncSGD bought, with a
  deterministic staleness bound instead of unbounded lag).

K=1 reduces to synchronous data parallelism (gradient pmean every step is
replaced by parameter pmean after the update — identical for SGD).  The
async_lagged discard knob maps to choosing K.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


__all__ = ["make_local_sgd_step"]


def make_local_sgd_step(loss_fn, mesh, sync_every: int, learning_rate: float,
                        axis_name: str = "dp"):
    """Build a jitted (params, x, y) -> (params', mean_loss) step running
    ``sync_every`` LOCAL SGD steps per call followed by one parameter pmean.

    loss_fn(params, x, y) -> scalar on one replica's shard; x/y arrive
    [B, ...] and are split B/n per replica on dim 0.  Each call consumes
    ``sync_every`` microbatches sliced from the leading batch dim.
    """
    from ..compat import shard_map

    grad_fn = jax.value_and_grad(loss_fn)

    def per_replica(params, x, y):
        K = sync_every
        # params arrive replicated; mark them device-VARYING so jax.grad
        # inside the body yields each replica's LOCAL gradient (the new
        # shard_map autodiff would otherwise psum cotangents of replicated
        # values on every step — the exact collective local SGD elides)
        params = jax.tree.map(
            lambda p: lax.pcast(p, (axis_name,), to="varying"), params)
        xs = x.reshape((K, x.shape[0] // K) + x.shape[1:])
        ys = y.reshape((K, y.shape[0] // K) + y.shape[1:])

        def local_step(params, xy):
            xb, yb = xy
            lval, g = grad_fn(params, xb, yb)
            params = jax.tree.map(lambda p, gr: p - learning_rate * gr,
                                  params, g)
            return params, lval

        params, losses = lax.scan(local_step, params, (xs, ys))
        # consensus: one collective per K local steps (the async-SGD
        # communication saving, with staleness bounded by K)
        params = jax.tree.map(lambda p: lax.pmean(p, axis_name), params)
        return params, lax.pmean(jnp.mean(losses), axis_name)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(params, x, y):
        specs = jax.tree.map(lambda _: P(), params)
        f = shard_map(per_replica, mesh=mesh,
                      in_specs=(specs, P(axis_name), P(axis_name)),
                      out_specs=(specs, P()))
        return f(params, x, y)

    return step
