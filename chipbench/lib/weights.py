"""Weights and inputs from ``--seed``, made on the device.

The startup program's random ops take their seed as a compile-time
constant (``Program.random_seed``), so a new seed there would be a new
program and a cold compile in every run.  The benchmark therefore runs the
startup program as it is (it also makes the state that is not drawn:
normalization statistics, optimizer accumulators, scales and shifts) and
then replaces every filter, matrix and table by ONE jitted draw whose key
is an argument: the same executable serves every seed, and every return to
the seeded start within a run.
"""
from __future__ import annotations

import math


def _is_weight(shape) -> bool:
    """A filter, matrix or table: at least two dimensions larger than 1.
    Everything else (biases [n] and [1, n], normalization scales and
    shifts) keeps the value the startup program gave it."""
    return sum(1 for d in shape if d > 1) >= 2


def _xavier_limit(shape) -> float:
    """Glorot-uniform limit (the layers API's own default initializer,
    re-derived), by rank: a matrix is [in, out]; a rank-3 weight is a stack
    of matrices [n, in, out], each drawn as the matrix it is used as
    (``layers.moe``'s experts [num_experts, D, hidden] and [num_experts,
    hidden, D], ``layers.bilinear_tensor_product``'s [size, dx, dy]); rank
    4 and more is a filter [out, in, k...]."""
    if len(shape) <= 3:
        fan_in, fan_out = shape[-2:]
    else:
        receptive = math.prod(shape[2:])
        fan_in, fan_out = shape[1] * receptive, shape[0] * receptive
    return math.sqrt(6.0 / (fan_in + fan_out))


def seeder(program, sharding=None):
    """``draw(seed) -> {name: array}`` for every trainable weight of
    ``program``: ONE uniform stream cut into the weights and scaled to each
    one's Xavier limit, jitted once (the seed is an argument)."""
    import jax
    import jax.numpy as jnp

    params = [(p.name, tuple(int(d) for d in p.shape))
              for p in program.global_block().all_parameters()
              if getattr(p, "trainable", True) and _is_weight(p.shape)]
    sizes = [math.prod(shape) for _, shape in params]

    def draw(key):
        flat = jax.random.uniform(key, (sum(sizes),), jnp.float32, -1.0, 1.0)
        out, at = {}, 0
        for (name, shape), n in zip(params, sizes):
            out[name] = (_xavier_limit(shape) * flat[at:at + n]).reshape(shape)
            at += n
        return out

    jitted = jax.jit(draw, out_shardings=sharding)
    return lambda seed: jitted(jax.random.PRNGKey(seed))


def reseed(scope, draw, seed: int):
    """Overwrite the weights in ``scope`` with ``draw(seed)`` (a
    ``seeder``'s)."""
    for name, value in draw(seed).items():
        scope.set(name, value)


def make_feeds(specs, batch: int, seed: int, sharding=None):
    """{name: device array [batch, ...]} from feed specs
    ({shape, dtype, high | fill}), one jitted draw keyed by ``seed``:
    floats standard normal (inputs as a pipeline normalizes them: zero
    mean, unit variance), integers uniform in [0, high), ``fill``
    constant."""
    import jax
    import jax.numpy as jnp

    names = sorted(specs)

    def draw(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            spec = specs[name]
            shape = (batch,) + tuple(spec["shape"])
            dtype = jax.dtypes.canonicalize_dtype(spec["dtype"])
            if "fill" in spec:
                out[name] = jnp.full(shape, spec["fill"], dtype)
            elif jnp.issubdtype(dtype, jnp.integer):
                out[name] = jax.random.randint(k, shape, 0, spec["high"],
                                               dtype)
            else:
                out[name] = jax.random.normal(k, shape, dtype)
        return out

    return jax.jit(draw, out_shardings=sharding)(jax.random.PRNGKey(seed))
