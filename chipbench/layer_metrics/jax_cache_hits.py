"""XLA compiles that JAX's persistent cache served during set-up."""


def compute(ctx):
    return ctx.before["compile"].get("jax_cache_hits", 0)
