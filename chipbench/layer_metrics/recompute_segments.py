"""How many stretches of the Program were lowered under ``jax.checkpoint``
while the cell's programs were traced (``route/recompute:checkpoint`` in
``profiler.compile_stats()``, at the end of set-up): engagement of the
recomputation, read, not assumed; 0 would mean the activations are kept.  A
loop traced once counts the stretches of its body once.  Nothing where the
program counts no such route."""


def compute(ctx):
    return ctx.before["compile"].get("route/recompute:checkpoint")
