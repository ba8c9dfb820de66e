"""Device time in collective operations (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) per training step, on one
device, from the trace."""


def compute(ctx):
    if ctx.trace is None or not ctx.trace.steps:
        return None
    return ctx.trace.collective_s * 1e3 / ctx.trace.steps
