"""Device time per training step: the union of the device-operation
intervals in the traced windows (averaged over the chips) over the steps
in them."""


def compute(ctx):
    if ctx.trace is None or not ctx.trace.steps:
        return None
    return ctx.trace.busy_s * 1e3 / ctx.trace.steps
