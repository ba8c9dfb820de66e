"""The part of the step during which a collective runs and no other
operation does on that device, in percent of the device's busy time."""


def compute(ctx):
    if ctx.trace is None or not ctx.trace.busy_s > 0:
        return None
    return 100.0 * ctx.trace.collective_exposed_s / ctx.trace.busy_s
