"""1 - busy / traced window, in percent, from the profiler's trace."""


def compute(ctx):
    if ctx.trace is None or not ctx.trace.window_s > 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
