"""How many times a ``gated_delta_scan`` op was lowered while the cell's
programs were traced, by whatever route (``route/gated_delta_scan:*`` in
``profiler.compile_stats()`` summed, at the end of set-up): engagement of
the chunked delta rule, read, not assumed.  Nothing where the program counts
no such route.  WHICH route is in ``detail["routes"]`` of a traced run."""


def compute(ctx):
    found = [v for k, v in ctx.before["compile"].items()
             if k.startswith("route/gated_delta_scan:")]
    return sum(found) if found else None
