"""How many times an ``ssd_scan`` op was lowered while the cell's programs
were traced, by whatever route (``route/ssd_scan:*`` in
``profiler.compile_stats()`` summed, at the end of set-up): engagement of
the chunked recurrence, read, not assumed.  Nothing where the program counts
no such route.  WHICH route is in ``detail["routes"]`` of a traced run
(``loss_from_logits_routes`` leaves every ``route/*`` counter there)."""


def compute(ctx):
    found = [v for k, v in ctx.before["compile"].items()
             if k.startswith("route/ssd_scan:")]
    return sum(found) if found else None
