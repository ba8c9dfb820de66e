"""How many times a ``moe`` op was lowered dropless while the cell's
programs were traced (``route/moe:dropless`` in
``profiler.compile_stats()``, at the end of set-up): engagement of the
sorted lowering, read, not assumed.  Nothing where the program counts no
such route.  Also leaves two diagnostics in ``detail`` (held to nothing):
every ``route/*`` counter, and what the configuration's reference saw of
the router's margins, if it keeps that."""


def compute(ctx):
    counters = ctx.before["compile"]
    ctx.detail["routes"] = {k: v for k, v in counters.items()
                            if k.startswith("route/")}
    margin = getattr(ctx.config, "LAST_ROUTER_MARGIN", None)
    if margin:
        ctx.detail["router_margin"] = dict(margin)
    return counters.get("route/moe:dropless")
