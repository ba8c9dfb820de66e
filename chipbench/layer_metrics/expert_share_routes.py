"""How many times a ``moe`` op was lowered as one chip's share of its
experts while the cell's programs were traced (``route/moe:share`` in
``profiler.compile_stats()``, at the end of set-up): engagement, read, not
assumed.  Nothing where the program counts no such route.  Also leaves
every ``route/*`` counter in ``detail["routes"]`` (held to nothing)."""


def compute(ctx):
    counters = ctx.before["compile"]
    ctx.detail["routes"] = {k: v for k, v in counters.items()
                            if k.startswith("route/")}
    return counters.get("route/moe:share")
