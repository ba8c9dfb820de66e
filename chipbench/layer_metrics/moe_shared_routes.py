"""How many times a ``moe`` op was lowered with a shared expert while the
cell's programs were traced (``route/moe:shared`` in
``profiler.compile_stats()``, at the end of set-up): engagement, read, not
assumed.  Nothing where the program counts no such route.
(``route/moe:single``, the un-gated experts' route, is in
``detail["routes"]`` of a traced run, which ``expert_share_routes``
leaves.)"""


def compute(ctx):
    return ctx.before["compile"].get("route/moe:shared")
