"""How many times a hard-label cross-entropy was lowered from the logits of
the softmax it reads (``route/cross_entropy:from_logits`` in
``profiler.compile_stats()``, at the end of set-up: ``cross_entropy`` behind
a ``softmax`` the lowering could see, and ``softmax_with_cross_entropy``):
engagement, read, not assumed.  Nothing where the program counts no such
route.  Leaves every ``route/*`` counter in ``detail`` (held to nothing), so
that ``route/cross_entropy:probabilities`` shows beside it."""


def compute(ctx):
    counters = ctx.before["compile"]
    ctx.detail["routes"] = {k: v for k, v in counters.items()
                            if k.startswith("route/")}
    return counters.get("route/cross_entropy:from_logits")
