"""How many passes of a sub-block the ``repeat`` lowerings of the cell's
programs counted while they were traced (``repeat_passes`` in
``profiler.compile_stats()``, at the end of set-up): ``total_ut_steps`` for
one looped stack.  The form the lowering took (``route/repeat:inlined``)
goes to ``detail["routes"]`` with every other ``route/*`` counter (held to
nothing).  Nothing where the program counts no
pass."""


def compute(ctx):
    counters = ctx.before["compile"]
    ctx.detail["routes"] = {k: v for k, v in counters.items()
                            if k.startswith("route/")}
    return counters.get("repeat_passes")
