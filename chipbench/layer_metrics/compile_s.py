"""Seconds the program's step variants spent in trace + lower + compile
during set-up (``profiler.compile_stats()``; with a warm persistent cache
the compile part is a disk read)."""


def compute(ctx):
    return ctx.before["compile_seconds"]
