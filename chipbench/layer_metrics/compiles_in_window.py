"""Compile requests inside the timed window (JAX's backend-compile event
plus the program's count of step traces).  Must be 0: a run where it is
not reports ``correct: false``."""


def compute(ctx):
    return ctx.compiles_in_window
