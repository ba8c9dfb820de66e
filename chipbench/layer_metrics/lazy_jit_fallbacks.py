"""Mesh-step calls that left the ahead-of-time executable for a lazily
specialized jit (``compile_stats``), over set-up and window: each first
one is a second compile of the step."""


def compute(ctx):
    return ctx.after["compile"].get("lazy_jit_fallbacks", 0)
