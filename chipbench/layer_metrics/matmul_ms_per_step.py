"""Device time per training step in ``mul`` / ``matmul`` / ``fc``, both
directions, by the innermost ``pt.`` scope (``lib/op_attribution.py``);
the step block of an ``rnn`` included."""
from chipbench.lib import op_attribution


def compute(ctx):
    return op_attribution.class_ms_per_step(ctx, "matmul")
