"""Device time per training step in ``batch_norm`` / ``layer_norm``, both
directions, by the innermost ``pt.`` scope (``lib/op_attribution.py``)."""
from chipbench.lib import op_attribution


def compute(ctx):
    return op_attribution.class_ms_per_step(ctx, "norm")
