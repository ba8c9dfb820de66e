"""Device time per training step in ``softmax`` / ``cross_entropy`` /
``softmax_with_cross_entropy``, both directions, by the innermost ``pt.``
scope (``lib/op_attribution.py``)."""
from chipbench.lib import op_attribution


def compute(ctx):
    return op_attribution.class_ms_per_step(ctx, "softmax_loss")
