"""Device time per training step in forward convolutions: events whose
innermost ``pt.`` scope is a ``conv2d`` / ``conv2d_transpose`` /
``depthwise_conv2d`` outside ``transpose(`` (``lib/op_attribution.py``: a
fusion goes by its heaviest instruction, so the batch-norm statistics
fused behind a convolution are convolution time)."""
from chipbench.lib import op_attribution


def compute(ctx):
    return op_attribution.class_ms_per_step(ctx, "conv", ("fwd",))
