"""Device time per training step in backward convolutions, input and
filter gradients together: events whose innermost ``pt.`` scope is a
``conv2d`` / ``conv2d_transpose`` / ``depthwise_conv2d`` inside
``transpose(`` (``lib/op_attribution.py``: a weight-gradient convolution
fused with the optimizer update is convolution time)."""
from chipbench.lib import op_attribution


def compute(ctx):
    return op_attribution.class_ms_per_step(ctx, "conv", ("bwd",))
