"""Device time per training step in optimizer ops (every op of
``paddle_tpu/ops/optimizer_ops.py``: ``momentum``, ``adam``, ``sgd`` ...),
by the innermost ``pt.`` scope (``lib/op_attribution.py``).  An update
that XLA fused behind a weight-gradient convolution or product is counted
there, by the fusion rule, and not here."""
from chipbench.lib import op_attribution


def compute(ctx):
    return op_attribution.class_ms_per_step(ctx, "optimizer")
