"""Device time per training step in the ``block_diffusion_attention`` op
(the noisy and clean streams' attention under the block-diffusion mask:
the forward kernel, the backward kernels, the rows' delta and the moves of
q, k, v between a sequence's two streams and its 2L positions that the op
lowers; the projections, norms and rope around it are other ops), both
directions and again where a layer is recomputed, by the innermost ``pt.``
scope (``lib/op_attribution.py``)."""
from chipbench.lib import op_attribution


def compute(ctx):
    joined = op_attribution.join(ctx)
    if not joined["ok"]:
        return None
    found = [r["ms_per_step"] for r in joined["rows"]
             if r["op_type"] == "block_diffusion_attention"]
    return sum(found) if found else None
