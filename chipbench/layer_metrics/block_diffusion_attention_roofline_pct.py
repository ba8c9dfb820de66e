"""The block-diffusion attention against the chip's roofline, in percent:
the least time the chip could take for the FLOPs and bytes the op needs in
a step (``configs/<config>.py block_diffusion_attention_work``: the
mask's L^2 + L * block entries a head of every layer run, forward and
backward, operands read and results written once a direction; the FLOPs
bound it) over the device time ``block_diffusion_attention_step_ms`` reads,
the SAME rows of the join (forward, backward and again where recomputed,
whatever implements the op): one time base, so that a change moves both or
neither.  The work is the mask's whatever computes it, so a kernel that
computed the tiles the mask empties reads lower."""
from chipbench.layer_metrics import block_diffusion_attention_step_ms
from chipbench.layer_metrics.moe_experts_roofline_pct import roofline_pct


def compute(ctx):
    work = getattr(ctx.config, "block_diffusion_attention_work", None)
    if work is None or ctx.trace is None:
        return None
    ms = block_diffusion_attention_step_ms.compute(ctx)
    if ms is None:
        return None
    return roofline_pct(ctx, ms * ctx.trace.steps / 1e3,
                        *work(ctx.sizes, ctx.obs["items_per_step"]))
