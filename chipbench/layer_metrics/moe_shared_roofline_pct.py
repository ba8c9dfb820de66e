"""The shared experts against the chip's roofline, in percent: the least
time the chip could take for the FLOPs and bytes they need in a step
(``configs/<config>.py moe_shared_work``: two dense products over every
token forward and four backward in every expert layer run; the FLOPs bound
it) over the device time ``moe_shared_step_ms`` reads, the SAME events (one
time base).  What a recomputed layer executes again is in the time and not
in the work."""
from chipbench.layer_metrics import moe_shared_step_ms
from chipbench.layer_metrics.moe_experts_roofline_pct import roofline_pct


def compute(ctx):
    work = getattr(ctx.config, "moe_shared_work", None)
    inside = moe_shared_step_ms.seconds(ctx) if work else None
    if inside is None:
        return None
    return roofline_pct(ctx, inside,
                        *work(ctx.sizes, ctx.obs["items_per_step"]))
