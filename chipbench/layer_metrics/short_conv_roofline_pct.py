"""The gated short convolutions against the chip's roofline, in percent:
the least time the chip could take for the FLOPs and bytes they need in a
step (``configs/<config>.py short_conv_work``: every ``conv`` layer run,
forward and backward; the bytes bound it) over the device time of every
event inside a ``pt.short_conv`` scope, whatever implements it (XLA's
fusions or a kernel)."""
from chipbench.layer_metrics.moe_experts_roofline_pct import (executed,
                                                              roofline_pct)


def compute(ctx):
    work = getattr(ctx.config, "short_conv_work", None)
    events = executed(ctx) if work else None
    if events is None:
        return None
    seconds = sum(s for s, op_name, _ in events if "pt.short_conv" in op_name)
    return roofline_pct(ctx, seconds,
                        *work(ctx.sizes, ctx.obs["items_per_step"]))
