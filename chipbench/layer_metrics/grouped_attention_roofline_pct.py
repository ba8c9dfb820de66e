"""The grouped-query attention kernels against the chip's roofline, in
percent: the least time the chip could take for the FLOPs and bytes
attention needs in a step (``configs/<config>.py grouped_attention_work``:
half the T x T square at the query heads, K and V moved at the heads they
have) over the device time of the kernels' own events (the custom calls
inside a ``pt.flash_attention`` scope), forward and backward."""
from chipbench.layer_metrics.moe_experts_roofline_pct import (executed,
                                                              roofline_pct)


def compute(ctx):
    work = getattr(ctx.config, "grouped_attention_work", None)
    events = executed(ctx) if work else None
    if events is None:
        return None
    seconds = sum(s for s, op_name, opcode in events
                  if "pt.flash_attention" in op_name
                  and opcode == "custom-call")
    return roofline_pct(ctx, seconds, *work(
        ctx.sizes, ctx.obs["items_per_step"] // ctx.sizes["seq_len"]))
