"""Model-FLOP utilization, in percent: the FLOPs the forward and backward
passes need per item (``configs/<config>.py flops_per_item``) times the
items per second per chip of the untraced windows, over the chip's
published bf16 peak (``lib/peaks.py``).  An end-to-end utilization; it is
not a kernel's roofline share and says nothing about idle time."""
from chipbench.lib.rates import train_items_per_s_per_chip


def compute(ctx):
    rate = train_items_per_s_per_chip(ctx)
    if ctx.peaks is None or rate is None:
        return None
    return 100.0 * ctx.config.flops_per_item(ctx.sizes, "train") * rate \
        / ctx.peaks.bf16_flops
