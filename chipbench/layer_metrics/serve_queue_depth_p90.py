"""90th percentile of the admission queue's depth as each batch was
formed (``serving/queue_depth`` histogram, window only; the upper edge of
the bucket that holds it)."""
from chipbench.lib.stats import histogram_delta_quantile

NAME = "serving/queue_depth"


def compute(ctx):
    return histogram_delta_quantile(ctx.before["registry"][NAME],
                                    ctx.after["registry"][NAME], 0.90)
