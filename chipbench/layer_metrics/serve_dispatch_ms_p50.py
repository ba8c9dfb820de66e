"""Median host time of one model call of the server (host rows ->
``Executor.run`` -> device -> drained), from the ``cb:model_fn`` span the
benchmark puts around the function it hands to ``serving.Model``."""
from chipbench.lib.stats import percentile


def compute(ctx):
    return percentile(sorted(ctx.obs.get("dispatch_ms", ())), 0.50)
