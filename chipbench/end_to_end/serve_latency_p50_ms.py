"""Median latency of the answered requests, from when each was due
(``lib/rates.py answered_latencies_ms``)."""
from chipbench.lib.rates import answered_latencies_ms
from chipbench.lib.stats import percentile


def compute(ctx):
    return percentile(answered_latencies_ms(ctx), 0.50)
