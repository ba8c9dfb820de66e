"""99th percentile of the same sample as ``serve_latency_p50_ms``; it
needs 1 100 answered requests so that ten lie beyond it, and is left out
of the line of a run that has fewer."""
from chipbench.lib.rates import answered_latencies_ms
from chipbench.lib.stats import percentile

MIN_SAMPLE = 1100


def compute(ctx):
    lat = answered_latencies_ms(ctx)
    return percentile(lat, 0.99) if len(lat) >= MIN_SAMPLE else None
