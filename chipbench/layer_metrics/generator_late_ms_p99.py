"""How late the load generator sent against its schedule, 99th
percentile: a starved generator reads as a fast server."""
from chipbench.lib.stats import percentile


def compute(ctx):
    late = sorted(r.late_s * 1e3 for r in ctx.obs.get("records", ())
                  if r.sent is not None)
    return percentile(late, 0.99)
