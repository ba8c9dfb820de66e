"""What several metric readers take from a driver's observations in the
same way: the throughput of a training run's windows, the latency sample of
a serving run."""
from __future__ import annotations

from typing import List, Optional

from .stats import median


def train_items_per_s_per_chip(ctx) -> Optional[float]:
    """Median over the untraced windows of items_per_step * K / seconds /
    chips.  A window is one ``run_steps(K)`` dispatch ended by reading its
    losses to the host (host clock around drained work)."""
    windows = [w for w in ctx.obs.get("windows", ()) if not w["traced"]]
    if not windows:
        return None
    return median([ctx.obs["items_per_step"] * w["steps"] / w["seconds"]
                   / ctx.obs["chips"] for w in windows])


def answered_latencies_ms(ctx) -> List[float]:
    """Ascending latencies of the answered requests, each from the instant
    the seeded schedule said it was due to its completed answer.  Failed,
    shed and expired requests are counted in ``failed`` and are not in the
    sample."""
    return sorted(r.latency_s * 1e3 for r in ctx.obs.get("records", ())
                  if r.error is None and r.done is not None)
