"""Requests per dispatched batch over the window (``Server.health()``:
served / batches)."""


def compute(ctx):
    if not ctx.obs.get("batches"):
        return None
    return ctx.obs["served"] / ctx.obs["batches"]
