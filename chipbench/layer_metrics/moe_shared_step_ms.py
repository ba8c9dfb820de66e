"""Device time per training step inside the ``moe.shared`` stage of the
``moe`` ops: the shared expert that every token passes through beside its
routed ones (two dense products forward, four backward, the activation
between them), both directions and again where a layer is recomputed; an
optimizer update that XLA fused behind a weight's gradient counts here.
The events are found by their own HLO ``op_name`` in the trace joined with
the module text (``moe_experts_roofline_pct.executed``).  Part of what
``expert_share_step_ms`` reads (the op owns the stage).  Nothing where the
program has no such stage.  ``detail["moe_shared_ms_per_step"]`` has it by
direction (held to nothing)."""
from chipbench.layer_metrics.moe_experts_roofline_pct import executed

OBS_KEY = "moe_shared_seconds"


def seconds(ctx):
    """Seconds of the traced window inside ``moe.shared``; None where there
    is no sound join or no such event.  Made once a run."""
    if OBS_KEY in ctx.obs:
        return ctx.obs[OBS_KEY]
    ctx.obs[OBS_KEY] = None
    events = executed(ctx)
    if events is None:
        return None
    per_step, ways = 1e3 / ctx.trace.steps, {}
    for s, op_name, _ in events:
        if "moe.shared" in op_name:
            way = "again" if "rematted_computation/" in op_name else \
                "bwd" if "transpose(" in op_name else "fwd"
            ways[way] = ways.get(way, 0.0) + s
    if ways:
        ctx.detail["moe_shared_ms_per_step"] = {
            k: round(v * per_step, 3) for k, v in sorted(ways.items())}
        ctx.obs[OBS_KEY] = sum(ways.values())
    return ctx.obs[OBS_KEY]


def compute(ctx):
    inside = seconds(ctx)
    return None if inside is None else inside * 1e3 / ctx.trace.steps
