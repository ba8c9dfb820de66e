"""The grouped products of the experts HELD against the chip's roofline,
in percent: the least time the chip could take for the FLOPs and bytes
they need in a step when the router is balanced
(``configs/<config>.py expert_share_work``: tokens x experts per token x
experts held / router width rows through every expert layer run) over the
device time of every event inside a ``moe.experts`` scope, forward and
backward (again, where a layer is recomputed).  The kernels skip the tiles
past the rows really held, so their time goes with those;
``detail["reference"]["train"]["routers"]`` gives them (``rows_held``,
on the check's batch) beside the balanced count and the static bound, and
``detail["expert_share_stages_ms_per_step"]`` the op's four stages by
direction: the gathers of ``dispatch`` and ``combine`` run over the bound."""
import re

from chipbench.layer_metrics.moe_experts_roofline_pct import (executed,
                                                              roofline_pct)


def compute(ctx):
    work = getattr(ctx.config, "expert_share_work", None)
    events = executed(ctx) if work else None
    if events is None:
        return None
    per_step = 1e3 / ctx.trace.steps
    stages = {}                  # the op's four stages, for the diagnostics
    for s, op_name, _ in events:
        stage = re.search(r"moe\.(route|dispatch|experts|combine)", op_name)
        if stage:
            key = stage.group(1) + ("_again" if "rematted_computation/"
                                    in op_name else
                                    "_bwd" if "transpose(" in op_name
                                    else "_fwd")
            stages[key] = stages.get(key, 0.0) + s * per_step
    ctx.detail["expert_share_stages_ms_per_step"] = stages
    seconds = sum(s for s, op_name, _ in events if "moe.experts" in op_name)
    return roofline_pct(ctx, seconds,
                        *work(ctx.sizes, ctx.obs["items_per_step"]))
