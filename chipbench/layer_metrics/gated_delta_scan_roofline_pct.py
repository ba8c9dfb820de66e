"""The chunked gated delta rule against the chip's roofline, in percent: the
least time the chip could take for the FLOPs and bytes ``gated_delta_scan``
needs in a step (``configs/<config>.py gated_delta_scan_work``: every KDA
layer run, operands read and results written once a direction, the chunked
form's own products; the bytes bound it) over the device time
``gated_delta_scan_step_ms`` reads, the SAME rows of the join (forward,
backward and again where recomputed, whatever implements the op): one time
base, so that a change moves both or neither.
``detail["gated_delta_scan_stages_ms_per_step"]`` has the op's five stages
(``delta.decay`` / ``solve`` / ``states`` / ``intra`` / ``out``) by
direction, held to nothing; an event outside every stage is ``other``.  The
stages are split by each event's own ``op_name`` and so also hold the
fusions that the join's rule gives another op."""
import re

from chipbench.layer_metrics import gated_delta_scan_step_ms
from chipbench.layer_metrics.moe_experts_roofline_pct import (executed,
                                                              roofline_pct)


def compute(ctx):
    work = getattr(ctx.config, "gated_delta_scan_work", None)
    events = executed(ctx) if work else None
    if events is None:
        return None
    ms = gated_delta_scan_step_ms.compute(ctx)
    if ms is None:
        return None
    per_step = 1e3 / ctx.trace.steps
    stages = {}
    for s, op_name, _ in events:
        if "pt.gated_delta_scan" not in op_name:
            continue
        stage = re.search(r"delta\.(decay|solve|states|intra|out)", op_name)
        key = (stage.group(1) if stage else "other") + (
            "_again" if "rematted_computation/" in op_name else
            "_bwd" if "transpose(" in op_name else "_fwd")
        stages[key] = stages.get(key, 0.0) + s * per_step
    ctx.detail["gated_delta_scan_stages_ms_per_step"] = {
        k: round(v, 3) for k, v in sorted(stages.items())}
    return roofline_pct(ctx, ms * ctx.trace.steps / 1e3,
                        *work(ctx.sizes, ctx.obs["items_per_step"]))
