"""The chunked state-space recurrence against the chip's roofline, in
percent: the least time the chip could take for the FLOPs and bytes
``ssd_scan`` needs in a step (``configs/<config>.py ssd_scan_work``: every
``mamba`` layer run, operands read and results written once a direction,
the chunked form's own products; the bytes bound it) over the device time
``ssd_scan_step_ms`` reads, the SAME rows of the join (forward, backward
and again where a layer is recomputed, whatever implements the op, XLA's
fusions or a kernel): one time base, so that a change moves both or neither.
``detail["ssd_scan_stages_ms_per_step"]`` has the op's five stages
(``ssd.decay`` / ``intra`` / ``states`` / ``pass`` / ``out``) by direction,
held to nothing; an event outside every stage is ``other``.  The stages are
split by each event's own ``op_name`` and so also hold the fusions that the
join's rule gives another op (their sum reads some 8 % over the rows')."""
import re

from chipbench.layer_metrics import ssd_scan_step_ms
from chipbench.layer_metrics.moe_experts_roofline_pct import (executed,
                                                              roofline_pct)


def compute(ctx):
    work = getattr(ctx.config, "ssd_scan_work", None)
    events = executed(ctx) if work else None
    if events is None:
        return None
    per_step = 1e3 / ctx.trace.steps
    stages = {}
    for s, op_name, _ in events:
        if "pt.ssd_scan" not in op_name:
            continue
        stage = re.search(r"ssd\.(decay|intra|states|pass|out)", op_name)
        key = (stage.group(1) if stage else "other") + (
            "_again" if "rematted_computation/" in op_name else
            "_bwd" if "transpose(" in op_name else "_fwd")
        stages[key] = stages.get(key, 0.0) + s * per_step
    ctx.detail["ssd_scan_stages_ms_per_step"] = {
        k: round(v, 3) for k, v in sorted(stages.items())}
    seconds = ssd_scan_step_ms.compute(ctx) * ctx.trace.steps / 1e3
    return roofline_pct(ctx, seconds,
                        *work(ctx.sizes, ctx.obs["items_per_step"]))
