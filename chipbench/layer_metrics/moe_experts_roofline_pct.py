"""The experts' grouped products against the chip's roofline, in percent:
the least time the chip could take for the FLOPs and bytes they need
(``configs/<config>.py moe_experts_work``; the larger of FLOPs over the
published bf16 peak and bytes over the published HBM bandwidth,
``lib/peaks.py``) over the device time of every event inside the
``moe.experts`` scope, forward and backward (the kernels and the
elementwise work between them)."""
from __future__ import annotations

import glob
import os
import re

from chipbench.lib import op_attribution, profile, trace_reduce

OBS_KEY = "scope_events"


def executed(ctx):
    """[(seconds inside the window, HLO op_name, opcode)] of the first
    chip's work events in the traced window, from the trace joined with the
    text of the module the window ran; None where ``op_attribution.join``
    found no sound join.  Made once a run."""
    if OBS_KEY in ctx.obs:
        return ctx.obs[OBS_KEY]
    ctx.obs[OBS_KEY] = None
    if not op_attribution.join(ctx)["ok"]:
        return None
    from paddle_tpu import profiler

    found = sorted(glob.glob(os.path.join(
        profile.trace_dir(ctx), "plugins", "profile", "*", "*.xplane.pb")))
    window = op_attribution.window_events(trace_reduce.from_xplane(found[-1]))
    text = profiler.compiled_hlo_text(
        max(window["prefixes"], key=window["prefixes"].get))
    instructions = op_attribution.parse_module(text)["instructions"]
    ctx.obs[OBS_KEY] = [
        (seconds, instructions[name]["op_name"], instructions[name]["opcode"])
        for name, seconds in window["events"] if name in instructions]
    return ctx.obs[OBS_KEY]


def roofline_pct(ctx, seconds, flops, bytes_):
    """Least time for (flops, bytes_) a step over ``seconds`` measured in
    the traced window, in percent."""
    if not seconds > 0:
        return None
    least = max(flops / ctx.peaks.bf16_flops, bytes_ / ctx.peaks.hbm_bytes_s)
    return 100.0 * least * ctx.trace.steps / seconds


def compute(ctx):
    work = getattr(ctx.config, "moe_experts_work", None)
    events = executed(ctx) if work else None
    if events is None:
        return None
    per_step = 1e3 / ctx.trace.steps
    stages = {}                  # the op's four stages, for the diagnostics
    for s, op_name, _ in events:
        stage = re.search(r"moe\.(route|dispatch|experts|combine)", op_name)
        if stage:
            key = stage.group(1) + (
                "_bwd" if "transpose(" in op_name else "_fwd")
            stages[key] = stages.get(key, 0.0) + s * per_step
    ctx.detail["moe_stages_ms_per_step"] = stages
    seconds = sum(s for s, op_name, _ in events if "moe.experts" in op_name)
    flops, bytes_ = work(ctx.sizes, ctx.obs["items_per_step"])
    return roofline_pct(ctx, seconds,
                        ctx.sizes["num_hidden_layers"] * flops,
                        ctx.sizes["num_hidden_layers"] * bytes_)
