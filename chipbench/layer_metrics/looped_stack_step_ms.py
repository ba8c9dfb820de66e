"""Device time per training step under the loop construct: every event
owned by an instruction whose HLO ``op_name`` passes through a
``pt.repeat:<block>.<position>`` scope, both directions, the backward
pass's recomputation included.  The ops inside keep their own ``pt.``
scopes, so the class metrics and the op table still see them one by one;
this is the whole looped stack as one owner.  Nothing where the program
ran no such loop.

``owned_events`` is what this metric and its two neighbours read: the
events of the traced window, each with the WHOLE ``op_name`` of the
instruction that owns it by ``lib/op_attribution.py``'s fusion rule (the
heaviest instruction of a fusion, else the scope most of its instructions
carry), where ``op_attribution.owner`` keeps the innermost scope only."""
from __future__ import annotations

import glob
import os

from chipbench.lib import op_attribution, profile, trace_reduce

OBS_KEY = "owned_events"


def _owner_op_name(module, name):
    ins = module["instructions"].get(name)
    if ins is None:
        return ""
    if ins["opcode"] == "fusion" and ins["calls"]:
        best, votes = None, {}
        for inner in op_attribution._fused(module, ins["calls"]):
            scope = op_attribution.innermost_scope(inner["op_name"])
            if scope is None:
                continue
            rank = op_attribution.RANK.get(inner["opcode"],
                                           op_attribution.OTHER_RANK)
            if best is None or rank < best[0]:
                best = (rank, inner["op_name"])
            votes.setdefault(scope, [0, inner["op_name"]])[0] += 1
        if best is not None:
            return best[1] if best[0] < op_attribution.OTHER_RANK \
                else max(votes.values(), key=lambda v: v[0])[1]
    return ins["op_name"]


def owned_events(ctx):
    """[(seconds inside the window, op_name of the owning instruction)] of
    the first chip's work events in the traced window; None where
    ``op_attribution.join`` found no sound join.  Made once a run."""
    if OBS_KEY in ctx.obs:
        return ctx.obs[OBS_KEY]
    ctx.obs[OBS_KEY] = None
    if not op_attribution.join(ctx)["ok"]:
        return None
    from paddle_tpu import profiler

    found = sorted(glob.glob(os.path.join(
        profile.trace_dir(ctx), "plugins", "profile", "*", "*.xplane.pb")))
    window = op_attribution.window_events(trace_reduce.from_xplane(found[-1]))
    module = op_attribution.parse_module(profiler.compiled_hlo_text(
        max(window["prefixes"], key=window["prefixes"].get)))
    names = {}
    ctx.obs[OBS_KEY] = [
        (seconds, names.setdefault(name, _owner_op_name(module, name)))
        for name, seconds in window["events"]
        if not trace_reduce.COLLECTIVE.match(name)]
    return ctx.obs[OBS_KEY]


def ms_per_step(ctx, owns):
    """Milliseconds a step of the events whose owner's ``op_name``
    satisfies ``owns``; None where there is no join or no such event."""
    events = owned_events(ctx)
    if events is None:
        return None
    seconds = sum(s for s, op_name in events if owns(op_name))
    return seconds * 1e3 / ctx.trace.steps if seconds > 0 else None


def compute(ctx):
    """Also leaves ``detail["looped_stack_ms_per_step"]`` (held to
    nothing): the loop's time by the op type of the innermost scope and by
    phase, ``fwd``, ``again`` (the backward pass's second forward) and
    ``bwd``."""
    events = owned_events(ctx)
    if events is None:
        return None
    parts, per_step = {}, 1e3 / ctx.trace.steps
    for seconds, op_name in events:
        if "pt.repeat:" in op_name:
            op_type, _, direction = op_attribution.innermost_scope(op_name)
            phase = "again" if "rematted_computation" in op_name else direction
            key = f"{op_type}_{phase}"
            parts[key] = parts.get(key, 0.0) + seconds * per_step
    ctx.detail["looped_stack_ms_per_step"] = {
        k: round(v, 3) for k, v in sorted(parts.items(), key=lambda kv: -kv[1])}
    return sum(parts.values()) if parts else None
