"""Set-up split by the program's own phase log.

``profiler.compile_stats().phases()`` (``paddle_tpu/core/compile_cache.py
PHASE_NAMES``) holds one record for each piece of a cold start, written
where the work happens, with its start on ``time.perf_counter()``: the
clock of ``run.py T_START`` and of ``ctx.before["t"]``, so a record lies
inside set-up exactly when its ``t0`` is before the end of set-up.  The
``setup_*`` readers under ``layer_metrics/`` all read the ONE split made
here, once a run, kept in ``ctx.obs``; its short form goes into
``ctx.detail["setup_phases"]`` (under :data:`DETAIL_BYTES`: a result is read
from the tail of a run's output).

A program without a phase log (a parent commit under these files) gives
``None``, every reader then returns ``None``, and the line is as before.
"""
from __future__ import annotations

import json
from typing import List, Optional

OBS_KEY = "setup_phases"
DETAIL_BYTES = 1200
#: the three phases whose sum ``compile_s`` is (CachedStep._compile)
COMPILE_PHASES = ("step/trace", "step/lower", "step/xla")
#: JAX's own seconds of tracing, lowering and compiling OUTSIDE the steps'
#: compiles, a nested event counted once (compile_stats counters)
JAX_COUNTERS = ("jax_trace_s", "jax_lower_s", "jax_backend_compile_s")


def union_seconds(records: List[dict], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that at least one record covers: overlapping
    records (an import inside a step's enter, two threads) count once."""
    spans = sorted((max(r["t0"], lo), min(r["t0"] + r["dur_s"], hi))
                   for r in records)
    covered, reach = 0.0, lo
    for start, end in spans:
        if end > reach:
            covered += end - max(start, reach)
            reach = end
    return covered


def line_form(result: dict, t_start: float) -> dict:
    """What the result line keeps: seconds and count by name, the union,
    how many XLA phases were cache reads, and the eight longest records as
    ``[name, label, fp8, start since T_START, seconds, how]`` (``how``:
    ``hit`` / ``compiled`` for an XLA phase).  Rows go before totals lose
    digits, so the form stays under :data:`DETAIL_BYTES`."""
    xla = [r for r in result["records"] if r["name"] == "step/xla"]
    form = {
        "s": {n: [round(t["seconds"], 3), t["count"]]
              for n, t in result["totals"].items()},
        "union_s": round(result["union_s"], 3),
        "xla_cache_reads": [sum(bool(r.get("cache_hit")) for r in xla),
                            len(xla)],
        "longest": [],
    }
    rows = sorted(result["records"], key=lambda r: -r["dur_s"])[:8]
    for r in rows:
        how = None
        if r["name"] == "step/xla":
            how = "hit" if r.get("cache_hit") else "compiled"
        form["longest"].append(
            [r["name"], r.get("label"), (r.get("fp") or "")[:8],
             round(r["t0"] - t_start, 2), round(r["dur_s"], 3), how])
    while len(json.dumps(form)) >= DETAIL_BYTES and form["longest"]:
        form["longest"].pop()
    return form


def split(ctx) -> Optional[dict]:
    """``{"records", "totals", "union_s"}`` of the phases that started
    before the end of set-up; None where the program keeps no phase log or
    set-up never ended."""
    if OBS_KEY in ctx.obs:
        return ctx.obs[OBS_KEY]
    from paddle_tpu import profiler

    stats = profiler.compile_stats()
    result = None
    if hasattr(stats, "phases") and ctx.before is not None:
        end = ctx.before["t"]
        records = [r for r in stats.phases() if r["t0"] < end]
        result = {"records": records,
                  "totals": stats.phase_totals(before=end),
                  "union_s": union_seconds(records, ctx.t_start, end)}
        ctx.detail[OBS_KEY] = line_form(result, ctx.t_start)
    ctx.obs[OBS_KEY] = result
    return result


def seconds(ctx, *names: str) -> Optional[float]:
    """Seconds of set-up under the named phases together (0.0 where the
    log holds none of them); None without a phase log."""
    result = split(ctx)
    if result is None:
        return None
    return sum(result["totals"].get(n, {"seconds": 0.0})["seconds"]
               for n in names)


def count(ctx, name: str) -> Optional[int]:
    result = split(ctx)
    if result is None:
        return None
    return result["totals"].get(name, {"count": 0})["count"]


def jax_seconds(ctx) -> Optional[float]:
    """JAX's own trace + lower + backend-compile seconds outside the steps'
    compiles, at the end of set-up; None where the program does not sum
    them."""
    counters = ctx.before["compile"] if ctx.before else {}
    if not any(k in counters for k in JAX_COUNTERS):
        return None
    return sum(counters.get(k, 0.0) for k in JAX_COUNTERS)
