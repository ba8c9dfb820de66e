"""Per-op runtime profiler + HBM timeline: the op-granular measurement
layer under the PR 10 step-level doctor.

``doctor`` decomposes a step into compute/fetch/staging/compile buckets;
this module answers the next question — **which op** — with three joined
views over the Program IR (the REGISTER_TIMER/globalStat per-layer-timer
capability of the reference, exceeded to per-op measured-vs-modeled):

* **Measured** — an eager per-op replay of one step via
  ``core.executor.run_op`` with ``jax.block_until_ready`` host timers and
  warmup-discarded repeated windows (``tuning.search.time_windows``),
  replicating the compiled step's input dtype coercion exactly as the
  NaN bisect does (``nanprov.make_eager_context``), so every op times at
  the precision the compiled step computes at.  Ops are walked in
  EXECUTION order — forward slice, the ``backward`` pseudo-op (one
  ``value_and_grad`` unit producing every ``@GRAD``), then the optimizer
  update ops — with per-op RNG keys aligned to the compiled trace
  (``ctx._op_uid`` reset per window, and to 0 before the backward, which
  is where the compiled step's forward uids start).
* **Modeled** — each measured op joined against the PR 7 static cost
  model's per-op FLOPs/HBM estimates (``analysis.cost_model``):
  predicted-vs-measured ratios, a roofline verdict per op
  (compute-bound vs memory-bound under the nominal constants), a
  per-op-TYPE calibration table extending the PR 10 ``calibration_row``
  format (keyed program digest + op type — what
  ``analysis.planner.plan(op_class_ratios=...)`` consumes instead of one
  program-wide scalar), and a ranked **XLA-loses-here** report naming
  the pre-registered Pallas candidates (``pallas/fused_optimizer_update``,
  ``pallas/lod_gather_scatter``) when their op classes dominate.
* **Memory timeline** — the liveness walk emitting a per-op live-bytes
  curve from the MEASURED array sizes of the replay, the peak position
  vs the cost model's per-device peak-HBM estimate, and (opt-in) the
  compiled executable's ``memory_analysis`` as the compiled-side
  cross-check (``compat.executable_memory_analysis`` — None where this
  jax hides it).

Surfaces: ``python -m paddle_tpu profile prog.json`` /
``doctor --per-op`` (cli.py) and ``benchmark/opprof.py``.

This module is imported LAZILY only (profile/doctor CLI branches, the
benchmark driver) — it pulls ``analysis.cost_model`` and
``tuning.search``, which the training hot path must never pay for
(repo-lint enforced, like ``attribution``).  Profiling is an offline
tool: it never touches compile fingerprints or the executor's step
cache, so ``Executor.run``/``run_steps`` stay byte-identical with it
loaded (tier-1 counter-delta + retrace_guard).
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional

from . import metrics as _metrics
from . import tracing as _tracing

logger = logging.getLogger("paddle_tpu")

__all__ = [
    "TOLERANCE", "PALLAS_CANDIDATES", "synth_feeds", "synth_state",
    "profile_program", "render_profile", "op_class_rows",
]

# Per-op measured table must sum to the eager-replay total within this
# fraction — pinned equal to attribution.BUDGET_TOLERANCE by tier-1
# (tests/test_opprof.py), kept a separate literal so loading the
# profiler never pulls the attribution/cost-model import chain early.
TOLERANCE = 0.15

# ROADMAP item 5's Pallas expansion candidates: op classes whose
# domination in a measured profile names a pre-registered tunable (the
# decision-rule IDs registered beside ops/optimizer_ops.py and
# ops/sequence_ops.py).  The optimizer family is pure memory traffic
# (one fused kernel over all param leaves is the candidate); the lod
# sequence family is gather/scatter over padded [B, T, ...] layouts.
_OPTIMIZER_OPS = frozenset((
    "sgd", "momentum", "adam", "adamax", "adagrad", "adadelta",
    "decayed_adagrad", "rmsprop", "ftrl", "proximal_gd",
    "proximal_adagrad"))
_LOD_SEQUENCE_OPS = frozenset((
    "sequence_pool", "sequence_softmax", "sequence_expand",
    "sequence_expand_as", "sequence_concat", "sequence_slice",
    "sequence_pad", "sequence_unpad", "sequence_reshape",
    "sequence_reverse", "lod_reset", "sub_nested_seq"))
PALLAS_CANDIDATES: Dict[str, str] = {
    **{t: "pallas/fused_optimizer_update" for t in _OPTIMIZER_OPS},
    **{t: "pallas/lod_gather_scatter" for t in _LOD_SEQUENCE_OPS},
}


# ---------------------------------------------------------------------------
# Feed/state synthesis (profiling a serialized prog.json needs values)
# ---------------------------------------------------------------------------
def _data_vars(program):
    out = []
    for b in program.blocks:
        for v in b.vars.values():
            if getattr(v, "is_data", False):
                out.append(v)
    return out


def _int_feed_bounds(program) -> Dict[str, int]:
    """Upper bounds for synthesized integer feeds, from their direct
    consumers: lookup_table ids must stay under the table's rows,
    cross_entropy labels under the logit width.  Anything else gets the
    conservative default (2)."""
    gb = program.global_block()
    bounds: Dict[str, int] = {}

    def dim(name, idx):
        v = gb._find_var_recursive(name)
        if v is None or v.shape is None or len(v.shape) <= idx:
            return None
        d = v.shape[idx]
        return int(d) if d and d > 0 else None

    for b in program.blocks:
        for op in b.ops:
            if op.type == "lookup_table":
                ws = op.inputs.get("W", [])
                vocab = dim(ws[0], 0) if ws else None
                if vocab:
                    for n in op.inputs.get("Ids", []):
                        bounds[n] = min(bounds.get(n, vocab), vocab)
            elif op.type in ("cross_entropy", "one_hot"):
                xs = op.inputs.get("X", [])
                classes = dim(xs[0], -1) if xs else None
                if classes:
                    for n in op.inputs.get("Label", []):
                        bounds[n] = min(bounds.get(n, classes), classes)
    return bounds


def synth_feeds(program, batch: int = 64, seq_len: int = 8,
                seed: int = 0) -> Dict[str, object]:
    """Seeded random feeds shaped from the program's data vars (the
    fake-data-provider role, for profiling a serialized program without
    its reader): floats ~ U[0,1), ints bounded by their consumers
    (:func:`_int_feed_bounds`), ``-1`` dims resolved to ``batch``
    (leading) / ``seq_len`` (sequence dims), with ``@LEN`` companions
    for ``lod_level`` > 0 vars."""
    import numpy as np
    rng = np.random.RandomState(seed)
    bounds = _int_feed_bounds(program)
    feeds: Dict[str, object] = {}
    for v in _data_vars(program):
        shape = list(v.shape if v.shape is not None else (-1,))
        dims = []
        for i, d in enumerate(shape):
            if d is None or int(d) < 0:
                dims.append(batch if i == 0 else seq_len)
            else:
                dims.append(int(d))
        if not dims:
            dims = [batch]
        dt = np.dtype(v.dtype) if v.dtype is not None else np.dtype("f4")
        if dt.kind in "iu":
            hi = max(2, int(bounds.get(v.name, 2)))
            feeds[v.name] = rng.randint(0, hi, size=dims).astype(dt)
        elif dt.kind == "b":
            feeds[v.name] = np.zeros(dims, dtype=dt)
        else:
            feeds[v.name] = rng.rand(*dims).astype(dt)
        lod = int(getattr(v, "lod_level", 0) or 0)
        if lod >= 1:
            t = dims[1] if len(dims) > 1 else seq_len
            feeds[v.name + "@LEN"] = np.full((dims[0],), t, dtype="int64")
        if lod >= 2 and len(dims) > 2:
            feeds[v.name + "@LEN2"] = np.full(
                (dims[0], dims[1]), dims[2], dtype="int64")
    return feeds


def synth_state(program, scope=None, batch: int = 64,
                seed: int = 0) -> Dict[str, object]:
    """Values for every persistable var the program references: the live
    ``scope`` value when present (a startup-initialized run profiles its
    real parameters), else a seeded synthetic — small positive uniforms,
    so learning rates / beta-pow accumulators stay in a sane range."""
    import numpy as np
    rng = np.random.RandomState(seed + 1)
    referenced = set()
    for b in program.blocks:
        for op in b.ops:
            referenced.update(op.input_names)
            referenced.update(op.output_names)
            referenced.update(op.attrs.get("params", ())
                              if op.type == "backward" else ())
    out: Dict[str, object] = {}
    for b in program.blocks:
        for v in b.vars.values():
            if not v.persistable or v.name in out \
                    or v.name not in referenced:
                continue
            if scope is not None and scope.has(v.name):
                out[v.name] = scope.get(v.name)
                continue
            shape = tuple(batch if (d is None or int(d) < 0) else int(d)
                          for d in (v.shape if v.shape is not None
                                    else (1,)))
            dt = np.dtype(v.dtype) if v.dtype is not None \
                else np.dtype("f4")
            if dt.kind == "f":
                out[v.name] = rng.uniform(0.01, 0.1, shape).astype(dt)
            else:
                out[v.name] = np.zeros(shape, dtype=dt)
    return out


# ---------------------------------------------------------------------------
# The measured walk
# ---------------------------------------------------------------------------
def _measure_windows(call, *, reps: int, warmup: int) -> dict:
    # the shared measurement harness: median of `reps` windows after
    # `warmup` discarded ones (compiles, cache warming)
    from ..tuning.search import time_windows
    return time_windows(call, reps=reps, warmup=warmup)


def _bw_out_names(op) -> List[str]:
    from ..core.program import grad_var_name
    names = [grad_var_name(p) for p in op.attrs.get("params", ())]
    loss = op.attrs.get("loss")
    if loss:
        names.append(loss)
    return names


def profile_program(program, *, executor=None, feed=None, state=None,
                    scope=None, batch: int = 64, seq_len: int = 8,
                    step: int = 0, is_test: bool = False, reps: int = 2,
                    warmup: int = 1, top: int = 10,
                    mesh_axes: Optional[Dict[str, int]] = None,
                    fetch_list=None, compiled_check: bool = False,
                    measure=None) -> dict:
    """Profile one step of ``program`` op by op; returns the joined
    measured/modeled/memory report (JSON-serializable except an optional
    ``fetches`` entry when ``fetch_list`` names vars to materialize —
    the dtype/value-parity hook).

    ``measure(call, reps=, warmup=)`` must run ``call`` at least once
    and return the ``time_windows`` dict — injectable so the test
    suite's fake-timer matrix exercises the whole join deterministically.
    The call order is frozen: one measurement per op in execution order,
    then ONE measurement of the full replay (the eager total the per-op
    table must sum to within :data:`TOLERANCE`)."""
    import jax
    import numpy as np

    from ..core import compile_cache
    from ..core.executor import Env, _run_backward, _to_bf16, run_op
    from .nanprov import make_eager_context

    if executor is None:
        from ..core.executor import Executor
        executor = Executor()
    if scope is None:
        from ..core.scope import global_scope
        scope = global_scope()

    gb = program.global_block()
    # feeds at their declared dtypes, as Executor.run coerces them
    feed_arrays = executor._coerce_feeds(
        program, feed if feed is not None
        else synth_feeds(program, batch=batch, seq_len=seq_len), None, False)
    if state is None:
        state = synth_state(program, scope=scope, batch=batch)

    env, ctx, bw_idx = make_eager_context(
        executor, program, feed_arrays, state, step, is_test)
    initial = dict(env.local)
    # AMP TRAINING precision parity: the compiled step runs every
    # forward op in bf16 INSIDE value_and_grad (the leaves cast down,
    # fp32 grads cast back out — executor._run_backward's recipe), so
    # the walk measures forward ops against a bf16 shadow env while the
    # backward/update ops keep the fp32 master-weight env (pure-
    # inference AMP needs no shadow: make_eager_context already cast
    # the whole env down)
    amp_train = bool(executor.amp) and bw_idx is not None
    fwd_env = None
    if amp_train:
        fwd_env = Env(gb)
        fwd_env.local.update({k: _to_bf16(v) for k, v in initial.items()})
    measure = measure or _measure_windows
    _metrics.inc_counter("opprof/runs")

    ops = gb.ops
    op_out_names: List[List[str]] = [
        _bw_out_names(op) if i == bw_idx
        else [n for names in op.outputs.values() for n in names]
        for i, op in enumerate(ops)]

    rows: List[dict] = []
    sizes: Dict[str, int] = {
        n: int(getattr(v, "nbytes", 0)) for n, v in env.local.items()}
    for idx, op in enumerate(ops):
        is_bw = idx == bw_idx
        # forward ops of an AMP training step time against the bf16
        # shadow; everything else against the fp32 env
        tenv = fwd_env if (fwd_env is not None and idx < bw_idx) else env
        # RNG parity with the compiled trace: inside the compiled step
        # the forward ops run INSIDE value_and_grad with uids starting
        # at 0, so the backward replays from uid 0; every other op
        # re-runs from the uid it first executed at
        uid0 = 0 if is_bw else ctx._op_uid
        out_names = op_out_names[idx]
        aliases: Dict[str, object] = {}
        if not is_bw:
            for n in set(op.input_names) & set(op.output_names):
                if tenv.has(n):
                    aliases[n] = tenv.get(n)
        if is_bw:
            in_names = list(op.attrs.get("params", ()))
        else:
            in_names = list(op.input_names)
        in_bytes = sum(int(getattr(tenv.get(n), "nbytes", 0))
                       for n in in_names if tenv.has(n))

        def call(op=op, uid0=uid0, aliases=aliases, is_bw=is_bw,
                 out_names=out_names, tenv=tenv):
            ctx._op_uid = uid0
            # in-place consumers (optimizer updates write their Param
            # input): restore the pre-op value so repeated windows run
            # the identical computation
            for n, val in aliases.items():
                tenv.set(n, val)
            if is_bw:
                _run_backward(ops[:bw_idx], op, env, ctx)
            else:
                run_op(op, tenv, ctx)
            jax.block_until_ready(
                [tenv.get(n) for n in out_names if tenv.has(n)])

        with _tracing.span("opprof/op", op_type=op.type, index=idx):
            w = measure(call, reps=reps, warmup=warmup)
        wall_ms = float(w["seconds"]) * 1e3
        _metrics.inc_counter("opprof/ops")
        _metrics.observe_hist("opprof/op_ms", wall_ms)
        out_bytes = 0
        out_shapes, out_dtypes = [], []
        for n in out_names:
            if not tenv.has(n):
                continue
            v = tenv.get(n)
            sizes[n] = int(getattr(v, "nbytes", 0))
            out_bytes += sizes[n]
            out_shapes.append(list(getattr(v, "shape", ())))
            out_dtypes.append(str(getattr(v, "dtype", "?")))
        phase = ("backward" if is_bw else
                 "forward" if bw_idx is None or idx < bw_idx else
                 "update")
        rows.append({
            "index": idx, "op_type": op.type, "phase": phase,
            "wall_ms": round(wall_ms, 6),
            "windows_ms": [round(t * 1e3, 6) for t in w.get("windows", ())],
            "spread_pct": w.get("spread_pct", 0.0),
            "bytes": int(in_bytes + out_bytes),
            "out_shapes": out_shapes, "out_dtypes": out_dtypes,
        })

    # -- eager total: one full replay measured end to end (same blocking
    #    discipline as the per-op windows, so the table can sum to it)
    def total_call():
        import jax as _jax
        env2 = Env(gb)
        env2.local.update(initial)
        fenv2 = None
        if amp_train:
            fenv2 = Env(gb)
            fenv2.local.update(
                {k: _to_bf16(v) for k, v in initial.items()})
        ctx2 = executor._lowering_context(executor._step_options(), program,
                                          ctx.base_key, is_test)
        for i, op in enumerate(ops):
            # same per-op env discipline as the measured walk, so the
            # per-op table can sum to this total
            tenv2 = fenv2 if (fenv2 is not None and i < bw_idx) else env2
            if i == bw_idx:
                ctx2._op_uid = 0
                _run_backward(ops[:bw_idx], op, env2, ctx2)
            else:
                run_op(op, tenv2, ctx2)
            _jax.block_until_ready(
                [tenv2.get(n) for n in op_out_names[i] if tenv2.has(n)])

    tw = measure(total_call, reps=reps, warmup=warmup)
    eager_total_ms = float(tw["seconds"]) * 1e3
    per_op_sum_ms = sum(r["wall_ms"] for r in rows)
    gap = (abs(per_op_sum_ms - eager_total_ms) / eager_total_ms
           if eager_total_ms > 0 else 0.0)

    # -- modeled join + per-op-class calibration + XLA-loses-here
    digest = compile_cache.fingerprint_hex(
        compile_cache.program_content_digest(program))[:16]
    cost = _join_modeled(program, rows, mesh_axes, batch)
    report: dict = {
        "program": digest, "batch": int(batch),
        "mesh_axes": dict(mesh_axes or {}),
        "reps": int(reps), "warmup": int(warmup),
        "ops": len(rows),
        "eager_total_ms": round(eager_total_ms, 6),
        "per_op_sum_ms": round(per_op_sum_ms, 6),
        "sum_gap_frac": round(gap, 4),
        "within_tolerance": bool(gap <= TOLERANCE),
        "tolerance": TOLERANCE,
        "rows": rows,
        "top": sorted(rows, key=lambda r: -r["wall_ms"])[:max(1, top)],
        "op_classes": op_class_rows(rows, digest, batch, mesh_axes),
        "xla_loses_here": _xla_loses_here(rows, per_op_sum_ms, top),
        "memory": _memory_view(program, sizes, bw_idx, mesh_axes, batch,
                               cost=cost),
    }
    if compiled_check:
        report["memory"]["executable"] = _compiled_facts(
            executor, program, feed_arrays, state, is_test)
    if fetch_list:
        report["fetches"] = {
            str(n): np.asarray(env.get(str(n))) for n in fetch_list
            if env.has(str(n))}
    return report


# ---------------------------------------------------------------------------
# Modeled join
# ---------------------------------------------------------------------------
def _join_modeled(program, rows, mesh_axes, assume_batch):
    from ..analysis.cost_model import estimate_cost
    try:
        cost = estimate_cost(program, mesh_axes or {},
                             assume_batch=assume_batch)
    except Exception as e:
        # a program the static model cannot walk still profiles measured-
        # only; the join is best-effort by design
        logger.warning("opprof: static cost model failed (%s: %s); "
                       "measured-only profile", type(e).__name__, e)
        return None
    by_idx = {c.loc[1]: c for c in cost.op_costs if c.loc[0] == 0}
    for row in rows:
        c = by_idx.get(row["index"])
        if c is None:
            continue
        compute_s = c.flops / cost.peaks.flops
        hbm_s = c.bytes / cost.peaks.hbm_bytes_s
        pred_ms = cost.peaks.seconds(c.flops, c.bytes,
                                     c.collective_bytes) * 1e3
        row["modeled"] = {
            "flops": c.flops, "hbm_bytes": c.bytes,
            "predicted_ms": round(pred_ms, 9),
            "roofline": ("compute-bound" if compute_s >= hbm_s
                         else "memory-bound"),
            "arithmetic_intensity": round(c.flops / c.bytes, 4)
            if c.bytes else None,
        }
        row["ratio"] = round(row["wall_ms"] / pred_ms, 4) \
            if pred_ms > 0 else None
    return cost


def _agg_by_type(rows) -> Dict[str, dict]:
    """One accumulation pass shared by the calibration table and the
    XLA-loses-here ranking: per op TYPE, measured/count over ALL rows
    plus the measured/predicted pair over the MODELED subset (only
    modeled rows can calibrate — a measured-only row has no ratio)."""
    agg: Dict[str, dict] = {}
    for row in rows:
        a = agg.setdefault(row["op_type"], {
            "measured_ms": 0.0, "count": 0, "modeled_measured_ms": 0.0,
            "modeled_predicted_ms": 0.0, "modeled_count": 0})
        a["measured_ms"] += row["wall_ms"]
        a["count"] += 1
        m = row.get("modeled")
        if m and m.get("predicted_ms"):
            a["modeled_measured_ms"] += row["wall_ms"]
            a["modeled_predicted_ms"] += m["predicted_ms"]
            a["modeled_count"] += 1
    return agg


def op_class_rows(rows, digest: str, assume_batch: int,
                  mesh_axes: Optional[Dict[str, int]]) -> List[dict]:
    """Aggregate per-op measured/predicted into one calibration row per
    op TYPE — the PR 10 ``calibration_row`` schema extended with the op
    class key, merged into the same table by
    ``attribution.save_op_class_calibration`` and consumed by
    ``analysis.planner.plan(op_class_ratios=...)``."""
    agg = _agg_by_type(rows)
    out = []
    for op_type in sorted(agg):
        a = agg[op_type]
        if not a["modeled_count"]:
            continue
        out.append({
            "program": digest, "op_type": op_type,
            "predicted_ms": round(a["modeled_predicted_ms"], 6),
            "measured_ms": round(a["modeled_measured_ms"], 6),
            "ratio": round(a["modeled_measured_ms"]
                           / a["modeled_predicted_ms"], 4)
            if a["modeled_predicted_ms"] > 0 else None,
            "count": a["modeled_count"],
            "assume_batch": int(assume_batch),
            "mesh_axes": dict(mesh_axes or {}),
            "model": "static-per-op",
        })
    return out


def _xla_loses_here(rows, total_ms: float, top: int) -> List[dict]:
    """Ranked where-the-time-goes by op class, each entry carrying the
    pre-registered Pallas-candidate tunable + decision rule when its
    class is one (ROADMAP item 5's 'grow Pallas coverage where
    attribution data says XLA underperforms' now has a committed,
    ranked answer)."""
    from ..core.registry import get_tunable, has_tunable
    agg = {t: {"measured_ms": a["measured_ms"], "count": a["count"],
               "predicted_ms": a["modeled_predicted_ms"]}
           for t, a in _agg_by_type(rows).items()}
    ranked = []
    for op_type, a in sorted(agg.items(),
                             key=lambda kv: -kv[1]["measured_ms"]):
        entry = {
            "op_type": op_type, "count": a["count"],
            "measured_ms": round(a["measured_ms"], 6),
            "share": round(a["measured_ms"] / total_ms, 4)
            if total_ms > 0 else 0.0,
            "predicted_ms": round(a["predicted_ms"], 6),
            "ratio": round(a["measured_ms"] / a["predicted_ms"], 4)
            if a["predicted_ms"] > 0 else None,
        }
        cand = PALLAS_CANDIDATES.get(op_type)
        if cand:
            entry["pallas_candidate"] = cand
            if has_tunable(cand):
                t = get_tunable(cand)
                entry["decision_rule"] = t["decision_rule"]
                entry["pending_hardware"] = t["pending_hardware"]
        ranked.append(entry)
    return ranked[:max(1, top)]


# ---------------------------------------------------------------------------
# Memory timeline
# ---------------------------------------------------------------------------
def _memory_view(program, sizes: Dict[str, int], bw_idx,
                 mesh_axes, assume_batch, cost=None) -> dict:
    """Per-op live-bytes curve from the MEASURED array sizes, using the
    cost model's liveness rules (a var lives producer -> last consumer;
    forward activations pin to the backward — XLA holds them for the
    VJP), plus the static model's per-device peak estimate alongside
    (read from ``cost``, the modeled join's CostReport; None when the
    static model could not walk this program — re-estimating here would
    only re-raise what the join already swallowed)."""
    gb = program.global_block()
    persistable = {v.name for b in program.blocks
                   for v in b.vars.values() if v.persistable}
    state_bytes = sum(sizes.get(n, 0) for n in persistable)

    def outs(i, op):
        if i == bw_idx:
            return _bw_out_names(op)
        return [n for names in op.outputs.values() for n in names]

    last_use: Dict[str, int] = {}
    produced_at: Dict[str, int] = {}
    for i, op in enumerate(gb.ops):
        for n in op.input_names:
            last_use[n] = i
        if i == bw_idx:
            for n in op.attrs.get("params", ()):
                last_use[n] = max(last_use.get(n, i), i)
    for i, op in enumerate(gb.ops):
        for n in outs(i, op):
            produced_at.setdefault(n, i)
    if bw_idx is not None:
        for n, born in produced_at.items():
            if born < bw_idx and n not in persistable:
                last_use[n] = max(last_use.get(n, born), bw_idx)

    live: Dict[str, int] = {}
    curve: List[dict] = []
    peak, peak_idx = 0, 0
    for i, op in enumerate(gb.ops):
        for n in outs(i, op):
            if n not in persistable and n not in live:
                live[n] = sizes.get(n, 0)
        cur = state_bytes + sum(live.values())
        if cur > peak:
            peak, peak_idx = cur, i
        curve.append({"index": i, "op_type": op.type,
                      "live_bytes": int(cur)})
        for n in [n for n in live if last_use.get(n, i) <= i]:
            del live[n]

    modeled = cost.peak_hbm_bytes_per_device if cost is not None else None
    out = {
        "timeline": curve,
        "state_bytes": int(state_bytes),
        "peak_bytes": int(peak), "peak_index": peak_idx,
        "peak_op": gb.ops[peak_idx].type if gb.ops else None,
        "modeled_peak_bytes": round(modeled, 1)
        if modeled is not None else None,
    }
    if modeled:
        out["peak_ratio"] = round(peak / modeled, 4)
    return out


def _compiled_facts(executor, program, feed_arrays, state, is_test):
    """Compiled-side cross-check: AOT-compile this step into a THROWAWAY
    executor + scope and read cost/memory analysis where this jax
    exposes them (``compat.executable_cost_analysis``/``_memory_analysis``
    via ``attribution.executable_facts``).  The throwaway executor keeps
    the module's zero-touch invariant: compiling through the caller's
    executor would install a step in ITS cache and bump ITS compile
    counters.  None on any failure — the profile is eager-first by
    design, and a backend without the API must not take the measured
    views down with it."""
    try:
        from ..core.executor import Executor
        from ..core.scope import Scope
        sc = Scope()
        for k, v in state.items():
            sc.set(k, v)
        exe = Executor(**executor._lowering_options())
        compiled = exe.compile(program, feed=feed_arrays,
                               fetch_list=[], scope=sc,
                               is_test=is_test)
        from . import attribution
        return attribution.executable_facts(compiled)
    except Exception as e:
        logger.warning("opprof: compiled-side memory cross-check "
                       "unavailable (%s: %s)", type(e).__name__, e)
        return None


# ---------------------------------------------------------------------------
# Rendering
# ---------------------------------------------------------------------------
def render_profile(report: dict, top: int = 10) -> str:
    """Human-readable profile rendering (the ``profile`` CLI's text
    form)."""
    lines = [
        f"per-op profile: {report['ops']} op(s), eager total "
        f"{report['eager_total_ms']:.3f} ms, per-op sum "
        f"{report['per_op_sum_ms']:.3f} ms (gap "
        f"{round(report['sum_gap_frac'] * 100, 2)}%"
        + ("" if report["within_tolerance"] else " — OVER TOLERANCE")
        + ")"]
    lines.append("top ops by measured time:")
    for r in report["top"][:top]:
        m = r.get("modeled") or {}
        share = (r["wall_ms"] / report["per_op_sum_ms"] * 100
                 if report["per_op_sum_ms"] else 0.0)
        extra = ""
        if m:
            extra = (f"  pred {m['predicted_ms']:.6f} ms"
                     + (f"  ratio {r['ratio']}x"
                        if r.get("ratio") is not None else "")
                     + f"  {m['roofline']}")
        lines.append(f"  #{r['index']:>3} {r['op_type']:<22} "
                     f"{r['wall_ms']:10.3f} ms ({share:4.1f}%)"
                     f" [{r['phase']}]{extra}")
    xl = report.get("xla_loses_here") or []
    if xl:
        lines.append("XLA loses here (by op class):")
        for e in xl[:top]:
            line = (f"  {e['op_type']} (x{e['count']}): "
                    f"{e['measured_ms']:.3f} ms "
                    f"({round(e['share'] * 100, 1)}%)"
                    + (f", ratio {e['ratio']}x" if e.get("ratio") else ""))
            if e.get("pallas_candidate"):
                line += (f" -> {e['pallas_candidate']}"
                         + (" [pending hardware]"
                            if e.get("pending_hardware") else ""))
            lines.append(line)
            if e.get("decision_rule"):
                lines.append(f"      rule: {e['decision_rule']}")
    mem = report.get("memory") or {}
    if mem:
        line = (f"memory: measured peak "
                f"{mem['peak_bytes'] / 1e6:.3f} MB at op "
                f"#{mem['peak_index']} ({mem['peak_op']})")
        if mem.get("modeled_peak_bytes"):
            line += (f"; modeled {mem['modeled_peak_bytes'] / 1e6:.3f} MB"
                     + (f" (ratio {mem['peak_ratio']})"
                        if mem.get("peak_ratio") else ""))
        lines.append(line)
        ex = mem.get("executable")
        if ex and isinstance(ex, dict) and ex.get("memory"):
            lines.append(f"  compiled-side memory_analysis: {ex['memory']}")
    return "\n".join(lines)
