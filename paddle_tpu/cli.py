"""Trainer CLI: the ``paddle train`` analog (reference:
trainer/TrainerMain.cpp — FLAGS_job one of train/test/checkgrad/time,
trainer.init(config) + ParamUtil save/load).

``python -m paddle_tpu --config=conf.py --job=train`` evaluates a v1 config
file verbatim (trainer_config_helpers DSL), builds the optimizer from its
settings(), and runs the requested job on the TPU runtime:

  train      steps over feeds, prints per-pass loss, saves params
  test       loads params, evaluates the config outputs on feeds
  time       TrainerMain's timing job: one untimed compiled window
             (compile+warmup), one timed window, ms/batch
  checkgrad  numeric-vs-autodiff gradient check on the config's cost

``python -m paddle_tpu check prog.json`` is the subcommand form of the
static program verifier (paddle_tpu.analysis): it loads a serialized
program — ``Program.to_json`` output, a ``save_inference_model``
``__model__`` meta, or a directory containing one — runs all passes, and
prints the ``PT0xx`` report (exit 1 on errors, and on warnings too with
``--strict``).  ``--mesh dp=8,mp=2`` enables the sharding lints; with a
v1 config (``check --config conf.py``) it verifies the built main and
startup programs instead.

``python -m paddle_tpu plan prog.json --mesh dp=8`` runs the static
auto-sharding planner (paddle_tpu.analysis.planner): it prints proposed
``param_specs``/``feed_specs`` for the mesh, the static cost breakdown
and the per-device peak-HBM estimate, and ``--out plan.json`` writes a
plan file that ``check --specs plan.json`` can later re-validate against
the program — a CI gate needing no Python config import.

``python -m paddle_tpu serve --model dir`` runs the production serving
runtime (paddle_tpu.serving) over exported StableHLO artifacts: dynamic
batching with admission control, per-request deadlines, load shedding,
per-model circuit breaking, and graceful SIGTERM drain — one JSON object
per line on stdin/stdout (see serving/cli.py for the protocol), or over
HTTP with ``--http PORT``.

``python -m paddle_tpu fleet --model dir --replicas N --http PORT``
scales that horizontally: N supervised serve replicas behind a
queue-depth-aware router and the HTTP front, with bounded-restart
relaunch of dead replicas and optional metric-driven autoscaling
(serving/fleet.py).

``python -m paddle_tpu elastic --config conf.py --data 'parts/*' --workers
K --root dir`` runs the elastic multi-worker training service
(distributed/elastic.py): K supervised trainer processes over the
master's slot-sharded exactly-once streams, die/rejoin with
bit-identical resume, and checkpointed mesh RESIZE on membership change
(drain -> merge replicas -> planner re-plan -> re-shard -> relaunch).

Feeds come from ``--feed-npz`` (named arrays matching the config's data
layers, with ``name@LEN`` companions for sequences); ``time`` and
``checkgrad`` synthesize random feeds from the declared shapes when none
are given (the reference's fake-data provider role).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, Optional

import numpy as np


def _parse_config_args(s: Optional[str]) -> Dict[str, str]:
    if not s:
        return {}
    out = {}
    for kv in s.split(","):
        k, _, v = kv.partition("=")
        out[k.strip()] = v.strip()
    return out


def _load_feeds(path: Optional[str]):
    if not path:
        return None
    data = np.load(path, allow_pickle=False)
    return {k: data[k] for k in data.files}


def _synth_feeds(cfg, batch: int, seed: int = 0, seq_len: int = 12):
    """Random feeds shaped from the config's data layers (the fake-data
    provider TrainerMain's time job leaned on)."""
    rng = np.random.RandomState(seed)
    feeds = {}
    for name, v in cfg.data_layers.items():
        if v.dtype == np.dtype("int64"):
            vocab = getattr(v, "v1_size", None) or 2
            if v.lod_level:
                T = seq_len
                feeds[name] = rng.randint(0, vocab, (batch, T))
                feeds[name + "@LEN"] = np.full(batch, T)
            else:
                # label-style: v1 size is the number of classes
                feeds[name] = rng.randint(0, max(vocab, 2), (batch, 1))
        else:
            dims = [int(d) for d in (v.shape or (1,))[1:] if d and d > 0]
            feeds[name] = rng.rand(batch, *dims).astype("float32")
    return feeds


def _used_feed_names(cfg):
    """Data layers actually consumed by ops (a config may declare inputs
    the network never reads, e.g. rnn_crf's 'features')."""
    used = set()
    for op in cfg.main_program.global_block().ops:
        for names in op.inputs.values():
            used.update(names)
    out = set()
    for n in cfg.data_layers:
        if n in used:
            out.add(n)
            out.add(n + "@LEN")
    return out


def job_train(cfg, exe, feeds, args):
    import paddle_tpu as pt

    loss = cfg.minimize_outputs()
    exe.run(cfg.startup_program, feed={}, fetch_list=[])
    if args.init_model_path:
        pt.load_persistables(exe, args.init_model_path, cfg.main_program)
    steps = args.steps_per_pass
    # --start_pass resume semantics (Flags.cpp:81, TrainerMain.cpp:25):
    # saved pass dirs keep their true index; num_passes is the TOTAL pass
    # index bound, so resuming past it is a usage error, not a no-op
    if not 0 <= args.start_pass < args.num_passes:
        raise SystemExit(
            f"--start_pass={args.start_pass} must be in [0, "
            f"--num_passes={args.num_passes}) — num_passes is the total "
            f"pass count, not additional passes")
    for p in range(args.start_pass, args.num_passes):
        # one compiled dispatch per pass (device-side scan over the steps)
        (vals,) = exe.run_steps(steps, cfg.main_program, feed=feeds,
                                fetch_list=[loss])
        vals = np.asarray(vals).reshape(-1)
        print(json.dumps({"pass": p, "loss": float(vals[-1]),
                          "mean_loss": float(np.mean(vals))}), flush=True)
        if args.save_dir:
            d = os.path.join(args.save_dir, f"pass-{p:05d}")
            os.makedirs(d, exist_ok=True)
            pt.save_persistables(exe, d, cfg.main_program)
    return 0


def job_test(cfg, exe, feeds, args):
    import paddle_tpu as pt

    exe.run(cfg.startup_program, feed={}, fetch_list=[])
    if args.init_model_path:
        pt.load_persistables(exe, args.init_model_path, cfg.main_program)
    outs = exe.run(cfg.main_program, feed=feeds, fetch_list=cfg.outputs,
                   is_test=True)
    for var, val in zip(cfg.outputs, outs):
        name = getattr(var, "name", str(var))
        print(json.dumps({"output": name,
                          "mean": float(np.mean(val)),
                          "shape": list(np.shape(val))}), flush=True)
    return 0


def job_time(cfg, exe, feeds, args):
    """TrainerMain's timing job with the compiled-window methodology
    (benchmark/RESULTS.md): the timed window is ONE run_steps dispatch, so
    host dispatch latency is out of the measurement."""
    cfg.minimize_outputs()
    loss = cfg.outputs[0]
    exe.run(cfg.startup_program, feed={}, fetch_list=[])
    # the untimed first call MUST use the same num_steps as the timed one:
    # run_steps compiles per scan length, so it is the compile + warmup
    (lv,) = exe.run_steps(args.iters, cfg.main_program, feed=feeds,
                          fetch_list=[loss], return_numpy=False)
    # unconditional materialization = the sync barrier (an assert would
    # vanish under python -O and the window would time async dispatch)
    if not np.isfinite(np.asarray(lv)[-1]):
        raise FloatingPointError("non-finite loss during warmup window")
    t0 = time.perf_counter()
    (lv,) = exe.run_steps(args.iters, cfg.main_program, feed=feeds,
                          fetch_list=[loss], return_numpy=False)
    last = float(np.asarray(lv)[-1])
    dt = (time.perf_counter() - t0) / args.iters
    if not np.isfinite(last):
        raise FloatingPointError("non-finite loss during timed window")
    print(json.dumps({"ms_per_batch": round(dt * 1e3, 3),
                      "batches_per_sec": round(1.0 / dt, 2)}), flush=True)
    return 0


def job_checkgrad(cfg, exe, feeds, args, eps=1e-4, rtol=1e-3):
    """Central-difference vs autodiff on the config's cost (Trainer::
    checkGradient): perturb a few elements of the first parameters.
    Backward ONLY — no optimizer ops, so probe runs don't move the
    weights they are probing.

    Precision instrument (round 5): the whole comparison runs in FLOAT64
    on the CPU backend (main() pins the platform before the backend
    initializes; ``Executor(compute_dtype="float64")`` upcasts the step) —
    at eps=1e-4 the f64 central difference is accurate to ~1e-8, so the
    1e-3 tolerance actually tests the lowerings, matching the double-
    precision rigor of the reference's checkgrad job."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.backward import append_backward
    from paddle_tpu.core.program import grad_var_name, program_guard

    if jax.config.jax_enable_x64 and jax.default_backend() == "cpu":
        exe = pt.Executor(compute_dtype="float64")
    else:                                  # pragma: no cover - fallback
        eps, rtol = 1e-3, 5e-2
        print(json.dumps({"warning": "x64 unavailable; f32 checkgrad at "
                          f"rtol={rtol}"}), flush=True)

    loss = cfg.outputs[0]
    with program_guard(cfg.main_program, cfg.startup_program):
        append_backward(loss)
    exe.run(cfg.startup_program, feed={}, fetch_list=[])
    scope = pt.global_scope()
    params = [v.name for v in
              cfg.main_program.global_block().vars.values()
              if v.persistable and scope.has(v.name) and
              np.asarray(scope.get(v.name)).dtype.kind == "f"][:3]
    if not params:
        print(json.dumps({"checkgrad": "FAIL",
                          "error": "no floating parameters found"}),
              flush=True)
        return 1
    failures = 0
    rng = np.random.RandomState(0)
    for pname in params:
        g, = exe.run(cfg.main_program, feed=feeds,
                     fetch_list=[grad_var_name(pname)])
        w0 = np.array(scope.get(pname))
        flat = w0.ravel()
        for idx in rng.choice(flat.size, size=min(3, flat.size),
                              replace=False):
            for sign, store in ((+1, "hi"), (-1, "lo")):
                w = flat.copy()
                w[idx] += sign * eps
                scope.set(pname, w.reshape(w0.shape))
                val = float(exe.run(cfg.main_program, feed=feeds,
                                    fetch_list=[loss], is_test=False)[0])
                if store == "hi":
                    hi = val
                else:
                    lo = val
            scope.set(pname, w0)
            num = (hi - lo) / (2 * eps)
            ana = float(np.asarray(g).ravel()[idx])
            ok = abs(num - ana) <= rtol * max(1.0, abs(num), abs(ana))
            if not ok:
                failures += 1
            print(json.dumps({"param": pname, "index": int(idx),
                              "numeric": num, "autodiff": ana,
                              "ok": bool(ok)}), flush=True)
    print(json.dumps({"checkgrad": "PASS" if failures == 0 else "FAIL",
                      "failures": failures}), flush=True)
    return 0 if failures == 0 else 1


def _parse_mesh(s: Optional[str]) -> Optional[Dict[str, int]]:
    """'dp=8,mp=2' -> {'dp': 8, 'mp': 2} for the sharding lints."""
    if not s:
        return None
    out: Dict[str, int] = {}
    for kv in s.split(","):
        k, _, v = kv.partition("=")
        try:
            size = int(v)
        except ValueError:
            raise SystemExit(f"--mesh: bad axis entry {kv!r} "
                             f"(want name=size,...)")
        if size < 1:
            # size <= 1 axes are skipped by the divisibility lints, so a
            # typo'd dp=0 would silently validate nothing and PASS
            raise SystemExit(f"--mesh: axis size must be >= 1, got {kv!r}")
        k = k.strip()
        if k in out:
            # dp=8,dp=2 (typo for dp=8,mp=2) would silently lint against
            # the last size only
            raise SystemExit(f"--mesh: duplicate axis {k!r}")
        out[k] = size
    return out


def _load_check_target(path: str):
    """(program, fetch_names) from a program JSON / __model__ meta / dir."""
    from paddle_tpu.core.program import Program

    if os.path.isdir(path):
        path = os.path.join(path, "__model__")
    try:
        with open(path) as f:
            d = json.load(f)
    except OSError as e:
        raise SystemExit(f"check: cannot read program {path!r}: {e}")
    except json.JSONDecodeError as e:
        raise SystemExit(f"check: {path!r} is not a program JSON "
                         f"(Program.to_json or save_inference_model "
                         f"__model__): {e}")
    try:
        if "program" in d:     # save_inference_model meta
            return Program.from_dict(d["program"]), d.get("fetch_var_names")
        return Program.from_dict(d), None
    except (KeyError, TypeError, ValueError) as e:
        raise SystemExit(f"check: {path!r} does not deserialize as a "
                         f"Program: {type(e).__name__}: {e}")


def _load_plan_file(path: str):
    """plan.json (analysis.planner.Plan.to_dict output) -> Plan."""
    from paddle_tpu.analysis.planner import Plan

    try:
        with open(path) as f:
            d = json.load(f)
    except OSError as e:
        raise SystemExit(f"check: cannot read plan {path!r}: {e}")
    except json.JSONDecodeError as e:
        raise SystemExit(f"check: {path!r} is not a plan JSON "
                         f"(paddle_tpu plan --out output): {e}")
    try:
        return Plan.from_dict(d)
    except (KeyError, TypeError, ValueError) as e:
        raise SystemExit(f"check: {path!r} does not deserialize as a "
                         f"sharding plan: {type(e).__name__}: {e}")


def job_check(argv):
    ap = argparse.ArgumentParser(
        prog="paddle_tpu check",
        description="static program verifier: shape/dtype inference, "
                    "well-formedness and graph lints with stable PT0xx "
                    "codes (the desc-layer InferShape analog; see "
                    "paddle_tpu.analysis)")
    ap.add_argument("program", nargs="?", default=None,
                    help="Program.to_json file, save_inference_model "
                         "__model__ meta, or a directory containing one")
    ap.add_argument("--config", default=None,
                    help="verify a v1 config's built programs instead")
    ap.add_argument("--config_args", default=None,
                    help="k=v,... forwarded to get_config_arg")
    ap.add_argument("--mesh", default=None,
                    help="axis=size,... — enables the sharding lints "
                         "(PT030/PT031/PT040) against this mesh")
    ap.add_argument("--specs", default=None,
                    help="plan.json (from `paddle_tpu plan --out`): "
                         "validate its param/feed specs against the "
                         "program — a CI gate for a committed plan; the "
                         "plan's own mesh applies when --mesh is omitted")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero on warnings too")
    ap.add_argument("--concurrency", action="store_true",
                    help="run the PT05x lock-discipline pass over the "
                         "paddle_tpu host source tree instead of a "
                         "program (analysis.concurrency): findings "
                         "beyond the frozen baseline fail the check")
    args = ap.parse_args(argv)
    if args.concurrency:
        if args.program is not None or args.config is not None:
            ap.error("--concurrency analyzes the host source tree; "
                     "it takes no program/--config")
        from paddle_tpu.analysis import concurrency as _cc
        findings = _cc.analyze_package()
        new, suppressed, stale = _cc.apply_baseline(findings)
        print(_cc.render_report(findings), flush=True)
        warn_new = [f for f in new
                    if _cc.CODES[f.code][0] != "error"]
        err_new = [f for f in new if _cc.CODES[f.code][0] == "error"]
        failed = bool(err_new or stale
                      or (args.strict and warn_new))
        print(json.dumps({"check": "FAIL" if failed else "PASS",
                          "findings": len(findings),
                          "new": len(new), "stale": len(stale),
                          "baselined": sum(suppressed.values())}),
              flush=True)
        return 1 if failed else 0
    if (args.program is None) == (args.config is None):
        ap.error("give exactly one of a program file or --config")

    mesh = _parse_mesh(args.mesh)
    param_specs = feed_specs = None
    if args.specs is not None:
        plan_obj = _load_plan_file(args.specs)
        param_specs = plan_obj.param_specs
        feed_specs = plan_obj.feed_specs
        if mesh is None:
            mesh = plan_obj.mesh_axes
    targets = []                 # (label, program, fetch_list)
    if args.config is not None:
        from paddle_tpu.trainer_config_helpers import load_v1_config
        cfg = load_v1_config(args.config,
                             **_parse_config_args(args.config_args))
        targets.append(("main", cfg.main_program, cfg.outputs))
        targets.append(("startup", cfg.startup_program, None))
    else:
        program, fetch_names = _load_check_target(args.program)
        targets.append((args.program, program, fetch_names))

    errors = warnings_ = 0
    for label, program, fetch_list in targets:
        report = program.validate(fetch_list=fetch_list, mesh=mesh,
                                  param_specs=param_specs,
                                  feed_specs=feed_specs)
        errors += len(report.errors)
        warnings_ += len(report.warnings)
        print(f"== {label}: {report.render()}", flush=True)
    print(json.dumps({"check": "FAIL" if errors or
                      (args.strict and warnings_) else "PASS",
                      "errors": errors, "warnings": warnings_}),
          flush=True)
    return 1 if errors or (args.strict and warnings_) else 0


def job_plan(argv):
    """Auto-sharding planner CLI: propose specs for a program + mesh."""
    ap = argparse.ArgumentParser(
        prog="paddle_tpu plan",
        description="static auto-sharding planner "
                    "(paddle_tpu.analysis.planner): propose "
                    "param_specs/feed_specs for a serialized program and "
                    "a mesh, print the cost breakdown and the per-device "
                    "peak-HBM estimate — pure static analysis, no chip "
                    "required.  The emitted plan passes the PT030/PT031 "
                    "sharding lints by construction; validate a committed "
                    "plan later with `paddle_tpu check prog.json --specs "
                    "plan.json`.")
    ap.add_argument("program",
                    help="Program.to_json file, save_inference_model "
                         "__model__ meta, or a directory containing one")
    ap.add_argument("--mesh", required=True,
                    help="axis=size,... (e.g. dp=8 or dp=4,tp=2)")
    ap.add_argument("--batch", type=int, default=64,
                    help="batch assumed for symbolic -1 dims in the cost "
                         "model (default 64)")
    ap.add_argument("--batch-axis", default="dp",
                    help="mesh axis feeds shard their batch dim on "
                         "(default dp)")
    ap.add_argument("--json", action="store_true",
                    help="print the plan as ONE JSON object only")
    ap.add_argument("--out", default=None,
                    help="also write the plan JSON to this file")
    args = ap.parse_args(argv)

    from paddle_tpu.analysis import planner

    mesh = _parse_mesh(args.mesh)
    program, _fetch_names = _load_check_target(args.program)
    try:
        plan_obj = planner.plan(program, mesh, batch_axis=args.batch_axis,
                                assume_batch=args.batch)
    except ValueError as e:
        raise SystemExit(f"plan: {e}")
    if args.out:
        try:
            with open(args.out, "w") as f:
                f.write(plan_obj.to_json())
        except OSError as e:
            raise SystemExit(f"plan: cannot write {args.out!r}: {e}")
    if args.json:
        print(json.dumps(plan_obj.to_dict(), sort_keys=True), flush=True)
    else:
        print(plan_obj.render(), flush=True)
        print(json.dumps({"plan": "OK", "candidate": plan_obj.candidate,
                          "params_sharded": len(plan_obj.param_specs),
                          "feeds_sharded": len(plan_obj.feed_specs)}),
              flush=True)
    return 0


def job_tune(argv):
    """Persistent-autotuner CLI: search one tunable's declared space and
    commit the winner for trace-time replay."""
    ap = argparse.ArgumentParser(
        prog="paddle_tpu tune",
        description="persistent autotuner (paddle_tpu.tuning): search a "
                    "registered tunable's declared space on its built-in "
                    "measurement target (grid or successive halving, "
                    "paired-A/B noise gate), and persist the winner under "
                    "<cache_dir>/tuning/ for trace-time replay via the "
                    "autotune opt-ins (Executor(autotune=True), "
                    "Trainer.train(autotune=True), PADDLE_TPU_AUTOTUNE=1).")
    ap.add_argument("target", nargs="?", default=None,
                    help="tunable name (e.g. executor/run_pipelined); "
                         "omit with --list to enumerate")
    ap.add_argument("--list", action="store_true",
                    help="list registered tunables (spaces, defaults) "
                         "and exit")
    ap.add_argument("--algo", default="grid", choices=["grid", "halving"],
                    help="search algorithm (default grid; halving for "
                         "large spaces under a tight budget)")
    ap.add_argument("--budget", type=int, default=None,
                    help="max configs evaluated (default: the full grid; "
                         "the shipped default config is always included)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timed windows per trial (median scores; "
                         "default 3)")
    ap.add_argument("--pairs", type=int, default=5,
                    help="alternating default/candidate pairs in the "
                         "final A/B (median of per-pair ratios; default 5)")
    ap.add_argument("--min-speedup", type=float, default=1.10,
                    help="noise-gate threshold on the median pair ratio "
                         "(default 1.10)")
    ap.add_argument("--trial-timeout-s", type=float, default=120.0,
                    help="soft per-trial budget; overruns record "
                         "'timeout' and the search continues (default "
                         "120)")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-fast target sizes (path check; winners "
                         "from smoke runs are still persisted — use "
                         "--no-save)")
    ap.add_argument("--cache-dir", default=None,
                    help="winner store root (default: the compile-cache "
                         "directory, core.compile_cache.cache_dir(); "
                         "records land under <dir>/tuning/)")
    ap.add_argument("--no-save", action="store_true",
                    help="search and report only; do not persist a "
                         "winner")
    ap.add_argument("--out", default=None,
                    help="also write the full result document (trial "
                         "table, A/B windows, verdict) to this JSON file")
    args = ap.parse_args(argv)

    from paddle_tpu.core.registry import registered_tunables
    from paddle_tpu.tuning import search, targets, tunables

    if args.list or args.target is None:
        if not args.list and args.target is None:
            ap.error("give a tunable name, or --list")
        # surface lazily-imported subsystems' declarations too
        for t in targets.target_names():
            targets.ensure_registered(t)
        for n in registered_tunables():
            has_target = n in targets.TARGETS
            print(tunables.describe(n)
                  + ("" if has_target else "\n  (no built-in target — "
                     "library use via paddle_tpu.tuning.tune)"),
                  flush=True)
            print(flush=True)
        return 0

    name = args.target
    targets.ensure_registered(name)
    try:
        measure = targets.build_target(name, smoke=args.smoke)
    except KeyError as e:
        raise SystemExit(f"tune: {e}")

    def on_trial(t):
        print(json.dumps({"trial": t.config, "status": t.status,
                          "seconds": t.seconds,
                          "spread_pct": t.spread_pct,
                          "error": t.error}), flush=True)

    doc = search.tune(
        name, measure, algo=args.algo, budget=args.budget,
        reps=args.reps, pairs=args.pairs,
        min_speedup=args.min_speedup,
        trial_timeout_s=args.trial_timeout_s,
        save=not args.no_save, base=args.cache_dir,
        on_trial=on_trial)
    if args.out:
        try:
            with open(args.out, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
        except OSError as e:
            raise SystemExit(f"tune: cannot write {args.out!r}: {e}")
    # one summary object on the last line (the trial table is in --out)
    summary = {k: doc.get(k) for k in
               ("tunable", "status", "winner", "record_path")
               if doc.get(k) is not None}
    if "ab" in doc:
        summary["speedup"] = doc["ab"]["speedup"]
        summary["pair_ratios"] = doc["ab"]["pair_ratios"]
        if doc["ab"]["refusal_reason"]:
            summary["refusal_reason"] = doc["ab"]["refusal_reason"]
    print(json.dumps(summary, sort_keys=True), flush=True)
    return 0


def job_stats(argv):
    """Summarize JSONL observability logs (PADDLE_TPU_METRICS_LOG)."""
    ap = argparse.ArgumentParser(
        prog="paddle_tpu stats",
        description="summarize one or more structured observability "
                    "logs (paddle_tpu.observability, flag metrics_log / "
                    "env PADDLE_TPU_METRICS_LOG): step-time statistics, "
                    "pipeline stall/busy numbers, last metrics snapshot, "
                    "NaN events.  Multiple files (a supervised run's "
                    "per-relaunch logs) merge in time order with restart "
                    "boundaries marked.")
    ap.add_argument("log", nargs="+", help="JSONL metrics log file(s)")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as ONE JSON object only")
    ap.add_argument("--prom", action="store_true",
                    help="print the logs' LAST metrics snapshot in "
                         "Prometheus text exposition format (scrape a "
                         "serving deployment without a new dependency) "
                         "and exit")
    args = ap.parse_args(argv)
    from paddle_tpu.observability import export
    if args.prom:
        try:
            events, _files = export.iter_log_events(args.log)
        except OSError as e:
            raise SystemExit(f"stats: cannot read log: {e}")
        snap = next((e for e in reversed(events)
                     if e.get("kind") == "snapshot"), None)
        if snap is None:
            raise SystemExit(
                "stats --prom: no snapshot events in the log — run with "
                "observe on and periodic reports (log_period), or call "
                "observability.periodic_report()")
        print(export.to_prometheus(snap), end="", flush=True)
        return 0
    try:
        summary = export.summarize_logs(args.log)
    except OSError as e:
        raise SystemExit(f"stats: cannot read log: {e}")
    if not args.json:
        print(export.render_summary(summary), flush=True)
    print(json.dumps(summary, default=repr), flush=True)
    return 0


def job_trace(argv):
    """Reconstruct per-trace timelines from a span-carrying JSONL log."""
    ap = argparse.ArgumentParser(
        prog="paddle_tpu trace",
        description="replay the tracing spans of one or more "
                    "observability logs (paddle_tpu.observability."
                    "tracing): per-trace timelines, the critical path of "
                    "the longest trace, and p50/p99 latency by span "
                    "name.  Multiple files merge in time order (a "
                    "resumed job's logs read as one).")
    ap.add_argument("log", nargs="+", help="JSONL metrics log file(s)")
    ap.add_argument("--json", action="store_true",
                    help="print ONE JSON object only")
    ap.add_argument("--limit", type=int, default=5,
                    help="timelines rendered (largest traces first; "
                         "default 5)")
    args = ap.parse_args(argv)
    from paddle_tpu.observability import export, tracing
    try:
        events, files = export.iter_log_events(args.log)
    except OSError as e:
        raise SystemExit(f"trace: cannot read log: {e}")
    traces = tracing.build_traces(events)
    stats = tracing.span_stats(events)
    if args.json:
        print(json.dumps({
            "files": files, "traces": len(traces), "span_stats": stats,
            "critical_path": [
                {"name": s["name"], "dur_ms": s.get("dur_ms")}
                for s in tracing.critical_path(
                    max(traces, key=lambda t: t["dur_ms"]))]
            if traces else [],
        }, default=repr), flush=True)
        return 0
    if not traces:
        print("no spans in this log — run with observe on and a "
              "metrics_log set", flush=True)
        return 0
    print(f"{len(traces)} trace(s), {sum(len(t['spans']) for t in traces)}"
          f" span(s)", flush=True)
    if len(files) > 1:
        for f in files:
            # [role:index] when the log stamped identity — a merged
            # fleet trace names which process each file came from
            print(f"  restart boundary: [{export.source_label(f)}] "
                  f"{f['file']} ({f['events']} event(s), from "
                  f"ts={f['t_first']})", flush=True)
    print("\nby span name:", flush=True)
    for name, s in stats.items():
        print(f"  {name}: count={s['count']} p50={s['p50_ms']}ms "
              f"p99={s['p99_ms']}ms max={s['max_ms']}ms "
              f"total={s['total_ms']}ms", flush=True)
    big = sorted(traces, key=lambda t: -t["dur_ms"])[:args.limit]
    for t in big:
        print("\n" + tracing.render_trace(t), flush=True)
    longest = max(traces, key=lambda t: t["dur_ms"])
    cp = tracing.critical_path(longest)
    print("\ncritical path of the longest trace "
          f"({longest['trace']}):", flush=True)
    for s in cp:
        print(f"  {s['name']} ({s.get('dur_ms', 0.0)} ms)", flush=True)
    return 0


def job_doctor(argv):
    """Measured-vs-modeled step/request budget: where did the time go."""
    ap = argparse.ArgumentParser(
        prog="paddle_tpu doctor",
        description="explain where the step (or request) time went: a "
                    "budget decomposing the measured wall into compute / "
                    "fetch / compile / staging / host-stall from the "
                    "log's step events and spans, the top bottleneck "
                    "with actionable hints, and — with --program — a "
                    "cost-model calibration row (predicted vs measured, "
                    "stored ratio for the planner; ROADMAP item 2).  "
                    "Budget components reconcile with the measured wall "
                    "within the pinned tolerance or the report says so.")
    ap.add_argument("log", nargs="+", help="JSONL metrics log file(s)")
    ap.add_argument("--program", default=None,
                    help="Program.to_json file / __model__ meta / dir: "
                         "confront the static cost model with this run")
    ap.add_argument("--batch", type=int, default=64,
                    help="batch assumed for symbolic -1 dims in the "
                         "static model (default 64)")
    ap.add_argument("--mesh", default=None,
                    help="axis=size,... the measured run was sharded "
                         "over (folds into the prediction)")
    ap.add_argument("--calibration-out", default=None,
                    help="merge the calibration row into this JSON "
                         "table (keyed by program digest)")
    ap.add_argument("--json", action="store_true",
                    help="print ONE JSON object only")
    args = ap.parse_args(argv)
    from paddle_tpu.observability import attribution
    program = None
    if args.program is not None:
        program, _fetch = _load_check_target(args.program)
    try:
        report = attribution.doctor_report(
            args.log, program=program, assume_batch=args.batch,
            mesh_axes=_parse_mesh(args.mesh))
    except OSError as e:
        raise SystemExit(f"doctor: cannot read log: {e}")
    if args.calibration_out and report.get("calibration"):
        try:
            attribution.save_calibration([report["calibration"]],
                                         args.calibration_out)
        except OSError as e:
            raise SystemExit(
                f"doctor: cannot write {args.calibration_out!r}: {e}")
    if not args.json:
        print(attribution.render_doctor(report), flush=True)
    print(json.dumps(report, default=repr), flush=True)
    return 0


def main(argv=None):
    if argv is None:
        argv = sys.argv[1:]
    if argv and argv[0] == "check":
        return job_check(argv[1:])
    if argv and argv[0] == "plan":
        return job_plan(argv[1:])
    if argv and argv[0] == "stats":
        return job_stats(argv[1:])
    if argv and argv[0] == "trace":
        return job_trace(argv[1:])
    if argv and argv[0] == "fleet-stats":
        # lazy: the fleet collector can dial sockets and pull the sparse
        # wire stack — only this subcommand pays for it (repo-lint
        # enforced, like the doctor's attribution engine)
        from paddle_tpu.observability import collector
        return collector.fleet_stats_main(argv[1:])
    if argv and argv[0] == "doctor":
        # lazy: the attribution engine pulls analysis.cost_model — only
        # the doctor pays for it
        return job_doctor(argv[1:])
    if argv and argv[0] == "tune":
        # lazy: `import paddle_tpu` must never pull the tuning package
        # (zero-cost-when-unused guard, tier-1 enforced)
        return job_tune(argv[1:])
    if argv and argv[0] == "serve":
        # lazy: `import paddle_tpu` must never pull the serving package
        # (zero-cost-when-unused guard, tier-1 enforced)
        from paddle_tpu.serving.cli import serve_main
        return serve_main(argv[1:])
    if argv and argv[0] == "fleet":
        # lazy: the fleet router/autoscaler rides the same
        # zero-cost-when-unused contract as the serving package
        from paddle_tpu.serving.fleet import fleet_main
        return fleet_main(argv[1:])
    if argv and argv[0] == "elastic":
        # lazy: the elastic training service (distributed/elastic.py)
        # rides the same zero-cost-when-unused contract — importing
        # paddle_tpu (or running a plain trainer) never loads it
        from paddle_tpu.distributed.elastic import elastic_main
        return elastic_main(argv[1:])
    if argv and argv[0] == "pserver":
        # lazy: the sparse wire tier (sparse/{wire,pserver,client})
        # rides the same zero-cost-when-unused contract — importing
        # paddle_tpu or paddle_tpu.sparse never loads a socket stack
        from paddle_tpu.sparse.pserver import pserver_main
        return pserver_main(argv[1:])
    ap = argparse.ArgumentParser(
        prog="paddle_tpu",
        description="TrainerMain analog: run a v1 config on the TPU "
                    "runtime.  Subcommands also exist: `paddle_tpu check "
                    "prog.json|__model__|dir` runs the static program "
                    "verifier, `paddle_tpu plan prog.json --mesh dp=8` "
                    "proposes auto-sharding specs with a static cost "
                    "breakdown, `paddle_tpu stats run.jsonl...` "
                    "summarizes observability metrics logs (--prom for "
                    "Prometheus exposition), `paddle_tpu fleet-stats "
                    "<logs|dir|host:port...>` merges per-process metrics "
                    "snapshots into one labeled fleet view, `paddle_tpu "
                    "trace "
                    "run.jsonl...` renders span timelines and critical "
                    "paths, `paddle_tpu doctor run.jsonl... [--program "
                    "prog.json]` explains where the "
                    "step/request time went and calibrates the cost "
                    "model, `paddle_tpu "
                    "tune <target>` searches and persists autotuner "
                    "winners, `paddle_tpu serve --model dir` runs "
                    "the batching inference server over exported "
                    "artifacts (stdio JSON, or HTTP with --http), and "
                    "`paddle_tpu fleet --model dir --replicas N` scales "
                    "it behind a queue-depth-aware router, and "
                    "`paddle_tpu elastic --config conf.py --data "
                    "'parts/*' --workers K --root dir` runs the elastic "
                    "multi-worker training service with checkpointed "
                    "mesh resize, and `paddle_tpu pserver --shard k/N "
                    "--dir dir` runs one sparse parameter-server shard "
                    "behind the batched binary wire protocol (see "
                    "`paddle_tpu check|plan|stats|fleet-stats|trace|"
                    "doctor|tune|serve|fleet|elastic|pserver "
                    "--help`).")
    ap.add_argument("--config", required=True, help="v1 config file")
    ap.add_argument("--job", default="train",
                    choices=["train", "test", "time", "checkgrad"])
    ap.add_argument("--config_args", default=None,
                    help="k=v,... forwarded to get_config_arg")
    ap.add_argument("--feed-npz", default=None,
                    help="npz of named feed arrays (+ name@LEN)")
    ap.add_argument("--batch", type=int, default=None,
                    help="synthetic-feed batch (default: settings batch)")
    ap.add_argument("--num_passes", type=int, default=1)
    ap.add_argument("--start_pass", type=int, default=0,
                    help="resume pass numbering (use with "
                         "--init_model_path)")
    ap.add_argument("--steps_per_pass", type=int, default=10)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seq_len", type=int, default=12,
                    help="synthetic-feed sequence length")
    ap.add_argument("--save_dir", default=None)
    ap.add_argument("--init_model_path", default=None)
    ap.add_argument("--use_amp", action="store_true")
    args = ap.parse_args(argv)

    if args.job == "checkgrad":
        # the precision instrument wants float64, which the TPU does not
        # implement: pin the CPU backend + x64 BEFORE first device touch.
        # If the backend already initialized (library use, not CLI),
        # job_checkgrad falls back to the f32 tolerance with a warning.
        import jax
        try:
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_enable_x64", True)
        except Exception:
            pass

    import paddle_tpu as pt
    from paddle_tpu.trainer_config_helpers import load_v1_config

    cfg = load_v1_config(args.config, **_parse_config_args(args.config_args))
    batch = args.batch or cfg.settings.get("batch_size") or 16
    feeds = _load_feeds(args.feed_npz) or _synth_feeds(cfg, batch, seq_len=args.seq_len)
    used = _used_feed_names(cfg)
    feeds = {k: v for k, v in feeds.items() if k in used}
    # stage feeds on device ONCE: re-uploading a big batch per dispatch
    # (79 MB for alexnet bs128) would dominate job=time's measurement
    import jax
    feeds = {k: jax.device_put(v) for k, v in feeds.items()}
    exe = pt.Executor(amp=args.use_amp)
    job = {"train": job_train, "test": job_test, "time": job_time,
           "checkgrad": job_checkgrad}[args.job]
    return job(cfg, exe, feeds, args)


if __name__ == "__main__":
    sys.exit(main())
