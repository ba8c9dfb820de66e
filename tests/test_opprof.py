"""Per-op runtime profiler (paddle_tpu.observability.opprof).

Pins the ISSUE 12 acceptance contract:

* the measured walk covers EVERY op in execution order — forward slice,
  the ``backward`` pseudo-op, optimizer updates — and the per-op table
  sums to the eager-replay total within the pinned tolerance
  (deterministic fake-timer matrix: the join/bookkeeping is what the
  tier-1 test pins; the real-timer acceptance rows live in
  benchmark/opprof_results.json);
* dtype-coercion + RNG parity with the COMPILED step: the eager replay
  reproduces a dropout-bearing training step's loss bit-identically;
* the per-op-class calibration table merges into the PR 10 format and
  ``analysis.planner`` demonstrably consumes it — a seeded table that
  inflates one op class flips the candidate ranking;
* zero overhead when off: with opprof merely loaded, ``Executor.run``
  hot paths write no metrics and never retrace;
* CLI rounds: ``profile`` in-process (tier-1) + subprocess (@slow),
  ``doctor --per-op`` joins the profile under the step budget.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags, layers
from paddle_tpu import observability as obs
from paddle_tpu.core.compile_cache import retrace_guard
from paddle_tpu.observability import export as obs_export
from paddle_tpu.observability import opprof


@pytest.fixture(autouse=True)
def clean_state():
    obs.registry().reset()
    prev = {n: flags.get_flag(n) for n in ("observe", "metrics_log")}
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    yield
    for n, v in prev.items():
        flags.set_flag(n, v)
    obs_export._reset_writer()
    obs.registry().reset()


def _build_net(dropout=True):
    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=16, act="relu")
    if dropout:
        h = layers.dropout(h, dropout_prob=0.3)
    pred = layers.fc(h, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _feed(batch=16, seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(batch, 8).astype("float32"),
            "y": rng.randint(0, 3, (batch, 1))}


def _fake_measure(op_ms=1.0):
    """Deterministic fake-timer: executes the call once (the walk's env
    state must advance for the join to see real shapes) and returns a
    scripted window.  Call order is frozen by profile_program: one call
    per op in execution order, then ONE full-replay total."""
    calls = []

    def measure(call, *, reps, warmup):
        call()
        calls.append(reps)
        return {"seconds": op_ms / 1e3, "windows": [op_ms / 1e3],
                "spread_pct": 0.0}

    measure.calls = calls
    return measure


# ---------------------------------------------------------------------------
# measured walk: coverage, phases, fake-timer sum
# ---------------------------------------------------------------------------
def test_fake_timer_matrix_rows_cover_ops_and_sum_to_total():
    loss = _build_net()
    prog = pt.default_main_program()
    n_ops = len(prog.global_block().ops)
    calls = {"n": 0}

    def measure(call, *, reps, warmup):
        call()
        calls["n"] += 1
        if calls["n"] <= n_ops:                 # per-op windows
            return {"seconds": 1e-3, "windows": [1e-3],
                    "spread_pct": 0.0}
        # the final call is the full-replay total: exactly the sum of
        # the per-op windows -> gap must be 0 and within tolerance
        return {"seconds": n_ops * 1e-3, "windows": [n_ops * 1e-3],
                "spread_pct": 0.0}

    rep = opprof.profile_program(prog, batch=8, measure=measure,
                                 fetch_list=[loss.name])
    assert calls["n"] == n_ops + 1
    assert rep["ops"] == n_ops
    assert [r["index"] for r in rep["rows"]] == list(range(n_ops))
    assert rep["per_op_sum_ms"] == pytest.approx(n_ops * 1.0)
    assert rep["eager_total_ms"] == pytest.approx(n_ops * 1.0)
    assert rep["sum_gap_frac"] == 0.0
    assert rep["within_tolerance"] is True
    assert rep["tolerance"] == opprof.TOLERANCE
    # every row joined against the static model carries a roofline
    joined = [r for r in rep["rows"] if r.get("modeled")]
    assert joined, "no rows joined against the static cost model"
    for r in joined:
        assert r["modeled"]["roofline"] in ("compute-bound",
                                            "memory-bound")
    # loss value materialized through the fetch hook
    assert np.isfinite(rep["fetches"][loss.name]).all()


def test_backward_and_update_ops_attributed_in_execution_order():
    _build_net()
    prog = pt.default_main_program()
    rep = opprof.profile_program(prog, batch=8,
                                 measure=_fake_measure())
    phases = [r["phase"] for r in rep["rows"]]
    types = [r["op_type"] for r in rep["rows"]]
    bw = types.index("backward")
    assert phases[bw] == "backward"
    assert set(phases[:bw]) == {"forward"}
    assert phases[bw + 1:] and set(phases[bw + 1:]) == {"update"}
    assert "sgd" in types[bw + 1:]
    # the backward row accounts the @GRAD outputs it produced
    bw_row = rep["rows"][bw]
    assert bw_row["bytes"] > 0 and bw_row["out_shapes"]


def test_tolerance_pinned_to_budget_tolerance():
    from paddle_tpu.observability import attribution
    assert opprof.TOLERANCE == attribution.BUDGET_TOLERANCE


def test_over_tolerance_is_reported_not_hidden():
    _build_net()
    prog = pt.default_main_program()
    n_ops = len(prog.global_block().ops)
    calls = {"n": 0}

    def measure(call, *, reps, warmup):
        call()
        calls["n"] += 1
        # total reads HALF the per-op sum -> gap 100%, over tolerance
        s = 1e-3 if calls["n"] <= n_ops else n_ops * 0.5e-3
        return {"seconds": s, "windows": [s], "spread_pct": 0.0}

    rep = opprof.profile_program(prog, batch=8, measure=measure)
    assert rep["within_tolerance"] is False
    assert "OVER TOLERANCE" in opprof.render_profile(rep)


# ---------------------------------------------------------------------------
# dtype-coercion + RNG parity with the compiled step (seeded program)
# ---------------------------------------------------------------------------
def test_eager_replay_parity_with_compiled_training_step():
    loss = _build_net(dropout=True)
    prog = pt.default_main_program()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    scope = pt.global_scope()
    state0 = {k: np.array(scope.get(k))
              for k in exe._state_keys(prog, scope)}
    feed = _feed()
    step = exe._step          # the step counter the next run will use
    (compiled_loss,) = exe.run(feed=feed, fetch_list=[loss])
    rep = opprof.profile_program(prog, executor=exe, feed=feed,
                                 state=state0, step=step, batch=16,
                                 reps=1, warmup=0,
                                 fetch_list=[loss.name])
    # bit-identical INCLUDING the dropout mask: the walk reproduces the
    # compiled trace's per-op RNG uid sequence (backward replays the
    # forward from uid 0, exactly as value_and_grad traces it)
    assert np.asarray(rep["fetches"][loss.name]) == pytest.approx(
        np.asarray(compiled_loss), abs=0.0)


def test_amp_inference_replay_matches_compiled_dtype():
    x = layers.data("x", shape=[8], dtype="float32")
    pred = layers.fc(x, size=4, act="softmax")
    prog = pt.default_main_program()
    exe = pt.Executor(amp=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    feed = {"x": _feed()["x"]}
    (compiled_out,) = exe.run(feed=feed, fetch_list=[pred],
                              is_test=True, return_numpy=False)
    rep = opprof.profile_program(prog, executor=exe, feed=feed,
                                 is_test=True, batch=16, reps=1,
                                 warmup=0, fetch_list=[pred.name])
    # pure-inference AMP coerces to bf16 — the replay must time (and
    # produce) the SAME precision the compiled step computed at.  Values
    # agree to bf16 rounding, not bitwise: jit fuses matmul+softmax into
    # one HLO computation while the per-op replay rounds to bf16 at each
    # op boundary.  One bf16 ulp is at most 2**-7 of a value; the replay's
    # rounded logits move a probability by up to one, and rounding the two
    # outputs themselves adds half of one each: 2**-6 in all (since
    # jax 0.9.0 one element of 64 sits at 0.0098, two representable
    # values apart; under rtol 2**-7 that failed).
    assert str(compiled_out.dtype) == "bfloat16"
    assert rep["rows"][-1]["out_dtypes"][-1] == "bfloat16"
    np.testing.assert_allclose(
        np.asarray(rep["fetches"][pred.name], dtype="float32"),
        np.asarray(compiled_out, dtype="float32"),
        rtol=2 ** -6, atol=0.0)


def test_amp_training_forwards_time_at_bf16_grads_stay_fp32():
    """AMP TRAINING parity: the compiled step runs forward ops in bf16
    inside value_and_grad while grads/updates stay fp32 (master
    weights) — the walk must measure each phase at that phase's
    compiled precision."""
    _build_net(dropout=False)
    prog = pt.default_main_program()
    exe = pt.Executor(amp=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    rep = opprof.profile_program(prog, executor=exe, feed=_feed(),
                                 batch=16, measure=_fake_measure())
    fwd = [r for r in rep["rows"] if r["phase"] == "forward"]
    assert fwd
    assert all(dt == "bfloat16" for r in fwd for dt in r["out_dtypes"])
    bw = next(r for r in rep["rows"] if r["phase"] == "backward")
    assert all(dt == "float32" for dt in bw["out_dtypes"])
    upd = [r for r in rep["rows"] if r["phase"] == "update"]
    assert upd
    assert all(dt == "float32" for r in upd for dt in r["out_dtypes"])


# ---------------------------------------------------------------------------
# real-timer smoke (tiny, reps=1): sums reconcile on a real walk too
# ---------------------------------------------------------------------------
def test_real_timer_smoke_reconciles():
    _build_net(dropout=False)
    prog = pt.default_main_program()
    rep = opprof.profile_program(prog, batch=4, reps=1, warmup=1)
    assert rep["eager_total_ms"] > 0 and rep["per_op_sum_ms"] > 0
    # no tolerance assert: this box's contention swings small windows;
    # the committed acceptance rows live in benchmark/opprof_results.json
    assert rep["ops"] == len(prog.global_block().ops)
    snap = obs.registry().snapshot()
    assert snap["opprof/runs"]["value"] == 1
    assert snap["opprof/ops"]["value"] == rep["ops"]
    assert snap["opprof/op_ms"]["count"] == rep["ops"]


# ---------------------------------------------------------------------------
# XLA-loses-here: pallas candidates referenced with their rule ids
# ---------------------------------------------------------------------------
def test_xla_loses_here_names_pallas_candidate_rules():
    _build_net()
    prog = pt.default_main_program()
    ops = prog.global_block().ops
    sgd_idx = {i for i, op in enumerate(ops) if op.type == "sgd"}
    calls = {"n": 0}

    def measure(call, *, reps, warmup):
        call()
        i = calls["n"]
        calls["n"] += 1
        # make the optimizer updates dominate the measured profile
        s = 50e-3 if i in sgd_idx else 0.1e-3
        return {"seconds": s, "windows": [s], "spread_pct": 0.0}

    rep = opprof.profile_program(prog, batch=8, measure=measure)
    top = rep["xla_loses_here"][0]
    assert top["op_type"] == "sgd"
    assert top["share"] > 0.5
    assert top["pallas_candidate"] == "pallas/fused_optimizer_update"
    assert top["pending_hardware"] is True
    assert "1.10x" in top["decision_rule"]
    rendered = opprof.render_profile(rep)
    assert "pallas/fused_optimizer_update" in rendered
    assert "rule:" in rendered


def test_pallas_candidate_tunables_preregistered():
    from paddle_tpu.core.registry import get_tunable
    for name in ("pallas/fused_optimizer_update",
                 "pallas/lod_gather_scatter"):
        e = get_tunable(name)
        assert e["side"] == "device"
        assert e["pending_hardware"] is True
        assert e["decision_rule"], name
    # and the profiler's candidate map points at exactly these ids
    assert set(opprof.PALLAS_CANDIDATES.values()) == {
        "pallas/fused_optimizer_update", "pallas/lod_gather_scatter"}
    assert opprof.PALLAS_CANDIDATES["sgd"] == \
        "pallas/fused_optimizer_update"
    assert opprof.PALLAS_CANDIDATES["sequence_expand"] == \
        "pallas/lod_gather_scatter"


# ---------------------------------------------------------------------------
# memory timeline
# ---------------------------------------------------------------------------
def test_memory_timeline_curve_and_modeled_peak():
    _build_net()
    prog = pt.default_main_program()
    rep = opprof.profile_program(prog, batch=8,
                                 measure=_fake_measure())
    mem = rep["memory"]
    n_ops = len(prog.global_block().ops)
    assert len(mem["timeline"]) == n_ops
    assert mem["peak_bytes"] >= mem["state_bytes"] > 0
    assert mem["peak_bytes"] == max(p["live_bytes"]
                                    for p in mem["timeline"])
    assert mem["timeline"][mem["peak_index"]]["live_bytes"] == \
        mem["peak_bytes"]
    # forward activations pin to the backward: the peak sits at (or
    # after) the backward op, never mid-forward
    bw = next(i for i, op in enumerate(prog.global_block().ops)
              if op.type == "backward")
    assert mem["peak_index"] >= bw
    assert mem["modeled_peak_bytes"] and mem["peak_ratio"] > 0


# ---------------------------------------------------------------------------
# calibration table -> planner (the acceptance wiring)
# ---------------------------------------------------------------------------
def _two_layer_mlp():
    x = layers.data("x", shape=[128], dtype="float32")
    h = layers.fc(x, size=128, act="relu")
    h2 = layers.fc(h, size=128, act="relu")
    layers.mean(h2)
    return pt.default_main_program()


def test_op_class_table_merges_into_pr10_format(tmp_path):
    from paddle_tpu.observability import attribution
    path = str(tmp_path / "cal.json")
    # a PR 10 per-program row already in the table must survive
    attribution.save_calibration([{"program": "aaaa", "predicted_ms": 1.0,
                                   "measured_ms": 2.0, "ratio": 2.0}],
                                 path)
    rows = [{"program": "bbbb", "op_type": "mul", "predicted_ms": 1.0,
             "measured_ms": 200.0, "ratio": 200.0, "count": 2},
            {"program": "bbbb", "op_type": "relu", "predicted_ms": 1.0,
             "measured_ms": 1.0, "ratio": 1.0, "count": 1}]
    doc = attribution.save_op_class_calibration(rows, path)
    assert doc["programs"]["aaaa"]["ratio"] == 2.0
    assert doc["op_classes"]["bbbb:mul"]["ratio"] == 200.0
    # re-profiling the same program overwrites, never duplicates
    rows[0]["ratio"] = 150.0
    doc = attribution.save_op_class_calibration([rows[0]], path)
    assert doc["op_classes"]["bbbb:mul"]["ratio"] == 150.0
    assert len(doc["op_classes"]) == 2
    # and the per-program row STILL survives a save_calibration pass
    doc = attribution.save_calibration(
        [{"program": "cccc", "ratio": 3.0}], path)
    assert "bbbb:mul" in doc["op_classes"]
    # the planner-facing loader: median ratio per op type
    ratios = attribution.load_op_class_ratios(path)
    assert ratios == {"mul": 150.0, "relu": 1.0}


def test_planner_ranking_flips_under_seeded_op_class_inflation(tmp_path):
    from paddle_tpu.analysis import planner
    from paddle_tpu.observability import attribution
    prog = _two_layer_mlp()
    nominal = planner.rank_candidates(prog, {"tp": 2}, assume_batch=512)
    assert nominal[0][0] == "dp"
    assert {n for n, _ in nominal} >= {"dp", "megatron"}
    # seed a table through the real save/load path (the planner
    # "demonstrably loads" the committed format, not a hand dict)
    path = str(tmp_path / "cal.json")
    attribution.save_op_class_calibration(
        [{"program": "feed", "op_type": "mul", "predicted_ms": 1.0,
          "measured_ms": 200.0, "ratio": 200.0, "count": 2}], path)
    ratios = attribution.load_op_class_ratios(path)
    calibrated = planner.rank_candidates(prog, {"tp": 2},
                                         assume_batch=512,
                                         op_class_ratios=ratios)
    assert calibrated[0][0] == "megatron"
    # plan() itself follows the same ranking and records the fact
    p = planner.plan(prog, {"tp": 2}, assume_batch=512,
                     op_class_ratios=ratios)
    assert p.candidate == "megatron"
    assert any("op-class calibration" in d for d in p.diagnostics)
    p0 = planner.plan(prog, {"tp": 2}, assume_batch=512)
    assert p0.candidate == "dp"


def test_profile_report_op_classes_feed_the_loader(tmp_path):
    from paddle_tpu.observability import attribution
    _build_net()
    prog = pt.default_main_program()
    rep = opprof.profile_program(prog, batch=8,
                                 measure=_fake_measure())
    assert rep["op_classes"], "no op-class calibration rows produced"
    for row in rep["op_classes"]:
        assert row["program"] == rep["program"]
        assert row["model"] == "static-per-op"
    path = str(tmp_path / "cal.json")
    attribution.save_op_class_calibration(rep["op_classes"], path)
    ratios = attribution.load_op_class_ratios(path)
    assert set(ratios) == {r["op_type"] for r in rep["op_classes"]
                           if r["ratio"]}


# ---------------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------------
def test_executor_hot_path_untouched_with_opprof_loaded():
    # opprof IS imported (module top); the executor hot path must stay
    # registry-silent and retrace-free regardless
    flags.set_flag("observe", False)
    loss = _build_net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    before = obs.registry().snapshot()
    exe.run(feed=_feed(), fetch_list=[loss])       # pays the one trace
    with retrace_guard():
        for i in range(3):
            exe.run(feed=_feed(seed=i), fetch_list=[loss])
    after = obs.registry().snapshot()
    deltas = [(n, s) for n, s in after.items()
              if s != before.get(n)]
    assert not deltas, f"hot path wrote metrics: {deltas}"


def test_profiling_does_not_retrace_the_compiled_cache():
    loss = _build_net()
    prog = pt.default_main_program()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    feed = _feed()
    exe.run(feed=feed, fetch_list=[loss])          # compile once
    opprof.profile_program(prog, executor=exe, feed=feed, batch=16,
                           reps=1, warmup=0)
    with retrace_guard():                          # eager walk left the
        exe.run(feed=feed, fetch_list=[loss])      # cache untouched


# ---------------------------------------------------------------------------
# synthesis helpers
# ---------------------------------------------------------------------------
def test_synth_feeds_bound_by_consumers_and_lod_companions():
    words = layers.data("words", shape=[], dtype="int64", lod_level=1)
    emb = layers.embedding(words, size=(37, 8))
    layers.mean(emb)
    prog = pt.default_main_program()
    feeds = opprof.synth_feeds(prog, batch=6, seq_len=5)
    assert feeds["words"].shape == (6, 5)
    assert feeds["words"].max() < 37        # bounded by the table rows
    assert feeds["words@LEN"].shape == (6,)
    assert (feeds["words@LEN"] == 5).all()


def test_synth_state_prefers_live_scope_values():
    _build_net()
    prog = pt.default_main_program()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    scope = pt.global_scope()
    state = opprof.synth_state(prog, scope=scope, batch=8)
    keys = set(exe._state_keys(prog, scope))
    assert keys <= set(state)
    k = next(iter(keys))
    assert np.asarray(state[k]) == pytest.approx(np.asarray(scope.get(k)))


# ---------------------------------------------------------------------------
# CLI rounds
# ---------------------------------------------------------------------------
def _save_program(tmp_path):
    _build_net()
    prog = pt.default_main_program()
    path = tmp_path / "prog.json"
    path.write_text(prog.to_json())
    return str(path)


def test_cli_profile_in_process(tmp_path, capsys):
    from paddle_tpu import cli
    path = _save_program(tmp_path)
    cal = str(tmp_path / "cal.json")
    rc = cli.main(["profile", path, "--batch", "4", "--reps", "1",
                   "--warmup", "0", "--json", "--calibration-out", cal])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rep["ops"] > 0 and rep["rows"]
    assert rep["xla_loses_here"]
    # the committed table round-trips into the planner loader
    from paddle_tpu.observability import attribution
    ratios = attribution.load_op_class_ratios(cal)
    assert ratios
    # `plan --calibration` accepts the same file (tp-splittable or not,
    # the load path is what this pins)
    doc = json.load(open(cal))
    assert doc["format"] == 2 and doc["op_classes"]


def test_cli_doctor_per_op_joins_profile(tmp_path, capsys):
    from paddle_tpu import cli
    log = tmp_path / "run.jsonl"
    flags.set_flag("observe", True)
    flags.set_flag("metrics_log", str(log))
    loss = _build_net()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    for i in range(3):
        exe.run(feed=_feed(seed=i), fetch_list=[loss])
    flags.set_flag("metrics_log", "")
    obs_export._reset_writer()
    path = _save_program(tmp_path) if False else None
    prog_path = tmp_path / "prog.json"
    prog_path.write_text(pt.default_main_program().to_json())
    rc = cli.main(["doctor", str(log), "--program", str(prog_path),
                   "--per-op", "--batch", "4", "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "training" in rep             # the PR 10 step budget
    assert rep["per_op"]["ops"] > 0      # joined under it
    assert rep["per_op"]["rows"]


def test_cli_doctor_per_op_requires_program(capsys, tmp_path):
    from paddle_tpu import cli
    log = tmp_path / "x.jsonl"
    log.write_text("")
    with pytest.raises(SystemExit):
        cli.main(["doctor", str(log), "--per-op"])


@pytest.mark.slow
@pytest.mark.timeout(180)
def test_cli_profile_subprocess_round(tmp_path):
    path = _save_program(tmp_path)
    cal = str(tmp_path / "cal.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu", "profile", path,
         "--batch", "4", "--reps", "1", "--warmup", "0", "--json",
         "--calibration-out", cal],
        capture_output=True, text=True, timeout=170, env=env,
        cwd="/root/repo")
    assert proc.returncode == 0, proc.stderr[-4000:]
    rep = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rep["ops"] > 0
    assert os.path.exists(cal)
