"""The phase log of ``CompileStats`` (core/compile_cache.py): what made a
process's start cold, one record a piece of work, written where the work
happens (``Executor._enter``, ``CachedStep._compile`` / ``__call__``,
``ShardedExecutor.place_state``, the package's ``__init__``).

The contract under test: a cold call writes its phases once each, in
order, sharing ``fp``; a warm dispatch writes NOTHING and moves no counter
but the entry cache's own ``hits``; the log is capped, survives nothing
but ``process/import`` across ``reset()``, takes only names of
``PHASE_NAMES``, reaches the metrics log as ``phase`` events and the
profiler's trace as ``pt:compile:<phase>:<fp12>`` annotations.
"""
import json
import time

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import flags, layers, profiler
from paddle_tpu.core import compile_cache
from paddle_tpu.core.compile_cache import (PHASE_LOG_CAP, PHASE_NAMES,
                                           RetraceError, retrace_guard)
from paddle_tpu.observability import export as obs_export

COLD_RUN = ["step/enter", "step/trace", "step/lower", "step/xla",
            "step/first_call"]


@pytest.fixture(autouse=True)
def _fresh_stats():
    compile_cache.stats().reset()
    yield
    compile_cache.stats().reset()


def _net():
    x = layers.data("x", shape=[4], dtype="float32")
    loss = layers.mean(layers.fc(x, size=3))
    pt.optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    return exe, loss, {"x": np.ones((8, 4), "float32")}


def _step_phases(fp=None):
    return [r for r in compile_cache.stats().phases()
            if r["name"] != "process/import" and (fp is None or r["fp"] == fp)]


def test_cold_run_writes_each_phase_once_in_order_inside_the_call():
    exe, loss, feed = _net()
    compile_cache.stats().reset()
    t0 = time.perf_counter()
    exe.run(feed=feed, fetch_list=[loss])
    t1 = time.perf_counter()
    recs = _step_phases()
    assert [r["name"] for r in recs] == COLD_RUN
    assert len({r["fp"] for r in recs}) == 1 and recs[0]["fp"]
    assert {r["label"] for r in recs} == {"run"}
    # the public call is named once, by the record of the code that knows
    # it (Executor._enter); the step's own records find it through fp
    assert [r["cause"] for r in recs] == ["run", None, None, None, None]
    starts = [r["t0"] for r in recs]
    assert starts == sorted(starts)
    for r in recs:
        assert r["dur_s"] >= 0
        assert t0 <= r["t0"] and r["t0"] + r["dur_s"] <= t1
    # one after the other: no phase starts before the one before it ended
    for a, b in zip(recs, recs[1:]):
        assert a["t0"] + a["dur_s"] <= b["t0"] + 1e-9
    assert isinstance(recs[3]["cache_hit"], bool)


def test_cold_run_steps_names_its_own_cause_and_label():
    exe, loss, feed = _net()
    compile_cache.stats().reset()
    exe.run_steps(3, feed=feed, fetch_list=[loss])
    recs = _step_phases()
    assert [r["name"] for r in recs] == COLD_RUN
    assert {r["label"] for r in recs} == {"run_steps"}
    assert [r["cause"] for r in recs] == ["run_steps"] + [None] * 4


def test_warm_dispatches_write_nothing_and_move_no_counter():
    """The zero-cost contract: 100 warm run calls and 10 warm run_steps
    calls leave the log's length and every counter where they were; only
    the entry cache's own ``hits`` counts them (one a call)."""
    exe, loss, feed = _net()
    exe.run(feed=feed, fetch_list=[loss])
    exe.run_steps(3, feed=feed, fetch_list=[loss])
    stats = compile_cache.stats()
    n, before = len(stats.phases()), stats.snapshot()
    for _ in range(100):
        exe.run(feed=feed, fetch_list=[loss])
    for _ in range(10):
        exe.run_steps(3, feed=feed, fetch_list=[loss])
    after = stats.snapshot()
    assert len(stats.phases()) == n
    assert after.pop("hits") - before.pop("hits", 0) == 110
    assert after == before
    # the startup program's, run's and run_steps': one first call each
    assert stats.phase_totals()["step/first_call"]["count"] == 3


def test_compile_writes_no_first_call_and_the_first_run_then_does():
    exe, loss, feed = _net()
    compile_cache.stats().reset()
    compiled = exe.compile(feed=feed, fetch_list=[loss])
    recs = _step_phases(compiled.fingerprint)
    assert [r["name"] for r in recs] == COLD_RUN[:4]
    assert recs[0]["cause"] == "compile"
    compiled.run(feed=feed)
    recs = _step_phases(compiled.fingerprint)
    assert [r["name"] for r in recs] == COLD_RUN
    compiled.run(feed=feed)
    assert len(_step_phases(compiled.fingerprint)) == 5
    # the times the benchmark's compile_s reads are the same three phases
    times = compiled.compile_times
    by_name = {r["name"]: r["dur_s"] for r in recs}
    assert times["trace_s"] == pytest.approx(by_name["step/trace"])
    assert times["lower_s"] == pytest.approx(by_name["step/lower"])
    assert times["compile_s"] == pytest.approx(by_name["step/xla"])


def test_trace_lower_xla_sum_to_total_compile_seconds():
    exe, loss, feed = _net()
    exe.run(feed=feed, fetch_list=[loss])
    exe.run_steps(2, feed=feed, fetch_list=[loss])
    stats = compile_cache.stats()
    totals = stats.phase_totals()
    three = sum(totals[n]["seconds"]
                for n in ("step/trace", "step/lower", "step/xla"))
    assert three == pytest.approx(stats.total_compile_seconds(), abs=1e-4)


def test_retrace_guard_and_assert_no_retrace_behave_as_before():
    exe, loss, feed = _net()
    with retrace_guard():
        for _ in range(3):
            exe.run(feed=feed, fetch_list=[loss])
    compile_cache.stats().assert_no_retrace()
    fp = _step_phases()[-1]["fp"]
    with pytest.raises(RetraceError):
        with retrace_guard():
            compile_cache.stats().record_trace(fp)
            compile_cache.stats().record_trace(fp)
    with pytest.raises(RetraceError):
        compile_cache.stats().assert_no_retrace()


def test_the_log_is_capped_and_counts_what_it_drops():
    stats = compile_cache.stats()
    have = len(stats.phases())
    for i in range(PHASE_LOG_CAP - have + 7):
        stats.record_phase("step/enter", float(i), float(i) + 0.5, fp="f")
    assert len(stats.phases()) == PHASE_LOG_CAP
    assert stats.snapshot()["phases_dropped"] == 7
    assert stats.phase_totals()["step/enter"]["count"] == \
        PHASE_LOG_CAP - have


def test_reset_clears_all_but_the_import():
    stats = compile_cache.stats()
    stats.record_phase("step/enter", 1.0, 2.0, fp="f", cause="run")
    stats.record_phase("state/place", 2.0, 3.0, bytes=8)
    stats.reset()
    assert [r["name"] for r in stats.phases()] == ["process/import"]
    assert "phases_dropped" not in stats.snapshot()


def test_an_unknown_name_raises():
    with pytest.raises(ValueError, match="PHASE_NAMES"):
        compile_cache.stats().record_phase("step/entre", 0.0, 1.0)
    assert all("/" in n and h for n, h in PHASE_NAMES)


def test_process_import_is_there_after_import_paddle_tpu():
    (rec,) = [r for r in compile_cache.stats().phases()
              if r["name"] == "process/import"]
    assert rec["dur_s"] > 0
    assert rec["t0"] + rec["dur_s"] <= time.perf_counter()
    assert isinstance(rec["jax_preimported"], bool)
    assert rec["fp"] is None and rec["cause"] is None


def test_phases_returns_copies():
    stats = compile_cache.stats()
    stats.phases()[0]["name"] = "mine"
    assert stats.phases()[0]["name"] == "process/import"


def test_place_state_writes_state_place_with_bytes():
    from paddle_tpu.parallel import ShardedExecutor, mesh_for_axes

    x = layers.data("x", shape=[4], dtype="float32")
    loss = layers.mean(layers.fc(x, size=3))
    pt.optimizer.SGD(0.1).minimize(loss)
    mesh = mesh_for_axes({"dp": 4}, devices=jax.devices()[:4])
    exe = ShardedExecutor(mesh=mesh, batch_axis="dp")
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    compile_cache.stats().reset()
    exe.place_state(pt.default_main_program())
    (rec,) = _step_phases()
    assert rec["name"] == "state/place"
    # fc: 4 x 3 weights and 3 biases, float32, plus the learning rate
    scope = pt.global_scope()
    block = pt.default_main_program().global_block()
    held = sum(int(scope.get(n).nbytes) for n in scope.keys()
               if block.has_var(n) and block.var(n).persistable)
    assert rec["bytes"] == held >= (4 * 3 + 3) * 4
    assert rec["dur_s"] > 0
    # and the mesh step names its label and cause like any other
    exe.run_steps(2, feed={"x": np.ones((8, 4), "float32")},
                  fetch_list=[loss])
    recs = _step_phases()[1:]
    assert [r["name"] for r in recs] == COLD_RUN
    assert {r["label"] for r in recs} == {"sharded_run_steps"}
    assert recs[0]["cause"] == "run_steps"


def test_a_metrics_log_gets_one_phase_event_a_record(tmp_path, capsys):
    log = tmp_path / "phases.jsonl"
    prev = flags.get_flag("metrics_log")
    try:
        exe, loss, feed = _net()        # no log yet: nothing is emitted
        assert not log.exists()
        compile_cache.stats().reset()
        flags.set_flag("metrics_log", str(log))
        exe.run(feed=feed, fetch_list=[loss])
        exe.run(feed=feed, fetch_list=[loss])       # warm: no event
    finally:
        flags.set_flag("metrics_log", prev)
        obs_export._reset_writer()
    events = [json.loads(ln) for ln in log.read_text().splitlines()]
    phases = [e for e in events if e["kind"] == "phase"]
    assert [e["name"] for e in phases] == COLD_RUN
    recs = _step_phases()
    assert [e["dur_s"] for e in phases] == [r["dur_s"] for r in recs]
    assert {e["fp"] for e in phases} == {recs[0]["fp"]}

    from paddle_tpu.cli import main as cli_main
    assert cli_main(["stats", str(log)]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["events"] == len(events)


def test_a_cold_compile_is_annotated_and_a_warm_call_is_not(monkeypatch):
    entered = []

    class Recorder:
        def __init__(self, name, **_kw):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    exe, loss, feed = _net()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    exe.run(feed=feed, fetch_list=[loss])
    fp12 = _step_phases()[-1]["fp"][:12]
    assert entered == [f"pt:compile:trace:{fp12}", f"pt:compile:lower:{fp12}",
                       f"pt:compile:xla:{fp12}"]
    exe.run(feed=feed, fetch_list=[loss])
    assert len(entered) == 3


def test_call_prepares_before_it_reads_its_flag():
    """``__call__`` stands above ``trace()`` and ``lower()`` while a step
    compiles, and the startup program's lowering read 0.1-0.4 s longer in
    four rounds of four with the flag read BEFORE ``self.prepare`` (PERF.md
    section 6, PR 36 (d2)): the call stays the first statement."""
    import ast
    import inspect
    import textwrap

    tree = ast.parse(textwrap.dedent(
        inspect.getsource(compile_cache.CachedStep.__call__)))
    assert ast.unparse(tree.body[0].body[0]) == \
        "self.prepare(feeds, state, step)"


JAX_SECONDS = ("jax_trace_s", "jax_lower_s", "jax_backend_compile_s")


def _jax_seconds():
    snap = compile_cache.stats().snapshot()
    return sum(snap.get(k, 0.0) for k in JAX_SECONDS)


def test_jax_seconds_leave_out_what_a_step_compiles():
    """The three counters are JAX's own seconds OUTSIDE a step's compile:
    a jitted function called inside a step's trace fires JAX's trace event
    there (and the step its own three), and reads nothing; the same kind
    of function called bare moves them, with no record each."""
    inner = jax.jit(lambda a: a * 2 + 1)

    def fn(feeds, state, step):
        return inner(feeds["x"]), state

    compile_cache.cache_dir()           # the listener is on
    step = compile_cache.CachedStep(fn, "f" * 40, label="run")
    before = _jax_seconds()
    step.prepare({"x": np.arange(3.0)}, {}, 0)
    assert [r["name"] for r in _step_phases()] == COLD_RUN[1:4]
    assert _jax_seconds() == before
    n = len(compile_cache.stats().phases())
    jax.jit(lambda a: a * 3 - 1)(np.arange(3.0))
    snap = compile_cache.stats().snapshot()
    for key in ("jax_trace_s", "jax_backend_compile_s"):
        assert isinstance(snap[key], float) and snap[key] > 0
    assert _jax_seconds() > before
    assert len(compile_cache.stats().phases()) == n


def test_jax_seconds_count_a_nested_event_once(monkeypatch):
    """JAX reports an event when it ENDS, the ones nested in it before it:
    an outer trace of 4 s that held an inner trace and an eager compile of
    1 s each adds 2 s, and the sum is the time somebody waited."""
    import types

    base = time.perf_counter() + 1000.0     # after every real event
    ends = iter(base + t for t in (3.0, 4.5, 5.0, 7.0, 9.0))
    monkeypatch.setattr(compile_cache, "time", types.SimpleNamespace(
        perf_counter=lambda: next(ends)))
    trace, xla = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
    stats = compile_cache.stats()
    stats.reset()
    compile_cache._on_jax_duration(trace, 1.0, fun_name="inner")  # [2, 3]
    compile_cache._on_jax_duration(xla, 1.0)                  # [3.5, 4.5]
    compile_cache._on_jax_duration(trace, 4.0)                    # [1, 5]
    compile_cache._on_jax_duration(trace, 1.0)                    # [6, 7]
    compile_cache._on_jax_duration("/jax/other", 1.0)     # not one of them
    snap = stats.snapshot()
    assert snap["jax_trace_s"] == pytest.approx(1.0 + 2.0 + 1.0)
    assert snap["jax_backend_compile_s"] == pytest.approx(1.0)
    assert _jax_seconds() == pytest.approx(5.0)     # the union of the four
    compile_cache._jit_local.in_step = 1            # inside a step's compile
    try:
        compile_cache._on_jax_duration(trace, 1.0)
    finally:
        compile_cache._jit_local.in_step = 0
    assert _jax_seconds() == pytest.approx(5.0)
    compile_cache._jit_local.spans.clear()  # the made-up clock's intervals


def test_profiler_report_prints_the_totals():
    exe, loss, feed = _net()
    exe.run(feed=feed, fetch_list=[loss])
    rep = profiler.report()
    assert "phases (seconds, count):" in rep
    for name in ["process/import"] + COLD_RUN:
        assert f"    {name}: " in rep
    assert rep.count("longest: ") == 5
    assert "StatSet" not in rep        # nothing was timed into it
    # a record's facts are printed beside it
    compile_cache.stats().record_phase("state/place", 0.0, 99.0, bytes=64)
    assert "longest: state/place 99.000s [-] bytes=64" in profiler.report()
