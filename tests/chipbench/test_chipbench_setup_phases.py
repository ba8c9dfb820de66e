"""The ``setup_*`` readers (``chipbench/layer_metrics/setup_*.py`` over
``chipbench/lib/setup_phases.py``): set-up split by the program's own
phase log.  Each reader on a synthetic context with a hand-written log,
the program WITHOUT a phase log (a parent commit under these files), and
``--rehearse`` runs of a one-chip cell and of the mesh cell."""
import importlib.util
import json
import os
import types

import pytest

from conftest import BENCH, ROOT, load_json

from chipbench.lib import setup_phases

BENCHMARK = load_json(ROOT, "BENCHMARK.json")
READERS = [m["name"] for m in BENCHMARK["per_layer"]
           if m["name"].startswith("setup_")]
T0 = 1000.0                    # T_START of the synthetic run
END = T0 + 30.0                # its end of set-up


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench.layer_metrics.{name}",
        os.path.join(BENCH, "layer_metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(name, start, dur, fp=None, label=None, cause=None, **facts):
    return {"name": name, "t0": T0 + start, "dur_s": dur, "fp": fp,
            "label": label, "cause": cause, **facts}


def _cold(fp, label, cause, start, enter, trace, lower, xla, call, hit):
    """One cold call's records, one after the other from ``start``."""
    who = {"fp": fp, "label": label, "cause": cause}
    out, t = [], start
    for name, dur, facts in (
            ("step/enter", enter, {}), ("step/trace", trace, {}),
            ("step/lower", lower, {}), ("step/xla", xla, {"cache_hit": hit}),
            ("step/first_call", call, {})):
        if dur is not None:
            out.append(_rec(name, t, dur, **who, **facts))
            t += dur
    return out


#: a set-up as a mesh cell's: the import, the startup program, a placement,
#: the K-step scan through Executor.compile, its first call in the warm-up
#: window; then, AFTER the end of set-up, the check's own cold step
LOG = ([_rec("process/import", 2.0, 6.0, jax_preimported=True)]
       + _cold("a" * 64, "sharded_run", "run", 9.0, 1.5, 0.25, 4.0, 0.75,
               0.5, True)
       + [_rec("state/place", 17.0, 1.25, bytes=1 << 20)]
       + _cold("b" * 64, "sharded_run_steps", "compile", 19.0, 0.5, 1.0,
               3.0, 2.5, None, False)
       + [_rec("step/first_call", 27.0, 0.125, fp="b" * 64,
               label="sharded_run_steps", cause="run_steps")]
       + _cold("c" * 64, "sharded_run", "run", 45.0, 0.5, 0.5, 5.0, 9.0,
               0.5, False))
EXPECTED = {
    "setup_import_s": 6.0,
    "setup_enter_s": 2.0,
    "setup_trace_s": 1.25,
    "setup_lower_s": 7.0,
    "setup_xla_s": 3.25,
    "setup_first_call_s": 0.625,
    "setup_place_state_s": 1.25,
    # JAX's 0.5 + 0.25 + 1.0 outside the steps' compiles
    "setup_jit_outside_steps_s": 1.75,
    # 30 s of set-up; the phases cover 6 + 7 + 1.25 + 7 + 0.125
    "setup_outside_program_s": 30.0 - 21.375,
    "setup_steps_compiled": 2,
}


def _stats(records):
    """The program's own ``CompileStats`` holding ``records``."""
    from paddle_tpu.core import compile_cache

    stats = compile_cache.CompileStats()
    for r in records:
        r = dict(r)
        name, t0, dur = r.pop("name"), r.pop("t0"), r.pop("dur_s")
        stats.record_phase(name, t0, t0 + dur, **r)
    return stats


def _ctx(monkeypatch, records, counters=None, with_log=True):
    """A context as ``run.py`` leaves it after the driver, over a program
    whose ``compile_stats()`` holds ``records`` (or no phase log at all)."""
    from paddle_tpu import profiler

    stats = _stats(records) if with_log else object()
    monkeypatch.setattr(profiler, "compile_stats", lambda: stats)
    if counters is None:
        counters = {"jax_trace_s": 0.5, "jax_lower_s": 0.25,
                    "jax_backend_compile_s": 1.0, "jax_cache_hits": 6}
    return types.SimpleNamespace(
        t_start=T0, setup_s=END - T0, obs={}, detail={"marks_s": {}},
        before={"t": END, "compile": counters,
                "compile_seconds": 11.5})


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_on_a_hand_written_log(monkeypatch, name):
    ctx = _ctx(monkeypatch, LOG)
    assert _reader(name).compute(ctx) == pytest.approx(EXPECTED[name])
    # whichever reader of the phase log ran first left its short form
    assert ("setup_phases" in ctx.detail) == \
        (name != "setup_jit_outside_steps_s")      # that one reads counters


def test_the_ten_are_the_benchmarks_and_all_move_setup_s():
    assert sorted(READERS) == sorted(EXPECTED)
    assert READERS == [m["name"] for m in BENCHMARK["per_layer"]][-10:]
    for m in BENCHMARK["per_layer"][-10:]:
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        assert m["unit"] == ("count" if m["name"] == "setup_steps_compiled"
                             else "s")
    place = [m for m in BENCHMARK["per_layer"]
             if m["name"] == "setup_place_state_s"][0]
    mesh_cells = [w["name"] for w in BENCHMARK["workloads"]
                  if w["chips"] > 1]
    assert place["workloads"] == mesh_cells


def test_phases_after_the_end_of_setup_are_left_out(monkeypatch):
    ctx = _ctx(monkeypatch, LOG)
    kept = setup_phases.split(ctx)["records"]
    assert len(kept) == len(LOG) - 5
    assert all(r["t0"] < END for r in kept)
    assert "c" * 64 not in {r["fp"] for r in kept}
    # ... and the check's nine-second compile is in no total
    assert _reader("setup_xla_s").compute(ctx) == pytest.approx(3.25)


def test_overlapping_intervals_count_once_in_outside_program(monkeypatch):
    # an import that runs INSIDE a step's enter (a lazily loaded module),
    # and a second thread's placement over both
    records = [_rec("step/enter", 10.0, 4.0, fp="a" * 64),
               _rec("process/import", 11.0, 2.0),
               _rec("state/place", 13.0, 3.0, bytes=8)]
    ctx = _ctx(monkeypatch, records)
    assert setup_phases.split(ctx)["union_s"] == pytest.approx(6.0)
    assert _reader("setup_outside_program_s").compute(ctx) == \
        pytest.approx(24.0)
    # the sums by name still count each phase whole
    assert _reader("setup_enter_s").compute(ctx) == pytest.approx(4.0)
    assert _reader("setup_import_s").compute(ctx) == pytest.approx(2.0)
    # a phase that began before T_START is clipped to the run
    early = [_rec("process/import", -5.0, 7.0)]
    assert setup_phases.union_seconds(early, T0, END) == pytest.approx(2.0)


def test_trace_lower_xla_is_compile_s_and_outside_closes_setup(monkeypatch):
    ctx = _ctx(monkeypatch, LOG)
    three = sum(_reader(n).compute(ctx)
                for n in ("setup_trace_s", "setup_lower_s", "setup_xla_s"))
    assert three == pytest.approx(ctx.before["compile_seconds"], abs=1e-3)
    outside = _reader("setup_outside_program_s").compute(ctx)
    assert outside + ctx.detail["setup_phases"]["union_s"] == \
        pytest.approx(ctx.setup_s, abs=1e-3)


def test_a_program_without_a_phase_log_gives_none_from_all_ten(monkeypatch):
    ctx = _ctx(monkeypatch, [], counters={"jax_cache_hits": 6},
               with_log=False)
    for name in READERS:
        assert _reader(name).compute(ctx) is None, name
    assert "setup_phases" not in ctx.detail
    # a phase log but none of JAX's totals: only that one reader is silent
    ctx = _ctx(monkeypatch, LOG, counters={"jax_cache_hits": 6})
    assert _reader("setup_jit_outside_steps_s").compute(ctx) is None
    assert _reader("setup_xla_s").compute(ctx) == pytest.approx(3.25)


def test_the_line_stays_short_at_a_full_log(monkeypatch):
    records = [_rec("process/import", 0.5, 3.123456789, jax_preimported=True)]
    for i in range(511):
        name = ("step/enter", "step/trace", "step/lower", "step/xla",
                "step/first_call", "state/place")[i % 6]
        records.append(_rec(name, 4.0 + i * 0.05, 0.0123456789 * (i + 1),
                            fp=f"{i:064x}", label="sharded_run_steps",
                            cause="run_steps", cache_hit=i % 4 == 3))
    ctx = _ctx(monkeypatch, records)
    for name in READERS:
        _reader(name).compute(ctx)
    added = {k: v for k, v in ctx.detail.items() if k != "marks_s"}
    assert set(added) == {"setup_phases"}
    assert len(json.dumps(added)) < 1200
    form = added["setup_phases"]
    assert len(form["longest"]) == 8 and len(form["s"]) == 7
    assert form["longest"][0][0] == "step/enter"       # the longest: i = 510
    assert form["xla_cache_reads"] == [43, 85]


def _rehearse(cell, cwd=ROOT):
    from conftest import run_cell

    chips = load_json(BENCH, "workloads", f"{cell}.json")["chips"]
    extra = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}"} \
        if chips > 1 else {}
    proc = run_cell(["--workload", cell, "--seed", "11", "--seconds", "1",
                     "--trace", "1", "--rehearse"], extra, cwd=cwd)
    stdout, stderr = proc.communicate(timeout=600)
    assert proc.returncode == 0, stderr[-2000:]
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("chips", [1, 4])
def test_a_rehearsal_counts_the_steps_it_compiled(chips):
    cell = [w["name"] for w in BENCHMARK["workloads"]
            if w["chips"] == chips][1 if chips == 1 else 0]
    line = _rehearse(cell)      # two cells, not the session's whole set
    # the startup program and the K-step scan, at the least
    assert line["metrics"]["setup_steps_compiled"]["value"] >= 2
    assert line["metrics"]["setup_steps_compiled"]["unit"] == "count"
    form = line["detail"]["setup_phases"]
    assert form["s"]["step/xla"][1] == \
        line["metrics"]["setup_steps_compiled"]["value"]
    assert form["s"]["process/import"][1] == 1
    assert ("state/place" in form["s"]) == (chips > 1)
    assert 0 < form["union_s"] < line["detail"]["marks_s"]["setup"]
    assert len(json.dumps(form)) < 1200


def test_rehearse_still_prints_its_line_over_a_program_without_phases(
        tmp_path):
    """These benchmark files laid over a program that keeps no phase log
    (the parent commit): the line comes out as before, with none of the
    ten."""
    import shutil

    bare = tmp_path / "parent"
    ignore = shutil.ignore_patterns("out", "__pycache__", "*.so")
    shutil.copytree(BENCH, bare / "chipbench", ignore=ignore)
    shutil.copytree(os.path.join(ROOT, "paddle_tpu"), bare / "paddle_tpu",
                    ignore=ignore)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    path = bare / "paddle_tpu" / "core" / "compile_cache.py"
    text = path.read_text()
    assert "    def phases(self)" in text
    path.write_text(text.replace("    def phases(self)",
                                 "    def _no_phases(self)"))
    cell = [w["name"] for w in BENCHMARK["workloads"] if w["chips"] == 1][1]
    line = _rehearse(cell, cwd=str(bare))
    assert line["correct"] is True
    assert not [m for m in line["metrics"] if m.startswith("setup_")]
    assert "setup_phases" not in line["detail"]
    assert line["metrics"]["compiles_in_window"]["value"] == 0
