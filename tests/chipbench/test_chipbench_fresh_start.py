"""Driver ``train_scan_fresh_start`` on the CPU, through the cell that runs
under it: the return to the seeded start releases the program's state
before the startup program runs again (and comes back to the same values),
a control handed to the reference through ``--set control=...`` comes out
``correct: false`` by the run's own comparison, and the configuration's cut
is the two keys it says."""
import importlib.util
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH, ROOT, load_json, run_cell

CELL = "granite-train-scan"
CONTROLS = [
    {"lower": "all"},                    # the reference computed in bfloat16
    {"fault": "chunk_reset"},                        # state not handed on
    {"fault": "no_d"},                               # D left out
    {"sizes": {"attention_multiplier": 0.125}},      # 1/8 for 1/64
    {"sizes": {"residual_multiplier": 1.0}},         # multiplier left out
]


@pytest.fixture(scope="module")
def controlled():
    """{control as JSON: last line} of the cell's rehearsal under each
    control, run side by side."""
    procs = {json.dumps(c): run_cell(
        ["--workload", CELL, "--seed", "11", "--seconds", "1", "--trace",
         "0", "--rehearse", "--set", f"control={json.dumps(c)}"])
        for c in CONTROLS}
    out = {}
    for key, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=900)
        lines = [ln for ln in stdout.splitlines() if ln.strip()]
        assert lines, stderr[-2000:]
        out[key] = json.loads(lines[-1])
    return out


@pytest.mark.parametrize("control", CONTROLS, ids=json.dumps)
def test_a_control_comes_out_not_correct(controlled, control):
    line = controlled[json.dumps(control)]
    held = line["detail"]["reference"]["train"]
    assert line["correct"] is False and held["ok"] is False
    # a fault moves some gradient by a tenth or more; the lower precision
    # moves every one by more than its limit and the loss by more than its
    assert max(held["grad_rel_err"].values()) > (
        0.01 if "lower" in control else 0.1)
    assert line["failed"] == 0                   # the program itself is sound


def test_the_cell_runs_under_this_driver_and_its_cut_is_two_keys():
    from chipbench.lib.contract import reduced_problems

    cell = load_json(BENCH, "workloads", f"{CELL}.json")
    assert cell["driver"] == "train_scan_fresh_start" and cell["chips"] == 1
    entry = next(c for c in load_json(ROOT, "BENCHMARK.json")["configs"]
                 if c["name"] == cell["config"])
    data = load_json(ROOT, entry["file"])
    assert reduced_problems(entry, data) == []
    assert data["reduced"] == ["num_hidden_layers: 40 -> 10",
                               "vocab_size: 100352 -> 12544"]
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert len(data["layer_types"]) == 40 \
        and data["layers_run"] == list(range(10))
    # nine state-space layers to one attention layer, in the cut as whole
    kinds = [data["layer_types"][i] for i in data["layers_run"]]
    assert kinds.count("mamba") == 9 and kinds.count("attention") == 1
    assert data["layer_types"].count("mamba") == 36


def test_the_return_to_the_start_releases_the_state_first():
    """``restore`` after a step: when the startup program runs again the
    scope holds none of the program's persistable state (so the new state
    never stands beside the old), and what it comes back to is the first
    start's values, weights and optimizer moments alike."""
    import paddle_tpu as pt
    from chipbench.drivers import train_scan_fresh_start as driver

    found = importlib.util.spec_from_file_location(
        "granite_config_for_driver",
        os.path.join(BENCH, "configs", "granite_4_0_h_micro.py"))
    config = importlib.util.module_from_spec(found)
    found.loader.exec_module(config)
    cell = load_json(BENCH, "workloads", f"{CELL}.json")
    sizes = {**load_json(BENCH, "configs", "granite_4_0_h_micro.json"),
             **cell["rehearse"]["sizes"], "layers_run": [4, 5],
             "num_hidden_layers": 2}
    built = config.build("train", 2, sizes)
    ctx = SimpleNamespace(mark=lambda what: None,
                          seed_for=lambda what: 5)
    exe = pt.Executor()
    start = driver._Start(ctx, exe, built, None)
    start.restore()
    first = start.state()
    assert len(first) > 30
    rng = np.random.RandomState(0)
    feed = {k: rng.randint(0, sizes["vocab_size"], (2, sizes["seq_len"]))
            for k in ("ids", "lbl")}
    exe.run(built["main"], feed=feed, fetch_list=[built["loss"]])
    moved = start.state()
    assert any(not np.array_equal(first[n], moved[n]) for n in first)
    held, run = [], exe.run

    def watched(program, **kw):
        if program is built["startup"]:
            held.append([n for n in first if pt.global_scope().has(n)])
        return run(program, **kw)

    exe.run = watched
    start.restore()
    assert held == [[]]
    again = start.state()
    assert sorted(again) == sorted(first)
    assert all(np.array_equal(first[n], again[n]) for n in first)


def test_a_hand_over_that_train_scan_no_longer_takes_fails_loudly(monkeypatch):
    """The driver replaces two names inside ``train_scan`` while it runs;
    a ``train_scan`` that comes to its end without having looked them up
    (a later edit there) must raise, not hand back a verdict."""
    from chipbench.drivers import train_scan
    from chipbench.drivers import train_scan_fresh_start as driver

    monkeypatch.setattr(train_scan, "run", lambda ctx: {
        "correct": True, "attempted": 1, "failed": 0})
    ctx = SimpleNamespace(cell={}, config=SimpleNamespace(
        reference=lambda *args, **kwargs: None))
    with pytest.raises(RuntimeError, match="no longer looks up"):
        driver.run(ctx)
