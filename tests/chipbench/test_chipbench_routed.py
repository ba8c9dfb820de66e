"""Driver ``train_scan_routed`` on the CPU: the reference is shown what
the program's routers read, and a control handed to it through ``--set
control=...`` comes out ``correct: false`` by the run's own comparison."""
import json

import pytest

from conftest import run_cell

CELL = "lfm2-train-scan"


def _rehearse(control=None):
    args = ["--workload", CELL, "--seed", "11", "--seconds", "1",
            "--trace", "0", "--rehearse"]
    if control is not None:
        args += ["--set", f"control={json.dumps(control)}"]
    stdout, stderr = run_cell(args).communicate(timeout=600)
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    assert lines, stderr[-2000:]
    return json.loads(lines[-1])


def test_the_reference_is_shown_the_routers_inputs():
    line = _rehearse()
    held = line["detail"]["reference"]["train"]
    assert line["correct"] is True and held["ok"] is True
    layers = {k: v for k, v in held["routers"].items() if k.startswith("l")}
    assert sorted(layers) == ["l1", "l2"]        # the rehearsal's experts
    for seen in layers.values():
        # true float32 on both sides: the same input, the same choice
        assert seen["input_rel_err"] < 1e-5
        assert seen["tokens_routed_otherwise"] == 0
        assert 0 < seen["rows_held"] <= held["routers"]["rows_bound"]
    assert held["router_input_rel_err"] == max(
        seen["input_rel_err"] for seen in layers.values())


@pytest.mark.parametrize("control", [
    {"sizes": {"use_expert_bias": False}},       # the bias left out
    {"sizes": {"norm_topk_prob": False}},        # weights not renormalised
    {"sizes": {"expert_parallel_rank": 0}},      # another rank's offset
    {"tokens": 8},                               # half the tokens left out
], ids=lambda c: json.dumps(c))
def test_a_control_comes_out_not_correct(control):
    line = _rehearse(control)
    held = line["detail"]["reference"]["train"]
    assert line["correct"] is False and held["ok"] is False
    assert max(held["grad_rel_err"].values()) > 0.3
