"""``nemotron-train-scan`` rehearsed on the CPU at its ``rehearse`` sizes (two
state-space groups, 2 experts held of a router 8 wide, an expert width that
is no multiple of a tile), under ``train_scan_routed`` (on the chip the
restart of its 8.0 GB of state fits, 16.01 of 16.91 GB at its peak, so no
third wrapper joins the routed check to the fresh start: PERF.md section
7): the run's check is the routed one; each control handed to the reference
through ``--set control=...`` comes out ``correct: false`` by the run's own
comparison; the configuration's cut is the three keys it says, its widths
the published ones."""
import json

import pytest

from conftest import BENCH, ROOT, load_json, run_cell

CELL = "nemotron-train-scan"
CONTROLS = [
    {"lower": "all"},                    # the reference computed in bfloat16
    {"fault": "no_shared"},              # the shared expert left out
    {"fault": "relu"},                   # relu for relu^2
    {"sizes": {"expert_parallel_rank": 0}},          # another rank's experts
    {"sizes": {"norm_topk_prob": False}},            # weights not renormalised
]


def _last_line(proc):
    stdout, stderr = proc.communicate(timeout=900)
    lines = [ln for ln in stdout.splitlines() if ln.strip()]
    assert lines, stderr[-2000:]
    return json.loads(lines[-1])


@pytest.fixture(scope="module")
def runs():
    """{control as JSON, or 'sound': last line} of the cell's rehearsal,
    sound and under each control, run side by side."""
    args = ["--workload", CELL, "--seed", "2400000011", "--seconds", "1",
            "--trace", "0", "--rehearse"]
    procs = {json.dumps(c): run_cell(
        args + ["--set", f"control={json.dumps(c)}"]) for c in CONTROLS}
    procs["sound"] = run_cell(args)
    return {key: _last_line(proc) for key, proc in procs.items()}


def test_the_rehearsal_is_correct_and_its_check_is_the_routed_one(runs):
    """The routed check ran: the line holds what each of the four routers
    saw and how far the reference's own inputs lie from the ones shown."""
    line = runs["sound"]
    held = line["detail"]["reference"]["train"]
    assert line["correct"] is True and line["failed"] == 0
    assert held["router_input_rel_err"] < 1e-4
    assert sorted(k for k in held["routers"] if k.startswith("l")) \
        == ["l1", "l3", "l6", "l8"]
    assert held["routers"]["rows_bound"] == 2 * 16 * 2
    assert all(0 < held["routers"][k]["rows_held"] < 64
               and held["routers"][k]["tokens_routed_otherwise"] == 0
               for k in ("l1", "l3", "l6", "l8"))
    assert set(held["grad_rel_err"]) == set(
        load_json(BENCH, "configs", "nemotron_3_nano_30b_a3b.json")
        ["check_params"])
    assert max(held["grad_rel_err"].values()) < 1e-4
    assert line["detail"]["marks_s"]["startup"] \
        < line["detail"]["marks_s"]["stepped_train"]


@pytest.mark.parametrize("control", CONTROLS, ids=json.dumps)
def test_a_control_comes_out_not_correct(runs, control):
    line = runs[json.dumps(control)]
    held = line["detail"]["reference"]["train"]
    assert line["correct"] is False and held["ok"] is False
    # a fault moves some gradient by a tenth or more; the lower precision
    # moves every matrix's by more than its limit
    assert max(held["grad_rel_err"].values()) > (
        0.02 if "lower" in control else 0.1)
    assert line["failed"] == 0                   # the program itself is sound


def test_the_cell_runs_under_the_routed_driver_and_its_cut_is_three_keys():
    from chipbench.lib.contract import reduced_problems

    cell = load_json(BENCH, "workloads", f"{CELL}.json")
    assert cell["driver"] == "train_scan_routed"
    assert cell["chips"] == 1 and cell["check_batch"] == 1
    entry = next(c for c in load_json(ROOT, "BENCHMARK.json")["configs"]
                 if c["name"] == cell["config"])
    data = load_json(ROOT, entry["file"])
    assert reduced_problems(entry, data) == []
    assert data["reduced"] == ["num_hidden_layers: 52 -> 9",
                               "n_routed_experts: 128 -> 8",
                               "vocab_size: 131072 -> 16384"]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    # every published width as it is
    assert {k: data[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "chunk_size", "conv_kernel",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "num_experts_per_tok", "routed_scaling_factor", "router_width")} == {
        "hidden_size": 2688, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 64,
        "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
        "chunk_size": 128, "conv_kernel": 4, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712,
        "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
        "router_width": 128}
    pattern = data["hybrid_override_pattern"]
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)
    run = "".join(pattern[i] for i in data["layers_run"])
    assert run == "MEMEM*EME" and data["layers_run"] == list(range(9))
    assert (data["expert_parallel_size"], data["expert_parallel_rank"]) \
        == (16, 0)
    assert data["router_width"] \
        == data["expert_parallel_size"] * data["n_routed_experts"]
    # the cell is listed by the accepted metrics that read what it runs
    listed = {m["name"] for m in load_json(ROOT, "BENCHMARK.json")
              ["per_layer"] if CELL in m.get("workloads", ())}
    assert listed >= {
        "ssd_scan_step_ms", "ssd_scan_roofline_pct", "ssd_scan_routes",
        "short_conv_step_ms", "short_conv_roofline_pct",
        "grouped_attention_step_ms", "grouped_attention_roofline_pct",
        "expert_share_step_ms", "expert_share_roofline_pct",
        "expert_share_routes", "moe_dropless_routes",
        "loss_from_logits_routes", "recompute_step_ms", "recompute_segments",
        "moe_shared_step_ms", "moe_shared_roofline_pct",
        "moe_shared_routes"}


def test_the_rehearsal_counts_the_new_routes(rehearsals):
    """The traced rehearsal (counts only on the CPU): the three new
    counters and the routes the cell's layers take."""
    code, line, stderr = rehearsals[CELL]
    assert code == 0 and line["correct"] is True, stderr[-2000:]
    assert line["metrics"]["moe_shared_routes"]["value"] == 4
    routes = line["detail"]["routes"]
    for route in ("moe:shared", "moe:single", "moe:share", "moe:sigmoid",
                  "moe:dropless", "moe_rows:tiles"):
        assert routes["route/" + route] == 4, route
    assert routes["route/recompute:checkpoint"] == 2
    assert routes["route/flash_attention:grouped"] == 1
    assert not routes.get("route/moe:gated_pair")
