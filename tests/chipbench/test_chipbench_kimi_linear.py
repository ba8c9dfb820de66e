"""``kimi-linear-train-scan`` rehearsed on the CPU at its ``rehearse`` sizes
(two KDA heads of 16, chunks of 32 in two sub-blocks, latent attention at
24 / 8 head sizes, 2 experts held of a router 8 wide) under
``train_scan_routed`` (on the chip the restart of its 7.2 GB of state fits
beside it, so no third wrapper joins the routed check to the fresh start):
the run's check is the routed one; each control handed to the reference
through ``--set control=...`` comes out ``correct: false`` by the run's own
comparison; the configuration's cut is the three keys it says, its widths
the published ones."""
import json

import pytest

from conftest import BENCH, ROOT, load_json, run_cell

CELL = "kimi-linear-train-scan"
CONTROLS = [
    {"lower": "all"},                    # the reference computed in bfloat16
    {"fault": "no_correction"},          # beta k k^T left out
    {"fault": "scalar_decay"},           # one decay a head for each channel's
    {"sizes": {"moe_renormalize": False}},          # weights not renormalised
    {"sizes": {"expert_parallel_rank": 0}},         # another rank's experts
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
        == ["l1", "l2", "l3", "l4"]
    assert held["routers"]["rows_bound"] == 2 * 64 * 2
    assert all(0 < held["routers"][k]["rows_held"] < 256
               and held["routers"][k]["tokens_routed_otherwise"] == 0
               for k in ("l1", "l2", "l3", "l4"))
    assert set(held["grad_rel_err"]) == set(
        load_json(BENCH, "configs", "kimi_linear_48b_a3b.json")
        ["check_params"])
    # (A_log's two entries are sums over every position and channel of a
    # head: float32 rounding reads up to 4e-4 there, 5e-6 elsewhere)
    assert max(held["grad_rel_err"].values()) < 1e-3
    assert line["detail"]["marks_s"]["startup"] \
        < line["detail"]["marks_s"]["stepped_train"]


@pytest.mark.parametrize("control", CONTROLS, ids=json.dumps)
def test_a_control_comes_out_not_correct(runs, control):
    line = runs[json.dumps(control)]
    held = line["detail"]["reference"]["train"]
    assert line["correct"] is False and held["ok"] is False
    # a fault moves some gradient by a tenth or more; the lower precision
    # moves some matrix's by more than its limit
    assert max(held["grad_rel_err"].values()) > (
        0.02 if "lower" in control else 0.1)
    assert line["failed"] == 0                   # the program itself is sound


def test_the_cell_takes_the_routed_check_and_its_cut_is_three_keys():
    from chipbench.lib.contract import reduced_problems

    cell = load_json(BENCH, "workloads", f"{CELL}.json")
    assert cell["driver"] == "train_scan_routed"
    assert cell["chips"] == 1 and cell["check_batch"] == 1
    assert cell["batch_per_chip"] == 1
    entry = next(c for c in load_json(ROOT, "BENCHMARK.json")["configs"]
                 if c["name"] == cell["config"])
    data = load_json(ROOT, entry["file"])
    assert reduced_problems(entry, data) == []
    assert data["reduced"] == ["num_hidden_layers: 27 -> 5",
                               "num_experts: 256 -> 8",
                               "vocab_size: 163840 -> 20480"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    # every published width as it is
    assert {k: data[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "moe_intermediate_size", "num_experts_per_token",
        "routed_scaling_factor", "router_width", "seq_len")} == {
        "hidden_size": 2304, "intermediate_size": 9216,
        "num_attention_heads": 32, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "moe_intermediate_size": 1024, "num_experts_per_token": 8,
        "routed_scaling_factor": 2.446, "router_width": 256, "seq_len": 8192}
    linear = data["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert len(linear["kda_layers"]) == 20
    assert len(linear["full_attn_layers"]) == 7
    assert data["layers_run"] == [0, 1, 2, 3, 4]
    assert (data["expert_parallel_size"], data["expert_parallel_rank"]) \
        == (32, 0)
    assert data["router_width"] \
        == data["expert_parallel_size"] * data["num_experts"]
    # the cell is listed by the accepted metrics that read what it runs
    listed = {m["name"] for m in load_json(ROOT, "BENCHMARK.json")
              ["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == {
        "gated_delta_scan_step_ms", "gated_delta_scan_roofline_pct",
        "gated_delta_scan_routes",
        "short_conv_step_ms", "short_conv_roofline_pct",
        "grouped_attention_step_ms", "grouped_attention_roofline_pct",
        "expert_share_step_ms", "expert_share_roofline_pct",
        "expert_share_routes", "moe_dropless_routes",
        "loss_from_logits_routes", "recompute_step_ms", "recompute_segments",
        "moe_shared_step_ms", "moe_shared_roofline_pct",
        "moe_shared_routes"}


def test_the_rehearsal_counts_the_new_routes(rehearsals):
    """The traced rehearsal (counts only on the CPU): the new counter and
    the routes the cell's layers take."""
    code, line, stderr = rehearsals[CELL]
    assert code == 0 and line["correct"] is True, stderr[-2000:]
    assert line["metrics"]["gated_delta_scan_routes"]["value"] == 4
    routes = line["detail"]["routes"]
    assert routes["route/gated_delta_scan:xla"] == 4
    for route in ("moe:shared", "moe:gated_pair", "moe:share", "moe:sigmoid",
                  "moe:dropless", "moe_rows:tiles"):
        assert routes["route/" + route] == 4, route
    assert routes["route/recompute:checkpoint"] == 4
    assert routes["route/flash_attention:value_dims"] == 1
    assert not routes.get("route/moe:single")
