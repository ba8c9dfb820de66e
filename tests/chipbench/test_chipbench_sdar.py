"""``sdar-train-scan`` rehearsed on the CPU at its ``rehearse`` sizes (four
query heads over two K / V heads of 8, 8 experts under their router, 4 a
token, 32 tokens in blocks of 4) under ``train_scan_routed``: the run's check is
the routed one; each control handed to the reference through ``--set
control=...`` comes out ``correct: false`` by the run's own comparison; the
configuration's cut is the three keys it says, its widths the published
ones; the attention's work is the mask's entries, counted one by one."""
import importlib.util
import json
import os

import pytest

from conftest import BENCH, ROOT, load_json, run_cell

CELL = "sdar-train-scan"
CONTROLS = [
    {"lower": "all"},                    # the reference computed in bfloat16
    {"fault": "no_reweight"},            # a masked token's loss at 1, not 1/t
    {"fault": "own_clean_block"},        # a noisy query sees its clean block
    {"fault": "causal_in_block"},        # causal inside the noisy block
    {"sizes": {"norm_topk_prob": False}},           # weights not renormalised
    {"sizes": {"rope_theta": 10000}},               # another rope base
]
# the catalog's copy of the published config.json, every key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def _config_module():
    spec = importlib.util.spec_from_file_location(
        "sdar_config_for_bench",
        os.path.join(BENCH, "configs", "sdar_30b_a3b.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


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
    saw (both streams' tokens) and how far the reference's own inputs lie
    from the ones shown."""
    line = runs["sound"]
    held = line["detail"]["reference"]["train"]
    assert line["correct"] is True and line["failed"] == 0
    assert held["router_input_rel_err"] < 1e-4
    routers = held["routers"]
    assert sorted(k for k in routers if k.startswith("l")) \
        == ["l0", "l1", "l2", "l3"]
    assert routers["tokens"] == 2 * 2 * 32       # 2 sequences, 2 streams
    assert routers["rows"] == routers["tokens"] * 4
    assert 0 < routers["tokens_masked"] < 2 * 32
    assert all(routers[k]["tokens_routed_otherwise"] == 0
               for k in ("l0", "l1", "l2", "l3"))
    # every gradient fetched is held but the router's, whose sound and
    # bfloat16 readings do not part on the chip (CHECKS)
    assert set(held["grad_rel_err"]) == set(
        load_json(BENCH, "configs", "sdar_30b_a3b.json")["check_params"]) \
        - {"sdar.l1.router"}
    assert max(held["grad_rel_err"].values()) < 1e-4
    assert line["detail"]["loss_last"] < line["detail"]["loss_first"]


@pytest.mark.parametrize("control", CONTROLS, ids=json.dumps)
def test_a_control_comes_out_not_correct(runs, control):
    line = runs[json.dumps(control)]
    held = line["detail"]["reference"]["train"]
    assert line["correct"] is False and held["ok"] is False
    # a fault moves some gradient by a tenth or more; the lower precision
    # moves some gradient by more than its limit
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
    assert data["reduced"] == ["num_hidden_layers: 48 -> 4",
                               "num_experts: 128 -> 16",
                               "vocab_size: 151936 -> 18992"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert data["source"] == entry["source"]
    # every published key under its own name, as published but the cut
    cut = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 18992}
    assert {k: data[k] for k in PUBLISHED} == {**PUBLISHED, **cut}
    # one chip of eight: its 16 experts under their own router, 1/8 of
    # each table
    assert data["expert_parallel_size"] == 8
    assert "router_width" not in data and "router" in data["assumed"]
    assert data["num_experts"] * data["expert_parallel_size"] \
        == PUBLISHED["num_experts"]
    assert data["vocab_size"] * data["expert_parallel_size"] \
        == PUBLISHED["vocab_size"]
    assert (data["seq_len"], data["block_size"], data["noise_grid"],
            data["noise_schedule"]) == (8192, 4, 1024, "linear")
    for key in ("block_size", "noise_schedule", "noise_grid", "mask_id",
                "qk_norm", "aux_loss", "recompute"):
        assert key in data["assumed"], key
    # the cell is listed by the accepted metrics that read what it runs
    listed = {m["name"] for m in load_json(ROOT, "BENCHMARK.json")
              ["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == {
        "block_diffusion_attention_step_ms",
        "block_diffusion_attention_roofline_pct",
        "block_diffusion_attention_routes",
        "moe_step_ms", "moe_experts_roofline_pct", "moe_dropless_routes",
        "loss_from_logits_routes", "recompute_step_ms", "recompute_segments"}


def _entries_one_by_one(t_len, block):
    """The mask's allowed (query, key) entries of one head and sequence,
    counted entry by entry from its four rules."""
    count = 0
    for i in range(2 * t_len):
        for j in range(2 * t_len):
            qb, kb = (i % t_len) // block, (j % t_len) // block
            if i < t_len:
                count += kb == qb if j < t_len else kb < qb
            elif j >= t_len:
                count += kb <= qb
    return count


@pytest.mark.parametrize("t_len,block", [(8, 1), (16, 4), (32, 4),
                                         (32, 16), (64, 8)])
def test_the_attention_work_is_the_mask_entries_counted_one_by_one(t_len,
                                                                   block):
    """``block_diffusion_attention_work`` counts the mask's allowed entries
    (L^2 + L * block a head), 4 * D FLOPs each a query head forward and
    2.5 times that backward, over every layer and sequence."""
    config = _config_module()
    sizes = {**load_json(BENCH, "configs", "sdar_30b_a3b.json"),
             "seq_len": t_len, "block_size": block}
    entries = _entries_one_by_one(t_len, block)
    assert entries == t_len * t_len + t_len * block
    flops, bytes_ = config.block_diffusion_attention_work(sizes, 3 * t_len)
    heads, hd = sizes["num_attention_heads"], sizes["head_dim"]
    assert flops == pytest.approx(4 * 3.5 * 4 * hd * heads * entries * 3)
    assert bytes_ > 0


def test_flops_per_item_is_the_step_the_configuration_describes():
    """One step forward at the cell's sizes is ~12.5 TFLOP (experts 40 %,
    attention 35 %, projections and router 20 %, head 5 %); training is
    three times it."""
    config = _config_module()
    sizes = load_json(BENCH, "configs", "sdar_30b_a3b.json")
    t = sizes["seq_len"]
    forward = config.flops_per_item(sizes, "eval") * t
    assert forward == pytest.approx(12.46e12, rel=0.01)
    attention = config.block_diffusion_attention_work(sizes, t)[0] / 3.5
    assert attention / forward == pytest.approx(0.353, abs=0.01)
    experts = sizes["num_hidden_layers"] * config.moe_experts_work(
        sizes, t)[0] / 3
    assert experts / forward == pytest.approx(0.397, abs=0.01)
    assert config.flops_per_item(sizes, "train") \
        == 3 * config.flops_per_item(sizes, "eval")


def test_the_rehearsal_counts_the_new_routes(rehearsals):
    """The traced rehearsal (counts only on the CPU): the new counter and
    the routes the cell's layers take."""
    code, line, stderr = rehearsals[CELL]
    assert code == 0 and line["correct"] is True, stderr[-2000:]
    assert line["metrics"]["block_diffusion_attention_routes"]["value"] == 4
    routes = line["detail"]["routes"]
    assert routes["route/block_diffusion_attention:xla"] == 4
    for route in ("moe:gated_pair", "moe:dropless", "moe_rows:take"):
        assert routes["route/" + route] == 4, route
    assert "route/moe:share" not in routes
    assert routes["route/recompute:checkpoint"] == 4
    # the formula: the kernels' counters name the mask, never a backward
    assert routes["route/flash_attention:reference"] == 4
    assert routes["route/flash_attention:block_diffusion"] == 4
    assert not any(r.startswith("route/flash_attention_bwd") for r in routes)
