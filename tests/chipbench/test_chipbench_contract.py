"""BENCHMARK.json against the contract it has to meet, and every name in
it (and in candidates.json) against the files the harness finds by name."""
import os
import re

import pytest

from conftest import BENCH, ROOT, all_cells, load_json

from chipbench.lib.contract import reduced_problems

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCHMARK = load_json(ROOT, "BENCHMARK.json")
CANDIDATES = load_json(BENCH, "candidates.json")


def _merged(key):
    return BENCHMARK[key] + CANDIDATES.get(key, [])


def test_keys_and_limits():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(BENCHMARK["paths"]) <= 16
    for p in BENCHMARK["paths"]:
        assert PLAIN_PATH.match(p) and len(p) <= 200 and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = BENCHMARK["command"]
    assert 1 <= len(cmd) <= 32 and all(isinstance(c, str) for c in cmd)
    for c in cmd:
        assert not c.startswith("/") and ".." not in c
        if os.path.exists(os.path.join(ROOT, c)):
            assert any(c.startswith(p + "/") for p in BENCHMARK["paths"])
    assert isinstance(BENCHMARK["run_seconds"], int)
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    # a full check with the full 24 cells fits into the driver's 43200 s
    n, s = 24, BENCHMARK["run_seconds"]
    assert (2 + 14 * n) * (s + 60) + n * 2 * 90 + 1200 <= 43200
    assert 1 <= len(BENCHMARK["configs"]) <= 24
    assert 2 <= len(BENCHMARK["workloads"]) <= 24
    assert 1 <= len(BENCHMARK["end_to_end"]) <= 16
    assert 1 <= len(BENCHMARK["per_layer"]) <= 128


def test_names_are_well_formed_and_used_once():
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in _merged(key)
             if key != "configs" or e in BENCHMARK["configs"]]
    for n in names:
        assert NAME.match(n), n
    assert len(names) == len(set(names))
    for key in ("configs", "workloads"):
        for e in _merged(key):
            assert len(e["why"]) <= 200, (e["name"], len(e["why"]))


def test_configs_resolve():
    used = {w["config"] for w in BENCHMARK["workloads"]}
    files = set()
    for c in BENCHMARK["configs"]:
        assert c["name"] in used, f"configuration {c['name']} has no cell"
        assert c["source"].startswith("http")
        assert any(c["file"].startswith(p + "/") for p in BENCHMARK["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        data = load_json(ROOT, c["file"])
        assert data["name"] == c["name"]
        assert data["source"] == c["source"]
        assert reduced_problems(c, data) == []
        assert os.path.isfile(os.path.join(
            BENCH, "configs", f"{c['name']}.py"))


@pytest.mark.parametrize("cell", all_cells())
def test_cell_resolves(cell):
    entry = [w for w in _merged("workloads") if w["name"] == cell]
    assert len(entry) == 1, f"{cell} has a file and no registry entry"
    entry = entry[0]
    data = load_json(BENCH, "workloads", f"{cell}.json")
    for key in ("name", "config", "traffic", "chips", "why"):
        assert data[key] == entry[key], key
    assert entry["chips"] in (1, 4)
    assert os.path.isfile(os.path.join(BENCH, "configs",
                                       f"{data['config']}.json"))
    assert os.path.isfile(os.path.join(BENCH, "drivers",
                                       f"{data['driver']}.py"))
    assert "rehearse" in data


def test_every_registered_cell_has_a_file_and_pairs_are_unique():
    assert sorted(w["name"] for w in _merged("workloads")) == all_cells()
    pairs = [(w["config"], w["traffic"]) for w in _merged("workloads")]
    assert len(pairs) == len(set(pairs))
    four = [w for w in BENCHMARK["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCHMARK["workloads"]) // 4)


def test_metrics_resolve_and_every_cell_is_covered():
    from chipbench import run as chipbench_run

    e2e = {m["name"]: m for m in _merged("end_to_end")}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in _merged("end_to_end"):
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("higher", "lower")
        assert os.path.isfile(os.path.join(BENCH, "end_to_end",
                                           f"{m['name']}.py"))
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
    for m in _merged("per_layer"):
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert LAYER.match(m["layer"]), (m["name"], m["layer"])
        assert "bound" not in m
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           f"{m['name']}.py"))
    cells = {w["name"] for w in _merged("workloads")}
    for key in ("end_to_end", "per_layer"):
        for m in _merged(key):
            assert set(m.get("workloads", ())) <= cells, m["name"]
    for cell in cells:
        ends = {m["name"] for m in chipbench_run.metrics_for(
            cell, _merged("end_to_end"))}
        assert "setup_s" in ends and len(ends) >= 2, cell
        layers = chipbench_run.metrics_for(cell, _merged("per_layer"))
        assert layers, cell
        for m in layers:             # reported only where what it moves is
            assert m["moves"] in ends, (cell, m["name"])


def test_no_workload_names_in_code():
    """The harness is driven by data: no file of code names a cell."""
    cells = all_cells()
    for dirpath, _, files in os.walk(BENCH):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    text = fh.read()
                for cell in cells:
                    assert cell not in text, (f, cell)
