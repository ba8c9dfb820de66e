"""The command's contract, on the CPU: every cell's ``--rehearse`` run,
the refusal to measure without a chip, and the references against the
system at a tiny size in float32."""
import os
import shutil

import pytest

from conftest import BENCH, ROOT, all_cells, load_json, run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", all_cells())
def test_rehearsal_meets_the_contract(rehearsals, cell):
    code, line, stderr = rehearsals[cell]
    assert code == 0, stderr[-2000:]
    assert KEYS <= set(line) and "breakdown" not in line
    chips = load_json(BENCH, "workloads", f"{cell}.json")["chips"]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "busy_s" not in line["device"]           # no device, no trace
    assert line["correct"] is True, line["detail"]
    # (a served request may miss its 100 ms deadline on a loaded CPU: that
    # is counted, and is not what a rehearsal is about)
    assert 0 <= line["failed"] < line["attempted"]
    if "reference" in line["detail"]:
        assert line["failed"] == 0
    # a CPU run gives counts only: no time, rate or share
    assert line["metrics"], "a rehearsal still reports its counts"
    for name, m in line["metrics"].items():
        assert m["unit"] == "count" and float(m["value"]).is_integer(), name
    assert line["metrics"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("cell", all_cells())
def test_reference_agrees_with_the_system_in_float32(rehearsals, cell):
    """The rehearsal computes in float32 at a tiny size, where the plain
    reference and the program have to agree far inside the chip's
    tolerances: same mathematics, other code."""
    detail = rehearsals[cell][1]["detail"]
    if "reference" in detail:                       # a training cell
        for name, r in detail["reference"].items():
            assert r["loss_rel_err"] < 2e-4, (name, r)
            assert all(e < 2e-3 for e in r.get("grad_rel_err", {}).values()
                       ), (name, r)
    else:                                           # a serving cell
        assert detail["logp_err"] < 1e-4 and detail["wrong"] == 0


def test_no_chip_no_number():
    proc = run_cell(["--workload", all_cells()[0], "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    stdout, stderr = proc.communicate(timeout=300)
    assert proc.returncode != 0
    assert stdout.strip() == "" and "needs a TPU" in stderr


def test_unknown_cell_and_bare_directory_are_refused(tmp_path):
    proc = run_cell(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    stdout, stderr = proc.communicate(timeout=120)
    assert proc.returncode != 0 and stdout.strip() == ""
    # a directory that holds only BENCHMARK.json and the benchmark's paths
    bare = tmp_path / "bare"
    shutil.copytree(BENCH, bare / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), bare)
    proc = run_cell(["--workload", all_cells()[0], "--seed", "1",
                     "--seconds", "1", "--trace", "0", "--rehearse"],
                    cwd=str(bare))
    stdout, stderr = proc.communicate(timeout=120)
    assert proc.returncode != 0 and stdout.strip() == ""
    assert "paddle_tpu" in stderr
