"""Shared by the chipbench tests: the repo's paths, the registries, and
ONE set of ``--rehearse`` runs (every cell, in parallel, once a session)
that several tests read."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def all_cells():
    """Names of every cell that has a file: those of BENCHMARK.json and
    those kept for later in candidates.json."""
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads"))
                  if f.endswith(".json"))


def run_cell(args, env_extra=None, cwd=ROOT):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.Popen(
        [sys.executable, os.path.join(cwd, "chipbench", "run.py")] + args,
        cwd=cwd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True)


@pytest.fixture(scope="session")
def rehearsals():
    """{cell: (returncode, last stdout line parsed, stderr)} of
    ``--rehearse --trace 1`` for every cell, run side by side."""
    procs = {}
    for cell in all_cells():
        chips = load_json(BENCH, "workloads", f"{cell}.json")["chips"]
        extra = {"XLA_FLAGS":
                 f"--xla_force_host_platform_device_count={chips}"} \
            if chips > 1 else {}
        procs[cell] = run_cell(
            ["--workload", cell, "--seed", "7", "--seconds", "2",
             "--trace", "1", "--rehearse"], extra)
    out = {}
    for cell, proc in procs.items():
        try:
            stdout, stderr = proc.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            proc.kill()
            stdout, stderr = proc.communicate()
        lines = [ln for ln in stdout.splitlines() if ln.strip()]
        try:
            last = json.loads(lines[-1]) if lines else None
        except ValueError:
            last = None
        out[cell] = (proc.returncode, last, stderr)
    return out
