"""chip_smoke.py / bench.py start-up contract, checked on the CPU.

* the smoke's explicit CPU pre-flight passes end to end (tiny sizes, Pallas
  interpreted) — the control flow the chip run will take;
* without the explicit request a CPU backend is refused: non-zero exit, the
  platform named, no result line (a CPU run can never look like a chip run);
* bench.py refuses the same way;
* importing the package, the CLI, the serving runtime or the supervisor
  initialises no JAX backend — a parent that imports them does not take the
  chip from the one process that needs it.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env_over):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **env_over)
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.timeout(600)
def test_chip_smoke_cpu_preflight_passes():
    r = _run(["chip_smoke.py", "--cpu-preflight"])
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = [json.loads(ln) for ln in r.stdout.strip().splitlines()]
    legs = [ln["leg"] for ln in lines[:-1]]
    assert legs == ["env", "resnet50_train", "serve_resnet50",
                    "flash_attention_train"]
    for ln in lines[:-1]:
        assert ln["platform"] == "cpu" and ln["device_count"] == 1
        assert "compile_s" in ln and "peak_bytes_in_use" in ln
    assert lines[-1] == {"ok": True, "preflight": True,
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}
    # the Pallas kernels ran (interpreted) — not the jnp reference
    assert lines[3]["routes"] == {"interpret": 3}


def test_chip_smoke_refuses_cpu_without_explicit_request():
    r = _run(["chip_smoke.py"])
    assert r.returncode not in (0, None)
    assert "'cpu'" in r.stderr and "needs a TPU" in r.stderr
    assert r.stdout.strip() == ""           # no result line of any kind


def test_bench_refuses_cpu():
    for args in (["bench.py"], ["bench.py", "--mesh", "dp=2"]):
        r = _run(args)
        assert r.returncode not in (0, None), args
        assert "'cpu'" in r.stderr and "needs a TPU" in r.stderr
        assert r.stdout.strip() == ""


def test_imports_initialise_no_backend():
    code = (
        "import paddle_tpu, paddle_tpu.cli, paddle_tpu.serving, "
        "paddle_tpu.distributed.supervisor\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n")
    r = _run(["-c", code])
    assert r.returncode == 0, r.stderr[-3000:]
