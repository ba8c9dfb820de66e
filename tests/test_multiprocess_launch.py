import pytest
"""2-process jax.distributed test over localhost (reference pattern:
send_recv_op_test.cc — distributed paths exercised in-process over
localhost; SURVEY §4 pattern 3).

Two OS processes jax.distributed.initialize against a local coordinator,
form one 4-device dp mesh (2 virtual CPU devices each), run identical
data-parallel training steps (losses must agree bitwise — GSPMD all-reduce
is doing the sync), then save a dp-sharded checkpoint where each process
writes only its addressable shards, and restore it bitwise through the
multi-process commit protocol in distributed/checkpoint.py."""
import json
import os
import socket
import subprocess
import sys

_WORKER = r'''
import json, os, sys
port, pid, tmpdir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed.launch import init_distributed, process_count
import paddle_tpu as pt
from paddle_tpu.distributed import CheckpointManager

init_distributed(coordinator_address=f"127.0.0.1:{port}",
                 num_processes=2, process_id=pid)
assert process_count() == 2, process_count()
devs = jax.devices()
assert len(devs) == 4, devs          # 2 local per process, 4 global
mesh = Mesh(np.array(devs).reshape(4), ("dp",))
dp = NamedSharding(mesh, P("dp", None))
rep = NamedSharding(mesh, P(None, None))

# global batch 8, each process contributes its local half
true_w = np.arange(4, dtype="float32").reshape(4, 1)
xl = np.random.RandomState(100 + pid).rand(4, 4).astype("float32")
yl = xl @ true_w
gx = jax.make_array_from_process_local_data(dp, xl, (8, 4))
gy = jax.make_array_from_process_local_data(dp, yl, (8, 1))

@jax.jit
def step(w, x, y):
    def loss_fn(w):
        return jnp.mean((x @ w - y) ** 2)
    l, g = jax.value_and_grad(loss_fn)(w)
    return w - 0.1 * g, l

w = jax.device_put(jnp.zeros((4, 1), "float32"), rep)
losses = []
for _ in range(5):
    w, l = step(w, gx, gy)
    losses.append(float(l))

# dp-sharded table: each process owns 2 of the 4 row-shards
table = jax.device_put(jnp.arange(8 * 3, dtype="float32").reshape(8, 3), dp)
scope = pt.Scope()
scope.set("w", w)
scope.set("table", table)
cm = CheckpointManager(tmpdir, async_save=False)
cm.save(1, scope)

def local_view(a):
    """This process's shards only — a global fetch is illegal here."""
    return sorted((str(s.index), np.asarray(s.data).tolist())
                  for s in a.addressable_shards)

w_ref, t_ref = np.asarray(w), local_view(table)
scope.set("w", jax.device_put(jnp.ones_like(w), rep))
scope.set("table", jax.device_put(jnp.zeros_like(table), dp))
got = cm.restore(1, scope=scope)
assert got == 1
assert np.array_equal(np.asarray(scope.get("w")), w_ref)
restored = scope.get("table")
assert not restored.is_fully_replicated        # landed back dp-sharded
assert local_view(restored) == t_ref

print("RESULT " + json.dumps({"pid": pid, "losses": losses,
                              "ndev": len(devs)}))
'''


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.needs_multiprocess_collectives
def test_two_process_distributed_train_and_checkpoint(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    ckpt = tmp_path / "ckpt"
    os.makedirs(ckpt)
    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(port), str(i), str(ckpt)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        for i in range(2)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=300)
        outs.append(out.decode())
    for p, out in zip(procs, outs):
        assert p.returncode == 0, out
    results = {}
    for out in outs:
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
        assert line, out
        r = json.loads(line[-1][len("RESULT "):])
        results[r["pid"]] = r
    assert set(results) == {0, 1}
    # the two processes ran ONE training computation: identical losses
    assert results[0]["losses"] == results[1]["losses"]
    assert results[0]["losses"][-1] < results[0]["losses"][0]
    assert results[0]["ndev"] == 4
    # the checkpoint on disk is the committed multi-process layout:
    # meta.json + per-process shard files for the dp-sharded table
    d = ckpt / "ckpt-1"
    meta = json.loads((d / "meta.json").read_text())
    tinfo = meta["vars"]["table"]
    assert tinfo["shape"] == [8, 3]
    owners = {sh["file"].split(".")[1][:2] for sh in tinfo["shards"]}
    assert owners == {"p0", "p1"}      # both processes wrote shards
