"""Pipeline and MoE as FIRST-CLASS framework features: declared in the
Paddle-style Program API (pt.pipeline_stage / layers.moe), lowered by
ShardedExecutor onto the pp/ep mesh axes, numerically equal to the
single-device run (the reference's test_CompareTwoNets strategy applied
to the pipeline — cf. ParallelNeuralNetwork.cpp whole-layer placement)."""
import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.parallel import MeshConfig, ShardedExecutor, make_mesh

WIDTH = 16


def _staged_mlp(n_stages, rng, batch=16):
    x = layers.data("x", shape=[WIDTH], dtype="float32")
    y = layers.data("y", shape=[WIDTH], dtype="float32")
    h = x
    for i in range(n_stages):
        with pt.pipeline_stage(i):
            h = layers.fc(h, size=WIDTH, act="tanh")
    loss = layers.mean(layers.square_error_cost(h, y))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    feeds = {"x": rng.randn(batch, WIDTH).astype("float32"),
             "y": rng.randn(batch, WIDTH).astype("float32")}
    return loss, feeds


def _train(exe, prog, feeds, loss, steps=3):
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    exe._step = 0
    return [float(exe.run(prog, feed=feeds, fetch_list=[loss])[0])
            for _ in range(steps)]


@pytest.mark.parametrize("mesh_cfg,microbatches", [
    (MeshConfig(pp=4), None),       # pure pipeline, M = S
    (MeshConfig(pp=4), 8),          # more microbatches than stages
    (MeshConfig(dp=2, pp=4), None),  # dp x pp composition
])
def test_pipeline_training_matches_single_device(rng, mesh_cfg, microbatches):
    """A pipeline_stage-annotated program trained through ShardedExecutor
    over pp (and dp x pp) must track the plain single-device Executor,
    which simply ignores the stage attrs."""
    loss, feeds = _staged_mlp(4, rng)
    prog = pt.default_main_program()

    single = _train(pt.Executor(), prog, feeds, loss)

    pt.core.reset_global_scope()
    mesh = make_mesh(mesh_cfg, devices=jax.devices()[:mesh_cfg.size])
    exe = ShardedExecutor(mesh=mesh, num_microbatches=microbatches)
    multi = _train(exe, prog, feeds, loss)

    assert single[-1] < single[0]          # it actually trains
    np.testing.assert_allclose(single, multi, rtol=2e-4, atol=1e-5)


def test_pipeline_stage_attrs_on_ops(rng):
    _staged_mlp(2, rng)
    staged = [op.attrs.get("pipeline_stage")
              for op in pt.default_main_program().global_block().ops
              if "pipeline_stage" in op.attrs]
    assert set(staged) == {0, 1}
    # startup initializer ops must NOT carry the attr
    for op in pt.default_startup_program().global_block().ops:
        assert "pipeline_stage" not in op.attrs


def test_pipeline_stage_count_mismatch_errors(rng):
    loss, feeds = _staged_mlp(2, rng)          # 2 stages declared
    mesh = make_mesh(MeshConfig(pp=4), devices=jax.devices()[:4])
    exe = ShardedExecutor(mesh=mesh)
    with pytest.raises(Exception, match="pipeline stages"):
        _train(exe, pt.default_main_program(), feeds, loss, steps=1)


def _moe_program(rng, batch=32, experts=8, hidden=32):
    x = layers.data("x", shape=[WIDTH], dtype="float32")
    y = layers.data("y", shape=[WIDTH], dtype="float32")
    out, aux, _ = layers.moe(x, num_experts=experts, expert_hidden=hidden,
                             top_k=2, capacity_factor=4.0)
    loss = layers.mean(layers.square_error_cost(out, y))
    total = layers.elementwise_add(
        loss, layers.scale(aux, scale=0.01))
    pt.optimizer.SGD(learning_rate=0.05).minimize(total)
    feeds = {"x": rng.randn(batch, WIDTH).astype("float32"),
             "y": rng.randn(batch, WIDTH).astype("float32")}
    return total, feeds


def test_moe_training_matches_single_device(rng):
    """layers.moe trained through ShardedExecutor over ep=8 (expert
    weights sharded P('ep',...), GSPMD all-to-all) must track the plain
    single-device Executor."""
    total, feeds = _moe_program(rng)
    prog = pt.default_main_program()

    single = _train(pt.Executor(), prog, feeds, total)

    pt.core.reset_global_scope()
    mesh = make_mesh(MeshConfig(ep=8))
    exe = ShardedExecutor(mesh=mesh)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    exe.place_state(prog)
    exe._step = 0
    multi = [float(exe.run(prog, feed=feeds, fetch_list=[total])[0])
             for _ in range(3)]

    assert single[-1] < single[0]
    np.testing.assert_allclose(single, multi, rtol=2e-4, atol=1e-5)
    # the expert weights really are distributed over the ep axis
    w1 = next(k for k in pt.global_scope().keys() if "moe" in k and
              pt.global_scope().get(k).ndim == 3)
    assert not pt.global_scope().get(w1).sharding.is_fully_replicated
