"""Core Program/Executor tests (analog of framework/executor_test,
operator_test.cc)."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers


def test_simple_program_runs():
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.scale(x, scale=2.0, bias=1.0)
    exe = pt.Executor()
    xin = np.arange(8, dtype=np.float32).reshape(2, 4)
    (out,) = exe.run(feed={"x": xin}, fetch_list=[y])
    np.testing.assert_allclose(out, xin * 2 + 1, rtol=1e-6)


def test_fc_forward_matches_numpy(rng):
    x = layers.data("x", shape=[8], dtype="float32")
    out = layers.fc(x, size=3, bias_attr=True)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    xin = rng.randn(5, 8).astype(np.float32)
    (o,) = exe.run(feed={"x": xin}, fetch_list=[out])
    scope = pt.global_scope()
    w_name = [k for k in scope.keys() if k.endswith(".w_0")][0]
    b_name = [k for k in scope.keys() if k.endswith(".b_0")][0]
    w = scope.numpy(w_name)
    b = scope.numpy(b_name)
    np.testing.assert_allclose(o, xin @ w + b, rtol=1e-5, atol=1e-5)


def test_persistable_state_updates():
    c = layers.create_global_var([1], 0.0, "float32", persistable=True,
                                 name="counter")
    layers.increment(c, 1.0)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    for i in range(3):
        exe.run(pt.default_main_program(), fetch_list=[])
    assert float(pt.global_scope().numpy("counter")[0]) == 3.0


def test_backward_computes_gradient(rng):
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.fc(x, size=1, bias_attr=False,
                  param_attr=pt.ParamAttr(name="w_lin"))
    loss = layers.mean(y)
    pt.append_backward(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    xin = rng.randn(6, 4).astype(np.float32)
    (g,) = exe.run(feed={"x": xin}, fetch_list=["w_lin@GRAD"])
    # d mean(x@w) / dw = mean over batch of x
    np.testing.assert_allclose(g.reshape(-1), xin.mean(0), rtol=1e-5,
                               atol=1e-6)


def test_sgd_training_reduces_loss(rng):
    x = layers.data("x", shape=[4], dtype="float32")
    yt = layers.data("yt", shape=[1], dtype="float32")
    pred = layers.fc(x, size=1)
    diff = layers.elementwise_sub(pred, yt)
    loss = layers.mean(layers.square(diff))
    opt = pt.optimizer.SGD(learning_rate=0.1)
    opt.minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program())
    w_true = rng.randn(4, 1).astype(np.float32)
    losses = []
    for i in range(30):
        xin = rng.randn(16, 4).astype(np.float32)
        yin = xin @ w_true
        (l,) = exe.run(feed={"x": xin, "yt": yin}, fetch_list=[loss])
        losses.append(float(l))
    assert losses[-1] < losses[0] * 0.1, losses[:3] + losses[-3:]


def test_program_clone_and_prune():
    x = layers.data("x", shape=[4], dtype="float32")
    h = layers.fc(x, size=3, act="relu")
    out = layers.fc(h, size=2)
    loss = layers.mean(out)
    pt.append_backward(loss)
    pt.optimizer.SGD(0.1).apply_gradients(
        [(p, pt.default_main_program().global_block().var(p.name + "@GRAD"))
         for p in pt.default_main_program().all_parameters()])
    inf = pt.default_main_program().prune([out])
    types = [op.type for op in inf.global_block().ops]
    assert "backward" not in types
    assert "sgd" not in types
    assert "mul" in types


def test_executor_nan_check():
    x = layers.data("x", shape=[2], dtype="float32")
    y = layers.log(x)
    exe = pt.Executor(check_nan_inf=True)
    with pytest.raises(FloatingPointError):
        exe.run(feed={"x": np.array([[-1.0, 1.0]], np.float32)},
                fetch_list=[y])


def test_program_serialization_roundtrip():
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.fc(x, size=2, bias_attr=True)
    prog = pt.default_main_program()
    restored = pt.Program.from_json(prog.to_json())
    assert [op.type for op in restored.global_block().ops] == \
        [op.type for op in prog.global_block().ops]


def test_while_loop_runs_and_terminates():
    """Regression: body writes must update the lax.while_loop carry."""
    i = layers.fill_constant([1], "int64", 0)
    limit = layers.fill_constant([1], "int64", 5)
    total = layers.fill_constant([1], "float32", 0.0)
    cond = layers.less_than(i, limit)
    w = layers.While(cond)
    with w.block():
        layers.sums([total, layers.ones([1], "float32")], out=total)
        layers.increment(i, 1.0)
        layers.less_than(i, limit, cond=cond)
    exe = pt.Executor()
    out, iv = exe.run(fetch_list=[total, i])
    assert float(out[0]) == 5.0
    assert int(iv[0]) == 5


def test_fc_has_bias_by_default():
    x = layers.data("x", shape=[4], dtype="float32")
    layers.fc(x, size=3)
    names = [p.name for p in pt.default_main_program().all_parameters()]
    assert any(".b_" in n for n in names), names


def test_program_roundtrip_keeps_parameters():
    x = layers.data("x", shape=[4], dtype="float32")
    y = layers.fc(x, size=2)
    prog = pt.default_main_program()
    restored = pt.Program.from_json(prog.to_json())
    assert len(restored.all_parameters()) == len(prog.all_parameters()) > 0


def test_check_nan_inf_localizes_producing_op(rng):
    """check_nan_inf names the op/var that FIRST produced the NaN (the
    executor.cc:116-124 per-op check), not just a fetched output."""
    import pytest
    x = layers.data("x", shape=[4], dtype="float32")
    h = layers.log(x)                  # NaN for negative input
    out = layers.reduce_sum(layers.exp(h))
    exe = pt.Executor(check_nan_inf=True)
    # clean input passes
    good = exe.run(pt.default_main_program(),
                   feed={"x": np.ones((2, 4), "float32")},
                   fetch_list=[out])
    assert np.isfinite(good[0]).all()
    with pytest.raises(FloatingPointError) as ei:
        exe.run(pt.default_main_program(),
                feed={"x": -np.ones((2, 4), "float32")},
                fetch_list=[out])
    msg = str(ei.value)
    assert "log" in msg                # the producing op, not the fetch
    assert "first produced" in msg


def test_trace_error_names_offending_op():
    """A trace-time shape error carries the op type and input shapes
    (PADDLE_ENFORCE context, enforce.h analog)."""
    import pytest
    a = layers.data("a", shape=[4], dtype="float32")
    b = layers.data("b", shape=[5], dtype="float32")
    bad = layers.elementwise_add(a, b)      # 4 vs 5: trace-time error
    exe = pt.Executor()
    with pytest.raises(Exception) as ei:
        exe.run(pt.default_main_program(),
                feed={"a": np.ones((2, 4), "float32"),
                      "b": np.ones((2, 5), "float32")},
                fetch_list=[bad])
    notes = getattr(ei.value, "__notes__", [])
    assert any("elementwise_add" in n for n in notes), notes


def test_weight_norm_param_attr(rng):
    """WeightNormParamAttr reparameterizes w = g * v/||v|| (per output
    column) and trains both pieces — the direction stays unit-norm in
    effect because g carries the magnitude."""
    import pytest
    from paddle_tpu.param_attr import WeightNormParamAttr

    x = layers.data("x", shape=[6], dtype="float32")
    t = layers.data("t", shape=[1], dtype="float32")
    y = layers.fc(x, size=1, bias_attr=False,
                  param_attr=WeightNormParamAttr(dim=1, name="wn"))
    loss = layers.mean(layers.square_error_cost(y, t))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    assert pt.global_scope().has("wn") and pt.global_scope().has("wn.g")
    feeds = {"x": rng.rand(8, 6).astype("float32"),
             "t": rng.rand(8, 1).astype("float32")}
    vals = [float(exe.run(pt.default_main_program(), feed=feeds,
                          fetch_list=[loss])[0]) for _ in range(10)]
    assert np.isfinite(vals).all() and vals[-1] < vals[0]
    # the effective weight equals g * v/||v||
    v = np.asarray(pt.global_scope().get("wn"))
    g = np.asarray(pt.global_scope().get("wn.g"))
    yv, = exe.run(pt.default_main_program(), feed=feeds, fetch_list=[y],
                  is_test=True)
    w_eff = g * v / np.linalg.norm(v, axis=0, keepdims=True)
    np.testing.assert_allclose(yv, feeds["x"] @ w_eff, rtol=1e-4,
                               atol=1e-5)


def test_run_steps_matches_per_step_run(rng):
    """run_steps(K) (one lax.scan dispatch, donated state) reproduces K
    sequential run() calls bitwise-closely, and feeds_stacked threads a
    different batch per step."""
    import paddle_tpu as pt
    from paddle_tpu import layers

    true_w = np.array([[1.0], [2.0], [-1.0], [0.5]], "float32")
    xb = rng.rand(8, 4).astype("float32")
    yb = xb @ true_w

    def build():
        pt.core.reset_default_programs()
        pt.core.reset_global_scope()
        pt.unique_name.reset()
        x = layers.data("x", shape=[4], dtype="float32")
        y = layers.data("y", shape=[1], dtype="float32")
        pred = layers.fc(x, size=1, name="w")
        loss = layers.mean(layers.square_error_cost(pred, y))
        pt.optimizer.Adam(0.1).minimize(loss)
        return loss

    loss = build()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    seq = [float(exe.run(feed={"x": xb, "y": yb}, fetch_list=[loss])[0])
           for _ in range(6)]
    w_seq = np.asarray(pt.global_scope().get("w.w_0")).copy()

    loss = build()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    (stacked,) = exe.run_steps(6, feed={"x": xb, "y": yb},
                               fetch_list=[loss])
    np.testing.assert_allclose(stacked.reshape(-1), seq, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pt.global_scope().get("w.w_0")),
                               w_seq, rtol=1e-5)

    xs = rng.rand(3, 8, 4).astype("float32")
    ys = np.einsum("kbd,dj->kbj", xs, true_w)
    (st2,) = exe.run_steps(3, feed={"x": xs, "y": ys}, fetch_list=[loss],
                           feeds_stacked=True)
    assert st2.shape[0] == 3 and np.isfinite(st2).all()
    with pytest.raises(ValueError):
        exe.run_steps(3, feed={"x": xb, "y": yb}, fetch_list=[loss],
                      feeds_stacked=True)      # missing leading K axis


def _removed(what):
    from paddle_tpu import flags
    from paddle_tpu.parallel import ShardedExecutor, mesh_for_axes
    if what == "flag":
        return flags.get_flag("conv1x1_pallas")
    if what == "sharded_auto_layout":
        return ShardedExecutor(mesh=mesh_for_axes({"dp": 2}),
                               auto_layout=True)
    return pt.Executor(**{what: True})


@pytest.mark.parametrize("what,error", [
    ("auto_layout", TypeError), ("sharded_auto_layout", TypeError),
    ("conv1x1_pallas", TypeError), ("flag", KeyError)])
def test_removed_executor_forks_are_refused(what, error):
    """The two forks that never won on the chip are gone, not ignored: an
    executor given either option raises like any unknown keyword (a
    ShardedExecutor used to accept auto_layout and never look at it), and
    the process flag no longer exists."""
    with pytest.raises(error):
        _removed(what)


def _prologue_net(kind):
    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.dropout(layers.fc(x, size=16, act="relu"), dropout_prob=0.3)
    pred = layers.fc(h, size=3, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, y))
    if kind != "amp_inference":
        pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    return loss


@pytest.mark.parametrize("kind", ["f32_train", "amp_inference", "amp_train"])
def test_nanprov_replay_starts_from_the_executors_prologue(rng, kind,
                                                           monkeypatch):
    """The NaN bisect's eager replay (observability.nanprov) and the
    executor start one step from the same env: every entry at the dtype
    the ``use_jit=False`` executor gives it, and the same first-step loss
    (dropout mask included: the same PRNG key)."""
    from paddle_tpu.core import executor as ex
    from paddle_tpu.observability import nanprov
    loss = _prologue_net(kind)
    prog = pt.default_main_program()
    exe = pt.Executor(use_jit=False, amp=kind != "f32_train")
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    scope = pt.global_scope()
    state0 = {k: scope.get(k) for k in exe._state_keys(prog, scope)}
    feed = {"x": rng.rand(4, 8).astype("float64"),     # coerced to f32
            "y": rng.randint(0, 3, (4, 1))}
    is_test = kind == "amp_inference"

    seen = []
    real = ex.interpret_block_with_backward

    def spy(block, env, ctx):
        seen.append({k: str(v.dtype) for k, v in env.local.items()})
        return real(block, env, ctx)
    monkeypatch.setattr(ex, "interpret_block_with_backward", spy)
    step = exe._step
    (want,) = exe.run(feed=feed, fetch_list=[loss], is_test=is_test)
    monkeypatch.undo()
    (executors_env,) = seen

    feeds = exe._coerce_feeds(prog, feed, None, False)
    env, ctx, bw_idx = nanprov.make_eager_context(exe, prog, feeds, state0,
                                                  step, is_test)
    assert {k: str(v.dtype) for k, v in env.local.items()} == executors_env
    assert executors_env["x"] == ("bfloat16" if is_test else "float32")
    assert (bw_idx is None) == is_test
    real(prog.global_block(), env, ctx)
    np.testing.assert_array_equal(np.asarray(env.get(loss.name)), want)


def test_traced_fn_outlives_its_executor():
    """``_make_fn`` snapshots the executor's options: the fn it returns
    holds no executor, so it still traces after its executor is gone
    (``__graft_entry__.entry`` returns a closure over it alone and the
    driver lowers that; ``export_model`` does the same)."""
    import gc
    import weakref

    import jax
    x = layers.data("x", shape=[8], dtype="float32")
    pred = layers.fc(x, size=3, act="softmax")
    prog = pt.default_main_program()
    exe = pt.Executor(use_jit=False, amp=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    scope = pt.global_scope()
    state = {k: scope.get(k) for k in exe._state_keys(prog, scope)}
    fn = exe._make_fn(prog, [pred.name], is_test=True)
    gone = weakref.ref(exe)
    del exe
    gc.collect()
    assert gone() is None
    lowered = jax.jit(fn).lower({"x": np.zeros((4, 8), np.float32)}, state, 0)
    assert "bf16" in lowered.as_text()       # amp=True rode the snapshot
