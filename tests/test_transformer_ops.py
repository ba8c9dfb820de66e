"""``rms_norm`` and ``rope`` (ops/nn_ops.py) against their equations: values
and gradients through the Program path, their shape rules, rope's
relative-position property, and ``rms_norm``'s row-wise rule moving it out
of an ``rnn`` step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.analysis.shape_infer import ShapeError, VarInfo
from paddle_tpu.core.registry import get_rowwise_fn, get_shape_fn
from paddle_tpu.layer_helper import LayerHelper


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(u, theta):
    """The equation, pair by pair: features (i, i + d/2) of position t turn
    by the angle t * theta^(-2i/d)."""
    t_len, d = u.shape[1], u.shape[3]
    out = np.zeros(u.shape, np.float64)
    for t in range(t_len):
        for i in range(d // 2):
            a = t * float(theta) ** (-2.0 * i / d)
            lo, hi = u[:, t, :, i], u[:, t, :, i + d // 2]
            out[:, t, :, i] = lo * np.cos(a) - hi * np.sin(a)
            out[:, t, :, i + d // 2] = hi * np.cos(a) + lo * np.sin(a)
    return out


def _run(build, feed, wrt):
    """(output, d mean(output * mix) / d wrt) through Program + Executor."""
    out, mix = build()
    loss = layers.mean(layers.elementwise_mul(out, mix))
    pt.optimizer.SGD(0.0).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    return exe.run(feed=feed, fetch_list=[out, f"{wrt}@GRAD"])


def test_rms_norm_values_and_gradients():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 8).astype("float32")
    mix = rng.randn(3, 5, 8).astype("float32")
    scale = rng.rand(8).astype("float32") + 0.5

    def build():
        xv = layers.data("x", shape=[5, 8], dtype="float32")
        xv.stop_gradient = False
        y = layers.rms_norm(xv, epsilon=1e-3, param_attr=pt.ParamAttr(
            name="g", initializer=pt.initializer.NumpyArrayInitializer(scale)))
        return y, layers.data("mix", shape=[5, 8], dtype="float32")

    got, g_scale = _run(build, {"x": x, "mix": mix}, "g")
    np.testing.assert_allclose(got, _rms(x, scale, 1e-3), rtol=1e-5,
                               atol=1e-6)
    ref = jax.grad(lambda g: jnp.mean(_rms(x, g, 1e-3) * mix))(
        jnp.asarray(scale))
    np.testing.assert_allclose(g_scale, ref, rtol=1e-4, atol=1e-7)


def test_rms_norm_scale_starts_at_one():
    x = layers.data("x", shape=[4], dtype="float32")
    layers.rms_norm(x, param_attr=pt.ParamAttr(name="g"))
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    np.testing.assert_array_equal(np.asarray(pt.global_scope().get("g")),
                                  np.ones(4, "float32"))


@pytest.mark.parametrize("t_len", [1, 7, 16])
def test_rope_values_and_gradients(t_len):
    rng = np.random.RandomState(t_len)
    x = rng.randn(2, t_len, 3, 8).astype("float32")
    mix = rng.randn(2, t_len, 3, 8).astype("float32")

    def build():
        # (a weight in front, so that there is a gradient to fetch)
        xv = layers.data("x", shape=[t_len, 3, 8], dtype="float32")
        w = LayerHelper("w").create_parameter(
            pt.ParamAttr(name="w",
                         initializer=pt.initializer.ConstantInitializer(1.0)),
            shape=[8], dtype="float32")
        y = layers.rope(layers.elementwise_mul(xv, w), theta=50.0)
        return y, layers.data("mix", shape=[t_len, 3, 8], dtype="float32")

    got, g_w = _run(build, {"x": x, "mix": mix}, "w")
    np.testing.assert_allclose(got, _rope(x, 50.0), rtol=1e-5, atol=1e-5)
    if t_len == 1:                       # position 0 turns nothing
        np.testing.assert_allclose(got, x, rtol=1e-6)
    # d/dw mean(rope(x * w) * mix): rope is linear, so rope(x e_i) * mix
    ref = [np.mean(_rope(x * np.eye(8)[i], 50.0) * mix) for i in range(8)]
    np.testing.assert_allclose(g_w, ref, rtol=1e-4, atol=1e-6)


def test_rope_scores_depend_on_the_distance_only():
    """<rope(q, s), rope(k, t)> is a function of s - t: the same q and k
    vectors at every position give a Toeplitz score matrix."""
    rng = np.random.RandomState(3)
    q, k = rng.randn(8).astype("float32"), rng.randn(8).astype("float32")
    t_len = 12
    x = layers.data("x", shape=[t_len, 1, 8], dtype="float32")
    y = layers.rope(x, theta=100.0)
    exe = pt.Executor()
    turned = exe.run(feed={"x": np.stack([np.tile(q, (t_len, 1, 1)),
                                          np.tile(k, (t_len, 1, 1))])},
                     fetch_list=[y])[0]
    scores = turned[0, :, 0] @ turned[1, :, 0].T          # [s, t]
    for offset in range(-t_len + 1, t_len):
        diagonal = np.diagonal(scores, offset)
        np.testing.assert_allclose(diagonal, diagonal[0], rtol=1e-4,
                                   atol=1e-5)
    assert np.ptp(scores) > 0.1


def test_shape_rules():
    rms, rope = get_shape_fn("rms_norm"), get_shape_fn("rope")
    x = VarInfo((4, 6, 8), "float32")
    assert rms(None, {"X": [x], "Scale": [VarInfo((8,), "float32")]},
               {})["Y"] == x
    with pytest.raises(ShapeError, match="Scale size"):
        rms(None, {"X": [x], "Scale": [VarInfo((6,), "float32")]}, {})
    x4 = VarInfo((-1, 6, 2, 8), "float32")
    assert rope(None, {"X": [x4]}, {})["Out"] == x4
    with pytest.raises(ShapeError, match="even D"):
        rope(None, {"X": [x]}, {})
    with pytest.raises(ShapeError, match="even D"):
        rope(None, {"X": [VarInfo((2, 6, 2, 7), "float32")]}, {})


def test_rms_norm_is_row_wise_and_leaves_an_rnn_step_and_rope_is_not():
    assert get_rowwise_fn("rope") is None
    seq = layers.data("seq", shape=[4], dtype="float32", lod_level=1)
    rnn = layers.control_flow.StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(seq)
        acc = rnn.memory(shape=[4])
        new = layers.elementwise_add(acc, layers.fc(x_t, size=4))
        rnn.update_memory(acc, new)
        rnn.step_output(layers.rms_norm(new))
    out = rnn()
    feed = {"seq": np.random.RandomState(0).rand(2, 3, 4).astype("float32"),
            "seq@LEN": np.array([3, 3])}
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    before = profiler.compile_stats().snapshot().get("rnn_ops_hoisted", 0)
    got = exe.run(feed=feed, fetch_list=[out])[0]
    assert profiler.compile_stats().snapshot()["rnn_ops_hoisted"] - before \
        == 1
    # the values are those of the step-by-step recurrence
    w = np.asarray(pt.global_scope().get(
        next(p.name for p in pt.default_main_program().global_block()
             .all_parameters() if tuple(p.shape) == (4, 4))))
    b = [np.asarray(pt.global_scope().get(p.name))
         for p in pt.default_main_program().global_block().all_parameters()
         if tuple(p.shape) in ((4,), (1, 4)) and "rms" not in p.name]
    state = np.zeros((2, 4), "float32")
    for t in range(3):
        state = state + feed["seq"][:, t] @ w + (b[0].reshape(-1) if b else 0)
        np.testing.assert_allclose(
            got[:, t], _rms(state, 1.0, 1e-5), rtol=1e-5, atol=1e-6)
