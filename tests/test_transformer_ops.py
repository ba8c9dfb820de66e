"""``rms_norm`` and ``rope`` (ops/nn_ops.py) against their equations: values
and gradients through the Program path, their shape rules, rope's
relative-position property, ``rope``'s kernel (interpreted) against the
formula it replaces on the TPU and the shapes each route takes, and
``rms_norm``'s row-wise rule moving it out of an ``rnn`` step."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.analysis.shape_infer import ShapeError, VarInfo
from paddle_tpu.core.registry import get_rowwise_fn, get_shape_fn
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import pallas_kernels


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


def _rope_tables(t_len, dim, theta):
    inv_freq = float(theta) ** (
        -2.0 * jnp.arange(dim // 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(t_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _rope_formula(x, theta):
    """The lowering as it stood before the kernel (the kernel's oracle):
    out = x * cos + concat(-x[D/2:], x[:D/2]) * sin."""
    half = x.shape[3] // 2
    cos, sin = _rope_tables(x.shape[1], x.shape[3], theta)
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + turned * sin


def _rope_routes():
    return {k.split(":", 1)[1]: v for k, v in
            profiler.compile_stats().snapshot().items()
            if k.startswith("route/rope:")}


def _rope_program(monkeypatch, shape, theta, seed=0):
    """(out, dX of mean(out * mix), x, mix, routes taken) of a ``rope`` op
    through Program + Executor, with the kernel interpreted wherever its
    shapes are eligible (the test steers the route; the program has no
    option for it)."""
    monkeypatch.setattr(pallas_kernels, "rope_route", functools.partial(
        pallas_kernels.rope_route, interpret=True))
    rng = np.random.RandomState(seed)
    x = rng.randn(*shape).astype("float32")
    mix = rng.randn(*shape).astype("float32")

    def build():
        # (X is a parameter, so that its gradient can be fetched)
        xv = LayerHelper("x").create_parameter(
            pt.ParamAttr(name="x",
                         initializer=pt.initializer.NumpyArrayInitializer(x)),
            shape=list(shape), dtype="float32")
        return layers.rope(xv, theta=theta), layers.data(
            "mix", shape=list(shape[1:]), dtype="float32")

    before = _rope_routes()
    out, d_x = _run(build, {"mix": mix}, "x")
    taken = {k: v - before.get(k, 0) for k, v in _rope_routes().items()
             if v - before.get(k, 0)}
    return out, d_x, x, mix, taken


@pytest.mark.parametrize("shape,theta", [((1, 256, 16, 128), 1e6),
                                         ((2, 512, 4, 128), 1e4),
                                         ((1, 256, 2, 128), 50.0)])
def test_rope_kernel_equals_the_formula_it_replaces(monkeypatch, shape,
                                                    theta):
    """The kernel computes the formula's two products and one sum an
    element, so its values are the formula's BIT FOR BIT.  Its gradient,
    ``g * cos + roll(g, D/2) * -(sin * sign)``, is bit for bit that closed
    form, whose terms are autodiff's of the formula: against autodiff it is
    held to one rounding of either product and not to the bit, because
    XLA's CPU backend contracts one product of each sum into a fused
    multiply-add and picks another one in the formula's backward pass
    (``g * cos - g' * sin`` in the upper half) than in the kernel."""
    out, d_x, x, mix, taken = _rope_program(monkeypatch, shape, theta)
    assert taken == {"interpret": 1}
    formula = functools.partial(_rope_formula, theta=theta)
    np.testing.assert_array_equal(out, jax.jit(formula)(x))

    half = shape[3] // 2
    sign = jnp.where(jnp.arange(shape[3]) < half, -1.0, 1.0)

    @jax.jit
    def closed_form(mix):
        g = mix / mix.size                    # d mean(out * mix) / d out
        cos, sin = _rope_tables(shape[1], shape[3], theta)
        straight, crossed = g * cos, jnp.roll(g, half, -1) * -(sin * sign)
        return (straight + crossed,
                jnp.finfo(jnp.float32).eps * (abs(straight) + abs(crossed)))

    closed, one_rounding = closed_form(mix)
    np.testing.assert_array_equal(d_x, closed)
    autodiff = jax.jit(jax.grad(lambda x: jnp.mean(formula(x) * mix)))(x)
    assert (np.abs(d_x - autodiff) <= one_rounding).all()


@pytest.mark.parametrize("shape", [(2, 256, 2, 8),       # D off the lanes
                                   (1, 384, 2, 128),     # T off the block
                                   (1, 100, 2, 128)])
def test_rope_shapes_the_kernel_does_not_take_run_the_formula(monkeypatch,
                                                              shape):
    out, d_x, x, mix, taken = _rope_program(monkeypatch, shape, 1e4)
    assert taken == {"reference": 1}
    np.testing.assert_array_equal(
        out, jax.jit(functools.partial(_rope_formula, theta=1e4))(x))
    autodiff = jax.jit(jax.grad(lambda x: jnp.mean(
        _rope_formula(x, 1e4) * mix)))(x)
    np.testing.assert_allclose(                   # the products' rounding
        d_x, autodiff, rtol=0, atol=2 * np.finfo("float32").eps
        * float(np.abs(autodiff).max()))


def test_rope_route_by_shape_dtype_and_backend(monkeypatch):
    route = pallas_kernels.rope_route
    ok = (2, 4096, 16, 128)
    assert route(ok, jnp.float32) == "reference"          # the CPU
    assert route(ok, jnp.float32, interpret=True) == "interpret"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert route(ok, jnp.float32) == route(ok, jnp.bfloat16) == "pallas"
    assert route((2, 4096, 16, 256), jnp.float32) == "pallas"
    for shape, dtype in [((2, 4096, 16, 64), jnp.float32),
                         ((2, 4000, 16, 128), jnp.float32),
                         ((2, 128, 16, 128), jnp.float32),
                         ((2, 4096, 1, 4096), jnp.float32),
                         (ok, jnp.float16), ((4096, 16, 128), jnp.float32)]:
        assert route(shape, dtype) == "reference", (shape, dtype)


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


def test_moe_shape_rule_takes_a_share_only_where_it_is_declared():
    moe = get_shape_fn("moe")
    x = VarInfo((-1, 16, 32), "float32")

    def ins(held, width=32, bias=None):
        stacks = {"W1": [VarInfo((held, 32, 24), "float32")],
                  "W2": [VarInfo((held, 24, 32), "float32")],
                  "WGate": [VarInfo((held, 32, 24), "float32")]}
        if bias is not None:
            stacks["SelectBias"] = [VarInfo((bias,), "float32")]
        return {"X": [x], "GateW": [VarInfo((32, width), "float32")],
                **stacks}

    assert moe(None, ins(32), {})["Out"] == x
    # fewer stacks than the router is wide: the parent's error, unless the
    # op says it holds a share
    with pytest.raises(ShapeError, match="W1 expert count 8 != GateW"):
        moe(None, ins(8), {})
    share = {"experts_held": 8, "expert_offset": 24}
    assert moe(None, ins(8, bias=32), share)["Out"] == x
    with pytest.raises(ShapeError, match="a share of 8 experts from 25"):
        moe(None, ins(8), {"experts_held": 8, "expert_offset": 25})
    with pytest.raises(ShapeError, match="a share of 8"):
        moe(None, ins(16), share)
    with pytest.raises(ShapeError, match="SelectBias"):
        moe(None, ins(8, bias=8), share)


def test_flash_attention_shape_rule_takes_heads_that_divide():
    rule = get_shape_fn("flash_attention")

    def qkv(q, k, v=None):
        return {"Q": [VarInfo(q, "float32")], "K": [VarInfo(k, "float32")],
                "V": [VarInfo(v or k, "float32")]}

    q = (-1, 64, 32, 64)
    assert rule(None, qkv(q, (-1, 64, 8, 64)), {})["Out"].shape == q
    assert rule(None, qkv(q, q), {})["Out"].shape == q
    assert rule(None, qkv((32, 64, 16), (8, 64, 16)), {})["Out"].shape \
        == (32, 64, 16)
    with pytest.raises(ShapeError, match="K's 5 heads do not divide Q's 32"):
        rule(None, qkv(q, (-1, 64, 5, 64)), {})
    with pytest.raises(ShapeError, match="V's 3 heads do not divide"):
        rule(None, qkv(q, (-1, 64, 8, 64), (-1, 64, 3, 64)), {})
    with pytest.raises(ShapeError, match="head dim mismatch"):
        rule(None, qkv(q, (-1, 64, 8, 32)), {})


def test_short_conv_shape_and_sharding_rules():
    from paddle_tpu.analysis.shard_prop import ShardConflict, ShardInfo
    from paddle_tpu.core.registry import get_shard_fn

    rule = get_shape_fn("short_conv")
    x = VarInfo((-1, 64, 96), "float32")
    w = VarInfo((32, 3), "float32")
    assert rule(None, {"X": [x], "Filter": [w]}, {})["Out"].shape \
        == (-1, 64, 32)
    with pytest.raises(ShapeError, match=r"not \[B, T, 3C\]"):
        rule(None, {"X": [VarInfo((-1, 64, 97), "float32")],
                    "Filter": [w]}, {})
    with pytest.raises(ShapeError, match=r"not \[B, T, 3C\]"):
        rule(None, {"X": [VarInfo((64, 96), "float32")], "Filter": [w]}, {})
    with pytest.raises(ShapeError, match=r"not \[C, taps\]"):
        rule(None, {"X": [x], "Filter": [VarInfo((33, 3), "float32")]}, {})

    shard = get_shard_fn("short_conv")
    on_batch = {"X": [ShardInfo(("dp", None, None), (-1, 64, 96))]}
    assert shard(None, on_batch, {})["Out"] == ("dp", None, None)
    for spec in ((None, "sp", None), (None, None, "mp")):
        with pytest.raises(ShardConflict, match="short_conv"):
            shard(None, {"X": [ShardInfo(spec, (-1, 64, 96))]}, {})
    assert shard(None, {"X": [ShardInfo(None, (-1, 64, 96))]}, {}) == {}
