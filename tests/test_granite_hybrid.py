"""``models.granite_hybrid`` and the two mechanisms it brought: the model
against the benchmark's plain float32 reference (loss and every gradient,
``A_log``, ``dt_bias`` and ``D`` among them); the chunked ``ssd_scan`` op
against the recurrence position by position, values and all six gradients,
at decays that underflow too; its shape and sharding rules; the ungated
``short_conv`` (bias, SiLU) against its formula through XLA's formula and
through the kernels (interpreted); the reference's controls."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.analysis.shape_infer import ShapeError, VarInfo
from paddle_tpu.core.registry import get_shape_fn, get_shard_fn
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import pallas_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config():
    spec = importlib.util.spec_from_file_location(
        "granite_4_0_h_micro_config",
        os.path.join(ROOT, "chipbench", "configs", "granite_4_0_h_micro.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(**over):
    """The configuration's file at the cell's rehearsal sizes, three of its
    layers (published layers 4-6: mamba, attention, mamba)."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite_4_0_h_micro.json")) as fh:
        sizes = json.load(fh)
    with open(os.path.join(ROOT, "chipbench", "workloads",
                           "granite-train-scan.json")) as fh:
        sizes.update(json.load(fh)["rehearse"]["sizes"])
    sizes.update(layers_run=[4, 5, 6], num_hidden_layers=3)
    sizes.update(over)
    return sizes


def _routes():
    return {k: v for k, v in profiler.compile_stats().snapshot().items()
            if k.startswith("route/")}


def _started(config, sizes, batch=2, seed=0):
    """(built, exe, host parameters, a seeded feed) after the startup
    program."""
    built = config.build("train", batch, sizes)
    exe = pt.Executor()
    exe.run(built["startup"], feed={}, fetch_list=[])
    scope = pt.global_scope()
    params = {n: np.asarray(scope.get(n))
              for n in config._parameter_names(sizes)}
    rng = np.random.RandomState(seed)
    feed = {k: rng.randint(0, sizes["vocab_size"], (batch, sizes["seq_len"]))
            for k in ("ids", "lbl")}
    return built, exe, params, feed


# ---------------------------------------------------------------------------
# the model against the configuration's reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("recompute,groups", [(True, 1), (False, 1),
                                              ([1], 2)])
def test_model_equals_the_reference_loss_and_every_gradient(recompute,
                                                            groups):
    config = _config()
    sizes = _sizes(recompute=recompute, mamba_n_groups=groups)
    before = _routes()
    built, exe, params, feed = _started(config, sizes)
    kinds = [op.type for b in built["main"].blocks for op in b.ops]
    assert kinds.count("ssd_scan") == 2 and kinds.count("short_conv") == 2
    assert kinds.count("flash_attention") == 1 and "rope" not in kinds
    trainable = [p.name for p in built["main"].global_block()
                 .all_parameters() if p.trainable]
    assert sorted(trainable) == sorted(config._parameter_names(sizes))
    got = exe.run(built["main"], feed=feed, fetch_list=[built["loss"]] + [
        f"{n}@GRAD" for n in trainable])
    sizes["check_params"] = trainable
    ref_loss, ref_grads = config.reference("train", params, feed, sizes)
    np.testing.assert_allclose(got[0], ref_loss, rtol=2e-6)
    assert float(config.reference("loss", params, feed, sizes)) \
        == pytest.approx(float(ref_loss), rel=1e-6)
    for name, grad in zip(trainable, got[1:]):
        assert np.abs(ref_grads[name]).max() > 0, name
        np.testing.assert_allclose(
            grad, ref_grads[name], rtol=2e-4,
            atol=2e-6 * float(np.abs(ref_grads[name]).max()), err_msg=name)
    seen = {k: v - before.get(k, 0) for k, v in _routes().items()}
    assert seen["route/ssd_scan:xla"] == 2
    assert seen["route/short_conv:xla"] == 2
    assert seen["route/flash_attention:grouped"] == 1
    assert seen.get("route/recompute:checkpoint", 0) == (
        3 if recompute is True else len(recompute or ()))


def test_the_startup_program_sets_the_vectors_and_the_reference_holds_them():
    """``A_log[h] = log(h + 1)``, ``dt_bias`` the inverse softplus of steps
    log-spaced over [time_step_min, time_step_max], ``D`` 1, the filter's
    bias 0: set by the startup program, the same whatever the seed; the
    reference refuses weights in which they are anything else."""
    config = _config()
    sizes = _sizes()
    _, _, params, feed = _started(config, sizes)
    heads = sizes["mamba_n_heads"]
    np.testing.assert_allclose(params["granite.l0.A_log"],
                               np.log(np.arange(1, heads + 1)), rtol=1e-6)
    step = np.log1p(np.exp(params["granite.l2.dt_bias"]))
    np.testing.assert_allclose(step[[0, -1]], [0.001, 0.1], rtol=1e-4)
    np.testing.assert_allclose(np.diff(np.log(step)),
                               np.log(100.0) / (heads - 1), rtol=1e-3)
    assert np.all(params["granite.l0.D"] == 1)
    assert not np.any(params["granite.l0.conv_bias"])
    assert params["granite.l0.conv_bias"].shape == (4 * 16 + 2 * 8,)
    for name in ("A_log", "dt_bias", "D", "conv_bias"):
        wrong = dict(params, **{f"granite.l2.{name}":
                                params[f"granite.l2.{name}"] + 0.01})
        with pytest.raises(ValueError, match=f"l2.{name} is not what"):
            config.reference("loss", wrong, feed, sizes)


def test_model_program_validates_clean():
    """Every op of the model has its shape rule and passes it."""
    config = _config()
    built = config.build("train", 2, _sizes())
    for program in (built["main"], built["startup"]):
        report = program.validate()
        assert len(report) == 0, report.render()


@pytest.mark.parametrize("control,moves", [
    ({"lower": "weights"}, (1e-3, 0.2)),
    ({"lower": "all"}, (0.01, 0.5)),
    ({"fault": "chunk_reset"}, (0.02, 50.0)),
    ({"fault": "no_d"}, (0.02, 50.0)),
    ({"sizes": {"attention_multiplier": 0.125}}, (0.02, 50.0)),
    ({"sizes": {"residual_multiplier": 1.0}}, (0.1, 50.0))])
def test_a_control_moves_the_reference(control, moves):
    """Each control the chip's check has to FAIL parts the reference from
    itself: the gradient it bears on most moves by a share inside
    ``moves``."""
    config = _config()
    sizes = _sizes()
    _, _, params, feed = _started(config, sizes, seed=3)
    sound = config.reference("train", params, feed, sizes)[1]
    other = config.reference("train", params, feed, sizes,
                             control=control)[1]
    apart = max(float(np.linalg.norm(other[n] - sound[n])
                      / np.linalg.norm(sound[n])) for n in sound)
    assert moves[0] < apart < moves[1], apart


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------
def _loop_ssd(u, delta, a, bm, cm, d):
    """Position by position, in float64."""
    u, delta, a, bm, cm, d = (np.asarray(x, np.float64)
                              for x in (u, delta, a, bm, cm, d))
    b, t_len, heads, p = u.shape
    per = heads // bm.shape[2]
    state = np.zeros((b, heads, p, bm.shape[3]))
    out = np.zeros_like(u)
    for t in range(t_len):
        bt, ct = (np.repeat(x[:, t], per, axis=1) for x in (bm, cm))
        state = np.exp(delta[:, t] * a)[..., None, None] * state \
            + (delta[:, t, :, None] * u[:, t])[..., None] * bt[:, :, None, :]
        out[:, t] = np.einsum("bhpn,bhn->bhp", state, ct) \
            + d[None, :, None] * u[:, t]
    return out


def _scan_ssd(u, delta, a, bm, cm, d):
    """The same recurrence as a ``lax.scan``, for autodiff."""
    per = u.shape[2] // bm.shape[2]

    def position(state, at):
        ut, dt, bt, ct = at
        bt, ct = jnp.repeat(bt, per, axis=1), jnp.repeat(ct, per, axis=1)
        state = jnp.exp(dt * a)[..., None, None] * state \
            + (dt[..., None] * ut)[..., None] * bt[:, :, None, :]
        return state, jnp.sum(state * ct[:, :, None, :], -1) \
            + d[None, :, None] * ut

    first = [jnp.moveaxis(x, 1, 0) for x in (u, delta, bm, cm)]
    zero = jnp.zeros(u.shape[:1] + u.shape[2:] + bm.shape[3:])
    return jnp.moveaxis(jax.lax.scan(position, zero, tuple(first))[1], 0, 1)


def _ssd_operands(rng, b, t_len, heads, p, groups, n):
    return (rng.randn(b, t_len, heads, p).astype("float32"),
            rng.uniform(0.01, 0.5, (b, t_len, heads)).astype("float32"),
            -rng.uniform(0.5, 4.0, heads).astype("float32"),
            rng.randn(b, t_len, groups, n).astype("float32"),
            rng.randn(b, t_len, groups, n).astype("float32"),
            rng.randn(heads).astype("float32"))


@pytest.mark.parametrize("chunk,groups", [(4, 2), (8, 2), (8, 1), (32, 1)])
def test_ssd_scan_equals_the_recurrence_position_by_position(chunk, groups):
    """The op through a Program: values against the loop over positions,
    all six gradients against autodiff of the recurrence as a scan."""
    b, t_len, heads, p, n = 2, 32, 4, 3, 5
    vals = _ssd_operands(np.random.RandomState(chunk + groups), b, t_len,
                         heads, p, groups, n)
    weight = np.random.RandomState(1).randn(b, t_len, heads, p) \
        .astype("float32")
    names = ("u", "delta", "a", "bm", "cm", "d")
    # every operand a parameter, so that each gradient can be fetched whole
    ins = [LayerHelper("operand").create_parameter(
        pt.ParamAttr(name=name,
                     initializer=pt.initializer.NumpyArrayInitializer(v)),
        shape=list(v.shape), dtype="float32")
        for name, v in zip(names, vals)]
    wt = layers.data("wt", shape=[t_len, heads, p], dtype="float32")
    out = layers.ssd_scan(*ins, chunk=chunk)
    assert tuple(out.shape) == (b, t_len, heads, p)
    loss = layers.reduce_sum(layers.elementwise_mul(out, wt))
    pt.optimizer.SGD(0.0).minimize(loss)
    before = _routes().get("route/ssd_scan:xla", 0)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    got = exe.run(feed={"wt": weight},
                  fetch_list=[out] + [f"{n}@GRAD" for n in names])
    assert _routes()["route/ssd_scan:xla"] - before == 1
    np.testing.assert_allclose(got[0], _loop_ssd(*vals), rtol=1e-4,
                               atol=1e-5)
    want = jax.grad(lambda *xs: jnp.sum(_scan_ssd(*xs) * weight),
                    argnums=range(6))(*(jnp.asarray(v) for v in vals))
    for name, grad, ref in zip(names, got[1:], want):
        np.testing.assert_allclose(
            grad, ref, rtol=2e-4, atol=2e-5 * float(jnp.abs(ref).max()),
            err_msg=name)


def test_ssd_scan_stays_finite_where_the_decays_underflow():
    """A = -64 at delta 0.15: a chunk's running sum reaches -2 400, where a
    quotient of exponentials is 0 / 0 and the upper triangle overflows.
    Values and all six gradients are finite and equal the recurrence's."""
    from paddle_tpu.ops.ssd_ops import ssd_chunked

    rng = np.random.RandomState(0)
    b, t_len, heads, p, n, chunk = 1, 512, 2, 4, 8, 256
    vals = [jnp.asarray(v) for v in (
        rng.randn(b, t_len, heads, p).astype("float32"),
        np.full((b, t_len, heads), 0.15, "float32"),
        np.array([-64.0, -1.0], "float32"),
        rng.randn(b, t_len, 1, n).astype("float32"),
        rng.randn(b, t_len, 1, n).astype("float32"),
        np.ones(heads, "float32"))]
    got = ssd_chunked(*vals, chunk)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, _loop_ssd(*vals), rtol=1e-4, atol=1e-4)
    grads = jax.grad(lambda *xs: jnp.sum(ssd_chunked(*xs, chunk) ** 2),
                     argnums=range(6))(*vals)
    want = jax.grad(lambda *xs: jnp.sum(_scan_ssd(*xs) ** 2),
                    argnums=range(6))(*vals)
    for grad, ref in zip(grads, want):
        assert bool(jnp.all(jnp.isfinite(grad)))
        np.testing.assert_allclose(
            grad, ref, rtol=1e-3, atol=1e-4 * float(jnp.abs(ref).max()))


def test_ssd_scan_shape_and_sharding_rules():
    from paddle_tpu.analysis.shard_prop import ShardConflict, ShardInfo

    rule = get_shape_fn("ssd_scan")

    def ins(u=(-1, 32, 4, 3), delta=(-1, 32, 4), a=(4,), bm=(-1, 32, 2, 5),
            cm=(-1, 32, 2, 5), d=(4,)):
        return {"U": [VarInfo(u, "float32")],
                "Delta": [VarInfo(delta, "float32")],
                "A": [VarInfo(a, "float32")], "Bm": [VarInfo(bm, "float32")],
                "Cm": [VarInfo(cm, "float32")], "D": [VarInfo(d, "float32")]}

    assert rule(None, ins(), {"chunk": 8})["Out"].shape == (-1, 32, 4, 3)
    with pytest.raises(ShapeError, match="not whole chunks of 5"):
        rule(None, ins(), {"chunk": 5})
    with pytest.raises(ShapeError, match="not whole chunks of 256"):
        rule(None, ins(), {})
    with pytest.raises(ShapeError, match=r"not \[B, T, H, P\]"):
        rule(None, ins(u=(-1, 32, 12)), {"chunk": 8})
    with pytest.raises(ShapeError, match="Delta"):
        rule(None, ins(delta=(-1, 32, 3)), {"chunk": 8})
    with pytest.raises(ShapeError, match=r"A \[3\]"):
        rule(None, ins(a=(3,)), {"chunk": 8})
    with pytest.raises(ShapeError, match="a divisor of U's 4 heads"):
        rule(None, ins(bm=(-1, 32, 3, 5), cm=(-1, 32, 3, 5)), {"chunk": 8})
    with pytest.raises(ShapeError, match="Cm"):
        rule(None, ins(cm=(-1, 32, 2, 6)), {"chunk": 8})
    # and the layer refuses it when the Program is built
    u = layers.data("u", shape=[32, 4, 3], dtype="float32")
    bc = layers.data("bc", shape=[32, 2, 5], dtype="float32")
    dt = layers.data("dt", shape=[32, 4], dtype="float32")
    vec = layers.data("vec", shape=[4], dtype="float32",
                      append_batch_size=False)
    layers.ssd_scan(u, dt, vec, bc, bc, vec, chunk=5)
    report = pt.default_main_program().validate()
    assert "not whole chunks of 5" in report.render()

    shard = get_shard_fn("ssd_scan")
    on_batch = {"U": [ShardInfo(("dp", None, None, None), (-1, 32, 4, 3))]}
    assert shard(None, on_batch, {})["Out"] == ("dp", None, None, None)
    for spec in ((None, "sp", None, None), (None, None, "mp", None)):
        with pytest.raises(ShardConflict, match="ssd_scan"):
            shard(None, {"U": [ShardInfo(spec, (-1, 32, 4, 3))]}, {})
    assert shard(None, {"U": [ShardInfo(None, (-1, 32, 4, 3))]}, {}) == {}


# ---------------------------------------------------------------------------
# the ungated short convolution
# ---------------------------------------------------------------------------
def _plain_filter(x, w, bias, act):
    taps = w.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(w[:, j] * padded[:, j:j + x.shape[1]] for j in range(taps)) \
        + bias
    return jax.nn.silu(c) if act else c


@pytest.mark.parametrize("interpret,shape,taps,act,bias", [
    (False, (2, 11, 24), 4, "silu", True), (False, (1, 5, 8), 3, None, True),
    (False, (2, 11, 24), 4, "silu", False),
    (True, (2, 192, 256), 4, "silu", True),
    (True, (1, 128, 640), 4, "silu", True),
    (True, (1, 128, 128), 2, None, False)])
def test_ungated_short_conv_equals_its_formula(interpret, shape, taps, act,
                                               bias):
    """The op through a Program (XLA's formula; the kernels interpreted
    where asked): values and the gradients of X, the filter and the bias
    against autodiff of the plain formula."""
    b, t_len, c = shape
    rng = np.random.RandomState(taps)
    x_val = rng.randn(b, t_len, c).astype("float32")
    weight = rng.randn(b, t_len, c).astype("float32")
    bias_val = rng.randn(c).astype("float32") if bias else np.zeros(c, "f")
    x = layers.data("x", shape=[t_len, c], dtype="float32")
    shift = LayerHelper("shift").create_parameter(
        pt.ParamAttr(name="shift",
                     initializer=pt.initializer.ConstantInitializer(0.0)),
        shape=[t_len, c], dtype="float32")
    wt = layers.data("wt", shape=[t_len, c], dtype="float32")
    out = layers.short_conv(
        layers.elementwise_add(x, shift, axis=1), taps,
        pt.ParamAttr(name="filter"), interpret=interpret, gated=False,
        bias_attr=pt.ParamAttr(
            name="bias", initializer=pt.initializer.NumpyArrayInitializer(
                bias_val)) if bias else False, act=act)
    assert tuple(out.shape) == (-1, t_len, c)
    loss = layers.reduce_sum(layers.elementwise_mul(out, wt))
    pt.optimizer.SGD(0.0).minimize(loss)
    assert len(pt.default_main_program().validate()) == 0
    before = _routes()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    filt = jnp.asarray(np.asarray(pt.global_scope().get("filter")))
    assert filt.shape == (c, taps)
    got = exe.run(feed={"x": x_val, "wt": weight},
                  fetch_list=[out, "shift@GRAD", "filter@GRAD"]
                  + (["bias@GRAD"] if bias else []))
    route = "interpret" if interpret else "xla"
    assert _routes()[f"route/short_conv:{route}"] \
        - before.get(f"route/short_conv:{route}", 0) == 1
    np.testing.assert_allclose(
        got[0], _plain_filter(x_val, filt, bias_val, act), rtol=1e-4,
        atol=1e-5)
    want = jax.grad(
        lambda x, w, bb: jnp.sum(_plain_filter(x, w, bb, act) * weight),
        argnums=(0, 1, 2))(jnp.asarray(x_val), filt, jnp.asarray(bias_val))
    np.testing.assert_allclose(got[1], jnp.sum(want[0], axis=0), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(got[2], want[1], rtol=1e-4, atol=1e-3)
    if bias:
        np.testing.assert_allclose(got[3], want[2], rtol=1e-4, atol=1e-3)


def test_short_conv_forms_routes_and_rules(monkeypatch):
    def route(shape, taps, dtype, act):
        return pallas_kernels.short_conv_route(shape, taps, dtype,
                                               gated=False, act=act)

    assert route((1, 4096, 4352), 4, jnp.float32, "silu") == "xla"  # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert route((1, 4096, 4352), 4, jnp.float32, "silu") == "pallas"
    assert route((2, 128, 128), 8, jnp.bfloat16, None) == "pallas"
    assert route((1, 4096, 4352), 4, jnp.float32, "gelu") == "xla"
    assert route((1, 4100, 4352), 4, jnp.float32, "silu") == "xla"  # rows
    assert route((1, 4096, 4300), 4, jnp.float32, "silu") == "xla"  # lanes
    assert route((1, 4096, 4352), 9, jnp.float32, "silu") == "xla"  # taps
    monkeypatch.undo()

    rule = get_shape_fn("short_conv")
    x = VarInfo((-1, 64, 80), "float32")
    plain = {"gated": False, "activation": "silu"}
    ok = {"X": [x], "Filter": [VarInfo((80, 4), "float32")],
          "Bias": [VarInfo((80,), "float32")]}
    assert rule(None, ok, plain)["Out"].shape == (-1, 64, 80)
    with pytest.raises(ShapeError, match=r"not \[C, taps\]"):
        rule(None, dict(ok, Filter=[VarInfo((81, 4), "float32")]), plain)
    with pytest.raises(ShapeError, match=r"Bias \[79\]"):
        rule(None, dict(ok, Bias=[VarInfo((79,), "float32")]), plain)
    # the gated form: channels that divide by 3, no bias, no activation
    with pytest.raises(ShapeError, match=r"not \[B, T, 3C\]"):
        rule(None, {"X": [x], "Filter": [VarInfo((80, 4), "float32")]}, {})
    with pytest.raises(ShapeError, match="no Bias and no activation"):
        rule(None, dict(ok, X=[VarInfo((-1, 64, 240), "float32")]), {})
    wide = layers.data("wide", shape=[64, 80], dtype="float32")
    with pytest.raises(ValueError, match="the gated form takes"):
        layers.short_conv(wide, 4)
    with pytest.raises(ValueError, match="the gated form takes"):
        layers.short_conv(layers.data("w3", shape=[64, 240],
                                      dtype="float32"), 4, act="silu")
