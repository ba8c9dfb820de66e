"""``models.nemotron_h`` and what it brought: the model against the
benchmark's plain float32 reference (loss and every gradient); the share
test (the parts the shares give, the shared expert counted once, add up to
the uncut layer); un-gated ``relu2`` experts and the shared expert against a
dense loop, the up stack held [E, D, H] and [E, H, D]; the un-gated
``grouped_matmul`` at a width that is a multiple of 64 and not of 128;
``ssd_scan`` at eight groups (a head block a group; values and gradients
through the interpreted kernels: ``tests/test_ssd_kernels.py``'s last case);
the group-wise gated norm; the reference's
controls and what it is shown."""
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
from paddle_tpu.core.registry import get_shape_fn
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import moe_ops, pallas_kernels, ssd_kernels
from paddle_tpu.ops.ssd_ops import ssd_chunked

from test_granite_hybrid import _ssd_operands

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "nemotron_3_nano_30b_a3b"
RELU2 = moe_ops._ACTS["relu2"]


def _config():
    spec = importlib.util.spec_from_file_location(
        NAME + "_config",
        os.path.join(ROOT, "chipbench", "configs", NAME + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(**over):
    """The configuration's file at the cell's rehearsal sizes, four of its
    layers (published layers 4-7: ``M*EM``)."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           NAME + ".json")) as fh:
        sizes = json.load(fh)
    with open(os.path.join(ROOT, "chipbench", "workloads",
                           "nemotron-train-scan.json")) as fh:
        sizes.update(json.load(fh)["rehearse"]["sizes"])
    sizes.update(layers_run=[4, 5, 6, 7], num_hidden_layers=4,
                 recompute=False)
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
@pytest.mark.parametrize("rank,recompute,widths", [
    (0, False, {}), (1, True, {}), (3, [2], {}),
    # the up stacks held [E, H, D]: hidden 128, a width of 192
    (1, [1, 2], {"hidden_size": 128, "moe_intermediate_size": 192})])
def test_model_equals_the_reference_loss_and_every_gradient(rank, recompute,
                                                            widths):
    config = _config()
    sizes = _sizes(recompute=recompute, expert_parallel_rank=rank, **widths)
    before = _routes()
    built, exe, params, feed = _started(config, sizes)
    kinds = [op.type for b in built["main"].blocks for op in b.ops]
    assert kinds.count("ssd_scan") == 2 and kinds.count("short_conv") == 2
    assert kinds.count("flash_attention") == 1 and "rope" not in kinds
    assert kinds.count("moe") == 1
    trainable = [p.name for p in built["main"].global_block()
                 .all_parameters() if p.trainable]
    # (the correction bias is a parameter without a gradient)
    assert sorted(trainable + ["nemotron.l2.expert_bias"]) \
        == sorted(config._parameter_names(sizes))
    d, h = sizes["hidden_size"], sizes["moe_intermediate_size"]
    assert params["nemotron.l2.experts_up"].shape == (
        (2, h, d) if widths else (2, d, h))
    got = exe.run(built["main"], feed=feed, fetch_list=[built["loss"]] + [
        f"{n}@GRAD" for n in trainable])
    sizes["check_params"] = trainable
    ref_loss, ref_grads, saw = config.reference("train", params, feed, sizes)
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
    for route in ("dropless", "sigmoid", "share", "single", "shared"):
        assert seen["route/moe:" + route] == 1, route
    assert not seen.get("route/moe:gated_pair")
    assert seen.get("route/recompute:checkpoint", 0) == (
        4 if recompute is True else len(recompute or ()))
    assert saw["rows_bound"] == 2 * 16 * 2 and "l2" in saw
    assert 0 < saw["l2"]["rows_held"] < saw["rows_bound"]


def test_the_startup_program_sets_the_vectors_and_the_reference_holds_them():
    """``A_log``, ``dt_bias``, ``D`` and the filter's bias as Granite's, and
    every expert layer's correction bias a draw in +-``expert_bias_range``:
    set by the startup program, the same whatever the seed; the reference
    refuses weights in which they are anything else."""
    config = _config()
    sizes = _sizes()
    _, _, params, feed = _started(config, sizes)
    heads = sizes["mamba_num_heads"]
    np.testing.assert_allclose(params["nemotron.l0.A_log"],
                               np.log(np.arange(1, heads + 1)), rtol=1e-6)
    step = np.log1p(np.exp(params["nemotron.l3.dt_bias"]))
    np.testing.assert_allclose(step[[0, -1]], [0.001, 0.1], rtol=1e-4)
    assert np.all(params["nemotron.l0.D"] == 1)
    assert params["nemotron.l0.conv_bias"].shape == (4 * 16 + 2 * 2 * 8,)
    bias = params["nemotron.l2.expert_bias"]
    assert bias.shape == (8,) and bias.dtype == np.float32
    assert 0 < np.abs(bias).max() <= 0.05
    for name in ("A_log", "dt_bias", "D", "conv_bias"):
        wrong = dict(params, **{f"nemotron.l3.{name}":
                                params[f"nemotron.l3.{name}"] + 0.01})
        with pytest.raises(ValueError, match=f"l3.{name} is not what"):
            config.reference("loss", wrong, feed, sizes)
    flat = dict(params, **{"nemotron.l2.expert_bias": np.zeros_like(bias)})
    with pytest.raises(ValueError, match="expert_bias is not a draw"):
        config.reference("train", flat, feed, sizes)


def test_model_program_validates_clean():
    """Every op of the model has its shape rule and passes it (the share's
    stacks under the wider router, the shared expert, the grouped norm)."""
    config = _config()
    built = config.build("train", 2, _sizes())
    for program in (built["main"], built["startup"]):
        report = program.validate()
        assert len(report) == 0, report.render()


def test_the_reference_takes_its_choice_of_experts_from_what_it_is_shown():
    """``build`` names each router's input; shown the program's own, the
    reference reads as alone; shown inputs that move some choices, it
    follows them and says how many tokens and how far."""
    config = _config()
    sizes = _sizes(expert_bias_range=0.5)
    built, exe, params, feed = _started(config, sizes, seed=5)
    assert sorted(built["check_fetches"]) == ["l2"]
    got = exe.run(built["main"], feed=feed,
                  fetch_list=list(built["check_fetches"].values()))
    shown = dict(zip(built["check_fetches"], got))
    alone = config.reference("train", params, feed, sizes)
    same = config.reference("train", params, feed, sizes, observed=shown)
    assert float(alone[0]) == float(same[0])
    assert same[2]["l2"]["tokens_routed_otherwise"] == 0
    assert same[2]["l2"]["input_rel_err"] < 1e-5
    rng = np.random.RandomState(6)
    moved = {k: v + 0.3 * rng.randn(*v.shape).astype(v.dtype)
             for k, v in shown.items()}
    other = config.reference("train", params, feed, sizes, observed=moved)
    assert other[2]["l2"]["tokens_routed_otherwise"] > 0
    assert 0.1 < other[2]["l2"]["input_rel_err"] < 0.5
    assert float(other[0]) != float(alone[0])


@pytest.mark.parametrize("control,moves", [
    ({"lower": "all"}, (0.01, 0.5)),
    ({"fault": "no_shared"}, (0.05, 50.0)),
    ({"fault": "relu"}, (0.05, 50.0)),
    ({"sizes": {"expert_parallel_rank": 0}}, (0.05, 50.0)),
    ({"sizes": {"norm_topk_prob": False}}, (0.05, 50.0))])
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
    with pytest.raises(ValueError, match="no control"):
        config.reference("train", params, feed, sizes,
                         control={"fault": "something"})


# ---------------------------------------------------------------------------
# un-gated relu^2 experts, the shared expert, the share
# ---------------------------------------------------------------------------
def _expert_layer(rng, n=48, d=16, h=24, hs=40, e=32):
    return {"x": jnp.asarray(rng.randn(n, d), jnp.float32),
            "router": jnp.asarray(rng.randn(d, e) * 0.7, jnp.float32),
            "bias": jnp.asarray(rng.uniform(-0.5, 0.5, e), jnp.float32),
            "up": jnp.asarray(rng.randn(e, d, h) * 0.4, jnp.float32),
            "down": jnp.asarray(rng.randn(e, h, d) * 0.4, jnp.float32),
            "shared_up": jnp.asarray(rng.randn(d, hs) * 0.4, jnp.float32),
            "shared_down": jnp.asarray(rng.randn(hs, d) * 0.4, jnp.float32)}


def _experts_out(w, x, first, held, shared=True, transposed=False, top_k=6):
    here = slice(first, first + held)
    up = w["up"][here]
    return moe_ops._dropless(
        x, w["router"], None, jnp.swapaxes(up, 1, 2) if transposed else up,
        w["down"][here], top_k, RELU2, scoring="sigmoid",
        select_bias=w["bias"], renormalize=True, routed_scale=2.5,
        expert_offset=first, up_transposed=transposed,
        shared=(None, w["shared_up"], w["shared_down"]) if shared else None
    )[0]


@pytest.mark.parametrize("held", [2, 8])
def test_the_shares_add_up_to_the_uncut_layer_the_shared_expert_once(held):
    """Output and input gradient of the routed parts the 16 (or 4) shares
    give, plus the shared expert counted ONCE (every chip computes it
    alike), sum to those of the layer that holds all 32 experts with its
    shared expert: choice and renormalisation run over all 32 on every
    chip, each computes its own experts' part, nothing is counted twice or
    left out.  A share WITH the shared expert is its routed part plus that
    same term."""
    w = _expert_layer(np.random.RandomState(0))
    ct = jnp.asarray(np.random.RandomState(1).randn(*w["x"].shape),
                     jnp.float32)

    def both(fn):
        out, vjp = jax.vjp(fn, w["x"])
        return out, vjp(ct)[0]

    whole = both(lambda x: _experts_out(w, x, 0, 32))
    routed = [both(lambda x, f=first: _experts_out(w, x, f, held,
                                                   shared=False))
              for first in range(0, 32, held)]
    once = both(lambda x: RELU2(x @ w["shared_up"]) @ w["shared_down"])
    rank1 = both(lambda x: _experts_out(w, x, held, held))
    for k in range(2):
        np.testing.assert_allclose(sum(p[k] for p in routed) + once[k],
                                   whole[k], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(routed[1][k] + once[k], rank1[k],
                                   rtol=1e-5, atol=1e-5)
    # a share is a proper part: no chip's routed part is the whole or nothing
    assert all(0.01 < float(jnp.linalg.norm(p[0]))
               / float(jnp.linalg.norm(whole[0])) < 0.95 for p in routed)


@pytest.mark.parametrize("transposed", [False, True])
def test_ungated_relu2_experts_equal_a_dense_loop(transposed):
    """``relu(x Wu)^2 Wd`` for the six chosen of 32 under their
    renormalised, scaled scores, plus the shared expert under weight 1:
    the op against a loop over tokens, values and every gradient; the up
    stack held [E, D, H] or [E, H, D], the same matrices."""
    w = _expert_layer(np.random.RandomState(2))
    score = jax.nn.sigmoid(jnp.dot(w["x"], w["router"],
                                   precision=jax.lax.Precision.HIGHEST))
    chosen = np.asarray(jax.lax.top_k(score + w["bias"], 6)[1])

    def oracle(w):
        rows = []
        for t in range(w["x"].shape[0]):
            s = jax.nn.sigmoid(w["x"][t] @ w["router"])[chosen[t]]
            g = s / (jnp.sum(s) + 1e-6) * 2.5
            rows.append(sum(
                g[k] * (RELU2(w["x"][t] @ w["up"][e]) @ w["down"][e])
                for k, e in enumerate(chosen[t]))
                + RELU2(w["x"][t] @ w["shared_up"]) @ w["shared_down"])
        return jnp.stack(rows)

    mix = jnp.asarray(np.random.RandomState(3).randn(*w["x"].shape),
                      jnp.float32)
    before = _routes()
    got = jax.value_and_grad(lambda w: jnp.sum(_experts_out(
        w, w["x"], 0, 32, transposed=transposed) * mix))(w)
    want = jax.value_and_grad(lambda w: jnp.sum(oracle(w) * mix))(w)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for name in ("x", "router", "up", "down", "shared_up", "shared_down"):
        np.testing.assert_allclose(
            got[1][name], want[1][name], rtol=2e-4,
            atol=2e-5 * float(jnp.abs(want[1][name]).max()), err_msg=name)
    assert not np.any(np.asarray(got[1]["bias"]))
    seen = {k: v - before.get(k, 0) for k, v in _routes().items()}
    assert seen["route/moe:single"] == seen["route/moe:shared"] >= 1
    assert not seen.get("route/moe:gated_pair")


@pytest.mark.parametrize("width,transposed", [(192, False), (192, True),
                                              (64, True)])
def test_ungated_grouped_matmul_at_a_width_off_the_lane_tile(width,
                                                             transposed):
    """The three kernels (interpreted) at a width that is a multiple of 64
    and not of 128, the stack [G, K, N] and [G, N, K] read transposed:
    value, the rows' gradient and the stack's against a loop over the
    groups."""
    rng = np.random.RandomState(width)
    groups, k, tm = 3, 128, 8
    counts = [13, 0, 21]                         # rows of each group
    tiles = [max(-(-c // tm), 1) for c in counts]
    tile_group = jnp.asarray(np.repeat(np.arange(groups), tiles), jnp.int32)
    num_tiles = jnp.asarray([sum(tiles)], jnp.int32)
    rows = sum(tiles) * tm
    in_use = np.concatenate([np.arange(t * tm) < c
                             for t, c in zip(tiles, counts)])
    lhs = jnp.asarray(rng.randn(rows, k) * in_use[:, None], jnp.float32)
    rhs = jnp.asarray(rng.randn(groups, k, width) * 0.3, jnp.float32)
    mix = jnp.asarray(rng.randn(rows, width) * in_use[:, None], jnp.float32)
    group_of_row = np.repeat(np.asarray(tile_group), tm)

    def loop(lhs, rhs):
        return jnp.sum(jnp.stack([lhs[r] @ rhs[group_of_row[r]]
                                  for r in range(rows)]) * mix)

    def kernels(lhs, rhs):
        stack = jnp.swapaxes(rhs, 1, 2) if transposed else rhs
        return jnp.sum(pallas_kernels.grouped_matmul(
            lhs, stack, tile_group, num_tiles, transpose_rhs=transposed)
            * mix)

    got = jax.value_and_grad(kernels, argnums=(0, 1))(lhs, rhs)
    want = jax.value_and_grad(loop, argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for g, ref in zip(got[1], want[1]):
        np.testing.assert_allclose(g, ref, rtol=2e-4, atol=2e-5)


def test_the_layer_holds_an_off_tile_up_stack_transposed_and_says_so():
    """``layers.moe``: an un-gated up stack whose width is no multiple of
    128 under an input width that is one is created [E, H, D] and the op
    told (``up_transposed``); every other stack stays [E, D, H] and the op's
    attributes stay what they were; the shared expert's matrices and the
    shape rule's refusals."""
    def built(d, h, gated, **more):
        pt.core.reset_default_programs()
        pt.unique_name.reset()
        x = layers.data("x", shape=[4, d], dtype="float32")
        layers.moe(x, 8, h, top_k=2, capacity_factor=None, act="relu2",
                   gated=gated, param_attr=pt.ParamAttr(name="e"), **more)
        block = pt.default_main_program().global_block()
        op = [op for op in block.ops if op.type == "moe"][0]
        return block, op

    block, op = built(128, 192, False)
    assert tuple(block.var("e_up").shape) == (8, 192, 128)
    assert tuple(block.var("e_down").shape) == (8, 192, 128)
    assert op.attrs["up_transposed"] is True
    for d, h, gated in ((128, 256, False), (96, 192, False),
                        (128, 192, True)):
        block, op = built(d, h, gated)
        assert tuple(block.var("e_up").shape) == (8, d, h)
        assert "up_transposed" not in op.attrs
    block, op = built(128, 192, True, shared_hidden=320,
                      shared_attr=pt.ParamAttr(name="s"))
    assert [tuple(block.var(n).shape) for n in ("s_up", "s_gate", "s_down")] \
        == [(128, 320), (128, 320), (320, 128)]
    assert [op.input(slot) for slot in ("SharedUp", "SharedGate",
                                        "SharedDown")] \
        == [["s_up"], ["s_gate"], ["s_down"]]
    rule = get_shape_fn("moe")
    f32 = "float32"
    ins = {"X": [VarInfo((4, 16), f32)], "GateW": [VarInfo((16, 8), f32)],
           "W1": [VarInfo((8, 16, 24), f32)],
           "W2": [VarInfo((8, 24, 16), f32)],
           "SharedUp": [VarInfo((16, 40), f32)],
           "SharedDown": [VarInfo((40, 16), f32)]}
    assert rule(None, ins, {})["Out"].shape == (4, 16)
    with pytest.raises(ShapeError, match="SharedUp"):
        rule(None, dict(ins, SharedDown=[VarInfo((16, 40), f32)]), {})
    with pytest.raises(ShapeError, match="SharedGate"):
        rule(None, dict(ins, SharedGate=[VarInfo((16, 41), f32)]), {})
    # the capacity lowering runs none of it
    pt.core.reset_default_programs()
    pt.unique_name.reset()
    x = layers.data("x", shape=[4, 16], dtype="float32")
    out, _, _ = layers.moe(x, 4, 8, top_k=2, shared_hidden=8)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    with pytest.raises(NotImplementedError, match="a shared expert"):
        exe.run(feed={"x": np.zeros((2, 4, 16), "float32")},
                fetch_list=[out])


# ---------------------------------------------------------------------------
# ssd_scan at eight groups, the group-wise gated norm
# ---------------------------------------------------------------------------
def test_ssd_scan_at_eight_groups_is_a_head_block_a_group():
    """64 heads of 64 over 8 groups of 128, chunks of 128 (the cell's
    layer): the route gives the kernels, one 512-lane head block a group
    (values and all six gradients through the interpreted kernels against
    the einsum form and the recurrence: the last of
    ``tests/test_ssd_kernels.py CASES``), and a group's Bm reaches its own
    eight heads and no other, through the kernels as through the einsum
    form."""
    b, t_len, heads, p, groups, n, chunk = 1, 256, 64, 64, 8, 128, 128
    assert ssd_kernels.heads_a_block(heads, groups, p) == 8
    assert ssd_kernels.ssd_scan_route(
        ((b, t_len, heads, p), (b, t_len, groups, n)), chunk, jnp.float32,
        interpret=True) == "interpret"
    vals = [jnp.asarray(v) for v in _ssd_operands(
        np.random.RandomState(8), b, t_len, heads, p, groups, n)]
    moved = list(vals)
    moved[3] = vals[3].at[:, :, 5].add(1.0)
    for scan in (lambda *xs: ssd_chunked(*xs, chunk),
                 lambda *xs: ssd_kernels.ssd_scan(*xs, chunk,
                                                  interpret=True)):
        apart = np.asarray(jnp.abs(scan(*moved) - scan(*vals)).max((0, 1, 3)))
        assert np.all(apart[40:48] > 0) and not np.any(np.delete(
            apart, np.arange(40, 48)))


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_rms_norm_over_groups_of_the_last_axis(groups):
    """``layers.rms_norm(groups=G)``: each of the G equal parts of the last
    axis normalised by its own mean square, ONE weight over all of it;
    value and both gradients against the formula written out; 1 group is
    the op as it was, attributes and all."""
    rng = np.random.RandomState(groups)
    b, t_len, d = 2, 5, 32
    x_val = rng.randn(b, t_len, d).astype("float32") * 3
    g_val = rng.uniform(0.5, 1.5, d).astype("float32")
    mix = rng.randn(b, t_len, d).astype("float32")
    pt.core.reset_default_programs()
    pt.unique_name.reset()
    # (a parameter, so that its gradient can be fetched whole)
    x = LayerHelper("operand").create_parameter(
        pt.ParamAttr(name="x", initializer=pt.initializer
                     .NumpyArrayInitializer(x_val)),
        shape=list(x_val.shape), dtype="float32")
    m = layers.data("m", shape=[t_len, d], dtype="float32")
    y = layers.rms_norm(x, 1e-5, pt.ParamAttr(
        name="g", initializer=pt.initializer.NumpyArrayInitializer(g_val)),
        groups=groups)
    assert tuple(y.shape) == tuple(x.shape)
    loss = layers.reduce_sum(layers.elementwise_mul(y, m))
    pt.optimizer.SGD(0.0).minimize(loss)
    op = [op for op in pt.default_main_program().global_block().ops
          if op.type == "rms_norm"][0]
    assert op.attrs.get("groups", 1) == groups
    assert ("groups" in op.attrs) == (groups != 1)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    got = exe.run(feed={"m": mix},
                  fetch_list=[y, "x@GRAD", "g@GRAD"])

    def formula(x, g):
        parts = x.reshape(b, t_len, groups, d // groups)
        parts = parts * jax.lax.rsqrt(
            jnp.mean(parts * parts, axis=-1, keepdims=True) + 1e-5)
        return parts.reshape(b, t_len, d) * g

    want = formula(jnp.asarray(x_val), jnp.asarray(g_val))
    grads = jax.grad(lambda x, g: jnp.sum(formula(x, g) * mix),
                     argnums=(0, 1))(jnp.asarray(x_val), jnp.asarray(g_val))
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[1], grads[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[2], grads[1], rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="equal groups"):
        layers.rms_norm(x, groups=5)
    with pytest.raises(ShapeError, match="equal groups"):
        get_shape_fn("rms_norm")(
            None, {"X": [VarInfo((2, 32), "float32")],
                   "Scale": [VarInfo((32,), "float32")]}, {"groups": 5})
