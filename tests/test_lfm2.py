"""``models.lfm2`` and the three mechanisms it brought: the model against the
benchmark's plain float32 reference (loss and every gradient) as one chip's
share of its experts; the four shares of an expert layer adding up to the
uncut layer; the gated short convolution against autodiff of its plain
formula and a loop over positions, through XLA's formula and through the
kernels (interpreted); grouped-query ``flash_attention`` (interpreted)
against the reference with K and V repeated; the selection bias."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import moe_ops, pallas_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config():
    spec = importlib.util.spec_from_file_location(
        "lfm2_8b_a1b_config",
        os.path.join(ROOT, "chipbench", "configs", "lfm2_8b_a1b.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(**over):
    """The configuration's file at the cell's rehearsal sizes."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "lfm2_8b_a1b.json")) as fh:
        sizes = json.load(fh)
    with open(os.path.join(ROOT, "chipbench", "workloads",
                           "lfm2-train-scan.json")) as fh:
        sizes.update(json.load(fh)["rehearse"]["sizes"])
    sizes.update(over)
    return sizes


def _routes():
    return {k: v for k, v in profiler.compile_stats().snapshot().items()
            if k.startswith("route/")}


@pytest.mark.parametrize("rank,recompute", [(0, False), (1, False),
                                            (3, True)])
def test_model_equals_the_reference_loss_and_every_gradient(rank, recompute):
    config = _config()
    sizes = _sizes(expert_parallel_rank=rank, recompute=recompute,
                   expert_bias_range=0.5)
    built = config.build("train", 2, sizes)
    block = built["main"].global_block()
    kinds = [op.type for b in built["main"].blocks for op in b.ops]
    assert "short_conv" in kinds and "flash_attention" in kinds
    trainable = [p.name for p in block.all_parameters() if p.trainable]
    assert sorted(trainable + [f"lfm2.l{i}.expert_bias" for i in (1, 2)]) \
        == sorted(config._parameter_names(sizes))
    before = _routes()
    exe = pt.Executor()
    exe.run(built["startup"], feed={}, fetch_list=[])
    scope = pt.global_scope()
    params = {n: np.asarray(scope.get(n))
              for n in config._parameter_names(sizes)}
    assert np.ptp(params["lfm2.l1.expert_bias"]) > 0.1
    rng = np.random.RandomState(rank)
    feed = {k: rng.randint(0, sizes["vocab_size"], (2, sizes["seq_len"]))
            for k in ("ids", "lbl")}
    got = exe.run(built["main"], feed=feed, fetch_list=[built["loss"]] + [
        f"{n}@GRAD" for n in trainable])
    sizes["check_params"] = trainable
    ref_loss, ref_grads, saw = config.reference("train", params, feed,
                                                sizes)
    np.testing.assert_allclose(got[0], ref_loss, rtol=2e-6)
    for name, grad in zip(trainable, got[1:]):
        np.testing.assert_allclose(
            grad, ref_grads[name], rtol=2e-4,
            atol=2e-6 * float(np.abs(ref_grads[name]).max()), err_msg=name)
    # the bias is held and not trained; the tied table got both gradients
    assert np.array_equal(np.asarray(scope.get("lfm2.l1.expert_bias")),
                          params["lfm2.l1.expert_bias"])
    seen = {k: v - before.get(k, 0) for k, v in _routes().items()}
    assert seen["route/moe:share"] == 2 and seen["route/moe:sigmoid"] == 2
    assert seen["route/flash_attention:grouped"] == 1
    assert seen["route/short_conv:xla"] == 2
    assert saw["rows_bound"] == 2 * 16 * 2 and set(saw) >= {"l1", "l2"}


def test_the_reference_takes_its_choice_of_experts_from_what_it_is_shown():
    """``build`` names each router's input; shown the program's own, the
    reference reads as alone; shown inputs that move some choices, it
    follows them (and says how many tokens, and how far the inputs lie
    from its own), its scores and weights staying its own."""
    config = _config()
    sizes = _sizes(expert_bias_range=0.5)
    built = config.build("train", 2, sizes)
    assert sorted(built["check_fetches"]) == ["l1", "l2"]
    exe = pt.Executor()
    exe.run(built["startup"], feed={}, fetch_list=[])
    scope = pt.global_scope()
    params = {n: np.asarray(scope.get(n))
              for n in config._parameter_names(sizes)}
    rng = np.random.RandomState(5)
    feed = {k: rng.randint(0, sizes["vocab_size"], (2, sizes["seq_len"]))
            for k in ("ids", "lbl")}
    got = exe.run(built["main"], feed=feed,
                  fetch_list=list(built["check_fetches"].values()))
    shown = dict(zip(built["check_fetches"], got))
    alone = config.reference("train", params, feed, sizes)
    same = config.reference("train", params, feed, sizes, observed=shown)
    assert float(alone[0]) == float(same[0])
    assert all(same[2][k]["tokens_routed_otherwise"] == 0
               and same[2][k]["input_rel_err"] < 1e-5 for k in shown)
    moved = {k: v + 0.3 * rng.randn(*v.shape).astype(v.dtype)
             for k, v in shown.items()}
    other = config.reference("train", params, feed, sizes, observed=moved)
    assert all(other[2][k]["tokens_routed_otherwise"] > 0
               and 0.1 < other[2][k]["input_rel_err"] < 0.5 for k in shown)
    assert float(other[0]) != float(alone[0])
    # a bias the startup program did not draw as asked is refused
    flat = dict(params, **{"lfm2.l1.expert_bias":
                           np.zeros_like(params["lfm2.l1.expert_bias"])})
    with pytest.raises(ValueError, match="expert_bias is not a draw"):
        config.reference("train", flat, feed, sizes)


def test_model_program_validates_clean():
    """Every op of the model has its shape rule and passes it (the share's
    stacks under the wider router, K / V heads that divide Q's)."""
    config = _config()
    built = config.build("train", 2, _sizes())
    for program in (built["main"], built["startup"]):
        report = program.validate()
        assert len(report) == 0, report.render()


def _expert_layer(rng, n=48, d=16, h=24, e=32):
    return {"x": jnp.asarray(rng.randn(n, d), jnp.float32),
            "router": jnp.asarray(rng.randn(d, e) * 0.7, jnp.float32),
            "bias": jnp.asarray(rng.uniform(-0.5, 0.5, e), jnp.float32),
            "gate": jnp.asarray(rng.randn(e, d, h) * 0.4, jnp.float32),
            "up": jnp.asarray(rng.randn(e, d, h) * 0.4, jnp.float32),
            "down": jnp.asarray(rng.randn(e, h, d) * 0.4, jnp.float32)}


def _experts_out(w, x, first, held, bias=True, **route):
    route = {"scoring": "sigmoid", "renormalize": True, **route}
    here = slice(first, first + held)
    return moe_ops._dropless(
        x, w["router"], w["gate"][here], w["up"][here], w["down"][here], 4,
        jax.nn.silu, select_bias=w["bias"] if bias else None,
        expert_offset=first, **route)[0]


@pytest.mark.parametrize("held", [8, 16])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(held):
    """Output and input gradient of the shares at offsets 0, 8, 16, 24 (or
    0, 16) sum to those of the layer that holds all 32 experts: choice and
    renormalisation run over all 32 on every chip, each computes its own
    experts' part, nothing is counted twice or left out."""
    w = _expert_layer(np.random.RandomState(0))
    ct = jnp.asarray(np.random.RandomState(1).randn(*w["x"].shape),
                     jnp.float32)

    def both(first, count):
        out, vjp = jax.vjp(lambda x: _experts_out(w, x, first, count),
                           w["x"])
        return out, vjp(ct)[0]

    whole = both(0, 32)
    parts = [both(first, held) for first in range(0, 32, held)]
    for k in range(2):
        np.testing.assert_allclose(sum(p[k] for p in parts), whole[k],
                                   rtol=1e-5, atol=1e-5)
    # a share is a proper part: no chip's share is the whole or nothing
    assert all(0.05 < float(jnp.linalg.norm(p[0]))
               / float(jnp.linalg.norm(whole[0])) < 0.95 for p in parts)


def test_the_selection_bias_changes_who_is_chosen_and_not_the_weights():
    w = _expert_layer(np.random.RandomState(2))
    score = jax.nn.sigmoid(jnp.dot(w["x"], w["router"],
                                   precision=jax.lax.Precision.HIGHEST))
    plain = np.asarray(jax.lax.top_k(score, 4)[1])
    biased = np.asarray(jax.lax.top_k(score + w["bias"], 4)[1])
    changed = [t for t in range(len(plain))
               if set(plain[t]) != set(biased[t])]
    assert len(changed) > 5

    def oracle(chosen):
        rows = []
        for t in range(w["x"].shape[0]):
            g = score[t, chosen[t]]
            g = g / (jnp.sum(g) + 1e-6)
            rows.append(sum(
                g[k] * ((jax.nn.silu(w["x"][t] @ w["gate"][e])
                         * (w["x"][t] @ w["up"][e])) @ w["down"][e])
                for k, e in enumerate(chosen[t])))
        return jnp.stack(rows)

    with_bias = _experts_out(w, w["x"], 0, 32)
    without = _experts_out(w, w["x"], 0, 32, bias=False)
    # the weights are the chosen experts' scores WITHOUT the bias
    np.testing.assert_allclose(with_bias, oracle(biased), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(without, oracle(plain), rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(with_bias - without)[np.asarray(changed)].max()) \
        > 1e-3
    # and the bias gets no gradient
    grad = jax.grad(lambda b: jnp.sum(moe_ops._dropless(
        w["x"], w["router"], w["gate"], w["up"], w["down"], 4, jax.nn.silu,
        scoring="sigmoid", select_bias=b, renormalize=True)[0]))(w["bias"])
    assert not np.any(np.asarray(grad))


def test_softmax_scores_renormalised_and_scaled():
    w = _expert_layer(np.random.RandomState(3))
    plain = _experts_out(w, w["x"], 0, 32, bias=False, scoring="softmax",
                         renormalize=False)
    probs = jax.nn.softmax(jnp.dot(w["x"], w["router"],
                                   precision=jax.lax.Precision.HIGHEST), -1)
    total = jnp.sum(jax.lax.top_k(probs, 4)[0], axis=-1, keepdims=True)
    scaled = _experts_out(w, w["x"], 0, 32, bias=False, scoring="softmax",
                          renormalize=True, routed_scale=2.5)
    np.testing.assert_allclose(scaled, plain / (total + 1e-6) * 2.5,
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------
def _plain_short_conv(x, w):
    c, taps = w.shape
    v = x[..., :c] * x[..., 2 * c:]
    padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    return x[..., c:2 * c] * sum(
        w[:, j] * padded[:, j:j + v.shape[1]] for j in range(taps))


def _loop_short_conv(x, w):
    """Position by position: c[t] = sum_j w[:, j] * v[t - (L-1) + j]."""
    c, taps = w.shape
    x = np.asarray(x, np.float64)
    v = x[..., :c] * x[..., 2 * c:]
    out = np.zeros(x.shape[:2] + (c,))
    for t in range(x.shape[1]):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                out[:, t] += np.asarray(w)[:, j] * v[:, t - (taps - 1) + j]
    return x[..., c:2 * c] * out


@pytest.mark.parametrize("interpret,shape,taps", [
    (False, (2, 11, 24), 3), (False, (1, 5, 8), 4),
    (True, (2, 192, 256), 3), (True, (1, 128, 128), 2)])
def test_short_conv_equals_its_formula_and_the_loop(interpret, shape, taps):
    """The op through a Program (XLA's formula; the kernels interpreted
    where asked): values against the loop, gradients of X and the filter
    against autodiff of the plain formula."""
    b, t_len, c = shape
    rng = np.random.RandomState(taps)
    x_val = rng.randn(b, t_len, 3 * c).astype("float32")
    weight = rng.randn(b, t_len, c).astype("float32")
    x = layers.data("x", shape=[t_len, 3 * c], dtype="float32")
    # (a zero weight added in front, one number a position and feature, so
    # that the gradient of X can be fetched, summed over the batch)
    shift = LayerHelper("shift").create_parameter(
        pt.ParamAttr(name="shift",
                     initializer=pt.initializer.ConstantInitializer(0.0)),
        shape=[t_len, 3 * c], dtype="float32")
    wt = layers.data("wt", shape=[t_len, c], dtype="float32")
    out = layers.short_conv(layers.elementwise_add(x, shift, axis=1), taps,
                            pt.ParamAttr(name="filter"), interpret=interpret)
    loss = layers.reduce_sum(layers.elementwise_mul(out, wt))
    pt.optimizer.SGD(0.0).minimize(loss)
    before = _routes()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    filt = jnp.asarray(np.asarray(pt.global_scope().get("filter")))
    assert filt.shape == (c, taps)
    got = exe.run(feed={"x": x_val, "wt": weight},
                  fetch_list=[out, "shift@GRAD", "filter@GRAD"])
    route = "interpret" if interpret else "xla"
    assert _routes()[f"route/short_conv:{route}"] \
        - before.get(f"route/short_conv:{route}", 0) == 1
    np.testing.assert_allclose(got[0], _loop_short_conv(x_val, filt),
                               rtol=1e-4, atol=1e-5)
    want = jax.grad(lambda x, w: jnp.sum(_plain_short_conv(x, w) * weight),
                    argnums=(0, 1))(jnp.asarray(x_val), filt)
    np.testing.assert_allclose(got[1], jnp.sum(want[0], axis=0), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(got[2], want[1], rtol=1e-4, atol=1e-3)


def test_short_conv_route_by_shape_dtype_and_backend(monkeypatch):
    route = pallas_kernels.short_conv_route
    assert route((1, 8192, 6144), 3, jnp.float32) == "xla"   # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert route((1, 8192, 6144), 3, jnp.float32) == "pallas"
    assert route((2, 128, 384), 8, jnp.bfloat16) == "pallas"
    assert route((1, 8192, 6144), 9, jnp.float32) == "xla"   # taps
    assert route((1, 8200, 6144), 3, jnp.float32) == "xla"   # rows
    assert route((1, 8192, 192), 3, jnp.float32) == "xla"      # lanes
    assert route((1, 8192, 6144), 3, jnp.float16) == "xla"


# ---------------------------------------------------------------------------
# grouped-query heads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("heads,kv_heads,causal,t_len,d,blocks,bwd", [
    (8, 2, True, 64, 16, (16, 32), "two_pass"),
    (4, 1, True, 64, 16, (16, 32), "two_pass"),
    (6, 3, False, 64, 16, (16, 32), "two_pass"),
    (4, 4, True, 64, 16, (16, 32), "two_pass"),
    # the cell's heads in small (4 query heads to a K/V head of 64), at
    # blocks the one-pass backward takes
    (8, 2, True, 256, 64, (128, 128), "one_pass"),
    (4, 1, False, 256, 64, (128, 128), "one_pass")])
def test_grouped_query_flash_attention_equals_repeated_k_and_v(
        heads, kv_heads, causal, t_len, d, blocks, bwd):
    """The kernels (interpreted), K and V read through the index maps,
    against ``_reference_attention`` on K and V repeated to Q's heads:
    output, dQ, and dK / dV summed over each group, by either route of the
    backward."""
    b = 2
    rng = np.random.RandomState(heads)
    q = jnp.asarray(rng.randn(b, t_len, heads, d), jnp.float32)
    k, v = (jnp.asarray(rng.randn(b, t_len, kv_heads, d), jnp.float32)
            for _ in range(2))
    ct = jnp.asarray(rng.randn(b, t_len, heads, d), jnp.float32)
    group = heads // kv_heads

    def kernels(q, k, v):
        return pallas_kernels.flash_attention(
            q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1],
            interpret=True)

    def repeated(q, k, v):
        def rows(x):
            return jnp.moveaxis(x, 2, 1).reshape(b * heads, t_len, d)
        out = pallas_kernels._reference_attention(
            rows(q), rows(jnp.repeat(k, group, axis=2)),
            rows(jnp.repeat(v, group, axis=2)), causal, d ** -0.5)
        return jnp.moveaxis(out.reshape(b, heads, t_len, d), 1, 2)

    before = _routes().get("route/flash_attention:grouped", 0)
    got, vjp = jax.vjp(kernels, q, k, v)
    assert _routes().get("route/flash_attention:grouped", 0) - before \
        == (group > 1)
    want, ref_vjp = jax.vjp(repeated, q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    before = _routes()
    grads = vjp(ct)
    seen = {r: n - before.get(r, 0) for r, n in _routes().items()
            if r.startswith("route/flash_attention_bwd:")}
    assert {r: n for r, n in seen.items() if n} \
        == {"route/flash_attention_bwd:" + bwd: 1}
    for a, r in zip(grads, ref_vjp(ct)):
        np.testing.assert_allclose(a, r, rtol=1e-4, atol=1e-4)
    # the reference route takes grouped heads too (every other backend)
    np.testing.assert_allclose(
        pallas_kernels.flash_attention(q, k, v, causal=causal), want,
        rtol=1e-5, atol=1e-5)


def test_flash_attention_refuses_heads_that_do_not_divide():
    q = jnp.zeros((1, 32, 6, 8))
    k = jnp.zeros((1, 32, 4, 8))
    with pytest.raises(ValueError, match="do not divide"):
        pallas_kernels.flash_attention(q, k, k)
