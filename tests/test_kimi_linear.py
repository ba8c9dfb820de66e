"""``models.kimi_linear`` and what it brought: the model against the
benchmark's plain float32 reference (loss and every gradient), with the
reference's recurrence over positions against the program's chunked
``gated_delta_scan`` and its plain softmax against ``flash_attention`` at a
value head size of its own; the share test (the parts the 32 ranks give,
the shared expert counted once, add up to the uncut layer); the startup
program's vectors; the reference's controls and what it is shown."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.ops import moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kimi_linear_48b_a3b"
SILU = moe_ops._ACTS["silu"]


def _config():
    spec = importlib.util.spec_from_file_location(
        NAME + "_config",
        os.path.join(ROOT, "chipbench", "configs", NAME + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(**over):
    """The configuration's file at the cell's rehearsal sizes (published
    layers 1-5, ``KKKAK``, the first with the dense MLP)."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           NAME + ".json")) as fh:
        sizes = json.load(fh)
    with open(os.path.join(ROOT, "chipbench", "workloads",
                           "kimi-linear-train-scan.json")) as fh:
        rehearse = json.load(fh)["rehearse"]["sizes"]
    sizes["linear_attn_config"] = {**sizes["linear_attn_config"],
                                   **rehearse.pop("linear_attn_config")}
    sizes.update(rehearse)
    sizes.update(recompute=False)
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
@pytest.mark.parametrize("rank,recompute,chunk", [
    (1, False, 32), (0, True, 16), (3, [0, 3], 64)])
def test_model_equals_the_reference_loss_and_every_gradient(rank, recompute,
                                                            chunk):
    config = _config()
    sizes = _sizes(recompute=recompute, expert_parallel_rank=rank,
                   chunk_size=chunk)
    before = _routes()
    built, exe, params, feed = _started(config, sizes)
    assert config._layers_run(sizes) == "KKKAK"
    kinds = [op.type for b in built["main"].blocks for op in b.ops]
    assert kinds.count("gated_delta_scan") == 4
    assert kinds.count("short_conv") == 4
    assert kinds.count("flash_attention") == 1 and "rope" not in kinds
    assert kinds.count("moe") == 4
    trainable = [p.name for p in built["main"].global_block()
                 .all_parameters() if p.trainable]
    # (the correction biases are parameters without a gradient)
    biases = [f"kimi.l{i}.expert_bias" for i in range(1, 5)]
    assert sorted(trainable + biases) \
        == sorted(config._parameter_names(sizes))
    got = exe.run(built["main"], feed=feed, fetch_list=[built["loss"]] + [
        f"{n}@GRAD" for n in trainable])
    sizes["check_params"] = trainable
    ref_loss, ref_grads, saw = config.reference("train", params, feed, sizes)
    np.testing.assert_allclose(got[0], ref_loss, rtol=2e-6)
    assert float(config.reference("loss", params, feed, sizes)) \
        == pytest.approx(float(ref_loss), rel=1e-6)
    for name, grad in zip(trainable, got[1:]):
        want = np.asarray(ref_grads[name])
        assert np.abs(want).max() > 0, name
        # (as the chip's check reads them: a norm of the difference over
        # the reference's; an entry near 0 is rounding alone)
        assert np.linalg.norm(grad - want) / np.linalg.norm(want) < 1e-4, \
            name
    seen = {k: v - before.get(k, 0) for k, v in _routes().items()}
    assert seen["route/gated_delta_scan:xla"] == 4
    assert seen["route/short_conv:xla"] == 4
    assert seen["route/flash_attention:value_dims"] == 1
    for route in ("dropless", "sigmoid", "share", "gated_pair", "shared"):
        assert seen["route/moe:" + route] == 4, route
    # a recomputed layer is two stretches, its mixer's and its ffn's
    assert seen.get("route/recompute:checkpoint", 0) == 2 * (
        5 if recompute is True else len(recompute or ()))
    assert saw["rows_bound"] == 2 * 64 * 2
    assert all(0 < saw[f"l{i}"]["rows_held"] < saw["rows_bound"]
               for i in range(1, 5))


def test_the_startup_program_sets_the_vectors_and_the_reference_holds_them():
    """``A_log`` (32 values evenly spaced from 1 to 16, logged) and
    ``dt_bias`` (the inverse softplus of steps log-spaced from 0.001 to 0.1)
    of every KDA layer, and every expert layer's correction bias a draw in
    +-``expert_bias_range``: set by the startup program, the same whatever
    the seed; the reference refuses weights in which they are anything
    else."""
    config = _config()
    sizes = _sizes()
    _, _, params, feed = _started(config, sizes)
    np.testing.assert_allclose(np.exp(params["kimi.l0.A_log"]),
                               np.linspace(1, 16, 2), rtol=1e-6)
    step = np.log1p(np.exp(params["kimi.l4.dt_bias"]))
    assert step.shape == (32,)
    np.testing.assert_allclose(step[[0, -1]], [0.001, 0.1], rtol=1e-4)
    assert "kimi.l3.A_log" not in params               # the MLA layer's
    bias = params["kimi.l2.expert_bias"]
    assert bias.shape == (8,) and bias.dtype == np.float32
    assert 0 < np.abs(bias).max() <= 0.05
    for name in ("A_log", "dt_bias"):
        wrong = dict(params, **{f"kimi.l1.{name}":
                                params[f"kimi.l1.{name}"] + 0.01})
        with pytest.raises(ValueError, match=f"l1.{name} is not what"):
            config.reference("loss", wrong, feed, sizes)
    flat = dict(params, **{"kimi.l2.expert_bias": np.zeros_like(bias)})
    with pytest.raises(ValueError, match="expert_bias is not a draw"):
        config.reference("train", flat, feed, sizes)


def test_model_program_validates_clean():
    """Every op of the model has its shape rule and passes it (the delta
    rule, attention at a value head size of its own, the share's stacks
    under the wider router, the shared expert)."""
    config = _config()
    built = config.build("train", 2, _sizes(recompute=True))
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
    assert sorted(built["check_fetches"]) == ["l1", "l2", "l3", "l4"]
    got = exe.run(built["main"], feed=feed,
                  fetch_list=list(built["check_fetches"].values()))
    shown = dict(zip(built["check_fetches"], got))
    alone = config.reference("train", params, feed, sizes)
    same = config.reference("train", params, feed, sizes, observed=shown)
    assert float(alone[0]) == float(same[0])
    assert all(same[2][f"l{i}"]["tokens_routed_otherwise"] == 0
               and same[2][f"l{i}"]["input_rel_err"] < 1e-5
               for i in range(1, 5))
    rng = np.random.RandomState(6)
    moved = {k: v + 0.3 * rng.randn(*v.shape).astype(v.dtype)
             for k, v in shown.items()}
    other = config.reference("train", params, feed, sizes, observed=moved)
    assert other[2]["l2"]["tokens_routed_otherwise"] > 0
    assert 0.1 < other[2]["l2"]["input_rel_err"] < 0.5
    assert float(other[0]) != float(alone[0])


@pytest.mark.parametrize("control,moves", [
    ({"lower": "all"}, (0.005, 2.0)),
    ({"fault": "no_correction"}, (0.05, 50.0)),
    ({"fault": "scalar_decay"}, (0.02, 50.0)),
    ({"sizes": {"expert_parallel_rank": 0}}, (0.05, 50.0)),
    ({"sizes": {"moe_renormalize": False}}, (0.05, 50.0))])
def test_a_control_moves_the_reference(control, moves):
    """Each control the chip's check has to FAIL parts the reference from
    itself: the gradient it bears on most moves by a share inside
    ``moves``."""
    config = _config()
    sizes = _sizes()
    _, _, params, feed = _started(config, sizes, seed=3)
    sizes["check_params"] = [n for n in config._parameter_names(sizes)
                             if not n.endswith("expert_bias")]
    sound = config.reference("train", params, feed, sizes)[1]
    other = config.reference("train", params, feed, sizes,
                             control=control)[1]
    apart = max(float(np.linalg.norm(other[n] - sound[n])
                      / np.linalg.norm(sound[n])) for n in sound
                if np.linalg.norm(sound[n]) > 0)
    assert moves[0] < apart < moves[1], apart
    with pytest.raises(ValueError, match="no control"):
        config.reference("train", params, feed, sizes,
                         control={"fault": "something"})


# ---------------------------------------------------------------------------
# the share: 32 ranks of 8 experts under a 256-wide router
# ---------------------------------------------------------------------------
def _expert_layer(rng, n=40, d=16, h=8, hs=12, e=256):
    def m(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.4, jnp.float32)

    return {"x": jnp.asarray(rng.randn(n, d), jnp.float32),
            "router": m(d, e),
            "bias": jnp.asarray(rng.uniform(-0.05, 0.05, e), jnp.float32),
            "gate": m(e, d, h), "up": m(e, d, h), "down": m(e, h, d),
            "shared_gate": m(d, hs), "shared_up": m(d, hs),
            "shared_down": m(hs, d)}


def _experts_out(w, x, first, held, shared=True):
    here = slice(first, first + held)
    return moe_ops._dropless(
        x, w["router"], w["gate"][here], w["up"][here], w["down"][here], 8,
        SILU, scoring="sigmoid", select_bias=w["bias"], renormalize=True,
        routed_scale=2.446, expert_offset=first,
        shared=(w["shared_gate"], w["shared_up"], w["shared_down"])
        if shared else None)[0]


def test_the_32_shares_add_up_to_the_uncut_layer_the_shared_expert_once():
    """Output and input gradient of the routed parts the 32 ranks give (8
    experts each of the router's 256, SiLU-gated), plus the shared expert
    counted ONCE (every chip computes it alike), sum to those of the layer
    that holds all 256 with its shared expert: choice and renormalisation
    run over all 256 on every chip, each computes its own experts' part,
    nothing is counted twice or left out."""
    w = _expert_layer(np.random.RandomState(0))
    ct = jnp.asarray(np.random.RandomState(1).randn(*w["x"].shape),
                     jnp.float32)

    def both(fn):
        out, vjp = jax.vjp(fn, w["x"])
        return out, vjp(ct)[0]

    whole = both(lambda x: _experts_out(w, x, 0, 256))
    routed = [both(lambda x, f=first: _experts_out(w, x, f, 8, shared=False))
              for first in range(0, 256, 8)]
    once = both(lambda x: (SILU(x @ w["shared_gate"]) * (x @ w["shared_up"]))
                @ w["shared_down"])
    for k in range(2):
        np.testing.assert_allclose(sum(p[k] for p in routed) + once[k],
                                   whole[k], rtol=1e-5, atol=1e-5)
    # a share is a proper part: no rank's routed part is the whole
    assert all(float(jnp.linalg.norm(p[0]))
               / float(jnp.linalg.norm(whole[0])) < 0.95 for p in routed)
    assert sum(float(jnp.linalg.norm(p[0])) > 0 for p in routed) > 16
