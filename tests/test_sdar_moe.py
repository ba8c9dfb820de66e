"""``models.sdar_moe`` and what it brought: the model trained by block
diffusion against the benchmark's plain float32 reference (loss and every
gradient), the noise (the rate of each block on the grid, the mask token,
the weights), the share test (the parts the 8 ranks give add up to the
uncut layer), the reference's controls and what it is shown."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.ops import moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "sdar_30b_a3b"
SILU = moe_ops._ACTS["silu"]


def _config():
    spec = importlib.util.spec_from_file_location(
        NAME + "_config",
        os.path.join(ROOT, "chipbench", "configs", NAME + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _sizes(**over):
    """The configuration's file at the cell's rehearsal sizes (4 layers, 8
    experts under their router, 4 a token, 32 tokens, blocks of 4)."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           NAME + ".json")) as fh:
        sizes = json.load(fh)
    with open(os.path.join(ROOT, "chipbench", "workloads",
                           "sdar-train-scan.json")) as fh:
        sizes.update(json.load(fh)["rehearse"]["sizes"])
    sizes.update(recompute=False)
    sizes.update(over)
    return sizes


def _routes():
    return {k: v for k, v in profiler.compile_stats().snapshot().items()
            if k.startswith("route/")}


def _feed(sizes, batch, seed):
    rng = np.random.RandomState(seed)
    t_len, grid = sizes["seq_len"], sizes["noise_grid"]
    return {"ids": rng.randint(0, sizes["vocab_size"] - 1, (batch, t_len)),
            "level": rng.randint(0, grid,
                                 (batch, t_len // sizes["block_size"])),
            "draw": rng.randint(0, grid, (batch, t_len))}


def _started(config, sizes, batch=2, seed=0):
    """(built, exe, host parameters, a seeded feed) after the startup
    program."""
    built = config.build("train", batch, sizes)
    exe = pt.Executor()
    exe.run(built["startup"], feed={}, fetch_list=[])
    scope = pt.global_scope()
    params = {n: np.asarray(scope.get(n))
              for n in config._parameter_names(sizes)}
    return built, exe, params, _feed(sizes, batch, seed)


# ---------------------------------------------------------------------------
# the model against the configuration's reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("experts,recompute,block", [
    (8, False, 4), (8, True, 4), (16, [0, 3], 16), (6, False, 1)])
def test_model_equals_the_reference_loss_and_every_gradient(experts,
                                                            recompute,
                                                            block):
    config = _config()
    sizes = _sizes(recompute=recompute, num_experts=experts,
                   block_size=block)
    before = _routes()
    built, exe, params, feed = _started(config, sizes)
    kinds = [op.type for b in built["main"].blocks for op in b.ops]
    assert kinds.count("block_diffusion_attention") == 4
    assert kinds.count("block_diffusion_noise") == 1
    assert kinds.count("rope") == 8 and kinds.count("moe") == 4
    assert "flash_attention" not in kinds
    trainable = [p.name for p in built["main"].global_block()
                 .all_parameters() if p.trainable]
    assert sorted(trainable) == sorted(config._parameter_names(sizes))
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
    assert seen["route/block_diffusion_attention:xla"] == 4
    assert seen["route/flash_attention:reference"] == 4
    for route in ("dropless", "gated_pair"):
        assert seen["route/moe:" + route] == 4, route
    assert not seen.get("route/moe:sigmoid")
    assert not seen.get("route/moe:share")
    assert seen.get("route/recompute:checkpoint", 0) == 2 * (
        4 if recompute is True else len(recompute or ()))
    # both streams of both sequences are routed
    assert saw["tokens"] == 2 * 2 * 32
    assert saw["rows"] == saw["tokens"] * 4


def test_model_program_validates_clean():
    """Every op of the model has its shape rule and passes it (the noise,
    the block-masked attention over the joined streams, the experts'
    stacks under their router)."""
    config = _config()
    built = config.build("train", 2, _sizes(recompute=True))
    for program in (built["main"], built["startup"]):
        report = program.validate()
        assert len(report) == 0, report.render()


def test_the_reference_takes_its_choice_of_experts_from_what_it_is_shown():
    """``build`` names each router's input ([2B, L, D], a sequence's two
    streams side by side); shown the program's own, the reference reads as
    alone; shown inputs that move some choices, it follows them and says
    how many tokens and how far."""
    config = _config()
    sizes = _sizes()
    built, exe, params, feed = _started(config, sizes, seed=5)
    assert sorted(built["check_fetches"]) == ["l0", "l1", "l2", "l3"]
    got = exe.run(built["main"], feed=feed,
                  fetch_list=list(built["check_fetches"].values()))
    shown = dict(zip(built["check_fetches"], got))
    assert shown["l0"].shape == (4, 32, 32)
    alone = config.reference("train", params, feed, sizes)
    same = config.reference("train", params, feed, sizes, observed=shown)
    assert float(alone[0]) == pytest.approx(float(same[0]), rel=1e-6)
    assert all(same[2][f"l{i}"]["tokens_routed_otherwise"] == 0
               and same[2][f"l{i}"]["input_rel_err"] < 1e-5
               for i in range(4))
    rng = np.random.RandomState(6)
    moved = {k: v + 0.3 * rng.randn(*v.shape).astype(v.dtype)
             for k, v in shown.items()}
    other = config.reference("train", params, feed, sizes, observed=moved)
    assert other[2]["l2"]["tokens_routed_otherwise"] > 0
    assert 0.1 < other[2]["l2"]["input_rel_err"] < 0.5
    assert float(other[0]) != float(alone[0])


@pytest.mark.parametrize("control,moves", [
    ({"lower": "all"}, (0.003, 2.0)),
    ({"fault": "no_reweight"}, (0.05, 50.0)),
    ({"fault": "own_clean_block"}, (0.05, 50.0)),
    ({"fault": "causal_in_block"}, (0.05, 50.0)),
    ({"sizes": {"rope_theta": 10000}}, (0.05, 50.0)),
    ({"sizes": {"norm_topk_prob": False}}, (0.05, 50.0))])
def test_a_control_moves_the_reference(control, moves):
    """Each control the chip's check has to FAIL parts the reference from
    itself: the gradient it bears on most moves by a share inside
    ``moves``."""
    config = _config()
    sizes = _sizes()
    _, _, params, feed = _started(config, sizes, seed=3)
    sizes["check_params"] = config._parameter_names(sizes)
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


@pytest.mark.parametrize("interpret,route", [(True, "kernel"),
                                              (False, "xla")])
def test_the_op_counts_its_route_and_both_give_the_formula(interpret, route):
    """``block_diffusion_attention`` as a program op: with ``interpret`` it
    takes the kernels (interpreted), else on this backend the formula;
    either way it counts ``route/block_diffusion_attention:<route>`` once
    and gives the masked softmax the kernels' tests hold."""
    from paddle_tpu.ops import pallas_kernels

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    t_len, heads, kv, d = 32, 4, 2, 16
    rng = np.random.RandomState(2)
    arrays = {n: rng.randn(1, 2 * t_len, h, d).astype(np.float32)
              for n, h in (("q", heads), ("k", kv), ("v", kv))}
    ins = {n: layers.data(n, shape=[2 * t_len, a.shape[2], d],
                          dtype="float32") for n, a in arrays.items()}
    out = layers.block_diffusion_attention(ins["q"], ins["k"], ins["v"], 4,
                                           block_q=16, block_k=16,
                                           interpret=interpret)
    before = _routes()
    got = pt.Executor().run(feed=arrays, fetch_list=[out])[0]
    seen = {k: v - before.get(k, 0) for k, v in _routes().items()
            if k.startswith("route/block_diffusion_attention")
            and v != before.get(k, 0)}
    assert seen == {"route/block_diffusion_attention:" + route: 1}

    def heads_first(a):
        return jnp.moveaxis(jnp.asarray(a[0]), 1, 0)

    want = pallas_kernels._reference_attention(
        *(heads_first(arrays[n]) for n in "qkv"),
        pallas_kernels.BlockDiffusionMask(4, t_len), d ** -0.5)
    np.testing.assert_allclose(got[0], np.moveaxis(np.asarray(want), 0, 1),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the noise
# ---------------------------------------------------------------------------
def _noised(ids, level, draw, block, mask_id, grid=1024):
    """(x_t, weight) of ``layers.block_diffusion_noise`` run as a program."""
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    t_len = ids.shape[1]
    x = layers.data("ids", shape=[t_len], dtype="int64")
    d = layers.data("level", shape=[t_len // block], dtype="int64")
    u = layers.data("draw", shape=[t_len], dtype="int64")
    out, weight = layers.block_diffusion_noise(x, d, u, block, mask_id, grid)
    return pt.Executor().run(feed={"ids": ids, "level": level, "draw": draw},
                             fetch_list=[out, weight])


def test_each_block_is_masked_at_its_rate_and_weighted_by_its_inverse():
    """Every level d of the grid (sequence d of a batch of 1024, all its
    blocks at d) against every draw u of the grid (token u of a sequence):
    exactly d + 1 of the 1024 tokens are masked, P(m) = t = (d + 1) / 1024
    with no rounding, each masked token weighted 1024 / (d + 1) and every
    other 0; a token not masked keeps its id.  Then random levels and draws:
    token i is masked exactly where draw_i <= the level of its block."""
    grid, block = 1024, 4
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 99, (grid, grid))
    level = np.repeat(np.arange(grid)[:, None], grid // block, axis=1)
    draw = np.tile(np.arange(grid), (grid, 1))
    out, weight = _noised(ids, level, draw, block, 99)
    masked = out == 99
    np.testing.assert_array_equal(masked.sum(axis=1), np.arange(grid) + 1)
    t = (np.arange(grid)[:, None] + 1) / grid
    np.testing.assert_allclose(weight, np.where(masked, 1 / t, 0.0),
                               rtol=1e-6)
    np.testing.assert_array_equal(out[~masked], ids[~masked])
    level = rng.randint(0, grid, (3, 64 // block))
    draw = rng.randint(0, grid, (3, 64))
    out, weight = _noised(ids[:3, :64], level, draw, block, 99)
    np.testing.assert_array_equal(
        out == 99, draw <= np.repeat(level, block, axis=1))


def test_the_mask_is_never_a_clean_id_and_the_loss_is_the_weighted_mean():
    """The cell's feeds draw clean ids below the mask (the last row of the
    slice); the program's loss is (1 / (B L)) sum of weight * CE over the
    noisy stream, 0 where nothing is masked."""
    from chipbench.lib import weights

    config = _config()
    sizes = _sizes()
    built = config.build("train", 2, sizes)
    assert built["feeds"]["ids"]["high"] == sizes["vocab_size"] - 1
    feeds = weights.make_feeds(built["feeds"], 4, 2400000011)
    ids = np.asarray(feeds["ids"])
    assert ids.max() < sizes["vocab_size"] - 1 and ids.min() >= 0
    exe = pt.Executor()
    exe.run(built["startup"], feed={}, fetch_list=[])
    feed = {k: np.asarray(v)[:2] for k, v in feeds.items()}
    none = dict(feed, level=np.zeros_like(feed["level"]),
                draw=np.full_like(feed["draw"], 5))      # u > d: unmasked
    assert float(exe.run(built["main"], feed=none,
                         fetch_list=[built["loss"]], is_test=True)[0]) == 0.0


# ---------------------------------------------------------------------------
# the share: 8 ranks of 16 experts under a 128-wide softmax router
# ---------------------------------------------------------------------------
def _expert_layer(rng, n=40, d=16, h=8, e=128):
    def m(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.4, jnp.float32)

    return {"x": jnp.asarray(rng.randn(n, d), jnp.float32),
            "router": m(d, e), "gate": m(e, d, h), "up": m(e, d, h),
            "down": m(e, h, d)}


def _experts_out(w, x, first, held):
    here = slice(first, first + held)
    return moe_ops._dropless(
        x, w["router"], w["gate"][here], w["up"][here], w["down"][here], 8,
        SILU, renormalize=True, expert_offset=first)[0]


def test_the_8_shares_add_up_to_the_uncut_layer():
    """Output and input gradient of the parts the 8 ranks give (16 experts
    each of the router's 128, softmax top-8 renormalised, no shared expert)
    sum to those of the layer that holds all 128: the softmax, the choice
    and the renormalisation run over all 128 on every chip, each computes
    its own experts' part, nothing is counted twice or left out."""
    w = _expert_layer(np.random.RandomState(0))
    ct = jnp.asarray(np.random.RandomState(1).randn(*w["x"].shape),
                     jnp.float32)

    def both(fn):
        out, vjp = jax.vjp(fn, w["x"])
        return out, vjp(ct)[0]

    whole = both(lambda x: _experts_out(w, x, 0, 128))
    parts = [both(lambda x, f=first: _experts_out(w, x, f, 16))
             for first in range(0, 128, 16)]
    for k in range(2):
        np.testing.assert_allclose(sum(p[k] for p in parts), whole[k],
                                   rtol=1e-5, atol=1e-5)
    # a share is a proper part: no rank's is the whole
    assert all(float(jnp.linalg.norm(p[0]))
               / float(jnp.linalg.norm(whole[0])) < 0.95 for p in parts)
    assert sum(float(jnp.linalg.norm(p[0])) > 0 for p in parts) == 8
