"""``models.ouro`` at the benchmark rehearsal's size: against the
configuration's plain float32 reference (loss and EVERY gradient), the
looped Program against the same layers written out pass by pass with
shared names, recomputation on against off, the exit distribution, and
``rope``'s kernel (interpreted) under the recomputed, inlined passes."""
import functools
import importlib
import importlib.util
import json
import os
import sys
from contextlib import contextmanager

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, models, profiler
from paddle_tpu.ops import pallas_kernels

# the module: ``models.ouro`` is the function of the same name
ouro_model = importlib.import_module("paddle_tpu.models.ouro")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = dict(hidden_size=32, num_layers=2, num_heads=2, ffn_size=48,
             total_ut_steps=3, rope_theta=1e6, rms_eps=1e-6)
VOCAB, T_LEN = 64, 16


def _feed(seed=0, batch=2):
    rng = np.random.RandomState(seed)
    return {"ids": rng.randint(0, VOCAB, (batch, T_LEN)),
            "lbl": rng.randint(0, VOCAB, (batch, T_LEN))}


def _data():
    return (layers.data("ids", shape=[T_LEN], dtype="int64"),
            layers.data("lbl", shape=[T_LEN], dtype="int64"))


def _seeded(program, seed=3, matrix_scale=0.3):
    """Every parameter drawn anew, so that norm scales, the gate and its
    bias are not at the values the startup program gives them."""
    rng = np.random.RandomState(seed)
    values = {}
    for p in program.all_parameters():
        scale = matrix_scale if len(p.shape) > 1 else 0.1
        values[p.name] = (rng.standard_normal(p.shape) * scale
                          + (1.0 if p.name.endswith("norm") else 0.0)
                          ).astype(np.float32)
    return values


def _run(loss, fetch, values, feed):
    pt.optimizer.SGD(0.0).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    for name, value in values.items():
        pt.global_scope().set(name, value)
    return exe.run(feed=feed, fetch_list=[loss] + list(fetch))


def _grads(program):
    return [f"{p.name}@GRAD" for p in program.all_parameters()]


def _reference_config():
    spec = importlib.util.spec_from_file_location(
        "ouro_config", os.path.join(ROOT, "chipbench", "configs",
                                    "ouro_2_6b.py"))
    config = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(config)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "ouro_2_6b.json")) as fh:
        sizes = json.load(fh)
    sizes.update(hidden_size=MODEL["hidden_size"], num_hidden_layers=2,
                 num_attention_heads=2, num_key_value_heads=2,
                 intermediate_size=48, total_ut_steps=3, vocab_size=VOCAB,
                 seq_len=T_LEN)
    return config, sizes


def test_ouro_equals_its_reference_on_the_loss_and_every_gradient():
    ids, lbl = _data()
    loss, _ = models.ouro_loss(ids, lbl, VOCAB, **MODEL)
    program = pt.default_main_program()
    # the layers stand ONCE: one loop over a block of 2 layers, a stretch
    # each, and one head weight behind three head products
    kinds = [op.type for b in program.blocks for op in b.ops]
    assert kinds.count("repeat") == 1
    assert kinds.count("flash_attention") == MODEL["num_layers"]
    assert kinds.count("recompute") == MODEL["num_layers"] + 3
    assert kinds.count("softmax_with_cross_entropy") == 3
    names = [p.name for p in program.all_parameters()]
    assert len(names) == len(set(names)) == 2 * 11 + 5

    values, feed = _seeded(program), _feed()
    got = _run(loss, _grads(program), values, feed)
    config, sizes = _reference_config()
    assert sorted(config._parameter_names(sizes)) == sorted(names)
    sizes["check_params"] = names
    ref_loss, ref_grads = config.reference("train", values, feed, sizes)
    np.testing.assert_allclose(got[0], ref_loss, rtol=1e-5)
    for name, grad in zip(names, got[1:]):
        assert np.linalg.norm(grad) > 0, name
        np.testing.assert_allclose(
            grad, ref_grads[name], rtol=2e-4,
            atol=2e-5 * float(np.abs(ref_grads[name]).max()), err_msg=name)


def test_rope_kernel_under_recomputed_inlined_passes_equals_the_reference(
        monkeypatch):
    """2 heads of 128 at 256 positions, which ``rope``'s kernel takes
    (interpreted here; the test steers the route): q and k of 2 layers in 3
    inlined passes turn in the kernel, its custom_vjp under every layer's
    ``jax.checkpoint``, and the loss and EVERY gradient are still the
    reference's."""
    monkeypatch.setitem(MODEL, "hidden_size", 256)
    monkeypatch.setattr(sys.modules[__name__], "T_LEN", 256)
    monkeypatch.setattr(pallas_kernels, "rope_route", functools.partial(
        pallas_kernels.rope_route, interpret=True))

    def routes():
        snap = profiler.compile_stats().snapshot()
        return [snap.get(k, 0) for k in ("route/rope:interpret",
                                         "route/rope:reference",
                                         "route/recompute:checkpoint")]

    ids, lbl = _data()
    loss, _ = models.ouro_loss(ids, lbl, VOCAB, **MODEL)
    program = pt.default_main_program()
    names = [p.name for p in program.all_parameters()]
    # (matrices 8 times as wide as this file's other tests': a smaller draw)
    values, feed = _seeded(program, matrix_scale=0.1), _feed(4, batch=1)
    before = routes()
    got = _run(loss, _grads(program), values, feed)
    assert [a - b for a, b in zip(routes(), before)] == [
        MODEL["num_layers"] * MODEL["total_ut_steps"] * 2, 0,
        MODEL["num_layers"] * MODEL["total_ut_steps"] + 3]
    config, sizes = _reference_config()
    sizes["check_params"] = names
    ref_loss, ref_grads = config.reference("train", values, feed, sizes)
    np.testing.assert_allclose(got[0], ref_loss, rtol=1e-5)
    for name, grad in zip(names, got[1:]):
        assert np.linalg.norm(grad) > 0, name
        np.testing.assert_allclose(
            grad, ref_grads[name], rtol=2e-4,
            atol=2e-5 * float(np.abs(ref_grads[name]).max()), err_msg=name)


def _written_out(ids, lbl):
    """The same model with no loop: every pass's layers appended again,
    under the names the first pass gave them."""
    h = layers.embedding(ids, size=[VOCAB, MODEL["hidden_size"]],
                         param_attr=pt.ParamAttr(name="ouro.embed"))
    hs = []
    for _ in range(MODEL["total_ut_steps"]):
        for i in range(MODEL["num_layers"]):
            h = ouro_model._layer(h, MODEL["hidden_size"], MODEL["num_heads"],
                                  MODEL["ffn_size"], MODEL["rope_theta"],
                                  MODEL["rms_eps"], f"ouro.l{i}")
        h = layers.rms_norm(h, MODEL["rms_eps"],
                            pt.ParamAttr(name="ouro.final_norm"))
        hs.append(h)
    probs = ouro_model.exit_distribution(
        [ouro_model._exit_gate(h_t, "ouro") for h_t in hs[:-1]])
    total = None
    for h_t, p in zip(hs, probs):
        ce = layers.softmax_with_cross_entropy(
            layers.reshape(ouro_model._head(h_t, VOCAB, "ouro"), [-1, VOCAB]),
            layers.reshape(lbl, [-1, 1]))
        term = ouro_model._exit_term(p, ce, 0.05)
        total = term if total is None else layers.elementwise_add(total, term)
    return layers.mean(total)


def _looped_then(other):
    """(looped model's loss and gradients, ``other``'s) on the same
    weights and batch; ``other`` builds a loss from (ids, lbl)."""
    ids, lbl = _data()
    loss, _ = models.ouro_loss(ids, lbl, VOCAB, **MODEL)
    program = pt.default_main_program()
    values, feed = _seeded(program), _feed(1)
    names = [p.name for p in program.all_parameters()]
    looped = _run(loss, _grads(program), values, feed)
    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    loss = other(*_data())
    program = pt.default_main_program()
    assert sorted(p.name for p in program.all_parameters()) == sorted(names)
    return looped, _run(loss, [f"{n}@GRAD" for n in names], values, feed)


def test_looped_program_equals_the_layers_written_out_with_shared_names():
    looped, flat = _looped_then(_written_out)
    kinds = [op.type for op in pt.default_main_program().global_block().ops]
    assert "repeat" not in kinds and "recompute" not in kinds
    assert kinds.count("flash_attention") == 3 * MODEL["num_layers"]
    for a, b in zip(looped, flat):      # the same sums in another order
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(np.abs(b).max()))


def test_recomputation_changes_no_loss_and_no_gradient(monkeypatch):
    def kept(ids, lbl):
        @contextmanager
        def keep():
            yield
        monkeypatch.setattr(layers, "recompute", keep)
        return models.ouro_loss(ids, lbl, VOCAB, **MODEL)[0]

    before = profiler.compile_stats().snapshot().get(
        "route/recompute:checkpoint", 0)
    marked, plain = _looped_then(kept)
    kinds = [op.type for b in pt.default_main_program().blocks
             for op in b.ops]
    assert "recompute" not in kinds and "repeat" in kinds
    # 2 layers traced in 3 inlined passes, and 3 heads; none when unmarked
    assert profiler.compile_stats().snapshot()[
        "route/recompute:checkpoint"] - before == 3 * 2 + 3
    # the forward pass is the same program: the loss is the same bits.  A
    # gradient that passes through a recomputed layer is the same sum
    # through other fusions (XLA compiles the second forward into the
    # backward pass), equal to float32 rounding and not to the bit.
    np.testing.assert_array_equal(marked[0], plain[0])
    for a, b in zip(marked[1:], plain[1:]):
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=4e-6 * float(np.abs(b).max()))


@pytest.mark.parametrize("bias,first,last", [(0.0, None, None),
                                             (-1e4, 0.0, 1.0),
                                             (1e4, 1.0, 0.0)])
def test_exit_distribution_sums_to_one_and_follows_a_forced_gate(
        bias, first, last):
    ids, lbl = _data()
    loss, probs = models.ouro_loss(ids, lbl, VOCAB, **MODEL)
    program = pt.default_main_program()
    values = _seeded(program)
    if bias:
        values["ouro.exit_gate"] = np.zeros_like(values["ouro.exit_gate"])
        values["ouro.exit_gate_bias"] = np.full([1], bias, np.float32)
    got = _run(loss, probs, values, _feed(2))
    assert np.isfinite(got[0])
    p = np.concatenate(got[1:], axis=1)                  # [N, R]
    assert p.shape == (2 * T_LEN, MODEL["total_ut_steps"])
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-6)
    if bias:
        np.testing.assert_array_equal(p[:, 0], first)
        np.testing.assert_array_equal(p[:, -1], last)
        np.testing.assert_array_equal(p[:, 1:-1], 0.0)
    else:
        assert p.min() > 0.0


def test_repeat_stacks_what_each_pass_leaves_and_sums_the_gradients():
    """h -> h W three times: the passes leave x W, x W^2, x W^3, stacked,
    and W, read by every pass, gets the gradient of all three."""
    import jax
    import jax.numpy as jnp

    x = layers.data("x", shape=[4], dtype="float32")
    loop = layers.Repeat(3)
    with loop.block():
        h = loop.carry(x)
        new = layers.fc(h, size=4, param_attr=pt.ParamAttr(name="w"),
                        bias_attr=False)
        loop.update(h, new)
        loop.output(new)
    hs = loop()
    assert hs.shape == (3, -1, 4)
    loss = layers.mean(hs)
    program = pt.default_main_program()
    assert [op.type for op in program.global_block().ops][:1] == ["repeat"]
    assert [op.type for op in program.blocks[1].ops] == ["mul"]
    rng = np.random.RandomState(0)
    w = rng.standard_normal((4, 4)).astype(np.float32) * 0.5
    feed = {"x": rng.standard_normal((5, 4)).astype(np.float32)}
    got = _run(loss, [hs, "w@GRAD"], {"w": w}, feed)

    def plain(w):
        a = feed["x"] @ w
        b = a @ w
        return jnp.stack([a, b, b @ w])

    np.testing.assert_allclose(got[1], plain(w), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        got[2], jax.grad(lambda w: jnp.mean(plain(w)))(w), rtol=1e-5,
        atol=1e-6)


def test_repeat_refuses_a_carry_nothing_updates_and_no_pass_at_all():
    with pytest.raises(ValueError, match="at least 1"):
        layers.Repeat(0)
    x = layers.data("x", shape=[4], dtype="float32")
    loop = layers.Repeat(2)
    with pytest.raises(ValueError, match="no update"):
        with loop.block():
            loop.output(layers.scale(loop.carry(x), 2.0))
