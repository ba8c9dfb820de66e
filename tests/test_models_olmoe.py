"""``models.olmoe`` at the benchmark rehearsal's size: the program with its
attention through the Pallas flash kernels (interpreted) equals the program
with attention on the reference route, logits and training loss, and both
equal the benchmark's plain float32 reference of the model's equations; at
a head size and length ``rope``'s kernel takes, the program through that
kernel (interpreted) equals the program through the formula."""
import functools
import importlib.util
import json
import os
import sys

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers, models, profiler
from paddle_tpu.ops import pallas_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZES = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
             num_experts=8, experts_per_tok=2, expert_width=16)
T_LEN = 16


def _build():
    ids = layers.data("ids", shape=[T_LEN], dtype="int64")
    lbl = layers.data("lbl", shape=[T_LEN], dtype="int64")
    logits, aux_losses = models.olmoe(ids, **SIZES)
    loss = layers.mean(layers.softmax_with_cross_entropy(
        layers.reshape(logits, [-1, SIZES["vocab_size"]]),
        layers.reshape(lbl, [-1, 1])))
    for aux, z in aux_losses:
        loss = layers.elementwise_add(loss, layers.elementwise_add(
            layers.scale(aux, scale=0.01), layers.scale(z, scale=0.001)))
    pt.optimizer.SGD(0.0).minimize(loss)
    return logits, loss


def test_olmoe_through_the_flash_kernels_equals_the_reference_route():
    logits, loss = _build()
    program = pt.default_main_program()
    kinds = [op.type for op in program.global_block().ops]
    assert kinds.count("moe") == 2 and kinds.count("flash_attention") == 2
    assert kinds.count("rope") == 4 and kinds.count("rms_norm") == 9
    rng = np.random.RandomState(0)
    feed = {"ids": rng.randint(0, 64, (2, T_LEN)),
            "lbl": rng.randint(0, 64, (2, T_LEN))}
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    fetch = [logits, loss, "olmoe.l0.wq@GRAD", "olmoe.l1.experts_up@GRAD"]

    def routes():
        return {k.split(":", 1)[1]: v for k, v in
                profiler.compile_stats().snapshot().items()
                if k.startswith("route/flash_attention:")}

    before = routes()
    plain = exe.run(feed=feed, fetch_list=fetch)
    assert routes().get("reference", 0) - before.get("reference", 0) == 2
    for op in program.global_block().ops:
        if op.type == "flash_attention":
            op.attrs["interpret"] = True
    program._bump_version()
    fused = exe.run(feed=feed, fetch_list=fetch)
    assert routes().get("interpret", 0) - before.get("interpret", 0) == 2
    for a, b in zip(fused, plain):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)

    # and the benchmark's reference, written from the equations
    spec = importlib.util.spec_from_file_location(
        "olmoe_config", os.path.join(ROOT, "chipbench", "configs",
                                     "olmoe_1b_7b.py"))
    config = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(config)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "olmoe_1b_7b.json")) as fh:
        sizes = json.load(fh)
    sizes.update(hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
                 num_experts=8, num_experts_per_tok=2, intermediate_size=16,
                 vocab_size=64, seq_len=T_LEN,
                 check_params=["olmoe.l0.wq", "olmoe.l1.experts_up"])
    params = {n: np.asarray(pt.global_scope().get(n))
              for n in config._parameter_names(sizes)}
    ref_loss, ref_grads = config.reference("train", params, feed, sizes)
    np.testing.assert_allclose(plain[1], ref_loss, rtol=1e-5)
    np.testing.assert_allclose(plain[2], ref_grads["olmoe.l0.wq"],
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(plain[3], ref_grads["olmoe.l1.experts_up"],
                               rtol=1e-4, atol=1e-6)


def test_olmoe_through_the_rope_kernel_equals_the_formula_route(monkeypatch):
    """2 heads of 128 at 256 positions: q and k of both layers turn in the
    kernel (4 ``rope`` ops, counted as they are lowered), after the QK-norm
    and before the attention that reads them head-major."""
    monkeypatch.setitem(SIZES, "hidden_size", 256)
    monkeypatch.setattr(sys.modules[__name__], "T_LEN", 256)
    logits, loss = _build()
    rng = np.random.RandomState(1)
    feed = {"ids": rng.randint(0, 64, (2, T_LEN)),
            "lbl": rng.randint(0, 64, (2, T_LEN))}
    fetch = [logits, loss, "olmoe.l0.wq@GRAD", "olmoe.l1.wk@GRAD",
             "olmoe.l0.q_norm@GRAD"]

    def routes():
        return {k.split(":", 1)[1]: v for k, v in
                profiler.compile_stats().snapshot().items()
                if k.startswith("route/rope:")}

    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    before = routes()
    plain = exe.run(feed=feed, fetch_list=fetch)
    assert routes().get("reference", 0) - before.get("reference", 0) == 4
    # the test steers the route (the program has no option for it); another
    # executor, because the route is no part of a program's fingerprint
    monkeypatch.setattr(pallas_kernels, "rope_route", functools.partial(
        pallas_kernels.rope_route, interpret=True))
    fused = pt.Executor().run(feed=feed, fetch_list=fetch)
    assert routes().get("interpret", 0) - before.get("interpret", 0) == 4
    np.testing.assert_array_equal(fused[0], plain[0])     # the same bits
    for a, b in zip(fused[1:], plain[1:]):
        assert np.linalg.norm(b) > 0
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-6 * float(np.abs(b).max()))
