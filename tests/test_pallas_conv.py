"""Pallas 1x1-conv kernel tests (interpret mode on CPU; the same kernels
compile for the MXU on TPU).  Covers the generic blocked matmul with its
custom VJP, the conv wrapper (stride 1 and 2), the fused BN-stats /
bias-grad epilogues, eligibility gating, and the end-to-end Executor
routing behind the opt-in switch."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.ops.pallas_conv import (conv1x1_eligible, conv2d_1x1,
                                        conv2d_1x1_grad_fused,
                                        conv2d_1x1_with_bn_stats,
                                        pallas_matmul)

R = np.random.RandomState(7)
DN = ("NCHW", "OIHW", "NCHW")


def _xla_conv(x, w, strides=(1, 1)):
    return lax.conv_general_dilated(
        x, w, strides, [(0, 0), (0, 0)], dimension_numbers=DN)


@pytest.mark.parametrize("ta,tb", [(False, False), (True, False),
                                   (False, True), (True, True)])
def test_pallas_matmul_transposes(ta, tb):
    M, K, N = 256, 384, 128
    a = R.randn(M, K).astype("float32")
    b = R.randn(K, N).astype("float32")
    ref = a @ b
    aa = jnp.asarray(a.T if ta else a)
    bb = jnp.asarray(b.T if tb else b)
    out = pallas_matmul(aa, bb, ta, tb, 128, 128, 128, True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)


def test_pallas_matmul_vjp_matches_xla():
    M, K, N = 256, 256, 128
    a = jnp.asarray(R.randn(M, K).astype("float32"))
    bt = jnp.asarray(R.randn(N, K).astype("float32"))   # stored transposed

    def f(a, b):
        return jnp.sum(pallas_matmul(a, b, False, True, 128, 128, 128,
                                     True) ** 2)

    def f_ref(a, b):
        return jnp.sum((a @ b.T) ** 2)

    ga, gb = jax.grad(f, (0, 1))(a, bt)
    gar, gbr = jax.grad(f_ref, (0, 1))(a, bt)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gar),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gbr),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("stride", [1, 2])
def test_conv2d_1x1_forward_and_grads(stride):
    x = jnp.asarray(R.randn(2, 128, 16, 16).astype("float32"))
    w = jnp.asarray(R.randn(256, 128, 1, 1).astype("float32"))
    s = (stride, stride)
    ref = _xla_conv(x, w, s)
    out = conv2d_1x1(x, w, s, 128, 128, 128, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

    g = jnp.asarray(R.randn(*ref.shape).astype("float32"))
    dxr, dwr = jax.grad(
        lambda x, w: jnp.sum(_xla_conv(x, w, s) * g), (0, 1))(x, w)
    # autodiff through the wrapper (the executor's append_backward path)
    dxa, dwa = jax.grad(
        lambda x, w: jnp.sum(conv2d_1x1(x, w, s, 128, 128, 128, True) * g),
        (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dxa), np.asarray(dxr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(dwa), np.asarray(dwr),
                               rtol=1e-3, atol=1e-2)
    # the explicit fused-gradient entry point (benchmark path)
    dx, dw, dsum = conv2d_1x1_grad_fused(x, w, g, s, 128, 128, 128, True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dxr),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dwr),
                               rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(dsum),
                               np.asarray(g).sum((0, 2, 3)),
                               rtol=1e-3, atol=1e-3)


def test_conv2d_1x1_bn_stats_epilogue():
    x = jnp.asarray(R.randn(2, 128, 16, 16).astype("float32"))
    w = jnp.asarray(R.randn(128, 128, 1, 1).astype("float32"))
    ref = np.asarray(_xla_conv(x, w))
    out, csum, csq = conv2d_1x1_with_bn_stats(x, w, (1, 1), 128, 128, 128,
                                              True)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(csum), ref.sum((0, 2, 3)),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(csq), (ref ** 2).sum((0, 2, 3)),
                               rtol=1e-3, atol=1e-2)


def test_eligibility_gate():
    ok = dict(strides=(1, 1), pads=(0, 0), dils=(1, 1), groups=1)
    assert conv1x1_eligible((128, 256, 14, 14), (512, 256, 1, 1), **ok)
    # 3x3 filter / groups / padding / dilation all fall back
    assert not conv1x1_eligible((128, 256, 14, 14), (512, 256, 3, 3), **ok)
    assert not conv1x1_eligible((128, 256, 14, 14), (512, 256, 1, 1),
                                strides=(1, 1), pads=(0, 0), dils=(1, 1),
                                groups=2)
    assert not conv1x1_eligible((128, 256, 14, 14), (512, 256, 1, 1),
                                strides=(1, 1), pads=(1, 1), dils=(1, 1),
                                groups=1)
    # non-128-divisible channels (ResNet stage-1 64-ch blocks) fall back
    assert not conv1x1_eligible((128, 64, 56, 56), (64, 64, 1, 1), **ok)
    # pixel count must tile too
    assert not conv1x1_eligible((2, 128, 4, 4), (128, 128, 1, 1), **ok)


def _bn_conv_program(use_pallas):
    img = layers.data("img", shape=[128, 8, 8], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    h = layers.conv2d(img, 128, 1, bias_attr=False,
                      param_attr=pt.ParamAttr(name="cw"),
                      use_pallas=use_pallas)
    h = layers.batch_norm(h)
    h = layers.pool2d(h, pool_size=8, pool_type="avg")
    pred = layers.fc(h, size=10, act="softmax",
                     param_attr=pt.ParamAttr(name="fw"))
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def test_executor_routing_end_to_end(rng, monkeypatch):
    """Same program trained 3 steps through XLA's conv emitter and through
    the Pallas route (interpret mode): losses must track, proving the
    op's ``use_pallas`` attribute alone (no executor option, no flag)
    routes the forward AND the autodiff gradients.  A
    counting wrapper on ``conv2d_1x1`` proves the route was actually
    taken — nn_ops has four silent fall-through gates, and without the
    probe a routing regression would make this test pass vacuously
    (both runs on XLA, trivially equal losses)."""
    feeds = {"img": rng.rand(4, 128, 8, 8).astype("float32") * 0.1,
             "label": rng.randint(0, 10, (4, 1))}

    loss = _bn_conv_program(use_pallas=None)
    prog = pt.default_main_program()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    base = [float(exe.run(prog, feed=feeds, fetch_list=[loss])[0])
            for _ in range(3)]

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    loss = _bn_conv_program(use_pallas=True)
    prog = pt.default_main_program()
    for op in prog.global_block().ops:
        if op.type == "conv2d":
            op.attrs["pallas_interpret"] = True   # CPU test: interpret mode

    from paddle_tpu.ops import pallas_conv
    calls = []
    real = pallas_conv.conv2d_1x1
    monkeypatch.setattr(
        pallas_conv, "conv2d_1x1",
        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    pallas = [float(exe.run(prog, feed=feeds, fetch_list=[loss])[0])
              for _ in range(3)]
    assert calls, "conv2d never routed to the Pallas kernel (silent " \
                  "fall-through in nn_ops._conv2d)"
    np.testing.assert_allclose(base, pallas, rtol=2e-4, atol=2e-5)


def test_executor_flag_off_is_default_path(rng, monkeypatch):
    """The kernel is OFF unless the op asks: a conv2d without the
    ``use_pallas`` attribute never consults it, eligible shape or not
    (``conv1x1_eligible`` and the kernel would both raise here)."""
    from paddle_tpu.ops import pallas_conv

    def refuse(*a, **kw):
        raise AssertionError("attr-free conv2d consulted the Pallas route")
    monkeypatch.setattr(pallas_conv, "conv1x1_eligible", refuse)
    monkeypatch.setattr(pallas_conv, "conv2d_1x1", refuse)
    feeds = {"img": rng.rand(4, 128, 8, 8).astype("float32") * 0.1,
             "label": rng.randint(0, 10, (4, 1))}
    loss = _bn_conv_program(use_pallas=None)
    prog = pt.default_main_program()
    assert all("use_pallas" not in op.attrs
               for op in prog.global_block().ops)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    v = float(exe.run(prog, feed=feeds, fetch_list=[loss])[0])
    assert np.isfinite(v)
