"""``ssd_scan``'s Pallas kernels (``ops/ssd_kernels.py``), interpreted on the
CPU: values and all six gradients against the einsum form ``ssd_chunked``
AND against the recurrence position by position; the rule that routes a call
and the counters it bumps.  (Compiled for a described v5e at the Granite
cell's shape: ``tests/test_tpu_compile.py``.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import ssd_kernels
from paddle_tpu.ops.ssd_ops import ssd_chunked

from test_granite_hybrid import _loop_ssd, _scan_ssd, _ssd_operands

NAMES = ("u", "delta", "a", "bm", "cm", "d")


def _routes():
    return {k: v for k, v in profiler.compile_stats().snapshot().items()
            if k.startswith("route/ssd_scan:")}


def _close(got, want, rtol, name):
    np.testing.assert_allclose(
        got, want, rtol=rtol, atol=rtol * float(jnp.abs(want).max()),
        err_msg=name)


# b, chunks, heads, p, groups, n, chunk: head blocks are 512 lanes inside
# one group, so (16 heads of 64, 1 group) is two blocks, (8, 128, 2) two of
# four heads, one a group; 2 and 3 chunks, so that the state and its
# cotangent cross a boundary
CASES = [
    (1, 2, 8, 64, 1, 128, 128),      # one block of four head pairs
    (2, 3, 16, 64, 1, 128, 128),     # two blocks, two sequences
    (1, 2, 16, 64, 2, 128, 128),     # a block a group
    (1, 3, 2, 64, 1, 256, 128),      # one pair; a state of two lane tiles
    (1, 2, 12, 64, 1, 128, 128),     # 12 heads: two blocks of 6 (384 lanes)
    (1, 3, 4, 128, 1, 128, 128),     # whole-tile heads, one block
    (2, 2, 16, 128, 2, 128, 128),    # two blocks a group, two groups
    (1, 2, 4, 64, 1, 128, 256),      # the cell's chunk
    (1, 3, 8, 128, 2, 128, 256),     # and at whole-tile heads, two groups
    (1, 2, 64, 64, 8, 128, 128),     # eight groups of eight heads, a block a
]                                    # group (Nemotron-3-Nano's layer)


@pytest.mark.parametrize("b,chunks,heads,p,groups,n,chunk", CASES)
def test_interpreted_kernels_equal_the_einsum_form_and_the_recurrence(
        b, chunks, heads, p, groups, n, chunk):
    t_len = chunks * chunk
    assert ssd_kernels.ssd_scan_route(
        ((b, t_len, heads, p), (b, t_len, groups, n)), chunk, jnp.float32,
        interpret=True) == "interpret"
    vals = [jnp.asarray(v) for v in _ssd_operands(
        np.random.RandomState(heads + p + chunk), b, t_len, heads, p, groups,
        n)]
    weight = jnp.asarray(np.random.RandomState(1).randn(
        b, t_len, heads, p).astype("float32"))

    def through(fn):
        return jax.value_and_grad(
            lambda *xs: jnp.sum(fn(*xs) * weight), argnums=range(6))

    got_y = ssd_kernels.ssd_scan(*vals, chunk, interpret=True)
    got = through(lambda *xs: ssd_kernels.ssd_scan(
        *xs, chunk, interpret=True))(*vals)[1]
    _close(got_y, ssd_chunked(*vals, chunk), 2e-5, "y, the einsum form")
    _close(got_y, _loop_ssd(*vals), 1e-4, "y, the recurrence")
    einsum = through(lambda *xs: ssd_chunked(*xs, chunk))(*vals)[1]
    scan = through(_scan_ssd)(*vals)[1]
    for name, grad, ref_e, ref_s in zip(NAMES, got, einsum, scan):
        assert grad.shape == ref_e.shape and grad.dtype == ref_e.dtype
        _close(grad, ref_e, 1e-4, name + ", the einsum form")
        _close(grad, ref_s, 3e-4, name + ", the recurrence")


def test_interpreted_kernels_stay_finite_where_the_decays_underflow():
    """A = -64 at delta 0.15 and a chunk of 256: the running sum reaches
    -2 400; the kernel route is finite and equals the recurrence, values and
    all six gradients."""
    rng = np.random.RandomState(0)
    b, t_len, heads, p, n, chunk = 1, 512, 2, 64, 128, 256
    vals = [jnp.asarray(v) for v in (
        rng.randn(b, t_len, heads, p).astype("float32"),
        np.full((b, t_len, heads), 0.15, "float32"),
        np.array([-64.0, -1.0], "float32"),
        rng.randn(b, t_len, 1, n).astype("float32"),
        rng.randn(b, t_len, 1, n).astype("float32"),
        np.ones(heads, "float32"))]

    def run(*xs):
        return ssd_kernels.ssd_scan(*xs, chunk, interpret=True)

    got = run(*vals)
    assert bool(jnp.all(jnp.isfinite(got)))
    _close(got, _loop_ssd(*vals), 1e-4, "y")
    _close(got, ssd_chunked(*vals, chunk), 1e-5, "y, the einsum form")
    grads = jax.grad(lambda *xs: jnp.sum(run(*xs) ** 2),
                     argnums=range(6))(*vals)
    want = jax.grad(lambda *xs: jnp.sum(_scan_ssd(*xs) ** 2),
                    argnums=range(6))(*vals)
    for name, grad, ref in zip(NAMES, grads, want):
        assert bool(jnp.all(jnp.isfinite(grad))), name
        _close(grad, ref, 1e-3, name)


def test_the_gradients_of_the_decays_hold_under_one_pass_products(
        monkeypatch):
    """The interpreter with every product of the kernels rounded as the chip
    rounds it (operands to bfloat16, one pass), on a layer like Granite's at
    its start: heads from a decay of 0.001 a position to one of 6.4, so that
    the fastest are all diagonal, and Bm . Cm small beside D.  The gradient
    of cs is then W's row sums less its column sums with W = dM o M, of
    which a thousandth is left: d delta and d A stay within a percent of the
    float32 einsum form only because both sums are taken of the SAME rounded
    operands (the control: with the operands of the sums left unrounded they
    do not)."""
    def rounded(x, interpret):
        return x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x

    def one_pass(a, b, contract, interpret):
        return jax.lax.dot_general(
            rounded(a, False).astype(jnp.float32),
            rounded(b, False).astype(jnp.float32), (contract, ((), ())),
            precision=jax.lax.Precision.HIGHEST)

    def retrace():               # the jitted calls keep what they traced
        ssd_kernels._ssd_fwd_call.clear_cache()
        ssd_kernels._ssd_bwd_call.clear_cache()

    monkeypatch.setattr(ssd_kernels, "_mxu_operand", rounded)
    monkeypatch.setattr(ssd_kernels, "_mxu_dot", one_pass)
    retrace()
    b, t_len, heads, p, n, chunk = 1, 512, 8, 64, 128, 256
    rng = np.random.RandomState(0)
    step = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), heads))
    u = rng.randn(b, t_len, heads, p)
    vals = [jnp.asarray(v, jnp.float32) for v in (
        u / (1 + np.exp(-u)),
        step * np.exp(0.3 * rng.randn(b, t_len, heads)),
        -np.linspace(1, 64, heads), 0.03 * rng.randn(b, t_len, 1, n),
        0.03 * rng.randn(b, t_len, 1, n), np.ones(heads))]
    weight = jnp.asarray(rng.randn(b, t_len, heads, p), jnp.float32)

    def gradients(fn):
        return jax.grad(lambda *xs: jnp.sum(fn(*xs) * weight),
                        argnums=(1, 2))(*vals)

    def apart(got, want):
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    def kernels(*xs):
        return ssd_kernels.ssd_scan(*xs, chunk, interpret=True)

    want = gradients(lambda *xs: ssd_chunked(*xs, chunk))
    try:
        for name, got, ref in zip(("delta", "a"), gradients(kernels), want):
            assert apart(got, ref) < 0.01, (name, apart(got, ref))
        monkeypatch.setattr(ssd_kernels, "_as_multiplied", lambda x, _: x)
        retrace()
        control = gradients(kernels)
        assert apart(control[1], want[1]) > 0.03, apart(control[1], want[1])
    finally:
        retrace()


def test_interpreted_kernels_take_bfloat16_operands():
    """bfloat16 in, bfloat16 out, computed in float32 as the einsum form
    does: both round the same operands."""
    b, t_len, heads, p, n, chunk = 1, 256, 4, 64, 128, 128
    vals = [jnp.asarray(v, jnp.bfloat16) for v in _ssd_operands(
        np.random.RandomState(3), b, t_len, heads, p, 1, n)]
    got = ssd_kernels.ssd_scan(*vals, chunk, interpret=True)
    want = ssd_chunked(*vals, chunk)
    assert got.dtype == want.dtype == jnp.bfloat16
    _close(got.astype(jnp.float32), want.astype(jnp.float32), 1e-2, "y")
    grads = jax.grad(lambda *xs: jnp.sum(ssd_kernels.ssd_scan(
        *xs, chunk, interpret=True).astype(jnp.float32)),
        argnums=range(6))(*vals)
    refs = jax.grad(lambda *xs: jnp.sum(ssd_chunked(*xs, chunk).astype(
        jnp.float32)), argnums=range(6))(*vals)
    for name, grad, ref in zip(NAMES, grads, refs):
        assert grad.dtype == jnp.bfloat16, name
        _close(grad.astype(jnp.float32), ref.astype(jnp.float32), 2e-2, name)


# what the Granite cell hands the op: B, T, H, P / G, N / chunk
CELL = ((1, 8192, 64, 64), (1, 8192, 1, 128))


@pytest.mark.parametrize("shapes,chunk,dtype,backend,interpret,route", [
    (CELL, 256, jnp.float32, "tpu", False, "pallas"),
    (CELL, 256, jnp.bfloat16, "tpu", False, "pallas"),
    (CELL, 256, jnp.float32, "tpu", True, "interpret"),
    (CELL, 256, jnp.float32, "cpu", True, "interpret"),
    (CELL, 256, jnp.float32, "cpu", False, "xla"),           # a CPU
    (CELL, 256, jnp.float32, "gpu", False, "xla"),
    (CELL, 256, jnp.float16, "tpu", False, "xla"),           # the dtype
    (CELL, 64, jnp.float32, "tpu", False, "xla"),            # the chunk
    (CELL, 8192, jnp.float32, "tpu", False, "xla"),          # VMEM
    (((2, 4096, 48, 128), (2, 4096, 8, 128)), 128, jnp.float32, "tpu",
     False, "pallas"),                                       # 6 heads a group
    (((1, 8192, 64, 32), (1, 8192, 1, 128)), 256, jnp.float32, "tpu",
     False, "xla"),                                          # P
    (((1, 8192, 64, 64), (1, 8192, 1, 64)), 256, jnp.float32, "tpu",
     False, "xla"),                                          # N
    (((1, 8192, 3, 64), (1, 8192, 1, 128)), 256, jnp.float32, "tpu",
     False, "xla"),                                          # half a tile
    (((1, 8192, 80, 64), (1, 8192, 8, 128)), 256, jnp.float32, "tpu",
     False, "pallas"),                                       # 10 a group
    (((1, 8192, 24, 64), (1, 8192, 2, 128)), 256, jnp.float32, "tpu",
     False, "pallas"),                                       # 12: blocks of 6
    (((1, 8192, 14, 64), (1, 8192, 2, 128)), 256, jnp.float32, "tpu",
     False, "xla"),                                          # 7: no pairs
    (((2, 32, 4, 3), (2, 32, 2, 5)), 8, jnp.float32, "tpu", False, "xla"),
    (((8192, 64, 64), (8192, 1, 128)), 256, jnp.float32, "tpu", False,
     "xla"),                                                 # the rank
])
def test_the_route_follows_what_the_code_can_see(monkeypatch, shapes, chunk,
                                                 dtype, backend, interpret,
                                                 route):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ssd_kernels.ssd_scan_route(shapes, chunk, dtype,
                                      interpret) == route


@pytest.mark.parametrize("interpret,mesh_size,backend,route", [
    (True, None, "cpu", "interpret"),    # the attribute, which a test sets
    (False, None, "cpu", "xla"),
    (True, 4, "cpu", "xla"),             # a mesh keeps the einsum form
    (True, 1, "cpu", "interpret"),       # a mesh of one device is none
])
def test_the_op_counts_the_route_it_takes(monkeypatch, interpret, mesh_size,
                                          backend, route):
    """Through a Program: the ``interpret`` attribute, a mesh and the
    backend decide, and ``route/ssd_scan:<route>`` moves by one a lowering;
    the result is the einsum form's whichever ran."""
    from paddle_tpu.core import executor as executor_mod

    b, t_len, heads, p, n, chunk = 1, 256, 2, 64, 128, 128
    vals = _ssd_operands(np.random.RandomState(5), b, t_len, heads, p, 1, n)
    ins = [LayerHelper("operand").create_parameter(
        pt.ParamAttr(name=name,
                     initializer=pt.initializer.NumpyArrayInitializer(v)),
        shape=list(v.shape), dtype="float32")
        for name, v in zip(NAMES, vals)]
    out = layers.ssd_scan(*ins, chunk=chunk, interpret=interpret)
    op = pt.default_main_program().global_block().ops[-1]
    assert op.attrs.get("interpret", False) is interpret
    assert ("interpret" in op.attrs) is interpret    # the digest keeps
    if mesh_size is not None:
        made = executor_mod.LoweringContext

        def with_mesh(*args, **kwargs):
            ctx = made(*args, **kwargs)
            ctx.mesh = jax.sharding.Mesh(
                np.array(jax.devices()[:mesh_size]), ("dp",))
            return ctx
        monkeypatch.setattr(executor_mod, "LoweringContext", with_mesh)
    before = _routes()
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    got, = exe.run(feed={}, fetch_list=[out])
    moved = {k: v - before.get(k, 0) for k, v in _routes().items()
             if v != before.get(k, 0)}
    assert moved == {"route/ssd_scan:" + route: 1}
    _close(got, ssd_chunked(*(jnp.asarray(v) for v in vals), chunk), 2e-5,
           "y")
