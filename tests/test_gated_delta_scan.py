"""``gated_delta_scan`` (ops/delta_ops.py): the chunked gated delta rule
against the recurrence it stands for, one position at a time: values and the
gradients of q, k, v, g and beta at chunks of 8, 16 and 64 positions, beta
near 0 and near 1, decays strong enough (g down to -30 a position) that any
exp(-gam) over a chunk overflows; several stretches; the elementwise sums
of the diagonal sub-blocks and their own backward; the blocked inverse of
a chunk's triangle; the op in a Program; its stage scopes, route, shape and
sharding rules.  Then the pair of Pallas kernels (``ops/delta_kernels.py``)
through the interpreter against both: values and the five gradients, strong
decays, the chunk-boundary states they keep, the gradients under products
rounded as the chip rounds them, bfloat16 operands; the rule that routes a
call and the count the op keeps of it."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.analysis.shape_infer import ShapeError, VarInfo
from paddle_tpu.core.registry import get_shape_fn, get_shard_fn
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.ops import delta_kernels, delta_ops
from paddle_tpu.ops.delta_ops import delta_chunked


def _recurrence(q, k, v, g, beta, scale):
    """o [B, T, H, V] of S[t] = (I - b k k^T) Diag(exp(g)) S[t-1] + b k v^T,
    o[t] = S[t]^T (scale q[t]), one position a step."""
    b, _, heads, kd = q.shape

    def position(state, at):                 # state [B, H, K, V]
        qt, kt, vt, gt, bt = at
        state = jnp.exp(gt)[..., None] * state
        old = jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - old)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, scale * qt)

    first = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)]
    _, o = jax.lax.scan(position,
                        jnp.zeros((b, heads, kd, v.shape[-1]), q.dtype),
                        tuple(first))
    return jnp.moveaxis(o, 0, 1)


def _operands(rng, b=2, t_len=64, heads=2, kd=16, vd=8, g_low=-1.0,
              beta=(0.0, 1.0)):
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    f = np.float32
    return (jnp.asarray(unit(rng.randn(b, t_len, heads, kd)), f),
            jnp.asarray(unit(rng.randn(b, t_len, heads, kd)), f),
            jnp.asarray(rng.randn(b, t_len, heads, vd), f),
            jnp.asarray(rng.uniform(g_low, 0.0, (b, t_len, heads, kd)), f),
            jnp.asarray(rng.uniform(*beta, (b, t_len, heads)), f))


def _both(fn, operands, ct):
    out, vjp = jax.vjp(fn, *operands)
    return out, vjp(ct)


CASES = {
    # chunk, T, g's least value, beta's range
    "chunk8": (8, 40, -1.0, (0.0, 1.0)),
    "chunk16": (16, 64, -1.0, (0.0, 1.0)),
    "chunk64": (64, 128, -1.0, (0.0, 1.0)),
    "beta_near_0": (16, 48, -0.5, (0.0, 1e-3)),
    "beta_near_1": (64, 128, -0.5, (0.999, 1.0)),
    # |g| up to 30 a position: gam reaches -1900 in a chunk of 64
    "strong_decays": (64, 128, -30.0, (0.0, 1.0)),
    "strong_decays_chunk16": (16, 48, -30.0, (0.0, 1.0)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_form_equals_the_recurrence(case):
    chunk, t_len, g_low, beta = CASES[case]
    rng = np.random.RandomState(len(case))
    operands = _operands(rng, t_len=t_len, g_low=g_low, beta=beta)
    ct = jnp.asarray(rng.randn(*operands[2].shape), jnp.float32)
    scale = 16 ** -0.5
    got, d_got = _both(lambda *x: delta_chunked(*x, scale, chunk), operands,
                       ct)
    want, d_want = _both(lambda *x: _recurrence(*x, scale), operands, ct)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for name, a, b in zip("qkvgb", d_got, d_want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.abs(b).max()) + 1e-7,
            err_msg=name)


@pytest.mark.parametrize("stretch", [32, 64])
def test_the_chunks_in_recomputed_stretches_equal_the_recurrence(
        monkeypatch, stretch):
    """Several stretches of chunks, the state handed from one to the next
    and each recomputed in the backward pass: values and gradients as
    one."""
    monkeypatch.setattr(delta_ops, "STRETCH", stretch)
    rng = np.random.RandomState(stretch)
    operands = _operands(rng, t_len=128, g_low=-3.0)
    ct = jnp.asarray(rng.randn(*operands[2].shape), jnp.float32)
    got, d_got = _both(lambda *x: delta_chunked(*x, 0.25, 16), operands, ct)
    want, d_want = _both(lambda *x: _recurrence(*x, 0.25), operands, ct)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for name, a, b in zip("qkvgb", d_got, d_want):
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=2e-5 * float(jnp.abs(b).max()) + 1e-7,
            err_msg=name)
    # one scan over 128 / stretch stretches, each under jax.checkpoint
    text = str(jax.make_jaxpr(lambda *x: delta_chunked(*x, 0.25, 16))(
        *operands))
    assert f"length={128 // stretch}" in text and "remat" in text


def test_a_strong_decay_forgets_where_an_unstabilised_form_overflows():
    """At g = -30 a position the state forgets all but the last position,
    which the op reads; the quotient form exp(gam_r) / exp(gam_i) over one
    chunk is 0 / 0 there."""
    rng = np.random.RandomState(3)
    q, k, v, _, beta = _operands(rng, t_len=64)
    g = jnp.full(k.shape, -30.0, jnp.float32)
    o = delta_chunked(q, k, v, g, beta, 1.0, 64)
    # S[t] ~ beta[t] k[t] v[t]^T alone
    last = jnp.einsum("bthk,bthk->bth", k, q)[..., None] * beta[..., None] * v
    np.testing.assert_allclose(o, last, rtol=1e-5, atol=1e-6)
    gam = jnp.cumsum(g[:, :64], axis=1)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-gam))))


@pytest.mark.parametrize("inclusive", [False, True])
def test_the_diagonal_sums_and_their_backward(inclusive):
    rng = np.random.RandomState(int(inclusive))
    x, y = (jnp.asarray(rng.randn(3, 16, 8), jnp.float32) for _ in range(2))
    gam = jnp.cumsum(jnp.asarray(rng.uniform(-3, 0, (3, 16, 8)),
                                 jnp.float32), axis=1)
    ct = jnp.asarray(rng.randn(3, 16, 16), jnp.float32)

    def plain(x, y, gam):
        mask = delta_ops._mask(16, inclusive)
        d = gam[:, :, None, :] - gam[:, None, :, :]
        e = jnp.where(mask[..., None], jnp.exp(jnp.where(mask[..., None], d,
                                                         0.0)), 0.0)
        return jnp.einsum("brk,bik,brik->bri", x, y, e)

    got, d_got = _both(lambda *a: delta_ops._decayed(*a, inclusive),
                       (x, y, gam), ct)
    want, d_want = _both(plain, (x, y, gam), ct)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for a, b in zip(d_got, d_want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("n", [1, 8, 48, 64])
def test_the_blocked_inverse_of_a_unit_lower_triangle(n):
    """(I + N)^-1 level by level equals the inverse, at a size that is no
    power of two too, and where every N[r, i] is 1 (the Neumann series of
    which has terms of 10^17 and cancels only in exact arithmetic)."""
    rng = np.random.RandomState(n)
    strict = np.tril(rng.uniform(-1, 1, (3, n, n)), -1)
    ones = np.tril(np.ones((1, n, n)), -1)
    for m in (strict, ones):
        got = delta_ops._unit_lower_inverse(jnp.asarray(m, jnp.float32))
        want = np.linalg.inv(np.eye(n) + m)
        np.testing.assert_allclose(got, want, rtol=1e-5,
                                   atol=1e-5 * np.abs(want).max())


def _program(operands, chunk=16, scale=0.5):
    """(out, the program's loss, feed): the op on fed q, k, v, g, beta, q
    and g each times a parameter vector of ones (so that the backward runs
    through the op), loss = sum(out^2), SGD."""
    from paddle_tpu.models.hybrid_mixers import vector

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    names = ("q", "k", "v", "g", "beta")
    ins = {n: layers.data(n, shape=list(x.shape[1:]), dtype="float32")
           for n, x in zip(names, operands)}
    kd = operands[0].shape[-1]
    q = layers.elementwise_mul(ins["q"], vector("t", "wq", np.ones(kd)))
    g = layers.elementwise_mul(ins["g"], vector("t", "wg", np.ones(kd)))
    out = layers.gated_delta_scan(q, ins["k"], ins["v"], g, ins["beta"],
                                  scale=scale, chunk=chunk)
    loss = layers.reduce_sum(layers.elementwise_mul(out, out))
    pt.optimizer.SGD(0.0).minimize(loss)
    pt.Executor().run(pt.default_startup_program(), feed={}, fetch_list=[])
    return out, loss, {n: np.asarray(x) for n, x in zip(names, operands)}


def test_the_op_in_a_program_equals_the_recurrence_and_counts_its_route():
    operands = _operands(np.random.RandomState(5), t_len=32, kd=8, vd=4)
    out, loss, feed = _program(operands)
    assert tuple(out.shape[1:]) == (32, 2, 4)
    before = profiler.compile_stats().snapshot().get(
        "route/gated_delta_scan:xla", 0)
    got = pt.Executor().run(pt.default_main_program(), feed=feed,
                            fetch_list=[out, "t.wq@GRAD", "t.wg@GRAD"])
    assert profiler.compile_stats().snapshot()[
        "route/gated_delta_scan:xla"] == before + 1
    want = _recurrence(*operands, 0.5)
    _, d_want = _both(lambda *x: _recurrence(*x, 0.5), operands, 2 * want)
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    for grad, d, x in ((got[1], d_want[0], operands[0]),
                       (got[2], d_want[3], operands[3])):
        np.testing.assert_allclose(grad, jnp.sum(d * x, axis=(0, 1, 2)),
                                   rtol=2e-3, atol=1e-5)


def test_the_op_names_its_five_stages_inside_its_op_scope():
    """``delta.decay`` / ``solve`` / ``states`` / ``intra`` / ``out`` sit
    inside ``pt.gated_delta_scan:<b>.<p>``, forward and backward; they do
    not start with ``pt.``, so the op stays the innermost owner."""
    operands = _operands(np.random.RandomState(6), t_len=32, kd=8, vd=4)
    _, loss, feed = _program(operands)
    text = pt.Executor().compile(feed=feed, fetch_list=[loss]).hlo_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for stage in ("decay", "solve", "states", "intra", "out"):
        assert any(re.search(rf"/jvp\(pt\.gated_delta_scan:0\.\d+\)/"
                             rf"(.*/)?delta\.{stage}/", n)
                   for n in names), stage
        assert any(re.search(rf"/transpose\(jvp\(pt\.gated_delta_scan:0"
                             rf"\.\d+\)\)/(.*/)?delta\.{stage}/", n)
                   for n in names), stage
    assert not any("pt.delta." in n for n in names)


def test_shape_and_sharding_rules():
    from paddle_tpu.analysis.shard_prop import ShardConflict, ShardInfo

    rule = get_shape_fn("gated_delta_scan")

    def ins(t=128, v=(-1, 128, 4, 32), beta=(-1, 128, 4), k=None):
        q = VarInfo((-1, t, 4, 16), "float32")
        return {"Q": [q], "K": [VarInfo(k or q.shape, "float32")],
                "G": [q], "V": [VarInfo(v, "float32")],
                "Beta": [VarInfo(beta, "float32")]}

    assert rule(None, ins(), {"chunk": 64})["Out"].shape == (-1, 128, 4, 32)
    # T must be whole chunks: a ragged tail is refused, here and when lowered
    with pytest.raises(ShapeError, match="T 100 is not whole chunks of 64"):
        rule(None, ins(t=100, v=(-1, 100, 4, 32), beta=(-1, 100, 4)),
             {"chunk": 64})
    with pytest.raises(ValueError, match="not whole chunks"):
        delta_ops._gated_delta_scan(None, {
            n: [jnp.zeros(s)] for n, s in (
                ("Q", (1, 100, 2, 8)), ("K", (1, 100, 2, 8)),
                ("V", (1, 100, 2, 4)), ("G", (1, 100, 2, 8)),
                ("Beta", (1, 100, 2)))}, {"chunk": 64, "scale": 1.0})
    with pytest.raises(ShapeError, match=r"V \[-1, 128, 3, 32\]"):
        rule(None, ins(v=(-1, 128, 3, 32)), {"chunk": 64})
    with pytest.raises(ShapeError, match="Beta"):
        rule(None, ins(beta=(-1, 128, 4, 1)), {"chunk": 64})
    with pytest.raises(ShapeError, match="K "):
        rule(None, ins(k=(-1, 128, 4, 8)), {"chunk": 64})

    shard = get_shard_fn("gated_delta_scan")
    shape = (-1, 128, 4, 16)
    assert shard(None, {"Q": [ShardInfo(("dp", None, "tp", None), shape)]},
                 {})["Out"] == ("dp", None, "tp", None)
    for spec in ((None, "sp", None, None), (None, None, None, "tp")):
        with pytest.raises(ShardConflict, match="gated_delta_scan"):
            shard(None, {"Q": [ShardInfo(spec, shape)]}, {})
    assert shard(None, {"Q": [ShardInfo(None, shape)]}, {}) == {}


# ---------------------------------------------------------------------------
# the Pallas kernels (ops/delta_kernels.py), interpreted on the CPU
# ---------------------------------------------------------------------------
def _kernels(*xs, scale=128 ** -0.5, chunk=64):
    return delta_kernels.gated_delta_scan(*xs, scale, chunk, interpret=True)


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("beta", [(0.0, 1e-3), (0.999, 1.0)],
                         ids=["beta_near_0", "beta_near_1"])
def test_interpreted_kernels_equal_the_chunked_form_and_the_recurrence(
        b, beta):
    """Two heads of 128, four chunks of 64: the value and the gradients of
    q, k, v, g and beta, against ``delta_chunked`` and against the
    recurrence position by position."""
    rng = np.random.RandomState(b + int(10 * beta[0]))
    operands = _operands(rng, b=b, t_len=256, heads=2, kd=128, vd=128,
                         g_low=-1.0, beta=beta)
    ct = jnp.asarray(rng.randn(*operands[2].shape), jnp.float32)
    scale = 128 ** -0.5
    assert delta_kernels.gated_delta_scan_route(
        (operands[0].shape, operands[2].shape), 64, jnp.float32,
        interpret=True) == "interpret"
    got, d_got = _both(_kernels, operands, ct)
    xla, d_xla = _both(lambda *x: delta_chunked(*x, scale, 64), operands, ct)
    want, d_want = _both(lambda *x: _recurrence(*x, scale), operands, ct)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, xla, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for name, a, x, w in zip("qkvgb", d_got, d_xla, d_want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert _rel(a, x) < 1e-4, (name, _rel(a, x))
        np.testing.assert_allclose(
            a, w, rtol=2e-3, atol=2e-5 * float(jnp.abs(w).max()) + 1e-7,
            err_msg=name)


def test_interpreted_kernels_stay_finite_under_strong_decays():
    """|g| up to 30 a position: gam reaches -1 900 in a chunk, where any
    exp(-gam) overflows; values and gradients finite and the recurrence's."""
    rng = np.random.RandomState(7)
    operands = _operands(rng, b=1, t_len=128, heads=2, kd=128, vd=128,
                         g_low=-30.0)
    ct = jnp.asarray(rng.randn(*operands[2].shape), jnp.float32)
    got, d_got = _both(_kernels, operands, ct)
    want, d_want = _both(lambda *x: _recurrence(*x, 128 ** -0.5), operands,
                         ct)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for name, a, w in zip("qkvgb", d_got, d_want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(
            a, w, rtol=2e-3, atol=2e-5 * float(jnp.abs(w).max()) + 1e-7,
            err_msg=name)


def test_the_boundary_states_are_the_recurrences_state_before_each_chunk():
    """What the forward kernel hands the backward: every head's state [K, V]
    before each chunk, the recurrence's after the chunk's last position
    (0 before the first)."""
    rng = np.random.RandomState(8)
    q, k, v, g, beta = _operands(rng, b=2, t_len=256, heads=2, kd=128,
                                 vd=128, g_low=-0.3)
    _, got = delta_kernels._fwd_call(q, k, v, g, beta, scale=0.5, chunk=64,
                                     interpret=True)
    assert got.shape == (2, 4, 2, 128, 128)           # S^T [V, K] a head

    def position(state, at):
        kt, vt, gt, bt = at
        state = jnp.exp(gt)[..., None] * state
        old = jnp.einsum("bhk,bhkv->bhv", kt, state)
        state = state + (bt[..., None] * kt)[..., None] \
            * (vt - old)[..., None, :]
        return state, state

    _, states = jax.lax.scan(position, jnp.zeros((2, 2, 128, 128)),
                             tuple(jnp.moveaxis(x, 1, 0)
                                   for x in (k, v, g, beta)))
    want = jnp.concatenate([jnp.zeros_like(states[:1]),
                            states[63:255:64]], axis=0)
    np.testing.assert_allclose(jnp.swapaxes(got, -1, -2),
                               jnp.moveaxis(want, 0, 1), rtol=1e-4, atol=1e-5)


def test_the_gradients_hold_under_one_pass_products(monkeypatch):
    """The interpreter with every large product of the kernels rounded as the
    chip rounds it (operands to bfloat16, one pass), at decays of up to 2 a
    position: each gradient stays within a percent of the float32 chunked
    form, as the chip read it.  The gradient of the decays through A and P
    (a term added at a row and taken off at a column) is taken of the
    operands as the products multiplied them; the control, with those
    operands left unrounded, puts g's gradient a third further off."""
    def rounded(x, interpret):
        return x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x

    def one_pass(a, b, contract, interpret):
        return jax.lax.dot_general(
            rounded(a, False).astype(jnp.float32),
            rounded(b, False).astype(jnp.float32), (contract, ((), ())),
            precision=jax.lax.Precision.HIGHEST)

    def retrace():               # the jitted calls keep what they traced
        delta_kernels._fwd_call.clear_cache()
        delta_kernels._bwd_call.clear_cache()

    monkeypatch.setattr(delta_kernels, "_mxu_operand", rounded)
    monkeypatch.setattr(delta_kernels, "_mxu_dot", one_pass)
    retrace()
    rng = np.random.RandomState(9)
    operands = _operands(rng, b=1, t_len=128, heads=2, kd=128, vd=128,
                         g_low=-2.0)
    ct = jnp.asarray(rng.randn(*operands[2].shape), jnp.float32)
    _, want = _both(lambda *x: delta_chunked(*x, 128 ** -0.5, 64), operands,
                    ct)
    try:
        _, got = _both(_kernels, operands, ct)
        for name, a, w in zip("qkvgb", got, want):
            assert _rel(a, w) < 0.01, (name, _rel(a, w))
        monkeypatch.setattr(delta_kernels, "_as_multiplied", lambda x, _: x)
        retrace()
        _, control = _both(_kernels, operands, ct)
        assert _rel(control[3], want[3]) > 1.25 * _rel(got[3], want[3]), (
            _rel(control[3], want[3]), _rel(got[3], want[3]))
    finally:
        retrace()


def test_interpreted_kernels_take_bfloat16_operands():
    """bfloat16 in, bfloat16 out, computed in float32 as the chunked form
    does: both round the same operands."""
    rng = np.random.RandomState(10)
    operands = tuple(x.astype(jnp.bfloat16) for x in _operands(
        rng, b=1, t_len=128, heads=2, kd=128, vd=128, g_low=-0.5))
    got = _kernels(*operands)
    want = delta_chunked(*operands, 128 ** -0.5, 64)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert _rel(got.astype(jnp.float32), want.astype(jnp.float32)) < 1e-2

    def total(fn):
        return jax.grad(lambda *x: jnp.sum(fn(*x).astype(jnp.float32)),
                        argnums=range(5))(*operands)

    for name, a, w in zip("qkvgb", total(_kernels), total(
            lambda *x: delta_chunked(*x, 128 ** -0.5, 64))):
        assert a.dtype == jnp.bfloat16, name
        assert _rel(a.astype(jnp.float32), w.astype(jnp.float32)) < 2e-2, name


# what the Kimi Linear cell hands the op: q [B, T, H, K], v [B, T, H, V]
CELL = ((1, 8192, 32, 128), (1, 8192, 32, 128))


@pytest.mark.parametrize("shapes,chunk,dtype,backend,interpret,route", [
    (CELL, 64, jnp.float32, "tpu", False, "pallas"),
    (CELL, 64, jnp.bfloat16, "tpu", False, "pallas"),
    (CELL, 64, jnp.float32, "tpu", True, "interpret"),
    (CELL, 64, jnp.float32, "cpu", True, "interpret"),
    (CELL, 64, jnp.float32, "cpu", False, "xla"),            # a CPU
    (CELL, 64, jnp.float32, "gpu", False, "xla"),
    (CELL, 64, jnp.float16, "tpu", False, "xla"),            # the dtype
    (CELL, 128, jnp.float32, "tpu", False, "pallas"),
    (CELL, 16, jnp.float32, "tpu", False, "pallas"),
    (CELL, 24, jnp.float32, "tpu", False, "xla"),    # no whole sub-blocks
    (CELL, 8, jnp.float32, "tpu", False, "xla"),
    (CELL, 2048, jnp.float32, "tpu", False, "xla"),          # VMEM
    (((1, 8192, 32, 64), (1, 8192, 32, 64)), 64, jnp.float32, "tpu", False,
     "xla"),                                                 # K = 64
    (((1, 8192, 32, 128), (1, 8192, 32, 64)), 64, jnp.float32, "tpu", False,
     "xla"),                                                 # V = 64
    (((2, 4096, 6, 256), (2, 4096, 6, 128)), 64, jnp.float32, "tpu", False,
     "pallas"),                                              # K = 256
    (((1, 8160, 32, 128), (1, 8160, 32, 128)), 64, jnp.float32, "tpu",
     False, "xla"),                                          # T
    (((8192, 32, 128), (8192, 32, 128)), 64, jnp.float32, "tpu", False,
     "xla"),                                                 # the rank
])
def test_the_route_follows_what_the_code_can_see(monkeypatch, shapes, chunk,
                                                 dtype, backend, interpret,
                                                 route):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert delta_kernels.gated_delta_scan_route(shapes, chunk, dtype,
                                                interpret) == route


def _routes():
    return {k: v for k, v in profiler.compile_stats().snapshot().items()
            if k.startswith("route/gated_delta_scan:")}


@pytest.mark.parametrize("interpret,mesh_size,route", [
    (True, None, "interpret"),           # the attribute, which a test sets
    (False, None, "xla"),
    (True, 4, "xla"),                    # a mesh keeps the chunked XLA form
    (True, 1, "interpret"),              # a mesh of one device is none
])
def test_the_op_counts_the_route_it_takes(monkeypatch, interpret, mesh_size,
                                          route):
    """Through a Program: the ``interpret`` attribute and a mesh decide, and
    ``route/gated_delta_scan:<route>`` moves by one a lowering; the result
    is the recurrence's whichever ran."""
    from paddle_tpu.core import executor as executor_mod

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    operands = _operands(np.random.RandomState(11), b=1, t_len=128, heads=2,
                         kd=128, vd=128)
    ins = [LayerHelper("operand").create_parameter(
        pt.ParamAttr(name=name,
                     initializer=pt.initializer.NumpyArrayInitializer(
                         np.asarray(x))),
        shape=list(x.shape), dtype="float32")
        for name, x in zip(("q", "k", "v", "g", "beta"), operands)]
    out = layers.gated_delta_scan(*ins, scale=0.25, chunk=64,
                                  interpret=interpret)
    op = pt.default_main_program().global_block().ops[-1]
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
    assert moved == {"route/gated_delta_scan:" + route: 1}
    np.testing.assert_allclose(got, _recurrence(*operands, 0.25), rtol=2e-4,
                               atol=2e-5)
