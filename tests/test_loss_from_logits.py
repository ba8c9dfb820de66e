"""A hard-label cross-entropy whose input is known to be a softmax is
computed from that softmax's logits (``ops/nn_ops.py _nll_from_logits``;
the note that says so: ``core/executor.py Env.softmax_of``).  Every case
is held to an oracle kept here: the lowerings of ``softmax``,
``cross_entropy`` and ``softmax_with_cross_entropy`` as they were before,
which write the probabilities and gather from them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.core import compile_cache, registry
from paddle_tpu.layers.control_flow import StaticRNN
from paddle_tpu.ops import nn_ops

B, T, D, H, V = 3, 5, 4, 6, 11
FROM_LOGITS = "route/cross_entropy:from_logits"
PROBABILITIES = "route/cross_entropy:probabilities"
CLAMPED = np.float32(-np.log(np.float32(1e-8)))


# ---------------------------------------------------------------------------
# the oracle: the three lowerings before this file's subject existed
# ---------------------------------------------------------------------------
def _old_softmax(ctx, ins, attrs):
    return {"Out": jax.nn.softmax(ins["X"][0], axis=attrs.get("axis", -1))}


def _old_cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1,
                        keepdims=True)
    else:
        lab = label.astype(jnp.int32)
        if lab.ndim == x.ndim:
            lab = lab.squeeze(-1)
        picked = jnp.take_along_axis(x, lab[..., None], axis=-1)
        loss = -jnp.log(jnp.maximum(picked, eps))
    return {"Y": loss}


def _old_softmax_with_ce(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lab = label.astype(jnp.int32)
        if lab.ndim == logits.ndim:
            lab = lab.squeeze(-1)
        loss = -jnp.take_along_axis(logp, lab[..., None], axis=-1)
    return {"Softmax": jnp.exp(logp), "Loss": loss}


OLD = {"softmax": _old_softmax, "cross_entropy": _old_cross_entropy,
       "softmax_with_cross_entropy": _old_softmax_with_ce}


def _grads():
    return [p.name + "@GRAD" for p in
            pt.default_main_program().global_block().all_parameters()]


def _state():
    scope = pt.global_scope()
    return {n: np.asarray(scope.get(n)) for n in scope.keys()}


def _both(monkeypatch, fetch, feed, amp=False, steps=None):
    """``fetch`` + every parameter gradient from the lowerings as they are
    and from the oracle's, each from the same start, and the route
    counters of the first."""
    fetch = list(fetch) + _grads()
    start = _state()

    def run():
        for n, v in start.items():
            pt.global_scope().set(n, v)
        exe = pt.Executor(amp=amp)       # one cache each: both must trace
        if steps:
            out = exe.run_steps(steps, feed=feed, fetch_list=fetch)
        else:
            out = exe.run(feed=feed, fetch_list=fetch)
        return [np.asarray(v, np.float32) for v in out]

    compile_cache.stats().reset()
    got = run()
    routes = profiler.compile_stats().snapshot()
    with monkeypatch.context() as m:
        for name, impl in OLD.items():
            m.setitem(registry._OP_IMPLS, name, impl)
        want = run()
    assert len(got) == len(want) > len(fetch) - len(_grads())
    return got, want, (routes.get(FROM_LOGITS, 0),
                       routes.get(PROBABILITIES, 0))


def _close(got, want, tol=1e-6):
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=tol * max(1.0, np.abs(w).max()),
            err_msg=f"fetch {i}")


def _start(loss, rate=0.0):
    pt.optimizer.SGD(rate).minimize(loss)
    pt.Executor().run(pt.default_startup_program(), feed={}, fetch_list=[])


def _rows_feed(rng, n=8):
    return {"x": rng.randn(n, D).astype("float32"),
            "y": rng.randint(0, V, (n, 1))}


def _xy():
    return (layers.data("x", shape=[D], dtype="float32"),
            layers.data("y", shape=[1], dtype="int64"))


# ---------------------------------------------------------------------------
# engaged: loss and every parameter gradient equal to the composition's
# ---------------------------------------------------------------------------
def _direct():
    x, y = _xy()
    probs = layers.fc(layers.fc(x, size=H, act="tanh"), size=V, act="softmax")
    rows = layers.cross_entropy(probs, y)
    return dict(fetch=[layers.mean(rows), rows], feed=_rows_feed)


def _through_reshape():
    x = layers.data("x", shape=[T, D], dtype="float32")
    y = layers.data("y", shape=[T], dtype="int64")
    probs = layers.fc(x, size=V, num_flatten_dims=2, act="softmax")
    rows = layers.cross_entropy(layers.reshape(probs, [-1, V]),
                                layers.reshape(y, [-1, 1]))
    return dict(fetch=[layers.mean(rows), rows],
                feed=lambda rng: {"x": rng.randn(B, T, D).astype("float32"),
                                  "y": rng.randint(0, V, (B, T))})


def _rnn_head(lens):
    """The seq2seq shape: the head in the step block, hoisted out of the
    scan; the configuration's reshape and cross_entropy outside."""
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    y = layers.data("y", shape=[], dtype="int64", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)
        h = rnn.memory(shape=[H])
        new = layers.tanh(layers.elementwise_add(
            layers.fc(x_t, size=H), layers.fc(h, size=H, bias_attr=False)))
        rnn.update_memory(h, new)
        rnn.step_output(layers.fc(new, size=V, act="softmax"))
    probs = rnn()
    rows = layers.cross_entropy(layers.reshape(probs, [-1, V]),
                                layers.reshape(y, [-1, 1]))
    lens = np.array(lens)
    return dict(fetch=[layers.mean(rows), rows, probs], lens=lens,
                feed=lambda rng: {"x": rng.randn(B, T, D).astype("float32"),
                                  "x@LEN": lens, "y@LEN": lens,
                                  "y": rng.randint(0, V, (B, T))})


def _clamped_row():
    """Row 0's inputs are large and its label is the least likely class:
    its probability is under 1e-8, so the clamp holds its loss and it
    gives no gradient."""
    x, y = _xy()
    probs = layers.fc(x, size=V, act="softmax")
    rows = layers.cross_entropy(probs, y)

    def feed(rng):
        f = _rows_feed(rng)
        f["x"][0] *= 200.0
        w = np.asarray(pt.global_scope().get(
            pt.default_main_program().global_block().all_parameters()[0]
            .name))
        f["y"][0, 0] = int(np.argmin(f["x"][0] @ w))
        return f

    return dict(fetch=[layers.mean(rows), rows], feed=feed, clamped=[0])


def _probs_read_too():
    x, y = _xy()
    probs = layers.fc(x, size=V, act="softmax")
    rows = layers.cross_entropy(probs, y)
    # a second reader, differentiated as well: both are functions of the logits
    loss = layers.elementwise_add(
        layers.mean(rows), layers.reduce_sum(layers.square(probs)))
    return dict(fetch=[loss, rows, probs], feed=_rows_feed)


def _amp_conv_head():
    """ResNet's end: convolution, pool, fc(act=softmax), cross_entropy,
    under bfloat16 mixed precision."""
    img = layers.data("x", shape=[3, 8, 8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    feat = layers.pool2d(layers.conv2d(img, num_filters=8, filter_size=3,
                                       padding=1, act="relu"),
                         pool_size=8, pool_type="avg")
    rows = layers.cross_entropy(layers.fc(feat, size=V, act="softmax"), y)
    return dict(fetch=[layers.mean(rows), rows], amp=True, tol=2.0 ** -6,
                feed=lambda rng: {"x": rng.randn(8, 3, 8, 8)
                                  .astype("float32"),
                                  "y": rng.randint(0, V, (8, 1))})


ENGAGED = {
    "softmax_then_cross_entropy": _direct,
    "through_a_reshape": _through_reshape,
    "rnn_head_hoisted_full_lengths": lambda: _rnn_head([T, T, T]),
    "rnn_head_hoisted_ragged_lengths": lambda: _rnn_head([T, 2, 4]),
    "row_under_the_clamp": _clamped_row,
    "probabilities_read_too": _probs_read_too,
    "run_steps": lambda: dict(_direct(), steps=3, rate=0.5),
    "amp_conv_head": _amp_conv_head,
}


@pytest.mark.parametrize("case", sorted(ENGAGED))
def test_loss_from_logits_equals_the_composition(case, monkeypatch):
    spec = ENGAGED[case]()
    _start(spec["fetch"][0], spec.get("rate", 0.0))
    feed = spec["feed"](np.random.RandomState(0))
    got, want, routes = _both(monkeypatch, spec["fetch"], feed,
                              amp=spec.get("amp", False),
                              steps=spec.get("steps"))
    assert routes == (1, 0)
    _close(got, want, spec.get("tol", 1e-6))
    rows = got[1]
    if "lens" in spec:             # padded rows: -log 1e-8, as before
        rows = rows.reshape(B, T)
        for b, ln in enumerate(spec["lens"]):
            assert (rows[b, ln:] == CLAMPED).all()
            assert (rows[b, :ln] < CLAMPED).all()
            assert not got[2][b, ln:].any()      # and their probabilities 0
    for r in spec.get("clamped", ()):
        assert rows[r, 0] == CLAMPED == want[1][r, 0]
    if spec.get("steps"):          # the state moved, and the loss with it
        assert got[0].shape == (3,) and got[0][2] < got[0][0]
    assert any(np.abs(g).max() > 0 for g in got[len(spec["fetch"]):])


def test_padded_and_clamped_rows_give_no_gradient():
    """What the oracle's equality cannot show alone: those rows' logits
    get exactly zero, whatever the cotangent."""
    rng = np.random.RandomState(1)
    z = jnp.asarray(rng.randn(4, V) * 3, jnp.float32).at[1, 2].set(-40.0)
    lab = jnp.asarray([0, 2, 5, 7])
    scale = jnp.asarray([[1.0], [1.0], [0.0], [1.0]])

    def loss(z):
        return jnp.sum(nn_ops._nll_from_logits(z, lab, scale)[0]
                       * jnp.asarray([[1.0], [2.0], [3.0], [4.0]]))

    rows = np.asarray(nn_ops._nll_from_logits(z, lab, scale)[0])
    assert rows[1, 0] == CLAMPED == rows[2, 0]
    dz = np.asarray(jax.grad(loss)(z))
    assert not dz[1].any() and not dz[2].any()
    p = np.asarray(jax.nn.softmax(z, -1))
    np.testing.assert_allclose(dz[3], 4.0 * (p[3] - np.eye(V)[7]), atol=1e-6)


# ---------------------------------------------------------------------------
# not engaged: what it was, bit for bit
# ---------------------------------------------------------------------------
def _soft_labels():
    x = layers.data("x", shape=[D], dtype="float32")
    y = layers.data("y", shape=[V], dtype="float32")
    rows = layers.cross_entropy(layers.fc(x, size=V, act="softmax"), y,
                                soft_label=True)

    def feed(rng):
        soft = rng.rand(8, V).astype("float32")
        return {"x": rng.randn(8, D).astype("float32"),
                "y": soft / soft.sum(-1, keepdims=True)}

    return dict(fetch=[layers.mean(rows), rows], feed=feed)


def _softmax_along_another_axis():
    x, y = _xy()
    probs = layers.softmax(layers.fc(x, size=V), axis=0)
    rows = layers.cross_entropy(probs, y)
    return dict(fetch=[layers.mean(rows), rows], feed=_rows_feed)


def _scale_between():
    x, y = _xy()
    probs = layers.scale(layers.fc(x, size=V, act="softmax"), scale=0.5)
    rows = layers.cross_entropy(probs, y)
    return dict(fetch=[layers.mean(rows), rows], feed=_rows_feed)


def _reshape_moves_the_classes():
    x = layers.data("x", shape=[D], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    probs = layers.reshape(layers.fc(x, size=2 * V, act="softmax"), [-1, V])
    rows = layers.cross_entropy(probs, y)
    return dict(fetch=[layers.mean(rows), rows],
                feed=lambda rng: {"x": rng.randn(4, D).astype("float32"),
                                  "y": rng.randint(0, V, (8, 1))})


def _name_bound_again():
    """The softmax's variable is overwritten in place before the loss
    reads it: the note is about the value, not the name."""
    x, y = _xy()
    probs = layers.fc(x, size=V, act="softmax")
    layers.sums([probs, probs], out=probs)
    rows = layers.cross_entropy(probs, y)
    return dict(fetch=[layers.mean(rows), rows], feed=_rows_feed)


def _softmax_in_the_scan():
    """Attention-like weights: a softmax the memory depends on stays in
    the scan body; its stacked output is read outside.  Nothing of the
    body's trace may be left for the loss to find."""
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    y = layers.data("y", shape=[], dtype="int64", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)
        h = rnn.memory(shape=[V])
        w = layers.softmax(layers.elementwise_add(
            layers.fc(x_t, size=V), layers.fc(h, size=V, bias_attr=False)))
        rnn.update_memory(h, w)
        rnn.step_output(w)
    rows = layers.cross_entropy(layers.reshape(rnn(), [-1, V]),
                                layers.reshape(y, [-1, 1]))
    lens = np.array([T, 2, 4])
    return dict(fetch=[layers.mean(rows), rows],
                feed=lambda rng: {"x": rng.randn(B, T, D).astype("float32"),
                                  "x@LEN": lens, "y@LEN": lens,
                                  "y": rng.randint(0, V, (B, T))})


NOT_ENGAGED = {
    "soft_labels": _soft_labels,
    "softmax_along_another_axis": _softmax_along_another_axis,
    "scale_between": _scale_between,
    "reshape_moves_the_classes": _reshape_moves_the_classes,
    "name_bound_again": _name_bound_again,
    "softmax_in_the_scan_read_outside": _softmax_in_the_scan,
}


@pytest.mark.parametrize("case", sorted(NOT_ENGAGED))
def test_not_engaged_is_what_it_was(case, monkeypatch):
    spec = NOT_ENGAGED[case]()
    _start(spec["fetch"][0])
    feed = spec["feed"](np.random.RandomState(0))
    got, want, routes = _both(monkeypatch, spec["fetch"], feed)
    assert routes == (0, 1)
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(g, w, err_msg=f"fetch {i}")
    assert np.isfinite(got[0]).all()


# ---------------------------------------------------------------------------
# softmax_with_cross_entropy: the same function, against log_softmax
# ---------------------------------------------------------------------------
def _swce_with_softmax(logits, label, soft):
    """``layers.softmax_with_cross_entropy`` returns the loss alone; the
    op's other output is declared by the layer, found here by its slot."""
    rows = layers.softmax_with_cross_entropy(logits, label, soft_label=soft)
    op = next(op for op in pt.default_main_program().global_block().ops
              if op.type == "softmax_with_cross_entropy")
    return rows, pt.default_main_program().global_block().var(
        op.outputs["Softmax"][0])


@pytest.mark.parametrize("case", ["loss_alone", "softmax_read_too",
                                  "far_label", "soft_labels"])
def test_softmax_with_cross_entropy_equals_log_softmax(case, monkeypatch):
    x = layers.data("x", shape=[D], dtype="float32")
    soft = case == "soft_labels"
    y = layers.data("y", shape=[V if soft else 1],
                    dtype="float32" if soft else "int64")
    logits = layers.fc(x, size=V)
    rows, probs = _swce_with_softmax(logits, y, soft)
    loss = layers.mean(rows)
    if case == "softmax_read_too":
        loss = layers.elementwise_add(
            loss, layers.reduce_sum(layers.square(probs)))
    _start(loss)
    rng = np.random.RandomState(0)
    feed = _rows_feed(rng)
    if soft:
        lab = rng.rand(8, V).astype("float32")
        feed["y"] = lab / lab.sum(-1, keepdims=True)
    if case == "far_label":        # under 1e-8, and NOT clamped here
        feed["x"][0] *= 200.0
        w = np.asarray(pt.global_scope().get(
            pt.default_main_program().global_block().all_parameters()[0]
            .name))
        feed["y"][0, 0] = int(np.argmin(feed["x"][0] @ w))
    got, want, routes = _both(monkeypatch, [loss, rows, probs], feed)
    assert routes == ((0, 0) if soft else (1, 0))
    _close(got, want, 0.0 if soft else 1e-6)
    if case == "far_label":
        assert got[1][0, 0] > CLAMPED
        assert any(np.abs(g).max() > 0.1 for g in got[3:])


# ---------------------------------------------------------------------------
# structure: no scatter, one rows x classes residual
# ---------------------------------------------------------------------------
def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _wide_scatters(fn, z):
    wide = z.shape
    return [e.primitive.name for e in _eqns(jax.make_jaxpr(jax.grad(fn))(z)
                                            .jaxpr)
            if e.primitive.name.startswith("scatter")
            and any(getattr(v.aval, "shape", None) == wide
                    for v in e.invars)]


def _wide_residuals(fn, z):
    return [why for aval, why in saved_residuals(fn, z)
            if getattr(aval, "shape", None) == z.shape]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gradient_holds_no_scatter_and_one_wide_residual(dtype):
    rng = np.random.RandomState(0)
    z = jnp.asarray(rng.randn(16, V), dtype)
    lab = jnp.asarray(rng.randint(0, V, (16, 1)))
    scale = jnp.ones((16, 1), dtype)

    def new(z):
        return jnp.sum(nn_ops._nll_from_logits(z, lab, scale)[0]
                       .astype(jnp.float32))

    def old(z):
        p = jax.nn.softmax(z, axis=-1) * scale
        picked = jnp.take_along_axis(p, lab, axis=-1)
        return jnp.sum(-jnp.log(jnp.maximum(picked, 1e-8))
                       .astype(jnp.float32))

    assert _wide_scatters(old, z) == ["scatter-add"]     # the walker sees one
    assert _wide_scatters(new, z) == []
    # the logits themselves, and nothing else of their size
    assert _wide_residuals(new, z) == ["from the argument z"]
    assert len(_wide_residuals(old, z)) >= 1
    assert "from the argument z" not in _wide_residuals(old, z)
    assert jax.grad(new)(z).dtype == z.dtype
    np.testing.assert_allclose(
        np.asarray(jax.grad(new)(z), np.float32),
        np.asarray(jax.grad(old)(z), np.float32),
        atol=1e-6 if dtype == "float32" else 2.0 ** -6)


def test_lse_comes_back_in_float32_with_its_gradient():
    """The second result, which ``softmax_with_cross_entropy`` builds its
    ``Softmax`` from, is differentiable like any logsumexp."""
    rng = np.random.RandomState(0)
    z = jnp.asarray(rng.randn(6, V), jnp.bfloat16)
    lab = jnp.asarray(rng.randint(0, V, (6,)))
    loss, lse = nn_ops._nll_from_logits(z, lab, clamp=False)
    assert loss.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    z = z.astype(jnp.float32)
    got = jax.grad(lambda z: jnp.sum(
        nn_ops._nll_from_logits(z, lab, clamp=False)[1] ** 2))(z)
    want = jax.grad(lambda z: jnp.sum(
        jax.nn.logsumexp(z, axis=-1) ** 2))(z)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# the hoisted tail's backward is whole before the scan's starts
# ---------------------------------------------------------------------------
def test_tail_cotangents_are_handed_on_together():
    from paddle_tpu.ops.control_flow_ops import _cotangents_together

    def loss(xs, held):
        a, b = _cotangents_together(xs) if held else xs
        return jnp.sum(a * a) + jnp.sum(jnp.sin(b))

    xs = (jnp.arange(3.0), jnp.arange(4.0))
    for got, want in zip(jax.grad(loss)(xs, True), jax.grad(loss)(xs, False)):
        np.testing.assert_array_equal(got, want)
    names = [e.primitive.name for e in
             _eqns(jax.make_jaxpr(jax.grad(loss), static_argnums=1)(xs, True)
                   .jaxpr)]
    assert names.count("optimization_barrier") == 1
    assert "optimization_barrier" not in [
        e.primitive.name for e in
        _eqns(jax.make_jaxpr(loss, static_argnums=1)(xs, True).jaxpr)]


def test_rnn_puts_what_its_tail_reads_through_the_barrier(monkeypatch):
    """Floating values only (labels and lengths have no cotangent), and
    only when a tail was hoisted."""
    from paddle_tpu.ops import control_flow_ops
    seen = []
    real = control_flow_ops._cotangents_together
    monkeypatch.setattr(control_flow_ops, "_cotangents_together",
                        lambda xs: seen.append(xs) or real(xs))
    spec = _rnn_head([T, 2, 4])
    _start(spec["fetch"][0])
    pt.Executor().run(feed=spec["feed"](np.random.RandomState(0)),
                      fetch_list=spec["fetch"][:1])
    (xs,) = seen
    # the stacked state, the head's weight and its bias
    assert sorted(x.shape for x in xs) == sorted([(V,), (B * T, H), (H, V)])
    assert all(jnp.issubdtype(x.dtype, jnp.floating) for x in xs)
