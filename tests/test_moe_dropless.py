"""The dropless lowering of the ``moe`` op (``capacity_factor=None``,
ops/moe_ops.py ``_dropless``): values, the two router losses and every
gradient against an oracle that loops over tokens and their experts; that
nothing it builds has a capacity; the refusal under expert parallelism; the
route counter; the two primitives of its row movement against the gathers
they replaced, and that nothing reads a tiled array past the tiles in use.
The grouped products and the row gather are the Pallas kernels, interpreted
here (ops/pallas_kernels.py ``grouped_matmul``, ``rows_from_tokens``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.ops import moe_ops


def _weights(rng, n, d, h, e, gated):
    return {"x": rng.randn(n, d).astype("float32"),
            "router": rng.randn(d, e).astype("float32") * 0.7,
            "gate": rng.randn(e, d, h).astype("float32") * 0.4
            if gated else None,
            "up": rng.randn(e, d, h).astype("float32") * 0.4,
            "down": rng.randn(e, h, d).astype("float32") * 0.4}


def _choice(w, top_k):
    """[N, top_k]: each token's experts, by numpy, largest logit first."""
    return np.argsort(-(w["x"] @ w["router"]), axis=-1,
                      kind="stable")[:, :top_k]


def _oracle(choice, w, top_k, here=None):
    """(out, aux, z): a Python loop over tokens and the experts ``choice``
    gives them, those of the range ``here`` where a share is held."""
    x, router = jnp.asarray(w["x"]), jnp.asarray(w["router"])
    n, e = x.shape[0], router.shape[1]
    logits = x @ router
    probs = jax.nn.softmax(logits, axis=-1)
    rows, count = [], np.zeros(e)
    for t in range(n):
        acc = jnp.zeros(w["down"].shape[-1], jnp.float32)
        for ex in choice[t]:
            count[ex] += 1
            if here is not None and ex not in here:
                continue
            u = x[t] @ w["up"][ex]
            hid = jax.nn.silu(u) if w["gate"] is None \
                else jax.nn.silu(x[t] @ w["gate"][ex]) * u
            acc = acc + probs[t, ex] * (hid @ w["down"][ex])
        rows.append(acc)
    aux = e * jnp.sum(jnp.asarray(count / n, jnp.float32)
                      * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return jnp.stack(rows), aux, z


def _system(w, top_k):
    return moe_ops._dropless(
        jnp.asarray(w["x"]), jnp.asarray(w["router"]),
        None if w["gate"] is None else jnp.asarray(w["gate"]),
        jnp.asarray(w["up"]), jnp.asarray(w["down"]), top_k, jax.nn.silu)


def _loss_and_grads(fn, w, top_k, mix):
    """((out, aux, z), {name: gradient}) of a scalar of all three outputs
    with respect to the input and every weight, in one jitted call."""
    held = {k: v for k, v in w.items() if v is not None}

    def loss(held):
        out, aux, z = fn({**w, **held}, top_k)
        return jnp.sum(out * mix) + 0.3 * aux + 0.2 * z, (out, aux, z)

    (_, outs), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(held)
    return outs, grads


CASES = {
    # name: (tokens, features, expert width, experts, top_k, gated, skew)
    "gated-top2": (12, 8, 6, 4, 2, True, None),
    "ungated-top2": (12, 8, 6, 4, 2, False, None),
    "top1": (12, 8, 6, 4, 1, True, None),
    "top8-of-16": (6, 8, 4, 16, 8, True, None),
    # experts 5 and 6 get a large negative logit from every token
    "an-expert-without-tokens": (10, 8, 6, 8, 2, True, "starve"),
    # expert 2 gets a large positive one: top-1 sends it every token
    "all-tokens-to-one-expert": (10, 8, 6, 4, 1, True, "flood"),
    # 11 rows for each of 3 experts: with tiles of 8 rows every expert has
    # two, the second part full
    "odd-row-count-several-tiles": (11, 8, 6, 3, 3, True, "tile8"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dropless_matches_the_token_loop(case, monkeypatch):
    n, d, h, e, top_k, gated, skew = CASES[case]
    rng = np.random.RandomState(sorted(CASES).index(case))
    w = _weights(rng, n, d, h, e, gated)
    if skew == "tile8":
        monkeypatch.setattr(moe_ops, "ROW_TILE", 8)
    elif skew:
        w["x"][:, 0] = 1.0
        w["router"][0] = 0.0
        if skew == "starve":
            w["router"][0, 5:7] = -40.0
        else:
            w["router"][0, 2] = 40.0
    mix = jnp.asarray(rng.randn(n, d).astype("float32"))
    (out, aux, z), grads = _loss_and_grads(_system, w, top_k, mix)
    (ref_out, ref_aux, ref_z), refs = _loss_and_grads(
        functools.partial(_oracle, _choice(w, top_k)), w, top_k, mix)
    np.testing.assert_allclose(out, ref_out, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-5)
    np.testing.assert_allclose(z, ref_z, rtol=1e-5)
    if skew == "flood":
        np.testing.assert_allclose(aux, e * jnp.mean(
            jax.nn.softmax(w["x"] @ w["router"], -1)[:, 2]), rtol=1e-5)
    assert set(grads) == {"x", "router", "up", "down"} | (
        {"gate"} if gated else set())
    for name in grads:                # the input and all the weights
        np.testing.assert_allclose(grads[name], refs[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)


def test_nothing_has_a_capacity_or_an_expert_axis_over_tokens():
    """Every array of the lowering, forward and backward, is a routed-rows
    array at most: none has the extent of a [E, C] dispatch (GShard's
    capacity at factor 1.0 already) or of tokens x experts x features."""
    n, d, h, e, top_k = 2048, 16, 24, 8, 2
    w = _weights(np.random.RandomState(0), n, d, h, e, True)
    held = {k: jnp.asarray(v) for k, v in w.items()}
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda hw: sum(jnp.sum(o) for o in _system(hw, top_k))))(held)
    routed_rows = (n * top_k // moe_ops.ROW_TILE + e) * moe_ops.ROW_TILE
    largest = max(routed_rows * max(d, h), e * d * h)
    capacity = top_k * n // e
    seen = 0

    def walk(jp):
        nonlocal seen
        for eqn in jp.eqns:
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                seen += 1
                assert np.prod(shape, dtype=np.int64) <= largest, \
                    (eqn.primitive, shape)
                assert not (e in shape and capacity in shape), \
                    (eqn.primitive, shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert seen > 50 and n * e * d > largest and capacity * e * d > 0
    assert moe_ops.ROW_TILE not in (e, capacity)


def _program(capacity_factor, **kw):
    x = layers.data("x", shape=[6, 8], dtype="float32")
    out, aux, z = layers.moe(x, num_experts=4, expert_hidden=5, top_k=2,
                             capacity_factor=capacity_factor, **kw)
    loss = layers.elementwise_add(layers.mean(out),
                                  layers.elementwise_add(aux, z))
    pt.optimizer.SGD(0.1).minimize(loss)
    return loss, {"x": np.random.RandomState(1).randn(3, 6, 8)
                  .astype("float32")}


@pytest.mark.parametrize("capacity_factor,gated,ran", [
    (None, True, {"dropless", "gated_pair"}),
    (None, False, {"dropless", "single"}),
    (1.25, False, {"capacity"})])
def test_the_lowering_that_ran_is_counted(capacity_factor, gated, ran):
    loss, feed = _program(capacity_factor,
                          **({"gated": gated, "act": "silu"}
                             if capacity_factor is None else {}))
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    before = profiler.compile_stats().snapshot()
    first = float(exe.run(feed=feed, fetch_list=[loss])[0])
    for _ in range(3):
        last = float(exe.run(feed=feed, fetch_list=[loss])[0])
    assert np.isfinite(first) and last < first
    after = profiler.compile_stats().snapshot()
    routes = {k.split(":", 1)[1]: after[k] - before.get(k, 0)
              for k in after if k.startswith("route/moe:")}
    assert {k: v for k, v in routes.items() if v} == dict.fromkeys(ran, 1)


def test_dropless_refuses_expert_parallelism():
    from paddle_tpu.parallel import MeshConfig, ShardedExecutor, make_mesh

    loss, feed = _program(None, gated=True, act="silu")
    exe = ShardedExecutor(mesh=make_mesh(MeshConfig(ep=4),
                                         devices=jax.devices()[:4]))
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    with pytest.raises(NotImplementedError, match="ep=4"):
        exe.run(feed=feed, fetch_list=[loss])


def test_gated_experts_need_the_dropless_lowering():
    loss, feed = _program(1.25, gated=True)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    with pytest.raises(NotImplementedError, match="dropless"):
        exe.run(feed=feed, fetch_list=[loss])


# ---------------------------------------------------------------------------
# the row movement between the token order and the tiled order
# ---------------------------------------------------------------------------
ROWS_CASES = {
    # name: (tokens, router outputs, experts held, first held, top_k, skew)
    "a-share-8-of-32-from-0": (24, 32, 8, 0, 4, None),
    "a-share-8-of-32-from-8": (24, 32, 8, 8, 4, None),
    "every-expert-held-top8": (12, 16, 16, 0, 8, None),
    # the second expert held gets a large negative logit from every token
    "an-expert-with-no-row": (24, 32, 8, 8, 4, "starve"),
    # every expert held does: num_tiles is the experts held, all padding
    "no-assignment-lands-here": (24, 32, 8, 8, 4, "none"),
    # four of the held get a large positive one: every slot of every token
    # is here, and the tiles in use reach the bound
    "every-slot-of-a-token-here": (24, 32, 8, 8, 4, "all"),
}
ROWS_TILE, ROWS_SPAN, ROWS_D = 8, 3, 16


def _logits(case):
    n, e, held, first, top_k, skew = ROWS_CASES[case]
    logits = np.random.RandomState(sorted(ROWS_CASES).index(case)).randn(
        n, e).astype("float32")
    if skew == "starve":
        logits[:, first + 1] = -40.0
    elif skew == "none":
        logits[:, first:first + held] = -40.0
    elif skew == "all":
        logits[:, first + 2:first + 6] += 40.0
    return logits


def _tiled_order(expert, held, first, tm):
    """By numpy, what ``_dropless`` computes with sorts and cumulative
    sums: (assignment of a tiled row, its token, tiled row of an
    assignment [n, top_k], num_tiles [1]); past the end where there is
    none."""
    n, top_k = expert.shape
    rows = (n * top_k // tm + held) * tm
    token = np.full(rows, n, np.int32)
    assignment = np.full(rows, n * top_k, np.int32)
    row_of = np.full(n * top_k, rows, np.int32)
    at = 0
    for e in range(first, first + held):
        mine = np.flatnonzero(expert.reshape(-1) == e)   # token-major
        token[at:at + len(mine)] = mine // top_k
        assignment[at:at + len(mine)] = mine
        row_of[mine] = at + np.arange(len(mine))
        at += max(-(-len(mine) // tm), 1) * tm
    return tuple(map(jnp.asarray, (assignment, token,
                                   row_of.reshape(n, top_k),
                                   np.array([at // tm], np.int32))))


def _past_the_count(a, num_tiles, value=np.nan):
    """``a`` with ``value`` in every row of the tiles past the count."""
    past = jnp.arange(a.shape[0]) >= num_tiles[0] * ROWS_TILE
    return jnp.where(past.reshape((-1,) + (1,) * (a.ndim - 1)), value, a)


def _take(x, index):
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def _index(case):
    """``_dropless``'s index tuple of a case, by numpy."""
    _, e, held, first, top_k, _ = ROWS_CASES[case]
    return _tiled_order(
        _choice({"x": _logits(case), "router": np.eye(e, dtype="float32")},
                top_k), held, first, ROWS_TILE)


def _check_dispatch(case):
    """rows-from-tokens forward, tokens-from-rows as its transpose."""
    n, e, held, _, top_k, _ = ROWS_CASES[case]
    rng = np.random.RandomState(7)
    index = _, token, row_of, num_tiles = _index(case)
    x = jnp.asarray(rng.randn(n, ROWS_D).astype("float32"))
    rows, vjp = jax.vjp(lambda x: moe_ops._dispatch(
        x, index, ROWS_TILE, held == e), x)
    used = int(num_tiles[0]) * ROWS_TILE     # past it nothing is written
    np.testing.assert_array_equal(rows[:used], _take(x, token)[:used])
    g = _past_the_count(jnp.asarray(
        rng.randn(*rows.shape).astype("float32")), num_tiles)
    # (the sum over a token's rows runs in tile order, not slot order)
    np.testing.assert_allclose(
        vjp(g)[0],
        _take(g, row_of.T.reshape(-1)).reshape(top_k, n, -1).sum(0),
        rtol=1e-6, atol=1e-6)


def _check_combine(case):
    """tokens-from-rows with the weights forward, rows-from-tokens with
    the weights and the weights' own gradient as its transpose."""
    n, e, held, _, top_k, _ = ROWS_CASES[case]
    rng = np.random.RandomState(8)
    index = assignment, token, row_of, num_tiles = _index(case)
    down = _past_the_count(jnp.asarray(rng.randn(
        token.shape[0], ROWS_D).astype("float32")), num_tiles)
    weight = jnp.asarray(rng.rand(n, top_k).astype("float32"))
    out, vjp = jax.vjp(lambda down, weight: moe_ops._combine(
        down, weight, index, ROWS_TILE, held == e), down, weight)
    picked = _take(down, row_of.T.reshape(-1)).reshape(top_k, n, -1)
    np.testing.assert_allclose(
        out, jnp.sum(picked * weight.T[..., None], axis=0),
        rtol=1e-6, atol=1e-6)
    g = jnp.asarray(rng.randn(n, ROWS_D).astype("float32"))
    d_down, d_weight = vjp(g)
    reader = jnp.where(assignment < n * top_k,
                       assignment % top_k * n + assignment // top_k,
                       n * top_k)
    used = int(num_tiles[0]) * ROWS_TILE     # past it nothing is written
    np.testing.assert_array_equal(                           # bit for bit
        d_down[:used], _take((g[None] * weight.T[..., None]).reshape(
            top_k * n, -1), reader)[:used])
    np.testing.assert_allclose(d_weight, jnp.sum(picked * g[None], -1).T,
                               rtol=1e-6, atol=1e-6)


def _share_of_the_op(case, gated):
    """(weights, mix, system) of a case: ``_dropless`` over the experts the
    case holds, the router the identity so that ``x`` IS the logits."""
    n, e, held, first, _, _ = ROWS_CASES[case]
    rng = np.random.RandomState(9)
    w = _weights(rng, n, e, 6, e, gated)
    w.update(x=_logits(case), router=np.eye(e, dtype="float32"))
    mix = jnp.asarray(rng.randn(n, e).astype("float32"))

    def system(w, top_k):
        return moe_ops._dropless(
            *(jnp.asarray(w[k]) for k in ("x", "router")),
            *(None if w[k] is None else jnp.asarray(w[k])[first:first + held]
              for k in ("gate", "up", "down")),
            top_k, jax.nn.silu, expert_offset=first)
    return w, mix, system


def _check_poison(case, monkeypatch):
    """The op with NaN in every tiled array past ``num_tiles`` (the rows
    handed to the experts, what they hand back, and the cotangents the
    other way): output and every gradient are the bits of the clean run,
    so nothing reads past the count."""
    from paddle_tpu.ops import pallas_kernels

    n, e, held, first, top_k, _ = ROWS_CASES[case]

    clean = {"single": pallas_kernels.grouped_matmul,
             "gated": pallas_kernels.gated_grouped_matmul}

    def run(value):
        """Through the same program twice: the rows past the count set to
        ``value`` on the way into the experts and out of them, forward and
        backward."""
        @jax.custom_vjp
        def past(a, num_tiles):
            return _past_the_count(a, num_tiles, value)

        past.defvjp(lambda a, num_tiles: (past(a, num_tiles), num_tiles),
                    lambda num_tiles, g: (past(g, num_tiles), None))

        def single(lhs, rhs, tile_group, num_tiles):
            return past(clean["single"](
                past(lhs, num_tiles), rhs, tile_group, num_tiles), num_tiles)

        def gated(rows, w_gate, w_up, tile_group, num_tiles, act, **how):
            return past(clean["gated"](
                past(rows, num_tiles), w_gate, w_up, tile_group, num_tiles,
                act, **how), num_tiles)

        with monkeypatch.context() as m:
            m.setattr(pallas_kernels, "grouped_matmul", single)
            m.setattr(pallas_kernels, "gated_grouped_matmul", gated)
            w, mix, system = _share_of_the_op(case, True)
            return _loss_and_grads(system, w, top_k, mix)

    (out, aux, z), grads = run(0.0)        # what the tiles hold anyway
    (p_out, p_aux, p_z), p_grads = run(np.nan)
    assert np.isfinite(out).all() and (
        bool(np.any(out)) or ROWS_CASES[case][5] == "none")
    for a, b in [(out, p_out), (aux, p_aux), (z, p_z)] + [
            (grads[k], p_grads[k]) for k in grads]:
        np.testing.assert_array_equal(a, b)


def _check_unwritten(case, gated, monkeypatch):
    """The op as it is, its kernels interpreted (the interpreter fills a
    result with NaN before a kernel runs): every tiled array that enters or
    leaves the experts, forward and backward, is NaN past ``num_tiles``,
    and output, losses and every gradient are finite and the token loop's:
    no kernel stores there and nothing relies on what stands there."""
    from paddle_tpu.ops import pallas_kernels

    n, e, held, first, top_k, _ = ROWS_CASES[case]
    seen = {}

    def spy(name):
        """The identity that shows its argument, and on the way back its
        cotangent, to the test."""
        @jax.custom_vjp
        def show(a):
            jax.debug.callback(lambda v: seen.__setitem__(name, v), a)
            return a

        def back(_, g):
            jax.debug.callback(lambda v: seen.__setitem__("d " + name, v), g)
            return (g,)

        show.defvjp(lambda a: (show(a), None), back)
        return show

    def spied(fn, name):
        def call(lhs, *rest, **kwargs):
            return spy(name)(fn(spy("rows of " + name)(lhs), *rest, **kwargs))
        return call

    monkeypatch.setattr(pallas_kernels, "grouped_matmul", spied(
        pallas_kernels.grouped_matmul, "down"))
    monkeypatch.setattr(pallas_kernels, "gated_grouped_matmul", spied(
        pallas_kernels.gated_grouped_matmul, "hidden"))
    w, mix, system = _share_of_the_op(case, gated)
    (out, aux, z), grads = jax.block_until_ready(
        _loss_and_grads(system, w, top_k, mix))
    (ref_out, ref_aux, ref_z), refs = _loss_and_grads(
        functools.partial(_oracle, _choice(w, top_k),
                          here=range(first, first + held)), w, top_k, mix)
    # (the skewed cases' inputs reach 40 and the outputs hundreds)
    np.testing.assert_allclose(out, ref_out, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(aux, ref_aux, rtol=1e-5)
    np.testing.assert_allclose(z, ref_z, rtol=1e-5)
    assert set(grads) == {"x", "router", "up", "down"} | (
        {"gate"} if gated else set())
    for name in grads:
        assert np.isfinite(grads[name]).all(), name
        np.testing.assert_allclose(grads[name], refs[name], rtol=1e-4,
                                   atol=1e-5, err_msg=name)
    used = int(_index(case)[3][0]) * ROWS_TILE
    assert set(seen) == {a + b for a in ("", "d ") for b in (
        "rows of hidden", "hidden", "rows of down", "down")}
    for name, tiled in seen.items():
        assert np.isfinite(tiled[:used]).all(), name
        assert np.isnan(tiled[used:]).all(), name


@pytest.mark.parametrize("check", ["dispatch", "combine", "poison",
                                   "unwritten-gated", "unwritten-single"])
@pytest.mark.parametrize("case", sorted(ROWS_CASES))
def test_rows_move_with_the_tiles_in_use(case, check, monkeypatch):
    """The two primitives of ``_dropless``'s row movement and their
    gradients against the ``jnp.take`` formulas they replaced (bit for bit
    where no sum changed its order, to 1e-6 where one did), in tiles of 8
    rows and passes of 3 tiles so that a last pass overlaps the one before;
    every tiled array holds NaN past ``num_tiles``: put there (``poison``),
    or left there by the interpreter because no kernel, the experts' among
    them, stores past the count (``unwritten``, a case each form)."""
    monkeypatch.setattr(moe_ops, "ROW_TILE", ROWS_TILE)
    monkeypatch.setattr(moe_ops, "TILE_SPAN", ROWS_SPAN)
    if check == "poison":
        _check_poison(case, monkeypatch)
    elif check.startswith("unwritten"):
        _check_unwritten(case, check.endswith("gated"), monkeypatch)
    else:
        {"dispatch": _check_dispatch, "combine": _check_combine}[check](case)
