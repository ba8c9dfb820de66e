"""The dropless lowering of the ``moe`` op (``capacity_factor=None``,
ops/moe_ops.py ``_dropless``): values, the two router losses and every
gradient against an oracle that loops over tokens and their experts; that
nothing it builds has a capacity; the refusal under expert parallelism; the
route counter.  The grouped products are the Pallas kernels, interpreted
here (ops/pallas_kernels.py ``grouped_matmul``)."""
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


def _oracle(choice, w, top_k):
    """(out, aux, z): a Python loop over tokens and the experts ``choice``
    gives them."""
    x, router = jnp.asarray(w["x"]), jnp.asarray(w["router"])
    n, e = x.shape[0], router.shape[1]
    logits = x @ router
    probs = jax.nn.softmax(logits, axis=-1)
    rows, count = [], np.zeros(e)
    for t in range(n):
        acc = 0.0
        for ex in choice[t]:
            count[ex] += 1
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
    (None, False, {"dropless"}),
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
