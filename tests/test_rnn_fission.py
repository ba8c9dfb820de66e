"""Loop fission of an ``rnn`` step block (``ops/control_flow_ops.py _rnn``):
the ops that no memory depends on run once after the scan, on the rows of
all steps.  Every case is held to an oracle kept here, which interprets the
WHOLE block step after step; what decides the split is only what the
lowering can observe in the Program."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as pt
from paddle_tpu import layers, models, profiler
from paddle_tpu.core import compile_cache, registry
from paddle_tpu.layers.control_flow import (ConditionalBlock, DynamicRNN,
                                             StaticRNN)

B, T, D, H, V = 3, 5, 4, 6, 11


def _whole_block_rnn(scan):
    """The ``rnn`` lowering without the split: the whole block, one step
    after the other — in a Python loop, or (``scan``) under ``lax.scan``,
    which is the lowering this repo had before the split."""

    def impl(ctx, ins, attrs):
        sub_idx = attrs["sub_block"]
        step_in_names = attrs["step_inputs"]
        mem_names = attrs["mem_step_names"]
        mem_update_names = attrs["mem_update_names"]
        out_step_names = attrs["step_output_names"]
        seqs, inits = ins.get("Inputs", []), ins.get("InitStates", [])
        env = ctx.env
        n_b, n_t = seqs[0].shape[:2]
        parents = ctx.op.inputs.get("Inputs", [])
        lens = next((ctx.get_len(nm) for nm in parents
                     if ctx.get_len(nm) is not None), None)
        if lens is None:
            lens = jnp.full((n_b,), n_t, jnp.int32)
        step_mask = (jnp.arange(n_t)[None, :] < lens[:, None]).astype(
            seqs[0].dtype).T
        xs = [jnp.swapaxes(s, 0, 1) for s in seqs]
        nested = [(nm, ctx.get_len2(p)) for nm, p in zip(step_in_names,
                                                         parents)
                  if ctx.get_len2(p) is not None]
        l2s = [jnp.swapaxes(l2, 0, 1) for _, l2 in nested]
        uid = ctx._op_uid

        def step(mems, inp):
            m_t = inp[0]
            slices = inp[1:1 + len(step_in_names)]
            benv = ctx.child_env(sub_idx, env)
            benv.local.update(zip(step_in_names, slices))
            for (nm, _), l2 in zip(nested, inp[1 + len(step_in_names):]):
                benv.local[nm + "@LEN"] = l2
            benv.local.update(zip(mem_names, mems))
            ctx._op_uid = uid     # a block traced once draws one key
            ctx.interpret_block(sub_idx, benv)
            new_mems = tuple(
                jnp.where(m_t.reshape((n_b,) + (1,) * (old.ndim - 1)) > 0,
                          benv.get(un), old) if un else old
                for un, old in zip(mem_update_names, mems))
            outs = tuple(
                benv.get(nm) * m_t.reshape(
                    (n_b,) + (1,) * (benv.get(nm).ndim - 1))
                for nm in out_step_names)
            return new_mems, outs

        scanned = tuple([step_mask] + xs + l2s)
        if scan:
            _, outs = lax.scan(step, tuple(inits), scanned)
        else:
            mems, per_t = tuple(inits), []
            for t in range(n_t):
                mems, o = step(mems, tuple(a[t] for a in scanned))
                per_t.append(o)
            outs = [jnp.stack(o) for o in zip(*per_t)]
        for nm, step_nm in zip(ctx.op.outputs.get("Outputs", []),
                               out_step_names):
            ctx.set_len(nm, lens)
            sv = ctx.block(sub_idx).vars.get(step_nm)
            if nested and sv is not None and sv.lod_level >= 1:
                ctx.set_len2(nm, nested[0][1])
        return {"Outputs": [jnp.swapaxes(o, 0, 1) for o in outs]}

    return impl


# ---------------------------------------------------------------------------
# step blocks.  Each returns (rnn outputs, ops that leave the scan).
# ---------------------------------------------------------------------------
def _recur(x_t, h):
    return layers.tanh(layers.elementwise_add(
        layers.fc(x_t, size=H), layers.fc(h, size=H, bias_attr=False)))


def _head_in_step(rnn_cls=StaticRNN):
    """The seq2seq shape: fc + softmax over the dictionary in the step."""
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = rnn_cls()
    with rnn.step():
        x_t = rnn.step_input(x)          # first: it sizes the memory
        h = rnn.memory(shape=[H])
        new = _recur(x_t, h)
        rnn.update_memory(h, new)
        rnn.step_output(layers.fc(new, size=V, act="softmax"))
    return [rnn()], 3


def _output_is_memory():
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)          # first: it sizes the memory
        h = rnn.memory(shape=[H])
        new = _recur(x_t, h)
        rnn.update_memory(h, new)
        rnn.step_output(new)
    return [rnn()], 0


def _tail_reads_input_and_old_memory():
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)
        h = rnn.memory(shape=[H])
        rnn.update_memory(h, _recur(x_t, h))
        # mul, add (bias), add (the memory BEFORE its update), sigmoid
        rnn.step_output(layers.sigmoid(layers.elementwise_add(
            layers.fc(x_t, size=H), h)))
    return [rnn()], 4


def _one_output_each():
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)          # first: it sizes the memory
        h = rnn.memory(shape=[H])
        new = _recur(x_t, h)
        rnn.update_memory(h, new)
        rnn.step_output(layers.fc(new, size=V, act="softmax"))
        rnn.step_output(new)
    return list(rnn()), 3


def _dropout_in_step():
    """One draw among the moved ops' positions, one on the way to an
    output (its ``fc`` stays with it), and the head behind them."""
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)          # first: it sizes the memory
        h = rnn.memory(shape=[H])
        new = _recur(x_t, h)
        rnn.update_memory(h, new)
        probs = layers.fc(new, size=V, act="softmax")
        kept = layers.dropout(layers.fc(new, size=H), dropout_prob=0.4)
        rnn.step_output(probs)
        rnn.step_output(layers.scale(kept, scale=2.0))
    return list(rnn()), 4            # the head and the scale


def _not_rowwise_on_the_way():
    """A sum over the batch, and layer_norm (no rule): they stay, with
    what feeds them; what comes after them leaves."""
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)          # first: it sizes the memory
        h = rnn.memory(shape=[H])
        new = _recur(x_t, h)
        rnn.update_memory(h, new)
        logits = layers.fc(new, size=V)
        centred = layers.elementwise_sub(
            logits, layers.reduce_sum(logits, dim=0, keep_dim=True))
        rnn.step_output(layers.softmax(centred))
        rnn.step_output(layers.tanh(layers.layer_norm(
            layers.fc(new, size=H))))
    return list(rnn()), 2            # softmax, tanh


def _name_bound_twice():
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)
        h = rnn.memory(shape=[H])
        new = _recur(x_t, h)
        rnn.update_memory(h, new)
        logits = layers.fc(new, size=V)
        layers.sums([logits, logits], out=logits)    # in place
        rnn.step_output(layers.softmax(logits))
    return [rnn()], 0


def _declared_shape_is_wrong():
    """The rules answer for declared shapes; the scan sees the real ones."""
    outs, _ = _head_in_step()
    block = pt.default_main_program().blocks[1]
    state = next(op for op in block.ops if op.type == "tanh").output("Out")[0]
    block.vars[state].shape = (-1, H, 1)
    return outs, 0


def _print_in_step():
    """A side effect on the way to an output: it stays, with its fc."""
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)
        h = rnn.memory(shape=[H])
        new = _recur(x_t, h)
        rnn.update_memory(h, new)
        logits = layers.fc(new, size=V)
        block = pt.default_main_program().current_block()
        seen = block.create_var(name="seen", dtype="float32",
                                shape=logits.shape)
        block.append_op("print", inputs={"In": [logits]},
                        outputs={"Out": [seen]}, attrs={"message": "step"})
        rnn.step_output(layers.softmax(seen))
    return [rnn()], 1


def _sub_block_in_step():
    """A conditional_block that reads the logits and rewrites their copy:
    all of that stays; the other output's layer leaves."""
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)
        h = rnn.memory(shape=[H])
        new = _recur(x_t, h)
        rnn.update_memory(h, new)
        logits = layers.fc(new, size=V)
        out = layers.scale(logits, scale=1.0)
        with ConditionalBlock([layers.less_than(
                layers.fill_constant([1], "float32", 0.0),
                layers.fill_constant([1], "float32", 1.0))]).block():
            layers.assign(layers.scale(logits, scale=2.0), output=out)
        rnn.step_output(layers.softmax(out))
        rnn.step_output(layers.fc(new, size=H, act="tanh"))
    return list(rnn()), 3


def _nested_sequence_input():
    x = layers.data("x", shape=[D], dtype="float32", lod_level=2)
    rnn = StaticRNN()
    with rnn.step():
        sub = rnn.step_input(x)          # [B, T', D], itself a sequence
        sub.lod_level = 1
        h = rnn.memory(shape=[H])
        new = _recur(layers.sequence_pool(sub, "sum"), h)
        rnn.update_memory(h, new)
        rnn.step_output(layers.fc(new, size=V, act="softmax"))
    return [rnn()], 0


CASES = {
    "head_in_step": dict(build=_head_in_step),
    "output_is_memory": dict(build=_output_is_memory),
    "tail_reads_input_and_old_memory":
        dict(build=_tail_reads_input_and_old_memory),
    "one_output_from_each_part": dict(build=_one_output_each),
    "ragged_to_length_one": dict(build=_head_in_step, lens=[1, T, 2]),
    "dynamic_rnn": dict(build=lambda: _head_in_step(DynamicRNN)),
    "dropout_in_step": dict(build=_dropout_in_step),
    "not_rowwise_on_the_way": dict(build=_not_rowwise_on_the_way),
    "nested_sequence_input": dict(build=_nested_sequence_input, nested=True),
    "name_bound_twice": dict(build=_name_bound_twice),
    "print_in_step": dict(build=_print_in_step),
    "sub_block_in_step": dict(build=_sub_block_in_step),
    "declared_shape_is_wrong": dict(build=_declared_shape_is_wrong),
    "is_test_stacked_output": dict(build=_head_in_step, is_test=True),
    "amp": dict(build=_head_in_step, amp=True, grad_tol=2.0 ** -6),
}


def _run(outs, loss, feed, is_test, amp):
    fetch = list(outs)
    if not is_test:
        fetch += [loss] + [p.name + "@GRAD" for p in
                           pt.default_main_program().global_block()
                           .all_parameters()]
    exe = pt.Executor(amp=amp)       # one cache each: both must trace
    return [np.asarray(v, np.float32) for v in
            exe.run(feed=feed, fetch_list=fetch, is_test=is_test)]


@pytest.mark.parametrize("case", sorted(CASES))
def test_split_block_equals_whole_block(case, monkeypatch):
    spec = CASES[case]
    outs, n_moved = spec["build"]()
    is_test, amp = spec.get("is_test", False), spec.get("amp", False)
    loss = None
    for o in outs:
        term = layers.reduce_sum(layers.square(o))
        loss = term if loss is None else layers.elementwise_add(loss, term)
    if not is_test:
        pt.optimizer.SGD(0.0).minimize(loss)
    pt.Executor().run(pt.default_startup_program(), feed={}, fetch_list=[])
    rng = np.random.RandomState(0)
    lens = np.array(spec.get("lens", [T, 2, 4]))
    if spec.get("nested"):
        feed = {"x": rng.randn(B, T, 3, D).astype("float32"), "x@LEN": lens,
                "x@LEN2": rng.randint(1, 4, (B, T))}
    else:
        feed = {"x": rng.randn(B, T, D).astype("float32"), "x@LEN": lens}

    compile_cache.stats().reset()
    got = _run(outs, loss, feed, is_test, amp)
    assert profiler.compile_stats().snapshot()["rnn_ops_hoisted"] == n_moved
    with monkeypatch.context() as m:
        m.setitem(registry._OP_IMPLS, "rnn", _whole_block_rnn(scan=False))
        want = _run(outs, loss, feed, is_test, amp)
    assert len(got) == len(want) > (0 if is_test else len(outs) + 1)
    for i, (g, w) in enumerate(zip(got, want)):
        tol = 1e-6 if i < len(outs) else spec.get("grad_tol", 1e-6)
        np.testing.assert_allclose(
            g, w, rtol=0, atol=tol * max(1.0, np.abs(w).max()),
            err_msg=f"{case}: fetch {i}")
    # padded steps emit zeros, and something was emitted
    for o in got[:len(outs)]:
        assert np.abs(o).max() > 0
        for b, ln in enumerate(lens):
            assert not o[b, ln:].any()


def _tiny_seq2seq():
    src = layers.data("src", shape=[], dtype="int64", lod_level=1)
    tgt = layers.data("tgt", shape=[], dtype="int64", lod_level=1)
    probs = models.seq2seq_attention(src, tgt, 13, V, emb_dim=D,
                                     hidden_dim=H)
    rng = np.random.RandomState(0)
    feed = {"src": rng.randint(0, 13, (B, 4)), "src@LEN": np.array([4, 2, 3]),
            "tgt": rng.randint(0, V, (B, T)), "tgt@LEN": np.array([T, 1, 3])}
    pt.Executor().run(pt.default_startup_program(), feed={}, fetch_list=[])
    return probs, feed


def _jaxpr(fetch, feed):
    """The jaxpr of the forward step, as the executor would trace it."""
    exe = pt.Executor()
    main = pt.default_main_program()
    state = {k: pt.global_scope().get(k)
             for k in exe._state_keys(main, pt.global_scope())}
    fn = exe._make_fn(main, [fetch.name], True)
    return jax.make_jaxpr(fn)({k: jnp.asarray(v) for k, v in feed.items()},
                              state, jnp.uint32(0))


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def test_seq2seq_head_is_not_in_the_while_body():
    probs, feed = _tiny_seq2seq()
    stacked = set()
    for scan in _scans(_jaxpr(probs, feed).jaxpr):
        body = scan.params["jaxpr"].jaxpr
        for eqn in body.eqns:
            if eqn.primitive.name == "dot_general":
                assert all(V not in v.aval.shape for v in eqn.invars), eqn
        n_ys = len(body.outvars) - scan.params["num_carry"]
        stacked |= {v.aval.shape for v in scan.outvars[-n_ys:]}
    assert (T, B, H) in stacked             # the decoder's states
    assert not any(V in shape for shape in stacked)


def test_empty_tail_gives_the_jaxpr_of_the_whole_block(monkeypatch):
    outs, n_moved = _output_is_memory()
    assert n_moved == 0
    pt.Executor().run(pt.default_startup_program(), feed={}, fetch_list=[])
    feed = {"x": np.zeros((B, T, D), "float32"), "x@LEN": np.array([T, 2, 4])}
    split = str(_jaxpr(outs[0], feed))
    monkeypatch.setitem(registry._OP_IMPLS, "rnn", _whole_block_rnn(scan=True))
    assert split == str(_jaxpr(outs[0], feed))


def test_compile_stats_count_the_moved_ops():
    probs, feed = _tiny_seq2seq()
    compile_cache.stats().reset()
    pt.Executor().run(feed=feed, fetch_list=[probs], is_test=True)
    counts = profiler.compile_stats().snapshot()
    assert (counts["rnn_ops_hoisted"], counts["rnn_ops_in_scan"]) == (3, 12)
    assert re.search(r"rnn_ops_hoisted: 3\b", profiler.compile_report())
    assert "rnn_ops_hoisted: 3" in profiler.report()

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    x = layers.data("x", shape=[D], dtype="float32", lod_level=1)
    hidden, _ = _lstm_step(x)
    pt.Executor().run(pt.default_startup_program(), feed={}, fetch_list=[])
    compile_cache.stats().reset()
    pt.Executor().run(feed={"x": np.ones((B, T, D), "float32"),
                            "x@LEN": np.array([T, 2, 4])},
                      fetch_list=[hidden], is_test=True)
    counts = profiler.compile_stats().snapshot()
    assert counts["rnn_ops_hoisted"] == 0 and counts["rnn_ops_in_scan"] > 0


def _lstm_step(x):
    """An LSTM written out in the step block: its output is its memory."""
    rnn = StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(x)
        h, c = rnn.memory(shape=[H]), rnn.memory(shape=[H])
        gates = layers.elementwise_add(
            layers.fc(x_t, size=4 * H), layers.fc(h, size=4 * H,
                                                  bias_attr=False))
        i, f, o, g = layers.split(gates, 4, dim=1)
        new_c = layers.elementwise_add(
            layers.elementwise_mul(layers.sigmoid(f), c),
            layers.elementwise_mul(layers.sigmoid(i), layers.tanh(g)))
        new_h = layers.elementwise_mul(layers.sigmoid(o), layers.tanh(new_c))
        rnn.update_memory(h, new_h)
        rnn.update_memory(c, new_c)
        rnn.step_output(new_h)
        rnn.step_output(new_c)
    return rnn()
