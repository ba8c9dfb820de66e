"""The device trace speaks the Program's language: every instruction a
compiled step emits carries ``pt.<op_type>:<block>.<position>`` in its HLO
``op_name`` (``LoweringContext.op_scope``, unconditional), the jitted step
functions have stable names (``jit_pt_run`` ...), and there is one public
way from a fingerprint to the text of what ran
(``CompiledProgram.hlo_text`` / ``profiler.compiled_hlo_text``)."""
import gc
import re

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, profiler
from paddle_tpu.core import compile_cache
from paddle_tpu.core.compile_cache import retrace_guard
from paddle_tpu.observability import metrics as obs


@pytest.fixture(autouse=True)
def _fresh_stats():
    compile_cache.stats().reset()
    obs.registry().reset()
    yield
    compile_cache.stats().reset()
    obs.registry().reset()
    compile_cache._observed_steps.clear()


def _conv_net():
    """conv + batch norm + mul + cross_entropy + Momentum, tiny."""
    img = layers.data("img", shape=[3, 8, 8], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    conv = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False)
    bn = layers.batch_norm(conv, act="relu")
    pred = layers.fc(bn, size=5, act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.Momentum(0.01, momentum=0.9).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"img": rng.rand(2, 3, 8, 8).astype("float32"),
            "label": rng.randint(0, 5, (2, 1))}
    return loss, feed


def _rnn_net():
    seq = layers.data("seq", shape=[4], dtype="float32", lod_level=1)
    rnn = layers.control_flow.StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(seq)
        acc = rnn.memory(shape=[4])
        new = layers.elementwise_add(acc, layers.fc(x_t, size=4))
        rnn.update_memory(acc, new)
        rnn.step_output(new)
    out = rnn()
    rng = np.random.RandomState(0)
    return out, {"seq": rng.rand(2, 3, 4).astype("float32"),
                 "seq@LEN": np.array([3, 3])}


def _op_names(text):
    return set(re.findall(r'op_name="([^"]*)"', text))


def _compile(exe, feed, fetch, **kw):
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    return exe.compile(feed=feed, fetch_list=[fetch], **kw)


def test_forward_backward_and_optimizer_scopes_reach_the_hlo():
    loss, feed = _conv_net()
    text = _compile(pt.Executor(), feed, loss).hlo_text()
    assert text.startswith("HloModule jit_pt_run,")
    names = _op_names(text)
    fwd = [n for n in names if re.search(r"/jvp\(pt\.conv2d:0\.\d+\)/", n)]
    bwd = [n for n in names
           if re.search(r"/transpose\(jvp\(pt\.conv2d:0\.\d+\)\)/", n)]
    assert fwd and bwd
    # the instance joins back to the Operator in the Program
    block, pos = re.search(r"pt\.conv2d:(\d+)\.(\d+)", fwd[0]).groups()
    op = pt.default_main_program().blocks[int(block)].ops[int(pos)]
    assert op.type == "conv2d"
    # an optimizer op runs after the backward pseudo-op: in neither
    opt = [n for n in names if "pt.momentum:" in n]
    assert opt and not any("jvp(" in n or "transpose(" in n for n in opt)
    for other in ("pt.batch_norm:", "pt.mul:", "pt.cross_entropy:"):
        assert any(other in n for n in names), other
    assert not any("/" in m for n in names
                   for m in re.findall(r"pt\.\w+:([^/()]*)", n))


def test_step_block_ops_nest_under_their_rnn():
    out, feed = _rnn_net()
    names = _op_names(_compile(pt.Executor(), feed, out).hlo_text())
    nested = [n for n in names
              if re.search(r"pt\.rnn:0\.\d+/.*pt\.mul:1\.\d+", n)]
    assert nested, sorted(names)
    block, pos = re.search(r"pt\.mul:(\d+)\.(\d+)", nested[0]).groups()
    assert pt.default_main_program().blocks[int(block)] \
        .ops[int(pos)].type == "mul"


def test_op_moved_out_of_the_scan_keeps_its_scopes():
    """The ``rnn`` lowering runs a step block's output layer once, after
    the scan: its instructions still read ``pt.rnn/pt.mul``, forward and
    backward, and none of them is in the rnn's ``while`` body, where the
    recurrence's own ``mul`` stays."""
    seq = layers.data("seq", shape=[4], dtype="float32", lod_level=1)
    rnn = layers.control_flow.StaticRNN()
    with rnn.step():
        x_t = rnn.step_input(seq)
        acc = rnn.memory(shape=[4])
        new = layers.elementwise_add(acc, layers.fc(x_t, size=4))
        rnn.update_memory(acc, new)
        rnn.step_output(layers.fc(new, size=7, act="softmax"))
    loss = layers.mean(layers.square(rnn()))
    pt.optimizer.SGD(0.1).minimize(loss)
    feed = {"seq": np.random.RandomState(0).rand(2, 3, 4).astype("float32"),
            "seq@LEN": np.array([3, 2])}
    names = _op_names(_compile(pt.Executor(), feed, loss).hlo_text())
    step_ops = pt.default_main_program().blocks[1].ops
    inner, head = [i for i, op in enumerate(step_ops) if op.type == "mul"]
    assert compile_cache.stats().snapshot()["rnn_ops_hoisted"] == 3

    def of(pos):
        return [n for n in names if f"/pt.mul:1.{pos}/" in n]

    assert any(re.search(r"/jvp\(pt\.rnn:0\.\d+\)/pt\.mul:1\.\d+/", n)
               for n in of(head))
    assert any(re.search(
        r"/transpose\(jvp\(pt\.rnn:0\.\d+\)\)/pt\.mul:1\.\d+/", n)
        for n in of(head))
    assert not any("while" in n for n in of(head))
    assert of(inner) and all(
        re.search(r"pt\.rnn:0\.\d+\)+/while/body/", n) for n in of(inner))


def test_dropless_moe_names_its_four_stages_inside_its_op_scope():
    """``moe.route`` / ``moe.dispatch`` / ``moe.experts`` / ``moe.combine``
    sit inside ``pt.moe:<b>.<p>``, forward and backward; they do not start
    with ``pt.``, so the op stays the innermost owner."""
    x = layers.data("x", shape=[6, 8], dtype="float32")
    out, aux, z = layers.moe(layers.fc(x, size=8, num_flatten_dims=2),
                             num_experts=4, expert_hidden=5, top_k=2,
                             capacity_factor=None, act="silu", gated=True)
    loss = layers.elementwise_add(layers.mean(out),
                                  layers.elementwise_add(aux, z))
    pt.optimizer.SGD(0.1).minimize(loss)
    feed = {"x": np.random.RandomState(0).rand(3, 6, 8).astype("float32")}
    names = _op_names(_compile(pt.Executor(), feed, loss).hlo_text())
    for stage in ("route", "dispatch", "experts", "combine"):
        assert any(re.search(
            rf"/jvp\(pt\.moe:0\.\d+\)/moe\.{stage}/", n) for n in names), stage
        assert any(re.search(
            rf"/transpose\(jvp\(pt\.moe:0\.\d+\)\)/moe\.{stage}/", n)
            for n in names), stage
    assert not any("pt.moe." in n for n in names)


def test_shared_expert_is_a_stage_of_its_moe_op_and_counted_once():
    """``moe.shared`` sits inside ``pt.moe:<b>.<p>`` forward, backward and,
    where the layer is a ``layers.recompute`` stretch, recomputed (JAX's
    ``rematted_computation`` before the op's scope); the four stages stay
    beside it; ``route/moe:shared`` and ``route/moe:single`` (the un-gated
    experts' route) are bumped ONCE a lowered op, the recomputed one too
    (``jax.checkpoint`` traces its stretch once)."""
    x = layers.data("x", shape=[6, 8], dtype="float32")
    h = layers.fc(x, size=8, num_flatten_dims=2)

    def experts(h):
        return layers.moe(h, num_experts=8, expert_hidden=5, top_k=2,
                          capacity_factor=None, act="relu2", gated=False,
                          scoring="sigmoid", renormalize=True,
                          experts_held=2, expert_offset=2,
                          shared_hidden=7)[0]

    with layers.recompute():
        h = layers.elementwise_add(h, experts(h))
    h = layers.elementwise_add(h, experts(h))
    loss = layers.mean(h)
    pt.optimizer.SGD(0.1).minimize(loss)
    feed = {"x": np.random.RandomState(0).rand(3, 6, 8).astype("float32")}
    names = _op_names(_compile(pt.Executor(), feed, loss).hlo_text())
    ways = {"fwd": r"/jvp\(pt\.moe:0\.\d+\)/moe\.{}/",
            "bwd": r"/transpose\(jvp\(pt\.moe:0\.\d+\)\)/moe\.{}/",
            "first": r"/jvp\(pt\.recompute:0\.\d+\)/pt\.moe:1\.\d+"
                     r"/moe\.{}/",
            "back": r"/transpose\(jvp\(pt\.recompute:0\.\d+\)\)/.*"
                    r"checkpoint/pt\.moe:1\.\d+/moe\.{}/",
            "again": r"/transpose\(jvp\(pt\.recompute:0\.\d+\)\)/.*"
                     r"rematted_computation/pt\.moe:1\.\d+/moe\.{}/"}
    for stage in ("shared", "experts", "combine"):
        for way, pattern in ways.items():
            # (of the other stages XLA drops what the backward does not read)
            assert stage != "shared" and way == "again" or any(
                re.search(pattern.format(stage), n) for n in names), \
                (stage, way)
    assert not any("pt.moe." in n for n in names)
    stats = compile_cache.stats().snapshot()
    assert stats["route/moe:shared"] == stats["route/moe:single"] \
        == stats["route/moe:share"] == stats["route/moe:dropless"] == 2
    assert "route/moe:gated_pair" not in stats


def test_ssd_scan_names_its_five_stages_inside_its_op_scope():
    """``ssd.decay`` / ``ssd.intra`` / ``ssd.states`` / ``ssd.pass`` /
    ``ssd.out`` sit inside ``pt.ssd_scan:<b>.<p>``, forward and backward;
    they do not start with ``pt.``, so the op stays the innermost owner."""
    t_len, heads, p, n = 16, 2, 4, 3
    x = layers.data("x", shape=[t_len, heads * p + heads + 2 * n],
                    dtype="float32")
    h = layers.fc(x, size=heads * p + heads + 2 * n, num_flatten_dims=2)

    def cut(start, stop, shape):
        return layers.reshape(layers.slice(h, axes=[2], starts=[start],
                                           ends=[stop]), shape)

    at = heads * p
    vec = layers.fc(layers.reduce_mean(x, dim=[1]), size=heads)
    out = layers.ssd_scan(
        cut(0, at, [-1, t_len, heads, p]),
        layers.softplus(cut(at, at + heads, [-1, t_len, heads])),
        layers.scale(layers.exp(layers.reduce_mean(vec, dim=[0])), -1.0),
        cut(at + heads, at + heads + n, [-1, t_len, 1, n]),
        cut(at + heads + n, at + heads + 2 * n, [-1, t_len, 1, n]),
        layers.reduce_mean(vec, dim=[0]), chunk=4)
    loss = layers.mean(out)
    pt.optimizer.SGD(0.1).minimize(loss)
    feed = {"x": np.random.RandomState(0).rand(
        3, t_len, heads * p + heads + 2 * n).astype("float32")}
    names = _op_names(_compile(pt.Executor(), feed, loss).hlo_text())
    for stage in ("decay", "intra", "states", "pass", "out"):
        assert any(re.search(
            rf"/jvp\(pt\.ssd_scan:0\.\d+\)/ssd\.{stage}/", n)
            for n in names), stage
        assert any(re.search(
            rf"/transpose\(jvp\(pt\.ssd_scan:0\.\d+\)\)/ssd\.{stage}/", n)
            for n in names), stage
    assert not any("pt.ssd." in n for n in names)


def test_gated_experts_kernels_sit_inside_the_experts_stage(monkeypatch):
    """Lowered for the TPU (no chip needed to LOWER), the experts stage of a
    gated ``moe`` op is seven Pallas custom calls: the gate/up pair forward
    (gate, up and ``act(gate) * up``), ``down`` forward, and backward the
    derivative of ``act(gate) * up`` (the cotangent, gate and up in, the
    two cotangents out), the pair's gradient of the rows (two cotangents
    and two stacks in), the pair's gradient of both stacks (two results)
    and ``down``'s two.  All carry
    ``pt.moe:<b>.<p>/moe.experts`` in their location, which is how
    ``moe_step_ms``, ``moe_experts_roofline_pct`` and the stages' table of
    a traced run find them.  The two calls of ``rows_from_tokens`` stay
    OUTSIDE that stage, under ``moe.dispatch`` forward and ``moe.combine``
    backward: the rooflines divide by the experts' time alone."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops

    n, d, h, e = 64, 128, 256, 4
    rng = np.random.RandomState(0)
    args = [jnp.asarray(rng.randn(*shape).astype("float32")) for shape in (
        (n, d), (d, e), (e, d, h), (e, d, h), (e, h, d))]

    def loss(xt, router, w_gate, w_up, w_down):
        with jax.named_scope("pt.moe:0.3"):
            out, aux, z = moe_ops._dropless(xt, router, w_gate, w_up, w_down,
                                            2, jax.nn.silu)
        return jnp.sum(out) + aux + z

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(jax.grad(loss, argnums=(0, 2, 3, 4))).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    calls = []
    for line in text.splitlines():
        if "tpu_custom_call" in line:
            operands, results, loc = re.search(
                r" : \((.*)\) -> (.*) loc\((#loc\d+)\)$", line).groups()
            calls.append((operands.count("tensor<"), results.count("tensor<"),
                          locs[loc].removeprefix("jit(loss)")))
    fwd = "/jvp(pt.moe:0.3)/moe.experts/pallas_call"
    bwd = "/transpose(jvp(pt.moe:0.3))/moe.experts/pallas_call"
    inner = "pallas_call"      # inside the jitted ``_rows_call``
    # (operands with the layout's two, the tiles in use as the grid's bound
    # and ``tile_group``, of which the derivative takes the bound alone;
    # results; where)
    assert sorted(calls) == sorted([
        (5, 3, fwd), (4, 1, fwd), (4, 2, bwd), (6, 1, bwd), (5, 2, bwd),
        (4, 1, bwd), (4, 1, bwd), (3, 1, inner), (5, 2, inner)])
    # the row kernels' call sites carry the stage (XLA inlines the calls
    # and joins the names, as for ``rope``)
    sites = sorted(locs[loc][locs[loc].index("/"):] for loc in re.findall(
        r"call @_rows_call\w*\(.* loc\((#loc\d+)\)$", text, re.M))
    assert sites == [
        "/jvp(pt.moe:0.3)/moe.dispatch/jit(_rows_call)",
        "/transpose(jvp(pt.moe:0.3))/moe.combine/jit(_rows_call)"]
    assert compile_cache.stats().snapshot()["route/moe:gated_pair"] == 1


def test_rope_kernel_call_sites_carry_the_ops_scope(monkeypatch):
    """Lowered for the TPU (no chip needed to LOWER), a ``rope`` op of a
    shape its kernel takes is a call into the jitted ``_rope_call`` (the
    Pallas custom call inside it, lowered once for a module), forward and
    backward, and each call site carries ``pt.rope:<b>.<p>`` with its
    direction.  XLA inlines the calls and joins the names, which is how
    ``attention_step_ms``, the looped stack's table and the class metrics
    of a traced run find the kernels (the compiled module's names:
    tests/test_tpu_compile.py)."""
    import jax

    x = layers.data("x", shape=[256, 256], dtype="float32")
    q = layers.fc(x, size=256, num_flatten_dims=2, bias_attr=False)
    q = layers.rope(layers.reshape(q, [0, 256, 2, 128]), theta=1e4)
    loss = layers.mean(q)
    pt.optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    position = [op.type for op in
                pt.default_main_program().global_block().ops].index("rope")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with exe._call_context(None):
        entry = exe._enter(None, {"x": ((1, 256, 256), "float32")}, [loss],
                           None, False, abstract=True)
    text = entry.fn._jit.trace(entry.feeds, entry.state, 0).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert compile_cache.stats().snapshot()["route/rope:pallas"] == 1
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    sites = sorted(locs[loc] for loc in re.findall(
        r"call @_rope_call\w*\(.* loc\((#loc\d+)\)$", text, re.M))
    assert sites == [
        f"jit(pt_run)/jvp(pt.rope:0.{position})/jit(_rope_call)",
        f"jit(pt_run)/transpose(jvp(pt.rope:0.{position}))/jit(_rope_call)"]
    # nothing of the rotation is left outside the kernel
    assert text.count("tpu_custom_call") == 2 and "x2x64xf32" not in text


def test_executor_emitted_work_has_scopes_of_its_own():
    loss, feed = _conv_net()
    cp = _compile(pt.Executor(amp=True), feed, loss, num_steps=3)
    text = cp.hlo_text()
    assert text.startswith("HloModule jit_pt_run_steps,")
    names = _op_names(text)
    assert any("pt.amp_cast" in n for n in names)
    assert any(n.endswith("pt.scan/while") for n in names)
    # ops inside the scan keep their own scope as the innermost
    assert any(re.search(r"pt\.scan/.*pt\.conv2d:0\.\d+", n) for n in names)


def test_scopes_do_not_depend_on_observe():
    """Unconditional: metadata is not in JAX's persistent-cache key, so a
    scope-less executable would be served to an observed run."""
    loss, feed = _conv_net()
    exe = pt.Executor(observe=False)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    off = exe.compile(feed=feed, fetch_list=[loss])
    on_exe = pt.Executor(observe=True)
    on = on_exe.compile(feed=feed, fetch_list=[loss])
    assert on.fingerprint == off.fingerprint
    # the same instructions under the same names (the text also records
    # the Python call sites, and the two compile() calls above differ)
    body = [re.sub(r"stack_frame_id=\d+|source_line=\d+", "", ln)
            for ln in on.hlo_text().splitlines() if " = " in ln]
    assert body == [re.sub(r"stack_frame_id=\d+|source_line=\d+", "", ln)
                    for ln in off.hlo_text().splitlines() if " = " in ln]
    assert any("pt.conv2d:" in ln for ln in body)


def test_fingerprints_traces_and_retrace_guard_as_before():
    loss, feed = _conv_net()
    exe = pt.Executor()
    with retrace_guard():
        cp = _compile(exe, feed, loss)
        traces = compile_cache.stats().snapshot()["traces"]
        for _ in range(3):
            exe.run(feed=feed, fetch_list=[loss])
            cp.run(feed=feed)
    assert traces == 2                       # startup + the step
    assert compile_cache.stats().snapshot()["traces"] == traces
    entry = compile_cache.stats().entries[cp.fingerprint]
    assert entry["traces"] == 1 and entry["label"] == "run"
    assert set(entry["times"]) == {"trace_s", "lower_s", "compile_s"}
    compile_cache.stats().assert_no_retrace()


def test_compiled_hlo_text_by_fingerprint_prefix_and_after_collection():
    loss, feed = _conv_net()
    exe = pt.Executor(observe=False)
    cp = _compile(exe, feed, loss)
    fp12 = cp.fingerprint[:12]
    assert profiler.compiled_hlo_text(fp12) == cp.hlo_text()
    assert profiler.compiled_hlo_text("") is None
    assert profiler.compiled_hlo_text("no-such-fp") is None
    exe.close()
    del cp, exe
    gc.collect()
    assert profiler.compiled_hlo_text(fp12) is None


def test_an_observed_dispatch_keeps_its_step_readable():
    """The reader of a trace runs when the window is over, often after the
    executor is gone: the steps of the last eight observed fingerprints
    (the ones a ``pt:<path>:<fp12>`` annotation names) outlive it."""
    loss, feed = _conv_net()
    exe = pt.Executor(observe=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    exe.run_steps(2, feed=feed, fetch_list=[loss])
    fps = [fp for fp, e in compile_cache.stats().entries.items()
           if e["label"] == "run_steps"]
    assert len(fps) == 1
    exe.close()
    del exe
    gc.collect()
    text = profiler.compiled_hlo_text(fps[0][:12])
    assert text.startswith("HloModule jit_pt_run_steps,")
    # ... until eight newer observed fingerprints have pushed it out
    for i in range(8):
        compile_cache._observed_steps.append(
            compile_cache.CachedStep(lambda f, s, t: (f, s), f"fp{i}"))
    gc.collect()
    assert profiler.compiled_hlo_text(fps[0][:12]) is None
    compile_cache._observed_steps.clear()


def test_memory_analysis_is_public():
    loss, feed = _conv_net()
    m = _compile(pt.Executor(), feed, loss).memory_analysis()
    assert m.argument_size_in_bytes > 0 and m.temp_size_in_bytes >= 0


def test_sharded_steps_are_named_and_scoped():
    from paddle_tpu.parallel import ShardedExecutor, mesh_for_axes
    loss, feed = _conv_net()
    exe = ShardedExecutor(mesh=mesh_for_axes({"dp": 2}), batch_axis="dp")
    text = _compile(exe, feed, loss).hlo_text()
    assert text.startswith("HloModule jit_pt_sharded_run,")
    assert "pt.conv2d:" in text


@pytest.mark.parametrize("fetch,return_numpy,drained", [
    (True, True, True), (True, False, False), (False, True, False)])
def test_undrained_dispatch_stays_out_of_step_time(tmp_path, fetch,
                                                   return_numpy, drained):
    """A dispatch that materialized no fetch timed the enqueue: tagged
    ``drained: false`` and kept out of ``executor/step_time_ms`` and
    ``executor/examples_per_sec``, like a cold compile."""
    import json

    from paddle_tpu import flags
    log = tmp_path / "run.jsonl"
    flags.set_flag("metrics_log", str(log))
    try:
        loss, feed = _conv_net()
        exe = pt.Executor(observe=True)
        exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
        fetch_list = [loss] if fetch else []
        exe.run(feed=feed, fetch_list=fetch_list)             # cold
        exe.run(feed=feed, fetch_list=fetch_list,
                return_numpy=return_numpy)                    # warm
        snap = obs.registry().snapshot()
        assert snap["executor/step_time_ms"]["count"] == int(drained)
        assert bool(snap["executor/examples_per_sec"]["values"]) == drained
        assert snap["executor/dispatches"]["value"] == 3
        last = [json.loads(ln) for ln in log.read_text().splitlines()
                if '"kind": "step"' in ln][-1]
        assert last["drained"] is drained and not last["cold_compile"]
        assert (last["step_ms"] is not None) == drained
    finally:
        flags.set_flag("metrics_log", "")
