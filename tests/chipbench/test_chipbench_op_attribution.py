"""Device time by Program op (``chipbench/lib/op_attribution.py``): the
rule on a hand-made module whose answers can be worked out on paper, on a
window recorded on the chip with the matching excerpt of its module's text,
and end to end against the scopes the program's own lowering emits."""
import copy
import gzip
import json
import os
import types

import pytest

from conftest import BENCH, ROOT, load_json

from chipbench import run as chipbench_run
from chipbench.lib import op_attribution as oa
from chipbench.lib import trace_reduce as tr

READERS = [m["name"] for m in load_json(ROOT, "BENCHMARK.json")["per_layer"]
           if m["name"].endswith("_ms_per_step")
           and m["name"] not in ("device_ms_per_step",
                                 "collective_ms_per_step")
           or m["name"] == "device_unattributed_share"]

P = "jit(pt_run_steps)/pt.scan/while/body/closed_call"
TEXT = f"""HloModule jit_pt_run_steps, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[]{{:T(128)}} parameter(0), metadata={{op_name="reduce_sum"}}
  %b = f32[]{{:T(128)}} parameter(1), metadata={{op_name="reduce_sum"}}
  ROOT %add.0 = f32[]{{:T(128)}} add(%a, %b), metadata={{op_name="jvp(pt.batch_norm:0.1)/reduce_sum"}}
}}

%fused_computation.1 (p0: bf16[8,3,9,9], p1: f32[4,3,3,3]) -> (f32[4,3,3,3], f32[4,3,3,3]) {{
  %p0 = bf16[8,3,9,9]{{0,1,3,2}} parameter(0)
  %p1 = f32[4,3,3,3]{{0,1,3,2}} parameter(1)
  %convert.1 = f32[4,3,3,3]{{0,1,3,2}} convert(%p1), metadata={{op_name="{P}/pt.amp_cast/convert_element_type"}}
  %conv.1 = f32[4,3,3,3]{{0,1,3,2}} convolution(%p0, %p0), window={{size=8x8}}, dim_labels=fb01_io01->bf01, metadata={{op_name="{P}/transpose(jvp(pt.conv2d:0.0))/conv_general_dilated" stack_frame_id=7}}
  %mul.1 = f32[4,3,3,3]{{0,1,3,2}} multiply(%p1, %p1), metadata={{op_name="{P}/pt.momentum:0.9/mul"}}
  %sub.1 = f32[4,3,3,3]{{0,1,3,2}} subtract(%convert.1, %conv.1), metadata={{op_name="{P}/pt.momentum:0.9/sub"}}
  ROOT %tuple.1 = (f32[4,3,3,3]{{0,1,3,2}}, f32[4,3,3,3]{{0,1,3,2}}) tuple(%sub.1, %mul.1)
}}

%fused_computation.3 (p0: bf16[8,4,9,9]) -> bf16[8,4,9,9] {{
  %p0.3 = bf16[8,4,9,9]{{0,1,3,2}} parameter(0)
  ROOT %max.3 = bf16[8,4,9,9]{{0,1,3,2}} maximum(%p0.3, %p0.3), metadata={{op_name="{P}/jvp(pt.relu:0.2)/max"}}
}}

%fused_computation.2 (p0: bf16[8,4,9,9]) -> bf16[8,4,9,9] {{
  %p0.2 = bf16[8,4,9,9]{{0,1,3,2}} parameter(0)
  %cast.2 = f32[8,4,9,9]{{0,1,3,2}} convert(%p0.2), metadata={{op_name="{P}/pt.amp_cast/convert_element_type"}}
  %sub.2 = f32[8,4,9,9]{{0,1,3,2}} subtract(%cast.2, %cast.2), metadata={{op_name="{P}/jvp(pt.batch_norm:0.1)/sub"}}
  %mul.2 = f32[8,4,9,9]{{0,1,3,2}} multiply(%sub.2, %sub.2), metadata={{op_name="{P}/jvp(pt.batch_norm:0.1)/mul"}}
  %back.2 = bf16[8,4,9,9]{{0,1,3,2}} convert(%mul.2), metadata={{op_name="{P}/jvp(pt.batch_norm:0.1)/convert_element_type"}}
  ROOT %fusion.3 = bf16[8,4,9,9]{{0,1,3,2}} fusion(%back.2), kind=kLoop, calls=%fused_computation.3
}}

%fused_computation.4 (p0: bf16[8,4,9,9]) -> bf16[8,3,9,9] {{
  %p0.4 = bf16[8,4,9,9]{{0,1,3,2}} parameter(0)
  ROOT %conv.4 = bf16[8,3,9,9]{{0,1,3,2}} convolution(%p0.4, %p0.4), window={{size=3x3}}, dim_labels=bf01_oi01->bf01, metadata={{op_name="{P}/transpose(jvp(pt.conv2d:0.0))/conv_general_dilated"}}
}}

%fused_computation.5 (p0: f32[4]) -> f32[4] {{
  %p0.5 = f32[4]{{0}} parameter(0)
  ROOT %bitcast.5 = f32[4]{{0}} bitcast(%p0.5)
}}

%body (arg: (s32[], bf16[8,3,9,9])) -> (s32[], bf16[8,3,9,9]) {{
  %arg = (s32[]{{:T(128)}}, bf16[8,3,9,9]{{0,1,3,2}}) parameter(0)
  %gte.1 = bf16[8,3,9,9]{{0,1,3,2}} get-tuple-element(%arg), index=1
  %copy-start.1 = (bf16[8,3,9,9]{{0,1,3,2:S(1)}}, bf16[8,3,9,9]{{0,1,3,2}}, u32[]{{:S(2)}}) copy-start(%gte.1)
  %copy-done.1 = bf16[8,3,9,9]{{0,1,3,2:S(1)}} copy-done(%copy-start.1)
  %conv_fwd.9 = bf16[8,4,9,9]{{0,1,3,2}} convolution(%copy-done.1, %copy-done.1), window={{size=3x3}}, dim_labels=bf01_oi01->bf01, metadata={{op_name="{P}/jvp(pt.conv2d:0.0)/conv_general_dilated"}}
  %fusion.2 = bf16[8,4,9,9]{{0,1,3,2}} fusion(%conv_fwd.9), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{P}/jvp(pt.relu:0.2)/max"}}
  %dot.7 = f32[8,5]{{1,0}} dot(%fusion.2, %fusion.2), metadata={{op_name="{P}/transpose(jvp(pt.rnn:0.3))/while/body/closed_call/pt.mul:1.0/dot_general"}}
  %fusion.4 = bf16[8,3,9,9]{{0,1,3,2}} fusion(%fusion.2), kind=kOutput, calls=%fused_computation.4, metadata={{op_name="{P}/transpose(jvp(pt.conv2d:0.0))/conv_general_dilated"}}
  %multiply_subtract_fusion.1 = (f32[4,3,3,3]{{0,1,3,2}}, f32[4,3,3,3]{{0,1,3,2}}) fusion(%copy-done.1, %gte.1), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{P}/pt.momentum:0.9/sub"}}
  %fusion.5 = f32[4]{{0}} fusion(%gte.1), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="{P}/pt.momentum:0.10/sub"}}
  %all-reduce.1 = f32[4]{{0}} all-reduce(%fusion.5), to_apply=%region_0.1, metadata={{op_name="{P}/transpose(jvp(pt.conv2d:0.0))/psum"}}
  %dus.1 = bf16[8,3,9,9]{{0,1,3,2}} dynamic-update-slice(%gte.1, %gte.1), metadata={{op_name="jit(pt_run_steps)/pt.scan/while/body/dynamic_update_slice"}}
  ROOT %tuple.9 = (s32[]{{:T(128)}}, bf16[8,3,9,9]{{0,1,3,2}}) tuple(%gte.1, %dus.1)
}}

ENTRY %main.1 (x: bf16[8,3,9,9]) -> bf16[8,3,9,9] {{
  %x = bf16[8,3,9,9]{{0,1,3,2}} parameter(0), metadata={{op_name="feeds['img']"}}
  %while.5 = (s32[]{{:T(128)}}, bf16[8,3,9,9]{{0,1,3,2}}) while(%x), condition=%region_0.1, body=%body, metadata={{op_name="jit(pt_run_steps)/pt.scan/while"}}
  ROOT %gte.9 = bf16[8,3,9,9]{{0,1,3,2}} get-tuple-element(%while.5), index=1
}}
"""
OPS = {"0.0": {"type": "conv2d", "shapes": {
    "Input": [[-1, 3, 9, 9]], "Filter": [[4, 3, 3, 3]],
    "Output": [[-1, 4, 9, 9]]}},
    "0.9": {"type": "momentum", "shapes": {"Param": [[4, 3, 3, 3]]}}}

# one step of 2 in the window: [xla name (as the trace prints it), start, ns]
EVENTS = [
    ["%while.5 = (s32[], bf16[8,3,9,9]) while(%x)", 1000, 9000],
    ["%copy-done.1 = bf16[8,3,9,9]{0,1,3,2:S(1)} copy-done(...)", 1000, 100],
    ["%conv_fwd.9 = bf16[8,4,9,9] convolution(...)", 1100, 1000],
    ["%fusion.2 = bf16[8,4,9,9] fusion(...)", 2100, 700],
    ["%dot.7 = f32[8,5] dot(...)", 2800, 300],
    ["%fusion.4 = bf16[8,3,9,9] fusion(...)", 3100, 2000],
    ["%multiply_subtract_fusion.1 = (f32[4,3,3,3]) fusion(...)", 5100, 2500],
    ["%fusion.5 = f32[4] fusion(...)", 7600, 50],
    ["%all-reduce.1 = f32[4] all-reduce(...)", 7650, 400],
    ["%dus.1 = bf16[8,3,9,9] dynamic-update-slice(...)", 8050, 150],
    ["%not_in_the_text.3 = f32[] add(...)", 8200, 800],
    ["%fusion.2 = bf16[8,4,9,9] fusion(...)", 11000, 700],   # outside
]


def _trace(events=EVENTS, fp="0123456789abcdef"):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events},
            {"name": "XLA Modules",
             "events": [["jit_pt_run_steps(77)", 1000, 9000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["cb:window", 0, 10000], [f"pt:run_steps:{fp[:12]}", 500, 9000],
            ["cb:check", 10000, 5000]]}]}]}


def test_innermost_scope_and_direction():
    s = oa.innermost_scope
    assert s(f"{P}/jvp(pt.conv2d:0.3)/conv_general_dilated") == \
        ("conv2d", "0.3", "fwd")
    assert s(f"{P}/transpose(jvp(pt.conv2d:0.3))/conv_general_dilated") == \
        ("conv2d", "0.3", "bwd")
    assert s(f"{P}/pt.momentum:0.9/mul") == ("momentum", "0.9", "fwd")
    # a step-block op takes its direction from the enclosing rnn
    assert s(f"{P}/transpose(jvp(pt.rnn:0.1))/while/body/closed_call/"
             f"pt.mul:1.0/transpose") == ("mul", "1.0", "bwd")
    assert s(f"{P}/jvp(pt.rnn:0.1)/while/body/closed_call/pt.mul:1.0/"
             f"dot_general") == ("mul", "1.0", "fwd")
    # the primitive called transpose is no direction
    assert s(f"{P}/jvp(pt.rnn:0.1)/transpose") == ("rnn", "0.1", "fwd")
    # the executor's own scopes have no instance; the innermost wins
    assert s("jit(pt_run_steps)/pt.scan/while") == ("scan", "", "fwd")
    assert s(f"{P}/pt.amp_cast/convert_element_type")[0] == "amp_cast"
    assert s("jit(pt_run_steps)/while/body/add") is None
    assert s("jit(f)/opt.update/mul") is None       # not a pt. scope
    assert s("") is None


def test_parse_module_reads_tuples_roots_and_calls():
    m = oa.parse_module(TEXT)
    assert m["module"] == "jit_pt_run_steps"
    assert m["computations"]["body"][0] == "arg"
    f = m["instructions"]["multiply_subtract_fusion.1"]
    assert f["opcode"] == "fusion" and f["calls"] == "fused_computation.1"
    assert f["operands"] == ["copy-done.1", "gte.1"]
    assert oa._dims(f["shape"]) == [(3, 3, 3, 4), (3, 3, 3, 4)]
    assert m["instructions"]["tuple.1"]["opcode"] == "tuple"
    assert m["instructions"]["while.5"]["calls"] is None
    assert m["instructions"]["copy-start.1"]["shape"].startswith("(bf16")
    assert m["instructions"]["conv.1"]["op_name"].endswith(
        "transpose(jvp(pt.conv2d:0.0))/conv_general_dilated")


def test_owner_follows_the_fusion_rule():
    m = oa.parse_module(TEXT)
    # a weight-gradient convolution fused with the optimizer update, named
    # by XLA after the subtraction at its end: convolution time, backward
    assert oa.owner(m, "multiply_subtract_fusion.1") == \
        ("conv2d", "0.0", "bwd")
    # elementwise only: the scope most instructions carry (3 batch_norm, 1
    # amp_cast first in text order, 1 relu in a nested fusion), whatever
    # XLA named the fusion after
    assert oa.owner(m, "fusion.2") == ("batch_norm", "0.1", "fwd")
    assert oa.owner(m, "conv_fwd.9") == ("conv2d", "0.0", "fwd")
    assert oa.owner(m, "dot.7") == ("mul", "1.0", "bwd")
    # no scoped instruction inside: the fusion's own op_name
    assert oa.owner(m, "fusion.5") == ("momentum", "0.10", "fwd")
    assert oa.owner(m, "dus.1") == ("scan", "", "fwd")
    assert oa.owner(m, "copy-done.1") is None
    assert oa.owner(m, "not_in_the_text.3") is None
    # whom the scope-less copy was moved for: its nearest reader
    assert oa.moved_for(m, "copy-done.1") == ("conv2d", "0.0", "fwd")
    assert oa.moved_for(m, "copy-start.1") == ("conv2d", "0.0", "fwd")


def test_attribute_on_paper():
    r = oa.attribute(oa.window_events(_trace()), 2, TEXT, OPS)
    assert r["ok"] and r["module"] == "jit_pt_run_steps"
    ns = 1e-9
    # the while is a container and the last event lies outside cb:window
    assert r["total_s"] == pytest.approx(8000 * ns)
    assert r["collective_s"] == pytest.approx(400 * ns)   # left out below
    assert r["unattributed_s"] == pytest.approx(900 * ns)
    assert dict(r["unattributed"]) == pytest.approx(
        {"not_in_the_text.3": 800 * ns, "copy-done.1": 100 * ns})
    assert r["class_s"] == pytest.approx({
        ("conv", "fwd"): 1000 * ns, ("conv", "bwd"): 4500 * ns,
        ("norm", "fwd"): 700 * ns, ("matmul", "bwd"): 300 * ns,
        ("optimizer", "fwd"): 50 * ns})
    rows = {(x["op_type"], x["direction"], x["wrt"]): x for x in r["rows"]}
    assert rows[("conv2d", "bwd", "filter")]["ms_per_step"] == \
        pytest.approx(2500e-6 / 2)
    assert rows[("conv2d", "bwd", "filter")]["xla"] == \
        ["multiply_subtract_fusion.1"]
    assert rows[("conv2d", "bwd", "input")]["xla"] == ["fusion.4"]
    assert rows[("conv2d", "fwd", None)]["shapes"]["Filter"] == [[4, 3, 3, 3]]
    assert rows[("scan", "fwd", None)]["class"] is None
    assert [x["ms_per_step"] for x in r["rows"]] == sorted(
        (x["ms_per_step"] for x in r["rows"]), reverse=True)
    # the parts sum to the total
    table = oa.op_table(r)
    parts = sum(table["class_ms_per_step"].values()) \
        + table["other_attributed_ms_per_step"] \
        + table["unattributed_ms_per_step"] + table["collective_ms_per_step"]
    assert parts == pytest.approx(table["device_ms_per_step"])
    assert table["class_ms_per_step"]["conv_bwd"] == pytest.approx(2.25e-3)
    assert table["other_attributed_ms_per_step"] == pytest.approx(75e-6)
    kinds = {k["opcode"]: k for k in table["unattributed_by_opcode"]}
    assert kinds["copy-done"]["moved_for"][0][0] == "conv2d:0.0 fwd"
    assert kinds["not in the text"]["xla_names"] == 1
    # and to what trace_reduce counts for the same window
    total = sum(tr.summarize(_trace(), 1, steps=2).ops.values())
    assert r["total_s"] == pytest.approx(total)


def test_a_join_that_cannot_be_trusted_is_refused():
    window = oa.window_events(_trace())
    assert not oa.attribute(window, 2, None)["ok"]
    assert not oa.attribute(window, 0, TEXT)["ok"]
    bare = oa.attribute(window, 2, TEXT.replace("pt.", "px."))
    assert not bare["ok"] and "no pt. scope" in bare["why"]
    other = oa.attribute(window, 2, TEXT.replace("jit_pt_run_steps,",
                                                 "jit_pt_run,"))
    assert not other["ok"] and "the window ran" in other["why"]
    short = oa.attribute(window, 2, TEXT, expect_total_s=9000e-9)
    assert not short["ok"] and "sum to" in short["why"]
    empty = oa.attribute(oa.window_events({"planes": []}), 2, TEXT)
    assert not empty["ok"]


def test_the_optimizer_class_is_what_optimizer_ops_registers():
    import paddle_tpu  # noqa: F401  (registers the ops)
    from paddle_tpu.core import registry
    registered = {name for name, fn in registry._OP_IMPLS.items()
                  if fn.__module__.endswith(".optimizer_ops")}
    assert registered == {k for k, v in oa.CLASS_OF.items()
                          if v == "optimizer"}
    for op_type in oa.CLASS_OF:
        assert op_type == "fc" or registry.has_op(op_type), op_type


# ---------------------------------------------------------------------------
# the readers, through a run's context
# ---------------------------------------------------------------------------
def _ctx(monkeypatch, root, trace, steps, text, ops=None, why=None):
    """A run's context as the readers see it (``root``: a directory in
    place of the checkout, for what the join writes), with the trace and
    the program's answer put in place of the files and the live program."""
    ctx = types.SimpleNamespace(
        trace=tr.summarize(trace, 1, steps=steps), obs={}, detail={},
        root=str(root), args=types.SimpleNamespace(workload="a-cell"))
    calls = []
    monkeypatch.setattr(oa.glob, "glob", lambda pattern: ["trace.xplane.pb"])
    monkeypatch.setattr(oa, "from_xplane",
                        lambda path: calls.append(path) or trace)
    if text is not ...:
        monkeypatch.setattr(oa, "_program_text_and_ops",
                            lambda prefixes: (text, ops, why))
    return ctx, calls


def _read(ctx):
    return {name: chipbench_run._load_module("layer_metrics", name)
            .compute(ctx) for name in READERS}


def _whole_table(ctx):
    """The table the join wrote beside the trace, found by the line."""
    where = ctx.detail["op_table"]["file"]
    assert where == os.path.join("chipbench", "out", "a-cell",
                                 oa.TABLE_FILE)
    return load_json(ctx.root, where)


def test_there_are_eight_readers_and_one_join(monkeypatch, tmp_path):
    assert len(READERS) == 8
    ctx, calls = _ctx(monkeypatch, tmp_path, _trace(), 2, TEXT, OPS)
    got = _read(ctx)
    assert len(calls) == 1                       # the eight share the join
    assert got["conv_fwd_ms_per_step"] == pytest.approx(0.5e-3)
    assert got["conv_bwd_ms_per_step"] == pytest.approx(2.25e-3)
    assert got["norm_ms_per_step"] == pytest.approx(0.35e-3)
    assert got["matmul_ms_per_step"] == pytest.approx(0.15e-3)
    assert got["optimizer_ms_per_step"] == pytest.approx(25e-6)
    assert got["device_unattributed_share"] == pytest.approx(100 * 900 / 8000)
    # a sound join measured a class no event of the window belongs to: 0
    assert got["softmax_loss_ms_per_step"] == 0.0
    assert got["recurrence_ms_per_step"] == 0.0
    # the line keeps a short table, the file beside the trace the whole one
    line = ctx.detail["op_table"]
    assert line["ok"] and len(json.dumps(line)) <= oa.LINE_BYTES
    assert line["class_ms_per_step"]["conv_bwd"] == pytest.approx(2.25e-3,
                                                                  abs=1e-4)
    whole = _whole_table(ctx)
    assert whole == json.loads(json.dumps(oa.op_table(ctx.obs[oa.OBS_KEY])))
    assert len(line["rows"]) == len(whole["rows"]) <= oa.TABLE_ROWS
    assert line["columns"] == oa.LINE_COLUMNS
    for short, row in zip(line["rows"], whole["rows"]):
        short = dict(zip(line["columns"], short))
        assert short.pop("ms_per_step") == pytest.approx(row["ms_per_step"],
                                                         abs=1e-4)
        assert short == {k: row[k] for k in short}


def _wide_table(rows):
    """A sound table of ``rows`` rows, each of six long XLA names."""
    return {
        "ok": True, "module": "jit_pt_run_steps", "steps": 286,
        "device_ms_per_step": 98.3231234, "class_ms_per_step": {
            "conv_bwd": 60.4812345, "conv_fwd": 18.2112345,
            "norm": 13.5212345, "matmul": 0.1212345, "optimizer": 0.0512345,
            "recurrence": 8.3612345, "softmax_loss": 3.0812345},
        "other_attributed_ms_per_step": 2.3812345,
        "unattributed_ms_per_step": 3.6912345,
        "collective_ms_per_step": 1.3112345,
        "rows": [{"op_type": "softmax_with_cross_entropy",
                  "instance": f"12.{300 + i}", "direction": "bwd",
                  "wrt": "filter", "class": "softmax_loss",
                  "ms_per_step": 40.1234567 - i, "events": 123456,
                  "xla": [f"bitcast_dynamic-update-slice_fusion.{i}{j}"
                          for j in range(6)],
                  "shapes": {"X": [[256, 30, 30000]]}}
                 for i in range(rows)],
        "unattributed": [[f"copy-done.{i}", 1.3651234]
                         for i in range(oa.UNATTRIBUTED_ROWS)],
        "unattributed_by_opcode": [],
        "seconds": {"read_trace": 2.1153869, "render_text": 0.0409767,
                    "join": 0.3265105},
        "text_bytes": 9463581}


@pytest.mark.parametrize("rows", [0, 3, oa.TABLE_ROWS])
def test_the_lines_table_fits_its_room(rows):
    """The result line is read from the tail of a run's output: whatever
    the window ran, ``detail["op_table"]`` stays under ``LINE_BYTES``, the
    heaviest rows first, and says where the whole table is."""
    table = _wide_table(rows)
    line = oa.line_table(table, "chipbench/out/a-cell/op_table.json")
    assert len(json.dumps(line)) <= oa.LINE_BYTES < 4096
    assert line["file"].endswith(oa.TABLE_FILE)
    assert line["class_ms_per_step"]["conv_bwd"] == 60.4812
    assert len(line["unattributed"]) == oa.LINE_UNATTRIBUTED_ROWS
    kept = len(line["rows"])
    assert kept == rows if rows <= 3 else 3 < kept < rows
    assert [r[1] for r in line["rows"]] == \
        [r["instance"] for r in table["rows"][:kept]]
    if kept:
        assert line["rows"][0][-1] == [
            "bitcast_dynamic-update-slice_fusion.00",
            "bitcast_dynamic-update-slice_fusion.01", "+4"]
    # a join that is not sound has no table to cut
    bad = {"ok": False, "why": "no traced window"}
    assert oa.line_table(bad) == bad


@pytest.mark.parametrize("text,why,says", [
    (TEXT.replace("pt.", "px."), None, "no pt. scope"),
    (None, "this program has no profiler.compiled_hlo_text", "has no"),
    (None, "no live compiled step for fingerprint 0123", "no live"),
])
def test_stale_or_missing_names_read_as_nothing(monkeypatch, tmp_path, text,
                                                why, says):
    ctx, _ = _ctx(monkeypatch, tmp_path, _trace(), 2, text, None, why)
    assert set(_read(ctx).values()) == {None}
    assert ctx.detail["op_table"]["ok"] is False
    assert says in ctx.detail["op_table"]["why"]
    assert "rows" not in ctx.detail["op_table"]
    assert not os.listdir(tmp_path)              # and no table is written


def test_a_join_that_raises_fails_no_run(monkeypatch, tmp_path):
    """A reader leaves its metric out, it does not take the result line
    with it: what the join raises is its ``why``."""
    ctx, _ = _ctx(monkeypatch, tmp_path, _trace(), 2, TEXT, OPS)

    def broken(path):
        raise ValueError("truncated xplane")

    monkeypatch.setattr(oa, "from_xplane", broken)
    assert set(_read(ctx).values()) == {None}
    assert ctx.detail["op_table"] == {
        "ok": False, "why": "the join failed: ValueError('truncated xplane')"}


def test_without_a_trace_the_readers_return_nothing():
    ctx = types.SimpleNamespace(trace=None, obs={}, detail={})
    assert set(_read(ctx).values()) == {None}


def test_a_program_that_names_no_op_is_asked_nothing(monkeypatch, tmp_path):
    """The parent of the change that named the ops has no
    ``profiler.compiled_hlo_text``: no error, no number."""
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "compiled_hlo_text")
    ctx, _ = _ctx(monkeypatch, tmp_path, _trace(), 2, ...)
    assert set(_read(ctx).values()) == {None}
    assert "compiled_hlo_text" in ctx.detail["op_table"]["why"]


# ---------------------------------------------------------------------------
# end to end with the program's own lowering, on the CPU's module text
# ---------------------------------------------------------------------------
def test_the_programs_scopes_are_what_the_reader_reads(monkeypatch, tmp_path):
    """A tiny conv + batch-norm + fc + loss + Momentum program compiled by
    the Executor; one fake event per instruction the step executes.  The
    reader finds the text by the pt: span's fingerprint, the Program by
    the default main program, and every class the program has."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import layers

    pt.core.reset_default_programs()
    pt.core.reset_global_scope()
    pt.unique_name.reset()
    img = layers.data("img", shape=[3, 8, 8], dtype="float32")
    label = layers.data("label", shape=[1], dtype="int64")
    conv = layers.conv2d(img, num_filters=4, filter_size=3, padding=1,
                         bias_attr=False)
    pred = layers.fc(layers.batch_norm(conv, act="relu"), size=5,
                     act="softmax")
    loss = layers.mean(layers.cross_entropy(pred, label))
    pt.optimizer.Momentum(0.01, momentum=0.9).minimize(loss)
    exe = pt.Executor(amp=True)
    exe.run(pt.default_startup_program(), feed={}, fetch_list=[])
    feed = {"img": np.zeros((2, 3, 8, 8), "float32"),
            "label": np.zeros((2, 1), "int64")}
    compiled = exe.compile(feed=feed, fetch_list=[loss], num_steps=3)
    module = oa.parse_module(compiled.hlo_text())
    inner = {i["calls"] for i in module["instructions"].values()
             if i["opcode"] == "fusion"}
    events, t = [], 1000.0
    for computation, names in module["computations"].items():
        if computation in inner or computation.startswith("region"):
            continue
        for name in names:
            if module["instructions"][name]["opcode"] in (
                    "parameter", "constant", "tuple", "get-tuple-element",
                    "while", "call", "bitcast"):
                continue
            events.append([f"%{name} = f32[] op()", t, 1000.0])
            t += 1000.0
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events},
            {"name": "XLA Modules",
             "events": [["jit_pt_run_steps(1)", 1000.0, t]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["cb:window", 0.0, t + 1000.0],
            [f"pt:run_steps:{compiled.fingerprint[:12]}", 500.0, t]]}]}]}
    ctx, _ = _ctx(monkeypatch, tmp_path, trace, 3, ...)
    got = _read(ctx)
    assert ctx.detail["op_table"]["ok"], ctx.detail["op_table"]
    for name in ("conv_fwd", "conv_bwd", "norm", "matmul", "softmax_loss",
                 "optimizer"):
        assert got[f"{name}_ms_per_step"] > 0, name
    assert got["recurrence_ms_per_step"] == 0.0
    assert got["device_unattributed_share"] < 25.0
    rows = _whole_table(ctx)["rows"]
    conv_rows = [r for r in rows if r["op_type"] == "conv2d"]
    assert {r["direction"] for r in conv_rows} == {"fwd", "bwd"}
    assert conv_rows[0]["shapes"]["Filter"] == [[4, 3, 3, 3]]
    assert {r["wrt"] for r in conv_rows if r["direction"] == "bwd"} <= \
        {"filter", "input"}
    del compiled, exe


# ---------------------------------------------------------------------------
# a window recorded on the chip
# ---------------------------------------------------------------------------
FIXTURE = os.path.join(BENCH, "fixtures", "ops_resnet50_scan_v5e.json.gz")
# milliseconds in the 6.5 ms excerpt (steps = 1), as the join gave them on
# the day the fixture was recorded; the same numbers came from joining the
# excerpt with the module's WHOLE text
PINNED = {
    "total_ms": 6.499017,
    "unattributed_ms": 0.479585,
    "stem_wgrad_ms": 1.994782,
    "class_ms": {("conv", "fwd"): 1.938779, ("conv", "bwd"): 1.994782,
                 ("norm", "fwd"): 1.239045, ("norm", "bwd"): 0.006103,
                 ("softmax_loss", "fwd"): 0.005363},
}


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as fh:
        return json.load(fh)


def _join(recorded, text=None):
    window = oa.window_events(recorded)
    steps = 1                           # the excerpt is under one step long
    return window, oa.attribute(
        window, steps, recorded["hlo_text"] if text is None else text,
        recorded["program_ops"])


def test_recorded_window_class_sums(recorded):
    """6.5 ms across a step boundary of ResNet-50 bs256 on the chip: the
    stem's weight gradient at the end of one step, then the stem's forward
    convolution, its batch norm and the max-pool of the next."""
    window, r = _join(recorded)
    assert r["ok"], r
    assert r["module"] == "jit_pt_run_steps" == window["modules"][0]
    ms = {k: v * 1e3 for k, v in r["class_s"].items()}
    assert ms == pytest.approx(PINNED["class_ms"], rel=1e-6)
    assert r["total_s"] * 1e3 == pytest.approx(PINNED["total_ms"], rel=1e-6)
    assert r["unattributed_s"] * 1e3 == \
        pytest.approx(PINNED["unattributed_ms"], rel=1e-6)
    assert r["collective_s"] == 0.0
    # the parts sum to what trace_reduce counts on the same events
    table = oa.op_table(r)
    parts = sum(table["class_ms_per_step"].values()) \
        + table["other_attributed_ms_per_step"] \
        + table["unattributed_ms_per_step"] + table["collective_ms_per_step"]
    total = sum(tr.summarize(recorded, 1, steps=1).ops.values()) * 1e3
    assert parts == pytest.approx(total, rel=1e-9)
    assert parts == pytest.approx(table["device_ms_per_step"], rel=1e-9)
    assert oa.attribute(window, 1, recorded["hlo_text"],
                        expect_total_s=total * 1e-3)["ok"]


def test_recorded_conv_with_optimizer_fusion_is_conv2d_backward(recorded):
    _, r = _join(recorded)
    module = oa.parse_module(recorded["hlo_text"])
    stem = "multiply_subtract_fusion.365"
    # XLA named it after the Momentum update fused at its end ...
    fused = [i["op_name"] for i in
             oa._fused(module, module["instructions"][stem]["calls"])]
    assert any("pt.momentum:" in n and n.endswith("/sub") for n in fused)
    # ... and it is the stem's weight-gradient convolution
    assert oa.owner(module, stem) == ("conv2d", "0.0", "bwd")
    row = next(x for x in r["rows"] if stem in x["xla"])
    assert (row["op_type"], row["direction"], row["wrt"]) == \
        ("conv2d", "bwd", "filter")
    assert row["shapes"]["Filter"] == [[64, 3, 7, 7]]
    assert row["ms_per_step"] == pytest.approx(PINNED["stem_wgrad_ms"],
                                               rel=1e-6)
    heaviest = r["rows"][0]
    assert (heaviest["op_type"], heaviest["instance"]) == ("conv2d", "0.0")


def test_recorded_container_collective_and_copies(recorded):
    window, r = _join(recorded)
    names = {n for n, _ in window["events"]}
    # the scan's while spans the whole excerpt and is not counted
    ops_line = next(line for line in recorded["planes"][0]["lines"]
                    if line["name"] == "XLA Ops")
    assert any(tr.short_name(e[0]).startswith("while") for e in
               ops_line["events"])
    assert not any(n.startswith("while") for n in names)
    assert r["total_s"] < 6.5e-3
    # XLA's copies between memory spaces carry no op_name: unattributed,
    # and the table says whom they were moved for
    kinds = {k["opcode"]: k for k in
             oa.op_table(r)["unattributed_by_opcode"]}
    assert "copy-done" in kinds and kinds["copy-done"]["moved_for"]
    assert all(n.startswith("copy-done") for n, _ in r["unattributed"][:3])
    assert all(n.startswith("copy") for n, _ in r["unattributed"][:8])
    assert not any("fusion" in n for n, _ in r["unattributed"])
    # a collective on the same chip is left out of every class
    with_collective = copy.deepcopy(recorded)
    line = next(line for line in with_collective["planes"][0]["lines"]
                if line["name"] == "XLA Ops")
    line["events"] = [e for e in line["events"]
                      if not tr.short_name(e[0]).startswith("while")]
    line["events"].append(
        ["%all-reduce.1 = bf16[25557032]{0} all-reduce(%x)", 7.0e6, 9.0e5])
    with_collective["planes"][1]["lines"][0]["events"].append(
        ["cb:window", 6.5e6, 2.0e6])           # the window goes on
    r2 = oa.attribute(oa.window_events(with_collective), 1,
                      recorded["hlo_text"], recorded["program_ops"])
    assert r2["collective_s"] == pytest.approx(9.0e-4)
    assert r2["class_s"] == pytest.approx(r["class_s"])
    assert r2["total_s"] == pytest.approx(r["total_s"] + 9.0e-4)


def test_recorded_text_without_scopes_reads_as_nothing(recorded, monkeypatch,
                                                       tmp_path):
    """An executable compiled before the lowering named its ops, served
    from a cache: every reader returns None, never a table in which
    everything is unattributed."""
    import re
    bare = re.sub(r"pt\.[A-Za-z0-9_]+(:[0-9.]+)?/?", "",
                  recorded["hlo_text"])
    assert "pt." not in bare and "jvp(" in bare
    ctx, _ = _ctx(monkeypatch, tmp_path, recorded, 1, bare,
                  recorded["program_ops"])
    assert set(_read(ctx).values()) == {None}
    assert ctx.detail["op_table"]["ok"] is False
    assert "no pt. scope" in ctx.detail["op_table"]["why"]
    # with the text as recorded the same readers give the pinned numbers
    ctx, _ = _ctx(monkeypatch, tmp_path, recorded, 1, recorded["hlo_text"],
                  recorded["program_ops"])
    got = _read(ctx)
    assert got["conv_bwd_ms_per_step"] == \
        pytest.approx(PINNED["class_ms"][("conv", "bwd")], rel=1e-6)
    assert got["norm_ms_per_step"] == pytest.approx(           # both ways
        PINNED["class_ms"][("norm", "fwd")]
        + PINNED["class_ms"][("norm", "bwd")], rel=1e-6)
    assert got["recurrence_ms_per_step"] == 0.0
    assert got["device_unattributed_share"] == pytest.approx(
        100 * PINNED["unattributed_ms"] / PINNED["total_ms"], rel=1e-6)
