"""Device time by the Program op that lowered it.

XLA names a device operation after the elementwise tail of its fusion
(``multiply_subtract_fusion.365`` is the stem's weight-gradient convolution
with the Momentum update fused behind it), and the names change whenever
the program does.  The program names its work itself: every instruction of
a compiled step carries, in its HLO ``op_name``, the
``pt.<op_type>:<block>.<position>`` scope of the ``Operator`` that lowered
it (``paddle_tpu/core/executor.py LoweringContext.op_scope``), wrapped by
JAX in ``jvp(...)`` for the forward pass and ``transpose(jvp(...))`` for
the backward pass; what the executor emits itself reads ``pt.scan``,
``pt.amp_cast``, ``pt.rng``, ``pt.dtype_cast``, ``pt.nan_check``.

This module joins the two: the first chip's ``XLA Ops`` work events inside
the ``cb:window`` spans (the events ``trace_reduce.summarize`` counts)
with the optimized module text of the executable the window ran, found by
the ``pt:<path>:<fp12>`` host annotation inside the window and asked of
the program through ``paddle_tpu.profiler.compiled_hlo_text(fp12)``.

The rule, for one event:

* a collective (``trace_reduce.COLLECTIVE``) is left out: it is
  ``collective_ms_per_step`` already;
* a plain instruction goes to the INNERMOST ``pt.`` scope of its
  ``op_name``;
* a fusion goes to the innermost ``pt.`` scope of the HEAVIEST instruction
  of its fused computation (nested fusions looked into), by the fixed order
  ``convolution`` > ``dot`` > ``reduce-window`` / ``select-and-scatter`` >
  ``reduce`` / ``scatter`` / ``gather``, the first in text order among
  equals.  So a weight-gradient convolution fused with the optimizer's
  update is convolution time of that ``conv2d``, backward.  A fusion of
  nothing but "anything else" (elementwise work, copies) goes to the scope
  that MOST of its instructions carry, the first in text order among
  equals: its first instruction alone is as a rule a cast of an argument
  (53 per-channel batch-norm fusions of ResNet-50 start with
  ``pt.amp_cast``).  Instructions that carry no ``pt.`` scope (parameters,
  constants, XLA's own bitcasts) are passed over, and a fusion none of
  whose instructions carries one goes by its own ``op_name``;
* direction is ``bwd`` when the scope sits inside ``transpose(`` (its own
  wrapper or an enclosing op's, as for the step block of an ``rnn``), else
  ``fwd``; a backward convolution is ``wrt: filter`` when a result of the
  event has the dimensions of the op's ``Filter`` in the Program, else
  ``wrt: input`` (table only);
* an event whose instruction is not in the text, or has no ``pt.`` scope
  anywhere, is ``unattributed`` and kept under XLA's name.  The table also
  says, by opcode, whom such data was moved for (``moved_for``): XLA's
  asynchronous copies and slices between memory spaces carry no
  ``op_name``, so no scope can reach them, but their readers have one.

Classes are keyed by OP TYPE (``CLASS_OF``), never by cell or model.  The
parts (classes + other attributed + unattributed + collectives) sum to the
first chip's total in ``ctx.trace.ops``; a join that misses that by more
than 0.5 % is refused.

Stale or missing names read as nothing, not as a number: where the window's
executable cannot be found, has no text, or carries no ``pt.`` scope at all
(compiled before the lowering named its ops and served from a cache),
``join(ctx)`` is not ``ok``, every class reader returns ``None`` and
``detail["op_table"]`` says why.  Where the join is sound, a class no event
of the window belongs to has taken 0 ms, and its reader says so: every
cell's traced line then carries all eight metrics.

The whole table (the forty heaviest rows with the Program's shapes and
every XLA name merged into each, the ten heaviest unattributed names, the
unattributed time by opcode and reader) goes to
``chipbench/out/<cell>/op_table.json``, beside the trace.  The result line
is read from the tail of a run's output, so ``detail["op_table"]`` keeps
the sums, where that file is, and as many of the heaviest rows as fit in
``LINE_BYTES``, in a short form.
"""
from __future__ import annotations

import glob
import json
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

from . import profile
from .trace_reduce import (COLLECTIVE, DEVICE_PLANE, OPS_LINE, _host_spans,
                           from_xplane, short_name, work_events)

#: op type -> class; a class is one per-layer metric (``conv`` is two, by
#: direction).  Optimizer ops: every op ``paddle_tpu/ops/optimizer_ops.py``
#: registers (a test holds the two lists together).
CLASS_OF: Dict[str, str] = {
    **dict.fromkeys(("conv2d", "conv2d_transpose", "depthwise_conv2d"),
                    "conv"),
    **dict.fromkeys(("batch_norm", "layer_norm"), "norm"),
    **dict.fromkeys(("mul", "matmul", "fc"), "matmul"),
    **dict.fromkeys(("softmax", "cross_entropy",
                     "softmax_with_cross_entropy"), "softmax_loss"),
    **dict.fromkeys(("rnn", "gru", "gru_unit", "lstm", "lstm_unit",
                     "sequence_pool"), "recurrence"),
    **dict.fromkeys(("sgd", "momentum", "adam", "adamax", "adagrad",
                     "adadelta", "decayed_adagrad", "rmsprop", "ftrl",
                     "proximal_gd", "proximal_adagrad"), "optimizer"),
}

#: the heaviest instruction of a fusion: lower rank wins
RANK = {"convolution": 0, "dot": 1, "reduce-window": 2,
        "select-and-scatter": 2, "reduce": 3, "scatter": 3, "gather": 3}
OTHER_RANK = 4

SUM_TOLERANCE = 0.005          # parts against the trace's own total
TABLE_ROWS, UNATTRIBUTED_ROWS = 40, 10
OBS_KEY = "op_attribution"
TABLE_FILE = "op_table.json"   # beside the trace: chipbench/out/<cell>/
#: what ``detail["op_table"]`` may add to the result line, in bytes of
#: JSON: the line is read from the tail of a run's output, and a line that
#: the tail cuts is no result (PR 22's lines are 2.5 KB without it)
LINE_BYTES = 3000
LINE_COLUMNS = ["op_type", "instance", "direction", "wrt", "ms_per_step",
                "events", "xla"]
LINE_XLA_NAMES, LINE_UNATTRIBUTED_ROWS = 2, 5

SCOPE = re.compile(
    r"(?<![A-Za-z0-9_])pt\.([A-Za-z0-9_]+)(?::([A-Za-z0-9_.]+))?")
PT_SPAN = re.compile(r"^pt:[^:]*:([0-9a-f]{6,})")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)}]+)")
_ARRAY = re.compile(r"[a-z]+[0-9]*\[([0-9,]*)\]")
_OPERAND = re.compile(r"%([^\s,()]+)")


# ---------------------------------------------------------------------------
# the module text
# ---------------------------------------------------------------------------
def _split_shape(rest: str) -> Tuple[str, str]:
    """('<result shape>', '<opcode>(...), attributes') of what follows
    ``%name = ``; a tuple shape is in balanced parentheses."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].lstrip()
    shape, _, tail = rest.partition(" ")
    return shape, tail


def parse_module(text: str) -> dict:
    """``{"module": name, "instructions": {name: {opcode, shape, operands,
    op_name, calls}}, "computations": {name: [instruction names, in text
    order]}}`` of an optimized HLO module as ``compiled.as_text()`` prints
    it."""
    head = re.match(r"HloModule\s+([^\s,]+)", text)
    instructions: Dict[str, dict] = {}
    computations: Dict[str, List[str]] = {}
    current: Optional[List[str]] = None
    for line in text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and "->" in line:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        shape, tail = _split_shape(m.group(2))
        opcode, _, rest = tail.partition("(")
        operands, rest = _split_shape("(" + rest)
        op_name = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        instructions[m.group(1)] = {
            "opcode": opcode.strip(), "shape": shape,
            "operands": _OPERAND.findall(operands),
            "op_name": op_name.group(1) if op_name else "",
            "calls": calls.group(1) if calls else None}
        current.append(m.group(1))
    return {"module": head.group(1) if head else None,
            "instructions": instructions, "computations": computations}


def innermost_scope(op_name: str) -> Optional[Tuple[str, str, str]]:
    """(op_type, instance, direction) of the innermost ``pt.`` scope of an
    HLO ``op_name``; None when it has none.  ``instance`` is '' for the
    executor's own scopes (``pt.scan`` ...)."""
    found = None
    for found in SCOPE.finditer(op_name):
        pass
    if found is None:
        return None
    end = op_name.find("/", found.end())
    upto = op_name if end < 0 else op_name[:end]
    return (found.group(1), found.group(2) or "",
            "bwd" if "transpose(" in upto else "fwd")


def _fused(module: dict, computation: str, seen=None):
    """The instructions of a fused computation in text order, nested
    fusions replaced by their own instructions."""
    seen = set() if seen is None else seen
    if computation in seen:
        return
    seen.add(computation)
    for name in module["computations"].get(computation, ()):
        ins = module["instructions"][name]
        if ins["opcode"] == "fusion" and ins["calls"]:
            yield from _fused(module, ins["calls"], seen)
        else:
            yield ins


def owner(module: dict, name: str) -> Optional[Tuple[str, str, str]]:
    """The (op_type, instance, direction) one executed instruction's time
    goes to, by the rule in this file's docstring; None = unattributed."""
    memo = module.setdefault("owners", {})
    if name not in memo:
        memo[name] = _owner(module, name)
    return memo[name]


def _owner(module: dict, name: str) -> Optional[Tuple[str, str, str]]:
    ins = module["instructions"].get(name)
    if ins is None:
        return None
    if ins["opcode"] == "fusion" and ins["calls"]:
        best = None
        votes: Dict[Tuple[str, str, str], int] = {}
        for inner in _fused(module, ins["calls"]):
            scope = innermost_scope(inner["op_name"])
            if scope is None:
                continue
            rank = RANK.get(inner["opcode"], OTHER_RANK)
            if best is None or rank < best[0]:
                best = (rank, scope)
            votes[scope] = votes.get(scope, 0) + 1
        if best is not None:
            # max() keeps the first of equals, and dicts keep text order
            return best[1] if best[0] < OTHER_RANK \
                else max(votes, key=votes.get)
    return innermost_scope(ins["op_name"])


def moved_for(module: dict, name: str, hops: int = 8
              ) -> Optional[Tuple[str, str, str]]:
    """For an instruction with no scope of its own (XLA's asynchronous
    copies and slices between memory spaces carry no ``op_name``): the
    owner of the nearest instruction that USES its result, looked for
    through other scope-less instructions (``copy-start`` ->
    ``copy-done`` -> the fusion that reads it), else of the nearest one
    that produced its operand.  Only for the table: whom the data was
    moved for; the time itself stays unattributed."""
    if "users" not in module:
        users: Dict[str, List[str]] = {}
        for user, ins in module["instructions"].items():
            for operand in ins["operands"]:
                users.setdefault(operand, []).append(user)
        module["users"] = users
    for step in (lambda n: module["users"].get(n, ()),
                 lambda n: module["instructions"][n]["operands"]
                 if n in module["instructions"] else ()):
        frontier, seen = [name], {name}
        for _ in range(hops):
            nxt = []
            for n in frontier:
                for other in step(n):
                    if other in seen:
                        continue
                    seen.add(other)
                    found = owner(module, other)
                    if found is not None:
                        return found
                    nxt.append(other)
            frontier = nxt
    return None


def _dims(shape: str) -> List[Tuple[int, ...]]:
    """Sorted dimensions of every array in a (possibly tuple) shape."""
    return [tuple(sorted(int(d) for d in dims.split(",") if d))
            for dims in _ARRAY.findall(shape)]


# ---------------------------------------------------------------------------
# the Program side of the join
# ---------------------------------------------------------------------------
def program_ops(program) -> Dict[str, dict]:
    """``{"<block>.<position>": {"type", "shapes": {slot: [shape]}}}`` of a
    ``paddle_tpu`` Program: what an instance of the table joins back to."""
    out = {}
    for block in program.blocks:
        for pos, op in enumerate(block.ops):
            shapes = {}
            for slot, names in list(op.inputs.items()) \
                    + list(op.outputs.items()):
                found = [list(block.var(n).shape) for n in names
                         if block.has_var(n)
                         and block.var(n).shape is not None]
                if found:
                    shapes[slot] = found
            out[f"{block.idx}.{pos}"] = {"type": op.type, "shapes": shapes}
    return out


# ---------------------------------------------------------------------------
# the join
# ---------------------------------------------------------------------------
def window_events(trace: dict, window_span: str = "cb:window") -> dict:
    """What the join reads of a trace (``trace_reduce``'s plain structure):
    ``events`` [(xla name, seconds inside the window)] of the first chip's
    work, ``prefixes`` {fingerprint prefix: annotated ns} of the ``pt:``
    spans inside the window, ``modules`` (names on ``XLA Modules``)."""
    planes = sorted((p for p in trace["planes"]
                     if DEVICE_PLANE.match(p["name"])),
                    key=lambda p: int(DEVICE_PLANE.match(p["name"]).group(1)))
    if not planes:
        return {"events": [], "prefixes": {}, "modules": []}
    work = work_events([e for line in planes[0]["lines"]
                        if line["name"] == OPS_LINE for e in line["events"]])
    # the window as trace_reduce.summarize takes it
    spans = _host_spans(trace)
    marks = [(s, e) for name, s, e in spans if name == window_span]
    if marks:
        w0, w1 = min(s for s, _ in marks), max(e for _, e in marks)
    elif work:
        w0 = min(e[1] for e in work)
        w1 = max(e[1] + e[2] for e in work)
    else:
        return {"events": [], "prefixes": {}, "modules": []}
    events = [(short_name(n), (min(s + d, w1) - max(s, w0)) * 1e-9)
              for n, s, d in work if s < w1 and s + d > w0]
    prefixes: Dict[str, float] = {}
    for name, s, e in spans:
        m = PT_SPAN.match(name)
        if m and s < w1 and e > w0:
            prefixes[m.group(1)] = prefixes.get(m.group(1), 0.0) + (e - s)
    modules = sorted({n.split("(", 1)[0] for line in planes[0]["lines"]
                      if line["name"] == "XLA Modules"
                      for n, s, d in line["events"]
                      if s < w1 and s + d > w0})
    return {"events": events, "prefixes": prefixes, "modules": modules}


def attribute(window: dict, steps: int, hlo_text: Optional[str],
              ops: Optional[Dict[str, dict]] = None,
              expect_total_s: Optional[float] = None) -> dict:
    """The join of one traced window (``window_events``) with its
    module's text.

    Returns ``{"ok": False, "why": ...}`` or ``{"ok": True, "steps",
    "total_s", "collective_s", "unattributed_s", "class_s": {(class,
    direction): s}, "rows": [...], "unattributed": [[xla name, s], ...],
    "module"}``; ``rows`` are sorted by time, heaviest first, and the XLA
    names of a row likewise."""
    events, modules = window["events"], window["modules"]
    if not events or not steps:
        return {"ok": False, "why": "no device work in the traced window"}
    if not hlo_text:
        return {"ok": False, "why": "no module text for the executable "
                                    "the window ran"}
    module = parse_module(hlo_text)
    if not any(SCOPE.search(i["op_name"])
               for i in module["instructions"].values()):
        return {"ok": False,
                "why": f"module {module['module']} carries no pt. scope "
                       f"(compiled before the lowering named its ops?)"}
    if module["module"] and modules and module["module"] not in modules:
        return {"ok": False, "why": f"the text is of {module['module']}, "
                                    f"the window ran {modules}"}

    def owner_and_wrt(name):
        """owner() plus, for a backward convolution of an op the Program
        knows, which gradient: a result of the event has the dimensions
        of the op's Filter, or not."""
        key = owner(module, name)
        if key is None or key[2] != "bwd" \
                or CLASS_OF.get(key[0]) != "conv":
            return key and key + (None,)
        info = (ops or {}).get(key[1])
        if not info or info["type"] != key[0] \
                or not info["shapes"].get("Filter"):
            return key + (None,)
        want = tuple(sorted(info["shapes"]["Filter"][0]))
        return key + ("filter" if want in _dims(
            module["instructions"][name]["shape"]) else "input",)

    total = collective = 0.0
    rows: Dict[Tuple[str, str, str, Optional[str]], dict] = {}
    unattributed: Dict[str, float] = {}
    owners: Dict[str, Optional[tuple]] = {}
    for name, seconds in events:
        total += seconds
        if COLLECTIVE.match(name):
            collective += seconds
            continue
        if name not in owners:
            owners[name] = owner_and_wrt(name)
        key = owners[name]
        if key is None:
            unattributed[name] = unattributed.get(name, 0.0) + seconds
            continue
        row = rows.setdefault(key, {"s": 0.0, "events": 0, "xla": {}})
        row["s"] += seconds
        row["events"] += 1
        row["xla"][name] = row["xla"].get(name, 0.0) + seconds

    if expect_total_s and abs(total - expect_total_s) \
            > SUM_TOLERANCE * expect_total_s:
        return {"ok": False,
                "why": f"the join's events sum to {total:.6f} s, the "
                       f"trace's to {expect_total_s:.6f} s"}

    class_s: Dict[Tuple[str, str], float] = {}
    table = []
    for (op_type, instance, direction, wrt), row in rows.items():
        cls = CLASS_OF.get(op_type)
        if cls is not None:
            class_s[(cls, direction)] = \
                class_s.get((cls, direction), 0.0) + row["s"]
        info = (ops or {}).get(instance)
        if info is not None and info["type"] != op_type:
            info = None                     # not the program that ran
        table.append({
            "op_type": op_type, "instance": instance,
            "direction": direction, "wrt": wrt, "class": cls,
            "ms_per_step": row["s"] * 1e3 / steps, "events": row["events"],
            "xla": sorted(row["xla"], key=lambda n: (-row["xla"][n], n)),
            "shapes": info["shapes"] if info else None})
    table.sort(key=lambda r: -r["ms_per_step"])

    # what the unattributed events are, and whom their data was moved for
    kinds: Dict[str, dict] = {}
    for name, seconds in unattributed.items():
        ins = module["instructions"].get(name)
        kind = kinds.setdefault(ins["opcode"] if ins else "not in the text",
                                {"s": 0.0, "names": 0, "for": {}})
        kind["s"] += seconds
        kind["names"] += 1
        whom = moved_for(module, name) if ins else None
        label = f"{whom[0]}:{whom[1]} {whom[2]}" if whom else "nobody found"
        kind["for"][label] = kind["for"].get(label, 0.0) + seconds
    return {"ok": True, "steps": steps, "total_s": total,
            "collective_s": collective,
            "unattributed_s": sum(unattributed.values()),
            "class_s": class_s, "rows": table, "module": module["module"],
            "unattributed": sorted(([k, v] for k, v in unattributed.items()),
                                   key=lambda kv: -kv[1]),
            "unattributed_kinds": kinds}


def _program_text_and_ops(prefixes: Dict[str, float]):
    """(module text or None, Program ops or None, why not) through the
    program's public surface; a program without it (the parent of the
    change that named the ops) gives (None, None, why)."""
    import paddle_tpu
    from paddle_tpu import profiler

    ask = getattr(profiler, "compiled_hlo_text", None)
    if ask is None:
        return None, None, ("this program has no "
                            "profiler.compiled_hlo_text: its lowering "
                            "names no op")
    if not prefixes:
        return None, None, "no pt:<path>:<fingerprint> span in the window"
    fp = max(prefixes, key=prefixes.get)
    text = ask(fp)
    if text is None:
        return None, None, f"no live compiled step for fingerprint {fp}"
    # the program the run built is the default main program (attribute()
    # drops an instance whose op type there is another)
    return text, program_ops(paddle_tpu.default_main_program()), None


def _join(ctx) -> dict:
    """The trace this run wrote, joined with the text of what it ran."""
    if ctx.trace is None or not ctx.trace.steps:
        return {"ok": False, "why": "no traced window"}
    t0 = time.perf_counter()
    found = sorted(glob.glob(os.path.join(
        profile.trace_dir(ctx), "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return {"ok": False,
                "why": "no .xplane.pb under " + profile.trace_dir(ctx)}
    window = window_events(from_xplane(found[-1]))
    t1 = time.perf_counter()
    text, ops, why = _program_text_and_ops(window["prefixes"])
    t2 = time.perf_counter()
    result = attribute(window, ctx.trace.steps, text, ops,
                       expect_total_s=sum(ctx.trace.ops.values())) \
        if text else {"ok": False, "why": why}
    result["seconds"] = {"read_trace": t1 - t0, "render_text": t2 - t1,
                         "join": time.perf_counter() - t2}
    result["text_bytes"] = len(text) if text else 0
    return result


def join(ctx) -> dict:
    """The join for this run, made once and kept in ``ctx.obs``; also
    writes ``ctx.detail["op_table"]`` and, of a sound join, the whole
    table beside the trace.  A reader leaves its metric out and does not
    fail the run: whatever the join raises is the ``why`` of a join that
    is not ``ok``."""
    if OBS_KEY in ctx.obs:
        return ctx.obs[OBS_KEY]
    try:
        result = _join(ctx)
        table, where = op_table(result), None
        if result["ok"]:
            folder = os.path.dirname(profile.trace_dir(ctx))
            os.makedirs(folder, exist_ok=True)
            with open(os.path.join(folder, TABLE_FILE), "w") as fh:
                json.dump(table, fh, indent=1)
            where = os.path.relpath(os.path.join(folder, TABLE_FILE),
                                    ctx.root)
    except Exception as exc:                # noqa: BLE001 (see docstring)
        table = result = {"ok": False, "why": f"the join failed: {exc!r}"}
        where = None
    ctx.obs[OBS_KEY] = result
    ctx.detail["op_table"] = line_table(table, where)
    return result


def op_table(result: dict) -> dict:
    """The whole table of a join, as ``join`` writes it to
    ``chipbench/out/<cell>/op_table.json``; of a join that is not ``ok``,
    why."""
    if not result["ok"]:
        return {k: result[k] for k in ("ok", "why", "seconds")
                if k in result}
    steps = result["steps"]
    per_step = 1e3 / steps
    classes: Dict[str, float] = {}
    for (cls, direction), s in sorted(result["class_s"].items()):
        key = f"{cls}_{direction}" if cls == "conv" else cls
        classes[key] = classes.get(key, 0.0) + s * per_step
    attributed = sum(r["ms_per_step"] for r in result["rows"])
    return {
        "ok": True, "module": result["module"], "steps": steps,
        "device_ms_per_step": result["total_s"] * per_step,
        "class_ms_per_step": classes,
        "other_attributed_ms_per_step":
            attributed - sum(classes.values()),
        "unattributed_ms_per_step": result["unattributed_s"] * per_step,
        "collective_ms_per_step": result["collective_s"] * per_step,
        "rows": result["rows"][:TABLE_ROWS],
        "unattributed": [[k, v * per_step] for k, v in
                         result["unattributed"][:UNATTRIBUTED_ROWS]],
        "unattributed_by_opcode": [
            {"opcode": opcode, "ms_per_step": kind["s"] * per_step,
             "xla_names": kind["names"],
             "moved_for": [[k, v * per_step] for k, v in sorted(
                 kind["for"].items(), key=lambda kv: -kv[1])[:5]]}
            for opcode, kind in sorted(
                result["unattributed_kinds"].items(),
                key=lambda kv: -kv[1]["s"])[:UNATTRIBUTED_ROWS]],
        "seconds": result.get("seconds"),
        "text_bytes": result.get("text_bytes")}


def line_table(table: dict, where: Optional[str] = None) -> dict:
    """What ``run.py`` prints under ``detail["op_table"]``: the sums of
    ``op_table``, ``file`` (where the whole table is, from the checkout's
    root), the heaviest unattributed XLA names and the heaviest ``rows``
    as lists under ``columns`` (a row's XLA names cut to the heaviest
    ``LINE_XLA_NAMES`` and ``"+<n>"`` for the others), as many as
    ``LINE_BYTES`` of JSON hold."""
    if not table["ok"]:
        return table

    def ms(x):
        return round(x, 4)

    line = {k: table[k] for k in ("ok", "module", "steps")}
    for key in ("device_ms_per_step", "other_attributed_ms_per_step",
                "unattributed_ms_per_step", "collective_ms_per_step"):
        line[key] = ms(table[key])
    line["class_ms_per_step"] = {k: ms(v) for k, v in
                                 table["class_ms_per_step"].items()}
    line["seconds"] = table["seconds"] and {
        k: round(v, 3) for k, v in table["seconds"].items()}
    line["text_bytes"] = table["text_bytes"]
    line["file"] = where
    line["unattributed"] = [[name, ms(v)] for name, v in
                            table["unattributed"][:LINE_UNATTRIBUTED_ROWS]]
    line["columns"] = LINE_COLUMNS
    line["rows"] = []
    room = LINE_BYTES - len(json.dumps(line))
    for row in table["rows"]:
        xla = row["xla"][:LINE_XLA_NAMES]
        if len(row["xla"]) > len(xla):
            xla = xla + [f"+{len(row['xla']) - len(xla)}"]
        short = [ms(row[c]) if c == "ms_per_step" else
                 xla if c == "xla" else row[c] for c in LINE_COLUMNS]
        room -= len(json.dumps(short)) + 2          # ", " between rows
        if room < 0:
            break
        line["rows"].append(short)
    return line


# ---------------------------------------------------------------------------
# what the metric readers call
# ---------------------------------------------------------------------------
def class_ms_per_step(ctx, cls: str,
                      directions: Sequence[str] = ("fwd", "bwd")
                      ) -> Optional[float]:
    """Device milliseconds per step of one class; None when the join is
    not ``ok``, and 0.0 when it is and the window ran no event of the
    class."""
    result = join(ctx)
    if not result["ok"]:
        return None
    return sum(result["class_s"].get((cls, d), 0.0)
               for d in directions) * 1e3 / result["steps"]


def unattributed_share(ctx) -> Optional[float]:
    """Device time of events with no ``pt.`` scope over the first chip's
    total, in percent."""
    result = join(ctx)
    if not result["ok"] or not result["total_s"] > 0:
        return None
    return 100.0 * result["unattributed_s"] / result["total_s"]
