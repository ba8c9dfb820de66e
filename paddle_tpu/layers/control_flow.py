"""Control-flow layers: While, StaticRNN, DynamicRNN, IfElse, Switch, and
dynamic-RNN plumbing (reference: fluid/layers/control_flow.py — StaticRNN:118,
While:342, lod_rank_table:399, lod_tensor_to_array:500, DynamicRNN:962).

TPU-native notes:
* ``While`` lowers to lax.while_loop (see ops/control_flow_ops.py).
* ``StaticRNN``/``DynamicRNN`` build a sub-block executed per step; the
  executor runs it under lax.scan via the ``rnn`` op — differentiable, unlike
  a raw while loop, and pipelined by XLA.  DynamicRNN masks finished
  sequences instead of shrinking the batch (shrink_rnn_memory_op analog).
* ``Repeat`` runs a sub-block a fixed number of times over carried values,
  with no sequence: the ops and their weights stand ONCE in the Program
  (a looped, weight-shared stack of layers), every pass reads the same
  parameters, and their gradients sum over the passes.  What a pass leaves
  (``output``) comes out stacked along a new leading axis.  The ``rnn``
  lowering walks a SEQUENCE (its length, its masks, the per-step slices);
  ``repeat`` has none of that and shares none of its code.
* ``recompute`` marks a stretch of the Program whose intermediate values
  the backward pass computes again instead of keeping them
  (``jax.checkpoint`` where the forward slice is differentiated): only what
  the stretch reads stays alive between the two passes.  The values are the
  same with and without it; what changes is the memory a step needs and
  the forward work it does twice.
"""
from __future__ import annotations

import contextlib

from ..core import unique_name
from ..core.program import Variable, default_main_program
from ..layer_helper import LayerHelper

__all__ = [
    "While", "StaticRNN", "DynamicRNN", "IfElse", "Switch", "increment",
    "less_than", "equal", "array_read", "array_write", "array_length",
    "create_array", "lod_rank_table", "max_sequence_len",
    "lod_tensor_to_array", "array_to_lod_tensor", "shrink_memory",
    "reorder_lod_tensor_by_rank", "ConditionalBlock", "Repeat", "recompute",
]


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment")
    out = x if in_place else helper.create_variable_for_type_inference(
        x.dtype, x.shape)
    helper.append_op(type="increment", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"step": float(value)})
    return out


def _cmp(op, x, y, cond=None):
    helper = LayerHelper(op)
    if cond is None:
        cond = helper.create_variable_for_type_inference("bool")
    helper.append_op(type=op, inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [cond]})
    return cond


def less_than(x, y, cond=None):
    return _cmp("less_than", x, y, cond)


def equal(x, y, cond=None):
    return _cmp("equal", x, y, cond)


class BlockGuard:
    def __init__(self, program):
        self.program = program

    def __enter__(self):
        self.program.create_block()
        return self

    def __exit__(self, *exc):
        self.program.rollback()
        return False


class While:
    """fluid While (control_flow.py:342): loop while ``cond`` is true.

    Vars written inside the block that are declared outside become the loop
    carry; the block must recompute ``cond``.
    """

    def __init__(self, cond, name=None):
        self.helper = LayerHelper("while", name=name)
        self.cond_var = cond
        self.program = self.helper.main_program

    @contextlib.contextmanager
    def block(self):
        parent_block = self.program.current_block()
        sub = self.program.create_block()
        ops_before = len(sub.ops)
        try:
            yield
        finally:
            # carried vars: outputs of sub-block ops that are declared in an
            # ancestor block (write-through semantics)
            written = []
            for op in sub.ops:
                for n in op.output_names:
                    if n not in sub.vars and n not in written:
                        written.append(n)
            self.program.rollback()
            parent_block.append_op(
                "while",
                inputs={"Condition": [self.cond_var],
                        "X": [n for n in written]},
                outputs={"Out": written},
                attrs={"sub_block": sub.idx})


class ConditionalBlock:
    def __init__(self, inputs, name=None):
        self.inputs = inputs
        self.helper = LayerHelper("conditional_block", name=name)
        self.program = self.helper.main_program

    @contextlib.contextmanager
    def block(self):
        parent_block = self.program.current_block()
        sub = self.program.create_block()
        try:
            yield
        finally:
            written = []
            for op in sub.ops:
                for n in op.output_names:
                    if n not in sub.vars and n not in written:
                        written.append(n)
            self.program.rollback()
            parent_block.append_op(
                "conditional_block",
                inputs={"Cond": [self.inputs[0]]},
                outputs={"Out": written},
                attrs={"sub_block": sub.idx})


def _names_once(names):
    return list(dict.fromkeys(names))


def _reads_from_outside(program, ops, visible_from):
    """The names ``ops`` (and the ops of their sub-blocks) read that no
    earlier one of them wrote and that ``visible_from`` can see: what a
    stretch of the Program takes from its surroundings."""
    from ..ops.control_flow_ops import _reads

    reads, written = [], set()
    for op in ops:
        reads += [n for n in _reads(program, op) if n not in written]
        written.update(op.output_names)
    return [n for n in _names_once(reads) if visible_from.has_var(n)]


@contextlib.contextmanager
def recompute():
    """``with layers.recompute(): ...``: the ops appended inside are a
    stretch whose intermediate values are NOT kept for the backward pass;
    it computes them again from what the stretch read (the executor lowers
    the stretch under ``jax.checkpoint``).  Same loss, same gradients;
    less memory, and the stretch's forward work done twice.

    The stretch becomes one ``recompute`` op over a sub-block.  It declares
    nothing of its own: the variables made inside belong to the enclosing
    block, so whatever follows reads them as if the stretch were not
    marked."""
    program = default_main_program()
    parent = program.current_block()
    sub = program.create_block()
    try:
        yield
    finally:
        program.rollback()
    for var in sub.vars.values():
        var.block = parent
    parent.vars.update(sub.vars)
    sub.vars = {}
    parent.append_op(
        "recompute",
        inputs={"X": _reads_from_outside(program, sub.ops, parent)},
        outputs={"Out": _names_once(n for op in sub.ops
                                    for n in op.output_names)},
        attrs={"sub_block": sub.idx})


class Repeat:
    """A block run ``times`` times over carried values, its ops and weights
    standing once in the Program::

        loop = layers.Repeat(times=4)
        with loop.block():
            h = loop.carry(h0)        # the value a pass starts from
            new = stack_of_layers(h)  # ONE set of weights, read every pass
            loop.update(h, new)       # ... and the next pass starts from
            loop.output(new)
        hs = loop()                   # [4, ...]: what each pass left

    Differentiable; a parameter read inside gets the sum of the passes'
    gradients.  Lowered by the ``repeat`` op (ops/control_flow_ops.py)."""

    def __init__(self, times, name=None):
        if int(times) < 1:
            raise ValueError(f"Repeat: times must be at least 1, got {times}")
        self.helper = LayerHelper("repeat", name=name)
        self.program = self.helper.main_program
        self.times = int(times)
        self.carries = {}         # carried var name -> [init var, update name]
        self.pass_outputs = []
        self.sub_block = None
        self.outputs = []

    @contextlib.contextmanager
    def block(self):
        parent_block = self.program.current_block()
        self.sub_block = self.program.create_block()
        try:
            yield
        finally:
            self.program.rollback()
        self._complete(parent_block)

    def carry(self, init):
        """The carried value as a pass sees it at its start: ``init`` in
        the first pass, what ``update`` named in the pass before after."""
        var = self.sub_block.create_var(
            name=unique_name.generate("repeat_carry"), dtype=init.dtype,
            shape=init.shape)
        self.carries[var.name] = [init, None]
        return var

    def update(self, carried, new):
        self.carries[carried.name][1] = new.name

    def output(self, *values):
        self.pass_outputs.extend(values)

    def _complete(self, parent_block):
        missing = [n for n, (_, new) in self.carries.items() if new is None]
        if missing:
            raise ValueError(f"Repeat: no update for carried {missing}")
        self.outputs = [
            parent_block.create_var(
                name=unique_name.generate("repeat_out"), dtype=o.dtype,
                shape=(self.times,) + tuple(o.shape)
                if o.shape is not None else None)
            for o in self.pass_outputs]
        names = list(self.carries)
        parent_block.append_op(
            "repeat",
            inputs={"Init": [self.carries[n][0] for n in names]},
            outputs={"Outputs": self.outputs},
            attrs={"sub_block": self.sub_block.idx, "times": self.times,
                   "carry_names": names,
                   "update_names": [self.carries[n][1] for n in names],
                   "output_names": [o.name for o in self.pass_outputs]})

    def __call__(self):
        return self.outputs if len(self.outputs) != 1 else self.outputs[0]


class StaticRNN:
    """Unrolled-over-time RNN builder (control_flow.py:118).  The step block
    becomes an ``rnn`` op lowered to lax.scan."""

    BEFORE_RNN_BLOCK = 0
    IN_RNN_BLOCK = 1
    AFTER_RNN_BLOCK = 2

    def __init__(self, name=None):
        self.helper = LayerHelper("static_rnn", name=name)
        self.program = self.helper.main_program
        self.seq_len_var = None
        self.inputs = []          # (x_var, step_var_name)
        self.memories = {}        # step name -> (init var, mem var, pre name)
        self.step_outputs = []    # (step var, out var)
        self.sub_block = None
        self.status = self.BEFORE_RNN_BLOCK
        self.parent_block = None

    @contextlib.contextmanager
    def step(self):
        self.status = self.IN_RNN_BLOCK
        self.parent_block = self.program.current_block()
        self.sub_block = self.program.create_block()
        try:
            yield
        finally:
            self.program.rollback()
            self.status = self.AFTER_RNN_BLOCK
            self._complete()

    def step_input(self, x):
        """x: [B, T, ...] sequence var; returns per-step [B, ...] var."""
        assert self.status == self.IN_RNN_BLOCK
        ipt = self.sub_block.create_var(
            name=unique_name.generate("rnn_step_in"), dtype=x.dtype,
            shape=(x.shape[0],) + tuple(x.shape[2:]) if x.shape else None)
        self.inputs.append((x, ipt.name))
        if self.seq_len_var is None:
            self.seq_len_var = x
        return ipt

    def memory(self, init=None, shape=None, batch_ref=None, value=0.0,
               dtype="float32"):
        assert self.status == self.IN_RNN_BLOCK
        if init is None:
            from . import tensor as T
            cur = self.program.current_block_idx
            self.program.current_block_idx = self.parent_block.idx
            try:
                init = T.fill_constant_batch_size_like(
                    batch_ref or self.seq_len_var,
                    [-1] + list(shape), dtype, value)
            finally:
                self.program.current_block_idx = cur
        mem = self.sub_block.create_var(
            name=unique_name.generate("rnn_mem"), dtype=init.dtype,
            shape=init.shape)
        self.memories[mem.name] = [init, None, None]
        return mem

    def update_memory(self, mem, new):
        self.memories[mem.name][1] = new.name

    def step_output(self, o):
        assert self.status == self.IN_RNN_BLOCK
        self.step_outputs.append(o)

    def output(self, *outputs):
        for o in outputs:
            self.step_output(o)

    def _complete(self):
        out_vars = []
        for o in self.step_outputs:
            ov = self.parent_block.create_var(
                name=unique_name.generate("rnn_out"), dtype=o.dtype,
                shape=(o.shape[0], -1) + tuple(o.shape[1:]) if o.shape
                else None, lod_level=1)
            out_vars.append(ov)
        self.outputs = out_vars
        mem_names = list(self.memories)
        self.parent_block.append_op(
            "rnn",
            inputs={"Inputs": [x.name for x, _ in self.inputs],
                    "InitStates": [self.memories[m][0].name
                                   for m in mem_names]},
            outputs={"Outputs": [v.name for v in out_vars]},
            attrs={
                "sub_block": self.sub_block.idx,
                "step_inputs": [n for _, n in self.inputs],
                "mem_step_names": mem_names,
                "mem_update_names": [self.memories[m][1] for m in mem_names],
                "step_output_names": [o.name for o in self.step_outputs],
            })

    def __call__(self):
        return self.outputs if len(self.outputs) > 1 else self.outputs[0]


class DynamicRNN(StaticRNN):
    """fluid DynamicRNN (control_flow.py:962).  With padded+masked scan, the
    dynamic and static RNN share one lowering; variable lengths come from the
    @LEN companions, and memories freeze when a sequence ends."""

    def __init__(self, name=None):
        super().__init__(name=name)

    @contextlib.contextmanager
    def block(self):
        with self.step():
            yield


class IfElse:
    """fluid IfElse: mask-select instead of batch partition (static shapes).

    true_block/false_block compute on the full batch; ``output`` merges with
    where(cond).  Semantics match when branch ops are per-row.
    """

    OUT_IF_ELSE_BLOCKS = 2

    def __init__(self, cond, name=None):
        self.helper = LayerHelper("ifelse", name=name)
        self.cond = cond
        self.in_true = True
        self.true_outs = []
        self.false_outs = []
        self.program = self.helper.main_program

    @contextlib.contextmanager
    def true_block(self):
        self.in_true = True
        yield

    @contextlib.contextmanager
    def false_block(self):
        self.in_true = False
        yield

    def input(self, x):
        return x

    def output(self, *outs):
        if self.in_true:
            self.true_outs.extend(outs)
        else:
            self.false_outs.extend(outs)

    def __call__(self):
        from .nn import _unary_layer
        results = []
        for t, f in zip(self.true_outs, self.false_outs):
            helper = LayerHelper("ifelse_merge")
            out = helper.create_variable_for_type_inference(t.dtype, t.shape)
            helper.append_op(type="merge_lod_tensor",
                             inputs={"InTrue": [t], "InFalse": [f],
                                     "Mask": [self.cond]},
                             outputs={"Out": [out]})
            results.append(out)
        return results if len(results) > 1 else results[0]


class Switch:
    """fluid Switch for lr schedules etc.: sequential case guards."""

    def __init__(self, name=None):
        self.helper = LayerHelper("switch", name=name)
        self.pre_not_conds = []

    @contextlib.contextmanager
    def case(self, condition):
        cb = ConditionalBlock([condition])
        with cb.block():
            yield

    @contextlib.contextmanager
    def default(self):
        yield

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


# -- tensor array helpers ----------------------------------------------------
def create_array(dtype):
    helper = LayerHelper("array")
    return helper.block.create_var(
        name=unique_name.generate("array"), dtype=dtype)


def array_write(x, i, array=None):
    helper = LayerHelper("array_write")
    if array is None:
        array = create_array(x.dtype)
    helper.append_op(type="write_to_array",
                     inputs={"X": [x], "I": [i]},
                     outputs={"Out": [array]})
    return array


def array_read(array, i):
    helper = LayerHelper("array_read")
    out = helper.create_variable_for_type_inference(array.dtype)
    helper.append_op(type="read_from_array",
                     inputs={"X": [array], "I": [i]},
                     outputs={"Out": [out]})
    return out


def array_length(array):
    helper = LayerHelper("array_length")
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(type="lod_array_length", inputs={"X": [array]},
                     outputs={"Out": [out]})
    return out


def lod_rank_table(x, level=0):
    helper = LayerHelper("lod_rank_table")
    out = helper.create_variable_for_type_inference("int32", lod_level=1)
    helper.append_op(type="lod_rank_table", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"level": level})
    return out


def max_sequence_len(rank_table):
    helper = LayerHelper("max_sequence_len")
    out = helper.create_variable_for_type_inference("int64")
    helper.append_op(type="max_sequence_len",
                     inputs={"RankTable": [rank_table]},
                     outputs={"Out": [out]})
    return out


def lod_tensor_to_array(x, table):
    helper = LayerHelper("lod_tensor_to_array")
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(type="lod_tensor_to_array",
                     inputs={"X": [x], "RankTable": [table]},
                     outputs={"Out": [out]})
    return out


def array_to_lod_tensor(x, table):
    helper = LayerHelper("array_to_lod_tensor")
    out = helper.create_variable_for_type_inference(x.dtype, lod_level=1)
    helper.append_op(type="array_to_lod_tensor",
                     inputs={"X": [x], "RankTable": [table]},
                     outputs={"Out": [out]})
    return out


def shrink_memory(x, i, table):
    helper = LayerHelper("shrink_memory")
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(type="shrink_rnn_memory",
                     inputs={"X": [x], "I": [i], "RankTable": [table]},
                     outputs={"Out": [out]})
    return out


def reorder_lod_tensor_by_rank(x, rank_table):
    helper = LayerHelper("reorder_lod_tensor_by_rank")
    out = helper.create_variable_for_type_inference(x.dtype, x.shape,
                                                    lod_level=x.lod_level)
    helper.append_op(type="reorder_lod_tensor_by_rank",
                     inputs={"X": [x], "RankTable": [rank_table]},
                     outputs={"Out": [out]})
    return out
