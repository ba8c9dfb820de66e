"""Control-flow op lowerings: while, conditional_block, tensor arrays,
IfElse split/merge, dynamic-RNN plumbing.

Reference: while_op.cc:35-102 and recurrent_op.cc:39-335 run a sub-block with
a nested Executor over StepScopes; conditional_block_op, split_lod_tensor_op/
merge_lod_tensor_op implement IfElse by *physically partitioning* the batch.

TPU-native redesign:
* ``while`` lowers to ``lax.while_loop`` interpreting the sub-block as the
  body — compiled control flow, zero host round-trips per iteration.
* Tensor arrays are fixed-capacity [T_max, ...] buffers updated with
  ``lax.dynamic_update_slice`` (static shapes; capacity from the time dim).
* IfElse keeps static shapes by computing both branches on the full batch and
  selecting by mask (split_lod_tensor -> mask pass-through, merge_lod_tensor
  -> where), instead of data-dependent batch partitioning.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core import compile_cache
from ..core.executor import Env, run_op
from ..core.program import LEN2_SUFFIX, LEN_SUFFIX, _sub_block_indices
from ..core.registry import Operand, get_rowwise_fn, register_op


@register_op("while")
def _while(ctx, ins, attrs):
    """attrs: sub_block (int).  inputs: Condition ([1] bool var), X (loop
    vars read).  outputs: Out (parent-declared vars written by the body —
    the loop carry).  The body must recompute Condition."""
    sub_idx = attrs["sub_block"]
    cond_name = ctx.op.inputs["Condition"][0]
    carry_names = list(ctx.op.outputs["Out"])
    env = ctx.env
    init = {n: env.get(n) for n in carry_names}
    init_cond = env.get(cond_name).reshape(())

    def cond_fn(state):
        c, _ = state
        return c

    def body_fn(state):
        _, vals = state
        benv = ctx.child_env(sub_idx, env)
        # shadow carried vars with loop state (write-through targets parent,
        # so bind locally first)
        for n, v in vals.items():
            benv.local[n] = v
        ctx.interpret_block(sub_idx, benv)
        new_vals = {n: benv.get(n) for n in carry_names}
        new_cond = benv.get(cond_name).reshape(())
        return new_cond, new_vals

    _, final = lax.while_loop(cond_fn, body_fn, (init_cond, init))
    return {"Out": [final[n] for n in carry_names]}


@register_op("conditional_block")
def _conditional_block(ctx, ins, attrs):
    """Run sub-block iff Cond is true; else outputs keep current values.
    Outputs must already have values (initialize with fill_constant)."""
    sub_idx = attrs["sub_block"]
    cond = ins["Cond"][0].reshape(())
    out_names = list(ctx.op.outputs.get("Out", []))
    env = ctx.env
    current = {n: env.get(n) for n in out_names}

    def true_fn(vals):
        benv = ctx.child_env(sub_idx, env)
        ctx.interpret_block(sub_idx, benv)
        return {n: benv.get(n) for n in out_names}

    def false_fn(vals):
        return vals

    final = lax.cond(cond, true_fn, false_fn, current)
    return {"Out": [final[n] for n in out_names]}


@register_op("split_lod_tensor")
def _split_lod_tensor(ctx, ins, attrs):
    """IfElse entry: both branches get the full tensor; Mask rides along
    (static-shape deviation from split_lod_tensor_op.cc, documented above)."""
    x, mask = ins["X"][0], ins["Mask"][0]
    return {"OutTrue": x, "OutFalse": x}


@register_op("merge_lod_tensor")
def _merge_lod_tensor(ctx, ins, attrs):
    x_true, x_false, mask = ins["InTrue"][0], ins["InFalse"][0], ins["Mask"][0]
    m = mask.reshape((-1,) + (1,) * (x_true.ndim - 1)).astype(bool)
    return {"Out": jnp.where(m, x_true, x_false)}


# ---------------------------------------------------------------------------
# tensor arrays (lod_tensor_array, tensor_array_read_write_op)
# ---------------------------------------------------------------------------
@register_op("write_to_array")
def _write_to_array(ctx, ins, attrs):
    """array[i] = x.  The array buffer is a [cap, ...] tensor; created on
    first write with capacity attr ``capacity`` (default 128)."""
    x = ins["X"][0]
    i = ins["I"][0].reshape(()).astype(jnp.int32)
    out_name = ctx.op.outputs["Out"][0]
    if ctx.env.has(out_name):
        buf = ctx.env.get(out_name)
    else:
        cap = int(attrs.get("capacity", 128))
        buf = jnp.zeros((cap,) + x.shape, x.dtype)
    buf = lax.dynamic_update_slice(buf, x[None], (i,) + (0,) * x.ndim)
    return {"Out": buf}


@register_op("read_from_array")
def _read_from_array(ctx, ins, attrs):
    buf = ins["X"][0]
    i = ins["I"][0].reshape(()).astype(jnp.int32)
    return {"Out": lax.dynamic_index_in_dim(buf, i, axis=0, keepdims=False)}


@register_op("lod_array_length")
def _lod_array_length(ctx, ins, attrs):
    return {"Out": jnp.asarray(ins["X"][0].shape[0], jnp.int64)}


@register_op("lod_tensor_to_array")
def _lod_tensor_to_array(ctx, ins, attrs):
    """[B,T,...] -> [T,B,...] time-major buffer (the reference instead
    builds per-step shrinking batches via the rank table)."""
    x = ins["X"][0]
    return {"Out": jnp.swapaxes(x, 0, 1)}


@register_op("array_to_lod_tensor")
def _array_to_lod_tensor(ctx, ins, attrs):
    x = ins["X"][0]
    out = jnp.swapaxes(x, 0, 1)
    rt = ctx.op.inputs.get("RankTable")
    if rt:
        lens = ctx.get_len(rt[0])
        if lens is not None:
            ctx.set_len(ctx.op.outputs["Out"][0], lens)
    return {"Out": out}


@register_op("lod_rank_table")
def _lod_rank_table(ctx, ins, attrs):
    """lod_rank_table_op: descending-length order of sequences.  Returns the
    permutation as int32 [B]; lengths companion is forwarded."""
    x = ins["X"][0]
    name = ctx.op.inputs["X"][0]
    lens = ctx.get_len(name)
    if lens is None:
        lens = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    order = jnp.argsort(-lens)
    ctx.set_len(ctx.op.outputs["Out"][0], lens[order])
    return {"Out": order.astype(jnp.int32)}


@register_op("reorder_lod_tensor_by_rank")
def _reorder_by_rank(ctx, ins, attrs):
    x, rank = ins["X"][0], ins["RankTable"][0]
    out = jnp.take(x, rank.astype(jnp.int32), axis=0)
    lens = ctx.get_len(ctx.op.inputs["X"][0])
    if lens is not None:
        ctx.set_len(ctx.op.outputs["Out"][0],
                    jnp.take(lens, rank.astype(jnp.int32)))
    return {"Out": out}


@register_op("shrink_rnn_memory")
def _shrink_rnn_memory(ctx, ins, attrs):
    """shrink_rnn_memory_op: the reference shrinks the live batch as short
    sequences finish; with static shapes we freeze finished rows instead
    (mask applied by the RNN step), so this is identity."""
    return {"Out": ins["X"][0]}


@register_op("rnn_memory_helper")
def _rnn_memory_helper(ctx, ins, attrs):
    return {"Out": ins["X"][0]}


def _reads(program, op):
    """Every name ``op`` may read: its inputs and, for an op with
    sub-blocks, what the ops of those read (they see this env)."""
    names = list(op.input_names)
    for idx in _sub_block_indices(op):
        for sub in program.blocks[idx].ops:
            names += _reads(program, sub)
    return names


def _split_step_block(program, block, batch, per_step, mem_update_names,
                      env):
    """Loop fission of an rnn step block at its recurrence.  Returns
    ``(tail, frontier)``: the positions of the ops that need not run
    inside the scan, and the per-step names they read from the rest.

    The CORE, which stays, is every op a memory update depends on, every op
    that may not move, and what those read, transitively.  An op may move
    when its row-wise rule (``core.registry.register_rowwise``) holds for
    its attrs and operands — so that it can be given the rows of all steps
    at once — and the names it reads and writes are bound once, the written
    ones in this block.  ``per_step`` maps the step-input and memory names
    to their shapes; the other values of a step have the shape their var
    declares, and rows if that is [``batch``, ...]; anything else is read
    from ``env`` and has no rows.
    """
    ops = block.ops
    writers = {}                  # name -> positions of the ops writing it
    for i, op in enumerate(ops):
        for n in op.output_names:
            writers.setdefault(n, []).append(i)

    def operand(n):
        """``n`` as a rule sees it; shape None where no rule may answer."""
        if n in per_step or n in writers:
            # a value of the step: its rows are the batch's, or no rule
            shape = per_step[n] if n in per_step \
                else getattr(block.vars.get(n), "shape", None)
            rows = shape and shape[0] in (-1, batch)
            return Operand(True, tuple(shape) if rows else None)
        return Operand(False, getattr(env.get(n), "shape", None)
                       if env.has(n) else None)

    def may_move(i, op):
        rule = get_rowwise_fn(op.type)
        # (a block built under pipeline_stage is lowered by its stage)
        if rule is None or "pipeline_stage" in op.attrs:
            return False
        # a name bound twice holds the order its readers and writers have
        if any(n in per_step or n not in block.vars or len(writers[n]) > 1
               for n in op.output_names):
            return False
        if any(n in per_step or len(writers[n]) > 1 or writers[n][0] >= i
               for n in op.input_names if n in writers):
            return False
        ins = {slot: [operand(n) for n in names]
               for slot, names in op.inputs.items() if names}
        return all(o.shape is not None for os in ins.values() for o in os) \
            and rule(op.attrs, ins)

    core = {i for i, op in enumerate(ops) if not may_move(i, op)}
    core.update(i for n in mem_update_names if n for i in writers.get(n, ()))
    todo = list(core)
    while todo:
        for n in _reads(program, ops[todo.pop()]):
            for i in writers.get(n, ()):
                if i not in core:
                    core.add(i)
                    todo.append(i)
    tail = set(range(len(ops))) - core
    moved = {n for i in tail for n in ops[i].output_names}
    frontier = list(dict.fromkeys(
        n for i in sorted(tail) for n in ops[i].input_names
        if (n in per_step or n in writers) and n not in moved))
    return tail, frontier


@jax.custom_vjp
def _cotangents_together(xs):
    """Identity on a tuple whose cotangents are handed on only once all of
    them exist (``lax.optimization_barrier``).  ``_rnn`` puts what its
    tail reads through it, so the tail's backward -- the wide products of
    an output layer and the sums for its bias -- is whole before the
    scan's backward starts.  Left free, XLA computes only the gradient the
    scan needs first and the weight's after the loop, and the loop's
    accumulators then share fast memory with the layer's weight and lose
    (seq2seq: the decoder's backward scan 2.3 ms slower, PERF.md PR 29)."""
    return xs


_cotangents_together.defvjp(
    lambda xs: (xs, None),
    lambda _, cts: (lax.optimization_barrier(cts),))


@register_op("rnn")
def _rnn(ctx, ins, attrs):
    """StaticRNN/DynamicRNN lowering: the recurrence of the step sub-block
    under lax.scan, the rest of it once on all steps.

    The reference RecurrentOp runs the sub-block once per step with a nested
    Executor and StepScopes (recurrent_op.cc:222-335); here the step block is
    traced ONCE — XLA pipelines the loop and the recurrence is
    differentiable (the reference needed a hand-written RecurrentGradOp).
    Finished sequences freeze their memories via the length mask.

    Only what the memories depend on has to run step after step.  The ops
    of the step block that merely turn a step's values into its outputs (an
    output layer over the dictionary, say) are split off
    (``_split_step_block``): the scan stacks the few values they read, and
    they run once after it with time folded into the rows, [B*T, ...], so
    nothing as wide as an output is stacked, sliced or re-laid-out per
    step.  A block with no such ops lowers whole, as it always did.
    """
    sub_idx = attrs["sub_block"]
    step_in_names = attrs["step_inputs"]          # sub-block per-step vars
    mem_names = attrs["mem_step_names"]           # sub-block memory vars
    mem_update_names = attrs["mem_update_names"]  # vars holding new memory
    out_step_names = attrs["step_output_names"]
    seqs = ins.get("Inputs", [])                  # [B,T,...] each
    inits = ins.get("InitStates", [])
    env = ctx.env

    T = seqs[0].shape[1]
    B = seqs[0].shape[0]
    seq_parent_names = ctx.op.inputs.get("Inputs", [])
    lens = None
    for nm in seq_parent_names:
        lens = ctx.get_len(nm)
        if lens is not None:
            break
    if lens is None:
        lens = jnp.full((B,), T, jnp.int32)
    step_mask = (jnp.arange(T)[None, :] < lens[:, None]).astype(
        seqs[0].dtype).T                          # [T, B]
    xs = [jnp.swapaxes(s, 0, 1) for s in seqs]    # time-major
    # NESTED sequences: an input [B, S, T', ...] with an @LEN2 companion
    # [B, S] is a sequence OF sequences — each outer step's slice is itself
    # a padded sequence, so the inner lengths scan along and land in the
    # step env as the slice's @LEN (the LoD level-2 analog)
    nested_names = []
    nested_l2 = []                                       # [B, S] each
    nested_scan = []
    for step_nm, parent_nm in zip(step_in_names, seq_parent_names):
        l2 = ctx.get_len2(parent_nm)
        if l2 is not None:
            nested_names.append(step_nm)
            nested_l2.append(l2)
            nested_scan.append(jnp.swapaxes(l2, 0, 1))   # [S, B]

    block = ctx.block(sub_idx)
    per_step = {nm: (B,) + s.shape[2:] for nm, s in zip(step_in_names, seqs)}
    per_step.update((nm, v.shape) for nm, v in zip(mem_names, inits))
    tail, frontier = set(), []
    if not nested_names:          # step-local @LEN companions: left whole
        tail, frontier = _split_step_block(ctx.program, block, B, per_step,
                                           mem_update_names, env)
    moved = {n for i in tail for n in block.ops[i].output_names}
    # a step input is folded from its sequence; the rest the scan stacks
    stacked = [n for n in frontier if n not in step_in_names]
    hoist = []                    # whether the tail moved: ``step`` says

    def run(part, benv):
        """The ops of the block that are (``part``) or are not in the
        tail, each under the PRNG number it has in the whole block
        (``ctx.rng`` folds in ``_op_uid``, which counts ``run_op`` calls).
        Only the core's pass may advance the count: no tail op draws."""
        uid = ctx._op_uid
        for i, op in enumerate(block.ops):
            if (i in tail) == part:
                run_op(op, benv, ctx)
            else:
                ctx._op_uid += 1
        if part:
            ctx._op_uid = uid

    def scanned():
        return [nm for nm in out_step_names
                if not (hoist[0] and nm in moved)]

    def rows_as_declared(nm, v):
        return nm in per_step or (
            v.ndim == len(block.vars[nm].shape) and v.shape[0] == B)

    def masked(v, mask):
        return v * mask.reshape(mask.shape + (1,) * (v.ndim - mask.ndim))

    def step(carry, inp):
        mems = carry
        m_t = inp[0]
        n_seq = len(step_in_names)
        slices = inp[1:1 + n_seq]
        l2_slices = inp[1 + n_seq:]
        benv = ctx.child_env(sub_idx, env)
        for nm, v in zip(step_in_names, slices):
            benv.local[nm] = v
        for nm, l2 in zip(nested_names, l2_slices):
            benv.local[nm + "@LEN"] = l2
        for nm, v in zip(mem_names, mems):
            benv.local[nm] = v
        if not tail:
            ctx.interpret_block(sub_idx, benv)
        else:
            run(False, benv)
        # the rules answered for the shapes the vars declare: where a
        # lowering gave another, the tail runs in here after all
        hoist[:] = [bool(tail) and all(rows_as_declared(n, benv.get(n))
                                       for n in stacked)]
        if tail and not hoist[0]:
            run(True, benv)
        new_mems = tuple(
            jnp.where(m_t.reshape((B,) + (1,) * (old.ndim - 1)) > 0,
                      benv.get(un), old) if un else old
            for un, old in zip(mem_update_names, mems))
        return new_mems, ([masked(benv.get(nm), m_t) for nm in scanned()],
                          [benv.get(nm) for nm in stacked] if hoist[0]
                          else [])

    init_mems = tuple(inits)
    _, (outs, stacks) = lax.scan(step, init_mems,
                                 tuple([step_mask] + xs + nested_scan))
    outs = {nm: jnp.swapaxes(o, 0, 1) for nm, o in zip(scanned(), outs)}
    if hoist[0]:
        # the tail, once, with time folded into the rows: [T,B,...] values
        # go in as [B*T,...], and what comes out is [B,T,...] as it stands
        tenv = ctx.child_env(sub_idx, env)
        for nm, v in zip(stacked, stacks):
            v = jnp.swapaxes(v, 0, 1)
            tenv.local[nm] = v.reshape((B * T,) + v.shape[2:])
        for nm, s in zip(step_in_names, seqs):
            if nm in frontier:
                tenv.local[nm] = s.reshape((B * T,) + s.shape[2:])
        # what the tail reads and differentiates: stacked values, weights
        reads = dict.fromkeys(n for i in sorted(tail)
                              for n in block.ops[i].input_names
                              if n not in moved)
        reads = [n for n in reads if jnp.issubdtype(
            jnp.result_type(tenv.get(n)), jnp.floating)]
        tenv.local.update(zip(reads, _cotangents_together(
            tuple(tenv.get(n) for n in reads))))
        run(True, tenv)
        for nm, out_nm in zip(out_step_names,
                              ctx.op.outputs.get("Outputs", [])):
            if nm in moved:
                v = tenv.get(nm)
                outs[nm] = masked(v.reshape((B, T) + v.shape[1:]),
                                  step_mask.T)
                note = tenv.softmax_note(nm, v)
                if note is not None and note[1] is None:
                    # Env.softmax_of: the output is that softmax of all
                    # steps' logits, times the length mask
                    env.note_softmax(
                        out_nm, outs[nm],
                        note[0].reshape((B, T) + note[0].shape[1:]),
                        step_mask.T.reshape((B, T) + (1,) * (v.ndim - 1)))
    results = [outs[nm] for nm in out_step_names]
    n_hoisted = len(tail) if hoist[0] else 0
    compile_cache.stats().bump("rnn_ops_hoisted", n_hoisted)
    compile_cache.stats().bump("rnn_ops_in_scan",
                               len(block.ops) - n_hoisted)
    sub_vars = block.vars
    for nm, step_nm in zip(ctx.op.outputs.get("Outputs", []),
                           out_step_names):
        ctx.set_len(nm, lens)
        # a stacked output is a sequence OF sequences only when the step
        # output was itself a sequence (e.g. the inner group's output);
        # per-step vectors stack to [B, S, H] and must NOT carry @LEN2
        sv = sub_vars.get(step_nm)
        if nested_l2 and sv is not None and sv.lod_level >= 1:
            ctx.set_len2(nm, nested_l2[0])
    return {"Outputs": results}


@register_op("repeat")
def _repeat(ctx, ins, attrs):
    """``layers.Repeat``: the sub-block ``times`` times over the carried
    values, every pass reading the same weights from the enclosing env (so
    their gradients sum); what a pass leaves comes out stacked [times, ...].
    attrs: sub_block, times, carry_names (sub-block vars bound to the
    carried values), update_names (what each becomes), output_names.

    The passes are INLINED: ``times`` traces of the one sub-block.  A
    ``lax.scan`` over the passes was measured beside it on the chip at the
    benchmark's looped decoder (PERF.md section 6, PR 32): 8 % faster and a
    third of the compile, but 2.9 GB more at the peak (the backward scan
    carries every weight's gradient accumulator, and XLA keeps bfloat16
    copies of the loop's weights), which put the one-sequence step over
    the 90 % of the chip that a cell may take."""
    sub_idx, times = attrs["sub_block"], int(attrs["times"])
    carry_names, update_names = attrs["carry_names"], attrs["update_names"]
    env = ctx.env

    def one_pass(carried):
        benv = ctx.child_env(sub_idx, env)
        benv.local.update(zip(carry_names, carried))
        ctx.interpret_block(sub_idx, benv)
        return (tuple(benv.get(n) for n in update_names),
                [benv.get(n) for n in attrs["output_names"]])

    carried, left = tuple(ins.get("Init", [])), []
    for _ in range(times):
        carried, outs = one_pass(carried)
        left.append(outs)
    compile_cache.stats().bump("route/repeat:inlined")
    compile_cache.stats().bump("repeat_passes", times)
    return {"Outputs": [jnp.stack(vals) for vals in zip(*left)]}


@register_op("recompute")
def _recompute(ctx, ins, attrs):
    """``layers.recompute``: the stretch of ops in the sub-block under
    ``jax.checkpoint``, so that a backward pass keeps what the stretch READ
    (X, and the length companions of those) and computes the rest again.
    The stretch runs in an env of its own with no parent: everything it
    reads is an argument of the checkpointed function, and everything it
    binds (Out, and companions its ops set) is a result."""
    env = ctx.env
    read = [n + suffix for n in ctx.op.inputs.get("X", [])
            for suffix in ("", LEN_SUFFIX, LEN2_SUFFIX) if env.has(n + suffix)]

    def stretch(values):
        benv = Env(ctx.block(attrs["sub_block"]))
        benv.local.update(values)
        ctx.interpret_block(attrs["sub_block"], benv)
        return {n: v for n, v in benv.local.items()
                if values.get(n) is not v}

    bound = jax.checkpoint(stretch)({n: env.get(n) for n in read})
    compile_cache.stats().bump("route/recompute:checkpoint")
    out_names = ctx.op.outputs.get("Out", [])
    env.local.update((n, v) for n, v in bound.items() if n not in out_names)
    return {"Out": [bound.get(n) for n in out_names]}


@register_op("print")
def _print(ctx, ins, attrs):
    x = ins.get("In", ins.get("X", [None]))[0]
    msg = attrs.get("message", "")
    jax.debug.print(msg + " {x}", x=x)
    return {"Out": x} if ctx.op.outputs.get("Out") else {}


@register_op("assert")
def _assert(ctx, ins, attrs):
    return {}


# ---------------------------------------------------------------------------
# Static shape/dtype rules (analysis.shape_infer).  The structured control
# flow ops (while/conditional_block/rnn) are allowlisted — their outputs are
# whatever the sub-block binds — but the tensor-array plumbing around them
# is statically knowable.
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import (VarInfo, first, no_outputs,  # noqa: E402
                                    passthrough, same_as)
from ..core.registry import register_shape_fn  # noqa: E402

register_shape_fn("shrink_rnn_memory", "rnn_memory_helper")(same_as("X"))
register_shape_fn("split_lod_tensor")(
    same_as("X", out="OutTrue", also=("OutFalse",)))
register_shape_fn("merge_lod_tensor")(same_as("InTrue"))
register_shape_fn("reorder_lod_tensor_by_rank")(same_as("X"))
register_shape_fn("print")(passthrough("In", "X"))
register_shape_fn("assert")(no_outputs())


@register_shape_fn("read_from_array")
def _read_from_array_shape(op, ins, attrs):
    buf = first(ins, "X")
    if buf.shape is None:
        return {"Out": buf}
    return {"Out": buf.with_shape(buf.shape[1:])}


@register_shape_fn("lod_array_length")
def _lod_array_length_shape(op, ins, attrs):
    return {"Out": VarInfo((), "int64")}


@register_shape_fn("lod_tensor_to_array", "array_to_lod_tensor")
def _swap01_shape(op, ins, attrs):
    x = first(ins, "X")
    if x.shape is None or len(x.shape) < 2:
        return {"Out": VarInfo(None, x.dtype)}
    return {"Out": x.with_shape((x.shape[1], x.shape[0]) + x.shape[2:])}


@register_shape_fn("lod_rank_table")
def _lod_rank_table_shape(op, ins, attrs):
    x = first(ins, "X")
    b = x.shape[0] if x.shape is not None else -1
    return {"Out": VarInfo((b,), "int32")}


# ---------------------------------------------------------------------------
# Sharding-propagation rules (analysis.shard_prop): memory helpers are
# shape-preserving; print/assert are transparent; the tensor-array and
# lod-rank machinery is data-dependent (deliberately unregistered — a
# sharded value reaching it is a real planner blind spot worth a PT042).
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import shard_noop, shard_same_as  # noqa: E402
from ..core.registry import register_shard_fn  # noqa: E402

register_shard_fn("shrink_rnn_memory", "rnn_memory_helper")(
    shard_same_as("X"))
register_shard_fn("print", "assert")(shard_noop())
