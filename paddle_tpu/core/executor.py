"""Executor: lowers a Program into one jitted XLA computation and runs it.

The reference Executor walks OpDescs one C++ kernel at a time
(paddle/framework/executor.cc:73-129, operator.cc:405-475).  The TPU-native
redesign instead *traces* the whole block through the registered JAX lowerings
into a single ``jax.jit`` function per (program-version, feed-signature):

    run(program, feed, fetch_list)
        └── compiled fn: (feeds, persistable-state, step) -> (fetches, state')

* Persistable vars (parameters, optimizer moments, evaluator states) live in a
  ``Scope`` between steps and are threaded functionally with buffer donation —
  the analog of the reference Scope (scope.h:38) without mutation-under-jit.
* A program containing a ``backward`` op (inserted by ``append_backward``) is
  split at that op: the forward slice is interpreted inside
  ``jax.value_and_grad`` so each forward op runs exactly once and every
  gradient ``X@GRAD`` var is produced by XLA's reverse-mode pass — replacing
  the reference's per-op GradOpDescMakers (framework/backward.cc:353-415).
* Random ops derive keys from (program seed, op position, step counter) so
  dropout masks differ per step but runs are reproducible — the analog of the
  reference's per-op seed attrs.
* ``check_nan_inf`` mirrors FLAGS_check_nan_inf (executor.cc:25-27,116-124)
  using post-run host checks on fetches/state (debug aid; off by default).
"""
from __future__ import annotations

import time
import warnings
import weakref
from collections import namedtuple
from contextlib import contextmanager, nullcontext
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import compile_cache
from .. import faults as _faults
from .. import flags as _flags
from .. import observability as obs
from ..testing import faultinject as _fi
from .program import (Block, Operator, Program, Variable,
                      default_main_program, grad_var_name)
from .registry import get_op_impl, register_tunable, resolve_tuned
from .scope import Scope, global_scope

# ---------------------------------------------------------------------------
# Autotuner knob declarations (paddle_tpu.tuning) — declared HERE, next to
# the implementations they control; nothing imports the tuning package
# until an autotune opt-in actually replays a winner.
# ---------------------------------------------------------------------------
register_tunable(
    "executor/run_pipelined",
    space={"steps_per_dispatch": (1, 2, 4, 8, 16),
           "prefetch_depth": (1, 2, 4)},
    default={"steps_per_dispatch": 4, "prefetch_depth": 2},
    description="run_pipelined dispatch chunking: steps stacked per "
                "compiled K-step scan, and staged dispatches in flight. "
                "Larger K amortizes host dispatch overhead; deeper "
                "prefetch hides staging — both trade memory and tail "
                "latency, and the right point is workload- and "
                "host-dependent.")

# ---------------------------------------------------------------------------
# Places — the analog of platform::Place (place.h:25-63).  A Place is a
# LABEL kept for API parity with reference scripts: it selects nothing.
# Placement is owned by JAX (the default backend, or the mesh a
# ShardedExecutor is given); to pin a run to a platform set JAX_PLATFORMS.
# ---------------------------------------------------------------------------
class Place:
    def __repr__(self):
        return f"{type(self).__name__}()"


class CPUPlace(Place):
    pass


class TPUPlace(Place):
    pass


# CUDAPlace alias for scripts written against the reference API surface.
CUDAPlace = TPUPlace


# ---------------------------------------------------------------------------
# Environment: per-block name -> traced value, with parent lookup
# (the trace-time analog of Scope::FindVar's parent chain, scope.h:58).
# ---------------------------------------------------------------------------
class Env:
    def __init__(self, block: Block, parent: Optional["Env"] = None):
        self.block = block
        self.parent = parent
        self.local: Dict[str, object] = {}
        self.softmax_of: Dict[str, tuple] = {}
        """The note beside a value: ``name -> (value, logits, row_scale)``
        says "the variable ``name``, while it holds ``value``, is
        ``softmax(logits, axis=-1) * row_scale``" (``row_scale`` None for
        1; else it broadcasts against ``logits`` with a last axis of 1).

        * WRITTEN by the ``softmax`` lowering along the last axis
          (``ops/nn_ops.py``), through :meth:`note_softmax`.
        * CARRIED by ``reshape`` when the last axis stays
          (``ops/tensor_ops.py``) and by ``rnn`` for a step output its tail
          computed after the scan (``ops/control_flow_ops.py``: the length
          mask it multiplies by becomes ``row_scale``).  Any other op drops
          the note by not knowing it.
        * READ by ``cross_entropy`` with hard labels, which then computes
          the loss from the logits (``ops/nn_ops.py _nll_from_logits``) and
          never reads the probabilities.

        It lives on the ``Env`` level the value is written to, not on the
        ``LoweringContext`` and not in ``local``: the logits are tracers
        of the trace that level belongs to (a scan body's, a
        ``value_and_grad``'s), the level dies with that trace, and nothing
        that copies ``local`` out of a trace (``snapshot``, the backward's
        aux) takes a note along.  A note whose ``value`` is no longer what
        the name holds is stale and reads as absent."""

    def get(self, name: str):
        e: Optional[Env] = self
        while e is not None:
            if name in e.local:
                return e.local[name]
            e = e.parent
        raise KeyError(f"variable {name!r} has no value; is it fed, "
                       f"initialized by the startup program, or produced by "
                       f"an earlier op?")

    def has(self, name: str) -> bool:
        e: Optional[Env] = self
        while e is not None:
            if name in e.local:
                return True
            e = e.parent
        return False

    def _level_written(self, name: str) -> "Env":
        # The nearest env level that either already BINDS the name
        # (loop-carry bindings made by while/rnn lowerings must capture body
        # writes locally, not leak into the parent trace) or DECLARES it
        # (fluid write-through semantics for sub-blocks).
        e: Optional[Env] = self
        while e is not None:
            if name in e.local or name in e.block.vars:
                return e
            e = e.parent
        return self

    def set(self, name: str, value):
        self._level_written(name).local[name] = value

    def note_softmax(self, name: str, value, logits, row_scale=None):
        """Leave the note of ``softmax_of`` for ``value``, which the caller
        is about to bind to ``name`` from this level."""
        self._level_written(name).softmax_of[name] = (value, logits,
                                                      row_scale)

    def softmax_note(self, name: str, value):
        """``(logits, row_scale)`` if ``name`` holds ``value`` and a note
        says what softmax that is, else None."""
        e: Optional[Env] = self
        while e is not None:
            if name in e.local:
                note = e.softmax_of.get(name)
                if note is not None and note[0] is value:
                    return note[1], note[2]
                return None
            e = e.parent
        return None

    def snapshot(self) -> Dict[str, object]:
        out: Dict[str, object] = {}
        e: Optional[Env] = self
        chain = []
        while e is not None:
            chain.append(e)
            e = e.parent
        for e in reversed(chain):
            out.update(e.local)
        return out


# ---------------------------------------------------------------------------
# Lowering context passed to op implementations
# ---------------------------------------------------------------------------
class LoweringContext:
    def __init__(self, program: Program, base_key, is_test: bool = False,
                 amp: bool = False, mesh=None,
                 pipeline_microbatches: Optional[int] = None,
                 compute_dtype=None):
        self.program = program
        self.base_key = base_key      # traced PRNG key folding in the step
        self.is_test = is_test
        self.amp = amp
        # precision-instrument mode: run_op upcasts floating op outputs so
        # in-graph f32 constants (fill_constant, zeros inits) do not leak
        # f32 back into an otherwise-f64 step (job_checkgrad)
        self.compute_dtype = compute_dtype
        # mesh set by ShardedExecutor: op lowerings may consult it to place
        # sharding constraints (moe) or lower staged regions (pipeline)
        self.mesh = mesh
        self.pipeline_microbatches = pipeline_microbatches
        self.op: Optional[Operator] = None
        self.env: Optional[Env] = None
        self._op_uid = 0
        self._op_pos: Dict[int, int] = {}     # id(op) -> position in block

    def op_scope(self, op: Operator):
        """``jax.named_scope("pt.<op_type>:<block>.<position>")`` around
        one op's lowering: the name rides into the HLO ``op_name`` of every
        instruction the op emits (JAX adds ``jvp(...)`` /
        ``transpose(jvp(...))`` for the direction), so a device trace can
        be read in the Program's terms; ``<block>.<position>`` joins back
        to ``program.blocks[block].ops[position]``."""
        pos = self._op_pos.get(id(op))
        if pos is None:          # once per block per trace
            for i, o in enumerate(op.block.ops):
                self._op_pos[id(o)] = i
            pos = self._op_pos.get(id(op), "x")   # an op outside its block
        return jax.named_scope(f"pt.{op.type}:{op.block.idx}.{pos}")

    @property
    def pp_size(self) -> int:
        return self.mesh_axis_size("pp")

    def mesh_axis_size(self, axis: str) -> int:
        if self.mesh is None or axis not in self.mesh.axis_names:
            return 1
        return self.mesh.shape[axis]

    def rng(self, offset: int = 0):
        """Per-op-instance PRNG key: stable across steps in structure, varied
        by the step counter folded into base_key by the executor."""
        seed = int(self.op.attrs.get("seed", 0) or 0) if self.op else 0
        k = jax.random.fold_in(self.base_key, self._op_uid)
        if seed:
            k = jax.random.fold_in(k, seed)
        if offset:
            k = jax.random.fold_in(k, offset)
        return k

    def block(self, idx: int) -> Block:
        return self.program.blocks[idx]

    def interpret_block(self, block_idx: int, env: Env):
        interpret_ops(self.program.blocks[block_idx].ops, env, self)

    def child_env(self, block_idx: int, parent_env: Env) -> Env:
        return Env(self.program.blocks[block_idx], parent=parent_env)

    def get_len(self, name: str):
        """Sequence-length companion of a lod_level>0 var, or None."""
        ln = name + "@LEN"
        return self.env.get(ln) if self.env.has(ln) else None

    def set_len(self, name: str, lens):
        """Emit the sequence-length companion for an output var."""
        self.env.local[name + "@LEN"] = lens

    def get_len2(self, name: str):
        """Inner-sequence lengths [B, S] of a lod_level-2 var, or None
        (nested sequences: [B, S, T, ...] padded, the LoD level-2 analog)."""
        ln = name + "@LEN2"
        return self.env.get(ln) if self.env.has(ln) else None

    def set_len2(self, name: str, lens2):
        self.env.local[name + "@LEN2"] = lens2


# ---------------------------------------------------------------------------
# Interpreter
# ---------------------------------------------------------------------------
# jax.named_scope names for work the executor itself emits outside any op
# (ops get "pt.<op_type>:<block>.<position>" from LoweringContext.op_scope).
# Unconditional, like the op scopes: JAX's persistent-cache key ignores
# metadata, so an executable compiled without them would be served to a
# traced run of the same program.
AMP_SCOPE = "pt.amp_cast"        # bf16 casts of state/feeds, f32 of grads
DTYPE_SCOPE = "pt.dtype_cast"    # compute_dtype (precision-instrument) casts
RNG_SCOPE = "pt.rng"             # the step's PRNG key
SCAN_SCOPE = "pt.scan"           # run_steps: the K-step lax.scan's plumbing
NAN_SCOPE = "pt.nan_check"       # check_nan_inf's per-var finite flags


def _normalize_outputs(op: Operator, result) -> Dict[str, List]:
    if result is None:
        return {}
    if not isinstance(result, dict):
        # single unnamed output: bind to the single output slot
        slots = [s for s, ns in op.outputs.items() if ns]
        if len(slots) != 1:
            raise ValueError(f"op {op.type}: ambiguous single-value return")
        result = {slots[0]: result}
    norm: Dict[str, List] = {}
    for slot, val in result.items():
        norm[slot] = val if isinstance(val, list) else [val]
    return norm


def run_op(op: Operator, env: Env, ctx: LoweringContext):
    impl = get_op_impl(op.type)
    ins = {slot: [env.get(n) for n in names]
           for slot, names in op.inputs.items() if names}
    prev_op, prev_env = ctx.op, ctx.env
    ctx.op, ctx.env = op, env
    ctx._op_uid += 1
    try:
        with ctx.op_scope(op):
            result = impl(ctx, ins, op.attrs)
    except Exception as e:
        # PADDLE_ENFORCE-style context (enforce.h): name the op and its
        # operand shapes so a trace-time shape error points at the graph
        # site, not just the jnp call inside the lowering
        shapes = {slot: [getattr(v, "shape", None) for v in vals]
                  for slot, vals in ins.items()}
        note = (f"[paddle_tpu] while lowering op {op.type!r} "
                f"(outputs {op.outputs}) with input shapes {shapes}")
        e.add_note(note)        # PEP 678 (jax itself needs python 3.11)
        raise
    finally:
        ctx.op, ctx.env = prev_op, prev_env
    outs = _normalize_outputs(op, result)
    for slot, names in op.outputs.items():
        if not names:
            continue
        vals = outs.get(slot)
        if vals is None:
            continue
        if len(vals) != len(names):
            raise ValueError(
                f"op {op.type} slot {slot}: produced {len(vals)} values for "
                f"{len(names)} outputs {names}")
        for n, v in zip(names, vals):
            if v is not None:
                if ctx.compute_dtype is not None and hasattr(v, "dtype") \
                        and jnp.issubdtype(v.dtype, jnp.floating) \
                        and v.dtype != jnp.dtype(ctx.compute_dtype):
                    with jax.named_scope(DTYPE_SCOPE):
                        v = v.astype(ctx.compute_dtype)
                env.set(n, v)


def interpret_ops(ops: Sequence[Operator], env: Env, ctx: LoweringContext):
    if ctx.pp_size > 1 and any("pipeline_stage" in op.attrs for op in ops):
        _interpret_ops_pipelined(ops, env, ctx)
        return
    for op in ops:
        run_op(op, env, ctx)


def _interpret_ops_pipelined(ops: Sequence[Operator], env: Env,
                             ctx: LoweringContext):
    """Interpret a block whose ops carry ``pipeline_stage`` attrs: the
    contiguous staged region lowers as a GPipe shard_map over the 'pp' mesh
    axis; everything around it interprets normally (GSPMD-sharded)."""
    from ..parallel.pipeline_program import lower_pipeline_region
    i = 0
    while i < len(ops):
        if "pipeline_stage" in ops[i].attrs:
            j = i
            while j < len(ops) and "pipeline_stage" in ops[j].attrs:
                j += 1
            lower_pipeline_region(ops[i:j], env, ctx)
            i = j
        else:
            run_op(ops[i], env, ctx)
            i += 1


def interpret_block_with_backward(block: Block, env: Env, ctx: LoweringContext):
    """Interpret a block, splitting at a top-level ``backward`` op so the
    forward slice runs exactly once inside jax.value_and_grad."""
    bw_idx = next((i for i, op in enumerate(block.ops)
                   if op.type == "backward"), None)
    if bw_idx is None:
        interpret_ops(block.ops, env, ctx)
        return
    pre, bw_op, post = block.ops[:bw_idx], block.ops[bw_idx], block.ops[bw_idx + 1:]
    _run_backward(pre, bw_op, env, ctx)
    interpret_ops(post, env, ctx)


def _run_backward(forward_ops: Sequence[Operator], bw_op: Operator,
                  env: Env, ctx: LoweringContext):
    """Lower the ``backward`` pseudo-op inserted by append_backward.

    attrs: loss (var name), params (list of var names to differentiate).
    Produces ``<p>@GRAD`` for every p in params and materializes every forward
    var in ``env`` from the primal pass (so later fetches/ops see them).
    """
    loss_name = bw_op.attrs["loss"]
    wrt_names = list(bw_op.attrs["params"])
    init = env.snapshot()
    wrt_vals = {n: init[n] for n in wrt_names}
    block = env.block
    amp = ctx.amp

    def f(wrt):
        fenv = Env(block)
        if amp:
            # bf16 mixed precision: forward+backward compute in bf16
            # (activations AND the in-graph copies of the params), while the
            # wrt leaves stay fp32 so grads come back fp32 for the master-
            # weight optimizer update.  jax.grad differentiates through the
            # cast, so this is the canonical AMP recipe at zero extra cost.
            with jax.named_scope(AMP_SCOPE):
                fenv.local.update({k: _to_bf16(v) for k, v in init.items()})
                fenv.local.update({k: _to_bf16(v) for k, v in wrt.items()})
        else:
            fenv.local.update(init)
            fenv.local.update(wrt)
        interpret_ops(forward_ops, fenv, ctx)
        loss = fenv.get(loss_name)
        if loss.ndim > 0:
            if loss.size != 1:
                raise ValueError(
                    f"append_backward loss {loss_name!r} must be a scalar, "
                    f"got shape {loss.shape}")
            loss = loss.reshape(())
        if amp:
            with jax.named_scope(AMP_SCOPE):
                loss = loss.astype(jnp.float32)
        return loss, fenv.local

    (loss_val, fwd_vals), grads = jax.value_and_grad(f, has_aux=True)(wrt_vals)
    for name, val in fwd_vals.items():
        env.set(name, val)
    # keep the master fp32 params visible downstream (optimizer ops read the
    # param name from env where the bf16 forward copy was materialized)
    if amp:
        for n, v in wrt_vals.items():
            env.set(n, v)
    env.set(loss_name, loss_val)
    for n in wrt_names:
        g = grads[n]
        if amp and g.dtype != wrt_vals[n].dtype:
            with jax.named_scope(AMP_SCOPE):
                g = g.astype(wrt_vals[n].dtype)
        env.set(grad_var_name(n), g)


def _to_bf16(v):
    if hasattr(v, "dtype") and v.dtype == jnp.float32:
        return v.astype(jnp.bfloat16)
    return v


# ---------------------------------------------------------------------------
# Feed staging helpers (host side of the input pipeline)
# ---------------------------------------------------------------------------
def stack_feeds(feeds: Sequence[Dict[str, object]]) -> Dict[str, np.ndarray]:
    """Stack K same-signature host feed dicts along a new leading axis —
    the form ``run_steps(feeds_stacked=True)`` accepts, turning K host
    batches into ONE device-side scan dispatch.

    Every dict must carry the same keys with same-shaped values; the
    result's entries have shape ``[K, ...]``.  ``np.stack`` copies, so
    feeds built in reusable staging buffers (``DataFeeder(staging_slots=
    ...)``) are safe to reuse once stacked.
    """
    if not feeds:
        raise ValueError("stack_feeds: need at least one feed dict")
    keys = feeds[0].keys()
    for f in feeds[1:]:
        if f.keys() != keys:
            raise ValueError(
                f"stack_feeds: feed keys differ: {sorted(keys)} vs "
                f"{sorted(f.keys())}")
    return {k: np.stack([np.asarray(f[k]) for f in feeds]) for k in keys}


def pad_batch(stacked: Dict[str, np.ndarray], to: int) -> Dict[str, np.ndarray]:
    """Pad every entry of a stacked feed dict (leading batch axis, the
    :func:`stack_feeds` output form) up to ``to`` rows by repeating the
    first row.

    The serving batcher uses this to round a coalesced batch up to its
    bucket size, bounding the number of compiled variants to the bucket
    list instead of one per observed batch size.  Repeating a REAL row
    (rather than zero-filling) keeps the pad rows inside the model's
    input distribution — index inputs stay valid vocab ids and float
    rows cannot manufacture NaN/Inf paths the live rows never take.
    Row-wise models (everything servable) make pad rows independent of
    live rows, which are sliced back out before delivery.
    """
    if to < 1:
        raise ValueError(f"pad_batch: target size must be >= 1, got {to}")
    out: Dict[str, np.ndarray] = {}
    for k, v in stacked.items():
        a = np.asarray(v)
        if a.ndim < 1:
            raise ValueError(
                f"pad_batch: entry {k!r} has no leading batch axis")
        n = a.shape[0]
        if n > to:
            raise ValueError(
                f"pad_batch: entry {k!r} already has {n} rows > target {to}")
        if n == to:
            out[k] = a
        else:
            pad = np.broadcast_to(a[:1], (to - n,) + a.shape[1:])
            out[k] = np.concatenate([a, pad], axis=0)
    return out


def _feed_signature(feed: Dict[str, object]):
    return tuple(sorted(
        (k, tuple(np.shape(v)),
         str(getattr(v, "dtype", None) or np.asarray(v).dtype))
        for k, v in feed.items()))


def _specs_sig(d):
    """Canonical hashable digest of a {name: spec/option} dict — shared by
    the cache fingerprints and the validation memo so the two can never
    disagree on how specs are keyed."""
    return tuple(sorted((k, repr(v)) for k, v in (d or {}).items()))


def _validation_ctx_key(mesh, param_specs, feed_specs):
    """Hashable digest of the sharding-lint inputs, folded into the
    validation memo key — a ShardedExecutor whose mesh or spec overrides
    change after a successful validation must re-run PT030/PT031.
    Recomputed on every validated run by design: the spec dicts are
    mutable and mutation is exactly what the memo must detect."""
    if mesh is None and not param_specs and not feed_specs:
        return None
    if isinstance(mesh, dict):
        mesh_key = tuple(sorted(mesh.items()))
    elif mesh is not None and hasattr(mesh, "shape"):
        mesh_key = tuple(dict(mesh.shape).items())
    else:
        mesh_key = repr(mesh)
    return (mesh_key, _specs_sig(param_specs), _specs_sig(feed_specs))


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------
# bound on per-program (scope, keys_version) -> state-keys entries; dead
# scopes are swept on every cache miss (satellite of the compile-cache
# work: these used to accumulate for the life of the program)
_STATE_KEYS_CACHE_MAX = 32


def _option(value, flag: str) -> bool:
    """An executor option left at None defers to the process flag."""
    return bool(_flags.get_flag(flag) if value is None else value)


# one resolved call (Executor._enter): what run, run_steps and compile need
# of it; `feeds` and `state` hold arrays, or ShapeDtypeStructs for compile
_StepEntry = namedtuple(
    "_StepEntry", "program scope fetch_names feeds state_keys state is_test "
                  "fp fn")


class Executor:
    """Compile-and-run a Program (reference: fluid/executor.py:56-119).

    ``run``, ``run_steps`` and ``compile`` resolve a call through one entry
    (``_enter``); the first two dispatch what it built inside one observed
    dispatch (``_observed``), and every step, compiled or replayed eagerly,
    starts from one prologue (``_step_prologue``).

    ``use_jit=False`` runs the interpreter eagerly op-by-op — the debugging
    analog of the reference's serial executor (and of jax.disable_jit).
    """

    def __init__(self, place: Optional[Place] = None, use_jit: bool = True,
                 check_nan_inf: bool = False, amp: bool = False,
                 compiler_options: Optional[Dict[str, object]] = None,
                 compute_dtype: Optional[str] = None,
                 validate: Optional[bool] = None,
                 observe: Optional[bool] = None,
                 retry_policy=None,
                 autotune: Optional[bool] = None):
        self.place = place or TPUPlace()
        self.use_jit = use_jit
        self.check_nan_inf = check_nan_inf
        self.amp = amp                # bf16 compute, fp32 master weights
        # precision-instrument mode (job_checkgrad): upcast every floating
        # feed/state to this dtype at step entry (e.g. "float64" under
        # jax.experimental.enable_x64 on CPU) so finite differences and
        # autodiff compare at double precision; persistable state keeps its
        # declared dtype across steps via the existing dtype-restore pass
        self.compute_dtype = compute_dtype
        # XLA backend knobs passed to Compiled (e.g. xla_tpu_scoped_vmem_
        # limit_kib); the FLAGS-registry analog of the reference's gflags
        # runtime switches, but scoped to one executor
        self.compiler_options = dict(compiler_options or {})
        # static program verification (paddle_tpu.analysis) before trace
        # AND before compile-cache fingerprinting, so an invalid program
        # never enters the cache; None defers to the `validate` flag
        # (PADDLE_TPU_VALIDATE=1).  Memoized per (program, version,
        # fetches) — zero cost in the stepped hot path.  Keyed by the
        # live Program object (weakly, so dead programs drop and an
        # id()-reused successor can never inherit a stale "validated").
        self.validate = validate
        self._validated: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        # runtime observability (paddle_tpu.observability): per-dispatch
        # step telemetry + XProf trace annotations.  None defers to the
        # `observe` flag (PADDLE_TPU_OBSERVE).  HOST-SIDE ONLY by
        # contract: never part of _config_sig/fingerprints, never inside
        # the traced fn — flipping it can neither retrace nor change math
        # (tier-1 asserts zero overhead and zero retraces when off).
        self.observe = observe
        # transient-error retry at the dispatch rim (paddle_tpu.faults.
        # RetryPolicy): retryable failures (RPC drops, transient runtime
        # errors, injected faults) re-dispatch with deterministic backoff;
        # fatal ones (OOM, shape errors, NaN trips) raise immediately.
        # HOST-SIDE ONLY like `observe`: never in fingerprints, and with
        # the default None (plus fault injection unset) the dispatch path
        # is byte-for-byte the old direct call — no new per-step work
        # (tier-1 counter-delta assertion).
        self.retry_policy = retry_policy
        # persisted-autotuner replay (paddle_tpu.tuning): tuned call
        # sites (run_pipelined chunking here) consult the winner store.
        # None defers to the `autotune` flag (PADDLE_TPU_AUTOTUNE=1).
        # Replay NEVER searches, and with no persisted record every site
        # resolves to its hand-picked default — byte-identical to
        # autotune off (tier-1 pins both).
        self.autotune = autotune
        # compiled step variants keyed by CONTENT fingerprint (survives
        # process restarts via the persistent layer; content-identical
        # programs share an entry), LRU-bounded with dead-program sweeping
        self._cache = compile_cache.ExecCache(
            int(_flags.get_flag("executor_cache_entries")))
        # what a ShardedExecutor sets: the mesh reaches op lowerings
        # through the LoweringContext (moe sharding constraints, pipeline
        # regions), with the GPipe microbatch count beside it
        self.mesh = None
        self.num_microbatches: Optional[int] = None
        self._step = 0

    def _validation_context(self):
        """(mesh, param_specs, feed_specs) for the sharding lints; the
        base executor has no mesh.  ShardedExecutor overrides."""
        return None, None, None

    def _maybe_validate(self, program: Program, fetch_names: Sequence[str]):
        """Run the static verifier once per (program, version, fetches).

        Called by run/run_steps/compile BEFORE the entry fingerprint is
        computed, so an invalid program is rejected before it can be
        installed in (or persisted to) the compilation cache.  Successful
        validations memoize; error reports re-raise on every call.
        """
        if not _option(self.validate, "validate"):
            return
        mesh, param_specs, feed_specs = self._validation_context()
        seen = self._validated.get(program)
        key = (program.version, tuple(fetch_names),
               _validation_ctx_key(mesh, param_specs, feed_specs))
        if seen is not None and key in seen:
            return
        from ..analysis import validate_program
        # an EMPTY fetch list (side-effect/warmup runs) means the targets
        # are unknown, not "nothing is live" — skip the dead-op lint
        report = validate_program(program,
                                  fetch_list=list(fetch_names) or None,
                                  mesh=mesh, param_specs=param_specs,
                                  feed_specs=feed_specs)
        report.raise_on_error()
        for d in report.warnings:
            warnings.warn(f"program verifier: {d.render()}", stacklevel=3)
        if seen is None:
            seen = self._validated.setdefault(program, set())
        else:
            # version bumps are monotonic, so stale-version keys can never
            # hit again — drop them, bounding the memo for long-lived
            # programs that are mutated and re-run under validation
            seen.difference_update(
                [k for k in seen if k[0] != program.version])
        seen.add(key)

    # -- autotuner replay ----------------------------------------------------
    def _autotuning(self) -> bool:
        """Resolved autotune switch: per-executor override, else flag."""
        return _option(self.autotune, "autotune")

    def _tuned(self, name: str, default: Dict[str, object]):
        """Tunable config for a call site: the persisted winner under the
        autotune opt-in, else ``default`` UNCHANGED (the same object).
        The tuning package loads lazily and only on the opted-in path."""
        return resolve_tuned(name, default, self.autotune)

    # -- observability -------------------------------------------------------
    def _observing(self) -> bool:
        """Resolved observe switch: per-executor override, else flag."""
        return _option(self.observe, "observe")

    def _observe_label(self) -> str:
        """Extra context folded into trace annotations and step events
        (ShardedExecutor reports its mesh)."""
        return ""

    def _trace_name(self, path: str, fp: Optional[str]) -> str:
        """XProf annotation name: framework path + fingerprint prefix, so
        device trace spans are attributable to framework programs."""
        label = self._observe_label()
        base = f"pt:{path}:{(fp or '')[:12]}"
        return f"{base}:{label}" if label else base

    def _record_dispatch(self, path: str, fp: Optional[str], steps: int,
                         wall_s: float, fetch_block_s: float,
                         feed_arrays: Dict[str, object], stacked: bool,
                         compile_before: Optional[Dict[str, int]] = None,
                         span=None, drained: bool = True):
        """Registry writes + JSONL step event for one compiled dispatch.
        Only reached when _observing() — the off path never touches the
        registry (counter-delta tier-1 assertion).

        ``compile_before`` is the CompileStats counter snapshot taken
        before the dispatch: a trace during the call means this wall
        time is dominated by COMPILE, not compute —
        the dispatch is tagged cold and kept OUT of the step-time
        histogram and throughput gauge (compile cost already has its own
        telemetry in compile_stats()).

        ``drained`` False: no fetch was materialized on the host
        (``return_numpy=False``, or nothing fetched), so the wall time is
        the ENQUEUE, not the step — tagged ``drained: false`` and kept out
        of the same two metrics for the same reason."""
        # this dispatch is in the profiler's trace as pt:<path>:<fp12>:
        # keep its step readable for whoever reads that trace afterwards
        compile_cache.keep_observed(fp)
        cold = False
        if compile_before is not None:
            after = compile_cache.stats().snapshot()
            cold = after.get("traces", 0) > compile_before.get("traces", 0)
        wall_ms = wall_s * 1e3
        step_ms = wall_ms / max(steps, 1)
        obs.inc_counter("executor/steps", steps)
        obs.inc_counter("executor/dispatches")
        obs.observe_hist("executor/dispatch_steps", steps)
        obs.observe_hist("executor/fetch_block_ms", fetch_block_s * 1e3)
        feed_bytes = int(sum(getattr(a, "nbytes", 0)
                             for a in feed_arrays.values()))
        if feed_bytes:
            obs.inc_counter("executor/feed_bytes", float(feed_bytes))
        examples_per_s = None
        timed = drained and not cold
        if timed:
            obs.observe_hist("executor/step_time_ms", step_ms)
            lead = 1 if stacked else 0      # stacked feeds: [K, B, ...]
            for _, a in sorted(feed_arrays.items()):
                shp = np.shape(a)
                if len(shp) > lead:
                    if wall_s > 0:
                        examples_per_s = shp[lead] * steps / wall_s
                        obs.set_gauge("executor/examples_per_sec",
                                      examples_per_s)
                    break
        obs.sample_device_memory()
        obs.emit_event(
            "step", path=path, fingerprint=(fp or "")[:12], steps=steps,
            wall_ms=round(wall_ms, 3),
            step_ms=round(step_ms, 3) if timed else None,
            cold_compile=cold, drained=drained, feed_bytes=feed_bytes,
            fetch_block_ms=round(fetch_block_s * 1e3, 3),
            examples_per_sec=round(examples_per_s, 2)
            if examples_per_s else None,
            label=self._observe_label() or None,
            # join key into the span tree: the step event IS the
            # executor/step span's quantitative payload
            trace=span.trace_id if span is not None else None,
            span=span.span_id if span is not None else None)

    def _dispatch(self, fn, feed_arrays, state, step, path: str,
                  trace_span=None):
        """One compiled-step dispatch through the fault-tolerance rim.

        With no retry policy and fault injection off this is a direct
        call (the zero-overhead off path).  Otherwise: the
        ``executor.dispatch`` injection site fires inside the retried
        region, retryable failures back off per the policy (counting
        ``fault/retries`` + emitting JSONL fault events, and attaching a
        ``retry`` event to the dispatch span when tracing), and retrying
        is refused once any state buffer has been donated away by a
        failed attempt — re-running on deleted buffers would turn a
        transient hiccup into undefined behavior.
        """
        policy = self.retry_policy
        if policy is None and not _fi.ENABLED:
            return fn(feed_arrays, state, step)

        def attempt():
            if _fi.ENABLED:
                action = _fi.check("executor.dispatch")
                if action is not None:
                    _fi.raise_for(action, "executor.dispatch")
            return fn(feed_arrays, state, step)

        if policy is None:
            # injection active but no retry policy: fail loudly (the
            # chaos suite tests the unprotected path this way too)
            return attempt()

        def cls(e):
            kind = _faults.classify(e)
            if kind == "retryable" and any(
                    getattr(v, "is_deleted", lambda: False)()
                    for v in state.values()):
                return "fatal"
            return kind

        def on_retry(i, e, d):
            obs.inc_counter("fault/retries")
            obs.emit_event("fault", event="retry",
                           site="executor.dispatch", step=int(step),
                           attempt=i + 1, delay_s=round(d, 4),
                           error=f"{type(e).__name__}: {e}")
            if trace_span is not None:
                trace_span.event("retry", attempt=i + 1,
                                 delay_s=round(d, 4),
                                 error=f"{type(e).__name__}: {e}")

        return _faults.retry_call(attempt, policy,
                                  what=f"dispatch {path}",
                                  classify_fn=cls, on_retry=on_retry)

    def _nan_diagnose(self, program: Program, feed_arrays, state,
                      step: int, is_test: bool, err: FloatingPointError):
        """Augment a check_nan_inf failure with eager op-bisect provenance
        (observability.nanprov): one-shot re-run of the failing step under
        run_op, naming the first op/var that produced a non-finite value.
        ``state`` is the live pre-step state (check_nan_inf variants
        compile without donation on every jit path).  Always emits a
        structured 'nan' event when a metrics log is set."""
        from ..observability import nanprov
        diag = nanprov.bisect_step(self, program, feed_arrays, state,
                                   step, is_test)
        if self._observing():
            obs.inc_counter("executor/nan_events")
        obs.emit_event("nan", original=str(err), step=step, **(diag or {}))
        if diag is None:
            return err
        return FloatingPointError(
            f"{err}\n[paddle_tpu] NaN provenance (eager re-run of step "
            f"{step}): {nanprov.format_diagnosis(diag)}")

    # -- the one way into a compiled step -------------------------------------
    def _call_context(self, program: Optional[Program]):
        """What a whole run/run_steps/compile call runs under: nothing
        here, the mesh for a ShardedExecutor."""
        return nullcontext()

    def _coerce_feeds(self, program: Program, feed: Dict[str, object],
                      steps, abstract: bool) -> Dict[str, object]:
        """Feeds at the dtypes the Program declares.  ``abstract`` (compile)
        reads only shapes and dtypes — of example arrays, ``(shape, dtype)``
        tuples or ShapeDtypeStructs — and returns ShapeDtypeStructs."""
        gb = program.global_block()
        # device-resident arrays stay on device (no host round-trip)
        as_is = (jax.Array, jax.ShapeDtypeStruct) if abstract else jax.Array
        out: Dict[str, object] = {}
        for name, val in feed.items():
            if abstract and (isinstance(val, tuple) and len(val) == 2
                             and not hasattr(val, "dtype")
                             and isinstance(val[0], (tuple, list))):
                val = jax.ShapeDtypeStruct(tuple(int(d) for d in val[0]),
                                           np.dtype(val[1]))
            elif not isinstance(val, as_is):
                val = np.asarray(val)
            if steps is not None and steps[1] \
                    and tuple(val.shape[:1]) != (steps[0],):
                raise ValueError(
                    f"run_steps(feeds_stacked=True): feed {name!r} must "
                    f"have leading dim {steps[0]}, got {tuple(val.shape)}")
            dtype = val.dtype
            if gb.has_var(name):
                dtype = jax.dtypes.canonicalize_dtype(gb.var(name).dtype)
            if abstract:
                val = jax.ShapeDtypeStruct(tuple(val.shape), dtype)
            elif val.dtype != dtype:
                val = val.astype(dtype)
            out[name] = val
        if not self.use_jit:
            # eager interpreting: op lowerings expect jax arrays (.at etc.)
            out = {k: jnp.asarray(v) for k, v in out.items()}
        return out

    def _enter(self, program: Optional[Program], feed, fetch_list, scope,
               is_test: bool, steps=None, abstract: bool = False):
        """Resolve a call into what run, run_steps and compile all need (a
        :class:`_StepEntry`): defaults, coerced feeds, the state the step
        threads, the validated program's fingerprint, and the cached-or-
        built step — one step, or the K-step scan for ``steps =
        (num_steps, feeds_stacked)``.  ``abstract``: feeds and state as
        ShapeDtypeStructs.

        A call that finds no cached step writes ``step/enter`` into the
        phase log (``compile_cache.PHASE_NAMES``): everything from here to
        the built step.  The fingerprint that says whether the call is cold
        is known only after that work, so every call takes the one
        timestamp; a warm one writes nothing."""
        t_enter = time.perf_counter()
        program = program or default_main_program()
        scope = global_scope() if scope is None else scope
        fetch_names = [v.name if isinstance(v, Variable) else str(v)
                       for v in (fetch_list or [])]
        feeds = self._coerce_feeds(program, feed or {}, steps, abstract)
        state_keys = self._state_keys(program, scope)
        state = {k: scope.get(k) for k in state_keys}
        if abstract:
            state = {k: jax.ShapeDtypeStruct(
                np.shape(v), v.dtype if hasattr(v, "dtype")
                else np.asarray(v).dtype) for k, v in state.items()}
        self._maybe_validate(program, fetch_names)   # before fingerprinting
        fp = compile_cache.fingerprint_hex(self._entry_sig(
            program, feeds, fetch_names, state_keys, is_test, steps=steps))
        fn = self._cache.get(fp, program)
        if fn is None:
            if steps is None:
                fn = self._build(program, sorted(feeds), fetch_names,
                                 sorted(state_keys), is_test, fingerprint=fp)
            else:
                fn = self._build_steps(
                    program, self._make_multi(program, fetch_names, is_test,
                                              *steps),
                    steps[1], fingerprint=fp)
            self._cache.put(fp, fn, program)
            compile_cache.stats().record_phase(
                "step/enter", t_enter, time.perf_counter(), fp=fp,
                label=getattr(fn, "label", None),
                cause="compile" if abstract
                else "run" if steps is None else "run_steps")
        return _StepEntry(program, scope, fetch_names, feeds, state_keys,
                          state, is_test, fp, fn)

    @contextmanager
    def _observed(self, entry: "_StepEntry", path: str, annotation: str,
                  steps: int, stacked: bool, return_numpy: bool):
        """Everything around the dispatch of a built step, observed when
        observing: the ``executor/step`` root span, the profiler's
        ``annotation`` and ``pt:<path>:<fp12>`` around the with-body, then
        the new state written back to the scope, the NaN check
        (check_nan_inf; run_steps refuses it), the fetches converted under
        ``executor/fetch_block``, and the dispatch recorded.  Off: no span,
        no timer, no registry write.

        Yields ``call``: its ``step`` and ``span`` go into the with-body,
        which is the caller's ONE line ``call.out = self._dispatch(...)``;
        the caller then returns ``call.fetches``.  A context manager and
        not a method around ``_dispatch``: the first dispatch traces and
        lowers the step, and JAX's lowering time moves by seconds with the
        Python stack that stands above it (PERF.md section 6, PR 30), so
        ``run`` and ``run_steps`` call ``_dispatch`` themselves."""
        program, scope, fetch_names, feed_arrays, _, state, is_test, fp, \
            _ = entry
        obs_on = self._observing()
        t_start = time.perf_counter() if obs_on else 0.0
        c0 = compile_cache.stats().snapshot() if obs_on else None
        sp = obs.tracing.start_span("executor/step", path=path, steps=steps,
                                    fingerprint=fp[:12]) if obs_on else None
        call = SimpleNamespace(step=self._step, span=None)
        self._step += steps
        try:
            if obs_on:
                with jax.profiler.StepTraceAnnotation(annotation,
                                                      step_num=call.step), \
                        jax.profiler.TraceAnnotation(
                            self._trace_name(path, fp)), \
                        obs.tracing.span("executor/dispatch",
                                         parent=sp) as call.span:
                    yield call
            else:
                yield call
            fetches, new_state = call.out
            fetches = list(fetches)
            for k, v in new_state.items():
                scope.set(k, v)

            if self.check_nan_inf:
                try:
                    # the per-var finite flags ride behind the fetches
                    if fetches and isinstance(fetches[-1], dict):
                        self._nan_localize(program, fetches.pop())
                    self._nan_check(fetch_names, fetches)
                except FloatingPointError as e:
                    raise self._nan_diagnose(program, feed_arrays, state,
                                             call.step, is_test, e) from e

            t_fetch = time.perf_counter() if obs_on else 0.0
            if return_numpy:
                with (obs.tracing.span("executor/fetch_block", parent=sp)
                      if sp is not None else nullcontext()):
                    fetches = [np.asarray(f) if f is not None else None
                               for f in fetches]
        except BaseException as e:
            # a FAILED step is exactly what a trace must explain: end
            # the root with the typed status so its dispatch child (and
            # any retry events) are not an orphaned fragment
            if sp is not None:
                sp.end(status=type(e).__name__)
            raise
        if obs_on:
            now = time.perf_counter()
            sp.end()
            self._record_dispatch(path, fp, steps=steps,
                                  wall_s=now - t_start,
                                  fetch_block_s=now - t_fetch,
                                  feed_arrays=feed_arrays, stacked=stacked,
                                  compile_before=c0, span=sp,
                                  drained=return_numpy and any(
                                      f is not None for f in fetches))
        call.fetches = fetches

    # -- public ------------------------------------------------------------
    def run(self, program: Optional[Program] = None,
            feed: Optional[Dict[str, object]] = None,
            fetch_list: Optional[Sequence] = None,
            scope: Optional[Scope] = None,
            return_numpy: bool = True,
            is_test: bool = False):
        with self._call_context(program):
            e = self._enter(program, feed, fetch_list, scope, is_test)
            with self._observed(e, "run", "paddle_tpu/step", 1, False,
                                return_numpy) as call:
                call.out = self._dispatch(e.fn, e.feeds, e.state, call.step,
                                          "run", trace_span=call.span)
            return call.fetches

    def run_steps(self, num_steps: int,
                  program: Optional[Program] = None,
                  feed: Optional[Dict[str, object]] = None,
                  fetch_list: Optional[Sequence] = None,
                  scope: Optional[Scope] = None,
                  return_numpy: bool = True,
                  is_test: bool = False,
                  feeds_stacked: bool = False):
        """Run ``num_steps`` training steps as ONE compiled dispatch — a
        device-side ``lax.scan`` over the per-step function with donated
        state threading.

        TPU-native training-loop design: the per-step host dispatch (and
        any host↔device link latency) is paid once per CHUNK instead of
        once per step, which is the difference between wire-latency-bound
        and device-bound throughput for small models (see
        benchmark/RESULTS.md methodology).  The reference's closest analog
        is the trainer's inner batch loop (trainer/Trainer.cpp), which is
        host-driven per batch; here the loop itself is compiled.

        ``feeds_stacked=False`` reuses ``feed`` for every step (timing
        windows, synthetic data).  ``feeds_stacked=True`` expects every
        feed to carry a leading ``num_steps`` axis — a device-resident
        input pipeline: stage K batches, dispatch once.

        Fetches come back stacked with a leading ``num_steps`` axis.
        """
        if self.check_nan_inf:
            raise ValueError(
                "run_steps: check_nan_inf needs per-step host inspection; "
                "use run() for NaN hunts")
        with self._call_context(program):
            e = self._enter(program, feed, fetch_list, scope, is_test,
                            steps=(num_steps, feeds_stacked))
            with self._observed(e, "run_steps", "paddle_tpu/dispatch",
                                num_steps, feeds_stacked,
                                return_numpy) as call:
                call.out = self._dispatch(e.fn, e.feeds, e.state, call.step,
                                          "run_steps", trace_span=call.span)
            return call.fetches

    def run_pipelined(self, feed_iter,
                      program: Optional[Program] = None,
                      fetch_list: Optional[Sequence] = None,
                      scope: Optional[Scope] = None,
                      steps_per_dispatch: Optional[int] = None,
                      prefetch_depth: Optional[int] = None,
                      return_numpy: bool = True,
                      is_test: bool = False):
        """Pipelined driver: generator over per-step fetch lists for a
        stream of host feed dicts, with host batch assembly and
        ``jax.device_put`` staging overlapped with device compute.

        ``feed_iter`` yields host feed dicts (e.g. ``DataFeeder.feed``
        output).  ``steps_per_dispatch``/``prefetch_depth`` default to
        the hand-picked (4, 2) — or, under the autotune opt-in
        (``Executor(autotune=...)`` / the ``autotune`` flag), to the
        persisted ``executor/run_pipelined`` winner for this host +
        topology; an explicit argument always wins.  A staging worker
        thread groups consecutive
        same-signature feeds into runs of ``steps_per_dispatch``, stacks
        each run along a new leading axis (:func:`stack_feeds`) and ships
        it to the device; up to ``prefetch_depth`` staged dispatches wait
        in a bounded queue while the device executes the current one
        (JAX's async dispatch returns control to this generator before
        the step finishes, so the worker fills the queue during compute).
        Full runs dispatch as ONE compiled K-step scan
        (``run_steps(feeds_stacked=True)`` — the chunked-dispatch data
        path); leftovers (tail of the stream, or a padding-bucket
        signature change) dispatch per step through :meth:`run`, which
        bounds compilation to two variants per feed signature.

        Step math is identical to calling :meth:`run` once per feed in
        order — same step-counter threading, same PRNG key derivation,
        same donated-state updates — so fetches are bit-identical to the
        sequential loop (tests/test_input_pipeline.py asserts this).

        The stream's lifecycle follows :mod:`paddle_tpu.reader.pipeline`
        rules: an exception in ``feed_iter`` re-raises here, and
        abandoning this generator early stops and joins the staging
        worker.
        """
        from ..reader.pipeline import prefetch as _prefetch
        if self.check_nan_inf:
            raise ValueError(
                "run_pipelined: check_nan_inf needs per-step host "
                "inspection; use run() for NaN hunts")
        program = program or default_main_program()
        if steps_per_dispatch is None or prefetch_depth is None:
            cfg = self._tuned("executor/run_pipelined",
                              {"steps_per_dispatch": 4,
                               "prefetch_depth": 2})
            if steps_per_dispatch is None:
                steps_per_dispatch = cfg["steps_per_dispatch"]
            if prefetch_depth is None:
                prefetch_depth = cfg["prefetch_depth"]
        K = int(steps_per_dispatch)
        if K < 1:
            raise ValueError(
                f"run_pipelined: steps_per_dispatch must be >= 1, got {K}")

        # resolved once: the staging worker and the queue instrumentation
        # below run for this generator's whole lifetime.  The root span
        # ties the whole causal chain into ONE trace: staging-worker
        # spans parent to it explicitly (cross-thread), and each
        # consuming run/run_steps call attaches it so the executor/step
        # spans nest under it.
        obs_on = self._observing()
        root = obs.tracing.start_span(
            "executor/run_pipelined", steps_per_dispatch=K,
            prefetch_depth=int(prefetch_depth)) if obs_on else None

        def staged():
            """Chunks of the feed stream, already device-resident."""
            def ship_scan(pend):
                with (obs.tracing.span("pipeline/stage", kind="scan",
                                       steps=len(pend))
                      if obs_on else nullcontext()):
                    t0 = time.perf_counter() if obs_on else 0.0
                    dev = {k: jax.device_put(v)
                           for k, v in stack_feeds(pend).items()}
                    if obs_on:
                        obs.observe_hist("executor/stage_put_ms",
                                         (time.perf_counter() - t0) * 1e3)
                return ("scan", dev, len(pend))

            def ship_singles(pend):
                for feed in pend:
                    with (obs.tracing.span("pipeline/stage",
                                           kind="single", steps=1)
                          if obs_on else nullcontext()):
                        t0 = time.perf_counter() if obs_on else 0.0
                        dev = {k: v if isinstance(v, jax.Array)
                               else jax.device_put(np.asarray(v))
                               for k, v in feed.items()}
                        if obs_on:
                            obs.observe_hist(
                                "executor/stage_put_ms",
                                (time.perf_counter() - t0) * 1e3)
                    yield ("single", dev, 1)

            pend, sig = [], None
            for feed in feed_iter:
                fsig = _feed_signature(feed)
                if pend and fsig != sig:
                    yield from ship_singles(pend)
                    pend = []
                sig = fsig
                pend.append(feed)
                if len(pend) == K:
                    if K > 1:
                        yield ship_scan(pend)
                    else:      # K=1: plain overlap, no scan stacking
                        yield from ship_singles(pend)
                    pend = []
            yield from ship_singles(pend)

        staged_reader = _prefetch(staged,
                                  buffer_size=max(1, int(prefetch_depth)),
                                  num_workers=1, instrument=obs_on,
                                  trace_parent=root)
        try:
            for kind, dev, n in staged_reader():
                if kind == "scan":
                    with (obs.tracing.attach(root) if root is not None
                          else nullcontext()):
                        outs = self.run_steps(
                            n, program, feed=dev, fetch_list=fetch_list,
                            scope=scope, return_numpy=return_numpy,
                            is_test=is_test, feeds_stacked=True)
                    for i in range(n):
                        yield [o[i] if o is not None else None
                               for o in outs]
                else:
                    # per-step fallback: stream tail, or a partially-
                    # filled stack flushed by a padding-bucket signature
                    # change — visible in telemetry so a bucketing
                    # mistake that degrades every dispatch to singles is
                    # diagnosable (K=1 dispatches singles by design:
                    # not a fallback)
                    if obs_on and K > 1:
                        obs.inc_counter("pipeline/fallback_steps")
                    with (obs.tracing.attach(root) if root is not None
                          else nullcontext()):
                        out = self.run(program, feed=dev,
                                       fetch_list=fetch_list,
                                       scope=scope,
                                       return_numpy=return_numpy,
                                       is_test=is_test)
                    yield out
        finally:
            if root is not None:
                root.end()

    def _make_multi(self, program: Program, fetch_names: List[str],
                    is_test: bool, num_steps: int, feeds_stacked: bool):
        """The K-step scan function run_steps compiles: a device-side
        ``lax.scan`` over the per-step fn with donated state threading."""
        step_fn = self._make_fn(program, fetch_names, is_test)

        def multi(feeds, st, step0):
            def body(carry, xs):
                s, step = carry
                f = xs if feeds_stacked else feeds
                fetches, new_s = step_fn(f, s, step)
                return (new_s, step + 1), fetches

            # everything the scan itself emits (the while, the carried
            # state's copies, the stacking of each step's fetches) reads
            # "pt.scan"; the ops inside nest their own scopes under it
            with jax.named_scope(SCAN_SCOPE):
                init = (st, jnp.asarray(step0, jnp.uint32))
                if feeds_stacked:
                    (s_out, _), ys = jax.lax.scan(body, init, feeds)
                else:
                    (s_out, _), ys = jax.lax.scan(body, init, None,
                                                  length=num_steps)
            return ys, s_out

        multi.prog_cell = step_fn.prog_cell
        return multi

    def _build_steps(self, program: Program, multi, feeds_stacked: bool,
                     fingerprint: Optional[str] = None):
        """jit wrapper for the K-step scan fn (ShardedExecutor overrides
        this to pin mesh shardings)."""
        return self._as_step(multi, fingerprint, "run_steps")

    def _as_step(self, fn, fingerprint: Optional[str], label: str):
        """``fn`` itself when interpreting eagerly, else a CachedStep named
        ``pt_<label>``; a check_nan_inf variant (run only) does not donate
        its pre-step state, which the NaN bisect replays from."""
        if not self.use_jit:
            return fn
        return compile_cache.CachedStep(
            fn, fingerprint,
            compiler_options=self.compiler_options,
            label=label, donate=not self.check_nan_inf)

    # -- fingerprinting ------------------------------------------------------
    def _lowering_options(self) -> Dict[str, object]:
        """The constructor arguments that change what a step TRACES to:
        ``_step_options`` hands them to the lowerings, ``_config_sig``
        fingerprints them, ``Executor(**them)`` replays this executor."""
        return {"amp": self.amp, "compute_dtype": self.compute_dtype}

    def _config_sig(self):
        """Executor-configuration component of every cache fingerprint —
        everything on `self` that changes the compiled computation."""
        return (self.use_jit, _specs_sig(self._lowering_options()),
                _specs_sig(self.compiler_options))

    def _fingerprint_extras(self, program: Program):
        """Subclass hook: extra fingerprint components (ShardedExecutor
        folds in mesh axes/devices and feed/param sharding specs)."""
        return ()

    def _entry_sig(self, program: Program, feed_arrays, fetch_names,
                   state_keys, is_test: bool, steps=None):
        """Structured cache signature for one compiled step variant.  The
        program component is a CONTENT digest (ops/attrs/var shapes/dtypes/
        random_seed via Program.to_dict), so the key is stable across
        processes and shared by content-identical programs; x64 mode is
        folded in because it changes every traced aval."""
        head = ("run",) if steps is None else ("steps",) + tuple(steps)
        return head + (
            compile_cache.program_content_digest(program),
            _feed_signature(feed_arrays), tuple(fetch_names),
            tuple(sorted(state_keys)), bool(is_test),
            self.check_nan_inf,   # changes the compiled fn's output arity
            bool(jax.config.jax_enable_x64),
            self._config_sig(), self._fingerprint_extras(program))

    # -- AOT -----------------------------------------------------------------
    def compile(self, program: Optional[Program] = None,
                feed: Optional[Dict[str, object]] = None,
                fetch_list: Optional[Sequence] = None,
                scope: Optional[Scope] = None,
                is_test: bool = False,
                num_steps: Optional[int] = None,
                feeds_stacked: bool = False):
        """Ahead-of-time compile ONE step variant and install it in the
        executor's cache, so the matching :meth:`run` (or :meth:`run_steps`
        when ``num_steps`` is given) executes without paying trace/lower/
        compile at first-request time — the deploy-time analog of
        ``jax.jit(...).lower().compile()``.

        ``feed`` maps feed names to example arrays, ``(shape, dtype)``
        tuples, or ``jax.ShapeDtypeStruct``s — only shapes/dtypes are read
        (declared Program var dtypes override, exactly as ``run`` coerces
        feeds).  For ``feeds_stacked=True`` the specs must carry the
        leading ``num_steps`` axis, as ``run_steps`` receives them.

        Call AFTER the startup program ran: the persistable state in
        ``scope`` is part of the step signature.  Returns a
        :class:`~paddle_tpu.core.compile_cache.CompiledProgram`.  The XLA
        compile lands in JAX's persistent compilation cache
        (``compile_cache.cache_dir()``) for warm process starts.
        """
        if not self.use_jit:
            raise ValueError("Executor.compile requires use_jit=True")
        if self.check_nan_inf and num_steps is not None:
            raise ValueError("run_steps: check_nan_inf needs per-step host "
                             "inspection")
        if feeds_stacked and num_steps is None:
            raise ValueError(
                "Executor.compile: feeds_stacked=True requires num_steps "
                "(stacked [K, ...] specs describe the run_steps scan "
                "variant; without num_steps the single-step variant would "
                "silently compile against the wrong shapes)")
        steps = None if num_steps is None else (num_steps, feeds_stacked)
        with self._call_context(program):
            entry = self._enter(program, feed, fetch_list, scope, is_test,
                                steps=steps, abstract=True)
            # every jitted step (CachedStep, the sharded wrapper) prepares
            step = entry.fn.prepare(entry.feeds, entry.state, 0)
        return compile_cache.CompiledProgram(
            self, entry.program, entry.fp, step, entry.fetch_names,
            entry.state_keys, num_steps=num_steps,
            feeds_stacked=feeds_stacked, is_test=is_test)

    # -- internals ---------------------------------------------------------
    def _state_keys(self, program: Program, scope: Scope) -> List[str]:
        """Persistable vars referenced by the program that exist in scope.

        Cached on the Program object (dies with it; cleared on version bump)
        with a weakref identity check on the Scope so an id()-reusing new
        Scope can never hit a stale entry.  This walks every op in the
        program, which would otherwise dominate the per-step host time for
        big nets (~ms/step on ResNet-50).
        """
        cache = getattr(program, "_state_keys_cache", None)
        if cache is None or cache["version"] != program.version:
            cache = {"version": program.version, "entries": {}}
            program._state_keys_cache = cache
        sk = (id(scope), scope.keys_version())
        entry = cache["entries"].get(sk)
        if entry is not None:
            scope_ref, keys = entry
            if scope_ref() is scope:
                return keys
        keys = self._state_keys_uncached(program, scope)
        entries = cache["entries"]
        # sweep entries whose scope died (id-keyed dead pairs used to
        # accumulate for the life of the program); misses are rare — once
        # per new (scope, keys_version) — so the O(entries) deref is cheap
        dead = [k for k, (ref, _) in entries.items() if ref() is None]
        if dead:
            for k in dead:
                del entries[k]
            compile_cache.stats().bump("state_keys_evictions", len(dead))
        while len(entries) >= _STATE_KEYS_CACHE_MAX:   # then FIFO-bound
            entries.pop(next(iter(entries)))
            compile_cache.stats().bump("state_keys_evictions")
        entries[sk] = (weakref.ref(scope), keys)
        return keys

    def _state_keys_uncached(self, program: Program,
                             scope: Scope) -> List[str]:
        referenced = set()
        for b in program.blocks:
            for op in b.ops:
                referenced.update(op.input_names)
                referenced.update(op.output_names)
        keys = []
        for name in referenced:
            v = None
            for b in program.blocks:
                if name in b.vars:
                    v = b.vars[name]
                    break
            if v is not None and v.persistable and scope.has(name):
                keys.append(name)
        return keys

    def _build(self, program: Program, feed_names: List[str],
               fetch_names: List[str], state_keys: List[str], is_test: bool,
               fingerprint: Optional[str] = None):
        return self._as_step(self._make_fn(program, fetch_names, is_test),
                             fingerprint, "run")

    def _step_options(self) -> Dict[str, object]:
        """A snapshot of what this executor hands the op lowerings (the
        LoweringContext's keywords).  ``_step_prologue`` is a function of
        it and not of the executor, so a traced fn outlives its executor
        as it always has (``__graft_entry__.entry``, ``export_model``)."""
        return dict(self._lowering_options(), mesh=self.mesh,
                    pipeline_microbatches=self.num_microbatches)

    @staticmethod
    def _lowering_context(options: Dict[str, object], program: Program,
                          base_key, is_test: bool) -> LoweringContext:
        """The ONE construction site (tests/test_repo_lint.py), so that a
        replay (``observability.nanprov``) lowers as the compiled step did."""
        return LoweringContext(program, base_key, is_test=is_test, **options)

    @staticmethod
    def _step_prologue(options: Dict[str, object], program: Program,
                       feed_arrays, state, step, is_test: bool):
        """``(env, ctx)`` one step starts from under ``options`` (an
        executor's ``_step_options()``): state and feeds in the env, the
        ``compute_dtype`` upcast, the pure-inference AMP cast, the step's
        PRNG key, the LoweringContext.  The traced fn of ``_make_fn``
        calls it, and so do the eager replays (``nanprov.
        make_eager_context``): they cannot start from another precision."""
        with jax.named_scope(RNG_SCOPE):
            base_key = jax.random.fold_in(
                jax.random.PRNGKey(program.random_seed), step)
        env = Env(program.global_block())
        env.local.update(state)
        env.local.update(feed_arrays)
        if options["compute_dtype"] is not None:
            cd = jnp.dtype(options["compute_dtype"])
            with jax.named_scope(DTYPE_SCOPE):
                env.local = {k: v.astype(cd) if hasattr(v, "dtype")
                             and jnp.issubdtype(v.dtype, jnp.floating)
                             else v for k, v in env.local.items()}
        if options["amp"] and not any(op.type == "backward"
                                      for op in program.global_block().ops):
            # pure-inference AMP: whole net computes in bf16 (a training
            # step casts inside value_and_grad, _run_backward)
            with jax.named_scope(AMP_SCOPE):
                env.local = {k: _to_bf16(v) for k, v in env.local.items()}
        return env, Executor._lowering_context(options, program, base_key,
                                               is_test)

    def _make_fn(self, program: Program, fetch_names: List[str],
                 is_test: bool):
        """The pure (feeds, state, step) -> (fetches, state') function the
        jit wrappers compile (ShardedExecutor adds mesh shardings).

        The program is captured by WEAKREF: the traced function only needs
        it while tracing (the interpreter walks its ops), and a strong
        closure would pin every cached program for the life of the
        Executor — the cache evicts entries whose programs died instead.
        The ref lives in a mutable cell exposed as ``fn.prog_cell`` so the
        cache can refresh it when a content-identical client Program hits
        the entry (the fingerprint guarantees any client traces the same
        computation); a re-trace after the original program died then uses
        the live client instead of failing.  The executor's options are
        snapshotted here: the fn holds no executor and outlives its own.
        """
        persistable_names = sorted(
            {v.name for b in program.blocks for v in b.vars.values()
             if v.persistable})
        check_nan = self.check_nan_inf
        options = self._step_options()
        prog_cell = [weakref.ref(program)]

        def fn(feed_arrays, state, step):
            program = prog_cell[0]()
            if program is None:
                raise RuntimeError(
                    "compiled step traced after its Program was "
                    "garbage-collected (cache entry outlived every "
                    "client program)")
            env, ctx = Executor._step_prologue(options, program, feed_arrays,
                                               state, step, is_test)
            interpret_block_with_backward(program.global_block(), env, ctx)
            fetches = [env.get(n) if env.has(n) else None for n in fetch_names]
            if check_nan:
                # per-VAR finite flags computed in-graph (one fused reduce
                # per float var): the executor.cc:116-124 analog for the
                # one-big-jit world — a NaN is localized to the op that
                # produced it, not to the whole step (see _nan_localize)
                with jax.named_scope(NAN_SCOPE):
                    finite = {
                        k: jnp.all(jnp.isfinite(v))
                        for k, v in env.local.items()
                        if hasattr(v, "dtype") and
                        jnp.issubdtype(v.dtype, jnp.floating)}
                fetches = fetches + [finite]
            new_state = {k: env.get(k) for k in persistable_names
                         if env.has(k)}
            # AMP: persistable state keeps its incoming dtype (bn running
            # stats etc. stay fp32 across steps; jit signature stays stable)
            for k, v in list(new_state.items()):
                old = state.get(k)
                if old is not None and hasattr(old, "dtype") and \
                        hasattr(v, "dtype") and v.dtype != old.dtype:
                    with jax.named_scope(AMP_SCOPE):
                        new_state[k] = v.astype(old.dtype)
            return fetches, new_state

        fn.prog_cell = prog_cell
        return fn

    def _nan_check(self, names, fetches):
        return _nan_check_impl(names, fetches)

    @staticmethod
    def _nan_localize(program: Program, finite_map):
        """Raise naming the FIRST op (program order) whose output went
        non-finite — the executor.cc:116-124 per-op check, recovered from
        the in-graph flags without leaving the one-jit model."""
        # ONE host transfer for all flags, not one blocking sync per var
        finite_map = jax.device_get(finite_map)
        bad = {n for n, flag in finite_map.items() if not bool(flag)}
        if not bad:
            return
        for op in program.global_block().ops:
            for slot, names in op.outputs.items():
                for n in names:
                    if n in bad:
                        raise FloatingPointError(
                            f"NaN/Inf first produced by op {op.type!r} in "
                            f"var {n!r} (output slot {slot}; "
                            f"check_nan_inf, executor.cc FLAGS_check_nan_inf"
                            f" analog)")
        # non-finite var with no producing op (e.g. a feed)
        n = sorted(bad)[0]
        raise FloatingPointError(
            f"NaN/Inf detected in var {n!r} (not produced by any op — "
            f"check the feed; check_nan_inf)")

    def close(self):
        self._cache.clear()


def _nan_check_impl(names, fetches):
    for n, f in zip(names, fetches):
        if f is None:
            continue
        a = np.asarray(f)
        if np.issubdtype(a.dtype, np.floating) and not np.all(np.isfinite(a)):
            raise FloatingPointError(
                f"NaN/Inf detected in fetched var {n!r} "
                f"(check_nan_inf, analog of FLAGS_check_nan_inf)")
