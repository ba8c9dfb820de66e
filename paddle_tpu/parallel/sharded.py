"""ShardedExecutor: the multi-chip training path.

One jit per (program, feed-signature) with explicit ``in_shardings`` /
``out_shardings`` over a named Mesh — GSPMD propagates the annotations and
inserts ICI collectives.  This single mechanism replaces the reference's
MultiGradientMachine ring reduce (MultiGradientMachine.h:60-110), both
parameter servers (paddle/pserver, go/pserver), and the NCCL op family
(operators/nccl/nccl_op.cu.cc) — there is no gradient-exchange code to write
because sharded-batch + replicated-params makes XLA emit the all-reduce.

Parallelism taxonomy (mesh axes, see parallel.mesh):
  dp — feeds sharded on batch dim 0 (data parallel)
  tp — Parameter.sharding PartitionSpecs (Megatron column/row, vocab-sharded
       embeddings — the SelectedRows/CTR analog)
  sp — sequence dim sharding on feeds declared lod_level>0 (NEW vs reference)
  pp/ep — via parallel.pipeline / expert specs on parameters.
"""
from __future__ import annotations

import time
import weakref
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core import compile_cache
from ..core.executor import Executor, _specs_sig
from ..core.program import Program
from .mesh import get_mesh


class ShardedExecutor(Executor):
    """Executor whose compiled step carries mesh shardings.

    feed_specs: optional {feed_name: PartitionSpec} overrides.  Default:
    batch dim sharded on ``batch_axis`` (and, when the program var has
    lod_level>0 and the mesh has an 'sp' axis of size>1, time dim on 'sp').
    Parameters use ``Parameter.sharding`` annotations; unannotated state
    replicates.
    """

    def __init__(self, mesh: Optional[Mesh] = None, batch_axis: str = "dp",
                 feed_specs: Optional[Dict[str, P]] = None,
                 param_specs: Optional[Dict[str, P]] = None,
                 num_microbatches: Optional[int] = None,
                 auto_shard: bool = False, **kw):
        super().__init__(**kw)
        self.mesh = mesh or get_mesh()
        self.batch_axis = batch_axis
        self.feed_specs = dict(feed_specs or {})
        self.param_specs = dict(param_specs or {})
        # GPipe microbatch count for pipeline_stage-annotated programs
        # (parallel/pipeline_program.py); default = the 'pp' axis size
        self.num_microbatches = num_microbatches
        # auto_shard=True: when BOTH spec dicts are omitted, the static
        # auto-sharding planner (analysis.planner) proposes them from the
        # first program that carries feeds — the plan is validated against
        # the PT030/PT031 lints before a single trace happens
        self.auto_shard = auto_shard
        self.auto_plan = None

    def _ensure_auto_plan(self, program: Optional[Program]):
        """Plan once, on the first fed program (the startup program has no
        feeds and carries no information the planner wants)."""
        if not self.auto_shard or self.auto_plan is not None:
            return
        if program is None:
            from ..core.program import default_main_program
            program = default_main_program()
        if self.param_specs or self.feed_specs:
            # explicit specs win — auto_shard only fills an omission
            self.auto_plan = False
            return
        if not any(v.is_data for b in program.blocks
                   for v in b.vars.values()):
            return
        from ..analysis import planner
        mesh_axes = {str(a): int(self.mesh.shape[a])
                     for a in self.mesh.axis_names}
        plan = planner.plan(program, mesh_axes,
                            batch_axis=self.batch_axis)
        param_specs, feed_specs = plan.as_partition_specs()
        self.param_specs.update(param_specs)
        self.feed_specs.update(feed_specs)
        self.auto_plan = plan

    def _validation_context(self):
        # the static verifier's sharding lints (PT030/PT031) check
        # Parameter.sharding and these overrides against the mesh
        return self.mesh, self.param_specs, self.feed_specs

    def _observe_label(self) -> str:
        # folded into XProf annotation names and step events so multi-chip
        # dispatches are attributable to their mesh in a device trace;
        # size-1 axes are noise (make_mesh declares all five) — drop them
        axes = [f"{a}{self.mesh.shape[a]}" for a in self.mesh.axis_names
                if self.mesh.shape[a] > 1]
        return "mesh=" + (",".join(axes) or "1")

    # -- sharding selection -------------------------------------------------
    def _find_var(self, program: Program, name: str):
        for b in program.blocks:
            if name in b.vars:
                return b.vars[name]
        return None

    def _feed_spec(self, program: Program, name: str, ndim: int,
                   shape=None) -> P:
        if name in self.feed_specs:
            return self.feed_specs[name]
        if ndim == 0:
            return P()
        base = name[:-4] if name.endswith("@LEN") else name
        v = self._find_var(program, base)
        axes = [self.batch_axis if self.batch_axis in self.mesh.axis_names
                else None]
        if (not name.endswith("@LEN") and v is not None and v.lod_level
                and "sp" in self.mesh.axis_names
                and self.mesh.shape["sp"] > 1 and ndim >= 2
                and (shape is None
                     or shape[1] % self.mesh.shape["sp"] == 0)):
            axes.append("sp")
        axes = axes[:ndim]
        return P(*axes)

    def _state_spec(self, program: Program, name: str) -> P:
        if name in self.param_specs:
            return self.param_specs[name]
        v = self._find_var(program, name)
        if v is not None and getattr(v, "sharding", None):
            return P(*v.sharding)
        return P()

    # -- overrides ----------------------------------------------------------
    def _call_context(self, program: Optional[Program]):
        # every run/run_steps/compile call plans first (auto_shard), then
        # resolves, traces and dispatches under the mesh
        self._ensure_auto_plan(program)
        return self.mesh

    def _fingerprint_extras(self, program: Program):
        """Mesh + sharding-spec fingerprint components: the same program/
        feed signature compiled under a different mesh shape, device set,
        batch axis or spec override is a different executable."""
        mesh = self.mesh
        return ("mesh", tuple(mesh.axis_names),
                tuple(int(mesh.shape[a]) for a in mesh.axis_names),
                tuple(str(d) for d in np.ravel(mesh.devices)),
                self.batch_axis, self.num_microbatches,
                _specs_sig(self.feed_specs),
                _specs_sig(self.param_specs))

    def _state_shardings(self, program: Program, state):
        """Pin only explicitly-annotated params; None leaves let jit keep
        whatever sharding GSPMD propagated onto the arrays (replicated
        params stay replicated, derived accumulators keep their layout)."""
        state_sh = {}
        for k in state:
            spec = self.param_specs.get(k)
            if spec is None:
                v = self._find_var(program, k)
                if v is not None and getattr(v, "sharding", None):
                    spec = P(*v.sharding)
            state_sh[k] = NamedSharding(self.mesh, spec) \
                if spec is not None else None
        return state_sh

    def _sharded_wrapper(self, program: Program, fn, fingerprint, label,
                         feeds_stacked=None):
        """Shared jit wrapper: one CachedStep per argument-name set, with
        mesh shardings pinned on the inputs.  The outer fingerprint already
        covers shapes/dtypes/specs, so in practice each wrapper holds
        exactly one step; the dict guards name-set drift.  ``feeds_stacked``
        None means the per-step path; True/False the K-step scan (stacked
        feeds shard their PER-STEP dims — the leading steps axis is scanned
        over, not distributed).

        The Program is resolved through the step fn's refreshable weakref
        cell (executor._make_fn) rather than captured strongly: a strong
        closure here would defeat ExecCache's dead-program sweeping for
        every sharded entry."""
        mesh = self.mesh
        jitted = {}
        prog_cell = getattr(fn, "prog_cell", None) or \
            [weakref.ref(program)]

        def get_step(feed_arrays, state):
            key = (tuple(sorted(feed_arrays)), tuple(sorted(state)))
            if key not in jitted:
                program = prog_cell[0]()
                if program is None:
                    raise RuntimeError(
                        "sharded step built after its Program was "
                        "garbage-collected (cache entry outlived every "
                        "client program)")
                lead = 1 if feeds_stacked else 0
                feed_sh = {}
                for n, a in feed_arrays.items():
                    spec = self._feed_spec(
                        program, n, len(np.shape(a)) - lead,
                        shape=tuple(np.shape(a))[lead:])
                    if feeds_stacked:
                        spec = P(None, *spec)
                    feed_sh[n] = NamedSharding(mesh, spec)
                # out_shardings stay unspecified: the produced state set can
                # exceed the fed state (first step materializes
                # accumulators) and GSPMD keeps params on input shardings.
                jitted[key] = compile_cache.CachedStep(
                    fn, fingerprint,
                    compiler_options=self.compiler_options,
                    in_shardings=(feed_sh,
                                  self._state_shardings(program, state),
                                  None),
                    label=label, donate=not self.check_nan_inf)
            return jitted[key]

        def wrapper(feed_arrays, state, step):
            return get_step(feed_arrays, state)(feed_arrays, state, step)

        wrapper.prog_cell = prog_cell
        wrapper.label = label        # as CachedStep.label (the phase log)
        # AOT hook for Executor.compile: prepare (and return) the inner
        # CachedStep from abstract avals
        wrapper.prepare = lambda feeds, state, step: \
            get_step(feeds, state).prepare(feeds, state, step)
        return wrapper

    def _build_steps(self, program: Program, multi, feeds_stacked: bool,
                     fingerprint=None):
        if not self.use_jit:
            return multi
        return self._sharded_wrapper(program, multi, fingerprint,
                                     "sharded_run_steps",
                                     feeds_stacked=feeds_stacked)

    def _build(self, program: Program, feed_names, fetch_names,
               state_keys, is_test, fingerprint=None):
        fn = self._make_fn(program, fetch_names, is_test)
        if not self.use_jit:
            return fn
        return self._sharded_wrapper(program, fn, fingerprint,
                                     "sharded_run")

    def place_state(self, program: Program, scope=None):
        """Pre-place persistable scope entries with their specs (params get
        Parameter.sharding; others replicate).  Call once after the startup
        program ran — the analog of MultiGradientMachine's value dispatch."""
        from ..core.scope import global_scope
        scope = global_scope() if scope is None else scope
        t0, nbytes = time.perf_counter(), 0
        for name in list(scope.keys()):
            v = self._find_var(program, name)
            if v is None or not v.persistable:
                continue
            spec = self._state_spec(program, name)
            value = scope.get(name)
            nbytes += int(getattr(value, "nbytes", 0))
            scope.set(name, jax.device_put(
                value, NamedSharding(self.mesh, spec)))
        # host time of the placement (device_put returns before the copies
        # land); bytes: what was handed over, once, not times the replicas
        compile_cache.stats().record_phase(
            "state/place", t0, time.perf_counter(), bytes=nbytes)
