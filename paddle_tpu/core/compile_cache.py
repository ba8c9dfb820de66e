"""Compilation-cache subsystem: fingerprints, LRU entry cache, the one
persistent-cache directory, and compile-time telemetry.

paddle_tpu's one-big-jit design (core/executor.py) pays trace->lower->compile
for every (program, feed-signature) variant.  This module makes that cost
*managed* instead of implicit, in three layers:

1. **Stable fingerprints** — a compiled step variant is keyed by a content
   hash of everything that determines the traced computation: the serialized
   Program (ops, attrs, var shapes/dtypes, random_seed), the feed signature
   (names/shapes/dtypes), fetch names, state keys, executor configuration
   (amp, compute_dtype, compiler_options, check_nan_inf),
   mesh + sharding specs (ShardedExecutor), x64 mode, and the jax +
   paddle_tpu versions.  Content-identical programs
   (``prune().clone(for_test=True)`` slices built per evaluation call)
   share one in-process :class:`ExecCache` entry.

2. **Persistent cache** — JAX's own persistent compilation cache, and
   nothing beside it.  :func:`cache_dir` resolves the ONE directory:
   ``JAX_COMPILATION_CACHE_DIR`` when the caller set it (JAX read it at
   import; this module never re-points it), else the fixed
   ``<checkout>/.jax_cache``.  A fresh process re-traces and re-lowers,
   and the XLA compile is a disk read; hits are counted from JAX's
   ``/jax/compilation_cache/cache_hits`` monitoring event
   (``jax_cache_hits`` in :class:`CompileStats`).  JAX's default size/time
   thresholds apply, so sub-second compiles never land on disk.

3. **Telemetry** — per-fingerprint trace/lower/compile wall times, cache
   hit/miss/eviction counters, trace-time kernel-routing counters
   (``route/<op>:<path>``), a retrace detector
   (:func:`retrace_guard` / :meth:`CompileStats.assert_no_retrace`) and
   the **phase log**: what made a process's start cold, one record a piece
   of work (:data:`PHASE_NAMES`), written where the work happens, always
   on, on ``time.perf_counter()``; a warm dispatch writes nothing.  All
   surfaced through ``paddle_tpu.profiler.compile_stats()``.

The deploy-time entry point is ``Executor.compile(...) -> CompiledProgram``
(AOT ``jit(...).lower().compile()``), so serving paths and
``Trainer.train(warmup=...)`` pay compile cost at a chosen moment instead of
first-request time.
"""
from __future__ import annotations

import collections
import hashlib
import json
import logging
import os
import threading
import time
import weakref
from typing import Dict, List, Optional

import jax
from jax import monitoring as _jax_monitoring

logger = logging.getLogger("paddle_tpu")

_env_key = None


def framework_version() -> str:
    try:
        from .. import __version__
        return __version__
    except Exception:
        return "0"


def environment_key():
    """Process-environment component of every fingerprint: a compiled
    executable is only valid for the same jax/paddle_tpu versions and the
    same backend topology."""
    global _env_key
    if _env_key is None:
        _env_key = (jax.__version__, framework_version(),
                    jax.default_backend(), jax.device_count())
    return _env_key


def fingerprint_hex(sig) -> str:
    """Stable hex digest of a structured signature tuple.

    ``sig`` must repr deterministically (strings, ints, bools, nested
    tuples); the Program component should be ``program.content_digest()``
    so the key survives process restarts."""
    payload = repr((sig, environment_key()))
    return hashlib.sha256(payload.encode()).hexdigest()


def program_content_digest(program) -> str:
    """Content hash of a serialized Program, cached per version bump.

    Serialization cost is paid once per program mutation, not per step —
    the same discipline as ``Executor._state_keys``."""
    key = (program.version, program.random_seed)   # random_seed mutates
    cached = getattr(program, "_content_digest", None)  # without a bump
    if cached is not None and cached[0] == key:
        return cached[1]
    payload = json.dumps(program.to_dict(), sort_keys=True,
                         separators=(",", ":"), default=repr)
    digest = hashlib.sha256(payload.encode()).hexdigest()
    program._content_digest = (key, digest)
    return digest


# ---------------------------------------------------------------------------
# Telemetry
# ---------------------------------------------------------------------------
class RetraceError(AssertionError):
    """Raised by :func:`retrace_guard` when a fingerprint traces twice."""


#: the phase log's names, frozen as ``SPAN_NAMES`` / ``METRIC_NAMES`` are:
#: every ``record_phase`` call passes one of these as a string literal
#: (tests/test_repo_lint.py holds call sites and table to each other)
PHASE_NAMES = (
    ("process/import",
     "first to last statement of paddle_tpu/__init__.py; fact "
     "jax_preimported"),
    ("step/enter",
     "Executor._enter when the entry cache had no step: feeds coerced, "
     "state keys, validation, the Program's fingerprint, the step built"),
    ("step/trace",
     "CachedStep._compile: jit.trace, Python over the Program's ops"),
    ("step/lower",
     "CachedStep._compile: traced.lower, jaxpr to StableHLO"),
    ("step/xla",
     "CachedStep._compile: lowered.compile, a read of JAX's persistent "
     "cache (fact cache_hit) or an XLA compile"),
    ("step/first_call",
     "CachedStep.__call__, the first call of a compiled step: argument "
     "check, the executable's load, the enqueue (host time, not device)"),
    ("state/place",
     "ShardedExecutor.place_state: device_put of every persistable under "
     "its sharding; fact bytes"),
)
_PHASE_NAME_SET = frozenset(n for n, _help in PHASE_NAMES)
#: the three phases of CachedStep._compile -> their key in
#: ``entries[fp]["times"]``: record_phase writes both, so the per-fingerprint
#: times and the log cannot disagree
_STEP_TIME_KEYS = {"step/trace": "trace_s", "step/lower": "lower_s",
                   "step/xla": "compile_s"}
#: records kept; beyond it a record only bumps ``phases_dropped``
PHASE_LOG_CAP = 512


class CompileStats:
    """Compile-time telemetry: counters, per-fingerprint phase times, and
    the phase log (:meth:`record_phase`).

    Counters:
      hits/misses/evictions       — in-process entry cache (ExecCache)
      jax_cache_hits              — XLA compiles served from JAX's
                                    persistent compilation cache
      lazy_jit_fallbacks          — mesh-step calls re-routed through a
                                    lazily-specialized jit (CachedStep)
      route/<op>:<path>           — trace-time kernel routing: which
                                    implementation an op with several
                                    lowered (e.g. flash_attention:pallas
                                    vs :reference), one bump per trace
      rnn_ops_in_scan /           — trace-time loop fission of ``rnn`` step
      rnn_ops_hoisted               blocks (ops/control_flow_ops.py _rnn): ops
                                    it left under lax.scan, and ops it ran
                                    once after the scan instead; both bumped
                                    per lowered rnn op
      traces                      — jit traces of step functions (a trace
                                    runs the Python interpreter over the
                                    whole Program; the retrace detector
                                    flags a fingerprint traced twice)
      state_keys_evictions        — Program._state_keys_cache sweeps
      validations                 — static-verifier runs (analysis
                                    .validate_program); the executor
                                    memoizes per (program, version,
                                    fetches), so this stays flat across
                                    steps — tests/test_analysis.py pins it
      jax_trace_s / jax_lower_s / — seconds (floats) of JAX's tracing,
      jax_backend_compile_s         lowering and backend compiles OUTSIDE a
                                    CachedStep's compile (seeded draws,
                                    device_puts, jnp glue): its jaxpr_trace
                                    / jaxpr_to_mlir_module / backend_compile
                                    duration events once :func:`cache_dir`
                                    has hooked them, an event nested in
                                    another counted once
                                    (:func:`_on_jax_duration`)
      phases_dropped              — phase records beyond PHASE_LOG_CAP

    The phase log answers what ``entries[fp]["times"]`` cannot: WHEN each
    piece of a cold start ran (``t0`` on ``time.perf_counter()``, the clock
    of a benchmark's own marks and of the host side of a profiler trace),
    what came before the trace and after the compile, and whether the XLA
    phase was a cache read.  Records of one cold call share ``fp``; its
    ``step/enter`` names in ``cause`` the public call that made the work
    necessary.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = collections.defaultdict(int)
        self.entries: Dict[str, dict] = {}
        self._guards: List[Dict[str, int]] = []
        self._phases: List[dict] = []

    # -- recording -------------------------------------------------------
    def entry(self, fp: str) -> dict:
        with self._lock:
            return self.entries.setdefault(
                fp, {"traces": 0, "hits": 0, "times": {}, "source": None,
                     "label": None})

    def bump(self, counter: str, n: int = 1):
        with self._lock:
            self.counters[counter] += n

    def record_trace(self, fp: Optional[str]):
        if fp is None:
            return
        e = self.entry(fp)
        with self._lock:
            e["traces"] += 1
            self.counters["traces"] += 1
            guard_hit = [g for g in self._guards if g.get(fp, 0) >= 1]
            for g in self._guards:
                g[fp] = g.get(fp, 0) + 1
        if guard_hit:
            # guard_hit[0] aliases a dict the loop above already bumped
            raise RetraceError(
                f"retrace detected: fingerprint {fp[:16]}… traced "
                f"{guard_hit[0][fp]} times inside retrace_guard() — the "
                f"same (program, feed-signature, config) re-paid its "
                f"trace cost; expected exactly one trace per fingerprint")

    def record_hit(self, fp: str):
        e = self.entry(fp)
        with self._lock:
            e["hits"] += 1
            self.counters["hits"] += 1

    def record_phase(self, name: str, t0: float, t1: float,
                     fp: Optional[str] = None, label: Optional[str] = None,
                     cause: Optional[str] = None, **facts):
        """Append one record to the phase log: ``name`` (a literal member
        of :data:`PHASE_NAMES`) ran from ``t0`` to ``t1`` on
        ``time.perf_counter()``, for the step ``fp`` / ``label``, because
        of the public call ``cause`` (``run``, ``run_steps``, ``compile``:
        ``Executor._enter`` knows it and writes it on ``step/enter``; a
        step's other records find it through ``fp``).  A phase of
        ``CachedStep._compile`` is also that fingerprint's
        ``entries[fp]["times"]`` (kept beyond the log's cap).  With a
        metrics log set the record is also one ``phase`` event, whatever
        ``observe`` says: a cold start's handful, none from a warm
        dispatch."""
        if name not in _PHASE_NAME_SET:
            raise ValueError(
                f"record_phase: {name!r} is not in PHASE_NAMES "
                f"({sorted(_PHASE_NAME_SET)})")
        rec = {"name": name, "t0": t0, "dur_s": t1 - t0, "fp": fp,
               "label": label, "cause": cause, **facts}
        key = _STEP_TIME_KEYS.get(name)
        e = self.entry(fp) if key and fp else None
        with self._lock:
            if e is not None:
                e["times"][key] = round(rec["dur_s"], 6)
                e["source"] = "compile"
                if label:
                    e["label"] = label
            if len(self._phases) >= PHASE_LOG_CAP:
                self.counters["phases_dropped"] += 1
                return
            self._phases.append(rec)
        from ..observability import export
        export.emit_event("phase", **rec)       # a no-op without a log

    # -- queries ---------------------------------------------------------
    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self.counters)

    def phases(self) -> List[dict]:
        """The phase log, oldest first (copies)."""
        with self._lock:
            return [dict(r) for r in self._phases]

    def phase_totals(self, before: Optional[float] = None
                     ) -> Dict[str, dict]:
        """``{name: {"seconds", "count"}}`` over the phase log, or over
        its records that started before ``before`` (the end of a set-up on
        ``time.perf_counter()``)."""
        out: Dict[str, dict] = {}
        with self._lock:
            for r in self._phases:
                if before is not None and r["t0"] >= before:
                    continue
                t = out.setdefault(r["name"], {"seconds": 0.0, "count": 0})
                t["seconds"] += r["dur_s"]
                t["count"] += 1
        return out

    def total_compile_seconds(self) -> float:
        """Wall time spent in trace/lower/compile phases (with a warm
        persistent cache, compile_s is the disk read)."""
        with self._lock:
            return sum(e["times"].get(k, 0.0)
                       for e in self.entries.values()
                       for k in ("trace_s", "lower_s", "compile_s"))

    def assert_no_retrace(self):
        bad = {fp: e["traces"] for fp, e in self.entries.items()
               if e["traces"] > 1}
        if bad:
            raise RetraceError(
                f"fingerprints traced more than once: "
                f"{ {fp[:16]: n for fp, n in bad.items()} }")

    def report(self) -> str:
        lines = ["======= CompileStats ======="]
        with self._lock:
            for k in sorted(self.counters):
                v = self.counters[k]
                lines.append(f"  {k}: {v:.3f}" if isinstance(v, float)
                             else f"  {k}: {v}")
            for fp, e in self.entries.items():
                t = " ".join(f"{k}={v * 1e3:.1f}ms"
                             for k, v in e["times"].items())
                lines.append(
                    f"  [{fp[:12]}] traces={e['traces']} hits={e['hits']} "
                    f"source={e['source']} {t}"
                    + (f" ({e['label']})" if e.get("label") else ""))
        totals = self.phase_totals()
        if totals:
            lines.append("  phases (seconds, count):")
            for name, _help in PHASE_NAMES:
                if name in totals:
                    t = totals[name]
                    lines.append(f"    {name}: {t['seconds']:.3f}s "
                                 f"x{t['count']}")
            for r in sorted(self.phases(), key=lambda r: -r["dur_s"])[:5]:
                said = " ".join(
                    f"{k}={v}" for k, v in r.items()
                    if v is not None
                    and k not in ("name", "t0", "dur_s", "fp"))
                lines.append(
                    f"    longest: {r['name']} {r['dur_s']:.3f}s "
                    f"[{(r['fp'] or '-')[:12]}] {said}".rstrip())
        return "\n".join(lines)

    def reset(self):
        with self._lock:
            self.counters.clear()
            self.entries.clear()
            # a process has one import: it outlives a reset
            self._phases = [r for r in self._phases
                            if r["name"] == "process/import"]


_stats = CompileStats()


def stats() -> CompileStats:
    return _stats


class retrace_guard:
    """Context manager: raise :class:`RetraceError` if any fingerprint is
    traced more than once while active.  Tests wrap training loops in this
    to pin the compile-once contract; note that cache eviction (LRU
    overflow) legitimately re-traces."""

    def __enter__(self):
        self._window: Dict[str, int] = {}
        with _stats._lock:
            _stats._guards.append(self._window)
        return self

    def __exit__(self, *exc):
        with _stats._lock:
            _stats._guards.remove(self._window)
        return False


# ---------------------------------------------------------------------------
# In-process entry cache: LRU + weakref sweeping
# ---------------------------------------------------------------------------
class _Entry:
    __slots__ = ("fn", "prog_refs")

    def __init__(self, fn, program):
        self.fn = fn
        self.prog_refs = [weakref.ref(program)]

    def _prog_cell(self):
        """The step fn's refreshable program-weakref cell (executor
        _make_fn), reachable through the jit wrappers' ``_fn``."""
        fn = self.fn
        for _ in range(3):
            cell = getattr(fn, "prog_cell", None)
            if cell is not None:
                return cell
            fn = getattr(fn, "_fn", None)
            if fn is None:
                return None
        return None

    def add_client(self, program):
        # retarget the step fn at this (content-identical — the fingerprint
        # guarantees it) client, so a later re-trace doesn't depend on the
        # CREATOR program still being alive
        cell = self._prog_cell()
        if cell is not None and cell[0]() is not program:
            cell[0] = weakref.ref(program)
        for r in self.prog_refs:
            if r() is program:
                return
        self.prog_refs = [r for r in self.prog_refs if r() is not None]
        self.prog_refs.append(weakref.ref(program))

    def dead(self) -> bool:
        return all(r() is None for r in self.prog_refs)


class ExecCache:
    """Fingerprint -> compiled-step cache with an LRU bound and dead-entry
    sweeping.

    Each entry tracks weakrefs to every Program that has used it (the step
    fn itself only weakly references its program — core/executor.py
    ``_make_fn``), so when the last client program is garbage-collected the
    entry is dropped on the next put/sweep instead of accumulating for the
    life of the Executor.  ``max_entries`` bounds live variants with LRU
    eviction; both eviction kinds count into :class:`CompileStats`.
    """

    def __init__(self, max_entries: int = 64):
        self.max_entries = max(1, int(max_entries))
        self._od: "collections.OrderedDict[str, _Entry]" = \
            collections.OrderedDict()
        self.evictions = 0

    def __len__(self):
        return len(self._od)

    def get(self, fp: str, program=None):
        e = self._od.get(fp)
        if e is None:
            _stats.bump("misses")
            return None
        self._od.move_to_end(fp)
        if program is not None:
            e.add_client(program)
        _stats.record_hit(fp)
        return e.fn

    def put(self, fp: str, fn, program):
        self.sweep()
        self._od[fp] = _Entry(fn, program)
        self._od.move_to_end(fp)
        while len(self._od) > self.max_entries:
            self._od.popitem(last=False)
            self.evictions += 1
            _stats.bump("evictions")

    def sweep(self):
        dead = [fp for fp, e in self._od.items() if e.dead()]
        for fp in dead:
            del self._od[fp]
            self.evictions += 1
            _stats.bump("evictions")

    def clear(self):
        self._od.clear()


# ---------------------------------------------------------------------------
# The one persistent-cache directory (JAX's own compilation cache)
# ---------------------------------------------------------------------------
#: fixed in-checkout default (git-ignored); the path is part of what makes a
#: cache reusable across processes, so never a temp name, a pid or a time
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: JAX's duration events, fired at the END of every trace, lowering and
#: backend compile of the process -> the float counter each is summed into
_JAX_DURATION_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower_s",
    "/jax/core/compile/backend_compile_duration": "jax_backend_compile_s",
}
_hit_listener_on = False
#: per thread: ``in_step``, the depth of CachedStep._compile (its trace,
#: lowering and compile are the step phases'), and ``spans``, the newest
#: disjoint intervals already counted
_jit_local = threading.local()
_JIT_SPANS_KEPT = 1024


def _on_jax_event(event: str, **_):
    if event == _CACHE_HIT_EVENT:
        _stats.bump("jax_cache_hits")


def _on_jax_duration(event: str, secs: float, **_):
    """Sum JAX's own seconds of work OUTSIDE a CachedStep's compile, each
    second once.  A jit called inside a step's trace fires its own trace
    event there, and an eager op inside any trace fires a whole compile:
    an event ends when it is reported, so it ran over the ``secs`` before
    now, the events it contains were reported before it, and it adds what
    they left uncovered."""
    counter = _JAX_DURATION_COUNTERS.get(event)
    if counter is None or getattr(_jit_local, "in_step", 0):
        return
    end = time.perf_counter()
    start = end - float(secs)
    spans = _jit_local.__dict__.setdefault("spans", [])
    inner = 0.0
    while spans and spans[-1][0] >= start:
        s, e = spans.pop()
        inner += e - s
    if spans and spans[-1][1] > start:
        start = spans[-1][1]
    spans.append((start, end))
    del spans[:-_JIT_SPANS_KEPT]
    _stats.bump(counter, end - start - inner)


def cache_dir() -> str:
    """THE persistent-cache directory, resolved in one place.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX read it at import — that
    directory is used as-is and this function makes NO
    ``jax_compilation_cache_dir`` update.  Unset: JAX's cache is pointed
    (once) at the fixed :data:`REPO_CACHE_DIR`.  The autotuner's winner
    store lives under ``<dir>/tuning``.  Also hooks JAX's monitoring events
    into :class:`CompileStats`: the cache hits (``jax_cache_hits``) and its
    own trace / lower / backend-compile seconds outside the steps' compiles
    (``jax_trace_s``, ``jax_lower_s``, ``jax_backend_compile_s``)."""
    global _hit_listener_on
    if not _hit_listener_on:
        _hit_listener_on = True
        _jax_monitoring.register_event_listener(_on_jax_event)
        _jax_monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.config.jax_compilation_cache_dir != REPO_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


# ---------------------------------------------------------------------------
# The jit wrapper: explicit trace/lower/compile with telemetry + disk
# ---------------------------------------------------------------------------
def named_step(fn, label: Optional[str]):
    """``fn`` under the name ``pt_<label>``, for ``jax.jit``: the HLO module
    is then ``jit_pt_run`` / ``jit_pt_run_steps`` / ... instead of whatever
    the closure happened to be called, so a device trace's ``XLA Modules``
    events say which step path ran.  The module name is part of JAX's
    persistent-cache key (op metadata is not), so it is also what keeps an
    executable compiled before the lowering named its ops from being
    served in place of one that carries the names."""
    def named(feeds, state, step):
        return fn(feeds, state, step)

    named.__name__ = named.__qualname__ = f"pt_{label or 'step'}"
    return named


#: live CachedSteps, so that a reader who holds only a fingerprint prefix
#: (the ``pt:<path>:<fp12>`` annotation of a trace) can ask what ran
_live_steps: "weakref.WeakSet[CachedStep]" = weakref.WeakSet()


#: the steps of the last few OBSERVED dispatches, held strongly: an observed
#: dispatch writes ``pt:<path>:<fp12>`` into a profiler trace, and whoever
#: reads that trace does so when the window is over — often after the
#: executor, and with it every other reference to the step, is gone
_observed_steps: "collections.deque[CachedStep]" = collections.deque(maxlen=8)


def keep_observed(fingerprint: Optional[str]):
    """Keep the live step(s) of ``fingerprint`` readable by
    :func:`compiled_hlo_text` until eight newer observed fingerprints have
    pushed them out.  Called by the executor for dispatches it annotates
    (``observe`` on); unobserved steps die with their executor as before."""
    if not fingerprint or any(s.fingerprint == fingerprint
                              for s in _observed_steps):
        return
    _observed_steps.extend(s for s in list(_live_steps)
                           if s.fingerprint == fingerprint)


def compiled_hlo_text(fp_prefix: str) -> Optional[str]:
    """The optimized HLO text of a live, compiled step whose fingerprint
    starts with ``fp_prefix``; None when there is none (never compiled,
    or collected with its executor: only the steps of the last observed
    dispatches outlive it, :func:`keep_observed`).  Rendered on request
    only."""
    if not fp_prefix:
        return None
    for step in list(_live_steps):
        if (step.fingerprint or "").startswith(fp_prefix):
            text = step.hlo_text()
            if text is not None:
                return text
    return None


class CachedStep:
    """AOT-compiled step function for ONE fingerprint.

    Replaces a bare ``jax.jit(fn, donate_argnums=(1,))`` in the executor's
    entry cache.  Semantics are identical (the executor's signature already
    pins shapes/dtypes/x64, so one specialization per instance is exact),
    but the explicit ``trace -> lower -> compile`` pipeline buys:

    * per-phase wall-time telemetry (CompileStats),
    * ``compiler_options`` support (plain jit has no per-call hook),
    * an AOT ``prepare()`` entry point taking abstract avals
      (``jax.ShapeDtypeStruct``) for ``Executor.compile`` /
      ``Trainer.train(warmup=...)``.

    A single-device step whose executable rejects a call's arguments
    raises: what ran is always the variant the telemetry recorded.  Mesh
    steps (``in_shardings`` given — ShardedExecutor) keep ONE named
    retry: out_shardings are unpinned there, so GSPMD may hand a state var
    back under another sharding than the executable took it in (a
    replicated bias returned tp-sharded), and the next call's argument
    check rejects it before donation.  Those calls go through the
    equivalent lazily-specialized ``jax.jit``, counted as
    ``lazy_jit_fallbacks`` in :class:`CompileStats` and logged once.
    """

    def __init__(self, fn, fingerprint: Optional[str],
                 compiler_options: Optional[dict] = None,
                 in_shardings=None, label: Optional[str] = None,
                 donate: bool = True):
        # donate=False: check_nan_inf variants keep the input state
        # buffers alive so the NaN-provenance bisect can re-run the
        # failing step from the true pre-step state without the executor
        # paying a per-step host snapshot (check_nan_inf is part of the
        # fingerprint, so donating and non-donating variants never mix)
        kw = {"donate_argnums": (1,)} if donate else {}
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
        self._fn = fn
        self._jit = jax.jit(named_step(fn, label), **kw)
        self._fp = fingerprint
        self._opts = dict(compiler_options or {})
        self._label = label
        self._compiled = None
        self._mesh_step = in_shardings is not None
        self._fallback_recorded = False
        self._times: Dict[str, float] = {}
        self._called = False         # the phase log's step/first_call
        _live_steps.add(self)

    # -- public ----------------------------------------------------------
    @property
    def fingerprint(self) -> Optional[str]:
        return self._fp

    @property
    def label(self) -> Optional[str]:
        return self._label

    def hlo_text(self) -> Optional[str]:
        """The optimized module as XLA prints it (``compiled.as_text()``),
        with the ``pt.<op_type>:<block>.<position>`` scopes of the lowering
        in each instruction's ``op_name``; None before the step compiled.
        Megabytes for a real model: rendered on each call, kept nowhere."""
        return None if self._compiled is None else self._compiled.as_text()

    def memory_analysis(self):
        """XLA's ``memory_analysis()`` of the executable (argument, output,
        alias, temporary and code bytes on one device); None before the
        step compiled."""
        return None if self._compiled is None \
            else self._compiled.memory_analysis()

    @property
    def times(self) -> Dict[str, float]:
        return dict(self._times)

    def prepare(self, feeds, state, step):
        """Ensure the executable exists; args may be abstract
        (ShapeDtypeStruct) or concrete — only shapes/dtypes are read."""
        if self._compiled is None:
            self._compiled = self._compile(feeds, state, step)
        return self

    def __call__(self, feeds, state, step):
        # prepare() FIRST, as before the phase log: this frame stands above
        # trace() and lower() while a step compiles (PERF.md section 6,
        # PR 36 (d2))
        self.prepare(feeds, state, step)
        first = not self._called    # a warm call: this flag, nothing else
        t0 = time.perf_counter() if first else 0.0
        try:
            out = self._compiled(feeds, state, step)
        except ValueError:
            # the argument check runs before donation; a deleted state
            # buffer means execution STARTED and the error is real
            if not self._mesh_step or any(
                    v.is_deleted() for v in state.values()
                    if hasattr(v, "is_deleted")):
                raise
            _stats.bump("lazy_jit_fallbacks")
            if not self._fallback_recorded:
                self._fallback_recorded = True
                logger.warning(
                    "compile cache: mesh step %s… got arguments under "
                    "other shardings than it was compiled for; falling "
                    "back to lazy jit for mismatching calls",
                    (self._fp or "?")[:12])
                # the jit trace is an honest retrace of this fingerprint
                _stats.record_trace(self._fp)
            out = self._jit(feeds, state, step)
        if first:
            self._called = True
            _stats.record_phase("step/first_call", t0, time.perf_counter(),
                                fp=self._fp, label=self._label)
        return out

    # -- internals -------------------------------------------------------
    def _compile(self, feeds, state, step):
        # Timestamps and ``with`` blocks IN PLACE, no helper around the
        # three calls: JAX's lowering time moves by seconds with the Python
        # stack above it, and a kernel's cache key holds that stack
        # (PERF.md section 6, PR 30).  The annotations cost nothing without
        # a profiler session; inside one they name the compile, and the
        # step, that an idle gap of the device was spent in.
        # JAX's own duration events of this thread are the step's until
        # the three phases end (_on_jax_duration).
        cache_dir()                  # JAX's persistent cache, placed once
        fp12 = (self._fp or "")[:12]
        _jit_local.in_step = getattr(_jit_local, "in_step", 0) + 1
        try:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"pt:compile:trace:{fp12}"):
                traced = self._jit.trace(feeds, state, step)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"pt:compile:lower:{fp12}"):
                lowered = traced.lower()
            t2 = time.perf_counter()
            # the trace happened inside trace(): record it now (the retrace
            # detector fires here if this fingerprint already traced)
            _stats.record_trace(self._fp)
            hits = _stats.snapshot().get("jax_cache_hits", 0)
            with jax.profiler.TraceAnnotation(f"pt:compile:xla:{fp12}"):
                compiled = lowered.compile(
                    compiler_options=self._opts if self._opts else None)
            t3 = time.perf_counter()
        finally:
            _jit_local.in_step -= 1
        self._times = {"trace_s": t1 - t0, "lower_s": t2 - t1,
                       "compile_s": t3 - t2}
        # the three records are also entries[fp]["times"]; a cache read
        # where JAX's hit counter moved during the compile
        who = {"fp": self._fp, "label": self._label}
        _stats.record_phase("step/trace", t0, t1, **who)
        _stats.record_phase("step/lower", t1, t2, **who)
        _stats.record_phase(
            "step/xla", t2, t3, **who,
            cache_hit=_stats.snapshot().get("jax_cache_hits", 0) > hits)
        return compiled


class CompiledProgram:
    """Handle returned by ``Executor.compile``: an ahead-of-time compiled
    step variant already installed in the executor's cache, so a matching
    ``Executor.run``/``run_steps`` call executes without tracing or
    compiling.  ``run(...)`` delegates with the bound fetch list."""

    def __init__(self, executor, program, fingerprint: str, step: CachedStep,
                 fetch_names, state_keys, num_steps=None,
                 feeds_stacked=False, is_test=False):
        self._executor = executor
        self.program = program
        self.fingerprint = fingerprint
        self._step = step
        self.fetch_names = list(fetch_names)
        self.state_keys = list(state_keys)
        self.num_steps = num_steps
        self.feeds_stacked = feeds_stacked
        self.is_test = is_test

    @property
    def executor(self):
        """The executor this variant is installed in (the serving runtime
        dispatches follow-up bucket sizes through it, sharing its cache)."""
        return self._executor

    @property
    def compile_times(self) -> Dict[str, float]:
        return self._step.times

    def hlo_text(self) -> Optional[str]:
        """The optimized HLO module this variant runs, as text; every
        instruction's ``op_name`` names the Program op that lowered it
        (``CachedStep.hlo_text``)."""
        return self._step.hlo_text()

    def memory_analysis(self):
        """XLA's memory analysis of this variant's executable: bytes of
        arguments, outputs, aliases, temporaries and code on one device."""
        return self._step.memory_analysis()

    def run(self, feed=None, scope=None, return_numpy=True):
        if self.num_steps is not None:
            return self._executor.run_steps(
                self.num_steps, self.program, feed=feed,
                fetch_list=self.fetch_names, scope=scope,
                return_numpy=return_numpy, is_test=self.is_test,
                feeds_stacked=self.feeds_stacked)
        return self._executor.run(
            self.program, feed=feed, fetch_list=self.fetch_names,
            scope=scope, return_numpy=return_numpy, is_test=self.is_test)

    def __repr__(self):
        return (f"CompiledProgram(fingerprint={self.fingerprint[:12]}…, "
                f"fetches={self.fetch_names}, num_steps={self.num_steps})")
