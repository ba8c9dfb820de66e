"""Profiling & timing utilities.

Reference analogs: v1 `Stat`/`REGISTER_TIMER` per-layer timers
(utils/Stat.h:63,114,230 printed per log period) and fluid's `cuda_profiler`
nvprof context manager (fluid/profiler.py:19-52).  TPU-native: jax.profiler
traces (viewable in TensorBoard/XProf) + host-side step timers.

This module is the human-facing surface of the observability layer
(paddle_tpu.observability): :func:`report` renders the merged StatSet +
CompileStats + Metrics view, :func:`metrics_snapshot` the structured one.

The StatSet (:class:`Stat`, :func:`timer`, :func:`global_stat`) is for a
user's own code, as ``REGISTER_TIMER`` was.  The program itself times
nothing into it: what a cold start costs (the package's import, a step's
enter / trace / lower / XLA-or-cache-read / first call, a mesh's state
placement) goes into the phase log of :func:`compile_stats`
(``compile_stats().phases()``, ``phase_totals()``; names in
``core/compile_cache.py PHASE_NAMES``), and a dispatch's time into the
metrics registry.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict

import jax

_trace_lock = threading.Lock()
_trace_depth = 0
_trace_started = False


@contextlib.contextmanager
def profiler(output_dir: str = "/tmp/paddle_tpu_trace", state=None,
             sorted_key=None):
    """Trace the enclosed steps with jax.profiler (cuda_profiler analog).

    ``state`` and ``sorted_key`` are accepted for reference API
    compatibility (fluid/profiler.py took 'GPU'/'total' etc.) and are
    IGNORED: jax.profiler always traces both host and device, and sorting
    belongs to the TensorBoard/XProf viewer, not the collector.

    Reentrant: nested scopes are no-op inner scopes — one trace session
    spans the outermost ``with`` (jax.profiler.start_trace raises if a
    trace is already active, so without this guard nesting crashed).
    """
    del state, sorted_key            # reference-compat, ignored (see doc)
    global _trace_depth, _trace_started
    with _trace_lock:
        _trace_depth += 1
        outermost = _trace_depth == 1
    if outermost:
        try:
            jax.profiler.start_trace(output_dir)
            with _trace_lock:
                _trace_started = True
        except BaseException:
            with _trace_lock:
                _trace_depth -= 1
            raise
    try:
        yield
    finally:
        # the LAST exiter stops the session (overlapping scopes from
        # different threads ride one session; outermost-exits-first must
        # not kill the trace under a still-active inner scope)
        with _trace_lock:
            _trace_depth -= 1
            stop = _trace_depth == 0 and _trace_started
            if stop:
                _trace_started = False
        if stop:
            jax.profiler.stop_trace()


cuda_profiler = profiler  # reference-name alias


class Stat:
    """Accumulating named timer (utils/Stat.h StatSet analog).

    Thread-safe: pipeline worker threads and the run_pipelined staging
    thread time into the same instance as the dispatch thread.  A
    ``reset()`` racing a live ``timer()`` scope is well-defined — the
    in-flight scope records into the fresh epoch when it closes, and
    ``report()`` renders a consistent snapshot either way."""

    def __init__(self):
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = collections.defaultdict(float)
        self._counts: Dict[str, int] = collections.defaultdict(int)

    @contextlib.contextmanager
    def timer(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._totals[name] += dt
                self._counts[name] += 1

    def report(self) -> str:
        with self._lock:
            totals = dict(self._totals)
            counts = dict(self._counts)
        lines = ["======= StatSet ======="]
        for name in sorted(totals, key=lambda n: -totals[n]):
            tot = totals[name]
            cnt = max(counts.get(name, 0), 1)
            lines.append(f"  {name}: total={tot*1e3:.2f}ms count={cnt} "
                         f"avg={tot/cnt*1e3:.3f}ms")
        return "\n".join(lines)

    def reset(self):
        with self._lock:
            self._totals.clear()
            self._counts.clear()


_global_stat = Stat()


def global_stat() -> Stat:
    return _global_stat


@contextlib.contextmanager
def timer(name: str):
    """REGISTER_TIMER analog on the global StatSet."""
    with _global_stat.timer(name):
        yield


# ---------------------------------------------------------------------------
# Compile-time telemetry (core/compile_cache.py)
# ---------------------------------------------------------------------------
def compile_stats():
    """The global :class:`~paddle_tpu.core.compile_cache.CompileStats`:
    per-fingerprint trace/lower/compile wall times, cache hit/miss/evict
    counters, and the retrace detector
    (``compile_stats().assert_no_retrace()``).  The compile-time analog of
    :func:`global_stat` — a cold start's cost lives here, not in step
    timers."""
    from .core import compile_cache
    return compile_cache.stats()


def compiled_hlo_text(fp_prefix: str):
    """The optimized HLO text of the live compiled step whose fingerprint
    starts with ``fp_prefix`` — e.g. the 12 characters of a trace's
    ``pt:run_steps:<fp12>`` annotation — or None when no such step is
    alive.  Each instruction's ``op_name`` carries the
    ``pt.<op_type>:<block>.<position>`` scope of the Program op that
    lowered it, which is how device time is read per op."""
    from .core import compile_cache
    return compile_cache.compiled_hlo_text(fp_prefix)


def compile_report() -> str:
    """Human-readable compile telemetry (StatSet-style report)."""
    return compile_stats().report()


# ---------------------------------------------------------------------------
# Merged observability surface (paddle_tpu.observability)
# ---------------------------------------------------------------------------
def metrics_snapshot() -> dict:
    """Structured merged snapshot: registry metrics + compile counters +
    per-device memory (see observability.export.metrics_snapshot)."""
    from .observability import metrics_snapshot as _snap
    return _snap()


def report() -> str:
    """ONE merged human-readable view: host-side StatSet timers (when any
    ran), compile telemetry with the phase log's totals, and the
    observability metrics registry — the v1
    ``printAllStatus`` every ``log_period`` analog (the trainer emits this
    via observability.maybe_periodic_report)."""
    from . import observability
    parts = [compile_report(), observability.report()]
    timers = _global_stat.report()
    if "\n" in timers:            # more than the header: a timer ran
        parts.insert(0, timers)
    return "\n".join(parts)
