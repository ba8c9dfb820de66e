"""What one run knows: its cell, its configuration, the devices, the
counters before and after the timed window, and what the driver observed.
Drivers fill it, metric readers read it, ``run.py`` prints from it."""
from __future__ import annotations

import contextlib
import time
import zlib
from typing import Optional

XLA_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RunContext:
    def __init__(self, args, root, cell, sizes, config, devices, peaks,
                 t_start):
        self.args = args
        self.root = root                  # the checkout
        self.cell = cell                  # workloads/<cell>.json
        self.sizes = sizes                # configs/<config>.json
        self.config = config              # configs/<config>.py
        self.devices = devices
        self.peaks = peaks                # None when rehearsing
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.before: Optional[dict] = None    # counters at end of set-up
        self.after: Optional[dict] = None     # counters after the window
        self.obs: dict = {}               # the driver's raw observations
        self.trace = None                 # trace_reduce.TraceSummary
        self.program_bytes: Optional[int] = None
        self.allocator_peak_bytes = 0
        self.detail: dict = {"marks_s": {}}   # diagnostics, last line
        self._xla_compiles = 0
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def mark(self, what: str):
        """Seconds since the start of the process when ``what`` first
        happened, for the diagnostics."""
        self.detail["marks_s"].setdefault(
            what, time.perf_counter() - self.t_start)

    # -- seeds ---------------------------------------------------------
    def seed_for(self, what: str) -> int:
        """A 31-bit seed for one use (weights, window batch, sample
        batch, arrivals ...), a pure function of ``--seed``."""
        return zlib.crc32(f"{self.args.seed}:{what}".encode()) & 0x7FFFFFFF

    @property
    def tracing(self) -> bool:
        return bool(self.args.trace) and not self.args.rehearse

    # -- counters ------------------------------------------------------
    def _on_duration(self, event: str, _secs: float, **_kw):
        if event == XLA_COMPILE_EVENT:
            self._xla_compiles += 1

    def compile_count(self) -> int:
        """XLA compile requests in this process so far (JAX's own event;
        a persistent-cache hit counts, because a trace and a lowering came
        before it), plus the program's own count of step traces."""
        from paddle_tpu import profiler
        return self._xla_compiles + \
            profiler.compile_stats().snapshot().get("traces", 0)

    def _snapshot(self) -> dict:
        from paddle_tpu import profiler
        from paddle_tpu.observability import registry
        stats = profiler.compile_stats()
        return {"t": time.perf_counter(),
                "compile": stats.snapshot(),
                "compile_seconds": stats.total_compile_seconds(),
                "compile_count": self.compile_count(),
                "registry": registry().snapshot()}

    def end_setup(self):
        """Everything before this call was set-up; the timed window
        starts now."""
        self.before = self._snapshot()
        self.setup_s = self.before["t"] - self.t_start
        self.detail["marks_s"]["setup"] = self.setup_s

    def end_window(self):
        from . import device
        self.after = self._snapshot()
        self.allocator_peak_bytes = device.allocator_peak_bytes(self.devices)

    @property
    def compiles_in_window(self) -> int:
        return self.after["compile_count"] - self.before["compile_count"]

    @property
    def memory_peak_bytes(self) -> int:
        """The larger of the allocator's peak and one execution of the
        window's program: libtpu's ``peak_bytes_in_use`` leaves out the
        executable's temporaries (PERF.md)."""
        return max(self.allocator_peak_bytes, self.program_bytes or 0)

    # -- spans ---------------------------------------------------------
    def span(self, what: str):
        """``cb:<what>`` on the profiler's clock, in a traced run."""
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"cb:{what}")
