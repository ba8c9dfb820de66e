"""The device as JAX reports it, and how full it got."""
from __future__ import annotations

import sys
from typing import Optional


def require_devices(chips: int, rehearse: bool):
    """The ``chips`` devices this cell runs on, or no run at all: without
    ``--rehearse`` the default backend has to be a TPU (whose kind
    ``run.py`` then looks up in ``lib/peaks.py``); with it, the CPU."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        if platform != "cpu":
            sys.exit(f"chipbench: --rehearse is the CPU mode (set "
                     f"JAX_PLATFORMS=cpu); the default backend here is "
                     f"{platform!r}")
    elif platform != "tpu":
        sys.exit(f"chipbench: needs a TPU; JAX's default backend here is "
                 f"{platform!r} ({devices[0].device_kind}).  The only CPU "
                 f"mode is --rehearse, which reports counts and no times.")
    if len(devices) < chips:
        sys.exit(f"chipbench: the cell asks for {chips} device(s), JAX "
                 f"found {len(devices)}")
    return devices[:chips]


def program_bytes(compiled_program) -> Optional[int]:
    """Bytes one execution of an ``Executor.compile`` result holds on a
    device, from XLA's ``memory_analysis()``: arguments + outputs - aliased
    + temporaries + code.  ``CompiledProgram`` does not expose its
    executable, so this reaches for it by attribute and returns None when
    it is not there (PERF.md lists the accessor the program should grow).
    """
    step = getattr(compiled_program, "_step", None)
    exe = getattr(step, "_compiled", None)
    if exe is None or not hasattr(exe, "memory_analysis"):
        return None
    m = exe.memory_analysis()
    if m is None:
        return None
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes
               + m.generated_code_size_in_bytes)


def allocator_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` on the fullest device (0 where the backend
    keeps no statistics, as the CPU does)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
