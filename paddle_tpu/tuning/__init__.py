"""Persistent autotuner: searched-and-cached configs for kernels, XLA
flags, and host-side pipeline/serving knobs.

Three layers (ROADMAP item 3 generalized from the PR 1 one-off VMEM
sweep into infrastructure):

* :mod:`.tunables` — the registry view.  Subsystems DECLARE knobs next
  to their implementation via ``core.registry.register_tunable`` (the
  ``register_shape_fn`` pattern; same repo-lint AST + live-registry
  gates) — dispatch chunking in ``core/executor.py``, reader prefetch in
  ``reader/pipeline.py``, the serving batcher in ``serving/server.py``,
  Pallas block configs and the scoped-VMEM XLA flag beside their
  kernels.  Declaring never imports this package.
* :mod:`.search` — grid + successive-halving searches under the PR 2
  measurement discipline (warmup discard, median of windows, paired
  alternating A/B with median-of-pair-ratios) and a NOISE GATE that
  refuses to declare a winner inside the container's demonstrated jitter
  band; per-trial fault containment (a raising or overrunning config is
  a recorded ``failed``/``timeout`` trial, never a crashed search).
* :mod:`.store` — winners persisted as JSON under
  ``<compile_cache.cache_dir()>/tuning/`` keyed by the PR 3 content-
  fingerprint scheme extended with the tunable's schema digest and the
  device topology; ``tuned(name, default)`` replays them at trace time
  with zero search cost — and returns the default untouched when no
  record exists, so an autotune-free run is byte-identical to today.

Entry points: ``python -m paddle_tpu tune <target> [--budget N]``,
``Executor(autotune=True)`` / ``Trainer.train(autotune=True)`` / the
``autotune`` flag (replay opt-ins), :mod:`.targets` (built-in
measurement workloads), ``benchmark/autotune.py`` (the committed
tuned-vs-default A/B).

This package is imported LAZILY everywhere outside itself (tier-1 lint):
training paths that never opt in never load it.
"""
from .search import (SearchResult, Trial, grid_search,  # noqa: F401
                     paired_ab, pending_stub, successive_halving,
                     time_windows, tune)
from .store import (TUNING_FORMAT, clear_memo, list_records,  # noqa: F401
                    load_record, record_fingerprint, save_record, tuned)
from .tunables import (get_tunable, grid_configs,  # noqa: F401
                       has_tunable, register_tunable,
                       registered_tunables, space_digest, validate_config)

__all__ = [
    "register_tunable", "get_tunable", "has_tunable",
    "registered_tunables", "grid_configs", "space_digest",
    "validate_config",
    "Trial", "SearchResult", "time_windows", "grid_search",
    "successive_halving", "paired_ab", "tune", "pending_stub",
    "TUNING_FORMAT", "tuned", "save_record", "load_record",
    "record_fingerprint", "list_records", "clear_memo",
]
