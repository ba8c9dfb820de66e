"""Process-level runtime flags (reference: gflags registry utils/Flags.cpp:18-113
— ~40 knobs like use_gpu/trainer_count/log_period — and fluid's InitGflags,
framework/init.cc:39).

TPU-native: a typed registry with environment-variable override
(``PADDLE_TPU_<NAME>``) and CLI parsing (``parse_args``).  Framework-internal
behavior toggles (check_nan_inf, log_period, seq_bucket_multiple...) read
from here so scripts and the environment can configure them uniformly.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_registry: Dict[str, dict] = {}


def define_flag(name: str, default, help: str = "", type_=None):
    t = type_ or (type(default) if default is not None else str)
    _registry[name] = {"default": default, "help": help, "type": t,
                       "value": _from_env(name, default, t)}


def _from_env(name, default, t):
    env = os.environ.get(f"PADDLE_TPU_{name.upper()}")
    if env is None:
        return default
    if t is bool:
        return env.lower() in ("1", "true", "yes", "on")
    return t(env)


def get_flag(name: str) -> Any:
    return _registry[name]["value"]


def set_flag(name: str, value):
    if name not in _registry:
        raise KeyError(f"unknown flag {name!r}; define_flag it first")
    _registry[name]["value"] = _registry[name]["type"](value) \
        if value is not None else None


def all_flags() -> Dict[str, Any]:
    return {n: e["value"] for n, e in _registry.items()}


def parse_args(argv):
    """Consume --name=value tokens (gflags style); returns leftovers."""
    rest = []
    for tok in argv:
        if tok.startswith("--") and "=" in tok:
            name, val = tok[2:].split("=", 1)
            if name in _registry:
                set_flag(name, val)
                continue
        rest.append(tok)
    return rest


# -- the reference's knobs that still mean something on TPU ------------------
define_flag("use_tpu", True, "run on the TPU backend when present "
            "(use_gpu analog, Flags.cpp:19)")
define_flag("trainer_count", 1, "data-parallel width hint (Flags.cpp:22); "
            "prefer explicit MeshConfig(dp=...)")
define_flag("trainer_id", 0, "this process's rank (Flags.cpp:67)")
define_flag("log_period", 100, "steps between stat reports (Flags.cpp:62)")
define_flag("check_nan_inf", False,
            "post-step NaN/Inf checks (FLAGS_check_nan_inf, executor.cc:25)")
define_flag("seed", 0, "global random seed override")
define_flag("beam_size", 4, "default generation beam width (Flags.cpp:74)")
define_flag("seq_bucket_multiple", 8,
            "pad sequence batches up to a multiple of this (recompile guard)")
define_flag("init_model_path", "", "checkpoint dir to resume from "
            "(Flags.cpp:81)")
define_flag("save_dir", "", "parameter save root (v1 --save_dir)")
define_flag("validate", False,
            "run the static program verifier (paddle_tpu.analysis) before "
            "every new step variant is traced — and before its compile-"
            "cache fingerprint is computed, so an invalid program can "
            "never enter the cache.  Errors raise "
            "ProgramVerificationError with stable PT0xx codes naming the "
            "op; warnings go to warnings.warn.  Per-executor override: "
            "Executor(validate=...).  (PADDLE_TPU_VALIDATE=1)")
define_flag("executor_cache_entries", 64,
            "max compiled step variants held per Executor (LRU; evictions "
            "and dead-program sweeps count into profiler.compile_stats())")
define_flag("observe", False,
            "runtime observability (paddle_tpu.observability): per-step/"
            "pipeline telemetry into the metrics registry, XProf trace "
            "annotations on dispatches, and JSONL export when metrics_log "
            "is set.  Zero overhead and zero retraces when off "
            "(tier-1-enforced; a metrics log still gets the `phase` events "
            "of a cold start, see metrics_log).  Per-executor override: "
            "Executor(observe=...).  (PADDLE_TPU_OBSERVE=1)")
define_flag("metrics_log", "",
            "JSONL structured metrics/event log path "
            "(PADDLE_TPU_METRICS_LOG); empty = off.  Spans and step events "
            "need observe on; what made a step COLD is written whatever "
            "observe says (one `phase` event a record of "
            "profiler.compile_stats().phases(): a handful a step variant, "
            "none from a warm dispatch).  Summarize with "
            "`python -m paddle_tpu stats <log.jsonl>`")
define_flag("autotune", False,
            "replay persisted autotuner winners (paddle_tpu.tuning) at the "
            "tuned call sites: run_pipelined dispatch chunking, reader "
            "prefetch workers/buffers, serving batcher, Pallas/XLA device "
            "knobs.  Off (default): every call site uses its hand-picked "
            "default, byte-identical to an autotune-free build (tier-1 "
            "enforced).  On with no persisted record: defaults again — "
            "replay never searches.  Per-executor override: "
            "Executor(autotune=...); search via `python -m paddle_tpu "
            "tune <target>`.  (PADDLE_TPU_AUTOTUNE=1)")
