"""Persistence + replay for autotuner winners.

A winner is one JSON file under ``<compile_cache.cache_dir()>/tuning/`` named
``ptat-<fingerprint>.json`` — the PR 3 compile-cache discipline applied
to configs instead of executables:

* **Keying** — :func:`record_fingerprint` hashes (format version,
  tunable name, the tunable's declared-space digest, topology, context)
  through :func:`~paddle_tpu.core.compile_cache.fingerprint_hex`, which
  folds in the jax + paddle_tpu versions, backend and device count.  A
  jax upgrade, a framework release, a different chip count/kind, or an
  edit to the tunable's declaration each produce a different fingerprint
  — the stale record is simply never found, and the call site keeps its
  default.  ``context`` is a free-form site key (e.g. a kernel shape)
  for tunables whose winner is shape-dependent.
* **Writes** — atomic tmp + ``os.replace`` (a concurrent reader never
  sees a truncated record); schema-versioned by :data:`TUNING_FORMAT`.
* **Replay** — :func:`tuned` is the ONLY surface the runtime call sites
  touch: stored winner merged over the caller's default, or the default
  object untouched.  Lookups memoize per (name, context) — including
  misses — so a training process pays at most one disk probe per call
  site, and a corrupt/foreign/schema-drifted record degrades to the
  default with a warning, never an error.
"""
from __future__ import annotations

import json
import logging
import os
import tempfile
import threading
import time
from typing import Dict, Optional

from ..core import compile_cache
from ..core.registry import get_tunable
from ..testing import lockwatch as _lw
from . import tunables as _tn

logger = logging.getLogger("paddle_tpu")

__all__ = [
    "TUNING_FORMAT", "store_dir", "record_fingerprint", "record_path",
    "save_record", "load_record", "tuned", "clear_memo", "list_records",
]

TUNING_FORMAT = 1               # bump to invalidate every stored winner
_PREFIX = "ptat-"

_lock = _lw.make_lock("tuning.store")
#: (name, context) -> record dict or None (negative lookups memoized too:
#: the zero-search-cost contract means at most ONE probe per call site)
_memo: Dict[tuple, Optional[dict]] = {}


def store_dir(base: Optional[str] = None) -> str:
    """Tuning-record directory: ``tuning/`` under the one resolved
    compile-cache directory, or under ``base`` (CLI --cache-dir, tests)."""
    d = base if base is not None else compile_cache.cache_dir()
    return os.path.join(d, "tuning")


def topology_key():
    """Device-topology fingerprint component beyond what
    ``environment_key`` already carries (backend + device count): the
    device KIND — a winner tuned on v4 must not replay on v5."""
    import jax
    devices = jax.devices()
    kind = getattr(devices[0], "device_kind", "unknown") if devices \
        else "none"
    return (str(kind), len(devices))


def record_fingerprint(name: str, context: str = "") -> str:
    entry = get_tunable(name)
    return compile_cache.fingerprint_hex(
        ("tunable", TUNING_FORMAT, name, _tn.space_digest(entry),
         topology_key(), str(context)))


def record_path(name: str, context: str = "",
                base: Optional[str] = None) -> str:
    return os.path.join(store_dir(base),
                        f"{_PREFIX}{record_fingerprint(name, context)}.json")


def save_record(name: str, config: Dict[str, object], *,
                context: str = "", base: Optional[str] = None,
                **extra) -> str:
    """Persist a winner config atomically; returns the path.  ``extra``
    (score/speedup/windows/algo/...) is stored verbatim for auditability
    — replay reads only ``config``."""
    entry = get_tunable(name)
    problems = _tn.validate_config(entry, config)
    if problems:
        raise ValueError(f"save_record({name!r}): config does not match "
                         f"the declared space: {problems}")
    d = store_dir(base)
    fp = record_fingerprint(name, context)
    payload = {
        "format": TUNING_FORMAT, "fingerprint": fp, "tunable": name,
        "context": str(context), "config": dict(config),
        "space_digest": _tn.space_digest(entry),
        "topology": list(topology_key()),
        "environment": list(compile_cache.environment_key()),
        "created": round(time.time(), 3),
        **extra,
    }
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=_PREFIX, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        path = os.path.join(d, f"{_PREFIX}{fp}.json")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    with _lock:
        # refresh every memoized view of this (name, context) — the
        # writing process should replay its own new winner
        for k in [k for k in _memo if k[0] == name and k[1] == str(context)]:
            del _memo[k]
    return path


def load_record(name: str, context: str = "",
                base: Optional[str] = None) -> Optional[dict]:
    """Read + validate the persisted record for (name, context), or None.

    Every failure mode is a MISS, never an error: missing file, unreadable
    or truncated JSON, format/fingerprint mismatch (foreign schema
    version or a hash collision), wrong tunable name, or a config the
    declared space no longer admits (schema drift).  Misses other than
    plain not-found log a warning naming the file."""
    path = record_path(name, context, base)
    try:
        with open(path) as f:
            payload = json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
        logger.warning("tuning store: unreadable record %s (%s: %s); "
                       "using defaults", path, type(e).__name__, e)
        return None
    fp = record_fingerprint(name, context)
    if not isinstance(payload, dict) \
            or payload.get("format") != TUNING_FORMAT \
            or payload.get("fingerprint") != fp \
            or payload.get("tunable") != name \
            or not isinstance(payload.get("config"), dict):
        logger.warning("tuning store: stale/foreign record %s "
                       "(format/fingerprint mismatch); using defaults",
                       path)
        return None
    problems = _tn.validate_config(get_tunable(name), payload["config"])
    if problems:
        logger.warning("tuning store: record %s no longer matches the "
                       "declared space (%s); using defaults", path,
                       "; ".join(problems))
        return None
    return payload


def tuned(name: str, default: Dict[str, object], *, context: str = "",
          base: Optional[str] = None) -> Dict[str, object]:
    """THE replay lookup: the persisted winner for (name, context) merged
    over ``default``, or ``default`` itself (the same object, untouched)
    when no valid record exists.

    Only keys present in ``default`` are overridden — a call site that
    consumes a subset of the tunable's params never receives foreign
    keys.  Memoized per (name, context): one disk probe per process,
    zero search cost always.  Call sites reach this lazily and only
    under an autotune opt-in (``Executor(autotune=...)`` / the
    ``autotune`` flag), so the off path never imports this package.
    """
    # base is part of the memo key (tests probe several stores in one
    # process)
    key = (name, str(context), base)
    with _lock:
        hit = key in _memo
        payload = _memo.get(key)
    if not hit:
        payload = load_record(name, context, base)
        with _lock:
            _memo[key] = payload
        if payload is not None:
            # cold path, once per (site, process): the replay event makes
            # a tuned run's provenance visible to `paddle_tpu stats`
            from ..observability import emit_event, inc_counter
            inc_counter("tuning/replays")
            emit_event("tuning", event="replay", tunable=name,
                       context=str(context), config=payload["config"])
    if payload is None:
        return default
    cfg = payload["config"]
    return {k: cfg.get(k, v) for k, v in default.items()}


def clear_memo():
    """Forget memoized lookups (tests; also after writing new records
    from a search so the same process replays them)."""
    with _lock:
        _memo.clear()


def list_records(base: Optional[str] = None):
    """(path, payload) for every readable record in the store."""
    d = store_dir(base)
    if not os.path.isdir(d):
        return []
    out = []
    for fn in sorted(os.listdir(d)):
        if not (fn.startswith(_PREFIX) and fn.endswith(".json")):
            continue
        path = os.path.join(d, fn)
        try:
            with open(path) as f:
                out.append((path, json.load(f)))
        except (OSError, json.JSONDecodeError, UnicodeDecodeError):
            out.append((path, None))
    return out
