"""Tunable-registry helpers: enumeration, config validation, schema digests.

The registry itself lives in :mod:`paddle_tpu.core.registry`
(``register_tunable``, beside ``register_shape_fn``/``register_shard_fn``)
so subsystems can DECLARE knobs next to their implementation without
importing this package — ``import paddle_tpu`` never loads the autotuner
(lazy-import lint, tests/test_repo_lint.py).  This module is the
autotuner's view of those declarations:

* :func:`grid_configs` — enumerate a tunable's full config grid in a
  deterministic order (the search engine's candidate source);
* :func:`validate_config` — check a (possibly deserialized) config
  against the declared space, so a persisted winner whose schema drifted
  falls back to defaults instead of injecting a foreign value;
* :func:`space_digest` — content hash of the declared space + default:
  the tunable-schema component of every persistence fingerprint.  Any
  edit to a tunable's axes or defaults invalidates its stored winners.
"""
from __future__ import annotations

import hashlib
import itertools
from typing import Dict, Iterator, List

from ..core.registry import (get_tunable, has_tunable,  # noqa: F401
                             register_tunable, registered_tunables)

__all__ = [
    "register_tunable", "get_tunable", "has_tunable",
    "registered_tunables", "grid_configs", "space_size",
    "validate_config", "space_digest", "describe",
]


def space_size(entry: dict) -> int:
    n = 1
    for values in entry["space"].values():
        n *= len(values)
    return n


def grid_configs(entry: dict) -> Iterator[Dict[str, object]]:
    """Every config in the declared space, deterministic order (sorted
    param names, axis order as declared), DEFAULT FIRST — a budget-capped
    search always re-evaluates the shipped config, so 'winner' is never
    an artifact of the default falling outside the cap."""
    params = sorted(entry["space"])
    default = entry["default"]
    yield dict(default)
    for combo in itertools.product(*(entry["space"][p] for p in params)):
        cfg = dict(zip(params, combo))
        if cfg != default:
            yield cfg


def validate_config(entry: dict, config: Dict[str, object]) -> List[str]:
    """Problems with ``config`` against the declared space ([] = valid).
    Used on persisted records at replay time: any problem means the
    record predates a schema change and must not be applied."""
    problems = []
    for param in entry["space"]:
        if param not in config:
            problems.append(f"missing param {param!r}")
    for param, value in config.items():
        axis = entry["space"].get(param)
        if axis is None:
            problems.append(f"unknown param {param!r}")
        elif value not in axis:
            problems.append(f"{param}={value!r} not in declared axis "
                            f"{axis}")
    return problems


def space_digest(entry: dict) -> str:
    """Schema-version digest: space axes + defaults.  Folded into the
    persistence fingerprint, so editing a tunable's declaration orphans
    its stored winners (they fall back to defaults silently)."""
    # "host" stands where a declaration's side once did (every tunable is
    # host-side): the digests, and so the winners already stored, hold
    payload = repr((entry["name"], "host",
                    tuple(sorted((p, tuple(v))
                          for p, v in entry["space"].items())),
                    tuple(sorted(entry["default"].items()))))
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def describe(name: str) -> str:
    """One human-readable block for the CLI's registry table."""
    e = get_tunable(name)
    lines = [e["name"]]
    if e["description"]:
        lines.append(f"  {e['description']}")
    for p in sorted(e["space"]):
        lines.append(f"  {p}: {list(e['space'][p])} (default "
                     f"{e['default'][p]!r})")
    return "\n".join(lines)
