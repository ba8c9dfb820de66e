"""The JAX surface this framework leans on, in one module.

Written against the installed jax (0.9.0): ``jax.shard_map`` with
``axis_names=``/``check_vma=`` partial-manual kwargs, the abstract mesh's
per-axis ``AxisType`` for detecting a surrounding shard_map manual region,
and the ``Compiled.cost_analysis()`` / ``memory_analysis()`` facts.  What a
jax release no longer offers is repaired at the call site, not shimmed
here.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

__all__ = ["shard_map", "manual_axes",
           "executable_cost_analysis", "executable_memory_analysis"]


def shard_map(f, mesh, in_specs, out_specs, axis_names=None, check_vma=None):
    """``jax.shard_map`` with the mesh positional.  ``axis_names``: the
    mesh axes the body is manual over (every other mesh axis stays
    auto/GSPMD-managed); ``None`` for either keyword keeps jax's default."""
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = frozenset(axis_names)
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def manual_axes() -> frozenset:
    """Mesh axes currently bound manual (i.e. we are tracing inside a
    shard_map body): frozenset of names, empty when outside."""
    am = jax.sharding.get_abstract_mesh()
    return frozenset(n for n, t in zip(am.axis_names, am.axis_types)
                     if t == AxisType.Manual)


def executable_cost_analysis(compiled) -> Optional[dict]:
    """XLA cost analysis of a compiled executable, normalized to one flat
    ``{"flops": ..., "bytes_accessed": ..., ...}`` dict.

    Some backends raise or return nothing; ``None`` means "unavailable"
    — callers fall back to the static cost model, never crash.
    """
    fn = getattr(compiled, "cost_analysis", None)
    if fn is None:
        return None
    try:
        ca = fn()
    except Exception:   # backend without the analysis API
        return None
    if not isinstance(ca, dict) or not ca:
        return None
    out = {}
    for k in ("flops", "transcendentals", "bytes accessed",
              "bytes_accessed", "optimal_seconds"):
        v = ca.get(k)
        if isinstance(v, (int, float)):
            out[k.replace(" ", "_")] = float(v)
    return out or None


def executable_memory_analysis(compiled) -> Optional[dict]:
    """``Compiled.memory_analysis()`` (an opaque ``CompiledMemoryStats``)
    normalized to plain ints.  ``None`` when unavailable."""
    fn = getattr(compiled, "memory_analysis", None)
    if fn is None:
        return None
    try:
        ma = fn()
    except Exception:   # backend without the analysis API
        return None
    if ma is None:
        return None
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if isinstance(v, (int, float)):
            out[k] = int(v)
    return out or None
