"""NaN/Inf provenance: eagerly bisect a failing step to the op that
first produced a non-finite value.

``Executor(check_nan_inf=True)`` detects non-finites with in-graph finite
flags (core/executor.py ``_nan_localize`` — executor.cc:116-124 analog),
which names a producer by PROGRAM order.  This module goes one step
further on the failure path: it re-runs the exact failing step EAGERLY —
same feeds, same pre-step state, same step-counter-derived PRNG key — one
``run_op`` at a time, checking every produced value on the host, so the
diagnostic carries the first non-finite producer in EXECUTION order with
shapes and NaN/Inf element counts.  For programs with a ``backward`` op
the forward slice is walked eagerly first (forward producers bisect
exactly); if the forward stays finite, the gradient pass runs as a whole
and each ``<p>@GRAD`` is checked by name.

One-shot and failure-path only: the bisect costs an extra eager step, paid
exactly once, after a step already failed.
"""
from __future__ import annotations

import logging
from typing import Dict, Optional

logger = logging.getLogger("paddle_tpu")

__all__ = ["bisect_step", "format_diagnosis", "make_eager_context"]


def make_eager_context(executor, program, feed_arrays, state, step: int,
                       is_test: bool = False):
    """``(env, ctx, bw_idx)`` for an eager per-op replay of one step: the
    executor's own prologue (core/executor.py ``_step_prologue``, the one
    the compiled step traces) on concrete arrays, plus the position of the
    ``backward`` op.  Shared by the NaN bisect here and the per-op
    profiler (``observability.opprof``) so both replay at the SAME
    precision the compiled step computed at — a diagnosis or a per-op
    timing taken at another precision would describe a different
    computation."""
    import jax.numpy as jnp

    env, ctx = executor._step_prologue(
        executor._step_options(), program,
        {k: jnp.asarray(v) for k, v in feed_arrays.items()},
        {k: jnp.asarray(v) for k, v in state.items()}, step, is_test)
    bw_idx = next((i for i, op in enumerate(program.global_block().ops)
                   if op.type == "backward"), None)
    return env, ctx, bw_idx


def _nonfinite(value) -> Optional[Dict[str, int]]:
    """{'nan': n, 'inf': n} when ``value`` holds non-finite floats."""
    import jax.numpy as jnp
    import numpy as np
    if not (hasattr(value, "dtype")
            and jnp.issubdtype(value.dtype, jnp.floating)):
        return None
    a = np.asarray(value)
    if np.all(np.isfinite(a)):
        return None
    return {"nan": int(np.isnan(a).sum()), "inf": int(np.isinf(a).sum())}


def _check_outputs(op, op_index, env, phase) -> Optional[dict]:
    for slot, names in op.outputs.items():
        for name in names:
            if not env.has(name):
                continue
            bad = _nonfinite(env.get(name))
            if bad is not None:
                value = env.get(name)
                return {
                    "op_index": op_index, "op_type": op.type, "var": name,
                    "slot": slot, "phase": phase,
                    "shape": list(getattr(value, "shape", ())),
                    "dtype": str(getattr(value, "dtype", "?")),
                    "nan_count": bad["nan"], "inf_count": bad["inf"],
                }
    return None


def bisect_step(executor, program, feed_arrays, state, step: int,
                is_test: bool = False) -> Optional[dict]:
    """Eagerly re-run one step and return a provenance dict for the first
    non-finite producer, or None when the re-run stays finite (or the
    bisect itself fails — it must never mask the original error).

    ``state`` must be the PRE-step values — check_nan_inf step variants
    compile without buffer donation (core/compile_cache.CachedStep
    ``donate=False``) exactly so these stay valid on the failure path.
    """
    try:
        return _bisect(executor, program, feed_arrays, state, step, is_test)
    except Exception as e:
        logger.warning("NaN-provenance bisect failed (%s: %s); reporting "
                       "the in-graph localization only",
                       type(e).__name__, e)
        return None


def _bisect(executor, program, feed_arrays, state, step, is_test):
    from ..core.executor import _run_backward, grad_var_name, run_op

    ops = program.global_block().ops
    # the shared context replicates the compiled step's input dtype
    # coercion — a non-finite that arose at the compiled precision must
    # reproduce at the SAME precision, or the bisect could blame the
    # wrong op
    env, ctx, bw_idx = make_eager_context(
        executor, program, feed_arrays, state, step, is_test)

    # a poisoned INPUT is not an op's fault — report it as the feed/state
    # (checked AFTER the casts: what the compiled step actually consumed)
    for name, value in env.local.items():
        bad = _nonfinite(value)
        if bad is not None:
            return {"op_index": -1, "op_type": None, "var": name,
                    "slot": None,
                    "phase": "feed" if name in feed_arrays else "state",
                    "shape": list(getattr(value, "shape", ())),
                    "dtype": str(getattr(value, "dtype", "?")),
                    "nan_count": bad["nan"], "inf_count": bad["inf"]}

    for idx, op in enumerate(ops):
        if idx == bw_idx:
            # the forward slice (indices < bw_idx) already ran eagerly,
            # per-op checked, in earlier iterations — here only the
            # gradient pass remains; it runs whole (grads come from ONE
            # value_and_grad) and each produced @GRAD is checked by name
            _run_backward(ops[:bw_idx], op, env, ctx)
            for pname in op.attrs.get("params", ()):
                gname = grad_var_name(pname)
                if not env.has(gname):
                    continue
                bad = _nonfinite(env.get(gname))
                if bad is not None:
                    g = env.get(gname)
                    return {"op_index": idx, "op_type": "backward",
                            "var": gname, "slot": None, "phase": "backward",
                            "shape": list(getattr(g, "shape", ())),
                            "dtype": str(getattr(g, "dtype", "?")),
                            "nan_count": bad["nan"],
                            "inf_count": bad["inf"]}
            continue
        run_op(op, env, ctx)
        phase = "forward" if bw_idx is None or idx < bw_idx else "update"
        found = _check_outputs(op, idx, env, phase)
        if found is not None:
            return found
    return None


def format_diagnosis(diag: dict) -> str:
    """One-line human rendering of a provenance dict."""
    where = (f"op #{diag['op_index']} {diag['op_type']!r}"
             if diag.get("op_type") else diag["phase"])
    return (f"first non-finite value produced by {where} -> var "
            f"{diag['var']!r} (phase {diag['phase']}, shape "
            f"{diag['shape']}, dtype {diag['dtype']}, "
            f"{diag['nan_count']} NaN / {diag['inf_count']} Inf elements)")
