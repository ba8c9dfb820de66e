"""Operator implementation registry.

The analog of the reference's OpRegistry/OpInfoMap
(paddle/framework/op_registry.h:148-290, op_info.h:68) — but an "op kernel"
here is a *JAX lowering*: a Python function that maps traced ``jax.Array``
inputs to outputs using jnp/lax (and Pallas for hand-tuned kernels).  There is
exactly one kernel per op — XLA owns device placement, layout, and dtype
specialization, so the reference's OpKernelType dispatch key
(op_kernel_type.h:27-73) and DataTransform machinery (data_transform.h:37) are
unnecessary.

Because gradients are derived with ``jax.vjp`` over these lowerings, there are
no separate grad-op registrations (contrast REGISTER_OP's auto grad-op maker,
op_registry.h:148).

Implementation signature::

    @register_op("elementwise_add")
    def _add(ctx, ins, attrs):
        return {"Out": ins["X"][0] + ins["Y"][0]}

* ``ins``  — dict slot -> list of input values (arrays / nested, per OpDesc).
* return   — dict slot -> value or list of values; normalized by the executor.
* ``ctx``  — LoweringContext: rng keys, sub-block interpretation, env access.

What a lowering may state beyond its outputs: that it is row-wise
(:func:`register_rowwise`, so ``rnn`` may move it out of its scan), and, of
one output, that it is a softmax of known logits (``ctx.env.note_softmax``;
``core.executor.Env.softmax_of`` has who writes, carries and reads that note:
``softmax`` -> ``reshape`` / ``rnn`` -> ``cross_entropy``).
"""
from __future__ import annotations

from typing import Callable, Dict, NamedTuple, Optional

_OP_IMPLS: Dict[str, Callable] = {}
_SHAPE_FNS: Dict[str, Callable] = {}
_SHARD_FNS: Dict[str, Callable] = {}
_ROWWISE_FNS: Dict[str, Callable] = {}
_TUNABLES: Dict[str, dict] = {}


def register_op(*names: str):
    """Register a lowering for one or more op type names."""

    def deco(fn):
        for n in names:
            if n in _OP_IMPLS:
                raise ValueError(f"op {n!r} registered twice")
            _OP_IMPLS[n] = fn
        return fn

    return deco


def get_op_impl(name: str) -> Callable:
    try:
        return _OP_IMPLS[name]
    except KeyError:
        raise NotImplementedError(
            f"No lowering registered for op type {name!r}. "
            f"Registered: {sorted(_OP_IMPLS)[:20]}...") from None


def has_op(name: str) -> bool:
    return name in _OP_IMPLS


def registered_ops():
    return sorted(_OP_IMPLS)


def register_shape_fn(*names: str):
    """Register a static shape/dtype inference rule for one or more op type
    names — the build-time companion of :func:`register_op` and the analog
    of the reference's per-op ``InferShape`` (operator.h InferShapeContext,
    run inside OpDesc construction by the C++ desc layer).

    A rule has the signature ``fn(op, ins, attrs) -> {slot: VarInfo|...}``
    where ``ins`` maps input slot -> list of
    :class:`paddle_tpu.analysis.shape_infer.VarInfo`; it must raise
    :class:`paddle_tpu.analysis.shape_infer.ShapeError` when the inputs are
    statically incompatible.  Rules run at validation time only — never
    inside the stepped hot path (core/executor.py memoizes per program
    version/signature).

    Ops without a rule must be listed in
    ``paddle_tpu.analysis.shape_infer.SHAPE_INFER_ALLOWLIST``; tier-1
    enforces that every registered op has exactly one of the two
    (tests/test_analysis.py), so inference coverage can only grow.
    """

    def deco(fn):
        for n in names:
            if n in _SHAPE_FNS:
                raise ValueError(f"shape fn for op {n!r} registered twice")
            _SHAPE_FNS[n] = fn
        return fn

    return deco


def get_shape_fn(name: str) -> Optional[Callable]:
    return _SHAPE_FNS.get(name)


def has_shape_fn(name: str) -> bool:
    return name in _SHAPE_FNS


def registered_shape_fns():
    return sorted(_SHAPE_FNS)


def register_shard_fn(*names: str):
    """Register a sharding-propagation rule for one or more op type names —
    the distributed companion of :func:`register_shape_fn`, consumed by the
    auto-sharding planner (``paddle_tpu.analysis.shard_prop``).

    A rule has the signature ``fn(op, ins, attrs) -> {out_slot: spec}``
    where ``ins`` maps input slot -> list of
    :class:`paddle_tpu.analysis.shard_prop.ShardInfo` (current per-dim
    sharding + static shape) and each returned spec is a tuple with one
    entry per output dim (``None`` = replicated, an axis name, or a tuple
    of axis names).  Rules raise
    :class:`paddle_tpu.analysis.shard_prop.ShardConflict` when the inputs
    carry shardings the op cannot realize without a reshard (surfaced as
    PT041).  A rule built by the helper factories in ``shard_prop`` also
    carries a ``.backward`` attribute used by the reverse propagation
    sweep; hand-written rules may attach one.

    Ops without a rule are propagation blind spots: a sharded value
    flowing into one is reported PT042 and treated as replicated
    downstream.  Rules run at planning/validation time only — never in
    the stepped hot path.
    """

    def deco(fn):
        for n in names:
            if n in _SHARD_FNS:
                raise ValueError(f"shard fn for op {n!r} registered twice")
            _SHARD_FNS[n] = fn
        return fn

    return deco


class Operand(NamedTuple):
    """One input of an op as a row-wise rule sees it: ``rows`` is whether
    its leading axis is the rows the question is about; ``shape`` is what
    is known of its shape (always a rank; a dim may be -1)."""
    rows: bool
    shape: tuple


def register_rowwise(*names: str):
    """Declare when an op is ROW-WISE over its leading axis: row ``i`` of
    every output is a function of row ``i`` of the operands that have rows
    and of the operands that have none (weights, biases), so the op may be
    given any number of rows at once — the rows of every step of a
    recurrence, for one (``ops/control_flow_ops.py _rnn`` moves such ops
    out of its scan).  Declaring an op also states that its lowering is a
    pure function of its inputs and attrs: no random draw (``ctx.rng``),
    no side effect, no ``sub_block``, no ``@LEN`` companion read or
    written.  An op without a rule is never moved.

    A rule has the signature ``fn(attrs, ins) -> bool`` where ``ins`` maps
    input slot -> list of :class:`Operand`; it answers for these attrs and
    these operands, and says False when it cannot tell.
    """

    def deco(fn):
        for n in names:
            if n in _ROWWISE_FNS:
                raise ValueError(f"row-wise rule for op {n!r} registered "
                                 f"twice")
            _ROWWISE_FNS[n] = fn
        return fn

    return deco


def get_rowwise_fn(name: str) -> Optional[Callable]:
    return _ROWWISE_FNS.get(name)


def rows_of_one_rank(attrs, ins):
    """The row-wise rule of an op that is elementwise over all its inputs:
    each has rows, and all have one rank (none is broadcast along them)."""
    operands = [o for slot in ins.values() for o in slot]
    return all(o.rows for o in operands) and \
        len({len(o.shape) for o in operands}) == 1


def register_tunable(name: str, *, space: Dict[str, tuple],
                     default: Dict[str, object],
                     description: str = "") -> dict:
    """Declare a named performance knob with a typed search space — the
    autotuner companion of :func:`register_shape_fn`/:func:`register_shard_fn`,
    declared NEXT TO the implementation whose behavior the knob controls
    and consumed by ``paddle_tpu.tuning`` (registry browse, search-space
    enumeration, persisted-winner validation).

    * ``name`` — namespaced ``<subsystem>/<knob>`` id (the persistence key
      component and the ``tuned(name, default)`` lookup key).  Must be a
      string LITERAL at the call site: tests/test_repo_lint.py runs the
      same duplicate-name AST scan + live-registry agreement gate as the
      op/shape/shard registries.
    * ``space`` — ``{param: (candidate, ...)}`` finite typed axes; the
      grid / successive-halving searches enumerate their product.
    * ``default`` — the config shipped today, one value per axis, each a
      member of its axis.  ``tuned(name, default)`` returns exactly this
      object when no persisted winner exists — the byte-identical-when-
      untuned contract pinned by tier-1.

    Registering is declaration only: nothing here imports the tuning
    package, so training paths that never opt in never load it
    (lazy-import lint, tests/test_repo_lint.py).
    """
    if name in _TUNABLES:
        raise ValueError(f"tunable {name!r} registered twice")
    if "/" not in name:
        raise ValueError(f"tunable {name!r} is not namespaced (sub/name)")
    if not space:
        raise ValueError(f"tunable {name!r}: empty search space")
    if set(default) != set(space):
        raise ValueError(
            f"tunable {name!r}: default keys {sorted(default)} != space "
            f"axes {sorted(space)}")
    norm = {}
    for param, values in space.items():
        values = tuple(values)
        if not values:
            raise ValueError(f"tunable {name!r}: axis {param!r} is empty")
        if len(set(values)) != len(values):
            raise ValueError(
                f"tunable {name!r}: axis {param!r} has duplicate values")
        if default[param] not in values:
            raise ValueError(
                f"tunable {name!r}: default {param}={default[param]!r} is "
                f"not in its axis {values} — the search must be able to "
                f"re-select the shipped config")
        norm[param] = values
    entry = {"name": name, "space": norm, "default": dict(default),
             "description": description}
    _TUNABLES[name] = entry
    return entry


def get_tunable(name: str) -> dict:
    try:
        return _TUNABLES[name]
    except KeyError:
        raise KeyError(
            f"no tunable registered under {name!r}; registered: "
            f"{sorted(_TUNABLES)}") from None


def resolve_tuned(name: str, default: Dict[str, object],
                  autotune: Optional[bool] = None) -> Dict[str, object]:
    """Call-site replay of a persisted tunable winner — the shared form
    of the per-module resolution copies (reader prefetch, serving
    batcher, executor dispatch, sparse session).  Returns ``default``
    UNCHANGED (the SAME object — the byte-identical-when-untuned
    contract pinned by tier-1) unless
    autotuning is on, in which case the persisted winner for ``name``
    replaces it.  ``autotune=None`` consults the global ``autotune``
    flag; an explicit bool overrides it (the per-instance opt-ins).
    The tuning package loads lazily and ONLY on the opted-in path
    (repo-lint lazy-import gate)."""
    if autotune is None:
        try:
            from .. import flags
            autotune = bool(flags.get_flag("autotune"))
        except KeyError:
            autotune = False
    if not autotune:
        return default
    from ..tuning.store import tuned
    return tuned(name, default)


def has_tunable(name: str) -> bool:
    return name in _TUNABLES


def registered_tunables():
    return sorted(_TUNABLES)


def get_shard_fn(name: str) -> Optional[Callable]:
    return _SHARD_FNS.get(name)


def has_shard_fn(name: str) -> bool:
    return name in _SHARD_FNS


def registered_shard_fns():
    return sorted(_SHARD_FNS)
