"""Mixture-of-Experts op lowering — the Program-level path to expert
parallelism over the 'ep' mesh axis.

GShard-style dense formulation: routing is einsums over a [T, E, C]
dispatch tensor (parallel/moe.py), and expert-parallelism is expressed as
SHARDING CONSTRAINTS, not hand-written collectives — when the lowering
context carries a mesh whose 'ep' axis is >1 (ShardedExecutor), the
[E, C, D] expert batches are constrained to P('ep', ...) matching the
P('ep', ...)-sharded expert weights, and GSPMD inserts the all-to-all
each way (exactly how GShard itself drove the XLA partitioner).  On a
single device the same graph runs constraint-free with identical math —
which is what the equivalence test asserts.

With ``capacity_factor`` None the op takes the DROPLESS lowering instead
(``_dropless``): no token is ever dropped and nothing has a capacity.  The
assignments are sorted by expert, their rows gathered, the experts run as
grouped products over the sorted rows (``ops/pallas_kernels.py
grouped_matmul``, ``gated_grouped_matmul``) and the results are weighted
and gathered back; work and memory go with the N * top_k routed rows.  It is the lowering of today's
sparse-expert decoders (top-8 of 64 and the like), where a [T, E, C]
dispatch tensor cannot be held.  Which of the two ran is counted at trace
time as ``route/moe:{dropless,capacity}`` in ``profiler.compile_stats()``.

Reference capability frame: the reference never shipped MoE; nearest
ancestors are per-layer device placement (ParallelNeuralNetwork.cpp) and
the sparse-update machinery (SelectedRows).  This is capability-forward
surface the ep mesh axis exists for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import compile_cache
from ..core.registry import register_op

_ACTS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "swish": jax.nn.swish,
    "silu": jax.nn.silu,
}

# rows of one grouped-product tile (a sublane multiple): every expert's rows
# are padded to whole tiles, half a tile on average, so small tiles waste
# little; 128 is the MXU's own edge on the v5e
ROW_TILE = 128


@jax.custom_vjp
def _gather_rows(x, index, readers):
    """``x[index]`` (zeros where ``index`` is past the end).  ``readers``
    [rows of x, r] lists, for each row of ``x``, the output rows that read
    it (past the end: none), so the gradient is a gather and a sum too: no
    scatter-add, the same bits every run."""
    return jnp.take(x, index, axis=0, mode="fill", fill_value=0)


def _gather_rows_fwd(x, index, readers):
    return _gather_rows(x, index, readers), readers


def _gather_rows_bwd(readers, g):
    # the r readers outermost, [r, rows, D]: a second-minor dimension of 4
    # would pad to a sublane tile of 8 and move twice the bytes
    return (jnp.take(g, readers.T, axis=0, mode="fill", fill_value=0)
            .sum(axis=0), None, None)


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _dropless(xt, gate_w, w_gate, w_up, w_down, top_k, act,
              scoring="softmax", select_bias=None, renormalize=False,
              routed_scale=1.0, expert_offset=0):
    """The sorted lowering on rows ``xt`` [N, D]: (out [N, D], aux, z).

    route: router product and scores in float32 at HIGHEST precision
    (the choice of experts is discontinuous in the logits).  ``softmax``:
    the ``top_k`` largest probabilities as weights.  ``sigmoid``: the scores
    are sigmoid(logits), the chosen are the ``top_k`` largest of score +
    ``select_bias`` (no gradient; the bias takes part in the CHOICE alone)
    and the weights the chosen experts' scores.  ``renormalize`` divides a
    token's weights by their sum + 1e-6, ``routed_scale`` multiplies them.
    aux = E * sum_e f_e P_e with f_e the share of the N tokens' assignments
    that expert e got (sum_e f_e = top_k; no gradient) and P_e its mean
    probability (sigmoid: its mean score over the token's sum of scores);
    z = mean_t logsumexp(logits_t)^2.
    dispatch: a stable sort of the N * top_k assignments by expert; each
    expert's rows laid out in whole tiles of ``ROW_TILE`` (at least one),
    padding rows zero.  experts: act(x Wg) * (x Wu) through Wd (no Wg:
    act(x Wu) Wd) as grouped products; with Wg, gate and up are ONE paired
    product a direction (``gated_grouped_matmul``: the rows read once for
    both stacks, ``act(gate) * up`` in the forward kernel's epilogue, the
    two gradients of the rows summed inside one kernel), counted at trace
    time as ``route/moe:gated_pair``, so the stage is six kernels forward
    and backward.  combine: every assignment's row gathered back, weighted,
    summed over the token's ``top_k`` (the slots outermost, [top_k, N, D]:
    a second-minor dimension of 4 would pad to a sublane tile of 8).

    **One chip's share of an expert-parallel layer**: the stacks hold fewer
    experts than the router has outputs, those from ``expert_offset`` on.
    Scores, choice and renormalisation run over ALL the router's experts,
    as on every chip of the deployment; an assignment to an expert that is
    not held sorts behind the held ones, gets no row and no product, and in
    ``combine`` reads past the end (the gather's zero): what the absent
    experts would have added is left out, and nothing stands in for their
    chips.  The tile count stays the static worst case (every assignment
    lands here; no token is dropped): the kernels skip the tiles past
    ``num_tiles``, so time goes with the rows held and memory with the
    bound.
    """
    from .pallas_kernels import gated_grouped_matmul, grouped_matmul

    n, _ = xt.shape
    experts = gate_w.shape[-1]           # the router's width
    held = w_up.shape[0]                 # the experts this chip holds
    assignments = n * top_k
    with jax.named_scope("moe.route"):
        logits = jnp.dot(xt.astype(jnp.float32), gate_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        if scoring == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
            score = probs
        else:
            score = jax.nn.sigmoid(logits)
            probs = score / jnp.sum(score, axis=-1, keepdims=True)
        if select_bias is None:
            weight, expert = lax.top_k(score, top_k)          # [N, k]
        else:
            _, expert = lax.top_k(score + lax.stop_gradient(
                select_bias.astype(jnp.float32)), top_k)
            weight = jnp.take_along_axis(score, expert, axis=-1)
        if renormalize:
            weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
        if routed_scale != 1.0:
            weight = weight * routed_scale
        count = jnp.bincount(expert.reshape(-1), length=experts)
        aux = experts * jnp.sum(count.astype(jnp.float32) / n
                                * jnp.mean(probs, axis=0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    with jax.named_scope("moe.dispatch"):
        # an expert that is not held takes the id past the held ones: its
        # assignments sort last and no tile reaches them (none, where every
        # expert is held)
        local = expert.reshape(-1) - expert_offset
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)
        count = lax.dynamic_slice_in_dim(count, expert_offset, held)
        tm = min(ROW_TILE, -(-assignments // held // 8) * 8)
        tiles = assignments // tm + held             # the static worst case
        order = jnp.argsort(key, stable=True)
        first = jnp.cumsum(count) - count            # in the sorted order
        used = jnp.maximum(-(-count // tm), 1)       # tiles of each expert
        tile_end = jnp.cumsum(used)
        num_tiles = tile_end[-1:]
        tile_group = jnp.minimum(
            jnp.searchsorted(tile_end, jnp.arange(tiles), side="right"),
            held - 1).astype(jnp.int32)
        first_row = (tile_end - used) * tm           # in the tiled layout
        row = jnp.arange(tiles * tm)
        group = tile_group[row // tm]
        offset = row - first_row[group]
        in_use = (offset < count[group]) & (row // tm < num_tiles[0])
        # the assignment (token * top_k + slot) a tiled row holds, and back
        assignment = jnp.where(
            in_use,
            order[jnp.minimum(first[group] + offset, assignments - 1)],
            assignments)
        sorted_at = jnp.argsort(order)               # assignment -> sorted
        at = jnp.minimum(key, held - 1)
        row_of = jnp.where(here, first_row[at] + sorted_at - first[at],
                           tiles * tm).reshape(n, top_k)
        rows = _gather_rows(xt, jnp.where(in_use, assignment // top_k, n),
                            row_of)
    with jax.named_scope("moe.experts"):
        if w_gate is None:
            hidden = act(grouped_matmul(rows, w_up, tile_group, num_tiles))
        else:
            compile_cache.stats().bump("route/moe:gated_pair")
            hidden = gated_grouped_matmul(rows, w_gate, w_up, tile_group,
                                          num_tiles, act)
        down = grouped_matmul(hidden, w_down, tile_group, num_tiles)
    with jax.named_scope("moe.combine"):
        # slot-major: assignment (token, slot) at slot * n + token
        reader = jnp.where(
            in_use, assignment % top_k * n + assignment // top_k,
            assignments)
        picked = _gather_rows(down, row_of.T.reshape(-1), reader[:, None])
        out = jnp.sum(picked.reshape(top_k, n, -1)
                      * weight.T[..., None].astype(picked.dtype), axis=0)
    return out, aux, z


@register_op("moe")
def _moe(ctx, ins, attrs):
    from ..parallel.moe import load_balancing_loss, moe_dispatch

    x = ins["X"][0]
    gate_w = ins["GateW"][0]
    w1 = ins["W1"][0]          # [E, D, H], sharded P('ep', ...) on a mesh
    w2 = ins["W2"][0]          # [E, H, D]
    top_k = int(attrs.get("top_k", 2))
    act = _ACTS[attrs.get("activation", "relu")]

    shape = x.shape
    D = shape[-1]
    xt = x.reshape(-1, D)
    T, E = xt.shape[0], gate_w.shape[-1]
    ep = ctx.mesh_axis_size("ep")

    cap_f = attrs.get("capacity_factor", 1.25)
    if cap_f is None:
        if ep > 1:
            raise NotImplementedError(
                f"moe: the dropless lowering (capacity_factor=None) runs "
                f"every expert on one device; this mesh has ep={ep}.  Give "
                f"a capacity_factor for the expert-parallel dispatch.")
        compile_cache.stats().bump("route/moe:dropless")
        scoring = attrs.get("scoring", "softmax")
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"moe: scoring {scoring!r} is neither "
                             f"'softmax' nor 'sigmoid'")
        if scoring == "sigmoid":
            compile_cache.stats().bump("route/moe:sigmoid")
        if w1.shape[0] != E:
            if attrs.get("experts_held") != w1.shape[0]:
                raise ValueError(
                    f"moe: W1 expert count {w1.shape[0]} != GateW experts "
                    f"{E} and no share of {w1.shape[0]} is declared "
                    f"(experts_held)")
            compile_cache.stats().bump("route/moe:share")
        gated = ins.get("WGate")
        bias = ins.get("SelectBias")
        out, aux, z = _dropless(
            xt, gate_w, gated[0] if gated else None, w1, w2, top_k, act,
            scoring=scoring, select_bias=bias[0] if bias else None,
            renormalize=bool(attrs.get("renormalize", False)),
            routed_scale=float(attrs.get("routed_scale", 1.0)),
            expert_offset=int(attrs.get("expert_offset", 0)))
        return {"Out": out.reshape(shape).astype(x.dtype),
                "AuxLoss": aux, "ZLoss": z}
    if ins.get("WGate") or ins.get("SelectBias") or w1.shape[0] != E or \
            attrs.get("scoring", "softmax") != "softmax" or \
            attrs.get("renormalize", False):
        raise NotImplementedError(
            "moe: gated experts, sigmoid scores, a selection bias, "
            "renormalised weights and a chip's share of the experts run in "
            "the dropless lowering only (capacity_factor=None)")
    compile_cache.stats().bump("route/moe:capacity")

    logits = xt @ gate_w
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
        x.dtype)
    capacity = max(1, int(cap_f * top_k * T / E))
    dispatch, combine = moe_dispatch(gates, capacity, top_k)
    aux = load_balancing_loss(gates, dispatch)

    def on_experts(a):
        if ep > 1:
            return lax.with_sharding_constraint(
                a, NamedSharding(ctx.mesh, P("ep", None, None)))
        return a

    expert_in = on_experts(jnp.einsum("tec,td->ecd", dispatch, xt))
    h = act(jnp.einsum("ecd,edh->ech", expert_in, w1))
    out_e = on_experts(jnp.einsum("ech,ehd->ecd", h, w2))
    out = jnp.einsum("tec,ecd->td", combine, out_e)
    z = jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2)
    return {"Out": out.reshape(shape),
            "AuxLoss": aux.reshape(()).astype(jnp.float32), "ZLoss": z}


# ---------------------------------------------------------------------------
# Static shape/dtype rules (analysis.shape_infer).
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import ShapeError, VarInfo, first  # noqa: E402
from ..core.registry import register_shape_fn  # noqa: E402


@register_shape_fn("moe")
def _moe_shape(op, ins, attrs):
    x, gate_w = first(ins, "X"), first(ins, "GateW")
    w1 = first(ins, "W1")
    if x.shape is not None and gate_w.shape is not None and \
            x.shape[-1] >= 0 and gate_w.shape[0] >= 0 and \
            x.shape[-1] != gate_w.shape[0]:
        raise ShapeError(
            f"moe: X feature dim {x.shape[-1]} != GateW rows "
            f"{gate_w.shape[0]}")
    held = attrs.get("experts_held")     # a chip's share, where declared
    if w1.shape is not None and gate_w.shape is not None and \
            w1.shape[0] >= 0 and gate_w.shape[-1] >= 0:
        e, offset = gate_w.shape[-1], attrs.get("expert_offset", 0)
        if held is None and w1.shape[0] != e:
            raise ShapeError(
                f"moe: W1 expert count {w1.shape[0]} != GateW experts {e}")
        if held is not None and not (
                w1.shape[0] == held and 0 <= offset
                and offset + held <= e):
            raise ShapeError(
                f"moe: a share of {held} experts from {offset} on needs "
                f"stacks of {held} under a router of at least "
                f"{offset + held}; W1 has {w1.shape[0]}, GateW {e}")
    bias = first(ins, "SelectBias")
    if ins.get("SelectBias") and bias.shape is not None and \
            gate_w.shape is not None and gate_w.shape[-1] >= 0 and \
            tuple(bias.shape) != (gate_w.shape[-1],):
        raise ShapeError(
            f"moe: SelectBias {list(bias.shape)} != [GateW experts "
            f"{gate_w.shape[-1]}]")
    gate = first(ins, "WGate")
    if ins.get("WGate") and gate.shape is not None and \
            w1.shape is not None and tuple(gate.shape) != tuple(w1.shape):
        raise ShapeError(
            f"moe: WGate {list(gate.shape)} != W1 {list(w1.shape)}")
    return {"Out": x, "AuxLoss": VarInfo((), "float32"),
            "ZLoss": VarInfo((), "float32")}


# ---------------------------------------------------------------------------
# Sharding-propagation rule (analysis.shard_prop): the fused MoE op is
# token-preserving — Out rides X's sharding, the two losses replicate.
# (Expert-parallel specs on W1/W2 partition the expert dim; the dispatch
# all-to-all is GSPMD's to insert and the cost model's to charge.)
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import first_in  # noqa: E402
from ..core.registry import register_shard_fn  # noqa: E402


@register_shard_fn("moe")
def _moe_shard(op, ins, attrs):
    x = first_in(ins, "X")
    if x.spec is None:
        return {}
    return {"Out": x.spec, "AuxLoss": (), "ZLoss": ()}
