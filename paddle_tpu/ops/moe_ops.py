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
assignments are sorted by expert and laid out in whole row tiles, the
experts run as grouped products over the tiled rows (``ops/pallas_kernels.py
grouped_matmul``, ``gated_grouped_matmul``), and every movement of rows
between the token order and the tiled order is driven from the tiled side,
over the tiles in use: rows-from-tokens (``rows_from_tokens``, a kernel)
and tokens-from-rows (``_tokens_from_rows``), each the other's transpose.
Memory goes with the N * top_k routed rows, time with the rows a call
really holds.  It is the lowering of today's sparse-expert decoders (top-8
of 64 and the like), where a [T, E, C] dispatch tensor cannot be held.
Which of the two ran is counted at trace time as
``route/moe:{dropless,capacity}`` in ``profiler.compile_stats()``, and which
form the tokens' sums took as ``route/moe_rows:{tiles,take}``.

Reference capability frame: the reference never shipped MoE; nearest
ancestors are per-layer device placement (ParallelNeuralNetwork.cpp) and
the sparse-update machinery (SelectedRows).  This is capability-forward
surface the ep mesh axis exists for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import compile_cache
from ..core.registry import register_op

_ACTS = {
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
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


# row tiles one pass of ``_tokens_from_rows`` spans (on the v5e a scatter-add
# costs ~45 us a call whatever it moves and 80-115 ns a row: at LFM2's shape
# 4.04 ms a tile a pass, 1.60 / 1.71 at 4 / 8 tiles; PERF.md section 6, PR 35)
TILE_SPAN = 8


def _tokens_from_rows(tiled, token, num_tiles, tm, n, scale=None):
    """``out[token[r]] += scale[r] * tiled[r]`` over the rows of the tiles in
    use, [n, D]: a loop whose trip count is ``num_tiles`` (a value, as the
    grouped kernels' is), ``TILE_SPAN`` tiles a pass.  The order of a
    token's sum is fixed: pass after pass in tile order, and inside a pass
    in the order XLA's scatter applies its updates, the same every run (a
    pass spans several experts' tiles, so a token may occur twice in it and
    ``unique_indices`` cannot be promised).  A padding row (``token[r]``
    past the end) adds nothing, and no row past ``num_tiles`` is read for
    its value."""
    rows, d = tiled.shape
    span = min(TILE_SPAN * tm, rows)

    def one(i, out):
        at = jnp.minimum(i * span, rows - span)
        index = lax.dynamic_slice(token, (at,), (span,))
        # the last pass ends at the bound: the rows the pass before took
        # are dropped from it
        index = jnp.where(at + jnp.arange(span) < i * span, n, index)
        values = lax.dynamic_slice(tiled, (at, 0), (span, d))
        if scale is not None:
            values = values * lax.dynamic_slice(scale, (at,), (span,))[:, None]
        return out.at[index].add(values, mode="drop")

    return lax.fori_loop(0, -(-(num_tiles[0] * tm) // span), one,
                         jnp.zeros((n, d), tiled.dtype))


def _tokens_from_rows_held(tiled, row_of, weight=None):
    """The same sum from the token side, for a call that holds EVERY expert
    (no assignment is absent, so nothing can be skipped): each of the
    ``n * top_k`` assignments' rows gathered (``row_of`` [n, top_k]) and
    summed over the slots, the slots outermost ([top_k, n, D]: a
    second-minor dimension of 4 would pad to a sublane tile of 8)."""
    n, top_k = row_of.shape
    picked = jnp.take(tiled, row_of.T.reshape(-1), axis=0, mode="fill",
                      fill_value=0).reshape(top_k, n, -1)
    if weight is not None:
        picked = picked * weight.T[..., None].astype(picked.dtype)
    return jnp.sum(picked, axis=0)


def _weight_of_rows(weight, assignment, dtype):
    return jnp.take(weight.reshape(-1), assignment, mode="fill",
                    fill_value=0).astype(dtype)


# ``index`` of the two stages: (assignment of a tiled row, its token, tiled
# row of an assignment [n, top_k], ``num_tiles``), past the end where none
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dispatch(xt, index, tm, all_held):
    """rows-from-tokens, and tokens-from-rows as its transpose: the tokens'
    rows in the tiled order, [tiles * tm, D], those of the tiles in use
    (the rest is never written)."""
    from .pallas_kernels import rows_from_tokens

    _, token, _, num_tiles = index
    return rows_from_tokens(xt, token, num_tiles, tm)


def _dispatch_fwd(xt, index, tm, all_held):
    return _dispatch(xt, index, tm, all_held), index


def _dispatch_bwd(tm, all_held, index, g):
    _, token, row_of, num_tiles = index
    return (_tokens_from_rows_held(g, row_of) if all_held else
            _tokens_from_rows(g, token, num_tiles, tm, row_of.shape[0])), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(down, weight, index, tm, all_held):
    """tokens-from-rows with the weights, and rows-from-tokens as its
    transpose: ``out[t] = sum over t's assignments held here of weight *
    its row of down``, [n, D], the weights applied in ``down``'s dtype.  No
    [top_k, n, D] array of picked rows is kept: the backward pass reads
    ``down`` itself (``d_down[r] = w(r) * g[token(r)]`` and ``d_w(r) =
    <g[token(r)], down[r]>`` on the same tile)."""
    assignment, token, row_of, num_tiles = index
    if all_held:
        return _tokens_from_rows_held(down, row_of, weight)
    return _tokens_from_rows(down, token, num_tiles, tm, weight.shape[0],
                             _weight_of_rows(weight, assignment, down.dtype))


def _combine_fwd(down, weight, index, tm, all_held):
    return _combine(down, weight, index, tm, all_held), (down, weight, index)


def _combine_bwd(tm, all_held, res, g):
    from .pallas_kernels import rows_from_tokens

    down, weight, (assignment, token, row_of, num_tiles) = res
    d_down, dots = rows_from_tokens(
        g, token, num_tiles, tm,
        _weight_of_rows(weight, assignment, g.dtype), down)
    d_weight = jnp.take(dots, row_of, mode="fill", fill_value=0)
    return d_down, d_weight.astype(weight.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _shared_expert(xt, shared, act):
    """The expert every token passes through, dense: ``act(x Wu) Wd``, or
    ``(act(x Wg) * (x Wu)) Wd`` where ``shared`` (gate or None, up, down)
    holds a gate; the products at the default precision, as an ``fc``'s."""
    gate, up, down = shared
    hidden = act(xt @ up) if gate is None else act(xt @ gate) * (xt @ up)
    return hidden @ down


def _dropless(xt, gate_w, w_gate, w_up, w_down, top_k, act,
              scoring="softmax", select_bias=None, renormalize=False,
              routed_scale=1.0, expert_offset=0, shared=None,
              up_transposed=False):
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
    padding rows zero.  A tiled row in use knows its token (``assignment //
    top_k``); a tile belongs to ONE expert and the sort is stable over
    token-major assignments, so within a tile (within an expert) the tokens
    are distinct and increasing.  The rows come by rows-from-tokens,
    ``tiled[r] = xt[token(r)]``, a tile a step of a kernel that stops at
    ``num_tiles``; the gradient is tokens-from-rows, ``d_xt[token(r)] +=
    d_tiled[r]`` over the same tiles.  experts: act(x Wg) * (x Wu) through
    Wd (no Wg: act(x Wu) Wd, counted ``route/moe:single``) as grouped
    products; with Wg, gate and up are ONE paired product a direction
    (``gated_grouped_matmul``: the rows read once for both stacks, the two
    gradients of the rows summed inside one kernel), counted at trace time
    as ``route/moe:gated_pair``.  Either form's activation is the epilogue
    of its forward kernel and its derivative a kernel of the backward pass,
    so the stage is kernels alone, two forward and five backward, their
    grids ending at ``num_tiles``, and XLA computes nothing over a tiled
    array.  combine: tokens-from-rows
    with the weights, ``out[token(r)] += w(r) * down[r]``, its gradient
    rows-from-tokens of the cotangent with ``w(r)`` and the weights' own
    gradient made on the same tile.  Where a call holds a share,
    tokens-from-rows is a scatter-add over the tiles in use, in tile order
    (``route/moe_rows:tiles``); where it holds EVERY expert the bound is the
    rows in use, nothing can be skipped, and a gather from the token side
    costs half as much a row: each assignment's row gathered ([top_k, N, D],
    the slots outermost) and summed over the slots (``route/moe_rows:take``;
    a static fact of the operands, ``held == experts``).

    **One chip's share of an expert-parallel layer**: the stacks hold fewer
    experts than the router has outputs, those from ``expert_offset`` on.
    Scores, choice and renormalisation run over ALL the router's experts,
    as on every chip of the deployment; an assignment to an expert that is
    not held sorts behind the held ones, gets no row and no product, and no
    tile holds it, so ``combine`` never meets it: what the absent experts
    would have added is left out, and nothing stands in for their chips.
    The tile count stays the static worst case (every assignment lands
    here; no token is dropped), and every stage stops at ``num_tiles``: the
    grouped kernels' grids end there, rows-from-tokens starts no copy
    for them and tokens-from-rows' loop ends before them, so time goes with
    the rows held and memory with the bound, in dispatch and combine as in
    the experts.  Past ``num_tiles`` the tiled arrays hold nothing anyone
    may read: no stage writes them, forward or backward (``rows``, the
    experts' products and every cotangent alike).

    ``up_transposed``: the un-gated up stack is held [E, H, D] and read
    transposed (``grouped_matmul``; ``layers.moe`` says when).

    **A shared expert** (``shared``: gate or None, up [D, Hs], down
    [Hs, D]) is one more expert of the same form that EVERY token passes
    through under weight 1, outside the routing: two (three) dense products
    over all N rows in the stage ``moe.shared``, added after ``combine``.
    Every chip of an expert-parallel deployment computes it alike on its own
    tokens, so where the shares of a layer are summed it is counted once.
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
        token = jnp.where(in_use, assignment // top_k, n)
        sorted_at = jnp.argsort(order)               # assignment -> sorted
        at = jnp.minimum(key, held - 1)
        row_of = jnp.where(here, first_row[at] + sorted_at - first[at],
                           tiles * tm).reshape(n, top_k)
        # tokens-from-rows runs from the tiled side unless every expert is
        # held: then the bound IS the rows in use and nothing can be skipped
        all_held = held == experts
        compile_cache.stats().bump(
            "route/moe_rows:" + ("take" if all_held else "tiles"))
        index = (assignment, token, row_of, num_tiles)
        rows = _dispatch(xt, index, tm, all_held)
    with jax.named_scope("moe.experts"):
        compile_cache.stats().bump(
            "route/moe:" + ("single" if w_gate is None else "gated_pair"))
        hidden = gated_grouped_matmul(rows, w_gate, w_up, tile_group,
                                      num_tiles, act,
                                      transpose_rhs=up_transposed)
        down = grouped_matmul(hidden, w_down, tile_group, num_tiles)
    with jax.named_scope("moe.combine"):
        out = _combine(down, weight, index, tm, all_held)
    if shared is not None:
        compile_cache.stats().bump("route/moe:shared")
        with jax.named_scope("moe.shared"):
            out = out + _shared_expert(xt, shared, act).astype(out.dtype)
    return out, aux, z


@register_op("moe")
def _moe(ctx, ins, attrs):
    from ..parallel.moe import load_balancing_loss, moe_dispatch

    x = ins["X"][0]
    gate_w = ins["GateW"][0]
    w1 = ins["W1"][0]          # [E, D, H], sharded P('ep', ...) on a mesh
    #                            ([E, H, D] with ``up_transposed``)
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
        shared = None
        if ins.get("SharedUp"):
            shared_gate = ins.get("SharedGate")
            shared = (shared_gate[0] if shared_gate else None,
                      ins["SharedUp"][0], ins["SharedDown"][0])
        out, aux, z = _dropless(
            xt, gate_w, gated[0] if gated else None, w1, w2, top_k, act,
            scoring=scoring, select_bias=bias[0] if bias else None,
            renormalize=bool(attrs.get("renormalize", False)),
            routed_scale=float(attrs.get("routed_scale", 1.0)),
            expert_offset=int(attrs.get("expert_offset", 0)), shared=shared,
            up_transposed=bool(attrs.get("up_transposed", False)))
        return {"Out": out.reshape(shape).astype(x.dtype),
                "AuxLoss": aux, "ZLoss": z}
    if ins.get("WGate") or ins.get("SelectBias") or w1.shape[0] != E or \
            attrs.get("scoring", "softmax") != "softmax" or \
            attrs.get("renormalize", False) or ins.get("SharedUp") or \
            attrs.get("up_transposed", False):
        raise NotImplementedError(
            "moe: gated experts, sigmoid scores, a selection bias, "
            "renormalised weights, a shared expert and a chip's share of "
            "the experts run in the dropless lowering only "
            "(capacity_factor=None)")
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
    if ins.get("SharedUp"):
        up, down = first(ins, "SharedUp"), first(ins, "SharedDown")
        if up.shape is not None and down.shape is not None and \
                x.shape is not None and x.shape[-1] >= 0 and (
                    len(up.shape) != 2 or up.shape[0] != x.shape[-1]
                    or tuple(down.shape) != tuple(up.shape[::-1])):
            raise ShapeError(
                f"moe: SharedUp {list(up.shape)} / SharedDown "
                f"{list(down.shape)} are not [D, Hs] / [Hs, D] for X's D "
                f"{x.shape[-1]}")
        gate = first(ins, "SharedGate")
        if ins.get("SharedGate") and gate.shape is not None and \
                up.shape is not None and \
                tuple(gate.shape) != tuple(up.shape):
            raise ShapeError(f"moe: SharedGate {list(gate.shape)} != "
                             f"SharedUp {list(up.shape)}")
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
