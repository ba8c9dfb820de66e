"""``ssd_scan``: the selective state-space recurrence of a Mamba-2 layer in
its CHUNKED form ("state-space duality"), forward and backward.

For u [B, T, H, P] (H heads of P features), delta [B, T, H] > 0, A [H] < 0,
Bm and Cm [B, T, G, N] (G groups of heads share one Bm / Cm of N state
features; head h reads group h // (H / G)) and D [H], every head keeps a
state S [P, N] that starts at 0:

    S[t] = exp(delta[t] A) S[t-1] + delta[t] * u[t] (x) Bm[t]
    y[t] = S[t] Cm[t] + D * u[t]

A T-step scan of that is T latency-bound steps of a small state, and the
T x T product of its unrolled form is quadratic.  Here T is cut into chunks
of ``chunk`` positions.  With a = delta * A and cs its running sum INSIDE a
chunk (cs <= 0, falling):

    ssd.decay   cs, and L[i, j] = exp(cs_i - cs_j) for i >= j, else 0
    ssd.intra   y_diag = ((Cm Bm^T) o L) (delta * u)       inside a chunk
    ssd.states  a chunk's own state
                own = sum_j exp(cs_last - cs_j) delta_j u_j (x) Bm_j
    ssd.pass    the states handed from chunk to chunk (a scan over T / chunk
                chunks):
                S_before[c] = exp(cs_last[c-1]) S_before[c-1] + own[c-1]
    ssd.out     y = y_diag + exp(cs_i) * (Cm_i . S_before) + D * u

Every decay is the exponential of a DIFFERENCE of running sums that is <= 0,
and the triangle is masked BEFORE the ``exp``: with A down to -64 and delta
to 0.15 a chunk's cs reaches -2 400, so a quotient exp(cs_i) / exp(cs_j) is
0 / 0 in float32, and the upper triangle's differences (up to +2 400)
overflow.  The stages are five ``jax.named_scope``s inside the op's own
``pt.ssd_scan:<block>.<position>`` (they do not start with ``pt.``, so the
op stays the innermost owner of its time).  Computed in float32; the
backward is JAX's transpose of the same five stages.

Two lowerings, chosen by ``ssd_kernels.ssd_scan_route`` from what the code
can see and counted at trace time as ``route/ssd_scan:{pallas,interpret,
xla}``: on a TPU, on one device, with P a whole or half lane tile (128 or
64), N and the chunk whole lane tiles, the heads of a group whole head
blocks and float32 or bfloat16 operands, the pair of Pallas kernels of
``ops/ssd_kernels.py`` (the same numerics on the [T, H * P] layout the model
has; a ``jax.custom_vjp`` whose residuals are the op's inputs and the
chunk-boundary states ``S_before``, never L or another [chunk, chunk]
array); everywhere else (every CPU run, a mesh, every other shape) the
einsum form ``ssd_chunked`` below, which is also the kernels' test
reference.  ``interpret`` is the kernels through the Pallas interpreter,
which only a test asks for through the op's ``interpret`` attribute.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..core import compile_cache
from ..core.registry import register_op
from . import ssd_kernels


def ssd_chunked(u, delta, a, bm, cm, d, chunk):
    """y [B, T, H, P] of the recurrence above, in chunks of ``chunk``
    positions (which divides T); float32 throughout."""
    b, t_len, heads, p = u.shape
    groups, n = bm.shape[2], bm.shape[3]
    per = heads // groups
    nc = t_len // chunk
    f32 = jnp.float32
    # heads as [G, per] so that a group's Bm / Cm broadcast over its heads
    u_c = u.astype(f32).reshape(b, nc, chunk, groups, per, p)
    dt = delta.astype(f32).reshape(b, nc, chunk, groups, per)
    bm_c = bm.astype(f32).reshape(b, nc, chunk, groups, n)
    cm_c = cm.astype(f32).reshape(b, nc, chunk, groups, n)
    with jax.named_scope("ssd.decay"):
        # [B, nc, G, per, Q]: positions last, on the lanes
        cs = jnp.cumsum(jnp.moveaxis(
            dt * a.astype(f32).reshape(groups, per), 2, -1), axis=-1)
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))
        within = jnp.exp(jnp.where(
            causal, cs[..., :, None] - cs[..., None, :], -jnp.inf))
        to_end = jnp.exp(cs[..., -1:] - cs)                  # [.., Q]
        from_start = jnp.exp(cs)                             # [.., Q]
        whole = jnp.exp(cs[..., -1])                         # [B, nc, G, per]
    x = u_c * dt[..., None]                                  # delta * u
    with jax.named_scope("ssd.intra"):
        scores = jnp.einsum("bcign,bcjgn->bcgij", cm_c, bm_c)
        y = jnp.einsum("bcgrij,bcjgrp->bcigrp",
                       scores[:, :, :, None] * within, x)
    with jax.named_scope("ssd.states"):
        own = jnp.einsum("bcgrj,bcjgrp,bcjgn->bcgrpn", to_end, x, bm_c)
    with jax.named_scope("ssd.pass"):
        def hand_on(before, chunk_):
            decay, state = chunk_
            return decay[..., None, None] * before + state, before

        _, before = lax.scan(
            hand_on, jnp.zeros_like(own[:, 0]),
            (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(own, 1, 0)))
        before = jnp.moveaxis(before, 0, 1)                  # [B, nc, ...]
    with jax.named_scope("ssd.out"):
        carried = jnp.einsum("bcign,bcgrpn->bcigrp", cm_c, before)
        y = y + carried * jnp.moveaxis(from_start, -1, 2)[..., None] \
            + u_c * d.astype(f32).reshape(groups, per)[..., None]
    return y.reshape(b, t_len, heads, p).astype(u.dtype)


@register_op("ssd_scan")
def _ssd_scan(ctx, ins, attrs):
    """U [B, T, H, P], Delta [B, T, H], A [H], Bm and Cm [B, T, G, N],
    D [H] -> Out [B, T, H, P] (this file's docstring); ``chunk`` positions
    a chunk, which divides T.  The kernels or the einsum form, by
    ``ssd_kernels.ssd_scan_route``."""
    u = ins["U"][0]
    chunk = int(attrs.get("chunk", 256))
    if u.shape[1] % chunk:
        raise ValueError(f"ssd_scan: T {u.shape[1]} is not whole chunks of "
                         f"{chunk}")
    operands = (u, ins["Delta"][0], ins["A"][0], ins["Bm"][0], ins["Cm"][0],
                ins["D"][0])
    single = ctx.mesh is None or getattr(ctx.mesh, "size", 1) == 1
    route = ssd_kernels.ssd_scan_route(
        (u.shape, operands[3].shape), chunk, u.dtype,
        attrs.get("interpret", False)) if single else "xla"
    compile_cache.stats().bump("route/ssd_scan:" + route)
    if route != "xla":
        return {"Out": ssd_kernels.ssd_scan(
            *operands, chunk, interpret=route == "interpret")}
    return {"Out": ssd_chunked(*operands, chunk)}


# ---------------------------------------------------------------------------
# Static shape/dtype rule (analysis.shape_infer).
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import ShapeError, dim_ok, first  # noqa: E402
from ..core.registry import register_shape_fn  # noqa: E402


@register_shape_fn("ssd_scan")
def _ssd_scan_shape(op, ins, attrs):
    u, delta = first(ins, "U"), first(ins, "Delta")
    bm, cm = first(ins, "Bm"), first(ins, "Cm")
    if u.shape is None:
        return {"Out": u}
    if len(u.shape) != 4:
        raise ShapeError(f"ssd_scan: U {list(u.shape)} is not [B, T, H, P]")
    t_len, heads = u.shape[1], u.shape[2]
    chunk = int(attrs.get("chunk", 256))
    if chunk < 1 or (t_len >= 0 and t_len % chunk):
        raise ShapeError(f"ssd_scan: T {t_len} is not whole chunks of "
                         f"{chunk} positions")
    if delta.shape is not None and not (
            len(delta.shape) == 3 and dim_ok(delta.shape[1], t_len)
            and dim_ok(delta.shape[2], heads)):
        raise ShapeError(f"ssd_scan: Delta {list(delta.shape)} is not "
                         f"[B, T, H] for U {list(u.shape)}")
    for slot in ("A", "D"):
        v = first(ins, slot)
        if v.shape is not None and not (
                len(v.shape) == 1 and dim_ok(v.shape[0], heads)):
            raise ShapeError(f"ssd_scan: {slot} {list(v.shape)} is not "
                             f"[H = {heads}]")
    if bm.shape is not None:
        if len(bm.shape) != 4 or not dim_ok(bm.shape[1], t_len) or (
                bm.shape[2] >= 0 and heads >= 0 and (
                    bm.shape[2] < 1 or heads % bm.shape[2])):
            raise ShapeError(
                f"ssd_scan: Bm {list(bm.shape)} is not [B, T, G, N] with G "
                f"a divisor of U's {heads} heads")
        if cm.shape is not None and not (
                len(cm.shape) == 4
                and all(dim_ok(x, y) for x, y in zip(cm.shape, bm.shape))):
            raise ShapeError(f"ssd_scan: Cm {list(cm.shape)} != Bm "
                             f"{list(bm.shape)}")
    return {"Out": u}


# ---------------------------------------------------------------------------
# Sharding-propagation rule (analysis.shard_prop)
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import ShardConflict, first_in  # noqa: E402
from ..core.registry import register_shard_fn  # noqa: E402


@register_shard_fn("ssd_scan")
def _ssd_scan_shard(op, ins, attrs):
    """Out keeps U's batch sharding.  The state runs along T (a sharded T
    would have to hand it from chip to chip) and the lowering regroups the
    heads, so a sharded T, head or feature axis is a conflict."""
    u = first_in(ins, "U")
    if u.spec is None:
        return {}
    if u.entry(1) or u.entry(2) or u.entry(3):
        raise ShardConflict(
            "ssd_scan: U sharded along T, the heads or the features: the "
            "state is handed on along T and the heads are regrouped")
    return {"Out": (u.entry(0), None, None, None)}
