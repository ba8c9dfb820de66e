"""``gated_delta_scan``: the gated delta rule of Kimi Delta Attention (KDA)
in its CHUNKED form, forward and backward.

For q and k [B, T, H, K] (L2-normalised by the caller), v [B, T, H, V], g
[B, T, H, K] (log-decays a key channel, <= 0) and beta [B, T, H] (in
(0, 1)), every head keeps a state S [K, V] that starts at 0:

    S[t] = (I - beta[t] k[t] k[t]^T) Diag(exp(g[t])) S[t-1]
           + beta[t] k[t] v[t]^T
    o[t] = S[t]^T (scale * q[t])

A T-step scan of that is T latency-bound steps.  Here T is cut into chunks
of ``chunk`` positions.  Inside a chunk, gam is the running sum of g (<= 0,
falling, a key channel each); S0 the state before the chunk; K+ = exp(gam) *
K, Q+ = exp(gam) * scale * Q and K- = exp(-gam) * K:

    delta.decay   gam and the decayed factors below
    delta.solve   A = strict_lower(K+ K-^T);
                  [U | TK] = (I + diag(beta) A)^-1 diag(beta) [V | K+]
                  (the inverse of one unit-lower-triangular matrix a chunk:
                  the rank-one corrections of the chunk's positions to one
                  another)
    delta.states  the chunks in turn (a scan over T / chunk chunks):
                  W = U - TK S0;  S_C = exp(gam_C) * S0 + (exp(gam_C - gam)
                  * K)^T W
    delta.intra   lower(Q+ K-^T) W, the chunk's positions on one another
    delta.out     o = Q+ S0 + that

**No exponent grows.**  -gam grows by |g| a position, and |g| reaches tens,
so exp(-gam) is never formed over a chunk: every decay between two
positions r >= i is exp(gam_r - gam_i) <= 1, by one of two forms.  Inside the
DIAGONAL sub-blocks of ``SUB`` positions it is taken elementwise, the
triangle masked before the ``exp`` (``_decayed``: its products over K are
summed where they are made, and its backward is two more such sums, so no
[SUB, SUB, K] array is ever written).  Between sub-blocks it goes through
the position b before the row's sub-block, i <= b < r:
exp(gam_r - gam_b) exp(gam_b - gam_i), both factors <= 1, one a row's and
one a column's, so the product is a matrix product.  Every stage runs in
float32.  The backward is JAX's transpose of the five stages (the two
elementwise sums have their own, above), taken a STRETCH of ``STRETCH``
positions at a time: the stretches run in turn, the state handed from one to
the next, each under ``jax.checkpoint``, so what a stretch keeps for its
backward (some 25 arrays the size of q) lives only while that stretch's
backward runs.  The stages are ``jax.named_scope``s
inside the op's own ``pt.gated_delta_scan:<block>.<position>``; they do not
start with ``pt.``, so the op stays the innermost owner of its time.

Two lowerings, chosen by ``delta_kernels.gated_delta_scan_route`` from what
the code can see and counted at trace time as ``route/gated_delta_scan:
{pallas,interpret,xla}``: on a TPU, on one device, with K and V whole lane
tiles, the chunk whole sub-blocks, T whole chunks and float32 or bfloat16
operands, the pair of Pallas kernels of ``ops/delta_kernels.py`` (the same
numerics on the [T, H * K] layout the model has, the state held in VMEM
across the chunks; a ``jax.custom_vjp`` whose residuals are the op's inputs
and the chunk-boundary states, so it needs no stretches); everywhere else
(every CPU run, a mesh, every other shape) ``delta_chunked`` below, which is
also the kernels' test reference.  ``interpret`` is the kernels through the
Pallas interpreter, which only a test asks for through the op's
``interpret`` attribute.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..core import compile_cache
from ..core.registry import register_op
from . import delta_kernels

SUB = delta_kernels.SUB  # positions of a diagonal sub-block (fla's)
STRETCH = 1024  # positions the backward recomputes at a time


def _mask(s, inclusive):
    at = jnp.arange(s)
    return at[:, None] >= at[None, :] if inclusive \
        else at[:, None] > at[None, :]


def _decays(gam, inclusive):
    """[..., s, s, K]: exp(gam_r - gam_i) where i < r (i <= r), else 0."""
    mask = _mask(gam.shape[-2], inclusive)[..., None]
    return jnp.exp(jnp.where(mask, gam[..., :, None, :] - gam[..., None, :, :],
                             -jnp.inf))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _decayed(x, y, gam, inclusive):
    """out[..., r, i] = sum_c x[r, c] y[i, c] exp(gam[r, c] - gam[i, c]) over
    the strict (``inclusive``: the whole) lower triangle of [..., s, s], for
    x, y, gam [..., s, K]."""
    return jnp.sum(x[..., :, None, :] * y[..., None, :, :]
                   * _decays(gam, inclusive), axis=-1)


def _decayed_fwd(x, y, gam, inclusive):
    return _decayed(x, y, gam, inclusive), (x, y, gam)


def _decayed_bwd(inclusive, res, ct):
    x, y, gam = res
    e = _decays(gam, inclusive) * ct[..., None]
    dx = jnp.sum(e * y[..., None, :, :], axis=-2)
    dy = jnp.sum(e * x[..., :, None, :], axis=-3)
    # gam enters as gam_r - gam_i
    return dx, dy, x * dx - y * dy


_decayed.defvjp(_decayed_fwd, _decayed_bwd)


def _block_diagonal(blocks):
    """[..., n, s, s] -> the [..., n s, n s] matrix with those on its
    diagonal (by selection: no product rounds them)."""
    n, s = blocks.shape[-3], blocks.shape[-1]
    on = jnp.eye(n, dtype=bool)[:, None, :, None]
    full = jnp.where(on, blocks[..., :, :, None, :], 0.0)
    return full.reshape(blocks.shape[:-3] + (n * s, n * s))


def _lower(x_sub, k_sub, gam_sub, left, right, inclusive):
    """The [..., C, C] matrix sum_c x_r[c] k_i[c] exp(gam_r[c] - gam_i[c])
    over i < r (``inclusive``: i <= r): the diagonal sub-blocks elementwise,
    the rows of sub-block R >= 1 against every earlier position as
    ``left[R - 1] @ right[R - 1]^T``."""
    full = _block_diagonal(_decayed(x_sub, k_sub, gam_sub, inclusive))
    if right is None:
        return full
    s, chunk = x_sub.shape[-2], full.shape[-1]
    off = jnp.einsum("...rsk,...rck->...rsc", left, right)
    off = jnp.concatenate([jnp.zeros(off.shape[:-3] + (1, s, chunk),
                                     off.dtype), off], axis=-3)
    return full + off.reshape(full.shape)


def _unit_lower_inverse(strict):
    """(I + strict)^-1 for ``strict`` [..., n, n] strictly lower
    triangular: the inverses of the diagonal blocks, doubled level by level,
    [[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C A^-1, D^-1]], every level
    one batched product over all the blocks of its size (a solve that runs
    position by position is n dependent steps).  n is padded to a power of
    two with the identity.  The products at HIGHEST precision: each level's
    rounding reaches every later one, and they are few."""
    n = strict.shape[-1]
    full = 1 << (n - 1).bit_length()
    lead = strict.shape[:-2]
    if full != n:
        strict = jnp.pad(strict, [(0, 0)] * len(lead)
                         + [(0, full - n), (0, full - n)])
    inv = jnp.ones(lead + (full, 1, 1), strict.dtype)     # blocks of 1
    b = 1
    while b < full:
        k = full // (2 * b)
        pairs = inv.reshape(lead + (k, 2, b, b))
        a_inv, d_inv = pairs[..., 0, :, :], pairs[..., 1, :, :]
        # the block below each pair's first: rows of the second, columns
        # of the first
        c = jnp.einsum("...iaib->...iab", strict.reshape(
            lead + (k, 2 * b, k, 2 * b)))[..., b:, :b]
        low = -jnp.einsum("...ij,...jk,...kl->...il", d_inv, c, a_inv,
                          precision=lax.Precision.HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], -1),
            jnp.concatenate([low, d_inv], -1)], -2)
        b *= 2
    return inv[..., 0, :n, :n]


def _stretch(state, at, chunk):
    """One stretch of chunks, [per, B, H, C, ...] each of ``at``, from the
    state before it: (the state after it, o [per, B, H, C, V])."""
    q_c, k_c, v_c, g_c, beta_c = at
    vd = v_c.shape[-1]
    s = min(SUB, chunk)
    n = chunk // s

    def subs(x):                     # [..., C, K] -> [..., n, s, K]
        return x.reshape(x.shape[:-2] + (n, s, x.shape[-1]))

    with jax.named_scope("delta.decay"):
        gam = jnp.cumsum(g_c, axis=-2)
        k_plus = jnp.exp(gam) * k_c
        q_plus = jnp.exp(gam) * q_c
        k_end = jnp.exp(gam[..., -1:, :] - gam) * k_c
        whole = jnp.exp(gam[..., -1, :])                   # [per, B, H, K]
        right = left_k = left_q = None
        if n > 1:
            # b: the position before each sub-block R >= 1
            gam_b = gam[..., s - 1:chunk - 1:s, :]        # [..., n-1, K]
            before = (jnp.arange(chunk)[None, :]
                      < jnp.arange(1, n)[:, None] * s)     # [n-1, C]
            right = k_c[..., None, :, :] * jnp.exp(jnp.where(
                before[..., None], gam_b[..., :, None, :]
                - gam[..., None, :, :], -jnp.inf))         # [..., n-1, C, K]
            rows = jnp.exp(subs(gam)[..., 1:, :, :] - gam_b[..., None, :])
            left_k = subs(k_c)[..., 1:, :, :] * rows
            left_q = subs(q_c)[..., 1:, :, :] * rows
    with jax.named_scope("delta.solve"):
        a = _lower(subs(k_c), subs(k_c), subs(gam), left_k, right, False)
        solved = jnp.einsum(
            "...rc,...cd->...rd", _unit_lower_inverse(beta_c[..., None] * a),
            beta_c[..., None] * jnp.concatenate([v_c, k_plus], -1))
        u, tk = solved[..., :vd], solved[..., vd:]
    with jax.named_scope("delta.states"):
        def hand_on(state, at):                            # state [B, H, K, V]
            tk_, u_, k_end_, whole_ = at
            w_ = u_ - jnp.einsum("bhck,bhkv->bhcv", tk_, state)
            return (whole_[..., None] * state
                    + jnp.einsum("bhck,bhcv->bhkv", k_end_, w_)), (state, w_)

        state, (start, w) = lax.scan(hand_on, state, (tk, u, k_end, whole))
    with jax.named_scope("delta.intra"):
        o = jnp.einsum("...rc,...cv->...rv",
                       _lower(subs(q_c), subs(k_c), subs(gam), left_q, right,
                              True), w)
    with jax.named_scope("delta.out"):
        o = o + jnp.einsum("...ck,...kv->...cv", q_plus, start)
    return state, o


def delta_chunked(q, k, v, g, beta, scale, chunk):
    """o [B, T, H, V] of the recurrence above, in chunks of ``chunk``
    positions (which divides T); float32 throughout.  The chunks go in
    stretches of ``STRETCH`` positions, one after the other, each
    recomputed in the backward pass (``jax.checkpoint``): what a stretch
    keeps for its backward lives while that stretch's backward runs, and
    the pass keeps only the inputs and the state between stretches."""
    b, t_len, heads, kd = q.shape
    vd = v.shape[-1]
    nc = t_len // chunk
    per = math.gcd(nc, max(1, STRETCH // chunk))           # chunks a stretch

    def stretches(x):       # [B, T, H, ...] -> [nc / per, per, B, H, C, ...]
        x = x.astype(jnp.float32).reshape(
            (b, nc // per, per, chunk, heads) + x.shape[3:])
        return jnp.moveaxis(x, (1, 2, 4), (0, 1, 3))

    _, o = lax.scan(
        jax.checkpoint(functools.partial(_stretch, chunk=chunk)),
        jnp.zeros((b, heads, kd, vd), jnp.float32),
        (stretches(q) * scale, stretches(k), stretches(v), stretches(g),
         stretches(beta)))
    o = jnp.moveaxis(o, (0, 1, 3), (1, 2, 4))      # [B, nc/per, per, C, H, V]
    return o.reshape(b, t_len, heads, vd).astype(v.dtype)


@register_op("gated_delta_scan")
def _gated_delta_scan(ctx, ins, attrs):
    """Q, K [B, T, H, K], V [B, T, H, V], G [B, T, H, K], Beta [B, T, H] ->
    Out [B, T, H, V] (this file's docstring); ``scale`` multiplies q,
    ``chunk`` positions a chunk, which divides T.  The kernels or the
    chunked XLA form, by ``delta_kernels.gated_delta_scan_route``."""
    q = ins["Q"][0]
    chunk = int(attrs.get("chunk", 64))
    if q.shape[1] % chunk:
        raise ValueError(f"gated_delta_scan: T {q.shape[1]} is not whole "
                         f"chunks of {chunk}")
    operands = (q, ins["K"][0], ins["V"][0], ins["G"][0], ins["Beta"][0],
                float(attrs["scale"]), chunk)
    single = ctx.mesh is None or getattr(ctx.mesh, "size", 1) == 1
    route = delta_kernels.gated_delta_scan_route(
        (q.shape, operands[2].shape), chunk, q.dtype,
        attrs.get("interpret", False)) if single else "xla"
    compile_cache.stats().bump("route/gated_delta_scan:" + route)
    if route != "xla":
        return {"Out": delta_kernels.gated_delta_scan(
            *operands, interpret=route == "interpret")}
    return {"Out": delta_chunked(*operands)}


# ---------------------------------------------------------------------------
# Static shape/dtype rule (analysis.shape_infer).
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import (ShapeError, VarInfo, dim_ok,  # noqa: E402
                                    first)
from ..core.registry import register_shape_fn  # noqa: E402


@register_shape_fn("gated_delta_scan")
def _gated_delta_scan_shape(op, ins, attrs):
    q, v, beta = first(ins, "Q"), first(ins, "V"), first(ins, "Beta")
    if q.shape is None or v.shape is None:
        return {"Out": VarInfo(None, v.dtype)}
    if len(q.shape) != 4 or len(v.shape) != 4:
        raise ShapeError(f"gated_delta_scan: Q {list(q.shape)} and V "
                         f"{list(v.shape)} are not [B, T, H, K] and "
                         f"[B, T, H, V]")
    chunk = int(attrs.get("chunk", 64))
    if chunk < 1 or (q.shape[1] >= 0 and q.shape[1] % chunk):
        raise ShapeError(f"gated_delta_scan: T {q.shape[1]} is not whole "
                         f"chunks of {chunk} positions")
    for slot in ("K", "G"):
        x = first(ins, slot)
        if x.shape is not None and not (
                len(x.shape) == 4
                and all(dim_ok(a, c) for a, c in zip(x.shape, q.shape))):
            raise ShapeError(f"gated_delta_scan: {slot} {list(x.shape)} != Q "
                             f"{list(q.shape)}")
    if not all(dim_ok(a, c) for a, c in zip(v.shape[:3], q.shape[:3])):
        raise ShapeError(f"gated_delta_scan: V {list(v.shape)} is not "
                         f"[B, T, H, V] for Q {list(q.shape)}")
    if beta.shape is not None and not (
            len(beta.shape) == 3
            and all(dim_ok(a, c) for a, c in zip(beta.shape, q.shape[:3]))):
        raise ShapeError(f"gated_delta_scan: Beta {list(beta.shape)} is not "
                         f"[B, T, H] for Q {list(q.shape)}")
    return {"Out": v}


# ---------------------------------------------------------------------------
# Sharding-propagation rule (analysis.shard_prop)
# ---------------------------------------------------------------------------
from ..analysis.shard_prop import ShardConflict, first_in  # noqa: E402
from ..core.registry import register_shard_fn  # noqa: E402


@register_shard_fn("gated_delta_scan")
def _gated_delta_scan_shard(op, ins, attrs):
    """Out keeps Q's batch and head sharding: the heads are independent.
    The state runs along T and sums over the key features, so a sharded T
    or feature axis is a conflict."""
    q = first_in(ins, "Q")
    if q.spec is None:
        return {}
    if q.entry(1) or q.entry(3):
        raise ShardConflict(
            "gated_delta_scan: Q sharded along T or the features: the state "
            "is handed on along T and sums over the key features")
    return {"Out": (q.entry(0), None, q.entry(2), None)}
