"""Ring attention: sequence/context parallelism over the 'sp' mesh axis.

A NEW capability relative to the 2017 reference (SURVEY §2.6 confirms the
reference has no sequence parallelism — long sequences were handled by LoD
packing only).  Required by the rebuild spec for long-context scaling.

Blockwise ring attention (Liu et al.): each sp shard holds a query block and
circulates key/value blocks around the ring with ppermute, maintaining
numerically-stable streaming softmax statistics (m, l) so the result is exact
full attention.  Communication overlaps compute; memory is O(T/sp).
Use inside shard_map with sequences sharded on 'sp'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import compat
from ..core import compile_cache


def _block_attn(q, k, v, bias=None):
    """Stable block attention returning (out_unnorm, m, l)."""
    s = jnp.einsum("...qd,...kd->...qk", q, k)
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("...qk,...kd->...qd", p, v)
    return o, m, l


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = False,
                   scale: float = None, use_flash=None, block_q: int = 256,
                   block_k: int = 256, interpret: bool = False):
    """Exact attention with K/V circulated around the sp ring.

    q,k,v: [B, T_local, H, D] (local sequence shard).  Returns [B,T_local,H,D].
    With ``causal``, blocks wholly in the future are skipped via masking
    (shapes stay static; the mask zeroes their contribution).

    ``use_flash`` (default: auto on TPU when block-divisible) computes each
    ring hop with the fused Pallas flash kernel via its (out, lse)
    residuals and merges hops by streaming-softmax — O(T_local) memory per
    hop instead of the [T_local, T_local] score matrix, composing the two
    long-context mechanisms (ring over ICI x flash in VMEM).
    """
    T_loc = q.shape[1]
    divisible = (T_loc % min(block_q, T_loc) == 0
                 and T_loc % min(block_k, T_loc) == 0)
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    # non-divisible local blocks always fall back to the exact jnp path —
    # same policy as the device-global wrapper, so forcing the kernel via
    # use_flash/interpret degrades instead of raising mid-training; the
    # choice is counted at trace time (route/ring_attention:*)
    if (use_flash or interpret) and divisible:
        compile_cache.stats().bump(
            "route/ring_attention:" + ("interpret" if interpret
                                       else "pallas"))
        return _ring_attention_flash(q, k, v, axis_name, causal, scale,
                                     block_q, block_k, interpret)
    compile_cache.stats().bump("route/ring_attention:reference")
    return _ring_attention_jnp(q, k, v, axis_name, causal, scale)


def _ring_attention_flash(q, k, v, axis_name, causal, scale, block_q,
                          block_k, interpret):
    from ..ops.pallas_kernels import flash_attention_with_lse

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, T, H, D = q.shape
    scale = scale if scale is not None else D ** -0.5

    def flat(x):
        return jnp.moveaxis(x, 2, 1).reshape(B * H, T, x.shape[-1])

    q3, k3, v3 = flat(q), flat(k), flat(v)
    in_dtype = q.dtype
    perm = [(i, (i + 1) % n) for i in range(n)]

    # hop 0 is ALWAYS this device's own K/V block (the causal diagonal), so
    # the kernel's static causal flag is exact here; later hops are whole
    # past/future blocks — full kernel plus a merge-level mask
    out, lse = flash_attention_with_lse(q3, k3, v3, causal=causal,
                                        sm_scale=scale, block_q=block_q,
                                        block_k=block_k, interpret=interpret)
    # the streaming merge runs in f32 (lse is f32); cast back after the ring
    out = out.astype(jnp.float32)
    kc = lax.ppermute(k3, axis_name, perm)
    vc = lax.ppermute(v3, axis_name, perm)

    def step(carry, i):
        kc, vc, out, lse = carry
        src = (my - i) % n
        o_b, lse_b = flash_attention_with_lse(
            q3, kc, vc, causal=False, sm_scale=scale, block_q=block_q,
            block_k=block_k, interpret=interpret)
        if causal:
            # future blocks (src > my) contribute nothing: -inf lse zeroes
            # their merge weight while shapes stay static
            lse_b = jnp.where(src < my, lse_b, -jnp.inf)
        m = jnp.maximum(lse, lse_b)
        a = jnp.exp(lse - m)
        b = jnp.exp(lse_b - m)
        denom = jnp.maximum(a + b, 1e-38)
        out = (out * a + o_b.astype(jnp.float32) * b) / denom
        lse = m + jnp.log(denom)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (kc, vc, out, lse), None

    if n > 1:
        (_, _, out, _), _ = lax.scan(step, (kc, vc, out, lse),
                                     jnp.arange(1, n))
    out = out.astype(in_dtype)
    return jnp.moveaxis(out.reshape(B, H, T, v.shape[-1]), 1, 2)


def _ring_attention_jnp(q, k, v, axis_name, causal, scale):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    q = q * scale
    # work in [B, H, T, D]
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    T = qh.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def bias_for(src_idx):
        if not causal:
            return None
        # global positions: my block rows, src block cols
        qpos = my * T + jnp.arange(T)[:, None]
        kpos = src_idx * T + jnp.arange(T)[None, :]
        return jnp.where(kpos <= qpos, 0.0, -1e30)

    def step(carry, i):
        kh_c, vh_c, o, m, l = carry
        src = (my - i) % n            # whose kv block we currently hold
        bias = bias_for(src)
        o_b, m_b, l_b = _block_attn(qh, kh_c, vh_c, bias)
        m_new = jnp.maximum(m, m_b)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_b - m_new)
        o = o * alpha + o_b * beta
        l = l * alpha + l_b * beta
        kh_n = lax.ppermute(kh_c, axis_name, perm)
        vh_n = lax.ppermute(vh_c, axis_name, perm)
        return (kh_n, vh_n, o, m_new, l), None

    o0 = jnp.zeros_like(qh)
    # derive from qh so the carries inherit its varying-manual-axes type
    # under shard_map (a constant init would fail lax.scan's carry check)
    m0 = jnp.full_like(qh[..., :1], -1e30)
    l0 = jnp.zeros_like(qh[..., :1])
    (_, _, o, m, l), _ = lax.scan(
        step, (kh, vh, o0, m0, l0), jnp.arange(n))
    out = o / jnp.maximum(l, 1e-20)
    return jnp.swapaxes(out, 1, 2)


def ring_attention_sharded(q, k, v, mesh, causal=False, axis_name: str = "sp",
                           scale=None, block_q: int = 1024,
                           block_k: int = 1024, use_flash=None,
                           interpret: bool = False):
    """Global-array entry point: a shard_map over the sp axis with
    :func:`ring_attention` inside.  q,k,v: global [B, T, H, D]; returns the
    same global shape, time axis sharded on ``axis_name``.

    When sp is the only mesh axis wider than 1 the region is manual over
    EVERY axis: Mosaic refuses a Pallas kernel inside a partially-manual
    region ("Mosaic kernels cannot be automatically partitioned"), and
    width-1 axes shard nothing, so this is the same computation.  With
    another axis in play (dp/tp stay GSPMD-managed, mirroring
    pipeline_program.py) the region is manual over sp only and the hops
    run the jnp blockwise path unless the caller forces a kernel.

    This is what the ``flash_attention`` op lowering calls when the mesh has
    sp>1 — the first-class framework path to sequence parallelism: a
    Paddle-API user writes ``layers.flash_attention(...)`` (or
    ``nets.scaled_dot_product_attention``) and long sequences shard over the
    ring without touching shard_map themselves.
    """
    spec = P(None, axis_name)
    sp_only = all(mesh.shape[a] == 1 for a in mesh.axis_names
                  if a != axis_name)
    if not sp_only and use_flash is None:
        use_flash = False
    body = functools.partial(ring_attention, axis_name=axis_name,
                             causal=causal, scale=scale, block_q=block_q,
                             block_k=block_k, use_flash=use_flash,
                             interpret=interpret)
    return compat.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=mesh.axis_names if sp_only else {axis_name},
        check_vma=False)(q, k, v)


def sequence_parallel_attention(q, k, v, axis_name="sp", causal=False):
    """Ulysses-style all-to-all alternative: swap sequence sharding for head
    sharding, run full attention locally, swap back.  Prefer when head count
    is divisible by sp and sequence length is moderate."""
    # [B, T/s, H, D] -> all_to_all -> [B, T, H/s, D]
    qt = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kt = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vt = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    d = qt.shape[-1]
    s = jnp.einsum("bthd,bshd->bhts", qt * (d ** -0.5), kt)
    if causal:
        T = s.shape[-1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhts,bshd->bthd", p, vt)
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)
