"""Block diffusion (BD3-LM, arXiv:2503.09573; SDAR, arXiv:2510.06303): a
language model trained to restore the masked tokens of a block given the
clean blocks before it, all blocks of a sequence in one step.  Two program
ops, each under its own ``pt.`` scope:

* ``block_diffusion_noise``: the forward process.  A sequence of L tokens
  is L / ``block_size`` blocks; block b draws its masking rate from the grid,
  t_b = (d_b + 1) / ``grid`` for an integer d_b in [0, grid), and token i
  of it is masked where u_i <= d_b, u_i an integer in [0, grid): so
  P(masked) = t_b exactly.  A masked token becomes ``mask_id``.  Its
  loss weight is 1 / t_b where masked, else 0: the linear schedule's
  negative ELBO (alpha_t = 1 - t) is the mean over the L tokens of
  weight * cross-entropy.  The draws are integer inputs, so the program
  itself holds no random state and a seed gives the same noise.
* ``block_diffusion_attention``: attention over the noisy stream and the
  clean one joined, 2L positions, under the block-diffusion mask
  (``pallas_kernels.BlockDiffusionMask``) through the flash kernels on a
  TPU (or interpreted, where the op's ``interpret`` attribute asks) where L
  is whole blocks of both, else the plain formula; counted as
  ``route/block_diffusion_attention:{kernel,xla}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core import compile_cache
from ..core.registry import register_op
from . import pallas_kernels


@register_op("block_diffusion_noise")
def _block_diffusion_noise(ctx, ins, attrs):
    """X [B, L] int (clean ids), Level [B, L / block_size] int in [0, grid),
    Draw [B, L] int in [0, grid) -> Out [B, L] (X with ``mask_id`` where
    masked), Weight [B, L] float32 (1 / t_b where masked, else 0)."""
    x0, level, draw = ins["X"][0], ins["Level"][0], ins["Draw"][0]
    block, grid = int(attrs["block_size"]), int(attrs["grid"])
    d = jnp.repeat(level.astype(jnp.int32), block, axis=1)     # [B, L]
    masked = draw.astype(jnp.int32) <= d
    rate = (d.astype(jnp.float32) + 1.0) / grid                # t_b
    return {"Out": jnp.where(masked, jnp.asarray(attrs["mask_id"], x0.dtype),
                             x0),
            "Weight": jnp.where(masked, 1.0 / rate, 0.0)}


@register_op("block_diffusion_attention")
def _block_diffusion_attention(ctx, ins, attrs):
    """Q [B, 2L, H, D], K and V [B, 2L, H_kv, D] (H_kv dividing H), the
    noisy stream at 0..L-1 and the clean one at L..2L-1 -> Out [B, 2L, H,
    D]: softmax at D^-1/2 under the block-diffusion mask of ``block_size``."""
    q = ins["Q"][0]
    length = q.shape[1] // 2
    mask = pallas_kernels.BlockDiffusionMask(int(attrs["block_size"]), length)
    block_q, block_k = attrs.get("block_q", 1024), attrs.get("block_k", 1024)
    interpret = bool(attrs.get("interpret", False))
    kernel = (jax.default_backend() == "tpu" or interpret) and not (
        length % min(block_q, length) or length % min(block_k, length))
    compile_cache.stats().bump("route/block_diffusion_attention:"
                               + ("kernel" if kernel else "xla"))
    return {"Out": pallas_kernels.flash_attention(
        q, ins["K"][0], ins["V"][0], mask=mask, block_q=block_q,
        block_k=block_k, use_pallas=kernel, interpret=interpret and kernel)}


# ---------------------------------------------------------------------------
# Static shape/dtype rules (analysis.shape_infer).
# ---------------------------------------------------------------------------
from ..analysis.shape_infer import (ShapeError, VarInfo, dim_ok,  # noqa: E402
                                    first)
from ..core.registry import register_shape_fn  # noqa: E402


@register_shape_fn("block_diffusion_noise")
def _block_diffusion_noise_shape(op, ins, attrs):
    x, level, draw = first(ins, "X"), first(ins, "Level"), first(ins, "Draw")
    block = int(attrs["block_size"])
    if x.shape is None:
        return {"Out": x, "Weight": VarInfo(None, "float32")}
    if len(x.shape) != 2:
        raise ShapeError(f"block_diffusion_noise: X {list(x.shape)} is not "
                         f"[B, L]")
    length = x.shape[1]
    if length >= 0 and length % block:
        raise ShapeError(f"block_diffusion_noise: L {length} is not whole "
                         f"blocks of {block}")
    if draw.shape is not None and not all(
            dim_ok(a, c) for a, c in zip(draw.shape, x.shape)):
        raise ShapeError(f"block_diffusion_noise: Draw {list(draw.shape)} "
                         f"!= X {list(x.shape)}")
    if level.shape is not None and length >= 0 and not dim_ok(
            level.shape[-1], length // block):
        raise ShapeError(f"block_diffusion_noise: Level {list(level.shape)} "
                         f"is not one level a block of X {list(x.shape)}")
    return {"Out": x, "Weight": VarInfo(tuple(x.shape), "float32")}


@register_shape_fn("block_diffusion_attention")
def _block_diffusion_attention_shape(op, ins, attrs):
    q, k, v = first(ins, "Q"), first(ins, "K"), first(ins, "V")
    if q.shape is None:
        return {"Out": q}
    if len(q.shape) != 4:
        raise ShapeError(f"block_diffusion_attention: Q {list(q.shape)} is "
                         f"not [B, 2L, H, D]")
    positions, block = q.shape[1], int(attrs["block_size"])
    if positions >= 0 and (positions % (2 * block)):
        raise ShapeError(f"block_diffusion_attention: {positions} positions "
                         f"are not two streams of whole blocks of {block}")
    for name, o in (("K", k), ("V", v)):
        if o.shape is None:
            continue
        if len(o.shape) != 4 or not dim_ok(o.shape[1], positions) or (
                name == "K" and not dim_ok(o.shape[3], q.shape[3])):
            raise ShapeError(f"block_diffusion_attention: Q {list(q.shape)} "
                             f"vs {name} {list(o.shape)}")
        if q.shape[2] >= 0 and o.shape[2] > 0 and q.shape[2] % o.shape[2]:
            raise ShapeError(f"block_diffusion_attention: {name}'s "
                             f"{o.shape[2]} heads do not divide Q's "
                             f"{q.shape[2]}")
    if v.shape is None:
        return {"Out": q}
    return {"Out": VarInfo(tuple(q.shape[:-1]) + (v.shape[-1],), q.dtype)}
