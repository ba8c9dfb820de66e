"""Sharded embedding tables — the CTR/sparse machinery on a mesh.

Reference capability being replaced (SURVEY §2.5-2.6): row-sparse embedding
storage + prefetch (SparseRowCpuMatrix/SparsePrefetchRowCpuMatrix,
SparseRowMatrix.h:31,206), the SparseRemoteParameterUpdater fetching only
the rows a batch touches (RemoteParameterUpdater.h:265), and SelectedRows
gradients (selected_rows.h:19, lookup_table_op sparse grad path).

TPU-native design: the table lives vocab-sharded over a mesh axis
(P('tp', None)).  Two lookup strategies:

* GSPMD path (default): a plain gather on the sharded table — XLA partitions
  it into local gathers + collectives automatically.  Used by
  layers.embedding when the Parameter carries sharding=('tp', None).
* Manual shard_map path (``sharded_lookup``): each device resolves hits in
  its local vocab shard and psums partial rows — explicit control for use
  inside shard_map kernels (mirrors the reference's row-prefetch protocol,
  one all-reduce instead of a pserver round trip).

Gradients: the gather's vjp is a scatter-add, which GSPMD keeps sharded —
the SelectedRows update without any sparse-row bookkeeping.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax



def sharded_lookup(local_table, ids, axis_name="tp"):
    """Lookup into a vocab-sharded table inside shard_map.

    local_table: [V/n, D] this member's shard (row r holds global row
    ``offset + r``).  ids: int [...] global row ids (replicated).
    Returns [..., D] replicated — one psum over the axis.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    vshard = local_table.shape[0]
    offset = idx * vshard
    local = ids - offset
    hit = (local >= 0) & (local < vshard)
    safe = jnp.clip(local, 0, vshard - 1)
    rows = local_table[safe]
    rows = jnp.where(hit[..., None], rows, jnp.zeros_like(rows))
    return lax.psum(rows, axis_name)


def sharded_lookup_grad_rows(ids, grad_out, vocab_size, axis_name="tp"):
    """Scatter-add grads back to this member's shard (SelectedRows apply).

    Utility for hand-rolled shard_map training loops; under jit+GSPMD this
    is derived automatically from sharded_lookup's vjp.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    vshard = vocab_size // n
    offset = idx * vshard
    local = ids - offset
    hit = (local >= 0) & (local < vshard)
    safe = jnp.where(hit, local, 0)
    g = jnp.where(hit[..., None], grad_out, jnp.zeros_like(grad_out))
    shard = jnp.zeros((vshard, grad_out.shape[-1]), grad_out.dtype)
    return shard.at[safe.reshape(-1)].add(
        g.reshape(-1, grad_out.shape[-1]))
