"""Collective primitives over mesh axes.

The TPU-native replacement for the reference's entire communication stack:
NCCLAllReduce/Reduce/Bcast kernels (operators/nccl/nccl_op.cu.cc:41-153), the
v1 pserver gradient exchange (ParameterServer2::addGradient/sendParameter),
and fluid's gRPC send/recv ops.  Inside shard_map these lower to XLA
collectives scheduled on ICI; outside they are jnp no-ops so the same model
code runs single-chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax



def _in_spmd(axis_name) -> bool:
    try:
        lax.axis_index(axis_name)
        return True
    except NameError:
        return False


def psum(x, axis_name="dp"):
    try:
        return lax.psum(x, axis_name)
    except NameError:
        return x


def all_reduce(x, axis_name="dp", op="sum"):
    try:
        if op == "sum":
            return lax.psum(x, axis_name)
        if op == "mean":
            return lax.pmean(x, axis_name)
        if op == "max":
            return lax.pmax(x, axis_name)
        if op == "min":
            return lax.pmin(x, axis_name)
    except NameError:
        return x
    raise ValueError(f"unknown all_reduce op {op}")


def all_gather(x, axis_name="tp", axis=0, tiled=True):
    try:
        return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)
    except NameError:
        return x


def reduce_scatter(x, axis_name="dp", axis=0):
    try:
        return lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)
    except NameError:
        return x


def ppermute(x, axis_name, perm):
    return lax.ppermute(x, axis_name, perm)


def broadcast(x, axis_name="dp", src=0):
    """Select src's value on every member (NCCLBcast analog)."""
    try:
        idx = lax.axis_index(axis_name)
    except NameError:
        return x
    n = lax.axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(src, i) for i in range(n)])


def barrier(axis_name="dp"):
    """pserver synchronize() analog: a psum forces a rendezvous."""
    return psum(jnp.ones(()), axis_name)


def all_to_all(x, axis_name, split_axis, concat_axis):
    return lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=True)
