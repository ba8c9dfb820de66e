"""Mixture-of-Experts layer with expert-parallel all-to-all dispatch.

Reference capability frame: the closest ancestors are the v1 per-layer
device placement (ParallelNeuralNetwork.cpp) and sparse gradient machinery
(SelectedRows / row-sparse CTR); the reference never shipped MoE, so this is
capability-forward surface the ep mesh axis exists for.

TPU-native design (Switch/GShard style, static shapes throughout):
tokens pick their top-k experts by a learned gate; a [T, E, C] one-hot
dispatch tensor (capacity C per expert, overflow tokens dropped — residual
connections carry them) turns routing into einsums that ride the MXU; the
[E, C, D] expert batches hop devices with ONE all_to_all over the 'ep' axis
each way (ICI), each device runs only its local experts' FFNs, and the
combine einsum restores token order weighted by gate probabilities.  The
load-balancing auxiliary loss is the standard E * sum(fraction_e * prob_e).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


__all__ = ["moe_dispatch", "moe_ffn", "load_balancing_loss"]


def _axis_size(axis_name):
    if axis_name is None:
        return 1
    try:
        return lax.axis_size(axis_name)
    except NameError:
        return 1


def moe_dispatch(gates, capacity: int, top_k: int = 2):
    """Routing tensors from gate probabilities.

    gates: [T, E] softmax probabilities.  Returns (dispatch [T, E, C] {0,1},
    combine [T, E, C] floats).  Token t goes to its k highest-probability
    experts, subject to each expert accepting at most ``capacity`` tokens
    (first-come order, GShard §3.2); overflow slots are dropped.
    """
    T, E = gates.shape
    dispatch = jnp.zeros((T, E, capacity), gates.dtype)
    combine = jnp.zeros((T, E, capacity), gates.dtype)
    masked = gates
    # occupancy carried across the k rounds so round-2 picks respect slots
    # taken in round 1
    occupancy = jnp.zeros((E,), jnp.int32)
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=1)                    # [T]
        mask = jax.nn.one_hot(idx, E, dtype=gates.dtype)    # [T, E]
        pos = occupancy[None, :] + (
            jnp.cumsum(mask, axis=0) - mask).astype(jnp.int32)  # [T, E]
        keep = mask * (pos < capacity)
        pos_t = jnp.sum(pos * mask, axis=1).astype(jnp.int32)   # [T]
        slot = jax.nn.one_hot(jnp.clip(pos_t, 0, capacity - 1),
                              capacity, dtype=gates.dtype)      # [T, C]
        d = keep[:, :, None] * slot[:, None, :]
        dispatch = dispatch + d
        combine = combine + d * jnp.sum(gates * mask, axis=1)[:, None, None]
        occupancy = occupancy + jnp.sum(keep, axis=0).astype(jnp.int32)
        masked = masked * (1.0 - mask)      # exclude picked expert next round
    return dispatch, combine


def load_balancing_loss(gates, dispatch):
    """E * sum_e(mean-fraction-of-tokens_e * mean-gate-prob_e) — the
    Switch-Transformer aux loss keeping experts evenly loaded."""
    E = gates.shape[1]
    frac = jnp.mean(jnp.sum(dispatch, axis=2), axis=0)   # [E] token fraction
    prob = jnp.mean(gates, axis=0)                       # [E]
    return E * jnp.sum(frac * prob)


def moe_ffn(x, gate_w, expert_w1, expert_w2, axis_name="ep", top_k=2,
            capacity_factor=1.25, activation=jax.nn.relu):
    """Expert-parallel MoE FFN for one device's tokens.

    x [T, D] this device's tokens; gate_w [D, E] (replicated);
    expert_w1 [E_local, D, H], expert_w2 [E_local, H, D] — THIS device's
    expert slice (shard the stacked weights P('ep', ...)).  E = E_local *
    axis_size.  Returns (out [T, D], aux_loss scalar).  Outside shard_map
    (axis absent) it degrades to a single-device MoE over all experts.
    """
    T, D = x.shape
    n = _axis_size(axis_name)
    e_local = expert_w1.shape[0]
    E = e_local * n
    logits = x @ gate_w                                  # [T, E]
    gates = jax.nn.softmax(logits, axis=-1)
    capacity = max(1, int(capacity_factor * top_k * T / E))
    dispatch, combine = moe_dispatch(gates, capacity, top_k)
    aux = load_balancing_loss(gates, dispatch)

    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)   # [E, C, D]
    if n > 1:
        # hop out (tiled all_to_all): the expert axis splits into n chunks
        # of e_local — chunk j travels to the device owning those experts —
        # and the n source batches concatenate on the token axis:
        #   [E, C, D] -> [e_local, n*C, D]
        arrived = lax.all_to_all(expert_in, axis_name, split_axis=0,
                                 concat_axis=1, tiled=True)
    else:
        arrived = expert_in

    h = activation(jnp.einsum("ecd,edh->ech", arrived, expert_w1))
    out_e = jnp.einsum("ech,ehd->ecd", h, expert_w2)

    if n > 1:
        # inverse hop: [e_local, n*C, D] -> [E, C, D], returning each
        # source's rows (the exact transpose of the hop out)
        returned = lax.all_to_all(out_e, axis_name, split_axis=1,
                                  concat_axis=0, tiled=True)
    else:
        returned = out_e

    out = jnp.einsum("tec,ecd->td", combine, returned)
    return out, aux
