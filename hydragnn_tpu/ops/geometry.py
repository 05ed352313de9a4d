"""Edge geometry helpers.

Replaces reference's get_edge_vectors_and_lengths
(reference: hydragnn/utils/model/operations.py:20) with PBC shift support.
"""
from __future__ import annotations

import jax.numpy as jnp

from .segment import edge_gather, neighbor_gather


LENGTH_EPS = 1e-9   # under the root of every length: d/dpos stays finite at 0


def edge_vectors(pos, senders, receivers, edge_shifts=None,
                 eps: float = LENGTH_EPS):
    """Displacement sender->receiver view: vec_k = pos[send_k] + shift_k - pos[recv_k].

    Returns (vec [E,3], length [E]). Padding edges (sender == receiver ==
    padding node, zero shift) get length 0; callers mask at aggregation.
    """
    vec = pos[senders] - pos[receivers]
    if edge_shifts is not None:
        vec = vec + edge_shifts
    length = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + eps)
    return vec, length


def slot_vectors(pos, batch, eps: float = 0.0):
    """`edge_vectors` in the dense neighbour layout: vec[i, a] =
    pos[nbr[i, a]] + shift - pos[i] per slot ([N, K, 3]) and its length
    ([N, K]), with `eps` under the root as `edge_vectors` has it. Padding
    slots get the zero vector and the length 1, put in BEFORE the root is
    taken, so that whatever is computed from them (and its gradient) is
    finite and a mask on the result leaves an exact 0. The one conversion
    from edge order is the 3-wide `edge_gather` of the shifts."""
    vec = neighbor_gather(pos, batch.nbr) - pos[:, None, :]
    if batch.edge_shifts is not None:
        vec = vec + edge_gather(batch.edge_shifts, batch)
    mask = batch.nbr_mask
    vec = jnp.where(mask[..., None], vec, 0.0)
    sq = jnp.sum(vec * vec, axis=-1)
    if eps:
        sq = sq + eps
    length = jnp.sqrt(jnp.where(mask, sq, 1.0))
    return vec, length


def edge_lengths(batch):
    """The length of every edge from the batch's positions, in the order
    of the layout the batch carries: per slot ([N, K]; a padding slot reads
    1) with the dense neighbour tables, per edge ([E]; a padding edge reads
    sqrt(eps)) without. Every real slot holds the bits of its edge, so a
    stack whose per-edge input is a function of distance makes it in slot
    order once a step and its convs convert no layout."""
    if batch.nbr is not None:
        return slot_vectors(batch.pos, batch, eps=LENGTH_EPS)[1]
    return edge_vectors(batch.pos, batch.senders, batch.receivers,
                        batch.edge_shifts)[1]
