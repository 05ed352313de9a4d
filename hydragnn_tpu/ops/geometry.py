"""Edge geometry helpers.

Replaces reference's get_edge_vectors_and_lengths
(reference: hydragnn/utils/model/operations.py:20) with PBC shift support.
"""
from __future__ import annotations

import jax.numpy as jnp

from .segment import edge_gather, neighbor_gather


def edge_vectors(pos, senders, receivers, edge_shifts=None, eps: float = 1e-9):
    """Displacement sender->receiver view: vec_k = pos[send_k] + shift_k - pos[recv_k].

    Returns (vec [E,3], length [E]). Padding edges (sender == receiver ==
    padding node, zero shift) get length 0; callers mask at aggregation.
    """
    vec = pos[senders] - pos[receivers]
    if edge_shifts is not None:
        vec = vec + edge_shifts
    length = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + eps)
    return vec, length


def slot_vectors(pos, batch):
    """`edge_vectors` in the dense neighbour layout: vec[i, a] =
    pos[nbr[i, a]] + shift - pos[i] per slot ([N, K, 3]) and its length
    ([N, K]). Padding slots get the zero vector and the length 1, put in
    BEFORE the root is taken, so that whatever is computed from them (and
    its gradient) is finite and a mask on the result leaves an exact 0."""
    vec = neighbor_gather(pos, batch.nbr) - pos[:, None, :]
    if batch.edge_shifts is not None:
        vec = vec + edge_gather(batch.edge_shifts, batch)
    mask = batch.nbr_mask
    vec = jnp.where(mask[..., None], vec, 0.0)
    length = jnp.sqrt(jnp.where(mask, jnp.sum(vec * vec, axis=-1), 1.0))
    return vec, length
