"""Multihead weighted loss + energy-force loss.

reference: hydragnn/models/Base.py:349-461 (`loss`, `loss_hpweighted`,
`energy_force_loss`). The reference's autograd-of-forward force path
(Base.py:389-395) becomes a clean nested `jax.grad` over positions.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..config.config import ModelConfig
from ..graphs.batch import GraphBatch
from ..ops.activations import masked_loss
from ..ops.segment import global_sum_pool


def head_targets(cfg: ModelConfig, batch: GraphBatch) -> List[jnp.ndarray]:
    """Slice packed labels into per-head targets using static offsets —
    the mask-based replacement for the reference's per-batch index math
    (`get_head_indices`, train/train_validate_test.py:314-377)."""
    targets = []
    for head in cfg.heads:
        y = batch.y_graph if head.head_type == "graph" else batch.y_node
        end = head.offset + head.output_dim
        if y is None or y.shape[1] < end:
            have = 0 if y is None else y.shape[1]
            raise ValueError(
                f"{head.head_type} head needs packed label columns "
                f"[{head.offset}:{end}) but the batch carries {have} — "
                "the dataset provides fewer targets than "
                "Variables_of_interest selects")
        targets.append(y[:, head.offset:end])
    return targets


def head_loss_mask(batch: GraphBatch, ih: int, head) -> jnp.ndarray:
    """The loss mask of head `ih`: real graphs (or real nodes) — and, on a
    multi-dataset mixture batch (``batch.dataset_id`` set, docs/gfm.md),
    only the entries belonging to head ih's member dataset. The head↔
    dataset convention is by index: head ih supervises graphs with
    ``dataset_id == ih`` (GfmMixtureLoader assigns ids in sorted member
    order; validate_member_heads pins the correspondence). Node-level
    heads broadcast the per-graph id through ``node_graph``; padding
    graphs carry id -1 so they match no head with or without the base
    mask."""
    if head.head_type == "graph":
        mask = batch.graph_mask
        if batch.dataset_id is not None:
            mask = mask & (batch.dataset_id == ih)
    else:
        mask = batch.node_mask
        if batch.dataset_id is not None:
            mask = mask & (batch.dataset_id[batch.node_graph] == ih)
    return mask


def multihead_loss(cfg: ModelConfig, loss_name: str, outputs, outputs_var,
                   batch: GraphBatch):
    """Per-task weighted sum (reference: Base.loss_hpweighted, Base.py:434-461).

    Returns (total, list of per-task losses).

    On mixture batches carrying ``dataset_id`` this IS the head-masked
    multi-task step (docs/gfm.md): the shared conv stack has already run
    once over the packed mixture, every head's output covers the full
    graph/node tensor, and each head's masked mean sees only its own
    dataset's entries. Determinism boundary (the PR 6/PR 8 contract):
    each per-head loss/grad is a fixed-shape masked reduction — bitwise
    reproducible — and per-head gradients only reassociate at this
    weighted-sum combine, so a one-hot-weighted mixture step matches the
    corresponding single-dataset step bitwise on exactly-representable
    data (tests/test_gfm.py pins it)."""
    with jax.named_scope("loss"):  # trace scope, PERF.md section 3
        targets = head_targets(cfg, batch)
        tot = 0.0
        tasks = []
        for ih, head in enumerate(cfg.heads):
            mask = head_loss_mask(batch, ih, head)
            var = outputs_var[ih] if outputs_var is not None else None
            li = masked_loss(loss_name, outputs[ih], targets[ih], mask, var)
            tasks.append(li)
            tot = tot + cfg.task_weights[ih] * li
        return tot, tasks


def auto_force_weight(energy, forces, graph_mask, node_mask,
                      energy_weight: float = 1.0):
    """The reference's force-loss balancing: scale the force term by the
    TRUE-label magnitude ratio so energy and forces contribute equally
    (reference: Base.energy_force_loss force_loss_weight,
    Base.py:400-404), computed over the masked labels of one batch."""
    gm = graph_mask[:, None]
    nm = node_mask[:, None]
    e_mean = (jnp.sum(jnp.abs(energy) * gm)
              / jnp.maximum(jnp.sum(gm), 1.0))
    f_mean = (jnp.sum(jnp.abs(forces) * nm)
              / jnp.maximum(jnp.sum(nm) * forces.shape[-1], 1.0))
    return energy_weight * e_mean / (f_mean + 1e-8)


def energy_forces_from_node_head(apply_fn: Callable, variables, batch,
                                 train: bool = False):
    """(graph_energies [G, 1], forces [N, 3], new_batch_stats) from a
    node-level energy head — THE EF-head convention, in one place: head
    0's first column is the per-node energy, graph energy is its masked
    segment sum, and forces are -d(sum of real-graph energies)/d pos.
    Shared by `energy_force_loss` (training/eval) and the serving
    engine's ``ef_forward`` mode (docs/serving.md), so the quantity the
    model is trained on and the quantity it serves can never drift.

    ``apply_fn(variables, batch, train) -> ((outputs, outputs_var),
    new_batch_stats_or_None)`` — the `energy_force_loss` apply contract.
    """
    def total_energy(pos):
        b = batch.replace(pos=pos)
        (outputs, _), new_bs = apply_fn(variables, b, train=train)
        node_e = outputs[0][:, :1]
        graph_e = global_sum_pool(node_e, b.node_graph, b.num_graphs,
                                  b.node_mask)
        # sum over real graphs only; padding contributes zero by masking
        return (jnp.sum(jnp.where(batch.graph_mask[:, None], graph_e,
                                  0.0)),
                (graph_e, new_bs))

    # trace scope (PERF.md section 3): the forward and its transpose with
    # respect to the positions, in training, evaluation and serving alike
    with jax.named_scope("ef_forces"):
        (_, (graph_e, new_bs)), neg_forces = jax.value_and_grad(
            total_energy, has_aux=True)(batch.pos)
        return graph_e, -neg_forces, new_bs


def energy_force_loss(apply_fn: Callable, variables, cfg: ModelConfig,
                      batch: GraphBatch, loss_name: str = "mae",
                      energy_weight: float = 1.0, force_weight: float = 1.0,
                      train: bool = False):
    """Energy + force loss via grad of summed nodal energies w.r.t. positions
    (reference: Base.energy_force_loss, Base.py:359-411).

    Head 0 must be a node-level energy head; graph energy = masked sum of
    node energies; forces = -dE/dpos.

    ``apply_fn(variables, batch, train) -> ((outputs, outputs_var),
    new_batch_stats_or_None)``: batch-norm stacks MUST thread their updated
    running stats out (the reference's torch train mode updates them on
    this path too — silently freezing them at init makes eval-mode
    normalization diverge from what training fit). Returned in the aux
    dict under "batch_stats"."""
    graph_e, forces_pred, new_bs = energy_forces_from_node_head(
        apply_fn, variables, batch, train=train)

    with jax.named_scope("loss"):
        e_loss = masked_loss(loss_name, graph_e, batch.energy,
                             batch.graph_mask)
        f_loss = masked_loss(loss_name, forces_pred, batch.forces,
                             batch.node_mask)
        if force_weight == "auto":
            force_weight = auto_force_weight(
                batch.energy, batch.forces, batch.graph_mask,
                batch.node_mask, energy_weight)
        total = energy_weight * e_loss + force_weight * f_loss
    return total, {"energy_loss": e_loss, "force_loss": f_loss,
                   "energy_pred": graph_e, "forces_pred": forces_pred,
                   "batch_stats": new_bs}
