"""What the plain references share: the structure container, the
energy-to-forces step, BatchNorm over real atoms, and the MAE losses.

A reference sees real atoms and real edges only: no padding, no masks, no
neighbour tables, no kernels. Everything runs in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
rounded to bfloat16 operands otherwise), so a reference is the yardstick
and the system's default-precision result is what gets measured against it.
"""
from __future__ import annotations

from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5       # models/layers.MaskedBatchNorm.epsilon
LENGTH_EPS = 1e-9   # ops/geometry.edge_vectors


def concat_structures(samples: Sequence) -> Dict[str, np.ndarray]:
    """GraphSamples -> one edge-list structure dict with global node ids."""
    offsets = np.cumsum([0] + [s.num_nodes for s in samples])
    return {
        "x": np.concatenate([s.x for s in samples]).astype(np.float32),
        "pos": np.concatenate([s.pos for s in samples]).astype(np.float32),
        "senders": np.concatenate(
            [s.senders + o for s, o in zip(samples, offsets)]),
        "receivers": np.concatenate(
            [s.receivers + o for s, o in zip(samples, offsets)]),
        "shifts": np.concatenate(
            [s.edge_shifts for s in samples]).astype(np.float32),
        "node_graph": np.repeat(np.arange(len(samples)),
                                [s.num_nodes for s in samples]),
        "energy": np.concatenate([s.energy for s in samples]),
        "forces": np.concatenate([s.forces for s in samples]),
    }


def edge_lengths(pos, struct):
    vec = pos[struct["senders"]] - pos[struct["receivers"]] + struct["shifts"]
    return jnp.sqrt(jnp.sum(vec * vec, axis=-1) + LENGTH_EPS)


def dense(p, x):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def mlp(p, x, act):
    """flax MLP: dense_0 .. dense_{n-1}, activation between, none at the end."""
    n = len(p)
    for i in range(n):
        x = dense(p[f"dense_{i}"], x)
        if i < n - 1:
            x = act(x)
    return x


def batch_norm(p, stats, x, train: bool):
    """BatchNorm1d over the real atoms of the batch (train) or with the
    running statistics (eval); biased variance, as torch normalises."""
    if train:
        mean = jnp.mean(x, axis=0)
        var = jnp.mean((x - mean) ** 2, axis=0)
    else:
        mean, var = stats["mean"], stats["var"]
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]


def energies_and_forces(node_energy_fn: Callable, variables, struct,
                        num_graphs: int, train: bool):
    """(E [G], F [N, 3]): E_g = sum of node energies of graph g,
    F = -d(sum_g E_g)/d pos."""
    def total(pos):
        node_e = node_energy_fn(variables, struct, pos, train)
        graph_e = jax.ops.segment_sum(node_e, struct["node_graph"],
                                      num_graphs)
        return jnp.sum(graph_e), graph_e

    with jax.default_matmul_precision("highest"):
        (_, graph_e), grad = jax.value_and_grad(total, has_aux=True)(
            jnp.asarray(struct["pos"]))
    return graph_e, -grad


def mae_losses(graph_e, forces, struct):
    """(energy MAE, force MAE), each a mean over its own elements — what
    train/loss.energy_force_loss computes with loss 'mae'. The total with
    unit weights is their sum."""
    e = np.mean(np.abs(np.asarray(graph_e) - struct["energy"]))
    f = np.mean(np.abs(np.asarray(forces) - struct["forces"]))
    return float(e), float(f)
