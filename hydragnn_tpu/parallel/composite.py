"""Composed-mesh training: data parallelism x graph (edge) sharding.

`Architecture.graph_shards > 1` trains each data shard's graph with its
EDGE set sharded over a second mesh axis — the user-reachable form of the
edge-sharded mode in parallel/graph_parallel.py (node features replicated
over the ``graph`` axis, edge memory and message compute cut by its size).
The reference has no analogue (its graphs fit one GPU; SURVEY.md §5.7);
this is the GNN counterpart of sequence/context parallelism for graphs too
large for one chip's HBM.

Design: GSPMD, not hand-written collectives. The step is written as a
global computation (`vmap` of the per-shard loss over the data axis); the
batch arrives with edge-leading leaves sharded ``P("data", "graph")`` and
everything else ``P("data")`` (replicated over ``graph``), and XLA's
partitioner inserts the partial-scatter + all-reduce pair that
`graph_parallel.edge_sharded_aggregate` spells out manually — the
scaling-book recipe (annotate shardings, let XLA insert collectives).
Gradients are exact because the whole step is differentiated globally; no
per-axis pmean bookkeeping can go wrong.

Works with every stack that aggregates through ops/segment (the dense
neighbor-list layout is node-major, so run_training turns it off when
graph_shards > 1).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config.config import ModelConfig
from ..graphs.batch import GraphBatch
from ..train.train_step import (TrainState, eval_metrics_and_outputs,
                                freeze_conv_grads, make_forward_fn,
                                make_loss_fn)

# GraphBatch fields whose per-shard leading dim is the edge axis — these
# shard over ("data", "graph"); all other leaves shard over ("data",) only
# (i.e. stay replicated across the graph axis)
EDGE_FIELDS = ("senders", "receivers", "edge_mask", "edge_attr",
               "edge_shifts")


def place_composed_batch(batch: GraphBatch, mesh: Mesh,
                         data_axis: str = "data",
                         graph_axis: Optional[str] = "graph") -> GraphBatch:
    """Device placement for the composed mesh (the shard_batch analogue):
    edge-leading leaves P(data, graph), everything else P(data).

    Built by field iteration, not tree_map over a spec tree — PartitionSpec
    subclasses tuple, so a pytree of specs flattens into its components."""
    placed = {}
    for f in dataclasses.fields(batch):
        a = getattr(batch, f.name)
        if a is None:
            placed[f.name] = None
            continue
        spec = (P(data_axis, graph_axis)
                if graph_axis and f.name in EDGE_FIELDS else P(data_axis))
        placed[f.name] = jax.device_put(a, NamedSharding(mesh, spec))
    return GraphBatch(**placed)


def _tree_mean0(tree):
    return jax.tree_util.tree_map(lambda a: jnp.mean(a, axis=0), tree)


def make_composed_train_step(model, cfg: ModelConfig,
                             tx: optax.GradientTransformation, mesh: Mesh,
                             loss_name: str = "mse",
                             compute_grad_energy: bool = False,
                             energy_weight: float = 1.0,
                             force_weight: float = 1.0,
                             compute_dtype=None,
                             zero_opt: bool = False,
                             zero_min_size: int = 2 ** 14):
    """train_step(state, placed_batch) -> (state, metrics) on a
    (data, graph) mesh. The batch must be placed with
    `place_composed_batch` (edge leaves P(data, graph)); the jit then
    propagates those shardings through the global computation.

    ``zero_opt=True`` shards the optimizer state over the data axis
    (same reduce-scatter/all-gather semantics as the spmd path)."""
    loss_fn = make_loss_fn(model, cfg, loss_name, compute_grad_energy,
                           energy_weight, force_weight, compute_dtype)

    def mean_loss(params, batch_stats, batch: GraphBatch):
        # vmap over the data-shard axis; XLA splits it over "data" from the
        # batch shardings. Mean-of-shard-losses == pmean-of-grads in the
        # shard_map formulation.
        losses, aux = jax.vmap(
            lambda b: loss_fn(params, batch_stats, b))(batch)
        new_bs, metrics = aux
        return jnp.mean(losses), (_tree_mean0(new_bs), _tree_mean0(metrics))

    def step_body(state: TrainState, batch: GraphBatch):
        grad_fn = jax.value_and_grad(mean_loss, has_aux=True)
        (_, (new_bs, metrics)), grads = grad_fn(
            state.params, state.batch_stats, batch)
        grads = freeze_conv_grads(grads, cfg)
        opt_state = state.opt_state
        if zero_opt:
            from .mesh import param_sharding_zero
            opt_spec = param_sharding_zero(mesh, opt_state,
                                           min_size=zero_min_size)
            opt_state = jax.lax.with_sharding_constraint(opt_state, opt_spec)
        with jax.named_scope("optimizer"):
            updates, new_opt = tx.update(grads, opt_state, state.params)
            updates = freeze_conv_grads(updates, cfg)
            if zero_opt:
                new_opt = jax.lax.with_sharding_constraint(new_opt,
                                                           opt_spec)
            new_params = optax.apply_updates(state.params, updates)
        return state.replace(params=new_params, batch_stats=new_bs,
                             opt_state=new_opt, step=state.step + 1), metrics

    return jax.jit(step_body, donate_argnums=(0,))


def make_composed_eval_step(model, cfg: ModelConfig,
                            loss_name: str = "mse",
                            compute_grad_energy: bool = False,
                            energy_weight: float = 1.0,
                            force_weight: float = 1.0,
                            compute_dtype=None):
    """Sample-weighted eval metrics over the composed mesh (weights handle
    unequal real-graph counts across data shards, matching
    spmd.make_spmd_eval_step)."""
    forward = make_forward_fn(model, cfg, compute_dtype)

    def per_shard(params, batch_stats, batch: GraphBatch):
        variables = {"params": params, "batch_stats": batch_stats}
        metrics, _ = eval_metrics_and_outputs(
            forward, cfg, loss_name, variables, batch, compute_grad_energy,
            energy_weight, force_weight)
        w = jnp.sum(batch.graph_mask.astype(jnp.float32))
        return metrics, w

    @jax.jit
    def eval_step(state: TrainState, batch: GraphBatch):
        if batch.x.ndim == 2:
            # unstacked single-shard batch (the trainer's eval loop feeds
            # loader batches directly): add the shard axis
            batch = jax.tree_util.tree_map(lambda a: a[None], batch)
        metrics, w = jax.vmap(
            lambda b: per_shard(state.params, state.batch_stats, b))(batch)
        wsum = jnp.maximum(jnp.sum(w), 1.0)
        return jax.tree_util.tree_map(
            lambda m: jnp.sum(m * w) / wsum, metrics)

    return eval_step
