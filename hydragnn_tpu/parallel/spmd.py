"""SPMD data-parallel train/eval steps via shard_map over a device mesh.

The TPU-native replacement for DDP + DistributedSampler + NCCL allreduce
(reference: hydragnn/utils/distributed/distributed.py:275-288,
train/train_validate_test.py:527-545). Batches arrive device-stacked
([D, ...], see datasets/loader.py); each device runs the per-shard forward/
backward on its self-contained sub-batch; gradients and metrics are averaged
with a single `lax.pmean` over the "data" axis — the only collective in the
step, riding ICI.

Optimizer-state sharding (ZeRO equivalent — reference ZeroRedundancyOptimizer
utils/optimizer/optimizer.py:43-101) is available via `zero_opt=True`:
optimizer state lives sharded over the data axis; the update runs on shards
of the (replicated) gradient, and updated params are re-broadcast — i.e.
reduce-scatter(grad) + all-gather(update) semantics, expressed with
jax.sharding constraints so XLA picks the collectives.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config.config import ModelConfig
from ..graphs.batch import GraphBatch
from ..train.train_step import (TrainState, _nonfinite_watchdog,
                                apply_optimizer, eval_metrics_and_outputs,
                                freeze_conv_grads, make_forward_fn,
                                make_loss_fn)


def _batch_spec(batch: GraphBatch):
    """PartitionSpec pytree: every non-None array split on leading (device)
    axis."""
    return jax.tree_util.tree_map(lambda _: P("data"), batch)


def _make_spmd_step_body(model, cfg: ModelConfig,
                         tx: optax.GradientTransformation, mesh: Mesh,
                         loss_name: str = "mse",
                         compute_grad_energy: bool = False,
                         energy_weight: float = 1.0,
                         force_weight: float = 1.0,
                         zero_opt: bool = False,
                         zero_min_size: int = 2 ** 14,
                         compute_dtype=None):
    """Pure (un-jitted) SPMD step body shared by make_spmd_train_step
    (direct jit) and make_spmd_multi_train_step (lax.scan).

    With ``zero_opt=True`` (reference: ZeroRedundancyOptimizer
    utils/optimizer/optimizer.py:43-101, DeepSpeed ZeRO stages
    run_training.py:136-149) the optimizer update runs OUTSIDE the
    shard_map with the optimizer-state pytree sharded over the data axis
    (mesh.param_sharding_zero): XLA partitions the elementwise update and
    inserts reduce-scatter/all-gather collectives itself — per-device
    optimizer-state memory drops by ~1/D for the large leaves.

    Architecture.dtype="bfloat16" (or `compute_dtype`) selects mixed
    precision exactly as in the single-device step — the loss body IS the
    single-device one (train_step.make_loss_fn)."""
    loss_fn = make_loss_fn(model, cfg, loss_name, compute_grad_energy,
                           energy_weight, force_weight, compute_dtype)

    def grads_per_device(params, batch_stats, batch: GraphBatch):
        # strip the leading device axis (size 1 inside the shard)
        local = jax.tree_util.tree_map(
            lambda a: None if a is None else a[0], batch)
        grads_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (total, (new_bs, metrics)), grads = grads_fn(params, batch_stats,
                                                     local)
        # per-replica watchdog flag BEFORE the gradient pmean (a pmean'd
        # NaN poisons every replica — the pre-reduce flag names the step
        # that actually went bad); pmax: the STEP is bad if ANY shard is
        nonfinite = _nonfinite_watchdog(total, grads)
        # the step's one gradient exchange, named for the trace (PERF.md
        # section 3); the small metric/BatchNorm reductions ride with it
        with jax.named_scope("grad_allreduce"):
            grads = freeze_conv_grads(jax.lax.pmean(grads, "data"), cfg)
            metrics = dict(jax.lax.pmean(metrics, "data"))
            metrics["nonfinite_steps"] = jax.lax.pmax(nonfinite, "data")
            # cross-replica BatchNorm running stats (SyncBatchNorm
            # semantics)
            new_bs = jax.lax.pmean(new_bs, "data")
        return grads, new_bs, metrics

    def per_device(params, batch_stats, opt_state, batch: GraphBatch):
        grads, new_bs, metrics = grads_per_device(params, batch_stats, batch)
        new_params, new_opt = apply_optimizer(tx, cfg, grads, opt_state,
                                              params)
        return new_params, new_bs, new_opt, metrics

    if zero_opt:
        from .mesh import param_sharding_zero

        def step_body(state: TrainState, batch: GraphBatch):
            mapped = shard_map(
                grads_per_device, mesh=mesh,
                in_specs=(P(), P(), _batch_spec(batch)),
                out_specs=(P(), P(), P()),
                )
            grads, new_bs, metrics = mapped(
                state.params, state.batch_stats, batch)
            # sharded optimizer update: constrain the opt-state pytree over
            # the data axis and let GSPMD partition the update
            opt_spec = param_sharding_zero(mesh, state.opt_state,
                                           min_size=zero_min_size)
            opt_state = jax.lax.with_sharding_constraint(
                state.opt_state, opt_spec)
            with jax.named_scope("optimizer"):
                updates, new_opt = tx.update(grads, opt_state, state.params)
                updates = freeze_conv_grads(updates, cfg)
                new_opt = jax.lax.with_sharding_constraint(new_opt,
                                                           opt_spec)
                new_params = optax.apply_updates(state.params, updates)
            return state.replace(params=new_params, batch_stats=new_bs,
                                 opt_state=new_opt,
                                 step=state.step + 1), metrics
    else:
        def step_body(state: TrainState, batch: GraphBatch):
            mapped = shard_map(
                per_device, mesh=mesh,
                in_specs=(P(), P(), P(), _batch_spec(batch)),
                out_specs=(P(), P(), P(), P()),
                )
            new_params, new_bs, new_opt, metrics = mapped(
                state.params, state.batch_stats, state.opt_state, batch)
            return state.replace(params=new_params, batch_stats=new_bs,
                                 opt_state=new_opt,
                                 step=state.step + 1), metrics

    return step_body


def make_spmd_train_step(model, cfg: ModelConfig,
                         tx: optax.GradientTransformation, mesh: Mesh,
                         loss_name: str = "mse", **kwargs):
    """Build train_step(state, device_stacked_batch) -> (state, metrics);
    see _make_spmd_step_body for the zero_opt semantics."""
    return jax.jit(
        _make_spmd_step_body(model, cfg, tx, mesh, loss_name, **kwargs),
        donate_argnums=(0,))


def make_spmd_multi_train_step(model, cfg: ModelConfig,
                               tx: optax.GradientTransformation, mesh: Mesh,
                               loss_name: str = "mse", **kwargs):
    """`lax.scan` of the SPMD train step over a leading steps axis: the
    stacked batch leaves are [S, D, ...] with the device axis sharded over
    the mesh (mesh.shard_stacked_batch) and the scan axis replicated. Same
    dispatch-amortization as train_step.make_multi_train_step, per shard."""
    body = _make_spmd_step_body(model, cfg, tx, mesh, loss_name, **kwargs)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def multi_step(state: TrainState, stacked: GraphBatch):
        return jax.lax.scan(body, state, stacked)

    return multi_step


def make_spmd_eval_step(model, cfg: ModelConfig, mesh: Mesh,
                        loss_name: str = "mse",
                        compute_grad_energy: bool = False,
                        energy_weight: float = 1.0, force_weight: float = 1.0,
                        compute_dtype=None):
    forward = make_forward_fn(model, cfg, compute_dtype)

    def per_device(params, batch_stats, batch: GraphBatch):
        local = jax.tree_util.tree_map(
            lambda a: None if a is None else a[0], batch)
        variables = {"params": params, "batch_stats": batch_stats}
        metrics, _ = eval_metrics_and_outputs(
            forward, cfg, loss_name, variables, local, compute_grad_energy,
            energy_weight, force_weight)
        # sample-weighted global mean: shards may hold unequal real-graph
        # counts (drop_last=False tail batches), so weight each shard's
        # masked mean by its real count before the cross-shard reduction
        w = jnp.sum(local.graph_mask.astype(jnp.float32))
        wsum = jax.lax.psum(w, "data")
        metrics = jax.tree_util.tree_map(
            lambda m: jax.lax.psum(m * w, "data") / jnp.maximum(wsum, 1.0),
            metrics)
        return metrics

    @jax.jit
    def eval_step(state: TrainState, batch: GraphBatch):
        mapped = shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P(), _batch_spec(batch)),
            out_specs=P(),
            )
        return mapped(state.params, state.batch_stats, batch)

    return eval_step


def make_spmd_dispatch_group(model, cfg: ModelConfig,
                             tx: optax.GradientTransformation, mesh: Mesh,
                             steps_per_call: int, **kwargs):
    """(multi_train_step, place_group_fn) pair for trainer steps-per-call
    grouping on an SPMD mesh, or (None, None) when grouping is off —
    shared by run_training and the multidataset driver."""
    if steps_per_call <= 1:
        return None, None
    from .mesh import shard_stacked_batch
    multi = make_spmd_multi_train_step(model, cfg, tx, mesh, **kwargs)
    return multi, (lambda b: shard_stacked_batch(b, mesh))


def make_spmd_forward(model, mesh: Mesh, cfg: Optional[ModelConfig] = None,
                      compute_dtype=None):
    """Per-head predictions over a device-stacked batch, taking a plain
    ``variables`` dict — each device runs the forward on its shard,
    outputs concatenate over the data axis (device-major — matching a
    [D, ...] -> [D*..., ...] flatten of the batch). The SPMD forward the
    serving engine dispatches for multi-device serving
    (serving/engine.py); ``make_spmd_predict_step`` wraps it for the
    TrainState-based run_prediction path."""
    forward = make_forward_fn(model, cfg, compute_dtype)

    def per_device(params, batch_stats, batch: GraphBatch):
        local = jax.tree_util.tree_map(
            lambda a: None if a is None else a[0], batch)
        outputs, _ = forward(
            {"params": params, "batch_stats": batch_stats}, local)
        return outputs

    @jax.jit
    def spmd_forward(variables, batch: GraphBatch):
        mapped = shard_map(
            per_device, mesh=mesh,
            in_specs=(P(), P(), _batch_spec(batch)),
            out_specs=P("data"),
            )
        return mapped(variables["params"], variables.get("batch_stats", {}),
                      batch)

    return spmd_forward


def make_spmd_predict_step(model, mesh: Mesh, cfg: Optional[ModelConfig] = None,
                           compute_dtype=None):
    """TrainState wrapper over ``make_spmd_forward`` — the SPMD half of
    run_prediction (reference: run_prediction evaluates under the same DDP
    layout as training, run_prediction.py:62-97, with per-rank gathers at
    train_validate_test.py:709-737). With a `cfg`, Architecture.dtype
    selects the same bf16 compute as the single-device eval, so
    predictions don't depend on the shard count."""
    spmd_forward = make_spmd_forward(model, mesh, cfg, compute_dtype)

    def predict_step(state: TrainState, batch: GraphBatch):
        return spmd_forward({"params": state.params,
                             "batch_stats": state.batch_stats}, batch)

    return predict_step
