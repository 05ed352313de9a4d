"""Jitted train/eval steps.

One fused `train_step(state, batch) -> (state, metrics)` replaces the
reference's per-batch Python sequence (zero_grad / forward / loss / backward /
step — reference: hydragnn/train/train_validate_test.py:449-565). Under pjit
over a data mesh, the gradient mean is an XLA-inserted psum over ICI — the
DDP allreduce (reference: distributed.py:275-288) with no explicit comm code.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import core, struct

from ..config.config import ModelConfig
from ..graphs.batch import GraphBatch
from .loss import energy_force_loss, multihead_loss


class TrainState(struct.PyTreeNode):
    params: core.FrozenDict
    batch_stats: Any
    opt_state: optax.OptState
    step: jnp.ndarray

    @classmethod
    def create(cls, variables, tx):
        params = variables["params"]
        return cls(params=params,
                   batch_stats=variables.get("batch_stats", {}),
                   opt_state=tx.init(params),
                   step=jnp.zeros((), jnp.int32))


def freeze_conv_grads(grads, cfg: ModelConfig):
    """Zero the gradients/updates of the conv stack + feature-norm layers
    when `freeze_conv_layers` is set — the transfer-learning freeze
    (reference: Base.py:139-143 sets requires_grad=False on graph_convs and
    feature_layers). Must be applied to the optimizer UPDATES as well as
    the gradients: decoupled weight decay (AdamW) moves parameters even
    for zero gradients."""
    if not getattr(cfg, "freeze_conv", False):
        return grads
    from flax.core import unfreeze
    num_conv = int(getattr(cfg, "num_conv_layers", 0))

    def is_encoder(key: str) -> bool:
        # encoder stack = conv_0..conv_{L-1} + feature_norm_*; node-head
        # convs are named conv_{L + 100*head + layer} (base.py make_conv)
        # and must stay trainable
        if key.startswith("feature_norm_"):
            return True
        if key.startswith("conv_"):
            try:
                return int(key.split("_")[-1]) < num_conv
            except ValueError:
                return False
        return False

    grads = unfreeze(grads)
    for key in grads:
        if is_encoder(key):
            grads[key] = jax.tree_util.tree_map(jnp.zeros_like, grads[key])
    return grads


def _cast_floats(tree, dtype):
    """Cast every floating-point leaf to `dtype`; ints/bools untouched."""
    def cast(a):
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(dtype)
        return a
    return jax.tree_util.tree_map(cast, tree)


def _resolve_compute_dtype(cfg: ModelConfig, compute_dtype):
    """bf16 mixed precision: params/opt-state/losses stay f32, model compute
    runs in bfloat16 (MXU-native). Precedence (train/precision.py): the
    explicit `compute_dtype` argument, then HYDRAGNN_PRECISION (strict
    parsing), then Architecture.dtype, then float32 — resolved HERE at
    construction time, never in trace."""
    from .precision import resolve_precision
    name = resolve_precision(getattr(cfg, "dtype", None), compute_dtype)
    if name == "int8":
        raise ValueError(
            "int8 is a serving-only precision (post-training "
            "quantization, docs/mixed_precision.md): casting "
            "float params/activations to int8 in a train/eval step "
            "would destroy them. Train in float32/bfloat16 and serve "
            "int8 via Serving.precision='int8' / "
            "HYDRAGNN_SERVE_PRECISION=int8 (serving/engine.py)")
    return jnp.dtype(name)


def make_loss_fn(model, cfg: ModelConfig, loss_name: str = "mse",
                 compute_grad_energy: bool = False,
                 energy_weight: float = 1.0, force_weight: float = 1.0,
                 compute_dtype: Optional[str] = None):
    """loss_fn(params, batch_stats, batch) -> (total, (new_batch_stats,
    metrics)) with the mixed-precision casting policy — the ONE training
    loss body, shared by the single-device step factories here and the
    SPMD factories in parallel/spmd.py so the two paths cannot drift."""
    cdtype = _resolve_compute_dtype(cfg, compute_dtype)
    mixed = cdtype != jnp.float32

    def loss_fn(params, batch_stats, batch: GraphBatch):
        if mixed:
            params = _cast_floats(params, cdtype)
            batch_stats = _cast_floats(batch_stats, cdtype)
        variables = {"params": params, "batch_stats": batch_stats}
        if compute_grad_energy:
            def apply_fn(v, b, train):
                if mixed:
                    b = _cast_floats(b, cdtype)
                out, mut = model.apply(
                    v, b, train=train, mutable=["batch_stats"])
                # losses/pooling accumulate in f32 regardless of compute dtype
                out = jax.tree_util.tree_map(
                    lambda o: o.astype(jnp.float32), out)
                return out, mut.get("batch_stats", {})
            total, aux = energy_force_loss(
                apply_fn, variables, cfg, batch, loss_name,
                energy_weight, force_weight, train=True)
            # batch-norm running stats update on the E-F path too (the
            # reference's torch train-mode forward does; freezing them at
            # init made eval-mode normalization garbage for SchNet-style
            # stacks). Stop-grad: the pos-grad must not differentiate them.
            new_bs = jax.lax.stop_gradient(aux["batch_stats"])
            if mixed:
                new_bs = _cast_floats(new_bs, jnp.float32)
            return total, (new_bs, {"loss": total, **{
                k: v for k, v in aux.items()
                if hasattr(v, "ndim") and v.ndim == 0}})
        outputs_and_var, mutated = model.apply(
            variables, _cast_floats(batch, cdtype) if mixed else batch,
            train=True, mutable=["batch_stats"])
        outputs, outputs_var = outputs_and_var
        if mixed:
            outputs = _cast_floats(outputs, jnp.float32)
            outputs_var = _cast_floats(outputs_var, jnp.float32)
        total, tasks = multihead_loss(cfg, loss_name, outputs, outputs_var, batch)
        metrics = {"loss": total}
        for i, t in enumerate(tasks):
            metrics[f"task_{i}"] = t
        new_bs = mutated["batch_stats"]
        if mixed:  # running statistics must not degrade to bf16 across epochs
            new_bs = _cast_floats(new_bs, jnp.float32)
        return total, (new_bs, metrics)

    return loss_fn


def apply_optimizer(tx: optax.GradientTransformation, cfg: ModelConfig,
                    grads, opt_state, params):
    """(new_params, new_opt_state): `tx.update` + the conv freeze on the
    updates + `apply_updates`, under the trace scope "optimizer" (PERF.md
    section 3) — the one copy the single-device and SPMD steps share."""
    with jax.named_scope("optimizer"):
        updates, new_opt = tx.update(grads, opt_state, params)
        updates = freeze_conv_grads(updates, cfg)
        return optax.apply_updates(params, updates), new_opt


def _nonfinite_watchdog(loss, grads):
    """1.0 when this step's loss or ANY gradient leaf carries a
    non-finite value, else 0.0 — the per-step brick of the bf16
    overflow watchdog. The any-reduction tree is cheap (one isfinite
    pass over the gradient pytree XLA fuses into the backward) and runs
    at every precision: an fp32 divergence deserves the same counter."""
    bad = ~jnp.isfinite(loss)
    for leaf in jax.tree_util.tree_leaves(grads):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            bad = bad | ~jnp.all(jnp.isfinite(leaf))
    return bad.astype(jnp.float32)


def _make_step_body(model, cfg: ModelConfig, tx: optax.GradientTransformation,
                    loss_name: str = "mse", compute_grad_energy: bool = False,
                    energy_weight: float = 1.0, force_weight: float = 1.0,
                    compute_dtype: Optional[str] = None):
    """Pure (un-jitted) train-step body shared by make_train_step (direct
    jit) and make_multi_train_step (lax.scan)."""
    loss_fn = make_loss_fn(model, cfg, loss_name, compute_grad_energy,
                           energy_weight, force_weight, compute_dtype)

    def step_body(state: TrainState, batch: GraphBatch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (total, (new_bs, metrics)), grads = grad_fn(
            state.params, state.batch_stats, batch)
        # NaN/overflow watchdog (docs/mixed_precision.md): bf16's
        # 8-bit significand and 8-bit exponent overflow/flush far earlier
        # than f32, and a silently-NaN'd optimizer poisons every later
        # step — count the bad steps where they happen. Computed BEFORE
        # the conv freeze (a frozen layer's non-finite gradient is still
        # a training bug worth surfacing); the trainer sums this per
        # epoch into history/TB `nonfinite_steps`.
        metrics = {**metrics,
                   "nonfinite_steps": _nonfinite_watchdog(total, grads)}
        grads = freeze_conv_grads(grads, cfg)
        new_params, new_opt = apply_optimizer(
            tx, cfg, grads, state.opt_state, state.params)
        new_state = state.replace(params=new_params, batch_stats=new_bs,
                                  opt_state=new_opt, step=state.step + 1)
        return new_state, metrics

    return step_body


# ------------------------------------------------- sampled giant-graph --
def _seed_loss_batch(batch: GraphBatch) -> GraphBatch:
    """Loss view of a sampled batch: node heads are supervised on SEED
    slots only (docs/sampling.md) — the hop-expansion slots exist to
    give seeds their receptive field, not to be predicted. multihead_loss
    masks node heads with node_mask, so the loss view swaps seed_mask in;
    the model forward keeps the full node_mask."""
    if batch.seed_mask is None:
        return batch
    return batch.replace(node_mask=batch.seed_mask)


def make_sampled_loss_fn(model, cfg: ModelConfig, loss_name: str = "ce",
                         compute_dtype: Optional[str] = None,
                         num_hist_layers: int = 0):
    """loss_fn(params, batch_stats, batch) -> (total, (new_batch_stats,
    metrics, hist_states_or_None)) for sampled giant-graph batches: the
    seed-masked loss plus (when `num_hist_layers` > 0) the encoder's
    fresh post-layer states, sown by BaseStack.encode and returned
    [L-1, N, H] for the historical-cache refresh."""
    cdtype = _resolve_compute_dtype(cfg, compute_dtype)
    mixed = cdtype != jnp.float32

    def loss_fn(params, batch_stats, batch: GraphBatch):
        if mixed:
            params = _cast_floats(params, cdtype)
            batch_stats = _cast_floats(batch_stats, cdtype)
        variables = {"params": params, "batch_stats": batch_stats}
        mutable = ["batch_stats"]
        if num_hist_layers:
            mutable.append("intermediates")
        (outputs, outputs_var), mutated = model.apply(
            variables, _cast_floats(batch, cdtype) if mixed else batch,
            train=True, mutable=mutable)
        if mixed:
            outputs = _cast_floats(outputs, jnp.float32)
            outputs_var = _cast_floats(outputs_var, jnp.float32)
        total, tasks = multihead_loss(cfg, loss_name, outputs,
                                      outputs_var, _seed_loss_batch(batch))
        metrics = {"loss": total}
        for i, t in enumerate(tasks):
            metrics[f"task_{i}"] = t
        new_bs = mutated["batch_stats"]
        if mixed:
            new_bs = _cast_floats(new_bs, jnp.float32)
        inter = None
        if num_hist_layers:
            sown = mutated["intermediates"]
            inter = jnp.stack(
                [sown[f"encoder_h{i}"][0].astype(jnp.float32)
                 for i in range(num_hist_layers)])
        return total, (new_bs, metrics, inter)

    return loss_fn


def make_sampled_train_step(model, cfg: ModelConfig,
                            tx: optax.GradientTransformation, *,
                            loss_name: str = "ce", staleness_k: int = 0,
                            compute_dtype: Optional[str] = None,
                            donate: bool = True):
    """Jitted train step for fixed-shape sampled batches
    (preprocess/sampling.py, docs/sampling.md) — every batch has
    identical shapes, so this compiles exactly ONCE for the whole run
    (BENCH_SAMPLE pins `jit_recompiles == 1`).

    ``staleness_k == 0`` (exact mode): `step(state, batch)`, the plain
    optimizer step under the seed-masked loss.

    ``staleness_k > 0`` (historical-embedding mode):
    `step(state, batch, tables, do_refresh)` additionally

    * substitutes the resident feature row and per-layer stale states
      for every hist-served slot (gathered by ``batch.node_global``;
      BaseStack.encode applies the per-layer override),
    * on ``do_refresh`` (a TRACED flag — both branches live in the one
      compiled program), scatters the rank's own fresh post-layer
      states back into the tables at the loader-deduplicated
      ``refresh_upto`` slots and version-stamps them.

    Refresh cadence is the CALLER's ``step % K == 0`` — K never enters
    the trace, so changing it cannot recompile."""
    hist = int(staleness_k) > 0
    num_hist = max(int(cfg.num_conv_layers) - 1, 0) if hist else 0
    loss_fn = make_sampled_loss_fn(model, cfg, loss_name, compute_dtype,
                                   num_hist)

    def optimizer_step(state: TrainState, batch: GraphBatch):
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (total, (new_bs, metrics, inter)), grads = grad_fn(
            state.params, state.batch_stats, batch)
        metrics = {**metrics,
                   "nonfinite_steps": _nonfinite_watchdog(total, grads)}
        grads = freeze_conv_grads(grads, cfg)
        new_params, new_opt = apply_optimizer(
            tx, cfg, grads, state.opt_state, state.params)
        new_state = state.replace(params=new_params, batch_stats=new_bs,
                                  opt_state=new_opt, step=state.step + 1)
        return new_state, metrics, inter

    if not hist:
        def step(state: TrainState, batch: GraphBatch):
            new_state, metrics, _ = optimizer_step(state, batch)
            return new_state, metrics

        return jax.jit(step, donate_argnums=(0,) if donate else ())

    def hist_step(state: TrainState, batch: GraphBatch, tables,
                  do_refresh):
        ids = batch.node_global
        x = jnp.where(batch.hist_mask[:, None], tables.feat[ids], batch.x)
        b = batch.replace(x=x, hist_states=tables.layers[:, ids])
        # staleness telemetry BEFORE the update: what this step consumed
        hist_n = jnp.sum(batch.hist_mask)
        staleness = (jnp.sum(jnp.where(
            batch.hist_mask, state.step - tables.versions[ids], 0))
            / jnp.maximum(hist_n, 1))
        new_state, metrics, inter = optimizer_step(state, b)
        metrics = {**metrics, "hist_staleness": staleness.astype(
            jnp.float32), "hist_frac": hist_n / batch.hist_mask.shape[0]}
        inter = jax.lax.stop_gradient(inter)
        dump = tables.feat.shape[0] - 1  # scatter-dump row, never read

        def do_ref(tb):
            new_layers = tb.layers
            for t in range(1, tb.layers.shape[0] + 1):
                safe = jnp.where(batch.refresh_upto >= t, ids, dump)
                new_layers = new_layers.at[t - 1, safe].set(inter[t - 1])
            safe0 = jnp.where(batch.refresh_upto >= 1, ids, dump)
            new_vers = tb.versions.at[safe0].set(new_state.step)
            return tb.replace(layers=new_layers, versions=new_vers)

        new_tables = jax.lax.cond(do_refresh, do_ref, lambda tb: tb,
                                  tables)
        return new_state, new_tables, metrics

    return jax.jit(hist_step, donate_argnums=(0, 2) if donate else ())


def make_sampled_eval_step(model, cfg: ModelConfig, loss_name: str = "ce",
                           staleness_k: int = 0,
                           compute_dtype: Optional[str] = None):
    """Jitted eval for sampled batches: seed-masked loss plus top-1
    accuracy counts for classification node heads (y_node wider than one
    column). Hist mode takes the tables and applies the same stale
    substitution as training — eval sees exactly the serving-time
    approximation."""
    forward = make_forward_fn(model, cfg, compute_dtype)

    def eval_core(state: TrainState, batch: GraphBatch):
        variables = {"params": state.params,
                     "batch_stats": state.batch_stats}
        outputs, outputs_var = forward(variables, batch, train=False)
        total, tasks = multihead_loss(cfg, loss_name, outputs,
                                      outputs_var, _seed_loss_batch(batch))
        metrics = {"loss": total}
        for i, t in enumerate(tasks):
            metrics[f"task_{i}"] = t
        if batch.y_node is not None and batch.y_node.shape[-1] > 1:
            nclass = batch.y_node.shape[-1]
            pred = jnp.argmax(outputs[0][..., :nclass], axis=-1)
            label = jnp.argmax(batch.y_node, axis=-1)
            sm = (batch.seed_mask if batch.seed_mask is not None
                  else batch.node_mask)
            metrics["correct"] = jnp.sum(
                jnp.where(sm, pred == label, False)).astype(jnp.float32)
            metrics["count"] = jnp.sum(sm).astype(jnp.float32)
        return metrics, outputs

    if int(staleness_k) <= 0:
        return jax.jit(eval_core)

    def hist_eval(state: TrainState, batch: GraphBatch, tables):
        ids = batch.node_global
        x = jnp.where(batch.hist_mask[:, None], tables.feat[ids],
                      batch.x)
        return eval_core(state, batch.replace(
            x=x, hist_states=tables.layers[:, ids]))

    return jax.jit(hist_eval)


def compiled_cost_flops(compiled):
    """Per-call FLOPs from an already-compiled executable's XLA cost
    analysis; None when the backend doesn't report it. Callers that
    already hold a ``.lower(...).compile()`` result (bench.py reuses one
    executable for cost analysis, memory analysis, and execution) use
    this directly instead of paying ``step_cost_flops``'s compile."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        f = float(ca.get("flops", 0.0))
        return f if f > 0 else None
    except Exception:
        return None


def step_cost_flops(step_fn, *args):
    """Per-call FLOPs of a jitted step from XLA's compiled cost analysis;
    None when the backend doesn't report it (or `step_fn` isn't
    lowerable). The ONE probe shared by bench.py and the telemetry MFU
    gauge (telemetry/mfu.py) so the numerator cannot drift between the
    bench row and the per-epoch trainer metric. Not free — it re-lowers
    and compiles the step for the probe shapes — so callers run it once
    per (run, shape), never per epoch."""
    try:
        return compiled_cost_flops(step_fn.lower(*args).compile())
    except Exception:
        return None


def make_train_step(model, cfg: ModelConfig, tx: optax.GradientTransformation,
                    loss_name: str = "mse", compute_grad_energy: bool = False,
                    energy_weight: float = 1.0, force_weight: float = 1.0,
                    donate: bool = True, compute_dtype: Optional[str] = None):
    """Build the jitted SPMD train step.

    `compute_grad_energy` selects the energy-force path
    (reference: Training.compute_grad_energy, train_validate_test.py:515-521).
    """
    body = _make_step_body(model, cfg, tx, loss_name, compute_grad_energy,
                           energy_weight, force_weight, compute_dtype)
    return jax.jit(body, donate_argnums=(0,) if donate else ())


def make_multi_train_step(model, cfg: ModelConfig,
                          tx: optax.GradientTransformation, **kwargs):
    """`lax.scan` of the train step over a leading steps axis: one device
    dispatch executes S sequential optimizer steps on S pre-staged batches
    (stack each GraphBatch leaf to [S, ...]).

    Mathematically identical to calling the single step S times; the win is
    host-side — per-dispatch latency is paid once per S steps instead of
    per step. The returned metrics keep the per-step leading axis so loss
    accounting stays per-batch exact.

    This is the throughput path the reference cannot express: its
    per-batch Python loop (train_validate_test.py:483-545) re-enters the
    framework every batch by construction."""
    donate = kwargs.pop("donate", True)
    body = _make_step_body(model, cfg, tx, **kwargs)

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def multi_step(state: TrainState, stacked: GraphBatch):
        return jax.lax.scan(body, state, stacked)

    return multi_step


def make_forward_fn(model, cfg: Optional[ModelConfig] = None,
                    compute_dtype: Optional[str] = None):
    """Mixed-precision inference forward — f32 variables/batch in, f32
    outputs out, model compute in Architecture.dtype (or `compute_dtype`).
    The ONE eval-side casting policy, shared by the single-device eval
    body here and the SPMD eval/predict factories in parallel/spmd.py."""
    cdtype = _resolve_compute_dtype(cfg, compute_dtype)
    mixed = cdtype != jnp.float32

    def forward(variables, batch, train=False):
        if mixed:
            variables = _cast_floats(variables, cdtype)
            batch = _cast_floats(batch, cdtype)
        out = model.apply(variables, batch, train=train)
        return _cast_floats(out, jnp.float32) if mixed else out

    return forward


def eval_metrics_and_outputs(forward, cfg: ModelConfig, loss_name: str,
                             variables, batch: GraphBatch,
                             compute_grad_energy: bool = False,
                             energy_weight: float = 1.0,
                             force_weight: float = 1.0):
    """(metrics, outputs) for one un-stacked batch given a `forward` from
    make_forward_fn — the shared core of the single-device and SPMD eval
    steps."""
    if compute_grad_energy:
        # eval forward mutates nothing; adapt to energy_force_loss's
        # (outputs, new_batch_stats) apply contract
        def apply_fn(v, b, train):
            return forward(v, b, train=train), None
        total, aux = energy_force_loss(
            apply_fn, variables, cfg, batch, loss_name, energy_weight,
            force_weight, train=False)
        metrics = {"loss": total,
                   "energy_loss": aux["energy_loss"],
                   "force_loss": aux["force_loss"]}
        return metrics, [aux["energy_pred"], aux["forces_pred"]]
    outputs, outputs_var = forward(variables, batch, train=False)
    total, tasks = multihead_loss(cfg, loss_name, outputs, outputs_var,
                                  batch)
    metrics = {"loss": total}
    for i, t in enumerate(tasks):
        metrics[f"task_{i}"] = t
    return metrics, outputs


def _make_eval_body(model, cfg: ModelConfig, loss_name: str = "mse",
                    compute_grad_energy: bool = False,
                    energy_weight: float = 1.0, force_weight: float = 1.0,
                    compute_dtype: Optional[str] = None):
    """Pure (un-jitted) eval body shared by make_eval_step (direct jit) and
    make_multi_eval_step (lax.scan)."""
    forward = make_forward_fn(model, cfg, compute_dtype)

    def eval_step(state: TrainState, batch: GraphBatch):
        variables = {"params": state.params, "batch_stats": state.batch_stats}
        return eval_metrics_and_outputs(
            forward, cfg, loss_name, variables, batch, compute_grad_energy,
            energy_weight, force_weight)

    return eval_step


def make_eval_step(model, cfg: ModelConfig, loss_name: str = "mse",
                   compute_grad_energy: bool = False,
                   energy_weight: float = 1.0, force_weight: float = 1.0,
                   compute_dtype: Optional[str] = None):
    """Jitted validation/test step returning (metrics, outputs)
    (reference: validate/test, train_validate_test.py:568-746)."""
    return jax.jit(_make_eval_body(model, cfg, loss_name,
                                   compute_grad_energy, energy_weight,
                                   force_weight, compute_dtype))


def make_multi_eval_step(model, cfg: ModelConfig, **kwargs):
    """Metrics-only `lax.scan` of the eval step over stacked batches — the
    val/test analogue of make_multi_train_step. Per-sample outputs are
    dropped in the scan body (XLA dead-code-eliminates their gathering), so
    use the single eval step where predictions are needed (run_prediction/
    test dumps)."""
    body = _make_eval_body(model, cfg, **kwargs)

    @jax.jit
    def multi_eval(state: TrainState, stacked: GraphBatch):
        def scan_body(st, b):
            metrics, _ = body(st, b)
            return st, metrics
        return jax.lax.scan(scan_body, state, stacked)[1]

    return multi_eval
