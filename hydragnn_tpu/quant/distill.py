"""Per-head student distillation for the int8 serving tier
(docs/mixed_precision.md "int8"; the FlashSchNet motivation in
PAPERS.md — a small distilled student preserves accuracy at a fraction
of the cost).

The int8 tier's error budget is spent in the quantized conv stack; the
decoder heads stay f32 and are therefore free parameters the tier can
use to claw accuracy back. ``distill_heads`` fine-tunes exactly those
head parameters — per head, against the fp32 TEACHER's outputs on the
calibration/serving distribution, through the QUANTIZED student forward
— so the student heads learn to compensate the conv stack's rounding.
The multi-head architecture makes this per-head-natural: each head's
masked MSE against its own teacher output is an independent term of the
distillation loss.

Deterministic by construction (no RNG: full-batch gradient descent on a
fixed collated batch for a fixed step count) — two identical calls
return bitwise-identical student variables; the tier-1 test pins it.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs.batch import GraphSample, collate
from ..telemetry.registry import get_registry
from .calibrate import CalibrationScales, encoder_param_key
from .ptq import make_quantized_forward


def _distill_batch(samples: Sequence[GraphSample]):
    rup = lambda v: -(-int(v + 1) // 8) * 8
    n_node = rup(sum(int(s.num_nodes) for s in samples))
    n_edge = rup(sum(int(s.num_edges) for s in samples))
    batch = collate(list(samples), n_node=n_node, n_edge=n_edge,
                    n_graph=len(samples) + 1, np_out=True)
    return batch.replace(y_graph=None, y_node=None, energy=None,
                         forces=None)


def _head_mse(outputs, teacher, mcfg, batch) -> List[jnp.ndarray]:
    """Per-head masked MSE between student and teacher outputs —
    padding rows carry garbage on both sides and are excluded."""
    g_mask = batch.graph_mask.astype(jnp.float32)
    n_mask = batch.node_mask.astype(jnp.float32)
    losses = []
    for ih, head in enumerate(mcfg.heads):
        mask = g_mask if head.head_type == "graph" else n_mask
        diff = (outputs[ih].astype(jnp.float32)
                - teacher[ih].astype(jnp.float32))
        per_row = jnp.sum(diff * diff, axis=-1)
        losses.append(jnp.sum(per_row * mask)
                      / jnp.maximum(jnp.sum(mask), 1.0))
    return losses


def distill_heads(model, variables, mcfg,
                  calibration: CalibrationScales,
                  samples: Sequence[GraphSample], *,
                  steps: int = 32, lr: float = 1e-4,
                  num_samples: Optional[int] = None
                  ) -> Tuple[dict, Dict[str, object]]:
    """Train the student heads of the int8 tier against the fp32
    teacher. Returns ``(student_variables, report)``: the student is
    `variables` with every NON-encoder param (heads, ``graph_shared``,
    head convs/norms) fine-tuned for up to `steps` full-batch Adam
    steps on the per-head distillation MSE; encoder params and batch
    stats are bitwise the teacher's. The BEST iterate by total loss is
    returned (iterate 0 is the teacher-initialized student, so the
    student is never WORSE than no distillation — an overshooting lr
    degrades to a no-op, not a regression). The report carries per-head
    MSE vs the teacher before/after plus the winning step, so callers
    (bench, tests) can adjudicate the claw-back."""
    import optax

    from ..train.train_step import make_forward_fn

    subset = list(samples)
    if num_samples is not None:
        subset = subset[:max(int(num_samples), 1)]
    if not subset:
        raise ValueError("distill_heads needs at least one sample")
    batch = _distill_batch(subset)
    num_conv = int(mcfg.num_conv_layers)

    teacher_fwd = make_forward_fn(model, mcfg, compute_dtype="float32")
    student_fwd = make_quantized_forward(model, mcfg, calibration)
    teacher_out, _ = jax.jit(
        lambda v, b: teacher_fwd(v, b, train=False))(variables, batch)
    teacher_out = [jax.lax.stop_gradient(t) for t in teacher_out]

    frozen = {key: encoder_param_key(key, num_conv)
              for key in variables["params"]}
    if all(frozen.values()):
        raise ValueError(
            "distill_heads found no head parameters to train — every "
            "top-level param key belongs to the encoder conv stack")
    batch_stats = variables.get("batch_stats", {})

    def loss_fn(params):
        outs, _ = student_fwd({"params": params,
                               "batch_stats": batch_stats},
                              batch, train=False)
        losses = _head_mse(outs, teacher_out, mcfg, batch)
        return sum(losses), losses

    tx = optax.adam(float(lr))

    @jax.jit
    def step(params, opt_state):
        (total, losses), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        # the encoder is frozen: its grads zero out BEFORE the update,
        # so Adam's moments never move the teacher's conv stack (the
        # freeze_conv_grads pattern, train/train_step.py)
        grads = {key: (jax.tree_util.tree_map(jnp.zeros_like, g)
                       if frozen[key] else g)
                 for key, g in grads.items()}
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, losses

    eval_losses = jax.jit(lambda p: loss_fn(p)[1])
    params = variables["params"]
    opt_state = tx.init(params)
    pre = [float(x) for x in eval_losses(params)]
    best_total, best_params, best_losses, best_step = (
        sum(pre), params, pre, 0)
    for it in range(max(int(steps), 1)):
        params, opt_state, _ = step(params, opt_state)
        cur = [float(x) for x in eval_losses(params)]
        if sum(cur) < best_total:
            best_total, best_params = sum(cur), params
            best_losses, best_step = cur, it + 1
    post = best_losses
    student = {"params": best_params, "batch_stats": batch_stats}
    report = {
        "steps": int(steps), "lr": float(lr),
        "best_step": int(best_step),
        "samples": len(subset),
        "head_mse_vs_teacher_pre": pre,
        "head_mse_vs_teacher_post": post,
        "improved": bool(sum(post) < sum(pre)),
        "trained_param_keys": sorted(k for k, fr in frozen.items()
                                     if not fr),
    }
    reg = get_registry()
    reg.counter_inc("quant.distillations_total",
                    help="head-wise distillation runs completed")
    reg.gauge_set("quant.distill_mse_post", float(sum(post)),
                  help="summed per-head MSE vs the fp32 teacher after "
                       "the most recent distillation")
    return student, report
