"""Optimizer selection — optax equivalents of the reference registry.

reference: hydragnn/utils/optimizer/optimizer.py:12-113 (SGD/Adam/Adadelta/
Adagrad/Adamax/AdamW/RMSprop/FusedLAMB, each with a ZeroRedundancy variant).
Here ZeRO is not a different optimizer: optimizer-state sharding is a
sharding spec on the opt-state pytree (parallel/mesh.py:param_sharding_zero),
applied uniformly to any optax transform.

`inject_hyperparams` makes learning_rate runtime-adjustable so the
ReduceLROnPlateau schedule (reference: train_validate_test.py:195) can scale
it without recompiling.
"""
from __future__ import annotations

from typing import Any, Dict

import optax

_FACTORIES = {
    "SGD": lambda lr, kw: optax.sgd(lr, momentum=kw.get("momentum", 0.9)),
    "Adam": lambda lr, kw: optax.adam(lr),
    "Adadelta": lambda lr, kw: optax.adadelta(lr),
    "Adagrad": lambda lr, kw: optax.adagrad(lr),
    "Adamax": lambda lr, kw: optax.adamax(lr),
    "AdamW": lambda lr, kw: optax.adamw(lr, weight_decay=kw.get("weight_decay", 1e-2)),
    "RMSprop": lambda lr, kw: optax.rmsprop(lr),
    "FusedLAMB": lambda lr, kw: optax.lamb(lr),
}


def select_optimizer(train_config: Dict[str, Any]) -> optax.GradientTransformation:
    """reference: select_optimizer (optimizer.py:104-113).

    `Training.gradient_accumulation_steps > 1` wraps the transform in
    optax.MultiSteps: each loader batch becomes a micro-batch whose
    gradients accumulate (averaged) and apply every k-th call — the
    reference only offers this through DeepSpeed's ds_config
    (gradient_accumulation_steps, config_utils.py:326-330); update_config
    maps that key here for reference configs."""
    opt_cfg = train_config.get("Optimizer", {"type": "AdamW"})
    name = opt_cfg.get("type", "AdamW")
    lr = float(opt_cfg.get("learning_rate", 1e-3))
    if name not in _FACTORIES:
        raise ValueError(f"unknown optimizer '{name}'; known: {sorted(_FACTORIES)}")
    factory = _FACTORIES[name]

    @optax.inject_hyperparams
    def make(learning_rate):
        tx = factory(learning_rate, opt_cfg)
        clip = train_config.get("grad_clip")
        if clip:
            tx = optax.chain(optax.clip_by_global_norm(float(clip)), tx)
        return tx

    tx = make(learning_rate=lr)
    accum = int(train_config.get("gradient_accumulation_steps", 1) or 1)
    if accum > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=accum) \
            .gradient_transformation()
    return tx


def _lr_state(opt_state):
    """The InjectHyperparamsState, descending through a MultiSteps wrapper
    (gradient accumulation) when present."""
    if hasattr(opt_state, "hyperparams"):
        return opt_state
    inner = getattr(opt_state, "inner_opt_state", None)
    if inner is not None and hasattr(inner, "hyperparams"):
        return inner
    return None


def get_learning_rate(opt_state) -> float:
    return float(_lr_state(opt_state).hyperparams["learning_rate"])


def set_learning_rate(opt_state, lr: float):
    """Overwrite the injected learning-rate hyperparameter in place. The
    new leaf keeps the old one's placement when that spans several
    devices: an SPMD step hands back its optimizer state replicated over
    the mesh, and a fresh single-device scalar in its place changes the
    step's input shardings — one full recompile of the train step at the
    first LR reduction (seen on the four-chip host, PR 21)."""
    import jax
    import jax.numpy as jnp
    target = _lr_state(opt_state)
    old = target.hyperparams["learning_rate"]
    new = jnp.asarray(lr, dtype=getattr(old, "dtype", jnp.float32))
    if isinstance(old, jax.Array) and len(old.sharding.device_set) > 1:
        new = jax.device_put(new, old.sharding)
    target.hyperparams["learning_rate"] = new
    return opt_state


def supports_lr_schedule(opt_state) -> bool:
    state = _lr_state(opt_state)
    return state is not None and "learning_rate" in state.hyperparams
