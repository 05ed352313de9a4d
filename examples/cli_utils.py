"""Shared boilerplate for the example CLIs.

Every reference example repeats the same driver scaffolding (seed/DDP
setup, config load + CLI overrides, split/train/report); the TPU
examples share it here instead.
"""
from __future__ import annotations

import json
import os
from typing import Optional, Sequence, Tuple


def setup_cpu_devices(n: int = 8) -> None:
    """Force the 8-device virtual CPU mesh (the examples' --cpu flag).

    Must run before jax initializes its backend (the first
    jax.devices() call); set through jax.config so it also works when
    jax was already imported."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={n}").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")


def load_example_config(here: str, inputfile: str,
                        num_epoch: Optional[int] = None,
                        batch_size: Optional[int] = None,
                        hidden_dim: Optional[int] = None) -> dict:
    """Read the example's JSON config and apply the common CLI overrides
    (epochs, batch size, and a proportional hidden/head width override)."""
    with open(os.path.join(here, inputfile)) as f:
        config = json.load(f)
    train_cfg = config["NeuralNetwork"]["Training"]
    if num_epoch is not None:
        train_cfg["num_epoch"] = num_epoch
    if batch_size is not None:
        train_cfg["batch_size"] = batch_size
    if hidden_dim is not None:
        arch = config["NeuralNetwork"]["Architecture"]
        arch["hidden_dim"] = hidden_dim
        for head in arch["output_heads"].values():
            if "dim_sharedlayers" in head:
                head["dim_sharedlayers"] = hidden_dim
            head["dim_headlayers"] = [hidden_dim] * len(
                head["dim_headlayers"])
    return config


def train_and_report(config: dict, splits: Tuple, **run_kwargs):
    """run_training + the one-line JSON result every example prints."""
    from hydragnn_tpu.run_training import run_training
    state, history, model, completed = run_training(
        config, datasets=splits, **run_kwargs)
    print(json.dumps({"final_train_loss": history["train_loss"][-1],
                      "final_val_loss": history["val_loss"][-1]}))
    return state, history, model, completed


def split_and_train(config: dict, samples: Sequence, **run_kwargs):
    """split_dataset by the config's perc_train, then train_and_report."""
    from hydragnn_tpu.preprocess.load_data import split_dataset
    splits = split_dataset(
        samples, config["NeuralNetwork"]["Training"]["perc_train"], False)
    return train_and_report(config, splits, **run_kwargs)
