"""hydragnn_tpu — a TPU-native (JAX/XLA/pjit) re-design of HydraGNN.

Multi-headed graph convolutional networks for atomistic materials data, built
TPU-first: static-shape padded graph batches, masked segment ops, functional
flax models, SPMD data parallelism over a jax.sharding.Mesh.

Top-level API mirrors the reference (hydragnn/__init__.py:1-3):
`run_training(config_or_path)`, `run_prediction(...)`.
"""
__version__ = "0.1.0"

from .run_training import run_training
from .run_prediction import run_prediction
