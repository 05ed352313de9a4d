"""Least time the chip could take for a stated amount of work.

Each ``benchmark/roofline/<conv>.py`` counts, from shapes alone, the
floating-point operations and the HBM bytes that ONE forward pass of its
stack needs for a number of REAL atoms and edges (padding needs nothing, so
padding shows as waste in the share). The passes that a job adds on top are
counted here, once, for every stack:

* ``forward_ef`` (serving energy and forces): the forward pass and the
  gradient with respect to positions. That gradient walks the same matmuls
  backwards for their inputs only (dX = dY W^T, no dW): 2 forward passes.
* ``train_ef`` (a training step on an energy+force loss): the 2 passes
  above are themselves differentiated with respect to the weights; reverse
  mode through a computation of matmuls costs twice the computation (dX and
  dW for each): 2 + 2 * 2 = 6 forward passes.

Recomputation is not counted. ``tools/profile_step.analytic`` used 5 and
counted the padded [N, K, F] tensors; this counts what is required.
"""
from __future__ import annotations

from typing import Dict, Tuple

PASSES = {"forward_ef": 2.0, "train_ef": 6.0}
FLOAT_BYTES = 4


def least_seconds(flops: float, hbm_bytes: float, peak: Dict
                  ) -> Tuple[float, str]:
    """(seconds, which roof binds)."""
    t_flops = flops / float(peak["flops_per_s"])
    t_bytes = hbm_bytes / float(peak["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "hbm")


def mlp_flops(widths) -> float:
    """Multiply-adds of a chain of dense layers, per row, as FLOPs."""
    return float(sum(2 * a * b for a, b in zip(widths[:-1], widths[1:])))


def head_flops(arch: Dict) -> float:
    head = arch["output_heads"]["node"]
    return mlp_flops([arch["hidden_dim"], *head["dim_headlayers"], 1])
