"""Operations and bytes of one forward pass of the SchNet stack
(``models/schnet.CFConv``), from shapes.

Per interaction with input width f (1 for the first, hidden_dim after),
hidden width h, filters c, Gaussians g:

  per edge   Gaussian smearing (4 g), filter network (2 g c + 2 c c),
             cutoff and product with the neighbour's row (3 c), sum (c)
  per atom   lin1 (2 f c), lin2 (2 c c), lin_out (2 c h), 10 h for
             BatchNorm and ReLU

Bytes, for an ideal fused interaction: each edge reads its neighbour's lin1
row once (c floats) — the filter is a function of the distance and never
needs to reach HBM — and each atom reads its input row and writes its
output row (f + h floats).
"""
from __future__ import annotations

from typing import Dict, Tuple

from .common import FLOAT_BYTES, head_flops


def forward(arch: Dict, atoms: float, edges: float) -> Tuple[float, float]:
    h = int(arch["hidden_dim"])
    c = int(arch["num_filters"])
    g = int(arch["num_gaussians"])
    flops = atoms * head_flops(arch)
    hbm = 0.0
    f = int(arch.get("input_dim", 1))
    for _ in range(int(arch["num_conv_layers"])):
        flops += edges * (4 * g + 2 * g * c + 2 * c * c + 4 * c)
        flops += atoms * (2 * f * c + 2 * c * c + 2 * c * h + 10 * h)
        hbm += FLOAT_BYTES * (edges * c + atoms * (f + h))
        f = h
    return float(flops), float(hbm)
