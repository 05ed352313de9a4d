"""Operations and bytes of one forward pass of the PNAPlus stack
(``models/convs.PNAConv`` with the radial embedding), from shapes.

Per conv layer with message width f (the layer's INPUT width: 1 for the
first layer, hidden_dim after), hidden width h, R radial functions:

  per atom   pre_i and pre_j (2 * 2 f f), post_nn on 16 f scaled
             aggregates (2 * 16 f h), lin (2 h h), 12 f for the four
             scalers, 10 h for BatchNorm and ReLU
  per edge   rbf_encoder (2 R f), rbf_proj (2 f f), 2 f to add the three
             message terms, 6 f for sum, sum of squares, min and max

Bytes, for an ideal fused layer: each edge reads its neighbour's projected
row once (f floats), each atom reads its input row and writes its output
row (f + h floats); messages never need to reach HBM.
"""
from __future__ import annotations

from typing import Dict, Tuple

from .common import FLOAT_BYTES, head_flops


def forward(arch: Dict, atoms: float, edges: float) -> Tuple[float, float]:
    h = int(arch["hidden_dim"])
    radial = int(arch.get("num_radial") or 6)
    flops = atoms * head_flops(arch)
    hbm = 0.0
    f = int(arch.get("input_dim", 1))
    for _ in range(int(arch["num_conv_layers"])):
        flops += atoms * (4 * f * f + 32 * f * h + 2 * h * h + 12 * f
                          + 10 * h)
        flops += edges * (2 * radial * f + 2 * f * f + 8 * f)
        hbm += FLOAT_BYTES * (edges * f + atoms * (f + h))
        f = h
    return float(flops), float(hbm)
