"""Operations and bytes of one forward pass of the DimeNet++ stack
(``models/dimenet.DimeNetConv``), from shapes.

The reader passes real atoms and edges only, so the edge PAIRS (k->j, j->i)
are taken as edges^2 / atoms - edges: every atom at the mean in-degree
d = edges / atoms gives each edge d - 1 partners, and by convexity any other
spread of the same edges over the same atoms has more. The least work is
counted, which keeps the share conservative.

Blocks: num_conv_layers, plus one per layer of a conv-type node head. Per
block with hidden width h (input width f: 1 for the first block, h after),
interaction width c, basis embedding b, output embedding o, R radial and
S x R spherical functions, nb / na layers before / after the skip:

  per pair   lin_sbf1 (2 SR b), lin_sbf2 (2 b c), product with the
             neighbour's row and sum (2 c)
  per edge   embedding: lin_rbf (2 R h), lin on [x_i, x_j, rbf] (6 h h);
             interaction: lin_ji, lin_kj (4 h h), lin_rbf1, lin_rbf2
             (2 R b + 2 b h), product (h), lin_down, lin_up (4 h c),
             skips (2 h h (nb + 1 + na)); output: lin_rbf (2 R h), product
             and sum (2 h)
  per atom   lin (2 f h), output lin_up (2 h o), lin_0 (2 o o), lin_out
             (2 o h); a conv-type head's block adds 10 h for its BatchNorm
             and activation, an MLP head its layers' matmuls

Once per forward pass: 4 operations for each of the SR basis values of a
pair (a sine, a cosine, the envelope and the Legendre factor: the least a
closed form takes) and for each of the R of an edge.

Bytes, for an ideal fused block: each pair reads the row of its (k->j)
message once (c floats), the basis being a function of two scalars that
never needs to reach HBM; each edge reads its neighbour's row (h floats);
each atom reads its input row and writes its output row (f + h floats).
At the published widths a pair has 7 operations a byte and an edge with
its ~32 pairs 80, under the v5e's 240: HBM binds.
"""
from __future__ import annotations

from typing import Dict, Tuple

from .common import FLOAT_BYTES


def forward(arch: Dict, atoms: float, edges: float) -> Tuple[float, float]:
    h = int(arch["hidden_dim"])
    c = int(arch["int_emb_size"])
    b = int(arch["basis_emb_size"])
    o = int(arch["out_emb_size"])
    radial = int(arch["num_radial"])
    sr = int(arch["num_spherical"]) * radial
    skips = int(arch["num_before_skip"]) + 1 + int(arch["num_after_skip"])
    head = arch["output_heads"]["node"]
    conv_head = head.get("type") == "conv"
    head_blocks = len(head["dim_headlayers"]) if conv_head else 0
    pairs = max(edges * edges / max(atoms, 1.0) - edges, 0.0)
    flops = 4.0 * (pairs * sr + edges * radial)
    hbm = 0.0
    f = int(arch.get("input_dim", 1))
    depth = int(arch["num_conv_layers"])
    for block in range(depth + head_blocks):
        flops += pairs * (2 * sr * b + 2 * b * c + 2 * c)
        flops += edges * (2 * radial * h + 6 * h * h + 4 * h * h
                          + 2 * radial * b + 2 * b * h + h + 4 * h * c
                          + 2 * h * h * skips + 2 * radial * h + 2 * h)
        flops += atoms * (2 * f * h + 2 * h * o + 2 * o * o + 2 * o * h
                          + (10 * h if block >= depth else 0))
        hbm += FLOAT_BYTES * (pairs * c + edges * h + atoms * (f + h))
        f = h
    # the head's linear layers, the last one to the atom's energy
    widths = [h] + ([] if conv_head else list(head["dim_headlayers"])) + [1]
    flops += atoms * sum(2 * a * b for a, b in zip(widths, widths[1:]))
    return float(flops), float(hbm)
