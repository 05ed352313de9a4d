"""SchNet stack (SCF) — continuous-filter convolutions.

reference: hydragnn/models/SCFStack.py:32-223 (custom CFConv copying PyG
schnet's + optional equivariant coordinate update; GaussianSmearing +
RadiusInteractionGraph recompute distances in-model :53-56).

TPU difference: edges come precomputed from the host pipeline (static
shapes); distances are recomputed from `pos` *inside* the traced function so
gradients flow pos -> energy for force training, same effect as the
reference's in-model interaction graph.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..ops import segment as seg
from ..ops.basis import gaussian_basis
from ..ops.geometry import (LENGTH_EPS, edge_lengths, edge_vectors,
                            slot_vectors)
from .base import BaseStack
from .layers import MLP


def shifted_softplus(x):
    return jax.nn.softplus(x) - np.log(2.0)


class CFConv(nn.Module):
    """Continuous-filter conv + interaction block
    (reference: SCFStack.py:143-223 CFConv; lin1 -> W-weighted add-aggregation
    -> lin2, then act + linear like PyG's InteractionBlock)."""
    out_dim: int
    num_filters: int
    num_gaussians: int
    cutoff: float
    equivariant: bool = False

    @nn.compact
    def __call__(self, x, pos, batch, cargs):
        # one algorithm in the layout the lengths arrive in: [N, K] in slot
        # order (`SCFStack.conv_args` on a batch with the neighbour tables)
        # or [E] in edge order (no tables; the pipeline trainer's lengths;
        # a true per-edge feature). The filters are made where the lengths
        # stand, so in slot order nothing converts a layout
        d = cargs["edge_length"]
        slot_order = d.ndim == 2
        rbf = gaussian_basis(d, 0.0, self.cutoff, self.num_gaussians)
        C = 0.5 * (jnp.cos(d * np.pi / self.cutoff) + 1.0)
        C = jnp.where(d <= self.cutoff, C, 0.0)
        # slot order runs the filter network on N x K rows: 17% more than E
        # in a training batch, 70% more in a serving bucket (sized by its
        # edges). Where the program is differentiated once (`train` False:
        # evaluation, the engine's energies and -dE/dpos) the network's
        # hidden activations are recomputed in the backward pass, not kept:
        # 5.8 GiB against 8.4 in the largest OC20 bucket, so the engine
        # keeps two batches in flight, and fewer bytes moved. The EF train
        # step differentiates twice and would recompute in both backward
        # passes (PERF.md section 6, PR 31): there they are kept. (`is
        # False`: under `conv_checkpointing` `cargs` arrive traced and the
        # whole conv is recomputed anyway; the pipeline trainer gives no
        # `train` key and keeps them too.)
        recompute = slot_order and cargs.get("train") is False
        W = (nn.remat(MLP) if recompute else MLP)(
            [self.num_filters, self.num_filters],
            activation=shifted_softplus, name="filter_nn")(rbf)
        W = W * C[..., None]

        h = nn.Dense(self.num_filters, use_bias=False, name="lin1")(x)

        if self.equivariant:
            # coordinate update (reference: SCFStack.py:173-181,201-208)
            if slot_order:
                vec, length = slot_vectors(pos, batch, eps=LENGTH_EPS)
            else:
                vec, length = edge_vectors(pos, batch.senders,
                                           batch.receivers, batch.edge_shifts)
            coord_diff = vec / (length + 1.0)[..., None]
            phi = MLP([self.num_filters, 1], activation=jax.nn.relu,
                      name="coord_mlp")(W)
            trans = jnp.clip(coord_diff * phi, -100.0, 100.0)
            pos = pos + (seg.neighbor_mean(trans, batch.nbr_mask)
                         if slot_order
                         else seg.edge_aggregate_mean(trans, batch))

        # filter-weighted aggregation: slot-order filters -> h[nbr] and a
        # masked K-axis reduction; edge-order ones -> h[senders], then the
        # layout conversion (tables) or the segment scatter (edge list)
        h = seg.filter_weighted_aggregate(h, W, batch)
        h = nn.Dense(self.num_filters, name="lin2")(h)
        h = shifted_softplus(h)
        h = nn.Dense(self.out_dim, name="lin_out")(h)
        return h, pos


class SCFStack(BaseStack):
    """reference: hydragnn/models/SCFStack.py:32 — equivariant feature layers
    are identity (no BatchNorm) when equivariance is on."""

    def make_conv(self, in_dim, out_dim, idx, final=False):
        return CFConv(out_dim=out_dim,
                      num_filters=int(self.cfg.num_filters or 128),
                      num_gaussians=int(self.cfg.num_gaussians or 50),
                      cutoff=float(self.cfg.radius),
                      equivariant=self.cfg.equivariance,
                      name=f"conv_{idx}")

    def conv_args(self, batch):
        """Edge lengths, once a step: a true per-edge feature stays in
        edge order ([E]); lengths from positions come in the order of the
        batch's layout ([N, K] with the neighbour tables)."""
        if batch.edge_attr is not None and self.cfg.edge_dim:
            length = jnp.linalg.norm(batch.edge_attr, axis=-1)
        else:
            length = edge_lengths(batch)
        return {"edge_length": length}
