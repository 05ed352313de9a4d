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
from ..ops.geometry import edge_vectors
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
        d = cargs["edge_length"]
        rbf = gaussian_basis(d, 0.0, self.cutoff, self.num_gaussians)
        C = 0.5 * (jnp.cos(d * np.pi / self.cutoff) + 1.0)
        C = jnp.where(d <= self.cutoff, C, 0.0)
        W = MLP([self.num_filters, self.num_filters],
                activation=shifted_softplus, name="filter_nn")(rbf)
        W = W * C[:, None]

        h = nn.Dense(self.num_filters, use_bias=False, name="lin1")(x)

        if self.equivariant:
            # coordinate update (reference: SCFStack.py:173-181,201-208)
            vec, length = edge_vectors(pos, batch.senders, batch.receivers,
                                       batch.edge_shifts)
            coord_diff = vec / (length + 1.0)[:, None]
            phi = MLP([self.num_filters, 1], activation=jax.nn.relu,
                      name="coord_mlp")(W)
            trans = jnp.clip(coord_diff * phi, -100.0, 100.0)
            pos = pos + seg.edge_aggregate_mean(trans, batch)

        # filter-weighted aggregation: dense layout -> masked K-axis
        # reduction; edge list -> gather + segment scatter
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
        if batch.edge_attr is not None and self.cfg.edge_dim:
            length = jnp.linalg.norm(batch.edge_attr, axis=-1)
        else:
            _, length = edge_vectors(batch.pos, batch.senders, batch.receivers,
                                     batch.edge_shifts)
        return {"edge_length": length}
