"""Concrete invariant stacks: GIN, SAGE, GAT, MFC, CGCNN, PNA, PNAPlus.

Each mirrors a reference stack file (hydragnn/models/<name>Stack.py) but
builds on the flax `BaseStack` + convs in `convs.py`.
"""
from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from ..ops.basis import bessel_basis
from ..ops.geometry import edge_lengths
from .base import BaseStack
from .convs import CGConv, GATv2Conv, GINConv, MFConv, PNAConv, SAGEConv


class GINStack(BaseStack):
    """reference: hydragnn/models/GINStack.py:21-48."""

    def make_conv(self, in_dim, out_dim, idx, final=False):
        return GINConv(out_dim=out_dim, name=f"conv_{idx}")


class SAGEStack(BaseStack):
    """reference: hydragnn/models/SAGEStack.py:21-42."""

    def make_conv(self, in_dim, out_dim, idx, final=False):
        return SAGEConv(out_dim=out_dim, name=f"conv_{idx}")


class GATStack(BaseStack):
    """reference: hydragnn/models/GATStack.py:21-120 (GATv2, heads=6,
    negative_slope=0.05 — hardcoded at create.py:195-196; concat heads on all
    but the final conv of each sub-stack)."""
    heads: int = 6
    negative_slope: float = 0.05

    def make_conv(self, in_dim, out_dim, idx, final=False):
        return GATv2Conv(out_dim=out_dim, heads=self.heads,
                         negative_slope=self.negative_slope,
                         concat=not final, name=f"conv_{idx}")


class MFCStack(BaseStack):
    """reference: hydragnn/models/MFCStack.py:21-69 (max_degree=max_neighbours)."""

    def make_conv(self, in_dim, out_dim, idx, final=False):
        return MFConv(out_dim=out_dim,
                      max_degree=int(self.cfg.max_neighbours or 10),
                      name=f"conv_{idx}")


class CGCNNStack(BaseStack):
    """reference: hydragnn/models/CGCNNStack.py:19-91. CGConv keeps channel
    count fixed, so hidden dim == input dim (reference: CGCNNStack.py:25-31);
    the factory enforces that before construction."""

    def make_conv(self, in_dim, out_dim, idx, final=False):
        return CGConv(out_dim=out_dim, name=f"conv_{idx}")

    def conv_args(self, batch):
        return {"edge_attr": batch.edge_attr}


class PNAStack(BaseStack):
    """reference: hydragnn/models/PNAStack.py:19-69."""

    def make_conv(self, in_dim, out_dim, idx, final=False):
        return PNAConv(out_dim=out_dim, deg_hist=self.cfg.pna_deg,
                       edge_dim=self.cfg.edge_dim, name=f"conv_{idx}")

    def conv_args(self, batch):
        return {"edge_attr": batch.edge_attr}


class PNAPlusStack(BaseStack):
    """reference: hydragnn/models/PNAPlusStack.py:39-282 — PNA with a Bessel
    radial embedding of edge lengths injected into every message
    (BesselBasisLayer :66-120, rbf in messages :228-250)."""

    def make_conv(self, in_dim, out_dim, idx, final=False):
        return PNAConv(out_dim=out_dim, deg_hist=self.cfg.pna_deg,
                       edge_dim=self.cfg.edge_dim, rbf=True,
                       name=f"conv_{idx}")

    def conv_args(self, batch):
        """The radial basis of every edge, made once a step in the order
        of the batch's layout ([N, K, R] with the neighbour tables, [E, R]
        without): `PNAConv` encodes it where it stands."""
        rbf = bessel_basis(edge_lengths(batch), float(self.cfg.radius),
                           int(self.cfg.num_radial or 6),
                           int(self.cfg.envelope_exponent or 5))
        return {"rbf": rbf, "edge_attr": batch.edge_attr}
