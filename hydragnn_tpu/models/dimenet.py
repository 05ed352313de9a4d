"""DimeNet++ stack — directional message passing over triplets.

reference: hydragnn/models/DIMEStack.py:31-254 (PyG InteractionPPBlock /
OutputPPBlock with a custom HydraEmbeddingBlock that embeds node features
instead of atomic numbers :208-229; per-batch triplets :181-205; angles in
_conv_args :135-169).

TPU design: triplet indices are host-precomputed padded arrays on the batch
(graphs/triplets.py) — no SparseTensor, no dynamic shapes. Angles and bases
are computed in-model from positions so force training differentiates
through them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import segment as seg
from ..ops.basis import bessel_basis
from ..ops.geometry import edge_vectors
from ..ops.spherical import spherical_basis
from .base import BaseStack
from .layers import MLP


class HydraEmbeddingBlock(nn.Module):
    """Edge embedding from node features + rbf (no atomic-number embedding —
    reference: DIMEStack.py:208-229)."""
    hidden: int
    num_radial: int
    edge_dim: int = 0

    @nn.compact
    def __call__(self, x, rbf, batch):
        send, recv = batch.senders, batch.receivers
        rbf_emb = jax.nn.silu(nn.Dense(self.hidden, name="lin_rbf")(rbf))
        parts = [seg.neighbor_gather(x, send), seg.neighbor_gather(x, recv),
                 rbf_emb]
        if self.edge_dim and batch.edge_attr is not None:
            parts.append(jax.nn.silu(
                nn.Dense(self.hidden, name="lin_edge")(batch.edge_attr)))
        return jax.nn.silu(
            nn.Dense(self.hidden, name="lin")(jnp.concatenate(parts, -1)))


class InteractionPPBlock(nn.Module):
    """reference: PyG interaction block wired at DIMEStack.py:95-102."""
    hidden: int
    int_emb_size: int
    basis_emb_size: int
    num_before_skip: int
    num_after_skip: int

    @nn.compact
    def __call__(self, e, rbf, sbf, batch):
        act = jax.nn.silu
        x_ji = act(nn.Dense(self.hidden, name="lin_ji")(e))
        x_kj = act(nn.Dense(self.hidden, name="lin_kj")(e))
        rbf_e = nn.Dense(self.basis_emb_size, use_bias=False, name="lin_rbf1")(rbf)
        rbf_e = nn.Dense(self.hidden, use_bias=False, name="lin_rbf2")(rbf_e)
        x_kj = x_kj * rbf_e
        x_kj = act(nn.Dense(self.int_emb_size, name="lin_down")(x_kj))
        sbf_e = nn.Dense(self.basis_emb_size, use_bias=False, name="lin_sbf1")(sbf)
        sbf_e = nn.Dense(self.int_emb_size, use_bias=False, name="lin_sbf2")(sbf_e)
        # gather k->j edge messages per triplet, modulate, scatter to j->i
        m = x_kj[batch.idx_kj] * sbf_e
        agg = seg.segment_sum(m, batch.idx_ji, e.shape[0], batch.triplet_mask)
        x_kj = act(nn.Dense(self.hidden, name="lin_up")(agg))
        h = x_ji + x_kj
        for i in range(self.num_before_skip):
            h = act(nn.Dense(self.hidden, name=f"before_skip_{i}")(h))
        h = act(nn.Dense(self.hidden, name="lin_skip")(h)) + e
        for i in range(self.num_after_skip):
            h = act(nn.Dense(self.hidden, name=f"after_skip_{i}")(h))
        return h


class OutputPPBlock(nn.Module):
    """reference: PyG output block wired at DIMEStack.py:103-111."""
    hidden: int
    out_emb: int
    out_dim: int
    num_layers: int = 1

    @nn.compact
    def __call__(self, e, rbf, batch, num_nodes):
        g = nn.Dense(self.hidden, use_bias=False, name="lin_rbf")(rbf)
        x = seg.edge_aggregate_sum(g * e, batch)
        x = nn.Dense(self.out_emb, use_bias=False, name="lin_up")(x)
        for i in range(self.num_layers):
            x = jax.nn.silu(nn.Dense(self.out_emb, name=f"lin_{i}")(x))
        return nn.Dense(self.out_dim, use_bias=False, name="lin_out")(x)


class DimeNetConv(nn.Module):
    """lin -> embedding -> interaction -> output (one reference "conv",
    DIMEStack.py:80-131)."""
    hidden: int
    out_dim: int
    cfg_int: dict

    @nn.compact
    def __call__(self, x, pos, batch, cargs):
        c = self.cfg_int
        x = nn.Dense(self.hidden, name="lin")(x)
        e = HydraEmbeddingBlock(hidden=self.hidden,
                                num_radial=c["num_radial"],
                                edge_dim=c["edge_dim"], name="emb")(
            x, cargs["rbf"], batch)
        e = InteractionPPBlock(hidden=self.hidden,
                               int_emb_size=c["int_emb_size"],
                               basis_emb_size=c["basis_emb_size"],
                               num_before_skip=c["num_before_skip"],
                               num_after_skip=c["num_after_skip"],
                               name="interaction")(
            e, cargs["rbf"], cargs["sbf"], batch)
        out = OutputPPBlock(hidden=self.hidden, out_emb=c["out_emb_size"],
                            out_dim=self.out_dim, name="output")(
            e, cargs["rbf"], batch, x.shape[0])
        return out, pos


class DIMEStack(BaseStack):
    """reference: hydragnn/models/DIMEStack.py:31 (identity feature layers)."""
    use_batch_norm: bool = False

    def make_conv(self, in_dim, out_dim, idx, final=False):
        cfg = self.cfg
        hidden = out_dim if in_dim == 1 else in_dim
        return DimeNetConv(
            hidden=hidden, out_dim=out_dim,
            cfg_int=dict(
                num_radial=int(cfg.num_radial),
                int_emb_size=int(cfg.int_emb_size),
                basis_emb_size=int(cfg.basis_emb_size),
                out_emb_size=int(cfg.out_emb_size),
                num_before_skip=int(cfg.num_before_skip),
                num_after_skip=int(cfg.num_after_skip),
                edge_dim=int(cfg.edge_dim or 0)),
            name=f"conv_{idx}")

    def conv_args(self, batch):
        """Edge rbf + triplet angles/sbf (reference: DIMEStack.py:135-169)."""
        if batch.idx_kj is None:
            raise ValueError(
                "DimeNet needs triplet indices; build loaders with "
                "graphs.triplets.make_triplet_transform")
        cfg = self.cfg
        vec, dist = edge_vectors(batch.pos, batch.senders, batch.receivers,
                                 batch.edge_shifts)
        rbf = bessel_basis(dist, float(cfg.radius), int(cfg.num_radial),
                           int(cfg.envelope_exponent or 5))
        # vec[e] = pos[send] + shift - pos[recv]; for e2=(j->i) that is
        # pos_j - pos_i, for e1=(k->j) it is pos_k - pos_j. The angle at j is
        # between (pos_i - pos_j) and (pos_k - pos_j):
        a = -vec[batch.idx_ji]       # pos_i - pos_j
        b = vec[batch.idx_kj]        # pos_k - pos_j
        cross = jnp.linalg.norm(jnp.cross(a, b), axis=-1)
        dot = jnp.sum(a * b, axis=-1)
        angle = jnp.arctan2(cross, dot)
        sbf = spherical_basis(dist[batch.idx_kj], angle, float(cfg.radius),
                              int(cfg.num_spherical), int(cfg.num_radial),
                              int(cfg.envelope_exponent or 5))
        return {"rbf": rbf, "sbf": sbf}
