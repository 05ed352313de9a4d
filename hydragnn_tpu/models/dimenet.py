"""DimeNet++ stack — directional message passing over triplets.

reference: hydragnn/models/DIMEStack.py:31-254 (PyG InteractionPPBlock /
OutputPPBlock with a custom HydraEmbeddingBlock that embeds node features
instead of atomic numbers :208-229; per-batch triplets :181-205; angles in
_conv_args :135-169).

TPU design: with the dense neighbour table on the batch (`batch.nbr`, the
default layout) the third index space is derived INSIDE the jitted program:
for slot (i, a) with j = nbr[i, a] the incoming edges of j are row
nbr[j, :], so the pairs are an [N, K, K] space, masked where either slot is
empty or k == i. Messages live in slots [N, K, H]; the (k->j) messages of a
slot are the ROWS x_kj[nbr[i, a]] (one contiguous [K, int_emb] block per
gather index), the basis is [N, K, K, S*R], and the aggregate is a
contraction over the last K axis: no scatter in the forward pass and
nothing extra on the batch, so packing, data-parallel SPMD and the serving
engine run the stack as they run any other. Without the table (graph
sharding, HYDRAGNN_NEIGHBOR_FORMAT=0) the pairs are host-precomputed padded
index arrays (graphs/triplets.py, a caller-passed batch transform).
Angles and bases are computed in-model from positions so force training
differentiates through them; every 1/x and sqrt sees a safe argument in
padding slots BEFORE it is evaluated (padding is 90% of the pair space, and
a masked slot must add exactly 0 to energies, forces and weight gradients).

Trace vocabulary (PERF.md section 3): `pair_basis` (the pair geometry and
the [N, K, K, S*R] basis, inside `geometry`), `pair_gather` (the row gather
and its transpose), `pair_aggregate` (the basis embedding, the product and
the K-sum), the last two inside `conv_<i>`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import segment as seg
from ..ops.basis import bessel_basis
from ..ops.geometry import edge_vectors, slot_vectors
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
        rbf_emb = jax.nn.silu(nn.Dense(self.hidden, name="lin_rbf")(rbf))
        edge_attr = batch.edge_attr
        if batch.nbr is not None:
            # slot layout [N, K, .]: the sender is the slot's neighbour,
            # the receiver the row itself
            x_j = seg.neighbor_gather(x, batch.nbr)
            parts = [x_j, jnp.broadcast_to(x[:, None, :], x_j.shape),
                     rbf_emb]
            if self.edge_dim and edge_attr is not None:
                edge_attr = seg.edge_gather(edge_attr, batch)
        else:
            parts = [seg.neighbor_gather(x, batch.senders),
                     seg.neighbor_gather(x, batch.receivers), rbf_emb]
        if self.edge_dim and edge_attr is not None:
            parts.append(jax.nn.silu(
                nn.Dense(self.hidden, name="lin_edge")(edge_attr)))
        return jax.nn.silu(
            nn.Dense(self.hidden, name="lin")(jnp.concatenate(parts, -1)))


class DenseKernel(nn.Module):
    """The kernel of a bias-free `nn.Dense` of the same name (same path,
    shape and initialiser), handed out instead of applied: the dense pair
    path contracts it after the K-sum."""
    features: int

    @nn.compact
    def __call__(self, in_features: int):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          (in_features, self.features))


def pair_basis(batch, vec, dist, basis):
    """sbf [N, K, K, S*R] over the pair space derived from the neighbour
    table. Pair (i, a, b): j = nbr[i, a] sends to i and k = nbr[j, b] sends
    to j; real when both slots are and k != i (by node index, as
    graphs/triplets.sample_triplets and PyG have it, periodic images
    included). `basis(d_kj, cos)` of the length of (k->j) and the angle at
    j between (pos_i - pos_j) = -vec[i, a] and (pos_k - pos_j) = vec[j, b],
    put to exactly 0 in masked pairs: no layer after it has a bias, so
    they add 0 to every sum and take no gradient."""
    # (a host batch closed over by a jitted caller holds numpy tables)
    nbr, mask = jnp.asarray(batch.nbr), jnp.asarray(batch.nbr_mask)
    with jax.named_scope("pair_basis"):
        vec_kj, d_kj, k_node, k_real = (
            seg.row_gather(a, nbr)
            for a in (vec, dist, nbr, mask))             # [N, K, K(, 3)]
        node = jnp.arange(nbr.shape[0], dtype=nbr.dtype)[:, None, None]
        pair_mask = mask[:, :, None] & k_real & (k_node != node)
        cos = -jnp.sum(vec[:, :, None, :] * vec_kj, axis=-1) / (
            dist[:, :, None] * d_kj)
        return jnp.where(pair_mask[..., None], basis(d_kj, cos), 0.0)


def pair_messages(x_kj, sbf_b, w2, nbr):
    """agg[i, a] = sum_b x_kj[j, b] * (sbf_b[i, a, b] @ w2) with
    j = nbr[i, a]: the (k->j) messages of slot (i, a) are ROW nbr[i, a] of
    x_kj [N, K, C] (one contiguous block per gather index), weighted by
    the embedded basis sbf_b [N, K, K, B]. The K-sum is taken BEFORE
    lin_sbf2's kernel w2 [B, C] (one batched matmul per slot, [B, K] x
    [K, C]), so the embedded basis is never formed at width C."""
    rows = seg.row_gather(x_kj, jnp.asarray(nbr))        # [N, K, K, C]
    with jax.named_scope("pair_aggregate"):
        per_basis = jnp.einsum("nabp,nabc->napc", sbf_b, rows)
        return jnp.einsum("napc,pc->nac", per_basis,
                          w2.astype(per_basis.dtype))


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
        lin_sbf1 = nn.Dense(self.basis_emb_size, use_bias=False,
                            name="lin_sbf1")
        if batch.nbr is not None:
            with jax.named_scope("pair_aggregate"):
                sbf_b = lin_sbf1(sbf)
            agg = pair_messages(
                x_kj, sbf_b, DenseKernel(self.int_emb_size, name="lin_sbf2")(
                    self.basis_emb_size), batch.nbr)
        else:
            sbf_e = nn.Dense(self.int_emb_size, use_bias=False,
                             name="lin_sbf2")(lin_sbf1(sbf))
            # gather k->j edge messages per triplet, modulate, scatter to
            # j->i
            m = x_kj[batch.idx_kj] * sbf_e
            agg = seg.segment_sum(m, batch.idx_ji, e.shape[0],
                                  batch.triplet_mask)
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
        if batch.nbr is not None:
            # messages are in slots already: the edge -> node sum is a sum
            # over K
            x = seg.neighbor_sum(g * e, batch.nbr_mask)
        else:
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
    # with the dense table the stack derives an [N, K, K] pair space from it
    # (whoever reports padding asks the loader for `pad_pair_share` too)
    derives_pair_space = True

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

    def _basis(self) -> dict:
        cfg = self.cfg
        return dict(cutoff=float(cfg.radius),
                    num_spherical=int(cfg.num_spherical),
                    num_radial=int(cfg.num_radial),
                    envelope_exponent=int(cfg.envelope_exponent or 5))

    def conv_args(self, batch):
        """Edge rbf + pair angles/sbf (reference: DIMEStack.py:135-169):
        per slot and per derived pair with the neighbour table, else per
        edge and over the host-built list."""
        basis = self._basis()
        cutoff, num_radial = basis["cutoff"], basis["num_radial"]
        exponent = basis["envelope_exponent"]
        if batch.nbr is not None:
            # a masked slot gets the zero vector and the length 1 A:
            # nothing divides by, or takes the root of, what padding left
            vec, dist = slot_vectors(batch.pos, batch)
            rbf = jnp.where(batch.nbr_mask[..., None],
                            bessel_basis(dist, cutoff, num_radial, exponent),
                            0.0)
            return {"rbf": rbf, "sbf": pair_basis(
                batch, vec, dist,
                functools.partial(spherical_basis, **basis))}
        if batch.idx_kj is None:
            raise ValueError(
                "DimeNet without the dense neighbour table needs triplet "
                "indices; build loaders with "
                "graphs.triplets.make_triplet_transform")
        vec, dist = edge_vectors(batch.pos, batch.senders, batch.receivers,
                                 batch.edge_shifts)
        rbf = bessel_basis(dist, cutoff, num_radial, exponent)
        # vec[e] = pos[send] + shift - pos[recv]; for e2=(j->i) that is
        # pos_j - pos_i, for e1=(k->j) it is pos_k - pos_j. The angle at j is
        # between (pos_i - pos_j) and (pos_k - pos_j):
        a = -vec[batch.idx_ji]       # pos_i - pos_j
        b = vec[batch.idx_kj]        # pos_k - pos_j
        cos = jnp.sum(a * b, axis=-1) / (dist[batch.idx_ji]
                                         * dist[batch.idx_kj])
        return {"rbf": rbf,
                "sbf": spherical_basis(dist[batch.idx_kj], cos, **basis)}
