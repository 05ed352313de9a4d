"""MACE stack — higher-body-order equivariant message passing.

reference: hydragnn/models/MACEStack.py:70-741 and mace_utils/ — spherical
harmonic edge attributes (:131-135), radial bases with polynomial cutoff and
Agnesi/Soft distance transforms (mace_utils/modules/radial.py), interaction
block with per-edge radial weights (RealAgnosticAttResidualInteractionBlock,
blocks.py:283-386), product basis via Clebsch-Gordan symmetric contraction
(blocks.py:163-199, symmetric_contraction.py), per-layer multihead readouts
summed across layers (n-body expansion, MACEStack.py:368-407, :509-643),
positions centered per graph (:414-419), 118-element one-hot (:474-507).

TPU-first redesign notes (capability-preserving, not a port):
* irreps features live as {l: [N, mul, 2l+1]} dicts; every mixing op is a
  per-l channel matmul (MXU-friendly einsum), no e3nn codegen;
* the symmetric contraction (correlation order nu) is realized as iterated
  depthwise CG tensor products A^(k+1) = TP(A^(k), A) projected to lmax,
  with learnable per-l channel mixes — same body-order expansion, simpler
  bookkeeping than the reference's U-matrix contraction;
* equivariance of the underlying algebra is proven by tests/test_irreps.py,
  and end-to-end rotation invariance by tests/test_equivariance.py.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..ops import segment as seg
from ..ops.basis import (DISTANCE_TRANSFORMS, RADIAL_BASES,
                         polynomial_cutoff)
from ..ops.geometry import edge_vectors
from ..ops.irreps import (IrrepsDict, real_spherical_harmonics, scalar_part,
                          tensor_product)
from ..ops.segment import global_mean_pool
from .base import BaseStack
from .layers import MLP, MLPNode, node_index_in_graph


class LinearIrreps(nn.Module):
    """Per-l channel mixing: [N, mul_in, 2l+1] -> [N, mul_out, 2l+1]."""
    mul_out: int
    name_prefix: str = "lin"

    @nn.compact
    def __call__(self, feats: IrrepsDict) -> IrrepsDict:
        out = {}
        for l, f in sorted(feats.items()):
            w = self.param(f"{self.name_prefix}_l{l}",
                           nn.initializers.lecun_normal(),
                           (f.shape[-2], self.mul_out))
            out[l] = jnp.einsum("...ui,uv->...vi", f, w) / math.sqrt(f.shape[-2])
        return out


class MACEInteraction(nn.Module):
    """Tensor-product conv with per-edge radial weights
    (reference: RealAgnosticAttResidualInteractionBlock, blocks.py:283-386)."""
    mul: int
    lmax_out: int
    avg_num_neighbors: float

    @nn.compact
    def __call__(self, feats: IrrepsDict, sh: IrrepsDict,
                 radial: jnp.ndarray, batch) -> IrrepsDict:
        send, recv = batch.senders, batch.receivers
        h = LinearIrreps(self.mul, name="lin_up")(feats)
        # enumerate TP paths to size the radial weight MLP output
        paths = []
        for l1 in sorted(h):
            for l2 in sorted(sh):
                for l3 in range(abs(l1 - l2), min(l1 + l2, self.lmax_out) + 1):
                    paths.append((l1, l2, l3))
        w = MLP([self.mul, self.mul * len(paths)], activation=jax.nn.silu,
                name="radial_weights")(radial)            # [E, P*mul]
        w = w.reshape(w.shape[:-1] + (len(paths), self.mul))
        weights = {p: w[..., i, :] for i, p in enumerate(paths)}
        h_e = {l: seg.neighbor_gather(f, send) for l, f in h.items()}
        sh_e = {l: f[:, None, :] for l, f in sh.items()}   # mul-broadcast
        msgs = tensor_product(h_e, sh_e, self.lmax_out, weights)
        agg = {l: seg.edge_aggregate_sum(m, batch) / self.avg_num_neighbors
               for l, m in msgs.items()}
        return LinearIrreps(self.mul, name="lin_out")(agg)


class MACEProduct(nn.Module):
    """Body-order product basis (reference: EquivariantProductBasisBlock +
    SymmetricContraction, blocks.py:163-199): iterated depthwise CG products
    up to `correlation`, each order linearly mixed then summed."""
    mul: int
    lmax: int
    correlation: int

    @nn.compact
    def __call__(self, a: IrrepsDict, residual: Optional[IrrepsDict]) -> IrrepsDict:
        total = LinearIrreps(self.mul, name="mix_1")(a)
        cur = a
        for nu in range(2, self.correlation + 1):
            cur = tensor_product(cur, a, self.lmax)
            mixed = LinearIrreps(self.mul, name=f"mix_{nu}")(cur)
            total = {l: total.get(l, 0.0) + mixed[l] for l in
                     set(total) | set(mixed)}
        if residual is not None:
            res = LinearIrreps(self.mul, name="sc")(residual)
            total = {l: (total[l] + res[l]) if l in res else total[l]
                     for l in total}
        return total


class MACEReadout(nn.Module):
    """Per-layer multihead readout on invariant (l=0) channels
    (reference: MultiheadDecoderBlock, MACEStack.py:509-643). Intermediate
    layers use a linear readout, the last layer a nonlinear MLP."""
    cfg: "ModelConfig"
    nonlinear: bool

    @nn.compact
    def __call__(self, scalars: jnp.ndarray, batch):
        from ..ops.activations import activation_function_selection
        cfg = self.cfg
        act = activation_function_selection(cfg.activation)
        widen = 1 + cfg.var_output
        outputs = []
        pooled = global_mean_pool(scalars, batch.node_graph, batch.num_graphs,
                                  batch.node_mask)
        for ih, head in enumerate(cfg.heads):
            odim = head.output_dim * widen
            if head.head_type == "graph":
                if self.nonlinear:
                    out = MLP(list(head.dim_headlayers) + [odim],
                              activation=act, name=f"head_{ih}")(pooled)
                else:
                    out = nn.Dense(odim, name=f"head_{ih}")(pooled)
            else:
                if head.node_arch == "mlp_per_node":
                    idx = node_index_in_graph(batch.node_graph, batch.num_graphs)
                    out = MLPNode(hidden_dims=head.dim_headlayers,
                                  output_dim=odim,
                                  num_nodes=max(cfg.num_nodes, 1),
                                  node_type="mlp_per_node", activation=act,
                                  name=f"head_{ih}")(scalars, idx)
                elif self.nonlinear:
                    out = MLP(list(head.dim_headlayers) + [odim],
                              activation=act, name=f"head_{ih}")(scalars)
                else:
                    out = nn.Dense(odim, name=f"head_{ih}")(scalars)
            outputs.append(out)
        return outputs


def process_node_attributes(x: jnp.ndarray, num_elements: int = 118):
    """One-hot of (clamped, rounded) atomic numbers
    (reference: MACEStack.py:474-507; non-integer features are tolerated for
    the CI datasets, values clamped into [1, 118])."""
    z = jnp.clip(jnp.round(x[:, 0]), 1, num_elements).astype(jnp.int32)
    return jax.nn.one_hot(z - 1, num_elements, dtype=x.dtype)


class MACEStack(BaseStack):
    """reference: hydragnn/models/MACEStack.py:70."""
    use_batch_norm: bool = False

    @nn.compact
    def __call__(self, batch, train: bool = False):
        cfg = self.cfg
        mul = cfg.hidden_dim
        lmax = int(cfg.max_ell or 1)
        node_lmax = int(cfg.node_max_ell or 1)
        corr = cfg.correlation
        if corr is None:
            corr = (2,)
        elif isinstance(corr, int):
            corr = (corr,)
        radial_type = cfg.radial_type or "bessel"
        num_basis = int(cfg.num_radial or 8)
        cutoff = float(cfg.radius)

        # ---- conv args (reference: _conv_args, MACEStack.py:409-455) ----
        with jax.named_scope("geometry"):
            pos_mean = global_mean_pool(batch.pos, batch.node_graph,
                                        batch.num_graphs, batch.node_mask)
            pos = batch.pos - pos_mean[batch.node_graph]
            node_attrs = process_node_attributes(batch.x, cfg.num_elements)
            vec, length = edge_vectors(pos, batch.senders, batch.receivers,
                                       batch.edge_shifts)
            sh = real_spherical_harmonics(vec, lmax)
            d = DISTANCE_TRANSFORMS[cfg.distance_transform or "None"](
                length)
            radial = RADIAL_BASES[radial_type](d, cutoff, num_basis)
            radial = radial * polynomial_cutoff(length, cutoff)[:, None]

        # ---- embeddings ----
        feats: IrrepsDict = {
            0: nn.Dense(mul, use_bias=False, name="node_embedding")(
                node_attrs)[..., None]}

        # ---- readout 0 on the raw embedding (MACEStack.py:381-385) ----
        outputs = MACEReadout(cfg=self.cfg, nonlinear=False, name="readout_0")(
            scalar_part(feats), batch)

        # ---- conv -> readout, summed (MACEStack.py:387-407) ----
        for i in range(cfg.num_conv_layers):
            last = i == cfg.num_conv_layers - 1
            layer_lmax = node_lmax if not last else 0
            nu = int(corr[i]) if i < len(corr) else int(corr[-1])
            # the modules keep their names (parameter paths); the trace
            # reads interaction + product as this stack's `conv_<i>`
            with jax.named_scope(f"conv_{i}"):
                msg = MACEInteraction(mul=mul, lmax_out=layer_lmax,
                                      avg_num_neighbors=float(
                                          cfg.avg_num_neighbors or 1.0),
                                      name=f"interaction_{i}")(
                    feats, sh, radial, batch)
                feats = MACEProduct(mul=mul, lmax=layer_lmax,
                                    correlation=nu,
                                    name=f"product_{i}")(msg, feats)
            out_i = MACEReadout(cfg=self.cfg, nonlinear=last,
                                name=f"readout_{i + 1}")(
                scalar_part(feats), batch)
            outputs = [o + oi for o, oi in zip(outputs, out_i)]

        widen_outputs, widen_vars = [], []
        for out, head in zip(outputs, cfg.heads):
            widen_outputs.append(out[..., :head.output_dim])
            if cfg.var_output:
                widen_vars.append(out[..., head.output_dim:] ** 2)
        if cfg.var_output:
            return widen_outputs, widen_vars
        return widen_outputs, None
