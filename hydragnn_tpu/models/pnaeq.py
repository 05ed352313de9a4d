"""PNAEq stack — PAINN-style equivariant message passing with PNA
degree-scaled multi-aggregation on the scalar channel.

reference: hydragnn/models/PNAEqStack.py:38-488 (PainnMessage :216-396 with
DegreeScalerAggregation, PainnUpdate :399-446, rbf_BasisLayer :448-488;
aggregators mean/min/max/std, scalers identity/amplification/attenuation/
linear/inverse_linear :47-54).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import segment as seg
from ..ops.basis import cosine_cutoff, sinc_expansion
from ..ops.geometry import edge_vectors
from .base import BaseStack
from .convs import pna_degree_stats
from .layers import MLP


def degree_scaler_aggregation(h, recv, num_nodes, edge_mask, deg_hist,
                              scalers=("identity", "amplification",
                                       "attenuation", "linear",
                                       "inverse_linear"), batch=None):
    """PyG DegreeScalerAggregation semantics: concat 4 aggregators, then
    concat one scaled copy per scaler. With a dense-layout `batch` the
    statistics come from masked K-axis reductions instead of segment
    scatters."""
    if batch is not None and batch.nbr_edge is not None:
        mean, mn, mx, sd, deg = seg.neighbor_aggregate(
            seg.edge_gather(h, batch), batch.nbr_mask)
    else:
        mean, mn, mx, sd, deg = seg.pna_aggregate(h, recv, num_nodes,
                                                  edge_mask)
    aggs = jnp.concatenate([mean, mn, mx, sd], axis=-1)
    avg_lin, avg_log = pna_degree_stats(deg_hist)
    logd = jnp.log(deg + 1.0)
    parts = []
    for s in scalers:
        if s == "identity":
            parts.append(aggs)
        elif s == "amplification":
            parts.append(aggs * (logd / avg_log)[:, None])
        elif s == "attenuation":
            parts.append(aggs * (avg_log / jnp.maximum(logd, 1e-6))[:, None])
        elif s == "linear":
            parts.append(aggs * (deg / avg_lin)[:, None])
        elif s == "inverse_linear":
            parts.append(aggs * (avg_lin / jnp.maximum(deg, 1.0))[:, None])
        else:
            raise ValueError(f"unknown scaler {s}")
    return jnp.concatenate(parts, axis=-1)


class PNAEqMessage(nn.Module):
    """reference: PNAEqStack.py:216-396."""
    node_size: int
    num_radial: int
    deg_hist: Sequence[int]
    edge_dim: Optional[int] = None

    @nn.compact
    def __call__(self, x, v, batch, rbf, edge_vec):
        send, recv = batch.senders, batch.receivers
        F = self.node_size
        rbf_attr = jnp.tanh(nn.Dense(F, name="rbf_emb")(rbf))
        parts = [seg.neighbor_gather(x, send), seg.neighbor_gather(x, recv),
                 rbf_attr]
        if self.edge_dim and batch.edge_attr is not None:
            parts.append(nn.Dense(F, name="edge_encoder")(batch.edge_attr))
        pre_in = jnp.concatenate(parts, axis=-1)
        msg = nn.Dense(F, name="pre_nn")(pre_in)
        scal = MLP([F, F, F * 3], activation=jax.nn.silu,
                   name="scalar_message_mlp")(jnp.tanh(msg))
        filt = scal * nn.Dense(F * 3, use_bias=False, name="rbf_lin")(rbf)
        gate_v, gate_e, msg_s = jnp.split(filt, 3, axis=-1)

        msg_v = seg.neighbor_gather(v, send) * gate_v[:, None, :] + \
            gate_e[:, None, :] * edge_vec[:, :, None]
        dv = seg.edge_aggregate_sum(msg_v, batch)

        agg = degree_scaler_aggregation(msg_s, recv, x.shape[0],
                                        batch.edge_mask, self.deg_hist,
                                        batch=batch)
        dx = nn.Dense(F, name="post_nn")(jnp.concatenate([x, agg], axis=-1))
        return x + dx, v + dv


class PNAEqUpdate(nn.Module):
    """reference: PNAEqStack.py:399-446 (same as PAINN update)."""
    node_size: int
    last_layer: bool = False

    @nn.compact
    def __call__(self, x, v):
        F = self.node_size
        Xv = nn.Dense(F, use_bias=False, name="update_X")(v)
        Vv = nn.Dense(F, use_bias=False, name="update_V")(v)
        Vv_norm = jnp.sqrt(jnp.sum(Vv * Vv, axis=1) + 1e-12)
        mult = 2 if self.last_layer else 3
        out = MLP([F, F * mult], activation=jax.nn.silu, name="update_mlp")(
            jnp.concatenate([Vv_norm, x], axis=-1))
        inner = jnp.sum(Xv * Vv, axis=1)
        if self.last_layer:
            a_xv, a_xx = jnp.split(out, 2, axis=-1)
            return x + a_xv * inner + a_xx, v
        a_vv, a_xv, a_xx = jnp.split(out, 3, axis=-1)
        return x + a_xv * inner + a_xx, v + a_vv[:, None, :] * Xv


class PNAEqConv(nn.Module):
    in_dim: int
    out_dim: int
    num_radial: int
    deg_hist: Sequence[int]
    edge_dim: Optional[int]
    last_layer: bool = False

    @nn.compact
    def __call__(self, x, v, batch, cargs):
        x, v = PNAEqMessage(node_size=self.in_dim, num_radial=self.num_radial,
                            deg_hist=self.deg_hist, edge_dim=self.edge_dim,
                            name="message")(
            x, v, batch, cargs["rbf"], cargs["edge_vec"])
        x, v = PNAEqUpdate(node_size=self.in_dim,
                           last_layer=self.last_layer, name="update")(x, v)
        x = nn.Dense(self.out_dim, name="node_embed_0")(x)
        x = jnp.tanh(x)
        x = nn.Dense(self.out_dim, name="node_embed_1")(x)
        if not self.last_layer:
            v = nn.Dense(self.out_dim, use_bias=False, name="vec_embed")(v)
        return x, v


class PNAEqStack(BaseStack):
    """reference: hydragnn/models/PNAEqStack.py:38 (identity feature layers)."""
    use_batch_norm: bool = False

    def conv_args(self, batch):
        vec, dist = edge_vectors(batch.pos, batch.senders, batch.receivers,
                                 batch.edge_shifts)
        norm_diff = vec / dist[:, None]
        rbf = sinc_expansion(dist, float(self.cfg.radius),
                             int(self.cfg.num_radial or 6))
        rbf = rbf * cosine_cutoff(dist, float(self.cfg.radius))[:, None]
        return {"rbf": rbf, "edge_vec": norm_diff}

    def encode(self, batch, cargs, act, train):
        cfg = self.cfg
        x = batch.x
        v = jnp.zeros((x.shape[0], 3, x.shape[-1]), x.dtype)
        in_dim = x.shape[-1]
        for i in range(cfg.num_conv_layers):
            last = i == cfg.num_conv_layers - 1
            conv = PNAEqConv(in_dim=in_dim, out_dim=cfg.hidden_dim,
                             num_radial=int(cfg.num_radial or 6),
                             deg_hist=cfg.pna_deg, edge_dim=cfg.edge_dim,
                             last_layer=last, name=f"conv_{i}")
            x, v = conv(x, v, batch, cargs)
            x = act(x)
            in_dim = cfg.hidden_dim
        # conv-type node heads thread the encoder's final vector channel
        # (reference: PNAEqStack.py forward, node conv branch)
        cargs["vec_channel_encoder"] = v
        return x, batch.pos

    def make_conv(self, in_dim, out_dim, idx, final=False):
        from .base import VecHeadConv
        return VecHeadConv(
            conv=PNAEqConv(in_dim=in_dim, out_dim=out_dim,
                           num_radial=int(self.cfg.num_radial or 6),
                           deg_hist=self.cfg.pna_deg,
                           edge_dim=self.cfg.edge_dim, last_layer=final),
            name=f"conv_{idx}")
