"""PAINN stack — polarizable atom interaction network with scalar + vector
node channels.

reference: hydragnn/models/PAINNStack.py:25-311 (PainnMessage :177-230,
PainnUpdate :233-286, sinc radial + cosine cutoff :288-306, custom forward
threading the vector channel v :104-151).

Design notes (TPU): the vector channel is a [N, 3, F] array; all ops are
channel-last matmuls (MXU) with the spatial axis broadcast. The vector
embedding between layers is bias-free (a bias on a Cartesian vector channel
would break E(3) equivariance; the reference uses a default Linear there).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import segment as seg
from ..ops.basis import cosine_cutoff, sinc_expansion
from ..ops.geometry import edge_vectors
from .base import BaseStack
from .layers import MLP


class PainnMessage(nn.Module):
    """reference: PAINNStack.py:177-230."""
    node_size: int
    edge_size: int
    cutoff: float

    @nn.compact
    def __call__(self, s, v, batch, norm_diff, dist):
        send, recv = batch.senders, batch.receivers
        F = self.node_size
        rbf = sinc_expansion(dist, self.cutoff, self.edge_size)
        W = nn.Dense(F * 3, name="filter_layer")(rbf)
        W = W * cosine_cutoff(dist, self.cutoff)[:, None]
        scal = MLP([F, F * 3], activation=jax.nn.silu,
                   name="scalar_message_mlp")(s)
        filt = W * seg.neighbor_gather(scal, send)
        gate_v, gate_e, msg_s = jnp.split(filt, 3, axis=-1)
        # the reference divides the (already normalized) direction by dist
        # again (PAINNStack.py:214-217) — kept for behavioral parity
        direction = norm_diff / jnp.maximum(dist, 1e-9)[:, None]
        msg_v = seg.neighbor_gather(v, send) * gate_v[:, None, :] + \
            gate_e[:, None, :] * direction[:, :, None]
        ds = seg.edge_aggregate_sum(msg_s, batch)
        dv = seg.edge_aggregate_sum(msg_v, batch)
        return s + ds, v + dv


class PainnUpdate(nn.Module):
    """reference: PAINNStack.py:233-286."""
    node_size: int
    last_layer: bool = False

    @nn.compact
    def __call__(self, s, v):
        F = self.node_size
        Uv = nn.Dense(F, use_bias=False, name="update_U")(v)
        Vv = nn.Dense(F, use_bias=False, name="update_V")(v)
        Vv_norm = jnp.sqrt(jnp.sum(Vv * Vv, axis=1) + 1e-12)
        out_mult = 3 if not self.last_layer else 2
        mlp_out = MLP([F, F * out_mult], activation=jax.nn.silu,
                      name="update_mlp")(
            jnp.concatenate([Vv_norm, s], axis=-1))
        inner = jnp.sum(Uv * Vv, axis=1)
        if not self.last_layer:
            a_vv, a_sv, a_ss = jnp.split(mlp_out, 3, axis=-1)
            new_s = s + a_sv * inner + a_ss
            new_v = v + a_vv[:, None, :] * Uv
            return new_s, new_v
        a_sv, a_ss = jnp.split(mlp_out, 2, axis=-1)
        return s + a_sv * inner + a_ss, v


class PainnConv(nn.Module):
    """Message + update + re-embedding (reference: get_conv,
    PAINNStack.py:55-102 — Tanh node embed to prevent exploding gradients,
    noted there)."""
    in_dim: int
    out_dim: int
    num_radial: int
    cutoff: float
    last_layer: bool = False

    @nn.compact
    def __call__(self, s, v, batch, cargs):
        s, v = PainnMessage(node_size=self.in_dim, edge_size=self.num_radial,
                            cutoff=self.cutoff, name="message")(
            s, v, batch, cargs["norm_diff"], cargs["dist"])
        s, v = PainnUpdate(node_size=self.in_dim, last_layer=self.last_layer,
                           name="update")(s, v)
        s = nn.Dense(self.out_dim, name="node_embed_0")(s)
        s = jnp.tanh(s)
        s = nn.Dense(self.out_dim, name="node_embed_1")(s)
        if not self.last_layer:
            v = nn.Dense(self.out_dim, use_bias=False, name="vec_embed")(v)
        return s, v


class PAINNStack(BaseStack):
    """reference: hydragnn/models/PAINNStack.py:25 (identity feature layers)."""
    use_batch_norm: bool = False

    def conv_args(self, batch):
        vec, dist = edge_vectors(batch.pos, batch.senders, batch.receivers,
                                 batch.edge_shifts)
        norm_diff = vec / dist[:, None]
        return {"norm_diff": norm_diff, "dist": dist}

    def encode(self, batch, cargs, act, train):
        cfg = self.cfg
        x = batch.x
        n = x.shape[0]
        v = jnp.zeros((n, 3, x.shape[-1]), x.dtype)
        in_dim = x.shape[-1]
        for i in range(cfg.num_conv_layers):
            last = i == cfg.num_conv_layers - 1
            conv = PainnConv(in_dim=in_dim, out_dim=cfg.hidden_dim,
                             num_radial=int(cfg.num_radial or 6),
                             cutoff=float(cfg.radius), last_layer=last,
                             name=f"conv_{i}")
            x, v = conv(x, v, batch, cargs)
            x = act(x)
            in_dim = cfg.hidden_dim
        # conv-type node heads thread the encoder's final vector channel
        # (reference: PAINNStack.py:139-145 forward, node conv branch)
        cargs["vec_channel_encoder"] = v
        return x, batch.pos

    def make_conv(self, in_dim, out_dim, idx, final=False):
        from .base import VecHeadConv
        return VecHeadConv(
            conv=PainnConv(in_dim=in_dim, out_dim=out_dim,
                           num_radial=int(self.cfg.num_radial or 6),
                           cutoff=float(self.cfg.radius), last_layer=final),
            name=f"conv_{idx}")
