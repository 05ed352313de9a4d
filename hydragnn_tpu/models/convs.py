"""Message-passing convolution layers (invariant family).

TPU-first re-implementations of the PyG convs the reference wraps
(reference: hydragnn/models/{GIN,SAGE,GAT,MFC,CGCNN,PNA}Stack.py). Each is a
flax module with signature ``conv(x, pos, batch, cargs) -> (x, pos)``:
gather node features to edges, apply an edge MLP (one big MXU matmul over
[E, F]), scatter-aggregate with masked segment ops. No dynamic shapes, no
sorting — XLA fuses the gather/matmul/scatter chain.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..ops import segment as seg
from .layers import MLP


class GINConv(nn.Module):
    """x_i' = MLP((1 + eps) x_i + sum_j x_j); eps trainable, init 100
    (reference: hydragnn/models/GINStack.py:26-34)."""
    out_dim: int
    eps_init: float = 100.0

    @nn.compact
    def __call__(self, x, pos, batch, cargs):
        eps = self.param("eps", lambda k: jnp.asarray(self.eps_init, jnp.float32))
        if batch.nbr is not None:
            agg = seg.neighbor_sum(seg.neighbor_gather(x, batch.nbr),
                                   batch.nbr_mask)
        else:
            agg = seg.segment_sum(seg.neighbor_gather(x, batch.senders),
                                  batch.receivers, x.shape[0],
                                  batch.edge_mask)
        h = (1.0 + eps) * x + agg
        h = MLP([self.out_dim, self.out_dim], activation=jax.nn.relu)(h)
        return h, pos


class SAGEConv(nn.Module):
    """x_i' = W_r x_i + W_l mean_j x_j (reference: SAGEStack.py:26)."""
    out_dim: int

    @nn.compact
    def __call__(self, x, pos, batch, cargs):
        if batch.nbr is not None:
            agg = seg.neighbor_mean(seg.neighbor_gather(x, batch.nbr),
                                    batch.nbr_mask)
        else:
            agg = seg.segment_mean(seg.neighbor_gather(x, batch.senders),
                                   batch.receivers, x.shape[0],
                                   batch.edge_mask)
        h = nn.Dense(self.out_dim, name="lin_l")(agg) + \
            nn.Dense(self.out_dim, name="lin_r")(x)
        return h, pos


class GATv2Conv(nn.Module):
    """GATv2 attention conv (reference: GATStack.py:95-120 wraps PyG
    GATv2Conv, heads=6, negative_slope=0.05, concat except final layer)."""
    out_dim: int
    heads: int = 6
    negative_slope: float = 0.05
    concat: bool = True

    @nn.compact
    def __call__(self, x, pos, batch, cargs):
        H, F = self.heads, self.out_dim
        g_l = nn.Dense(H * F, name="lin_l")(x).reshape(-1, H, F)  # target/self
        g_r = nn.Dense(H * F, name="lin_r")(x).reshape(-1, H, F)  # source
        att = self.param("att", nn.initializers.lecun_normal(), (1, H, F))
        use_ea = batch.edge_attr is not None and "edge_attr_dim" in cargs
        if batch.nbr is not None:
            # dense layout: attention softmax is a masked reduction over the
            # K axis — no segment softmax, no scatters
            e = g_l[:, None] + seg.neighbor_gather(g_r, batch.nbr)  # [N,K,H,F]
            if use_ea:
                e = e + seg.edge_gather(nn.Dense(H * F, name="lin_edge")(
                    batch.edge_attr).reshape(-1, H, F), batch)
            e_act = jax.nn.leaky_relu(e, self.negative_slope)
            logits = jnp.sum(e_act * att, axis=-1)                # [N, K, H]
            alpha = seg.neighbor_softmax(logits, batch.nbr_mask)
            out = seg.neighbor_sum(
                seg.neighbor_gather(g_r, batch.nbr) * alpha[..., None],
                batch.nbr_mask)                                   # [N, H, F]
        else:
            e = (seg.neighbor_gather(g_l, batch.receivers)
                 + seg.neighbor_gather(g_r, batch.senders))       # [E, H, F]
            if use_ea:
                e = e + nn.Dense(H * F, name="lin_edge")(
                    batch.edge_attr).reshape(-1, H, F)
            e_act = jax.nn.leaky_relu(e, self.negative_slope)
            logits = jnp.sum(e_act * att, axis=-1)                # [E, H]
            alpha = seg.segment_softmax(logits, batch.receivers, x.shape[0],
                                        batch.edge_mask)
            msgs = seg.neighbor_gather(g_r, batch.senders) * alpha[..., None]
            out = seg.segment_sum(msgs, batch.receivers, x.shape[0],
                                  batch.edge_mask)
        if self.concat:
            out = out.reshape(-1, H * F)
        else:
            out = jnp.mean(out, axis=1)
        return out, pos


class MFConv(nn.Module):
    """Molecular-fingerprint conv with degree-specific weights
    (reference: MFCStack.py:33 wraps PyG MFConv, max_degree=max_neighbours).

    Weight banks [max_degree+1, in, out] gathered by clamped node degree —
    one batched einsum instead of PyG's per-degree Python loop."""
    out_dim: int
    max_degree: int = 10

    @nn.compact
    def __call__(self, x, pos, batch, cargs):
        n, fin = x.shape
        d = self.max_degree + 1
        if batch.nbr is not None:
            agg = seg.neighbor_sum(seg.neighbor_gather(x, batch.nbr),
                                   batch.nbr_mask)
            deg = jnp.sum(batch.nbr_mask, axis=1)
        else:
            agg = seg.segment_sum(seg.neighbor_gather(x, batch.senders),
                                  batch.receivers, n, batch.edge_mask)
            deg = seg.degree(batch.receivers, n, batch.edge_mask)
        deg = jnp.clip(deg.astype(jnp.int32), 0, self.max_degree)
        w_l = self.param("w_l", nn.initializers.lecun_normal(), (d, fin, self.out_dim))
        b_l = self.param("b_l", nn.initializers.zeros, (d, self.out_dim))
        w_r = self.param("w_r", nn.initializers.lecun_normal(), (d, fin, self.out_dim))
        b_r = self.param("b_r", nn.initializers.zeros, (d, self.out_dim))
        out = (jnp.einsum("ni,nio->no", agg, w_l[deg]) + b_l[deg]
               + jnp.einsum("ni,nio->no", x, w_r[deg]) + b_r[deg])
        return out, pos


class CGConv(nn.Module):
    """Crystal-graph conv: x_i' = x_i + sum_j sigmoid(W_f z) * softplus(W_s z),
    z = [x_i, x_j, e_ij] (reference: CGCNNStack.py:43 wraps PyG CGConv;
    hidden dim is forced equal to input dim, CGCNNStack.py:25-31)."""
    out_dim: int

    @nn.compact
    def __call__(self, x, pos, batch, cargs):
        ea = cargs.get("edge_attr", batch.edge_attr)
        if batch.nbr is not None:
            k = batch.nbr.shape[1]
            xi = jnp.broadcast_to(x[:, None], (x.shape[0], k, x.shape[-1]))
            parts = [xi, seg.neighbor_gather(x, batch.nbr)]
            if ea is not None:
                parts.append(seg.edge_gather(ea, batch))
            z = jnp.concatenate(parts, axis=-1)                  # [N, K, ·]
            gate = jax.nn.sigmoid(nn.Dense(x.shape[-1], name="lin_f")(z))
            core = jax.nn.softplus(nn.Dense(x.shape[-1], name="lin_s")(z))
            agg = seg.neighbor_sum(gate * core, batch.nbr_mask)
        else:
            z = jnp.concatenate([seg.neighbor_gather(x, batch.receivers),
                                 seg.neighbor_gather(x, batch.senders)],
                                axis=-1)
            if ea is not None:
                z = jnp.concatenate([z, ea], axis=-1)
            gate = jax.nn.sigmoid(nn.Dense(x.shape[-1], name="lin_f")(z))
            core = jax.nn.softplus(nn.Dense(x.shape[-1], name="lin_s")(z))
            agg = seg.segment_sum(gate * core, batch.receivers, x.shape[0],
                                  batch.edge_mask)
        return x + agg, pos


def pna_degree_stats(deg_hist: Sequence[int]):
    """avg linear/log degree from the training degree histogram
    (PyG PNAConv.avg_deg; histogram from reference config completion
    config_utils.py:48-56)."""
    hist = np.asarray(deg_hist, dtype=np.float64)
    total = max(hist.sum(), 1.0)
    degs = np.arange(len(hist))
    avg_lin = float((hist * degs).sum() / total)
    avg_log = float((hist * np.log(degs + 1)).sum() / total)
    return max(avg_lin, 1e-6), max(avg_log, 1e-6)


class PNAConv(nn.Module):
    """Principal Neighbourhood Aggregation conv
    (reference: PNAStack.py:41-66 wraps PyG PNAConv with aggregators
    mean/min/max/std and scalers identity/amplification/attenuation/linear,
    pre_layers=1, post_layers=1, divide_input=False).

    `rbf_dim > 0` adds the PNAPlus Bessel radial embedding injected into each
    message (reference: PNAPlusStack.py:122-264)."""
    out_dim: int
    deg_hist: Sequence[int]
    edge_dim: Optional[int] = None
    rbf: bool = False

    @nn.compact
    def __call__(self, x, pos, batch, cargs):
        n, fin = x.shape
        # the message pre-layer Dense([x_i || x_j || ...]) factors into
        # per-node projections gathered per edge: W@concat = Wi@x_i + Wj@x_j
        # + ... — this moves the dominant matmul from [E, 2F] to two [N, F]
        # operands (E ~ 30N for radius graphs), leaving only adds per edge
        proj_i = nn.Dense(fin, name="pre_i")(x)           # carries the bias
        proj_j = nn.Dense(fin, use_bias=False, name="pre_j")(x)
        ea = cargs.get("edge_attr", batch.edge_attr)

        def edge_terms(h):
            """Add the per-edge encoder terms to the messages `h`. Each is
            encoded in the order its input arrives in: one already laid out
            like `h` (the edge list; slot order `[N, K, .]` from
            `PNAPlusStack.conv_args` on the dense layout) is added as it
            stands, an edge-order one on the dense layout (`edge_attr`)
            goes through the `nbr_edge` gather."""
            def like_h(ev):
                return ev if ev.ndim == h.ndim else seg.edge_gather(ev, batch)

            if self.edge_dim:
                enc = nn.Dense(fin, name="edge_encoder")(ea)
                h = h + like_h(nn.Dense(fin, use_bias=False,
                                        name="edge_proj")(enc))
            if self.rbf:
                enc = nn.Dense(fin, name="rbf_encoder")(cargs["rbf"])
                h = h + like_h(nn.Dense(fin, use_bias=False,
                                        name="rbf_proj")(enc))
            return h

        if batch.nbr is not None:
            # dense neighbor-list layout: [N, K, F] messages, axis-1
            # reductions, no scatter in the forward pass
            # (with_neighbor_format)
            h = proj_i[:, None, :] + seg.neighbor_gather(proj_j, batch.nbr)
            h = edge_terms(h)
            mean, mn, mx, sd, deg = seg.neighbor_aggregate(h, batch.nbr_mask)
        else:
            h = (seg.neighbor_gather(proj_i, batch.receivers)
                 + seg.neighbor_gather(proj_j, batch.senders))
            h = edge_terms(h)
            mean, mn, mx, sd, deg = seg.pna_aggregate(
                h, batch.receivers, n, batch.edge_mask)
        aggs = jnp.concatenate([mean, mn, mx, sd], axis=-1)      # [N, 4F]

        avg_lin, avg_log = pna_degree_stats(self.deg_hist)
        logd = jnp.log(deg + 1.0)
        amp = (logd / avg_log)[:, None]
        att = (avg_log / jnp.maximum(logd, 1e-6))[:, None]
        lin = (deg / avg_lin)[:, None]
        scaled = jnp.concatenate(
            [aggs, aggs * amp, aggs * att, aggs * lin], axis=-1)  # [N, 16F]
        out = nn.Dense(self.out_dim, name="post_nn")(scaled)
        out = nn.Dense(self.out_dim, name="lin")(out)
        return out, pos
