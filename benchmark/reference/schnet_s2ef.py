"""Plain reference for the `schnet-s2ef` configuration.

Written from ``models/schnet.py`` (``SCFStack``/``CFConv``) as an edge-list
computation. The interaction follows SchNet (Schuett et al. 2017,
arXiv:1706.08566; PyG ``CFConv`` + ``InteractionBlock``): Gaussian smearing
of the distance, a two-layer filter network with shifted softplus, a cosine
cutoff, lin1 -> filter-weighted neighbour sum -> lin2 -> ssp -> lin.
Departures of the system's stack from OCP's SchNet module, kept as they are:

* no atom-type embedding: the first interaction reads the 1-wide node
  feature, so its lin1 is 1 -> num_filters;
* no residual connection: x <- ReLU(BatchNorm(interaction(x)));
* BatchNorm over the real atoms after every interaction;
* the read-out is the stack's MLP node head, summed per structure.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common


def ssp(x):
    return jax.nn.softplus(x) - np.log(2.0)


def cf_conv(p, x, d, struct, cutoff: float, num_gaussians: int):
    mu = jnp.linspace(0.0, cutoff, num_gaussians, dtype=d.dtype)
    gamma = 0.5 / (mu[1] - mu[0]) ** 2
    rbf = jnp.exp(-gamma * (d[:, None] - mu) ** 2)
    envelope = jnp.where(d <= cutoff,
                         0.5 * (jnp.cos(d * np.pi / cutoff) + 1.0), 0.0)
    w = common.mlp(p["filter_nn"], rbf, ssp) * envelope[:, None]
    h = common.dense(p["lin1"], x)
    h = jax.ops.segment_sum(h[struct["senders"]] * w, struct["receivers"],
                            x.shape[0])
    return common.dense(p["lin_out"], ssp(common.dense(p["lin2"], h)))


def node_energies(arch):
    """arch: the completed Architecture dict (radius, num_gaussians,
    num_conv_layers)."""
    def fn(variables, struct, pos, train):
        params, stats = variables["params"], variables.get("batch_stats", {})
        d = common.edge_lengths(pos, struct)
        x = jnp.asarray(struct["x"])
        for i in range(int(arch["num_conv_layers"])):
            x = cf_conv(params[f"conv_{i}"], x, d, struct,
                        float(arch["radius"]), int(arch["num_gaussians"]))
            x = jax.nn.relu(common.batch_norm(
                params[f"feature_norm_{i}"], stats[f"feature_norm_{i}"],
                x, train))
        return common.mlp(params["head_0"]["MLP_0"], x, jax.nn.relu)[:, 0]
    return fn
