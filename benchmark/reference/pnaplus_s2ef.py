"""Plain reference for the `pnaplus-s2ef` configuration.

Written from ``models/stacks.PNAPlusStack`` and ``models/convs.PNAConv`` as
an edge-list computation. It states this repo's PNAPlus; where that differs
from upstream (PyG ``PNAConv`` as wrapped by hydragnn/models/PNAPlusStack.py)
the difference is the system's and is noted here, not repaired:

* the message pre-layer is ONE linear layer on [x_i || x_j || rbf-embedding]
  (upstream ``pre_layers=1``, ``towers=1``), written as three matmuls summed;
* the post-layer sees the scaled aggregates only — upstream concatenates
  x_i in front of them ([x_i || aggregates] -> post_nn);
* 'std' is sqrt(max(E[h^2] - E[h]^2, 0) + 1e-5), and an atom with no
  neighbour gets min = max = mean = 0;
* the attenuation scaler is avg_log / max(log(deg + 1), 1e-6);
* every conv is followed by BatchNorm over the real atoms and ReLU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import common


def bessel_rbf(d, cutoff: float, num_radial: int, exponent: int):
    """DimeNet's Bessel basis with its polynomial envelope (ops/basis.py)."""
    p = exponent + 1
    a, b, c = -(p + 1) * (p + 2) / 2.0, p * (p + 2.0), -p * (p + 1) / 2.0
    x = d / cutoff
    xp = x ** (p - 1)
    env = 1.0 / jnp.maximum(x, 1e-9) + a * xp + b * xp * x + c * xp * x * x
    freq = jnp.arange(1, num_radial + 1, dtype=d.dtype) * np.pi
    return env[:, None] * jnp.sin(freq * x[:, None])


def degree_scalers(deg_hist):
    hist = np.asarray(deg_hist, np.float64)
    degs = np.arange(len(hist))
    total = max(hist.sum(), 1.0)
    return (max(float((hist * degs).sum() / total), 1e-6),
            max(float((hist * np.log(degs + 1)).sum() / total), 1e-6))


def pna_conv(p, x, rbf, struct, deg_hist):
    n = x.shape[0]
    send, recv = struct["senders"], struct["receivers"]
    msg = (common.dense(p["pre_i"], x)[recv]
           + common.dense(p["pre_j"], x)[send]
           + common.dense(p["rbf_proj"], common.dense(p["rbf_encoder"], rbf)))
    deg = jax.ops.segment_sum(jnp.ones(msg.shape[0], msg.dtype), recv, n)
    cnt = jnp.maximum(deg, 1.0)[:, None]
    has = (deg > 0)[:, None]
    mean = jax.ops.segment_sum(msg, recv, n) / cnt
    sq_mean = jax.ops.segment_sum(msg * msg, recv, n) / cnt
    std = jnp.sqrt(jnp.maximum(sq_mean - mean * mean, 0.0) + 1e-5)
    mn = jnp.where(has, jax.ops.segment_min(msg, recv, n), 0.0)
    mx = jnp.where(has, jax.ops.segment_max(msg, recv, n), 0.0)
    aggs = jnp.concatenate([mean, mn, mx, std], axis=-1)
    avg_lin, avg_log = degree_scalers(deg_hist)
    logd = jnp.log(deg + 1.0)
    scaled = jnp.concatenate([
        aggs, aggs * (logd / avg_log)[:, None],
        aggs * (avg_log / jnp.maximum(logd, 1e-6))[:, None],
        aggs * (deg / avg_lin)[:, None]], axis=-1)
    return common.dense(p["lin"], common.dense(p["post_nn"], scaled))


def node_energies(arch):
    """arch: the completed Architecture dict (radius, num_radial,
    envelope_exponent, num_conv_layers, pna_deg)."""
    def fn(variables, struct, pos, train):
        params, stats = variables["params"], variables.get("batch_stats", {})
        rbf = bessel_rbf(common.edge_lengths(pos, struct),
                         float(arch["radius"]), int(arch["num_radial"]),
                         int(arch["envelope_exponent"]))
        x = jnp.asarray(struct["x"])
        for i in range(int(arch["num_conv_layers"])):
            x = pna_conv(params[f"conv_{i}"], x, rbf, struct,
                         arch["pna_deg"])
            x = jax.nn.relu(common.batch_norm(
                params[f"feature_norm_{i}"], stats[f"feature_norm_{i}"],
                x, train))
        return common.mlp(params["head_0"]["MLP_0"], x, jax.nn.relu)[:, 0]
    return fn
