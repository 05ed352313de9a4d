"""Plain reference for the `dimenetpp-s2ef` configuration.

Written from ``models/dimenet.py`` (``DIMEStack`` / ``DimeNetConv``, after
hydragnn/models/DIMEStack.py, SURVEY.md:81) as a computation on the REAL
edge list: directional message passing of DimeNet++ (Gasteiger et al. 2020,
arXiv:2011.14115; PyG ``InteractionPPBlock`` / ``OutputPPBlock``). Messages
live on edges; edge (j->i) gathers from every edge (k->j) with k != i,
weighted by a basis of the distance d_kj and the angle at j.

The harness jits this over a structure's arrays, so the edge pairs are
enumerated in traced ``jax.numpy`` by a route of this file's own: the edges
are sorted by receiver, ranked within their receiver's group and written
into an [atoms, max_neighbours] table of edge ids; the pairs of edge e2 =
(j->i) are then the row of j = sender(e2). (The system derives them from its
padded [N, K] slot layout, or on the host with a per-edge loop.) The
spherical Bessel functions, their zeros, the Legendre polynomials and the
envelope are this file's code as well.

``departures``: where the system's stack differs from arXiv:2011.14115 and
from OCP's ``DimeNetPlusPlusWrap``, the difference is the system's and is
noted here, not repaired:

* edge embeddings are REBUILT from node features in every conv (lin ->
  embedding -> interaction -> output -> node features); the paper embeds
  once and carries the edge messages from block to block;
* ONE output layer per block (``lin_0``): the published 3 are not a key of
  the stack;
* node features (the 1-wide atomic number) through a Dense layer in place
  of an atomic-number embedding table;
* the read-out is HydraGNN's conv-type node head, not the sum of the
  blocks' outputs: every layer of the head is one more block of the same
  kind (the configuration has one, the third of its three), followed by
  BatchNorm over the real atoms of the batch and ReLU, then one linear
  layer to the atom's energy, summed per structure;
* every encoder conv is followed by the stack's activation (ReLU), without
  BatchNorm (identity feature layers, as upstream): the head's is the
  model's only one;
* the envelope has no (x < 1) cut: no edge is longer than the cutoff.

j_l(x) in float32: the closed forms (and the upward recurrence that makes
them) cancel to nothing below x ~ l (at l = 6, d = 1 A: a fifth of the
value), so below x = l + 1.5 the ascending series is summed and above it the
closed form sin(x) A_l(1/x) + cos(x) B_l(1/x), whose coefficients are made
exactly, in integers, by the recurrence on polynomials.
"""
from __future__ import annotations

import functools
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np

from . import common

SERIES_TERMS = 14


# ----------------------------------------------------------- the bases ---

@functools.lru_cache(maxsize=None)
def closed_form(l: int):
    """(A_l, B_l): coefficient lists in t = 1/x, lowest power first, with
    j_l(x) = sin(x) A_l(t) + cos(x) B_l(t). From j_0 = sin(x) t,
    j_1 = sin(x) t^2 - cos(x) t and j_{k+1} = (2k+1) t j_k - j_{k-1}."""
    def times_t(poly, factor):
        return [Fraction(0)] + [factor * c for c in poly]

    def minus(a, b):
        n = max(len(a), len(b))
        a = a + [Fraction(0)] * (n - len(a))
        b = b + [Fraction(0)] * (n - len(b))
        return [x - y for x, y in zip(a, b)]
    one = Fraction(1)
    sin = [[Fraction(0), one], [Fraction(0), Fraction(0), one]]
    cos = [[Fraction(0)], [Fraction(0), -one]]
    for k in range(1, l):
        sin.append(minus(times_t(sin[k], 2 * k + 1), sin[k - 1]))
        cos.append(minus(times_t(cos[k], 2 * k + 1), cos[k - 1]))
    return ([float(c) for c in sin[l]], [float(c) for c in cos[l]])


def polynomial(coefficients, t):
    out = jnp.zeros_like(t)
    for c in reversed(coefficients):
        out = out * t + c
    return out


def spherical_bessel(l: int, x):
    """j_l(x), x > 0."""
    switch = l + 1.5
    low = x < switch
    big = jnp.where(low, switch, x)
    a, b = closed_form(l)
    t = 1.0 / big
    closed = jnp.sin(big) * polynomial(a, t) + jnp.cos(big) * polynomial(b, t)
    small = jnp.where(low, x, switch)
    half_sq = 0.5 * small * small
    term = jnp.ones_like(small)
    total = term
    for k in range(1, SERIES_TERMS + 1):
        term = -term * half_sq / (k * (2 * l + 2 * k + 1))
        total = total + term
    odd_product = float(np.prod([2 * i + 1 for i in range(l + 1)]))
    return jnp.where(low, small ** l / odd_product * total, closed)


@functools.lru_cache(maxsize=None)
def bessel_zeros(num_l: int, num_n: int) -> np.ndarray:
    """zeros[l, n]: the (n+1)-th positive zero of j_l, by bisection in
    float64 between the zeros of j_{l-1}, which interlace them (those of
    j_0 are the multiples of pi)."""
    def j(l, x):
        a, b = closed_form(l)
        t = 1.0 / x
        return (np.sin(x) * np.polyval(a[::-1], t)
                + np.cos(x) * np.polyval(b[::-1], t))
    brackets = np.arange(1, num_n + num_l + 1) * np.pi
    out = np.zeros((num_l, num_n))
    out[0] = brackets[:num_n]
    for l in range(1, num_l):
        found = []
        for lo, hi in zip(brackets[:-1], brackets[1:]):
            f_lo = j(l, lo)
            for _ in range(200):
                mid = 0.5 * (lo + hi)
                if (j(l, mid) > 0) == (f_lo > 0):
                    lo = mid
                else:
                    hi = mid
            found.append(0.5 * (lo + hi))
        brackets = np.asarray(found)
        out[l] = brackets[:num_n]
    return out


def legendre_polynomials(l_max: int, c):
    """[P_0(c) .. P_{l_max}(c)] by Bonnet's recurrence."""
    out = [jnp.ones_like(c), c]
    for l in range(1, l_max):
        out.append(((2 * l + 1) * c * out[l] - l * out[l - 1]) / (l + 1))
    return out[:l_max + 1]


def envelope(x, exponent: int):
    """u(x) = 1/x + a x^(p-1) + b x^p + c x^(p+1), p = exponent + 1."""
    p = exponent + 1
    a, b, c = -(p + 1) * (p + 2) / 2.0, p * (p + 2.0), -p * (p + 1) / 2.0
    return 1.0 / x + x ** (p - 1) * (a + x * (b + x * c))


def radial_basis(d, cutoff: float, num_radial: int, exponent: int):
    x = d / cutoff
    n = jnp.arange(1, num_radial + 1, dtype=d.dtype)
    return envelope(x, exponent)[:, None] * jnp.sin(np.pi * n * x[:, None])


def spherical_basis(d, cos_angle, cutoff: float, num_spherical: int,
                    num_radial: int, exponent: int):
    """[pairs, num_spherical * num_radial], index l * num_radial + n:
    u(d/c) j_l(z_ln d/c) / |j_{l+1}(z_ln)| sqrt((2l+1)/4pi) P_l(cos)."""
    zeros = bessel_zeros(num_spherical, num_radial)
    x = d / cutoff
    u = envelope(x, exponent)
    p = legendre_polynomials(num_spherical - 1, cos_angle)
    columns = []
    for l in range(num_spherical):
        a, b = closed_form(l + 1)
        t = 1.0 / zeros[l]
        scale = 1.0 / np.abs(np.sin(zeros[l]) * np.polyval(a[::-1], t)
                             + np.cos(zeros[l]) * np.polyval(b[::-1], t))
        angular = np.sqrt((2 * l + 1) / (4 * np.pi)) * p[l]
        for n in range(num_radial):
            columns.append(u * spherical_bessel(l, zeros[l, n] * x)
                           * (scale[n] * angular))
    return jnp.stack(columns, axis=-1)


# ---------------------------------------------------------- the pairs ---

def edge_pairs(struct, num_atoms: int, max_neighbours: int):
    """(kj, ji, real): for every edge e2 and every rank r, the r-th edge
    INTO e2's sender; `real` where that edge exists and does not come from
    e2's receiver. All three are [edges * max_neighbours]."""
    send, recv = struct["senders"], struct["receivers"]
    edges = send.shape[0]
    order = jnp.argsort(recv, stable=True)
    sorted_recv = recv[order]
    first = jnp.searchsorted(sorted_recv, jnp.arange(num_atoms))
    rank = jnp.arange(edges) - first[sorted_recv]
    table = jnp.full((num_atoms, max_neighbours), -1, order.dtype)
    table = table.at[sorted_recv, rank].set(order)
    kj = table[send]                                  # [edges, max_nb]
    ji = jnp.broadcast_to(jnp.arange(edges)[:, None], kj.shape)
    exists = kj >= 0
    kj = jnp.where(exists, kj, 0)
    real = exists & (send[kj] != recv[:, None])
    return kj.reshape(-1), ji.reshape(-1), real.reshape(-1)


# ---------------------------------------------------------- the blocks ---

def silu(x):
    return x * jax.nn.sigmoid(x)


def embedding(p, x, rbf, struct):
    rbf_emb = silu(common.dense(p["lin_rbf"], rbf))
    both = jnp.concatenate([x[struct["senders"]], x[struct["receivers"]],
                            rbf_emb], axis=-1)
    return silu(common.dense(p["lin"], both))


def interaction(p, e, rbf, sbf, pairs, num_before: int, num_after: int):
    kj, ji, real = pairs
    x_ji = silu(common.dense(p["lin_ji"], e))
    x_kj = silu(common.dense(p["lin_kj"], e))
    x_kj = x_kj * common.dense(p["lin_rbf2"], common.dense(p["lin_rbf1"],
                                                           rbf))
    x_kj = silu(common.dense(p["lin_down"], x_kj))
    sbf_e = common.dense(p["lin_sbf2"], common.dense(p["lin_sbf1"], sbf))
    messages = jnp.where(real[:, None], x_kj[kj] * sbf_e, 0.0)
    x_kj = jax.ops.segment_sum(messages, ji, e.shape[0])
    h = x_ji + silu(common.dense(p["lin_up"], x_kj))
    for i in range(num_before):
        h = silu(common.dense(p[f"before_skip_{i}"], h))
    h = silu(common.dense(p["lin_skip"], h)) + e
    for i in range(num_after):
        h = silu(common.dense(p[f"after_skip_{i}"], h))
    return h


def output(p, e, rbf, struct, num_atoms: int):
    x = jax.ops.segment_sum(common.dense(p["lin_rbf"], rbf) * e,
                            struct["receivers"], num_atoms)
    x = silu(common.dense(p["lin_0"], common.dense(p["lin_up"], x)))
    return common.dense(p["lin_out"], x)


def block_on(arch, struct, pos):
    """block(p, x): one DimeNet++ block (lin -> embedding -> interaction ->
    output) on the structures at `pos`, node features in, node features
    out. The edge vectors, the pair space and both bases are made here,
    once, and every block of the model reads them. arch: the completed
    Architecture dict (radius, num_radial, num_spherical,
    envelope_exponent, num_before_skip, num_after_skip, max_neighbours)."""
    cutoff = float(arch["radius"])
    radial, spherical = int(arch["num_radial"]), int(arch["num_spherical"])
    exponent = int(arch["envelope_exponent"])
    send, recv = struct["senders"], struct["receivers"]
    vec = pos[send] - pos[recv] + struct["shifts"]
    d = jnp.sqrt(jnp.sum(vec * vec, axis=-1))
    pairs = edge_pairs(struct, pos.shape[0], int(arch["max_neighbours"]))
    kj, ji, real = pairs
    # angle at j between (pos_i - pos_j) = -vec[ji], (pos_k - pos_j)
    cos = -jnp.sum(vec[ji] * vec[kj], axis=-1) / (d[ji] * d[kj])
    rbf = radial_basis(d, cutoff, radial, exponent)
    sbf = spherical_basis(d[kj], cos, cutoff, spherical, radial, exponent)

    def block(p, x):
        x = common.dense(p["lin"], x)
        e = embedding(p["emb"], x, rbf, struct)
        e = interaction(p["interaction"], e, rbf, sbf, pairs,
                        int(arch["num_before_skip"]),
                        int(arch["num_after_skip"]))
        return output(p["output"], e, rbf, struct, pos.shape[0])
    return block


def node_energies(arch):
    """arch: the completed Architecture dict (`block_on`'s keys,
    num_conv_layers, output_heads)."""
    def fn(variables, struct, pos, train):
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        block = block_on(arch, struct, pos)
        x = jnp.asarray(struct["x"])
        depth = int(arch["num_conv_layers"])
        for i in range(depth):
            x = jax.nn.relu(block(params[f"conv_{i}"], x))
        for i in range(len(arch["output_heads"]["node"]["dim_headlayers"])):
            x = jax.nn.relu(common.batch_norm(
                params[f"head_0_norm_{i}"], stats[f"head_0_norm_{i}"],
                block(params[f"conv_{depth + i}"], x), train))
        return common.dense(params["head_0_out"], x)[:, 0]
    return fn
