"""Radial basis expansions, cutoffs, and distance transforms.

Covers the reference's radial machinery:
- BesselBasisLayer + Envelope (reference: hydragnn/models/PNAPlusStack.py:66-120,
  torch_geometric DimeNet bases used at hydragnn/models/DIMEStack.py:65)
- GaussianSmearing (reference: hydragnn/models/SCFStack.py:53, PyG schnet)
- sinc radial + cosine cutoff (reference: hydragnn/models/PAINNStack.py:288-306)
- MACE radial suite: Bessel / Chebyshev / Gaussian bases, polynomial cutoff,
  Agnesi and Soft distance transforms
  (reference: hydragnn/models/mace_utils/modules/radial.py:23,66,94,118,151,204)

All are pure jnp functions of distance arrays — shape-polymorphic ([E] in
edge order, [N, K] in slot order: `ops/geometry.edge_lengths`), mask-free: a
padding edge has the distance sqrt(1e-9) and a padding slot the length 1
(`ops/geometry.slot_vectors`), both finite in every basis here and in its
derivative; masking happens at aggregation time.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def envelope(x, exponent: int = 5):
    """DimeNet smooth polynomial envelope u(x) on x = d/cutoff in [0, 1]."""
    p = exponent + 1
    a = -(p + 1) * (p + 2) / 2
    b = p * (p + 2)
    c = -p * (p + 1) / 2
    xp = jnp.power(x, p - 1)
    return (1.0 / jnp.maximum(x, 1e-9) + a * xp + b * xp * x + c * xp * x * x)


def bessel_basis(d, cutoff: float, num_radial: int, envelope_exponent: int = 5):
    """Bessel RBF with envelope: env(d/c) * sin(n pi d / c)."""
    freq = jnp.arange(1, num_radial + 1, dtype=d.dtype) * np.pi
    x = d / cutoff
    env = envelope(x, envelope_exponent)
    return env[..., None] * jnp.sin(freq * x[..., None])


def bessel_basis_mace(d, cutoff: float, num_basis: int = 8):
    """MACE's normalized e0 Bessel basis: sqrt(2/c) * sin(n pi d/c) / d."""
    freq = jnp.arange(1, num_basis + 1, dtype=d.dtype) * (np.pi / cutoff)
    safe_d = jnp.maximum(d, 1e-9)
    prefac = np.sqrt(2.0 / cutoff)
    return prefac * jnp.sin(freq * safe_d[..., None]) / safe_d[..., None]


def gaussian_basis(d, start: float, stop: float, num_gaussians: int):
    """SchNet GaussianSmearing: exp(-gamma (d - mu_k)^2)."""
    mu = jnp.linspace(start, stop, num_gaussians, dtype=d.dtype)
    gamma = 0.5 / ((mu[1] - mu[0]) ** 2) if num_gaussians > 1 else 1.0
    diff = d[..., None] - mu
    return jnp.exp(-gamma * diff * diff)


def gaussian_basis_mace(d, cutoff: float, num_basis: int = 8):
    """MACE GaussianBasis: centers in [0, cutoff]."""
    return gaussian_basis(d, 0.0, cutoff, num_basis)


def chebyshev_basis(d, cutoff: float, num_basis: int = 8):
    """MACE ChebychevBasis: T_n(2d/c - 1) for n = 1..num_basis.

    Uses the T_{n+1} = 2x T_n - T_{n-1} recurrence rather than
    cos(n*arccos(x)): arccos has an infinite derivative at x = +-1, which
    poisons force gradients for edges at d = 0 or d = cutoff; the
    polynomial recurrence is smooth everywhere.
    """
    x = jnp.clip(2.0 * d / cutoff - 1.0, -1.0, 1.0)
    t_prev = jnp.ones_like(x)  # T_0
    t_cur = x                  # T_1
    out = [t_cur]
    for _ in range(num_basis - 1):
        t_prev, t_cur = t_cur, 2.0 * x * t_cur - t_prev
        out.append(t_cur)
    return jnp.stack(out, axis=-1)


def cosine_cutoff(d, cutoff: float):
    """PAINN cosine cutoff: 0.5 (cos(pi d/c) + 1), zero beyond c."""
    out = 0.5 * (jnp.cos(np.pi * d / cutoff) + 1.0)
    return jnp.where(d < cutoff, out, 0.0)


def sinc_expansion(d, cutoff: float, num_basis: int):
    """PAINN sinc radial: sin(n pi d / c) / d (reference: PAINNStack.py:288-297)."""
    n = jnp.arange(1, num_basis + 1, dtype=d.dtype)
    safe_d = jnp.maximum(d, 1e-9)
    return jnp.sin(n * np.pi * safe_d[..., None] / cutoff) / safe_d[..., None]


def polynomial_cutoff(d, cutoff: float, p: int = 6):
    """MACE PolynomialCutoff (smooth to p-th order at d = cutoff)."""
    x = d / cutoff
    f = (1.0
         - 0.5 * (p + 1) * (p + 2) * jnp.power(x, p)
         + p * (p + 2) * jnp.power(x, p + 1)
         - 0.5 * p * (p + 1) * jnp.power(x, p + 2))
    return jnp.where(x < 1.0, f, 0.0)


def agnesi_transform(d, q: float = 0.9183, p: float = 4.5791, a: float = 1.0):
    """MACE AgnesiTransform distance warp (radial.py:151)."""
    ap = jnp.power(a * d, q)
    return 1.0 / (1.0 + ap / (1.0 + jnp.power(a * d, q - p)))


def soft_transform(d, a: float = 0.2, b: float = 3.0):
    """MACE SoftTransform distance warp (radial.py:204)."""
    return d * jnp.tanh(jnp.power(d / b, 2) + a * d) / jnp.tanh(1.0 + a * d)


RADIAL_BASES = {
    "bessel": lambda d, cutoff, n: bessel_basis_mace(d, cutoff, n),
    "gaussian": lambda d, cutoff, n: gaussian_basis_mace(d, cutoff, n),
    "chebyshev": lambda d, cutoff, n: chebyshev_basis(d, cutoff, n),
}

DISTANCE_TRANSFORMS = {
    "None": lambda d: d,
    "Agnesi": agnesi_transform,
    "Soft": soft_transform,
}
