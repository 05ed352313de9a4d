"""Spherical Bessel / spherical-harmonic bases for DimeNet.

reference: torch_geometric's BesselBasisLayer/SphericalBasisLayer used at
hydragnn/models/DIMEStack.py:65-66. The reference relies on sympy codegen;
here the basis is closed-form jnp: spherical Bessel j_l via its ascending
series below x = l + 1 and the upward recurrence above (`spherical_jn`),
Legendre P_l via recurrence, zeros of j_l precomputed once with scipy.
"""
from __future__ import annotations

import functools

import numpy as np
import jax.numpy as jnp

from .basis import envelope


@functools.lru_cache(maxsize=None)
def spherical_bessel_zeros(num_l: int, num_n: int) -> np.ndarray:
    """zeros[l, n] = (n+1)-th positive zero of j_l (host precompute)."""
    from scipy import optimize, special
    zeros = np.zeros((num_l, num_n))
    # j_0 zeros are exactly k*pi; use them to bracket successive j_l zeros
    grid = np.arange(1, num_n + num_l + 2) * np.pi
    prev = grid  # zeros of j_0
    zeros[0] = grid[:num_n]
    for l in range(1, num_l):
        f = lambda x: special.spherical_jn(l, x)
        cur = []
        # zeros of j_l interlace those of j_{l-1}
        for a, b in zip(prev[:-1], prev[1:]):
            cur.append(optimize.brentq(f, a + 1e-9, b - 1e-9))
        prev = np.asarray(cur)
        zeros[l] = prev[:num_n]
    return zeros


SERIES_TERMS = 10


def spherical_jn(l: int, x):
    """j_l(x) for x >= 0, stable in float32 over the basis's whole range.

    The upward recurrence j_{k+1} = (2k+1)/x j_k - j_{k-1} (and the closed
    forms it generates) loses every digit below x ~ l: at l = 6 and
    d = 1 A of a 6 A cutoff (x = 1.75) its error is a fifth of the value.
    So below x = l + 1 the ascending series
    x^l / (2l+1)!! * sum_k (-x^2/2)^k / (k! (2l+3)(2l+5)...(2l+2k+1))
    is taken (ten terms, Horner), and the recurrence above it; both are
    within 1e-6 of the function's own scale at the switch. Each branch
    sees an argument clamped to its own side, so neither overflows nor
    divides by a small x where the other is selected, and the gradient
    of the unselected branch is an exact zero."""
    switch = float(l + 1)
    low = x < switch
    x_up = jnp.where(low, switch, x)
    cur = jnp.sin(x_up) / x_up
    if l >= 1:
        prev, cur = cur, (cur - jnp.cos(x_up)) / x_up
    for k in range(2, l + 1):
        prev, cur = cur, (2 * k - 1) / x_up * cur - prev
    x_lo = jnp.where(low, x, switch)
    q = -0.5 * x_lo * x_lo
    acc = jnp.ones_like(x_lo)
    for k in range(SERIES_TERMS, 0, -1):
        acc = 1.0 + q / (k * (2 * l + 2 * k + 1)) * acc
    double_factorial = float(np.prod(np.arange(1, 2 * l + 2, 2)))
    series = x_lo ** l * (acc / double_factorial)
    return jnp.where(low, series, cur)


def legendre(l_max: int, x):
    """P_0..P_{l_max}(x) via recurrence. Returns list of arrays."""
    out = [jnp.ones_like(x)]
    if l_max >= 1:
        out.append(x)
    for l in range(2, l_max + 1):
        out.append(((2 * l - 1) * x * out[-1] - (l - 1) * out[-2]) / l)
    return out


def spherical_basis(d, cos_angle, cutoff: float, num_spherical: int,
                    num_radial: int, envelope_exponent: int = 5):
    """sbf[..., l*num_radial + n] = env(d/c) j_l(z_ln d/c) P~_l(cos angle).

    `d` is the k->j edge length of each pair, `cos_angle` the cosine of the
    (i,j,k) angle — SphericalBasisLayer(dist[idx_kj], angle) in the
    reference stack, which only ever takes the angle's cosine. Callers pass
    the cosine itself (a.b / |a||b|): arctan2(|a x b|, a.b) has no gradient
    where the pair is collinear, and the polynomials in cos have one
    everywhere. `d` must be positive (callers put a safe length in padding
    slots before the call, not a mask after it).
    """
    from scipy import special
    zeros = spherical_bessel_zeros(num_spherical, num_radial)
    # normalizer 1/|j_{l+1}(z_ln)| (DimeNet appendix)
    norm = np.zeros_like(zeros)
    for l in range(num_spherical):
        norm[l] = 1.0 / np.abs(special.spherical_jn(l + 1, zeros[l]))
    x = d / cutoff
    env = envelope(x, envelope_exponent)
    pl = legendre(num_spherical - 1, cos_angle)    # list of [...]
    parts = []
    for l in range(num_spherical):
        z = jnp.asarray(zeros[l], d.dtype)          # [num_radial]
        jl = spherical_jn(l, x[..., None] * z)      # [..., num_radial]
        yl = np.sqrt((2 * l + 1) / (4 * np.pi)) * pl[l]
        parts.append(env[..., None] * jl * jnp.asarray(norm[l], d.dtype)
                     * yl[..., None])
    return jnp.concatenate(parts, axis=-1)          # [..., L*N]
