"""Plain forms of the robust aggregate, the attacks and the optimizer.

VRMOM follows the paper's eq. (7) with the MAD scale: the coordinate
median, minus the scale times the summed quantile-indicator deviations
over all rows and K levels, over (rows x sum of the normal density at
the levels). Where the scale is 0 the median is the answer.
"""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

MAD_TO_SIGMA = statistics.NormalDist().inv_cdf(0.75)


def levels(K: int) -> list:
    nd = statistics.NormalDist()
    return [nd.inv_cdf(k / (K + 1)) for k in range(1, K + 1)]


def median(x):
    """Coordinate median over axis 0 (mean of the two middle values)."""
    xs = jnp.sort(x, axis=0)
    m = x.shape[0]
    return 0.5 * (xs[(m - 1) // 2] + xs[m // 2])


def vrmom(x, K: int, eps: float = 1e-12):
    """x [M, ...] float32 -> [...]."""
    M = x.shape[0]
    med = median(x)
    s = median(jnp.abs(x - med[None])) / MAD_TO_SIGMA
    z = (x - med[None]) / jnp.maximum(s, eps)[None]
    nd = statistics.NormalDist()
    lv = levels(K)
    psi = sum(nd.pdf(d) for d in lv)
    dev = sum(jnp.sum((z <= d).astype(jnp.float32) - 0.5, axis=0)
              for d in lv)
    out = med - s * dev / (M * psi)
    return jnp.where(s <= eps, med, out)


def signflip_stack(x, m: int, n_bad: int):
    """[m, ...] stack of m copies of x with the last n_bad negated."""
    return jnp.stack([x] * (m - n_bad) + [-x] * n_bad)


def adam(p, g, m, v, t: int, lr, b1, b2, eps):
    """One AdamW step (no weight decay) in float32; p keeps its dtype."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return (p.astype(jnp.float32) - lr * u).astype(p.dtype), m, v
