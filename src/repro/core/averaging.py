"""Two-track weighted averaging of dual iterates (paper Sec. 3.6).

BCFW-avg maintains  bar_phi^(k+1) = k/(k+2) bar_phi^(k) + 2/(k+2) phi^(k+1)
(the incremental form of the 2/(k(k+1)) * sum t*phi^(t) weighted average).

MP-BCFW-avg keeps TWO averages — one updated after every *exact* oracle
call, one after every *approximate* call — and at extraction time returns
the interpolation of the two with the best dual bound F (closed form, same
algebra as the BCFW line search).
"""
from __future__ import annotations

import jax.numpy as jnp

from .types import AveragingState
from .ssvm import dual_value


def init_averaging(d: int) -> AveragingState:
    # Two buffers, not one shared zero: the fused engines donate the
    # state, and a buffer cannot be donated twice.
    return AveragingState(bar_exact=jnp.zeros((d + 1,), jnp.float32),
                          bar_approx=jnp.zeros((d + 1,), jnp.float32),
                          k_exact=jnp.zeros((), jnp.int32),
                          k_approx=jnp.zeros((), jnp.int32))


def update_average(avg: AveragingState, phi: jnp.ndarray,
                   *, exact: bool) -> AveragingState:
    """Incremental weighted-average update after one oracle call."""
    if exact:
        k = avg.k_exact.astype(jnp.float32)
        bar = (k / (k + 2.0)) * avg.bar_exact + (2.0 / (k + 2.0)) * phi
        return avg._replace(bar_exact=bar, k_exact=avg.k_exact + 1)
    k = avg.k_approx.astype(jnp.float32)
    bar = (k / (k + 2.0)) * avg.bar_approx + (2.0 / (k + 2.0)) * phi
    return avg._replace(bar_approx=bar, k_approx=avg.k_approx + 1)


def extract(avg: AveragingState, lam: float) -> jnp.ndarray:
    """Best-F interpolation between the exact and approximate averages.

    maximize_beta F((1-beta) bar_exact + beta bar_approx), beta in [0,1];
    F is a concave quadratic in beta, so this is a clipped closed form.
    If a track has no updates yet, fall back to the other.
    """
    a, b = avg.bar_exact, avg.bar_approx
    diff = b - a
    num = -jnp.dot(a[:-1], diff[:-1]) + lam * diff[-1]
    den = jnp.dot(diff[:-1], diff[:-1])
    beta = jnp.clip(jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0),
                    0.0, 1.0)
    beta = jnp.where(avg.k_approx > 0, beta, 0.0)
    beta = jnp.where(avg.k_exact > 0, beta, 1.0)
    return (1.0 - beta) * a + beta * b


__all__ = ["init_averaging", "update_average", "extract", "dual_value"]
