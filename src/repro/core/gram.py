"""Inner-product (kernel) recurrences for approximate steps (paper Sec. 3.5).

When the approximate oracle is applied to the same block several times in a
row (the paper uses 10 repeats), all the quantities needed by the BCFW line
search can be maintained from scalar recurrences over cached Gram products
<phi_a*, phi_b*>, making each inner step Theta(|W_i|) instead of
Theta(|W_i| d).  The Gram matrices live *inside* the plane cache
(:class:`repro.cache.PlaneCache` with ``CacheLayout(gram=True)``): rows are
refreshed by :func:`repro.cache.insert` whenever a plane lands in a slot —
the "computed on demand and cached" scheme of the paper, and the hook for
kernelized SSVMs.  This module holds only the optimization math that
*consumes* those matrices; there is no separate gram state to thread
through passes anymore (which is exactly what lets the mesh-sharded engine
run this variant: the gram leaf shards with the blocks).

Recurrences (phi' = phi + g(phi_j - phi_i); phi_i' = (1-g)phi_i + g phi_j):
    a_j = <phi_j*, phi*>   ->  a_j + g (G[j,h] - b_j)
    b_j = <phi_j*, phi_i*> -> (1-g) b_j + g G[j,h]
    c   = <phi_i*, phi_i*> -> (1-g)^2 c + 2g(1-g) b_h + g^2 G[h,h]
    e   = <phi_i*, phi*>   -> (1-g)(e + g(b_h - c)) + g(a_h + g(G[h,h]-b_h))
with h the argmax plane.  The final phi_i is materialized from the tracked
convex-combination coefficients with one (cap+1, d+1) matvec, and
phi' - phi_i' = phi - phi_i is invariant, so phi is materialized for free.

"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import cache as plane_cache
from ..cache import NEG_INF, PlaneCache
from ..obs import spans
from .averaging import update_average
from .types import AveragingState, BCFWState


def multi_step_block_update(planes_i: jnp.ndarray, valid_i: jnp.ndarray,
                            gram_i: jnp.ndarray, phi: jnp.ndarray,
                            phi_i: jnp.ndarray, lam: float, steps: int):
    """``steps`` repeated approximate BCFW updates on one block, O(cap)/step.

    Returns (phi_i', phi', won) where ``won[j]`` marks planes that were
    returned by the approximate oracle at least once (for activity).
    """
    cap = planes_i.shape[0]
    star = planes_i[:, :-1]
    circ = planes_i[:, -1]
    a = star @ phi[:-1]
    b = star @ phi_i[:-1]
    c = jnp.dot(phi_i[:-1], phi_i[:-1])
    e = jnp.dot(phi_i[:-1], phi[:-1])
    oi = phi_i[-1]
    oo = phi[-1]

    # Convex-combination coefficients of phi_i over [phi_i_init, planes].
    beta0 = jnp.float32(1.0)
    beta = jnp.zeros((cap,), jnp.float32)
    won = jnp.zeros((cap,), bool)

    def step(carry, _):
        a, b, c, e, oi, oo, beta0, beta, won = carry
        scores = jnp.where(valid_i, -a / lam + circ, NEG_INF)
        h = jnp.argmax(scores)
        gh = gram_i[:, h]
        num = (e - a[h]) - lam * (oi - circ[h])
        den = c - 2.0 * b[h] + gram_i[h, h]
        g = jnp.clip(jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0),
                     0.0, 1.0)
        g = jnp.where(jnp.any(valid_i), g, 0.0)
        e_new = (1 - g) * (e + g * (b[h] - c)) \
            + g * (a[h] + g * (gram_i[h, h] - b[h]))
        a_new = a + g * (gh - b)
        b_new = (1 - g) * b + g * gh
        c_new = (1 - g) ** 2 * c + 2 * g * (1 - g) * b[h] \
            + g ** 2 * gram_i[h, h]
        oo_new = oo + g * (circ[h] - oi)
        oi_new = (1 - g) * oi + g * circ[h]
        beta0_new = (1 - g) * beta0
        beta_new = ((1 - g) * beta).at[h].add(g)
        won = won.at[h].set(jnp.any(valid_i))
        return (a_new, b_new, c_new, e_new, oi_new, oo_new,
                beta0_new, beta_new, won), None

    carry = (a, b, c, e, oi, oo, beta0, beta, won)
    carry, _ = jax.lax.scan(step, carry, None, length=steps)
    a, b, c, e, oi, oo, beta0, beta, won = carry

    new_phi_i = beta0 * phi_i + beta @ planes_i
    new_phi = phi + (new_phi_i - phi_i)  # phi - phi_i is invariant
    return new_phi_i, new_phi, won


def approx_pass_gram(inner: BCFWState, cache: PlaneCache,
                     avg: AveragingState, perm: jnp.ndarray,
                     outer_it: jnp.ndarray, lam: float, steps: int = 10):
    """Approximate pass using the cached-Gram multi-step scheme.

    ``cache`` must carry gram blocks (``CacheLayout(gram=True)``).
    Returns ``(inner, cache, avg)``.
    """

    def body(carry, i):
        st, c, av = carry
        phi_i, phi, won = multi_step_block_update(
            c.planes[i], c.valid[i], c.gram[i], st.phi, st.phi_i[i],
            lam, steps)
        st = st._replace(phi_i=st.phi_i.at[i].set(phi_i), phi=phi,
                         n_approx=st.n_approx + steps)
        c = plane_cache.mark_active_where(c, i, won, outer_it)
        av = update_average(av, st.phi, exact=False)
        return (st, c, av), None

    with spans.scope(spans.APPROX_PASS):
        (inner, cache, avg), _ = jax.lax.scan(body, (inner, cache, avg),
                                              perm)
    return inner, cache, avg


@functools.partial(jax.jit, static_argnames=("lam", "steps"))
def jit_approx_pass_gram(inner, cache, avg, perm, outer_it,
                         *, lam: float, steps: int = 10):
    return approx_pass_gram(inner, cache, avg, perm, outer_it, lam, steps)
