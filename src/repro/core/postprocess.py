"""Shared geodesic/eigen post-processing helpers.

These two transforms used to be re-implemented inside every Isomap driver
(local, distributed, landmark) with identical bodies; they are the single
source of truth now, used by the pipeline stages and the landmark tail.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("clamp")
def clamp_disconnected(a: jax.Array) -> jax.Array:
    """Replace +inf geodesics (disconnected components) by 1.1x the graph
    diameter.  A no-op on connected graphs (the paper's k is chosen for a
    single component), but keeps the spectral stage finite otherwise.

    A graph with no finite off-diagonal entry (every point isolated) has
    diameter 0; clamping to 1.1 * 0 would silently collapse all pairwise
    distances to zero, so the fallback substitutes a unit distance - the
    embedding is meaningless either way, but stays finite and non-degenerate
    instead of mapping every point to the origin."""
    finite = jnp.isfinite(a)
    diam = jnp.max(jnp.where(finite, a, 0.0))
    diam = jnp.where(diam > 0, diam, 1.0)
    return jnp.where(finite, a, 1.1 * diam)


def embedding_from_eig(q: jax.Array, lam: jax.Array) -> jax.Array:
    """Y = Q_d . Delta_d^{1/2} (Alg. 1 step 5), clamping negative
    eigenvalues (noise floor of the centered Gram matrix) to zero."""
    lam = jnp.maximum(lam, 0.0)
    return q * jnp.sqrt(lam)[None, :]
