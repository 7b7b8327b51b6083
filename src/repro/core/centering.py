"""Double centering of the feature matrix (paper SIII-C).

B = -1/2 * H A H with H = I - (1/n) 11^T, computed the direct way the paper
uses instead of two matrix products: subtract column means and row means,
add back the global mean.  A here is the *squared* geodesic distance matrix
(Alg. 1 step 3 centers A^{o2}).

Under pjit the reductions shard transparently (GSPMD emits the psums); a
shard_map variant is provided for the explicit-collective path so the whole
distributed pipeline can run inside a single shard_map region.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


@jax.jit
@jax.named_scope("center")
def double_center(a_sq: jax.Array) -> jax.Array:
    """-1/2 H (A^{o2}) H for a full (n, n) squared-distance matrix."""
    col_mean = jnp.mean(a_sq, axis=0, keepdims=True)   # (1, n)
    row_mean = jnp.mean(a_sq, axis=1, keepdims=True)   # (n, 1)
    grand = jnp.mean(a_sq)
    return -0.5 * (a_sq - col_mean - row_mean + grand)


@jax.named_scope("center")
def double_center_local(a_sq_loc, *, data_axis: str, model_axis: str, n: int):
    """shard_map body: local (nr, nc) tile of A^{o2} -> centered tile.

    Column means reduce over the data axis, row means over the model axis,
    the grand mean over both - O(n) scalars communicated, exactly the
    paper's column-sums -> driver-reduce -> broadcast pattern without the
    driver round-trip.
    """
    col_sum = jax.lax.psum(jnp.sum(a_sq_loc, axis=0, keepdims=True), data_axis)
    row_sum = jax.lax.psum(jnp.sum(a_sq_loc, axis=1, keepdims=True), model_axis)
    grand = jax.lax.psum(jnp.sum(col_sum), model_axis)
    nf = float(n)  # python-int n*n overflows int32 at n >= 2^16
    col_mean = col_sum / nf
    row_mean = row_sum / nf
    grand_mean = grand / (nf * nf)
    return -0.5 * (a_sq_loc - col_mean - row_mean + grand_mean)


def double_center_sharded(a_sq: jax.Array, mesh: Mesh,
                          data_axis: str = "data", model_axis: str = "model"):
    fn = _make_double_center_sharded(mesh, a_sq.shape[0], data_axis,
                                     model_axis)
    return fn(a_sq)


@functools.lru_cache(maxsize=None)
def _make_double_center_sharded(mesh: Mesh, n: int, data_axis: str,
                                model_axis: str):
    """The jitted shard_map of :func:`double_center_sharded`, built once
    per mesh and n."""
    fn = jax.shard_map(
        functools.partial(double_center_local, data_axis=data_axis,
                          model_axis=model_axis, n=n),
        mesh=mesh,
        in_specs=P(data_axis, model_axis),
        out_specs=P(data_axis, model_axis),
    )
    return jax.jit(fn)
