"""Spectral decomposition by simultaneous power iteration (paper SIII-D,
Alg. 2).

The paper splits the work between Spark executors (the O(n^2 d) product
V = A Q) and the driver (QR of the tall-skinny (n, d) V, convergence check,
broadcast of Q).  On a TPU mesh there is no driver: the product is sharded,
V is all-gathered (n x d is small), and the QR + convergence check run
*replicated* on every chip - redundant compute is cheaper than a
centralization round-trip.

Eigenvalues come from the Rayleigh quotient diag(Q^T A Q) rather than the
paper's diag(R), which is only correct at exact convergence; both are
exposed for the faithfulness tests.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


class EigResult(NamedTuple):
    eigenvectors: jax.Array   # (n, d)
    eigenvalues: jax.Array    # (d,)
    iterations: jax.Array     # ()
    delta: jax.Array          # final ||Q_i - Q_{i-1}||_F


def _sign_fix(q):
    """Fix the sign ambiguity of QR so convergence checks are meaningful."""
    s = jnp.sign(jnp.sum(q, axis=0))
    s = jnp.where(s == 0, 1.0, s)
    return q * s[None, :]


@functools.partial(jax.jit, static_argnames=("d", "max_iter"))
@jax.named_scope("eigen")
def power_iteration(
    a: jax.Array, *, d: int, max_iter: int = 100, tol: float = 1e-9
) -> EigResult:
    """Top-d eigenpairs of symmetric a (n, n). Single-device reference."""
    n = a.shape[0]
    v0 = jnp.eye(n, d, dtype=a.dtype)          # V^1 = I_{n x d} (Alg. 2 l.1)
    q0, _ = jnp.linalg.qr(v0)
    q0 = _sign_fix(q0)

    def cond(carry):
        _, delta, it = carry
        return (delta >= tol) & (it < max_iter)

    def body(carry):
        q, _, it = carry
        v = a @ q                               # Alg. 2 l.4
        q_new, _ = jnp.linalg.qr(v)             # Alg. 2 l.5
        q_new = _sign_fix(q_new)
        delta = jnp.linalg.norm(q_new - q)      # Alg. 2 l.6
        return q_new, delta, it + 1

    q, delta, it = jax.lax.while_loop(
        cond, body, (q0, jnp.array(jnp.inf, a.dtype), jnp.array(0))
    )
    lam = jnp.diag(q.T @ (a @ q))               # Rayleigh quotient
    order = jnp.argsort(-jnp.abs(lam))
    return EigResult(q[:, order], lam[order], it, delta)


# ------------------------------------------------------------- sharded ----


def matvec_sharded(a_loc, q, *, data_axis, model_axis, nc):
    """Local (nr, nc) tile times replicated (n, d): returns replicated V.

    The shared "sharded matrix x replicated tall-skinny" building block:
    slice q by model index, contract the local tile, psum the column
    partials over `model_axis`, all-gather the row blocks over `data_axis`.
    Used by the power-iteration body below and by the streaming mapper's
    sharded triangulation (row statistics of the sharded geodesics).
    Must be called inside a ``shard_map`` over both axes."""
    from repro.sharding.logical import folded_axis_index

    mi = folded_axis_index(model_axis)
    q_loc = jax.lax.dynamic_slice_in_dim(q, mi * nc, nc, axis=0)
    v_loc = a_loc @ q_loc                               # (nr, d) partial
    v_loc = jax.lax.psum(v_loc, model_axis)             # contract columns
    v = jax.lax.all_gather(v_loc, data_axis, axis=0, tiled=True)  # (n, d)
    return v


@functools.lru_cache(maxsize=None)
def make_power_iteration_sharded(
    mesh: Mesh,
    *,
    n: int,
    d: int,
    max_iter: int = 100,
    tol: float = 1e-9,
    data_axis: str = "data",
    model_axis: str = "model",
):
    """Returns jit'd fn(a_sharded) -> EigResult with replicated outputs
    (memoized: one executable per mesh, n and parameters)."""
    from repro.sharding.logical import mesh_axis_size

    pd, pm = mesh_axis_size(mesh, data_axis), mesh_axis_size(mesh, model_axis)
    nr, nc = n // pd, n // pm

    @jax.named_scope("eigen")
    def shard_fn(a_loc):
        q0, _ = jnp.linalg.qr(jnp.eye(n, d, dtype=a_loc.dtype))
        q0 = _sign_fix(q0)

        def cond(carry):
            _, delta, it = carry
            return (delta >= tol) & (it < max_iter)

        def body(carry):
            q, _, it = carry
            v = matvec_sharded(
                a_loc, q, data_axis=data_axis, model_axis=model_axis, nc=nc
            )
            q_new, _ = jnp.linalg.qr(v)      # replicated redundant QR
            q_new = _sign_fix(q_new)
            delta = jnp.linalg.norm(q_new - q)
            return q_new, delta, it + 1

        q, delta, it = jax.lax.while_loop(
            cond, body, (q0, jnp.array(jnp.inf, a_loc.dtype), jnp.array(0))
        )
        aq = matvec_sharded(
            a_loc, q, data_axis=data_axis, model_axis=model_axis, nc=nc
        )
        lam = jnp.diag(q.T @ aq)
        order = jnp.argsort(-jnp.abs(lam))
        return EigResult(q[:, order], lam[order], it, delta)

    fn = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=P(data_axis, model_axis),
        out_specs=EigResult(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)
