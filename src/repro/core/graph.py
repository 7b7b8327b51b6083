"""Neighbourhood-graph construction (paper SIII-A, last stage).

Converts kNN lists into the dense (n, n) adjacency matrix consumed by the
APSP solver: entry (i, j) = Euclidean distance if j is a neighbour of i,
+inf otherwise, symmetrized with min(G, G^T) and zero diagonal.  The paper
writes the kNN triples back into the same RDD block layout used for the
distance matrix; here the scatter lands directly in the (sharded) array.

The sparse scale regime never builds that matrix: :func:`knn_to_padded_csr`
emits the same symmetrized graph as fixed-shape padded neighbour lists
(ELL layout, O(n * deg)), and
:func:`connected_components_lower_bound_csr` runs the connectivity probe
directly on them, so validation does not reintroduce O(n^2) either.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("n",))
@jax.named_scope("graph")
def knn_to_graph(dists: jax.Array, idx: jax.Array, *, n: int) -> jax.Array:
    """(n, k) squared kNN distances + indices -> dense (n, n) graph.

    Returns Euclidean (not squared) edge lengths, inf off-graph.
    """
    k = dists.shape[1]
    rows = jnp.repeat(jnp.arange(n), k)
    cols = idx.reshape(-1)
    vals = jnp.sqrt(jnp.maximum(dists.reshape(-1), 0.0))
    g = jnp.full((n, n), jnp.inf, dtype=jnp.float32)
    g = g.at[rows, cols].min(vals)
    g = jnp.minimum(g, g.T)  # kNN relation is not symmetric; the graph is
    g = jnp.where(jnp.eye(n, dtype=bool), 0.0, g)
    return g


def connected_components_lower_bound(g: jax.Array, iters: int = 32):
    """Cheap connectivity probe: label propagation on the kNN graph.

    Returns the number of distinct labels after `iters` sweeps - an upper
    bound on the component count (equals it once converged).  Used by tests
    and the pipeline to validate the paper's requirement that k yields a
    single connected component.
    """
    n = g.shape[0]
    adj = jnp.isfinite(g) & (g >= 0)

    def body(_, lab):
        neigh = jnp.where(adj, lab[None, :], n + 1)
        return jnp.minimum(lab, jnp.min(neigh, axis=1))

    lab = jax.lax.fori_loop(0, iters, body, jnp.arange(n))
    return jnp.unique(lab).shape[0]


@functools.partial(jax.jit, static_argnames=("n", "deg"))
@jax.named_scope("csr_graph")
def _padded_csr_device(dists, idx, *, n: int, deg: int):
    """Fixed-shape XLA form of the symmetrize/dedupe/bucket pipeline.

    Every step is shape-static: the data-dependent filtering the old
    host-numpy build did with boolean masks is replaced by *retiring*
    edges to a virtual row n that sorts past every real row and falls
    out of bounds at the scatter — a three-key ``lax.sort`` puts each
    row's deduplicated edges in a contiguous run, a ``searchsorted`` of
    the row keys against themselves recovers each edge's lane within its
    row, and one uniquely-indexed scatter writes the (n, deg) padded
    lists.  Returns (nbr, w, overflow) where ``overflow`` is True iff
    some row holds more than ``deg`` live edges (its tail edges were
    dropped) — the caller retries with a doubled cap.
    """
    k = dists.shape[1]
    rows = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    cols = idx.reshape(-1).astype(jnp.int32)
    vals = jnp.sqrt(jnp.maximum(dists.reshape(-1), 0.0)).astype(jnp.float32)
    # symmetrize: each directed kNN pair contributes both orientations
    # (stack + reshape rather than concatenate: XLA's partitioner
    # mis-lowers axis-0 concatenation of row-sharded operands on some
    # backends, sum-combining the replicated mesh axis)
    src = jnp.stack([rows, cols]).reshape(-1)
    dst = jnp.stack([cols, rows]).reshape(-1)
    val = jnp.stack([vals, vals]).reshape(-1)
    # self-edges are implicit (distance 0); kNN pad lanes carry index -1
    # and weight +inf — retire both kinds to the overflow row
    dead = (src == dst) | (src < 0) | (dst < 0) | ~jnp.isfinite(val)
    src = jnp.where(dead, n, src)
    dst = jnp.where(dead, n, dst)
    val = jnp.where(dead, jnp.inf, val)
    # dedupe (src, dst) keeping the min weight: sort by (src, dst, val),
    # keep first occurrences, retire the duplicates
    src, dst, val = jax.lax.sort((src, dst, val), num_keys=3)
    pos = jnp.arange(src.shape[0], dtype=jnp.int32)
    first = (pos == 0) | (src != jnp.roll(src, 1)) | (dst != jnp.roll(dst, 1))
    first &= src < n
    src = jnp.where(first, src, n)
    dst = jnp.where(first, dst, n)
    val = jnp.where(first, val, jnp.inf)
    # compact: stable sort by row alone keeps each row's (dst, val)
    # order, then an edge's lane is its offset into its row's run
    src, dst, val = jax.lax.sort((src, dst, val), num_keys=1, is_stable=True)
    lane = (
        jnp.arange(src.shape[0], dtype=jnp.int32)
        - jnp.searchsorted(src, src, side="left").astype(jnp.int32)
    )
    overflow = jnp.any((src < n) & (lane >= deg))
    # every in-bounds (row, lane) is unique: live edges have unique lanes
    # within their row; retired edges (src == n) and overflowing lanes
    # (lane >= deg) are sent out of bounds and dropped.  Uniqueness lets
    # the SPMD partitioner keep the overwrite semantics — with colliding
    # indices it may lower the scatter with a sum combiner, which
    # multiplies replicated updates by the replication factor.
    nbr = jnp.tile(jnp.arange(n, dtype=jnp.int32)[:, None], (1, deg))
    w = jnp.full((n, deg), jnp.inf, dtype=jnp.float32)
    nbr = nbr.at[src, lane].set(dst, mode="drop", unique_indices=True)
    w = w.at[src, lane].set(val, mode="drop", unique_indices=True)
    return nbr, w, overflow


def knn_to_padded_csr(
    dists, idx, *, n: int, deg: int | None = None
) -> tuple[jax.Array, jax.Array]:
    """(n, k) squared kNN distances + indices -> padded-CSR adjacency.

    Returns ``(nbr, w)`` with shapes (n, deg) int32 / (n, deg) float32:
    the symmetrized union graph (edge i-j present when either endpoint
    listed the other), deduplicated per row with the min edge weight kept
    — exactly the edge set :func:`knn_to_graph` produces, but in
    O(n * deg).  Padded lanes point at the row itself with weight +inf
    so the frontier kernel's min never selects them.  kNN pad lanes
    (index -1, weight +inf) are ignored.

    Built on device (:func:`_padded_csr_device`): sort-based dedupe +
    one fixed-shape scatter, O(n k log(n k)), no host round-trip of the
    O(n k) edge lists.  The row width is the only data-dependent piece:
    ``deg`` starts at 2k (the typical in+out bound) and doubles — one
    scalar host sync per attempt — while some hub row overflows; pass
    ``deg`` explicitly to pin the width (e.g. to match a checkpoint).
    """
    k = idx.shape[1]
    cap = max(n - 1, 1)  # a row's deduped neighbours exclude itself
    pinned = deg is not None
    if not pinned:
        deg = min(max(2 * k, 1), cap)
    while True:
        nbr, w, overflow = _padded_csr_device(dists, idx, n=n, deg=deg)
        if pinned or deg >= cap or not bool(overflow):
            return nbr, w
        deg = min(2 * deg, cap)


def connected_components_lower_bound_csr(nbr, w, iters: int = 32):
    """Label-propagation connectivity probe on the padded-CSR adjacency.

    Same contract as :func:`connected_components_lower_bound` (an upper
    bound on the component count, exact once converged) but O(n * deg)
    per sweep — the sparse regime's validation never densifies.
    """
    n, _ = nbr.shape
    live = jnp.isfinite(w)

    def body(_, lab):
        neigh = jnp.where(live, lab[nbr], n + 1)
        return jnp.minimum(lab, jnp.min(neigh, axis=1))

    lab = jax.lax.fori_loop(0, iters, body, jnp.arange(n))
    return jnp.unique(lab).shape[0]
