"""Plain float64 references for what the timed path produces.

Nothing here imports the program or reads anything it made: the inputs
are the run's own data (``bench/data.py``) and, for serving, the serving
state the benchmark itself built from the seed.  Each function states the
semantics it reproduces; the comparisons that decide ``correct`` are in
``bench/checks.py``.

* kNN: exact k nearest neighbours by Euclidean distance, self excluded
  (a k-d tree in float64).
* Geodesics: the symmetrised kNN graph (edge i-j when either lists the
  other, weight = Euclidean length), single-source shortest paths by
  Dijkstra in float64.
* Embedding: landmark classical MDS (de Silva and Tenenbaum) from the
  geodesic rows of a seed-drawn set of sources.  On the Euler-isometric
  roll the geodesics are those of a flat strip, so landmark MDS and the
  program's full classical MDS recover the same chart up to a rigid
  motion; the comparison aligns the two by Procrustes.
* Out-of-sample mapping: the k-anchor geodesic estimate and the
  L-Isomap triangulation against the base embedding, evaluated in
  float64 from the exact strip distances.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse
import scipy.sparse.csgraph
import scipy.spatial


def knn_rows(x: np.ndarray, rows: np.ndarray, k: int, tree=None):
    """Exact kNN of ``x[rows]`` among all of ``x``, self excluded.

    -> (dists (r, k) Euclidean, ascending; idx (r, k))."""
    x = np.asarray(x, np.float64)
    tree = tree if tree is not None else scipy.spatial.cKDTree(x)
    d, i = tree.query(x[rows], k=k + 1)
    # drop each row's own point (distance 0); a duplicate point would tie
    # with it, so drop by index rather than by position
    keep = i != np.asarray(rows)[:, None]
    d = np.stack([r[m][:k] for r, m in zip(d, keep)])
    i = np.stack([r[m][:k] for r, m in zip(i, keep)])
    return d, i


def knn_graph(x: np.ndarray, k: int):
    """The symmetrised kNN graph of all points as a scipy CSR matrix of
    Euclidean edge lengths (float64)."""
    x = np.asarray(x, np.float64)
    n = x.shape[0]
    tree = scipy.spatial.cKDTree(x)
    d, i = tree.query(x, k=k + 1)
    rows = np.repeat(np.arange(n), k + 1)
    cols = i.reshape(-1)
    vals = d.reshape(-1)
    live = rows != cols
    g = scipy.sparse.csr_matrix(
        (vals[live], (rows[live], cols[live])), shape=(n, n)
    )
    # min(G, G^T): both directions carry the same length, so the max of
    # the two stored values (0 where absent) is that length
    return g.maximum(g.T).tocsr(), tree


def geodesic_rows(graph, sources: np.ndarray) -> np.ndarray:
    """(s, n) shortest-path lengths from each source, +inf where
    unreachable."""
    return scipy.sparse.csgraph.dijkstra(
        graph, directed=False, indices=np.asarray(sources)
    )


def landmark_mds(rows: np.ndarray, sources: np.ndarray, d: int):
    """(n, d) landmark-MDS chart of all points from the (s, n) geodesic
    rows of landmark nodes ``sources``."""
    d2 = np.square(np.asarray(rows, np.float64))
    sub = d2[:, sources]
    s = sub.shape[0]
    h = np.eye(s) - 1.0 / s
    b = -0.5 * h @ sub @ h
    lam, vec = np.linalg.eigh(0.5 * (b + b.T))
    lam, vec = lam[::-1][:d], vec[:, ::-1][:, :d]
    pinv = vec / np.sqrt(np.maximum(lam, 1e-12))[None, :]
    mean2 = sub.mean(axis=1)
    return -0.5 * (d2 - mean2[:, None]).T @ pinv


def procrustes_disparity(a: np.ndarray, b: np.ndarray) -> float:
    """Sum of squared differences after the best translation, scaling
    and rotation of ``b`` onto ``a``, both standardised (scipy); 1.0, the
    largest, where either collapses to one point."""
    try:
        return float(scipy.spatial.procrustes(
            np.asarray(a, np.float64), np.asarray(b, np.float64)
        )[2])
    except ValueError:
        return 1.0


def principal_chart(latent: np.ndarray) -> np.ndarray:
    """Classical MDS of exact Euclidean distances in the plane: the
    centred latent coordinates on their principal axes (the eigenbasis
    of the double-centred squared-distance matrix)."""
    z = np.asarray(latent, np.float64)
    z = z - z.mean(axis=0)
    _, _, vt = np.linalg.svd(z, full_matrices=False)
    return z @ vt.T


def strip_distances(za: np.ndarray, zb: np.ndarray) -> np.ndarray:
    """(a, b) exact geodesics of the roll: Euclidean distances between
    latent strip coordinates."""
    za = np.asarray(za, np.float64)
    zb = np.asarray(zb, np.float64)
    return np.sqrt(np.sum(np.square(za[:, None, :] - zb[None, :, :]), -1))


def map_points(
    x_new: np.ndarray, x_base: np.ndarray, latent_base: np.ndarray,
    y_base: np.ndarray, k: int,
) -> np.ndarray:
    """L-Isomap out-of-sample mapping of ``x_new`` (m, D) against a base
    whose exact geodesics are the strip distances of ``latent_base`` and
    whose embedding is ``y_base`` (n, d):

        geo_j = min_a  |x_new - x_a| + A[a, j]    over the k nearest a
        y     = -1/2 (geo^2 - mean_i A[i, .]^2) @ y_base / (lam n)
        lam   = column sums of y_base^2 / n
    """
    x_base = np.asarray(x_base, np.float64)
    y_base = np.asarray(y_base, np.float64)
    z = np.asarray(latent_base, np.float64)
    n = x_base.shape[0]
    tree = scipy.spatial.cKDTree(x_base)
    anchor_d, idx = tree.query(np.asarray(x_new, np.float64), k=k)
    # row means of A^2 in closed form: mean_i |z_i - z_j|^2
    zc = z - z.mean(axis=0)
    mean_sq = np.sum(zc * zc, axis=1) + np.mean(np.sum(zc * zc, axis=1))
    lam = np.sum(y_base * y_base, axis=0) / n
    pinv = y_base / (np.maximum(lam, 1e-12)[None, :] * n)
    out = np.empty((x_new.shape[0], y_base.shape[1]))
    for r in range(x_new.shape[0]):
        a = strip_distances(z[idx[r]], z)              # (k, n)
        geo = np.min(anchor_d[r][:, None] + a, axis=0)
        out[r] = -0.5 * (np.square(geo) - mean_sq) @ pinv
    return out
