"""Hierarchical landmark selection for the sparse scale regime.

The sparse regime answers geodesic queries through an m-landmark panel
(m << n), so landmark placement controls embedding quality.  Plain
farthest-point sampling (FPS) over all n points is O(n * m) distance
evaluations with a serial dependency — fine, but it chases outliers and
its tail picks are dominated by a few sparse regions.  The hierarchical
variant here recurses FPS over a coarse cover instead:

1. a coarse FPS pass picks ``coarse ~ sqrt(m)`` cover centers, seeded
   from the point with the largest kNN radius (``knn_dists[:, -1]`` —
   the sparsest point, a deterministic start that needs no RNG);
2. every point is assigned to its nearest cover center (chunked, never
   materializing (n, coarse) beyond a chunk);
3. the m-landmark budget is split across cells by largest-remainder
   allocation proportional to cell population (every cell keeps at least
   its center, no cell gets more than its population);
4. per-cell masked FPS fills each quota, seeded from the cell's center.

Everything runs host-side in float64-free numpy on gathered inputs, so
the selection is bit-deterministic and backend-independent — the mesh
path computes it from the same gathered host copy the dense regime's
gate/border logic already uses, which is what makes checkpoints and the
sparse-vs-dense agreement tests reproducible across backends.

The host spans ``repro:landmarks:cover`` (steps 1-3) and
``repro:landmarks:fill`` (step 4) name the selection's time in a
profiler trace; neither waits on the device.
"""
from __future__ import annotations

import numpy as np

from repro.core import telemetry


def _fps(x: np.ndarray, m: int, start: int, cand=None) -> np.ndarray:
    """Farthest-point sampling: m indices, greedily maximizing the min
    squared distance to the already-selected set.  ``cand`` masks the
    eligible points (selection never leaves it); ``start`` must be
    eligible."""
    n = x.shape[0]
    sel = np.empty(m, dtype=np.int64)
    sel[0] = start
    d = np.full(n, np.inf, dtype=np.float32)
    if cand is not None:
        d[~cand] = -np.inf  # ineligible: never argmax while any d >= 0
    cur = start
    for t in range(1, m):
        delta = np.sum((x - x[cur]) ** 2, axis=1, dtype=np.float32)
        d = np.minimum(d, delta)
        cur = int(np.argmax(d))
        sel[t] = cur
    return sel


def _assign(x: np.ndarray, centers: np.ndarray, chunk: int = 8192):
    """Nearest-center assignment, chunked over points."""
    out = np.empty(x.shape[0], dtype=np.int64)
    for i in range(0, x.shape[0], chunk):
        blk = x[i:i + chunk]
        d = (
            np.sum(blk * blk, axis=1)[:, None]
            + np.sum(centers * centers, axis=1)[None, :]
            - 2.0 * blk @ centers.T
        )
        out[i:i + chunk] = np.argmin(d, axis=1)
    return out


def _largest_remainder(sizes: np.ndarray, m: int) -> np.ndarray:
    """Split m across cells proportionally to ``sizes`` (largest-remainder
    method), with every cell getting at least 1 and at most its size.
    Requires sum(sizes) >= m >= len(sizes)."""
    n = int(sizes.sum())
    ideal = m * sizes / n
    q = np.minimum(
        np.maximum(np.floor(ideal).astype(np.int64), 1), sizes
    )
    rem = ideal - np.floor(ideal)
    grow = np.argsort(-rem, kind="stable")
    i = 0
    while q.sum() < m:  # capacity exists: sum(sizes) = n >= m
        c = grow[i % len(grow)]
        if q[c] < sizes[c]:
            q[c] += 1
        i += 1
    shrink = np.argsort(rem, kind="stable")
    i = 0
    while q.sum() > m:  # slack exists: all-ones sums to len(sizes) <= m
        c = shrink[i % len(shrink)]
        if q[c] > 1:
            q[c] -= 1
        i += 1
    return q


def hierarchical_landmarks(
    x, knn_dists, *, m: int, coarse: int | None = None
) -> np.ndarray:
    """Select m landmark indices by FPS recursed over a coarse cover.

    ``x`` (n, D) features, ``knn_dists`` (n, k) squared kNN distances
    (only the last column — the kNN radius — is read, to seed the coarse
    pass from the sparsest point).  Returns sorted unique indices,
    shape (min(m, n),), deterministically: pure host-side argmax chains,
    no RNG, so a fixed input always yields the same landmarks on every
    backend.
    """
    x = np.asarray(x, dtype=np.float32)
    n = x.shape[0]
    m = min(m, n)
    if m <= 0:
        raise ValueError(f"landmark budget m={m} must be positive")
    if m == n:
        return np.arange(n, dtype=np.int64)
    radius = np.asarray(knn_dists)[:, -1]
    start = int(np.argmax(radius))
    if coarse is None:
        coarse = int(round(np.sqrt(m)))
    coarse = max(1, min(coarse, m))
    with telemetry.span("landmarks:cover"):
        centers = _fps(x, coarse, start)
        cell = _assign(x, x[centers])
        # every center claims its own cell even under distance ties
        cell[centers] = np.arange(coarse)
        sizes = np.bincount(cell, minlength=coarse)
        quota = _largest_remainder(sizes, m)
    picks = []
    with telemetry.span("landmarks:fill"):
        for c in range(coarse):
            mask = cell == c
            picks.append(_fps(x, int(quota[c]), int(centers[c]), cand=mask))
    return np.sort(np.unique(np.concatenate(picks)))
