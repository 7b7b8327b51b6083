"""The comparisons that decide ``correct``, and their limits.

Each number is the widest gap between what the timed path produced and
the float64 reference (``bench/reference.py``), taken over everything the
run kept (every fit of the window; a seed-drawn sample of the requests):

* ``knn_gap`` - squared kNN distances of seed-drawn rows, k-th by k-th,
  as a share of the reference's, floored at the square of the median
  k-th neighbour distance h (float32's error in ||x||^2 + ||y||^2 - 2 x.y
  is absolute, so the closest pairs would otherwise set the number).
* ``geo_gap`` - geodesic rows (dense) or landmark-panel rows (sparse) of
  seed-drawn sources, as a share of the reference distance, floored at
  the rows' median geodesic, so that a small absolute detour near a
  source does not read as a large relative error.  On the chip it reads
  up to about 5e-3 dense and 1.3e-2 sparse.  Dense, the graph sets it:
  a few kNN lists in 10^4 differ from the exact graph at near ties, and
  the program's rows lie within 4e-4 of Dijkstra on its own graph
  (PERF.md, Open questions).  Rows that disagree on which nodes are
  reachable read ``UNREACHABLE``.
* ``emb_gap`` - Procrustes disparity between the embedding and the
  reference's landmark-MDS chart of the same points.
* ``map_gap`` - mapped coordinates of sampled requests, as a share of
  the base embedding's RMS radius.  It swings by its nature: where a
  point's k-th and (k+1)-th anchors lie within float32's error of a tie,
  the program may take the other one, which moves the point by up to
  2.2e-2 on this roll (the largest such move over the 8192 pool points,
  in float64); one seed read 1.25e-2 so.

Every limit was set between two readings on the chip, as ``PERF.md``
records: the largest that sound runs gave over a dozen seeds or more, and
the smallest that the control gave (the same references computed in
bfloat16 in the program's place, ``bench/control.py``).
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref

UNREACHABLE = 1.0e9

#: each between the largest reading of sound runs over a dozen seeds and
#: the smallest of the control on three or more (one TPU v5e; PERF.md
#: section 2)
LIMITS = {
    "knn_gap": 1e-2,     # sound 1.38e-3, control 22.6
    "geo_gap": 0.1,      # sound 1.23e-2, control 1.0
    "emb_gap": 1e-3,     # sound 3.16e-5, control 0.996
    "map_gap": 4e-2,     # sound 1.25e-2, control 0.0796
}


def _rel_gap(got, want, floor):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.array_equal(np.isfinite(got), np.isfinite(want)):
        return UNREACHABLE
    fin = np.isfinite(want)
    return float(np.max(
        np.abs(got[fin] - want[fin]) / np.maximum(want[fin], floor)
    ))


def fit_values(kept: list, k: int, d: int) -> dict:
    """Widest gaps over the fits kept by ``generator.closed_fits``."""
    out = {"knn_gap": 0.0, "geo_gap": 0.0, "emb_gap": 0.0}
    for f in kept:
        graph, tree = ref.knn_graph(f["x"], k)
        d_ref, _ = ref.knn_rows(f["x"], f["knn_rows"], k, tree)
        d2_got = np.sort(np.asarray(f["knn_d2"], np.float64), axis=1)
        scale = float(np.median(d_ref[:, -1]))
        out["knn_gap"] = max(out["knn_gap"], _rel_gap(
            d2_got, np.square(d_ref), scale**2))
        src = np.asarray(f["geo_src"])
        lms = np.asarray(f["lmds_src"])
        both = np.unique(np.concatenate([src, lms]))
        rows = ref.geodesic_rows(graph, both)
        at = {s: r for s, r in zip(both, rows)}
        want = np.stack([at[s] for s in src])
        typical = float(np.median(want[np.isfinite(want)]))
        out["geo_gap"] = max(out["geo_gap"], _rel_gap(
            f["geo"], want, typical))
        chart = ref.landmark_mds(np.stack([at[s] for s in lms]), lms, d)
        out["emb_gap"] = max(out["emb_gap"], ref.procrustes_disparity(
            chart, f["embedding"]))
    return out


def read_values(kept: dict, k: int) -> dict:
    """Widest mapping gap over the requests kept by
    ``generator.open_reads`` (an unanswered request is not compared here;
    it counts as failed)."""
    pairs = [(x, y) for x, y in zip(kept["x_new"], kept["y_new"])
             if y is not None]
    if not pairs:
        return {"map_gap": UNREACHABLE}
    x_new = np.concatenate([p[0] for p in pairs])
    y_got = np.concatenate([np.asarray(p[1]) for p in pairs])
    y_ref = ref.map_points(
        x_new, kept["x_base"], kept["latent"], kept["y_base"], k
    )
    y_base = np.asarray(kept["y_base"], np.float64)
    scale = float(np.sqrt(np.mean(np.sum(y_base * y_base, axis=1))))
    gap = np.sqrt(np.sum(np.square(y_got - y_ref), axis=1)) / scale
    return {"map_gap": float(np.max(gap))}


def values(kind: str, kept, cfg: dict) -> dict:
    if kind == "closed_fits":
        return fit_values(kept, cfg["k"], cfg["d"])
    return read_values(kept, cfg["k"])


def decide(vals: dict, failed: int) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}) with ``failed`` compared
    against its limit 0."""
    checks = {name: {"value": v, "limit": LIMITS[name]}
              for name, v in vals.items()}
    checks["failed"] = {"value": failed, "limit": 0}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
