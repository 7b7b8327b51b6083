"""The control: the references put in the program's place, in bfloat16.

``correct`` has to tell a sound run from one that computes below the
configuration's float32.  The control is that tempting step made whole:
the same semantics as ``bench/reference.py``, every array and every
operation in bfloat16 (eigendecompositions of the small landmark block
excepted: no bfloat16 solver exists, and that block is formed and used in
bfloat16), at the cell's own sizes.  Its outputs go through the same
comparisons (``bench/checks.py``) as a run's; a limit is sound only if
the control fails it.

    python3 bench/control.py --workload <cell> --seeds <n> <n> <n> ...

prints the control's readings for each seed, on the chip it starts on.
The benchmark's own runs never run it.

* kNN distances: ||x||^2 + ||y||^2 - 2 x.y, the form a matrix unit
  computes, in bfloat16; exact top-k on those.
* Geodesics: the symmetrised graph of those lists, Bellman-Ford sweeps
  in bfloat16 to their fixed point.
* Embedding: landmark MDS of those rows in bfloat16.
* Mapping: anchors, the k-anchor geodesic estimate and the triangulation
  in bfloat16.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BF16 = "bfloat16"


def _jnp():
    import jax
    import jax.numpy as jnp

    return jax, jnp


def knn(x, queries, k: int, *, exclude_self: bool):
    """-> (d2 (q, k) float32 of bfloat16 values, idx (q, k)): kNN of the
    rows ``queries`` (indices into x) or of new points (an array)."""
    jax, jnp = _jnp()
    xb = jnp.asarray(x, BF16)
    sq = jnp.sum(xb * xb, axis=1)
    q_idx = np.asarray(queries)
    qb = xb[q_idx] if q_idx.ndim == 1 else jnp.asarray(queries, BF16)
    out_d, out_i = [], []
    for lo in range(0, qb.shape[0], 1024):
        blk = qb[lo:lo + 1024]
        d2 = (jnp.sum(blk * blk, axis=1)[:, None] + sq[None, :]
              - 2 * jnp.dot(blk, xb.T, preferred_element_type=BF16))
        if exclude_self:
            rows = jnp.asarray(q_idx[lo:lo + 1024])
            d2 = d2.at[jnp.arange(blk.shape[0]), rows].set(jnp.inf)
        neg, idx = jax.lax.top_k(-d2, k)
        out_d.append(np.asarray(-neg, np.float32))
        out_i.append(np.asarray(idx))
    return np.concatenate(out_d), np.concatenate(out_i)


def graph(d2, idx):
    """Padded neighbour lists (nbr (n, deg), w (n, deg) bfloat16) of the
    symmetrised kNN graph."""
    n, k = idx.shape
    w = np.sqrt(np.maximum(d2, 0)).astype(np.float32)
    src = np.concatenate([np.repeat(np.arange(n), k), idx.reshape(-1)])
    dst = np.concatenate([idx.reshape(-1), np.repeat(np.arange(n), k)])
    val = np.concatenate([w.reshape(-1), w.reshape(-1)])
    order = np.lexsort((val, dst, src))
    src, dst, val = src[order], dst[order], val[order]
    first = np.ones(src.shape[0], bool)
    first[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
    src, dst, val = src[first], dst[first], val[first]
    deg = int(np.bincount(src, minlength=n).max())
    lane = np.arange(src.shape[0]) - np.searchsorted(src, src)
    nbr = np.tile(np.arange(n)[:, None], (1, deg))
    wts = np.full((n, deg), np.inf, np.float32)
    nbr[src, lane] = dst
    wts[src, lane] = val
    return nbr, wts


def geodesic_rows(nbr, w, sources):
    """(s, n) Bellman-Ford fixed point from each source, in bfloat16."""
    jax, jnp = _jnp()
    nbr_d = jnp.asarray(nbr)
    w_d = jnp.asarray(w, BF16)
    s = len(sources)
    d0 = jnp.full((s, nbr.shape[0]), jnp.inf, BF16)
    d0 = d0.at[jnp.arange(s), jnp.asarray(sources)].set(0)

    def sweep(carry):
        d, _ = carry
        new = jnp.minimum(d, jnp.min(d[:, nbr_d] + w_d[None], axis=2))
        return new, jnp.any(new != d)

    d, _ = jax.lax.while_loop(lambda c: c[1], sweep, (d0, jnp.bool_(True)))
    return np.asarray(d, np.float32)


def landmark_mds(rows, sources, d: int):
    jax, jnp = _jnp()
    d2 = jnp.square(jnp.asarray(rows, BF16))
    sub = d2[:, jnp.asarray(sources)]
    s = sub.shape[0]
    h = jnp.eye(s, dtype=BF16) - jnp.asarray(1.0 / s, BF16)
    b = -0.5 * (h @ sub @ h)
    lam, vec = np.linalg.eigh(np.asarray(b, np.float32))
    lam, vec = lam[::-1][:d], vec[:, ::-1][:, :d]
    pinv = jnp.asarray(vec / np.sqrt(np.maximum(lam, 1e-12)), BF16)
    mean2 = jnp.mean(sub, axis=1)
    y = -0.5 * jnp.dot((d2 - mean2[:, None]).T, pinv,
                       preferred_element_type=BF16)
    return np.asarray(y, np.float32)


def fit_kept(x, latent, knn_rows, sources, k: int, d: int) -> dict:
    """A fit's outputs as the control computes them, in the form
    ``generator.closed_fits`` keeps the program's."""
    d2_all, idx_all = knn(x, np.arange(x.shape[0]), k, exclude_self=True)
    nbr, w = graph(d2_all, idx_all)
    rows = geodesic_rows(nbr, w, sources)
    return {
        "x": x, "latent": latent, "knn_rows": knn_rows,
        "knn_d2": d2_all[knn_rows],
        "embedding": landmark_mds(rows, sources, d),
        "geo_src": sources, "geo": rows, "lmds_src": sources,
    }


def map_points(x_new, x_base, latent, y_base, k: int):
    """The out-of-sample mapping of ``reference.map_points`` in
    bfloat16 (A's row means squared in blocks of rows)."""
    jax, jnp = _jnp()
    d2, idx = knn(x_base, x_new, k, exclude_self=False)
    anchor = jnp.sqrt(jnp.maximum(jnp.asarray(d2, BF16), 0))
    z = jnp.asarray(latent, BF16)

    def dist(za):
        du = za[:, 0, None] - z[None, :, 0]
        dh = za[:, 1, None] - z[None, :, 1]
        return jnp.sqrt(du * du + dh * dh)

    n = z.shape[0]
    mean_sq = jnp.concatenate([
        jnp.mean(jnp.square(dist(z[lo:lo + 1024])), axis=1)
        for lo in range(0, n, 1024)
    ])
    yb = jnp.asarray(y_base, BF16)
    lam = jnp.sum(yb * yb, axis=0) / n
    pinv = yb / (lam[None, :] * n)
    out = []
    for r in range(idx.shape[0]):
        a = dist(z[jnp.asarray(idx[r])])                  # (k, n)
        geo = jnp.min(anchor[r][:, None] + a, axis=0)
        out.append(-0.5 * jnp.dot(jnp.square(geo) - mean_sq, pinv,
                                  preferred_element_type=BF16))
    return np.asarray(jnp.stack(out), np.float32)


def readings(cell: dict, cfg: dict, traffic: dict, seed: int) -> dict:
    """The control's numbers on one seed, for the inputs the cell's run
    with that seed would draw."""
    from bench import checks, data, generator
    from bench.reference import principal_chart

    n, k = cfg["n"], cfg["k"]
    if traffic["kind"] == "closed_fits":
        pick = data.rng_for(seed, 2)
        knn_rows = np.sort(pick.choice(n, traffic["check_knn_rows"],
                                       replace=False))
        sources = np.sort(pick.choice(n, traffic["check_sources"],
                                      replace=False))
        x, latent = data.points(cfg, n)
        kept = [fit_kept(x, latent, knn_rows, sources, k, cfg["d"])]
        return checks.values("closed_fits", kept, cfg)
    x, latent = data.points(cfg, n + traffic["pool"])
    x_base, lat_base, pool = x[:n], latent[:n], x[n:]
    y_base = principal_chart(lat_base)
    _, sizes = generator.schedule(traffic, 10.0, seed)
    pick = data.rng_for(seed, 4)
    x_new = [pool[pick.integers(0, pool.shape[0], s)]
             for s in sizes[: traffic["check_requests"]]]
    y_new = [map_points(xq, x_base, lat_base, y_base, k) for xq in x_new]
    kept = {"x_base": x_base, "latent": lat_base, "y_base": y_base,
            "x_new": x_new, "y_new": y_new}
    return checks.values("open_reads", kept, cfg)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0] = root
    from bench import run

    _, cell, cfg, traffic = run.find_cell(args.workload)
    run.prepare(cell["chips"])
    for seed in args.seeds:
        vals = readings(cell, cfg, traffic, seed)
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control": vals}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
