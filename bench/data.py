"""Inputs of every cell: one point set per configuration.

The Euler-isometric Swiss roll (Schoeneman et al. 2017, the dataset of
arXiv:1808.10776 section IV): a strip of the plane (arc length u, height
h) wound onto the spiral r = t in 3-D, parametrised by arc length so that
geodesic distance on the roll equals Euclidean distance in the strip.  The
strip is convex, so the exact geodesic between two points is the straight
segment between their latent coordinates; the references use that.

This is a copy of the program's generator, kept here so that the
yardstick does not move when the program changes.
"""
from __future__ import annotations

import numpy as np

T_SPAN = (np.pi, 4 * np.pi)
HEIGHT = 20.0


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...): every fit, pool
    and sample of a run has its own, so the same seed gives the same
    inputs whatever ran before."""
    return np.random.default_rng([seed % 2**63, *stream])


def _arc_length_table():
    ts = np.linspace(*T_SPAN, 20001)
    ds = np.sqrt(ts**2 + 1.0)
    s = np.concatenate(
        [[0.0], np.cumsum(0.5 * (ds[1:] + ds[:-1]) * np.diff(ts))]
    )
    return ts, s


def swiss_roll(n: int, rng: np.random.Generator):
    """-> (x (n, 3) float32, latent (n, 2) float64): points on the roll
    and their (arc length, height) coordinates in the strip."""
    ts, s = _arc_length_table()
    u = rng.uniform(0.0, s[-1], n)
    h = rng.uniform(0.0, HEIGHT, n)
    t = np.interp(u, s, ts)
    x = np.stack([t * np.cos(t), h, t * np.sin(t)], axis=1)
    return x.astype(np.float32), np.stack([u, h], axis=1)


#: data sets by the name a configuration's ``dataset`` gives
DATASETS = {"euler_isometric_swiss_roll": swiss_roll}


def points(cfg: dict, n: int):
    """-> (x, latent) of ``n`` points of the configuration's data set.

    The points are one fixed draw, the deployment's data (``draw`` in the
    configuration), in one fixed order, whatever the seed: every run does
    the same work.  (Any reordering moves float32 rounding, and with it
    where the dense eigensolver's iteration reaches a fixed point: fits
    of different draws took 15.408 or 15.556 s.)  A run's seed draws
    what is checked, and which points the reads ask for."""
    x, latent = DATASETS[cfg["dataset"]](n, rng_for(cfg["draw"], 0))
    if x.shape[1] != cfg["D"]:
        raise ValueError(f"{cfg['dataset']} has D = {x.shape[1]}, the "
                         f"configuration states {cfg['D']}")
    return x, latent
