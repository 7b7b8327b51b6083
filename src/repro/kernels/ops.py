"""Public jit'd wrappers for the Pallas kernels.

This module is the single dispatch point between the Pallas kernels and
their pure-jnp oracles (:mod:`repro.kernels.ref`), and the place where
tile sizes are resolved and validated.  On a TPU backend the Pallas
kernels run natively; everywhere else (this container is CPU) they
execute in interpret mode or fall back to the references, selectable via
``mode``:

  - "auto":     pallas on TPU, reference elsewhere (default; used by the
                distributed paths so dry-run lowering stays pure-XLA)
  - "pallas":   force the Pallas kernel (interpret=True off-TPU) - used by
                the kernel test suite
  - "ref":      force the jnp oracle

Tile resolution for the tiled kernels (``minplus``, ``minplus_update``,
the Phase-2 panel kernels, and the border-expansion kernel):

  1. Explicit ``bm``/``bn``/``bk``/``unroll`` kwargs win and are
     validated *up front* - a non-divisible tile raises a ``ValueError``
     naming the offending dimension instead of surfacing as a raw
     assertion from inside the Pallas trace.  The operands run as given.
  2. Otherwise every dim longer than ``autotune.WHOLE_DIM_MAX`` is
     padded with +inf to :func:`repro.kernels.autotune.padded_shape`
     (a padded contraction term is +inf and never wins a min; padded
     output rows/columns are stripped), so any shape runs on tiles the
     chip accepts.  The fused kernels then consult the trace-time
     roofline autotuner for the padded problem
     (:mod:`repro.kernels.autotune`: in-process cache, env overrides
     ``REPRO_MINPLUS_TILES`` / ``REPRO_MINPLUS_AUTOTUNE=0``).
  3. Plain ``minplus`` takes the kernels' static defaults, which divide
     every padded dim.

This module also hosts the roofline decision for the APSP Phase-2
``split_panels`` variant (:func:`auto_split_panels`): whether each mesh
rank should compute a 1/p slice of the panel product and all-gather the
result, trading redundant panel FLOPs for one extra ICI gather per
iteration.  ``REPRO_SPLIT_PANELS=0/1`` pins it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels import ref as _ref
from repro.kernels.floyd_warshall import floyd_warshall as _fw_pallas
from repro.kernels.minplus import minplus as _mp_pallas
from repro.kernels.minplus_border import minplus_border as _mb_pallas
from repro.kernels.minplus_panel import (
    minplus_panel_col as _mpc_pallas,
    minplus_panel_row as _mpr_pallas,
)
from repro.kernels.frontier import frontier_relax as _fr_pallas
from repro.kernels.knn_topk import PAD_IDX, knn_topk as _kt_pallas
from repro.kernels.minplus_update import minplus_update as _mpu_pallas
from repro.kernels.pairwise_dist import pairwise_sq_dists as _pd_pallas

ENV_SPLIT_PANELS = "REPRO_SPLIT_PANELS"


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: str) -> tuple[bool, bool]:
    """-> (use_pallas, interpret)"""
    if mode == "auto":
        return (True, False) if _on_tpu() else (False, False)
    if mode == "pallas":
        return True, not _on_tpu()
    if mode == "ref":
        return False, False
    raise ValueError(f"unknown kernel mode {mode!r}")


def _source_suffix(source: str) -> str:
    """Human-readable provenance clause for tile-validation errors, so a
    bad env pin or calibration-store entry is attributed to what
    supplied it, not to the call site."""
    if not source or source == "explicit kwargs":
        return ""
    if source.startswith("env:"):
        return f" (supplied by the {source[4:]} environment variable)"
    if source in ("store", "measured", "corrected"):
        return (" (supplied by the calibration store, see "
                "REPRO_TUNING_PATH / repro.kernels.measure)")
    return f" (supplied by {source})"


def _validate_tiles(
    name: str, m: int, n: int, k: int, tile_kw: dict,
    source: str = "explicit kwargs",
) -> None:
    """Fail fast on bad tile overrides.

    Mirrors the kernels' own clamping (``bm = min(bm, m)`` etc.) and then
    checks divisibility, so an invalid override raises a clear
    ``ValueError`` here instead of a raw assertion from inside the Pallas
    trace.  Runs regardless of dispatch path so a bad override is caught
    even where the reference implementation would silently ignore it.
    *All* invalid knobs are reported in one error (unknown keys, bad
    values, and non-dividing tiles together), and ``source`` names what
    supplied them (explicit kwargs, a ``REPRO_*_TILES`` env pin, or a
    calibration-store entry).
    """
    problems = []
    unknown = set(tile_kw) - {"bm", "bn", "bk", "unroll"}
    if unknown:
        problems.append(
            f"unknown tile kwargs {sorted(unknown)} "
            "(expected bm/bn/bk/unroll)"
        )
    bad_vals = set()
    for key, val in tile_kw.items():
        if key not in unknown and (not isinstance(val, int) or val < 1):
            bad_vals.add(key)
            problems.append(f"tile {key}={val!r} must be a positive int")

    def knob(key, dflt, cap):
        val = tile_kw.get(key, dflt)
        if key in bad_vals or not isinstance(val, int):
            val = dflt
        return min(val, cap)

    bm = knob("bm", autotune.DEFAULT.bm, m)
    bn = knob("bn", autotune.DEFAULT.bn, n)
    bk = knob("bk", autotune.DEFAULT.bk, k)
    unroll = knob("unroll", autotune.DEFAULT.unroll, bk)
    if bm >= 1 and m % bm:
        problems.append(f"bm={bm} does not divide m={m}")
    if bn >= 1 and n % bn:
        problems.append(f"bn={bn} does not divide n={n}")
    if bk >= 1 and k % bk:
        problems.append(f"bk={bk} does not divide k={k}")
    if unroll >= 1 and bk >= 1 and bk % unroll:
        problems.append(f"unroll={unroll} does not divide bk={bk}")
    if problems:
        raise ValueError(
            f"{name}: invalid tile override for ({m}, {n}) with "
            f"contraction {k}: " + "; ".join(problems)
            + _source_suffix(source)
        )


def _tiles(
    op: str, m: int, n: int, k: int, tile_kw: dict, *, tuned: bool = True
) -> tuple[dict, tuple[int, int, int]]:
    """Resolve the tile kwargs for one min-plus launch -> (tile kwargs,
    the (m, n, k) the kernel runs at).  An explicit override is validated
    against the operands as given, which then run unpadded; otherwise
    the problem is :func:`autotune.padded_shape` and, when ``tuned``,
    the autotuner tiles it (env pins, the measured-calibration store,
    then the analytic sweep)."""
    if tile_kw:
        _validate_tiles(op, m, n, k, tile_kw)
        return tile_kw, (m, n, k)
    m, n, k = autotune.padded_shape(m, n, k)
    if not tuned:
        return {}, (m, n, k)
    resolved, source = autotune.resolve_tiles(op, m, n, k)
    if resolved:
        # analytic configs divide by construction; this guards the
        # REPRO_MINPLUS_TILES env pin and calibration-store entries with
        # the same clear error, attributed to their source
        _validate_tiles(op, m, n, k, resolved, source=source)
    return resolved, (m, n, k)


def _pad_inf(x, rows: int, cols: int):
    """``x`` padded with +inf to (rows, cols)."""
    pr, pc = rows - x.shape[0], cols - x.shape[1]
    if not (pr or pc):
        return x
    return jnp.pad(x, ((0, pr), (0, pc)), constant_values=jnp.inf)


def minplus(a, b, *, mode: str = "auto", **tile_kw):
    """Tropical (min-plus) matrix product C[i,j] = min_k A[i,k] + B[k,j].

    a (m, k), b (k, n) -> (m, n).  Tile kwargs (bm/bn/bk/unroll) are
    validated up front; without them the operands are padded and the
    kernel's static defaults apply.
    """
    m, k = a.shape
    n = b.shape[1]
    tile_kw, (mp, np_, kp) = _tiles("minplus", m, n, k, tile_kw,
                                    tuned=False)
    use_pallas, interpret = _resolve(mode)
    if not use_pallas:
        return _ref.minplus_ref(a, b)
    out = _mp_pallas(_pad_inf(a, mp, kp), _pad_inf(b, kp, np_),
                     interpret=interpret, **tile_kw)
    return out[:m, :n]


def minplus_update(g, c, r, *, mode: str = "auto", **tile_kw):
    """Fused Phase-3 relaxation O = min(G, C (x) R) without the (m, n)
    min-plus intermediate.

    g (m, n), c (m, k), r (k, n) -> (m, n).  The accumulator is seeded
    from G's tile, so the product C (x) R never exists in HBM.  Tiles:
    explicit kwargs win (validated up front), else the trace-time
    autotuner picks per-shape (see :mod:`repro.kernels.autotune`).
    """
    m, n = g.shape
    k = c.shape[1]
    tile_kw, (mp, np_, kp) = _tiles("minplus_update", m, n, k, tile_kw)
    use_pallas, interpret = _resolve(mode)
    if not use_pallas:
        return _ref.minplus_update_ref(g, c, r)
    out = _mpu_pallas(
        _pad_inf(g, mp, np_), _pad_inf(c, mp, kp), _pad_inf(r, kp, np_),
        interpret=interpret, **tile_kw,
    )
    return out[:m, :n]


def minplus_panel_row(d, r, *, mode: str = "auto", **tile_kw):
    """Fused Phase-2 row-panel update R' = min(R, D (x) R).

    d (b, b) is the Floyd-Warshall-closed diagonal block, r (b, n) the
    block row.  R is both the accumulator seed and the contraction
    operand, so no (b, n) min-plus intermediate is materialized - the
    update is in place at the tile level.  Bit-identical to
    :func:`repro.kernels.ref.minplus_panel_row_ref` on every backend.
    Tiles: explicit kwargs win (validated up front), else autotuned.
    """
    b, n = r.shape
    tile_kw, (bp, np_, _) = _tiles("minplus_panel_row", b, n, b, tile_kw)
    use_pallas, interpret = _resolve(mode)
    if not use_pallas:
        return _ref.minplus_panel_row_ref(d, r)
    out = _mpr_pallas(_pad_inf(d, bp, bp), _pad_inf(r, bp, np_),
                      interpret=interpret, **tile_kw)
    return out[:b, :n]


def minplus_panel_col(c, d, *, mode: str = "auto", **tile_kw):
    """Fused Phase-2 column-panel update C' = min(C, C (x) D).

    c (m, b) is the block column, d (b, b) the Floyd-Warshall-closed
    diagonal block.  C is both the accumulator seed and the contraction
    operand, so no (m, b) min-plus intermediate is materialized.
    Bit-identical to :func:`repro.kernels.ref.minplus_panel_col_ref` on
    every backend.  Tiles: explicit kwargs win (validated up front),
    else autotuned.
    """
    m, b = c.shape
    tile_kw, (mp, bp, _) = _tiles("minplus_panel_col", m, b, b, tile_kw)
    use_pallas, interpret = _resolve(mode)
    if not use_pallas:
        return _ref.minplus_panel_col_ref(c, d)
    out = _mpc_pallas(_pad_inf(c, mp, bp), _pad_inf(d, bp, bp),
                      interpret=interpret, **tile_kw)
    return out[:m, :b]


def minplus_border(e, a, *, mode: str = "auto", **tile_kw):
    """Fused border relaxation B = min(E, E (x) A) without the (m, n)
    min-plus intermediate.

    e (m, n) border edge rows, a (n, n) closed base system -> (m, n).
    The first step of incremental geodesic expansion
    (:mod:`repro.core.update`): the new points' edge rows are relaxed
    through the base matrix with the accumulator seeded from E.
    Bit-identical to :func:`repro.kernels.ref.minplus_border_ref` on
    every backend.  Tiles: explicit kwargs win (validated up front),
    else autotuned.
    """
    m, n = e.shape
    tile_kw, (mp, np_, _) = _tiles("minplus_border", m, n, n, tile_kw)
    use_pallas, interpret = _resolve(mode)
    if not use_pallas:
        return _ref.minplus_border_ref(e, a)
    out = _mb_pallas(_pad_inf(e, mp, np_), _pad_inf(a, np_, np_),
                     interpret=interpret, **tile_kw)
    return out[:m, :n]


def frontier_relax(dist, nbr, w, hi, *, mode: str = "auto", **tile_kw):
    """One masked frontier-relaxation sweep over the padded-CSR graph,
    nodes-major: O[j,q] = min(D[j,q], min_d where(D[nbr[j,d], q] < hi)
    + w[j,d]).

    dist (n, s) with the s sources on the lanes, nbr/w (n, deg), hi
    scalar -> (n, s).  The only tile knob is ``bn`` (node rows per grid
    step); without it the frontier autotuner picks per-shape
    (``REPRO_FRONTIER_TILES=bs,bn,bucket`` pins all three solver knobs,
    :func:`repro.kernels.autotune.frontier_config`).  ``n`` is padded
    internally to a ``bn`` multiple with +inf-weight self-edges, so
    padded rows never win the min and real rows are bit-identical to
    the unpadded oracle.
    """
    n, s = dist.shape
    deg = nbr.shape[1]
    problems = []
    unknown = set(tile_kw) - {"bn"}
    if unknown:
        problems.append(
            f"unknown tile kwargs {sorted(unknown)} (expected bn)"
        )
    bn = tile_kw.get("bn")
    if bn is not None and (not isinstance(bn, int) or bn < 1):
        problems.append(f"tile bn={bn!r} must be a positive int")
    if problems:
        raise ValueError(
            f"frontier_relax: invalid tile override for ({n}, {s}): "
            + "; ".join(problems)
        )
    if bn is None:
        bn = autotune.frontier_config(n, deg, s).bn
    bn = min(bn, n)
    use_pallas, interpret = _resolve(mode)
    if not use_pallas:
        return _ref.frontier_relax_ref(dist, nbr, w, hi)
    pad = -n % bn
    if pad:
        dist = jnp.pad(dist, ((0, pad), (0, 0)), constant_values=jnp.inf)
        nbr = jnp.pad(nbr, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)), constant_values=jnp.inf)
    out = _fr_pallas(dist, nbr, w, hi, bn=bn, interpret=interpret)
    return out[:n] if pad else out


def floyd_warshall(d, *, mode: str = "auto"):
    """In-VMEM Floyd-Warshall closure of a dense (b, b) block (Phase 1)."""
    use_pallas, interpret = _resolve(mode)
    if use_pallas:
        return _fw_pallas(d, interpret=interpret)
    return _ref.floyd_warshall_ref(d)


def pairwise_sq_dists(x, y, *, mode: str = "auto", **tile_kw):
    """Squared Euclidean distances between rows of x (m, D) and y (n, D).

    Tiles: explicit ``bm``/``bn``/``bd`` kwargs win and are validated up
    front (a non-dividing override raises a ``ValueError`` naming the
    shapes and tiles instead of surfacing as the kernel's raw assert);
    otherwise :func:`repro.kernels.autotune.pairwise_tiles` picks tiles
    the chip accepts and the operands are zero-padded to tile multiples
    (zero rows are stripped from the result; zero features add exact
    zeros), so arbitrary shapes run on the Pallas path without the
    caller tiling by hand.
    """
    m, d = x.shape
    n, d2 = y.shape
    if d != d2:
        raise ValueError(
            f"pairwise_sq_dists: feature dims differ: x {(m, d)} vs "
            f"y {(n, d2)}"
        )
    problems = []
    unknown = set(tile_kw) - {"bm", "bn", "bd"}
    if unknown:
        problems.append(
            f"unknown tile kwargs {sorted(unknown)} (expected bm/bn/bd)"
        )
    bad_vals = set()
    for key, val in tile_kw.items():
        if key not in unknown and (not isinstance(val, int) or val < 1):
            bad_vals.add(key)
            problems.append(f"tile {key}={val!r} must be a positive int")
    auto = autotune.pairwise_tiles(m, n, d)
    tiles = {**auto, **{k_: v for k_, v in tile_kw.items()
                        if k_ not in unknown and k_ not in bad_vals}}
    for key, dim, name in (("bm", m, "m"), ("bn", n, "n"), ("bd", d, "D")):
        tiles[key] = min(tiles[key], dim)
        if key in tile_kw and key not in bad_vals and dim % tiles[key]:
            problems.append(
                f"{key}={tiles[key]} does not divide {name}={dim}"
            )
    if problems:
        raise ValueError(
            f"pairwise_sq_dists: invalid tile override for "
            f"({m}, {d})x({n}, {d}): " + "; ".join(problems)
        )
    use_pallas, interpret = _resolve(mode)
    if not use_pallas:
        return _ref.pairwise_sq_dists_ref(x, y)
    pm, pn, pd = -m % tiles["bm"], -n % tiles["bn"], -d % tiles["bd"]
    if pm or pn or pd:
        x = jnp.pad(x, ((0, pm), (0, pd)))
        y = jnp.pad(y, ((0, pn), (0, pd)))
    out = _pd_pallas(x, y, interpret=interpret, **tiles)
    return out[:m, :n] if pm or pn else out


def knn_topk(
    x,
    y,
    seed_d,
    seed_i,
    *,
    row0=0,
    col0=0,
    n_valid=None,
    mode: str = "auto",
    **tile_kw,
):
    """Fused distances + per-row top-k merge: rank y's rows into x's
    running candidate lists without the (m, n) distance matrix.

    x (m, D) query rows at global row offset ``row0``; y (n, D)
    candidate rows at global column offset ``col0``; seed_d/seed_i
    (m, k) the incoming candidate lists ((+inf, -1) when empty) —
    seeding is what chains the kernel across column tiles and ring
    steps.  Columns at or beyond ``n_valid`` (a global count, default
    ``col0 + n``; traced values fine) and each row's self-match are
    masked to (+inf, -1) in-kernel.  Returns (dists (m, k) f32,
    idx (m, k) int32) ranked by (distance, then column index); rows
    with fewer than k live candidates carry (+inf, -1) tails.

    Tiles: explicit ``bm``/``bn`` kwargs win (any positive size — the
    wrapper pads m/n to tile multiples and strips the pad); otherwise
    the trace-time roofline autotuner picks per shape
    (``REPRO_KNN_TILES=bm,bn`` / ``REPRO_KNN_AUTOTUNE=0`` pin, see
    :func:`repro.kernels.autotune.knn_config`).  Bit-identical to
    :func:`repro.kernels.ref.knn_topk_ref` across tilings.
    """
    m, dfeat = x.shape
    n, d2 = y.shape
    if dfeat != d2:
        raise ValueError(
            f"knn_topk: feature dims differ: x {(m, dfeat)} vs "
            f"y {(n, d2)}"
        )
    if seed_d.ndim != 2 or seed_d.shape[0] != m:
        raise ValueError(
            f"knn_topk: seed_d {seed_d.shape} must be (m={m}, k)"
        )
    k = seed_d.shape[1]
    if seed_i.shape != (m, k):
        raise ValueError(
            f"knn_topk: seed_i {seed_i.shape} must match seed_d "
            f"{seed_d.shape}"
        )
    problems = []
    unknown = set(tile_kw) - {"bm", "bn"}
    if unknown:
        problems.append(
            f"unknown tile kwargs {sorted(unknown)} (expected bm/bn)"
        )
    for key, val in tile_kw.items():
        if key not in unknown and (not isinstance(val, int) or val < 1):
            problems.append(f"tile {key}={val!r} must be a positive int")
    if problems:
        raise ValueError(
            f"knn_topk: invalid tile override for ({m}, {n}) with "
            f"k={k}: " + "; ".join(problems)
        )
    if "bm" in tile_kw and "bn" in tile_kw:
        # fully pinned: skip resolution entirely (this is also what the
        # measured-calibration sweep relies on to avoid re-entering the
        # autotuner while timing candidates)
        bm, bn = min(tile_kw["bm"], m), min(tile_kw["bn"], n)
    else:
        cfg = autotune.knn_config(m, n, dfeat, k)
        bm = min(tile_kw.get("bm", cfg.bm), m)
        bn = min(tile_kw.get("bn", cfg.bn), n)

    # one set of norms per call, for whichever path runs (see
    # ref.sq_norms for why neither path recomputes them)
    x2 = _ref.sq_norms(x)
    y2 = _ref.sq_norms(y)
    use_pallas, interpret = _resolve(mode)
    if not use_pallas:
        return _ref.knn_topk_ref(
            x, y, seed_d, seed_i, x2, y2,
            row0=row0, col0=col0, n_valid=n_valid, chunk=bn,
        )

    # the kernel masks columns >= hi: both the caller's global validity
    # bound and this call's own row padding are upper bounds on the
    # contiguous [col0, col0 + n) range, so one scalar carries both
    c0 = jnp.asarray(col0, jnp.int32)
    hi = c0 + n if n_valid is None else jnp.minimum(
        c0 + n, jnp.asarray(n_valid, jnp.int32)
    )
    meta = jnp.stack([jnp.asarray(row0, jnp.int32), c0, hi])
    y2 = y2.T
    seed_d = seed_d.astype(jnp.float32)
    seed_i = seed_i.astype(jnp.int32)
    pm, pn = -m % bm, -n % bn
    if pm:
        x = jnp.pad(x, ((0, pm), (0, 0)))
        x2 = jnp.pad(x2, ((0, pm), (0, 0)))
        seed_d = jnp.pad(seed_d, ((0, pm), (0, 0)),
                         constant_values=jnp.inf)
        seed_i = jnp.pad(seed_i, ((0, pm), (0, 0)),
                         constant_values=PAD_IDX)
    if pn:
        y = jnp.pad(y, ((0, pn), (0, 0)))
        y2 = jnp.pad(y2, ((0, 0), (0, pn)))
    out_d, out_i = _kt_pallas(
        x, y, x2, y2, seed_d, seed_i, meta, bm=bm, bn=bn,
        interpret=interpret,
    )
    return (out_d[:m], out_i[:m]) if pm else (out_d, out_i)


# ---------------------------------------------- Phase-2 panel splitting ----


def auto_split_panels(
    n: int, b: int, pd: int, pm: int, *, itemsize: int = 4
) -> bool:
    """Roofline decision for the APSP Phase-2 split-panel variant.

    In the baseline schedule every rank of a row/column group redundantly
    computes the full panel product (the paper's one-block-one-task
    mapping); with ``split_panels`` each rank computes a 1/p slice in
    place and the group all-gathers the result.  Worth it exactly when
    the redundant-FLOP saving outruns the extra gather:

      saved  = 2 b^2 (n/pm) (1 - 1/pd) / VPU  +  2 b^2 (n/pd) (1 - 1/pm) / VPU
      gather = itemsize * (b (n/pm) (pd-1)/pd + (n/pd) b (pm-1)/pm) / ICI

    using the shared machine constants from :mod:`repro.kernels.autotune`
    (single source with the stage-level rooflines).  The split is only
    legal when the per-rank slice stays tile-aligned: ``b`` divisible by
    both mesh axes with the slice at least one (8,)-sublane register row.

    ``REPRO_SPLIT_PANELS=1`` / ``0`` pins the decision (an illegal forced
    split is still refused).  Consulted by
    :func:`repro.core.apsp.make_apsp_segment` when ``split_panels`` is
    left unset.
    """
    aligned = (
        pd > 1 or pm > 1
    ) and b % pd == 0 and b % pm == 0 and (b // pd) % 8 == 0 \
        and (b // pm) % 8 == 0
    raw = os.environ.get(ENV_SPLIT_PANELS)
    if raw is not None:
        want = raw.strip().lower() not in ("0", "false", "off", "")
        return want and aligned
    if not aligned:
        return False
    nr, nc = n // pd, n // pm
    saved = (
        2.0 * b * b * nc * (1.0 - 1.0 / pd)
        + 2.0 * b * b * nr * (1.0 - 1.0 / pm)
    ) / autotune.chip().vpu_ops
    gather = itemsize * (
        b * nc * (pd - 1) / pd + nr * b * (pm - 1) / pm
    ) / autotune.chip().ici_bw
    return saved > gather
