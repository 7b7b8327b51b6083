"""Trace-time tile autotuner for the fused min-plus Pallas kernels.

The fused kernels (:func:`repro.kernels.ops.minplus_update`,
:func:`~repro.kernels.ops.minplus_panel_row`,
:func:`~repro.kernels.ops.minplus_panel_col`,
:func:`~repro.kernels.ops.minplus_border`) take static tile sizes
``(bm, bn, bk, unroll)``.  The historical defaults (256, 256, 256, 8) are
a fine center of the space but are not optimal for every problem shape:
small panels leave the grid degenerate and big tiles can blow the VMEM
working set.

This module picks the tiles **at trace time** from an analytic roofline
model (:data:`CHIPS` holds its machine constants).
Min-plus runs on the VPU (the MXU systolic array only does *,+), so a
candidate's cost is::

    time = max(compute, memory)
    compute = 2*m*n*k / (vpu_ops * lane_fill * sublane_fill)
              * bundles_per_vreg_term / 0.5
    memory  = HBM bytes(tiling) / hbm_bw

where ``lane_fill``/``sublane_fill`` penalize tiles under the (8, 128)
VPU register shape, ``bundles_per_vreg_term`` is the kernel's static
schedule at the block's register sub-tile over its VALU floor of 0.5
(:data:`repro.kernels.minplus.BUNDLES_PER_VREG_TERM`: a narrow block is
bound by lane broadcasts, not by the VALU), and HBM bytes count the seed
read + output write + the per-grid-pass contraction re-reads (``a`` is
re-read n/bn times, ``b`` m/bm times).  Candidates whose double-buffered
VMEM working set exceeds the budget are discarded.  ``unroll`` is not
swept: it is inert in the kernel, and every candidate carries the
default grouping (:func:`grouping`).

A dim longer than :data:`WHOLE_DIM_MAX` is tiled only in multiples of the
chip's (8, 128) register tiling; :func:`padded_shape` is the problem
ops.py runs after padding such a dim with +inf, and the sweep tiles that
problem.

The sweep is pure arithmetic over some tens of candidates, cached
in-process per ``(op, m, n, k, itemsize)`` - so the cost is paid once per
problem shape per process, at trace time, exactly like the kernels' own
jit cache.

Overrides (both read at every :func:`tiles_for` call):

* ``REPRO_MINPLUS_TILES="bm,bn,bk,unroll"`` - pin all four knobs for
  every fused kernel call (the kernels still clamp to the problem shape;
  non-divisible pins fail fast with a ``ValueError`` in ops.py).
* ``REPRO_MINPLUS_AUTOTUNE=0`` - disable the sweep and use the static
  defaults.

Explicit tile kwargs at an ``ops.*`` call site always win over both.

Between the env pins and the analytic sweep sits the **measured
calibration layer** (:mod:`repro.kernels.measure`): when
``REPRO_MEASURE_AUTOTUNE`` enables it (or a persisted calibration store
exists at ``REPRO_TUNING_PATH``), per-device measured winners and
fitted machine-constant corrections are consulted before the analytic
model — see that module for the store format and semantics.
"""
from __future__ import annotations

import functools
import math
import os
from typing import Iterator, NamedTuple

from repro.kernels.minplus import (
    BUNDLES_PER_VREG_TERM, MAX_RN, row_scratch, subtile,
)

# ----------------------------------------------------------- machine model --


class Chip(NamedTuple):
    """Per-chip machine constants of one TPU generation."""

    peak_flops: float   # bf16 FLOP/s (MXU) - reference only; min-plus is VPU
    vpu_ops: float      # f32 elementwise ops/s
    hbm_bw: float       # bytes/s
    ici_bw: float       # bytes/s per link
    vmem_bytes: int     # scoped VMEM a kernel gets without vmem_limit_bytes
    source: str


#: machine constants keyed by ``jax.Device.device_kind``
CHIPS = {
    "TPU v5 lite": Chip(
        peak_flops=197e12,
        vpu_ops=3.9e12,
        hbm_bw=819e9,
        ici_bw=50e9,
        vmem_bytes=16 * 2**20,
        source=(
            "Google Cloud TPU v5e documentation: 197 TFLOP/s bf16, "
            "819 GB/s HBM, 1,600 Gbit/s ICI (taken as 4 links of "
            "50 GB/s). vpu_ops is derived (8x128 lanes x 4 ALUs), not "
            "published; vmem_bytes is the compiler's default scoped limit"
        ),
    ),
}

#: the chip tiles are picked for where no TPU is attached (the CPU times
#: nothing, but its tiles must be ones this chip's compiler accepts)
DEFAULT_CHIP = "TPU v5 lite"


@functools.lru_cache(maxsize=1)
def chip() -> Chip:
    """Constants of the chip this process computes on.

    A TPU whose ``device_kind`` has no entry in :data:`CHIPS` raises
    rather than being tuned with another chip's numbers; off-TPU the
    :data:`DEFAULT_CHIP` entry applies.
    """
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind if dev.platform == "tpu" else DEFAULT_CHIP
    if kind not in CHIPS:
        raise ValueError(
            f"no machine constants for device_kind {kind!r}; add an "
            f"entry with its source to repro.kernels.autotune.CHIPS "
            f"(known: {sorted(CHIPS)})"
        )
    return CHIPS[kind]


def vmem_budget() -> int:
    """VMEM a kernel's pipelined working set (double-buffered streamed
    inputs) may take on the chip in use (:func:`chip`): half the scoped
    limit, leaving headroom for the compiler's own temporaries."""
    return chip().vmem_bytes // 2

ENV_TILES = "REPRO_MINPLUS_TILES"
ENV_AUTOTUNE = "REPRO_MINPLUS_AUTOTUNE"

#: ops that seed the accumulator from an (m, n) input (one extra HBM read)
FUSED_OPS = (
    "minplus_update", "minplus_panel_row", "minplus_panel_col",
    "minplus_border",
)
_UNSEEDED = ("minplus",)


class TileConfig(NamedTuple):
    """Static tile knobs of one fused min-plus kernel launch."""

    bm: int
    bn: int
    bk: int
    unroll: int


DEFAULT = TileConfig(bm=256, bn=256, bk=256, unroll=8)


class Cost(NamedTuple):
    """Roofline terms for one (config, problem) pair, in seconds/bytes."""

    time_s: float
    compute_s: float
    hbm_s: float
    hbm_bytes: float
    vmem_bytes: int


def clamp(cfg: TileConfig, m: int, n: int, k: int) -> TileConfig:
    """Clamp a config to the problem dims exactly like the kernels do
    (``bm = min(bm, m)`` etc., ``unroll = min(unroll, bk)``)."""
    bm, bn, bk = min(cfg.bm, m), min(cfg.bn, n), min(cfg.bk, k)
    return TileConfig(bm, bn, bk, min(cfg.unroll, bk))


def divides(cfg: TileConfig, m: int, n: int, k: int) -> bool:
    """True when the (clamped) config tiles the problem exactly."""
    c = clamp(cfg, m, n, k)
    return (
        m % c.bm == 0 and n % c.bn == 0 and k % c.bk == 0
        and c.bk % c.unroll == 0
    )


def modeled_cost(
    op: str, m: int, n: int, k: int, cfg: TileConfig, *, itemsize: int = 4,
    hbm_bw: float | None = None, launch_s: float = 0.0,
) -> Cost:
    """Roofline terms for running ``op`` on an (m, n) output with
    contraction depth k under tile config ``cfg``.

    ``op``: one of :data:`FUSED_OPS` (seeded accumulate) or
    ``"minplus"`` (plain product, no seed read).

    ``hbm_bw``/``launch_s`` override the analytic machine constants —
    the measured-calibration layer (:mod:`repro.kernels.measure`) passes
    the per-device fitted bandwidth and launch cost here so unmeasured
    shapes are ranked under the corrected model.
    """
    if op not in FUSED_OPS and op not in _UNSEEDED:
        raise ValueError(f"unknown op {op!r}; expected one of "
                         f"{FUSED_OPS + _UNSEEDED}")
    bm, bn, bk, _ = clamp(cfg, m, n, k)
    seeded = op in FUSED_OPS

    hw = chip()
    # compute: 2 VPU ops (add + min) per (i, j, k) triple, derated by
    # register fill and by the sub-tile's schedule over the VALU floor
    lane_fill = min(bn, 128) / 128.0
    sublane_fill = min(bm, 8) / 8.0
    eff_ops = hw.vpu_ops * lane_fill * sublane_fill
    compute_s = (2.0 * m * n * k) / eff_ops * (
        bundles_per_vreg_term(bm, bn) / 0.5)

    # memory: contraction operands are re-fetched once per orthogonal
    # grid pass; seed read + output write land once per output tile
    hbm_bytes = itemsize * (
        m * k * (n // bn)          # a tiles, re-read per j pass
        + k * n * (m // bm)        # b tiles, re-read per i pass
        + m * n                    # output write
        + (m * n if seeded else 0)  # seed read
    )
    hbm_s = hbm_bytes / (hbm_bw if hbm_bw else hw.hbm_bw)

    # VMEM working set: a + b tiles (double-buffered while streaming),
    # output tile (+ seed tile), and the kernel's row copy of b
    vmem = itemsize * (
        2 * (bm * bk + bk * bn)
        + (3 if seeded else 2) * bm * bn
        + sum(math.prod(r.shape) for r in row_scratch(bk, bn, "float32"))
    )
    return Cost(
        time_s=max(compute_s, hbm_s) + launch_s,
        compute_s=compute_s,
        hbm_s=hbm_s,
        hbm_bytes=float(hbm_bytes),
        vmem_bytes=vmem,
    )


def bundles_per_vreg_term(bm: int, bn: int) -> float:
    """Bundles per vreg-term of the kernel's schedule at a (bm, bn)
    block: the entry of its register sub-tile's width in vregs
    (:func:`repro.kernels.minplus.subtile`), or of the widest tabled
    width below it."""
    v = -(-subtile(bm, bn)[1] // 128)
    return BUNDLES_PER_VREG_TERM[max(w for w in BUNDLES_PER_VREG_TERM if w <= v)]


#: longest dim a block may span whole; a longer dim is tiled in multiples
#: of the chip's (8, 128) register tiling, so one block never holds more
#: than this many rows or lanes of an unaligned dim
WHOLE_DIM_MAX = 256


def padded_dim(dim: int) -> int:
    """``dim`` itself up to :data:`WHOLE_DIM_MAX`, else rounded up to a
    multiple of it.  A function of the value alone, so the dims a kernel
    binds to one buffer (a panel's rows and its contraction) pad alike,
    and the static default's 256-tiles divide every padded dim."""
    if dim <= WHOLE_DIM_MAX:
        return dim
    return -(-dim // WHOLE_DIM_MAX) * WHOLE_DIM_MAX


def padded_shape(m: int, n: int, k: int) -> tuple[int, int, int]:
    """The (m, n, k) problem ops.py runs a min-plus kernel at: each dim
    :func:`padded_dim`-padded with +inf, which never wins a min."""
    return padded_dim(m), padded_dim(n), padded_dim(k)


def _tile_sizes(dim: int, *, cap: int = 512, align: int = 8) -> list[int]:
    """Power-of-two tile sizes up to ``cap`` dividing ``dim`` that are
    multiples of ``align``, plus ``dim`` itself when it is at most
    :data:`WHOLE_DIM_MAX`.  The chip takes a block dim that is a multiple
    of its (8, 128) register tiling or the whole array dim: ``align`` is
    8 for a sublane dim, 128 for a lane dim.  Every :func:`padded_dim`
    gets at least one size."""
    sizes = [t for t in (8, 16, 32, 64, 128, 256, 512, 1024)
             if t % align == 0 and t <= min(dim, cap) and dim % t == 0]
    if dim <= WHOLE_DIM_MAX:
        sizes.append(dim)
    return sorted(set(sizes))


#: largest row tile.  The kernel's register sub-tile keeps its schedule
#: the same whatever the rows (512 rows compile for a v5e), so taller
#: blocks only cut the re-reads of ``b``, which the compute-bound kernel
#: does not wait for, at twice the seed and output tiles' VMEM
MAX_BM = 256


def grouping(bk: int) -> int:
    """The ``unroll`` every candidate carries: the default grouping,
    halved until it divides ``bk``."""
    u = min(DEFAULT.unroll, bk)
    while bk % u:
        u //= 2
    return u


def candidates(m: int, n: int, k: int) -> Iterator[TileConfig]:
    """Enumerate tile configs for the :func:`padded_shape` of an
    (m, n, k) problem: power-of-two tiles dividing each padded dim that
    the chip's compiler accepts (``bn`` and ``bk`` are lane dims of some
    block; ``bn`` up to the widest register sub-tile), each with the
    default :func:`grouping`.  The (clamped) static default is included
    when it is such a tiling."""
    m, n, k = padded_shape(m, n, k)
    seen = set()
    for bm in _tile_sizes(m, cap=MAX_BM):
        for bn in _tile_sizes(n, cap=MAX_RN, align=128):
            for bk in _tile_sizes(k, align=128):
                cfg = TileConfig(bm, bn, bk, grouping(bk))
                if cfg not in seen:
                    seen.add(cfg)
                    yield cfg
    dflt = clamp(DEFAULT, m, n, k)
    if dflt not in seen and divides(dflt, m, n, k) and _chip_legal(dflt, m, n, k):
        yield dflt


def _chip_legal(cfg: TileConfig, m: int, n: int, k: int) -> bool:
    """The (clamped) config is a tiling the chip's compiler accepts."""
    c = clamp(cfg, m, n, k)
    return (
        c.bm <= MAX_BM and (c.bm % 8 == 0 or c.bm == m)
        and (c.bn % 128 == 0 or c.bn == n)
        and (c.bk % 128 == 0 or c.bk == k)
    )


@functools.lru_cache(maxsize=4096)
def best_config(
    op: str, m: int, n: int, k: int, *, itemsize: int = 4,
    hbm_bw: float | None = None, launch_s: float = 0.0,
) -> tuple[TileConfig, Cost]:
    """Sweep :func:`candidates` under :func:`modeled_cost` and return the
    winner with its cost, both for the :func:`padded_shape` of the
    problem.  Cached in-process per (op, m, n, k, itemsize); by
    construction the winner's modeled time never exceeds the static
    default's (the default is part of the sweep).  ``hbm_bw``/
    ``launch_s`` rank under measured-corrected machine constants."""
    m, n, k = padded_shape(m, n, k)
    best = None
    fallback = None  # smallest-working-set candidate, if none fit budget
    budget = vmem_budget()
    for cfg in candidates(m, n, k):
        cost = modeled_cost(op, m, n, k, cfg, itemsize=itemsize,
                            hbm_bw=hbm_bw, launch_s=launch_s)
        fkey = (cost.vmem_bytes, cost.time_s)
        if fallback is None or fkey < fallback[0]:
            fallback = (fkey, cfg, cost)
        if cost.vmem_bytes > budget:
            continue
        # tie-break toward larger tiles (fewer grid steps, less refetch)
        key = (cost.time_s, (m // cfg.bm) * (n // cfg.bn) * (k // cfg.bk),
               -(cfg.bm * cfg.bn))
        if best is None or key < best[0]:
            best = (key, cfg, cost)
    if best is None:
        # every candidate busts the budget: return the smallest working
        # set rather than a non-divisible config
        best = fallback
    return best[1], best[2]


def default_config(m: int, n: int, k: int) -> TileConfig:
    """The static default, clamped to the problem shape."""
    return clamp(DEFAULT, m, n, k)


def _parse_knobs(env: str, raw: str, names: tuple[str, ...]):
    """Parse an env tile pin into ints, reporting *all* invalid knobs in
    one ValueError that names the env var that supplied them."""
    parts = raw.split(",")
    if len(parts) != len(names):
        count = ("two", "three", "four")[len(names) - 2]
        raise ValueError(
            f"{env}={raw!r}: expected '{','.join(names)}' "
            f"({count} comma-separated ints)"
        )
    vals, problems = [], []
    for name, part in zip(names, parts):
        try:
            val = int(part)
        except ValueError:
            problems.append(f"{name}={part!r} is not an int")
            continue
        if val < 1:
            problems.append(f"{name}={val} must be >= 1")
        vals.append(val)
    if problems:
        kind = "tiles" if names[0] == "bm" else "knobs"
        raise ValueError(
            f"{env}={raw!r}: {kind} must be >= 1 ints: "
            + "; ".join(problems)
        )
    return vals


def _parse_override(raw: str) -> TileConfig:
    return TileConfig(
        *_parse_knobs(ENV_TILES, raw, ("bm", "bn", "bk", "unroll"))
    )


def _measure_layer():
    """Lazy import of the measured-calibration layer (it imports this
    module at top level, so the dependency must point one way)."""
    from repro.kernels import measure

    return measure


def resolve_tiles(
    op: str, m: int, n: int, k: int, *, itemsize: int = 4
) -> tuple[dict, str]:
    """Resolve the tile kwargs for one fused-kernel launch, with
    provenance.  Resolution order:

    1. ``REPRO_MINPLUS_TILES=bm,bn,bk,unroll`` — pinned for every call
       (absolute precedence over the calibration store).
    2. ``REPRO_MINPLUS_AUTOTUNE=0`` — empty dict (kernels' static
       defaults apply; the measured layer is bypassed too).
    3. The measured-calibration layer (:mod:`repro.kernels.measure`):
       persisted per-device winners, a fresh measurement sweep when
       ``REPRO_MEASURE_AUTOTUNE`` enables one, or the analytic sweep
       re-ranked under measured-corrected constants.
    4. Otherwise the cached analytic roofline sweep
       (:func:`best_config`).

    Returns ``(tile kwargs, source)`` where source names what supplied
    the tiles (``"env:REPRO_MINPLUS_TILES"``, ``"store"``,
    ``"measured"``, ``"corrected"``, ``"modeled"``, or ``"default"``) —
    ops.py puts the source in its validation errors.
    """
    raw = os.environ.get(ENV_TILES)
    if raw:
        return _parse_override(raw)._asdict(), f"env:{ENV_TILES}"
    if os.environ.get(ENV_AUTOTUNE, "1").lower() in ("0", "false", "off"):
        return {}, "default"
    measure = _measure_layer()
    if measure.active():
        got = measure.resolve_minplus(op, m, n, k, itemsize=itemsize)
        if got is not None:
            cfg, source = got
            return cfg._asdict(), source
    cfg, _ = best_config(op, m, n, k, itemsize=itemsize)
    return cfg._asdict(), "modeled"


def tiles_for(op: str, m: int, n: int, k: int, *, itemsize: int = 4) -> dict:
    """Resolve the tile kwargs for one fused-kernel launch (see
    :func:`resolve_tiles` for the resolution order; this wrapper drops
    the provenance).  Returns a dict suitable for ``**kwargs`` into the
    kernel wrappers."""
    return resolve_tiles(op, m, n, k, itemsize=itemsize)[0]


# ------------------------------------------------------- frontier kernel --
# Knobs of the sparse frontier-relaxation kernel (repro.kernels.frontier)
# and its SSSP driver (repro.core.sparse.sssp_panel).  Unlike the min-plus
# family, the tunables span two layers: ``bn`` is the kernel's node-tile
# height, while ``bs`` (sources per launch) and ``bucket`` (masked sweeps
# per convergence check) belong to sssp_panel — they are tuned together
# because the per-sweep gathered (deg, n, bs) block couples them.

ENV_FRONTIER_TILES = "REPRO_FRONTIER_TILES"
ENV_FRONTIER_AUTOTUNE = "REPRO_FRONTIER_AUTOTUNE"

#: prior on the rounds a batch runs: its *slowest* source's sweeps to
#: settle (about the kNN graph's hop eccentricity, which the max over a
#: batch's sources saturates at); only the ratio of check cost to sweep
#: cost times this prior steers ``bucket``, so a mis-estimate moves the
#: knob logarithmically.
FRONTIER_SWEEPS_PRIOR = 32

#: lane width of the chip's (8, 128) register and HBM tiling: the
#: frontier's sources ride the lanes, so a batch of fewer sources still
#: moves and computes a full lane tile
LANES = 128

#: fixed cost of one gathered row, on top of its bytes (the gather is
#: charged per row: deg * n rows a sweep whatever their width).  On a
#: TPU v5e a sweep at n = 40960, deg = 20, bs = 128 (819200 rows of
#: 512 B) took 2.05 ms, 0.43 ms more than its 1.32 GB at the HBM peak.
FRONTIER_GATHER_ROW_S = 5e-10


class FrontierConfig(NamedTuple):
    """Static knobs of one sparse-geodesic solve."""

    bs: int      # landmark sources per kernel launch (the lanes)
    bn: int      # node rows per grid step
    bucket: int  # masked sweeps between convergence checks


FRONTIER_DEFAULT = FrontierConfig(bs=LANES, bn=256, bucket=4)


def _lanes(s: int) -> int:
    """Lanes an s-wide minor dim occupies in the chip's tiling."""
    return -(-s // LANES) * LANES


def frontier_landmark_s(
    n: int, m: int, cfg: FrontierConfig, sweep_s: float, *,
    itemsize: int = 4, hbm_bw: float | None = None,
) -> float:
    """Panel time per landmark of m sources solved in ``cfg.bs``-wide
    batches, from one sweep's time: each batch runs as many rounds as
    its slowest source needs (:data:`FRONTIER_SWEEPS_PRIOR`, whatever
    ``bs``) plus the expected (bucket-1)/2 sweeps of bucket overshoot,
    and reads its (n, bs) pair once per ``bucket`` sweeps for the
    convergence check; the m sources take ``ceil(m / bs)`` batches (the
    last shifted back, its overlap recomputed)."""
    bw = hbm_bw if hbm_bw else chip().hbm_bw
    check_s = itemsize * 2 * n * _lanes(cfg.bs) / bw
    rounds = FRONTIER_SWEEPS_PRIOR
    batch_s = (sweep_s * (rounds + (cfg.bucket - 1) / 2.0)
               + check_s * rounds / cfg.bucket)
    m = max(m, 1)
    return -(-m // min(cfg.bs, m)) * batch_s / m


def frontier_cost(
    n: int, deg: int, m: int, cfg: FrontierConfig, *, itemsize: int = 4,
    hbm_bw: float | None = None, launch_s: float = 0.0,
) -> Cost:
    """Roofline terms of the frontier solve of m landmark sources, with
    sources on the lanes, per landmark.

    One sweep: the XLA row gather pulls deg * n rows of the (n, bs)
    distances into the (deg, n, bs) block, charged per row
    (:data:`FRONTIER_GATHER_ROW_S`) plus its bytes read and written; the
    kernel reads that block, the (n, deg) weights and the (n, bs) seed
    and writes the (n, bs) result; the VPU does 3 ops (mask select, add,
    running min) per (node, slot, lane).  Every lane-minor array moves
    and computes whole 128-lane tiles (:func:`_lanes`), so a batch under
    the lane width costs a full-width sweep.

    ``time_s`` is the panel time per landmark
    (:func:`frontier_landmark_s`: batches, each as long as its slowest
    source), so configs with different batch sizes are comparable;
    ``hbm_bytes`` and ``hbm_s`` are one sweep's.
    """
    hw = chip()
    bw = hbm_bw if hbm_bw else hw.hbm_bw
    lanes = _lanes(cfg.bs)
    rows = deg * n
    compute_s = 3.0 * rows * lanes / hw.vpu_ops
    hbm_bytes = itemsize * (
        3 * rows * lanes     # gathered block: gather read + write, kernel read
        + n * _lanes(deg)    # w, its deg-wide rows lane-padded
        + 2 * n * lanes      # seed read, output write
        + n * deg            # nbr, read by the gather
    )
    hbm_s = hbm_bytes / bw
    sweep_s = rows * FRONTIER_GATHER_ROW_S + max(compute_s, hbm_s) + launch_s
    time_s = frontier_landmark_s(n, m, cfg, sweep_s, itemsize=itemsize,
                                 hbm_bw=bw)
    # double-buffered (deg, bn, bs) gathered tiles, (bn, deg) weight tiles
    # and (bn, bs) seed + output tiles, plus the running min and one slot
    vmem = itemsize * cfg.bn * (
        2 * deg * lanes + 2 * _lanes(deg) + 4 * lanes + 2 * lanes
    )
    return Cost(
        time_s=time_s,
        compute_s=compute_s,
        hbm_s=hbm_s,
        hbm_bytes=float(hbm_bytes),
        vmem_bytes=vmem,
    )


def frontier_batch(m: int) -> int:
    """Landmark sources per batch: the lane width, or all m sources
    where fewer (a full-dim lane block).  Single source of the batch
    sssp_panel and the stage segmentation both use (units = ceil(m /
    frontier_batch))."""
    return max(1, min(m, LANES))


def frontier_candidates(
    n: int, deg: int, m: int
) -> Iterator[FrontierConfig]:
    """Enumerate frontier configs: the lane-width batch
    (:func:`frontier_batch`; a narrower one moves and computes the same
    lane tiles for fewer sources, in more batches), node tiles of 64 to
    2048 rows (multiples of the chip's 8-row sublane tiling; ops.py pads
    n to a multiple, so no divisibility constraint), buckets 1..16."""
    bs = frontier_batch(m)
    for bn in (64, 128, 256, 512, 1024, 2048):
        if bn > n and bn != 64:
            continue
        for bucket in (1, 2, 4, 8, 16):
            yield FrontierConfig(bs, min(bn, n), bucket)


def frontier_default(n: int, m: int) -> FrontierConfig:
    """:data:`FRONTIER_DEFAULT` clamped to the problem."""
    return FrontierConfig(
        min(FRONTIER_DEFAULT.bs, frontier_batch(m)),
        min(FRONTIER_DEFAULT.bn, n),
        FRONTIER_DEFAULT.bucket,
    )


@functools.lru_cache(maxsize=4096)
def best_frontier_config(
    n: int, deg: int, m: int, *, itemsize: int = 4,
    hbm_bw: float | None = None, launch_s: float = 0.0,
) -> tuple[FrontierConfig, Cost]:
    """Sweep :func:`frontier_candidates` under :func:`frontier_cost`; the
    (clamped) default is part of the sweep so the winner never models
    slower than it.  Candidates busting VMEM fall back to the smallest
    working set.  ``hbm_bw``/``launch_s`` rank under measured-corrected
    constants."""
    best = None
    fallback = None
    seen = set()
    budget = vmem_budget()
    dflt = frontier_default(n, m)
    for cfg in list(frontier_candidates(n, deg, m)) + [dflt]:
        if cfg in seen:
            continue
        seen.add(cfg)
        cost = frontier_cost(n, deg, m, cfg, itemsize=itemsize,
                             hbm_bw=hbm_bw, launch_s=launch_s)
        fkey = (cost.vmem_bytes, cost.time_s)
        if fallback is None or fkey < fallback[0]:
            fallback = (fkey, cfg, cost)
        if cost.vmem_bytes > budget:
            continue
        key = (cost.time_s, -cfg.bs, -cfg.bn)
        if best is None or key < best[0]:
            best = (key, cfg, cost)
    if best is None:
        best = fallback
    return best[1], best[2]


def _parse_frontier_override(raw: str) -> FrontierConfig:
    return FrontierConfig(
        *_parse_knobs(ENV_FRONTIER_TILES, raw, ("bs", "bn", "bucket"))
    )


def resolve_frontier_config(
    n: int, deg: int, m: int
) -> tuple[FrontierConfig, str]:
    """Resolve the frontier knobs for one sparse-geodesic solve, with
    provenance (same ordering as :func:`resolve_tiles`):

    1. ``REPRO_FRONTIER_TILES=bs,bn,bucket`` — pinned.
    2. ``REPRO_FRONTIER_AUTOTUNE=0`` — the static default, clamped to
       the problem (:func:`frontier_default`).
    3. The measured-calibration layer (persisted winner / fresh sweep /
       corrected-constant re-rank).
    4. Otherwise the cached analytic sweep
       (:func:`best_frontier_config`).
    """
    raw = os.environ.get(ENV_FRONTIER_TILES)
    if raw:
        return _parse_frontier_override(raw), f"env:{ENV_FRONTIER_TILES}"
    if os.environ.get(ENV_FRONTIER_AUTOTUNE, "1").lower() in (
        "0", "false", "off"
    ):
        return frontier_default(n, m), "default"
    measure = _measure_layer()
    if measure.active():
        got = measure.resolve_frontier(n, deg, m)
        if got is not None:
            return got
    cfg, _ = best_frontier_config(n, deg, m)
    return cfg, "modeled"


def frontier_config(n: int, deg: int, m: int) -> FrontierConfig:
    """:func:`resolve_frontier_config` without the provenance."""
    return resolve_frontier_config(n, deg, m)[0]


# ----------------------------------------------------- fused kNN kernel --
# Knobs of the fused top-k kNN kernel (repro.kernels.knn_topk): (bm, bn)
# query/candidate tile sizes.  Unlike the min-plus family the distance
# tile rides the MXU (f32 matmul) while the k-merge selection is VPU
# work, so the cost model sums both terms; and because ops.knn_topk pads
# to tile multiples, candidates need not divide the problem — padded
# fractions are charged in the model instead.

ENV_KNN_TILES = "REPRO_KNN_TILES"
ENV_KNN_AUTOTUNE = "REPRO_KNN_AUTOTUNE"


class KnnConfig(NamedTuple):
    """Static tile knobs of one fused kNN kernel launch."""

    bm: int
    bn: int


KNN_DEFAULT = KnnConfig(bm=256, bn=256)


def knn_cost(
    m: int, n: int, d: int, k: int, cfg: KnnConfig, *, itemsize: int = 4,
    hbm_bw: float | None = None, launch_s: float = 0.0,
) -> Cost:
    """Roofline terms for one fused kNN launch: m query rows against n
    candidate rows of depth d, keeping k per row.

    Compute is MXU matmul (2 m n d f32 FLOPs over the padded problem)
    plus the VPU k-merge: per (bm, bn) tile, k extraction steps over the
    (bm, bn + k) candidate stream at ~6 elementwise ops each (min,
    compare, masked-min, select x2, retire), derated by register fill.
    HBM traffic is the tiled re-reads of the point blocks plus one
    seed-read / output-write of the (m, k) lists — the distance tile
    itself never reaches HBM, which is the point of the fusion.
    """
    hw = chip()
    bm, bn = min(cfg.bm, m), min(cfg.bn, n)
    mp = -(-m // bm) * bm       # padded problem dims (ops.knn_topk pads)
    np_ = -(-n // bn) * bn
    gm, gn = mp // bm, np_ // bn

    matmul_s = (2.0 * mp * np_ * d) / (hw.peak_flops / 2)
    lane_fill = min(bn + k, 128) / 128.0
    sublane_fill = min(bm, 8) / 8.0
    select_s = (6.0 * k * (bn + k) * bm * gm * gn) / (
        hw.vpu_ops * lane_fill * sublane_fill
    )
    compute_s = matmul_s + select_s

    hbm_bytes = itemsize * (
        mp * d * gn        # x tiles, re-read per column pass
        + np_ * d * gm     # y tiles, re-read per row pass
        + 2 * mp * k       # seed lists read (dists + indices)
        + 2 * mp * k       # output lists write
    )
    hbm_s = hbm_bytes / (hbm_bw if hbm_bw else hw.hbm_bw)

    # VMEM: double-buffered point tiles, the distance tile, the
    # (bm, bn + k) vals/idxs/pos merge working set, running + output lists
    vmem = itemsize * (
        2 * (bm * d + bn * d)
        + bm * bn
        + 3 * bm * (bn + k)
        + 4 * bm * k
    )
    return Cost(
        time_s=max(compute_s, hbm_s) + launch_s,
        compute_s=compute_s,
        hbm_s=hbm_s,
        hbm_bytes=float(hbm_bytes),
        vmem_bytes=vmem,
    )


def _pow2_tiles(dim: int, *, cap: int = 512) -> list[int]:
    """Power-of-two tile sizes up to the first one covering ``dim`` (no
    divisibility requirement — ops.knn_topk pads to a tile multiple)."""
    return [t for t in (8, 16, 32, 64, 128, 256, 512)
            if t <= cap and (t == 8 or t < 2 * dim)]


def knn_candidates(m: int, n: int, k: int) -> Iterator[KnnConfig]:
    """Enumerate fused-kNN tile configs; the clamped static default is
    always included so the winner never models slower than it.  ``bn``
    is a lane dim of the kernel's (1, bn) norm block, so it is a multiple
    of 128 or, for n < 128, the whole dim."""
    seen = set()
    for bm in _pow2_tiles(m):
        for bn in [t for t in _pow2_tiles(n) if t % 128 == 0] or [n]:
            cfg = KnnConfig(bm, bn)
            if cfg not in seen:
                seen.add(cfg)
                yield cfg
    dflt = KnnConfig(min(KNN_DEFAULT.bm, m), min(KNN_DEFAULT.bn, n))
    if dflt not in seen:
        yield dflt


@functools.lru_cache(maxsize=4096)
def best_knn_config(
    m: int, n: int, d: int, k: int, *, itemsize: int = 4,
    hbm_bw: float | None = None, launch_s: float = 0.0,
) -> tuple[KnnConfig, Cost]:
    """Sweep :func:`knn_candidates` under :func:`knn_cost`; candidates
    busting the VMEM budget fall back to the smallest working set.
    ``hbm_bw``/``launch_s`` rank under measured-corrected constants."""
    best = None
    fallback = None
    budget = vmem_budget()
    for cfg in knn_candidates(m, n, k):
        cost = knn_cost(m, n, d, k, cfg, itemsize=itemsize,
                        hbm_bw=hbm_bw, launch_s=launch_s)
        fkey = (cost.vmem_bytes, cost.time_s)
        if fallback is None or fkey < fallback[0]:
            fallback = (fkey, cfg, cost)
        if cost.vmem_bytes > budget:
            continue
        # tie-break toward larger tiles (fewer grid passes, less refetch)
        key = (cost.time_s, -(cfg.bm * cfg.bn))
        if best is None or key < best[0]:
            best = (key, cfg, cost)
    if best is None:
        best = fallback
    return best[1], best[2]


def _parse_knn_override(raw: str) -> KnnConfig:
    return KnnConfig(*_parse_knobs(ENV_KNN_TILES, raw, ("bm", "bn")))


def resolve_knn_config(
    m: int, n: int, d: int, k: int
) -> tuple[KnnConfig, str]:
    """Resolve the fused-kNN tiles for one launch, with provenance
    (same ordering as :func:`resolve_tiles`):

    1. ``REPRO_KNN_TILES=bm,bn`` — pinned for every call.
    2. ``REPRO_KNN_AUTOTUNE=0`` — the static default, clamped.
    3. The measured-calibration layer (persisted winner / fresh sweep /
       corrected-constant re-rank).
    4. Otherwise the cached analytic sweep (:func:`best_knn_config`).
    """
    raw = os.environ.get(ENV_KNN_TILES)
    if raw:
        return _parse_knn_override(raw), f"env:{ENV_KNN_TILES}"
    if os.environ.get(ENV_KNN_AUTOTUNE, "1").lower() in (
        "0", "false", "off"
    ):
        return KnnConfig(
            min(KNN_DEFAULT.bm, m), min(KNN_DEFAULT.bn, n)
        ), "default"
    measure = _measure_layer()
    if measure.active():
        got = measure.resolve_knn(m, n, d, k)
        if got is not None:
            return got
    cfg, _ = best_knn_config(m, n, d, k)
    return cfg, "modeled"


def knn_config(m: int, n: int, d: int, k: int) -> KnnConfig:
    """:func:`resolve_knn_config` without the provenance."""
    return resolve_knn_config(m, n, d, k)[0]


# --------------------------------------------------- pairwise auto-shrink --


def pairwise_tiles(m: int, n: int, d: int, *, cap: int = 512) -> dict:
    """Tiles for the (non-fused) pairwise kernel — the path
    :func:`repro.kernels.ops.pairwise_sq_dists` takes when no explicit
    tiles are given.  A dim up to ``cap`` is one whole-dim tile; a longer
    one gets ``cap``-wide tiles (a multiple of the chip's (8, 128) register
    tiling) and the wrapper pads it to a tile multiple, so every shape
    gets a tiling the chip's compiler accepts."""
    return {"bm": min(m, cap), "bn": min(n, cap), "bd": min(d, cap)}


def clear_cache() -> None:
    """Drop the in-process sweep caches AND the measured layer's
    store-backed caches (tests / constant or store hot-swapping)."""
    chip.cache_clear()
    best_config.cache_clear()
    best_frontier_config.cache_clear()
    best_knn_config.cache_clear()
    _measure_layer().clear_cache()
