"""Benchmark harness - one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  * scaling_*      - paper Tables I-III analogue: per-stage wall time of the
                     end-to-end Isomap pipeline vs problem size n (CPU
                     measurements; the device-count dimension of the paper's
                     tables is covered by the dry-run roofline model).
  * blocksize_*    - paper Fig. 6 analogue: end-to-end time vs block size b.
  * kernel_*       - min-plus / FW / pairwise kernel microbenchmarks
                     (interpret-mode Pallas is not representative on CPU, so
                     kernels are benchmarked through their jnp reference
                     path, which is what executes off-TPU).
  * apsp2_*        - Phase-2 panel sweep: fused in-place panel kernels vs
                     the materializing min(panel, minplus(...)) composition
                     (asserted bit-identical and intermediate-free), and the
                     trace-time autotuner's tile choice vs the static
                     default under the shared roofline model.
  * knn_*          - fused top-k kNN kernel: fused distance+merge vs the
                     materializing tile-then-top_k baseline at equal tiles
                     (asserted bit-identical; zero HBM-resident distance
                     tiles on the fused path, asserted by jaxpr variable
                     counting), the kNN autotuner's tile choice vs the
                     static default, and the device-side padded-CSR build.
  * frontier_*     - sparse scale regime: landmark-panel geodesics vs the
                     dense APSP at the same n (asserted faster above the
                     crossover), the frontier autotuner's knobs vs the
                     static default under the roofline model, and the
                     (n, n)-free residency of the whole sparse path
                     (asserted by jaxpr variable counting).
  * stage_*        - per-stage breakdown at a fixed n (kNN/APSP/center/eig).

Every run also writes the collected rows to ``BENCH_<date>.json`` at the
repo root (merged by row name into an existing same-day file, so the
headline groups - apsp_phase2, frontier, and bench_serving.py's serving
rows - accumulate into one artifact CI can upload).
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _timeit(fn, *args, repeats=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


#: rows collected by :func:`_row` for the BENCH_<date>.json artifact
_ROWS: list[dict] = []


def _row(name, seconds, derived=""):
    print(f"{name},{seconds * 1e6:.1f},{derived}")
    _ROWS.append({
        "name": str(name),
        "us_per_call": round(seconds * 1e6, 1),
        "derived": str(derived),
    })


class _measuring:
    """Force the measured-autotune layer on (``refresh``) for a bench
    block, restoring the caller's mode and caches after.  The bench is
    the natural calibration entry point: its sweeps populate the store
    at ``REPRO_TUNING_PATH`` (default ``checkpoints/tuning.json``), so
    subsequent runs pick measured winners without re-timing."""

    def __enter__(self):
        from repro.kernels import autotune

        self._prev = os.environ.get("REPRO_MEASURE_AUTOTUNE")
        os.environ["REPRO_MEASURE_AUTOTUNE"] = "refresh"
        autotune.clear_cache()
        return self

    def __exit__(self, *exc):
        from repro.kernels import autotune

        if self._prev is None:
            os.environ.pop("REPRO_MEASURE_AUTOTUNE", None)
        else:
            os.environ["REPRO_MEASURE_AUTOTUNE"] = self._prev
        autotune.clear_cache()
        return False


def bench_json_path() -> str:
    """``BENCH_<date>.json`` at the repo root (the parent of this file's
    directory) - one artifact per day, shared by every bench entrypoint."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(root, f"BENCH_{time.strftime('%Y-%m-%d')}.json")


def write_bench_json(rows, path: str | None = None) -> str:
    """Merge `rows` (dicts with a ``name`` key) into the day's BENCH json.

    Later rows win on name collision, so re-running a group refreshes its
    rows in place instead of duplicating them."""
    path = path or bench_json_path()
    merged: dict[str, dict] = {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                for r in json.load(fh).get("rows", []):
                    merged[r.get("name", "")] = r
        except (OSError, ValueError):
            merged = {}
    for r in rows:
        merged[r["name"]] = dict(r)
    payload = {
        "date": time.strftime("%Y-%m-%d"),
        "backend": jax.default_backend(),
        "rows": list(merged.values()),
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, indent=1)
    os.replace(tmp, path)
    return path


def bench_scaling():
    """Tables I-III analogue: total + per-stage time vs n."""
    from repro.core import apsp, centering, graph, knn, spectral
    from repro.data import euler_isometric_swiss_roll

    for n in (256, 512, 1024):
        x, _ = euler_isometric_swiss_roll(n, seed=0)
        x = jnp.asarray(x)
        b = min(256, n)
        t_knn = _timeit(
            lambda: knn.knn_blocked(x, k=10, block=b), repeats=2
        )
        d, i = knn.knn_blocked(x, k=10, block=b)
        g = graph.knn_to_graph(d, i, n=n)
        t_apsp = _timeit(lambda: apsp.apsp_blocked(g, block=b), repeats=2)
        a = apsp.apsp_blocked(g, block=b)
        t_cen = _timeit(lambda: centering.double_center(jnp.square(a)))
        bmat = centering.double_center(jnp.square(a))
        t_eig = _timeit(
            lambda: spectral.power_iteration(bmat, d=2, max_iter=50, tol=1e-9),
            repeats=2,
        )
        total = t_knn + t_apsp + t_cen + t_eig
        _row(f"scaling_total_n{n}", total, f"n={n}")
        _row(f"scaling_knn_n{n}", t_knn, f"{t_knn / total:.0%}_of_total")
        _row(f"scaling_apsp_n{n}", t_apsp, f"{t_apsp / total:.0%}_of_total")
        _row(f"scaling_center_n{n}", t_cen, "")
        _row(f"scaling_eig_n{n}", t_eig, "")


def bench_blocksize():
    """Fig. 6 analogue: APSP time vs logical block size b at fixed n."""
    from repro.core import apsp, graph, knn
    from repro.data import euler_isometric_swiss_roll

    n = 1024
    x, _ = euler_isometric_swiss_roll(n, seed=0)
    x = jnp.asarray(x)
    d, i = knn.knn_blocked(x, k=10, block=256)
    g = graph.knn_to_graph(d, i, n=n)
    for b in (64, 128, 256, 512, 1024):
        t = _timeit(lambda: apsp.apsp_blocked(g, block=b), repeats=2)
        _row(f"blocksize_apsp_b{b}", t, f"q={n // b}")


def bench_kernels():
    from repro.kernels import ops

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.uniform(0, 10, (512, 512)), jnp.float32)
    t = _timeit(lambda: ops.minplus(a, a, mode="ref"))
    _row("kernel_minplus_512", t, f"{2 * 512**3 / t / 1e9:.1f}_Gop_s")
    t = _timeit(lambda: ops.floyd_warshall(a, mode="ref"))
    _row("kernel_fw_512", t, f"{2 * 512**3 / t / 1e9:.1f}_Gop_s")
    # fused Phase-3 update vs unfused min(G, minplus(C, R))
    g = jnp.asarray(rng.uniform(0, 30, (512, 512)), jnp.float32)
    c = jnp.asarray(rng.uniform(0, 10, (512, 64)), jnp.float32)
    r = jnp.asarray(rng.uniform(0, 10, (64, 512)), jnp.float32)
    t = _timeit(lambda: ops.minplus_update(g, c, r, mode="ref"))
    _row("kernel_minplus_update_512x64", t, "fused")
    t = _timeit(lambda: jnp.minimum(g, ops.minplus(c, r, mode="ref")))
    _row("kernel_minplus_unfused_512x64", t, "unfused_baseline")
    x = jnp.asarray(rng.normal(size=(1024, 784)), jnp.float32)
    t = _timeit(lambda: ops.pairwise_sq_dists(x, x, mode="ref"))
    _row("kernel_pairwise_1024x784", t, f"{2 * 1024 * 1024 * 784 / t / 1e9:.1f}_GFLOP_s")


def _shaped_vars(jaxpr, shape, *, skip_pallas: bool = False) -> int:
    """Count intermediate variables of `shape` across a closed jaxpr
    (recursing into sub-jaxprs).  A materializing composition carries the
    full min-plus product as an extra variable of the panel's shape; the
    fused kernels never create one.

    skip_pallas: don't recurse into pallas_call bodies.  Variables inside
    a kernel body live in VMEM by construction; with the bodies skipped,
    the count is exactly the HBM-resident variables of `shape` — a
    pallas_call *output* of that shape still counts (outvars are walked
    before params), which is what distinguishes a kernel that returns a
    distance tile from one that merges it in VMEM."""
    count = 0

    def walk(jx):
        nonlocal count
        for eq in jx.eqns:
            for v in eq.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "shape", None) == shape:
                    count += 1
            if skip_pallas and eq.primitive.name == "pallas_call":
                continue
            for sub in eq.params.values():
                subs = sub if isinstance(sub, (list, tuple)) else (sub,)
                for s in subs:
                    if hasattr(s, "jaxpr"):
                        walk(s.jaxpr)        # ClosedJaxpr (jit, loops)
                    elif hasattr(s, "eqns"):
                        walk(s)              # raw Jaxpr (shard_map body)

    walk(jaxpr.jaxpr)
    return count


def bench_apsp_phase2(smoke: bool = False):
    """Phase-2 panel sweep (--only apsp_phase2; CI runs it with --smoke).

    Three claims, asserted rather than just reported:

    1. the fused in-place panel kernels are bit-identical to the
       materializing ``min(panel, minplus(...))`` composition;
    2. the fused path materializes no (b, n)/(n, b) min-plus intermediate
       (strictly fewer panel-shaped jaxpr variables than the
       materializing baseline on the path that executes);
    3. the autotuner's tile choice beats or matches the static default
       under the shared roofline model, and the *measured* winner (the
       calibration sweep times the top-K candidates and the default on
       this device) never loses to the measured default.
    """
    from repro.kernels import autotune, ops, ref

    b, n = (128, 512) if smoke else (256, 2048)
    mode = "auto"  # what actually executes: pallas on TPU, ref elsewhere
    rng = np.random.default_rng(0)
    d = jnp.asarray(
        ref.floyd_warshall_ref(
            jnp.asarray(rng.uniform(1, 10, (b, b)), jnp.float32)
        )
    )  # FW-closed diagonal block (zero diagonal), as Phase 2 sees it
    r = jnp.asarray(rng.uniform(0, 30, (b, n)), jnp.float32)
    c = jnp.asarray(rng.uniform(0, 30, (n, b)), jnp.float32)

    panels = {
        "row": (
            (b, n),
            lambda: ops.minplus_panel_row(d, r, mode=mode),
            lambda: jnp.minimum(r, ops.minplus(d, r, mode=mode)),
        ),
        "col": (
            (n, b),
            lambda: ops.minplus_panel_col(c, d, mode=mode),
            lambda: jnp.minimum(c, ops.minplus(c, d, mode=mode)),
        ),
    }
    for name, (shape, fused_fn, mat_fn) in panels.items():
        t_fused = _timeit(fused_fn, repeats=2)
        t_mat = _timeit(mat_fn, repeats=2)
        got, want = np.asarray(fused_fn()), np.asarray(mat_fn())
        assert np.array_equal(got, want), (
            f"fused {name} panel is not bit-identical to the "
            "materializing composition"
        )
        t0 = time.perf_counter()
        n_fused = _shaped_vars(jax.make_jaxpr(fused_fn)(), shape)
        n_mat = _shaped_vars(jax.make_jaxpr(mat_fn)(), shape)
        t_probe = time.perf_counter() - t0
        assert n_fused < n_mat, (
            f"{name} panel: fused path has {n_fused} panel-shaped "
            f"intermediates vs materializing {n_mat} - the (b, n) "
            "min-plus intermediate is back"
        )
        _row(
            f"apsp2_{name}_fused_b{b}_n{n}", t_fused,
            f"{t_mat / t_fused:.2f}x_vs_materializing",
        )
        _row(f"apsp2_{name}_materializing_b{b}_n{n}", t_mat, "baseline")
        _row(
            f"apsp2_{name}_intermediates", t_probe,
            f"fused={n_fused}_materializing={n_mat}",
        )

    # border expansion (the absorb path): same fusion discipline - the
    # grown system's interior/border updates must materialize no min-plus
    # intermediate, (n, n) or panel-shaped
    from repro.core.update import (
        expand_geodesics, expand_geodesics_materializing,
    )

    m = b // 2
    e = jnp.asarray(rng.uniform(0, 30, (m, n)), jnp.float32)
    f_new = jnp.asarray(rng.uniform(0, 10, (m, m)), jnp.float32)
    f_new = jnp.minimum(f_new, f_new.T)
    f_new = jnp.where(jnp.eye(m, dtype=bool), 0.0, f_new)
    a_base = jnp.asarray(rng.uniform(0, 30, (n, n)), jnp.float32)
    a_base = jnp.minimum(a_base, a_base.T)
    a_base = jnp.where(jnp.eye(n, dtype=bool), 0.0, a_base)

    def fused_expand():
        return expand_geodesics(a_base, e, f_new, mode=mode)

    def materializing_expand():
        return expand_geodesics_materializing(a_base, e, f_new, mode=mode)

    got, want = np.asarray(fused_expand()), np.asarray(materializing_expand())
    assert np.array_equal(got, want), (
        "fused border expansion is not bit-identical to the "
        "materializing composition"
    )
    t0 = time.perf_counter()
    n_fused = _shaped_vars(jax.make_jaxpr(fused_expand)(), (n, n))
    n_mat = _shaped_vars(jax.make_jaxpr(materializing_expand)(), (n, n))
    t_probe = time.perf_counter() - t0
    assert n_fused < n_mat, (
        f"border expansion: fused path has {n_fused} (n, n)-shaped "
        f"intermediates vs materializing {n_mat} - the (n, n) min-plus "
        "intermediate is back"
    )
    t_fused = _timeit(fused_expand, repeats=2)
    t_mat = _timeit(materializing_expand, repeats=2)
    _row(
        f"apsp2_border_fused_m{m}_n{n}", t_fused,
        f"{t_mat / t_fused:.2f}x_vs_materializing",
    )
    _row(
        f"apsp2_border_intermediates", t_probe,
        f"fused={n_fused}_materializing={n_mat}",
    )

    # trace-time autotune: modeled time of the chosen config vs the
    # static default for all fused kernels at this problem shape
    shapes = {
        "minplus_panel_row": (b, n, b),
        "minplus_panel_col": (n, b, b),
        "minplus_update": (n, n, b),
        "minplus_border": (m, n, n),
    }
    for op, (m_, n_, k_) in shapes.items():
        cfg, cost = autotune.best_config(op, m_, n_, k_)
        dflt = autotune.default_config(m_, n_, k_)
        dcost = autotune.modeled_cost(op, m_, n_, k_, dflt)
        assert cost.time_s <= dcost.time_s * (1.0 + 1e-9), (
            f"autotuned {op} config {cfg} models slower than the "
            f"static default {dflt}"
        )
        _row(
            f"apsp2_autotune_{op}", cost.time_s,
            f"bm{cfg.bm}_bn{cfg.bn}_bk{cfg.bk}_u{cfg.unroll}_"
            f"{dcost.time_s / cost.time_s:.2f}x_vs_default_modeled",
        )
    # measured autotune: time the top-K modeled candidates (plus the
    # static default) on this device through the executing path and
    # report the measured winner vs the measured default.  The winner is
    # the min over a set that includes the default, so measured <=
    # default by construction; the sweep itself is the calibration that
    # populates the tuning store, and its wall time is tracked too.
    from repro.kernels import measure as kmeasure

    with _measuring():
        for op, (m_, n_, k_) in shapes.items():
            got = kmeasure.calibrate_minplus(op, m_, n_, k_, mode=mode)
            assert got is not None and got.source == "measured"
            assert got.time_s <= got.default_time_s, (
                f"measured {op} winner {got.config} slower than the "
                f"measured default {got.default_config}"
            )
            cfg = got.config
            speedup = (got.default_time_s / got.time_s
                       if got.time_s > 0 else 1.0)
            _row(
                f"apsp2_autotune_{op}_measured", got.time_s,
                f"bm{cfg.bm}_bn{cfg.bn}_bk{cfg.bk}_u{cfg.unroll}_"
                f"{speedup:.2f}x_vs_default_measured",
            )
            _row(
                f"apsp2_autotune_{op}_measure_overhead", got.sweep_s,
                "calibration_sweep",
            )


def bench_frontier(smoke: bool = False):
    """Sparse scale regime sweep (--only frontier; CI runs it --smoke).

    Three claims, asserted rather than just reported:

    1. above the crossover n, the landmark-panel geodesics beat the dense
       blocked APSP wall-clock (same graph, the panel's m rows vs all n);
    2. the frontier autotuner's (bs, bn, bucket) choice models no slower
       than the static default under the shared roofline, and the
       measured (bs, bn) winner never loses to the measured default;
    3. the jitted sparse path - CSR relaxation through panel embedding -
       carries ZERO (n, n)-shaped jaxpr variables: peak residency stays
       O(n k + m n) by construction, not by allocator luck.
    """
    from repro.core import apsp, graph, knn, sparse
    from repro.core.landmarks import hierarchical_landmarks
    from repro.data import euler_isometric_swiss_roll
    from repro.kernels import autotune

    n = 512 if smoke else 2048
    k = 10
    x, _ = euler_isometric_swiss_roll(n, seed=0)
    x = jnp.asarray(x)
    d_knn, i_knn = knn.knn_blocked(x, k=k, block=min(256, n))
    nbr, w = graph.knn_to_padded_csr(d_knn, i_knn, n=n)
    deg = nbr.shape[1]
    m = sparse.default_landmarks(n)
    lm = jnp.asarray(
        hierarchical_landmarks(np.asarray(x), np.asarray(d_knn), m=m),
        jnp.int32,
    )
    m = int(lm.shape[0])

    # 1. crossover: the (m, n) panel vs the dense (n, n) APSP, wall-clock
    g = graph.knn_to_graph(d_knn, i_knn, n=n)
    t_dense = _timeit(
        lambda: apsp.apsp_blocked(g, block=min(256, n)), repeats=2
    )
    t_sparse = _timeit(lambda: sparse.sssp_panel(nbr, w, lm), repeats=2)
    assert t_sparse < t_dense, (
        f"sparse panel ({t_sparse:.3f}s, m={m}) is not beating the dense "
        f"APSP ({t_dense:.3f}s) at n={n} - the crossover regressed"
    )
    _row(
        f"frontier_panel_m{m}_n{n}", t_sparse,
        f"{t_dense / t_sparse:.2f}x_vs_dense_apsp",
    )
    _row(f"frontier_dense_apsp_n{n}", t_dense, "baseline")

    # 2. autotuned knobs model no slower than the clamped static default
    cfg, cost = autotune.best_frontier_config(n, deg, m)
    dflt = autotune.frontier_default(n, m)
    dcost = autotune.frontier_cost(n, deg, m, dflt)
    assert cost.time_s <= dcost.time_s * (1.0 + 1e-9), (
        f"autotuned frontier config {cfg} models slower than the static "
        f"default {dflt}"
    )
    _row(
        "frontier_autotune", cost.time_s,
        f"bs{cfg.bs}_bn{cfg.bn}_bucket{cfg.bucket}_"
        f"{dcost.time_s / cost.time_s:.2f}x_vs_default_modeled",
    )
    # measured: time the top-K modeled (bs, bn) knobs on this device
    # (bucket keeps its analytic amortization applied to measured sweeps)
    from repro.kernels import measure as kmeasure

    with _measuring():
        got = kmeasure.calibrate_frontier(n, deg, m, mode="auto")
        assert got is not None and got.time_s <= got.default_time_s, (
            f"measured frontier winner {got and got.config} slower than "
            f"the measured default"
        )
        mcfg = got.config
        speedup = (got.default_time_s / got.time_s
                   if got.time_s > 0 else 1.0)
        _row(
            "frontier_autotune_measured", got.time_s,
            f"bs{mcfg.bs}_bn{mcfg.bn}_bucket{mcfg.bucket}_"
            f"{speedup:.2f}x_vs_default_measured",
        )
        _row(
            "frontier_autotune_measure_overhead", got.sweep_s,
            "calibration_sweep",
        )

    # 3. residency: the whole jitted sparse path carries no (n, n) var
    def sparse_path(nbr, w, lm):
        panel = sparse.sssp_panel(nbr, w, lm)
        return sparse.landmark_mds_general(panel, lm, d=2).embedding

    t0 = time.perf_counter()
    jx = jax.make_jaxpr(sparse_path)(nbr, w, lm)
    n_dense_vars = _shaped_vars(jx, (n, n))
    n_panel_vars = _shaped_vars(jx, (m, n))
    t_probe = time.perf_counter() - t0
    assert n_dense_vars == 0, (
        f"sparse path materializes {n_dense_vars} (n, n)-shaped jaxpr "
        "vars - the dense base is back"
    )
    assert n_panel_vars > 0, "jaxpr walk saw no (m, n) panel - bad probe"
    _row(
        "frontier_residency", t_probe,
        f"nn_vars={n_dense_vars}_panel_vars={n_panel_vars}",
    )


def bench_knn(smoke: bool = False):
    """Fused top-k kNN sweep (--only knn; CI runs it --smoke).

    Three claims, asserted rather than just reported:

    1. the fused distance+merge kNN path is bit-identical to the
       materializing compute-tile-then-top_k composition at the same
       tile sizes, and beats it wall-clock (the chunked fold wins off-TPU
       too — it tops-k over (block, k + chunk) instead of (block, block));
    2. the fused path's jaxpr carries ZERO HBM-resident variables of the
       distance-tile shape — the (bm, bn) tile lives only in VMEM —
       while the materializing baseline returns one per column step;
    3. the kNN autotuner's (bm, bn) choice models no slower than the
       clamped static default under the shared roofline, and the
       measured winner never loses to the measured default.
    """
    from repro.core import graph, knn
    from repro.data import euler_isometric_swiss_roll
    from repro.kernels import autotune

    n = 512 if smoke else 2048
    k = 10
    block = min(256, n)
    x, _ = euler_isometric_swiss_roll(n, seed=0)
    x = jnp.asarray(x)
    dfeat = x.shape[1]

    # 1. + 2. run both paths at the SAME pinned (block, block) tiles so
    # the comparison isolates the fusion, not a tile-size difference
    prev = os.environ.get(autotune.ENV_KNN_TILES)
    os.environ[autotune.ENV_KNN_TILES] = f"{block},{block}"
    autotune.clear_cache()
    knn.knn_blocked.clear_cache()
    knn.knn_blocked_materializing.clear_cache()
    try:
        def fused():
            return knn.knn_blocked(x, k=k, block=block)

        def materializing():
            return knn.knn_blocked_materializing(x, k=k, block=block)

        t_fused = _timeit(fused, repeats=2)
        t_mat = _timeit(materializing, repeats=2)
        fd, fi = fused()
        md, mi = materializing()
        assert np.array_equal(np.asarray(fd), np.asarray(md)) and (
            np.array_equal(np.asarray(fi), np.asarray(mi))
        ), "fused kNN is not bit-identical to the materializing baseline"
        assert t_fused < t_mat, (
            f"fused kNN ({t_fused:.4f}s) is not beating the "
            f"materializing baseline ({t_mat:.4f}s) at equal "
            f"({block}, {block}) tiles"
        )
        _row(
            f"knn_fused_n{n}_b{block}", t_fused,
            f"{t_mat / t_fused:.2f}x_vs_materializing",
        )
        _row(f"knn_materializing_n{n}_b{block}", t_mat, "baseline")

        # 2. residency: trace what the TPU executes (mode="pallas") and
        # count HBM-resident (block, block) vars — kernel-internal VMEM
        # vars are skipped, kernel *outputs* still count, so the
        # materializing path's returned distance tile is visible
        jx_fused = jax.make_jaxpr(
            lambda x: knn.knn_blocked(x, k=k, block=block, mode="pallas")
        )(x)
        jx_mat = jax.make_jaxpr(
            lambda x: knn.knn_blocked_materializing(
                x, k=k, block=block, mode="pallas"
            )
        )(x)
        shape = (block, block)
        t0 = time.perf_counter()
        n_fused = _shaped_vars(jx_fused, shape, skip_pallas=True)
        n_mat = _shaped_vars(jx_mat, shape, skip_pallas=True)
        t_probe = time.perf_counter() - t0
        assert n_fused == 0, (
            f"fused kNN path materializes {n_fused} ({block}, {block}) "
            "distance tiles in HBM - the fusion regressed"
        )
        assert n_mat > 0, "jaxpr walk saw no distance tile - bad probe"
        _row(
            "knn_residency", t_probe,
            f"fused_tile_vars={n_fused}_materializing={n_mat}",
        )
    finally:
        if prev is None:
            os.environ.pop(autotune.ENV_KNN_TILES, None)
        else:
            os.environ[autotune.ENV_KNN_TILES] = prev
        autotune.clear_cache()
        knn.knn_blocked.clear_cache()
        knn.knn_blocked_materializing.clear_cache()

    # 3. autotuned tiles model no slower than the clamped static default
    # (one launch = block query rows against all n points)
    cfg, cost = autotune.best_knn_config(block, n, dfeat, k)
    dflt = autotune.KnnConfig(
        min(autotune.KNN_DEFAULT.bm, block), min(autotune.KNN_DEFAULT.bn, n)
    )
    dcost = autotune.knn_cost(block, n, dfeat, k, dflt)
    assert cost.time_s <= dcost.time_s * (1.0 + 1e-9), (
        f"autotuned kNN config {cfg} models slower than the static "
        f"default {dflt}"
    )
    _row(
        "knn_autotune", cost.time_s,
        f"bm{cfg.bm}_bn{cfg.bn}_"
        f"{dcost.time_s / cost.time_s:.2f}x_vs_default_modeled",
    )
    # measured: time the top-K modeled (bm, bn) tiles through the fused
    # kernel on this device (one launch: block query rows against all n)
    from repro.kernels import measure as kmeasure

    with _measuring():
        got = kmeasure.calibrate_knn(block, n, dfeat, k, mode="auto")
        assert got is not None and got.time_s <= got.default_time_s, (
            f"measured kNN winner {got and got.config} slower than the "
            f"measured default"
        )
        mcfg = got.config
        speedup = (got.default_time_s / got.time_s
                   if got.time_s > 0 else 1.0)
        _row(
            "knn_autotune_measured", got.time_s,
            f"bm{mcfg.bm}_bn{mcfg.bn}_"
            f"{speedup:.2f}x_vs_default_measured",
        )
        _row(
            "knn_autotune_measure_overhead", got.sweep_s,
            "calibration_sweep",
        )

    # device-side CSR build on the fused path's output (one host sync
    # for the overflow scalar, no O(n k) edge-list round-trip)
    d_knn, i_knn = knn.knn_blocked(x, k=k, block=block)
    t_csr = _timeit(lambda: graph.knn_to_padded_csr(d_knn, i_knn, n=n))
    nbr, _w = graph.knn_to_padded_csr(d_knn, i_knn, n=n)
    _row(f"knn_csr_device_n{n}", t_csr, f"deg={nbr.shape[1]}")


def bench_spectral():
    """Alg. 2 convergence: iterations + time vs d."""
    from repro.core import centering, spectral
    from repro.data import euler_isometric_swiss_roll
    from repro.core import apsp, graph, knn

    n = 512
    x, _ = euler_isometric_swiss_roll(n, seed=0)
    x = jnp.asarray(x)
    d_, i_ = knn.knn_blocked(x, k=10, block=256)
    g = graph.knn_to_graph(d_, i_, n=n)
    a = apsp.apsp_blocked(g, block=256)
    bmat = centering.double_center(jnp.square(a))
    for d in (2, 3, 8):
        eig = spectral.power_iteration(bmat, d=d, max_iter=100, tol=1e-9)
        t = _timeit(
            lambda d=d: spectral.power_iteration(
                bmat, d=d, max_iter=100, tol=1e-9
            ),
            repeats=2,
        )
        _row(f"spectral_d{d}", t, f"iters={int(eig.iterations)}")


def bench_pipeline(checkpoint_secs: float | None = None):
    """Staged ManifoldPipeline end-to-end + streaming serve throughput +
    checkpoint-payload discipline (liveness pruning keeps every boundary
    O(n^2), asserted, not just reported).

    checkpoint_secs: size the APSP panel segments of the checkpointed run
    from this wall-clock target (measured per-panel time) instead of one
    segment per stage - the knob ``--checkpoint-secs`` exposes."""
    import os
    import tempfile

    from repro.checkpoint import CheckpointManager
    from repro.core.pipeline import (
        LocalBackend, ManifoldPipeline, PipelineConfig,
    )
    from repro.core.streaming import StreamingMapper
    from repro.data import euler_isometric_swiss_roll

    n, n_stream = 512, 128
    x, _ = euler_isometric_swiss_roll(n + n_stream, seed=0)
    x_base = jnp.asarray(x[:n])
    x_new = jnp.asarray(x[n:])
    pipe = ManifoldPipeline(cfg=PipelineConfig(k=10, d=2, block=128))

    def fit():
        return pipe.run(x_base)["embedding"]

    t = _timeit(fit, repeats=2)
    _row(f"pipeline_fit_n{n}", t, f"stages={len(pipe.stages)}")

    art = pipe.run(x_base)
    mapper = StreamingMapper.from_artifacts(art, k=10, batch=64)
    t = _timeit(lambda: mapper(x_new), repeats=2)
    _row(
        f"pipeline_stream_m{n_stream}", t,
        f"{n_stream / t / 1e3:.1f}_kpts_s",
    )

    # checkpoint payloads: the lifecycle engine persists only the live
    # artifact set, so no boundary may exceed ~2 (n, n) fp32 arrays (the
    # worst boundary holds geodesics + gram) + small n-sized extras
    with tempfile.TemporaryDirectory() as td:
        mgr = CheckpointManager(td, keep=100)
        ckpt_pipe = ManifoldPipeline(
            cfg=PipelineConfig(k=10, d=2, block=128), checkpoint=mgr,
            backend=LocalBackend(checkpoint_secs=checkpoint_secs),
        )
        ckpt_pipe.run(x_base)
        nn_bytes = n * n * 4
        budget = int(2.25 * nn_bytes)
        worst = 0
        for step in mgr.all_steps():
            payload = os.path.getsize(
                os.path.join(td, f"step_{step:010d}", "arrays.npz")
            )
            worst = max(worst, payload)
            assert payload <= budget, (
                f"step {step} checkpoint payload {payload}B exceeds the "
                f"O(n^2) budget {budget}B - liveness pruning regressed"
            )
        final = mgr.read_manifest(mgr.all_steps()[-1])
        dropped = {"graph", "geodesics_raw", "gram"}
        assert not dropped & set(final["keys"]), final["keys"]
        _row(
            f"pipeline_ckpt_worst_n{n}", worst / 1e6,
            f"{worst / nn_bytes:.2f}_nn_arrays",
        )

    # Phase-2 fusion discipline: the APSP segment the pipeline actually
    # runs must carry no (b, n)/(n, b) min-plus intermediate - strictly
    # fewer panel-shaped jaxpr variables than a materializing Phase 2
    from repro.core import apsp as apsp_mod
    from repro.kernels import ops as kops

    bsz = 128
    gz = jnp.zeros((n, n), jnp.float32)
    real = jax.make_jaxpr(
        lambda g: apsp_mod.apsp_blocked_segment(
            g, jnp.int32(0), jnp.int32(1), block=bsz
        )
    )(gz)

    def materializing_segment(g):
        d = kops.floyd_warshall(
            jax.lax.dynamic_slice(g, (0, 0), (bsz, bsz))
        )
        r = jax.lax.dynamic_slice(g, (0, 0), (bsz, n))
        c = jax.lax.dynamic_slice(g, (0, 0), (n, bsz))
        r = jnp.minimum(r, kops.minplus(d, r))
        c = jnp.minimum(c, kops.minplus(c, d))
        return kops.minplus_update(g, c, r)

    mat = jax.make_jaxpr(materializing_segment)(gz)
    for shape, tag in (((bsz, n), "row"), ((n, bsz), "col")):
        t0 = time.perf_counter()
        n_real = _shaped_vars(real, shape)
        n_mat = _shaped_vars(mat, shape)
        t_probe = time.perf_counter() - t0
        assert n_real < n_mat, (
            f"APSP Phase 2 {tag} panel materializes again: "
            f"{n_real} panel-shaped vars vs {n_mat} in the "
            "materializing baseline"
        )
        _row(
            f"pipeline_apsp2_{tag}_intermediates", t_probe,
            f"fused={n_real}_materializing={n_mat}",
        )


def bench_lm_smoke():
    """One smoke train-step timing per architecture family."""
    from repro.configs import get_smoke_config
    from repro.models.model import build_model
    from repro.sharding import materialize

    for arch in ("llama3-8b", "granite-moe-1b-a400m", "jamba-v0.1-52b",
                 "xlstm-350m"):
        cfg = get_smoke_config(arch)
        model = build_model(cfg)
        params = materialize(model.param_specs(), jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((2, 33), jnp.int32)}
        if cfg.kind == "encdec":
            batch["frames"] = jnp.ones((2, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        fn = jax.jit(lambda p, b: model.loss(p, b)[0])
        t = _timeit(fn, params, batch, repeats=2)
        _row(f"lm_smoke_loss_{arch}", t, "")


def bench_embedding(smoke=False):
    """Embedding-objective comparison: wall clock + residual variance of
    the spectral / stress / path tails on one dense fit (the headline
    stress-vs-spectral row the docs quote).  Asserts the stress refine
    actually lowers Sammon stress below its spectral init."""
    from repro.core import metrics
    from repro.core.pipeline import (
        LocalBackend, ManifoldPipeline, PipelineConfig, stages_for,
    )
    from repro.data import euler_isometric_swiss_roll

    n = 256 if smoke else 512
    x, _ = euler_isometric_swiss_roll(n, seed=0)
    x = jnp.asarray(x)
    for obj in ("spectral", "stress", "path"):
        cfg = PipelineConfig(
            k=10, d=2, block=min(128, n), regime="dense", objective=obj
        )
        pipe = ManifoldPipeline(
            stages_for(cfg, n), cfg=cfg, backend=LocalBackend()
        )
        t0 = time.perf_counter()
        art = pipe.run(x)
        jax.block_until_ready(art["embedding"])
        t = time.perf_counter() - t0
        rv = float(metrics.residual_variance(
            art["geodesics"], art["embedding"]
        ))
        derived = f"rv={rv:.4f}"
        if obj == "stress":
            s, s0 = float(art["stress"]), float(art["stress_init"])
            assert s < s0, (
                f"stress refine must beat its spectral init: {s} >= {s0}"
            )
            derived += f",stress={s:.4f},stress_init={s0:.4f}"
        _row(f"embedding_{obj}_n{n}", t, derived)


_BENCHES = {
    "kernels": bench_kernels,
    "apsp_phase2": bench_apsp_phase2,
    "frontier": bench_frontier,
    "knn": bench_knn,
    "scaling": bench_scaling,
    "blocksize": bench_blocksize,
    "spectral": bench_spectral,
    "pipeline": bench_pipeline,
    "embedding": bench_embedding,
    "lm": bench_lm_smoke,
}


def main() -> None:
    import argparse
    import inspect

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", choices=sorted(_BENCHES), action="append",
        help="run just the named benchmark group(s); default all "
        "(CI runs --only pipeline for the checkpoint-payload assertions "
        "and --only apsp_phase2 --smoke for the fused-panel ones)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="shrink problem sizes for CI (groups that support it)",
    )
    ap.add_argument(
        "--checkpoint-secs", type=float, default=None,
        help="target wall-clock interval between mid-stage checkpoints "
        "for the checkpointed pipeline bench (segment sizes derived from "
        "measured per-unit time)",
    )
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    for name, fn in _BENCHES.items():
        if args.only and name not in args.only:
            continue
        kwargs = {}
        params = inspect.signature(fn).parameters
        if "smoke" in params:
            kwargs["smoke"] = args.smoke
        if "checkpoint_secs" in params:
            kwargs["checkpoint_secs"] = args.checkpoint_secs
        fn(**kwargs)
    if _ROWS:
        path = write_bench_json(_ROWS)
        print(f"# wrote {len(_ROWS)} rows to {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
