"""Smoke run of exact Isomap (fit, serve, absorb) on TPU chips.

    python chip_smoke.py             # one chip: phases (b)-(d) below
    python chip_smoke.py --chips 4   # the 2x2 mesh fit vs the one-chip fit

Everything runs in this one process, through the entry points a user
calls (:func:`repro.launch.serve.serve_manifold`, ``ManifoldPipeline``):

(a) device check: without a TPU the script names the device it found and
    exits non-zero; it never carries on on the CPU.
(b) dense: fit n_base = 16384 on the Euler-isometric Swiss roll, absorb
    two flushes of 32 arrivals (the second grows a base of 16416 points,
    no multiple of 128) and serve 1024 reads; the lowered APSP segment
    must hold ``tpu_custom_call`` (the Pallas kernels ran, not the
    references).
(c) reference: at n = 4096 the fit with ``kernel_mode="ref"`` and the
    default fit agree — kNN lists and geodesics bit for bit, the
    oracles' contract.
(d) sparse: fit n_base = 65536 (a size the dense budget refuses) with
    default landmarks, absorb two flushes and serve; the frontier
    solver's rows at that size agree with a host Dijkstra on the same
    graph.

``--chips 4`` runs only the mesh phase: ``serve_manifold`` on a (2, 2)
mesh at n_base = 16384 with two absorb flushes, and the mesh fit's kNN
lists, geodesics and embedding against the one-chip fit on device 0.

Each phase prints its numbers on its own line; every time is cold-cache
set-up (compilation included), not a steady-state speed.  The last line
of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
#: quickstart's limit on the Procrustes disparity to the latent chart
PROCRUSTES_LIMIT = 5e-3
#: geodesic agreement when the kernel's and XLA's distance products
#: differ in the last bit (then kNN lists must still agree)
GEODESIC_RTOL = 1e-6
#: mesh vs one-chip embedding, as a share of the embedding's range
EMBEDDING_RTOL = 1e-3
#: every serving phase absorbs 64 arrivals in two flushes of 32: the
#: second grows a base the first left at no multiple of 128
ABSORB = dict(absorb=64, absorb_flushes=2)


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(phase: str, **nums) -> None:
    body = " ".join(f"{k}={v}" for k, v in nums.items())
    print(f"[{phase}] {body}", flush=True)


def peak_bytes(dev) -> int | str:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def check_absorbs(phase: str, out: dict) -> None:
    check(out["absorbed"] > 0, f"{phase} absorb folded nothing in")
    check(out["serving_version"] >= ABSORB["absorb_flushes"],
          f"{phase}: {out['serving_version']} absorb flushes published, "
          f"{ABSORB['absorb_flushes']} expected")


def serve_numbers(out: dict) -> dict:
    return {
        "fit_s": out["fit_s"],
        "serve_pts_s": out["points_per_s"],
        "p50_ms": out["latency_p50_ms"],
        "p99_ms": out["latency_p99_ms"],
        "absorbed": out["absorbed"],
        "serving_version": out["serving_version"],
        "procrustes_error": out["procrustes_error"],
        "residual_variance": out["residual_variance"],
    }


def phase_dense(dev, *, n_base=16384, n_stream=1024, block=128):
    import jax
    import jax.numpy as jnp

    from repro.core import apsp
    from repro.launch.serve import serve_manifold

    out = serve_manifold(
        regime="dense", n_base=n_base, k=10, d=2, block=block,
        n_stream=n_stream, stream_batch=64, **ABSORB,
    )
    log("dense", **serve_numbers(out), peak_bytes_in_use=peak_bytes(dev))
    check(out["regime"] == "dense", f"dense phase ran {out['regime']}")
    check(out["procrustes_error"] < PROCRUSTES_LIMIT,
          f"dense procrustes {out['procrustes_error']} >= {PROCRUSTES_LIMIT}")
    check_absorbs("dense", out)
    g = jax.ShapeDtypeStruct((n_base, n_base), jnp.float32)
    lowered = apsp.apsp_blocked_segment.lower(
        g, jnp.int32(0), jnp.int32(1), block=block, mode="auto"
    ).as_text()
    check("tpu_custom_call" in lowered,
          "APSP segment lowered without tpu_custom_call (reference ran)")
    log("dense", apsp_tpu_custom_call=True)


def _fit(x, *, mode, block, backend=None):
    from repro.core.pipeline import (
        ManifoldPipeline, PipelineConfig, stages_for,
    )

    cfg = PipelineConfig(k=10, d=2, block=block, regime="dense",
                         kernel_mode=mode)
    pipe = ManifoldPipeline(
        stages_for(cfg, x.shape[0]), cfg=cfg, backend=backend,
        exports=("knn_dists", "knn_idx", "geodesics", "embedding"),
    )
    return pipe.run(x)


def phase_reference(dev, *, n=4096, block=128):
    import jax.numpy as jnp
    import numpy as np

    from repro.data import euler_isometric_swiss_roll

    x, _ = euler_isometric_swiss_roll(n, seed=0)
    x = jnp.asarray(x)
    t0 = time.time()
    ref = _fit(x, mode="ref", block=block)
    t_ref = time.time() - t0
    t0 = time.time()
    ker = _fit(x, mode="auto", block=block)
    t_ker = time.time() - t0
    a = {k: np.asarray(ref[k]) for k in ("knn_dists", "knn_idx", "geodesics")}
    b = {k: np.asarray(ker[k]) for k in ("knn_dists", "knn_idx", "geodesics")}
    check(np.array_equal(a["knn_idx"], b["knn_idx"]),
          "kNN lists differ between kernel_mode='ref' and the kernels")
    same_dists = np.array_equal(a["knn_dists"], b["knn_dists"])
    same_geo = np.array_equal(a["geodesics"], b["geodesics"])
    fin = np.isfinite(a["geodesics"])
    rel = float(np.max(
        np.abs(a["geodesics"][fin] - b["geodesics"][fin])
        / np.maximum(np.abs(a["geodesics"][fin]), 1e-30)
    ))
    log("reference", n=n, fit_ref_s=t_ref, fit_kernels_s=t_ker,
        knn_idx_equal=True, knn_dists_bit_equal=same_dists,
        geodesics_bit_equal=same_geo, geodesics_max_rel_diff=rel,
        peak_bytes_in_use=peak_bytes(dev))
    if same_dists:
        check(same_geo, "same kNN graph, yet geodesics differ (min-plus "
              "kernels broke bit-identity)")
    else:
        check(np.array_equal(fin, np.isfinite(b["geodesics"]))
              and rel <= GEODESIC_RTOL,
              f"geodesics differ by {rel} > {GEODESIC_RTOL}")


def phase_sparse(dev, *, n_base=65536, n_stream=256, sources=16):
    import jax.numpy as jnp
    import numpy as np
    import scipy.sparse
    import scipy.sparse.csgraph

    from repro.core import graph, knn, sparse
    from repro.data import euler_isometric_swiss_roll
    from repro.launch.serve import serve_manifold

    out = serve_manifold(
        regime="sparse", n_base=n_base, k=10, d=2, n_stream=n_stream,
        stream_batch=64, **ABSORB,
    )
    log("sparse", **serve_numbers(out),
        dense_budget_ok=sparse.dense_budget_ok(n_base),
        peak_bytes_in_use=peak_bytes(dev))
    check(out["regime"] == "sparse", f"sparse phase ran {out['regime']}")
    check(out["procrustes_error"] < PROCRUSTES_LIMIT,
          f"sparse procrustes {out['procrustes_error']} >= "
          f"{PROCRUSTES_LIMIT}")
    check_absorbs("sparse", out)

    # the frontier solver at this size against a host Dijkstra on the
    # same padded-CSR graph (tests/test_sparse.py's 1e-5 tolerance)
    x, _ = euler_isometric_swiss_roll(n_base + n_stream, seed=0)
    x = jnp.asarray(x[:n_base])
    d, i = knn.knn_blocked(x, k=10, block=128)
    nbr, w = graph.knn_to_padded_csr(d, i, n=n_base)
    src = np.linspace(0, n_base - 1, sources).astype(np.int32)
    t0 = time.time()
    panel = np.asarray(sparse.sssp_panel(nbr, w, jnp.asarray(src)))
    t_panel = time.time() - t0
    nbr_np, w_np = np.asarray(nbr), np.asarray(w).astype(np.float64)
    live = np.isfinite(w_np)
    cols = np.broadcast_to(np.arange(n_base)[:, None], nbr_np.shape)
    adj = scipy.sparse.csr_matrix(
        (w_np[live], (nbr_np[live], cols[live])), shape=(n_base, n_base)
    )
    want = scipy.sparse.csgraph.dijkstra(adj, indices=src)
    check(np.array_equal(np.isinf(panel), np.isinf(want)),
          "sparse panel reachability differs from Dijkstra")
    fin = np.isfinite(want)
    err = np.abs(panel[fin] - want[fin])
    check(bool(np.all(err <= 1e-5 + 1e-5 * np.abs(want[fin]))),
          f"sparse panel off Dijkstra by up to {float(err.max())}")
    log("sparse", sources=sources, panel_s=t_panel,
        panel_vs_dijkstra_max_abs=float(err.max()),
        peak_bytes_in_use=peak_bytes(dev))


def phase_mesh(devs, *, n_base=16384, n_stream=256, block=128):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.pipeline import LocalBackend, MeshBackend
    from repro.data import euler_isometric_swiss_roll
    from repro.launch import mesh as mesh_lib
    from repro.launch.serve import serve_manifold

    out = serve_manifold(
        regime="dense", n_base=n_base, k=10, d=2, block=block,
        n_stream=n_stream, stream_batch=64, mesh_shape=(2, 2), **ABSORB,
    )
    log("mesh", **serve_numbers(out))
    check(out["procrustes_error"] < PROCRUSTES_LIMIT,
          f"mesh procrustes {out['procrustes_error']} >= {PROCRUSTES_LIMIT}")
    check_absorbs("mesh", out)

    # the same fit on the mesh and on device 0 alone (features padded to
    # the width serve_manifold uses on a mesh; zero columns change no
    # distance)
    x, _ = euler_isometric_swiss_roll(n_base, seed=0)
    x = np.pad(x, ((0, 0), (0, -x.shape[1] % 4)))
    mesh = mesh_lib.make_mesh((2, 2), ("data", "model"))
    xm = jax.device_put(jnp.asarray(x), NamedSharding(mesh, P("data", "model")))
    t0 = time.time()
    fm = _fit(xm, mode="auto", block=block, backend=MeshBackend(mesh))
    jax.block_until_ready(fm["embedding"])
    t_mesh = time.time() - t0
    per_dev = [peak_bytes(d) for d in devs]
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs]
    x0 = jax.device_put(jnp.asarray(x), devs[0])
    t0 = time.time()
    f1 = _fit(x0, mode="auto", block=block, backend=LocalBackend())
    jax.block_until_ready(f1["embedding"])
    t_one = time.time() - t0
    im, i1 = np.asarray(fm["knn_idx"]), np.asarray(f1["knn_idx"])
    same_idx = np.array_equal(im, i1)
    same_dists = np.array_equal(np.asarray(fm["knn_dists"]),
                                np.asarray(f1["knn_dists"]))
    same_geo = np.array_equal(np.asarray(fm["geodesics"]),
                              np.asarray(f1["geodesics"]))
    # the sharded centering and eigensolve sum in another order, so the
    # embedding matches up to rounding and each column's sign
    em, e1 = np.asarray(fm["embedding"]), np.asarray(f1["embedding"])
    em = em * np.sign(np.sum(em * e1, axis=0))
    emb_dev = float(np.max(np.abs(em - e1)) / np.max(np.abs(e1)))
    log("mesh", fit_mesh_s=t_mesh, fit_one_chip_s=t_one,
        knn_idx_equal=same_idx,
        knn_rows_differing=int(np.sum(np.any(im != i1, axis=1))),
        knn_dists_bit_equal=same_dists, geodesics_bit_equal=same_geo,
        embedding_max_rel_dev=emb_dev,
        bytes_in_use_per_device=in_use, peak_bytes_per_device=per_dev)
    check(same_idx, "mesh kNN lists differ from the one-chip fit")
    check(same_geo, "mesh geodesics differ from the one-chip fit")
    check(emb_dev < EMBEDDING_RTOL, f"mesh embedding off the one-chip fit "
          f"by {emb_dev} of its range")
    check(all(b > 0 for b in in_use),
          f"not every device holds a shard: bytes_in_use {in_use}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the (2, 2) mesh phase")
    args = ap.parse_args(argv)
    t_start = time.time()

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run this from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX's device is {dev.platform} "
              f"({dev.device_kind}, {len(devs)} device(s)). This smoke "
              "runs only on the chip.", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1

    from repro.kernels import measure
    from repro.launch.compile_cache import enable_compile_cache

    if measure.active():
        print("chip_smoke: a tile calibration store would steer the "
              f"kernels ({measure.tuning_path()}, or "
              f"{measure.ENV_MEASURE} is on); the smoke compiles what the "
              "committed code picks. Move the store away or point "
              f"{measure.ENV_TUNING_PATH} elsewhere.", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devs), compile_cache=cache,
        setup_s=time.time() - t_start)

    t0 = time.time()
    try:
        if args.chips == 4:
            phase_mesh(devs)
        else:
            phase_dense(dev)
            phase_reference(dev)
            phase_sparse(dev)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log("total", wall_s=time.time() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
