"""Serving benchmark: open-loop arrivals through the batched request queue.

Drives the BatchedMapperService + StreamingMapper stack the way a load
balancer would: fit the base manifold once, then submit per-request arrival
groups at a target open-loop rate and measure per-request latency at the
scheduler's two knobs (max batch size, max batch latency).  Reports CSV:

    backend,rate_pts_s,offered,p50_ms,p99_ms,mean_batch,sustained_pts_s

on either pipeline backend:

  * ``--backend local``  - single-device StreamingMapper.
  * ``--backend mesh``   - the mapper dispatches through MeshBackend: the
    anchor relaxation runs row-sharded over a fake 8-device CPU mesh
    (XLA_FLAGS is set before jax imports, so run this as a script, not an
    import).

``--smoke`` shrinks sizes so CI exercises the queue scheduler in seconds.

``--absorb`` runs the streaming-absorb smoke instead of the rate sweep:
serve -> absorb through the service write path -> serve again, asserting
that reads complete while the absorb is in flight without serializing
behind it, that post-absorb queries are answered from the grown base,
and that the grown geodesics match refitting exact Isomap on base ∪
accepted (same neighbourhood structure) within 1e-5.

``--regime sparse`` drives the sparse scale regime instead: the fit is
pinned under a REPRO_DENSE_BYTES budget the dense chain cannot hold at
this n (asserted - the dense pipeline must refuse), serving and absorb
run through the (m, n) landmark panel (LandmarkStreamingMapper), and
the absorb path is asserted free of (n, n)-shaped jaxpr variables.

Every run merges its rows into the day's ``BENCH_<date>.json`` at the
repo root (shared with benchmarks/run.py; CI uploads it).
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=("local", "mesh"), default="local")
    ap.add_argument("--n-base", type=int, default=1024)
    ap.add_argument("--n-stream", type=int, default=512)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-latency-ms", type=float, default=25.0)
    ap.add_argument("--arrival", type=int, default=1,
                    help="points per submitted request")
    ap.add_argument("--rates", type=float, nargs="*", default=None,
                    help="offered load in points/s (0 = closed loop, "
                         "submit-all-at-once); default sweeps a small grid")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="tiny sizes + local-friendly rates for CI")
    ap.add_argument("--absorb", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="run the streaming-absorb smoke "
                         "(serve -> absorb -> serve) instead of the sweep")
    ap.add_argument("--regime", choices=("dense", "sparse"),
                    default="dense",
                    help="dense: exact (n, n) chain (the default, what "
                    "the oracle assertions compare against); sparse: "
                    "landmark-panel chain under a dense-refusing "
                    "REPRO_DENSE_BYTES budget")
    ap.add_argument("--replicas", type=int, default=0,
                    help="run the replication smoke with this many "
                    "log-shipped reader replicas instead of the sweep")
    ap.add_argument("--read-delay-ms", type=float, default=20.0,
                    help="per-flush sleep injected into each replica's "
                    "mapper for the replication smoke: models device "
                    "latency (sleeps release the GIL), so throughput "
                    "scaling with replica count is measurable on one CPU")
    return ap


def _fit(args):
    """Fit the base manifold on the requested backend; returns
    (x_base, x_stream, backend, art, n_base, n_stream)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.pipeline import (
        LocalBackend, ManifoldPipeline, MeshBackend, PipelineConfig,
    )
    from repro.data import euler_isometric_swiss_roll

    n_base, n_stream = args.n_base, args.n_stream
    if args.smoke:
        n_base, n_stream = 256, 96

    x, _ = euler_isometric_swiss_roll(n_base + n_stream, seed=args.seed)
    if args.backend == "mesh":
        x = np.pad(x, ((0, 0), (0, 1)))  # 4 features for the model axis
    x_base, x_stream = jnp.asarray(x[:n_base]), np.asarray(x[n_base:])

    if args.backend == "mesh":
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_mesh

        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev // 2, 2), ("data", "model"))
        backend = MeshBackend(mesh)
        x_base = jax.device_put(
            x_base, NamedSharding(mesh, P("data", "model"))
        )
        block = min(args.block, n_base // (n_dev // 2))  # fit the tile
    else:
        backend = LocalBackend()
        block = min(args.block, n_base)

    cfg = PipelineConfig(
        k=args.k, d=2, block=block,
        regime=getattr(args, "regime", "dense"),
    )
    from repro.core.pipeline import stages_for

    pipe = ManifoldPipeline(
        stages_for(cfg, n_base), backend=backend, cfg=cfg,
    )
    t0 = time.perf_counter()
    art = pipe.run(x_base)
    fit_s = time.perf_counter() - t0
    print(f"# fit backend={args.backend} regime={cfg.regime} "
          f"n_base={n_base} fit_s={fit_s:.2f}", file=sys.stderr)
    return x_base, x_stream, backend, art, n_base, n_stream


def run_absorb_smoke(args) -> dict:
    """serve -> absorb -> serve through one BatchedMapperService.

    Asserted, not just reported:

    * reads submitted before and alongside the absorb all complete, and
      are not serialized behind the write path: collecting them takes a
      small fraction of the time the absorb is in flight (the absorb
      runs between flushes against a versioned snapshot);
    * the absorb actually grew the served base (version bump + n_base);
    * post-absorb queries are answered from the grown base: they match a
      fresh mapper built directly on refit artifacts (exact Isomap on
      base ∪ accepted with the same neighbourhood structure) within 1e-5;
    * the grown geodesics match that refit within 1e-5.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import apsp as apsp_mod
    from repro.core import update as update_mod
    from repro.core.postprocess import embedding_from_eig
    from repro.core.streaming import StreamingMapper
    from repro.launch.serving import BatchedMapperService

    x_base, x_stream, backend, art, n_base, n_stream = _fit(args)
    n_absorb = 16
    x_absorb, x_query = x_stream[:n_absorb], x_stream[n_absorb:]

    mapper = StreamingMapper.from_artifacts(
        art, k=args.k, batch=args.max_batch, backend=backend
    )
    service = BatchedMapperService(
        mapper, max_batch=args.max_batch,
        max_latency_ms=args.max_latency_ms,
    )
    with service:
        service.warmup(x_stream.shape[1])
        # phase 1: serve, and interleave the absorb with live reads
        t0 = time.perf_counter()
        pre = [service.submit(x_query[i]) for i in range(16)]
        absorb_fut = service.submit_absorb(x_absorb)
        mid = [service.submit(x_query[16 + i]) for i in range(16)]
        for f in pre + mid:
            assert f.result(timeout=60) is not None
        read_s = time.perf_counter() - t0
        report = absorb_fut.result(timeout=120)
        absorb_wall_s = time.perf_counter() - t0
        # reads must not have waited for the O(n^2) expansion: with the
        # queue non-empty the scheduler flushes reads first, so the read
        # wave completes in a fraction of the absorb's wall time (0.5s
        # floor keeps the check meaningful only when the absorb is slow
        # enough to matter)
        assert read_s < max(0.5 * absorb_wall_s, 0.5), (
            f"reads took {read_s:.2f}s while the absorb was in flight "
            f"for {absorb_wall_s:.2f}s - the read path serialized "
            "behind the write path"
        )
        # phase 2: post-absorb reads come from the grown base
        post = [service.submit(p) for p in x_query[32:]]
        y_post = np.concatenate([f.result(timeout=60) for f in post])
    stats = service.stats()

    assert report.absorbed > 0, report
    assert mapper.version >= 1, mapper.version
    assert mapper.n_base == n_base + report.absorbed, (
        mapper.n_base, n_base, report.absorbed
    )

    # fusion discipline (--only apsp_phase2 contract), asserted on the
    # expansion path the absorb actually ran: local inspects the fused
    # expand_geodesics for (n, n)-shaped product intermediates; mesh
    # inspects the shard body for tile-shaped ones - both against their
    # materializing twins
    import jax

    from run import _shaped_vars

    mm = report.absorbed
    az = jnp.zeros((n_base, n_base), jnp.float32)
    ez = jnp.zeros((mm, n_base), jnp.float32)
    fz = jnp.zeros((mm, mm), jnp.float32)
    if args.backend == "mesh":
        pd = backend.mesh.shape[backend.data_axis]
        pm = backend.mesh.shape[backend.model_axis]
        shape = (n_base // pd, n_base // pm)   # the local interior tile
        fused_fn = update_mod.make_expand_sharded(
            backend.mesh, n_base, mm,
            data_axis=backend.data_axis, model_axis=backend.model_axis,
        )
        mat_fn = update_mod.make_expand_sharded(
            backend.mesh, n_base, mm,
            data_axis=backend.data_axis, model_axis=backend.model_axis,
            fused=False,
        )
    else:
        shape = (n_base, n_base)
        fused_fn = update_mod.expand_geodesics
        mat_fn = update_mod.expand_geodesics_materializing
    n_fused = _shaped_vars(jax.make_jaxpr(fused_fn)(az, ez, fz), shape)
    n_mat = _shaped_vars(jax.make_jaxpr(mat_fn)(az, ez, fz), shape)
    assert n_fused < n_mat, (
        f"border expansion carries {n_fused} {shape}-shaped jaxpr vars "
        f"vs {n_mat} materializing - a min-plus intermediate is back"
    )

    # refit oracle: exact Isomap on base ∪ accepted with the same
    # (augmented) neighbourhood structure, from scratch
    from repro.core.update import UpdateConfig

    threshold = UpdateConfig().threshold   # the gate the service used
    accepted = x_absorb[report.errors <= threshold][: report.absorbed]
    m = accepted.shape[0]
    g_aug = update_mod.augmented_graph(
        np.asarray(x_base), accepted, k=args.k
    )
    want_geo = np.asarray(
        apsp_mod.apsp_blocked(jnp.asarray(g_aug), block=n_base + m,
                              mode="ref")
    )
    got_geo = np.asarray(mapper.geodesics)
    np.testing.assert_allclose(got_geo, want_geo, rtol=1e-5, atol=1e-5)

    # post-absorb queries match a mapper built directly on the refit
    from repro.core.centering import double_center
    from repro.core.spectral import power_iteration

    eig = power_iteration(
        double_center(jnp.square(jnp.asarray(want_geo))), d=2,
        max_iter=100, tol=1e-9,
    )
    y_refit = embedding_from_eig(eig.eigenvectors, eig.eigenvalues)
    x_grown = np.concatenate([np.asarray(x_base), accepted])
    refit_mapper = StreamingMapper(
        jnp.asarray(x_grown), jnp.asarray(want_geo), y_refit, k=args.k,
        batch=args.max_batch,
    )
    want_post = np.asarray(refit_mapper(jnp.asarray(x_query[32:])))
    # eigenvector sign is arbitrary: align each embedding column before
    # comparing the triangulated coordinates
    sign = np.sign(np.sum(y_post * want_post, axis=0))
    np.testing.assert_allclose(y_post, want_post * sign, rtol=1e-4,
                               atol=1e-4)

    row = {
        "backend": args.backend,
        "absorbed": report.absorbed,
        "version": mapper.version,
        "reads_during_absorb_s": read_s,
        "p50_ms": stats["latency_p50_ms"],
        "p99_ms": stats["latency_p99_ms"],
    }
    print("backend,absorbed,version,reads_during_absorb_s,p50_ms,p99_ms")
    print(",".join(str(row[c]) for c in row))
    from run import write_bench_json

    write_bench_json([
        {"name": f"serving_dense_absorb_{args.backend}", **row}
    ])
    return row


def run_absorb_smoke_sparse(args) -> dict:
    """Sparse-regime fit -> serve -> absorb smoke (--regime sparse --absorb).

    Asserted, not just reported:

    * the run is pinned under a ``REPRO_DENSE_BYTES`` budget the dense
      chain cannot hold at this n, and the dense pipeline actually
      *refuses* (DenseBudgetError) - so everything below genuinely ran
      without the (n, n) base;
    * serve -> absorb -> serve works end to end through the service:
      absorbed > 0, version bump, base and panel columns grown;
    * the absorb expansion (:func:`repro.core.update.expand_panel`)
      carries ZERO (n, n)-shaped jaxpr variables, before or after the
      growth - the sparse write path never densifies either.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import sparse as sparse_mod, update as update_mod
    from repro.core.pipeline import (
        LocalBackend, ManifoldPipeline, PipelineConfig,
    )
    from repro.core.streaming import LandmarkStreamingMapper
    from repro.launch.serving import BatchedMapperService
    from run import _shaped_vars, write_bench_json

    # pin a budget the dense chain cannot hold at this n (CI sets its
    # own; a local run self-pins so the refusal assertion is meaningful)
    nb = 256 if args.smoke else args.n_base
    os.environ.setdefault(
        "REPRO_DENSE_BYTES", str(sparse_mod.dense_fit_bytes(nb) - 1)
    )
    x_base, x_stream, backend, art, n_base, n_stream = _fit(args)

    # the dense regime must refuse this n under the pinned budget
    xb_host = jnp.asarray(np.asarray(x_base))
    try:
        ManifoldPipeline(
            backend=LocalBackend(),
            cfg=PipelineConfig(k=args.k, d=2, block=min(args.block, n_base)),
        ).run(xb_host)
    except sparse_mod.DenseBudgetError:
        pass
    else:
        raise AssertionError(
            f"dense pipeline fitted n={n_base} under "
            f"REPRO_DENSE_BYTES={os.environ['REPRO_DENSE_BYTES']} - the "
            "budget refusal regressed, this smoke is not testing the "
            "sparse regime under pressure"
        )

    n_absorb = 16
    x_absorb, x_query = x_stream[:n_absorb], x_stream[n_absorb:]
    mapper = LandmarkStreamingMapper.from_artifacts(
        art, k=args.k, batch=args.max_batch, backend=backend
    )
    m = int(mapper.lm_idx.shape[0])
    service = BatchedMapperService(
        mapper, max_batch=args.max_batch,
        max_latency_ms=args.max_latency_ms,
    )
    with service:
        service.warmup(x_stream.shape[1])
        t0 = time.perf_counter()
        pre = [service.submit(x_query[i]) for i in range(16)]
        absorb_fut = service.submit_absorb(x_absorb)
        mid = [service.submit(x_query[16 + i]) for i in range(16)]
        for f in pre + mid:
            assert f.result(timeout=60) is not None
        report = absorb_fut.result(timeout=120)
        post = [service.submit(p) for p in x_query[32:]]
        y_post = np.concatenate([f.result(timeout=60) for f in post])
    stats = service.stats()

    assert report.absorbed > 0, report
    assert mapper.version >= 1, mapper.version
    assert mapper.n_base == n_base + report.absorbed, (
        mapper.n_base, n_base, report.absorbed
    )
    assert mapper.panel.shape == (m, n_base + report.absorbed), (
        mapper.panel.shape, m, n_base, report.absorbed
    )
    assert np.isfinite(y_post).all(), "post-absorb queries went non-finite"

    # residency discipline on the write path: expand_panel must carry no
    # (n, n)-shaped vars, neither at the old nor the grown size
    g = report.absorbed
    pz = jnp.zeros((m, n_base), jnp.float32)
    ez = jnp.zeros((g, n_base), jnp.float32)
    fz = jnp.zeros((g, g), jnp.float32)
    jx = jax.make_jaxpr(update_mod.expand_panel)(pz, ez, fz)
    for nn in (n_base, n_base + g):
        bad = _shaped_vars(jx, (nn, nn))
        assert bad == 0, (
            f"expand_panel materializes {bad} ({nn}, {nn})-shaped jaxpr "
            "vars - the sparse absorb densified"
        )
    assert _shaped_vars(jx, (m, n_base)) > 0, "jaxpr probe saw no panel"

    row = {
        "name": f"serving_sparse_absorb_{args.backend}",
        "backend": args.backend,
        "regime": "sparse",
        "landmarks": m,
        "absorbed": report.absorbed,
        "version": mapper.version,
        "p50_ms": stats["latency_p50_ms"],
        "p99_ms": stats["latency_p99_ms"],
    }
    print("backend,regime,landmarks,absorbed,version,p50_ms,p99_ms")
    print(",".join(str(row[c]) for c in list(row)[1:]))
    write_bench_json([row])
    return row


class _DelayedMapper:
    """Mapper wrapper sleeping `delay_s` per mapped batch: a stand-in for
    device latency (time.sleep releases the GIL), so replica-count
    scaling is measurable on a single CPU.  Everything else (absorb,
    apply_log_entry, version, ...) delegates to the wrapped mapper."""

    def __init__(self, mapper, delay_s: float):
        self._mapper = mapper
        self._delay_s = delay_s

    def __call__(self, x):
        time.sleep(self._delay_s)
        return self._mapper(x)

    def __getattr__(self, name):
        return getattr(self._mapper, name)


def run_replication_smoke(args) -> dict:
    """Writer + N log-shipped reader replicas behind the consistent-hash
    router (--replicas N).

    Asserted, not just reported:

    * read throughput scales with replica count: the same closed-loop
      read wave through N >= 2 replicas sustains > 1.3x the single-replica
      points/s (each replica's mapper carries a --read-delay-ms sleep
      standing in for device latency, so the comparison is meaningful on
      one CPU);
    * reads keep completing while a replica is killed and restarted
      mid-wave - every submitted future resolves;
    * absorbs remain single-writer: they flow through the writer's
      update log, and after :meth:`ReplicatedMapperFleet.sync` every
      replica's geodesics/embedding are bit-identical to the writer's
      (including the replica that was restarted mid-run, which converged
      by replay alone).
    """
    import numpy as np

    from repro.core.streaming import LandmarkStreamingMapper, StreamingMapper
    from repro.core.update import UpdateConfig
    from repro.launch.replication import ReplicatedMapperFleet
    from run import write_bench_json

    assert args.replicas >= 2, "--replicas must be >= 2 for the smoke"
    x_base, x_stream, backend, art, n_base, n_stream = _fit(args)
    n_absorb = 8
    x_absorb, x_query = x_stream[:n_absorb], x_stream[n_absorb:]
    delay_s = args.read_delay_ms / 1e3

    mapper_cls = (
        LandmarkStreamingMapper if getattr(args, "regime", "dense") == "sparse"
        else StreamingMapper
    )
    art_host = {a: np.asarray(art[a]) for a in mapper_cls.SERVING_ARTIFACTS}

    def make_mapper(update_cfg):
        return _DelayedMapper(
            mapper_cls.from_artifacts(
                art_host, k=args.k, batch=args.max_batch, backend=backend,
                update=update_cfg,
            ),
            delay_s,
        )

    def fleet_for(log_dir, n_replicas):
        return ReplicatedMapperFleet(
            make_mapper, log_dir,
            replicas=n_replicas, update=UpdateConfig(),
            max_batch=args.max_batch, max_latency_ms=args.max_latency_ms,
            pipeline_depth=1,   # scaling must come from replicas alone
        )

    def read_wave(fleet, repeats=4):
        t0 = time.perf_counter()
        futures = [
            fleet.submit(x_query[i % x_query.shape[0]])
            for i in range(repeats * x_query.shape[0])
        ]
        for f in futures:
            assert f.result(timeout=120) is not None
        wall = time.perf_counter() - t0
        return len(futures) / wall

    import tempfile

    # compile the fixed serving shape once, outside the timed waves (the
    # services pad every coalesced batch to max_batch rows)
    make_mapper(UpdateConfig())(
        np.zeros((args.max_batch, x_query.shape[1]), np.float32)
    )

    # throughput: 1 replica vs N replicas over the identical read wave
    with fleet_for(tempfile.mkdtemp(prefix="repl-1-"), 1) as fleet:
        pts_s_1 = read_wave(fleet)
    with fleet_for(tempfile.mkdtemp(prefix="repl-n-"), args.replicas) as fleet:
        pts_s_n = read_wave(fleet)
    scale = pts_s_n / pts_s_1
    assert scale > 1.3, (
        f"{args.replicas} replicas sustained {pts_s_n:.0f} pts/s vs "
        f"{pts_s_1:.0f} with one ({scale:.2f}x) - read throughput is not "
        "scaling with replica count"
    )

    # fault injection under live absorbs: kill + restart a replica
    # mid-wave; every read resolves, and after sync every replica is
    # bit-identical to the writer
    log_dir = tempfile.mkdtemp(prefix="repl-fault-")
    with fleet_for(log_dir, args.replicas) as fleet:
        futures = [
            fleet.submit(x_query[i % x_query.shape[0]])
            for i in range(x_query.shape[0])
        ]
        victim = next(iter(fleet.replicas))
        fleet.kill_replica(victim)
        futures += [
            fleet.submit(x_query[i % x_query.shape[0]])
            for i in range(x_query.shape[0])
        ]
        report = fleet.absorb(x_absorb)
        fleet.restart_replica(victim)
        for f in futures:
            assert f.result(timeout=120) is not None
        assert fleet.sync(timeout=120), "replicas failed to catch up"
        writer = fleet.writer_mapper
        state_key = "panel" if args.regime == "sparse" else "geodesics"
        for name, replica in fleet.replicas.items():
            m = replica.mapper
            assert m.version == writer.version, (name, m.version)
            assert np.array_equal(
                np.asarray(getattr(m, state_key)),
                np.asarray(getattr(writer, state_key)),
            ), f"replica {name} diverged from the writer ({state_key})"
            assert np.array_equal(
                np.asarray(m.embedding), np.asarray(writer.embedding)
            ), f"replica {name} diverged from the writer (embedding)"
        lag = max(s["lag_steps"] for s in fleet.stats()["replicas"])

    row = {
        "backend": args.backend,
        "replicas": args.replicas,
        "pts_s_1_replica": pts_s_1,
        "pts_s_n_replicas": pts_s_n,
        "scale": scale,
        "absorbed": report.absorbed,
        "post_sync_lag_steps": lag,
    }
    print("backend,replicas,pts_s_1_replica,pts_s_n_replicas,scale,"
          "absorbed,post_sync_lag_steps")
    print(",".join(str(row[c]) for c in row))
    write_bench_json([
        {"name": f"serving_replication_{args.backend}", **row}
    ])
    return row


def run(args) -> list[dict]:
    from repro.core.streaming import LandmarkStreamingMapper, StreamingMapper
    from repro.launch.serving import BatchedMapperService

    rates = args.rates
    if args.smoke:
        rates = rates if rates is not None else [0.0]
    elif rates is None:
        rates = [500.0, 2000.0, 0.0]

    x_base, x_stream, backend, art, n_base, n_stream = _fit(args)

    mapper_cls = (
        LandmarkStreamingMapper if getattr(args, "regime", "dense") == "sparse"
        else StreamingMapper
    )
    mapper = mapper_cls.from_artifacts(
        art, k=args.k, batch=args.max_batch, backend=backend
    )

    rows = []
    for rate in rates:
        service = BatchedMapperService(
            mapper,
            max_batch=args.max_batch,
            max_latency_ms=args.max_latency_ms,
        )
        with service:
            service.warmup(x_stream.shape[1])
            gap = args.arrival / rate if rate > 0 else 0.0
            futures = []
            t_start = time.perf_counter()
            for i, lo in enumerate(range(0, n_stream, args.arrival)):
                if gap:
                    # open loop: pace submissions at the offered rate
                    sleep = t_start + i * gap - time.perf_counter()
                    if sleep > 0:
                        time.sleep(sleep)
                futures.append(service.submit(x_stream[lo:lo + args.arrival]))
            for f in futures:
                f.result()
        stats = service.stats()
        row = {
            "backend": args.backend,
            "rate_pts_s": rate,
            "offered": n_stream,
            "p50_ms": stats["latency_p50_ms"],
            "p99_ms": stats["latency_p99_ms"],
            "mean_batch": stats["mean_batch"],
            "sustained_pts_s": stats["points_per_s"],
        }
        rows.append(row)
        print(",".join(
            f"{row[k]:.1f}" if isinstance(row[k], float) else str(row[k])
            for k in ("backend", "rate_pts_s", "offered", "p50_ms",
                      "p99_ms", "mean_batch", "sustained_pts_s")
        ))
    return rows


def main(argv=None):
    args = build_parser().parse_args(argv)
    if args.backend == "mesh" and "XLA_FLAGS" not in os.environ:
        # must happen before any jax import in this process
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.replicas:
        return run_replication_smoke(args)
    if args.absorb:
        if args.regime == "sparse":
            return run_absorb_smoke_sparse(args)
        return run_absorb_smoke(args)
    print("backend,rate_pts_s,offered,p50_ms,p99_ms,mean_batch,"
          "sustained_pts_s")
    rows = run(args)
    # the queue must actually have coalesced and served everything
    assert rows and all(r["p50_ms"] == r["p50_ms"] for r in rows), rows
    from run import write_bench_json

    write_bench_json([
        {
            "name": f"serving_{args.regime}_{r['backend']}"
                    f"_rate{r['rate_pts_s']:g}",
            **r,
        }
        for r in rows
    ])
    return rows


if __name__ == "__main__":
    main()
