"""Serving launcher: LM generation loop + manifold streaming service.

``python -m repro.launch.serve --arch smollm-135m --smoke`` runs a real
batched generation on CPU; the same prefill/decode step functions are what
the dry-run lowers for the prefill_32k / decode_32k / long_500k shapes.

``python -m repro.launch.serve --manifold swissroll`` drives the staged
ManifoldPipeline instead: fit exact Isomap on a base batch (stage-boundary
checkpointed), then serve streamed arrivals as a request/response service -
per-point requests flow through the BatchedMapperService arrival queue
(max-batch-size / max-batch-latency scheduling) into the StreamingMapper,
and the driver reports p50/p99 request latency alongside throughput.
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch import mesh as mesh_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.sharding import LogicalRules, materialize, spec_shardings


def generate(
    arch: str,
    *,
    batch: int = 4,
    prompt_len: int = 16,
    gen_len: int = 16,
    smoke: bool = True,
    mesh=None,
    temperature: float = 0.0,
    seed: int = 0,
):
    cfg = configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
    mesh = mesh or mesh_lib.make_mesh((1, 1), ("data", "model"))
    rules = LogicalRules(mesh)
    model = build_model(cfg)
    p_specs = model.param_specs()

    rng = np.random.default_rng(seed)
    prompts = rng.integers(1, cfg.vocab, (batch, prompt_len), dtype=np.int32)
    feed = {"tokens": jnp.asarray(prompts)}
    if cfg.kind == "encdec":
        feed["frames"] = jnp.asarray(
            rng.normal(size=(batch, cfg.enc_seq, cfg.d_model)), jnp.bfloat16
        )
    if cfg.vision_tokens:
        feed["patches"] = jnp.asarray(
            rng.normal(size=(batch, cfg.vision_tokens, cfg.d_model)),
            jnp.bfloat16,
        )

    with mesh:
        params = materialize(p_specs, jax.random.PRNGKey(0), rules)
        prefill = jax.jit(
            functools.partial(model.prefill, pad_to=prompt_len + gen_len)
        )
        decode = jax.jit(model.decode_step)

        t0 = time.time()
        logits, cache = prefill(params, feed)
        out_tokens = []
        key = jax.random.PRNGKey(seed)
        kv_len = jnp.full((batch,), prompt_len + (cfg.vision_tokens or 0),
                          jnp.int32)
        tok = _sample(logits[:, -1], key, temperature)
        out_tokens.append(np.asarray(tok))
        t_prefill = time.time() - t0

        t0 = time.time()
        for i in range(gen_len - 1):
            key, sub = jax.random.split(key)
            logits, cache = decode(
                params, {"token": tok[:, None], "kv_len": kv_len, "cache": cache}
            )
            kv_len = kv_len + 1
            tok = _sample(logits[:, -1], sub, temperature)
            out_tokens.append(np.asarray(tok))
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    gen = np.stack(out_tokens, axis=1)
    return {
        "prompts": prompts,
        "generated": gen,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "tok_per_s": batch * (gen_len - 1) / max(t_decode, 1e-9),
    }


# Fixed feature-padding width for sharded manifold serving: checkpoints
# stay portable across any mesh whose model axis divides it (1/2/4).
_FEATURE_PAD = 4


def serve_manifold(
    *,
    n_base: int = 512,
    n_stream: int = 256,
    stream_batch: int = 64,
    k: int = 10,
    d: int = 2,
    block: int = 128,
    max_latency_ms: float = 25.0,
    arrival: int = 1,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    checkpoint_secs: float | None = None,
    absorb: int = 0,
    absorb_flushes: int = 1,
    mesh_shape: tuple[int, int] | None = None,
    regime: str = "auto",
    landmarks: int = 0,
    objective: str = "spectral",
    replicas: int = 0,
    router_vnodes: int = 64,
    pipeline_depth: int = 2,
    seed: int = 0,
):
    """Fit the staged Isomap pipeline on a base batch, then serve streamed
    arrivals as a request/response service: each arrival group (``arrival``
    points) is submitted to a :class:`BatchedMapperService` whose scheduler
    coalesces requests up to ``stream_batch`` points or ``max_latency_ms``
    of queueing, whichever first, and drains them into the StreamingMapper.

    checkpoint_dir/resume: a server restart restores the fitted artifacts
    from the stage-boundary checkpoints instead of refitting - and because
    the restore path is placement-aware, the restart may land on a
    *different* mesh shape (features are padded to a fixed mesh-independent
    width so the checkpointed input matches): artifacts are ``device_put``
    straight onto the current mesh's tile sharding.  A restore also
    replays the persisted update log, so absorbed arrivals survive the
    restart.
    checkpoint_secs: size the mid-stage (APSP panel) checkpoint segments
    to this wall-clock cadence from the measured per-panel time, instead
    of a fixed unit count (the paper's every-10-iterations rule, in
    seconds).
    absorb: fold the first `absorb` streamed arrivals back into the base
    geodesics through the service's write path (admission-controlled,
    runs between read flushes) before serving the rest.
    absorb_flushes: split those arrivals into this many write calls, so
    each later flush grows a base an earlier one already grew.
    replicas: serve reads from this many log-shipped reader replicas
    behind a consistent-hash router instead of one service; all absorbs
    still go through the single writer, whose update-log appends the
    replicas tail (:mod:`repro.launch.replication`).  0 (default) keeps
    the single-service path.
    router_vnodes: ring points per replica in the consistent-hash router.
    pipeline_depth: in-flight flush window per replica service (>1
    overlaps a slow flush with the next batch's coalescing).
    mesh_shape: (data, model) device grid; None serves single-device.
    regime/landmarks: scale-regime selection
    (:func:`repro.core.pipeline.stages_for`) - "dense" pins the exact
    (n, n) chain, "sparse" the landmark-panel chain (serving and absorb
    then run through :class:`LandmarkStreamingMapper`, never touching
    anything O(n^2)), "auto" picks by the ``REPRO_DENSE_BYTES`` budget.
    Returns timing + per-request latency percentiles + quality."""
    from repro.core import metrics
    from repro.core.pipeline import (
        LocalBackend, ManifoldPipeline, MeshBackend, PipelineConfig,
        stages_for,
    )
    from repro.core.streaming import LandmarkStreamingMapper, StreamingMapper
    from repro.data import euler_isometric_swiss_roll
    from repro.launch.serving import BatchedMapperService

    x, latent = euler_isometric_swiss_roll(n_base + n_stream, seed=seed)
    x_base, x_stream = jnp.asarray(x[:n_base]), np.asarray(x[n_base:])

    backend = None
    if mesh_shape is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        # pad features to a fixed multiple of _FEATURE_PAD, independent of
        # the current mesh, so a checkpoint written under one mesh shape
        # resumes under another (the input value-check compares x): any
        # model axis dividing _FEATURE_PAD sees the same padded width.
        # Zero feature columns leave all pairwise distances unchanged.
        pm = mesh_shape[1]
        if _FEATURE_PAD % pm:
            raise ValueError(
                f"model axis {pm} must divide {_FEATURE_PAD} (the fixed "
                "feature padding width that keeps checkpoints portable "
                "across mesh shapes)"
            )
        D = x_base.shape[1]
        if D % _FEATURE_PAD:
            pad = _FEATURE_PAD - D % _FEATURE_PAD
            x_base = jnp.pad(x_base, ((0, 0), (0, pad)))
            x_stream = np.pad(x_stream, ((0, 0), (0, pad)))
        mesh = mesh_lib.make_mesh(mesh_shape, ("data", "model"))
        backend = MeshBackend(mesh, checkpoint_secs=checkpoint_secs)
        x_base = jax.device_put(
            x_base, NamedSharding(mesh, P("data", "model"))
        )

    checkpoint = None
    if checkpoint_dir:
        from repro.checkpoint import CheckpointManager

        checkpoint = CheckpointManager(checkpoint_dir)

    pcfg = PipelineConfig(
        k=k, d=d, block=block, regime=regime, landmarks=landmarks,
        objective=objective,
    )
    stages = stages_for(pcfg, n_base)
    sparse_fit = any(s.name == "sparse_geodesics" for s in stages)
    pipe = ManifoldPipeline(
        stages,
        cfg=pcfg,
        backend=backend or LocalBackend(checkpoint_secs=checkpoint_secs),
        checkpoint=checkpoint,
    )
    t0 = time.time()
    art = pipe.run(x_base, resume=resume)
    jax.block_until_ready(art["embedding"])
    t_fit = time.time() - t0

    update_cfg = None
    if checkpoint_dir:
        import os

        from repro.core.update import UPDATE_LOG_DIR, UpdateConfig

        update_cfg = UpdateConfig(
            log_dir=os.path.join(checkpoint_dir, UPDATE_LOG_DIR)
        )
    mapper_cls = LandmarkStreamingMapper if sparse_fit else StreamingMapper
    mapper = mapper_cls.from_artifacts(
        art, k=k, batch=stream_batch, backend=backend, update=update_cfg,
        objective=objective,
    )
    if resume and checkpoint_dir:
        # a restarted server replays absorbed arrivals, not just the fit
        mapper.replay_update_log(checkpoint_dir)
    n_absorbed = 0
    if absorb_flushes < 1:
        raise ValueError(
            f"absorb_flushes must be >= 1, got {absorb_flushes}"
        )
    absorb_parts = (
        np.array_split(x_stream[:absorb], absorb_flushes) if absorb else []
    )
    replica_stats: list[dict] = []
    if replicas:
        import os
        import tempfile

        from repro.core.update import UPDATE_LOG_DIR, UpdateConfig
        from repro.launch.replication import ReplicatedMapperFleet

        # replicas rebuild their mappers from the base artifacts, so the
        # fit is pulled to host exactly once and shared by every factory
        # call (start, restart, generation reset)
        art_host = {
            a: np.asarray(art[a]) for a in mapper_cls.SERVING_ARTIFACTS
        }

        def make_mapper(update_cfg):
            return mapper_cls.from_artifacts(
                art_host, k=k, batch=stream_batch, backend=backend,
                update=update_cfg, objective=objective,
            )

        log_dir = (
            os.path.join(checkpoint_dir, UPDATE_LOG_DIR)
            if checkpoint_dir
            else tempfile.mkdtemp(prefix="repro-replication-")
        )
        fleet = ReplicatedMapperFleet(
            make_mapper, log_dir,
            replicas=replicas, vnodes=router_vnodes,
            update=UpdateConfig(), pipeline_depth=pipeline_depth,
            max_batch=stream_batch, max_latency_ms=max_latency_ms,
        )
        with fleet:
            t0 = time.time()
            for part in absorb_parts:
                n_absorbed += fleet.absorb(part).absorbed
            if absorb:
                # serve from the absorbed generation: wait for every
                # replica to cut over before the read burst (otherwise a
                # lagging replica answers from the pre-absorb frame -
                # internally consistent, but a different eigenbasis than
                # the quality check below compares against)
                fleet.sync(timeout=60.0)
            futures = [
                fleet.submit(x_stream[lo : lo + arrival])
                for lo in range(0, n_stream, arrival)
            ]
            y_stream = np.concatenate([f.result() for f in futures], axis=0)
            t_serve = time.time() - t0
            fleet.sync(timeout=60.0)
            fstats = fleet.stats()
        mapper = fleet.writer_mapper
        replica_stats = fstats["replicas"]
        reqs = sum(s["requests"] for s in replica_stats)
        stats = {
            # pooled read-path numbers: p50 averages the replicas, p99 is
            # the worst replica (tail latency is a max, not a mean)
            "latency_p50_ms": float(np.mean(
                [s["latency_p50_ms"] for s in replica_stats]
            )) if reqs else float("nan"),
            "latency_p99_ms": float(np.max(
                [s["latency_p99_ms"] for s in replica_stats]
            )) if reqs else float("nan"),
            "mean_batch": float(np.mean(
                [s["mean_batch"] for s in replica_stats]
            )) if reqs else float("nan"),
            "requests": reqs,
            "queue_wait_s": sum(s["queue_wait_s"] for s in replica_stats),
            "service_s": sum(s["service_s"] for s in replica_stats),
        }
    else:
        service = BatchedMapperService(
            mapper, max_batch=stream_batch, max_latency_ms=max_latency_ms,
            pipeline_depth=pipeline_depth,
        )
        with service:
            service.warmup(x_stream.shape[1])
            t0 = time.time()
            # write path: fold early arrivals into the base geodesics;
            # every arrival is still queried below (absorbed points are
            # then answered from the grown base they are part of)
            for part in absorb_parts:
                n_absorbed += service.absorb(part).absorbed
            futures = [
                service.submit(x_stream[lo : lo + arrival])
                for lo in range(0, n_stream, arrival)
            ]
            y_stream = np.concatenate([f.result() for f in futures], axis=0)
            t_serve = time.time() - t0
        stats = service.stats()
    reqs_served = max(stats["requests"], 1)

    # quality in the *served* frame: the absorb republished the base
    # embedding (possibly with flipped eigenvector signs), and every
    # query above was answered from that version - so the base rows must
    # come from the current serving snapshot, not the version-0 artifacts
    full = np.concatenate(
        [np.asarray(mapper.embedding)[:n_base], y_stream]
    )
    err = float(
        metrics.procrustes_error(jnp.asarray(full), jnp.asarray(latent))
    )
    # residual variance (Tenenbaum's 1 - r^2) of the served base frame:
    # geodesic-vs-embedded distance agreement, comparable across
    # objectives (procrustes needs the latent oracle; this does not)
    snap = mapper.snapshot()
    if sparse_fit:
        rv = float(metrics.residual_variance_panel(
            snap["panel"], snap["embedding"], snap["lm_idx"]
        ))
    else:
        rv = float(metrics.residual_variance(
            snap["geodesics"], snap["embedding"]
        ))
    return {
        "fit_s": t_fit,
        "serve_s": t_serve,
        "points_per_s": n_stream / max(t_serve, 1e-9),
        "latency_p50_ms": stats["latency_p50_ms"],
        "latency_p99_ms": stats["latency_p99_ms"],
        # each request's latency = its queue wait + its flush's service
        "queue_wait_ms": 1e3 * stats["queue_wait_s"] / reqs_served,
        "service_ms": 1e3 * stats["service_s"] / reqs_served,
        "mean_batch": stats["mean_batch"],
        "requests": stats["requests"],
        "procrustes_error": err,
        "residual_variance": rv,
        "n_base": n_base,
        "n_stream": n_stream,
        "absorbed": n_absorbed,
        "serving_version": mapper.version,
        "regime": "sparse" if sparse_fit else "dense",
        "objective": objective,
        "replicas": replicas,
        "replica_stats": replica_stats,
        "replication_lag_steps": (
            max((s["lag_steps"] for s in replica_stats), default=0)
        ),
    }


def _sample(logits, key, temperature):
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, logits / temperature).astype(jnp.int32)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCHS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    # BooleanOptionalAction: --smoke / --no-smoke (store_true with
    # default=True made the full configs unreachable from the CLI)
    ap.add_argument(
        "--smoke", action=argparse.BooleanOptionalAction, default=True
    )
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--manifold", choices=("swissroll",),
        help="serve the manifold pipeline instead of an LM arch",
    )
    ap.add_argument("--n-base", type=int, default=512)
    ap.add_argument("--n-stream", type=int, default=256)
    ap.add_argument("--stream-batch", type=int, default=64,
                    help="scheduler max batch size (points)")
    ap.add_argument("--max-latency-ms", type=float, default=25.0,
                    help="scheduler max queueing latency before flush")
    ap.add_argument("--arrival", type=int, default=1,
                    help="points per submitted request")
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--d", type=int, default=2)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--checkpoint-dir", default=None,
        help="persist/restore the fitted pipeline at stage boundaries",
    )
    ap.add_argument(
        "--resume", action="store_true",
        help="restore the fitted pipeline from --checkpoint-dir instead "
        "of refitting (placement-aware: works across mesh shapes); also "
        "replays the persisted update log of absorbed arrivals",
    )
    ap.add_argument(
        "--checkpoint-secs", type=float, default=None,
        help="target wall-clock interval between mid-stage checkpoints; "
        "segment sizes are derived from the measured per-unit time "
        "(default: one segment per stage)",
    )
    ap.add_argument(
        "--absorb", type=int, default=0,
        help="fold this many early arrivals back into the base geodesics "
        "through the service write path before serving the rest",
    )
    ap.add_argument(
        "--mesh", default=None, metavar="DxM",
        help="serve sharded over a (data, model) device grid, e.g. 4x2 "
        "(device count must be available; set XLA_FLAGS for fake CPUs)",
    )
    ap.add_argument(
        "--regime", choices=("auto", "dense", "sparse"), default="auto",
        help="scale regime: dense pins the exact (n, n) chain, sparse "
        "the landmark-panel chain (O(n k + m n) residency; serving and "
        "absorb run through the panel), auto picks by the "
        "REPRO_DENSE_BYTES budget",
    )
    ap.add_argument(
        "--landmarks", type=int, default=0,
        help="sparse-regime landmark budget m (0: ~4 sqrt(n) default)",
    )
    ap.add_argument(
        "--replicas", type=int, default=0,
        help="serve reads from this many log-shipped reader replicas "
        "behind a consistent-hash router (0: single service); absorbs "
        "always go through the single writer",
    )
    ap.add_argument(
        "--router", type=int, default=64, metavar="VNODES",
        help="consistent-hash ring points per replica (more flattens "
        "load at O(vnodes) join/leave cost)",
    )
    ap.add_argument(
        "--pipeline-depth", type=int, default=2,
        help="in-flight flush window per service (>1 overlaps a slow "
        "flush with the next batch's coalescing; 1 is strictly serial)",
    )
    ap.add_argument(
        "--objective", choices=("spectral", "stress", "path"),
        default="spectral",
        help="embedding objective: spectral = classical-MDS eigensolve "
        "(the paper's tail), stress = Sammon stress refined by AdamW on "
        "the spectral init, path = path-based landmark Isomap over "
        "reference shortest paths (repro.core.embedding)",
    )
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()
    enable_compile_cache()
    if args.manifold:
        mesh_shape = None
        if args.mesh:
            parts = args.mesh.lower().split("x")
            if len(parts) != 2 or not all(p.isdigit() and p for p in parts):
                ap.error("--mesh must look like 4x2 (data x model)")
            mesh_shape = (int(parts[0]), int(parts[1]))
        out = serve_manifold(
            n_base=args.n_base,
            n_stream=args.n_stream,
            stream_batch=args.stream_batch,
            max_latency_ms=args.max_latency_ms,
            arrival=args.arrival,
            k=args.k,
            d=args.d,
            block=args.block,
            seed=args.seed,
            checkpoint_dir=args.checkpoint_dir,
            resume=args.resume,
            checkpoint_secs=args.checkpoint_secs,
            absorb=args.absorb,
            mesh_shape=mesh_shape,
            regime=args.regime,
            landmarks=args.landmarks,
            objective=args.objective,
            replicas=args.replicas,
            router_vnodes=args.router,
            pipeline_depth=args.pipeline_depth,
        )
        print(
            f"[serve manifold] regime={out['regime']} "
            f"objective={out['objective']} "
            f"fit={out['fit_s']:.2f}s "
            f"serve={out['serve_s']:.3f}s "
            f"({out['points_per_s']:.0f} pts/s) "
            f"p50={out['latency_p50_ms']:.1f}ms "
            f"p99={out['latency_p99_ms']:.1f}ms "
            f"wait={out['queue_wait_ms']:.1f}ms "
            f"service={out['service_ms']:.1f}ms "
            f"mean_batch={out['mean_batch']:.1f} "
            f"absorbed={out['absorbed']} v{out['serving_version']} "
            f"err={out['procrustes_error']:.2e} "
            f"rv={out['residual_variance']:.3f}"
        )
        for s in out["replica_stats"]:
            print(
                f"  [replica {s['replica']}] requests={s['requests']} "
                f"p50={s['latency_p50_ms']:.1f}ms "
                f"p99={s['latency_p99_ms']:.1f}ms "
                f"applied_step={s['applied_step']} "
                f"lag={s['lag_steps']} alive={s['alive']}"
            )
        return
    if not args.arch:
        ap.error("--arch is required unless --manifold is given")
    out = generate(
        args.arch,
        batch=args.batch,
        prompt_len=args.prompt_len,
        gen_len=args.gen_len,
        smoke=args.smoke,
        temperature=args.temperature,
    )
    print(
        f"[serve {args.arch}] prefill={out['prefill_s']:.2f}s "
        f"decode={out['decode_s']:.2f}s ({out['tok_per_s']:.1f} tok/s)"
    )
    print("sample generation:", out["generated"][0][:16])


if __name__ == "__main__":
    main()
