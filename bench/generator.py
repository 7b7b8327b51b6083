"""The one load generator: every traffic mix is a data file it reads.

``bench/traffic/<mix>.json`` names its ``kind`` and parameters:

* ``closed_fits`` - a batch job embedding data: fits run back to back
  through ``ManifoldPipeline.run`` (built by ``stages_for``), each on the
  configuration's n points (``data.points``) and each ended by
  ``block_until_ready`` on the embedding.  Set-up fits
  once, which compiles (or loads) every program the window will use.
* ``open_reads`` - independent clients mapping new points: an open loop
  of requests into ``BatchedMapperService.submit`` over the mapper of the
  configuration's regime, arriving as a Poisson process at
  ``rate_pts_s``, each request a seed-drawn group of held-out points.
  Every seed gets the same multiset of request sizes and inter-arrival
  gaps, in its own order, so the offered work is the same in every run.
  Each request is timed from when it was due, not from when the
  generator got round to submitting it.

Both write what they timed into the :class:`Run` and keep what the checks
compare (``bench/checks.py``) for after the window.  What a cell runs on
comes from its files alone: the data set and regime from the
configuration, the backend from the chips the cell asks for
(:func:`backend`), the load from the traffic file.  :func:`validate`
refuses files that name anything else, or hold a key nothing reads.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any

import numpy as np

from bench import data
from bench.stages import Spans, spanned

#: longest wait for an answer after the window closes
ANSWER_WAIT_S = 60.0

#: configuration keys the harness reads, and those that only describe
CONFIG_READ = {"dataset", "draw", "D", "n", "k", "d", "regime", "block",
               "landmarks", "max_iter", "tol", "precision"}
CONFIG_DESCRIBE = {"source", "guarantees", "deployment", "reduced",
                   "assumed"}
#: traffic keys each kind reads (``why`` describes)
TRAFFIC_KEYS = {
    "closed_fits": {"kind", "why", "check_knn_rows", "check_sources"},
    "open_reads": {"kind", "why", "rate_pts_s", "size", "pool", "service",
                   "warmup_requests", "check_requests"},
}
#: the program computes in float32 only
PRECISIONS = {"float32"}
REGIMES = {"dense", "sparse"}
SIZE_DISTS = {"geometric"}


def validate(cell: dict, cfg: dict, traffic: dict) -> None:
    """Raise ValueError where a cell's files name something the harness
    cannot run as stated, or hold a key that nothing reads."""
    kind = traffic.get("kind")
    if kind not in TRAFFIC_KEYS:
        raise ValueError(f"unknown traffic kind {kind!r}")
    extra = set(traffic) - TRAFFIC_KEYS[kind]
    missing = TRAFFIC_KEYS[kind] - set(traffic)
    if extra or missing:
        raise ValueError(f"traffic {cell['traffic']!r}: keys nothing reads "
                         f"{sorted(extra)}, missing {sorted(missing)}")
    extra = set(cfg) - CONFIG_READ - CONFIG_DESCRIBE
    missing = CONFIG_READ - set(cfg)
    if extra or missing:
        raise ValueError(f"configuration {cell['config']!r}: keys nothing "
                         f"reads {sorted(extra)}, missing {sorted(missing)}")
    for key, known in (("dataset", data.DATASETS), ("precision", PRECISIONS),
                       ("regime", REGIMES)):
        if cfg[key] not in known:
            raise ValueError(f"{key} {cfg[key]!r} is not one of "
                             f"{sorted(known)}")
    if kind == "open_reads":
        if cfg["regime"] not in MAPPERS:
            raise ValueError(f"no mapper for the {cfg['regime']} regime")
        if cell["chips"] != 1:
            raise ValueError("the reads traffic serves from one chip")
        if traffic["size"]["dist"] not in SIZE_DISTS:
            raise ValueError(f"size dist {traffic['size']['dist']!r}")
    if cell["chips"] not in (1, 4):
        raise ValueError(f"chips {cell['chips']!r} is not 1 or 4")


def backend(devices):
    """The pipeline backend for the chips a cell asks for: one chip runs
    ``LocalBackend``, four a ``MeshBackend`` over a (2, 2) mesh."""
    from repro.core.pipeline import LocalBackend, MeshBackend

    if len(devices) == 1:
        return LocalBackend()
    from jax.sharding import Mesh

    grid = np.asarray(devices, dtype=object).reshape(2, len(devices) // 2)
    return MeshBackend(Mesh(grid, ("data", "model")))


@dataclasses.dataclass
class Run:
    """What one run of one cell knows and gathers."""

    cell: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: Any = None            # the chips the cell uses
    profile: Any = None            # context manager around the window
    counter: Any = None            # compile counter, on in the window
    spans: Spans = dataclasses.field(default_factory=Spans)
    window: tuple = (0.0, 0.0)     # perf_counter start, end
    e2e: dict = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    kept: Any = None               # what the checks compare


def generate(run: Run) -> None:
    kinds = {"closed_fits": closed_fits, "open_reads": open_reads}
    kind = run.traffic["kind"]
    if kind not in kinds:
        raise ValueError(f"unknown traffic kind {kind!r}")
    kinds[kind](run)


class _GcPauses:
    """Counts the collector's pauses (``gc_pauses``, ``gc_max_ms``,
    ``gc_total_ms``) into ``counters``: a host stall that is not the
    program's own shows here."""

    def __init__(self, counters: dict):
        self.c = counters
        self.c.update(gc_pauses=0, gc_max_ms=0.0, gc_total_ms=0.0)
        self.t0 = 0.0

    def __call__(self, phase, _info):
        if phase == "start":
            self.t0 = time.perf_counter()
            return
        ms = (time.perf_counter() - self.t0) * 1e3
        self.c["gc_pauses"] += 1
        self.c["gc_max_ms"] = max(self.c["gc_max_ms"], ms)
        self.c["gc_total_ms"] += ms


def _window(run: Run):
    """Profiler (traced run only) and the ``bench:window`` span."""
    import contextlib
    import gc

    # set-up's objects out of the collector's way: a full collection
    # inside the window would stall the host for what set-up left
    gc.collect()
    gc.freeze()
    stack = contextlib.ExitStack()
    pauses = _GcPauses(run.spans.counters)
    gc.callbacks.append(pauses)
    stack.callback(gc.callbacks.remove, pauses)
    if run.profile is not None:
        stack.enter_context(run.profile())
    stack.enter_context(run.spans.span("window"))
    if run.counter is not None:
        run.counter.on = True
        stack.callback(setattr, run.counter, "on", False)
    return stack


# ------------------------------------------------------------ fits ----


def _take_rows():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda a, i: jnp.take(a, i, axis=0))


def closed_fits(run: Run) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.pipeline import (
        ManifoldPipeline, PipelineConfig, stages_for,
    )

    cfg, tr = run.cfg, run.traffic
    n = cfg["n"]
    pcfg = PipelineConfig(
        k=cfg["k"], d=cfg["d"], block=cfg["block"], regime=cfg["regime"],
        landmarks=cfg["landmarks"], max_iter=cfg["max_iter"],
        tol=cfg["tol"],
    )
    stages = stages_for(pcfg, n)
    if run.trace:
        stages = spanned(stages, run.spans)
    be = backend(run.devices)
    served = ManifoldPipeline(stages, cfg=pcfg, backend=be)
    pipe = ManifoldPipeline(
        stages, cfg=pcfg, backend=be,
        exports=(*served.exports, "knn_dists", "knn_idx",
                 *(("csr_nbr",) if cfg["regime"] == "sparse" else ())),
    )
    sparse = cfg["regime"] == "sparse"
    pick = data.rng_for(run.seed, 2)
    knn_rows = np.sort(pick.choice(n, tr["check_knn_rows"], replace=False))
    geo_rows = np.sort(pick.choice(n, tr["check_sources"], replace=False))
    take = _take_rows()

    # on a mesh the features shard over its model axis: zero columns pad
    # them to a multiple, as serve_manifold pads them (no distance moves)
    pad = -cfg["D"] % be.mesh.shape["model"] if hasattr(be, "mesh") else 0

    def feed(x):
        return jnp.asarray(np.pad(x, ((0, 0), (0, pad))))

    def fit():
        x, latent = data.points(cfg, n)
        art = pipe.run(feed(x))
        jax.block_until_ready(art["embedding"])
        return x, latent, art

    def keep(x, latent, art):
        out = {
            "x": x, "latent": latent, "knn_rows": knn_rows,
            "knn_d2": np.asarray(take(art["knn_dists"], knn_rows)),
            "embedding": np.asarray(art["embedding"]),
        }
        # the work a fit did, for the log: eigensolver iterations (dense)
        # and padded-CSR lanes (sparse), set by the data alone
        shape = run.spans.counters.setdefault("fit_shape", [])
        if sparse:
            shape.append(int(art["csr_nbr"].shape[1]))
            lm = np.asarray(art["lm_idx"])
            rows = np.sort(data.rng_for(run.seed, 3).choice(
                lm.shape[0], tr["check_sources"], replace=False))
            out["geo_src"] = lm[rows]
            out["geo"] = np.asarray(take(art["panel"], rows))
            out["lmds_src"] = geo_rows
        else:
            shape.append(int(art["iterations"]))
            out["geo_src"] = geo_rows
            out["geo"] = np.asarray(take(art["geodesics"], geo_rows))
            out["lmds_src"] = geo_rows
        return out

    # set-up: a fit of the same points compiles or loads every program
    # the window will use (every shape is set by the points, which all
    # fits share)
    x0, _ = data.points(cfg, n)
    art0 = pipe.run(feed(x0))
    jax.block_until_ready(art0["embedding"])
    keep(x0, None, art0)
    del art0
    kept = []
    t_end = None
    with _window(run):
        t0 = time.perf_counter()
        while t_end is None or t_end - t0 < run.seconds:
            x, latent, art = fit()
            t_end = time.perf_counter()
            kept.append(keep(x, latent, art))
            del art
    run.window = (t0, t_end)
    run.attempted = len(kept)
    run.e2e["fit_s"] = (t_end - t0) / len(kept)
    run.spans.counters["fits"] = len(kept)
    run.kept = kept


# ----------------------------------------------------------- reads ----


def schedule(tr: dict, seconds: float, seed: int):
    """-> (due offsets in s, request sizes) of the requests due in the
    window.  Gaps and sizes are one fixed multiset (drawn from stream 0),
    permuted by the run's seed."""
    size = tr["size"]
    mean_size = size["mean"]
    rate = tr["rate_pts_s"] / mean_size                    # requests/s
    total = rate * seconds
    base = data.rng_for(0, 0)
    gaps = base.exponential(1.0, int(total * 1.2) + 64)
    n_req = int(np.searchsorted(np.cumsum(gaps), total, side="right"))
    gaps = gaps[:n_req]
    sizes = np.minimum(base.geometric(1.0 / mean_size, n_req), size["max"])
    own = data.rng_for(seed, 5)
    due = np.cumsum(own.permutation(gaps)) / rate
    return due, own.permutation(sizes)


def strip_geodesics(latent):
    """(n, n) float32 exact geodesics of the roll, made on the device in
    one call: distances between latent strip coordinates."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(z):
        du = z[:, 0, None] - z[None, :, 0]
        dh = z[:, 1, None] - z[None, :, 1]
        return jnp.sqrt(du * du + dh * dh)

    return make(jnp.asarray(latent, jnp.float32))


def latencies(t_due, t_done, failed, t_close):
    """-> (latency of each request from its due time, answered-by-close
    mask).  A request that failed or was never answered (``t_done`` NaN)
    is missed: it reads as answered at the end of the wait,
    ``ANSWER_WAIT_S`` past the close, and never as in the window."""
    t_due = np.asarray(t_due, np.float64)
    t_done = np.asarray(t_done, np.float64)
    missed = np.isnan(t_done) | np.asarray(failed, bool)
    done = np.where(missed, t_close + ANSWER_WAIT_S, t_done)
    return done - t_due, ~missed & (done <= t_close)


class _Answers:
    """Completion times, failures and the checked answers of the
    window's requests, filled in by the futures' callbacks.  The window
    keeps no future alive, so the live heap (and with it a collection's
    pause) stays the size of the requests in flight."""

    def __init__(self, n: int, keep):
        import threading

        self.t_done = np.full(n, np.nan)
        self.failed = np.zeros(n, bool)
        self.keep = set(int(i) for i in keep)
        self.answers: dict[int, np.ndarray] = {}
        self.count = 0
        self.lock = threading.Lock()

    def callback(self, i: int):
        import functools

        return functools.partial(self._done, i)

    def _done(self, i, fut):
        t = time.perf_counter()
        exc = fut.exception()
        with self.lock:
            self.t_done[i] = t
            self.failed[i] = exc is not None
            if exc is None and i in self.keep:
                self.answers[i] = np.asarray(fut.result())
            self.count += 1

    def wait(self, deadline: float):
        """Until every request has an outcome or ``deadline`` passes."""
        while self.count < self.t_done.shape[0]:
            if time.perf_counter() >= deadline:
                return
            time.sleep(0.005)


def _dense_mapper(cfg, x_base, lat_base, y_base, batch):
    """The dense regime's ``StreamingMapper`` over the base points, their
    exact geodesics and their chart."""
    import jax.numpy as jnp

    from repro.core.streaming import StreamingMapper

    return StreamingMapper(
        jnp.asarray(x_base), strip_geodesics(lat_base),
        jnp.asarray(y_base, jnp.float32), k=cfg["k"], batch=batch,
    )


#: the mapper the reads traffic serves through, by the regime
MAPPERS = {"dense": _dense_mapper}


def open_reads(run: Run) -> None:
    from bench.reference import principal_chart
    from repro.launch.serving import BatchedMapperService

    cfg, tr = run.cfg, run.traffic
    n, svc_cfg = cfg["n"], tr["service"]
    max_batch = svc_cfg["max_batch"]
    x, latent = data.points(cfg, n + tr["pool"])
    x_base, lat_base, pool = x[:n], latent[:n], x[n:]
    y_base = principal_chart(lat_base)
    mapper = MAPPERS[cfg["regime"]](cfg, x_base, lat_base, y_base, max_batch)
    call = mapper
    if run.trace:
        def call(xb):
            with run.spans.span("map"):
                return mapper(xb)

    due, sizes = schedule(tr, run.seconds, run.seed)
    pick = data.rng_for(run.seed, 4)
    points = [pick.integers(0, pool.shape[0], s) for s in sizes]
    # every request's array is built here, in set-up: built in the
    # window, the generator's own indexing took the host from the service
    # and set the knee (33000-37000 points/s on one v5e, against over
    # 44000 with them built here; PERF.md)
    reqs = [pool[p] for p in points]
    n_req = len(due)
    check = np.sort(data.rng_for(run.seed, 6).choice(
        n_req, min(tr["check_requests"], n_req), replace=False))
    rec = _Answers(n_req, check)
    t_sub = np.zeros(n_req)
    svc = BatchedMapperService(
        call, max_batch=max_batch,
        max_latency_ms=svc_cfg["max_latency_ms"],
        pipeline_depth=svc_cfg["pipeline_depth"],
    )
    with svc:
        # set-up: the one padded (max_batch, D) program, then a short
        # burst through the queue so threads and pools are up
        svc.warmup(x.shape[1])
        warm = [svc.submit(pool[pick.integers(0, pool.shape[0], s)])
                for s in sizes[: tr["warmup_requests"]]]
        for f in warm:
            f.result()
        del warm
        s0 = svc.stats()
        with _window(run):
            t0 = time.perf_counter()
            for i in range(n_req):
                wait = t0 + due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                t_sub[i] = time.perf_counter()
                svc.submit(reqs[i]).add_done_callback(rec.callback(i))
            t_close = t0 + run.seconds
            wait = t_close - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
        rec.wait(t_close + ANSWER_WAIT_S)
        s1 = svc.stats()
    run.window = (t0, t_close)
    t_due = t0 + due
    lat, in_window = latencies(t_due, rec.t_done, rec.failed, t_close)
    run.attempted = n_req
    run.failed = int(np.sum(np.isnan(rec.t_done) | rec.failed))
    run.e2e["read_p99_ms"] = float(np.percentile(lat, 99) * 1e3)
    run.e2e["read_pts_s"] = float(np.sum(sizes[in_window]) / run.seconds)
    c = run.spans.counters
    c["requests"] = n_req
    c["points"] = s1["points"] - s0["points"]
    c["flushes"] = s1["batches"] - s0["batches"]
    c["max_batch"] = max_batch
    c["gen_late_p99_ms"] = float(np.percentile(t_sub - t_due, 99) * 1e3)
    c["read_p50_ms"] = float(np.percentile(lat, 50) * 1e3)
    run.kept = {
        "x_base": x_base, "latent": lat_base, "y_base": y_base,
        "x_new": [reqs[i] for i in check],
        "y_new": [rec.answers.get(i) for i in check],
    }
