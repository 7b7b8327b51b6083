"""One run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name:
``BENCHMARK.json`` names them, ``bench/configs/<config>.json`` holds the
deployment's sizes, ``bench/traffic/<traffic>.json`` the load, and each
per-layer metric is read by ``bench/metrics/<metric>.py``.

A run loads, warms every program the cell's traffic uses (set-up, timed
as ``setup_s``), measures for ``--seconds``, reads the device's peak
memory, frees the program's state, and then compares what the timed path
produced with the float64 reference (``bench/checks.py``).  With
``--trace 1`` the window runs under the profiler and the line carries the
per-layer metrics instead of the end-to-end ones.  Earlier lines are
free-form; the last line of standard output is one JSON object, and the
last lines of standard error give each compared number beside its limit.

The run refuses (non-zero exit, no result) off a TPU, with fewer chips
than the cell asks for, with an active tile calibration store, or outside
a checkout that holds the program.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: persistent compilation cache: one fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: profiler output of the traced run (removed once reduced)
TRACE_DIR = os.path.join(ROOT, "bench", ".trace")


class Refused(RuntimeError):
    """The run cannot measure here; it prints no result."""


def log(tag: str, **nums) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in nums.items()),
          flush=True)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def find_cell(name: str):
    """-> (benchmark, cell, config file dict, traffic dict)."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(conf["file"])
    traffic = load_json("bench", "traffic", cell["traffic"] + ".json")
    from bench import generator

    generator.validate(cell, cfg, traffic)
    return bench, cell, cfg, traffic


def metrics_for(bench: dict, cell: str, key: str) -> list[dict]:
    """The cell's metrics of one kind: those whose ``workloads`` list it,
    or that list none."""
    return [m for m in bench[key]
            if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def prepare(chips: int):
    """Import JAX on the chip, refuse anywhere else; turn on the
    persistent compilation cache.  -> the devices the cell uses."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise Refused(f"no program under {src}: run from a checkout of "
                      "the repository")
    sys.path.insert(0, src)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform} "
                      f"({devs[0].device_kind}, {len(devs)} device(s))")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devs)}")
    from repro.kernels import measure

    if measure.active():
        raise Refused("a tile calibration store would steer the kernels "
                      f"({measure.tuning_path()} or "
                      f"{measure.ENV_MEASURE}); move it away")
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return devs[:chips]


class CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    def __init__(self):
        from jax import monitoring

        self.on = False
        self.traces = 0
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if not self.on:
            return
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


def profiler():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    return jax.profiler.trace(TRACE_DIR, profiler_options=opts)


def measure_cell(args, bench, cell, cfg, traffic, devs) -> dict:
    """Set-up, window, memory, references: -> the result object."""
    from bench import checks, generator, trace as trace_mod

    run = generator.Run(
        cell=cell["name"], cfg=cfg, traffic=traffic, seed=args.seed,
        seconds=float(args.seconds), trace=bool(args.trace), devices=devs,
        profile=profiler if args.trace else None,
    )
    counter = run.counter = CompileCounter()
    generator.generate(run)
    setup_s = run.window[0] - T_START
    log("window", seconds=run.window[1] - run.window[0],
        traces_in_window=counter.traces,
        compiles_in_window=counter.compiles, setup_s=setup_s)
    if counter.compiles:
        print(f"warning: {counter.compiles} compilation(s) inside the "
              "window: the warm-up missed a shape", file=sys.stderr)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}

    result_metrics = {}
    breakdown = None
    if args.trace:
        from bench import vpu_probe

        red = trace_mod.reduce(trace_mod.load(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        peaks = load_json("bench", "peaks.json")[devs[0].device_kind]
        rate = vpu_probe.measure()
        log("vpu_probe", addmin_ops_per_s=rate,
            table_vpu_ops_per_s=peaks["vpu_ops_per_s"],
            within_table=rate <= peaks["vpu_ops_per_s"])
        ctx = Context(run=run, reduced=red, peaks=peaks)
        for m in metrics_for(bench, cell["name"], "per_layer"):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result_metrics[m["name"]] = {"value": value,
                                             "unit": m["unit"]}
    else:
        values = dict(run.e2e, setup_s=setup_s)
        for m in metrics_for(bench, cell["name"], "end_to_end"):
            result_metrics[m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    log("counters", **run.spans.counters)

    # the program's state went with the generator's frames: what is
    # left is host data for the references
    vals = checks.values(traffic["kind"], run.kept, cfg)
    correct, compared = checks.decide(vals, run.failed)
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": result_metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = compared
    return out


class Context:
    """What a per-layer metric reader gets: the run (its counters, spans,
    configuration and traffic), the reduced trace and the peaks table."""

    def __init__(self, run, reduced, peaks):
        self.run = run
        self.reduced = reduced
        self.peaks = peaks
        self.cfg = run.cfg
        self.counters = run.spans.counters
        self.spans = run.spans


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        bench, cell, cfg, traffic = find_cell(args.workload)
        devs = prepare(cell["chips"])
    except (Refused, OSError, KeyError, ValueError) as e:
        print(f"bench/run.py: refused: {e}", file=sys.stderr)
        return 2
    out = measure_cell(args, bench, cell, cfg, traffic, devs)
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(f"correct={out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    if sys.path and os.path.abspath(sys.path[0]) == os.path.dirname(
            os.path.abspath(__file__)):
        # this directory holds trace.py; keep it from shadowing the
        # standard library's module of that name
        sys.path[0] = ROOT
    sys.exit(main())
