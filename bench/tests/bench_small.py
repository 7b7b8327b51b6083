"""Whole runs of the cells at sizes a test run holds (CPU)."""
import types

#: small sizes a test run holds; everything else is the cell's own
SMALL = {"fit.swissroll-dense": 1024, "fit.swissroll-sparse": 2048,
         "serve.swissroll-dense-over": 1024}


def small_cell(name):
    from bench import generator, run

    bench, cell, cfg, traffic = run.find_cell(name)
    cfg = dict(cfg, n=SMALL[name])
    if traffic["kind"] == "open_reads":
        traffic = dict(traffic, rate_pts_s=400, pool=512,
                       warmup_requests=8)
    generator.validate(cell, cfg, traffic)
    return bench, cell, cfg, traffic


def run_small(name, seed=2**31 + 5, seconds=1.0):
    """A whole run of the cell at a small size on the CPU, past the
    harness's look for a chip: -> the result object."""
    import jax

    from bench import run

    bench, cell, cfg, traffic = small_cell(name)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    devs = jax.devices()[: cell["chips"]]
    return run.measure_cell(args, bench, cell, cfg, traffic, devs)
