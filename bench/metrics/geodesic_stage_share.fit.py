"""Share of the fits' wall time spent inside the geodesic stage (dense
``apsp`` or sparse ``sparse_geodesics``), from the benchmark's spans
around each stage call in the traced run."""

STAGES = ("stage:apsp", "stage:sparse_geodesics")


def read(ctx):
    window = ctx.run.window
    geo = sum(ctx.spans.total(s, window) for s in STAGES)
    wall = window[1] - window[0]
    if geo == 0.0 or wall == 0.0:
        return None
    return 100.0 * geo / wall
