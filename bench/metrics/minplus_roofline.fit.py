"""Roofline share of the min-plus family in the dense APSP.

The least time the chip could take for every min-plus call of the fits in
the traced window, over the device time of those calls' events.  Per
blocked Floyd-Warshall iteration (n / b of them per fit, block b):

    Phase 1  floyd_warshall       (b, b)       b^3 terms
    Phase 2  minplus_panel_row    (b, b)(b, n) b^2 n terms
             minplus_panel_col    (n, b)(b, b) n b^2 terms
    Phase 3  minplus_update       (n, b)(b, n) n^2 b terms

Each term is one add and one min on the VPU; the bytes are each call's
operands read once and its result written once (float32).  A call's
bound is the larger of ops / VPU peak and bytes / HBM peak.
"""
import re

#: the kernels' op families in the trace (named by the launching function)
PATTERN = re.compile(r"^(minplus|floyd_warshall)")


def calls(n: int, b: int):
    """-> [(terms, bytes, calls)] of one dense fit."""
    q = n // b
    return [
        (b**3, 4 * 2 * b * b, q),
        (b * b * n, 4 * (b * b + 2 * b * n), q),
        (n * b * b, 4 * (b * b + 2 * n * b), q),
        (n * n * b, 4 * (2 * n * n + 2 * n * b), q),
    ]


def bound_s(n: int, b: int, vpu: float, hbm: float) -> float:
    return sum(c * max(2 * t / vpu, by / hbm) for t, by, c in calls(n, b))


def read(ctx):
    evs = ctx.reduced.events(PATTERN)
    if not evs:
        return None
    device_s = sum(e - s for _, s, e, _ in evs) / 1e9
    n, b = ctx.cfg["n"], ctx.cfg["block"]
    need = ctx.counters["fits"] * ctx.reduced.devices * bound_s(
        n, b, ctx.peaks["vpu_ops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * need / device_s
