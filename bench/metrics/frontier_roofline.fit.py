"""Roofline share of the frontier relaxation kernel in the sparse fit.

Each ``frontier_relax`` call of the traced window sweeps an (s, deg, n)
block of gathered tentative distances: s sources, n nodes, deg neighbour
lanes.  Its terms are s deg n, one add and one min each on the VPU; its
bytes are the gathered block, the (deg, n) weights and the (s, n) seed
read once and the (s, n) result written once (float32).  The shape of
each call is read from the op's long name in the trace; a call whose
shape the trace does not give is not counted.
"""
import re

PATTERN = re.compile(r"^frontier_relax$")
SHAPE = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def bound_s(s: int, deg: int, n: int, vpu: float, hbm: float) -> float:
    ops = 2 * s * deg * n
    nbytes = 4 * (s * deg * n + deg * n + 2 * s * n)
    return max(ops / vpu, nbytes / hbm)


def read(ctx):
    need = 0.0
    device_s = 0.0
    for _, s0, e0, text in ctx.reduced.events(PATTERN):
        m = SHAPE.search(text)
        if m is None:
            continue
        s, deg, n = map(int, m.groups())
        need += bound_s(s, deg, n, ctx.peaks["vpu_ops_per_s"],
                        ctx.peaks["hbm_bytes_per_s"])
        device_s += (e0 - s0) / 1e9
    if device_s == 0.0:
        return None
    return 100.0 * need / device_s
