"""How far the busiest chip's frontier work exceeds the mean over chips.

On a mesh each chip solves its own slice of the landmark rows, with no
collective in the loop, so the fit waits for the chip whose slice needs
the most sweeps.  Per chip this sums the device seconds of the window's
``frontier_relax`` calls (one a sweep), and gives (max / mean - 1) in %:
0 where every chip sweeps as long as the others.  Nothing to read (None)
with fewer than two chips or no such calls.
"""
import re

PATTERN = re.compile(r"^frontier_relax$")


def read(ctx):
    ops = ctx.reduced.ops
    if len(ops) < 2:
        return None
    per_chip = [sum(e0 - s0 for name, s0, e0, _ in evs
                    if PATTERN.search(name)) / 1e9
                for evs in ops.values()]
    mean = sum(per_chip) / len(per_chip)
    if mean == 0.0:
        return None
    return 100.0 * (max(per_chip) / mean - 1.0)
