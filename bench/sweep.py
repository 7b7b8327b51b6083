"""Sweep the offered rate of an open-loop reads cell to find its knee.

    python3 bench/sweep.py --workload serve.swissroll-dense-over --seed <n> \
        --seconds 5 --rates 2000 4000 8000 ...

Runs the cell's traffic once per rate, in one process on the chip it
starts on, and prints for each the offered and completed points/s, the
p50/p99 latency from due time, how late the generator ran and the mean
flush fill.  The knee is the highest rate whose completed rate keeps up
with the offered one while the tail stays flat; a cell's traffic file
fixes its rate from it, below the knee where tails are judged, above it
where the points answered are.  The benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    from bench import generator, run

    _, cell, cfg, traffic = run.find_cell(args.workload)
    run.prepare(cell["chips"])
    for rate in args.rates:
        r = generator.Run(cell=cell["name"], cfg=cfg,
                          traffic=dict(traffic, rate_pts_s=rate),
                          seed=args.seed, seconds=args.seconds, trace=False)
        generator.open_reads(r)
        c = r.spans.counters
        print(json.dumps({
            "offered_pts_s": rate, "completed_pts_s": r.e2e["read_pts_s"],
            "p50_ms": c["read_p50_ms"], "p99_ms": r.e2e["read_p99_ms"],
            "gen_late_p99_ms": c["gen_late_p99_ms"],
            "fill": c["points"] / max(c["flushes"], 1) / c["max_batch"],
            "failed": r.failed,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
