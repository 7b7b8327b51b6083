"""Reduction of a profiler trace to the numbers the benchmark reports.

``load`` turns the profiler's ``.xplane.pb`` into a small normalised form
(plain lists, JSON-safe, also the form of the committed test fixture)::

    {"device": {"<id>": [[name, start_ns, dur_ns, text], ...]},
     "host":   [[name, start_ns, dur_ns], ...]}

``device`` holds the operations of each accelerator (the plane's
``XLA Ops`` line).  The profiler names each by its HLO instruction
(``%minplus_update.11 = f32[24576,24576]{...} custom-call(...)``); ``name``
is the op's family, the instruction name without its ``%`` and numeric
suffix (``minplus_update``: a Pallas kernel is named by the function that
launched it), and ``text`` is the whole instruction, operand shapes
included.  ``host`` holds only the benchmark's own spans (names starting
``bench:``), written with ``jax.profiler.TraceAnnotation`` on the same
clock.

``reduce`` cuts both to the ``bench:window`` span and gives busy time
(the union of op intervals), per-family sums (control-flow ops such as
``while``, which enclose the ops of their body, are left out of the
sums), and the idle gaps, each gap named by the innermost benchmark span
the host was in at its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench:"
WINDOW = "bench:window"
OPS_LINE = "XLA Ops"
#: ops that enclose other ops of the same line
CONTAINERS = {"while", "conditional", "call"}
_SUFFIX = re.compile(r"\.\d+$")


def family(hlo: str) -> str:
    """``%minplus_update.11 = f32[...] custom-call(...)`` -> ``minplus_update``."""
    return _SUFFIX.sub("", hlo.split(" = ", 1)[0].strip().lstrip("%"))


def load(trace_dir: str) -> dict:
    """Normalised form of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            line = lines.get(OPS_LINE)
            if line is None:
                continue
            dev = plane.name.rsplit(":", 1)[-1]
            evs = device.setdefault(dev, [])
            for e in line.events:
                evs.append([family(e.name), int(e.start_ns),
                            int(e.duration_ns), e.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": host}


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # mean over devices
    devices: int
    op_s: dict                         # family -> seconds, mean over devices
    ops: dict                          # device -> [[name, s_ns, e_ns, text]]
    gaps: dict                         # span name -> idle s, mean over devices
    spans: list                        # [[name, start_ns, end_ns]] in window
    window_ns: tuple

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}

    def events(self, pattern) -> list:
        """Ops in the window (every device) whose family matches the
        compiled regex ``pattern``: [[family, start_ns, end_ns, text]]."""
        return [e for evs in self.ops.values() for e in evs
                if pattern.search(e[0])]


def _innermost(spans, t):
    """Name of the shortest span containing time t ('window' if none)."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0][len(SPAN_PREFIX):] if best else "window"


def reduce(norm: dict) -> Reduced:
    wins = [h for h in norm["host"] if h[0] == WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {WINDOW} span")
    w0 = wins[0][1]
    w1 = w0 + wins[0][2]
    spans = [[n, max(s, w0), min(s + d, w1)] for n, s, d in norm["host"]
             if n != WINDOW and s < w1 and s + d > w0]
    devs = sorted(norm["device"])
    if not devs:
        raise ValueError("trace holds no device operations")
    busy = 0.0
    op_s: dict[str, float] = {}
    gaps: dict[str, float] = {}
    ops = {}
    for dev in devs:
        clipped = []
        for name, s, d, text in norm["device"][dev]:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                clipped.append([name, s0, e0, text])
                if name not in CONTAINERS:
                    op_s[name] = op_s.get(name, 0.0) + (e0 - s0) / 1e9
        ops[dev] = clipped
        merged = _union([(s, e) for _, s, e, _ in clipped])
        busy += sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 > g0:
                name = _innermost(spans, (g0 + g1) / 2)
                gaps[name] = gaps.get(name, 0.0) + (g1 - g0) / 1e9
    k = len(devs)
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy / k,
        devices=k,
        op_s={n: v / k for n, v in op_s.items()},
        ops=ops,
        gaps={n: v / k for n, v in gaps.items()},
        spans=spans,
        window_ns=(w0, w1),
    )

