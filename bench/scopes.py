"""The program's own spans and scopes in a profiler trace.

The program names its work in two ways (``repro.core.telemetry``): host
spans ``repro:<name>`` (``TraceAnnotation``, one thread each), and
``jax.named_scope`` paths that every device operation carries in its HLO
metadata (``op_name``, e.g. ``jit(apsp_blocked_segment)/apsp/while/body/
update/...``).  ``bench/trace.py`` keeps neither; this module reads both
from the same ``.xplane.pb``.  On a TPU an op's ``op_name`` is the
``tf_op`` stat of its event metadata, or, for ops without one (``while``
loops), the op's instruction in the HLO of its program, which the trace
keeps in its metadata plane.  ``load`` gives::

    {"device": {"<id>": [[family, start_ns, dur_ns, scope], ...]},
     "host":   [[name, start_ns, dur_ns, thread], ...]}

``host`` holds the benchmark's ``bench:`` spans and the program's
``repro:`` spans with the thread that opened each (its line's name and
place in the host plane).  ``scope`` is the op's scope path
(``apsp/update``, ``sparse_geodesics/gather``):
its ``op_name`` without JAX's own name-stack entries (``jit(f)``,
``while``, ``body``, ``shard_map``, ...) and without the primitive, and
``""`` where the op carries none.

``reduce`` cuts both to the ``bench:window`` span and gives

* ``scope_s``: device seconds per scope path, mean over devices, with
  control-flow containers (``trace.CONTAINERS``) left out.  An op with
  no scope of its own (XLA's loop-carried copies carry no metadata) takes
  the scope of the innermost container op around it on its device, and
  is ``none`` outside every scoped container;
* ``program_gaps``: the device's idle gaps (mean over devices), each
  named by the innermost ``repro:`` span open at its midpoint on each
  host thread, the names of all threads sorted and joined with ``+``, or
  ``none`` where no thread is inside a program span.

The ``read_*`` functions are the readings a per-layer metric takes from
that reduction and from the service's ``stats()`` counters.

Run as a script, it runs one cell traced through ``bench/run.py``
(same arguments, ``--trace`` forced to 1) and then logs the program's
view of the window: ``[scopes]``, ``[program_gaps]`` and
``[program_readings]``::

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s> \\
        [--dump <file.json>]

``--dump`` also writes the whole normalised window, the stats of each
distinct device op and the window's counters to a file.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

if __package__ in (None, ""):
    import sys

    # run as a script: the checkout (for ``bench``) and its program
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0] = _ROOT
    sys.path.insert(1, os.path.join(_ROOT, "src"))

from bench import trace  # noqa: E402

PROGRAM = "repro:"
SPAN_PREFIXES = (trace.SPAN_PREFIX, PROGRAM)
#: the device line of program executions (named ``<module>(<id>)``)
MODULES_LINE = "XLA Modules"
#: stats of a device op that may carry its HLO ``op_name``, by preference
OP_NAME_STATS = ("tf_op",)
#: entries JAX itself puts on the name stack
_STACK = {"while", "body", "cond", "closed_call", "core_call", "shard_map",
          "remat", "checkpoint", "pjit", "custom_jvp_call",
          "custom_vjp_call", "scan"}
_BRANCH = re.compile(r"^branch_\d+")
#: scope roots of the geodesic stage (dense and sparse chains)
GEODESIC = ("apsp", "sparse_geodesics")
GATHER = "sparse_geodesics/gather"
EXCHANGE = "apsp/exchange"
#: service counters (``BatchedMapperService.stats()``) of the flush path
FLUSH_HOST = ("pack_s", "fetch_s", "reply_s")


def scope_of(op_name: str) -> str:
    """``jit(f)/apsp/while/body/update/min`` -> ``apsp/update``."""
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts if p and "(" not in p
                    and p not in _STACK and not _BRANCH.match(p))


def _instruction(hlo: str) -> str:
    """``%fusion.13 = f32[...] fusion(...)`` -> ``fusion.13``."""
    return hlo.split(" = ", 1)[0].strip().lstrip("%")


def _op_name(stats: dict) -> str:
    for key in OP_NAME_STATS:
        if stats.get(key):
            return str(stats[key])
    return ""


# ----------------------------------------------------- xplane metadata --
#
# An op's HLO metadata sits in its event *metadata* (``XEventMetadata``
# stats of its plane), which ``jax.profiler.ProfileData`` does not
# expose: these read the few message fields needed straight from the
# protobuf wire format (tsl/profiler/protobuf/xplane.proto), skipping
# the events themselves.


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of one message: ints for varints, (start,
    end) for length-delimited fields; fixed-width fields are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        else:
            i += 8 if wire == 1 else 4
            continue
        yield field, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _entry(buf, span):
    """A map entry: -> its value message's (start, end)."""
    for f, v in _fields(buf, *span):
        if f == 2:
            return v
    return (span[0], span[0])


def _plane(buf, span):
    """-> (plane name, [(names, {stat name: str value or (start, end)
    of bytes})] of its event metadata)."""
    name, events, stat_names = "", [], {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 4:
            events.append(_entry(buf, v))
        elif f == 5:
            sm = dict(_fields(buf, *_entry(buf, v)))
            stat_names[sm.get(1, 0)] = _text(buf, sm.get(2, (0, 0)))
    out = []
    for span_ in events:
        names, stats = [], {}
        for f, v in _fields(buf, *span_):
            if f in (2, 4):
                names.append(_text(buf, v))
            elif f == 5:
                st = dict(_fields(buf, *v))
                key = stat_names.get(st.get(1), "")
                if 5 in st:
                    stats[key] = _text(buf, st[5])
                elif 6 in st:
                    stats[key] = st[6]
                elif 7 in st:
                    stats[key] = stat_names.get(st[7], "")
                elif 3 in st or 4 in st:
                    stats[key] = str(st.get(3, st.get(4)))
        out.append(([n for n in names if n], stats))
    return name, out


def _hlo_op_names(buf, span) -> dict:
    """An ``HloProto``: -> {instruction name: its metadata's op_name}."""
    out = {}
    for f, module in _fields(buf, *span):
        if f != 1:
            continue
        for g, comp in _fields(buf, *module):
            if g != 3:
                continue
            for h, inst in _fields(buf, *comp):
                if h != 2:
                    continue
                name = op_name = ""
                for k, v in _fields(buf, *inst):
                    if k == 1:
                        name = _text(buf, v)
                    elif k == 7:
                        op_name = _text(buf, dict(_fields(buf, *v)).get(
                            2, (0, 0)))
                out[name] = op_name
    return out


def xplane_metadata(path: str) -> tuple[dict, dict]:
    """-> ({device plane name: {op name: {stat name: value}}}, {program
    name: {HLO instruction name: op_name}}) of an ``.xplane.pb``: the ops'
    own metadata stats, and the HLO of each program the trace keeps."""
    with open(path, "rb") as f:
        buf = f.read()
    planes, programs = {}, {}
    for field, span in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name, events = _plane(buf, span)
        if name.startswith("/device:"):
            planes[name] = {n: {k: v for k, v in st.items()
                                if isinstance(v, str)}
                            for names, st in events for n in names}
        elif name == "/host:metadata":
            for names, st in events:
                hlo = next((v for k, v in st.items()
                            if "hlo" in k.lower() and isinstance(v, tuple)),
                           None)
                if hlo is not None and names:
                    programs[names[0]] = _hlo_op_names(buf, hlo)
    return planes, programs


def _program_of(module: str, programs: dict) -> dict:
    """A program's instruction names by the module's name in the trace,
    or by its name without the program id."""
    if module in programs:
        return programs[module]
    base = module.split("(", 1)[0]
    merged: dict = {}
    for name, ops in programs.items():
        if name.split("(", 1)[0] == base:
            merged.update(ops)
    programs[module] = merged
    return merged


def load(trace_dir: str, meta: dict | None = None) -> dict:
    """Normalised program view of the newest ``.xplane.pb`` under
    ``trace_dir``; ``meta``, if given, gets the metadata stats of each
    distinct device op, by its HLO text.  An op's ``op_name`` comes from
    its own metadata stats or, failing them, from the HLO of the program
    running on its device at the op's start (the ``XLA Modules`` line)."""
    import jax

    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    op_meta, programs = xplane_metadata(paths[-1])
    device: dict[str, list] = {}
    host: list = []
    scopes: dict[tuple, str] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            line = lines.get(trace.OPS_LINE)
            if line is None:
                continue
            mods = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for e in getattr(lines.get(MODULES_LINE), "events", ()))
            m = 0
            evs = device.setdefault(plane.name.rsplit(":", 1)[-1], [])
            plane_meta = op_meta.get(plane.name, {})
            for e in sorted(line.events, key=lambda e: e.start_ns):
                while m + 1 < len(mods) and mods[m + 1][0] <= e.start_ns:
                    m += 1
                module = mods[m][2] if mods and mods[m][0] <= e.start_ns \
                    else ""
                key = (module, e.name)
                if key not in scopes:
                    st = plane_meta.get(e.name, {})
                    op_name = _op_name(st) or _program_of(
                        module, programs).get(_instruction(e.name), "")
                    scopes[key] = scope_of(op_name)
                    if meta is not None:
                        meta[e.name] = dict(st, op_name=op_name,
                                            module=module)
                evs.append([trace.family(e.name), int(e.start_ns),
                            int(e.duration_ns), scopes[key]])
        elif plane.name.startswith("/host:"):
            # one line a thread; threads often share a name ("python3"),
            # so the line's place tells them apart
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns),
                                     f"{line.name}/{i}"])
    return {"device": device, "host": host}


@dataclasses.dataclass
class ProgramReduced:
    window_s: float
    devices: int
    scope_s: dict           # scope path -> device s, mean over devices
    program_gaps: dict      # label -> idle s, mean over devices
    ops: dict               # device -> [[family, s_ns, e_ns, scope]]
    window_ns: tuple


def _window(norm: dict) -> tuple:
    wins = [h for h in norm["host"] if h[0] == trace.WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {trace.WINDOW} span")
    return wins[0][1], wins[0][1] + wins[0][2]


def _scoped_ops(evs, w0, w1) -> list:
    """Ops of one device clipped to the window, each with its own scope
    or, failing that, its innermost enclosing container's."""
    order = sorted(evs, key=lambda e: (e[1], -e[2]))
    stack: list = []               # open containers: [end_ns, scope]
    out = []
    for fam, s, d, scope in order:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if fam in trace.CONTAINERS:
            stack.append([s + d, scope or (stack[-1][1] if stack else "")])
        if not scope and stack and fam not in trace.CONTAINERS:
            scope = stack[-1][1]
        s0, e0 = max(s, w0), min(s + d, w1)
        if e0 > s0:
            out.append([fam, s0, e0, scope or "none"])
    return out


class _Innermost:
    """Innermost span open at increasing times, on one thread (spans on
    one thread nest)."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
        self.i = 0
        self.stack: list = []

    def at(self, t):
        while self.i < len(self.spans) and self.spans[self.i][0] <= t:
            s, e, name = self.spans[self.i]
            while self.stack and self.stack[-1][0] <= s:
                self.stack.pop()
            self.stack.append((e, name))
            self.i += 1
        while self.stack and self.stack[-1][0] <= t:
            self.stack.pop()
        return self.stack[-1][1] if self.stack else None


def reduce(norm: dict) -> ProgramReduced:
    w0, w1 = _window(norm)
    devs = sorted(norm["device"])
    if not devs:
        raise ValueError("trace holds no device operations")
    threads: dict[str, list] = {}
    for name, s, d, thread in norm["host"]:
        if name.startswith(PROGRAM) and s < w1 and s + d > w0:
            threads.setdefault(thread, []).append(
                (s, s + d, name[len(PROGRAM):]))
    scope_s: dict[str, float] = {}
    gaps: dict[str, float] = {}
    ops = {}
    for dev in devs:
        clipped = _scoped_ops(norm["device"][dev], w0, w1)
        ops[dev] = clipped
        for fam, s0, e0, scope in clipped:
            if fam not in trace.CONTAINERS:
                scope_s[scope] = scope_s.get(scope, 0.0) + (e0 - s0) / 1e9
        merged = trace._union([(s, e) for _, s, e, _ in clipped])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        cursors = [_Innermost(sp) for sp in threads.values()]
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 > g0:
                mid = (g0 + g1) / 2
                names = sorted({n for n in (c.at(mid) for c in cursors)
                                if n is not None})
                label = "+".join(names) or "none"
                gaps[label] = gaps.get(label, 0.0) + (g1 - g0) / 1e9
    k = len(devs)
    return ProgramReduced(
        window_s=(w1 - w0) / 1e9, devices=k,
        scope_s={n: v / k for n, v in scope_s.items()},
        program_gaps={n: v / k for n, v in gaps.items()},
        ops=ops, window_ns=(w0, w1),
    )


def top(d: dict, n: int = 12) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


# ------------------------------------------------------------ readings --


def _under(scope: str, root: str) -> bool:
    return scope == root or scope.startswith(root + "/")


def read_geodesic_dev_s(red: ProgramReduced, fits: int):
    """Device seconds per fit under the geodesic stage's scopes."""
    s = sum(v for k, v in red.scope_s.items()
            if any(_under(k, r) for r in GEODESIC))
    return s / fits if fits and s else None


def read_frontier_gather_dev_s(red: ProgramReduced, fits: int):
    """Device seconds per fit of the frontier's gather."""
    s = sum(v for k, v in red.scope_s.items() if _under(k, GATHER))
    return s / fits if fits and s else None


def _measure(intervals) -> int:
    return sum(e - s for s, e in trace._union(intervals))


def read_collective_exposed_share(red: ProgramReduced):
    """Share of the window (%, mean over devices) in which a device runs
    an op of the APSP panel exchange and no other op."""
    shares = []
    for clipped in red.ops.values():
        work = [op for op in clipped if op[0] not in trace.CONTAINERS]
        exch = [(s, e) for _, s, e, sc in work if _under(sc, EXCHANGE)]
        if not exch:
            continue
        rest = [(s, e) for _, s, e, sc in work if not _under(sc, EXCHANGE)]
        exposed = _measure(exch + rest) - _measure(rest)
        shares.append(100.0 * exposed / (red.window_s * 1e9))
    return sum(shares) / len(shares) if shares else None


def read_map_call_ms(counters: dict):
    """Host milliseconds per flush in the mapper call (dispatch)."""
    flushes = counters.get("flushes")
    if not flushes or "map_call_s" not in counters:
        return None
    return 1e3 * counters["map_call_s"] / flushes


def read_flush_host_ms(counters: dict):
    """Host milliseconds per flush packing, fetching and replying."""
    flushes = counters.get("flushes")
    if not flushes or any(k not in counters for k in FLUSH_HOST):
        return None
    return 1e3 * sum(counters[k] for k in FLUSH_HOST) / flushes


# ---------------------------------------------------------------- tool --


def _stats_window(snaps: list) -> dict:
    """The service's time counters differenced across the window (the
    reads traffic takes one ``stats()`` before and one after it); none
    from a program whose service has no such counters."""
    if len(snaps) < 2:
        return {}
    return {k: snaps[-1][k] - snaps[-2][k] for k in FLUSH_HOST + (
        "map_call_s", "queue_wait_s", "service_s") if k in snaps[-1]}


def main(argv=None) -> int:
    import argparse
    import json

    from bench import generator, run as run_mod
    from repro.launch import serving

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args(argv)

    seen: dict = {"snaps": []}
    meta: dict = {}
    load_trace, generate = trace.load, generator.generate
    stats = serving.BatchedMapperService.stats

    def load_both(trace_dir):
        seen["norm"] = load(trace_dir, meta)
        return load_trace(trace_dir)

    def keep_run(run):
        seen["run"] = run
        generate(run)

    def keep_stats(svc):
        out = stats(svc)
        seen["snaps"].append(out)
        return out

    trace.load, generator.generate = load_both, keep_run
    serving.BatchedMapperService.stats = keep_stats
    try:
        rc = run_mod.main(["--workload", args.workload, "--seed",
                           str(args.seed), "--seconds", str(args.seconds),
                           "--trace", "1"])
    finally:
        trace.load, generator.generate = load_trace, generate
        serving.BatchedMapperService.stats = stats
    if rc or "norm" not in seen:
        return rc or 1
    counters = {k: v for k, v in dict(
        seen["run"].spans.counters, **_stats_window(seen["snaps"])
    ).items() if not isinstance(v, list)}
    if args.dump:
        with open(args.dump, "w") as f:
            json.dump({"norm": seen["norm"], "meta": meta,
                       "counters": counters}, f)
    red = reduce(seen["norm"])
    fits = counters.get("fits", 0)
    busy = sum(red.scope_s.values())
    readings = {
        "geodesic_dev_s.fit": read_geodesic_dev_s(red, fits),
        "frontier_gather_dev_s.fit": read_frontier_gather_dev_s(red, fits),
        "collective_exposed_share.fit": read_collective_exposed_share(red),
        "map_call_ms.serve": read_map_call_ms(counters),
        "flush_host_ms.serve": read_flush_host_ms(counters),
    }
    print("[scopes] " + json.dumps(top(red.scope_s, 40)), flush=True)
    print("[program_gaps] " + json.dumps(top(red.program_gaps, 20)),
          flush=True)
    print("[program_readings] " + json.dumps(dict(
        readings, unscoped_share=(red.scope_s.get("none", 0.0) / busy
                                  if busy else None),
        window_s=red.window_s, e2e=seen["run"].e2e, counters=counters)),
        flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
