"""The program's spans and scopes in a trace (``bench/scopes.py``): scope
paths from op names, device seconds per scope, idle gaps named by the
program's spans on each thread, and the readings taken from them, on
hand-made traces and on windows recorded on the chip."""
import json
import os

import pytest

from bench import scopes, trace

FIX = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.mark.parametrize("op_name, want", [
    ("jit(apsp_blocked_segment)/apsp/while/body/update/min", "apsp/update"),
    ("jit(_apsp_shard_body)/shard_map/apsp/while/body/exchange/psum",
     "apsp/exchange"),
    ("jit(sparse_panel_segment)/sparse_geodesics/while/body/while/body/"
     "while/body/closed_call/jit(frontier_relax)/gather/jit(_take)/gather",
     "sparse_geodesics/gather"),
    ("jit(f)/eigen/cond/branch_1_fun/mul", "eigen"),
    ("jit(f)/sin", ""),
    ("", ""),
])
def test_scope_paths_leave_out_jax_own_names(op_name, want):
    assert scopes.scope_of(op_name) == want


SERVE = {
    "host": [
        ["bench:window", 1000, 10000, "main"],
        ["repro:serve:coalesce", 1000, 3000, "sched"],
        ["repro:serve:map", 2500, 1000, "flush_0"],
        ["repro:serve:fetch", 3500, 4100, "flush_0"],
        ["repro:serve:reply", 10600, 300, "sched"],
        ["repro:serve:pack", 500, 200, "flush_0"],   # before the window
    ],
    "device": {"0": [
        ["fusion", 1000, 500, "map"],
        ["while", 4000, 3000, "apsp"],
        ["copy", 4500, 1000, ""],              # takes the loop's scope
        ["minplus_update", 5500, 1000, "apsp/update"],
        ["all-reduce", 8000, 1000, "apsp/exchange"],
        ["fusion", 8500, 1000, "apsp/panels"],
        ["x", 10000, 500, ""],                 # outside every container
    ]},
}


def test_device_seconds_per_scope_leave_containers_out():
    red = scopes.reduce(SERVE)
    assert red.window_s == pytest.approx(10000e-9)
    assert red.scope_s == pytest.approx({
        "map": 500e-9, "apsp": 1000e-9, "apsp/update": 1000e-9,
        "apsp/exchange": 1000e-9, "apsp/panels": 1000e-9, "none": 500e-9,
    })
    # the same device time as the benchmark's own sums
    assert sum(red.scope_s.values()) == pytest.approx(
        sum(trace.reduce(_plain(SERVE)).op_s.values()))


def _plain(norm):
    """The program view in ``trace.load``'s form."""
    return {"host": [h[:3] for h in norm["host"]],
            "device": {d: [[f, s, du, f] for f, s, du, _ in evs]
                       for d, evs in norm["device"].items()}}


def test_idle_gaps_are_named_by_each_threads_innermost_program_span():
    red = scopes.reduce(SERVE)
    # gaps [1500,4000) mid 2750: coalescing and mapping at once;
    # [7000,8000) mid 7500: fetching; [9500,10000): no program span;
    # [10500,11000) mid 10750: replying
    assert red.program_gaps == pytest.approx({
        "serve:coalesce+serve:map": 2500e-9, "serve:fetch": 1000e-9,
        "none": 500e-9, "serve:reply": 500e-9,
    })
    # the idle time is the benchmark's, only named differently
    plain = trace.reduce(_plain(SERVE))
    assert sum(red.program_gaps.values()) == pytest.approx(
        plain.window_s - plain.busy_s)


def test_nested_spans_on_one_thread_name_a_gap_by_the_innermost():
    norm = {
        "host": [["bench:window", 0, 1000, "main"],
                 ["repro:fit", 0, 1000, "main"],
                 ["repro:stage:knn", 0, 200, "main"],
                 ["repro:stage:apsp", 200, 700, "main"],
                 ["repro:checkpoint", 600, 100, "main"]],
        "device": {"0": [["a", 100, 200, "knn"], ["b", 400, 100, "apsp"],
                         ["c", 950, 50, "eigen"]]},
    }
    red = scopes.reduce(norm)
    # [0,100): knn; [300,400): apsp; [500,950) mid 725: apsp (the
    # checkpoint span ended at 700)
    assert red.program_gaps == pytest.approx({
        "stage:knn": 100e-9, "stage:apsp": 550e-9})


def test_readings_from_the_scopes():
    red = scopes.reduce(SERVE)
    # apsp, apsp/update, apsp/exchange, apsp/panels over two fits
    assert scopes.read_geodesic_dev_s(red, 2) == pytest.approx(2000e-9)
    assert scopes.read_frontier_gather_dev_s(red, 2) is None
    # [8000,8500): the exchange runs and nothing else
    assert scopes.read_collective_exposed_share(red) == pytest.approx(5.0)
    gathered = dict(SERVE, device={"0": SERVE["device"]["0"] + [
        ["fusion", 1600, 400, "sparse_geodesics/gather"],
        ["copy", 2000, 100, "sparse_geodesics/gather"]]})
    red = scopes.reduce(gathered)
    assert scopes.read_frontier_gather_dev_s(red, 1) == pytest.approx(
        500e-9)
    assert scopes.read_geodesic_dev_s(red, 1) == pytest.approx(4500e-9)


def test_devices_are_averaged_and_a_window_is_required():
    two = dict(SERVE, device={"0": SERVE["device"]["0"],
                              "1": [["fusion", 1000, 10000, "apsp/update"]]})
    red = scopes.reduce(two)
    assert red.devices == 2
    assert red.scope_s["apsp/update"] == pytest.approx(
        (1000e-9 + 10000e-9) / 2)
    # device 1 is never idle; device 0's gaps count half
    assert sum(red.program_gaps.values()) == pytest.approx(4500e-9 / 2)
    with pytest.raises(ValueError):
        scopes.reduce({"host": SERVE["host"][1:], "device": SERVE["device"]})
    with pytest.raises(ValueError):
        scopes.reduce({"host": SERVE["host"], "device": {}})


def test_flush_readings_from_the_service_counters():
    c = {"flushes": 4, "map_call_s": 0.002, "pack_s": 0.001,
         "fetch_s": 0.003, "reply_s": 0.0004}
    assert scopes.read_map_call_ms(c) == pytest.approx(0.5)
    assert scopes.read_flush_host_ms(c) == pytest.approx(1.1)
    # a program without the counters reads nothing
    assert scopes.read_map_call_ms({"flushes": 4}) is None
    assert scopes.read_flush_host_ms({"flushes": 4, "pack_s": 1.0}) is None
    assert scopes.read_map_call_ms({"flushes": 0, "map_call_s": 1.0}) is None


#: ``trace.reduce`` of the recorded dense-fit slice, as it read before
#: the program carried spans and scopes of its own
TRACE_CHIP = {
    "busy_s": 0.188842754,
    "window_s": 0.2,
    "gaps": {"window": 0.007975996, "stage:knn": 0.001130298999999999,
             "stage:graph": 0.002050601, "stage:apsp": 3.5e-07},
    "breakdown": {
        "device_ops": [
            ["knn_topk", 0.07801586799999996], ["minplus_update", 0.038478338],
            ["fusion", 0.031999087999999995], ["copy", 0.028952989],
            ["reshape", 0.007405407], ["broadcast", 0.00368131],
            ["dynamic_slice", 6.175799999999988e-05],
            ["multiply_reduce_fusion", 5.6138999999999965e-05],
            ["constant_dynamic-slice_fusion", 5.3712000000000003e-05],
            ["pad_add_fusion", 1.7715000000000002e-05]],
        "idle_gaps": [
            ["window", 0.007975996], ["stage:graph", 0.002050601],
            ["stage:knn", 0.001130298999999999], ["stage:apsp", 3.5e-07]],
    },
    "families": 21,
}


def test_the_recorded_dense_slice_reduces_as_before():
    with open(os.path.join(FIX, "trace_chip.json")) as f:
        red = trace.reduce(json.load(f))
    assert red.busy_s == pytest.approx(TRACE_CHIP["busy_s"], rel=1e-12)
    assert red.window_s == pytest.approx(TRACE_CHIP["window_s"], rel=1e-12)
    assert red.gaps == pytest.approx(TRACE_CHIP["gaps"], rel=1e-12)
    assert len(red.op_s) == TRACE_CHIP["families"]
    bd = red.breakdown()
    for key in ("device_ops", "idle_gaps"):
        assert [k for k, _ in bd[key]] == [
            k for k, _ in TRACE_CHIP["breakdown"][key]]
        assert [v for _, v in bd[key]] == pytest.approx(
            [v for _, v in TRACE_CHIP["breakdown"][key]], rel=1e-12)


def test_op_names_come_from_the_traces_own_program_protos(tmp_path):
    """``xplane_metadata`` reads each traced program's HLO from the
    trace's metadata plane (the wire format, no protobuf bindings): every
    instruction's op_name, scope included."""
    import glob

    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        with jax.named_scope("apsp"):
            x = jnp.cos(x)
            with jax.named_scope("update"):
                return jax.lax.fori_loop(
                    0, 2, lambda i, y: jnp.sin(y) @ y + i, x)

    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        step(x).block_until_ready()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    _, programs = scopes.xplane_metadata(path)
    name = next(n for n in programs if n.startswith("jit_step("))
    got = {scopes.scope_of(o) for o in programs[name].values()
           if o.startswith("jit(step)")}
    assert got == {"apsp", "apsp/update"}, got
    # a module named with another id reads the program by its name
    assert scopes._program_of("jit_step(123)", programs) == programs[name]
    assert scopes._instruction("%fusion.13 = f32[2] fusion(%a)") == \
        "fusion.13"


def _recorded(name):
    with open(os.path.join(FIX, f"trace_program_{name}.json")) as f:
        return json.load(f)


def test_recorded_serve_window_names_its_ops_and_idle_time():
    """A slice of a traced serve run on one TPU v5e (``bench/scopes.py``'s
    dump): the mapper's ops under ``map``, and the idle time named by the
    flush path's spans on the service's threads."""
    fx = _recorded("serve")
    red = scopes.reduce(fx)
    plain = trace.reduce(_plain(fx))
    assert sum(red.scope_s.values()) == pytest.approx(
        sum(plain.op_s.values()))
    assert red.scope_s["map"] > 0.95 * sum(red.scope_s.values())
    idle = sum(red.program_gaps.values())
    assert idle == pytest.approx(plain.window_s - plain.busy_s)
    assert 1.0 - red.program_gaps.get("none", 0.0) / idle >= 0.9
    serve_spans = {"serve:coalesce", "serve:pack", "serve:map",
                   "serve:fetch", "serve:reply", "map:put"}
    for label in red.program_gaps:
        assert label == "none" or set(label.split("+")) <= serve_spans
    threads = {h[3] for h in fx["host"] if h[0].startswith("repro:")}
    assert len(threads) >= 2
    # the flush-path readings from the window's service counters
    c = fx["counters"]
    assert scopes.read_map_call_ms(c) == pytest.approx(
        1e3 * c["map_call_s"] / c["flushes"])
    assert scopes.read_map_call_ms(c) == pytest.approx(0.92999855, rel=1e-6)
    assert scopes.read_flush_host_ms(c) == pytest.approx(1.36134021,
                                                         rel=1e-6)
    assert scopes.read_geodesic_dev_s(red, 1) is None


def test_recorded_sparse_window_reads_the_frontier_gather():
    """A slice of a traced sparse fit on one TPU v5e: the frontier's gather
    and its transpose under ``sparse_geodesics/gather``, its kernel under
    ``sparse_geodesics/frontier_relax``, and the host's landmark
    selection as the idle gap it is."""
    fx = _recorded("sparse")
    red = scopes.reduce(fx)
    w0, w1 = red.window_ns
    want = sum(min(s + d, w1) - max(s, w0)
               for _, s, d, sc in fx["device"]["0"]
               if sc == scopes.GATHER and s < w1 and s + d > w0) / 1e9
    assert want > 0
    assert scopes.read_frontier_gather_dev_s(red, 1) == pytest.approx(want)
    assert scopes.read_geodesic_dev_s(red, 1) > want
    assert {sc for f, _, _, sc in fx["device"]["0"]
            if f == "frontier_relax"} == {"sparse_geodesics/frontier_relax"}
    assert {f for f, _, _, sc in fx["device"]["0"]
            if sc == scopes.GATHER} >= {"fusion", "copy"}
    gap, _ = scopes.top(red.program_gaps, 1)[0]
    assert gap == "stage:landmarks"
    assert scopes.read_collective_exposed_share(red) is None
