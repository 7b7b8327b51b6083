"""The reduction from a trace to busy time, idle share, per-op sums and
the breakdown, on a hand-made trace and on one recorded on the chip."""
import json
import os
import re

import pytest

from bench import trace

FIX = os.path.join(os.path.dirname(__file__), "fixtures")

HAND = {
    "host": [["bench:window", 1000, 10000],
             ["bench:stage:apsp", 2000, 4000],
             ["bench:stage:knn", 6000, 3000]],
    "device": {"0": [
        ["opE", 0, 1200, "opE"],
        ["minplus_update", 1500, 1000, "%minplus_update.1 = f32[8]"],
        ["fusion", 2000, 1000, "fusion"],
        ["fusion", 7000, 1000, "fusion"],
        ["opD", 10500, 1500, "opD"],
    ]},
}


def test_busy_is_the_union_of_op_intervals_in_the_window():
    red = trace.reduce(HAND)
    # [1000,1200) + [1500,3000) + [7000,8000) + [10500,11000)
    assert red.window_s == pytest.approx(10000e-9)
    assert red.busy_s == pytest.approx(3200e-9)
    assert red.idle_share() == pytest.approx(0.68)


def test_per_op_sums_are_clipped_to_the_window():
    red = trace.reduce(HAND)
    assert red.op_s["fusion"] == pytest.approx(2000e-9)
    assert red.op_s["opE"] == pytest.approx(200e-9)
    assert red.op_s["opD"] == pytest.approx(500e-9)
    assert red.op_s["minplus_update"] == pytest.approx(1000e-9)
    assert len(red.events(re.compile("minplus"))) == 1


def test_idle_gaps_are_named_by_the_innermost_span():
    red = trace.reduce(HAND)
    # [1200,1500) and [8000,10500) lie outside every stage span;
    # [3000,7000) has its midpoint inside stage:apsp
    assert red.gaps == pytest.approx({"window": 2800e-9,
                                      "stage:apsp": 4000e-9})
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["fusion", pytest.approx(2000e-9)]
    assert bd["idle_gaps"][0] == ["stage:apsp", pytest.approx(4000e-9)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_control_flow_ops_are_left_out_of_the_sums():
    with_loop = dict(HAND, device={"0": HAND["device"]["0"]
                                   + [["while", 1000, 9000, "%while = ..."]]})
    red = trace.reduce(with_loop)
    assert "while" not in red.op_s
    assert red.busy_s == pytest.approx(9500e-9)
    assert trace.family("%minplus_update.11 = f32[8,8] custom-call()") \
        == "minplus_update"


def test_devices_are_averaged():
    two = dict(HAND, device={"0": HAND["device"]["0"],
                             "1": [["x", 1000, 10000, "x"]]})
    red = trace.reduce(two)
    assert red.devices == 2
    assert red.busy_s == pytest.approx((3200e-9 + 10000e-9) / 2)


def test_a_trace_without_window_or_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"host": [], "device": HAND["device"]})
    with pytest.raises(ValueError):
        trace.reduce({"host": HAND["host"], "device": {}})


def _load_recorded():
    with open(os.path.join(FIX, "trace_chip.json")) as f:
        return json.load(f)


def test_recorded_chip_trace_reduces_to_its_union_and_sums():
    """A slice of a traced dense-fit run on one TPU v5e (normalised by
    trace.load): busy time against a per-nanosecond-free recount."""
    norm = _load_recorded()
    red = trace.reduce(norm)
    w0, w1 = red.window_ns
    evs = sorted((max(s, w0), min(s + d, w1))
                 for _, s, d, _ in norm["device"]["0"]
                 if s < w1 and s + d > w0)
    busy, end = 0, w0
    for s, e in evs:
        if e > end:
            busy += e - max(s, end)
            end = e
    assert red.busy_s == pytest.approx(busy / 1e9)
    assert 0.0 < red.idle_share() < 1.0
    for fam in ("knn_topk", "minplus_update", "copy"):
        want = sum(min(s + d, w1) - max(s, w0)
                   for f, s, d, _ in norm["device"]["0"]
                   if f == fam and s < w1 and s + d > w0)
        assert red.op_s[fam] == pytest.approx(want / 1e9)
    assert "while" not in red.op_s
    assert sum(red.gaps.values()) == pytest.approx(
        red.window_s - red.busy_s)
    assert red.events(re.compile("minplus"))
