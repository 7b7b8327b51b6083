"""Operation and byte counts of the kernel rooflines, against hand
counts, and the readers that divide them by device time."""
import importlib.util
import os
import re
import types

import pytest

from bench import trace

METRICS = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "metrics")
PEAKS = {"vpu_ops_per_s": 6e12, "hbm_bytes_per_s": 8e11}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_minplus_counts_match_a_hand_count():
    mp = reader("minplus_roofline.fit")
    n, b = 256, 128
    calls = mp.calls(n, b)
    terms = sum(t * c for t, _, c in calls)
    # two diagonal iterations of b^3 + 2 b^2 n + n^2 b terms = n^3 (1 +
    # 2b/n + b^2/n^2)
    assert terms == 2 * (128**3 + 2 * 128 * 128 * 256 + 256 * 256 * 128)
    assert terms == 37748736
    update = calls[3]
    assert update == (256 * 256 * 128, 4 * (2 * 256**2 + 2 * 256 * 128), 2)
    # VPU-bound: 2 ops a term over 6e12 beats the bytes over 8e11
    assert mp.bound_s(n, b, **{"vpu": 6e12, "hbm": 8e11}) == pytest.approx(
        sum(c * max(2 * t / 6e12, by / 8e11) for t, by, c in calls))


def test_frontier_counts_match_a_hand_count():
    fr = reader("frontier_roofline.fit")
    s, deg, n = 16, 20, 1024
    ops = 2 * 16 * 20 * 1024
    nbytes = 4 * (16 * 20 * 1024 + 20 * 1024 + 2 * 16 * 1024)
    assert ops == 655360 and nbytes == 1523712
    # memory-bound at 0.43 op/byte
    assert fr.bound_s(s, deg, n, 6e12, 8e11) == pytest.approx(nbytes / 8e11)


def _ctx(events, cfg, counters):
    norm = {"host": [["bench:window", 0, 10**9]], "device": {"0": events}}
    return types.SimpleNamespace(reduced=trace.reduce(norm), cfg=cfg,
                                 counters=counters, peaks=PEAKS)


def test_minplus_reader_divides_the_bound_by_kernel_time():
    mp = reader("minplus_roofline.fit")
    n, b = 256, 128
    need = mp.bound_s(n, b, 6e12, 8e11)
    dur = int(4 * need * 1e9)          # the kernels took four times the bound
    ctx = _ctx([["minplus_update", 0, dur, "%minplus_update.3 = ..."],
                ["fusion", dur, 1000, "%fusion.2 = ..."]],
               {"n": n, "block": b}, {"fits": 1})
    assert mp.read(ctx) == pytest.approx(25.0, rel=1e-4)
    assert mp.read(_ctx([["fusion", 0, 10, "%fusion = ..."]],
                        {"n": n, "block": b}, {"fits": 1})) is None


def test_frontier_reader_takes_shapes_from_the_long_name():
    fr = reader("frontier_roofline.fit")
    need = fr.bound_s(16, 20, 1024, 6e12, 8e11)
    dur = int(2 * need * 1e9)
    text = ("%frontier_relax.5 = f32[16,1024]{1,0} custom-call(f32[1] "
            "%b.1, f32[16,20,1024]{2,1,0} %s.2, f32[20,1024]{1,0} %b.3, "
            "f32[16,1024]{1,0} %c)")
    assert trace.family(text) == "frontier_relax"
    ctx = _ctx([["frontier_relax", 0, dur, text]], {"n": 1024}, {})
    assert fr.read(ctx) == pytest.approx(50.0, rel=1e-3)
    assert fr.read(_ctx([["frontier_relax", 0, dur, "%frontier_relax"]],
                        {}, {})) is None
    assert fr.SHAPE.search(text).groups() == ("16", "20", "1024")
    assert not re.search(fr.PATTERN, trace.family("%fusion.13 = f32[2]"))
