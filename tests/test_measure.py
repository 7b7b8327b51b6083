"""Measured-autotune calibration layer: store round-trip and atomicity,
corrupt/stale fallback, precedence, constant-correction monotonicity
under a scripted timer, warm-store zero-sweep behavior, and cache
invalidation."""
import json
import os
import time

import pytest

from repro.kernels import autotune, measure, ops


@pytest.fixture(autouse=True)
def _isolated(tmp_path, monkeypatch):
    """Every test gets its own store path, measuring off by default, and
    clean caches on both sides (the resolution memo is keyed by mode,
    not path, so stale entries would leak across tests otherwise)."""
    monkeypatch.setenv(measure.ENV_TUNING_PATH,
                       str(tmp_path / "tuning.json"))
    monkeypatch.delenv(measure.ENV_MEASURE, raising=False)
    monkeypatch.delenv(autotune.ENV_TILES, raising=False)
    monkeypatch.delenv(autotune.ENV_AUTOTUNE, raising=False)
    autotune.clear_cache()
    yield
    autotune.clear_cache()
    measure.timer = time.perf_counter


def _seed_store(winners=None, constants=None, samples=None, path=None):
    store = measure._empty_store()
    store["devices"][measure.device_kind()] = {
        "winners": winners or {},
        "constants": constants or {},
        "samples": samples or [],
    }
    return measure.save_store(store, path)


def _winner_entry(cfg, t=1e-4, dflt=None, t_dflt=2e-4):
    return {
        "config": list(cfg),
        "time_s": t,
        "default_config": list(dflt if dflt is not None else cfg),
        "default_time_s": t_dflt,
    }


# ------------------------------------------------------------ the store --


def test_store_roundtrip_is_atomic_and_exact(tmp_path):
    exact, cls = measure._keys("minplus:minplus_update", (32, 64, 32), 4)
    path = _seed_store({exact: _winner_entry((32, 64, 32, 8))})
    assert not os.path.exists(path + ".tmp"), "tmp file left behind"
    loaded = measure.load_store(path, cache=False)
    assert loaded["version"] == measure.STORE_VERSION
    rec = loaded["devices"][measure.device_kind()]
    assert rec["winners"][exact]["config"] == [32, 64, 32, 8]
    # and through the resolution path: a persisted winner applies even
    # with measuring off (REPRO_MEASURE_AUTOTUNE unset) - that is what
    # makes a fleet-shipped calibration file work
    cfg, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert source == "store"
    assert cfg == {"bm": 32, "bn": 64, "bk": 32, "unroll": 8}


def test_missing_store_is_empty_without_warning(tmp_path):
    import warnings as w

    with w.catch_warnings():
        w.simplefilter("error")
        store = measure.load_store(str(tmp_path / "absent.json"),
                                   cache=False)
    assert store == measure._empty_store()


def test_corrupt_store_warns_and_falls_back_to_analytic(tmp_path):
    path = measure.tuning_path()
    with open(path, "w") as fh:
        fh.write("{not json")
    with pytest.warns(measure.TuningStoreWarning, match="unreadable"):
        cfg, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert source == "modeled"
    assert cfg == autotune.best_config("minplus_update", 32, 64, 32)[0]._asdict()


def test_stale_version_warns_and_falls_back(tmp_path):
    path = measure.tuning_path()
    with open(path, "w") as fh:
        json.dump({"version": measure.STORE_VERSION + 1, "devices": {}}, fh)
    with pytest.warns(measure.TuningStoreWarning, match="version"):
        _, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert source == "modeled"


def test_invalid_store_entry_is_skipped_with_warning():
    # a winner whose tiles do not divide the actual shape (e.g. written
    # for another build) must be skipped, not crash the kernel launch
    exact, cls = measure._keys("minplus:minplus_update", (32, 64, 32), 4)
    _seed_store({exact: _winner_entry((48, 48, 48, 4))})
    with pytest.warns(measure.TuningStoreWarning, match="invalid config"):
        _, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert source == "modeled"


def test_shape_class_miss_is_silent_exact_mismatch_warns():
    # a shape-class (pow2) entry that simply does not divide this exact
    # shape is a normal miss — no warning; the same mismatch under the
    # *exact* key still warns (the entry was written for this shape)
    import warnings as w

    _, cls = measure._keys("minplus:minplus_update", (32, 64, 32), 4)
    _seed_store({cls: _winner_entry((48, 48, 48, 4))})
    with w.catch_warnings():
        w.simplefilter("error", measure.TuningStoreWarning)
        _, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert source == "modeled"
    # malformed (non-positive tile) warns even under the class key
    autotune.clear_cache()
    _seed_store({cls: _winner_entry((0, 16, 16, 4))})
    with pytest.warns(measure.TuningStoreWarning, match="invalid config"):
        _, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert source == "modeled"


def test_persist_merges_on_disk_winners():
    # a winner written by another process after our in-process cache was
    # primed must survive our next persist (merge, not last-writer-wins)
    other = _winner_entry((4, 4, 4, 1))
    path = _seed_store({"knn/4x4x4x2/i4": other})
    measure.load_store(path)  # prime the stale in-process view
    data = json.load(open(path))
    data["devices"][measure.device_kind()]["winners"][
        "frontier/8x4x2/i4"] = other
    with open(path, "w") as fh:
        json.dump(data, fh)
    measure._persist("minplus:minplus_update", (16, 16, 16), 4,
                     autotune.TileConfig(16, 16, 16, 1), 1e-4,
                     autotune.TileConfig(16, 16, 16, 1), 2e-4,
                     [[1e6, 0.0, 1e-4]])
    winners = measure.load_store(path, cache=False)[
        "devices"][measure.device_kind()]["winners"]
    assert "knn/4x4x4x2/i4" in winners
    assert "frontier/8x4x2/i4" in winners, "concurrent winner dropped"
    assert any(k.startswith("minplus:minplus_update/16x16x16")
               for k in winners)


def test_env_pin_takes_precedence_over_store(monkeypatch):
    exact, _ = measure._keys("minplus:minplus_update", (32, 64, 32), 4)
    _seed_store({exact: _winner_entry((32, 64, 32, 8))})
    monkeypatch.setenv(autotune.ENV_TILES, "16,16,16,4")
    autotune.clear_cache()
    cfg, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert source == f"env:{autotune.ENV_TILES}"
    assert cfg == {"bm": 16, "bn": 16, "bk": 16, "unroll": 4}
    # ... and REPRO_MINPLUS_AUTOTUNE=0 bypasses the store entirely
    monkeypatch.delenv(autotune.ENV_TILES)
    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "0")
    autotune.clear_cache()
    cfg, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert (cfg, source) == ({}, "default")


def test_shape_class_key_applies_to_nearby_shapes():
    # winner stored under the pow2 shape-class key only: a different
    # exact shape in the same class picks it up when it validates
    _, cls = measure._keys("minplus:minplus_update", (32, 64, 32), 4)
    _seed_store({cls: _winner_entry((16, 16, 16, 4))})
    got = measure.calibrate_minplus("minplus_update", 32, 48, 32)
    assert got is not None and got.source == "store"
    assert tuple(got.config) == (16, 16, 16, 4)


# ------------------------------------------------- constant correction --


def test_fit_constants_recovers_bandwidth_and_launch():
    bw, launch = 100e9, 5e-6
    samples = [[b, 0.0, b / bw + launch]
               for b in (1e6, 4e6, 16e6, 64e6)]
    got = measure.fit_constants(samples)
    assert got["hbm_bw"] == pytest.approx(bw, rel=1e-6)
    assert got["launch_s"] == pytest.approx(launch, rel=1e-6)
    # monotone: uniformly 2x slower timings fit half the bandwidth
    slower = [[b, c, 2 * t] for b, c, t in samples]
    got2 = measure.fit_constants(slower)
    assert got2["hbm_bw"] == pytest.approx(bw / 2, rel=1e-6)
    assert got2["launch_s"] >= got["launch_s"]


def test_fit_constants_degenerate_falls_back():
    assert measure.fit_constants([])["hbm_bw"] == float(autotune.chip().hbm_bw)
    # identical times regardless of bytes: launch-dominated, analytic
    # bandwidth passes through
    flat = [[b, 0.0, 1e-3] for b in (1e6, 4e6)]
    got = measure.fit_constants(flat)
    assert got["launch_s"] >= 0.0


def test_scripted_timer_correction_is_monotone(monkeypatch):
    """Calibrate the same shape under two scripted timers (every timed
    call appears to take dt vs 2*dt): the slower device must fit a
    launch/bandwidth combination that models every config slower."""

    def scripted(dt):
        state = {"t": 0.0}

        def tick():
            state["t"] += dt
            return state["t"]

        return tick

    consts = {}
    for name, dt in (("fast", 1e-4), ("slow", 2e-4)):
        monkeypatch.setenv(measure.ENV_MEASURE, "refresh")
        monkeypatch.setenv(measure.ENV_TUNING_PATH,
                           measure.tuning_path() + "." + name)
        autotune.clear_cache()
        measure.timer = scripted(dt)
        got = measure.calibrate_minplus("minplus_update", 16, 32, 16,
                                        mode="ref")
        assert got is not None and got.source == "measured"
        assert got.time_s == pytest.approx(dt)
        consts[name] = measure.corrected_constants()
        assert consts[name] is not None
    fast, slow = consts["fast"], consts["slow"]
    t_fast = 1e6 / fast["hbm_bw"] + fast["launch_s"]
    t_slow = 1e6 / slow["hbm_bw"] + slow["launch_s"]
    assert t_slow > t_fast, (fast, slow)


def test_corrected_constants_rerank_unmeasured_shapes():
    # constants only (no winner for this shape): resolution re-ranks the
    # analytic sweep under the fitted bandwidth/launch
    _seed_store(constants={"hbm_bw": float(autotune.chip().hbm_bw) / 4,
                           "launch_s": 1e-5, "n_samples": 8})
    cfg, source = autotune.resolve_tiles("minplus_update", 512, 512, 512)
    assert source == "corrected"
    want, _ = autotune.best_config(
        "minplus_update", 512, 512, 512,
        hbm_bw=float(autotune.chip().hbm_bw) / 4, launch_s=1e-5,
    )
    assert cfg == want._asdict()
    # the frontier and kNN families consult the same constants
    _, fsrc = autotune.resolve_frontier_config(512, 16, 64)
    _, ksrc = autotune.resolve_knn_config(128, 512, 3, 10)
    assert fsrc == "corrected" and ksrc == "corrected"


def test_sweep_jits_once_per_candidate(monkeypatch):
    """The timed callable must reuse one jitted function per candidate:
    re-tracing inside the timed repeats would fold compile time into the
    measurements and persist wrong winners."""
    traces = {"n": 0}
    real = ops.minplus_update

    def counting(*a, **kw):
        traces["n"] += 1  # runs once per jit trace, not per call
        return real(*a, **kw)

    monkeypatch.setattr(ops, "minplus_update", counting)
    monkeypatch.setenv(measure.ENV_MEASURE, "refresh")
    autotune.clear_cache()
    before = measure.sweep_count()
    got = measure.calibrate_minplus("minplus_update", 16, 32, 16,
                                    mode="ref")
    assert got is not None and got.source == "measured"
    n_candidates = measure.sweep_count() - before
    assert n_candidates > 0
    assert traces["n"] == n_candidates, (
        "timed callable re-traced per call: compile overhead pollutes "
        "the measured times")


def test_frontier_fit_samples_use_raw_sweep_time(monkeypatch):
    """Constant-fit samples from the frontier sweep must carry the raw
    measured sweep time (matching the single-sweep hbm_bytes), not the
    bucket-amortized per-source winner metric."""
    dt = 1e-4
    state = {"t": 0.0}

    def tick():
        state["t"] += dt
        return state["t"]

    monkeypatch.setenv(measure.ENV_MEASURE, "refresh")
    autotune.clear_cache()
    measure.timer = tick
    # m past the lane width: the measured sweeps are lane-wide (n, 128)
    got = measure.calibrate_frontier(256, 4, 160, mode="ref")
    assert got is not None and got.source == "measured"
    assert got.config.bs == autotune.LANES
    rec = measure.load_store(cache=False)[
        "devices"][measure.device_kind()]
    assert rec["samples"], "no fit samples persisted"
    for _, _, t in rec["samples"]:
        assert t == pytest.approx(dt), (
            "fit sample carries the amortized metric, not the raw "
            "sweep time")


# ------------------------------------------------- sweeps and caching --


def test_warm_store_performs_zero_sweeps(monkeypatch):
    monkeypatch.setenv(measure.ENV_MEASURE, "1")
    autotune.clear_cache()
    measure.timer = (lambda s={"t": 0.0}: (
        lambda: s.__setitem__("t", s["t"] + 1e-5) or s["t"]))()
    got = measure.calibrate_minplus("minplus_update", 16, 32, 16,
                                    mode="ref")
    assert got is not None and got.source == "measured"
    cold = measure.sweep_count()
    assert cold > 0
    # fresh process-state, same store: resolution must be lookup-only
    autotune.clear_cache()
    got2 = measure.calibrate_minplus("minplus_update", 16, 32, 16,
                                     mode="ref")
    assert got2 is not None and got2.source == "store"
    assert tuple(got2.config) == tuple(got.config)
    assert measure.sweep_count() == cold, "warm store re-measured"
    # refresh mode re-measures despite the store hit
    monkeypatch.setenv(measure.ENV_MEASURE, "refresh")
    autotune.clear_cache()
    got3 = measure.calibrate_minplus("minplus_update", 16, 32, 16,
                                     mode="ref")
    assert got3 is not None and got3.source == "measured"
    assert measure.sweep_count() > cold


def test_clear_cache_invalidates_store_backed_caches():
    exact, cls = measure._keys("minplus:minplus_update", (32, 64, 32), 4)
    path = _seed_store({exact: _winner_entry((32, 64, 32, 8)),
                        cls: _winner_entry((32, 64, 32, 8))})
    cfg, _ = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert cfg["unroll"] == 8
    # swap the file behind the caches: still the old answer (memoized)
    store = json.load(open(path))
    for key in (exact, cls):
        store["devices"][measure.device_kind()]["winners"][key][
            "config"] = [32, 64, 32, 4]
    with open(path, "w") as fh:
        json.dump(store, fh)
    cfg, _ = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert cfg["unroll"] == 8
    # clear_cache drops both the parsed-store cache and the memo
    autotune.clear_cache()
    cfg, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert (cfg["unroll"], source) == (4, "store")


def test_measured_layer_inactive_without_store_or_mode():
    assert not measure.active()
    _, source = autotune.resolve_tiles("minplus_update", 32, 64, 32)
    assert source == "modeled"


# ----------------------------------------- ops.py validation reporting --


def test_ops_reports_all_invalid_knobs_in_one_error():
    import numpy as np

    g = np.zeros((64, 64), np.float32)
    with pytest.raises(ValueError) as ei:
        ops.minplus_update(g, g, g, mode="ref", bm=48, bk=-1, bogus=2)
    msg = str(ei.value)
    assert "bogus" in msg                      # unknown key
    assert "bk=-1" in msg                      # bad value
    assert "bm=48 does not divide m=64" in msg  # non-dividing tile


def test_store_supplied_tiles_are_attributed_in_errors():
    # a store winner that validates per-family but fails the ops-level
    # divisibility check must name the calibration store as its source
    exact, cls = measure._keys("minplus:minplus", (64, 64, 64), 4)
    entry = _winner_entry((32, 48, 32, 4))  # bn=48 does not divide 64
    with pytest.warns(measure.TuningStoreWarning):
        _seed_store({exact: entry, cls: entry})
        got = autotune.resolve_tiles("minplus", 64, 64, 64)
    # the resolve layer already rejects it (divides-validation), so the
    # analytic path applies and no broken config reaches the kernel
    assert got[1] in ("modeled", "corrected")
    # but a source string is carried into the error when validation at
    # the ops layer is what catches it:
    with pytest.raises(ValueError, match="REPRO_MINPLUS_TILES"):
        ops._validate_tiles("minplus", 64, 64, 64, {"bn": 48},
                            source=f"env:{autotune.ENV_TILES}")
    with pytest.raises(ValueError, match="calibration store"):
        ops._validate_tiles("minplus", 64, 64, 64, {"bn": 48},
                            source="store")
