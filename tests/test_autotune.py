"""Trace-time minplus tile autotuner: model sanity, cache behavior, env
overrides, and the ops.py integration."""
import numpy as np
import pytest

from repro.kernels import autotune, ops, ref


def test_best_config_is_valid_and_beats_default():
    for op in autotune.FUSED_OPS:
        for m, n, k in ((256, 2048, 256), (128, 512, 128), (512, 512, 512)):
            cfg, cost = autotune.best_config(op, m, n, k)
            assert autotune.divides(cfg, m, n, k), (op, m, n, k, cfg)
            assert cost.vmem_bytes <= autotune.vmem_budget()
            dflt = autotune.default_config(m, n, k)
            dcost = autotune.modeled_cost(op, m, n, k, dflt)
            assert cost.time_s <= dcost.time_s * (1.0 + 1e-9)


@pytest.mark.parametrize("n", [24576, 18432])
def test_dense_cell_updates_get_the_widest_sub_tile(n):
    """The benchmark's dense updates, one chip's n = 24576 and a mesh
    chip's n = 18432 quarter, rank 128: blocks 1024 lanes wide, whose
    register sub-tiles the kernel schedules fastest."""
    from repro.kernels.minplus import BUNDLES_PER_VREG_TERM, subtile

    cfg, cost = autotune.best_config("minplus_update", n, n, 128)
    assert (cfg.bm, cfg.bn, cfg.bk) == (256, 1024, 128)
    assert subtile(cfg.bm, cfg.bn) == (8, 1024)
    assert autotune.bundles_per_vreg_term(cfg.bm, cfg.bn) == min(
        BUNDLES_PER_VREG_TERM.values())
    assert cost.vmem_bytes <= autotune.vmem_budget()


def test_odd_shapes_get_a_config():
    # shapes with no power-of-two divisor still resolve (whole-dim tile)
    cfg, _ = autotune.best_config("minplus_update", 20, 20, 20)
    assert autotune.divides(cfg, 20, 20, 20)
    # ... and a long dim with no aligned divisor (the 700x700 landmark
    # sweep shape, a base grown by an absorb) gets aligned tiles of the
    # padded problem ops.py runs, never a whole-dim block of 700
    cfg, cost = autotune.best_config("minplus_update", 700, 700, 140)
    assert autotune.padded_shape(700, 700, 140) == (768, 768, 140)
    assert autotune.divides(cfg, 768, 768, 140)
    assert cfg.bm % 8 == 0 and cfg.bn % 128 == 0
    assert cost.vmem_bytes > 0
    assert autotune.padded_shape(16416, 32, 16416) == (16640, 32, 16640)


def test_seeded_ops_cost_more_memory_than_minplus():
    cfg = autotune.default_config(256, 256, 256)
    seeded = autotune.modeled_cost("minplus_update", 256, 256, 256, cfg)
    plain = autotune.modeled_cost("minplus", 256, 256, 256, cfg)
    assert seeded.hbm_bytes == plain.hbm_bytes + 256 * 256 * 4


def test_unknown_op_rejected():
    with pytest.raises(ValueError, match="unknown op"):
        autotune.modeled_cost("matmul", 8, 8, 8, autotune.DEFAULT)


def test_sweep_is_cached():
    autotune.clear_cache()
    autotune.best_config("minplus_update", 384, 384, 384)
    first = autotune.best_config.cache_info()
    assert first.misses >= 1
    autotune.best_config("minplus_update", 384, 384, 384)
    second = autotune.best_config.cache_info()
    assert second.hits == first.hits + 1
    assert second.misses == first.misses


def test_env_tile_override(monkeypatch):
    monkeypatch.setenv(autotune.ENV_TILES, "32,32,32,4")
    assert autotune.tiles_for("minplus_update", 256, 256, 256) == {
        "bm": 32, "bn": 32, "bk": 32, "unroll": 4,
    }
    monkeypatch.setenv(autotune.ENV_TILES, "32,32,32")
    with pytest.raises(ValueError, match="four comma-separated ints"):
        autotune.tiles_for("minplus_update", 256, 256, 256)
    monkeypatch.setenv(autotune.ENV_TILES, "32,32,32,x")
    with pytest.raises(ValueError):
        autotune.tiles_for("minplus_update", 256, 256, 256)
    monkeypatch.setenv(autotune.ENV_TILES, "32,32,0,4")
    with pytest.raises(ValueError, match=">= 1"):
        autotune.tiles_for("minplus_update", 256, 256, 256)


def test_env_override_reports_all_bad_knobs_at_once(monkeypatch):
    """A pin with several invalid knobs raises ONE error naming every
    problem and the env var that supplied them, not just the first."""
    monkeypatch.setenv(autotune.ENV_TILES, "0,32,-2,x")
    with pytest.raises(ValueError) as ei:
        autotune.tiles_for("minplus_update", 256, 256, 256)
    msg = str(ei.value)
    assert autotune.ENV_TILES in msg
    assert "bm=0" in msg and "bk=-2" in msg and "unroll='x'" in msg
    monkeypatch.setenv(autotune.ENV_KNN_TILES, "0,y")
    with pytest.raises(ValueError) as ei:
        autotune.knn_config(256, 2048, 3, 10)
    msg = str(ei.value)
    assert autotune.ENV_KNN_TILES in msg
    assert "bm=0" in msg and "bn='y'" in msg
    monkeypatch.setenv(autotune.ENV_FRONTIER_TILES, "-1,0,z")
    with pytest.raises(ValueError) as ei:
        autotune.frontier_config(2048, 16, 64)
    msg = str(ei.value)
    assert autotune.ENV_FRONTIER_TILES in msg
    assert "bs=-1" in msg and "bn=0" in msg and "bucket='z'" in msg


@pytest.mark.parametrize("m, bs, units", [(808, 128, 7), (40, 40, 1)])
def test_frontier_batch_is_lane_width(monkeypatch, tmp_path, m, bs, units):
    """Sources ride the lanes: the benchmark's sparse cell (n = 40960,
    20 CSR lanes, 808 landmarks) solves 128-wide batches, 7 of them; a
    landmark set under the lane width is one full-dim batch."""
    from repro.core import sparse
    from repro.kernels import measure

    monkeypatch.delenv(autotune.ENV_FRONTIER_TILES, raising=False)
    monkeypatch.delenv(autotune.ENV_FRONTIER_AUTOTUNE, raising=False)
    monkeypatch.setenv(measure.ENV_MEASURE, "0")
    monkeypatch.setenv(measure.ENV_TUNING_PATH, str(tmp_path / "t.json"))
    autotune.clear_cache()
    cfg = autotune.frontier_config(40960, 20, m)
    assert cfg.bs == bs
    assert sparse.sparse_units(m, cfg.bs) == units
    assert autotune.frontier_cost(40960, 20, m, cfg).vmem_bytes <= (
        autotune.vmem_budget())


def test_env_autotune_disable(monkeypatch):
    monkeypatch.delenv(autotune.ENV_TILES, raising=False)
    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "0")
    assert autotune.tiles_for("minplus_update", 256, 2048, 256) == {}
    monkeypatch.setenv(autotune.ENV_AUTOTUNE, "1")
    assert autotune.tiles_for("minplus_update", 256, 2048, 256)


def test_ops_uses_autotuned_tiles_and_stays_exact(rng):
    """mode='pallas' with autotuned tiles must stay bit-identical to the
    oracle - the tuner may only change the schedule, never the result."""
    d = np.asarray(
        ref.floyd_warshall_ref(rng.uniform(1, 10, (64, 64)).astype(np.float32))
    )
    r = rng.uniform(0, 30, (64, 256)).astype(np.float32)
    got = ops.minplus_panel_row(d, r, mode="pallas")
    assert np.array_equal(
        np.asarray(got), np.asarray(ref.minplus_panel_row_ref(d, r))
    )
    g = rng.uniform(0, 30, (128, 128)).astype(np.float32)
    c = rng.uniform(0, 10, (128, 64)).astype(np.float32)
    rr = rng.uniform(0, 10, (64, 128)).astype(np.float32)
    got = ops.minplus_update(g, c, rr, mode="pallas")
    assert np.array_equal(
        np.asarray(got), np.asarray(ref.minplus_update_ref(g, c, rr))
    )


def test_env_override_reaches_kernel_validation(rng, monkeypatch):
    """A pinned non-divisible tile fails fast with the ops.py ValueError,
    not a Pallas trace assertion."""
    g = rng.uniform(0, 10, (64, 64)).astype(np.float32)
    monkeypatch.setenv(autotune.ENV_TILES, "48,32,32,4")
    with pytest.raises(ValueError, match="does not divide"):
        ops.minplus_update(g, g, g, mode="pallas")


# --------------------------------------------- Phase-2 split-panel auto ----


def test_auto_split_panels_pinned_decisions():
    """The roofline decision on known shapes: big panels over a wide mesh
    split (redundant-FLOP saving dominates), small panels don't (the
    gather costs more than the saved compute)."""
    # n=4096, b=512 over a 4x2 mesh: saving ~2.8e-4 s vs gather ~8.4e-5 s
    assert ops.auto_split_panels(4096, 512, 4, 2) is True
    # n=256, b=64 over the same mesh: saving ~2e-7 s vs gather ~6.6e-7 s
    assert ops.auto_split_panels(256, 64, 4, 2) is False
    # single-device mesh: nothing to split
    assert ops.auto_split_panels(4096, 512, 1, 1) is False


def test_auto_split_panels_requires_tile_alignment():
    """b must divide both mesh axes with >= one (8,)-sublane row per
    slice, or the split is refused regardless of the model."""
    assert ops.auto_split_panels(4096, 500, 4, 2) is False   # 500 % 8
    assert ops.auto_split_panels(4096, 24, 4, 2) is False    # 24/4 = 6 < 8
    assert ops.auto_split_panels(4096, 512, 3, 2) is False   # 512 % 3


def test_auto_split_panels_env_override(monkeypatch):
    monkeypatch.setenv(ops.ENV_SPLIT_PANELS, "1")
    assert ops.auto_split_panels(256, 64, 4, 2) is True      # forced on
    # ... but an unaligned forced split is still refused
    assert ops.auto_split_panels(4096, 500, 4, 2) is False
    monkeypatch.setenv(ops.ENV_SPLIT_PANELS, "0")
    assert ops.auto_split_panels(4096, 512, 4, 2) is False   # forced off


def test_minplus_border_is_a_seeded_op():
    """The border kernel shares the fused-op cost model (seed read in the
    HBM term) and resolves valid tiles for its (m, n, n) shapes."""
    assert "minplus_border" in autotune.FUSED_OPS
    cfg, cost = autotune.best_config("minplus_border", 16, 512, 512)
    assert autotune.divides(cfg, 16, 512, 512)
    plain = autotune.modeled_cost("minplus", 16, 512, 512, cfg)
    assert cost.hbm_bytes > plain.hbm_bytes


# ------------------------------------------------------- fused kNN tiles --


def test_knn_best_config_beats_default():
    for m, n, d, k in ((256, 2048, 3, 10), (64, 500, 8, 7), (8, 8, 2, 3)):
        cfg, cost = autotune.best_knn_config(m, n, d, k)
        assert cost.vmem_bytes <= autotune.vmem_budget()
        dflt = autotune.KnnConfig(
            min(autotune.KNN_DEFAULT.bm, m), min(autotune.KNN_DEFAULT.bn, n)
        )
        dcost = autotune.knn_cost(m, n, d, k, dflt)
        assert cost.time_s <= dcost.time_s * (1.0 + 1e-9), (m, n, d, k, cfg)


def test_knn_env_tile_override(monkeypatch):
    monkeypatch.setenv(autotune.ENV_KNN_TILES, "64,128")
    assert autotune.knn_config(256, 2048, 3, 10) == autotune.KnnConfig(
        64, 128
    )
    monkeypatch.setenv(autotune.ENV_KNN_TILES, "64")
    with pytest.raises(ValueError, match="expected 'bm,bn'"):
        autotune.knn_config(256, 2048, 3, 10)
    monkeypatch.setenv(autotune.ENV_KNN_TILES, "64,0")
    with pytest.raises(ValueError, match="tiles must be >= 1"):
        autotune.knn_config(256, 2048, 3, 10)


def test_knn_env_autotune_disable(monkeypatch):
    monkeypatch.setenv(autotune.ENV_KNN_AUTOTUNE, "0")
    assert autotune.knn_config(256, 2048, 3, 10) == autotune.KnnConfig(
        min(autotune.KNN_DEFAULT.bm, 256), min(autotune.KNN_DEFAULT.bn, 2048)
    )
    # clamped to the problem when it is smaller than the default tiles
    assert autotune.knn_config(8, 16, 2, 3) == autotune.KnnConfig(8, 16)


def test_pairwise_tiles_divide():
    """Auto tiles are a whole dim or a multiple of the chip's (8, 128)
    register tiling, and divide the operands ops.pairwise_sq_dists pads
    to tile multiples (zero rows stripped, zero features exact)."""
    for m, n, d in ((100, 52, 3), (97, 31, 7), (512, 512, 784), (1, 1, 1),
                    (64, 1000, 3)):
        t = autotune.pairwise_tiles(m, n, d)
        for key, dim, align in (("bm", m, 8), ("bn", n, 128), ("bd", d, 128)):
            assert t[key] == dim or t[key] % align == 0, (m, n, d, t)
        assert max(t.values()) <= 512
    rng = np.random.default_rng(0)
    x = rng.normal(size=(100, 700)).astype(np.float32)
    y = rng.normal(size=(600, 700)).astype(np.float32)
    got = ops.pairwise_sq_dists(x, y, mode="pallas")
    assert got.shape == (100, 600)
    np.testing.assert_allclose(
        got, ref.pairwise_sq_dists_ref(x, y), rtol=1e-4, atol=1e-3
    )


def test_machine_constants_keyed_by_device_kind(monkeypatch):
    """Constants come from the entry of the chip in use; a TPU without
    an entry raises instead of being tuned with v5e numbers, and off-TPU
    the v5e entry picks tiles that chip accepts."""
    import types

    import jax

    def fake(platform, kind):
        dev = types.SimpleNamespace(platform=platform, device_kind=kind)
        monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
        autotune.chip.cache_clear()

    try:
        fake("tpu", "TPU v5 lite")
        assert autotune.chip() is autotune.CHIPS["TPU v5 lite"]
        assert "Google Cloud" in autotune.chip().source
        fake("cpu", "cpu")
        assert autotune.chip() is autotune.CHIPS[autotune.DEFAULT_CHIP]
        fake("tpu", "TPU v99")
        with pytest.raises(ValueError, match="TPU v99"):
            autotune.chip()
        with pytest.raises(ValueError, match="no machine constants"):
            autotune.best_config("minplus_update", 640, 640, 640)
    finally:
        autotune.chip.cache_clear()
        autotune.best_config.cache_clear()


def test_minplus_candidates_are_chip_tilings():
    """Every min-plus candidate is a tiling the chip's compiler takes:
    bm a multiple of 8 up to MAX_BM, bn/bk multiples of 128, or whole
    dims."""
    for m, n, k in ((16384, 16384, 128), (128, 16384, 128),
                    (16384, 128, 128), (32, 16384, 16384), (96, 200, 40)):
        for cfg in autotune.candidates(m, n, k):
            c = autotune.clamp(cfg, m, n, k)
            assert c.bm <= autotune.MAX_BM or c.bm == m
            assert c.bm % 8 == 0 or c.bm == m
            assert c.bn % 128 == 0 or c.bn == n
            assert c.bk % 128 == 0 or c.bk == k
