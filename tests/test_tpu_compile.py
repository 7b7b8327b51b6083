"""Every main-path Pallas kernel compiles for a TPU v5e, at the shapes
``chip_smoke.py`` runs, with the tiles the autotuner picks.

No chip is needed: the TPU compiler compiles for a described (not
attached) v5e:2x2 topology.  The dispatch in :mod:`repro.kernels.ops` is
steered to the native kernels (``_on_tpu``), so the wrappers' padding and
tile resolution are compiled too.  Each test asserts that the compiled
program holds the kernel (``tpu_custom_call``).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops
from repro.kernels.knn_topk import PAD_IDX

N = 16384          # dense phase n_base
B = 128            # dense phase APSP block
N_SPARSE = 65536   # sparse phase n_base


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def native(monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


def test_minplus_update_compiles(one_chip, native):
    s = one_chip
    _assert_kernel(
        ops.minplus_update,
        _spec(s, (N, N)), _spec(s, (N, B)), _spec(s, (B, N)),
    )


@pytest.mark.parametrize("panel", ["row", "col"])
def test_minplus_panels_compile(panel, one_chip, native):
    s = one_chip
    if panel == "row":
        _assert_kernel(ops.minplus_panel_row, _spec(s, (B, B)),
                       _spec(s, (B, N)))
    else:
        _assert_kernel(ops.minplus_panel_col, _spec(s, (N, B)),
                       _spec(s, (B, B)))


@pytest.mark.parametrize("b", [B, 512])
def test_floyd_warshall_compiles(b, one_chip, native):
    _assert_kernel(ops.floyd_warshall, _spec(one_chip, (b, b)))


@pytest.mark.parametrize("n", [N, N + 32])
def test_minplus_border_compiles(n, one_chip, native):
    # N + 32: the base after one 32-point absorb, no multiple of 128
    s = one_chip
    _assert_kernel(ops.minplus_border, _spec(s, (32, n)), _spec(s, (n, n)))


def test_absorb_expansions_compile_on_grown_bases(one_chip, native):
    """The second absorb of the smoke: dense expansion from an
    (N + 32)-point base, sparse panel expansion from an unaligned
    (N_SPARSE + 32)-point base."""
    from repro.core import update

    s = one_chip
    n, g = N + 32, 32
    _assert_kernel(
        lambda a, e, f: update.expand_geodesics(a, e, f),
        _spec(s, (n, n)), _spec(s, (g, n)), _spec(s, (g, g)),
    )
    n, m = N_SPARSE + 32, 1024
    _assert_kernel(
        lambda p, e, f: update.expand_panel(p, e, f),
        _spec(s, (m, n)), _spec(s, (g, n)), _spec(s, (g, g)),
    )


def test_knn_topk_compiles(one_chip, native):
    s = one_chip
    k = 10

    def fn(x):
        seed_d = jnp.full((N, k), jnp.inf, jnp.float32)
        seed_i = jnp.full((N, k), PAD_IDX, jnp.int32)
        return ops.knn_topk(x, x, seed_d, seed_i)

    _assert_kernel(fn, _spec(s, (N, 3)))


@pytest.mark.parametrize("n", [N, 1000])
def test_pairwise_sq_dists_compiles(n, one_chip, native):
    # n = 1000 is no multiple of 128: the wrapper pads to its tiles
    s = one_chip
    _assert_kernel(ops.pairwise_sq_dists, _spec(s, (64, 3)),
                   _spec(s, (n, 3)))


@pytest.mark.parametrize("n, m", [(N_SPARSE, 1024), (40960, 808)])
def test_frontier_relax_compiles(n, m, one_chip, native):
    # 2k-wide padded CSR, default landmarks: the smoke's sparse phase and
    # the benchmark's sparse cell; nodes-major (n, bs), sources on lanes
    s = one_chip
    deg = 20
    bs = autotune.frontier_config(n, deg, m).bs
    assert bs == 128
    _assert_kernel(
        lambda d, nbr, w: ops.frontier_relax(d, nbr, w, 1.0),
        _spec(s, (n, bs)), _spec(s, (n, deg), jnp.int32),
        _spec(s, (n, deg)),
    )
