"""Substrate tests: the AdamW optimizer behind the stress objective, the
checkpoint manager, and the compile-cache placement."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, adamw_update, cosine_schedule


# ----------------------------------------------------------- optimizer ----


def _adamw_state(params):
    """Zero moments and step, built as core/embedding.py builds them."""
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "step": jnp.zeros((), jnp.int32)}


def test_adamw_minimizes_quadratic():
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (8,))}
    state = _adamw_state(params)
    cfg = AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=0, total_steps=200)
    target = jnp.arange(8.0)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    l0 = float(loss(params))
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state, _ = adamw_update(cfg, g, state, params)
    assert float(loss(params)) < l0 * 1e-2


def test_grad_clip_engages():
    params = {"w": jnp.ones((4,), jnp.float32)}
    state = _adamw_state(params)
    cfg = AdamWConfig(grad_clip=1.0)
    huge = {"w": jnp.full((4,), 1e6)}
    _, _, m = adamw_update(cfg, huge, state, params)
    assert float(m["grad_norm"]) > 1e6  # reported pre-clip


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
    assert float(cosine_schedule(cfg, jnp.asarray(0))) == 0.0
    assert abs(float(cosine_schedule(cfg, jnp.asarray(10))) - 1.0) < 1e-6
    end = float(cosine_schedule(cfg, jnp.asarray(100)))
    assert abs(end - 0.1) < 1e-6


def test_cosine_schedule_no_warmup():
    # warmup_steps=0 must mean "no ramp": full lr from step 0, not a
    # division-by-zero or a forced-zero first step
    cfg = AdamWConfig(lr=0.5, warmup_steps=0, total_steps=100, min_lr_frac=0.1)
    first = float(cosine_schedule(cfg, jnp.asarray(0)))
    assert abs(first - 0.5) < 1e-6
    end = float(cosine_schedule(cfg, jnp.asarray(100)))
    assert abs(end - 0.05) < 1e-6
    assert np.isfinite(first) and np.isfinite(end)


def test_cosine_schedule_all_warmup():
    # total_steps == warmup_steps leaves no decay phase: the schedule
    # must hold at full lr after warmup instead of collapsing to
    # min_lr_frac (or emitting nan from 0/0 progress)
    cfg = AdamWConfig(lr=1.0, warmup_steps=50, total_steps=50, min_lr_frac=0.1)
    mid = float(cosine_schedule(cfg, jnp.asarray(25)))
    assert abs(mid - 0.5) < 1e-6          # still ramping
    at = float(cosine_schedule(cfg, jnp.asarray(50)))
    after = float(cosine_schedule(cfg, jnp.asarray(80)))
    assert abs(at - 1.0) < 1e-6
    assert abs(after - 1.0) < 1e-6
    assert np.isfinite(at) and np.isfinite(after)


# ----------------------------------------------------------- checkpoint ---


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": {"c": jnp.ones((2,))}}
    mgr.save(5, tree, blocking=True)
    proto = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    out = mgr.restore(5, proto)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]), np.ones((2,)))


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3):
        mgr.save(s, {"x": jnp.asarray([float(s)])}, blocking=True)
    assert mgr.latest_step() == 3
    assert mgr.all_steps() == [2, 3]


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.zeros((1000, 100))})
    mgr.wait()
    assert mgr.latest_step() == 1


def test_checkpoint_elastic_reshard(tmp_path):
    """Save under one mesh, restore under a different mesh shape."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mgr = CheckpointManager(str(tmp_path))
    x = jnp.arange(64.0).reshape(8, 8)
    mgr.save(1, {"x": x}, blocking=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = NamedSharding(mesh, P("data", "model"))
    out = mgr.restore(
        1, {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}, shardings={"x": sh}
    )
    np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(x))
    assert out["x"].sharding == sh


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is left to JAX; otherwise the
    cache goes to one fixed directory inside the checkout, which git
    ignores."""
    import pathlib

    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_CACHE_DIR)
        got = compile_cache.enable_compile_cache()
        assert got == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == got
        root = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(got) == root / ".jax_cache"
        ignored = (root / ".gitignore").read_text().split()
        assert ".jax_cache/" in ignored
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
