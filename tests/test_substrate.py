"""Substrate tests: optimizer, checkpoint manager, data pipeline,
sharding rules, fault-tolerant restart."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.data.tokens import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, adamw_init_specs, adamw_update, cosine_schedule
from repro.sharding import (
    LogicalRules,
    ParamSpec,
    eval_shape_tree,
    materialize,
    spec_shardings,
)


# ----------------------------------------------------------- optimizer ----


def test_adamw_minimizes_quadratic():
    specs = {"w": ParamSpec((8,), (None,), init="normal", scale=1.0)}
    params = materialize(specs, jax.random.PRNGKey(0))
    state = materialize(adamw_init_specs(specs), jax.random.PRNGKey(1))
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
    specs = {"w": ParamSpec((4,), (None,), init="ones")}
    params = materialize(specs, jax.random.PRNGKey(0))
    state = materialize(adamw_init_specs(specs), jax.random.PRNGKey(1))
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


# ------------------------------------------------------------- data -------


def test_pipeline_deterministic_and_seekable():
    p1 = TokenPipeline(1000, 4, 16, seed=7)
    p2 = TokenPipeline(1000, 4, 16, seed=7)
    b5a = p1.batch_at(5)["tokens"]
    b5b = p2.batch_at(5)["tokens"]
    np.testing.assert_array_equal(b5a, b5b)
    # iteration matches random access (restart = skip ahead)
    it = iter(p1)
    seq = [next(it)["tokens"] for _ in range(3)]
    np.testing.assert_array_equal(seq[2], p2.batch_at(2)["tokens"])
    assert not np.array_equal(p1.batch_at(0)["tokens"], p1.batch_at(1)["tokens"])


# ----------------------------------------------------------- sharding -----


def _abstract_mesh(shape, axes):
    """Rules only need shape/axis_names; AbstractMesh avoids requiring
    real devices in the 1-CPU test process."""
    from jax.sharding import AbstractMesh, AxisType

    return AbstractMesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def test_logical_rules_divisibility_fallback():
    mesh = _abstract_mesh((2, 4), ("data", "model"))
    rules = LogicalRules(mesh)
    # 9 heads don't divide 4 -> replicated; 1536 mlp does
    spec = rules.partition_spec((576, 9, 64), ("embed", "heads", "head_dim"))
    assert spec == jax.sharding.PartitionSpec("data")
    spec = rules.partition_spec((576, 1536), ("embed", "mlp"))
    assert spec == jax.sharding.PartitionSpec("data", "model")


def test_logical_rules_axis_used_once():
    mesh = _abstract_mesh((2, 4), ("data", "model"))
    rules = LogicalRules(mesh)
    # batch takes "data"; a later "embed" dim must not reuse it
    spec = rules.partition_spec((8, 16, 64), ("batch", None, "embed"))
    assert spec == jax.sharding.PartitionSpec("data")


def test_materialize_and_eval_shape():
    specs = {
        "w": ParamSpec((4, 6), ("embed", "mlp"), init="scaled"),
        "b": ParamSpec((6,), ("mlp",), init="zeros"),
    }
    sds = eval_shape_tree(specs)
    assert sds["w"].shape == (4, 6)
    vals = materialize(specs, jax.random.PRNGKey(0))
    assert float(jnp.sum(jnp.abs(vals["b"]))) == 0.0
    assert float(jnp.std(vals["w"])) > 0.0


# -------------------------------------------------- fault-tolerant loop ---


def test_train_restart_bitwise(tmp_path):
    """Kill-and-restart equals uninterrupted run (checkpoint + step-indexed
    data => bitwise resume)."""
    from repro.launch.train import train

    d1 = str(tmp_path / "a")
    p_full, _, _ = train(
        "smollm-135m", steps=6, smoke=True, ckpt_dir=d1, ckpt_every=100,
        log_every=100,
    )
    d2 = str(tmp_path / "b")
    train("smollm-135m", steps=3, smoke=True, ckpt_dir=d2, ckpt_every=3,
          log_every=100)
    p_resumed, _, _ = train(
        "smollm-135m", steps=6, smoke=True, ckpt_dir=d2, ckpt_every=3,
        log_every=100,
    )
    for a, b in zip(jax.tree.leaves(p_full), jax.tree.leaves(p_resumed)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
        )


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
