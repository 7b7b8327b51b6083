"""Embedding-objective seam tests: SpectralMDS bit-identity with the
pre-seam tails (dense and sparse), the stress and path objectives end to
end (fit -> serve -> absorb -> serve in both regimes), and objective
identity in the resume fingerprints (pipeline resume, mapper restore,
update-log replay)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core import metrics, streaming
from repro.core.embedding import (
    PathIsomap, SpectralMDS, StressMDS, get_objective,
)
from repro.core.pipeline import (
    APSPStage, CenterStage, ClampStage, EigenStage, GraphStage, KNNStage,
    LocalBackend, ManifoldPipeline, PipelineConfig, stages_for,
)
from repro.core.sparse import landmark_mds_general
from repro.core.update import UpdateConfig
from repro.data import euler_isometric_swiss_roll


@pytest.fixture(scope="module")
def data():
    x, _ = euler_isometric_swiss_roll(192, seed=0)
    x = np.asarray(x)
    return x[:160], x[160:]


def _fit(base, **cfg_kw):
    cfg = PipelineConfig(k=10, d=2, **cfg_kw)
    pipe = ManifoldPipeline(
        stages_for(cfg, base.shape[0]), cfg=cfg, backend=LocalBackend()
    )
    return pipe.run(jnp.asarray(base))


# ------------------------------------------------------------ registry ----


def test_get_objective_resolution():
    assert isinstance(get_objective(None), SpectralMDS)
    assert isinstance(get_objective("stress"), StressMDS)
    obj = PathIsomap()
    assert get_objective(obj) is obj
    with pytest.raises(ValueError, match="unknown embedding objective"):
        get_objective("huh")
    with pytest.raises(TypeError):
        get_objective(42)


def test_identity_carries_params():
    ident = StressMDS(steps=17).identity()
    assert ident["objective"] == "stress" and ident["steps"] == 17
    assert get_objective("spectral").identity() == {"objective": "spectral"}


def test_non_spectral_objectives_have_no_lle_tail():
    with pytest.raises(ValueError, match="no LLE tail"):
        StressMDS().lle_tail_stages()
    # spectral keeps the historical LLE chain
    names = [s.name for s in SpectralMDS().lle_tail_stages()]
    assert names == ["lle_weights", "lle_eigen"]


# -------------------------------------------- spectral bit-identity ----


def test_spectral_dense_bit_identical_to_pre_seam_chain(data):
    base, _ = data
    art = _fit(base, regime="dense", objective="spectral")
    old = ManifoldPipeline(
        [KNNStage(), GraphStage(), APSPStage(), ClampStage(),
         CenterStage(), EigenStage()],
        cfg=PipelineConfig(k=10, d=2),
        backend=LocalBackend(),
    ).run(jnp.asarray(base))
    assert np.array_equal(
        np.asarray(art["embedding"]), np.asarray(old["embedding"])
    )


def test_spectral_sparse_bit_identical_to_direct_landmark_mds(data):
    base, _ = data
    art = _fit(
        base, regime="sparse", landmarks=32, objective="spectral"
    )
    want = landmark_mds_general(
        art["panel"], art["lm_idx"], d=2, max_iter=100, tol=1e-9
    )
    assert np.array_equal(
        np.asarray(art["embedding"]), np.asarray(want.embedding)
    )
    assert np.array_equal(
        np.asarray(art["lm_pinv"]), np.asarray(want.pinv)
    )


# ------------------------------------------------------------- stress ----


def test_stress_dense_beats_spectral_init(data):
    base, _ = data
    art = _fit(base, regime="dense", objective="stress")
    s, s0 = float(art["stress"]), float(art["stress_init"])
    assert np.isfinite(s) and s < s0
    rv = float(
        metrics.residual_variance(art["geodesics"], art["embedding"])
    )
    assert np.isfinite(rv)


def test_stress_panel_beats_spectral_init(data):
    base, _ = data
    art = _fit(
        base, regime="sparse", landmarks=32, objective="stress"
    )
    s, s0 = float(art["stress"]), float(art["stress_init"])
    assert np.isfinite(s) and s < s0
    rv = float(metrics.residual_variance_panel(
        art["panel"], art["embedding"], art["lm_idx"]
    ))
    assert np.isfinite(rv)


# --------------------------------------------------------------- path ----


def test_path_objective_fits_both_regimes(data):
    base, _ = data
    art = _fit(base, regime="dense", objective="path")
    y = np.asarray(art["embedding"])
    idx = np.asarray(art["path_idx"])
    assert y.shape == (base.shape[0], 2) and np.all(np.isfinite(y))
    assert idx.ndim == 1 and len(np.unique(idx)) == idx.shape[0]
    assert np.all((0 <= idx) & (idx < base.shape[0]))

    art_s = _fit(base, regime="sparse", landmarks=32, objective="path")
    ys = np.asarray(art_s["embedding"])
    idx_s = np.asarray(art_s["path_idx"])
    assert ys.shape == (base.shape[0], 2) and np.all(np.isfinite(ys))
    # sparse path landmarks are a subset of the panel's landmark set
    assert set(idx_s.tolist()) <= set(np.asarray(art_s["lm_idx"]).tolist())


# --------------------------------------- serve -> absorb -> serve ----


@pytest.mark.parametrize("objective", ["spectral", "stress", "path"])
def test_dense_serve_absorb_serve(data, objective):
    base, new = data
    art = _fit(base, regime="dense", objective=objective)
    mapper = streaming.StreamingMapper.from_artifacts(
        art, k=10, objective=objective,
        update=UpdateConfig(threshold=1e9),
    )
    y1 = np.asarray(mapper(jnp.asarray(new[:16])))
    assert y1.shape == (16, 2) and np.all(np.isfinite(y1))
    report = mapper.absorb(new[:16])
    assert report.absorbed == 16 and mapper.n_base == base.shape[0] + 16
    y2 = np.asarray(mapper(jnp.asarray(new[16:])))
    assert y2.shape == (16, 2) and np.all(np.isfinite(y2))


@pytest.mark.parametrize("objective", ["spectral", "stress", "path"])
def test_sparse_serve_absorb_serve(data, objective):
    base, new = data
    art = _fit(
        base, regime="sparse", landmarks=32, objective=objective
    )
    mapper = streaming.LandmarkStreamingMapper.from_artifacts(
        art, k=10, objective=objective,
        update=UpdateConfig(threshold=1e9),
    )
    y1 = np.asarray(mapper(jnp.asarray(new[:16])))
    assert y1.shape == (16, 2) and np.all(np.isfinite(y1))
    report = mapper.absorb(new[:16])
    assert report.absorbed == 16 and mapper.n_base == base.shape[0] + 16
    y2 = np.asarray(mapper(jnp.asarray(new[16:])))
    assert y2.shape == (16, 2) and np.all(np.isfinite(y2))


# ----------------------------------------- fingerprint discipline ----


def test_pipeline_resume_rejects_objective_mismatch(data, tmp_path):
    """A checkpoint fitted under one objective must not seed a resume
    under another - the config fingerprint mismatch forces a clean full
    re-run (the same discipline as a k mismatch)."""
    base, _ = data
    cfg_spec = PipelineConfig(
        k=10, d=2, regime="dense", objective="spectral"
    )
    mgr = CheckpointManager(str(tmp_path), keep=10)
    ManifoldPipeline(
        stages_for(cfg_spec, base.shape[0]), cfg=cfg_spec, checkpoint=mgr
    ).run(jnp.asarray(base))

    ran = []

    class Tracker:
        def __init__(self, inner):
            self.inner = inner
            self.name = inner.name
            self.requires = inner.requires
            self.provides = inner.provides
            for extra in ("exports", "params"):
                if hasattr(inner, extra):
                    setattr(self, extra, getattr(inner, extra))
            if hasattr(inner, "objective_id"):
                self.objective_id = inner.objective_id

        def run(self, ctx, a):
            ran.append(self.name)
            return self.inner.run(ctx, a)

    cfg_str = PipelineConfig(
        k=10, d=2, regime="dense", objective="stress"
    )
    mgr2 = CheckpointManager(str(tmp_path), keep=10)
    stages = [Tracker(s) for s in stages_for(cfg_str, base.shape[0])]
    ManifoldPipeline(stages, cfg=cfg_str, checkpoint=mgr2).run(
        jnp.asarray(base), resume=True
    )
    # nothing resumed: the front of the chain re-ran from knn
    assert ran[0] == "knn" and "apsp" in ran, ran


def test_mapper_restore_rejects_objective_mismatch(data, tmp_path):
    """Serving a spectral checkpoint as a stress answer must raise with
    the saved objective named, not silently serve the wrong frame."""
    base, _ = data
    cfg = PipelineConfig(
        k=10, d=2, regime="dense", objective="spectral"
    )
    mgr = CheckpointManager(str(tmp_path), keep=10)
    ManifoldPipeline(
        stages_for(cfg, base.shape[0]), cfg=cfg, checkpoint=mgr
    ).run(jnp.asarray(base))
    with pytest.raises(ValueError, match="objective 'spectral'"):
        streaming.StreamingMapper.from_checkpoint(
            CheckpointManager(str(tmp_path), keep=10),
            k=10, objective="stress",
        )
    # matching objective restores fine
    m = streaming.StreamingMapper.from_checkpoint(
        CheckpointManager(str(tmp_path), keep=10),
        k=10, objective="spectral",
    )
    assert m.n_base == base.shape[0]


def test_replay_rejects_objective_mismatch(data, tmp_path):
    """An update log absorbed under one objective must not be replayed
    by a mapper serving another (the log's published versions were
    re-embedded under the recorded objective)."""
    base, new = data
    art = _fit(base, regime="dense", objective="spectral")
    m1 = streaming.StreamingMapper.from_artifacts(
        art, k=10, objective="spectral",
        update=UpdateConfig(
            threshold=1e9, log_dir=str(tmp_path / "updates")
        ),
    )
    m1.absorb(new[:8])
    m2 = streaming.StreamingMapper.from_artifacts(
        art, k=10, objective="stress"
    )
    with pytest.raises(ValueError, match="objective 'spectral'"):
        m2.replay_update_log(str(tmp_path))


# ------------------------------------------------- mesh backend (slow) ----


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.core import streaming
from repro.core.pipeline import (
    LocalBackend, ManifoldPipeline, MeshBackend, PipelineConfig, stages_for,
)
from repro.core.update import UpdateConfig
from repro.data import euler_isometric_swiss_roll
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
backend = MeshBackend(mesh)
x, _ = euler_isometric_swiss_roll(272, seed=0)
x = np.pad(np.asarray(x), ((0, 0), (0, 1)))  # model axis divides features
base, new = x[:256], x[256:]

for regime, Mapper, extra in (
    ("dense", streaming.StreamingMapper, {}),
    ("sparse", streaming.LandmarkStreamingMapper, {"landmarks": 32}),
):
    for obj in ("spectral", "stress", "path"):
        cfg = PipelineConfig(k=10, d=2, regime=regime, objective=obj, **extra)
        art = ManifoldPipeline(
            stages_for(cfg, 256), cfg=cfg, backend=LocalBackend()
        ).run(jnp.asarray(base))
        m_loc = Mapper.from_artifacts(
            art, k=10, objective=obj, update=UpdateConfig(threshold=1e9)
        )
        m_mesh = Mapper.from_artifacts(
            art, k=10, backend=backend, objective=obj,
            update=UpdateConfig(threshold=1e9),
        )
        y_l = np.asarray(m_loc(jnp.asarray(new[:8])))
        y_m = np.asarray(m_mesh(jnp.asarray(new[:8])))
        np.testing.assert_allclose(y_m, y_l, rtol=1e-4, atol=1e-4)
        rep = m_mesh.absorb(new[:8])
        assert rep.absorbed == 8, (regime, obj, rep)
        assert m_mesh.n_base == 264
        y2 = np.asarray(m_mesh(jnp.asarray(new[8:])))
        assert np.all(np.isfinite(y2)), (regime, obj)
        print("OK", regime, obj)
print("ALL-OBJECTIVE-MESH-OK")
"""


@pytest.mark.slow
def test_objectives_on_mesh_backend():
    """All three objectives serve and absorb through MeshBackend, and
    mesh serving matches local within float tolerance (subprocess with 8
    fake CPU devices)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_SCRIPT],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "ALL-OBJECTIVE-MESH-OK" in proc.stdout
