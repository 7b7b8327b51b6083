"""Whole fit runs at a small size on the CPU, past the look for a chip:
sound, they come out correct; with the timed path broken underneath,
they do not."""
import numpy as np
import pytest

from bench_small import run_small

CELLS = ["fit.swissroll-dense", "fit.swissroll-sparse"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_fit_run_is_correct(cell):
    out = run_small(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"setup_s", "fit_s"}


def _state_unchanged(monkeypatch):
    """The geodesic step hands its state back untouched."""
    from repro.core import pipeline, sparse

    for cls in (pipeline.APSPStage, sparse.SparseGeodesicStage):
        monkeypatch.setattr(cls, "run_segment",
                            lambda self, ctx, art, state, lo, hi: state)


def _half_batch(monkeypatch):
    """kNN searches only the first half of the points as candidates."""
    import jax.numpy as jnp

    from repro.core import pipeline

    real = pipeline.LocalBackend.knn

    def knn(self, cfg, x):
        n = x.shape[0]
        far = jnp.where(jnp.arange(n)[:, None] >= n // 2, 1e4, 0.0)
        d, i = real(self, cfg, x + far)
        return d, i

    monkeypatch.setattr(pipeline.LocalBackend, "knn", knn)


def _answer_altered(monkeypatch):
    """The embedding is altered where it is produced: its rows are
    rotated by one place."""
    from repro.core import pipeline, sparse

    def wrap(cls):
        run = cls.run

        def altered(self, ctx, art):
            out = dict(run(self, ctx, art))
            out["embedding"] = np.roll(np.asarray(out["embedding"]), 1, 0)
            return out

        monkeypatch.setattr(cls, "run", altered)

    wrap(pipeline.EigenStage)
    wrap(sparse.SparseEmbedStage)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_a_broken_fit_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = run_small(cell)
    assert not out["correct"], out["checks"]
