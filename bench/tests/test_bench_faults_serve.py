"""A whole serving run at a small size on the CPU, past the look for a
chip: sound, it comes out correct; with the mapper broken underneath, it
does not."""
import numpy as np
import pytest

from bench_small import run_small

CELL = "serve.swissroll-dense-over"


def test_a_sound_serving_run_is_correct():
    out = run_small(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "read_pts_s"}


def _state_unchanged(monkeypatch):
    """Every flush answers with the first flush's coordinates."""
    from repro.core.streaming import StreamingMapper

    real = StreamingMapper.__call__
    first = {}

    def stale(self, x):
        if "y" not in first:
            first["y"] = np.asarray(real(self, x))
        return first["y"][: np.asarray(x).shape[0]]

    monkeypatch.setattr(StreamingMapper, "__call__", stale)


def _half_batch(monkeypatch):
    """Each flush maps the first half of its rows; the rest read the
    mean of those."""
    from repro.core.streaming import StreamingMapper

    real = StreamingMapper.__call__

    def half(self, x):
        x = np.asarray(x)
        h = max(1, x.shape[0] // 2)
        y = np.asarray(real(self, x[:h]))
        return np.concatenate([y, np.repeat(y.mean(0, keepdims=True),
                                            x.shape[0] - h, 0)])

    monkeypatch.setattr(StreamingMapper, "__call__", half)


def _answer_altered(monkeypatch):
    """One coordinate of each flush's first point is moved."""
    from repro.core.streaming import StreamingMapper

    real = StreamingMapper.__call__

    def altered(self, x):
        y = np.array(real(self, x))
        y[0, 0] += 1.0
        return y

    monkeypatch.setattr(StreamingMapper, "__call__", altered)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_a_broken_serving_run_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = run_small(CELL)
    assert not out["correct"], out["checks"]
