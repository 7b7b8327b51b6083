"""Latency from due time with missed and failed requests, and the
open-loop schedule's fixed work per seed."""
import numpy as np
import pytest

from bench import generator


def test_latency_counts_from_due_time_and_misses_count_as_the_wait():
    due = [0.0, 1.0, 2.0, 3.0]
    done = [0.5, np.nan, 2.2, 12.0]
    failed = [False, False, True, False]     # 1: never answered
    lat, in_window = generator.latencies(due, done, failed, 10.0)
    wait = generator.ANSWER_WAIT_S
    assert lat == pytest.approx([0.5, 10.0 + wait - 1.0,
                                 10.0 + wait - 2.0, 9.0])
    assert list(in_window) == [True, False, False, False]
    # one miss in four puts the 99th percentile at the wait
    assert np.percentile(lat, 99) > wait


TRAFFIC = {"rate_pts_s": 2000, "size": {"mean": 4, "max": 64}}


def test_answers_are_recorded_from_callbacks_without_keeping_futures():
    from concurrent.futures import Future

    rec = generator._Answers(3, keep=[2])
    futs = [Future() for _ in range(3)]
    for i, f in enumerate(futs):
        f.add_done_callback(rec.callback(i))
    futs[0].set_result(np.ones(1))
    futs[1].set_exception(RuntimeError("flush failed"))
    rec.wait(0.0)                     # past its deadline: returns at once
    assert rec.count == 2
    futs[2].set_result(np.full(2, 7.0))
    rec.wait(float("inf"))
    assert list(rec.failed) == [False, True, False]
    assert not np.isnan(rec.t_done).any()
    assert list(rec.answers) == [2] and rec.answers[2].tolist() == [7, 7]


def test_every_seed_offers_the_same_work_in_its_own_order():
    a_due, a_sizes = generator.schedule(TRAFFIC, 10.0, 1)
    b_due, b_sizes = generator.schedule(TRAFFIC, 10.0, 2**31 + 3)
    assert len(a_due) == len(b_due)
    assert sorted(a_sizes) == sorted(b_sizes)
    assert sorted(np.diff(a_due, prepend=0)) == pytest.approx(
        sorted(np.diff(b_due, prepend=0)))
    assert not np.array_equal(a_sizes, b_sizes)
    assert 0 < a_due.min() and a_due.max() <= 10.0
    assert a_sizes.min() >= 1 and a_sizes.max() <= 64
    assert np.sum(a_sizes) / 10.0 == pytest.approx(2000, rel=0.1)
