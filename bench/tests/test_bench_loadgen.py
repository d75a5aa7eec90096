"""The open-loop generator: its schedule and how it measures lateness."""
import threading
import time

import bench_tiny  # noqa: F401
import numpy as np
import pytest

from bench import loadgen, stats

MIX = {"kind": "open_loop", "rate_profile": [[1.0, 2000.0]],
       "rows": {"median": 8, "log_sigma": 1.2, "min": 1, "max": 256}}


def test_mean_rate_and_sizes():
    s = loadgen.schedule(MIX, 10.0, np.random.default_rng(3))
    assert len(s.due_s) == 20_000
    assert s.due_s[0] > 0 and np.all(np.diff(s.due_s) > 0)
    # n arrivals spread over the window: the mean rate is the mix's
    assert len(s.due_s) / s.due_s[-1] == pytest.approx(2000.0, rel=1e-3)
    assert np.median(s.rows) == 8
    assert s.rows.min() == 1 and s.rows.max() == 256


def test_every_seed_gets_the_same_work_in_its_own_order():
    a = loadgen.schedule(MIX, 2.0, np.random.default_rng(1))
    b = loadgen.schedule(MIX, 2.0, np.random.default_rng(2))
    assert sorted(a.rows) == sorted(b.rows)
    np.testing.assert_allclose(np.sort(np.diff(a.due_s, prepend=0.0)),
                               np.sort(np.diff(b.due_s, prepend=0.0)),
                               rtol=1e-9, atol=1e-12)
    assert not np.array_equal(a.rows, b.rows)


def test_bursts_follow_the_profile():
    mix = dict(MIX, rate_profile=[[1.0, 1000.0], [1.0, 0.0]])
    s = loadgen.schedule(mix, 4.0, np.random.default_rng(0))
    assert len(s.due_s) == 2000
    on = (s.due_s % 2.0) < 1.0
    assert on.all()


class _Done:
    def __init__(self):
        self.ev = threading.Event()

    def done(self):
        return self.ev.is_set()

    def result(self, timeout=None):
        if not self.ev.wait(timeout):
            raise TimeoutError
        return 0


def test_lateness_and_latency_run_from_the_due_time():
    """A submit that stalls 50 ms makes every later request late; the
    latency of each counts from its due time, so the stall shows in all."""
    due = np.arange(5) * 0.001
    answers = {}

    def submit(i):
        if i == 0:
            time.sleep(0.05)
        f = _Done()
        f.ev.set()
        return f

    loop = loadgen.OpenLoop(submit, due, lambda name: _null(),
                            answers.__setitem__)
    start = time.perf_counter() + 0.005
    loop.run(start)
    loop.join(close=time.perf_counter(), grace_s=5.0)
    late = loop.sent - loop.due
    lat = loop.done - loop.due
    assert np.allclose(loop.due - start, due)
    assert late.min() >= 0.045         # every request waited for the stall
    assert np.all(lat >= late)
    assert stats.percentile_ms(late, 99) >= 45.0
    assert answers == {i: 0 for i in range(5)} and not loop.failed


def test_refused_and_unanswered_requests_are_failed():
    def submit(i):
        if i == 0:
            raise RuntimeError("queue full")
        return _Done()                 # never completes

    loop = loadgen.OpenLoop(submit, np.array([0.0, 0.001]),
                            lambda name: _null(), lambda i, a: None)
    loop.run(time.perf_counter())
    loop.join(close=time.perf_counter(), grace_s=0.2)
    assert np.isnan(loop.done).all()
    assert isinstance(loop.failed[0], RuntimeError)
    assert isinstance(loop.failed[1], TimeoutError)


def test_percentile_is_an_observed_sample():
    xs = [0.001, 0.002, 0.010]
    assert stats.percentile_ms(xs, 50) == 2.0
    assert stats.percentile_ms(xs, 99) == 10.0


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
