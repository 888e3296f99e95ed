"""The traffic generator: seeded schedules, rates, open-loop timing."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench import traffic

POISSON = {"loop": "open", "images_per_s": 2000.0}
BURSTY = {"loop": "open", "images_per_s": 2000.0,
          "burst": {"median": 8, "sigma": 1.5, "max": 512}}


@pytest.mark.parametrize("mix", [POISSON, BURSTY])
def test_same_seed_same_schedule_and_every_seed_the_same_work(mix):
    a1, s1 = traffic.open_schedule(mix, 2**40 + 1, 10.0)
    a2, s2 = traffic.open_schedule(mix, 2**40 + 1, 10.0)
    b1, t1 = traffic.open_schedule(mix, 5, 10.0)
    assert np.array_equal(a1, a2) and np.array_equal(s1, s2)
    assert not np.array_equal(s1, t1) or mix is POISSON
    assert not np.array_equal(a1, b1)
    assert np.array_equal(np.sort(s1), np.sort(t1))
    assert np.allclose(np.sort(np.diff(a1, prepend=0.0)),
                       np.sort(np.diff(b1, prepend=0.0)))


@pytest.mark.parametrize("mix", [POISSON, BURSTY])
def test_mean_rate_is_the_target(mix):
    seconds = 10.0
    arrivals, sizes = traffic.open_schedule(mix, 3, seconds)
    assert sizes.sum() == round(mix["images_per_s"] * seconds)
    assert arrivals[0] > 0.0
    assert arrivals[-1] == pytest.approx(seconds, rel=1e-9)
    # Poisson arrivals: the gaps' coefficient of variation is 1
    gaps = np.diff(arrivals)
    n = len(gaps)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 5 / np.sqrt(n)
    if "burst" in mix:
        assert sizes.min() >= 1 and sizes.max() <= 512
        med = np.median(sizes)
        assert 6 <= med <= 10, med


def _record(t_done, t_due=None):
    rec = traffic.Record(len(t_done))
    rec.n = len(t_done)
    rec.t0 = 100.0
    rec.t_done[:] = t_done
    rec.t_due[:] = rec.t0 if t_due is None else t_due
    return rec, np.arange(len(t_done))


@pytest.mark.parametrize("late", [0, 3])
def test_throughput_counts_only_answers_inside_the_window(late):
    from bench.run_cell import end_to_end
    inside = 100.0 + np.linspace(0.1, 9.9, 50)
    rec, done = _record(np.concatenate([inside, 110.5 + np.arange(late)]))
    assert end_to_end(rec, done, 10.0)["images_per_s"] == 5.0


def test_longest_gap_spans_the_window_edges():
    from bench.run_cell import longest_gap
    rec, done = _record([101.0, 101.5, 105.0, 109.0, 112.0])
    assert longest_gap(rec, done, 10.0) == pytest.approx(4.0)
    rec, done = _record([100.2, 100.4])
    assert longest_gap(rec, done, 10.0) == pytest.approx(9.6)


def test_open_loop_latency_counts_from_the_scheduled_time():
    """A submit that stalls makes the client late; the requests due during
    the stall are still timed from when they were due."""
    stall = 0.2
    calls = []

    def submit(_idx):
        f = Future()
        if not calls:
            time.sleep(stall)
        calls.append(time.perf_counter())
        f.set_result(np.zeros(10))
        return f

    arrivals = np.array([0.0, 0.05, 0.1, 0.3])
    sizes = np.ones(4, np.int64)
    rec = traffic.drive_open(submit, arrivals, sizes, lambda: 0)
    lat = rec.t_done[:rec.n] - rec.t_due[:rec.n]
    assert lat[0] >= stall
    assert lat[1] >= stall - 0.05 - 1e-3
    assert lat[2] >= stall - 0.1 - 1e-3
    assert rec.lateness[1] >= stall - 0.05 - 1e-3
    assert lat[3] < 0.05


def test_closed_loop_keeps_its_requests_outstanding():
    outstanding, peak = [0], [0]
    lock = threading.Lock()
    pending = []

    def submit(_idx):
        f = Future()
        with lock:
            outstanding[0] += 1
            peak[0] = max(peak[0], outstanding[0])
            pending.append(f)
        return f

    def server():
        while not stop.is_set():
            with lock:
                batch = pending[:]
                pending.clear()
                outstanding[0] -= len(batch)
            for f in batch:
                f.set_result(np.zeros(10))
            time.sleep(0.002)

    stop = threading.Event()
    th = threading.Thread(target=server, daemon=True)
    th.start()
    try:
        rec = traffic.drive_closed(submit, {"outstanding": 8}, 0.3,
                                   lambda: 0)
    finally:
        stop.set()
        th.join(timeout=5)
    assert not th.is_alive()
    assert peak[0] == 8
    done, failed = rec.results()
    assert len(failed) == 0 and len(done) == rec.n > 8
