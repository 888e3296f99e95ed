"""The one traffic generator. A traffic mix is a data file,
``bench/traffic/<name>.json``, read by :func:`load`:

* ``{"loop": "closed", "outstanding": N}`` -- one client keeps N
  single-image requests outstanding and sends the next as each returns;
* ``{"loop": "open", "images_per_s": R, "burst": {...}}`` -- groups of
  single-image requests arrive as a Poisson process whose mean image rate
  is R. Without ``burst`` every group is one image. ``burst`` draws group
  sizes from a log-normal with the given ``median`` and ``sigma``, rounded
  and clipped to [1, ``max``].

The sizes and gaps of an open mix are one fixed multiset per mix and
window length, drawn from the mix's own ``base_seed``; a run's seed only
permutes them, so every seed offers the same work in another order, and
large bursts fall together wherever the permutation puts them.
Open-loop latency is timed from each image's scheduled arrival.
"""

from __future__ import annotations

import json
import math
import queue
import threading
import time
from pathlib import Path

import numpy as np

#: how long to wait for answers still due once the window has closed
DRAIN_S = 60.0


def load(root: Path, name: str) -> dict:
    mix = json.loads((Path(root) / "bench" / "traffic" / f"{name}.json")
                     .read_text())
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"traffic {name}: loop must be 'closed' or 'open'")
    return mix


def open_schedule(mix: dict, seed: int, seconds: float):
    """(arrival offsets in s, group sizes) of an open mix over a window
    of ``seconds``: a fixed multiset of gaps and sizes, permuted by
    ``seed``. The mean image rate over the window is exactly the mix's."""
    base = np.random.default_rng([int(mix.get("base_seed", 0)), 7])
    burst = mix.get("burst")
    target_images = int(round(mix["images_per_s"] * seconds))
    if burst is None:
        sizes = np.ones(target_images, np.int64)
    else:
        mu = math.log(burst["median"])
        sizes = []
        total = 0
        while total < target_images:
            s = int(min(burst["max"], max(1, round(
                math.exp(base.normal(mu, burst["sigma"]))))))
            s = min(s, target_images - total)
            sizes.append(s)
            total += s
        sizes = np.asarray(sizes, np.int64)
    gaps = base.exponential(1.0, len(sizes))
    gaps *= seconds / gaps.sum()      # the last group arrives at the close
    run = np.random.default_rng([int(seed), 11])
    order = run.permutation(len(sizes))
    gaps = gaps[run.permutation(len(gaps))]
    return np.cumsum(gaps), sizes[order]


class Record:
    """Per-request timestamps of one window (host clock, seconds)."""

    def __init__(self, n: int):
        self.t_due = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.image = np.zeros(n, np.int64)
        self.futures = [None] * n
        self.n = 0
        self.t0 = self.t_close = float("nan")
        self.lateness = np.zeros(0)

    def grow(self):
        m = len(self.t_due)
        for a in ("t_due", "t_done"):
            setattr(self, a, np.concatenate([getattr(self, a),
                                             np.full(m, np.nan)]))
        self.image = np.concatenate([self.image, np.zeros(m, np.int64)])
        self.futures.extend([None] * m)

    def results(self):
        """(done, failed): request indices that returned an answer, and
        those that raised or never came."""
        done, failed = [], []
        for i in range(self.n):
            f = self.futures[i]
            if f.done() and f.exception() is None:
                done.append(i)
            else:
                failed.append(i)
        return np.asarray(done, np.int64), np.asarray(failed, np.int64)


def _track(rec: Record, i: int, fut, done_q=None):
    def cb(_f, i=i):
        rec.t_done[i] = time.perf_counter()
        if done_q is not None:
            done_q.put(i)
    fut.add_done_callback(cb)


def drive_closed(submit, mix: dict, seconds: float, pick) -> Record:
    """Keep ``mix["outstanding"]`` requests in flight for ``seconds``.
    ``submit(image_index) -> Future``; ``pick()`` draws an image index."""
    n_out = int(mix["outstanding"])
    rec = Record(4 * n_out)
    done_q: queue.SimpleQueue = queue.SimpleQueue()

    def launch(t_due):
        if rec.n == len(rec.t_due):
            rec.grow()
        i = rec.n
        rec.image[i] = pick()
        rec.t_due[i] = t_due
        rec.futures[i] = submit(int(rec.image[i]))
        rec.n += 1
        _track(rec, i, rec.futures[i], done_q)

    rec.t0 = time.perf_counter()
    t_end = rec.t0 + seconds
    for _ in range(n_out):
        launch(time.perf_counter())
    while True:
        done_q.get()
        now = time.perf_counter()
        if now >= t_end:
            break
        launch(now)
    rec.t_close = t_end
    _wait(rec)
    return rec


def drive_open(submit, arrivals, sizes, pick) -> Record:
    """Send each group at its scheduled offset from the window's start,
    however late the client runs; records how late it was."""
    rec = Record(int(np.sum(sizes)))
    late = np.zeros(len(sizes))
    rec.t0 = t0 = time.perf_counter()
    i = 0
    for k, (ta, sz) in enumerate(zip(arrivals, sizes)):
        t_due = t0 + ta
        wait = t_due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late[k] = max(0.0, time.perf_counter() - t_due)
        for _ in range(int(sz)):
            rec.image[i] = pick()
            rec.t_due[i] = t_due
            rec.futures[i] = submit(int(rec.image[i]))
            _track(rec, i, rec.futures[i])
            i += 1
    rec.n = i
    rec.t_close = t0 + (arrivals[-1] if len(arrivals) else 0.0)
    rec.lateness = late
    _wait(rec)
    return rec


def _wait(rec: Record) -> None:
    deadline = time.perf_counter() + DRAIN_S
    for f in rec.futures[:rec.n]:
        left = deadline - time.perf_counter()
        if left <= 0:
            break
        try:
            f.exception(timeout=left)
        except TimeoutError:
            break
    # a future's callbacks run just after it resolves: let the last land
    resolved = [i for i in range(rec.n) if rec.futures[i].done()]
    deadline = time.perf_counter() + 1.0
    while (np.isnan(rec.t_done[resolved]).any()
           and time.perf_counter() < deadline):
        time.sleep(0.002)


class Window:
    """Calls ``start`` on a thread ``delay`` s after :meth:`begin` and
    ``stop`` ``span`` s later (the profiler, while the client keeps its
    schedule); each returns the host time it was called at."""

    def __init__(self, delay: float, span: float, start, stop):
        self.t_start = self.t_stop = float("nan")
        self._thread = threading.Thread(
            target=self._run, args=(delay, span, start, stop), daemon=True)
        self.error = None

    def _run(self, delay, span, start, stop):
        try:
            time.sleep(delay)
            self.t_start = start()
            time.sleep(span)
            self.t_stop = stop()
        except Exception as e:  # noqa: BLE001 -- reported after the window
            self.error = e

    def begin(self):
        self._thread.start()

    def join(self):
        self._thread.join()
        if self.error is not None:
            raise self.error
