"""The per-layer readers of the serving worker's batch spans, on made-up
spans with values computed by hand. They read only the batches that
ended before the traced span began (host ns below ``trace_lo +
offset_ns``), whose device puts the profiler did not slow."""

import types
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]
MS = 1_000_000                        # ns


def span(name, t0, t1, **args):
    return types.SimpleNamespace(name=name, t0_ns=t0, t1_ns=t1, trace_id=0,
                                 args=args)


def batch(bid, t, phases):
    """One batch's spans laid end to end from ``t`` (ms):
    ``phases`` = [(name, length ms)]."""
    out = []
    for name, ms in phases:
        out.append(span(name, t * MS, (t + ms) * MS, batch_id=bid))
        t += ms
    return out


def ctx(spans, window, cut_ms):
    """The traced span starts at ``cut_ms`` on the host: trace ns 0 plus
    an offset."""
    return types.SimpleNamespace(spans=spans, window=window, trace_lo=0,
                                 offset_ns=cut_ms * MS)


NINES = [("form", 9.0), ("stack", 9.0), ("transfer", 9.0),
         ("device", 9.0), ("readback", 9.0)]


@pytest.mark.parametrize("metric, phase, want", [
    ("stack_ms_per_batch.saturate", "stack", (1.0 + 3.0) / 2),
    ("transfer_ms_per_batch.saturate", "transfer", (40.0 + 30.0) / 2),
    ("device_ms_per_batch.saturate", "device", (36.0 + 38.0) / 2),
    ("readback_ms_per_batch.saturate", "readback", (0.5 + 0.25) / 2),
    ("form_ms_per_batch.saturate", "form", (2.0 + 4.0) / 2),
])
def test_phase_readers(metric, phase, want):
    spans = (batch(1, 10, [("form", 2.0), ("stack", 1.0),
                           ("transfer", 40.0), ("device", 36.0),
                           ("readback", 0.5)])
             + batch(2, 100, [("form", 4.0), ("stack", 3.0),
                              ("transfer", 30.0), ("device", 38.0),
                              ("readback", 0.25)])
             # a request's queue span carries its batch's id: not a phase
             + [span("queue", 0, 500 * MS, batch_id=2, batch=256)]
             # begun before the window, ended after the traced span
             # began, begun after it: not counted
             + batch(0, -20, NINES) + batch(3, 190, NINES)
             + batch(4, 300, NINES))
    read = spec.reader(ROOT, metric)
    assert read(ctx(spans, (0, 400 * MS), 200)) == pytest.approx(want)
    # a program without these spans (the parent's) reads nothing
    others = [s for s in spans if s.name != phase]
    assert read(ctx(others, (0, 400 * MS), 200)) is None
    # nor does a window whose batches all met the profiler
    assert read(ctx(spans, (0, 400 * MS), 5)) is None


def test_idle_in_transfer_share():
    """Batch 1 spans [0, 17] ms, batch 2 [20, 37]; the device is busy in
    their ``device`` spans [6, 16] and [26, 36], so idle [0, 6], [16, 26]
    and [36, 37]: 17 ms. The transfers [3, 6] and [23, 26] lie in it:
    6 of 17 ms. Batch 3 ends after the traced span began (45 ms)."""
    read = spec.reader(ROOT, "idle_in_transfer_share.saturate")
    phases = [("form", 1.0), ("stack", 2.0), ("transfer", 3.0),
              ("device", 10.0), ("readback", 1.0)]
    spans = (batch(1, 0, phases) + batch(2, 20, phases)
             + batch(3, 40, [("form", 1.0), ("transfer", 30.0)]))
    assert read(ctx(spans, (0, 400 * MS), 45)) == pytest.approx(
        100.0 * 6 / 17)
    # a transfer that covers its batch covers all of the idle time
    whole = [span("transfer", 0, 37 * MS, batch_id=1),
             span("device", 10 * MS, 30 * MS, batch_id=1)]
    assert read(ctx(whole, (0, 400 * MS), 45)) == pytest.approx(100.0)
    # nothing to read: no transfer spans, or no batch before the trace
    assert read(ctx([s for s in spans if s.name != "transfer"],
                    (0, 400 * MS), 45)) is None
    assert read(ctx(spans, (0, 400 * MS), 10)) is None
