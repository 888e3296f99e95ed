"""Micro-batches of a window, read from the serving runtime's spans.

Every request of a micro-batch carries the batch's own ``schedule``,
``execute`` and ``finalize`` spans, and its ``queue`` span ends when the
batch was formed, with the batch's size as ``batch``. So one micro-batch
is one distinct (start, end) of a phase.
"""

from __future__ import annotations


def phase(spans, name: str, lo_ns: float, hi_ns: float):
    """Distinct (start, end) ns of phase ``name`` begun in [lo, hi]."""
    return sorted({(s.t0_ns, s.t1_ns) for s in spans
                   if s.name == name and lo_ns <= s.t0_ns <= hi_ns})


def sizes(spans, lo_ns: float, hi_ns: float):
    """Size of every micro-batch formed in [lo, hi]."""
    formed = {(s.t1_ns, s.args.get("batch")) for s in spans
              if s.name == "queue" and lo_ns <= s.t1_ns <= hi_ns}
    return [b for _, b in formed if b is not None]


def phase_ms_per_batch(ctx, name: str):
    """Mean length of phase ``name`` per micro-batch of the window, ms."""
    ph = phase(ctx.spans, name, *ctx.window)
    if not ph:
        return None
    return sum(b - a for a, b in ph) / len(ph) * 1e-6


def images_per_batch(ctx):
    """Mean number of images per micro-batch formed in the window."""
    s = sizes(ctx.spans, *ctx.window)
    if not s:
        return None
    return sum(s) / len(s)
