"""The serving worker's batch spans, read from the batches the profiler
did not see.

Each micro-batch records one span per phase (``form``, ``schedule``,
``execute`` with ``stack``, ``launch``, ``transfer``, ``device`` and
``readback`` inside it, ``finalize``, ``emit``), each with the batch's
``batch_id``. While the profiler records, it slows every batch's device
put by tens of ms (it records the input's layout on the device event by
event), so the spans of the batches it saw read its cost, not the
system's. These helpers read only the batches of the window that ended
before the traced span began: host ns below ``ctx.trace_lo +
ctx.offset_ns``. A program without batch spans reads nothing.
"""

from __future__ import annotations

from bench import trace


def untraced(ctx) -> dict:
    """{batch_id: {phase: (start, end) ns}} of every batch whose spans
    all lie between the window's start and the traced span's start."""
    cut = ctx.trace_lo + ctx.offset_ns
    out = {}
    for s in ctx.spans:
        bid = (s.args or {}).get("batch_id")
        if bid is not None and s.name != "queue":
            out.setdefault(bid, {})[s.name] = (s.t0_ns, s.t1_ns)
    return {b: ph for b, ph in out.items()
            if min(a for a, _ in ph.values()) >= ctx.window[0]
            and max(e for _, e in ph.values()) < cut}


def ms_per_batch(ctx, name: str):
    """Mean length of phase ``name`` over the untraced batches, ms."""
    ph = [p[name] for p in untraced(ctx).values() if name in p]
    if not ph:
        return None
    return sum(b - a for a, b in ph) / len(ph) * 1e-6


def idle_in(ctx, name: str):
    """Share of the device's idle time, as the host sees it, that falls
    inside some untraced batch's phase ``name``, %. The device counts as
    busy inside each batch's ``device`` span, and idle everywhere else
    from the first of these batches' spans to the end of the last."""
    batches = untraced(ctx).values()
    busy = [p["device"] for p in batches if "device" in p]
    inside = [p[name] for p in batches if name in p]
    if not busy or not inside:
        return None
    lo = min(a for p in batches for a, _ in p.values())
    hi = max(b for p in batches for _, b in p.values())
    idle = trace.gaps(busy, lo, hi)
    idle_ns = sum(b - a for a, b in idle)
    if idle_ns <= 0:
        return None
    return 100.0 * sum(trace.busy_ns(inside, a, b)
                       for a, b in idle) / idle_ns
