"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

The device's operations are the events on the ``XLA Ops`` line of each
``/device:`` plane. Busy time is the union of their intervals inside the
traced window, so overlapping operations count once. Host spans of the
serving runtime are put on the trace's clock by a marker annotation
(``SYNC``) recorded at a known host-clock instant.
"""

from __future__ import annotations

import glob
import os
import re

SYNC = "bench_clock_sync"
OPS_LINE = "XLA Ops"


def newest_xplane(log_dir) -> str:
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def device_ops(profile) -> dict:
    """{plane name: [(name, start_ns, end_ns), ...]} of every device
    plane's operations, in start order. On the TPU an operation's name is
    its whole HLO instruction, shapes included."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                evs.append((e.name, float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns)))
        if evs:
            evs.sort(key=lambda t: t[1])
            out[plane.name] = evs
    return out


def host_marker(profile, name: str = SYNC):
    """Start (trace clock, ns) of the first host event called ``name``."""
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == name:
                    return float(e.start_ns)
    return None


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(intervals, lo, hi)))


def gaps(intervals, lo: float, hi: float):
    """Idle intervals of [lo, hi] between the merged busy ones."""
    out, t = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def reduce(profile, lo_ns: float, hi_ns: float) -> dict:
    """Busy and window seconds (averaged over the device planes), and
    every operation inside [lo_ns, hi_ns] with its device plane."""
    planes = device_ops(profile)
    if not planes:
        return {"busy_s": 0.0, "window_s": (hi_ns - lo_ns) * 1e-9,
                "ops": [], "planes": 0}
    busy = [busy_ns([(a, b) for _, a, b in evs], lo_ns, hi_ns)
            for evs in planes.values()]
    ops = [(p, n, a, b) for p, evs in planes.items()
           for n, a, b in evs if a >= lo_ns and b <= hi_ns]
    return {"busy_s": sum(busy) / len(busy) * 1e-9,
            "window_s": (hi_ns - lo_ns) * 1e-9, "ops": ops,
            "planes": len(planes)}


_HLO = re.compile(r"^%?(\S+) = (\w+\[[\d,]*\])?.*?\s([a-z][\w-]*)\(")


def short_name(name: str) -> str:
    """``run.9 custom-call u32[2,1,256,32,32,2]`` for an operation whose
    trace name is its whole HLO instruction."""
    m = _HLO.match(name)
    if m is None:
        return name[:120]
    return " ".join(p for p in (m.group(1), m.group(3), m.group(2)) if p)


def top_ops(ops, k: int = 10):
    """The ``k`` operations with the most device time: [[name, s]]."""
    tot = {}
    for _, name, a, b in ops:
        name = short_name(name)
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [[n, t * 1e-9] for n, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]
