"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run_cell.py --workload resnet9-w2a2.saturate \\
        --seed 12345 --seconds 10 --trace 0

The cell serves its configuration through the normal path
(``launch/serve.CNNServer`` -> serving registry, batcher, scheduler,
service -> ``compiler/executor.BucketedRunner`` -> Pallas kernels) under
its traffic mix for ``--seconds``. The architecture comes from the model
module the configuration names (``bench/models/<model>.py``): its seeded
weights, the graph the server compiles, and the plain reference. ``--trace
0`` prints the cell's end-to-end metrics; ``--trace 1`` records a device
trace of part of the window and prints its per-layer metrics. Both check
the answers the window returned against the model's reference and print
each compared number beside its limit, last on standard error and last in
the result line.

Exits non-zero, printing no result, without a TPU, with fewer chips than
the cell asks for, or on a device kind that ``bench/peaks.json`` lacks.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import roofline, spec, trace, traffic  # noqa: E402

#: seconds into the window at which the traced run starts the profiler,
#: and how long it records
TRACE_DELAY_S = 2.0
TRACE_SPAN_S = 1.0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ set-up

class Served:
    """The cell's server, its weights and its images."""

    def __init__(self, plan: dict, seed: int):
        import jax
        from bench import quantref
        from repro.launch.serve import CNNServer
        from repro.models.layers import QuantPolicy
        from repro.runtime.compile_cache import enable_compile_cache

        cfg = self.cfg = plan["config"]
        model = plan["model"]
        enable_compile_cache()
        # every program of the run is cached, however short its compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.params = model.make_params(cfg, seed)
        hw, c = int(cfg["input_hw"]), int(cfg["input_channels"])
        self.calib = quantref.make_images(seed, int(cfg["calib_images"]),
                                          hw, c, stream=1)
        self.pool = quantref.make_images(seed, int(cfg["image_pool"]), hw,
                                         c, stream=2)
        graph = model.graph(self.params, cfg)
        policy = QuantPolicy(
            mode="serial", w_bits=int(cfg["w_bits"]),
            a_bits=int(cfg["a_bits"]), w_signed=bool(cfg["w_signed"]),
            a_signed=bool(cfg["a_signed"]),
            radix_bits=int(cfg["radix_bits"]))
        self.server = CNNServer(
            graph, calib=jax.numpy.asarray(self.calib),
            backend=cfg["backend"], policy=policy,
            max_batch=int(cfg["max_batch"]),
            max_wait_s=float(cfg["max_wait_s"]))
        self.service = self.server.service
        self.key = self.server.key
        self.bucket_compiles = self.service.warmup(self.key)
        self.runner = self.service._runner_for(self.key)
        # every batch size the batcher can form: the runner pads a batch
        # to its bucket and slices the answers with eager ops, each of
        # which is a program of its own per size
        for n in range(1, int(cfg["max_batch"]) + 1):
            jax.block_until_ready(self.runner(self.pool[:n]))
        self.rng = np.random.default_rng([int(seed), 3])

    def pick(self) -> int:
        return int(self.rng.integers(len(self.pool)))

    def submit(self, idx: int):
        return self.service.submit(self.key, self.pool[idx])

    def close(self) -> None:
        self.server.close()
        self.server = self.service = self.runner = None
        gc.unfreeze()
        gc.collect()


class GcTime:
    """Seconds the garbage collector ran, and its full collections, while
    it is open."""

    def __init__(self):
        self.s = 0.0
        self.full = 0
        self._t = 0.0

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        self.s += time.perf_counter() - self._t
        self.full += info["generation"] == 2

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)


class CompileCount:
    """Counts the XLA programs built (compiled or loaded from the cache)
    while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def _on(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


# ------------------------------------------------------------------ window

def drive(served: Served, plan: dict, seed: int, seconds: float,
          profile_dir=None):
    """Run the window; with ``profile_dir`` also record a device trace of
    part of it, and keep the service's spans as they stood at its end in
    ``served.spans``. Returns the record and the traced span."""
    mix = plan["traffic"]
    win = None
    if profile_dir is not None:
        import jax
        delay = min(TRACE_DELAY_S, seconds / 4)
        span = min(TRACE_SPAN_S, seconds / 2)

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # a Python tracer slows the host
        opts.host_tracer_level = 1      # user annotations: the clock marker

        def start():
            jax.profiler.start_trace(str(profile_dir),
                                     profiler_options=opts)
            t = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(trace.SYNC):
                pass
            return t

        def stop():
            t = time.perf_counter_ns()
            # the service keeps its last 65,536 spans: take them before
            # the profiler's write lets later batches push these out
            served.spans = served.service.tracer.spans()
            jax.profiler.stop_trace()
            return t

        win = traffic.Window(delay, span, start, stop)
    if mix["loop"] == "closed":
        if win:
            win.begin()
        rec = traffic.drive_closed(served.submit, mix, seconds, served.pick)
    else:
        arrivals, sizes = traffic.open_schedule(mix, seed, seconds)
        if win:
            win.begin()
        rec = traffic.drive_open(served.submit, arrivals, sizes, served.pick)
    if win:
        win.join()
    return rec, win


def longest_gap(rec, done, seconds: float) -> float:
    """The longest time in the window, in s, in which no answer came."""
    t = np.sort(rec.t_done[done])
    t = t[t <= rec.t0 + seconds]
    return float(np.max(np.diff(t, prepend=rec.t0, append=rec.t0 + seconds)))


def end_to_end(rec, done, seconds: float) -> dict:
    t_done = rec.t_done[done]
    lat = (t_done - rec.t_due[done]) * 1e3
    out = {
        "images_per_s": float(np.sum(t_done <= rec.t0 + seconds)) / seconds,
    }
    if len(lat):
        out["latency_p50_ms"] = float(np.percentile(lat, 50))
        out["latency_p95_ms"] = float(np.percentile(lat, 95))
    return out


# ------------------------------------------------------------------- check

def gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per answer: the largest logit difference over the reference's
    largest logit magnitude."""
    return (np.max(np.abs(got - ref), axis=1)
            / np.max(np.abs(ref), axis=1))


def numbers(got: np.ndarray, ref: np.ndarray) -> dict:
    g = gaps(got, ref)
    return {"gap_mean": float(np.mean(g)), "gap_max": float(np.max(g))}


def sample(rec, done, plan: dict, seed: int) -> np.ndarray:
    """Request indices to check: a seeded sample of the answers due in
    the window."""
    k = min(int(plan["config"]["check_images"]), len(done))
    rng = np.random.default_rng([int(seed), 5])
    return np.sort(rng.choice(done, size=k, replace=False))


def verdict(nums: dict, limits: dict):
    rows = {k: {"value": v, "limit": limits.get(k)} for k, v in nums.items()}
    ok = all(r["limit"] is not None and r["value"] <= r["limit"]
             for r in rows.values())
    return ok, rows


# ------------------------------------------------------------------ traced

def traced_context(served, plan, rec, done, win, profile_dir):
    """What the per-layer readers read: the reduced device trace of the
    traced span, the service's spans from the window's start to the
    traced span's end, and the sizes."""
    prof = trace.load(trace.newest_xplane(profile_dir))
    sync = trace.host_marker(prof)
    if sync is None:
        raise RuntimeError("the traced window has no clock marker")
    offset = win.t_start - sync          # host ns = trace ns + offset
    lo, hi = sync, win.t_stop - offset
    red = trace.reduce(prof, lo, hi)
    t_done = rec.t_done[done]
    in_trace = int(np.sum((t_done * 1e9 >= win.t_start)
                          & (t_done * 1e9 <= win.t_stop)))
    return types.SimpleNamespace(
        cfg=plan["config"], model=plan["model"], traffic=plan["traffic"],
        trace=red, offset_ns=offset, spans=served.spans, trace_lo=lo,
        trace_hi=hi,
        window=(rec.t0 * 1e9, win.t_stop),
        images_in_trace=in_trace, peaks=served.peaks)


def label_gaps(ctx, k: int = 10) -> list:
    """The ``k`` longest idle gaps of the device in the traced span, each
    named by the service phase open on the host at its middle."""
    busy = [(a, b) for _, _, a, b in ctx.trace["ops"]]
    idle = trace.gaps(busy, ctx.trace_lo, ctx.trace_hi)
    # one worker thread: its phases never overlap
    phases = sorted({(s.name, s.t0_ns, s.t1_ns) for s in ctx.spans
                     if s.name in ("schedule", "execute", "finalize")},
                    key=lambda p: p[1])
    starts = np.asarray([p[1] for p in phases], np.float64)
    out = []
    for a, b in sorted(idle, key=lambda g: g[0] - g[1])[:k]:
        mid = (a + b) / 2 + ctx.offset_ns
        i = int(np.searchsorted(starts, mid, side="right")) - 1
        label = "no batch on the host"
        if i >= 0 and phases[i][2] >= mid:
            label = f"host {phases[i][0]}"
        out.append([label, (b - a) * 1e-9])
    return out


# -------------------------------------------------------------------- main

def run(plan: dict, seed: int, seconds: float, traced: bool, *,
        device, peaks: dict) -> dict:
    """One run of a cell: set-up, window, check. The result's
    ``metrics`` are the end-to-end ones, or with ``traced`` the per-layer
    ones."""
    served = Served(plan, seed)
    served.peaks = peaks
    # what set-up built lives as long as the run: keep the collector's
    # full passes in the window off it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s: {served.bucket_compiles} bucket "
        f"compiles warmed")
    profile_dir = Path(tempfile.mkdtemp(prefix="bench_trace_")) \
        if traced else None
    try:
        with CompileCount() as built, GcTime() as gct:
            rec, win = drive(served, plan, seed, seconds, profile_dir)
        done, failed = rec.results()
        stats = device.memory_stats() or {}
        mem_peak = int(stats.get("peak_bytes_in_use", 0))
        log(f"window: {rec.n} requests, {len(done)} answered, "
            f"{len(failed)} failed, {built.n} programs built in the window")
        log(f"window: longest gap between answers "
            f"{longest_gap(rec, done, seconds) * 1e3:.1f} ms, garbage "
            f"collector {gct.s * 1e3:.1f} ms in {gct.full} full passes")
        if len(rec.lateness):
            log("generator lateness ms: p50 "
                f"{np.percentile(rec.lateness, 50) * 1e3:.3f} p95 "
                f"{np.percentile(rec.lateness, 95) * 1e3:.3f} max "
                f"{rec.lateness.max() * 1e3:.3f}")
        result = {"attempted": int(rec.n), "failed": int(len(failed))}
        metrics = {}
        if traced:
            ctx = traced_context(served, plan, rec, done, win, profile_dir)
            for m in plan["per_layer"]:
                v = spec.reader(ROOT, m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": float(v),
                                          "unit": m["unit"]}
            result["breakdown"] = {"device_ops": trace.top_ops(
                ctx.trace["ops"]), "idle_gaps": label_gaps(ctx)}
            result["busy_s"] = ctx.trace["busy_s"]
            result["window_s"] = ctx.trace["window_s"]
        else:
            e2e = end_to_end(rec, done, seconds)
            e2e["setup_s"] = setup_s
            for m in plan["end_to_end"]:
                if m["name"] in e2e:
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
        picked = sample(rec, done, plan, seed)
        got = [rec.futures[i].result() for i in picked]
        served_imgs = rec.image[picked]
        params, calib, pool = served.params, served.calib, served.pool
        served.close()
        del served
        got = np.stack([np.asarray(g) for g in got])
        ref = plan["model"].logits(params, calib, pool[served_imgs],
                                   plan["config"])
        nums = numbers(got, ref)
    finally:
        if profile_dir is not None:
            shutil.rmtree(profile_dir, ignore_errors=True)
    ok, rows = verdict(nums, plan["config"]["check_limits"])
    result.update(metrics=metrics, mem_peak=mem_peak, correct=ok,
                  check=rows, checked=int(len(picked)))
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        plan = spec.cell(ROOT, args.workload)
    except (spec.SpecError, OSError, ValueError, KeyError) as e:
        log(f"error: {e}")
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"error: no TPU (JAX platform {dev.platform!r}); this "
            "benchmark measures the chip and never falls back to the CPU")
        return 1
    chips = int(plan["workload"]["chips"])
    if len(devices) < chips:
        log(f"error: the cell asks for {chips} chips, JAX sees "
            f"{len(devices)}")
        return 1
    try:
        peaks = roofline.peaks(ROOT, dev.device_kind)
    except KeyError as e:
        log(f"error: {e}")
        return 1
    res = run(plan, args.seed, args.seconds, bool(args.trace), device=dev,
              peaks=peaks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips, "memory_peak_bytes": res["mem_peak"]}
    if args.trace:
        device["busy_s"] = res["busy_s"]
        device["window_s"] = res["window_s"]
    for k, r in res["check"].items():
        log(f"check {k}: {r['value']!r} limit {r['limit']!r}")
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": device}
    if args.trace:
        line["breakdown"] = res["breakdown"]
    line["check"] = res["check"]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
