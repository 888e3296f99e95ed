"""The serving worker's batch-scoped spans: one set per micro-batch
(``form``, ``schedule``, ``execute`` with its ``stack``, ``launch``,
``transfer``, ``device`` and ``readback`` parts, ``finalize``, ``emit``),
each request's ``queue`` span linked to them by ``batch_id``; the
per-batch ``serve_batch`` profiler step, on the worker and on the finalize
pool; the runner's padded-rows counter; and the Program step names in the
lowered computation."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_with_devices
from repro.compiler import executor
from repro.obs import Tracer, chrome_trace, trace_summary
from repro.obs.export import PHASES
from repro.serving import InferenceService, ModelRegistry
from test_serving import CALIB, serial_policy, tiny_cnn_graph

BATCH_PHASES = ("form", "schedule", "execute", "stack", "launch",
                "transfer", "device", "readback", "finalize", "emit")


@pytest.fixture(scope="module")
def tiny():
    reg = ModelRegistry(backend="xla")
    key = reg.register_graph("tiny", tiny_cnn_graph(), CALIB,
                             serial_policy(2, 2))
    return reg, key


def _images(n, seed=0):
    return np.random.RandomState(seed).rand(n, 8, 8, 8).astype(np.float32)


def _serve(reg, key, xs, **kw):
    """Serve ``xs`` as one batch (``max_batch`` = len(xs), a long
    coalescing window); returns the service and the answers."""
    svc = InferenceService(reg, max_batch=len(xs), max_wait_s=5.0, **kw)
    with svc:
        futs = svc.submit_many(key, list(xs))
        svc.drain(timeout=120)
    return svc, np.stack([np.asarray(f.result(timeout=0)) for f in futs])


def _batch_spans(tracer):
    return [s for s in tracer.spans()
            if s.trace_id == 0 and "batch_id" in s.args]


def test_one_set_of_batch_spans_per_batch(tiny):
    reg, key = tiny
    svc, _ = _serve(reg, key, _images(8))
    spans = _batch_spans(svc.tracer)
    names = sorted(s.name for s in spans)
    assert names == sorted(BATCH_PHASES)
    by = {s.name: s for s in spans}
    bid = by["execute"].args["batch_id"]
    assert all(s.args["batch_id"] == bid for s in spans)
    assert by["execute"].args["requests"] == 8
    assert by["execute"].args["padded"] == 0
    ex = by["execute"]
    for name in ("stack", "launch", "device", "readback"):
        s = by[name]
        assert ex.t0_ns <= s.t0_ns <= s.t1_ns <= ex.t1_ns, name
    # the parts follow one another: the put starts the launch, the
    # device starts when the input is ready, the read-back when the
    # output is
    assert by["stack"].t1_ns == by["launch"].t0_ns == by["transfer"].t0_ns
    assert by["launch"].t1_ns <= by["transfer"].t1_ns == by["device"].t0_ns
    assert by["device"].t1_ns == by["readback"].t0_ns
    assert by["readback"].t1_ns == ex.t1_ns == by["finalize"].t0_ns
    # the spans' own emission closes the batch's time on the host
    assert by["finalize"].t1_ns == by["emit"].t0_ns <= by["emit"].t1_ns
    assert by["form"].t1_ns <= by["schedule"].t0_ns
    assert by["schedule"].t1_ns == ex.t0_ns
    # every request keeps its own queue span, linked to the batch
    queue = [s for s in svc.tracer.spans() if s.name == "queue"]
    assert len(queue) == 8 and {s.trace_id for s in queue} == set(
        range(1, 9))
    for s in queue:
        assert s.args["batch_id"] == bid and s.args["batch"] == 8
        assert s.t1_ns == by["form"].t1_ns
    # nothing else is written per request
    assert {s.name for s in svc.tracer.spans() if s.trace_id} == {"queue"}


def test_summary_joins_requests_to_their_batch(tiny):
    reg, key = tiny
    svc, _ = _serve(reg, key, _images(4))
    rows = trace_summary(chrome_trace(svc.tracer))
    assert len(rows) == 4
    ex = next(s for s in _batch_spans(svc.tracer) if s.name == "execute")
    for r in rows:
        assert set(r["phases"]) >= set(PHASES) | set(BATCH_PHASES)
        assert r["phases"]["execute"] == pytest.approx(ex.wall_us)
        assert r["cycles"] > 0


def test_sampled_out_batch_and_disabled_tracer(tiny):
    reg, key = tiny
    xs = _images(4, seed=3)
    on, want = _serve(reg, key, xs)
    off, got = _serve(reg, key, xs, tracer=Tracer(enabled=False))
    np.testing.assert_array_equal(got, want)
    assert off.tracer.spans() == []
    # every 100th request sampled: none of these four, so no batch or
    # request spans (the scheduler's occupancy rows are not sampled)
    few, got = _serve(reg, key, xs, trace_sample_every=100)
    np.testing.assert_array_equal(got, want)
    assert _batch_spans(few.tracer) == []
    assert not any(s.trace_id for s in few.tracer.spans())
    assert _batch_spans(on.tracer)


def test_padded_rows_on_the_runner_and_the_batch_span(tiny):
    reg, key = tiny
    prog = reg.program(key)
    runner = executor.make_bucketed_runner(prog, max_batch=8)
    xs = _images(5, seed=1)
    y, x_dev, padded = runner.launch(xs)
    assert padded == 3 and x_dev.shape == (5, 8, 8, 8)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(runner(xs)))
    padded_rows = runner.metrics_registry.counter("runner_padded_rows_total")
    assert padded_rows.value() == 6
    runner(_images(8))
    assert padded_rows.value() == 6
    # a batch of 5 served under max_batch 8 (bucket 8) after its window
    svc = InferenceService(reg, max_batch=8, max_wait_s=0.05)
    with svc:
        futs = svc.submit_many(key, list(xs))
        svc.drain(timeout=120)
    np.testing.assert_array_equal(
        np.stack([f.result(timeout=0) for f in futs]), np.asarray(y))
    ex = [s for s in _batch_spans(svc.tracer) if s.name == "execute"]
    assert [(s.args["requests"], s.args["padded"]) for s in ex] == [(5, 3)]


def test_serve_batch_steps_in_a_profiler_trace(tiny, tmp_path):
    """Each batch is one ``serve_batch`` step of a jax.profiler trace,
    under its batch id, and starts where its ``form`` span ends: one
    offset between the two clocks for every batch."""
    from jax.profiler import ProfileData
    reg, key = tiny
    svc = InferenceService(reg, max_batch=4, max_wait_s=5.0)
    with svc:
        svc.warmup(key)
        jax.profiler.start_trace(str(tmp_path))
        try:
            for seed in range(3):
                svc.submit_many(key, list(_images(4, seed)))
                svc.drain(timeout=120)
        finally:
            jax.profiler.stop_trace()
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    steps = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "serve_batch":
                    steps[dict(e.stats)["step_num"]] = e.start_ns
    formed = {s.args["batch_id"]: s.t1_ns for s in _batch_spans(svc.tracer)
              if s.name == "form"}
    assert sorted(steps) == sorted(formed) and len(steps) == 3
    offsets = [formed[b] - steps[b] for b in steps]
    assert max(offsets) - min(offsets) < 5e6        # ns


def test_serve_batch_steps_on_the_finalize_pool():
    """With two banks the batch's rows are waited for on the finalize
    pool: its ``serve_batch`` step starts on the worker when the batch is
    formed and ends on the pool thread when ``finalize`` ends."""
    run_with_devices(n=2, body="""
        import glob, tempfile
        from jax.profiler import ProfileData
        from repro.compiler.bench_graphs import tiny_mixed_cnn
        from repro.models.layers import QuantPolicy
        from repro.serving import InferenceService, ModelRegistry

        graph, calib = tiny_mixed_cnn()[:2]
        reg = ModelRegistry(backend="xla")
        key = reg.register_graph("tiny", graph, calib, QuantPolicy(
            mode="serial", w_bits=2, a_bits=2, radix_bits=7))
        svc = InferenceService(reg, max_batch=4, max_wait_s=5.0, n_banks=2,
                               placement="banked")
        rng = np.random.RandomState(0)
        out = tempfile.mkdtemp()
        with svc:
            svc.warmup(key)
            jax.profiler.start_trace(out)
            try:
                for _ in range(3):
                    svc.submit_many(key, list(rng.rand(4, 8, 8, 8).astype(
                        np.float32)))
                    svc.drain(timeout=120)
            finally:
                jax.profiler.stop_trace()
        path = glob.glob(out + "/**/*.xplane.pb", recursive=True)[0]
        steps = {}
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name == "serve_batch":
                        steps[dict(e.stats)["step_num"]] = (
                            e.start_ns, e.start_ns + e.duration_ns)
        spans = {(s.name, s.args["batch_id"]): s for s in svc.tracer.spans()
                 if s.trace_id == 0 and "batch_id" in s.args}
        bids = sorted(b for n, b in spans if n == "form")
        assert sorted(steps) == bids and len(bids) == 3, (steps, bids)
        for b in bids:
            fin = spans["finalize", b]
            assert fin.track.startswith("serving-finalize"), fin.track
            t0, t1 = steps[b]
            off = spans["form", b].t1_ns - t0
            # the step ends when finalize does, on the trace's clock
            assert abs(fin.t1_ns - (t1 + off)) < 5e6, (b, fin, t1 + off)
    """)


def test_step_names_in_the_lowered_program(tiny):
    reg, key = tiny
    prog = reg.program(key)
    text = jax.jit(executor.make_runner(prog)).lower(
        prog.params, jnp.zeros((2, 8, 8, 8))).as_text(debug_info=True)
    for st in prog.steps:
        assert f"jit(run)/{st.name}/" in text, st.name
