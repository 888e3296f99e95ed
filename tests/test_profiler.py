"""Measured-profiler + calibration tests: per-step profiling of a
compiled Program (coverage, fenced timings, command-stream cycle
attribution, roofline terms), the measured Chrome-trace track, the
ns-per-cycle fit (robust to a synthetic outlier, ArtifactStore
roundtrip), the scheduler/service calibration surface, the LM engine's
per-decode-step wall samples, the measured tile re-rank (never slower
than the analytic pick, memoized + persisted), the profiler's
zero-cost-off-path guarantee on the serving spine, and the
``--metrics-port`` HTTP endpoint of ``launch.serve``."""

import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.compiler import Graph, Node, compile_graph
from repro.compiler.artifact import ArtifactStore
from repro.core.bitserial import SerialSpec
from repro.core.codegen import CommandStream
from repro.core.mvu import MVUJob, OpKind
from repro.kernels import tuning
from repro.models.layers import QuantPolicy
from repro.obs import (Tracer, chrome_trace, fit, fit_samples,
                       format_calibration, format_profile,
                       profile_program, MetricsRegistry)
from repro.obs import calibrate
from repro.obs.profiler import (SERIAL_KINDS, _roofline_terms,
                                DEVICE_PEAKS, stream_cycles_by_layer)
from repro.serving import (ContinuousLMEngine, InferenceService,
                           ModelRegistry, SlotScheduler)


# --------------------------------------------------------------- fixtures

def small_graph(name="prof_cnn", seed=0):
    """Two serial layers (packed conv + gemm) plus host glue — the same
    shape family the serving benches use, small enough for fast tier."""
    rng = np.random.RandomState(seed)
    g = Graph(
        name, {"x": (None, 8, 8, 8)}, ["y"],
        [Node("c1", "conv2d", ["x", "c1.w"], "c1.y",
              {"stride": 1, "padding": 1}),
         Node("c1.relu", "relu", ["c1.y"], "c1.r"),
         Node("gap", "global_avg_pool", ["c1.r"], "pooled"),
         Node("fc", "gemm", ["pooled", "fc.w"], "y")],
        {"c1.w": (rng.randn(3, 3, 8, 16) * 0.2).astype(np.float32),
         "fc.w": (rng.randn(16, 10) * 0.2).astype(np.float32)})
    return g, rng.rand(4, 8, 8, 8)


@pytest.fixture(scope="module")
def prog():
    import jax.numpy as jnp
    g, calib = small_graph()
    return compile_graph(g, jnp.asarray(calib, jnp.float32),
                         policy=QuantPolicy(mode="serial", w_bits=2,
                                            a_bits=2, radix_bits=7),
                         backend="xla")


@pytest.fixture(scope="module")
def prof(prog):
    return profile_program(prog, batch=4, warmup=1, repeats=2)


def host_stream() -> CommandStream:
    jobs = [
        MVUJob(op=OpKind.GEMV, mvu=0, a_bits=2, w_bits=2,
               m_tiles=4, k_tiles=4, tag="l0"),
        MVUJob(op=OpKind.GEMV, mvu=1, a_bits=4, w_bits=4,
               m_tiles=2, k_tiles=2, tag="l1", depends_on=(0,)),
    ]
    return CommandStream(jobs=jobs, mode="pipelined")


def make_cal(ns=8.0):
    return calibrate.Calibration(
        backend="xla", interpret=False, ns_per_cycle={"*": ns},
        residuals={}, outliers=(), tolerance=1.0, n_samples=4,
        max_abs_residual=0.1)


# ------------------------------------------------------------- profiler

def test_profile_covers_every_step(prog, prof):
    assert [s.name for s in prof.steps] == [st.name for st in prog.steps]
    assert all(s.wall_ns > 0 for s in prof.steps)
    assert all(s.runs == 2 for s in prof.steps)
    assert prof.total_wall_ns == sum(s.wall_ns for s in prof.steps)
    assert prof.batch == 4 and prof.backend == "xla"
    serial = prof.serial_steps
    assert len(serial) == 2                 # packed conv + packed gemm
    for s in serial:
        assert s.kind in SERIAL_KINDS
        assert s.pred_cycles > 0
        # the test device is the CPU, which has no entry in the peak
        # table: the analytic terms are there, the bound is not measured
        assert s.bound is None and s.t_compute_s is None
        assert s.flops > 0 and s.bytes_hbm > 0
        assert s.precision == "W2A2"
    # host glue is measured but never priced by the cost model
    for s in prof.steps:
        if s.kind not in SERIAL_KINDS:
            assert s.pred_cycles == 0 and s.bound is None


def test_pred_cycles_match_command_stream(prog, prof):
    expected = stream_cycles_by_layer(prog, mode="pipelined")
    names = {st.name for st in prog.steps}
    assert set(expected) <= names           # XFER jobs fold onto layers
    for s in prof.steps:
        assert s.pred_cycles == expected.get(s.name, 0)
    assert sum(s.pred_cycles for s in prof.steps) == \
        sum(expected.values())


def test_profile_summary_and_groupings(prof):
    s = prof.summary()
    assert s["steps"] == len(prof.steps)
    assert s["total_wall_us"] == pytest.approx(
        prof.total_wall_ns / 1e3, rel=1e-3)
    assert s["device_kind"] == "cpu"
    assert s["compute_bound_layers"] is None
    assert s["memory_bound_layers"] is None
    assert s["total_flops"] > 0
    assert sum(prof.by_kind().values()) == pytest.approx(
        prof.total_wall_ns)
    assert sum(prof.by_precision().values()) == pytest.approx(
        prof.total_wall_ns)
    table = format_profile(prof)
    assert "c1" in table and "fc" in table and "wall_us" in table
    assert "not measured" in table


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_device_peaks_unknown_kind_gives_no_bound(prog, kind):
    """Only a device kind in the peak table is classified; any other
    device, the CPU included, gets no bound and never v5e's numbers."""
    assert DEVICE_PEAKS.get(kind) is None
    node = next(n for n in prog.cost_nodes if n.name == "c1")
    terms = _roofline_terms(node, 4, 2, 2, DEVICE_PEAKS.get(kind))
    assert terms["flops"] > 0 and "bound" not in terms


def test_device_peaks_v5e():
    peaks = DEVICE_PEAKS["TPU v5 lite"]
    assert peaks == {"bf16_flops": 197e12, "int8_ops": 393e12,
                     "hbm_bytes_s": 819e9}


def test_profile_metrics_registry_opt_in(prog):
    m = MetricsRegistry()
    profile_program(prog, batch=2, warmup=1, repeats=1, metrics=m)
    c = m.get("profiler_step_wall_ns_total")
    assert c.value(step="c1", kind="conv_packed") > 0
    assert m.get("profiler_runs_total").value() == 1


# ------------------------------------------------------- measured track

def test_measured_spans_third_trace_track(prof):
    tr = Tracer()
    ctx = tr.start_trace(t_ns=1_000)
    tr.span(ctx, "execute", 1_000, 2_000, cycle_start=0, cycle_end=10)
    doc = chrome_trace(tr, extra_spans=prof.spans())
    measured = [e for e in doc["traceEvents"] if e["pid"] == "measured"]
    assert len(measured) == len(prof.steps)
    # synthetic end-to-end timeline from 0, contiguous
    measured.sort(key=lambda e: e["ts"])
    assert measured[0]["ts"] == 0.0
    for a, b in zip(measured, measured[1:]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur"])
    for e in measured:
        assert e["args"]["domain"] == "measured"
        assert "pred_cycles" in e["args"] and "kind" in e["args"]
    # the wall domain is untouched: still rebased to its own first span
    wall = [e for e in doc["traceEvents"] if e["pid"] == "wall"]
    assert len(wall) == 1 and wall[0]["ts"] == 0.0
    assert "measured" in doc["otherData"]["domains"]


# ---------------------------------------------------------- calibration

def test_fit_from_profile(prof):
    cal = fit(prof)
    assert cal.backend == "xla" and not cal.interpret
    assert cal.ns_for() > 0
    assert cal.ns_for("conv_packed") > 0
    assert cal.ns_for("no_such_kind") == cal.ns_for()   # pooled fallback
    priced = {s.name for s in prof.steps if s.pred_cycles > 0}
    assert set(cal.residuals) == priced
    assert cal.n_samples == len(priced)
    assert set(cal.outliers) <= priced
    assert cal.predict_wall_seconds(1e6) == pytest.approx(
        1e6 * cal.ns_for() * 1e-9)
    assert cal.meta["graph"] == prof.graph_name
    text = format_calibration(cal)
    assert "ns/cycle" in text and "samples=" in text
    table = format_profile(prof, cal)
    assert "ns/cyc" in table and "resid" in table


def test_fit_samples_flags_synthetic_outlier():
    samples = [("l0", "gemm_packed", 1000, 8000.0),
               ("l1", "gemm_packed", 1000, 8200.0),
               ("l2", "gemm_packed", 1000, 7900.0),
               ("slow", "gemm_packed", 1000, 80000.0)]
    cal = fit_samples(samples, tolerance=1.0)
    assert cal.outliers == ("slow",)
    assert cal.residuals["slow"] > 1.0
    assert cal.max_abs_residual == pytest.approx(
        abs(cal.residuals["slow"]))
    # median-of-ratios: the outlier cannot drag the fit
    assert cal.ns_for("gemm_packed") == pytest.approx(8.1)
    assert "slow" in format_calibration(cal)
    # zero/negative samples are dropped, not fit
    assert fit_samples([("z", "k", 0, 100.0)]).n_samples == 0


def test_calibration_store_roundtrip(tmp_path):
    store = ArtifactStore(str(tmp_path))
    cal = fit_samples([("l0", "conv_packed", 500, 4000.0),
                       ("l1", "gemm_packed", 200, 1500.0)])
    key = calibrate.save(store, cal, "cnn@W2A2")
    assert key == calibrate.calibration_key("xla", "cnn@W2A2")
    loaded = calibrate.load(store, "xla", "cnn@W2A2")
    assert loaded == cal
    assert calibrate.load(store, "pallas_v2", "cnn@W2A2") is None
    assert calibrate.load(store, "xla", "missing") is None
    # a tuning record under the same key namespace is not a calibration
    store.tuning_put(calibrate.calibration_key("xla", "bogus"), "tile",
                     {"block_m": 8})
    assert calibrate.load(store, "xla", "bogus") is None


# -------------------------------------------------- scheduler / service

def test_scheduler_est_seconds_uses_calibration():
    sched = SlotScheduler()
    cs = host_stream()
    adm = sched.admit("m@W2A2", 1, stream=cs)
    assert adm.est_seconds == pytest.approx(
        adm.est_cycles / sched.controller.freq_hz)
    m = sched.metrics()["calibration"]
    assert m["source"] == "nominal"
    assert m["ns_per_cycle"] == pytest.approx(
        1e9 / sched.controller.freq_hz)

    sched.set_calibration(make_cal(ns=8.0))
    adm2 = sched.admit("m@W2A2", 1, stream=cs)
    assert adm2.est_seconds == pytest.approx(adm2.est_cycles * 8.0e-9)
    sched.complete(adm2, adm2.est_cycles * 8.0e-9)
    m = sched.metrics()["calibration"]
    assert m["source"] == "fitted" and m["ns_per_cycle"] == 8.0
    assert m["observed_ns_per_cycle"] == pytest.approx(8.0, rel=1e-3)
    assert m["predicted_finish_seconds"] == round(
        sched.virtual_cycles * 8.0e-9, 6)
    sched.set_calibration(None)             # revert to the nominal clock
    assert sched.metrics()["calibration"]["source"] == "nominal"


def test_service_calibration_passthrough():
    reg = ModelRegistry()
    key = reg.register_callable("eng", lambda reqs: [r * 2 for r in reqs],
                                stream=host_stream())
    svc = InferenceService(reg, max_wait_s=0.0)
    svc.set_calibration(make_cal(ns=4.0))
    with svc:
        futs = svc.submit_many(key, [1.0, 2.0])
        svc.drain()
        assert [f.result() for f in futs] == [2.0, 4.0]
    m = svc.metrics()["scheduler"]["calibration"]
    assert m["source"] == "fitted" and m["ns_per_cycle"] == 4.0
    assert m["observed_ns_per_cycle"] is not None


# -------------------------------------------------------- LM wall samples

def test_lm_engine_wall_samples_feed_calibration():
    from repro.models.transformer import ModelConfig

    class R:
        def __init__(self, prompt, n):
            self.prompt = prompt
            self.max_new_tokens = n
            self.out_tokens = None

    cfg = ModelConfig(
        name="cal-test", family="dense", n_layers=1, d_model=32,
        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64,
        dtype="float32", remat=False,
        policy=QuantPolicy(mode="qat", w_bits=4, a_bits=8))
    eng = ContinuousLMEngine(cfg, batch_slots=2, max_len=16, seed=0)
    eng.warmup()
    assert eng.wall_samples() == []         # warmup resets the samples
    eng.bind_runtime(SlotScheduler(), "lm@W4A8")
    eng.serve([R(np.zeros(2, np.int32), 8)])
    samples = eng.wall_samples()
    assert samples and all(c > 0 and w > 0 for c, w in samples)
    cal = fit_samples([("decode_step", "lm_decode", c, w)
                       for c, w in samples])
    assert cal.ns_for("lm_decode") > 0
    em = eng.engine_metrics()
    assert em["step_wall_seconds"] > 0
    assert em["observed_ns_per_cycle"] is not None


# ---------------------------------------------------- measured re-rank

def test_measured_rerank_never_slower_and_persists(tmp_path):
    spec = SerialSpec(8, 4, True, True, 7)
    m, k, n = 64, 256, 128
    old = tuning.set_persistent_store(ArtifactStore(str(tmp_path)))
    try:
        tuning.clear_cache()
        analytic = tuning.choose_tile(m, k, n, spec)
        short = tuning._enumerate_tiles(m, k, n, spec, out_bits=None,
                                        tpu=tuning.TPUConfig())[:3]
        assert short[0] == analytic and len(short) == 3
        # adversarial timings: the analytically *worst* shortlisted tile
        # is the measured fastest
        t = {c: float(3 - i) for i, c in enumerate(short)}
        calls = []

        def measure(c):
            calls.append(c)
            return t[c]

        chosen = tuning.choose_tile_measured(m, k, n, spec,
                                             measure=measure, top_k=3)
        assert chosen == short[-1]
        assert t[chosen] <= t[analytic]     # never slower under measure
        assert len(calls) == 3
        # L1 memoized: no re-measurement
        again = tuning.choose_tile_measured(m, k, n, spec,
                                            measure=measure, top_k=3)
        assert again == chosen and len(calls) == 3
        # L2 persisted: a cold process (cleared L1) replays the decision
        # without ever calling measure
        tuning.clear_cache()

        def boom(c):
            raise AssertionError("persisted decision must not re-measure")

        warm = tuning.choose_tile_measured(m, k, n, spec, measure=boom,
                                           top_k=3)
        assert warm == chosen
    finally:
        tuning.set_persistent_store(old)
        tuning.clear_cache()


def test_measured_rerank_tie_keeps_analytic():
    tuning.clear_cache()
    spec = SerialSpec(2, 2, True, True, 7)
    analytic = tuning.choose_tile(32, 64, 64, spec)
    chosen = tuning.choose_tile_measured(32, 64, 64, spec,
                                         measure=lambda c: 1.0, top_k=4)
    assert chosen == analytic               # strict < keeps rank 1 on ties
    tuning.clear_cache()


def test_measured_rerank_conv():
    tuning.clear_cache()
    spec = SerialSpec(2, 2, True, True, 7)
    kw = dict(fh=3, fw=3, stride=1, padding=1, spec=spec)
    analytic = tuning.choose_conv_tile(4, 8, 8, 8, 16, **kw)
    seen = []

    def measure(c):
        seen.append(c)
        return 1.0                          # all tie: analytic must win
    chosen = tuning.choose_conv_tile_measured(4, 8, 8, 8, 16,
                                              measure=measure, **kw)
    assert chosen == analytic and seen
    tuning.clear_cache()


# ------------------------------------------------------ off-path zeroes

def test_serving_path_emits_no_measured_spans():
    """The profiler is opt-in: a traced serving run produces wall and
    virtual-cycle events only — the measured track exists solely when a
    profile's spans are passed in explicitly."""
    reg = ModelRegistry()
    key = reg.register_callable("eng", lambda reqs: reqs,
                                stream=host_stream())
    svc = InferenceService(reg, max_wait_s=0.0)
    with svc:
        svc.submit_many(key, [1.0, 2.0, 3.0])
        svc.drain()
    doc = chrome_trace(svc.tracer)
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert "measured" not in pids
    assert pids == {"wall", "virtual-cycles"}


# ------------------------------------------- launch.serve --metrics-port

def test_obs_session_metrics_port_scrape_and_shutdown():
    from repro.launch.serve import _ObsSession
    reg = ModelRegistry()
    key = reg.register_callable("eng", lambda reqs: [r + 1 for r in reqs],
                                stream=host_stream())
    svc = InferenceService(reg, max_wait_s=0.0)
    with svc:
        obs = _ObsSession(svc, metrics_port=0)      # port 0: auto-assign
        port = obs._http.server.server_address[1]
        assert port != 0
        fut = svc.submit(key, 1.0)
        svc.drain()
        assert fut.result() == 2.0
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5)
        assert body.status == 200
        assert body.headers["Content-Type"].startswith("text/plain")
        text = body.read().decode()
        assert "# TYPE repro_service_completed_total counter" in text
        assert "repro_service_completed_total 1" in text
        assert "repro_scheduler_admitted_requests_total 1" in text
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope",
                                   timeout=5)
        obs.close()                         # clean shutdown
        obs._http.join(timeout=5)
        assert not obs._http.is_alive()


def test_obs_session_without_port_starts_no_server():
    reg = ModelRegistry()
    from repro.launch.serve import _ObsSession
    svc = InferenceService(reg, max_wait_s=0.0)
    obs = _ObsSession(svc)
    assert obs._http is None
    obs.close()                             # no-op, must not raise
