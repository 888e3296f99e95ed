"""Observability tests: the metrics registry, the HPM counter file (and
its core invariant — per-hart busy+xfer cycles equal
``SimReport.per_mvu_busy`` exactly), SimReport edge cases (hart_free
carry-over, cycle_scale x XFER, utilization with idle harts), the tracer
(sampling, ring bound, two clock domains), the exporters (Perfetto JSON,
Prometheus text, trace summary, /metrics server), and the serving-spine
integrations: an end-to-end traced request through InferenceService, the
BankFailure requeue path, and the per-decode-step straggler detector."""

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core.codegen import CommandStream
from repro.core.mvu import MVUJob, OpKind
from repro.obs import (MetricsRegistry, Tracer, HPMCounterFile,
                       chrome_trace, write_chrome_trace, prometheus_text,
                       trace_summary, format_trace_summary,
                       start_metrics_server)
from repro.obs.export import PHASES
from repro.runtime.controller import BarrelController
from repro.runtime.fault_tolerance import BankFailure
from repro.serving import InferenceService, ModelRegistry


# ------------------------------------------------------------ shared stream

def mixed_stream() -> CommandStream:
    """Two precisions, two harts, an XFER hop and a HOST tail — small but
    exercises every counter class the HPM file keeps."""
    jobs = [
        MVUJob(op=OpKind.GEMV, mvu=0, a_bits=2, w_bits=2,
               m_tiles=5, k_tiles=5, tag="l0"),
        MVUJob(op=OpKind.XFER, mvu=0, tag="x01", depends_on=(0,)),
        MVUJob(op=OpKind.GEMV, mvu=1, a_bits=8, w_bits=4,
               m_tiles=3, k_tiles=3, tag="l1", depends_on=(1,)),
        MVUJob(op=OpKind.HOST, mvu=-1, tag="head", depends_on=(2,)),
    ]
    return CommandStream(jobs=jobs, mode="pipelined")


# ------------------------------------------------------- metrics registry

def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2)
    c.inc(bank="0")
    c.inc(3, bank="1")
    assert c.value() == 3
    assert c.value(bank="0") == 1
    assert c.value(bank="1") == 3
    # label order is canonicalized
    c.inc(a="x", b="y")
    c.inc(b="y", a="x")
    assert c.value(b="y", a="x") == 2
    # idempotent family registration: same object back
    assert reg.counter("reqs_total") is c


def test_registry_disabled_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c")
    g = reg.gauge("g")
    h = reg.histogram("h")
    c.inc()
    g.set(5)
    h.observe(0.1)
    assert c.value() == 0 and g.value() == 0 and h.value() == 0
    reg.enable()
    c.inc()
    assert c.value() == 1
    reg.disable()
    c.inc()
    assert c.value() == 1


def test_gauge_set_max():
    g = MetricsRegistry().gauge("peak")
    g.set_max(3)
    g.set_max(7)
    g.set_max(5)
    assert g.value() == 7
    g.set(2)
    assert g.value() == 2


def test_histogram_buckets_sum_quantile():
    h = MetricsRegistry().histogram("lat", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 2.0):
        h.observe(v)
    assert h.value() == 5                       # observation count
    assert h.sum() == pytest.approx(2.605)
    assert h.bucket_counts() == [1, 2, 1, 1]    # incl. +Inf overflow
    assert h.quantile(0.5) == 0.1
    assert h.quantile(1.0) == float("inf")


def test_family_type_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


# --------------------------------------------------------- HPM counter file

def test_hpm_invariant_sums_to_per_mvu_busy():
    """The acceptance-criteria invariant: per-hart busy+xfer == the
    controller's per_mvu_busy, exactly, on a mixed stream."""
    ctrl = BarrelController(harts=2)
    rep = ctrl.simulate(mixed_stream())
    assert rep.hpm is not None
    assert rep.hpm.total == rep.per_mvu_busy
    # class split: hart 0 ran compute (2x2x25=100) + the 64-cycle XFER
    assert rep.hpm.busy == [100, 8 * 4 * 9]
    assert rep.hpm.xfer == [64, 0]
    assert rep.per_mvu_busy == [164, 288]
    # issue overhead per issued job (HOST never issues)
    assert rep.hpm.issue == [2 * ctrl.issue_overhead, ctrl.issue_overhead]
    # attribution: tags count busy+xfer; precisions count compute only
    assert rep.hpm.per_tag == {"l0": 100, "x01": 64, "l1": 288}
    assert rep.hpm.per_precision == {"W2A2": 100, "W4A8": 288}
    assert rep.hpm.jobs == {"gemv": 2, "xfer": 1, "host": 1}
    # hart 1 stalled waiting for the XFER chain, never the reverse
    assert rep.hpm.stall[1] > 0 and rep.hpm.stall[0] == 0


def test_hpm_counter_file_merges_and_mirrors():
    ctrl = BarrelController(harts=2)
    rep = ctrl.simulate(mixed_stream())
    m = MetricsRegistry()
    f = HPMCounterFile(2, metrics=m, bank=3)
    f.record(rep, None)
    f.record(rep, None)
    snap = f.snapshot()
    assert snap["records"] == 2 and snap["bank"] == 3
    assert snap["busy"] == [2 * b for b in rep.hpm.busy]
    assert snap["per_tag"]["l1"] == 2 * rep.hpm.per_tag["l1"]
    # registry mirror carries the same totals, labelled
    c = m.get("hpm_hart_cycles_total")
    assert c.value(bank="3", hart="0", cls="busy") == snap["busy"][0]
    assert c.value(bank="3", hart="0", cls="xfer") == snap["xfer"][0]
    assert (m.get("hpm_precision_cycles_total")
            .value(bank="3", precision="W4A8") == snap["per_precision"]["W4A8"])
    assert f.top_tags(1) == [("l1", snap["per_tag"]["l1"])]


def test_hpm_record_requires_counters():
    class NoHPM:
        hpm = None
    with pytest.raises(ValueError, match="no hpm"):
        HPMCounterFile(2).record(NoHPM(), None)


def test_execute_path_counts_jobs():
    ctrl = BarrelController(harts=2)
    ctrl.register(OpKind.GEMV, lambda job, env: None)
    ctrl.register(OpKind.XFER, lambda job, env: None)
    f = HPMCounterFile(2)
    ctrl.execute(mixed_stream(), {}, hpm=f)
    snap = f.snapshot()
    assert snap["jobs"] == {"gemv": 2, "xfer": 1, "host": 1}
    # modelled cycles attributed on dispatch (XFER has no cycle model here)
    assert snap["busy"] == [100, 288]
    assert snap["per_precision"] == {"W2A2": 100, "W4A8": 288}


# ------------------------------------------------------ SimReport edge cases

def test_simulate_hart_free_carries_over():
    """Consecutive simulate calls seeded with the previous hart_free share
    the fabric: the second stream starts no earlier than the first freed."""
    ctrl = BarrelController(harts=2)
    cs = mixed_stream()
    r1 = ctrl.simulate(cs)
    r2 = ctrl.simulate(cs, hart_free=r1.hart_free)
    fresh = ctrl.simulate(cs)
    for i, j in enumerate(cs.jobs):
        if j.op == OpKind.HOST:
            continue
        h = j.mvu % 2
        assert r2.per_job_start[i] >= r1.hart_free[h]
        assert r2.per_job_start[i] >= fresh.per_job_start[i]
    # busy work is schedule-invariant; the seeded run shifts, not grows
    assert r2.per_mvu_busy == fresh.per_mvu_busy
    assert r2.hpm.total == r2.per_mvu_busy
    # the caller's seed list must not be mutated
    seed = list(r1.hart_free)
    ctrl.simulate(cs, hart_free=seed)
    assert seed == r1.hart_free
    with pytest.raises(ValueError, match="hart_free"):
        ctrl.simulate(cs, hart_free=[0])


def test_simulate_cycle_scale_scales_xfer_too():
    ctrl = BarrelController(harts=2)
    cs = CommandStream(jobs=[
        MVUJob(op=OpKind.GEMV, mvu=0, a_bits=2, w_bits=2, m_tiles=2,
               k_tiles=2, tag="g"),
        MVUJob(op=OpKind.XFER, mvu=1, tag="x"),
    ], mode="pipelined")
    r1 = ctrl.simulate(cs, xfer_cycles_per_job=10, cycle_scale=1)
    r3 = ctrl.simulate(cs, xfer_cycles_per_job=10, cycle_scale=3)
    assert r1.hpm.busy[0] == 16 and r1.hpm.xfer[1] == 10
    assert r3.hpm.busy[0] == 48 and r3.hpm.xfer[1] == 30
    assert r3.per_mvu_busy == [48, 30]
    assert r3.hpm.total == r3.per_mvu_busy
    # issue overhead is per-job fixed cost: cycle_scale must not touch it
    assert r3.hpm.issue == r1.hpm.issue


def test_utilization_all_idle_and_partial():
    ctrl = BarrelController(harts=4)
    host_only = CommandStream(jobs=[
        MVUJob(op=OpKind.HOST, mvu=-1, tag="h0"),
        MVUJob(op=OpKind.HOST, mvu=-1, tag="h1", depends_on=(0,)),
    ], mode="pipelined")
    rep = ctrl.simulate(host_only)
    assert rep.makespan_cycles == 0
    assert rep.per_mvu_busy == [0, 0, 0, 0]
    assert rep.utilization == 0.0           # no 0/0, no NaN
    assert rep.hpm.total == rep.per_mvu_busy
    # partial idle: only hart 0 works; idle harts don't dilute utilization
    one = CommandStream(jobs=[
        MVUJob(op=OpKind.GEMV, mvu=0, a_bits=2, w_bits=2, m_tiles=2,
               k_tiles=2, tag="g")], mode="pipelined")
    rep = ctrl.simulate(one)
    busy = rep.per_mvu_busy[0]
    assert busy > 0 and rep.per_mvu_busy[1:] == [0, 0, 0]
    assert rep.utilization == busy / rep.makespan_cycles


# ----------------------------------------------------------------- tracer

def test_tracer_sampling_every_nth():
    tr = Tracer(sample_every=3)
    ctxs = [tr.start_trace() for _ in range(9)]
    assert sum(c.sampled for c in ctxs) == 3
    for c in ctxs:
        tr.span(c, "phase", 0, 10)
    assert len(tr.spans()) == 3
    assert tr.stats()["started"] == 9 and tr.stats()["sampled"] == 3
    assert tr.stats()["dropped_spans"] == 6


def test_tracer_ring_is_bounded():
    tr = Tracer(capacity=8)
    ctx = tr.start_trace()
    for i in range(20):
        tr.span(ctx, f"s{i}", i, i + 1)
    spans = tr.spans()
    assert len(spans) == 8
    assert spans[0].name == "s12"           # oldest fell off


def test_tracer_disabled_null_context():
    tr = Tracer(enabled=False)
    ctx = tr.start_trace()
    assert ctx.trace_id == 0 and not ctx.sampled
    tr.span(ctx, "x", 0, 1)
    tr.cycle_span("y", 0, 10, track="bank0/hart0")
    assert tr.spans() == []
    assert tr.stats()["dropped_spans"] == 2


# --------------------------------------------------------------- exporters

def test_chrome_trace_two_clock_domains():
    tr = Tracer()
    ctx = tr.start_trace(t_ns=1_000_000)
    tr.span(ctx, "execute", 1_000_000, 2_000_000,
            cycle_start=100, cycle_end=600, track="worker")
    tr.cycle_span("tiny@W2A2", 100, 600, track="bank0/hart1", batch=4)
    doc = chrome_trace(tr)
    assert doc["displayTimeUnit"] == "ms"
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert pids == {"wall", "virtual-cycles"}
    wall = [e for e in doc["traceEvents"] if e["pid"] == "wall"]
    assert wall[0]["ts"] == 0.0 and wall[0]["dur"] == 1000.0  # rebased µs
    assert wall[0]["args"]["cycles"] == 500
    cyc = {e["tid"]: e for e in doc["traceEvents"]
           if e["pid"] == "virtual-cycles"}
    # the request span gets its own cycle row; the occupancy span keeps
    # its bank/hart track
    assert f"req-{ctx.trace_id}" in cyc and "bank0/hart1" in cyc
    assert cyc["bank0/hart1"]["ts"] == 100.0
    assert cyc["bank0/hart1"]["dur"] == 500.0


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests").inc(3, bank="0")
    reg.gauge("depth").set(7)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = prometheus_text(reg)
    assert "# HELP repro_reqs_total requests" in text
    assert "# TYPE repro_reqs_total counter" in text
    assert 'repro_reqs_total{bank="0"} 3' in text
    assert "repro_depth 7" in text
    # cumulative buckets + +Inf + sum + count
    assert 'repro_lat_seconds_bucket{le="0.1"} 1' in text
    assert 'repro_lat_seconds_bucket{le="1"} 2' in text
    assert 'repro_lat_seconds_bucket{le="+Inf"} 3' in text
    assert "repro_lat_seconds_count 3" in text
    # duplicate families across registries render one header
    reg2 = MetricsRegistry()
    reg2.counter("reqs_total", "requests").inc(9, bank="1")
    both = prometheus_text([reg, reg2])
    assert both.count("# TYPE repro_reqs_total counter") == 1
    assert 'repro_reqs_total{bank="1"} 9' in both


def test_trace_summary_ranks_and_formats():
    tr = Tracer()
    us = 1000                                   # 1 µs in ns
    for total_q in (5, 50):                     # trace 2 is the slow one
        ctx = tr.start_trace(t_ns=0)
        t = 0
        for name, dur in zip(PHASES, (total_q, 2, 3, 1)):
            tr.span(ctx, name, t * us, (t + dur) * us,
                    cycle_start=0, cycle_end=100)
            t += dur
    rows = trace_summary(chrome_trace(tr), top_k=10)
    assert [r["trace_id"] for r in rows] == [2, 1]
    assert rows[0]["phases"]["queue"] == pytest.approx(50.0)   # µs
    assert rows[0]["total_us"] == pytest.approx(56.0)
    table = format_trace_summary(rows)
    assert "queue_ms" in table and "cycles" in table
    assert format_trace_summary([]) == "(no request spans in trace)"


def test_metrics_http_server():
    reg = MetricsRegistry()
    reg.counter("up_total").inc(5)
    t = start_metrics_server(0, lambda: [reg])
    port = t.server.server_address[1]
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "repro_up_total 5" in body
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5)
    finally:
        t.server.shutdown()


# ------------------------------------------------- serving spine end-to-end

def test_service_trace_end_to_end_perfetto(tmp_path):
    """One request through InferenceService produces a Perfetto-loadable
    trace with queue/schedule/execute/finalize spans carrying both wall-ns
    and virtual-cycle timings, per-bank hart occupancy rows, and HPM
    counters that reconcile with the scheduler's busy clock."""
    reg = ModelRegistry()
    key = reg.register_callable("eng", lambda reqs: [r * 2 for r in reqs],
                                stream=mixed_stream())
    svc = InferenceService(reg, max_wait_s=0.0)
    with svc:
        futs = svc.submit_many(key, [float(i) for i in range(4)])
        svc.drain()
        assert [f.result() for f in futs] == [0.0, 2.0, 4.0, 6.0]
    path = write_chrome_trace(svc.tracer, str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())      # Perfetto-loadable = valid JSON
    ev = doc["traceEvents"]
    wall_names = {e["name"] for e in ev if e["pid"] == "wall"}
    assert set(PHASES) <= wall_names
    # schedule+execute carry the booked cycle window -> cycle-domain rows
    # (recorded once per micro-batch, on the batch's own row)
    req_cyc = [e for e in ev if e["pid"] == "virtual-cycles"
               and str(e["tid"]).startswith(("req-", "batch-"))]
    assert {e["name"] for e in req_cyc} >= {"schedule", "execute"}
    assert all(e["dur"] > 0 for e in req_cyc)
    # the scheduler's per-hart occupancy rows (both harts of mixed_stream)
    tracks = {e["tid"] for e in ev if e["pid"] == "virtual-cycles"}
    assert {"bank0/hart0", "bank0/hart1"} <= tracks
    # every sampled request has a full 4-phase trace
    rows = trace_summary(doc)
    assert len(rows) == 4
    for r in rows:
        assert set(r["phases"]) >= set(PHASES)
        assert r["cycles"] > 0
    # HPM reconciliation: committed counter file == scheduler busy clock
    hpm = svc.scheduler.hpm()[0]
    total = [b + x for b, x in zip(hpm["busy"], hpm["xfer"])]
    assert total == svc.scheduler._busy[0] and any(total)
    assert svc.scheduler.metrics()["hpm"][0]["per_precision"] == \
        hpm["per_precision"]
    # the spine shares one registry; engine/bucket registries would append
    regs = svc.registries()
    assert regs[0] is svc.metrics_registry
    assert svc.batcher.metrics_registry is svc.metrics_registry
    text = prometheus_text(regs)
    assert "repro_service_completed_total 4" in text
    assert "repro_hpm_hart_cycles_total" in text


def test_service_requeues_on_bank_failure():
    """Satellite: a transient BankFailure requeues the micro-batch through
    the batcher (bounded by max_retries) and counts requeues_total."""
    calls = {"n": 0}

    def flaky(reqs):
        calls["n"] += 1
        if calls["n"] == 1:
            raise BankFailure("bank 0 dropped off the mesh", bank=0)
        return [r + 1 for r in reqs]

    reg = ModelRegistry()
    key = reg.register_callable("flaky", flaky)
    svc = InferenceService(reg, max_wait_s=0.0, max_retries=1)
    with svc:
        # one request keeps the failing batch's composition deterministic
        fut = svc.submit(key, 1.0)
        svc.drain()
        assert fut.result() == 2.0
    assert svc.requeues == 1 and svc.failed == 0
    m = svc.metrics()
    assert m["requeues"] == 1 and m["completed"] == 1
    assert svc.metrics_registry.get("service_requeues_total").value() == 1


def test_service_bank_failure_exhausts_retries():
    def always_down(reqs):
        raise BankFailure("bank 1 is gone", bank=1)

    reg = ModelRegistry()
    key = reg.register_callable("down", always_down)
    svc = InferenceService(reg, max_wait_s=0.0, max_retries=1)
    with svc:
        fut = svc.submit(key, 1.0)
        svc.drain()
    with pytest.raises(BankFailure) as ei:
        fut.result()
    assert ei.value.bank == 1
    assert svc.requeues == 1 and svc.failed == 1


# ------------------------------------------- LM engine straggler detection

@pytest.fixture(scope="module")
def lm_engine():
    from repro.models.layers import QuantPolicy
    from repro.models.transformer import ModelConfig
    from repro.serving import ContinuousLMEngine
    cfg = ModelConfig(
        name="obs-test", family="dense", n_layers=1, d_model=32, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64, dtype="float32",
        remat=False, policy=QuantPolicy(mode="qat", w_bits=4, a_bits=8))
    eng = ContinuousLMEngine(cfg, batch_slots=2, max_len=16, seed=0)
    eng.warmup()
    return eng


def test_lm_engine_flags_slow_decode_step(lm_engine):
    """Satellite regression: one synthetically slow arena step must be
    flagged by the engine's per-step MAD detector (not averaged away)."""

    class R:
        def __init__(self, prompt, n):
            self.prompt = prompt
            self.max_new_tokens = n
            self.out_tokens = None

    # baseline: fill the detector's window with honest step timings
    lm_engine.serve([R(np.zeros(2, np.int32), 12)])
    assert lm_engine.step_straggler.observed >= 8
    events0 = len(lm_engine.step_straggler.events)

    real_step = lm_engine._step
    hits = {"n": 0}

    def slow_step(*args):
        hits["n"] += 1
        if hits["n"] == 6:
            time.sleep(0.25)        # one GC-pause-shaped outlier
        return real_step(*args)

    lm_engine._step = slow_step
    try:
        lm_engine.serve([R(np.zeros(2, np.int32), 12)])
    finally:
        lm_engine._step = real_step
    assert len(lm_engine.step_straggler.events) > events0
    snap = lm_engine.stats()["straggler"]
    assert snap["events"] > events0
    assert snap["last_event"]["severity"] > 1.0
