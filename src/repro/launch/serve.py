"""Serving driver: batched generation over the quantized (bit-transposed)
deployment path — prefill + decode with KV caches, greedy or top-k sampling,
continuous request batching.

The weights run through the BARVINN serial matmul (`backend='xla'` on
CPU/dry-run; `'pallas'` on TPU); per-layer precisions come from the arch's
QuantPolicy, settable at run time — no recompilation of the *weights*, just
of the step function, mirroring "run-time programmability without hardware
reconfiguration".

CNN archs (``family == "cnn"``) serve through :class:`CNNServer`, whose
default path is the **graph compiler** (`repro.compiler`): model → IR →
passes → packed Program — the hand-written ``resnet9_forward_packed`` is
kept only as the golden reference the compiled path is tested against.

Both servers are now thin wrappers over the multi-tenant serving runtime
(:mod:`repro.serving`): ``CNNServer`` registers its compiled Program in a
:class:`~repro.serving.ModelRegistry` and classifies through the
dynamic-batching :class:`~repro.serving.InferenceService` (padding-bucket
jit cache — no re-jit per batch shape); :func:`make_lm_engine` adapts a
:class:`Server` so autoregressive generation serves through the same
``submit``/``drain`` front end.
"""

from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.models.transformer import (ModelConfig, decode_step, init_params,
                                      pack_params, prefill, serve_policy)

__all__ = ["Server", "GenRequest", "CNNServer", "make_lm_engine"]


class _ObsSession:
    """``--trace-out`` / ``--metrics-port`` / ``--metrics-every`` wiring
    for one demo run, plus the single console writer.

    Every output line — the demo's own prints *and* the periodic metrics
    dump — goes through :meth:`emit` under one lock, so the dump thread
    can never tear a demo line mid-print (the interleaving bug the
    periodic snapshots used to have)."""

    def __init__(self, service, *, trace_out: Optional[str] = None,
                 metrics_port: Optional[int] = None,
                 metrics_every: float = 0.0):
        self.service = service
        self.trace_out = trace_out
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._http = None
        self._dumper = None
        if metrics_port is not None:
            from repro.obs import start_metrics_server
            self._http = start_metrics_server(metrics_port,
                                              service.registries)
            port = self._http.server.server_address[1]
            self.emit(f"metrics: serving Prometheus text on "
                      f"http://127.0.0.1:{port}/metrics")
        if metrics_every and metrics_every > 0:
            self._dumper = threading.Thread(
                target=self._dump_loop, args=(float(metrics_every),),
                name="metrics-dump", daemon=True)
            self._dumper.start()

    def emit(self, *lines) -> None:
        """The single writer: one locked print per call."""
        with self._lock:
            print("\n".join(str(l) for l in lines), flush=True)

    def _dump_loop(self, every: float) -> None:
        while not self._stop.wait(every):
            m = self.service.metrics()
            self.emit(f"[metrics] completed={m['completed']} "
                      f"failed={m['failed']} requeues={m['requeues']} "
                      f"queue={m['queue_depth']} "
                      f"p50={m['latency_p50_ms']}ms "
                      f"p99={m['latency_p99_ms']}ms")

    def close(self) -> None:
        self._stop.set()
        if self._dumper is not None:
            self._dumper.join(timeout=5)
        if self._http is not None:
            self._http.server.shutdown()
        if self.trace_out:
            from repro.obs import write_chrome_trace
            path = write_chrome_trace(self.service.tracer, self.trace_out)
            st = self.service.tracer.stats()
            self.emit(f"trace: {st['buffered']} spans "
                      f"({st['sampled']}/{st['started']} requests sampled) "
                      f"-> {path}",
                      "       load it in https://ui.perfetto.dev or run "
                      f"`python -m repro.launch.serve trace {path}`")


@dataclasses.dataclass
class GenRequest:
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int = 16
    out_tokens: Optional[List[int]] = None


class Server:
    """Static-batch server with slot-based continuous batching.

    ``backend`` retargets the serial matmul at run time ('xla' | 'pallas' |
    'pallas_v2') without repacking the weights — the v2 backend runs the
    packed-activation kernel with cost-model-tuned block sizes.
    """

    def __init__(self, cfg: ModelConfig, params=None, *, batch_slots: int = 4,
                 max_len: int = 128, seed: int = 0, quantized: bool = True,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None):
        cfg = serve_policy(cfg, backend=backend, interpret=interpret)
        self.cfg = cfg
        self.max_len = max_len
        self.batch_slots = batch_slots
        if params is None:
            params = init_params(jax.random.PRNGKey(seed), cfg)
        if quantized:
            params = pack_params(params, cfg)  # bit-transposed deployment
        self.params = params
        self._prefill = jax.jit(
            lambda p, b: prefill(p, b, cfg, max_len=max_len))
        self._decode = jax.jit(
            lambda p, c, t, pos: decode_step(p, c, t, pos, cfg))

    def generate(self, requests: List[GenRequest]) -> List[GenRequest]:
        """Serve a batch of same-length-padded prompts.

        The decode loop carries tokens **on device** — one host transfer at
        the end, instead of a per-token ``int()`` sync every step (which
        serialized the whole loop on dispatch latency).
        """
        if not requests:
            raise ValueError("generate() needs at least one request")
        if len(requests) > self.batch_slots:
            raise ValueError(f"{len(requests)} requests exceed "
                             f"batch_slots={self.batch_slots} — use "
                             "make_lm_engine / the serving runtime to "
                             "queue larger loads")
        too_long = [(i, len(r.prompt)) for i, r in enumerate(requests)
                    if len(r.prompt) > self.max_len]
        if too_long:
            raise ValueError(
                f"prompt(s) longer than max_len={self.max_len}: "
                + ", ".join(f"request {i} has {n} tokens"
                            for i, n in too_long))
        # the decode loop writes KV at positions up to
        # len(prompt) + max_new_tokens - 2; past max_len the
        # dynamic_update_slice clamps and silently overwrites the last
        # cache entry, so reject over-budget requests up front
        over = [(i, len(r.prompt) + r.max_new_tokens)
                for i, r in enumerate(requests)
                if len(r.prompt) + r.max_new_tokens > self.max_len]
        if over:
            raise ValueError(
                f"len(prompt) + max_new_tokens exceeds the KV budget "
                f"max_len={self.max_len}: "
                + ", ".join(f"request {i} needs {n}" for i, n in over))
        n_real = len(requests)
        # pad free slots with minimal dummies: a single masked token and a
        # zero decode budget, so dummies neither replicate a real prompt's
        # prefill work nor count toward any token/latency accounting
        while len(requests) < self.batch_slots:
            requests = requests + [GenRequest(np.zeros(1, np.int32), 0)]
        s = max(len(r.prompt) for r in requests)
        toks = np.zeros((len(requests), s), np.int32)
        for i, r in enumerate(requests):
            toks[i, -len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(toks)}
        logits, caches = self._prefill(self.params, batch)
        tok = jnp.argmax(logits, -1)[:, None]
        n_new = max((r.max_new_tokens for r in requests), default=0)
        steps = [tok]                       # device-side token columns
        for t in range(1, n_new):
            logits, caches = self._decode(self.params, caches, tok,
                                          jnp.int32(s + t - 1))
            tok = jnp.argmax(logits, -1)[:, None]
            steps.append(tok)
        if n_new:
            all_toks = np.asarray(jnp.concatenate(steps, axis=1))  # 1 sync
        else:
            all_toks = np.zeros((len(requests), 0), np.int32)
        for i, r in enumerate(requests):
            r.out_tokens = [int(v) for v in all_toks[i, :r.max_new_tokens]]
        self.last_stats = {        # dummies excluded from all accounting
            "real_requests": n_real,
            "padded_slots": len(requests) - n_real,
            "real_tokens": sum(len(r.out_tokens)
                               for r in requests[:n_real]),
            "decode_steps": max(0, n_new - 1),
        }
        return requests[:n_real]  # dummies pad the batch; don't return them


def make_lm_engine(server: "Server"):
    """**Thin compat shim**: adapt a :class:`Server` to the serving
    runtime's engine contract (``fn(requests) -> results``, one result per
    request, in order) by draining loads in sequential slot-sized chunks —
    every chunk decodes to its longest member's ``max_new_tokens``.

    This is the *static-batch baseline*. New code should serve LM traffic
    through :class:`repro.serving.ContinuousLMEngine`, which joins/leaves
    the batch at token boundaries (a finished request frees its slot for
    the next queued one) and books scheduler cycles per decode step; it is
    kept for benchmark comparison and for families the slot arena can't
    host (see :func:`repro.serving.supports_continuous`).

    Register with :meth:`repro.serving.ModelRegistry.register_callable`
    (pass ``max_batch=server.batch_slots`` so the batcher respects the
    slot count); every payload must be a :class:`GenRequest`.
    """

    def engine(requests: List[GenRequest]) -> List[GenRequest]:
        out: List[GenRequest] = []
        for i in range(0, len(requests), server.batch_slots):
            out.extend(server.generate(requests[i:i + server.batch_slots]))
        return out

    return engine


class CNNServer:
    """Batched CNN inference server over the **compiled** deployment path.

    ``graph``: a compiler IR graph (default: ResNet9 from random init —
    pass a real one from :func:`repro.models.resnet.resnet9_graph` or an
    importer). The graph is compiled once (passes + calibration + AOT
    weight packing + tile autotuning) and registered in a
    :class:`~repro.serving.ModelRegistry`; ``classify`` goes through the
    dynamic-batching :class:`~repro.serving.InferenceService`, so any
    batch size is served out of the power-of-two padding-bucket jit cache
    instead of re-jitting per shape. The service worker is a daemon
    thread; ``close()`` (or use as a context manager) stops it.

    ``n_banks``/``placement`` scale the service across a device mesh (one
    8-slot MVU bank per jax device — on CPU, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first):
    ``placement="banked"`` load-balances micro-batches across banks,
    ``"sharded"`` splits each micro-batch evenly over all of them.

    ``store`` (an :class:`~repro.compiler.ArtifactStore` or directory
    path) warm-boots compiles from disk and persists fresh ones;
    ``artifact="model@precision"`` serves a precompiled artifact by its
    store tag with **no** graph, calibration data, or autotuner at all —
    the BARVINN fleet story: ship the command stream, not the compiler.
    """

    def __init__(self, graph=None, *, calib=None, seed: int = 0,
                 calib_batch: int = 8, backend: str = "xla",
                 interpret: bool = False, policy=None, max_batch: int = 32,
                 max_wait_s: float = 0.0, n_banks: Optional[int] = None,
                 placement: str = "banked", store=None,
                 artifact: Optional[str] = None):
        from repro.serving import InferenceService, ModelRegistry
        if artifact is not None:
            # fleet path: serve a precompiled artifact by its store tag —
            # no graph construction, no calibration data, no autotuner
            if store is None:
                raise ValueError("artifact=... requires store=")
            model, _, prec = artifact.partition("@")
            if not prec:
                raise ValueError(f"artifact must be 'model@precision', "
                                 f"got {artifact!r}")
            self.graph = self.calib = None
            self.registry = ModelRegistry(backend=backend,
                                          interpret=interpret, store=store)
            self.key = self.registry.register_artifact(model, precision=prec)
        else:
            from repro.models.layers import QuantPolicy
            from repro.models.resnet import (ResNet9Config, resnet9_graph,
                                             resnet9_init)
            if graph is None:
                cfg = ResNet9Config()
                params = resnet9_init(jax.random.PRNGKey(seed), cfg)
                graph = resnet9_graph(params, cfg)
                if policy is None:
                    policy = QuantPolicy(mode="serial", w_bits=cfg.w_bits,
                                         a_bits=cfg.a_bits,
                                         radix_bits=cfg.radix_bits)
            if policy is None:
                policy = QuantPolicy(mode="serial", w_bits=2, a_bits=2,
                                     radix_bits=7)
            if calib is None:
                in_shape = next(iter(graph.inputs.values()))
                calib = jax.random.uniform(
                    jax.random.PRNGKey(seed + 1),
                    (calib_batch,) + tuple(int(d) for d in in_shape[1:]))
            self.graph, self.calib = graph, calib
            self.registry = ModelRegistry(backend=backend,
                                          interpret=interpret, store=store)
            self.key = self.registry.register_graph(graph.name or "cnn",
                                                    graph, calib, policy)
        self.service = InferenceService(
            self.registry, max_batch=max_batch, max_wait_s=max_wait_s,
            n_banks=n_banks, placement=placement)
        self.service.start()

    @property
    def program(self):
        """The compiled Program (lazy — first access compiles)."""
        return self.registry.program(self.key)

    def warm_boot(self) -> dict:
        """Restore every variant from the artifact store and pre-jit its
        padding buckets (see :meth:`InferenceService.warm_boot`)."""
        return self.service.warm_boot()

    def add_precision(self, policy, *, precision: Optional[str] = None):
        """Serve this server's graph at another precision too; returns the
        new variant's key for :meth:`classify`. The variant compiles on
        first use and shares packed weight planes with the variants that
        quantize them alike — several precisions resident in one registry,
        no reconfiguration."""
        if self.graph is None:
            raise ValueError("an artifact-backed server has no graph to "
                             "recompile at another precision")
        return self.registry.register_graph(
            self.graph.name or "cnn", self.graph, self.calib, policy,
            precision=precision)

    def classify(self, images, key=None) -> np.ndarray:
        """Logits for a batch of images (NHWC float): per-image requests
        to variant ``key`` (default: the first registered) through the
        service, re-assembled in order."""
        futures = self.service.submit_many(self.key if key is None else key,
                                           list(np.asarray(images)))
        return np.stack([f.result() for f in futures])

    def metrics(self) -> dict:
        """The serving runtime's metrics snapshot (latency percentiles,
        bucket-cache counters, slot utilization, straggler events)."""
        return self.service.metrics()

    def cycle_report(self, mode: str = "pipelined") -> str:
        """Accelerator cycle estimate of the compiled model (paper §3.3)."""
        cs = self.program.to_command_stream(mode=mode)
        return cs.summary()

    def close(self) -> None:
        self.service.stop()

    def __enter__(self) -> "CNNServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _main_cnn(args, cfg) -> None:
    """CNN arch serving demo: compiled-path classification + cycle report.
    The default backend is the Pallas kernels on a TPU, XLA elsewhere."""
    backend = args.backend or ("pallas_v2" if jax.default_backend() == "tpu"
                               else "xla")
    if backend == "pallas":
        # the packed conv/matmul ops have no v1 path; v2 is its successor
        print("note: CNN path has no 'pallas' (v1) backend — using pallas_v2")
        backend = "pallas_v2"
    if args.no_quant:
        print("note: --no-quant is ignored on the CNN path (the compiled "
              "Program is the quantized deployment form)")
    if args.placement != "banked" and not args.banks:
        print(f"note: --placement {args.placement} has no effect without "
              "--banks N (serving single-device)")
    server = CNNServer(backend=backend, interpret=args.interpret,
                       n_banks=args.banks, placement=args.placement,
                       store=args.store, artifact=args.artifact)
    obs = _ObsSession(server.service, trace_out=args.trace_out,
                      metrics_port=args.metrics_port,
                      metrics_every=args.metrics_every)
    if args.store:
        t0 = time.perf_counter()
        report = server.warm_boot()
        obs.emit(f"warm boot in {(time.perf_counter()-t0)*1e3:.0f}ms: "
                 f"restored={report['restored']} "
                 f"compiled={report['compiled']} "
                 f"bucket_compiles={report['bucket_compiles']}")
    if args.banks and args.banks > 1:
        obs.emit(f"serving across {server.service.n_banks} MVU banks "
                 f"(placement={server.service.placement})")
    rng = np.random.RandomState(0)
    images = rng.rand(args.batch, 32, 32, 3).astype(np.float32)
    server.classify(images)  # warmup/compile
    t0 = time.perf_counter()
    logits = server.classify(images)
    dt = time.perf_counter() - t0
    obs.emit(f"classified {len(logits)} images in {dt*1e3:.1f}ms "
             f"({len(logits)/dt:.1f} img/s, compiled path, "
             f"backend={backend})",
             f"sample logits: {logits[0, :4]}")
    m = server.metrics()
    obs.emit(f"serving: p50={m['latency_p50_ms']}ms "
             f"p99={m['latency_p99_ms']}ms "
             f"bucket_caches={m['bucket_caches']}")
    if m["banks"]["n_banks"] > 1:
        sched = m["scheduler"]
        obs.emit(f"banks: util={sched['bank_utilization']} "
                 f"requests={sched['bank_requests']} "
                 f"replica_cache={m['banks']['replica_cache']}")
    if args.store:
        st = m["artifact_store"]
        obs.emit(f"artifact store: hits={st['hits']} misses={st['misses']} "
                 f"loads={st['loads']} load_p50={st['load_p50_ms']}ms "
                 f"bytes_on_disk={st['bytes_on_disk']} "
                 f"dedup_ratio={st['dedup_ratio']}")
    obs.emit(server.cycle_report())
    obs.close()
    server.close()


def _parse_precisions(spec: Optional[str], cfg) -> list:
    """``"W2A2,W8A8"`` → [(2, 8), ...]; default: the arch's own policy."""
    import re
    if not spec:
        return [(int(cfg.w_bits), int(cfg.a_bits))]
    out = []
    for tok in spec.split(","):
        m = re.fullmatch(r"[Ww](\d+)[Aa](\d+)", tok.strip())
        if not m:
            raise SystemExit(f"bad precision {tok!r} — expected e.g. W2A2")
        out.append((int(m.group(1)), int(m.group(2))))
    return out


def _main_compile(argv) -> None:
    """The offline BARVINN "code generator" run: graph → passes →
    calibration → packing → autotuning → artifact store. A serving
    process pointed at ``--store`` then boots with zero recompiles and
    needs neither ONNX nor calibration data nor the autotuner."""
    from repro.models.layers import QuantPolicy
    from repro.models.resnet import (ResNet9Config, resnet9_graph,
                                     resnet9_init)
    from repro.serving import ModelRegistry
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.serve compile",
        description="AOT-compile an arch into an artifact store")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--store", required=True,
                    help="artifact store directory (created if missing)")
    ap.add_argument("--precisions", default=None,
                    help="comma-separated variants, e.g. W2A2,W8A8 "
                         "(default: the arch policy)")
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas_v2"])
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calib-batch", type=int, default=8)
    ap.add_argument("--gc", action="store_true",
                    help="after compiling, drop store artifacts no ref "
                         "tag reaches (untagged manifests + orphaned "
                         "blobs)")
    ap.add_argument("--gc-dry-run", action="store_true",
                    help="report what --gc would delete without deleting")
    args = ap.parse_args(argv)
    cfg = get_arch(args.arch).smoke
    if getattr(cfg, "family", None) != "cnn":
        raise SystemExit(f"compile: arch {args.arch!r} is not a CNN — only "
                         "graph-compiled archs produce Program artifacts")
    # the arch entry is a registry sentinel; the graph comes from the real
    # CNN config, exactly as CNNServer builds it
    mcfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(args.seed), mcfg)
    graph = resnet9_graph(params, mcfg)
    in_shape = next(iter(graph.inputs.values()))
    calib = jax.random.uniform(
        jax.random.PRNGKey(args.seed + 1),
        (args.calib_batch,) + tuple(int(d) for d in in_shape[1:]))
    registry = ModelRegistry(backend=args.backend,
                             interpret=args.interpret, store=args.store)
    for w_bits, a_bits in _parse_precisions(args.precisions, mcfg):
        policy = QuantPolicy(mode="serial", w_bits=w_bits, a_bits=a_bits,
                             radix_bits=mcfg.radix_bits)
        key = registry.register_graph(graph.name or "cnn", graph, calib,
                                      policy)
        hits0 = registry.artifact_hits
        t0 = time.perf_counter()
        registry.program(key)   # store hit or compile+save
        dt = time.perf_counter() - t0
        e = registry.entry(key)
        how = ("store hit" if registry.artifact_hits > hits0
               else "compiled")
        print(f"{key}: {e.ref[:12]}… ({how}) in {dt*1e3:.0f}ms")
    if args.gc or args.gc_dry_run:
        rep = registry.store.gc(dry_run=args.gc_dry_run)
        mode = "gc dry-run" if rep["dry_run"] else "gc"
        print(f"{mode}: removed_programs={rep['removed_programs']} "
              f"removed_blobs={rep['removed_blobs']} "
              f"bytes_freed={rep['bytes_freed']} "
              f"(live: {rep['live_programs']} programs, "
              f"{rep['live_blobs']} blobs)")
    st = registry.store.stats()
    print(f"store {args.store}: programs={st['programs']} "
          f"blobs={st['blobs']} bytes_on_disk={st['bytes_on_disk']} "
          f"dedup_ratio={st['dedup_ratio']}")


def _main_profile(argv) -> None:
    """Measured-time profile of a compiled model: per-layer wall-ns next
    to the cost model's predicted virtual cycles, a fitted ns/cycle per
    op kind, and the misprediction-outlier list (DESIGN.md §10)."""
    from repro.models.layers import QuantPolicy
    from repro.models.resnet import (ResNet9Config, resnet9_graph,
                                     resnet9_init)
    from repro.obs import (Tracer, fit, format_calibration, format_profile,
                           profile_program, write_chrome_trace)
    from repro.obs import calibrate as _calibrate
    from repro.serving import ModelRegistry
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.serve profile",
        description="profile a compiled model step-by-step and calibrate "
                    "the cycle cost model against measured wall time")
    ap.add_argument("--model", default="resnet9",
                    help="graph-compiled model (resnet9)")
    ap.add_argument("--precision", default=None,
                    help="comma-separated variants, e.g. w2a2,w8a8 "
                         "(default: the model's own policy)")
    ap.add_argument("--backend", default="xla",
                    choices=["xla", "pallas_v2"])
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed runs per step (best-of-k)")
    ap.add_argument("--mode", default="pipelined",
                    choices=["pipelined", "distributed"],
                    help="command-stream mapping for predicted cycles")
    ap.add_argument("--tolerance", type=float, default=1.0,
                    help="|relative residual| beyond which a layer is "
                         "reported as a cost-model outlier")
    ap.add_argument("--store", default=None,
                    help="artifact store: warm-boot the compile and "
                         "persist the fitted Calibration record")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the measured spans as the third "
                         "('measured') track of a Chrome trace JSON")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calib-batch", type=int, default=8)
    args = ap.parse_args(argv)
    if args.model not in ("resnet9", "cnn"):
        raise SystemExit(f"profile: unknown model {args.model!r} — only "
                         "graph-compiled CNNs (resnet9) profile per-step")
    mcfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(args.seed), mcfg)
    graph = resnet9_graph(params, mcfg)
    in_shape = next(iter(graph.inputs.values()))
    calib = jax.random.uniform(
        jax.random.PRNGKey(args.seed + 1),
        (args.calib_batch,) + tuple(int(d) for d in in_shape[1:]))
    registry = ModelRegistry(backend=args.backend,
                             interpret=args.interpret, store=args.store)
    precisions = _parse_precisions(args.precision, mcfg)
    for w_bits, a_bits in precisions:
        policy = QuantPolicy(mode="serial", w_bits=w_bits, a_bits=a_bits,
                             radix_bits=mcfg.radix_bits)
        key = registry.register_graph(graph.name or "cnn", graph, calib,
                                      policy)
        program = registry.program(key)
        prof = profile_program(program, batch=args.batch,
                               warmup=args.warmup, repeats=args.repeats,
                               mode=args.mode)
        cal = fit(prof, tolerance=args.tolerance)
        print(f"== {key} (backend={args.backend}"
              f"{', interpret' if args.interpret else ''}) ==")
        print(format_profile(prof, cal))
        print(format_calibration(cal))
        if registry.store is not None:
            name = f"{graph.name or 'cnn'}@W{w_bits}A{a_bits}"
            k = _calibrate.save(registry.store, cal, name)
            print(f"calibration persisted: {k}")
        if args.trace_out:
            out = args.trace_out
            if len(precisions) > 1:   # one trace file per variant
                stem, dot, ext = out.rpartition(".")
                out = (f"{stem}.W{w_bits}A{a_bits}.{ext}" if dot
                       else f"{out}.W{w_bits}A{a_bits}")
            path = write_chrome_trace(Tracer(), out,
                                      extra_spans=prof.spans())
            print(f"measured trace ({len(prof.steps)} step spans on the "
                  f"'measured' track) -> {path}")
        print()


def _main_trace(argv) -> None:
    """Summarize a saved Chrome trace: top-k slowest requests by phase."""
    import json
    from repro.obs import format_trace_summary, trace_summary
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.serve trace",
        description="pretty-print a saved --trace-out file: the top-k "
                    "slowest requests with per-phase wall breakdowns")
    ap.add_argument("file", help="Chrome trace JSON from --trace-out")
    ap.add_argument("--top-k", type=int, default=10)
    args = ap.parse_args(argv)
    with open(args.file) as f:
        doc = json.load(f)
    print(format_trace_summary(trace_summary(doc, top_k=args.top_k)))
    other = doc.get("otherData", {})
    st = other.get("tracer")
    if st:
        print(f"tracer: {st['sampled']}/{st['started']} requests sampled, "
              f"{st['buffered']} spans buffered "
              f"(sample_every={st['sample_every']})")
    domains = other.get("domains")
    if domains:
        print("domains: " + "; ".join(f"{k}: {v}"
                                      for k, v in domains.items()))


def main():
    import sys
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if len(sys.argv) > 1 and sys.argv[1] == "compile":
        # offline code-generator run (kept out of argparse subparsers so
        # the plain `--arch ...` serving invocation stays unchanged)
        _main_compile(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "trace":
        _main_trace(sys.argv[2:])
        return
    if len(sys.argv) > 1 and sys.argv[1] == "profile":
        _main_profile(sys.argv[2:])
        return
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--backend", default=None,
                    choices=["xla", "pallas", "pallas_v2"],
                    help="serial-matmul backend (default: arch policy)")
    ap.add_argument("--interpret", action="store_true",
                    help="run pallas backends interpreted (CPU)")
    ap.add_argument("--banks", type=int, default=None,
                    help="serve across N MVU banks (one per jax device; "
                         "CNN path only — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--placement", default="banked",
                    choices=["banked", "sharded"],
                    help="multi-bank placement: load-balance whole "
                         "micro-batches (banked) or split each across "
                         "all banks (sharded)")
    ap.add_argument("--store", default=None,
                    help="artifact store directory: warm-boot compiled "
                         "Programs from disk, persist fresh compiles "
                         "(populate offline with the `compile` subcommand)")
    ap.add_argument("--artifact", default=None, metavar="MODEL@PRECISION",
                    help="serve a precompiled artifact by its store tag "
                         "(requires --store; CNN path; skips graph build, "
                         "calibration, and the autotuner entirely)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's request trace as Chrome trace "
                         "JSON (Perfetto-loadable; summarize with the "
                         "`trace` subcommand)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text on 127.0.0.1:PORT/metrics "
                         "for the duration of the run (0 = any free port)")
    ap.add_argument("--metrics-every", type=float, default=0.0,
                    help="dump a one-line metrics snapshot every S seconds "
                         "through the single console writer (0 = off)")
    args = ap.parse_args()
    if args.artifact and not args.store:
        ap.error("--artifact requires --store")
    cfg = get_arch(args.arch).smoke
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())}")
    if getattr(cfg, "family", None) == "cnn":
        _main_cnn(args, cfg)  # compiled graph path (the CNN default)
        return
    if args.store or args.artifact:
        print("note: --store/--artifact apply to compiled CNN archs only")
    from repro.serving import (ContinuousLMEngine, InferenceService,
                               ModelRegistry, supports_continuous)
    print(f"kernel backend: {args.backend or cfg.policy.backend}"
          f"{' (the arch policy default)' if not args.backend else ''}")
    max_len = 64
    rng = np.random.RandomState(0)
    if supports_continuous(cfg):
        # token-granular continuous batching through the serving runtime:
        # requests join/leave the slot arena at token boundaries, and the
        # scheduler is booked per decode step
        engine = ContinuousLMEngine(cfg, batch_slots=args.batch,
                                    max_len=max_len,
                                    quantized=not args.no_quant,
                                    backend=args.backend,
                                    interpret=args.interpret or None)
        warm = engine.warmup()
        print(f"engine warmup: {warm['compiles']} traces "
              f"(buckets {warm['buckets']}) in {warm['seconds']}s")
        registry = ModelRegistry()
        key = registry.register_callable(args.arch, engine)
        # heterogeneous demo traffic: mixed prompt lengths + decode budgets
        # (the shape continuous batching wins on)
        n_load = max(args.batch * 4, 8)
        m_long = max(1, min(args.new_tokens, max_len - 16))
        reqs = [GenRequest(
            rng.randint(0, cfg.vocab_size,
                        (int(rng.randint(4, 17)),)).astype(np.int32),
            m_long if i % 4 == 0 else max(1, m_long // 4))
            for i in range(n_load)]
        with InferenceService(registry, max_wait_s=0.0) as svc:
            obs = _ObsSession(svc, trace_out=args.trace_out,
                              metrics_port=args.metrics_port,
                              metrics_every=args.metrics_every)
            t0 = time.perf_counter()
            futures = svc.submit_many(key, reqs)
            svc.drain()
            dt = time.perf_counter() - t0
            out = [f.result() for f in futures]
            m = svc.metrics()
            total = sum(len(r.out_tokens) for r in out)
            em = m["engines"][str(key)]
            obs.emit(f"generated {total} tokens over {len(out)} requests "
                     f"in {dt:.2f}s ({total/dt:.1f} tok/s, continuous "
                     f"batching, quantized={not args.no_quant})",
                     f"engine: occupancy={em['slot_occupancy']} "
                     f"decode_steps={em['decode_steps']} "
                     f"recompiles_after_warmup="
                     f"{em['jit']['recompiles_after_warmup']} "
                     f"scheduler_steps={m['scheduler']['admitted_batches']}",
                     f"sample: {out[0].out_tokens}")
            obs.close()
        return
    print(f"note: family={cfg.family!r} doesn't fit the continuous slot "
          "arena (SSM/hybrid state, rolling windows, or encoder inputs) — "
          "serving via the static batch path")
    if args.trace_out or args.metrics_port is not None or args.metrics_every:
        print("note: --trace-out/--metrics-port/--metrics-every apply to "
              "the serving-runtime paths only (static batch has no spine)")
    server = Server(cfg, batch_slots=args.batch, max_len=max_len,
                    quantized=not args.no_quant, backend=args.backend,
                    interpret=args.interpret or None)
    reqs = [GenRequest(rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32),
                       args.new_tokens) for _ in range(args.batch)]
    t0 = time.perf_counter()
    out = server.generate(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(r.out_tokens) for r in out)
    print(f"generated {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s, quantized={not args.no_quant})")
    print("sample:", out[0].out_tokens)


if __name__ == "__main__":
    main()
