"""Measured-time Program profiler: per-step wall-ns attribution.

Everything in :mod:`repro.obs` up to here reports the *virtual* cycle
domain — the barrel-controller cost model that scheduling and HPM
counters are built on. This module closes the predicted-vs-measured
loop: it executes a compiled :class:`~repro.compiler.lower.Program`
step-by-step (one jitted callable per IR node via
:func:`~repro.compiler.executor.make_step_runner`), fences every call
with ``jax.block_until_ready``, and attributes best-of-k wall-ns to
each step alongside the cycles the cost model predicted for it.

The profiler is strictly opt-in: the serving/executor fast path never
imports it, emits no measured spans, and allocates no profiler
counters — "disabled" is the absence of the object, not a flag check
(asserted via trace counters in the calibration bench and tests).

Roofline terms (folded in from the retired ``benchmarks/roofline.py``):
each serial conv/gemm step also gets analytic FLOPs and HBM traffic at
its packed precision, so summaries report which layers are compute- vs
memory-bound on a device whose peaks are in :data:`DEVICE_PEAKS`.

Measured spans are exported as a third Chrome-trace track ("measured"
process) next to PR 8's wall and virtual-cycle tracks::

    write_chrome_trace(tracer, path, extra_spans=profile.spans())
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from repro.obs.tracing import Span, now_ns

# Published single-chip peaks for the bound classification, keyed by the
# ``device_kind`` jax reports. Source: Google Cloud documentation, "TPU
# v5e" (system architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 819 GB/s
# HBM bandwidth per chip. A device that is not listed (the CPU included)
# gets no bound classification, never another chip's numbers.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9},
}


# op kinds whose cycles the barrel-controller cost model predicts (the
# calibration targets); everything else is host-side glue
SERIAL_KINDS = ("conv_packed", "gemm_packed")


def _layer_tag(tag: str) -> str:
    """Fold codegen's pipelined XFER jobs (``"<layer>->next"``) and
    distributed replicas (``"<layer>@r0"``) onto their producing layer."""
    return tag.split("->", 1)[0].split("@", 1)[0]


def stream_cycles_by_layer(program, *, mode: str = "pipelined") -> Dict[str, int]:
    """Predicted virtual cycles per cost-model layer name, from the
    Program's own command stream (compute + its output XFER jobs; HOST
    jobs carry no MVU cycles)."""
    stream = program.to_command_stream(mode=mode)
    out: Dict[str, int] = {}
    for j in stream.jobs:
        if j.mvu < 0:
            continue
        name = _layer_tag(j.tag)
        out[name] = out.get(name, 0) + int(j.cycles)
    return out


def _bits_for(program, name: str) -> Tuple[Optional[int], Optional[int]]:
    """(a_bits, w_bits) for one layer from the Program's per-layer plan."""
    plb = getattr(program, "per_layer_bits", None) or {}
    bits = plb.get(name)
    if bits is None:
        return None, None
    if isinstance(bits, dict):
        return bits.get("a_bits"), bits.get("w_bits")
    a, w = bits
    return int(a), int(w)


def _roofline_terms(node, batch: int, a_bits: Optional[int],
                    w_bits: Optional[int],
                    peaks: Optional[Dict[str, float]]) -> Dict:
    """Analytic FLOPs / HBM bytes for one lowered conv or gemm cost node
    at its packed precision, and its bound classification against the
    device's ``peaks`` (none without peaks)."""
    ab = a_bits or 8
    wb = w_bits or 8
    if getattr(node, "kind", None) == "conv2d":
        ho = (node.h + 2 * node.padding - node.fh) // node.stride + 1
        wo = (node.w + 2 * node.padding - node.fw) // node.stride + 1
        flops = 2.0 * batch * ho * wo * node.c_out * node.c_in \
            * node.fh * node.fw
        bytes_hbm = (batch * node.h * node.w * node.c_in * ab
                     + node.fh * node.fw * node.c_in * node.c_out * wb
                     + batch * ho * wo * node.c_out * ab) / 8.0
    elif getattr(node, "kind", None) == "gemm":
        flops = 2.0 * batch * node.k * node.n
        bytes_hbm = (batch * node.k * ab + node.k * node.n * wb
                     + batch * node.n * ab) / 8.0
    else:
        return {}
    out = {"flops": flops, "bytes_hbm": bytes_hbm}
    if peaks is not None:
        t_compute = flops / peaks["int8_ops"]
        t_memory = bytes_hbm / peaks["hbm_bytes_s"]
        out.update(t_compute_s=t_compute, t_memory_s=t_memory,
                   bound="compute" if t_compute >= t_memory else "memory")
    return out


@dataclasses.dataclass
class StepProfile:
    """One IR node's measured + predicted record."""
    name: str
    kind: str
    wall_ns: float                       # best-of-k fenced wall time
    runs: int
    a_bits: Optional[int] = None
    w_bits: Optional[int] = None
    pred_cycles: int = 0                 # command-stream virtual cycles
    flops: float = 0.0
    bytes_hbm: float = 0.0
    t_compute_s: Optional[float] = None  # None: no peaks for the device
    t_memory_s: Optional[float] = None
    bound: Optional[str] = None          # "compute" | "memory" | None
    out_shape: Tuple[int, ...] = ()

    @property
    def wall_us(self) -> float:
        return self.wall_ns / 1e3

    @property
    def precision(self) -> str:
        if self.a_bits is None or self.w_bits is None:
            return "-"
        return f"W{self.w_bits}A{self.a_bits}"


@dataclasses.dataclass
class ProgramProfile:
    """Measured profile of one compiled Program (one batch shape)."""
    graph_name: str
    backend: str
    interpret: bool
    batch: int
    warmup: int
    repeats: int
    mode: str
    device_kind: str = ""
    steps: List[StepProfile] = dataclasses.field(default_factory=list)

    @property
    def bounds_measured(self) -> bool:
        """Whether the device has peaks to classify bounds against."""
        return DEVICE_PEAKS.get(self.device_kind) is not None

    @property
    def total_wall_ns(self) -> float:
        return sum(s.wall_ns for s in self.steps)

    @property
    def serial_steps(self) -> List[StepProfile]:
        return [s for s in self.steps if s.kind in SERIAL_KINDS]

    def by_kind(self) -> Dict[str, float]:
        """Total measured wall-ns per op kind."""
        out: Dict[str, float] = {}
        for s in self.steps:
            out[s.kind] = out.get(s.kind, 0.0) + s.wall_ns
        return out

    def by_precision(self) -> Dict[str, float]:
        """Total measured wall-ns per WxAy precision bucket."""
        out: Dict[str, float] = {}
        for s in self.steps:
            out[s.precision] = out.get(s.precision, 0.0) + s.wall_ns
        return out

    def spans(self) -> List[Span]:
        """Measured spans on a synthetic end-to-end timeline, tagged
        ``domain="measured"`` so the Chrome-trace exporter routes them
        to the third ("measured") track."""
        out: List[Span] = []
        cum = 0
        for s in self.steps:
            t1 = cum + max(1, int(round(s.wall_ns)))
            out.append(Span(
                0, s.name, cum, t1, track="measured",
                args={"domain": "measured", "kind": s.kind,
                      "precision": s.precision,
                      "pred_cycles": s.pred_cycles,
                      "bound": s.bound or "-"}))
            cum = t1
        return out

    def summary(self) -> Dict:
        serial = self.serial_steps
        n_compute = sum(1 for s in serial if s.bound == "compute")
        n_memory = sum(1 for s in serial if s.bound == "memory")
        return {
            "graph": self.graph_name,
            "backend": self.backend,
            "interpret": self.interpret,
            "batch": self.batch,
            "steps": len(self.steps),
            "total_wall_us": round(self.total_wall_ns / 1e3, 1),
            "serial_wall_us": round(
                sum(s.wall_ns for s in serial) / 1e3, 1),
            "pred_cycles": sum(s.pred_cycles for s in self.steps),
            "by_kind_us": {k: round(v / 1e3, 1)
                           for k, v in sorted(self.by_kind().items())},
            "by_precision_us": {k: round(v / 1e3, 1)
                                for k, v in
                                sorted(self.by_precision().items())},
            "device_kind": self.device_kind,
            "compute_bound_layers": (n_compute if self.bounds_measured
                                     else None),
            "memory_bound_layers": (n_memory if self.bounds_measured
                                    else None),
            "total_flops": sum(s.flops for s in self.steps),
            "total_bytes_hbm": sum(s.bytes_hbm for s in self.steps),
        }


def profile_program(program, x=None, *, batch: int = 1,
                    backend: Optional[str] = None,
                    interpret: Optional[bool] = None,
                    warmup: int = 1, repeats: int = 3,
                    mode: str = "pipelined",
                    metrics=None) -> ProgramProfile:
    """Execute ``program`` step-by-step and measure each IR node.

    Each step gets its own ``jax.jit`` closure (so XLA cannot fuse
    across step boundaries and hide attribution), one compile+warmup
    call, ``warmup-1`` further warm calls, then ``repeats`` fenced timed
    calls of which the minimum is recorded — best-of-k suppresses
    scheduler noise, which matters on shared CI hosts. Interpret-mode
    Pallas programs profile fine, just slowly; the flag is recorded so
    calibration never mixes the two populations.

    ``metrics``: optional :class:`~repro.obs.metrics.MetricsRegistry`
    that receives ``profiler_step_wall_ns_total{step,kind}`` and
    ``profiler_runs_total``. Off-path cost is zero: no registry, no
    counters.
    """
    import jax
    import jax.numpy as jnp

    from repro.compiler.executor import make_step_runner

    backend = backend or program.backend
    interpret = program.interpret if interpret is None else interpret
    if x is None:
        shape = program.meta.get("input_shape") if program.meta else None
        if shape is None:
            raise ValueError("program has no recorded input_shape — pass "
                             "x explicitly")
        x = jnp.zeros((batch,) + tuple(int(d) for d in shape),
                      jnp.float32)
    x = jnp.asarray(x)
    batch = int(x.shape[0])

    pred = stream_cycles_by_layer(program, mode=mode)
    nodes = {n.name: n for n in (program.cost_nodes or ())}

    c_wall = c_runs = None
    if metrics is not None:
        c_wall = metrics.counter(
            "profiler_step_wall_ns_total",
            "best-of-k measured wall ns per profiled step")
        c_runs = metrics.counter(
            "profiler_runs_total", "profile_program invocations")

    device_kind = next(iter(x.devices())).device_kind
    peaks = DEVICE_PEAKS.get(device_kind)
    prof = ProgramProfile(
        graph_name=program.graph_name, backend=backend,
        interpret=bool(interpret), batch=batch, warmup=warmup,
        repeats=repeats, mode=mode, device_kind=device_kind)

    env = {program.input_name: x}
    for st in program.steps:
        run = jax.jit(make_step_runner(program, st, backend=backend,
                                       interpret=interpret))
        args = [env[i] for i in st.inputs]
        out = run(program.params, *args)       # compile + first warmup
        jax.block_until_ready(out)
        for _ in range(max(0, warmup - 1)):
            jax.block_until_ready(run(program.params, *args))
        best = None
        for _ in range(max(1, repeats)):
            t0 = now_ns()
            jax.block_until_ready(run(program.params, *args))
            dt = now_ns() - t0
            best = dt if best is None else min(best, dt)
        env[st.output] = out

        a_bits, w_bits = _bits_for(program, st.name)
        rec = StepProfile(
            name=st.name, kind=st.kind, wall_ns=float(best),
            runs=max(1, repeats), a_bits=a_bits, w_bits=w_bits,
            pred_cycles=int(pred.get(st.name, 0)),
            out_shape=tuple(int(d) for d in out.shape))
        node = nodes.get(st.name)
        if node is not None and st.kind in SERIAL_KINDS:
            rec.__dict__.update(_roofline_terms(node, batch, a_bits,
                                                w_bits, peaks))
        prof.steps.append(rec)
        if c_wall is not None:
            c_wall.inc(rec.wall_ns, step=st.name, kind=st.kind)
    if c_runs is not None:
        c_runs.inc()
    return prof


def format_profile(profile: ProgramProfile, calibration=None) -> str:
    """Per-layer table: measured wall, predicted cycles, and (when a
    fitted :class:`~repro.obs.calibrate.Calibration` is supplied) the
    fitted ns/cycle, relative residual, and outlier flag."""
    rows = []
    head = ["layer", "kind", "prec", "wall_us", "pred_cycles", "bound"]
    if calibration is not None:
        head += ["ns/cyc", "resid", "flag"]
    rows.append(head)
    for s in profile.steps:
        bound = s.bound or ("not measured" if s.kind in SERIAL_KINDS
                            and not profile.bounds_measured else "-")
        row = [s.name, s.kind, s.precision, f"{s.wall_us:10.1f}",
               f"{s.pred_cycles:12d}", bound]
        if calibration is not None:
            if s.pred_cycles > 0:
                r = calibration.residuals.get(s.name)
                row += [f"{calibration.ns_for(s.kind):8.2f}",
                        f"{r:+7.2f}" if r is not None else "      -",
                        "OUTLIER" if s.name in calibration.outliers
                        else ""]
            else:
                row += ["       -", "      -", ""]
        rows.append(row)
    widths = [max(len(str(r[i])) for r in rows)
              for i in range(len(rows[0]))]
    lines = []
    for i, r in enumerate(rows):
        lines.append("  ".join(str(c).ljust(w)
                               for c, w in zip(r, widths)).rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    s = profile.summary()
    bounds = (f"{s['compute_bound_layers']} compute-bound / "
              f"{s['memory_bound_layers']} memory-bound serial layers"
              if profile.bounds_measured else
              f"bounds not measured (no peaks for {s['device_kind']!r})")
    lines.append("")
    lines.append(
        f"total {s['total_wall_us']:.1f}us over {s['steps']} steps "
        f"(batch={s['batch']}, backend={s['backend']}"
        f"{', interpret' if s['interpret'] else ''}); {bounds}")
    return "\n".join(lines)
