"""Program executor: jit-runs a lowered :class:`~repro.compiler.lower.Program`
on batched inputs.

Each step kind maps to one dispatch function; the whole step list closes
over a single traced function (:func:`make_runner`) so ``jax.jit`` fuses the
entire compiled model into one XLA computation — the executor adds zero
per-step runtime dispatch beyond the Python walk at trace time (measured by
the ``compile`` benchmark group's dispatch-overhead row).

The packed kernel calls go through :mod:`repro.kernels.ops`, so the same
Program retargets between the XLA oracle lowering (CPU / dry-run) and the
Pallas v2 kernels (TPU) via ``backend=`` without re-lowering; the tile
choices baked in at compile time are forwarded to the Pallas dispatch.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Set

import jax
import jax.numpy as jnp

from repro.core.pipeline_modules import maxpool_relu
from repro.core.quant import QuantSpec, quantize_int
from repro.kernels.ops import (pack_activations, serial_conv2d_packed_op,
                               serial_matmul_packed_op)

__all__ = ["make_runner", "make_step_runner", "bucket_sizes", "bucket_for",
           "BucketedRunner", "make_bucketed_runner"]


def _requant_spec(attrs) -> Optional[QuantSpec]:
    if attrs.get("out") in ("packed", "codes", "requant_codes"):
        return QuantSpec(attrs["requant_bits"], attrs["requant_signed"])
    return None


def _conv_packed(st, p, x, backend, interpret):
    return serial_conv2d_packed_op(
        x, p["w_packed"], p["scale"], p.get("bias"),
        spec=st.attrs["spec"], ci=st.attrs["ci"], stride=st.attrs["stride"],
        padding=st.attrs["padding"], relu=st.attrs["relu"],
        requant=_requant_spec(st.attrs),
        requant_scale=p.get("requant_scale"),
        emit_packed=st.attrs["out"] == "packed",
        backend=backend, interpret=interpret, **st.attrs["tile"])


def _gemm_packed(st, p, x, backend, interpret):
    return serial_matmul_packed_op(
        x, p["w_packed"], p["scale"], p.get("bias"),
        spec=st.attrs["spec"], k=st.attrs["k"], relu=st.attrs["relu"],
        requant=_requant_spec(st.attrs),
        requant_scale=p.get("requant_scale"),
        emit_packed=st.attrs["out"] == "packed",
        backend=backend, interpret=interpret, **st.attrs["tile"])


def _host_conv(st, p, x, backend, interpret):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype),
        (st.attrs["stride"], st.attrs["stride"]),
        [(st.attrs["padding"], st.attrs["padding"])] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if "scale" in p:
        y = y * p["scale"]
    if "bias" in p:
        y = y + p["bias"]
    return jnp.maximum(y, 0) if st.attrs["relu"] else y


def _host_gemm(st, p, x, backend, interpret):
    y = x @ p["w"].astype(x.dtype)
    if "scale" in p:
        y = y * p["scale"]
    if "bias" in p:
        y = y + p["bias"]
    return jnp.maximum(y, 0) if st.attrs["relu"] else y


def _quantize_pack(st, p, x, backend, interpret):
    codes = quantize_int(x, p["act_alpha"],
                         QuantSpec(st.attrs["bits"], st.attrs["signed"]))
    return pack_activations(codes, st.attrs["bits"])


def _maxpool(st, p, x, backend, interpret):
    # integer codes pool as int32 (max commutes with the monotone
    # quantizer, so pooling codes == pooling floats then quantizing)
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)
    return maxpool_relu(x, st.attrs["window"], st.attrs["stride"],
                        with_relu=False)


_APPLY: Dict[str, Callable] = {
    "conv_packed": _conv_packed,
    "gemm_packed": _gemm_packed,
    "host_conv": _host_conv,
    "host_gemm": _host_gemm,
    "quantize_pack": _quantize_pack,
    "pack_codes": lambda st, p, x, b, i: pack_activations(
        x.astype(jnp.int32), st.attrs["bits"]),
    "maxpool": _maxpool,
    "global_pool": lambda st, p, x, b, i: jnp.mean(x, axis=(1, 2)),
    "flatten": lambda st, p, x, b, i: x.reshape(x.shape[0], -1),
    "relu": lambda st, p, x, b, i: jnp.maximum(x, 0),
    "add": lambda st, p, a, b_, *rest: a + b_,
    "dequant": lambda st, p, x, b, i: x.astype(jnp.float32) * p["alpha"],
    "fake_quant": lambda st, p, x, b, i: quantize_int(
        x, p["scale"], QuantSpec(st.attrs["bits"], st.attrs["signed"])
    ).astype(jnp.float32) * p["scale"],
}


def make_runner(program, *, backend: Optional[str] = None,
                interpret: Optional[bool] = None, steps=None,
                input_name: Optional[str] = None,
                output_name: Optional[str] = None) -> Callable:
    """Build ``run(params, x) -> output`` for one Program.

    The step list and attrs are static (closed over); ``params`` is the
    traced pytree, so ``jax.jit(make_runner(p))`` compiles once per
    (backend, batch shape) and weight updates never retrigger tracing.

    ``steps``/``input_name``/``output_name`` override the Program's own
    (default: the whole step list). A contiguous slice of steps plus its
    boundary tensor names yields a *stage* runner — the building block of
    :class:`repro.distributed.program_parallel.PipelinedProgram`, which
    maps consecutive slices onto consecutive devices.
    """
    backend = backend or program.backend
    interpret = program.interpret if interpret is None else interpret
    steps = program.steps if steps is None else tuple(steps)
    input_name = program.input_name if input_name is None else input_name
    output_name = program.output_name if output_name is None else output_name

    def run(params, x):
        env = {input_name: x}
        for st in steps:
            fn = _APPLY.get(st.kind)
            if fn is None:
                raise KeyError(f"no executor for step kind {st.kind!r}")
            args = [env[i] for i in st.inputs]
            # the step's name on every op it lowers to, so a device trace
            # or HLO dump names the layer
            with jax.named_scope(st.name):
                if st.kind == "add":
                    env[st.output] = fn(st, params.get(st.name, {}), *args,
                                        backend, interpret)
                else:
                    env[st.output] = fn(st, params.get(st.name, {}),
                                        args[0], backend, interpret)
        return env[output_name]

    return run


def make_step_runner(program, step, *, backend: Optional[str] = None,
                     interpret: Optional[bool] = None) -> Callable:
    """Build ``run(params, *inputs) -> output`` for a single Program step.

    The whole-Program runner fuses every step into one XLA computation,
    which is what serving wants but hides per-step cost. The profiler
    (:mod:`repro.obs.profiler`) needs the opposite: one jit-able callable
    per IR node so each can be fenced with ``block_until_ready`` and timed
    in isolation. Multi-input steps (``add``) take their inputs
    positionally in ``step.inputs`` order.
    """
    backend = backend or program.backend
    interpret = program.interpret if interpret is None else interpret
    fn = _APPLY.get(step.kind)
    if fn is None:
        raise KeyError(f"no executor for step kind {step.kind!r}")

    if step.kind == "add":
        def run(params, *inputs):
            return fn(step, params.get(step.name, {}), *inputs,
                      backend, interpret)
    else:
        def run(params, *inputs):
            return fn(step, params.get(step.name, {}), inputs[0],
                      backend, interpret)
    return run


# --------------------------------------------------------------------------
# batch-bucket entry points (the serving runtime's jit-cache discipline)
# --------------------------------------------------------------------------

def bucket_sizes(max_batch: int, multiple: int = 1) -> List[int]:
    """Padding buckets: powers of two up to (and always including)
    ``max_batch`` — the closed set of batch shapes serving ever compiles.

    ``multiple``: every bucket is a multiple of it (the bank count, when a
    bucket is batch-sharded across a device mesh — each bank must receive
    an equal shard). ``max_batch`` is rounded up to the next multiple.
    """
    if max_batch < 1:
        raise ValueError("max_batch must be >= 1")
    if multiple < 1:
        raise ValueError("bucket multiple must be >= 1")
    cap = -(-max_batch // multiple) * multiple
    sizes, b = [], multiple
    while b < cap:
        sizes.append(b)
        b *= 2
    sizes.append(cap)
    return sizes


def bucket_for(n: int, max_batch: int, multiple: int = 1) -> int:
    """Smallest bucket holding ``n`` examples."""
    for b in bucket_sizes(max_batch, multiple):
        if n <= b:
            return b
    raise ValueError(f"batch {n} exceeds max_batch={max_batch}")


class BucketedRunner:
    """Jit-cached Program caller with padding buckets.

    A bare ``Program.__call__`` retraces on every new batch shape; under
    traffic with arbitrary batch sizes that is a recompile per size. The
    bucketed runner pads each batch with zero rows up to the next
    power-of-two bucket, so the set of compiled shapes is closed
    (``bucket_sizes(max_batch)``) and steady-state traffic never
    recompiles. Per-example outputs are unchanged: every lowered step is
    example-independent (convs/gemms act per row, the activation
    quantizers use calibration-time constants), so padding rows cannot
    leak into real rows — asserted bit-exactly by the serving soak test.

    Device placement (the mesh-of-MVU-banks serving path — one of):

    * default — the whole batch runs on the default device (seed behavior);
    * ``mesh`` — each bucket is batch-**sharded** across the bank mesh via
      :class:`repro.distributed.program_parallel.ShardedProgram`; buckets
      are multiples of the bank count so every bank gets an equal shard;
    * ``banks`` (device list) — the whole batch is **placed** on one bank:
      ``runner(x, bank=b)`` runs against that bank's parameter replica
      (replicated once per device through ``replica_cache``, so variants
      sharing packed planes share the per-bank buffers too). jax caches
      one executable per (bucket, device placement), so the jit cache is
      the closed set {bucket} x {bank} — warmed up front, zero steady-state
      recompiles.

    ``compiles``/``hits`` count (bank, bucket)-cache misses/hits: a miss
    is exactly one XLA compile (the jit function is private to this
    runner, so a first-seen (bucket shape, placement) is a first-seen jit
    key).
    """

    def __init__(self, program, *, max_batch: int = 32,
                 backend: Optional[str] = None,
                 interpret: Optional[bool] = None,
                 mesh=None, banks=None, replica_cache=None,
                 metrics=None):
        import threading
        if mesh is not None and banks is not None:
            raise ValueError("pass mesh= (sharded) or banks= (placed), "
                             "not both")
        self.program = program
        self.max_batch = max_batch
        self._multiple = 1
        self._sharded = None
        self._banks = None
        if mesh is not None:
            from repro.distributed.program_parallel import ShardedProgram
            self._sharded = ShardedProgram(
                program, mesh, backend=backend, interpret=interpret,
                replica_cache=replica_cache)
            self._multiple = self._sharded.n_banks
            self.n_banks = self._sharded.n_banks
            self.placement = "sharded"
        elif banks is not None:
            from repro.distributed.program_parallel import replicate_params
            self._banks = list(banks)
            if not self._banks:
                raise ValueError("banks= needs at least one device")
            self.n_banks = len(self._banks)
            self.placement = "banked"
            self._bank_params = [
                replicate_params(program.params, d, cache=replica_cache)
                for d in self._banks]
            self._fn = jax.jit(make_runner(program, backend=backend,
                                           interpret=interpret))
        else:
            self.n_banks = 1
            self.placement = "single"
            self._fn = jax.jit(make_runner(program, backend=backend,
                                           interpret=interpret))
        self._seen: Set[tuple] = set()   # (bank, bucket) jit-cache keys
        # counters mutate on the serving worker while metrics() snapshots
        # them from user threads; registry-backed (writes under self._lock
        # keep the totals exact), legacy attribute names stay as properties
        self._lock = threading.Lock()
        from repro.obs.metrics import MetricsRegistry
        self.metrics_registry = (metrics if metrics is not None
                                 else MetricsRegistry())
        self._c_compiles = self.metrics_registry.counter(
            "runner_bucket_compiles_total", "new (bank, bucket) jit keys")
        self._c_hits = self.metrics_registry.counter(
            "runner_bucket_hits_total", "warm (bank, bucket) jit hits")
        self._c_padded = self.metrics_registry.counter(
            "runner_padded_rows_total",
            "zero rows added to fill a batch to its bucket")

    @property
    def compiles(self) -> int:
        return int(self._c_compiles.value())

    @property
    def hits(self) -> int:
        return int(self._c_hits.value())

    def __call__(self, x, *, bank: Optional[int] = None):
        return self.launch(x, bank=bank)[0]

    def launch(self, x, *, bank: Optional[int] = None):
        """Put ``x`` on the device, pad it to its bucket and dispatch the
        Program, without waiting for either.

        Returns ``(y, x_dev, padded)``: the output rows (in flight), the
        input as put on the device (before padding; ready once the
        host-to-device transfer is done), and the zero rows added."""
        x = x_dev = jnp.asarray(x)
        n = x.shape[0]
        b = bucket_for(n, self.max_batch, self._multiple)
        if b != n:
            pad = jnp.zeros((b - n,) + x.shape[1:], x.dtype)
            x = jnp.concatenate([x, pad], axis=0)
        if self._banks is not None:
            bank = 0 if bank is None else bank
            if not 0 <= bank < self.n_banks:
                raise ValueError(f"bank {bank} out of range "
                                 f"[0, {self.n_banks})")
            key = (bank, b)
        else:
            key = (0, b)
        with self._lock:
            if key in self._seen:
                self._c_hits.inc()
            else:
                self._seen.add(key)
                self._c_compiles.inc()
            self._c_padded.inc(b - n)
        if self._sharded is not None:
            y = self._sharded(x)
        elif self._banks is not None:
            y = self._fn(self._bank_params[bank], x)
        else:
            y = self._fn(self.program.params, x)
        return y[:n], x_dev, b - n

    def warmup(self, example_shape=None) -> int:
        """Compile every (bucket, bank) ahead of traffic; returns the
        number of compiles triggered."""
        shape = (tuple(example_shape) if example_shape is not None
                 else self.program.meta.get("input_shape"))
        if shape is None:
            raise ValueError("program has no recorded input_shape — pass "
                             "example_shape explicitly")
        before = self.compiles
        banks = (range(self.n_banks) if self._banks is not None else (None,))
        for b in bucket_sizes(self.max_batch, self._multiple):
            for bank in banks:
                key = (bank or 0, b)
                if key not in self._seen:
                    jax.block_until_ready(
                        self(jnp.zeros((b,) + shape, jnp.float32),
                             bank=bank))
        return self.compiles - before

    def replica_devices(self) -> List[int]:
        """Ids of the devices holding this runner's parameter replicas."""
        if self._banks is not None:
            trees = self._bank_params
        elif self._sharded is not None:
            trees = [self._sharded.params]
        else:
            trees = [self.program.params]
        return sorted({d.id for t in trees for a in jax.tree.leaves(t)
                       if hasattr(a, "devices") for d in a.devices()})

    def stats(self) -> Dict:
        devices = self.replica_devices()
        with self._lock:
            return {"compiles": self.compiles, "hits": self.hits,
                    "replica_devices": devices,
                    "buckets": sorted({b for _, b in self._seen}),
                    "bucket_set": bucket_sizes(self.max_batch,
                                               self._multiple),
                    "n_banks": self.n_banks,
                    "placement": self.placement}


def make_bucketed_runner(program, *, max_batch: int = 32,
                         backend: Optional[str] = None,
                         interpret: Optional[bool] = None,
                         mesh=None, banks=None,
                         replica_cache=None) -> BucketedRunner:
    """The serving entry point: ``runner(x) -> y`` over padding buckets."""
    return BucketedRunner(program, max_batch=max_batch, backend=backend,
                          interpret=interpret, mesh=mesh, banks=banks,
                          replica_cache=replica_cache)
