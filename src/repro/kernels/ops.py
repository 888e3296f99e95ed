"""Jit'd dispatch wrappers around the bit-serial matmul.

``backend`` selection:

* ``"pallas"``    — the v1 TPU kernel (``interpret=True`` on CPU for tests),
* ``"pallas_v2"`` — the v2 TPU kernel: bit-packed activations on the HBM
                    side, hoisted digit-plane assembly in VMEM scratch, and
                    (optionally) the fused requant→bit-transpose-pack
                    epilogue. Block sizes come from the cost-model autotuner
                    (:mod:`repro.kernels.tuning`) unless given explicitly.
* ``"xla"``       — the pure-JAX plane path (used by the multi-pod dry-run
                    so XLA's cost analysis sees the real dataflow),
* ``"ref"``       — alias of the oracle in :mod:`repro.kernels.ref`.

The higher-level :func:`quantized_linear` is what the model zoo calls in
``serve_step``: runtime activation quantization → serial matmul from packed
weights → fused dequant scaler/bias (and optional ReLU / requant).
:func:`pack_activations` + :func:`serial_matmul_packed_op` are the v2
layer-chaining pair: a layer whose epilogue emitted packed planes feeds the
next layer's matmul with no intermediate unpacked tensor.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bitops
from repro.core.bitserial import (SerialSpec, plan_spec, serial_conv2d_packed_acts,
                                  serial_matmul_packed,
                                  serial_matmul_packed_acts)
from repro.core.quant import QuantSpec, QuantizedWeight, quantize_int, qrange
from repro.kernels import tuning
from repro.kernels.bitserial_conv import bitserial_conv2d_v2_pallas
from repro.kernels.bitserial_matmul import (bitserial_matmul_pallas,
                                            bitserial_matmul_v2_pallas)
from repro.kernels.ref import bitserial_matmul_ref

__all__ = ["serial_matmul_op", "serial_matmul_packed_op", "pack_activations",
           "serial_conv2d_packed_op", "quantized_linear"]


def pack_activations(codes: jax.Array, a_bits: int) -> jax.Array:
    """Bit-transpose-pack integer activation codes: (..., K) ints ->
    (a_bits, ..., ceil(K/32)) uint32 — the activation-RAM format the v2
    matmul consumes (identical layout to ``quantize_pack_pallas``)."""
    planes = bitops.pad_to(bitops.to_bitplanes(codes, a_bits), 32, axis=-1)
    return bitops.pack_bitplanes(planes, axis=-1)


def _epilogue_xla(acc, scale, bias, *, relu, out_dtype, requant,
                  requant_scale, emit_packed):
    out = acc.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
    if bias is not None:
        out = out + jnp.asarray(bias, jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    if requant is None:
        return out.astype(out_dtype)
    qn, qp = qrange(requant.bits, requant.signed)
    rs = jnp.asarray(1.0 if requant_scale is None else requant_scale,
                     jnp.float32)
    codes = jnp.clip(jnp.round(out / rs), qn, qp).astype(jnp.int32)
    if emit_packed:
        return pack_activations(codes, requant.bits)
    return codes.astype(jnp.int8 if requant.bits <= 8 else jnp.int32)


def serial_matmul_packed_op(
    x_packed: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    spec: SerialSpec,
    k: int,
    relu: bool = False,
    out_dtype=jnp.float32,
    requant: Optional[QuantSpec] = None,
    requant_scale: Optional[jax.Array] = None,
    emit_packed: bool = False,
    backend: str = "pallas_v2",
    interpret: bool = False,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    cache_weights: Optional[bool] = None,
    cache_acts: Optional[bool] = None,
) -> jax.Array:
    """v2 fused serial matmul over **bit-packed activations**.

    ``x_packed``: (a_bits, ..., ceil(K/32)) uint32 (lane axis packed, any
    leading batch dims); ``w_packed``: (w_bits, ceil(K/32), N). With
    ``requant`` + ``emit_packed`` the output is (requant.bits, ...,
    ceil(N/32)) uint32 — directly consumable by the next layer.

    Block sizes default to the cost-model autotuner's choice for this
    (shape, spec); pass explicit blocks to override.
    """
    if emit_packed and requant is None:
        raise ValueError("emit_packed requires requant")  # both backends
    lead = x_packed.shape[1:-1]
    m = 1
    for d in lead:
        m *= d
    x2 = x_packed.reshape((x_packed.shape[0], m, x_packed.shape[-1]))
    n = w_packed.shape[-1]

    if backend == "pallas_v2":
        tile_kwargs = {}
        if block_m is None or block_n is None or block_k is None:
            tc = tuning.choose_tile(
                m, k, n, spec,
                out_bits=requant.bits if (requant and emit_packed) else None)
            tile_kwargs = tc.kernel_kwargs()
        if block_m is not None:
            tile_kwargs["block_m"] = block_m
        if block_n is not None:
            tile_kwargs["block_n"] = block_n
        if block_k is not None:
            tile_kwargs["block_k"] = block_k
        # AOT-tuned configs (the compiler) pin the cache flags too — without
        # these, explicit blocks would silently fall back to kernel defaults
        if cache_weights is not None:
            tile_kwargs["cache_weights"] = cache_weights
        if cache_acts is not None:
            tile_kwargs["cache_acts"] = cache_acts
        out = bitserial_matmul_v2_pallas(
            x2, w_packed, scale, bias, spec=spec, k=k, relu=relu,
            out_dtype=out_dtype, requant=requant,
            requant_scale=requant_scale, emit_packed=emit_packed,
            interpret=interpret, **tile_kwargs)
    elif backend == "xla":
        acc = serial_matmul_packed_acts(x2, w_packed, spec=spec, k=k)
        out = _epilogue_xla(acc, scale, bias, relu=relu, out_dtype=out_dtype,
                            requant=requant, requant_scale=requant_scale,
                            emit_packed=emit_packed)
    else:
        raise ValueError(f"unknown packed-act backend {backend!r}")

    if emit_packed and requant is not None:
        return out.reshape((requant.bits,) + lead + (out.shape[-1],))
    return out.reshape(lead + (out.shape[-1],))


def serial_conv2d_packed_op(
    x_packed: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    spec: SerialSpec,
    ci: int,
    stride: int = 1,
    padding: int = 1,
    relu: bool = False,
    out_dtype=jnp.float32,
    requant: Optional[QuantSpec] = None,
    requant_scale: Optional[jax.Array] = None,
    emit_packed: bool = False,
    backend: str = "pallas_v2",
    interpret: bool = False,
    block_co: Optional[int] = None,
    block_nb: Optional[int] = None,
    cache_weights: Optional[bool] = None,
) -> jax.Array:
    """Fused implicit-GEMM serial conv2d over **bit-packed activations**.

    ``x_packed``: (a_bits, N, H, W, ceil(Ci/32)) uint32 (channel axis
    packed — what :func:`pack_activations` / a previous layer's fused
    epilogue emits); ``w_packed``: (w_bits, FH, FW, ceil(Ci/32), Co). With
    ``requant`` + ``emit_packed`` the output is (requant.bits, N, Ho, Wo,
    ceil(Co/32)) uint32 — directly consumable by the next conv layer, so
    ResNet stages chain packed end-to-end.

    ``backend="pallas_v2"`` is the Pallas kernel (block sizes from the conv
    cost-model autotuner unless given); ``backend="xla"`` lowers the same
    tap-walk dataflow with XLA (the oracle — also the fast CPU path).
    """
    if emit_packed and requant is None:
        raise ValueError("emit_packed requires requant")
    ba, n, h, w_in, _ = x_packed.shape
    bw, fh, fw, _, co = w_packed.shape

    if backend == "pallas_v2":
        if block_co is not None and block_nb is not None:
            tile_kwargs = dict(block_co=block_co, block_nb=block_nb)
            if cache_weights is not None:
                tile_kwargs["cache_weights"] = cache_weights
        else:
            # pinned axes constrain the tuner; the rest (other axis + cache
            # flag) is still tuned and VMEM-validated jointly
            tc = tuning.choose_conv_tile(
                n, h, w_in, ci, co, fh=fh, fw=fw, stride=stride,
                padding=padding, spec=spec,
                out_bits=requant.bits if (requant and emit_packed) else None,
                fix_bco=block_co, fix_bnb=block_nb)
            tile_kwargs = tc.kernel_kwargs()
        return bitserial_conv2d_v2_pallas(
            x_packed, w_packed, scale, bias, spec=spec, ci=ci, stride=stride,
            padding=padding, relu=relu, out_dtype=out_dtype, requant=requant,
            requant_scale=requant_scale, emit_packed=emit_packed,
            interpret=interpret, **tile_kwargs)
    if backend == "xla":
        acc = serial_conv2d_packed_acts(
            x_packed, w_packed, spec=spec, ci=ci, stride=stride,
            padding=padding)
        nn, ho, wo, _ = acc.shape
        out = _epilogue_xla(acc.reshape(nn * ho * wo, co), scale, bias,
                            relu=relu, out_dtype=out_dtype, requant=requant,
                            requant_scale=requant_scale,
                            emit_packed=emit_packed)
        if emit_packed:
            return out.reshape((requant.bits, nn, ho, wo, out.shape[-1]))
        return out.reshape((nn, ho, wo, out.shape[-1]))
    raise ValueError(f"unknown packed-conv backend {backend!r}")


def serial_matmul_op(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    spec: SerialSpec,
    k: int,
    relu: bool = False,
    out_dtype=jnp.float32,
    requant: Optional[QuantSpec] = None,
    backend: str = "xla",
    interpret: bool = False,
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jax.Array:
    """Dispatch one fused serial matmul. ``x``: (..., K) int codes."""
    lead = x.shape[:-1]
    x2 = x.reshape((-1, x.shape[-1]))
    if backend == "pallas":
        out = bitserial_matmul_pallas(
            x2, w_packed, scale, bias, spec=spec, k=k, relu=relu,
            out_dtype=out_dtype, requant=requant, interpret=interpret,
            block_m=block_m or 128, block_n=block_n or 128,
            block_k=block_k or 512)
    elif backend == "pallas_v2":
        xp = pack_activations(x2, spec.a_bits)
        # v1-compatible requant semantics: ``scale`` already folds the
        # requant step, so the epilogue divides by 1.
        out = serial_matmul_packed_op(
            xp, w_packed, scale, bias, spec=spec, k=k, relu=relu,
            out_dtype=out_dtype, requant=requant,
            requant_scale=None if requant is None else jnp.asarray(1.0),
            backend="pallas_v2", interpret=interpret, block_m=block_m,
            block_n=block_n, block_k=block_k)
    elif backend in ("xla", "ref"):
        if backend == "ref":
            out = bitserial_matmul_ref(
                x2, w_packed, scale, bias, spec=spec, k=k, relu=relu,
                out_dtype=out_dtype, requant=requant,
                requant_scale=jnp.asarray(1.0, jnp.float32))
        else:
            acc = serial_matmul_packed(x2.astype(jnp.int32), w_packed,
                                       spec=spec, k=k)
            out = acc.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
            if bias is not None:
                out = out + jnp.asarray(bias, jnp.float32)
            if relu:
                out = jnp.maximum(out, 0.0)
            if requant is not None:
                qn, qp = qrange(requant.bits, requant.signed)
                out = jnp.clip(jnp.round(out), qn, qp).astype(
                    jnp.int8 if requant.bits <= 8 else jnp.int32)
            else:
                out = out.astype(out_dtype)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return out.reshape(lead + (out.shape[-1],))


def quantized_linear(
    x: jax.Array,
    qw: QuantizedWeight,
    act_alpha: jax.Array,
    *,
    a_bits: int = 8,
    a_signed: bool = True,
    radix_bits: int = 7,
    bias: Optional[jax.Array] = None,
    relu: bool = False,
    backend: str = "xla",
    interpret: bool = False,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Full deployment linear: float acts → int codes → serial matmul →
    dequant. ``scale`` folds ``act_alpha * w_scale`` per output channel
    (the scaler RAM contents). The digit plan is re-selected per spec
    (:func:`repro.core.bitserial.plan_spec`) — radix is a kernel-internal
    choice and never changes the integer result."""
    aspec = QuantSpec(a_bits, a_signed)
    codes = quantize_int(x, act_alpha, aspec)
    spec = plan_spec(SerialSpec(a_bits=a_bits, w_bits=qw.bits,
                                a_signed=a_signed, w_signed=qw.signed,
                                radix_bits=radix_bits))
    scale = jnp.asarray(act_alpha, jnp.float32) * jnp.asarray(qw.scale, jnp.float32)
    return serial_matmul_op(
        codes, qw.packed, scale, bias, spec=spec, k=qw.k, relu=relu,
        out_dtype=out_dtype, backend=backend, interpret=interpret)
