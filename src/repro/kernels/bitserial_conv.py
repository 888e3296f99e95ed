"""Pallas TPU kernel: implicit-GEMM arbitrary-precision bit-serial conv2d
over bit-packed NHWC activations and bit-packed HWIO weights.

TPU mapping of the MVU's conv mode (paper §3.1.3): the FPGA never builds an
im2col tensor — the AGU walks the ``K = FH*FW*Ci`` reduction as a loop nest
of GEMV tiles over the activation RAM. This kernel does the same walk in
VMEM:

* HBM holds activations **bit-packed along the channel axis** —
  ``(a_bits, N, H, W, ceil(Ci/32))`` uint32, the exact format the fused
  requant-pack epilogue (and :func:`repro.kernels.ops.pack_activations`)
  emits — and weights as ``(w_bits, FH, FW, ceil(Ci/32), Co)`` uint32.
  Bytes moved scale with the configured precisions; **no patch tensor is
  ever materialized in HBM** (the seed path round-tripped a ~FH·FW× blown
  f32 im2col tensor through HBM for every conv).
* Grid ``(Co/bn, (N/bnb)·Ho, FH)``: one grid step covers ``bnb`` images ×
  one output row × one filter-row tap. The k-step (``f_h``) selects the
  input row ``ih = oh·stride + f_h`` directly in the BlockSpec index map
  (the AGU's row walk); the ``f_w`` taps are walked *inside* the kernel by
  static strided loads of the packed row held in VMEM (the AGU's column
  walk) — patch generation is free address arithmetic, exactly like the
  hardware.
* Digit planes are assembled by ``digits_from_planes`` (int32 shifts and
  adds, int8 only for the MXU operands) and cached in VMEM scratch
  mirroring the v2 matmul kernel: weight-tap digits once per (Co-block,
  f_h) — reused by every output row — and the activation tap digits of
  each (image block, output row, f_h) once — reused by every Co-block.
  ``radix_bits=1`` reproduces Algorithm 1 literally; ``radix_bits=7/8`` is
  the MXU-native digit-serial variant (radix chosen by ``plan_spec``).
* The epilogue fuses the MVU post-pipeline on the last tap: per-output
  channel scaler + bias, optional ReLU comparator, optional
  quantizer/serializer — with ``emit_packed=True`` it writes
  ``(requant.bits, N, Ho, Wo, ceil(Co/32))`` uint32 planes that the next
  conv layer consumes directly, so ResNet stages chain packed with no
  host-format hop.

Block sizes ``(block_co, block_nb)`` + cache flags come from the conv cost
model (:func:`repro.kernels.tuning.choose_conv_tile`) unless given.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import bitops
from repro.core.bitserial import SerialSpec, conv_out_hw, digits_from_planes
from repro.core.quant import QuantSpec, qrange
from repro.kernels.bitserial_matmul import (_Planes, _channel_rows,
                                            _compiler_params,
                                            _digit_matmul_acc, _pack_codes,
                                            _smem_scalar)

__all__ = ["bitserial_conv2d_v2_pallas"]


def _assemble_tap_digits(w_ref, spec: SerialSpec, fw: int):
    """Weight block (1, bw, 1, FW, G, bn) uint32 -> (nd_w, FW, 32G, bn)
    int8 digits."""
    words = w_ref[0, :, 0]
    return jnp.stack(
        [digits_from_planes(_Planes(words[:, i_fw], 0), spec.w_bits,
                            spec.radix_bits, spec.w_signed)
         for i_fw in range(fw)], axis=1)


def _assemble_row_digits(x_ref, spec: SerialSpec, *, nb: int, fw: int,
                         wo: int, stride: int):
    """The FW tap windows of one input row block -> (FW, nd_a, nb*wo,
    32G) int8 digits. Tap ``f_w`` takes columns ``f_w + stride*o``: a
    strided load of the packed words (the AGU's column walk), then the
    images of the block stacked along the GEMM's M axis."""
    taps = []
    for i_fw in range(fw):
        cols = pl.ds(i_fw, wo, stride)
        words = jnp.concatenate(
            [x_ref[:, b, 0, cols, :] for b in range(nb)], axis=1)
        taps.append(digits_from_planes(_Planes(words, 1), spec.a_bits,
                                       spec.radix_bits, spec.a_signed))
    return jnp.stack(taps)


def _kernel(x_ref, w_ref, scale_ref, bias_ref, rs_ref, out_ref, acc_ref,
            *scratch, spec: SerialSpec, fh: int, fw: int, stride: int,
            wo: int, nb: int, relu: bool, out_dtype,
            requant: Optional[QuantSpec], emit_packed: bool,
            cache_weights: bool, cache_acts: bool):
    j = pl.program_id(0)    # Co-block (outermost)
    m = pl.program_id(1)    # (image-block, output-row) pair
    kk = pl.program_id(2)   # filter-row tap f_h (innermost reduction)

    scr = list(scratch)
    w_scr = scr.pop(0) if cache_weights else None
    a_scr = scr.pop(0) if cache_acts else None

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- weight-tap digit planes: assembled once per (j, f_h) on the first
    # (image, row) step, reused by every later one -----------------------
    if cache_weights:
        @pl.when(m == 0)
        def _fill_w():
            w_scr[pl.ds(kk, 1)] = _assemble_tap_digits(
                w_ref, spec, fw)[None]
        wd = w_scr[pl.ds(kk, 1)][0]
    else:
        wd = _assemble_tap_digits(w_ref, spec, fw)

    # --- activation tap digit planes of (image block, output row, f_h):
    # assembled while j == 0 and reused by every later Co-block ----------
    row = functools.partial(_assemble_row_digits, x_ref, spec, nb=nb,
                            fw=fw, wo=wo, stride=stride)
    if cache_acts:
        slot = m * fh + kk

        @pl.when(j == 0)
        def _fill_a():
            a_scr[pl.ds(slot, 1)] = row()[None]
        xd = a_scr[pl.ds(slot, 1)][0]
    else:
        xd = row()

    # --- the f_w taps: one digit-plane GEMM each ------------------------
    tile = None
    for i_fw in range(fw):
        p = _digit_matmul_acc(xd[i_fw], wd[:, i_fw], spec.radix_bits)
        tile = p if tile is None else tile + p
    acc_ref[...] += tile

    @pl.when(kk == fh - 1)
    def _epilogue():
        out = acc_ref[...].astype(jnp.float32) * scale_ref[0] + bias_ref[0]
        if relu:
            out = jnp.maximum(out, 0.0)
        if requant is None:
            y = out.astype(out_dtype)
        else:
            qn, qp = qrange(requant.bits, requant.signed)
            codes = jnp.clip(jnp.round(out / rs_ref[0]), qn, qp).astype(
                jnp.int32)
            if emit_packed:
                words = _pack_codes(codes, requant.bits)
                for b in range(nb):
                    out_ref[:, 0, b, 0] = words[:, b * wo:(b + 1) * wo]
                return
            y = codes.astype(jnp.int8 if requant.bits <= 8 else jnp.int32)
        for b in range(nb):
            out_ref[0, b, 0] = y[b * wo:(b + 1) * wo]


def bitserial_conv2d_v2_pallas(
    x_packed: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    spec: SerialSpec,
    ci: int,
    stride: int = 1,
    padding: int = 1,
    block_co: int = 128,
    block_nb: int = 1,
    relu: bool = False,
    out_dtype=jnp.float32,
    requant: Optional[QuantSpec] = None,
    requant_scale: Optional[jax.Array] = None,
    emit_packed: bool = False,
    cache_weights: bool = True,
    cache_acts: bool = True,
    tpu=None,
    interpret: bool = False,
) -> jax.Array:
    """Fused implicit-GEMM MVU conv forward from packed planes.

    ``x_packed``: (a_bits, N, H, W, ceil(Ci/32)) uint32 NHWC activations,
    channel axis packed; ``w_packed``: (w_bits, FH, FW, ceil(Ci/32), Co)
    uint32 HWIO weights; ``scale``/``bias``: (Co,).

    Returns (N, Ho, Wo, Co) — fp32 (or ``out_dtype``), int8 codes with
    ``requant``, or (requant.bits, N, Ho, Wo, ceil(Co/32)) uint32 packed
    planes with ``emit_packed=True`` (the next layer's input format).
    ``requant`` semantics: ``codes = clip(round(out / requant_scale))`` —
    bit-identical to ``quantize_pack_ref`` of the float epilogue output.
    """
    ba, n, h, w_in, ciw = x_packed.shape
    if ba != spec.a_bits:
        raise ValueError(f"x_packed carries {ba} bit-planes, spec wants "
                         f"a_bits={spec.a_bits}")
    bw, fh, fw, ciw_w, co = w_packed.shape
    if bw != spec.w_bits:
        raise ValueError(f"w_packed carries {bw} bit-planes, spec wants "
                         f"w_bits={spec.w_bits}")
    if not (ciw == ciw_w == -(-ci // 32)):
        raise ValueError(f"channel-word mismatch: x {ciw}, w {ciw_w}, "
                         f"ceil(ci/32)={-(-ci // 32)}")
    if requant is not None and requant_scale is None:
        raise ValueError("requant requires requant_scale")
    if emit_packed:
        if requant is None:
            raise ValueError("emit_packed requires requant")
        if block_co % 32:
            raise ValueError("emit_packed requires block_co % 32 == 0")

    ho, wo = conv_out_hw(h, w_in, fh, fw, stride, padding)
    hp = h + 2 * padding
    # pad W so every f_w tap's strided column window [f_w, f_w + wo*stride)
    # stays in bounds (zero words decode to value 0 — safe padding)
    wp = (fw - 1) + wo * stride
    nb = max(1, min(block_nb, n))
    np_img = -(-n // nb) * nb
    co_p = -(-co // block_co) * block_co
    n_nb = np_img // nb
    n_j = co_p // block_co
    x_packed = jnp.pad(
        x_packed,
        ((0, 0), (0, np_img - n), (padding, hp - h - padding),
         (padding, wp - w_in - padding), (0, 0)))
    # Co-blocks ride a leading axis of the weights, scaler/bias and output,
    # so that every block's last two dims are the array's own and any
    # block_co % 32 meets Mosaic's (8, 128) tiling rule (with one Co-block
    # these are free reshapes)
    w_packed = jnp.pad(w_packed, ((0, 0), (0, 0), (0, 0), (0, 0),
                                  (0, co_p - co)))
    w_packed = jnp.moveaxis(
        w_packed.reshape(bw, fh, fw, ciw, n_j, block_co), 4, 0)
    scale, bias = (v.reshape(n_j, 1, block_co)
                   for v in _channel_rows(scale, bias, co, co_p))
    rs = _smem_scalar(requant_scale)
    grid = (n_j, n_nb * ho, fh)

    nd_a = bitops.num_digits(spec.a_bits, spec.radix_bits, spec.a_signed)
    nd_w = bitops.num_digits(spec.w_bits, spec.radix_bits, spec.w_signed)
    ci_pad = ciw * 32

    # VMEM safety net for explicit-block callers, using the SAME estimate
    # and budget as the tuner (a tuner-approved config therefore always
    # passes unmodified — pass the tuner's ``tpu`` when using a non-default
    # part): drop caches, activations first, until the working set fits.
    from repro.core.cost_model import conv_kernel_vmem_bytes, vmem_budget_bytes
    budget = vmem_budget_bytes(tpu)

    def _vmem(cw, ca):
        return conv_kernel_vmem_bytes(
            n, h, w_in, ci, co, fh=fh, fw=fw, stride=stride, padding=padding,
            a_bits=spec.a_bits, w_bits=spec.w_bits, nd_a=nd_a, nd_w=nd_w,
            bnb=nb, bco=block_co, cache_weights=cw, cache_acts=ca,
            out_bits=requant.bits if (requant and emit_packed) else None)
    if cache_acts and _vmem(cache_weights, True) > budget:
        cache_acts = False
    if cache_weights and _vmem(True, cache_acts) > budget:
        cache_weights = False

    scratch = [pltpu.VMEM((nb * wo, block_co), jnp.int32)]
    if cache_weights:
        scratch.append(pltpu.VMEM((fh, nd_w, fw, ci_pad, block_co), jnp.int8))
    if cache_acts:
        scratch.append(pltpu.VMEM((n_nb * ho * fh, fw, nd_a, nb * wo,
                                   ci_pad), jnp.int8))

    if emit_packed:
        out_shape = jax.ShapeDtypeStruct(
            (requant.bits, n_j, np_img, ho, wo, block_co // 32), jnp.uint32)
        out_spec = pl.BlockSpec(
            (requant.bits, 1, nb, 1, wo, block_co // 32),
            lambda j, m, kk: (0, j, m // ho, m % ho, 0, 0))
    else:
        out_dt = (jnp.int8 if requant is not None and requant.bits <= 8
                  else (jnp.int32 if requant is not None else out_dtype))
        out_shape = jax.ShapeDtypeStruct((n_j, np_img, ho, wo, block_co),
                                         out_dt)
        out_spec = pl.BlockSpec((1, nb, 1, wo, block_co),
                                lambda j, m, kk: (j, m // ho, m % ho, 0, 0))

    kernel = functools.partial(
        _kernel, spec=spec, fh=fh, fw=fw, stride=stride, wo=wo, nb=nb,
        relu=relu, out_dtype=out_dtype, requant=requant,
        emit_packed=emit_packed, cache_weights=cache_weights,
        cache_acts=cache_acts)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            # input row ih = oh*stride + f_h: the AGU row walk lives in the
            # index map (H block size 1 => block index == element row)
            pl.BlockSpec((ba, nb, 1, wp, ciw),
                         lambda j, m, kk: (0, m // ho,
                                           (m % ho) * stride + kk, 0, 0)),
            pl.BlockSpec((1, bw, 1, fw, ciw, block_co),
                         lambda j, m, kk: (j, 0, kk, 0, 0, 0)),
            pl.BlockSpec((1, 1, block_co), lambda j, m, kk: (j, 0, 0)),
            pl.BlockSpec((1, 1, block_co), lambda j, m, kk: (j, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        # scratch reuse spans grid steps along every dimension, so all three
        # must stay sequential on one core ("arbitrary", not "parallel")
        compiler_params=_compiler_params("arbitrary", "arbitrary",
                                         "arbitrary"),
        interpret=interpret,
    )(x_packed, w_packed, scale, bias, rs)
    # Co-blocks back onto the channel axis
    out = jnp.moveaxis(out, -5, -2)
    out = out.reshape(out.shape[:-2] + (-1,))
    if emit_packed:
        return out[:, :n, :, :, : -(-co // 32)]
    return out[:n, :, :, :co]
