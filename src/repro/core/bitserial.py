"""Serial arbitrary-precision matmul — BARVINN Algorithm 1 on TPU.

Two radices, one algorithm (see DESIGN.md §2):

* ``radix_bits=1`` — the **paper-faithful** bit-serial scheme. Every
  (activation-bit j, weight-bit k) pair produces a {0,1} plane product;
  partial products of equal magnitude ``m=j+k`` are summed first and the
  accumulator is shifted once per magnitude step (magnitude-major Horner,
  exactly Algorithm 1, including the negated MSB plane for two's-complement
  operands). ``b_a·b_w`` plane products — the cycle count of the MVU.

* ``radix_bits=s>1`` — the **TPU-native digit-serial** generalization. Bits
  are grouped into int8 digits in VMEM and each digit pair is one int8 MXU
  matmul, Horner-combined with coefficient ``2^{s(J+K)}``. For signed
  ``b<=8`` this is a single MXU matmul; storage stays bit-packed at ``b``
  bits, so the paper's memory scaling is preserved.

Both paths return the *exact* int32 integer matmul result; this invariant is
property-tested in ``tests/test_bitserial.py`` and is the oracle for the
Pallas kernel.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitops

__all__ = ["SerialSpec", "serial_matmul", "serial_matmul_packed",
           "serial_matmul_packed_acts", "serial_conv2d",
           "serial_conv2d_packed_acts", "conv_out_hw", "plan_spec"]


@dataclasses.dataclass(frozen=True)
class SerialSpec:
    """Operand precision configuration — the per-MVU CSR settings
    (weight/activation precision + signedness, paper §3.2)."""

    a_bits: int = 8
    w_bits: int = 4
    a_signed: bool = True
    w_signed: bool = True
    radix_bits: int = 1  # 1 = faithful bit-serial; 7/8 = MXU digit-serial

    def __post_init__(self):
        for b in (self.a_bits, self.w_bits):
            if not 1 <= b <= 16:
                raise ValueError(f"bit depth {b} outside the MVU's 1..16 range")

    @property
    def cycles_per_tile(self) -> int:
        """MVU cycles per 64x64 tile (paper §3.1.1): b_w * b_a."""
        return self.a_bits * self.w_bits

    @property
    def num_plane_products(self) -> int:
        na = bitops.num_digits(self.a_bits, self.radix_bits, self.a_signed)
        nw = bitops.num_digits(self.w_bits, self.radix_bits, self.w_signed)
        return na * nw


def plan_spec(spec: SerialSpec) -> SerialSpec:
    """Digit-plan selection for the TPU-native path (DESIGN.md §2.4).

    ``radix_bits == 1`` is the paper-faithful mode and is never rewritten.
    For digit-serial specs the integer result is radix-invariant, so we are
    free to pick the radix that minimizes MXU issues (``nd_a * nd_w`` plane
    products): e.g. W4A8 signed/signed at the default radix 7 takes two
    matmuls, but radix 8 (signed single-digit) takes one.
    """
    if spec.radix_bits <= 1:
        return spec
    best, best_cost = spec, spec.num_plane_products
    for r in (7, 8):
        try:
            na = bitops.num_digits(spec.a_bits, r, spec.a_signed)
            nw = bitops.num_digits(spec.w_bits, r, spec.w_signed)
        except ValueError:
            continue
        if na * nw < best_cost:
            best = dataclasses.replace(spec, radix_bits=r)
            best_cost = na * nw
    return best


def _plane_dot(xp: jax.Array, wp: jax.Array) -> jax.Array:
    """One partial-product matmul: (..., K) x (K, N) -> (..., N) int32.

    int8 operands with an int32 accumulator — the MXU-native contraction
    (the FPGA's adder tree + accumulator in one hardware instruction).
    """
    return jax.lax.dot_general(
        xp,
        wp,
        (((xp.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def serial_matmul(x: jax.Array, w: jax.Array, spec: SerialSpec) -> jax.Array:
    """Exact integer matmul via serial plane products.

    ``x``: (..., K) integer-valued; ``w``: (K, N) integer-valued. Values must
    be representable in the spec's bit widths (enforced by the quantizer
    upstream); out-of-range bits are truncated exactly as the RAMs would.
    """
    s = spec.radix_bits
    if s == 1:
        # ---- faithful Algorithm 1 ----------------------------------------
        xb = bitops.to_bitplanes(x, spec.a_bits)  # (ba, ..., K) {0,1}
        wb = bitops.to_bitplanes(w, spec.w_bits)  # (bw, K, N)
        ca = bitops.plane_coeffs(spec.a_bits, spec.a_signed)
        cw = bitops.plane_coeffs(spec.w_bits, spec.w_signed)
        sa = np.sign(ca)  # MSB plane of a signed operand weighs negative
        sw = np.sign(cw)
        max_mag = (spec.a_bits - 1) + (spec.w_bits - 1)
        # partial products of equal magnitude are accumulated first ...
        partials = [None] * (max_mag + 1)
        for j in range(spec.a_bits):
            for k in range(spec.w_bits):
                p = _plane_dot(xb[j], wb[k])
                if sa[j] * sw[k] < 0:
                    p = -p
                m = j + k
                partials[m] = p if partials[m] is None else partials[m] + p
        # ... then the accumulator shifts left once per magnitude step.
        acc = partials[max_mag]
        for m in range(max_mag - 1, -1, -1):
            acc = (acc << 1) + partials[m]
        return acc
    # ---- digit-serial (radix 2^s) ----------------------------------------
    xd = bitops.to_digits(x, spec.a_bits, s, spec.a_signed)
    wd = bitops.to_digits(w, spec.w_bits, s, spec.w_signed)
    return _digit_combine(xd, wd, s)


def digits_from_planes(planes: jax.Array, bits: int, radix_bits: int,
                       signed: bool) -> jax.Array:
    """Assemble int8 digit planes directly from {0,1} bit planes
    (``planes``: (bits, ...), any integer dtype). The Pallas kernels run
    this per VMEM tile, so it sticks to what the TPU vector unit supports:
    shifts and adds in int32, with one cast to int8 per digit for the MXU.

    Signed top digit: the MSB plane enters with negative weight
    −2^{bits−1−lo} (two's complement), so every digit fits int8 for
    radix_bits ≤ 8.
    """
    s = radix_bits
    n = bitops.num_digits(bits, s, signed)
    out = []
    for j in range(n):
        lo = j * s
        d = None
        for t in range(lo, min(lo + s, bits)):
            term = jnp.left_shift(planes[t].astype(jnp.int32), t - lo)
            if signed and t == bits - 1:
                d = -term if d is None else d - term
            else:
                d = term if d is None else d + term
        out.append(d.astype(jnp.int8))
    return jnp.stack(out)


def _digit_combine(xd: jax.Array, wd: jax.Array, radix_bits: int) -> jax.Array:
    """Horner-combine digit plane products: sum_{J,K} 2^{s(J+K)} (x_J . w_K)."""
    na, nw = xd.shape[0], wd.shape[0]
    max_mag = (na - 1) + (nw - 1)
    partials = [None] * (max_mag + 1)
    for j in range(na):
        for k in range(nw):
            p = _plane_dot(xd[j], wd[k])
            m = j + k
            partials[m] = p if partials[m] is None else partials[m] + p
    acc = partials[max_mag]
    for m in range(max_mag - 1, -1, -1):
        acc = (acc << radix_bits) + partials[m]
    return acc


def serial_matmul_packed(
    x_int: jax.Array,
    w_packed: jax.Array,
    *,
    spec: SerialSpec,
    k: int,
) -> jax.Array:
    """Serial matmul consuming **bit-transposed packed weights** — the
    deployment path. ``w_packed``: (w_bits, ceil(K/32), N) uint32 (lane axis
    packed); ``x_int``: (..., K) integer activations (already quantized).

    The unpack → digit-assembly → matmul sequence mirrors what the Pallas
    kernel does per VMEM tile; lowering this with XLA keeps the HBM side of
    the roofline honest (weight bytes scale with w_bits).
    """
    planes = bitops.unpack_bitplanes(w_packed, k, axis=1)  # (bw, K, N) {0,1}
    s = spec.radix_bits
    if s == 1:
        wb = planes
        xb = bitops.to_bitplanes(x_int, spec.a_bits)
        ca = bitops.plane_coeffs(spec.a_bits, spec.a_signed)
        cw = bitops.plane_coeffs(spec.w_bits, spec.w_signed)
        acc = None
        max_mag = (spec.a_bits - 1) + (spec.w_bits - 1)
        partials = [None] * (max_mag + 1)
        for j in range(spec.a_bits):
            for kk in range(spec.w_bits):
                p = _plane_dot(xb[j], wb[kk])
                if np.sign(ca[j]) * np.sign(cw[kk]) < 0:
                    p = -p
                m = j + kk
                partials[m] = p if partials[m] is None else partials[m] + p
        acc = partials[max_mag]
        for m in range(max_mag - 1, -1, -1):
            acc = (acc << 1) + partials[m]
        return acc
    wd = digits_from_planes(planes, spec.w_bits, s, spec.w_signed)
    xd = bitops.to_digits(x_int, spec.a_bits, s, spec.a_signed)
    return _digit_combine(xd, wd, s)


def serial_matmul_packed_acts(
    x_packed: jax.Array,
    w_packed: jax.Array,
    *,
    spec: SerialSpec,
    k: int,
) -> jax.Array:
    """Serial matmul with **both operands bit-packed** — the v2 deployment
    path (DESIGN.md §2.3). ``x_packed``: (a_bits, M, ceil(K/32)) uint32, the
    exact format :func:`repro.kernels.quantize_pack.quantize_pack_pallas`
    emits; ``w_packed``: (w_bits, ceil(K/32), N) uint32.

    Activation HBM bytes scale with ``a_bits`` just like weight bytes scale
    with ``w_bits`` — this is the XLA oracle of the v2 Pallas kernel, and
    digit planes are assembled on BOTH sides by :func:`digits_from_planes`
    (int32 shifts and adds, int8 digits for the matmuls).
    """
    a_planes = bitops.unpack_bitplanes(x_packed, k, axis=-1)  # (ba, M, K)
    w_planes = bitops.unpack_bitplanes(w_packed, k, axis=1)   # (bw, K, N)
    s = spec.radix_bits
    xd = digits_from_planes(a_planes, spec.a_bits, s, spec.a_signed)
    wd = digits_from_planes(w_planes, spec.w_bits, s, spec.w_signed)
    return _digit_combine(xd, wd, s)


def conv_out_hw(h: int, w: int, fh: int, fw: int, stride: int,
                padding: int) -> tuple:
    """Output spatial extent of a VALID conv over padded input."""
    ho = (h + 2 * padding - fh) // stride + 1
    wo = (w + 2 * padding - fw) // stride + 1
    return ho, wo


def _tap_slices(x: jax.Array, fh: int, fw: int, stride: int, ho: int,
                wo: int):
    """Yield ((i_fh, i_fw), slice) pairs: the (N, Ho, Wo, Ci) input window
    of each filter tap, taken by pure integer strided slicing — the AGU's
    per-tap walk, never a materialized patch tensor."""
    for i_fh in range(fh):
        for i_fw in range(fw):
            yield (i_fh, i_fw), jax.lax.slice(
                x,
                (0, i_fh, i_fw, 0),
                (x.shape[0], i_fh + (ho - 1) * stride + 1,
                 i_fw + (wo - 1) * stride + 1, x.shape[3]),
                (1, stride, stride, 1))


def serial_conv2d(
    x: jax.Array,
    w: jax.Array,
    spec: SerialSpec,
    *,
    stride: int = 1,
    padding: int = 1,
) -> jax.Array:
    """Quantized 2D convolution via the serial matmul (NHWC / HWIO).

    The MVU executes convs as AGU-driven walks over 64x64 GEMV tiles
    (paper §3.1.3); the JAX equivalent is im2col + the same serial GEMM.
    Patches are extracted by integer strided slicing — no float32
    round-trip (the seed's ``conv_general_dilated_patches`` path cast to
    f32 and back, an extra 9x-blown conv plus a precision hazard for wide
    accumulations). ``x``: (N, H, W, C_i) ints; ``w``: (F_H, F_W, C_i, C_o).
    """
    n, h, wdt, ci = x.shape
    fh, fw, _, co = w.shape
    x = x.astype(jnp.int32)
    x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding), (0, 0)))
    ho, wo = conv_out_hw(h, wdt, fh, fw, stride, padding)
    # im2col in integer dtype, tap-major feature order (FH, FW, Ci) — matches
    # HWIO's natural reshape, so no weight transpose is needed.
    patches = jnp.concatenate(
        [s for _, s in _tap_slices(x, fh, fw, stride, ho, wo)], axis=-1)
    wmat = w.reshape(fh * fw * ci, co)
    out = serial_matmul(patches.reshape(n * ho * wo, fh * fw * ci), wmat, spec)
    return out.reshape(n, ho, wo, co)


def serial_conv2d_packed_acts(
    x_packed: jax.Array,
    w_packed: jax.Array,
    *,
    spec: SerialSpec,
    ci: int,
    stride: int = 1,
    padding: int = 1,
) -> jax.Array:
    """Implicit-GEMM serial conv with **both operands bit-packed** — the XLA
    oracle of :func:`repro.kernels.bitserial_conv.bitserial_conv2d_v2_pallas`.

    ``x_packed``: (a_bits, N, H, W, ceil(Ci/32)) uint32 — NHWC activations
    packed along the channel (lane) axis, the exact format
    :func:`repro.kernels.ops.pack_activations` emits. ``w_packed``:
    (w_bits, FH, FW, ceil(Ci/32), Co) uint32. Returns the exact int32
    conv accumulator (N, Ho, Wo, Co).

    The reduction K = FH*FW*Ci is walked one filter row at a time: the f_h
    rows come from strided slices and the FW taps of a row merge into a
    single digit-plane GEMM of width FW*Ci, mirroring the paper's §3.1.3
    AGU tile walks. The largest intermediate is one row's tap gather
    (N, Ho, Wo, FW*Ci) — bounded at FW x one activation map, never the
    FH*FW x im2col patch tensor (and the Pallas kernel materializes
    nothing at all). Digit planes are assembled on both sides by
    :func:`digits_from_planes` (int32 shifts and adds, int8 digits).
    """
    ba, n, h, wdt, _ = x_packed.shape
    bw, fh, fw, _, co = w_packed.shape
    s = spec.radix_bits
    a_planes = bitops.unpack_bitplanes(x_packed, ci, axis=-1)
    w_planes = bitops.unpack_bitplanes(w_packed, ci, axis=3)
    xd = digits_from_planes(a_planes, spec.a_bits, s, spec.a_signed)
    wd = digits_from_planes(w_planes, spec.w_bits, s, spec.w_signed)
    # spatial zero padding on digit planes: value 0 has all-zero digits
    xd = jnp.pad(xd, ((0, 0), (0, 0), (padding, padding),
                      (padding, padding), (0, 0)))
    ho, wo = conv_out_hw(h, wdt, fh, fw, stride, padding)
    nd_w = wd.shape[0]
    out = None
    for i_fh in range(fh):
        cols = [jax.lax.slice(
            xd,
            (0, 0, i_fh, i_fw, 0),
            (xd.shape[0], n, i_fh + (ho - 1) * stride + 1,
             i_fw + (wo - 1) * stride + 1, ci),
            (1, 1, stride, stride, 1)) for i_fw in range(fw)]
        xrow = jnp.concatenate(cols, axis=-1)          # (nd_a,N,Ho,Wo,FW*Ci)
        wrow = wd[:, i_fh].reshape(nd_w, fw * ci, co)  # K-order (f_w, c_i)
        p = _digit_combine(xrow, wrow, s)
        out = p if out is None else out + p
    return out
