"""Pallas TPU kernel: arbitrary-precision serial matmul over bit-transposed
packed weights, with the MVU post-pipeline (scaler/bias/ReLU/requant) fused
as the epilogue.

TPU mapping of the BARVINN MVU (DESIGN.md §2):

* HBM holds weights **bit-packed** (``uint32`` words, lane axis packed) — the
  bytes moved scale with the configured ``w_bits``, exactly like the FPGA
  weight RAM.
* Each grid step copies one ``(w_bits, block_k/32, block_n)`` packed tile
  into VMEM (BlockSpec pipelining = the AGU walking RAM tiles), unpacks the
  bit planes with vector shifts (VREG work), assembles radix-``2^s`` digit
  planes, and issues one int8 MXU matmul per (activation-digit, weight-digit)
  pair — magnitude-major, Horner-accumulated into an int32 VMEM scratch
  accumulator (the VVP shifter-accumulator).
* ``radix_bits=1`` reproduces Algorithm 1 literally: ``b_a*b_w`` {0,1}-plane
  MXU matmuls per tile, MSB planes entering with negative sign for signed
  operands. ``radix_bits=7/8`` is the MXU-native digit-serial variant.
* On the last reduction step the epilogue applies the per-output-channel
  scaler + bias, the ReLU comparator, and optionally the quantizer/serializer
  (emitting low-bit integer codes ready for bit-transposed repacking).

Grid: ``(M/bm, N/bn, K/bk)``; m/n parallel, k sequential ("arbitrary").
Default blocks (128, 128, 512) keep the working set ≪ VMEM: x-tile 64 KiB
int8, packed w-tile ``w_bits*8`` KiB, unpacked plane 64 KiB, acc 64 KiB.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core import bitops
from repro.core.bitserial import SerialSpec, digits_from_planes
from repro.core.quant import QuantSpec, qrange

__all__ = ["bitserial_matmul_pallas", "bitserial_matmul_v2_pallas"]

def _compiler_params(*semantics: str):
    """Mosaic compile options shared by the bit-serial kernels: the grid's
    dimension semantics, and the scoped-VMEM limit set to the same
    ``TPUConfig.vmem_bytes`` the tile tuner budgets against."""
    from repro.core.cost_model import TPUConfig
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=TPUConfig().vmem_bytes)


def _unpack_words(words, axis: int):
    """uint32 packed words of one plane -> {0,1} int32 bit plane, the word
    axis ``axis`` expanded 32-fold in place (element ``32g+t`` = bit ``t``
    of word ``g``, the :func:`repro.core.bitops.pack_bitplanes` layout).

    Each word is repeated over its 32 lanes and shifted by the lane's bit
    index: int32 shifts and masks only, which Mosaic lowers along either
    the lane or the sublane axis."""
    axis %= words.ndim
    rep = jnp.repeat(jax.lax.bitcast_convert_type(words, jnp.int32), 32,
                     axis=axis)
    shape = [1] * rep.ndim
    shape[axis] = rep.shape[axis]
    t = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis)
    return jnp.bitwise_and(jnp.right_shift(rep, jnp.bitwise_and(t, 31)), 1)


class _Planes:
    """The bit planes of packed words (``words[t]`` = plane ``t``),
    unpacked along ``axis`` only when indexed: digit assembly reads each
    plane once, in order, so one int32 plane is live at a time in VMEM."""

    def __init__(self, words, axis: int):
        self.words, self.axis = words, axis

    def __getitem__(self, t):
        return _unpack_words(self.words[t], self.axis)


_PACK_LANES = 1024   # lanes gathered by one packing matmul
_PACK_WORDS = 128    # words of one output lane tile


def _pack_matrices(lanes: int, words: int, word0: int):
    """The two (lanes, words) bf16 power-of-two matrices that gather
    ``lanes`` code lanes into the ``words`` words of one output lane tile:
    lane ``l`` feeds word ``word0 + l // 32`` with weight ``2^(l % 16)``,
    the low 16 lanes of a word in the first matrix and the high 16 in the
    second."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (lanes, words), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (lanes, words), 1)
    t = jnp.bitwise_and(lane, 31)
    own = jnp.right_shift(lane, 5) + word0 == word
    pow2 = jnp.left_shift(1, jnp.bitwise_and(t, 15))
    return [jnp.where(own & ((t >= 16) == hi), pow2, 0).astype(
        jnp.float32).astype(jnp.bfloat16) for hi in (False, True)]


def _pack_codes(codes, bits: int):
    """(R, N) int32 codes -> (bits, R, N/32) uint32 packed planes.

    The in-kernel serializer, in the word layout of
    :func:`repro.core.bitops.pack_bitplanes` (lane ``t`` of a 32-lane group
    -> bit ``t`` of its word). Gathering 32 lanes into one word is a
    matmul of each {0,1} plane with a block-diagonal matrix of powers of
    two, done in two 16-bit halves so that every product and partial sum
    is an integer below 2^16: exact with bf16 operands and f32
    accumulation. The matmuls go one output lane tile (128 words) and
    1024 lanes at a time, so the matrices stay at most (1024, 128)
    whatever N is.
    """
    n = codes.shape[-1]
    n_words = n // 32
    u = jnp.bitwise_and(codes, (1 << bits) - 1)
    bit_planes = [jnp.bitwise_and(jnp.right_shift(u, b), 1).astype(
        jnp.float32).astype(jnp.bfloat16) for b in range(bits)]
    tiles = [[] for _ in range(bits)]
    for w0 in range(0, n_words, _PACK_WORDS):
        words = min(_PACK_WORDS, n_words - w0)
        halves = [None] * bits
        for l0 in range(w0 * 32, (w0 + words) * 32, _PACK_LANES):
            lanes = min(_PACK_LANES, (w0 + words) * 32 - l0)
            mats = _pack_matrices(lanes, words, (l0 - w0 * 32) // 32)
            for b, bit in enumerate(bit_planes):
                part = [jnp.dot(bit[:, l0:l0 + lanes], m,
                                preferred_element_type=jnp.float32)
                        for m in mats]
                halves[b] = (part if halves[b] is None else
                             [h + p for h, p in zip(halves[b], part)])
        for b in range(bits):
            lo, hi = (h.astype(jnp.int32) for h in halves[b])
            tiles[b].append(jax.lax.bitcast_convert_type(
                jnp.bitwise_or(lo, jnp.left_shift(hi, 16)), jnp.uint32))
    return jnp.stack([t[0] if len(t) == 1 else jnp.concatenate(t, axis=-1)
                      for t in tiles])


def _channel_rows(scale, bias, n: int, np_: int):
    """Per-output-channel scaler and bias as (1, np_) f32 rows (zero-padded
    past ``n``): 2-D, so that their (1, block) tiles lay out on the
    vector unit the same way in XLA and in Mosaic."""
    scale = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), (n,))
    bias = (jnp.zeros((n,), jnp.float32) if bias is None
            else jnp.asarray(bias, jnp.float32))
    return (jnp.pad(v, (0, np_ - n)).reshape(1, np_) for v in (scale, bias))


def _smem_scalar(v) -> jax.Array:
    """A kernel's scalar operand (the requant step) as a (1,) f32 array
    for SMEM (1.0 when absent)."""
    return jnp.broadcast_to(
        jnp.asarray(1.0 if v is None else v, jnp.float32), (1,))


def _weight_operands(planes, spec: SerialSpec):
    """Assemble weight digit planes (int8) + their Horner magnitudes.

    radix_bits == 1: the bit planes themselves (faithful Algorithm 1), with
    the signed-MSB plane carrying a negative unit coefficient.
    radix_bits > 1 : int8 digits assembled from the planes.
    ``planes[k]``: (bk, bn) {0,1} int32.
    Returns list of (plane:int8 (bk,bn), magnitude:int, negate:bool).
    """
    s = spec.radix_bits
    bw = spec.w_bits
    if s == 1:
        return [(planes[kbit].astype(jnp.int8), kbit,
                 spec.w_signed and kbit == bw - 1) for kbit in range(bw)]
    digits = digits_from_planes(planes, bw, s, spec.w_signed)
    return [(digits[j], j * s, False) for j in range(digits.shape[0])]


def _act_operands(x_tile, spec: SerialSpec):
    """Activation planes from int8/int32 codes (the activation RAM side)."""
    s = spec.radix_bits
    ba = spec.a_bits
    xi = x_tile.astype(jnp.int32)
    if s == 1:
        u = jnp.bitwise_and(xi, (1 << ba) - 1)
        out = []
        for j in range(ba):
            p = jnp.bitwise_and(jnp.right_shift(u, j), 1).astype(jnp.int8)
            neg = spec.a_signed and j == ba - 1
            out.append((p, j, neg))
        return out
    nd = bitops.num_digits(ba, s, spec.a_signed)
    if spec.a_signed:
        u = jnp.bitwise_and(xi, (1 << ba) - 1)
        xi = u - jnp.left_shift(
            jnp.bitwise_and(jnp.right_shift(u, ba - 1), 1), ba)
    else:
        xi = jnp.bitwise_and(xi, (1 << ba) - 1)
    out = []
    for j in range(nd):
        d = jnp.right_shift(xi, j * s)
        if j < nd - 1:
            d = jnp.bitwise_and(d, (1 << s) - 1)
        out.append((d.astype(jnp.int8), j * s, False))
    return out


def _kernel(x_ref, w_ref, scale_ref, bias_ref, out_ref, acc_ref, *,
            spec: SerialSpec, relu: bool, out_dtype,
            requant: Optional[QuantSpec], n_k: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w_ops = _weight_operands(_Planes(w_ref, 0), spec)
    x_ops = _act_operands(x_ref[...], spec)

    # magnitude-major Horner over plane pairs (Algorithm 1): gather equal
    # magnitudes first, then a single shift per magnitude step.
    max_mag = max(mx for _, mx, _ in x_ops) + max(mw for _, mw, _ in w_ops)
    partials = [None] * (max_mag + 1)
    for xp, mx, nx in x_ops:
        for wp, mw, nw in w_ops:
            p = jax.lax.dot_general(
                xp, wp, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            if nx != nw:
                p = -p
            m = mx + mw
            partials[m] = p if partials[m] is None else partials[m] + p
    tile_acc = partials[max_mag]
    if tile_acc is None:
        tile_acc = jnp.zeros_like(acc_ref)
    for m in range(max_mag - 1, -1, -1):
        tile_acc = (tile_acc << 1)
        if partials[m] is not None:
            tile_acc = tile_acc + partials[m]
    acc_ref[...] += tile_acc

    @pl.when(kk == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * scale_ref[...] + bias_ref[...]
        if relu:
            out = jnp.maximum(out, 0.0)
        if requant is not None:
            qn, qp = qrange(requant.bits, requant.signed)
            out = jnp.clip(jnp.round(out), qn, qp)
        out_ref[...] = out.astype(out_dtype)


def bitserial_matmul_pallas(
    x: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    spec: SerialSpec,
    k: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    relu: bool = False,
    out_dtype=jnp.float32,
    requant: Optional[QuantSpec] = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused MVU forward: ``relu?((x @ W)*scale + bias)`` from packed planes.

    ``x``: (M, K) int codes; ``w_packed``: (w_bits, ceil(K/32), N) uint32;
    ``scale``/``bias``: (N,). When ``requant`` is given, the epilogue emits
    integer codes (int8) — the quantizer/serializer stage — and ``scale``
    must already fold the requant step size.
    """
    m, kx = x.shape
    if kx != k:
        raise ValueError(f"x has K={kx}, caller declared k={k}")
    bw, kwords, n = w_packed.shape
    if bw != spec.w_bits:
        raise ValueError(f"w_packed carries {bw} bit-planes, spec wants "
                         f"w_bits={spec.w_bits}")
    # pad to block multiples (the code generator pads tiles the same way)
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    kp = -(-k // block_k) * block_k
    if block_k % 32 != 0:
        raise ValueError(f"block_k={block_k} must be a multiple of the "
                         "32-bit packing word")
    x = jnp.pad(x.astype(jnp.int8 if spec.a_bits <= 8 else jnp.int32),
                ((0, mp - m), (0, kp - k)))
    w_packed = jnp.pad(w_packed, ((0, 0), (0, kp // 32 - kwords), (0, np_ - n)))
    scale, bias = _channel_rows(scale, bias, n, np_)

    n_k = kp // block_k
    grid = (mp // block_m, np_ // block_n, n_k)
    out_dt = jnp.int8 if requant is not None and requant.bits <= 8 else out_dtype

    kernel = functools.partial(
        _kernel, spec=spec, relu=relu, out_dtype=out_dt, requant=requant,
        n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bw, block_k // 32, block_n),
                         lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dt),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
        interpret=interpret,
        name="bitserial_matmul",
    )(x, w_packed, scale, bias)
    return out[:m, :n]


# ===========================================================================
# v2: packed activations, hoisted plane work, fused requant-pack epilogue
# ===========================================================================

def _assemble_w_digits(w_words, spec: SerialSpec):
    """(bw, G, bn) uint32 words (array or ref) -> (nd_w, 32G, bn) int8
    digit planes."""
    return digits_from_planes(_Planes(w_words, 0), spec.w_bits,
                              spec.radix_bits, spec.w_signed)


def _assemble_a_digits(a_words, spec: SerialSpec):
    """(ba, bm, G) uint32 words (array or ref) -> (nd_a, bm, 32G) int8
    digit planes."""
    return digits_from_planes(_Planes(a_words, 1), spec.a_bits,
                              spec.radix_bits, spec.a_signed)


def _digit_matmul_acc(xd, wd, radix_bits: int):
    """Magnitude-major Horner over int8 digit plane pairs -> int32 tile.

    Digits already carry the two's-complement sign (assembled by
    :func:`digits_from_planes`), so no negate flags are needed — partial
    products of equal magnitude ``m = j_a + j_w`` accumulate first, then the
    accumulator shifts by ``radix_bits`` once per magnitude step (the VVP
    shifter-accumulator, Algorithm 1 re-based to radix ``2^s``).
    """
    na, nw = xd.shape[0], wd.shape[0]
    max_mag = (na - 1) + (nw - 1)
    partials = [None] * (max_mag + 1)
    for j in range(na):
        for k in range(nw):
            p = jax.lax.dot_general(
                xd[j], wd[k], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            m = j + k
            partials[m] = p if partials[m] is None else partials[m] + p
    acc = partials[max_mag]
    for m in range(max_mag - 1, -1, -1):
        acc = (acc << radix_bits) + partials[m]
    return acc


def _kernel_v2(x_ref, w_ref, scale_ref, bias_ref, rs_ref, out_ref, acc_ref,
               *scratch, spec: SerialSpec, relu: bool, out_dtype,
               requant: Optional[QuantSpec], emit_packed: bool, n_k: int,
               cache_weights: bool, cache_acts: bool):
    j = pl.program_id(0)   # n-block (outermost)
    i = pl.program_id(1)   # m-block
    kk = pl.program_id(2)  # k-step (innermost, sequential reduction)

    scr = list(scratch)
    w_scr = scr.pop(0) if cache_weights else None
    a_scr = scr.pop(0) if cache_acts else None

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- weight digit planes: assembled once per (j, kk), reused for every
    # m-block (hoisted out of the i loop via VMEM scratch) ----------------
    if cache_weights:
        @pl.when(i == 0)
        def _fill_w():
            w_scr[pl.ds(kk, 1)] = _assemble_w_digits(w_ref, spec)[None]
        wd = w_scr[pl.ds(kk, 1)][0]
    else:
        wd = _assemble_w_digits(w_ref, spec)

    # --- activation digit planes: assembled once per (i, kk), reused for
    # every n-block ------------------------------------------------------
    if cache_acts:
        slot = i * n_k + kk
        @pl.when(j == 0)
        def _fill_a():
            a_scr[pl.ds(slot, 1)] = _assemble_a_digits(x_ref, spec)[None]
        xd = a_scr[pl.ds(slot, 1)][0]
    else:
        xd = _assemble_a_digits(x_ref, spec)

    acc_ref[...] += _digit_matmul_acc(xd, wd, spec.radix_bits)

    @pl.when(kk == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * scale_ref[...] + bias_ref[...]
        if relu:
            out = jnp.maximum(out, 0.0)
        if requant is None:
            out_ref[...] = out.astype(out_dtype)
        else:
            qn, qp = qrange(requant.bits, requant.signed)
            codes = jnp.clip(jnp.round(out / rs_ref[0]), qn, qp).astype(
                jnp.int32)
            if emit_packed:
                out_ref[...] = _pack_codes(codes, requant.bits)
            else:
                out_ref[...] = codes.astype(
                    jnp.int8 if requant.bits <= 8 else jnp.int32)


def bitserial_matmul_v2_pallas(
    x_packed: jax.Array,
    w_packed: jax.Array,
    scale: jax.Array,
    bias: Optional[jax.Array] = None,
    *,
    spec: SerialSpec,
    k: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    relu: bool = False,
    out_dtype=jnp.float32,
    requant: Optional[QuantSpec] = None,
    requant_scale: Optional[jax.Array] = None,
    emit_packed: bool = False,
    cache_weights: bool = True,
    cache_acts: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """v2 fused MVU forward over **bit-packed operands on both sides**.

    ``x_packed``: (a_bits, M, ceil(K/32)) uint32 — the exact format
    :func:`repro.kernels.quantize_pack.quantize_pack_pallas` emits, so
    activation HBM bytes scale with ``a_bits`` (DESIGN.md §2.3).
    ``w_packed``: (w_bits, ceil(K/32), N) uint32; ``scale``/``bias``: (N,).

    Improvements over the v1 kernel (DESIGN.md §2.2):

    * grid is reordered to ``(N/bn, M/bm, K/bk)`` and assembled int8 digit
      planes are cached in VMEM scratch — weight planes are unpacked once
      per (n-block, k-step) instead of once per grid step, activation planes
      once per (m-block, k-step),
    * digits are assembled by ``digits_from_planes`` (int32 shifts and
      adds, cast to int8 only for the MXU operands),
    * with ``requant`` + ``emit_packed`` the epilogue fuses the
      quantizer/serializer AND the bit-transpose packer: the kernel emits
      ``(requant.bits, M, ceil(N/32))`` uint32 planes that the next layer's
      v2 matmul consumes directly — layers chain with no separate
      ``quantize_pack`` pass.

    ``requant`` semantics: ``codes = clip(round(out / requant_scale))`` —
    identical to :func:`repro.kernels.ref.bitserial_matmul_ref` and, for the
    packed output, bit-identical to ``quantize_pack_ref(out, requant_scale,
    requant)``.
    """
    ba, m, kwords = x_packed.shape
    if ba != spec.a_bits:
        raise ValueError(f"x_packed carries {ba} bit-planes, spec wants "
                         f"a_bits={spec.a_bits}")
    bw, kwords_w, n = w_packed.shape
    if bw != spec.w_bits:
        raise ValueError(f"w_packed carries {bw} bit-planes, spec wants "
                         f"w_bits={spec.w_bits}")
    if not (kwords == kwords_w == -(-k // 32)):
        raise ValueError(f"K-word mismatch: x {kwords}, w {kwords_w}, "
                         f"ceil(k/32)={-(-k // 32)}")
    if block_k % 32 != 0:
        raise ValueError(f"block_k={block_k} must be a multiple of the "
                         "32-bit packing word")
    if requant is not None and requant_scale is None:
        raise ValueError("requant requires requant_scale")
    if emit_packed:
        if requant is None:
            raise ValueError("emit_packed requires requant")
        if block_n % 32:
            raise ValueError("emit_packed requires block_n % 32 == 0")

    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    kp = -(-k // block_k) * block_k
    x_packed = jnp.pad(x_packed,
                       ((0, 0), (0, mp - m), (0, kp // 32 - kwords)))
    w_packed = jnp.pad(w_packed,
                       ((0, 0), (0, kp // 32 - kwords), (0, np_ - n)))
    scale, bias = _channel_rows(scale, bias, n, np_)
    rs = _smem_scalar(requant_scale)

    n_i, n_j, n_k = mp // block_m, np_ // block_n, kp // block_k
    grid = (n_j, n_i, n_k)

    nd_w = bitops.num_digits(spec.w_bits, spec.radix_bits, spec.w_signed)
    nd_a = bitops.num_digits(spec.a_bits, spec.radix_bits, spec.a_signed)
    # Safety net for callers that pass explicit blocks and bypass the
    # tuner's VMEM filter: the digit-plane caches grow with the *whole*
    # padded problem (weights: nd_w*Kp*bn; acts: nd_a*Mp*Kp) — drop them,
    # activations first, when they cannot fit rather than fail Mosaic
    # compilation. Same estimate and budget as the tuner
    # (kernels/tuning.py), so a tuner-approved config passes unmodified.
    from repro.core.cost_model import kernel_vmem_bytes, vmem_budget_bytes
    budget = vmem_budget_bytes()

    def _vmem(cw, ca):
        return kernel_vmem_bytes(
            m, k, n, a_bits=spec.a_bits, w_bits=spec.w_bits, nd_a=nd_a,
            nd_w=nd_w, bm=block_m, bn=block_n, bk=block_k,
            cache_weights=cw, cache_acts=ca,
            out_bits=requant.bits if emit_packed else None)
    if cache_acts and _vmem(cache_weights, True) > budget:
        cache_acts = False
    if cache_weights and _vmem(True, cache_acts) > budget:
        cache_weights = False
    scratch = [pltpu.VMEM((block_m, block_n), jnp.int32)]
    if cache_weights:
        scratch.append(pltpu.VMEM((n_k, nd_w, block_k, block_n), jnp.int8))
    if cache_acts:
        scratch.append(pltpu.VMEM((n_i * n_k, nd_a, block_m, block_k),
                                  jnp.int8))

    if emit_packed:
        out_shape = jax.ShapeDtypeStruct(
            (requant.bits, mp, np_ // 32), jnp.uint32)
        out_spec = pl.BlockSpec((requant.bits, block_m, block_n // 32),
                                lambda j, i, kk: (0, i, j))
    else:
        out_dt = (jnp.int8 if requant is not None and requant.bits <= 8
                  else (jnp.int32 if requant is not None else out_dtype))
        out_shape = jax.ShapeDtypeStruct((mp, np_), out_dt)
        out_spec = pl.BlockSpec((block_m, block_n),
                                lambda j, i, kk: (i, j))

    kernel = functools.partial(
        _kernel_v2, spec=spec, relu=relu, out_dtype=out_dtype,
        requant=requant, emit_packed=emit_packed, n_k=n_k,
        cache_weights=cache_weights, cache_acts=cache_acts)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ba, block_m, block_k // 32),
                         lambda j, i, kk: (0, i, kk)),
            pl.BlockSpec((bw, block_k // 32, block_n),
                         lambda j, i, kk: (0, kk, j)),
            pl.BlockSpec((1, block_n), lambda j, i, kk: (0, j)),
            pl.BlockSpec((1, block_n), lambda j, i, kk: (0, j)),
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
        name="bitserial_matmul_v2",
    )(x_packed, w_packed, scale, bias, rs)
    if emit_packed:
        return out[:, :m, : -(-n // 32)]
    return out[:m, :n]
