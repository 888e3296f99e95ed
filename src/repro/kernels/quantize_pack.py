"""Pallas kernel for the quantizer/serializer unit (paper §3.1.4 QuantSer):
fused quantize → clip → bit-transpose pack.

Takes float activations, emits uint32-packed bit planes (lane axis packed),
i.e. the format the next layer's serial matmul consumes — on the FPGA this
unit is why only the first layer ever needs a host-side transpose; on TPU
it keeps requantized activations at b-bit in HBM between layers.

Grid tiles the (rows, lanes) plane; each program quantizes a
(block_r, block_l) tile and packs ``block_l/32`` words per bit plane.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.quant import QuantSpec, qrange
from repro.kernels.bitserial_matmul import _pack_codes, _smem_scalar

__all__ = ["quantize_pack_pallas", "quantize_pack_ref"]

_BLOCK_ELEMS = 256 * 2048   # input elements of one default block


def quantize_pack_ref(x: jax.Array, scale: jax.Array,
                      spec: QuantSpec) -> jax.Array:
    """Oracle: (R, L) floats -> (bits, R, ceil(L/32)) uint32 packed planes."""
    from repro.core import bitops
    from repro.core.quant import quantize_int
    codes = quantize_int(x, scale, spec)
    planes = bitops.pad_to(bitops.to_bitplanes(codes, spec.bits), 32, axis=-1)
    return bitops.pack_bitplanes(planes, axis=-1)


def _kernel(x_ref, scale_ref, out_ref, *, bits: int, signed: bool):
    qn, qp = qrange(bits, signed)
    x = x_ref[...].astype(jnp.float32)
    codes = jnp.clip(jnp.round(x / scale_ref[0]), qn, qp).astype(jnp.int32)
    out_ref[...] = _pack_codes(codes, bits)


def quantize_pack_pallas(x: jax.Array, scale: jax.Array, spec: QuantSpec, *,
                         block_r: Optional[int] = None,
                         block_l: Optional[int] = None,
                         interpret: bool = False) -> jax.Array:
    """x: (R, L) float; scale: scalar step size. Returns
    (bits, R, ceil(L/32)) uint32 — identical to the oracle.

    ``block_l`` (a multiple of 32) defaults to the whole padded row, so the
    packed output block ``(bits, block_r, block_l/32)`` spans the array's
    last dim as Mosaic's tiling rule requires on the chip (a multiple of
    4096 lanes meets it too). ``block_r`` defaults to 256 rows, fewer for
    rows wider than 2048 so that one input block stays at 2 MiB of f32
    and the kernel within its scoped VMEM."""
    r, l = x.shape
    block_l = -(-l // 32) * 32 if block_l is None else max(block_l, 32)
    lp = -(-l // block_l) * block_l
    block_l = min(block_l, lp)
    if block_r is None:
        block_r = max(8, min(256, _BLOCK_ELEMS // block_l // 8 * 8))
    rp = -(-r // block_r) * block_r
    x = jnp.pad(x, ((0, rp - r), (0, lp - l)))

    kernel = functools.partial(_kernel, bits=spec.bits, signed=spec.signed)
    out = pl.pallas_call(
        kernel,
        grid=(rp // block_r, lp // block_l),
        in_specs=[
            pl.BlockSpec((block_r, block_l), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((spec.bits, block_r, block_l // 32),
                               lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((spec.bits, rp, lp // 32),
                                       jnp.uint32),
        interpret=interpret,
    )(x, _smem_scalar(scale))
    return out[:, :r, : -(-l // 32)]
