"""BARVINN cycle cost model — reproduces the paper's performance tables.

The MVU computes one 64x64 tile MAC per cycle at 1-bit/1-bit, and a
``b_a``-bit x ``b_w``-bit tile in ``b_a*b_w`` cycles (paper §3.1.1). Layer
cost = tiles walked by the AGU loop nest x ``b_a*b_w``. Three edge-handling
variants are provided because the paper's Table 3 itself mixes them (its
stride-1 rows follow ``(H-2)*W`` positions, its downsampling rows ``(H-1)*W``
— see benchmarks/table3 for the per-row reconciliation):

* ``dense``     — every output position counts (upper bound),
* ``pad_skip``  — AGU skips kernel rows falling in vertical zero padding
                  (the hardware's documented behaviour, §3.1.3),
* ``paper_edge``— only rows with full vertical kernel support (``H-2`` rows
                  for 3x3 pad-1), which matches most of Table 3.

Execution modes (paper §3.1.6): **pipelined** throughput = freq / bottleneck
stage cycles (one layer per MVU, crossbar streaming); **distributed** latency
= sum of layer cycles / MVU count (each layer split across all MVUs).
"""

from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Sequence

from repro.core.mvu import LANES, MVU_COUNT

__all__ = ["HWConfig", "ConvLayer", "LinearLayer", "layer_cycles",
           "pipelined_fps", "distributed_fps", "network_cycles",
           "RESNET9_CIFAR10", "CNV_CIFAR10", "resnet50_layers",
           "TPUConfig", "vmem_budget_bytes", "kernel_vmem_bytes",
           "kernel_cost", "conv_row_cols", "conv_ring_lanes",
           "conv_act_columns",
           "conv_kernel_vmem_bytes", "conv_vpu_ops", "conv_kernel_cost"]


@dataclasses.dataclass(frozen=True)
class HWConfig:
    """The paper's Alveo U250 base configuration."""

    freq_hz: float = 250e6
    mvus: int = MVU_COUNT
    lanes: int = LANES
    power_w: float = 21.504  # Table 4 overall dynamic power

    @property
    def peak_macs(self) -> float:
        """1-bit MAC/s: 8 MVUs x 64x64 lanes x freq = 8.2 TMAC/s (abstract)."""
        return self.mvus * self.lanes * self.lanes * self.freq_hz


@dataclasses.dataclass(frozen=True)
class ConvLayer:
    name: str
    c_in: int
    c_out: int
    h: int            # input spatial height (= width assumed square)
    w: int
    fh: int = 3
    fw: int = 3
    stride: int = 1
    padding: int = 1
    on_host: bool = False  # first/last layers stay full precision on host


@dataclasses.dataclass(frozen=True)
class LinearLayer:
    name: str
    k: int
    n: int
    on_host: bool = False


def _tiles(n: int, lanes: int) -> int:
    return max(1, math.ceil(n / lanes))


def _conv_positions(l: ConvLayer, edge: str) -> int:
    ho = (l.h + 2 * l.padding - l.fh) // l.stride + 1
    wo = (l.w + 2 * l.padding - l.fw) // l.stride + 1
    if edge == "dense":
        return ho * wo * l.fh * l.fw
    if edge == "pad_skip":
        total = 0
        for oy in range(ho):
            iy0 = oy * l.stride - l.padding
            valid = sum(1 for f in range(l.fh) if 0 <= iy0 + f < l.h)
            total += valid
        return total * wo * l.fw
    if edge == "paper_edge":
        # Reverse-engineered from Table 3: stride-1 pad-1 layers count H-2
        # full rows (both vertical-padding rows elided); strided layers count
        # H_out-1 rows (only the top padding row elided).
        if l.padding == 0:
            rows = ho
        elif l.stride == 1:
            rows = max(1, ho - 2)
        else:
            rows = max(1, ho - 1)
        return rows * wo * l.fh * l.fw
    raise ValueError(edge)


def layer_cycles(layer, a_bits: int, w_bits: int, *, lanes: int = LANES,
                 edge: str = "pad_skip") -> int:
    """Cycles for one layer on ONE MVU."""
    if getattr(layer, "on_host", False):
        return 0
    bb = a_bits * w_bits
    if isinstance(layer, ConvLayer):
        cit = _tiles(layer.c_in, lanes)
        cot = _tiles(layer.c_out, lanes)
        return bb * cit * cot * _conv_positions(layer, edge)
    if isinstance(layer, LinearLayer):
        return bb * _tiles(layer.k, lanes) * _tiles(layer.n, lanes)
    raise TypeError(type(layer))


def network_cycles(layers: Sequence, a_bits: int, w_bits: int,
                   edge: str = "pad_skip") -> List[int]:
    return [layer_cycles(l, a_bits, w_bits, edge=edge) for l in layers]


def pipelined_fps(layers: Sequence, a_bits: int, w_bits: int,
                  hw: HWConfig = HWConfig(), edge: str = "pad_skip") -> float:
    """Pipelined mode: layer i on MVU i; throughput set by the bottleneck
    stage. Layers beyond ``hw.mvus`` wrap around (subset laps, §3.1.6):
    stages executing k layers cost the sum of those layers."""
    cyc = [c for c in network_cycles(layers, a_bits, w_bits, edge) if c > 0]
    if not cyc:
        return float("inf")
    stages = [0] * hw.mvus
    for i, c in enumerate(cyc):
        stages[i % hw.mvus] += c
    return hw.freq_hz / max(stages)


def distributed_fps(layers: Sequence, a_bits: int, w_bits: int,
                    hw: HWConfig = HWConfig(), edge: str = "pad_skip") -> float:
    """Distributed mode: every layer split across all MVUs; latency-optimal.
    Ideal split (the user copies shared input regions, §3.1.6)."""
    total = sum(network_cycles(layers, a_bits, w_bits, edge))
    if total == 0:
        return float("inf")
    return hw.freq_hz / (total / hw.mvus)


# --------------------------------------------------------------------------
# TPU kernel cost model (v2 Pallas serial matmul — DESIGN.md §2.5)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TPUConfig:
    """Constants for the Pallas kernel tile autotuner.

    ``vmem_bytes`` is the scoped-VMEM limit the kernels compile under: the
    kernels pass it to Mosaic as ``vmem_limit_bytes``, and 16 MiB is also
    the compiler's default scoped limit on TPU v5e. The other figures only
    set the ratio between the HBM, MXU and VPU terms; the tuner ranks
    *relative* tile costs.
    """

    vmem_bytes: int = 16 * 2 ** 20        # scoped VMEM limit per kernel
    vmem_budget_frac: float = 0.75        # leave headroom for the compiler
    hbm_bw: float = 8.0e11                # bytes/s
    int8_macs: float = 2.6e14             # MXU int8 MAC/s
    vpu_ops: float = 4.0e12               # VPU elementwise ops/s


def vmem_budget_bytes(tpu: "TPUConfig" = None) -> int:
    """The VMEM ceiling a tuned tile must fit under — the single
    definition shared by the tile enumerators (:mod:`repro.kernels.tuning`)
    and the program verifier (:mod:`repro.analysis.verify_ir`), so the
    budget the tuner enumerated with is exactly the one verification
    re-checks against."""
    tpu = tpu or TPUConfig()
    return int(tpu.vmem_bytes * tpu.vmem_budget_frac)


def _tiled(shape, itemsize: int) -> int:
    """Bytes a VMEM buffer takes in Mosaic's native tiling: the last dim
    padded to 128 lanes, the second-last to 8 sublanes of 32-bit words
    (16 rows for 16-bit, 32 for 8-bit types)."""
    *lead, r, c = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    sub = 8 * (4 // itemsize)
    return (math.prod(lead) * (-(-r // sub) * sub) * (-(-c // 128) * 128)
            * itemsize)


def _packer_bytes(rows: int, n: int, bits: int) -> int:
    """Live VMEM of the in-kernel packer (``_pack_codes``) for a
    (rows, n) code block: the {0,1} bf16 planes, the f32 word halves, and
    one chunk's two (<=1024, <=128) bf16 power-of-two matrices with the
    int32 temporaries that build them."""
    mat = (min(n, 1024), min(n // 32, 128))
    return (bits * _tiled((rows, n), 2) + 2 * bits * _tiled((rows, n // 32), 4)
            + 2 * _tiled(mat, 2) + 3 * _tiled(mat, 4))


def _grid_shape(m, k, n, bm, bn, bk):
    return (-(-n // bn), -(-m // bm), -(-k // bk))  # (n_j, n_i, n_k)


def kernel_vmem_bytes(m: int, k: int, n: int, *, a_bits: int, w_bits: int,
                      nd_a: int, nd_w: int, bm: int, bn: int, bk: int,
                      cache_weights: bool, cache_acts: bool,
                      out_bits: Optional[int] = None) -> int:
    """VMEM working set of one v2 kernel invocation (bytes, tile-padded).

    BlockSpec-pipelined buffers are double-buffered (x2); scratch buffers
    (accumulator + cached digit planes) are single instances that persist
    across the whole grid; the int8 digits of one step and the int32
    plane being unpacked are live values the compiler also places in VMEM,
    as are the packer's operands when the output is packed.
    """
    n_j, n_i, n_k = _grid_shape(m, k, n, bm, bn, bk)
    x_tile = _tiled((a_bits, bm, bk // 32), 4)    # packed act tile, uint32
    w_tile = _tiled((w_bits, bk // 32, bn), 4)    # packed weight tile
    out_tile = (_tiled((out_bits, bm, bn // 32), 4) if out_bits
                else _tiled((bm, bn), 4))
    pipelined = 2 * (x_tile + w_tile + out_tile + 2 * _tiled((1, bn), 4))
    acc = _tiled((bm, bn), 4)
    live = (nd_a * _tiled((bm, bk), 1) + 3 * _tiled((bm, bk), 4)
            + nd_w * _tiled((bk, bn), 1) + 3 * _tiled((bk, bn), 4)
            + (_packer_bytes(bm, bn, out_bits) if out_bits else 0))
    w_scr = _tiled((n_k * nd_w, bk, bn), 1) if cache_weights else 0
    a_scr = _tiled((n_i * n_k * nd_a, bm, bk), 1) if cache_acts else 0
    return pipelined + acc + live + w_scr + a_scr


def kernel_cost(m: int, k: int, n: int, *, a_bits: int, w_bits: int,
                nd_a: int, nd_w: int, bm: int, bn: int, bk: int,
                cache_weights: bool, cache_acts: bool,
                out_bits: Optional[int] = None,
                tpu: TPUConfig = TPUConfig()) -> float:
    """Modeled seconds per v2 kernel call — roofline over HBM + MXU, plus a
    VPU term for the digit-plane assembly work.

    The assembly term is where the v2 hoisting shows up: cached weight
    planes are unpacked once per (n-block, k-step) instead of once per grid
    step; cached activation planes once per (m-block, k-step). The HBM term
    uses *padded* shapes, so the model also penalizes block sizes that
    over-pad ragged operands.
    """
    n_j, n_i, n_k = _grid_shape(m, k, n, bm, bn, bk)
    mp, np_, kp = n_i * bm, n_j * bn, n_k * bk

    # HBM traffic: BlockSpec re-fetches a tile each grid step it is mapped
    act_bytes = n_j * (a_bits * mp * (kp // 32) * 4)
    w_bytes = n_i * (w_bits * (kp // 32) * np_ * 4)
    out_bytes = (out_bits * mp * (np_ // 32) * 4 if out_bits else mp * np_ * 4)
    hbm = act_bytes + w_bytes + out_bytes

    macs = float(nd_a * nd_w) * mp * kp * np_

    # digit-plane assembly (unpack shifts + int8 scale-adds), VPU-bound
    w_asm = (w_bits + nd_w) * kp * np_ * (1 if cache_weights else n_i)
    a_asm = (a_bits + nd_a) * mp * kp * (1 if cache_acts else n_j)
    epilogue = mp * np_ * (3 + (out_bits or 0))
    vpu = w_asm + a_asm + epilogue

    return max(hbm / tpu.hbm_bw, macs / tpu.int8_macs) + vpu / tpu.vpu_ops


# --------------------------------------------------------------------------
# TPU implicit-GEMM conv kernel cost model (kernels/bitserial_conv.py)
# --------------------------------------------------------------------------

def _conv_geom(n, h, w, ci, fh, fw, stride, padding, bnb, bco, co):
    ho = (h + 2 * padding - fh) // stride + 1
    wo = (w + 2 * padding - fw) // stride + 1
    hp = h + 2 * padding
    wp = (fw - 1) + wo * stride
    ciw = -(-ci // 32)
    n_nb = -(-n // bnb)
    n_j = -(-co // bco)
    return ho, wo, hp, wp, ciw, n_nb, n_j


def conv_row_cols(fw: int, wo: int, stride: int, wp: int) -> int:
    """Columns of one padded input row that the conv kernel's row ring
    assembles per image: the whole row where the FW tap windows overlap
    (``fw >= stride``), else only the windows' own columns, side by side."""
    return wp if fw >= stride else fw * wo


def _ring_rows(ho: int, fh: int, stride: int) -> int:
    """Input rows the conv kernel's row ring assembles (and fetches) per
    image block: the FH rows of its first output row, then the
    ``min(stride, fh)`` rows each later output row adds."""
    return fh + (ho - 1) * min(stride, fh)


def conv_ring_lanes(ci_pad: int) -> int:
    """Lane width of one slab of the conv kernel's row ring: the channel
    lanes split into slabs of at most 128, since Mosaic's strided loads
    (the tap windows of a strided conv) take a base of one lane tile."""
    return ci_pad if ci_pad <= 128 else math.gcd(ci_pad, 128)


def conv_act_columns(n: int, h: int, w: int, ci: int, co: int, *, fh: int,
                     fw: int, stride: int, padding: int, bnb: int,
                     bco: int) -> tuple:
    """``(served, assembled)`` activation digit columns of one conv call,
    counted in (image, column) pairs: ``served`` by the FH x FW tap
    windows (each ``wo`` columns wide), ``assembled`` by the row ring,
    which unpacks an input row when its first tap reads it
    (:func:`_ring_rows` rows per image block). Their ratio is how often each
    assembled digit is reused (about 8 for a 32x32 3x3 stride-1 conv; 1
    where every tap window is unpacked on its own)."""
    ho, wo, hp, wp, ciw, n_nb, n_j = _conv_geom(
        n, h, w, ci, fh, fw, stride, padding, bnb, bco, co)
    images = n_j * n_nb * bnb
    return (images * ho * fh * fw * wo,
            images * _ring_rows(ho, fh, stride)
            * conv_row_cols(fw, wo, stride, wp))


def conv_kernel_vmem_bytes(n: int, h: int, w: int, ci: int, co: int, *,
                           fh: int, fw: int, stride: int, padding: int,
                           a_bits: int, w_bits: int, nd_a: int, nd_w: int,
                           bnb: int, bco: int, cache_weights: bool,
                           out_bits: Optional[int] = None) -> int:
    """VMEM working set of one implicit-GEMM conv invocation (bytes,
    tile-padded).

    Same accounting as :func:`kernel_vmem_bytes`: BlockSpec-pipelined
    buffers double-buffered (x2); scratches (accumulator, the FH-slot
    row ring of int32 activation digits, the weight digit cache) single
    instances; one row's assembly, the tap windows of one step and the
    packer's operands live.
    """
    ho, wo, hp, wp, ciw, n_nb, n_j = _conv_geom(
        n, h, w, ci, fh, fw, stride, padding, bnb, bco, co)
    ci_pad = ciw * 32
    rows = bnb * wo                               # GEMM rows per step
    ring_rows = bnb * conv_row_cols(fw, wo, stride, wp)
    x_tile = _tiled((a_bits * bnb, wp, ciw), 4)   # one packed input row
    w_tile = _tiled((w_bits * fw, ciw, bco), 4)   # one packed filter-row tap
    out_tile = (_tiled((out_bits * bnb, wo, bco // 32), 4) if out_bits
                else _tiled((bnb, wo, bco), 4))
    pipelined = 2 * (x_tile + w_tile + out_tile + 2 * _tiled((1, bco), 4))
    acc = _tiled((rows, bco), 4)
    lanes = conv_ring_lanes(ci_pad)
    ring = _tiled((fh * nd_a * (ci_pad // lanes), ring_rows, lanes), 4)
    live = ((nd_a + 3) * _tiled((ring_rows, ci_pad), 4)
            + fw * nd_a * _tiled((rows, ci_pad), 1)
            + nd_a * _tiled((rows, ci_pad), 4)
            + fw * nd_w * _tiled((ci_pad, bco), 1)
            + 3 * _tiled((ci_pad, bco), 4)
            + (_packer_bytes(rows, bco, out_bits) if out_bits else 0))
    w_scr = _tiled((fh * nd_w * fw, ci_pad, bco), 1) if cache_weights else 0
    return pipelined + acc + ring + live + w_scr


def conv_vpu_ops(n: int, h: int, w: int, ci: int, co: int, *, fh: int,
                 fw: int, stride: int, padding: int, a_bits: int,
                 w_bits: int, nd_a: int, nd_w: int, bnb: int, bco: int,
                 cache_weights: bool,
                 out_bits: Optional[int] = None) -> dict:
    """Vector-unit element operations of one conv call, by term:
    ``weights`` (weight-tap digit assembly: once per (Co-block, f_h) when
    cached, else every grid step), ``act_rows`` (unpacking the a_bits
    planes of each row the ring assembles into its digits: once per
    assembled column, not once per tap window), ``act_windows`` (the
    int8 cast of every tap window's digits) and ``epilogue``."""
    ho, wo, hp, wp, ciw, n_nb, n_j = _conv_geom(
        n, h, w, ci, fh, fw, stride, padding, bnb, bco, co)
    ci_pad = ciw * 32
    n_m = n_nb * ho
    served, assembled = conv_act_columns(
        n, h, w, ci, co, fh=fh, fw=fw, stride=stride, padding=padding,
        bnb=bnb, bco=bco)
    tap_work = (w_bits + nd_w) * fw * ci_pad * bco
    return {
        "weights": tap_work * n_j * fh * (1 if cache_weights else n_m),
        "act_rows": (a_bits + nd_a) * assembled * ci_pad,
        "act_windows": nd_a * served * ci_pad,
        "epilogue": n_m * bnb * wo * n_j * bco * (3 + (out_bits or 0)),
    }


def conv_kernel_cost(n: int, h: int, w: int, ci: int, co: int, *,
                     fh: int, fw: int, stride: int, padding: int,
                     a_bits: int, w_bits: int, nd_a: int, nd_w: int,
                     bnb: int, bco: int, cache_weights: bool,
                     out_bits: Optional[int] = None,
                     tpu: TPUConfig = TPUConfig()) -> float:
    """Modeled seconds per implicit-GEMM conv call — roofline over HBM +
    MXU plus a VPU term for digit-plane assembly (:func:`conv_vpu_ops`).

    Cached weight-tap planes are assembled once per (Co-block, f_h)
    instead of once per grid step. Activation digits are assembled once
    per input row of each (image block, Co-block) by the kernel's row
    ring and every tap window that reads the row slices them, so the
    unpack is charged per assembled column (:func:`conv_act_columns`).
    """
    ho, wo, hp, wp, ciw, n_nb, n_j = _conv_geom(
        n, h, w, ci, fh, fw, stride, padding, bnb, bco, co)
    ci_pad = ciw * 32
    steps = n_j * n_nb * ho * fh
    rows = n_j * n_nb * _ring_rows(ho, fh, stride)

    # HBM: a packed input row is fetched by the step that assembles it,
    # a weight tap each grid step it is mapped
    act_bytes = rows * a_bits * bnb * wp * ciw * 4
    w_bytes = steps * w_bits * fw * ciw * bco * 4
    out_bytes = (out_bits * n_nb * bnb * ho * wo * (n_j * bco // 32) * 4
                 if out_bits else n_nb * bnb * ho * wo * n_j * bco * 4)
    hbm = act_bytes + w_bytes + out_bytes

    macs = float(nd_a * nd_w) * steps * fw * (bnb * wo) * ci_pad * bco
    vpu = sum(conv_vpu_ops(
        n, h, w, ci, co, fh=fh, fw=fw, stride=stride, padding=padding,
        a_bits=a_bits, w_bits=w_bits, nd_a=nd_a, nd_w=nd_w, bnb=bnb,
        bco=bco, cache_weights=cache_weights, out_bits=out_bits).values())

    return max(hbm / tpu.hbm_bw, macs / tpu.int8_macs) + vpu / tpu.vpu_ops


# --------------------------------------------------------------------------
# Paper model zoo
# --------------------------------------------------------------------------

#: ResNet9 (plain-CNN, residual-distilled) for CIFAR10 — paper Table 3.
RESNET9_CIFAR10: List = [
    ConvLayer("conv0", 3, 64, 32, 32, on_host=True),      # <64 input ch
    ConvLayer("conv1", 64, 64, 32, 32),
    ConvLayer("conv2", 64, 64, 32, 32),
    ConvLayer("conv3", 64, 128, 32, 32, stride=2),        # table out 16x16
    ConvLayer("conv4", 128, 128, 16, 16),                 # table in 16x16
    ConvLayer("conv5", 128, 256, 16, 16, stride=2),       # table out 8x8
    ConvLayer("conv6", 256, 256, 8, 8),
    ConvLayer("conv7", 256, 512, 8, 8, stride=2),         # table out 4x4
    ConvLayer("conv8", 512, 512, 4, 4),
    LinearLayer("fc", 512, 10, on_host=True),             # last layer on host
]

#: paper Table 3 reference cycle counts (as printed, incl. its edge quirks).
RESNET9_PAPER_CYCLES = {
    "conv1": 34560, "conv2": 34560, "conv3": 17280, "conv4": 32256,
    "conv5": 16128, "conv6": 27648, "conv7": 13824, "conv8": 18432,
}
RESNET9_PAPER_TOTAL = 194688

#: FINN CNV topology (CIFAR10) — paper Table 5. 3x3 VALID convs, 2x2 pools.
CNV_CIFAR10: List = [
    ConvLayer("conv1", 3, 64, 32, 32, padding=0, on_host=True),
    ConvLayer("conv2", 64, 64, 30, 30, padding=0),
    ConvLayer("conv3", 64, 128, 14, 14, padding=0),
    ConvLayer("conv4", 128, 128, 12, 12, padding=0),
    ConvLayer("conv5", 128, 256, 5, 5, padding=0),
    ConvLayer("conv6", 256, 256, 3, 3, padding=0),
    LinearLayer("fc1", 256, 512),
    LinearLayer("fc2", 512, 512),
    LinearLayer("fc3", 512, 10),
]

CNV_PAPER_FPS = {(1, 1): 61035, (1, 2): 30517, (2, 2): 15258}
RESNET50_PAPER = {"fps": 2296, "fps_per_watt": 106.8, "bits": (1, 2)}


def resnet50_layers() -> List:
    """ResNet-50 (ImageNet 224x224) conv stack; first conv + fc on host."""
    layers: List = [ConvLayer("conv1", 3, 64, 224, 224, fh=7, fw=7, stride=2,
                              padding=3, on_host=True)]
    # (blocks, c_in of stage, bottleneck width, stride of first block, H in)
    cfg = [(3, 64, 64, 1, 56), (4, 256, 128, 2, 56),
           (6, 512, 256, 2, 28), (3, 1024, 512, 2, 14)]
    for si, (blocks, c_in, width, stride, h) in enumerate(cfg):
        for b in range(blocks):
            s = stride if b == 0 else 1
            cin = c_in if b == 0 else width * 4
            hh = h if b == 0 else h // stride
            layers += [
                ConvLayer(f"s{si}b{b}_1x1a", cin, width, hh, hh, fh=1, fw=1,
                          stride=s, padding=0),
                ConvLayer(f"s{si}b{b}_3x3", width, width, hh // s, hh // s),
                ConvLayer(f"s{si}b{b}_1x1b", width, width * 4, hh // s,
                          hh // s, fh=1, fw=1, padding=0),
            ]
            if b == 0:
                layers.append(ConvLayer(f"s{si}b{b}_proj", cin, width * 4,
                                        hh, hh, fh=1, fw=1, stride=s,
                                        padding=0))
    layers.append(LinearLayer("fc", 2048, 1000, on_host=True))
    return layers
