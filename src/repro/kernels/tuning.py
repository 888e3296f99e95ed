"""Cost-model-driven block-size autotuner for the v2 bit-serial matmul.

The seed kernel ran every problem with fixed ``(128, 128, 512)`` blocks.
FINN-R and SPEED both show low-precision throughput is won by tuning tile
geometry per precision: the right block shape depends on the configured
``a_bits``/``w_bits`` (they set the packed tile footprints and the number of
digit-plane matmuls) as much as on M/K/N. This module enumerates candidate
tiles, keeps those that meet Mosaic's (8, 128) tiling rule and fit the
VMEM working-set estimate, scores the survivors
with the :mod:`repro.core.cost_model` roofline and picks the cheapest —
including whether the hoisted digit-plane caches (weights / activations)
fit.

Selection is pure arithmetic (no compilation, no device), deterministic,
and memoized in an in-process cache so a serving loop pays the enumeration
once per (shape, spec) and every later call is a dict hit.

The in-process LRU is the L1; :func:`set_persistent_store` attaches an
:class:`~repro.compiler.artifact.ArtifactStore` as an L2, persisting every
decision (keyed by shape/precision/backend knobs) so restarts never re-run
the enumeration and tuning is deterministic across boots.
``cache_info()['enumerations']`` counts actual enumerations — the counter
warm-boot tests assert stays at zero.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Optional, Tuple

from repro.core import bitops, cost_model
from repro.core.bitserial import SerialSpec
from repro.core.cost_model import (TPUConfig, conv_kernel_cost,
                                   conv_kernel_vmem_bytes, kernel_cost,
                                   kernel_vmem_bytes)

__all__ = ["TileConfig", "choose_tile", "choose_tile_measured",
           "ConvTileConfig", "choose_conv_tile", "choose_conv_tile_measured",
           "matmul_tile_ok", "conv_tile_ok",
           "clear_cache", "cache_info", "set_cache_limit",
           "set_persistent_store"]


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """One tuned kernel configuration (kwargs for the v2 Pallas call)."""

    block_m: int
    block_n: int
    block_k: int
    cache_weights: bool
    cache_acts: bool
    cost: float = 0.0          # modeled seconds/call (diagnostic)
    vmem_bytes: int = 0        # modeled VMEM working set (diagnostic)

    def kernel_kwargs(self) -> dict:
        return dict(block_m=self.block_m, block_n=self.block_n,
                    block_k=self.block_k, cache_weights=self.cache_weights,
                    cache_acts=self.cache_acts)


_BM_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)
_BN_CANDIDATES = (32, 64, 128, 256, 512)     # %32: packed-output word axis
_BK_CANDIDATES = (32, 64, 128, 256, 512, 1024, 2048, 4096)

# Bounded LRU: a long-lived multi-tenant service facing churning shapes
# (every new (shape, spec) is one entry) must not grow this without bound.
# Re-tuning an evicted key is pure arithmetic — ~ms, no compilation — so a
# modest cap only costs the rare cold re-enumeration.
_CACHE_LIMIT_DEFAULT = 4096
_cache: "collections.OrderedDict" = collections.OrderedDict()
_cache_lock = threading.Lock()
_cache_limit = _CACHE_LIMIT_DEFAULT
_cache_stats = {"hits": 0, "misses": 0, "evictions": 0,
                "persist_hits": 0, "enumerations": 0}
# L2: a persistent ArtifactStore consulted on L1 misses and written on
# every fresh enumeration (see set_persistent_store)
_persist = None


def set_persistent_store(store):
    """Attach (or with ``None`` detach) a persistent L2 tuning store — an
    :class:`~repro.compiler.artifact.ArtifactStore` (or anything with its
    ``tuning_get``/``tuning_put`` contract). Returns the previous store so
    callers/tests can restore it."""
    global _persist
    with _cache_lock:
        old, _persist = _persist, store
    return old


def _persist_lookup(key, cls):
    """L2 consult: decode a persisted decision for ``key``, or None."""
    with _cache_lock:
        store = _persist
    if store is None:
        return None
    rec = store.tuning_get(repr(key))
    if rec is None:
        return None
    try:
        cfg = cls(**rec["config"])
    except (KeyError, TypeError):
        return None              # stale/foreign record: just re-tune
    with _cache_lock:
        _cache_stats["persist_hits"] += 1
    return cfg


def _persist_record(key, kind, cfg) -> None:
    with _cache_lock:
        store = _persist
        _cache_stats["enumerations"] += 1
    if store is not None:
        store.tuning_put(repr(key), kind, dataclasses.asdict(cfg))


def _cache_get(key):
    with _cache_lock:
        hit = _cache.get(key)
        if hit is not None:
            _cache.move_to_end(key)        # LRU touch
            _cache_stats["hits"] += 1
        else:
            _cache_stats["misses"] += 1
        return hit


def _cache_put(key, value) -> None:
    with _cache_lock:
        _cache[key] = value
        _cache.move_to_end(key)
        while len(_cache) > _cache_limit:
            _cache.popitem(last=False)
            _cache_stats["evictions"] += 1


def set_cache_limit(limit: int) -> int:
    """Resize the tuner cache (evicting LRU overflow); returns the old
    limit so callers/tests can restore it."""
    global _cache_limit
    if limit < 1:
        raise ValueError(f"cache limit must be >= 1, got {limit}")
    with _cache_lock:
        old, _cache_limit = _cache_limit, limit
        while len(_cache) > _cache_limit:
            _cache.popitem(last=False)
            _cache_stats["evictions"] += 1
    return old


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _candidates(dim: int, options: Tuple[int, ...], mult: int):
    """Candidate block sizes for one axis: every option below the (padded)
    axis, plus one block covering the whole padded axis."""
    cap = _round_up(max(dim, 1), mult)
    return [b for b in options if b < cap] + [cap]


def _fits_tiling(block: int, dim: int, mult: int) -> bool:
    """Mosaic's tiling rule for one of a block's last two dims: a multiple
    of the native tile (8 sublanes, 128 lanes) or the whole (padded) array
    dim, which is what a block covering ``dim`` becomes."""
    return block % mult == 0 or block >= dim


def matmul_tile_ok(m: int, k: int, n: int, bm: int, bn: int, bk: int, *,
                   packed_out: bool = False) -> bool:
    """Whether a v2 matmul tile meets Mosaic's (8, 128) rule on every
    block of :func:`repro.kernels.bitserial_matmul.bitserial_matmul_v2_pallas`:
    packed activations ``(a_bits, bm, bk/32)``, packed weights
    ``(w_bits, bk/32, bn)``, scaler/bias ``(1, bn)`` and the output
    ``(bm, bn)`` or, packed, ``(bits, bm, bn/32)``. (The weights' ``bk/32``
    sublane rule is implied by the activations' lane rule.)"""
    kw, nw = -(-k // 32), -(-n // 32)
    if bk % 32 or bn % 32:
        return False
    return (_fits_tiling(bm, m, 8)
            and _fits_tiling(bk // 32, kw, 128)
            and _fits_tiling(bn, n, 128)
            and (not packed_out or _fits_tiling(bn // 32, nw, 128)))


def conv_tile_ok(bco: int) -> bool:
    """Whether a conv tile meets Mosaic's (8, 128) rule. The conv kernel
    blocks Co along a leading axis and takes whole rows and channel words,
    so every block's last two dims are the array's own: only the 32-lane
    packing word constrains ``block_co``."""
    return bco % 32 == 0


def _enumerate_tiles(m, k, n, spec, *, out_bits, tpu):
    """All VMEM-feasible matmul tile configs that meet the tiling rule,
    best-first (modeled cost ascending, larger block volume breaking
    ties). A digit-plane cache is only considered where a later grid step
    reuses it."""
    nd_a = bitops.num_digits(spec.a_bits, spec.radix_bits, spec.a_signed)
    nd_w = bitops.num_digits(spec.w_bits, spec.radix_bits, spec.w_signed)
    budget = cost_model.vmem_budget_bytes(tpu)

    cands = []
    for bm in _candidates(m, _BM_CANDIDATES, 8):
        for bn in _candidates(n, _BN_CANDIDATES, 32):
            for bk in _candidates(k, _BK_CANDIDATES, 32):
                if not matmul_tile_ok(m, k, n, bm, bn, bk,
                                      packed_out=out_bits is not None):
                    continue
                n_j, n_i = -(-n // bn), -(-m // bm)
                for cw, ca in ((True, True), (True, False),
                               (False, True), (False, False)):
                    if (cw and n_i == 1) or (ca and n_j == 1):
                        continue
                    kw = dict(a_bits=spec.a_bits, w_bits=spec.w_bits,
                              nd_a=nd_a, nd_w=nd_w, bm=bm, bn=bn, bk=bk,
                              cache_weights=cw, cache_acts=ca,
                              out_bits=out_bits)
                    vmem = kernel_vmem_bytes(m, k, n, **kw)
                    if vmem > budget:
                        continue
                    cost = kernel_cost(m, k, n, **kw, tpu=tpu)
                    cands.append(TileConfig(bm, bn, bk, cw, ca, cost,
                                            vmem))
    cands.sort(key=lambda c: (c.cost, -(c.block_m * c.block_n
                                        * c.block_k)))
    return cands


def _no_tile(what: str) -> ValueError:
    return ValueError(f"no {what} tile meets the (8, 128) tiling rule "
                      "within the VMEM budget")


def choose_tile(m: int, k: int, n: int, spec: SerialSpec, *,
                out_bits: Optional[int] = None,
                tpu: TPUConfig = TPUConfig()) -> TileConfig:
    """Pick (block_m, block_n, block_k, cache flags) for one matmul shape.

    ``out_bits``: set when the fused requant-pack epilogue is used (the
    packed output constrains ``block_n`` to whole 128-word lane tiles or
    the whole of N, and changes the output HBM term). Only tiles that meet
    Mosaic's tiling rule (:func:`matmul_tile_ok`) and fit the VMEM budget
    are considered; raises ``ValueError`` when none does. Results are
    memoized per (shape, spec, out_bits, tpu).
    """
    key = (m, k, n, spec, out_bits, tpu)
    hit = _cache_get(key)
    if hit is not None:
        return hit
    persisted = _persist_lookup(key, TileConfig)
    if persisted is not None:
        _cache_put(key, persisted)
        return persisted

    cands = _enumerate_tiles(m, k, n, spec, out_bits=out_bits, tpu=tpu)
    if not cands:
        raise _no_tile(f"matmul ({m}x{k}x{n})")
    best = cands[0]
    _persist_record(key, "tile", best)
    _cache_put(key, best)
    return best


def choose_tile_measured(m: int, k: int, n: int, spec: SerialSpec, *,
                         measure, out_bits: Optional[int] = None,
                         top_k: int = 4,
                         tpu: TPUConfig = TPUConfig()) -> TileConfig:
    """Measured re-rank: shortlist the ``top_k`` analytically cheapest
    matmul tiles, time each with the caller-supplied ``measure(cfg) ->
    seconds``, and pick the measured winner.

    The analytic best always heads the shortlist and strict-``<``
    comparison keeps it on ties, so the result is never slower than
    :func:`choose_tile`'s choice under ``measure`` — gated by the
    calibration benchmark. ``measure`` stays caller-supplied so this
    module remains jax-free (the bench times the actual Pallas kernel).
    Winners persist/memoize like analytic decisions (kind
    ``tile_measured``); warm boots replay them without re-measuring.
    """
    key = ("measured", m, k, n, spec, out_bits, top_k, tpu)
    hit = _cache_get(key)
    if hit is not None:
        return hit
    persisted = _persist_lookup(key, TileConfig)
    if persisted is not None:
        _cache_put(key, persisted)
        return persisted

    cands = _enumerate_tiles(m, k, n, spec, out_bits=out_bits,
                             tpu=tpu)[:max(1, top_k)]
    if not cands:
        raise _no_tile(f"matmul ({m}x{k}x{n})")
    best, best_t = None, None
    for c in cands:                    # analytic order; ties keep rank 1
        t = float(measure(c))
        if best is None or t < best_t:
            best, best_t = c, t
    _persist_record(key, "tile_measured", best)
    _cache_put(key, best)
    return best


@dataclasses.dataclass(frozen=True)
class ConvTileConfig:
    """One tuned implicit-GEMM conv configuration (kwargs for the Pallas
    call): Co-block width, images per grid step, cache flags."""

    block_co: int
    block_nb: int
    cache_weights: bool
    cache_acts: bool
    cost: float = 0.0          # modeled seconds/call (diagnostic)
    vmem_bytes: int = 0        # modeled VMEM working set (diagnostic)

    def kernel_kwargs(self) -> dict:
        return dict(block_co=self.block_co, block_nb=self.block_nb,
                    cache_weights=self.cache_weights,
                    cache_acts=self.cache_acts)


_BCO_CANDIDATES = (32, 64, 128, 256, 512)    # %32: packed-output word axis
_BNB_CANDIDATES = (1, 2, 4, 8)               # images per grid step


def _enumerate_conv_tiles(n, h, w, ci, co, *, fh, fw, stride, padding,
                          spec, out_bits, fix_bco, fix_bnb, tpu):
    """All VMEM-feasible conv tile configs that meet the tiling rule,
    best-first (modeled cost ascending, larger Co-block × image group
    breaking ties). A digit-plane cache is only considered where a later
    grid step reuses it."""
    nd_a = bitops.num_digits(spec.a_bits, spec.radix_bits, spec.a_signed)
    nd_w = bitops.num_digits(spec.w_bits, spec.radix_bits, spec.w_signed)
    budget = cost_model.vmem_budget_bytes(tpu)

    bco_opts = ([fix_bco] if fix_bco is not None
                else _candidates(co, _BCO_CANDIDATES, 32))
    bnb_opts = ([fix_bnb] if fix_bnb is not None
                else [b for b in _BNB_CANDIDATES if b <= max(1, n)])
    ho = (h + 2 * padding - fh) // stride + 1
    cands = []
    for bco in bco_opts:
        if not conv_tile_ok(bco):
            continue
        for bnb in bnb_opts:
            n_j, n_m = -(-co // bco), -(-n // bnb) * ho
            for cw, ca in ((True, True), (True, False),
                           (False, True), (False, False)):
                if (cw and n_m == 1) or (ca and n_j == 1):
                    continue
                kw = dict(fh=fh, fw=fw, stride=stride, padding=padding,
                          a_bits=spec.a_bits, w_bits=spec.w_bits,
                          nd_a=nd_a, nd_w=nd_w, bnb=bnb, bco=bco,
                          cache_weights=cw, cache_acts=ca,
                          out_bits=out_bits)
                vmem = conv_kernel_vmem_bytes(n, h, w, ci, co, **kw)
                if vmem > budget:
                    continue
                cost = conv_kernel_cost(n, h, w, ci, co, **kw, tpu=tpu)
                cands.append(ConvTileConfig(bco, bnb, cw, ca, cost, vmem))
    cands.sort(key=lambda c: (c.cost, -(c.block_co * c.block_nb)))
    return cands


def choose_conv_tile(n: int, h: int, w: int, ci: int, co: int, *,
                     fh: int, fw: int, stride: int, padding: int,
                     spec: SerialSpec, out_bits: Optional[int] = None,
                     fix_bco: Optional[int] = None,
                     fix_bnb: Optional[int] = None,
                     tpu: TPUConfig = TPUConfig()) -> ConvTileConfig:
    """Pick (block_co, block_nb, cache flags) for one conv shape.

    The spatial/M blocking is fixed by the kernel's AGU walk (one output
    row × ``block_nb`` images per grid step; K-blocking = the FH grid axis
    + in-kernel FW walk), so the tuner's degrees of freedom are the
    Co-block width, the image grouping, and whether the digit-plane caches
    fit VMEM. ``fix_bco``/``fix_bnb`` pin one axis (caller override) while
    the rest is still tuned and VMEM-validated jointly. Raises
    ``ValueError`` when no tile meets the tiling rule within the VMEM
    budget. Memoized per (shape, spec, out_bits, pins, tpu).
    """
    key = ("conv", n, h, w, ci, co, fh, fw, stride, padding, spec, out_bits,
           fix_bco, fix_bnb, tpu)
    hit = _cache_get(key)
    if hit is not None:
        return hit
    persisted = _persist_lookup(key, ConvTileConfig)
    if persisted is not None:
        _cache_put(key, persisted)
        return persisted

    cands = _enumerate_conv_tiles(n, h, w, ci, co, fh=fh, fw=fw,
                                  stride=stride, padding=padding,
                                  spec=spec, out_bits=out_bits,
                                  fix_bco=fix_bco, fix_bnb=fix_bnb,
                                  tpu=tpu)
    if not cands:
        raise _no_tile(f"conv ({n}x{h}x{w}x{ci}->{co})")
    best = cands[0]
    _persist_record(key, "conv_tile", best)
    _cache_put(key, best)
    return best


def choose_conv_tile_measured(n: int, h: int, w: int, ci: int, co: int, *,
                              fh: int, fw: int, stride: int, padding: int,
                              spec: SerialSpec, measure,
                              out_bits: Optional[int] = None,
                              top_k: int = 4,
                              tpu: TPUConfig = TPUConfig()
                              ) -> ConvTileConfig:
    """Measured re-rank for conv tiles — same contract as
    :func:`choose_tile_measured` (analytic top-``top_k`` shortlist, timed
    by the caller's ``measure(cfg) -> seconds``, never slower than the
    analytic choice under ``measure``, persisted as
    ``conv_tile_measured``)."""
    key = ("conv_measured", n, h, w, ci, co, fh, fw, stride, padding,
           spec, out_bits, top_k, tpu)
    hit = _cache_get(key)
    if hit is not None:
        return hit
    persisted = _persist_lookup(key, ConvTileConfig)
    if persisted is not None:
        _cache_put(key, persisted)
        return persisted

    cands = _enumerate_conv_tiles(n, h, w, ci, co, fh=fh, fw=fw,
                                  stride=stride, padding=padding,
                                  spec=spec, out_bits=out_bits,
                                  fix_bco=None, fix_bnb=None,
                                  tpu=tpu)[:max(1, top_k)]
    if not cands:
        raise _no_tile(f"conv ({n}x{h}x{w}x{ci}->{co})")
    best, best_t = None, None
    for c in cands:                    # analytic order; ties keep rank 1
        t = float(measure(c))
        if best is None or t < best_t:
            best, best_t = c, t
    _persist_record(key, "conv_tile_measured", best)
    _cache_put(key, best)
    return best


def clear_cache() -> None:
    with _cache_lock:
        _cache.clear()
        for k in _cache_stats:
            _cache_stats[k] = 0


def cache_info() -> dict:
    with _cache_lock:
        return {"entries": len(_cache), "limit": _cache_limit,
                "persistent_store": _persist is not None, **_cache_stats}
