"""Benchmark harness — one function per paper table + kernel micro-bench +
calibration gates. Prints ``name,us_per_call,derived`` CSV rows and writes a
machine-readable ``BENCH_kernels.json`` (name → us_per_call + derived) so
the perf trajectory is tracked PR-over-PR. Conv-kernel + ResNet9
end-to-end rows are additionally dumped to ``BENCH_conv.json``; the graph-
compiler rows (compiled vs hand-written packed path, executor dispatch
overhead) to ``BENCH_compile.json``; the serving-runtime rows (bucketed
steady-state vs re-jit-per-shape, latency percentiles, precision mix) to
``BENCH_serving.json``; the bank-scaling rows (1 vs 4 MVU banks, virtual
+ wall domains, sharded/pipelined placements) to
``BENCH_distributed.json``; the AOT artifact-store rows (cold compile vs
warm boot of a 2-model x 2-precision registry) to ``BENCH_coldstart.json``;
the continuous-batching LM rows (static chunked vs token-granular decode
on a heterogeneous stream) to ``BENCH_lm.json``; the observability
overhead rows (serving smoke with tracing off vs on, metric write cost
enabled vs disabled) to ``BENCH_obs.json``; the measured-profiler /
cost-model calibration rows (fitted ns-per-virtual-cycle, max relative
residual, measured tile re-rank never-slower gate, profiler off-path
zero-overhead gate) to ``BENCH_calibration.json``. After a run,
``python -m benchmarks.history`` appends the gated scalars to
``BENCH_history.jsonl`` and ``python -m benchmarks.regress`` gates the
newest record against the rolling baseline.

Run: PYTHONPATH=src python -m benchmarks.run
     [--only kernels,tables,conv,compile,serving,distributed,coldstart,
      lm,obs,calibration]
     [--json BENCH_kernels.json] [--conv-json BENCH_conv.json]
     [--compile-json BENCH_compile.json]
     [--serving-json BENCH_serving.json]
     [--distributed-json BENCH_distributed.json]
"""

from __future__ import annotations

import argparse
import json
import os
import time
import timeit

import numpy as np

_ROWS: dict = {}
# per-group artifact keys: group tag -> row names (dumped to the group's
# own BENCH_*.json next to the all-rows dump)
_GROUP_KEYS: dict = {"conv": [], "compile": [], "serving": [],
                     "distributed": [], "coldstart": [], "lm": [],
                     "obs": [], "calibration": []}


def _emit(name: str, us: float, derived: str = "",
          group: str = None) -> None:
    """One result row: CSV to stdout + recorded for the JSON dump(s).
    ``group`` additionally tags the row for that group's artifact."""
    print(f"{name},{us:.0f},{derived}")
    _ROWS[name] = {"us_per_call": round(float(us), 1), "derived": derived}
    if group is not None:
        _GROUP_KEYS[group].append(name)


def _time_us(fn, n=5, warmup=1, repeat=3):
    """Best-of-``repeat`` mean over ``n`` calls — the minimum strips
    scheduler/contention spikes, which otherwise dominate on shared CI
    machines and make speedup ratios unstable."""
    for _ in range(warmup):
        fn()
    return min(timeit.repeat(fn, number=n, repeat=repeat)) / n * 1e6


def _time_interleaved_us(fns, n=2, rounds=4):
    """Time several candidates under the SAME load: alternate them
    round-robin and take each one's best round. Timing A fully then B fully
    lets a background-load shift land entirely on one side and corrupt the
    A/B ratio; interleaving makes both sides sample every load phase."""
    for fn in fns:
        fn()  # warmup/compile
    best = [float("inf")] * len(fns)
    for _ in range(rounds):
        for i, fn in enumerate(fns):
            best[i] = min(best[i], timeit.timeit(fn, number=n) / n * 1e6)
    return best


def table2_model_sizes():
    """Paper Table 2: ResNet9 model sizes (fp32 vs int2 packed)."""
    import jax
    from repro.core.codegen import export_weights
    from repro.models.resnet import ResNet9Config, resnet9_init
    cfg = ResNet9Config()
    t0 = time.time()
    params = resnet9_init(jax.random.PRNGKey(0), cfg)
    conv = {n: params[n]["w"] for n, *_ in cfg.layers}
    exported = export_weights(conv, w_bits=2)
    packed = sum(v.packed.nbytes for v in exported.values())
    fp32 = sum(params[n]["w"].nbytes for n, *_ in cfg.layers)
    us = (time.time() - t0) * 1e6
    # paper: Plain-CNN fp32 18912487 B, Int2 1181360 B
    _emit("table2_fp32_bytes", us, f"{fp32} (paper 18912487)")
    _emit("table2_int2_bytes", us, f"{packed} (paper 1181360)")
    _emit("table2_compression", us, f"{fp32/packed:.1f}x")


def table3_resnet9_cycles():
    """Paper Table 3: per-layer ResNet9 cycles at W2/A2."""
    import repro.core.cost_model as cm
    t0 = time.time()
    cyc = cm.network_cycles(cm.RESNET9_CIFAR10, 2, 2, edge="paper_edge")
    named = {l.name: c for l, c in zip(cm.RESNET9_CIFAR10, cyc)}
    us = (time.time() - t0) * 1e6
    exact = 0
    for k, v in cm.RESNET9_PAPER_CYCLES.items():
        match = named[k] == v
        exact += match
        _emit(f"table3_{k}", us,
              f"{named[k]} (paper {v} {'EXACT' if match else 'dev'})")
    total = sum(cyc)
    _emit("table3_total", us,
          f"{total} (paper {cm.RESNET9_PAPER_TOTAL} "
          f"{'EXACT' if total == cm.RESNET9_PAPER_TOTAL else ''}) "
          f"[{exact}/8 layers exact]")
    # the other edge variants, for the reconciliation note
    for edge in ("dense", "pad_skip"):
        t = sum(cm.network_cycles(cm.RESNET9_CIFAR10, 2, 2, edge=edge))
        _emit(f"table3_total_{edge}", us, str(t))


def table5_cnv_fps():
    """Paper Table 5: CNV throughput vs precision (scaling law)."""
    import repro.core.cost_model as cm
    t0 = time.time()
    us = (time.time() - t0) * 1e6
    for (w, a), paper in cm.CNV_PAPER_FPS.items():
        fps = cm.pipelined_fps(cm.CNV_CIFAR10, a, w)
        _emit(f"table5_cnv_W{w}A{a}", us,
              f"{fps:.0f} FPS (paper {paper}; ratio {fps/paper:.2f})")
    f11 = cm.pipelined_fps(cm.CNV_CIFAR10, 1, 1)
    f22 = cm.pipelined_fps(cm.CNV_CIFAR10, 2, 2)
    _emit("table5_scaling_1x1_over_2x2", us, f"{f11/f22:.2f} (paper 4.00)")


def table6_resnet50():
    """Paper Table 6: ResNet-50 FPS and FPS/W."""
    import repro.core.cost_model as cm
    t0 = time.time()
    layers = cm.resnet50_layers()
    fps_d = cm.distributed_fps(layers, 2, 1, edge="paper_edge")
    fps_p = cm.pipelined_fps(layers, 2, 1, edge="paper_edge")
    us = (time.time() - t0) * 1e6
    hw = cm.HWConfig()
    _emit("table6_resnet50_fps", us,
          f"{fps_d:.0f} (paper {cm.RESNET50_PAPER['fps']}; "
          "distributed-mode estimate)")
    _emit("table6_resnet50_fps_per_watt", us,
          f"{fps_d/hw.power_w:.1f} "
          f"(paper {cm.RESNET50_PAPER['fps_per_watt']}; FILM-QNN 8.4)")
    _emit("table6_resnet50_fps_pipelined", us, f"{fps_p:.0f}")


def bench_serial_matmul():
    """Micro-bench: XLA serve path, seed digit plan (radix 7, two plane
    products at W4A8) vs the v2 plan-selected path (radix 8, one).

    CPU timings are indicative only; the TPU target uses the Pallas kernel.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import bitops
    from repro.core.bitserial import (SerialSpec, plan_spec,
                                      serial_matmul_packed)
    rng = np.random.RandomState(0)
    m, k, n = 256, 1024, 1024
    x = jnp.asarray(rng.randint(-128, 128, (m, k)), jnp.int32)
    w = rng.randint(-8, 8, (k, n)).astype(np.int32)
    planes = bitops.pad_to(bitops.to_bitplanes(jnp.asarray(w), 4), 32, axis=1)
    wp = bitops.pack_bitplanes(planes, axis=1)
    xf = jnp.asarray(rng.randn(m, k), jnp.float32)
    wf = jnp.asarray(rng.randn(k, n), jnp.float32)

    f_float = jax.jit(lambda a, b: a @ b)
    shape_tag = f"{m}x{k}x{n}"
    cases = [
        ("seed", SerialSpec(8, 4, True, True, 7)),           # seed default
        ("v2", plan_spec(SerialSpec(8, 4, True, True, 7))),  # tuned plan
        ("bitserial_r2", SerialSpec(8, 4, True, True, 1)),   # faithful
    ]
    fns = []
    for _, spec in cases:
        f = jax.jit(lambda xx, ww, s=spec: serial_matmul_packed(
            xx, ww, spec=s, k=k))
        fns.append(lambda f=f: jax.block_until_ready(f(x, wp)))
    times = _time_interleaved_us(fns, n=2, rounds=6)
    results = {}
    for (name, spec), us in zip(cases, times):
        results[name] = us
        _emit(f"bench_serial_matmul_W4A8_{name}_{shape_tag}", us,
              f"{spec.num_plane_products} plane products "
              f"(radix {spec.radix_bits})")
    _emit("bench_serial_matmul_W4A8_v2_speedup", 0,
          f"{results['seed']/results['v2']:.2f}x vs seed")
    us_f = _time_us(lambda: jax.block_until_ready(f_float(xf, wf)))
    _emit(f"bench_float_matmul_{shape_tag}", us_f, "fp32 reference")


def bench_pallas_kernel():
    """Pallas kernels in interpret mode, W4A8, same logical shape: seed v1
    (int-code acts, per-step plane unpack) vs v2 (packed acts, hoisted
    VMEM-scratch digit planes, tuned digit plan)."""
    import jax
    import jax.numpy as jnp
    from repro.core import bitops
    from repro.core.bitserial import SerialSpec, plan_spec
    from repro.kernels.bitserial_matmul import (bitserial_matmul_pallas,
                                                bitserial_matmul_v2_pallas)
    rng = np.random.RandomState(0)
    m, k, n = 128, 512, 128
    bm, bn, bk = 16, 32, 128       # multi-block grid on every axis
    x = rng.randint(-128, 128, (m, k)).astype(np.int32)
    w = rng.randint(-8, 8, (k, n)).astype(np.int32)
    wp = bitops.pack_bitplanes(
        bitops.pad_to(bitops.to_bitplanes(jnp.asarray(w), 4), 32, axis=1),
        axis=1)
    xp = bitops.pack_bitplanes(
        bitops.pad_to(bitops.to_bitplanes(jnp.asarray(x), 8), 32, axis=-1),
        axis=-1)
    scale = np.ones(n, np.float32)
    shape_tag = f"{m}x{k}x{n}"

    from repro.core.quant import QuantSpec
    seed_spec = SerialSpec(8, 4, True, True, 7)
    v2_spec = plan_spec(seed_spec)
    fn_v1 = jax.jit(lambda xx, ww: bitserial_matmul_pallas(
        jnp.asarray(xx), ww, scale, None, spec=seed_spec, k=k, block_m=bm,
        block_n=bn, block_k=bk, interpret=True))
    fn_v2 = jax.jit(lambda xx, ww: bitserial_matmul_v2_pallas(
        xx, ww, scale, None, spec=v2_spec, k=k, block_m=bm, block_n=bn,
        block_k=bk, interpret=True))
    # fused requant->bit-transpose-pack epilogue (layer-chaining output)
    fn_v2p = jax.jit(lambda xx, ww: bitserial_matmul_v2_pallas(
        xx, ww, scale, None, spec=v2_spec, k=k, requant=QuantSpec(8, True),
        requant_scale=jnp.asarray(0.5), emit_packed=True, block_m=bm,
        block_n=bn, block_k=bk, interpret=True))
    us_v1, us_v2, us_v2p = _time_interleaved_us([
        lambda: jax.block_until_ready(fn_v1(x, wp)),
        lambda: jax.block_until_ready(fn_v2(xp, wp)),
        lambda: jax.block_until_ready(fn_v2p(xp, wp)),
    ], n=2, rounds=4)
    _emit(f"bench_pallas_kernel_W4A8_seed_{shape_tag}", us_v1,
          f"v1, blocks ({bm},{bn},{bk}), interpret")
    _emit(f"bench_pallas_kernel_W4A8_v2_{shape_tag}", us_v2,
          "v2, packed acts + hoisted planes, interpret")
    _emit("bench_pallas_kernel_W4A8_v2_speedup", 0,
          f"{us_v1/us_v2:.2f}x vs seed")
    _emit(f"bench_pallas_kernel_W4A8_v2_fusedpack_{shape_tag}", us_v2p,
          "v2 + fused requant-pack epilogue, interpret")


def bench_tuner():
    """Autotuner overhead: cold enumeration vs in-process cache hit."""
    from repro.core.bitserial import SerialSpec
    from repro.kernels import tuning
    spec = SerialSpec(8, 4, True, True, 8)
    tuning.clear_cache()
    t0 = time.time()
    tc = tuning.choose_tile(64, 4096, 4096, spec)
    cold = (time.time() - t0) * 1e6
    us_hit = _time_us(lambda: tuning.choose_tile(64, 4096, 4096, spec),
                      n=100, warmup=1)
    _emit("bench_tuner_cold_us", cold,
          f"blocks ({tc.block_m},{tc.block_n},{tc.block_k}) "
          f"cw={tc.cache_weights} ca={tc.cache_acts} "
          f"vmem={tc.vmem_bytes/2**20:.2f}MiB")
    _emit("bench_tuner_cache_hit_us", us_hit,
          f"{tuning.cache_info()['entries']} entries")


def _resnet9_conv_shapes():
    """(name, c_in, c_out, input H=W, stride) of every hidden conv, derived
    from the ResNet9Config the model actually runs (3x3 pad-1 convs, 2x2
    pools after the flagged stages) so benchmark and model cannot drift."""
    from repro.models.resnet import ResNet9Config
    cfg = ResNet9Config()
    shapes, h = [], 32
    for (name, ci, co, stride, pool) in cfg.layers:
        shapes.append((name, ci, co, h, stride))
        h = (h - 1) // stride + 1
        if pool:
            h //= 2
    return shapes


def bench_conv_layers():
    """ResNet9 W2A2 conv layers: the seed path (f32 im2col round-trip +
    v1 serial GEMM) vs the implicit-GEMM packed path (tap-walk dataflow of
    the conv kernel, XLA lowering — CPU timings indicative; the TPU target
    runs kernels/bitserial_conv.py)."""
    import jax
    import jax.numpy as jnp
    from repro.core import bitops
    from repro.core.bitserial import SerialSpec, plan_spec, serial_matmul
    from repro.kernels.ops import pack_activations, serial_conv2d_packed_op
    spec = plan_spec(SerialSpec(2, 2, True, True, 7))
    rng = np.random.RandomState(0)

    def seed_conv(x, w, stride):
        # the seed serial_conv2d: f32 patch extraction (a ~9x blown patch
        # tensor through HBM) -> cast back -> one big serial GEMM
        patches = jax.lax.conv_general_dilated_patches(
            x.astype(jnp.float32), (3, 3), (stride, stride),
            [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.int32)
        wmat = jnp.transpose(w, (2, 0, 1, 3)).reshape(-1, w.shape[-1])
        return serial_matmul(patches, wmat, spec)

    tot_seed = tot_imp = 0.0
    for (name, ci, co, hw, stride) in _resnet9_conv_shapes():
        x = jnp.asarray(rng.randint(-2, 2, (8, hw, hw, ci)), jnp.int32)
        w = jnp.asarray(rng.randint(-2, 2, (3, 3, ci, co)), jnp.int32)
        xp = pack_activations(x, 2)
        wp = bitops.pack_bitplanes(
            bitops.pad_to(bitops.to_bitplanes(w, 2), 32, axis=3), axis=3)
        scale = jnp.ones(co, jnp.float32)
        f_seed = jax.jit(lambda a, b, s=stride: seed_conv(a, b, s))
        f_imp = jax.jit(lambda a, b, s=stride, c=ci: serial_conv2d_packed_op(
            a, b, scale, None, spec=spec, ci=c, stride=s, padding=1,
            backend="xla"))
        us_seed, us_imp = _time_interleaved_us([
            lambda: jax.block_until_ready(f_seed(x, w)),
            lambda: jax.block_until_ready(f_imp(xp, wp)),
        ], n=1, rounds=3)
        tot_seed += us_seed
        tot_imp += us_imp
        _emit(f"bench_conv_W2A2_{name}_seed_im2col", us_seed,
              f"8x{hw}x{hw}x{ci}->{co} s{stride}", group="conv")
        _emit(f"bench_conv_W2A2_{name}_implicit", us_imp,
              f"{us_seed / us_imp:.2f}x vs seed", group="conv")
    _emit("bench_conv_W2A2_resnet9_stack_speedup", 0,
          f"{tot_seed / tot_imp:.2f}x vs im2col+v1 serial GEMM "
          f"(stack {tot_seed:.0f}us -> {tot_imp:.0f}us)", group="conv")


def bench_conv_pallas_kernel():
    """Pallas kernels in interpret mode, one W2A2 conv stage: seed recipe
    (host int im2col + v1 serial matmul kernel) vs the implicit-GEMM conv
    kernel (patch generation inside the kernel, digit-plane caches)."""
    import jax
    import jax.numpy as jnp
    from repro.core import bitops
    from repro.core.bitserial import SerialSpec, plan_spec
    from repro.kernels.bitserial_matmul import bitserial_matmul_pallas
    from repro.kernels.bitserial_conv import bitserial_conv2d_v2_pallas
    spec = plan_spec(SerialSpec(2, 2, True, True, 7))
    rng = np.random.RandomState(0)
    n, hw, ci, co, stride = 2, 8, 64, 64, 1
    x = jnp.asarray(rng.randint(-2, 2, (n, hw, hw, ci)), jnp.int32)
    w = jnp.asarray(rng.randint(-2, 2, (3, 3, ci, co)), jnp.int32)
    scale = np.ones(co, np.float32)
    from repro.kernels.ops import pack_activations
    xp = pack_activations(x, 2)
    wp_conv = bitops.pack_bitplanes(
        bitops.pad_to(bitops.to_bitplanes(w, 2), 32, axis=3), axis=3)
    wmat = jnp.transpose(w, (2, 0, 1, 3)).reshape(-1, co)
    wp_mat = bitops.pack_bitplanes(
        bitops.pad_to(bitops.to_bitplanes(wmat, 2), 32, axis=1), axis=1)
    k = 9 * ci

    def seed_kernel(xx):
        patches = jax.lax.conv_general_dilated_patches(
            xx.astype(jnp.float32), (3, 3), (stride, stride),
            [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC")).astype(jnp.int32)
        return bitserial_matmul_pallas(
            patches.reshape(-1, k), wp_mat, scale, None, spec=spec, k=k,
            block_m=32, block_n=32, block_k=192, interpret=True)

    f_v1 = jax.jit(seed_kernel)
    f_v2 = jax.jit(lambda a: bitserial_conv2d_v2_pallas(
        a, wp_conv, scale, None, spec=spec, ci=ci, stride=stride,
        padding=1, block_co=32, block_nb=2, interpret=True))
    us_v1, us_v2 = _time_interleaved_us([
        lambda: jax.block_until_ready(f_v1(x)),
        lambda: jax.block_until_ready(f_v2(xp)),
    ], n=1, rounds=3)
    tag = f"{n}x{hw}x{hw}x{ci}->{co}"
    _emit(f"bench_conv_pallas_W2A2_seed_{tag}", us_v1,
          "im2col + v1 matmul kernel, interpret", group="conv")
    _emit(f"bench_conv_pallas_W2A2_v2_{tag}", us_v2,
          f"implicit-GEMM conv kernel, interpret; "
          f"{us_v1 / us_v2:.2f}x vs seed", group="conv")


def bench_resnet9_e2e():
    """ResNet9/CIFAR10 end-to-end (paper Tables 2/3 workload, batch 4):
    seed quantized forward (per-call weight re-quantization + f32 im2col)
    vs the hoisted forward vs the packed deployment path (implicit-GEMM,
    stages chained in packed format)."""
    import jax
    import jax.numpy as jnp
    from repro.models.resnet import (ResNet9Config, resnet9_init,
                                     resnet9_forward, resnet9_pack,
                                     resnet9_forward_packed,
                                     resnet9_quantize_weights)
    cfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(0), cfg)
    images = jnp.asarray(np.random.RandomState(0).rand(4, 32, 32, 3),
                         jnp.float32)
    t0 = time.time()
    qw = resnet9_quantize_weights(params, cfg)
    qw = jax.tree_util.tree_map(jax.block_until_ready, qw)
    us_quant = (time.time() - t0) * 1e6
    t0 = time.time()
    packed = resnet9_pack(params, images, cfg)
    packed = jax.tree_util.tree_map(jax.block_until_ready, packed)
    us_pack = (time.time() - t0) * 1e6
    f_seed = jax.jit(lambda p, im: resnet9_forward(p, im, cfg))
    f_hoist = jax.jit(lambda p, im, q: resnet9_forward(p, im, cfg,
                                                       qweights=q))
    f_packed = jax.jit(lambda p, im: resnet9_forward_packed(
        p, im, cfg, backend="xla"))
    us_seed, us_hoist, us_packed = _time_interleaved_us([
        lambda: jax.block_until_ready(f_seed(params, images)),
        lambda: jax.block_until_ready(f_hoist(params, images, qw)),
        lambda: jax.block_until_ready(f_packed(packed, images)),
    ], n=1, rounds=3)
    _emit("bench_resnet9_W2A2_seed_forward", us_seed,
          "per-call weight quant + f32 im2col, batch 4", group="conv")
    _emit("bench_resnet9_W2A2_hoisted_forward", us_hoist,
          f"one-time weight quant ({us_quant:.0f}us); "
          f"{us_seed / us_hoist:.2f}x vs seed", group="conv")
    _emit("bench_resnet9_W2A2_packed_forward", us_packed,
          f"implicit-GEMM packed chain (pack {us_pack:.0f}us one-time); "
          f"{us_seed / us_packed:.2f}x vs seed", group="conv")


def bench_compile_resnet9():
    """Graph-compiler ResNet9 vs the hand-written packed path: same calib
    batch, same XLA packed-kernel lowering — the compiled Program must sit
    within 5% of `resnet9_forward_packed` (acceptance: the compiler
    generalizes the PR1/PR2 wins without taxing the hand-tuned path)."""
    import jax
    import jax.numpy as jnp
    from repro.core.codegen import generate
    from repro.models.resnet import (ResNet9Config, resnet9_compile,
                                     resnet9_cost_layers,
                                     resnet9_forward_packed, resnet9_init,
                                     resnet9_pack)
    cfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(0), cfg)
    images = jnp.asarray(np.random.RandomState(0).rand(4, 32, 32, 3),
                         jnp.float32)
    t0 = time.time()
    prog = resnet9_compile(params, images, cfg, backend="xla")
    prog(images).block_until_ready()  # include first jit in compile cost
    us_compile = (time.time() - t0) * 1e6
    packed = resnet9_pack(params, images, cfg)
    f_hand = jax.jit(lambda p, im: resnet9_forward_packed(
        p, im, cfg, backend="xla"))
    # deterministic same-computation evidence first: XLA cost analysis of
    # both jitted programs (CPU wall-clock on shared CI is noisy)
    def _cost(f, *a):
        c = f.lower(*a).compile().cost_analysis()
        c = c[0] if isinstance(c, list) else c
        return (c or {}).get("flops"), (c or {}).get("bytes accessed")
    from repro.compiler import executor as _pex
    f_comp = jax.jit(_pex.make_runner(prog))
    cost_hand = _cost(f_hand, packed, images)
    cost_comp = _cost(f_comp, prog.params, images)
    us_hand, us_comp = _time_interleaved_us([
        lambda: jax.block_until_ready(f_hand(packed, images)),
        lambda: jax.block_until_ready(prog(images)),
    ], n=2, rounds=8)
    exact = bool(jnp.all(prog(images) == f_hand(packed, images)))
    ratio = us_comp / us_hand
    _emit("bench_compile_resnet9_hand_packed", us_hand,
          "resnet9_forward_packed, XLA, batch 4", group="compile")
    _emit("bench_compile_resnet9_compiled", us_comp,
          f"graph-compiler Program; {ratio:.3f}x hand time "
          f"(within 5%: {ratio <= 1.05}); bit_exact={exact}", group="compile")
    _emit("bench_compile_resnet9_hlo_cost", 0,
          f"flops/bytes hand={cost_hand} compiled={cost_comp} "
          f"(identical: {cost_hand == cost_comp})", group="compile")
    _emit("bench_compile_resnet9_compile_time", us_compile,
          "one-time: passes+calibration+packing+tuning+first jit", group="compile")
    hand_cs = generate(resnet9_cost_layers(cfg), a_bits=cfg.a_bits,
                       w_bits=cfg.w_bits)
    comp_cs = prog.to_command_stream()
    _emit("bench_compile_resnet9_cycles", 0,
          f"per-MVU {comp_cs.per_mvu_cycles} "
          f"(matches hand codegen: "
          f"{comp_cs.per_mvu_cycles == hand_cs.per_mvu_cycles})", group="compile")


def bench_compile_dispatch():
    """Executor dispatch overhead: a trivial one-gemm Program, jitted call
    (the serving path — whole step list fused into one XLA computation)
    vs eager step-walk (`Program.run`)."""
    import jax
    import jax.numpy as jnp
    from repro.compiler import Graph, Node, compile_graph
    from repro.models.layers import QuantPolicy
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(8, 256), jnp.float32)
    g = Graph("one_gemm", {"x": (None, 256)}, ["y"],
              [Node("fc", "gemm", ["x", "w"], "fy"),
               Node("r", "relu", ["fy"], "y")],
              {"w": (rng.randn(256, 256) * 0.1).astype(np.float32)})
    prog = compile_graph(g, x, policy=QuantPolicy(
        mode="serial", w_bits=4, a_bits=8, radix_bits=7), backend="xla")
    prog(x)  # compile
    us_jit = _time_us(lambda: jax.block_until_ready(prog(x)), n=20)
    us_eager = _time_us(lambda: jax.block_until_ready(prog.run(x)), n=5)
    _emit("bench_compile_dispatch_jit", us_jit,
          "jitted Program call (serving path)", group="compile")
    _emit("bench_compile_dispatch_eager", us_eager,
          f"eager step walk; jit removes {us_eager - us_jit:.0f}us/call "
          "of dispatch", group="compile")


def bench_quantized_lm_serve():
    """Tokens/s of the smoke LM through the full quantized serve path."""
    from repro.configs import get_arch
    from repro.launch.serve import GenRequest, Server
    cfg = get_arch("stablelm-1.6b").smoke
    server = Server(cfg, batch_slots=2, max_len=48)
    rng = np.random.RandomState(0)
    reqs = [GenRequest(rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32),
                       8) for _ in range(2)]
    server.generate(reqs)  # warmup/compile
    t0 = time.perf_counter()
    out = server.generate(reqs)
    dt = time.perf_counter() - t0
    ntok = sum(len(r.out_tokens) for r in out)
    _emit("bench_lm_serve_W4A8", dt / max(ntok, 1) * 1e6,
          f"{ntok/dt:.1f} tok/s (smoke cfg, CPU)")


def _serving_bench_graph(name="serving_cnn", seed=0):
    """Small two-serial-layer CNN: cheap to compile at several precisions,
    still exercises the packed conv + gemm serving kernels."""
    from repro.compiler import Graph, Node
    rng = np.random.RandomState(seed)
    g = Graph(
        name, {"x": (None, 8, 8, 8)}, ["y"],
        [Node("c1", "conv2d", ["x", "c1.w"], "c1.y",
              {"stride": 1, "padding": 1}),
         Node("c1.relu", "relu", ["c1.y"], "c1.r"),
         Node("gap", "global_avg_pool", ["c1.r"], "pooled"),
         Node("fc", "gemm", ["pooled", "fc.w"], "y")],
        {"c1.w": (rng.randn(3, 3, 8, 16) * 0.2).astype(np.float32),
         "fc.w": (rng.randn(16, 10) * 0.2).astype(np.float32)})
    calib = rng.rand(4, 8, 8, 8).astype(np.float32)
    return g, calib


def bench_serving():
    """Multi-tenant serving runtime vs the seed behavior it replaces.

    Workload: a mixed stream — the same CNN at W2A2 and W4A8, client
    batches of every size 1..12 (each precision sees every size once).
    Baseline = the pre-serving ``CNNServer.classify`` discipline: direct
    jitted Program calls, so every previously-unseen (precision, batch
    shape) pays a trace+compile in-window. Bucketed = the serving runtime
    post-warmup: per-example submit through the dynamic batcher, padded to
    power-of-two buckets, jit-cache closed over {variant} x {bucket} —
    steady state never recompiles (asserted from the cache counters).
    """
    import jax
    import jax.numpy as jnp
    from repro.models.layers import QuantPolicy
    from repro.serving import InferenceService, ModelRegistry
    g, calib = _serving_bench_graph()
    reg = ModelRegistry(backend="xla")
    k_lo = reg.register_graph("cnn", g, calib, QuantPolicy(
        mode="serial", w_bits=2, a_bits=2, radix_bits=7))
    k_hi = reg.register_graph("cnn", g, calib, QuantPolicy(
        mode="serial", w_bits=4, a_bits=8, radix_bits=7))
    rng = np.random.RandomState(1)
    sizes = list(range(1, 13))
    client = [((k_lo, k_hi)[i % 2], rng.rand(s, 8, 8, 8).astype(np.float32))
              for i, s in enumerate(sizes + sizes)]
    nreq = sum(x.shape[0] for _, x in client)

    # ---- baseline: re-jit per shape (the seed CNNServer.classify path)
    progs = {k: reg.program(k) for k in (k_lo, k_hi)}
    for p in progs.values():
        p._jit_cache.clear()              # a fresh server facing the stream
    t0 = time.perf_counter()
    for k, x in client:
        jax.block_until_ready(progs[k](jnp.asarray(x)))
    dt_base = time.perf_counter() - t0
    _emit("bench_serving_rejit_baseline", dt_base / nreq * 1e6,
          f"{nreq/dt_base:.1f} req/s over {nreq} reqs; "
          f"{len(sizes)} shapes x 2 precisions each trace+compile",
          group="serving")

    # ---- serving runtime: same stream, per-example submit, buckets
    svc = InferenceService(reg, max_batch=16, max_wait_s=0.001)
    with svc:
        n_warm = svc.warmup()
        warm = {k: v["compiles"]
                for k, v in svc.metrics()["bucket_caches"].items()}
        t0 = time.perf_counter()
        futs = []
        for k, x in client:
            futs += svc.submit_many(k, list(x))
        svc.drain()
        dt_svc = time.perf_counter() - t0
        for f in futs:
            f.result()
        m = svc.metrics()
    recompiles = sum(v["compiles"] - warm[k]
                     for k, v in m["bucket_caches"].items())
    _emit("bench_serving_bucketed", dt_svc / nreq * 1e6,
          f"{nreq/dt_svc:.1f} req/s steady-state; "
          f"p50 {m['latency_p50_ms']:.1f}ms p99 {m['latency_p99_ms']:.1f}ms; "
          f"recompiles_after_warmup={recompiles} "
          f"({n_warm} bucket compiles at warmup)", group="serving")
    _emit("bench_serving_speedup", 0,
          f"{dt_base/dt_svc:.2f}x vs re-jit-per-shape baseline "
          f"(>=2x required)", group="serving")
    sched = m["scheduler"]
    _emit("bench_serving_precision_mix", 0,
          f"W2A2+W4A8 co-scheduled on {len(sched['slot_utilization'])} "
          f"virtual MVU slots; mean busy-slot utilization "
          f"{sched['mean_busy_utilization']:.3f}; "
          f"{sched['admitted_batches']} batches "
          f"{sched['admitted_requests']} reqs "
          f"{sched['virtual_cycles']} virtual cycles", group="serving")
    _emit("bench_serving_queue", 0,
          f"peak depth {m['peak_queue_depth']}; "
          f"straggler events {m['straggler']['events']}", group="serving")


def bench_obs():
    """Observability overhead gate (``BENCH_obs.json``, CI-gated).

    The serving smoke A/B'd under the same load: one service with the
    tracer disabled (the null TraceContext fast path), one with it
    enabled at ``sample_every=1`` so every request records
    queue/schedule/execute/finalize spans plus per-hart cycle tracks.
    Rounds are interleaved (best-of) so a background-load shift cannot
    land on one side and fake a regression. Enabled tracing must stay
    within 5% of disabled throughput; a disabled registry's counter
    write must cost ~one flag check (≈0 at machine scale).
    """
    from repro.compiler import Graph, Node
    from repro.models.layers import QuantPolicy
    from repro.obs import MetricsRegistry, Tracer
    from repro.serving import InferenceService, ModelRegistry

    # heavier than _serving_bench_graph on purpose: the relative gate is
    # meaningless on a microsecond-scale toy (a fixed ~8us/req emit cost
    # would dominate any ratio); this two-conv CNN puts per-request time
    # at realistic serving scale while the absolute cost is still emitted
    rng = np.random.RandomState(5)
    g = Graph(
        "obs_cnn", {"x": (None, 8, 8, 16)}, ["y"],
        [Node("c1", "conv2d", ["x", "c1.w"], "c1.y",
              {"stride": 1, "padding": 1}),
         Node("c1.relu", "relu", ["c1.y"], "c1.r"),
         Node("c2", "conv2d", ["c1.r", "c2.w"], "c2.y",
              {"stride": 1, "padding": 1}),
         Node("c2.relu", "relu", ["c2.y"], "c2.r"),
         Node("gap", "global_avg_pool", ["c2.r"], "pooled"),
         Node("fc", "gemm", ["pooled", "fc.w"], "y")],
        {"c1.w": (rng.randn(3, 3, 16, 32) * 0.2).astype(np.float32),
         "c2.w": (rng.randn(3, 3, 32, 32) * 0.2).astype(np.float32),
         "fc.w": (rng.randn(32, 10) * 0.2).astype(np.float32)})
    calib = rng.rand(4, 8, 8, 16).astype(np.float32)
    reg = ModelRegistry(backend="xla")
    key = reg.register_graph("obs_cnn", g, calib, QuantPolicy(
        mode="serial", w_bits=2, a_bits=2, radix_bits=7))
    payloads = [rng.rand(8, 8, 16).astype(np.float32) for _ in range(96)]
    n = len(payloads)

    def pass_once(svc):
        futs = svc.submit_many(key, payloads)
        svc.drain()
        for f in futs:
            f.result()

    best = {False: float("inf"), True: float("inf")}
    svc_off = InferenceService(reg, max_batch=16, max_wait_s=0.001,
                               tracer=Tracer(enabled=False))
    svc_on = InferenceService(reg, max_batch=16, max_wait_s=0.001,
                              tracer=Tracer(enabled=True))
    with svc_off, svc_on:
        for svc in (svc_off, svc_on):
            svc.warmup()
            pass_once(svc)          # close every jit cache pre-timing
        for _ in range(4):          # interleaved A/B rounds, best-of
            for enabled, svc in ((False, svc_off), (True, svc_on)):
                t0 = time.perf_counter()
                pass_once(svc)
                best[enabled] = min(best[enabled],
                                    time.perf_counter() - t0)
        tstats = svc_on.tracer.stats()
        off_buffered = svc_off.tracer.stats()["buffered"]
    dis, en = best[False], best[True]
    overhead = (en - dis) / dis * 100.0
    _emit("bench_obs_tracing_disabled", dis / n * 1e6,
          f"{n/dis:.1f} req/s, tracer off "
          f"({off_buffered} spans buffered)", group="obs")
    _emit("bench_obs_tracing_enabled", en / n * 1e6,
          f"{n/en:.1f} req/s, tracer on sample_every=1 "
          f"({tstats['buffered']} spans, {tstats['sampled']} requests "
          "sampled)", group="obs")
    # us_per_call carries the clamped percentage so CI can gate on the
    # numeric field; derived keeps the signed value for the report.
    _emit("bench_obs_tracing_overhead_pct", max(overhead, 0.0),
          f"{overhead:+.2f}% enabled vs disabled (<=5% gated); "
          f"absolute {(en - dis)/n*1e6:+.1f}us/req", group="obs")

    # ---- metric write path: a disabled registry must cost ~nothing
    on = MetricsRegistry().counter("bench_writes_total")
    off = MetricsRegistry(enabled=False).counter("bench_writes_total")
    writes = 50_000

    def spin(c):
        for _ in range(writes):
            c.inc()

    ns_on = _time_us(lambda: spin(on), n=1, warmup=1, repeat=5) \
        / writes * 1e3
    ns_off = _time_us(lambda: spin(off), n=1, warmup=1, repeat=5) \
        / writes * 1e3
    # _ns rows: us_per_call holds nanoseconds (a sub-0.1us value would
    # round to zero in the JSON dump and be ungateable)
    _emit("bench_obs_counter_inc_enabled_ns", ns_on,
          f"{ns_on:.0f} ns/inc, labelled counter write", group="obs")
    _emit("bench_obs_counter_inc_disabled_ns", ns_off,
          f"{ns_off:.0f} ns/inc — one enabled-flag check "
          f"({ns_on/max(ns_off, 1e-9):.1f}x cheaper than enabled)",
          group="obs")

    # ---- static verification (REPRO_VERIFY): the off path must invoke
    # the verifier exactly zero times, and the on path must stay within
    # 10% of compile_graph wall time. Interleaving is pointless here (the
    # flag flips a whole phase), so each side takes best-of-3 on a fresh
    # gemm graph; the tile-tuner cache is warm for both after round one.
    from repro import analysis
    from repro.compiler.lower import compile_graph

    def _verify_workload():
        rng2 = np.random.RandomState(7)
        gg = Graph(
            "verify_gemm", {"x": (None, 16)}, ["y"],
            [Node("fc", "gemm", ["x", "fc.w"], "y")],
            {"fc.w": (rng2.randn(16, 8) * 0.2).astype(np.float32)})
        return gg, rng2.rand(4, 16).astype(np.float32)

    def _compile_best_of(rounds=3):
        best_s = float("inf")
        for _ in range(rounds):
            gg, cal = _verify_workload()
            t0 = time.perf_counter()
            prog = compile_graph(gg, cal)
            prog.to_command_stream()
            best_s = min(best_s, time.perf_counter() - t0)
        return best_s

    saved_flag = os.environ.pop("REPRO_VERIFY", None)
    try:
        analysis.reset_counters()
        t_off = _compile_best_of()
        gated_calls = sum(analysis.counters()[s]
                          for s in analysis.GATED_SITES)
        if gated_calls:
            raise AssertionError(
                f"verification ran {gated_calls} time(s) with "
                "REPRO_VERIFY unset — the disabled path must be free")
        os.environ["REPRO_VERIFY"] = "1"
        analysis.reset_counters()
        t_on = _compile_best_of()
        on_calls = sum(analysis.counters()[s]
                       for s in analysis.GATED_SITES)
    finally:
        if saved_flag is None:
            os.environ.pop("REPRO_VERIFY", None)
        else:
            os.environ["REPRO_VERIFY"] = saved_flag
    verify_pct = (t_on - t_off) / t_off * 100.0
    _emit("bench_obs_verify_off_path", t_off * 1e6,
          f"verifier_calls=0 across 3 compile+stream rounds with "
          "REPRO_VERIFY unset (counter-proven)", group="obs")
    _emit("bench_obs_verify_compile_overhead_pct", max(verify_pct, 0.0),
          f"{verify_pct:+.2f}% compile_graph wall with verification on "
          f"({on_calls} verifier calls; <=10% gated)", group="obs")


def bench_lm():
    """Continuous-batching LM decode vs the static chunked baseline.

    Workload: a heterogeneous stream of 16 greedy requests (prompts 4-16
    tokens; every 4th request wants a long completion, the rest short) on
    the stablelm smoke config. Static = ``Server.generate`` in arrival-
    order chunks of ``batch_slots``: each chunk decodes
    ``max(max_new_tokens)`` steps, so one straggler pins three finished
    slots. Continuous = ``ContinuousLMEngine.serve``: requests join/leave
    the slot arena at token boundaries, a freed slot admits the next
    prompt on the very next step. Both paths run post-warmup (closed jit
    caches); the continuous row asserts zero steady-state recompiles and
    every request is checked bit-exact against a single-request static
    decode before the rows are emitted.
    """
    from repro.configs.base import get_arch
    from repro.launch.serve import GenRequest, Server
    from repro.serving import ContinuousLMEngine

    cfg = get_arch("stablelm-1.6b").smoke
    slots, max_len = 4, 64
    rng = np.random.RandomState(7)
    reqs = []
    for i in range(16):
        L = int(rng.randint(4, 17))
        if i % 4 == 0:                      # 1-in-4 long completions
            M = int(min(40 + rng.randint(0, 9), max_len - L))
        else:
            M = int(rng.randint(4, 9))
        reqs.append((rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32),
                     M))
    n_tok = sum(m for _, m in reqs)

    # ---- static baseline: chunked Server.generate, post-warmup
    server = Server(cfg, batch_slots=slots, max_len=max_len, seed=0)
    chunks = [reqs[i:i + slots] for i in range(0, len(reqs), slots)]
    for c in chunks:                        # warm the per-shape jit cache
        server.generate([GenRequest(p.copy(), m) for p, m in c])
    lat_static, t0 = [], time.perf_counter()
    for c in chunks:
        server.generate([GenRequest(p.copy(), m) for p, m in c])
        done = time.perf_counter() - t0     # whole chunk finishes together
        lat_static += [done * 1e3] * len(c)
    dt_static = time.perf_counter() - t0
    steps_static = sum(max(m for _, m in c) for c in chunks)
    _emit("bench_lm_static", dt_static / n_tok * 1e6,
          f"{n_tok/dt_static:.1f} tok/s over {len(reqs)} reqs "
          f"({n_tok} tokens, {steps_static} chunk-steps); "
          f"p50 {np.percentile(lat_static, 50):.1f}ms "
          f"p99 {np.percentile(lat_static, 99):.1f}ms; "
          f"chunks of {slots} decode max(max_new) steps", group="lm")

    # ---- continuous engine: same stream through the slot arena
    engine = ContinuousLMEngine(cfg, batch_slots=slots, max_len=max_len,
                                seed=0)
    engine.warmup()
    t0 = time.perf_counter()
    out = engine.serve([GenRequest(p.copy(), m) for p, m in reqs])
    dt_cont = time.perf_counter() - t0
    em = engine.engine_metrics()
    recompiles = engine.stats()["recompiles_after_warmup"]
    assert recompiles == 0, f"steady-state recompiles: {engine.stats()}"
    _emit("bench_lm_continuous", dt_cont / n_tok * 1e6,
          f"{n_tok/dt_cont:.1f} tok/s ({em['decode_steps']} decode steps); "
          f"p50 {em['latency_p50_ms']:.1f}ms "
          f"p99 {em['latency_p99_ms']:.1f}ms; "
          f"slot_occupancy={em['slot_occupancy']:.2f}; "
          f"recompiles_after_warmup={recompiles}", group="lm")
    _emit("bench_lm_speedup", 0,
          f"{dt_static/dt_cont:.2f}x tokens/s vs static chunked baseline "
          f"(>=2x required)", group="lm")

    # ---- greedy outputs must be bit-exact per request vs a
    # single-request static decode (no co-resident may perturb anyone)
    exact = all(
        r.out_tokens == server.generate(
            [GenRequest(p.copy(), m)])[0].out_tokens
        for r, (p, m) in zip(out, reqs))
    assert exact, "continuous decode diverged from single-request static"
    _emit("bench_lm_bit_exact", 0,
          f"bit_exact={exact} over {len(reqs)} requests vs "
          f"single-request static decode", group="lm")


def bench_coldstart():
    """AOT artifact store: cold compile vs warm boot of a 2-model x
    2-precision registry.

    Cold = a fresh registry materializing all 4 variants through
    ``compile_graph`` (passes + calibration + packing + autotuning),
    persisting each to an artifact store. Warm = a restarted process (fresh
    registry, fresh graph objects, empty tuner L1) pointed at the same
    store: ``warm_boot()`` must restore every variant with **zero**
    compiles and zero autotuner enumerations, serve bit-exact, and be
    >=5x faster than the cold path (the CI gate)."""
    import shutil
    import tempfile
    from repro.kernels import tuning
    from repro.models.layers import QuantPolicy
    from repro.serving import ModelRegistry

    pols = [QuantPolicy(mode="serial", w_bits=2, a_bits=2, radix_bits=7),
            QuantPolicy(mode="serial", w_bits=4, a_bits=8, radix_bits=7)]

    def register_all(reg):
        # fresh graph objects each time — compiling annotates a graph in
        # place, and a restarted process never sees the annotated one
        keys = []
        for name, seed in (("cold_a", 0), ("cold_b", 7)):
            g, calib = _serving_bench_graph(name, seed)
            keys += [reg.register_graph(name, g, calib, p) for p in pols]
        return keys

    root = tempfile.mkdtemp(prefix="coldstart_store_")
    x = np.random.RandomState(3).rand(2, 8, 8, 8).astype(np.float32)
    try:
        tuning.clear_cache()
        reg = ModelRegistry(store=root)
        keys = register_all(reg)
        t0 = time.perf_counter()
        outs = {str(k): np.asarray(reg.program(k)(x)) for k in keys}
        dt_cold = time.perf_counter() - t0
        _emit("bench_coldstart_cold_compile", dt_cold * 1e6,
              f"{len(keys)} variants (2 models x 2 precisions); "
              f"compiles={reg.compiles}", group="coldstart")

        tuning.clear_cache()                 # a restart has an empty L1
        reg2 = ModelRegistry(store=root)
        keys2 = register_all(reg2)
        t0 = time.perf_counter()
        report = reg2.warm_boot()
        dt_warm = time.perf_counter() - t0
        enums = tuning.cache_info()["enumerations"]
        exact = all(np.array_equal(outs[str(k)],
                                   np.asarray(reg2.program(k)(x)))
                    for k in keys2)
        _emit("bench_coldstart_warm_boot", dt_warm * 1e6,
              f"restored={len(report['restored'])} "
              f"recompiles_after_warm_boot={reg2.compiles} "
              f"autotuner_enumerations={enums} bit_exact={exact}",
              group="coldstart")
        _emit("bench_coldstart_speedup", 0,
              f"{dt_cold/dt_warm:.1f}x warm boot vs cold compile "
              f"(>=5x required)", group="coldstart")
        st = reg2.store.stats()
        _emit("bench_coldstart_store", 0,
              f"programs={st['programs']} blobs={st['blobs']} "
              f"bytes_on_disk={st['bytes_on_disk']} "
              f"dedup_ratio={st['dedup_ratio']} "
              f"load_p50_ms={st['load_p50_ms']}", group="coldstart")
    finally:
        tuning.set_persistent_store(None)
        tuning.clear_cache()
        shutil.rmtree(root, ignore_errors=True)


def bench_distributed():
    """Mesh-of-MVU-banks scaling: the mixed W2A2+W4A8 serving stream at 1
    vs 4 banks (one 8-slot bank per device).

    Runs :func:`benchmarks.distributed.run` in this process on its own
    devices. On the CPU backend with fewer than four devices it runs that
    module as a worker process instead, which forces
    ``--xla_force_host_platform_device_count=8`` before jax initializes;
    that worker is never used where a chip may be held. A failure in
    either raises, so the run exits non-zero. Scaling is reported in two
    domains: **virtual** (the barrel-controller cycle clock the paper
    tables model — the >=2x CI gate) and **wall** (this host; fake
    devices share the physical cores, so wall scaling is informational).
    """
    import jax
    from benchmarks import distributed
    if len(jax.devices()) >= 4:
        r = distributed.run()
    elif os.environ.get("JAX_PLATFORMS") == "cpu":
        import json as _json
        import subprocess
        import sys
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)      # worker sets its own device count
        env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.distributed"],
            capture_output=True, text=True, env=env, timeout=1200,
            check=True)
        r = _json.loads(out.stdout.strip().splitlines()[-1])
    else:
        raise RuntimeError(
            f"bench_distributed needs 4 devices, found {len(jax.devices())}")
    w1, w4 = r["wall"]["1"], r["wall"]["4"]
    v1, v4 = r["virtual"]["1"], r["virtual"]["4"]
    vscale = v1["virtual_seconds"] / v4["virtual_seconds"]
    wscale = w4["req_s"] / w1["req_s"]
    _emit("bench_distributed_banks1", 1e6 / w1["req_s"],
          f"{w1['req_s']:.1f} req/s wall; "
          f"{v1['req_per_vsec']:.0f} req/vsec virtual (8 slots); "
          f"recompiles_after_warmup={w1['recompiles']}", group="distributed")
    _emit("bench_distributed_banks4", 1e6 / w4["req_s"],
          f"{w4['req_s']:.1f} req/s wall; "
          f"{v4['req_per_vsec']:.0f} req/vsec virtual (32 slots); "
          f"recompiles_after_warmup={w4['recompiles']}; "
          f"bit_exact={w4['bit_exact']}; "
          f"bank_util={w4['scheduler']['bank_utilization']}", group="distributed")
    _emit("bench_distributed_scaling", 0,
          f"{vscale:.2f}x virtual-throughput scaling 1->4 banks "
          f"(modeled 8->32 MVU slots on the booked mixed W2A2+W4A8 "
          f"stream; >=2x required); wall {wscale:.2f}x on this host "
          f"({r['n_devices']} fake devices over {r['cpu_count']} cores)",
          group="distributed")
    sh = r["sharded"]
    _emit("bench_distributed_sharded_batch", 1e6 / sh["img_s_n"],
          f"batch {sh['batch']} sharded over 4 banks: "
          f"{sh['img_s_n']:.0f} img/s vs {sh['img_s_1']:.0f} single-device "
          f"({sh['img_s_n']/sh['img_s_1']:.2f}x wall); "
          f"bit_exact={sh['bit_exact']}", group="distributed")
    pl = r["pipelined"]
    _emit("bench_distributed_pipeline", 1e6 / pl["img_s"],
          f"{pl['img_s']:.0f} img/s over {len(pl['stages'])} pipeline "
          f"stages (steps {pl['stages']}); bit_exact={pl['bit_exact']}",
          group="distributed")


def bench_calibration():
    """Measured profiler + cost-model calibration gates (EXPERIMENTS.md
    §Calibration):

    - per-step profile of a small compiled W2A2 CNN, then the fitted
      ns-per-virtual-cycle and max |relative residual| of the cost model
      (both trajectory-tracked scalars);
    - measured tile re-rank: the measured winner is never slower than
      the analytic choice (``never_slower=True`` gated in ``derived``);
    - profiler off-path: plain serving runs emit zero measured spans —
      the profiler is opt-in (``measured_spans=0`` gated in ``derived``).
    """
    import jax
    import jax.numpy as jnp
    from repro.compiler import compile_graph
    from repro.core import bitops
    from repro.core.bitserial import SerialSpec, plan_spec
    from repro.kernels import tuning
    from repro.kernels.bitserial_matmul import bitserial_matmul_v2_pallas
    from repro.models.layers import QuantPolicy
    from repro.obs import Tracer, chrome_trace, fit, profile_program

    # --- profile a compiled Program and fit the calibration ------------
    # three serial layers (two convs + gemm) so the per-kind fit has
    # multiple conv samples and the residual row is non-trivial
    from repro.compiler import Graph, Node
    rng = np.random.RandomState(11)
    g = Graph(
        "calib_cnn", {"x": (None, 8, 8, 8)}, ["y"],
        [Node("c1", "conv2d", ["x", "c1.w"], "c1.y",
              {"stride": 1, "padding": 1}),
         Node("c1.relu", "relu", ["c1.y"], "c1.r"),
         Node("c2", "conv2d", ["c1.r", "c2.w"], "c2.y",
              {"stride": 1, "padding": 1}),
         Node("c2.relu", "relu", ["c2.y"], "c2.r"),
         Node("gap", "global_avg_pool", ["c2.r"], "pooled"),
         Node("fc", "gemm", ["pooled", "fc.w"], "y")],
        {"c1.w": (rng.randn(3, 3, 8, 16) * 0.2).astype(np.float32),
         "c2.w": (rng.randn(3, 3, 16, 16) * 0.2).astype(np.float32),
         "fc.w": (rng.randn(16, 10) * 0.2).astype(np.float32)})
    x = jnp.asarray(rng.rand(4, 8, 8, 8), jnp.float32)
    prog = compile_graph(g, x, policy=QuantPolicy(
        mode="serial", w_bits=2, a_bits=2, radix_bits=7), backend="xla")
    t0 = time.perf_counter()
    prof = profile_program(prog, batch=4, warmup=1, repeats=2)
    prof_us = (time.perf_counter() - t0) * 1e6
    cal = fit(prof)
    _emit("bench_calibration_profile", prof_us,
          f"{len(prof.steps)} steps profiled "
          f"({len(prof.serial_steps)} serial), warmup=1 best-of-2",
          group="calibration")
    _emit("bench_calibration_fit", cal.ns_for(),
          f"fitted ns/virtual-cycle (pooled, {cal.n_samples} samples; "
          "ns in us_per_call)", group="calibration")
    _emit("bench_calibration_residual", cal.max_abs_residual,
          f"max |rel residual|; outliers={list(cal.outliers)}",
          group="calibration")

    # --- measured tile re-rank: never slower than the analytic pick ----
    m, k, n = 64, 256, 128
    spec = SerialSpec(8, 4, True, True, 7)
    v2 = plan_spec(spec)
    rng = np.random.RandomState(3)
    wp = bitops.pack_bitplanes(bitops.pad_to(bitops.to_bitplanes(
        jnp.asarray(rng.randint(-8, 8, (k, n)).astype(np.int32)), 4),
        32, axis=1), axis=1)
    xp = bitops.pack_bitplanes(bitops.pad_to(bitops.to_bitplanes(
        jnp.asarray(rng.randint(-128, 128, (m, k)).astype(np.int32)), 8),
        32, axis=-1), axis=-1)
    scale = np.ones(n, np.float32)
    times: dict = {}

    def measure(cfg):
        key = tuple(sorted(cfg.kernel_kwargs().items()))
        if key not in times:
            fn = jax.jit(lambda xx, ww: bitserial_matmul_v2_pallas(
                xx, ww, scale, None, spec=v2, k=k, interpret=True,
                **cfg.kernel_kwargs()))
            jax.block_until_ready(fn(xp, wp))      # compile + warmup
            times[key] = _time_us(
                lambda: jax.block_until_ready(fn(xp, wp)), n=2) * 1e-6
        return times[key]

    tuning.clear_cache()
    analytic = tuning.choose_tile(m, k, n, spec)
    chosen = tuning.choose_tile_measured(m, k, n, spec, measure=measure,
                                         top_k=3)
    t_an, t_ch = measure(analytic), measure(chosen)
    _emit("bench_calibration_rerank", t_ch * 1e6,
          f"measured ({chosen.block_m},{chosen.block_n},{chosen.block_k})"
          f" vs analytic ({analytic.block_m},{analytic.block_n},"
          f"{analytic.block_k}) {t_an * 1e6:.0f}us over "
          f"{len(times)} timed tiles; never_slower={t_ch <= t_an}",
          group="calibration")

    # --- off-path: the profiler must cost nothing when not invoked -----
    tr = Tracer()
    jax.block_until_ready(prog(x))
    jax.block_until_ready(prog(x))
    trace = chrome_trace(tr)
    n_measured = sum(1 for ev in trace["traceEvents"]
                     if ev.get("pid") == "measured")
    _emit("bench_calibration_off_path", 0,
          f"measured_spans={n_measured} "
          f"buffered={tr.stats()['buffered']} (profiler is opt-in)",
          group="calibration")


GROUPS = {
    "tables": [table2_model_sizes, table3_resnet9_cycles, table5_cnv_fps,
               table6_resnet50],
    "kernels": [bench_serial_matmul, bench_pallas_kernel, bench_tuner],
    "conv": [bench_conv_layers, bench_conv_pallas_kernel, bench_resnet9_e2e],
    "compile": [bench_compile_resnet9, bench_compile_dispatch],
    "serve": [bench_quantized_lm_serve],
    "serving": [bench_serving],
    "distributed": [bench_distributed],
    "coldstart": [bench_coldstart],
    "lm": [bench_lm],
    "obs": [bench_obs],
    "calibration": [bench_calibration],
}


def main(argv=None) -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench groups "
                         f"({'/'.join(GROUPS)}); default: all")
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="path for the machine-readable dump "
                         "('' disables)")
    ap.add_argument("--conv-json", default="BENCH_conv.json",
                    help="path for the conv/ResNet9 rows dump "
                         "('' disables)")
    ap.add_argument("--compile-json", default="BENCH_compile.json",
                    help="path for the graph-compiler rows dump "
                         "('' disables)")
    ap.add_argument("--serving-json", default="BENCH_serving.json",
                    help="path for the serving-runtime rows dump "
                         "('' disables)")
    ap.add_argument("--distributed-json", default="BENCH_distributed.json",
                    help="path for the bank-scaling rows dump "
                         "('' disables)")
    ap.add_argument("--coldstart-json", default="BENCH_coldstart.json",
                    help="path for the artifact warm-boot rows dump "
                         "('' disables)")
    ap.add_argument("--lm-json", default="BENCH_lm.json",
                    help="path for the continuous-batching LM rows dump "
                         "('' disables)")
    ap.add_argument("--obs-json", default="BENCH_obs.json",
                    help="path for the observability overhead rows dump "
                         "('' disables)")
    ap.add_argument("--calibration-json", default="BENCH_calibration.json",
                    help="path for the profiler/calibration rows dump "
                         "('' disables)")
    args = ap.parse_args(argv)
    groups = list(GROUPS) if not args.only else [
        g.strip() for g in args.only.split(",") if g.strip()]
    unknown = [g for g in groups if g not in GROUPS]
    if unknown:
        ap.error(f"unknown bench group(s) {unknown}; "
                 f"choose from {list(GROUPS)}")
    print("name,us_per_call,derived")
    for g in groups:
        for fn in GROUPS[g]:
            fn()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(_ROWS, f, indent=1, sort_keys=True)
        print(f"# wrote {len(_ROWS)} rows to {args.json}")
    group_paths = {"conv": args.conv_json, "compile": args.compile_json,
                   "serving": args.serving_json,
                   "distributed": args.distributed_json,
                   "coldstart": args.coldstart_json,
                   "lm": args.lm_json,
                   "obs": args.obs_json,
                   "calibration": args.calibration_json}
    for grp, path in group_paths.items():
        keys = _GROUP_KEYS[grp]
        if not path or not keys:
            continue
        rows = {k: _ROWS[k] for k in keys}
        with open(path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
        print(f"# wrote {len(rows)} rows to {path}")


if __name__ == "__main__":
    main()
