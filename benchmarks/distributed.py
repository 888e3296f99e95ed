"""Bank-scaling benchmark worker: serving a mixed W2A2+W4A8 stream across
a mesh of MVU banks (one 8-slot bank per jax device).

:func:`run` measures on the devices the process has (four or more). The
harness (:func:`benchmarks.run.bench_distributed`) calls it in-process
where there are enough devices. On the CPU backend only, it starts this
module as a worker process instead, which forces a multi-device host view
(``--xla_force_host_platform_device_count``) before jax initializes and
prints the result as JSON; a failure exits non-zero.

Two scaling views are reported, deliberately separate:

* **virtual** — the barrel-controller cycle domain the repo's paper tables
  (Table 3/5) already model: the same canonical batch stream booked on 1
  bank (8 slots) vs 4 banks (32 slots) through the serving scheduler's
  least-finish placement. This is the paper's claim ("more banks on a
  bigger part → proportional throughput") measured on real compiled
  command streams, and is what the CI gate asserts ``>= 2x`` on.
* **wall** — end-to-end req/s of the live InferenceService at 1 vs 4
  banks on this host. On a CI box the fake host-platform devices all
  share a couple of physical cores (and XLA's intra-op thread pool
  already spreads the 1-bank run across them), so wall scaling is
  reported for honesty but NOT gated.
"""

import json
import os
import time

import numpy as np


def build_registry():
    from repro.compiler.bench_graphs import tiny_mixed_cnn
    from repro.models.layers import QuantPolicy
    from repro.serving import ModelRegistry
    # the same canonical workload the mesh soak test measures
    g, calib = tiny_mixed_cnn()
    reg = ModelRegistry(backend="xla")
    k_lo = reg.register_graph("cnn", g, calib, QuantPolicy(
        mode="serial", w_bits=2, a_bits=2, radix_bits=7))
    k_hi = reg.register_graph("cnn", g, calib, QuantPolicy(
        mode="serial", w_bits=4, a_bits=8, radix_bits=7))
    return reg, k_lo, k_hi


BURST_SIZES = [1, 3, 16, 6, 9, 16]


def stream(keys, n_requests, seed=1):
    """The canonical mixed-precision client stream: (key, examples[])."""
    rng = np.random.RandomState(seed)
    out, i, total = [], 0, 0
    while total < n_requests:
        n = BURST_SIZES[i % len(BURST_SIZES)]
        xs = [rng.rand(8, 8, 8).astype(np.float32) for _ in range(n)]
        out.append((keys[i % 2], xs))
        total += n
        i += 1
    return out


def serve_wall(reg, keys, n_banks, n_requests=240):
    """Live service throughput at ``n_banks`` (wall clock) + metrics."""
    from repro.serving import InferenceService
    svc = InferenceService(reg, max_batch=16, max_wait_s=0.001,
                          max_queue=1024,
                          n_banks=None if n_banks == 1 else n_banks)
    bursts = stream(keys, n_requests)
    nreq = sum(len(xs) for _, xs in bursts)
    with svc:
        svc.warmup()
        warm = {k: v["compiles"]
                for k, v in svc.metrics()["bucket_caches"].items()}
        t0 = time.perf_counter()
        futs = []
        for key, xs in bursts:
            futs += svc.submit_many(key, xs)
        svc.drain(timeout=600)
        dt = time.perf_counter() - t0
        results = [np.asarray(f.result()) for f in futs]
        m = svc.metrics()
    recompiles = sum(v["compiles"] - warm[k]
                     for k, v in m["bucket_caches"].items())
    # spot-check bit-exactness vs direct single-device Program calls
    import jax.numpy as jnp
    flat = [(k, x) for k, xs in bursts for x in xs]
    bit_exact = True
    progs = {k: reg.program(k) for k in keys}
    for idx in range(0, nreq, max(1, nreq // 16)):
        k, x = flat[idx]
        direct = np.asarray(progs[k](jnp.asarray(x[None]))[0])
        bit_exact &= bool(np.array_equal(results[idx], direct))
    return {"req_s": nreq / dt, "nreq": nreq, "wall_s": dt,
            "recompiles": recompiles, "bit_exact": bit_exact,
            "p50_ms": m["latency_p50_ms"], "p99_ms": m["latency_p99_ms"],
            "scheduler": m["scheduler"], "banks": m["banks"]}


def virtual_scaling(reg, keys, banks=(1, 4), n_requests=240):
    """The canonical stream booked on n banks' worth of MVU slots: the
    cycle-domain makespan each fabric needs — the paper's scaling axis."""
    from repro.serving import SlotScheduler
    progs = {k: reg.program(k) for k in keys}
    bursts = stream(keys, n_requests)
    out = {}
    for nb in banks:
        sched = SlotScheduler(n_banks=nb)
        for key, xs in bursts:
            sched.admit(key, len(xs), program=progs[key])
        m = sched.metrics()
        out[nb] = {"virtual_cycles": m["virtual_cycles"],
                   "virtual_seconds": m["virtual_seconds"],
                   "req_per_vsec": (m["admitted_requests"]
                                    / m["virtual_seconds"]),
                   "bank_utilization": m["bank_utilization"]}
    return out


def sharded_batch(reg, key, n_banks=4, batch=256, iters=10):
    """One big batch: single device vs batch-sharded over the bank mesh."""
    import jax
    import jax.numpy as jnp
    from repro.distributed import program_parallel as pp
    prog = reg.program(key)
    rng = np.random.RandomState(2)
    x = rng.rand(batch, 8, 8, 8).astype(np.float32)
    ref = prog(jnp.asarray(x))
    jax.block_until_ready(ref)
    t0 = time.perf_counter()
    outs = [prog(jnp.asarray(x)) for _ in range(iters)]
    jax.block_until_ready(outs)
    dt1 = time.perf_counter() - t0
    sp = pp.ShardedProgram(prog, pp.bank_mesh(n_banks))
    got = sp(x)
    jax.block_until_ready(got)
    bit_exact = bool(np.array_equal(np.asarray(got), np.asarray(ref)))
    t0 = time.perf_counter()
    outs = [sp(x) for _ in range(iters)]
    jax.block_until_ready(outs)
    dtn = time.perf_counter() - t0
    return {"img_s_1": batch * iters / dt1, "img_s_n": batch * iters / dtn,
            "bit_exact": bit_exact, "batch": batch}


def pipelined(reg, key, n_stages=2, batch=32, iters=10):
    """Consecutive Program steps on consecutive banks (chip-to-chip
    streaming, the paper's pipelined mapping)."""
    import jax
    import jax.numpy as jnp
    from repro.distributed import program_parallel as pp
    prog = reg.program(key)
    rng = np.random.RandomState(3)
    x = rng.rand(batch, 8, 8, 8).astype(np.float32)
    ref = np.asarray(prog(jnp.asarray(x)))
    pl = pp.PipelinedProgram(prog, n_stages=n_stages)
    got = np.asarray(pl(x, n_microbatches=4))
    bit_exact = bool(np.array_equal(got, ref))
    t0 = time.perf_counter()
    outs = [pl(x, n_microbatches=4) for _ in range(iters)]
    jax.block_until_ready(outs)
    dt = time.perf_counter() - t0
    return {"img_s": batch * iters / dt, "bit_exact": bit_exact,
            "stages": [list(b) for b in pl.stage_bounds]}


def run():
    """Every measurement of this module on the process's own devices."""
    import jax
    n_dev = len(jax.devices())
    if n_dev < 4:
        raise RuntimeError(f"bank scaling needs 4 devices, found {n_dev}")
    reg, k_lo, k_hi = build_registry()
    keys = (k_lo, k_hi)
    wall1 = serve_wall(reg, keys, 1)
    wall4 = serve_wall(reg, keys, 4)
    virt = virtual_scaling(reg, keys)
    shard = sharded_batch(reg, k_lo)
    pipe = pipelined(reg, k_lo)
    return {
        "n_devices": n_dev,
        "cpu_count": os.cpu_count(),
        "wall": {"1": wall1, "4": wall4},
        "virtual": {str(k): v for k, v in virt.items()},
        "sharded": shard,
        "pipelined": pipe,
    }


if __name__ == "__main__":
    n = int(os.environ.get("BENCH_BANK_DEVICES", "8"))
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")
    print(json.dumps(run()))
