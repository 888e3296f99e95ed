"""Chip smoke test: serve the paper's ResNet9 on a TPU through the normal
serving entry point, with the Pallas kernels, and check it against the XLA
path on the same chip.

    python chip_smoke.py                  # one TPU chip (fails without one)
    python chip_smoke.py --chips 4        # bank mesh on four chips only
    JAX_PLATFORMS=cpu python chip_smoke.py --interpret   # CPU rehearsal

One chip: ResNet9 at the paper's widths (64->512 channels, 32x32 inputs,
random weights from ``--seed``) is registered at W2A2 and W4A8 in one
``CNNServer``, compiled with ``backend="pallas_v2"``, warmed on every
padding bucket, and asked to classify seeded CIFAR-shaped images per
precision. A second server does the same with ``backend="xla"``. The
Pallas logits must equal the XLA logits. ``--chips 4`` instead serves
W2A2 across four banks (``placement`` banked and sharded) and compares with
one bank. Any failed check raises, so the exit code is non-zero and no
result line is printed; the last line of a passing run is one JSON object
naming the device.

Times printed here are one run each and informational only.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PRECISIONS = {"W2A2": (2, 2), "W4A8": (4, 8)}


def _policy(name: str):
    from repro.models.layers import QuantPolicy
    w, a = PRECISIONS[name]
    return QuantPolicy(mode="serial", w_bits=w, a_bits=a, radix_bits=7)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _compiles(server, key) -> int:
    return server.metrics()["bucket_caches"][str(key)]["compiles"]


def _serve(graph, images, *, backend, interpret, max_batch, seed, names,
           **server_kw):
    """One CNNServer holding every precision in ``names``: compile, check
    the Programs, warm every bucket, classify; returns {name: logits} and
    the server's final metrics."""
    import jax
    import jax.numpy as jnp
    from repro.compiler.executor import make_runner
    from repro.launch.serve import CNNServer

    out = {}
    server = CNNServer(graph, seed=seed, backend=backend,
                       interpret=interpret, policy=_policy(names[0]),
                       max_batch=max_batch, **server_kw)
    keys = {names[0]: server.key}
    for name in names[1:]:
        keys[name] = server.add_precision(_policy(name), precision=name)
    with server:
        for name, key in keys.items():
            tag = f"{backend} {name}"
            if server_kw:
                tag += " " + " ".join(f"{k}={v}" for k, v in server_kw.items())
            t0 = time.perf_counter()
            prog = server.registry.program(key)
            t_prog = time.perf_counter() - t0
            _check(prog.backend == backend and prog.interpret is interpret,
                   f"{tag}: Program has backend={prog.backend!r} "
                   f"interpret={prog.interpret!r}")
            if backend == "pallas_v2" and not interpret:
                x = jnp.zeros((max_batch,) + prog.meta["input_shape"],
                              jnp.float32)
                hlo = jax.jit(make_runner(prog)).lower(prog.params,
                                                       x).as_text()
                _check("tpu_custom_call" in hlo,
                       f"{tag}: no tpu_custom_call in the bucket step")
            t0 = time.perf_counter()
            bucket_compiles = server.service.warmup(key)
            t_warm = time.perf_counter() - t0
            before = _compiles(server, key)
            t0 = time.perf_counter()
            logits = server.classify(images, key)
            t_run = time.perf_counter() - t0
            recompiles = _compiles(server, key) - before
            print(f"{tag}: compile_graph {t_prog:.2f}s, "
                  f"{bucket_compiles} bucket compiles {t_warm:.2f}s, "
                  f"classify {len(images)} images {t_run * 1e3:.1f}ms "
                  f"(one run), recompiles_after_warmup={recompiles}",
                  flush=True)
            _check(recompiles == 0, f"{tag}: {recompiles} recompiles after "
                                    "warmup")
            _check(logits.shape == (len(images), 10)
                   and bool(np_isfinite(logits)),
                   f"{tag}: logits of shape {logits.shape}, or not finite")
            out[name] = logits
        metrics = server.metrics()
    return out, metrics, keys


def np_isfinite(x) -> bool:
    import numpy as np
    return bool(np.all(np.isfinite(x)))


# The one difference a comparison may show, and only on the CPU: XLA's
# CPU backend computes the classifier's dot for a one-image batch as a
# matrix-vector product, whose f32 summation order differs from the
# matrix-matrix product of a larger batch (last ulps of the logits). A
# bank fed one image of a micro-batch meets it; on the chip the logits
# must be identical.
CPU_ONE_ROW_DOT = "XLA CPU one-row dot (matrix-vector summation order)"


def _compare(tag: str, got, want, *, ulp_cause: str = None) -> None:
    """The two logit arrays must be identical. Where ``ulp_cause`` names a
    known float-order difference, they may instead agree on every top-1
    with max |dlogit| <= 1e-5 * max |logit|; the difference is printed
    either way."""
    import numpy as np
    if np.array_equal(got, want):
        print(f"{tag}: logits identical", flush=True)
        return
    diff = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    top1 = bool(np.array_equal(got.argmax(-1), want.argmax(-1)))
    print(f"{tag}: logits differ: max|dlogit|={diff!r} "
          f"max|logit|={scale!r} top1_identical={top1}"
          + (f" (known cause: {ulp_cause})" if ulp_cause else ""),
          flush=True)
    _check(ulp_cause is not None and top1 and diff <= 1e-5 * scale,
           f"{tag}: logits disagree")


def one_chip(graph, images, *, interpret, max_batch, seed) -> None:
    names = list(PRECISIONS)
    pallas, _, _ = _serve(graph, images, backend="pallas_v2",
                          interpret=interpret, max_batch=max_batch,
                          seed=seed, names=names)
    xla, _, _ = _serve(graph, images, backend="xla", interpret=False,
                       max_batch=max_batch, seed=seed, names=names)
    for name in names:
        _compare(f"{name} pallas_v2 vs xla", pallas[name], xla[name])


def four_chips(graph, images, *, interpret, max_batch, seed) -> None:
    import jax
    _check(len(jax.devices()) >= 4,
           f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    ref, _, _ = _serve(graph, images, backend="pallas_v2",
                       interpret=interpret, max_batch=max_batch, seed=seed,
                       names=["W2A2"])
    for placement in ("banked", "sharded"):
        got, m, keys = _serve(graph, images, backend="pallas_v2",
                              interpret=interpret, max_batch=max_batch,
                              seed=seed, names=["W2A2"], n_banks=4,
                              placement=placement)
        reqs = m["scheduler"]["bank_requests"]
        devs = m["bucket_caches"][str(keys["W2A2"])]["replica_devices"]
        print(f"{placement}: bank_requests={reqs} replica_devices={devs}",
              flush=True)
        _check(len(reqs) == 4 and all(r > 0 for r in reqs),
               f"{placement}: not every bank served requests: {reqs}")
        _check(len(set(devs)) == 4,
               f"{placement}: replicas on devices {devs}, not 4 distinct")
        _compare(f"W2A2 {placement} 4 banks vs 1 bank", got["W2A2"],
                 ref["W2A2"],
                 ulp_cause=CPU_ONE_ROW_DOT if interpret else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the bank-mesh "
                         "phase")
    ap.add_argument("--interpret", action="store_true",
                    help="CPU rehearsal: Pallas in interpret mode, few "
                         "images")
    ap.add_argument("--images", type=int, default=None,
                    help="images per precision (default 64; 4 with "
                         "--interpret)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np
    from repro.models.resnet import ResNet9Config, resnet9_graph, resnet9_init

    devices = jax.devices()
    platform = devices[0].platform
    print(f"device: platform={platform} kind={devices[0].device_kind!r} "
          f"count={len(devices)}", flush=True)
    if args.interpret and platform != "cpu":
        # an interpret-mode run on a chip would print the chip's result
        # line although no kernel ran on it
        print(f"error: --interpret is a CPU rehearsal (JAX platform "
              f"{platform!r}); set JAX_PLATFORMS=cpu", file=sys.stderr)
        return 1
    if not args.interpret and platform != "tpu":
        print(f"error: no TPU found (JAX platform {platform!r}); "
              "use --interpret for a CPU rehearsal", file=sys.stderr)
        return 1

    n_images = args.images or (4 if args.interpret else 64)
    # the one-chip phase fills the service's largest bucket; the bank
    # phase cuts the images into eight micro-batches, so that every bank
    # is given work
    max_batch = (min(32, n_images) if args.chips == 1
                 else max(1, min(8, n_images // 8)))
    cfg = ResNet9Config()
    graph = resnet9_graph(resnet9_init(jax.random.PRNGKey(args.seed), cfg),
                          cfg)
    images = np.random.RandomState(args.seed).rand(
        n_images, 32, 32, 3).astype(np.float32)
    phase = one_chip if args.chips == 1 else four_chips
    phase(graph, images, interpret=args.interpret, max_batch=max_batch,
          seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
