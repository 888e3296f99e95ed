"""The graph-compiler flow end to end: import → compile → execute → cost.

 1. build ResNet9 as an IR graph, save it to the native JSON format and
    re-import it (the always-available front end),
 2. compile: passes (fold/fuse/annotate/DCE) → calibration → AOT weight
    packing + per-node tile autotuning → executable packed Program,
 3. execute the Program and cross-check against the hand-written packed
    path (`resnet9_forward_packed` — bit-exact),
 4. lower the same Program to the controller CommandStream and print the
    per-MVU cycle estimate (paper §3.3's artifact, now for ANY imported
    model),
 5. save the Program to an artifact store, load it back through a fresh
    store handle with ``load_program`` — no ONNX, calibration data, or
    autotuner on the serving side (the BARVINN deployment story: ship the
    command stream, not the compiler) — and check it serves identical
    logits; all in this process, which may already hold the chip,
 6. if the optional `onnx` package is installed, also build a tiny ONNX
    model in-process and run it through the ONNX-subset importer;
    otherwise print the graceful skip.

Run: PYTHONPATH=src python examples/compile_and_run.py
"""

import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

from repro.compiler import (HAS_ONNX, compile_graph, graph_from_json,
                            graph_to_json, import_onnx)
from repro.models.resnet import (ResNet9Config, resnet9_init, resnet9_graph,
                                 resnet9_pack, resnet9_forward_packed)
from repro.models.layers import QuantPolicy


def main():
    cfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.rand(8, 32, 32, 3).astype(np.float32))

    print("=== 1. native JSON graph round-trip ===")
    g = resnet9_graph(params, cfg)
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "resnet9.json")
        graph_to_json(g, path)
        size_kb = os.path.getsize(path) / 1024
        g = graph_from_json(path)
    print(f"resnet9 -> {size_kb:.0f} KiB JSON -> {len(g.nodes)} nodes, "
          f"{len(g.initializers)} initializers")

    print("\n=== 2. compile ===")
    policy = QuantPolicy(mode="serial", w_bits=cfg.w_bits, a_bits=cfg.a_bits,
                         radix_bits=cfg.radix_bits)
    prog = compile_graph(g, images, policy=policy, backend="xla")
    kinds = {}
    for s in prog.steps:
        kinds[s.kind] = kinds.get(s.kind, 0) + 1
    print(f"{len(prog.steps)} steps: {kinds}")
    for name, tc in list(prog.meta["tiles"].items())[:2]:
        print(f"  tuned {name}: {tc.kernel_kwargs()}")

    print("\n=== 3. execute vs hand-written packed path ===")
    out = prog(images)
    packed = resnet9_pack(params, images, cfg)
    ref = resnet9_forward_packed(packed, images, cfg, backend="xla")
    print(f"logits {out.shape}; bit-exact vs resnet9_forward_packed: "
          f"{bool(jnp.all(out == ref))}")

    print("\n=== 4. cycle estimate (CommandStream lowering) ===")
    cs = prog.to_command_stream(mode="pipelined")
    busiest = max(cs.per_mvu_cycles)
    print(f"{len(cs.jobs)} jobs; per-MVU cycles {cs.per_mvu_cycles}; "
          f"pipelined FPS @250MHz ~ {250e6/busiest:.0f}")

    print("\n=== 5. artifact save -> load with no compiler -> serve ===")
    from repro.compiler import ArtifactStore, load_program, save_program
    with tempfile.TemporaryDirectory() as td:
        ref = save_program(prog, ArtifactStore(td), name="resnet9@W2A2")
        st = ArtifactStore(td).stats()
        print(f"saved {ref[:12]}… ({st['blobs']} blobs, "
              f"{st['bytes_on_disk']/1024:.0f} KiB on disk)")
        # a fresh store handle and load_program: no graph, calibration
        # data or tuner — what a fleet node that only ships the artifact
        # runs (in this process, which may already hold the chip)
        loaded = load_program("resnet9@W2A2", ArtifactStore(td))
        got = np.asarray(loaded(images))
        here = np.asarray(prog(images))
        if not np.array_equal(got, here):
            raise SystemExit("loaded artifact served different logits")
        print(f"loaded artifact served logits sum {float(got.sum())} — "
              "identical to the compiled Program")

    print("\n=== 6. ONNX importer (optional extra) ===")
    if not HAS_ONNX:
        print("onnx not installed — skipping (pip install onnx to enable; "
              "the native JSON front end above needs no extra deps)")
        return
    import onnx
    from onnx import helper, numpy_helper
    w = rng.randn(4, 3, 3, 3).astype(np.float32) * 0.3  # OIHW
    wfc = rng.randn(4, 10).astype(np.float32) * 0.3
    model = helper.make_model(helper.make_graph(
        [helper.make_node("Conv", ["x", "w"], ["c"], strides=[1, 1],
                          pads=[1, 1, 1, 1]),
         helper.make_node("Relu", ["c"], ["r"]),
         helper.make_node("GlobalAveragePool", ["r"], ["p"]),
         helper.make_node("Flatten", ["p"], ["f"]),
         helper.make_node("Gemm", ["f", "wfc"], ["y"])],
        "tiny_onnx",
        [helper.make_tensor_value_info(
            "x", onnx.TensorProto.FLOAT, [2, 3, 8, 8])],   # NCHW
        [helper.make_tensor_value_info("y", onnx.TensorProto.FLOAT, [2, 10])],
        [numpy_helper.from_array(w, "w"),
         numpy_helper.from_array(wfc, "wfc")]))
    gg = import_onnx(model)
    calib = jnp.asarray(rng.rand(2, 8, 8, 3).astype(np.float32))  # NHWC
    prog2 = compile_graph(gg, calib, policy=QuantPolicy(
        mode="serial", w_bits=4, a_bits=4, radix_bits=7), backend="xla")
    print(f"imported {len(gg.nodes)} ONNX nodes -> {len(prog2.steps)} steps; "
          f"logits {prog2(calib).shape}")


if __name__ == "__main__":
    main()
