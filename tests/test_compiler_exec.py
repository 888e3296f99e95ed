"""Golden tests for the compiled execution path.

The compiled ResNet9 Program must be **bit-exact** against the hand-written
packed deployment path (`resnet9_forward_packed`) — same calibration batch,
same kernels, zero ULP of slack — and must agree with the float reference
on argmax. The same Program's CommandStream lowering must reproduce the
hand-built codegen path's per-MVU cycle summary.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.codegen import generate
from repro.models.resnet import (ResNet9Config, resnet9_compile,
                                 resnet9_cost_layers, resnet9_forward,
                                 resnet9_forward_float,
                                 resnet9_forward_packed, resnet9_init,
                                 resnet9_pack)


@pytest.fixture(scope="module")
def setup():
    cfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(0), cfg)
    images = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3),
                         jnp.float32)
    prog = resnet9_compile(params, images, cfg, backend="xla")
    return cfg, params, images, prog


def test_compiled_resnet9_bit_exact_vs_hand_packed(setup):
    cfg, params, images, prog = setup
    packed = resnet9_pack(params, images, cfg)
    ref = resnet9_forward_packed(packed, images, cfg, backend="xla")
    out = prog(images)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_compiled_resnet9_matches_reference_paths(setup):
    cfg, params, images, prog = setup
    out = np.asarray(prog(images))
    # quantized reference forward: identical integer path, so bit-exact
    # modulo the packed chain's fused requant — argmax must agree with the
    # quantized path and the logits stay in the float path's ballpark
    q = np.asarray(resnet9_forward(params, images, cfg))
    f = np.asarray(resnet9_forward_float(params, images, cfg))
    assert out.shape == q.shape == f.shape
    assert np.all(np.isfinite(out))
    assert np.array_equal(np.argmax(out, -1), np.argmax(q, -1))
    # W2A2 vs fp32: coarse quantization — demand finite, same scale
    assert np.max(np.abs(out - f)) < 10 * (np.max(np.abs(f)) + 1)


def test_compiled_command_stream_matches_hand_codegen(setup):
    cfg, params, images, prog = setup
    for mode in ("pipelined", "distributed"):
        hand = generate(resnet9_cost_layers(cfg), mode=mode,
                        a_bits=cfg.a_bits, w_bits=cfg.w_bits)
        comp = prog.to_command_stream(mode=mode)
        assert comp.per_mvu_cycles == hand.per_mvu_cycles
        assert comp.total_cycles_pipelined() == hand.total_cycles_pipelined()
    # fused conv+relu+requant nodes map to CONV2D jobs (the codegen fix):
    comp = prog.to_command_stream()
    conv_jobs = [j for j in comp.jobs if j.op.value == "conv2d"]
    assert len(conv_jobs) == len(cfg.layers)
    assert all(j.use_relu for j in conv_jobs)
    assert {j.tag for j in conv_jobs} == {n for n, *_ in cfg.layers}


def test_compiled_program_reruns_on_new_batch(setup):
    """The Program re-jits per batch shape; weights stay packed."""
    cfg, params, images, prog = setup
    out = prog(jnp.concatenate([images, images], axis=0))
    ref = prog(images)
    np.testing.assert_array_equal(np.asarray(out[:2]), np.asarray(ref))


def test_compiled_backend_retarget_is_exact_small():
    """XLA oracle lowering vs the Pallas v2 kernels (interpret mode on
    CPU) — the same Program, no re-lowering, identical bits. Reduced
    stack: full ResNet9 in interpret mode is CPU-prohibitive (same scale
    as test_conv_v2's pallas e2e)."""

    class SmallCfg(ResNet9Config):
        layers = (("conv1", 64, 32, 1, False),
                  ("conv2", 32, 32, 2, False),
                  ("conv3", 32, 48, 1, True))

    cfg = SmallCfg()
    params = resnet9_init(jax.random.PRNGKey(1), cfg)
    images = jnp.asarray(np.random.RandomState(0).rand(2, 16, 16, 3),
                         jnp.float32)
    prog = resnet9_compile(params, images, cfg, backend="xla", input_hw=16)
    o_xla = prog(images, backend="xla")
    o_pl = prog(images, backend="pallas_v2", interpret=True)
    np.testing.assert_array_equal(np.asarray(o_xla), np.asarray(o_pl))


def test_cnn_server_compiled_default():
    """launch.serve.CNNServer serves through the compiler by default."""
    from repro.launch.serve import CNNServer
    server = CNNServer(calib_batch=2, backend="xla")
    logits = server.classify(np.random.RandomState(0)
                             .rand(2, 32, 32, 3).astype(np.float32))
    assert logits.shape == (2, 10)
    assert np.all(np.isfinite(logits))
    rep = server.cycle_report()
    assert "conv1" in rep and "mvu" in rep


def test_cnn_server_serves_two_precisions():
    """One CNNServer, one registry, two resident precisions: each key
    serves its own Program's logits."""
    from repro.launch.serve import CNNServer
    from repro.models.layers import QuantPolicy
    images = np.random.RandomState(1).rand(2, 32, 32, 3).astype(np.float32)
    with CNNServer(calib_batch=2, backend="xla") as server:
        hi = server.add_precision(QuantPolicy(
            mode="serial", w_bits=4, a_bits=8, radix_bits=7))
        assert str(hi).endswith("@W4A8")
        for key in (server.key, hi):
            want = np.asarray(server.registry.program(key)(images))
            np.testing.assert_array_equal(server.classify(images, key),
                                          want)
        caches = server.metrics()["bucket_caches"]
        assert {c["replica_devices"][0] for c in caches.values()} == {0}
