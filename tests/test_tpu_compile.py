"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

Interpret mode never meets Mosaic's tiling, layout and VMEM checks, so
each kernel of the serving path is compiled here at real widths by the
TPU compiler for one chip of a described ``v5e:2x2`` topology, and the
result must hold the kernel (``tpu_custom_call``). Nothing runs: these
tests say the kernels compile, not that they are right or fast.

The topology is described inside a fixture (never at import time), and
every case compiles in this process: only one process at a time may load
the TPU compiler's library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bitserial import SerialSpec, plan_spec
from repro.core.quant import QuantSpec


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip holds no arrays: its compiles must not be cached
    # for a later load
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(w_bits, a_bits):
    return plan_spec(SerialSpec(a_bits=a_bits, w_bits=w_bits, radix_bits=7))


# ResNet9 layers at the paper's widths, batch 8: (h, c_in, c_out, stride)
CONVS = {"conv1": (32, 64, 64, 1), "conv3": (32, 64, 128, 2),
         "conv7": (8, 256, 512, 2)}


@pytest.mark.parametrize("layer", list(CONVS))
def test_conv_v2_compiles(one_chip, layer):
    """W2A2 with the packed requant epilogue and W4A8 with float output,
    each at the tuner's tile."""
    from repro.kernels.ops import serial_conv2d_packed_op
    h, ci, co, stride = CONVS[layer]
    for (w, a), packed in (((2, 2), True), ((4, 8), False)):
        def conv(x, wp, scale, bias, rs, w=w, a=a, packed=packed):
            return serial_conv2d_packed_op(
                x, wp, scale, bias, spec=_spec(w, a), ci=ci, stride=stride,
                padding=1, relu=True,
                requant=QuantSpec(a, True) if packed else None,
                requant_scale=rs if packed else None, emit_packed=packed,
                backend="pallas_v2")
        txt = _compile_text(
            conv, one_chip, ((a, 8, h, h, ci // 32), jnp.uint32),
            ((w, 3, 3, ci // 32, co), jnp.uint32), ((co,), jnp.float32),
            ((co,), jnp.float32), ((), jnp.float32))
        assert "tpu_custom_call" in txt, (layer, w, a)


@pytest.mark.parametrize("w,a", [(4, 8), (2, 2)], ids=["W4A8", "W2A2"])
def test_conv1_compiles_at_served_batch(one_chip, w, a):
    """conv1 at the served batch of 256 with the tuner's tile and the
    packed requant epilogue: Mosaic's tiling and VMEM checks see the row
    ring at the size the benchmark runs."""
    from repro.kernels import tuning
    from repro.kernels.ops import serial_conv2d_packed_op
    n, h, ci, co = 256, 32, 64, 64
    spec = _spec(w, a)
    tc = tuning.choose_conv_tile(n, h, h, ci, co, fh=3, fw=3, stride=1,
                                 padding=1, spec=spec, out_bits=a)
    assert tc.block_nb == 8

    def conv(x, wp, scale, bias, rs):
        return serial_conv2d_packed_op(
            x, wp, scale, bias, spec=spec, ci=ci, stride=1, padding=1,
            relu=True, requant=QuantSpec(a, True), requant_scale=rs,
            emit_packed=True, backend="pallas_v2", **tc.kernel_kwargs())
    txt = _compile_text(
        conv, one_chip, ((a, n, h, h, ci // 32), jnp.uint32),
        ((w, 3, 3, ci // 32, co), jnp.uint32), ((co,), jnp.float32),
        ((co,), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in txt


def test_matmul_v2_compiles_at_lm_width(one_chip):
    """The stablelm-1.6b MLP up-projection, W4A8, 256 rows."""
    from repro.kernels.ops import serial_matmul_packed_op
    m, k, n = 256, 2048, 5632

    def mm(x, wp, scale, bias):
        return serial_matmul_packed_op(x, wp, scale, bias, spec=_spec(4, 8),
                                       k=k, backend="pallas_v2")
    txt = _compile_text(mm, one_chip, ((8, m, k // 32), jnp.uint32),
                        ((4, k // 32, n), jnp.uint32), ((n,), jnp.float32),
                        ((n,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_matmul_v2_packed_output_compiles_at_4096(one_chip):
    """W2A2 with the packed requant epilogue over a 4096-wide output
    block: the packer's matrices stay one (1024, 128) chunk at a time."""
    from repro.kernels.ops import serial_matmul_packed_op
    m, k, n = 256, 128, 4096

    def mm(x, wp, scale, bias, rs):
        return serial_matmul_packed_op(
            x, wp, scale, bias, spec=_spec(2, 2), k=k, relu=True,
            requant=QuantSpec(2, True), requant_scale=rs, emit_packed=True,
            backend="pallas_v2")
    txt = _compile_text(mm, one_chip, ((2, m, k // 32), jnp.uint32),
                        ((2, k // 32, n), jnp.uint32), ((n,), jnp.float32),
                        ((n,), jnp.float32), ((), jnp.float32))
    assert "tpu_custom_call" in txt


def test_quantize_pack_compiles(one_chip):
    from repro.kernels.quantize_pack import quantize_pack_pallas

    def qp(x, scale):
        return quantize_pack_pallas(x, scale, QuantSpec(8, True))
    txt = _compile_text(qp, one_chip, ((256, 2048), jnp.float32),
                        ((), jnp.float32))
    assert "tpu_custom_call" in txt


def test_quantize_pack_compiles_at_lm_width(one_chip):
    """A row as wide as the stablelm-1.6b MLP: the default block takes
    fewer rows, so it stays within the scoped VMEM."""
    from repro.kernels.quantize_pack import quantize_pack_pallas

    def qp(x, scale):
        return quantize_pack_pallas(x, scale, QuantSpec(8, True))
    txt = _compile_text(qp, one_chip, ((256, 5632), jnp.float32),
                        ((), jnp.float32))
    assert "tpu_custom_call" in txt


def test_matmul_v1_compiles(one_chip):
    from repro.kernels.bitserial_matmul import bitserial_matmul_pallas
    m, k, n = 256, 2048, 2048

    def mm(x, wp, scale, bias):
        return bitserial_matmul_pallas(x, wp, scale, bias, spec=_spec(2, 2),
                                       k=k)
    txt = _compile_text(mm, one_chip, ((m, k), jnp.int8),
                        ((2, k // 32, n), jnp.uint32), ((n,), jnp.float32),
                        ((n,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_resnet9_bucket_step_compiles(one_chip):
    """One whole jitted bucket step of a pallas_v2 ResNet9 Program: every
    hidden conv is a kernel."""
    from repro.compiler import compile_graph
    from repro.compiler.executor import make_runner
    from repro.models.layers import QuantPolicy
    from repro.models.resnet import ResNet9Config, resnet9_graph, resnet9_init
    cfg = ResNet9Config()
    graph = resnet9_graph(resnet9_init(jax.random.PRNGKey(0), cfg), cfg)
    calib = np.random.RandomState(1).rand(8, 32, 32, 3).astype(np.float32)
    prog = compile_graph(graph, calib, backend="pallas_v2", policy=QuantPolicy(
        mode="serial", w_bits=2, a_bits=2, radix_bits=7))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        prog.params)
    x = jax.ShapeDtypeStruct((8, 32, 32, 3), jnp.float32, sharding=one_chip)
    txt = jax.jit(make_runner(prog)).lower(params, x).compile().as_text()
    assert txt.count("tpu_custom_call") >= len(cfg.layers)
