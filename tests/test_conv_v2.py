"""Implicit-GEMM packed bit-serial conv2d (kernels/bitserial_conv.py) vs the
XLA oracles, interpret mode on CPU.

Golden references:
* ``serial_conv2d`` (integer im2col + serial GEMM) for the int32 conv
  accumulator — itself checked against ``lax.conv_general_dilated``,
* ``serial_conv2d_packed_acts`` for the packed-operand implicit-GEMM
  dataflow,
* ``quantize_pack_ref`` for the fused requant → bit-transpose-pack
  epilogue (bit-identical packed words),
* ``resnet9_forward`` for the end-to-end packed deployment path.
"""

import itertools

import numpy as np
import jax
import jax.lax as lax
import jax.numpy as jnp
import pytest

from repro.core import bitops
from repro.core.bitserial import (SerialSpec, plan_spec, serial_conv2d,
                                  serial_conv2d_packed_acts)
from repro.core.quant import QuantSpec, qrange
from repro.kernels import tuning
from repro.kernels.bitserial_conv import bitserial_conv2d_v2_pallas
from repro.kernels.ops import pack_activations, serial_conv2d_packed_op
from repro.kernels.quantize_pack import quantize_pack_ref


def _pack_w(w, bits):
    planes = bitops.pad_to(bitops.to_bitplanes(jnp.asarray(w), bits), 32,
                           axis=3)
    return bitops.pack_bitplanes(planes, axis=3)


def _rand_case(rng, ba, bw, sa, sw, n, h, w, ci, co, fs=3):
    la, ha = qrange(ba, sa)
    lw, hw = qrange(bw, sw)
    x = rng.randint(la, ha + 1, (n, h, w, ci)).astype(np.int32)
    wt = rng.randint(lw, hw + 1, (fs, fs, ci, co)).astype(np.int32)
    return x, wt


def _dense_ref(x, w, stride, padding):
    out = lax.conv_general_dilated(
        jnp.asarray(x, jnp.float32), jnp.asarray(w, jnp.float32),
        (stride, stride), [(padding, padding)] * 2,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return np.asarray(out).astype(np.int64)


# ---------------------------------------------------------------- bit sweep

BITS_SWEEP = [
    (ba, bw, signed)
    for ba, bw in itertools.product((1, 2, 4, 8), repeat=2)
    for signed in (True, False)
]


@pytest.mark.parametrize("ba,bw,signed", BITS_SWEEP,
                         ids=[f"a{a}w{w}{'s' if s else 'u'}"
                              for a, w, s in BITS_SWEEP])
def test_conv_v2_bits_sweep_matches_oracle(ba, bw, signed):
    """Packed-activation input, exact integer conv accumulator."""
    rng = np.random.RandomState(ba * 37 + bw * 11 + signed)
    x, w = _rand_case(rng, ba, bw, signed, signed, 1, 5, 6, 33, 8)
    spec = plan_spec(SerialSpec(ba, bw, signed, signed, 7))
    ref = _dense_ref(x, w, 1, 1)
    # oracle sanity: integer im2col path and packed implicit-GEMM path
    out_i = serial_conv2d(jnp.asarray(x), jnp.asarray(w), spec,
                          stride=1, padding=1)
    np.testing.assert_array_equal(np.asarray(out_i), ref)
    xp, wp = pack_activations(jnp.asarray(x), ba), _pack_w(w, bw)
    acc = serial_conv2d_packed_acts(xp, wp, spec=spec, ci=33,
                                    stride=1, padding=1)
    np.testing.assert_array_equal(np.asarray(acc), ref)
    out = bitserial_conv2d_v2_pallas(
        xp, wp, np.ones(8, np.float32), None, spec=spec, ci=33,
        stride=1, padding=1, block_co=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64), ref)


def test_conv_v2_faithful_radix1():
    """radix_bits=1 (paper-faithful Algorithm 1) through the conv kernel."""
    rng = np.random.RandomState(3)
    x, w = _rand_case(rng, 3, 5, False, True, 1, 5, 5, 32, 16)
    spec = SerialSpec(3, 5, False, True, 1)
    out = bitserial_conv2d_v2_pallas(
        pack_activations(jnp.asarray(x), 3), _pack_w(w, 5),
        np.ones(16, np.float32), None, spec=spec, ci=32, stride=1,
        padding=1, block_co=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64),
                                  _dense_ref(x, w, 1, 1))


# ------------------------------------------------ stride / padding / ragged

@pytest.mark.parametrize("stride,padding", [(1, 1), (2, 1), (1, 0), (2, 0)])
def test_conv_v2_stride_padding(stride, padding):
    rng = np.random.RandomState(stride * 10 + padding)
    x, w = _rand_case(rng, 4, 4, True, True, 2, 7, 9, 33, 40)
    spec = SerialSpec(4, 4, True, True, 8)
    scale = (rng.rand(40) + 0.5).astype(np.float32)
    bias = rng.randn(40).astype(np.float32)
    out = bitserial_conv2d_v2_pallas(
        pack_activations(jnp.asarray(x), 4), _pack_w(w, 4), scale, bias,
        spec=spec, ci=33, stride=stride, padding=padding, block_co=32,
        block_nb=2, relu=True, interpret=True)
    ref = np.maximum(_dense_ref(x, w, stride, padding) * scale + bias, 0.0)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-6)


@pytest.mark.parametrize("n,h,w,ci,co,bnb", [
    (1, 4, 4, 32, 32, 1),    # minimal aligned
    (3, 5, 8, 33, 17, 2),    # nothing divides; image block pads batch
    (2, 9, 3, 64, 40, 1),    # tall-narrow
])
def test_conv_v2_ragged_shapes(n, h, w, ci, co, bnb):
    rng = np.random.RandomState(n * 100 + h * 10 + ci)
    x, wt = _rand_case(rng, 8, 4, True, True, n, h, w, ci, co)
    spec = SerialSpec(8, 4, True, True, 8)
    out = bitserial_conv2d_v2_pallas(
        pack_activations(jnp.asarray(x), 8), _pack_w(wt, 4),
        np.ones(co, np.float32), None, spec=spec, ci=ci, stride=1,
        padding=1, block_co=32, block_nb=bnb, interpret=True)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64),
                                  _dense_ref(x, wt, 1, 1))


@pytest.mark.parametrize("fs,stride,padding", [(1, 1, 0), (1, 2, 0),
                                               (5, 1, 2)])
def test_conv_v2_filter_sizes(fs, stride, padding):
    """Non-3x3 filters: 1x1 (ResNet50 bottlenecks) and 5x5."""
    rng = np.random.RandomState(fs * 10 + stride)
    x, w = _rand_case(rng, 4, 4, True, True, 2, 6, 6, 32, 16, fs=fs)
    spec = SerialSpec(4, 4, True, True, 8)
    xp, wp = pack_activations(jnp.asarray(x), 4), _pack_w(w, 4)
    ref = _dense_ref(x, w, stride, padding)
    acc = serial_conv2d_packed_acts(xp, wp, spec=spec, ci=32, stride=stride,
                                    padding=padding)
    np.testing.assert_array_equal(np.asarray(acc), ref)
    out = bitserial_conv2d_v2_pallas(
        xp, wp, np.ones(16, np.float32), None, spec=spec, ci=32,
        stride=stride, padding=padding, block_co=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64), ref)


def test_serial_conv2d_integer_patches_wide_operands():
    """The im2col reference path extracts patches in integer dtype — exact
    for wide operands whose accumulators exceed f32's 24-bit mantissa
    (satellite fix: no float32 round-trip)."""
    rng = np.random.RandomState(9)
    x = rng.randint(-(1 << 11), 1 << 11, (1, 6, 6, 16)).astype(np.int64)
    w = rng.randint(-(1 << 11), 1 << 11, (3, 3, 16, 8)).astype(np.int64)
    out = serial_conv2d(jnp.asarray(x, jnp.int32), jnp.asarray(w, jnp.int32),
                        SerialSpec(12, 12, True, True, 7),
                        stride=1, padding=1)
    # exact int64 reference (f32 conv would round above 2^24)
    xp = np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    ref = np.zeros((1, 6, 6, 8), np.int64)
    for fh in range(3):
        for fw in range(3):
            ref += np.einsum("nhwc,co->nhwo",
                             xp[:, fh:fh + 6, fw:fw + 6], w[fh, fw])
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64), ref)


# ------------------------------------------------- fused requant-pack epilogue

@pytest.mark.parametrize("out_bits,out_signed", [(2, True), (4, True),
                                                 (8, True), (3, False)])
def test_conv_v2_fused_pack_epilogue(out_bits, out_signed):
    """Packed output is bit-identical to quantize_pack_ref of the float
    epilogue output — the QuantSer unit fused into the conv."""
    rng = np.random.RandomState(out_bits * 7 + out_signed)
    x, w = _rand_case(rng, 4, 4, True, True, 2, 6, 6, 33, 40)
    spec = SerialSpec(4, 4, True, True, 8)
    scale = np.full(40, 0.03, np.float32)
    rs = 0.4
    rq = QuantSpec(out_bits, out_signed)
    # reference epilogue in f32, same op order as the kernel (a float64
    # intermediate would round differently at quantization boundaries)
    fl = (jnp.asarray(_dense_ref(x, w, 1, 1), jnp.float32)
          * jnp.asarray(scale))
    if not out_signed:
        fl = jnp.maximum(fl, 0.0)
    ref = np.asarray(quantize_pack_ref(
        fl.reshape(-1, 40), jnp.asarray(rs), rq)).reshape(
            out_bits, 2, 6, 6, -1)
    for backend in ("xla", "pallas_v2"):
        out = serial_conv2d_packed_op(
            pack_activations(jnp.asarray(x), 4), _pack_w(w, 4), scale, None,
            spec=spec, ci=33, stride=1, padding=1, relu=not out_signed,
            requant=rq, requant_scale=rs, emit_packed=True, backend=backend,
            block_co=32, block_nb=1, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), ref)


def test_conv_v2_layer_chaining_no_host_hop():
    """Stage L emits packed planes from its fused epilogue; stage L+1's conv
    consumes them directly — numerically identical to the unfused
    conv → requant → pack pipeline."""
    rng = np.random.RandomState(11)
    x, w1 = _rand_case(rng, 4, 4, True, True, 1, 6, 6, 32, 32)
    w2 = rng.randint(-8, 8, (3, 3, 32, 16)).astype(np.int32)
    spec = SerialSpec(4, 4, True, True, 8)
    rs = 0.25
    aq = QuantSpec(4, True)
    xp = pack_activations(jnp.asarray(x), 4)
    packed_h = serial_conv2d_packed_op(
        xp, _pack_w(w1, 4), np.full(32, 0.1, np.float32), None, spec=spec,
        ci=32, stride=1, padding=1, relu=True, requant=aq, requant_scale=rs,
        emit_packed=True, backend="pallas_v2", block_co=32, interpret=True)
    # unfused reference: float epilogue, quantize, pack, second conv
    h_float = np.maximum(_dense_ref(x, w1, 1, 1) * 0.1, 0.0)
    h_codes = np.clip(np.round(h_float / rs), -8, 7).astype(np.int32)
    out = serial_conv2d_packed_op(
        packed_h, _pack_w(w2, 4), np.ones(16, np.float32), None, spec=spec,
        ci=32, stride=1, padding=1, backend="pallas_v2", block_co=32,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64),
                                  _dense_ref(h_codes, w2, 1, 1))


# ------------------------------------------------------------- the row ring

# (a_bits, w_bits, signed, radix, n, h, w, ci, co, fs, stride, padding,
#  block_co, block_nb, out): every case pins block_co below co (so the
# ring refills for each of several Co-blocks) and an image count that
# block_nb does not divide
RING_CASES = {
    "s1p1_float": (4, 4, True, 8, 3, 7, 9, 33, 64, 3, 1, 1, 32, 2, "float"),
    "s2p1_codes": (4, 4, True, 8, 3, 7, 9, 33, 64, 3, 2, 1, 32, 2, "codes"),
    "s1p0_packed": (2, 2, True, 7, 3, 6, 8, 40, 64, 3, 1, 0, 32, 2,
                    "packed"),
    "s2p0_float": (8, 4, True, 8, 5, 9, 7, 32, 64, 3, 2, 0, 32, 4, "float"),
    "fh1_s1_codes": (4, 4, True, 8, 3, 5, 6, 33, 64, 1, 1, 0, 32, 2,
                     "codes"),
    "fh1_s2_packed": (4, 4, True, 8, 3, 6, 6, 33, 64, 1, 2, 0, 32, 2,
                      "packed"),
    "radix1_s1_float": (3, 2, False, 1, 3, 5, 5, 32, 64, 3, 1, 1, 32, 2,
                        "float"),
    "radix1_s2_packed": (2, 3, True, 1, 3, 7, 7, 33, 64, 3, 2, 1, 32, 2,
                         "packed"),
    "wide_ci_s2_codes": (2, 2, True, 7, 3, 5, 5, 300, 64, 3, 2, 1, 32, 2,
                         "codes"),
}


@pytest.mark.parametrize("case", list(RING_CASES))
def test_conv_v2_row_ring_bit_exact(case):
    """Each input row's digits are assembled once into the ring and every
    tap window is sliced from it: bit-exact with the packed implicit-GEMM
    oracle for every stride, padding, filter height, ragged image block,
    Co-block count, multi-digit radix-1 activation (nd_a > 1), channel
    width over one lane slab, and output format."""
    (ba, bw, signed, radix, n, h, w, ci, co, fs, stride, padding, bco, bnb,
     out_kind) = RING_CASES[case]
    rng = np.random.RandomState(sum(map(ord, case)))
    x, wt = _rand_case(rng, ba, bw, signed, signed, n, h, w, ci, co, fs=fs)
    spec = SerialSpec(ba, bw, signed, signed, radix)
    if radix == 1:
        assert bitops.num_digits(ba, radix, signed) > 1
    xp, wp = pack_activations(jnp.asarray(x), ba), _pack_w(wt, bw)
    acc = np.asarray(serial_conv2d_packed_acts(
        xp, wp, spec=spec, ci=ci, stride=stride, padding=padding))
    np.testing.assert_array_equal(acc, _dense_ref(x, wt, stride, padding))
    if out_kind == "float":
        out = bitserial_conv2d_v2_pallas(
            xp, wp, np.ones(co, np.float32), None, spec=spec, ci=ci,
            stride=stride, padding=padding, block_co=bco, block_nb=bnb,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(out).astype(np.int64), acc)
        return
    scale = (rng.rand(co) * 0.05).astype(np.float32)
    kw = dict(spec=spec, ci=ci, stride=stride, padding=padding, relu=True,
              requant=QuantSpec(ba, signed), requant_scale=0.5,
              emit_packed=out_kind == "packed", block_co=bco, block_nb=bnb,
              interpret=True)
    got = serial_conv2d_packed_op(xp, wp, scale, None, backend="pallas_v2",
                                  **kw)
    want = serial_conv2d_packed_op(xp, wp, scale, None, backend="xla", **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_conv_act_columns_reuse_at_resnet9_geometry():
    """conv1/conv2 (32x32, 3x3, stride 1) serve each assembled digit
    column to about 8 tap windows, conv3 (stride 2) to about 2; a 1x1
    stride-1 conv has nothing to share."""
    from repro.core.cost_model import conv_act_columns
    geo = dict(fh=3, fw=3, padding=1, bnb=8)
    s, a = conv_act_columns(256, 32, 32, 64, 64, stride=1, bco=64, **geo)
    assert s / a == pytest.approx(9 * 32 * 32 / 34 ** 2)
    assert round(s / a, 2) == 7.97
    s, a = conv_act_columns(256, 32, 32, 64, 128, stride=2, bco=128, **geo)
    assert round(s / a, 2) == 2.05
    s, a = conv_act_columns(256, 16, 16, 64, 64, fh=1, fw=1, stride=1,
                            padding=0, bnb=8, bco=64)
    assert s == a


def test_conv_cost_charges_act_assembly_per_assembled_row():
    """The activation unpack term of conv1's geometry is the per-tap-window
    charge divided by the reuse factor; the ring is in the VMEM estimate
    and scales with the image block."""
    from repro.core import cost_model
    spec = plan_spec(SerialSpec(8, 4, True, True, 7))
    nd_a = bitops.num_digits(8, spec.radix_bits, True)
    nd_w = bitops.num_digits(4, spec.radix_bits, True)
    geo = dict(fh=3, fw=3, stride=1, padding=1, a_bits=8, w_bits=4,
               nd_a=nd_a, nd_w=nd_w, bco=64, cache_weights=True, out_bits=8)
    ops = cost_model.conv_vpu_ops(256, 32, 32, 64, 64, bnb=8, **geo)
    # what unpacking every tap window on its own costs: (a_bits + nd_a)
    # element ops per channel lane of each window column
    per_window = (8 + nd_a) * 256 * 32 * 9 * 32 * 64
    assert per_window / ops["act_rows"] == pytest.approx(
        9 * 32 * 32 / 34 ** 2)
    assert ops["act_windows"] == nd_a * 256 * 32 * 9 * 32 * 64
    v = {b: cost_model.conv_kernel_vmem_bytes(256, 32, 32, 64, 64, bnb=b,
                                              **geo) for b in (4, 8)}
    ring8 = 3 * nd_a * 8 * 34 * 128 * 4        # (fh*nd_a, 272 rows, 128)
    assert v[8] - v[4] >= ring8 // 2
    assert v[8] > ring8


def test_conv_act_digit_reuse_gauge():
    """Building a Program exports each conv layer's reuse as
    ``conv_act_digit_reuse`` in the registry's metrics: about 7.97 for a
    32x32 stride-1 layer, 2.05 for a stride-2 one."""
    from repro.models.layers import QuantPolicy
    from repro.models.resnet import (ResNet9Config, resnet9_graph,
                                     resnet9_init)
    from repro.serving import ModelRegistry

    class Cfg(ResNet9Config):
        layers = (("conv1", 64, 64, 1, False), ("conv3", 64, 128, 2, False))

    cfg = Cfg()
    graph = resnet9_graph(resnet9_init(jax.random.PRNGKey(0), cfg), cfg)
    calib = np.random.RandomState(0).rand(8, 32, 32, 3).astype(np.float32)
    reg = ModelRegistry(backend="xla")
    key = reg.register_graph("cnn", graph, calib, QuantPolicy(
        mode="serial", w_bits=2, a_bits=2, radix_bits=7))
    prog = reg.program(key)
    g = reg.metrics_registry.get("conv_act_digit_reuse")
    assert prog.meta["tiles"]["conv1"].block_nb == 8
    assert round(g.value(variant=str(key), layer="conv1"), 2) == 7.97
    assert round(g.value(variant=str(key), layer="conv3"), 2) == 2.05


# ----------------------------------------------------------------- autotuner

def test_conv_tuner_respects_vmem_and_caches():
    spec = SerialSpec(2, 2, True, True, 8)
    tc = tuning.choose_conv_tile(8, 32, 32, 64, 64, fh=3, fw=3, stride=1,
                                 padding=1, spec=spec)
    assert tc.block_co % 32 == 0 and tc.block_nb >= 1
    assert tc.vmem_bytes <= int(tuning.TPUConfig().vmem_bytes * 0.75)
    # huge activation rows: the row ring scales with the image block, so
    # the tuner shrinks the block until the ring fits
    tc_big = tuning.choose_conv_tile(64, 224, 224, 512, 512, fh=3, fw=3,
                                     stride=1, padding=1, spec=spec)
    ring8 = 3 * 8 * 226 * 512 * 4          # fh slots x 8 images' rows, int32
    assert ring8 > int(tuning.TPUConfig().vmem_bytes * 0.75) * 0.8
    assert tc_big.block_nb < 8
    assert tc_big.vmem_bytes >= 3 * tc_big.block_nb * 226 * 512 * 4
    assert tc_big.vmem_bytes <= int(tuning.TPUConfig().vmem_bytes * 0.75)


def test_conv_tuner_pinned_axes():
    """A caller-pinned block axis constrains the search; the other axis and
    cache flags are still tuned and VMEM-validated jointly."""
    spec = SerialSpec(2, 2, True, True, 8)
    kw = dict(fh=3, fw=3, stride=1, padding=1, spec=spec)
    tc = tuning.choose_conv_tile(8, 32, 32, 64, 128, fix_bco=32, **kw)
    assert tc.block_co == 32
    assert tc.vmem_bytes <= int(tuning.TPUConfig().vmem_bytes * 0.75)
    tc = tuning.choose_conv_tile(8, 32, 32, 64, 128, fix_bnb=2, **kw)
    assert tc.block_nb == 2
    assert tc.vmem_bytes <= int(tuning.TPUConfig().vmem_bytes * 0.75)


def test_conv_tuner_cache_hit_is_stable():
    spec = SerialSpec(2, 2, True, True, 8)
    kw = dict(fh=3, fw=3, stride=2, padding=1, spec=spec)
    a = tuning.choose_conv_tile(4, 16, 16, 64, 128, **kw)
    b = tuning.choose_conv_tile(4, 16, 16, 64, 128, **kw)
    assert a == b


def test_conv_tuned_blocks_run_bit_exact():
    """The conv tuner's pick actually runs (interpret) and stays exact."""
    rng = np.random.RandomState(13)
    x, w = _rand_case(rng, 2, 2, True, True, 2, 6, 6, 32, 32)
    spec = SerialSpec(2, 2, True, True, 8)
    out = serial_conv2d_packed_op(
        pack_activations(jnp.asarray(x), 2), _pack_w(w, 2),
        np.ones(32, np.float32), None, spec=spec, ci=32, stride=1,
        padding=1, backend="pallas_v2", interpret=True)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64),
                                  _dense_ref(x, w, 1, 1))


# ------------------------------------------------------------ ResNet9 packed

@pytest.mark.slow
def test_resnet9_pack_hoists_weight_quantization():
    from repro.models.resnet import (ResNet9Config, resnet9_init,
                                     resnet9_forward,
                                     resnet9_quantize_weights)
    cfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(0), cfg)
    images = jnp.asarray(np.random.RandomState(0).rand(1, 32, 32, 3),
                         jnp.float32)
    qw = resnet9_quantize_weights(params, cfg)
    ref = resnet9_forward(params, images, cfg)
    out = resnet9_forward(params, images, cfg, qweights=qw)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


@pytest.mark.slow
def test_resnet9_packed_forward_matches_reference_xla():
    """conv1–conv8 end-to-end on the implicit-GEMM packed path (XLA
    backend) == the seed serial_conv2d forward, same calibration batch."""
    from repro.models.resnet import (ResNet9Config, resnet9_init,
                                     resnet9_forward, resnet9_pack,
                                     resnet9_forward_packed)
    cfg = ResNet9Config()
    params = resnet9_init(jax.random.PRNGKey(0), cfg)
    images = jnp.asarray(np.random.RandomState(0).rand(2, 32, 32, 3),
                         jnp.float32)
    ref = resnet9_forward(params, images, cfg)
    packed = resnet9_pack(params, images, cfg)
    out = resnet9_forward_packed(packed, images, cfg, backend="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_resnet9_packed_forward_pallas_small():
    """The same end-to-end chain through the Pallas kernel (interpret) on a
    reduced stack — packed chaining + pool-on-codes + strided stages."""
    from repro.models.resnet import (ResNet9Config, resnet9_init,
                                     resnet9_forward, resnet9_pack,
                                     resnet9_forward_packed)

    class SmallCfg(ResNet9Config):
        # last layer pools too: covers the final-stage pool-after branch
        layers = (("conv1", 64, 32, 1, False),
                  ("conv2", 32, 32, 2, False),
                  ("conv3", 32, 48, 1, True),
                  ("conv4", 48, 48, 1, True))

    cfg = SmallCfg()
    params = resnet9_init(jax.random.PRNGKey(1), cfg)
    images = jnp.asarray(np.random.RandomState(0).rand(2, 16, 16, 3),
                         jnp.float32)
    ref = resnet9_forward(params, images, cfg)
    packed = resnet9_pack(params, images, cfg)
    out = resnet9_forward_packed(packed, images, cfg, backend="pallas_v2",
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
