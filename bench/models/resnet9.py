"""ResNet9 as the benchmark serves and checks it: the seeded weights, the
compiler graph the server takes, the plain reference, and the convs and
multiply-accumulates of the served graph.

The reference imports nothing of the system under test. The semantics are
BARVINN's deployment flow as the configuration file states it:

* conv0 (3 -> 64, 3x3, pad 1) and the classifier stay float on the host;
* every hidden conv quantizes its input per tensor and its weights per
  output channel, both with the LSQ initial step ``2 * mean|x| /
  sqrt(Qp) + 1e-8`` and signed codes ``clip(round(x / step), Qn, Qp)``
  (round half to even);
* the activation steps are calibrated once, by replaying this forward on
  the calibration images with float inputs to every layer;
* a hidden conv's integer sum is scaled by ``act step * weight step *
  bn scale``, biased, passed through ReLU and, where the layer pools,
  a 2x2 max-pool; the last hidden conv feeds a global average pool and
  the float classifier.

Float work is float32, and the float convolution and matmul (conv0, the
classifier) run at the platform's default matmul precision, as the
configuration states: on a TPU that multiplies bfloat16-rounded operands
and accumulates in float32. The control does every float step in bfloat16
instead. The integer convolutions are exact in both.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp
import numpy as np

from bench import quantref, roofline
from bench.quantref import key_for
from bench.quantref import lsq_codes as _codes
from bench.quantref import lsq_step as _step

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT


def layers(cfg):
    """(name, c_in, c_out, stride, pool) of every hidden conv."""
    return [tuple(l) for l in cfg["layers"]]


def make_params(cfg, seed: int):
    """Seeded float weights in the served layout, made on the device in one
    jitted call: ``{"conv0": {"w"}, <hidden>: {"w", "scale", "bias"},
    "fc": {"w"}}``. The scale/bias pair stands in for a folded batch
    norm, so the scaler and bias paths do real work."""
    ls = layers(cfg)
    c0 = int(cfg["conv0_channels"])
    ncls = int(cfg["num_classes"])
    cin = int(cfg["input_channels"])

    def make(key):
        ks = jax.random.split(key, 3 * len(ls) + 2)
        p = {"conv0": {"w": jax.random.normal(ks[0], (3, 3, cin, c0))
                       * (1.0 / np.sqrt(9 * cin))}}
        for i, (name, ci, co, _, _) in enumerate(ls):
            kw, ks_, kb = ks[1 + 3 * i: 4 + 3 * i]
            p[name] = {
                "w": jax.random.normal(kw, (3, 3, ci, co))
                * (1.0 / np.sqrt(9 * ci)),
                "scale": jax.random.uniform(ks_, (co,), minval=0.75,
                                            maxval=1.25),
                "bias": 0.05 * jax.random.normal(kb, (co,)),
            }
        p["fc"] = {"w": jax.random.normal(ks[-1], (ls[-1][2], ncls))
                   * (1.0 / np.sqrt(ls[-1][2]))}
        return p

    return jax.jit(make)(key_for(seed, 0))


def _conv(x, w, stride: int, precision=HIGHEST):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _int_conv(codes, wq, stride: int):
    """Exact integer convolution: the codes and sums fit float32's 24-bit
    significand at the configured widths (W4A8: 9*512*127*8 < 2**24)."""
    return _conv(codes.astype(jnp.float32), wq.astype(jnp.float32), stride)


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def _dense_layers(params, cfg, dtype):
    """Per-layer weight codes, weight steps and float scale/bias in
    ``dtype``."""
    out = {}
    for name, _, co, _, _ in layers(cfg):
        w = params[name]["w"].astype(dtype)
        aw = _step(w, cfg["w_bits"], axis=(0, 1, 2))
        out[name] = {"wq": _codes(w, aw, cfg["w_bits"]), "aw": aw,
                     "scale": params[name]["scale"].astype(dtype),
                     "bias": params[name]["bias"].astype(dtype)}
    return out


def _host_conv0(params, images, dtype):
    x = _conv(images.astype(dtype), params["conv0"]["w"].astype(dtype), 1,
              precision=DEFAULT)
    return jnp.maximum(x, 0).astype(dtype)


def _hidden(q, codes, act_step, stride, dtype):
    """One hidden conv's float output (before ReLU/pool)."""
    co = q["scale"].shape[0]
    acc = _int_conv(codes, q["wq"], stride).astype(dtype)
    folded = (act_step * q["aw"].reshape(1, 1, 1, co)
              * q["scale"]).reshape(co).astype(dtype)
    return acc * folded + q["bias"]


def calibrate(params, calib_images, cfg, dtype):
    """Replay the quantized forward on the calibration images with float
    inputs to every layer; returns the activation step of every hidden
    conv and the weight codes. Run op by op, as written: each step is one
    float32 mean over the whole calibration tensor, and a fused program
    would sum it in another order (section 6 of PERF.md)."""
    q = _dense_layers(params, cfg, dtype)
    x = _host_conv0(params, jnp.asarray(calib_images), dtype)
    steps = {}
    for name, _, _, stride, pool in layers(cfg):
        ax = _step(x, cfg["a_bits"]).astype(dtype)
        steps[name] = ax
        y = jnp.maximum(_hidden(q[name], _codes(x, ax, cfg["a_bits"]), ax,
                                stride, dtype), 0)
        x = _pool(y) if pool else y
    return {"q": q, "steps": steps}


def _forward(params, cal, images, cfg, dtype):
    q, steps = cal["q"], cal["steps"]
    ls = layers(cfg)
    x = _host_conv0(params, images, dtype)
    codes = _codes(x, steps[ls[0][0]], cfg["a_bits"])
    for i, (name, _, _, stride, pool) in enumerate(ls):
        y = jnp.maximum(_hidden(q[name], codes, steps[name], stride, dtype),
                        0)
        if pool:
            y = _pool(y)
        if i + 1 < len(ls):
            codes = _codes(y, steps[ls[i + 1][0]], cfg["a_bits"])
    g = jnp.mean(y, axis=(1, 2))
    logits = jnp.dot(g, params["fc"]["w"].astype(dtype), precision=DEFAULT)
    return logits.astype(jnp.float32)


def logits(params, calib_images, images, cfg, *, dtype=jnp.float32,
           block: int = 256) -> np.ndarray:
    """Logits of ``images`` (N, H, W, C): the reference (float32) or the
    control (``dtype=jnp.bfloat16``), computed ``block`` images at a time
    so that the whole set never sits on the device."""
    cal = calibrate(params, calib_images, cfg, dtype)
    return quantref.blocked(_forward, params, cal, images, cfg, dtype=dtype,
                            block=block)


def graph(params, cfg):
    """The compiler graph the server takes, from the program's own ResNet9
    builder."""
    from repro.models.resnet import resnet9_graph
    return resnet9_graph(params, types.SimpleNamespace(
        layers=layers(cfg), num_classes=int(cfg["num_classes"])),
        input_hw=int(cfg["input_hw"]))


def convs(cfg: dict) -> list:
    """Every hidden conv as it is served: 3x3, pad 1, the layer's stride;
    input and output size, and the format it writes (the next layer's
    packed planes, int8 codes where a pool follows, float after the
    last)."""
    out, h = [], int(cfg["input_hw"])
    ls = layers(cfg)
    for i, (name, ci, co, stride, pool) in enumerate(ls):
        ho = (h + 2 - 3) // stride + 1
        last = i == len(ls) - 1
        out.append({"name": name, "h": h, "w": h, "ci": ci, "co": co,
                    "fh": 3, "fw": 3, "stride": stride, "padding": 1,
                    "ho": ho, "wo": ho,
                    "out": "float" if last else ("codes" if pool
                                                 else "packed")})
        h = ho // 2 if pool else ho
    return out


def macs_per_image(cfg: dict) -> int:
    """Multiply-accumulates of one image through the whole served graph:
    conv0, the hidden convs and the classifier."""
    hw = int(cfg["input_hw"])
    conv0 = hw * hw * 9 * int(cfg["input_channels"]) * int(
        cfg["conv0_channels"])
    hidden = sum(roofline.conv_macs(l, 1) for l in convs(cfg))
    fc = int(cfg["layers"][-1][2]) * int(cfg["num_classes"])
    return conv0 + hidden + fc
