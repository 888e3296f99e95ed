"""Plain reference of the served quantized ResNet9, and the weights both
sides use.

Nothing here imports the system under test. The semantics are BARVINN's
deployment flow as the configuration file states it:

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

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT


def layers(cfg):
    """(name, c_in, c_out, stride, pool) of every hidden conv."""
    return [tuple(l) for l in cfg["layers"]]


def key_for(seed: int, stream: int):
    """A JAX key from a seed of any size, 32 bits or more."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))


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


def make_images(seed: int, n: int, hw: int, c: int, stream: int) -> np.ndarray:
    """``n`` CIFAR-shaped images in [0, 1), float32, on the host."""
    rng = np.random.default_rng([int(seed), stream])
    return rng.random((n, hw, hw, c), dtype=np.float32)


def _qrange(bits: int):
    return -(1 << (bits - 1)), (1 << (bits - 1)) - 1


def _step(x, bits: int, axis=None):
    _, qp = _qrange(bits)
    m = jnp.mean(jnp.abs(x), axis=axis, keepdims=axis is not None)
    return 2.0 * m / np.sqrt(max(qp, 1)) + 1e-8


def _codes(x, step, bits: int):
    qn, qp = _qrange(bits)
    return jnp.clip(jnp.round(x / step), qn, qp)


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
    fwd = jax.jit(_forward, static_argnums=(3, 4))
    out = []
    for i in range(0, len(images), block):
        chunk = images[i:i + block]
        n = len(chunk)
        if n < block:   # one compiled shape for every block
            chunk = np.concatenate(
                [chunk, np.zeros((block - n,) + chunk.shape[1:],
                                 chunk.dtype)])
        out.append(np.asarray(fwd(params, cal, jnp.asarray(chunk),
                                  _Frozen(cfg), dtype))[:n])
    return np.concatenate(out) if out else np.zeros((0, cfg["num_classes"]))


class _Frozen(dict):
    """A configuration dict that jit can take as a static argument."""

    def __hash__(self):
        return hash(repr(sorted((k, repr(v)) for k, v in self.items())))
