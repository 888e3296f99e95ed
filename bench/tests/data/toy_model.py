"""A toy CNN unlike ResNet9, for the test that adds a model as files alone:
a float 3x3 conv0 on the host, a 1x1 hidden conv, an unpadded 3x3
stride-2 hidden conv, a global average pool and a float classifier.
``cfg["layers"]`` rows are (name, c_in, c_out, filter, stride, padding).
The quantized semantics are ResNet9's (``bench/models/resnet9.py``)."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import quantref, roofline

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULT = jax.lax.Precision.DEFAULT


def _layers(cfg):
    return [tuple(l) for l in cfg["layers"]]


def make_params(cfg, seed):
    ls, cin = _layers(cfg), int(cfg["input_channels"])
    c0, ncls = int(cfg["conv0_channels"]), int(cfg["num_classes"])

    def make(key):
        ks = jax.random.split(key, 3 * len(ls) + 2)
        p = {"conv0": {"w": jax.random.normal(ks[0], (3, 3, cin, c0))
                       / np.sqrt(9 * cin)}}
        for i, (name, ci, co, f, _, _) in enumerate(ls):
            kw, ks_, kb = ks[1 + 3 * i: 4 + 3 * i]
            p[name] = {"w": jax.random.normal(kw, (f, f, ci, co))
                       / np.sqrt(f * f * ci),
                       "scale": jax.random.uniform(ks_, (co,), minval=0.75,
                                                   maxval=1.25),
                       "bias": 0.05 * jax.random.normal(kb, (co,))}
        p["fc"] = {"w": jax.random.normal(ks[-1], (ls[-1][2], ncls))
                   / np.sqrt(ls[-1][2])}
        return p

    return jax.jit(make)(quantref.key_for(seed, 0))


def graph(params, cfg):
    from repro.compiler.ir import Graph, Node
    hw = int(cfg["input_hw"])
    inits = {"conv0.w": np.asarray(params["conv0"]["w"]),
             "fc.w": np.asarray(params["fc"]["w"])}
    nodes = [Node("conv0", "conv2d", ["images", "conv0.w"], "conv0.y",
                  {"stride": 1, "padding": 1, "host": True}),
             Node("conv0.relu", "relu", ["conv0.y"], "conv0.out")]
    x = "conv0.out"
    for name, _, _, _, stride, pad in _layers(cfg):
        for k in ("w", "scale", "bias"):
            inits[f"{name}.{k}"] = np.asarray(params[name][k])
        nodes += [Node(name, "conv2d", [x, f"{name}.w", f"{name}.scale",
                                        f"{name}.bias"], f"{name}.y",
                       {"stride": stride, "padding": pad}),
                  Node(f"{name}.relu", "relu", [f"{name}.y"], f"{name}.r")]
        x = f"{name}.r"
    nodes += [Node("gap", "global_avg_pool", [x], "pooled"),
              Node("fc", "gemm", ["pooled", "fc.w"], "logits",
                   {"host": True})]
    g = Graph(name="toy", inputs={"images": (None, hw, hw, 3)},
              outputs=["logits"], nodes=nodes, initializers=inits)
    g.validate()
    return g


def _conv(x, w, stride, pad, precision=HIGHEST):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)


def _host_conv0(params, images, dtype):
    x = _conv(images.astype(dtype), params["conv0"]["w"].astype(dtype), 1,
              1, precision=DEFAULT)
    return jnp.maximum(x, 0).astype(dtype)


def _hidden(q, x, step, stride, pad, cfg, dtype):
    """One hidden conv of float input ``x``: ReLU of its scaled integer
    sum."""
    codes = quantref.lsq_codes(x, step, cfg["a_bits"])
    acc = _conv(codes, q["wq"], stride, pad).astype(dtype)
    co = q["scale"].shape[0]
    folded = (step * q["aw"].reshape(1, 1, 1, co)
              * q["scale"]).reshape(co).astype(dtype)
    return jnp.maximum(acc * folded + q["bias"], 0)


def _calibrate(params, calib_images, cfg, dtype):
    x = _host_conv0(params, jnp.asarray(calib_images), dtype)
    q, steps = {}, {}
    for name, _, _, _, stride, pad in _layers(cfg):
        w = params[name]["w"].astype(dtype)
        aw = quantref.lsq_step(w, cfg["w_bits"], axis=(0, 1, 2))
        q[name] = {"wq": quantref.lsq_codes(w, aw, cfg["w_bits"]), "aw": aw,
                   "scale": params[name]["scale"].astype(dtype),
                   "bias": params[name]["bias"].astype(dtype)}
        steps[name] = quantref.lsq_step(x, cfg["a_bits"]).astype(dtype)
        x = _hidden(q[name], x, steps[name], stride, pad, cfg, dtype)
    return {"q": q, "steps": steps}


def _forward(params, cal, images, cfg, dtype):
    x = _host_conv0(params, images, dtype)
    for name, _, _, _, stride, pad in _layers(cfg):
        x = _hidden(cal["q"][name], x, cal["steps"][name], stride, pad, cfg,
                    dtype)
    g = jnp.mean(x, axis=(1, 2))
    return jnp.dot(g, params["fc"]["w"].astype(dtype),
                   precision=DEFAULT).astype(jnp.float32)


def logits(params, calib_images, images, cfg, *, dtype=jnp.float32,
           block=256):
    cal = _calibrate(params, calib_images, cfg, dtype)
    return quantref.blocked(_forward, params, cal, images, cfg, dtype=dtype,
                            block=block)


def convs(cfg):
    out, h = [], int(cfg["input_hw"])
    ls = _layers(cfg)
    for i, (name, ci, co, f, stride, pad) in enumerate(ls):
        ho = (h + 2 * pad - f) // stride + 1
        out.append({"name": name, "h": h, "w": h, "ci": ci, "co": co,
                    "fh": f, "fw": f, "stride": stride, "padding": pad,
                    "ho": ho, "wo": ho,
                    "out": "float" if i == len(ls) - 1 else "packed"})
        h = ho
    return out


def macs_per_image(cfg):
    hw = int(cfg["input_hw"])
    conv0 = hw * hw * 9 * int(cfg["input_channels"]) * int(
        cfg["conv0_channels"])
    fc = int(cfg["layers"][-1][2]) * int(cfg["num_classes"])
    return conv0 + sum(roofline.conv_macs(l, 1) for l in convs(cfg)) + fc
