"""Operations and bytes of the served ResNet9, from the configuration's
shapes and bits alone, and the chip's roofline time for them.

The served graph: conv0 (float, on the host side of the Program), the
hidden convs of ``cfg["layers"]`` (3x3, pad 1, the given stride; a 2x2
max-pool after the layers marked so), a global average pool and the float
classifier. A hidden conv reads its input as bit-packed planes at
``a_bits`` and its weights as packed planes at ``w_bits``; it writes the
next layer's packed planes, int8 codes where a pool follows, or float32
after the last layer.
"""

from __future__ import annotations

import json
from pathlib import Path


def peaks(root: Path, device_kind: str) -> dict:
    """The peak rates of ``device_kind``; an unknown device is an error."""
    table = json.loads((Path(root) / "bench" / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json") from None


def _words(c: int) -> int:
    return -(-c // 32)


def hidden_layers(cfg: dict) -> list:
    """Every hidden conv as it is served: input size, output size, and
    the format it writes."""
    out, h = [], int(cfg["input_hw"])
    ls = [tuple(l) for l in cfg["layers"]]
    for i, (name, ci, co, stride, pool) in enumerate(ls):
        ho = (h + 2 - 3) // stride + 1
        last = i == len(ls) - 1
        out.append({"name": name, "h": h, "ci": ci, "co": co,
                    "stride": stride, "ho": ho,
                    "out": "float" if last else ("codes" if pool
                                                 else "packed")})
        h = ho // 2 if pool else ho
    return out


def conv_macs(layer: dict, n: int) -> int:
    return n * layer["ho"] ** 2 * 9 * layer["ci"] * layer["co"]


def conv_bytes(layer: dict, n: int, cfg: dict) -> int:
    a, w = int(cfg["a_bits"]), int(cfg["w_bits"])
    x = a * n * layer["h"] ** 2 * _words(layer["ci"]) * 4
    wt = w * 9 * _words(layer["ci"]) * layer["co"] * 4
    pix = n * layer["ho"] ** 2
    y = {"packed": a * pix * _words(layer["co"]) * 4,
         "codes": pix * layer["co"],
         "float": 4 * pix * layer["co"]}[layer["out"]]
    return x + wt + y + 8 * layer["co"]


def roofline_s(ops: float, nbytes: float, pk: dict):
    """(least seconds on the chip, the bound that sets it)."""
    t_ops = ops / pk["int8_ops_per_s"]
    t_mem = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


def macs_per_image(cfg: dict) -> int:
    """Multiply-accumulates of one image through the whole served graph."""
    hw = int(cfg["input_hw"])
    conv0 = hw * hw * 9 * int(cfg["input_channels"]) * int(
        cfg["conv0_channels"])
    hidden = sum(conv_macs(l, 1) for l in hidden_layers(cfg))
    fc = int(cfg["layers"][-1][2]) * int(cfg["num_classes"])
    return conv0 + hidden + fc


def ops_per_image(cfg: dict) -> int:
    return 2 * macs_per_image(cfg)
