"""The chip's roofline: its peak rates, and the least time a call needs for
its operations and bytes. The operations and bytes of one served conv
come from its own geometry (a model module's ``convs(cfg)`` entry) and the
configuration's bits.

A served conv reads its input as bit-packed planes at ``a_bits`` and its
weights as packed planes at ``w_bits``; it writes the next layer's packed
planes, int8 codes where a pool follows, or float32 after the last layer.
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


def conv_macs(layer: dict, n: int) -> int:
    return (n * layer["ho"] * layer["wo"] * layer["fh"] * layer["fw"]
            * layer["ci"] * layer["co"])


def conv_bytes(layer: dict, n: int, cfg: dict) -> int:
    a, w = int(cfg["a_bits"]), int(cfg["w_bits"])
    x = a * n * layer["h"] * layer["w"] * _words(layer["ci"]) * 4
    wt = (w * layer["fh"] * layer["fw"] * _words(layer["ci"]) * layer["co"]
          * 4)
    pix = n * layer["ho"] * layer["wo"]
    y = {"packed": a * pix * _words(layer["co"]) * 4,
         "codes": pix * layer["co"],
         "float": 4 * pix * layer["co"]}[layer["out"]]
    return x + wt + y + 8 * layer["co"]


def roofline_s(ops: float, nbytes: float, pk: dict):
    """(least seconds on the chip, the bound that sets it)."""
    t_ops = ops / pk["int8_ops_per_s"]
    t_mem = nbytes / pk["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
