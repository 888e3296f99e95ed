"""The served conv kernel's calls in a device trace, matched to their
layers by the shapes in the operation's name, which on the TPU is its
whole HLO instruction.

A Pallas conv call is a ``tpu_custom_call`` whose operands are the packed
input ``u32[a_bits, N, H + 2, W', ceil(Ci/32)]`` and the packed weights
``u32[Co_blocks, w_bits, 3, 3, ceil(Ci/32), block_co]``
(``kernels/bitserial_conv.py``); N is the padded batch it ran over.
"""

from __future__ import annotations

import re

from bench import roofline

_SHAPE = re.compile(r"\b(u32|s8|f32|s32|bf16)\[([0-9,]*)\]")
def conv_calls(ops, cfg: dict):
    """[(layer, n, seconds)] for every conv kernel call among ``ops``
    (``trace.reduce(...)["ops"]``) that matches a layer of ``cfg``."""
    index = {(l["h"], -(-l["ci"] // 32), l["co"]): l
             for l in roofline.hidden_layers(cfg)}
    out = []
    for _, name, a, b in ops:
        if "custom-call" not in name:
            continue
        shapes = [(t, tuple(int(d) for d in dims.split(",") if d))
                  for t, dims in _SHAPE.findall(name)]
        x = next((d for t, d in shapes if t == "u32" and len(d) == 5
                  and d[0] == int(cfg["a_bits"])), None)
        w = next((d for t, d in shapes if t == "u32" and len(d) == 6
                  and d[1] == int(cfg["w_bits"]) and d[2:4] == (3, 3)),
                 None)
        if x is None or w is None:
            continue
        layer = index.get((x[2] - 2, x[4], w[0] * w[5]))
        if layer is not None:
            out.append((layer, x[1], (b - a) * 1e-9))
    return out
