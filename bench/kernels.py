"""The served conv kernel's calls in a device trace, matched to their
layers by the shapes in the operation's name, which on the TPU is its
whole HLO instruction.

A Pallas conv call is a ``tpu_custom_call`` whose operands are the packed,
padded input ``u32[a_bits, N, H + 2 * padding, (FW - 1) + Wo * stride,
ceil(Ci/32)]`` and the packed weights ``u32[Co_blocks, w_bits, FH, FW,
ceil(Ci/32), block_co]`` (``kernels/bitserial_conv.py``); N is the padded
batch it ran over. The layers are the model module's ``convs(cfg)``, so
each is matched by its own filter, padding and stride.
"""

from __future__ import annotations

import re

_SHAPE = re.compile(r"\b(u32|s8|f32|s32|bf16)\[([0-9,]*)\]")


def _key(l: dict) -> tuple:
    """What a conv's operands show of it: padded input height and width,
    input words, output channels, filter height and width."""
    return (l["h"] + 2 * l["padding"], l["fw"] - 1 + l["wo"] * l["stride"],
            -(-l["ci"] // 32), l["co"], l["fh"], l["fw"])


def conv_calls(ops, convs: list, cfg: dict):
    """[(layer, n, seconds)] for every conv kernel call among ``ops``
    (``trace.reduce(...)["ops"]``) that matches one of ``convs``. Layers
    of one shape are indistinguishable; the last of them is named."""
    index = {_key(l): l for l in convs}
    a_bits, w_bits = int(cfg["a_bits"]), int(cfg["w_bits"])
    out = []
    for _, name, a, b in ops:
        if "custom-call" not in name:
            continue
        shapes = [(t, tuple(int(d) for d in dims.split(",") if d))
                  for t, dims in _SHAPE.findall(name)]
        xs = [d for t, d in shapes if t == "u32" and len(d) == 5
              and d[0] == a_bits]
        ws = [d for t, d in shapes if t == "u32" and len(d) == 6
              and d[1] == w_bits]
        keys = [(x[1], (x[2], x[3], x[4], w[0] * w[5], w[2], w[3]))
                for x in xs for w in ws]
        hit = next(((index[k], n) for n, k in keys if k in index), None)
        if hit is not None:
            out.append((*hit, (b - a) * 1e-9))
    return out
