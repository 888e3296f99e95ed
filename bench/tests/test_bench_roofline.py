"""Operation and byte counts of the served ResNet9 (its model module's
convs and multiply-accumulates, counted by ``bench/roofline.py``), and the
peaks table."""

import json
from pathlib import Path

import pytest

from bench import roofline, spec

ROOT = Path(__file__).resolve().parents[2]
RESNET9 = spec.model(ROOT, "resnet9")


def _cfg(name="resnet9-w2a2"):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def test_macs_match_an_independent_count():
    # by hand from the paper's Table 3: output maps 32, 32, 16, 16, 8, 8,
    # 4, 4, each 3x3 tap over c_in x c_out
    hidden = (32 * 32 * 9 * (64 * 64 + 64 * 64) + 16 * 16 * 9 * (64 * 128
              + 128 * 128) + 8 * 8 * 9 * (128 * 256 + 256 * 256)
              + 4 * 4 * 9 * (256 * 512 + 512 * 512))
    assert hidden == 245_366_784
    cfg = _cfg()
    per_layer = [roofline.conv_macs(l, 1)
                 for l in RESNET9.convs(cfg)]
    assert sum(per_layer) == hidden
    assert RESNET9.macs_per_image(cfg) == (hidden + 32 * 32 * 27 * 64
                                           + 512 * 10)


@pytest.mark.parametrize("name", ["resnet9-w2a2", "resnet9-w4a8"])
def test_layers_match_the_programs_geometry(name):
    """Every hidden conv reads the map, widths and stride of the program's
    transcription of the paper's Table 3 (``RESNET9_CIFAR10``)."""
    from repro.core.cost_model import RESNET9_CIFAR10, ConvLayer
    ours = {l["name"]: l for l in RESNET9.convs(_cfg(name))}
    paper = [l for l in RESNET9_CIFAR10
             if isinstance(l, ConvLayer) and not l.on_host]
    assert [l.name for l in paper] == list(ours)
    for l in paper:
        o = ours[l.name]
        assert (l.h, l.w, l.c_in, l.c_out, l.stride) == (
            o["h"], o["w"], o["ci"], o["co"], o["stride"])
        assert (l.fh, l.fw, l.padding) == (o["fh"], o["fw"], o["padding"])
        assert ours[l.name]["ho"] == (l.h + 2 * l.padding - l.fh) \
            // l.stride + 1


@pytest.mark.parametrize("n", [1, 256])
def test_roofline_time_does_not_fall_as_bits_rise(n):
    pk = roofline.peaks(ROOT, "TPU v5 lite")
    cfg = _cfg()
    for layer in RESNET9.convs(cfg):
        prev = 0.0
        for w, a in [(1, 1), (2, 2), (2, 4), (4, 4), (4, 8), (8, 8)]:
            c = dict(cfg, w_bits=w, a_bits=a)
            t, _ = roofline.roofline_s(2 * roofline.conv_macs(layer, n),
                                       roofline.conv_bytes(layer, n, c), pk)
            assert t >= prev
            prev = t


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks(ROOT, "TPU v9 imaginary")
    assert roofline.peaks(ROOT, "TPU v5 lite")["int8_ops_per_s"] == 393e12
