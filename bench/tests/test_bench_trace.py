"""The trace reduction: interval arithmetic, and a trace recorded on the
chip (``data/v5e_w2a2_saturate.xplane.pb``)."""

from pathlib import Path

import pytest

from bench import spec, trace

DATA = Path(__file__).parent / "data"
RESNET9 = spec.model(Path(__file__).resolve().parents[2], "resnet9")


def test_busy_counts_overlapping_operations_once():
    ops = [(0, 10), (5, 15), (20, 30), (25, 26), (40, 50)]
    assert trace.union(ops) == [(0, 15), (20, 30), (40, 50)]
    assert trace.busy_ns(ops, 0, 60) == 35
    # clipped to the window [8, 45]
    assert trace.busy_ns(ops, 8, 45) == 7 + 10 + 5
    assert trace.gaps(ops, 0, 60) == [(15, 20), (30, 40), (50, 60)]
    assert trace.gaps(ops, 8, 45) == [(15, 20), (30, 40)]
    assert trace.busy_ns([], 0, 10) == 0
    assert trace.gaps([], 0, 10) == [(0, 10)]


def test_top_ops_sums_by_name():
    ops = [("d", "a", 0, 10), ("d", "b", 10, 40), ("d", "a", 50, 60)]
    top = trace.top_ops(ops)
    assert [n for n, _ in top] == ["b", "a"]
    assert [t for _, t in top] == pytest.approx([30e-9, 20e-9])
    assert len(trace.top_ops(ops, 1)) == 1


def test_conv_calls_are_matched_to_layers_by_operand_shapes():
    import json
    from bench import kernels
    cfg = json.loads((Path(__file__).parents[1] / "configs"
                      / "resnet9-w2a2.json").read_text())
    conv3 = ("%run.10 = u32[2,1,256,16,16,4]{5,4,3,2,1,0:T(8,128)S(1)} "
             "custom-call(%pad.4, %bitcast.69), custom_call_target="
             "\"tpu_custom_call\", operand_layout_constraints={"
             "u32[2,256,34,34,2]{4,3,2,1,0}, u32[1,2,3,3,2,128]{5,4,3,2,1,0}, "
             "f32[1,1,128]{2,1,0}}")
    other = "%fusion.3 = f32[256,32,32,64]{3,2,1,0} fusion(%a), kind=kLoop"
    ops = [("/device:TPU:0", conv3, 0.0, 2e6),
           ("/device:TPU:0", other, 2e6, 3e6)]
    calls = kernels.conv_calls(ops, RESNET9.convs(cfg), cfg)
    assert len(calls) == 1
    layer, n, secs = calls[0]
    assert layer["name"] == "conv3" and n == 256
    assert secs == pytest.approx(2e-3)


def test_recorded_chip_trace():
    """Two steps of the W2A2 Program (a 256 bucket, then an 8 bucket)
    traced on a v5e: every conv call is found and matched to its layer,
    and the device is busy for part of the window only."""
    import json
    from bench import kernels
    cfg = json.loads((Path(__file__).parents[1] / "configs"
                      / "resnet9-w2a2.json").read_text())
    prof = trace.load(DATA / "v5e_w2a2_saturate.xplane.pb")
    lo = trace.host_marker(prof)
    assert lo is not None
    ops = trace.device_ops(prof)
    assert list(ops) == ["/device:TPU:0"]
    hi = max(b for _, _, b in ops["/device:TPU:0"])
    red = trace.reduce(prof, lo, hi)
    assert 0 < red["busy_s"] < red["window_s"]
    calls = kernels.conv_calls(red["ops"], RESNET9.convs(cfg), cfg)
    # eight hidden convs per step (conv1 and conv2 share a shape)
    assert sorted(n for _, n, _ in calls) == [8] * 8 + [256] * 8
    names = [l["name"] for l, n, _ in calls if n == 256]
    assert sorted(set(names)) == ["conv2", "conv3", "conv4", "conv5",
                                  "conv6", "conv7", "conv8"]
    assert all(t > 0 for _, _, t in calls)


def test_recorded_chip_trace_readings_are_pinned():
    """The conv roofline and whole-step readers, through the ResNet9 model
    module, read the recorded trace as the harness that read ResNet9's
    shapes directly did (values pinned from it, 264 images answered)."""
    import json
    import types
    from bench import roofline
    root = Path(__file__).resolve().parents[2]
    cfg = json.loads((root / "bench" / "configs" / "resnet9-w2a2.json")
                     .read_text())
    prof = trace.load(DATA / "v5e_w2a2_saturate.xplane.pb")
    hi = max(b for _, _, b in trace.device_ops(prof)["/device:TPU:0"])
    ctx = types.SimpleNamespace(
        trace=trace.reduce(prof, trace.host_marker(prof), hi), cfg=cfg,
        model=RESNET9, peaks=roofline.peaks(root, "TPU v5 lite"),
        images_in_trace=264)
    assert spec.reader(root, "conv_roofline.saturate")(ctx) \
        == 0.965518282809736
    assert spec.reader(root, "step_mfu.saturate")(ctx) \
        == 0.41079539250537694
