"""The tile tuner keeps to Mosaic's (8, 128) tiling rule and to the VMEM
budget, and raises instead of handing out a tile that breaks either."""

import pytest

from repro.core.bitserial import SerialSpec, plan_spec
from repro.core.cost_model import TPUConfig
from repro.kernels import tuning

W4A8 = plan_spec(SerialSpec(8, 4, True, True, 7))
W2A2 = plan_spec(SerialSpec(2, 2, True, True, 7))


@pytest.mark.parametrize("m,k,n,spec,out_bits", [
    (256, 2048, 5632, W4A8, None),     # stablelm-1.6b MLP up-projection
    (256, 5632, 2048, W4A8, None),     # ... and down-projection
    (64, 512, 10, W2A2, None),         # a classifier head
    (32, 576, 512, W2A2, 2),           # packed requant epilogue
    (8, 8192, 1024, W4A8, None),       # K past one 128-word lane tile
])
def test_tuned_matmul_tile_meets_tiling_rule(m, k, n, spec, out_bits):
    tc = tuning.choose_tile(m, k, n, spec, out_bits=out_bits)
    assert tuning.matmul_tile_ok(m, k, n, tc.block_m, tc.block_n,
                                 tc.block_k, packed_out=out_bits is not None)
    assert tc.vmem_bytes <= int(TPUConfig().vmem_bytes * 0.75)


def test_matmul_tile_rule():
    ok = tuning.matmul_tile_ok
    # packed-activation words (bk/32) must be whole lane tiles or all of K
    assert not ok(256, 2048, 5632, 128, 256, 512)
    assert ok(256, 2048, 5632, 128, 256, 2048)
    assert ok(8, 8192, 1024, 8, 128, 4096)
    # block_n: a multiple of 128 lanes, or all of N
    assert not ok(256, 2048, 5632, 128, 64, 2048)
    assert ok(64, 512, 10, 64, 32, 512)
    # a packed output needs whole 128-word lane tiles of N/32, or all of N
    assert not ok(32, 576, 512, 32, 256, 576, packed_out=True)
    assert ok(32, 576, 512, 32, 512, 576, packed_out=True)
    # rows: a multiple of 8 sublanes, or all of M
    assert not ok(64, 512, 256, 12, 256, 512)


def test_tuner_raises_when_no_tile_fits():
    tiny = TPUConfig(vmem_bytes=64 * 1024)
    with pytest.raises(ValueError, match="tiling rule"):
        tuning.choose_tile(256, 2048, 5632, W4A8, tpu=tiny)
    with pytest.raises(ValueError, match="tiling rule"):
        tuning.choose_conv_tile(8, 32, 32, 64, 64, fh=3, fw=3, stride=1,
                                padding=1, spec=W2A2, tpu=tiny)
    # a pinned Co-block that is not a whole packing word
    with pytest.raises(ValueError, match="tiling rule"):
        tuning.choose_conv_tile(8, 32, 32, 64, 64, fh=3, fw=3, stride=1,
                                padding=1, spec=W2A2, fix_bco=48)
