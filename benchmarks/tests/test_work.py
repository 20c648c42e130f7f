"""The FLOP and byte functions on hand-worked shapes."""

import pytest

from benchmarks.reduce import work

LARGE = {"corr_levels": 4, "corr_radius": 4, "motion_corr_widths": [256, 192],
         "motion_flow_widths": [128, 64], "motion_out_channels": 128,
         "gru_hidden": 128, "gru_kernels": [[1, 5], [5, 1]],
         "context_encoder_widths": [64, 64, 96, 128, 256],
         "feature_encoder_widths": [64, 64, 96, 128, 256],
         "feature_encoder_block": "residual", "context_encoder_block": "residual",
         "flow_head_hidden": 256, "use_mask_predictor": True,
         "mask_predictor_hidden": 256}


def test_conv_flops():
    # 3x3 conv, 64 -> 96 channels, 10 x 20 output: 2 * 200 * 64 * 96 * 9
    assert work.conv_flops(10, 20, 64, 96, 3, 3) == 2 * 200 * 64 * 96 * 9


def test_lookup_work_per_pixel():
    # raft_large: 4 levels x 9x9 = 324 taps; per pixel 324 * 8 FLOPs to
    # interpolate + 2 * 324 * 256 to project
    assert work.taps(LARGE) == 324
    assert work.lookup_flops(LARGE, 1) == 324 * 8 + 2 * 324 * 256
    # bytes at bf16: 4 levels x (2*4+2)^2 = 400 cells x 2 B, weights
    # 324 x 256 x 2 B once, output 256 x 2 B per pixel
    q = 1000
    assert work.lookup_bytes(LARGE, q, 2, 2) == q * 400 * 2 + 324 * 256 * 2 + q * 256 * 2


def test_encoder_flops_residual_by_hand():
    # 16x16 input, widths (4, 4, 8, 8, 16): stem 8x8; stage1 8x8; stage2 4x4; stage3 2x2
    c = work.conv_flops
    want = c(8, 8, 3, 4, 7, 7)
    want += 4 * c(8, 8, 4, 4, 3, 3)                                  # layer1: 2 blocks x 2 convs
    want += c(4, 4, 4, 8, 3, 3) + c(4, 4, 8, 8, 3, 3) + c(4, 4, 4, 8, 1, 1) + 2 * c(4, 4, 8, 8, 3, 3)
    want += c(2, 2, 8, 8, 3, 3) + c(2, 2, 8, 8, 3, 3) + c(2, 2, 8, 8, 1, 1) + 2 * c(2, 2, 8, 8, 3, 3)
    want += c(2, 2, 8, 16, 1, 1)
    assert work.encoder_flops([4, 4, 8, 8, 16], "residual", 16, 16) == want


def test_pair_flops_scale():
    serve = work.pair_flops(LARGE, 440, 1024, 32)
    assert 1.0e12 < serve < 2.5e12          # ~1.5 TFLOP a pair (ISSUE 25)
    more = work.pair_flops(LARGE, 440, 1024, 33)
    assert more - serve == pytest.approx(work.update_flops(LARGE, 55, 128))
    train = work.pair_flops(LARGE, 368, 768, 12, train=True)
    fwd = work.pair_flops(LARGE, 368, 768, 12)
    assert train > 3 * fwd                  # upsample after every update, x3
