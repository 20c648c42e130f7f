"""The work a stream session needs, from shapes alone, built from
``work.py``'s functions: a frame is encoded once (one pass through the
feature encoder and one through the context encoder), and a pair is the
all-pairs volume, its updates and the upsample. ``work.pair_flops`` counts
two feature-encoder passes a pair, which is what an unrelated pair costs
and a session does not."""

from __future__ import annotations

from benchmarks.reduce import work


def frame_flops(arch, h, w):
    """One frame through both encoders."""
    return (work.encoder_flops(arch["feature_encoder_widths"],
                               arch["feature_encoder_block"], h, w)
            + work.encoder_flops(arch["context_encoder_widths"],
                                 arch["context_encoder_block"], h, w))


def refine_flops(arch, h, w, iters):
    """One pair from its two frames' features: the all-pairs volume,
    ``iters`` updates (lookup included) and the upsample."""
    h8, w8 = h // 8, w // 8
    q = h8 * w8
    return (2.0 * q * q * arch["feature_encoder_widths"][-1]
            + iters * work.update_flops(arch, h8, w8)
            + work.upsample_flops(arch, h8, w8))
