"""Pallas TPU kernels for the hot correlation path."""

from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock, lookup_pyramid_fused

__all__ = [
    "FusedLookupCorrBlock",
    "lookup_pyramid_fused",
]
