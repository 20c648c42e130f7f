"""Feature / context encoder: 7x7 stride-2 stem, three 2-block stages, 1x1 head.

Downsamples exactly 8x (stem 2x, stages 1x/2x/2x). Used both as the feature
encoder (shared across both frames via batch stacking) and the context
encoder. Tree names (``convnormrelu``, ``layer1..3`` with ``layers_0/1``
children, ``conv``) follow the converted-checkpoint contract (reference
``jax_raft/model.py:219-257``).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple, Type

import flax.linen as nn
import jax

from raft_tpu.models.layers import ConvNormAct, ResidualBlock, conv

__all__ = ["EncoderStage", "FeatureEncoder"]


class EncoderStage(nn.Module):
    """Two residual/bottleneck blocks; the first may be strided."""

    block: Type[nn.Module]
    features: int
    stride: int
    norm: Optional[str]
    axis_name: Optional[str] = None
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        x = self.block(
            self.features, self.norm, self.stride,
            axis_name=self.axis_name, dtype=self.dtype, name="layers_0",
        )(x, train=train)
        x = self.block(
            self.features, self.norm, 1,
            axis_name=self.axis_name, dtype=self.dtype, name="layers_1",
        )(x, train=train)
        return x


def _depth_form(x) -> bool:
    """Whether ``x``'s frames go on a depth axis of batch-1 convs
    (``layers.frames_conv``) for an instance-normed encoder: where one
    device computes fewer than 8 of them. The TPU compiler computes a
    conv of fewer than 8 frames with its width split into the batch, and
    a per-frame statistic stays in that split only if no frame axis is
    left in the batch; from 8 frames on the batch fills the tiles itself,
    nothing is split, and the batch form is the cheaper one (PERF.md,
    PR 31: the shapes both sides were read at). Under a mesh
    (``parallel.mesh.traced_under``: the sharded train step, the serve
    mesh) the batch is sharded over ``data`` and a device computes its
    share."""
    mesh = jax.sharding.get_abstract_mesh()
    return x.shape[0] // dict(mesh.shape).get("data", 1) < 8


class FeatureEncoder(nn.Module):
    """RAFT encoder. ``widths`` = (stem, stage1, stage2, stage3, out)."""

    block: Type[nn.Module] = ResidualBlock
    widths: Tuple[int, int, int, int, int] = (64, 64, 96, 128, 256)
    norm: Optional[str] = "instance"
    axis_name: Optional[str] = None
    dtype: Optional[Any] = None
    s2d_stem: bool = False

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        stem, w1, w2, w3, out = self.widths
        frames_on_depth = self.norm == "instance" and _depth_form(x)
        if frames_on_depth:
            x = x[None]
        x = ConvNormAct(
            stem, 7, 2, self.norm, use_bias=True,
            axis_name=self.axis_name, dtype=self.dtype, s2d=self.s2d_stem,
            name="convnormrelu",
        )(x, train=train)
        x = EncoderStage(self.block, w1, 1, self.norm, self.axis_name, self.dtype, name="layer1")(x, train=train)
        x = EncoderStage(self.block, w2, 2, self.norm, self.axis_name, self.dtype, name="layer2")(x, train=train)
        x = EncoderStage(self.block, w3, 2, self.norm, self.axis_name, self.dtype, name="layer3")(x, train=train)
        x = conv(out, 1, dtype=self.dtype, name="conv")(x)
        return x[0] if frames_on_depth else x
