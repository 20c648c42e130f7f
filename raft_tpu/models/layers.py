"""Core NN building blocks.

Parameter-tree contract: child-module names (``layers_0`` for the conv,
``layers_1`` for the norm, block names ``convnormrelu*`` / ``downsample``)
reproduce the tree that torchvision checkpoints convert into (see
reference ``jax_raft/model.py:120-216`` and
``scripts/convert_checkpoint.py:11-32``), so converted msgpack checkpoints
load directly. The implementation itself is original: norms are selected by a
string spec (config-serializable), BatchNorm takes an optional ``axis_name``
for cross-replica statistics under data parallelism, and blocks are explicit
compact modules rather than a registered-list Sequential.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp

Dtype = Any

__all__ = [
    "kaiming_normal_init",
    "conv",
    "make_norm",
    "instance_norm",
    "frames_conv",
    "Conv",
    "ConvNormAct",
    "ResidualBlock",
    "BottleneckBlock",
]


def instance_norm(x, eps: float = 1e-5, relu: bool = False):
    """Parameter-free instance norm (+ optional relu) as one tight chain.

    ``x`` is ``(B, H, W, C)``, or ``(1, B, H, W, C)`` with the frames on
    a depth axis (:func:`frames_conv`); either way the statistics are
    per frame and channel, over ``(H, W)``. Exactly
    ``nn.InstanceNorm(use_bias=False, use_scale=False)`` numerics
    (one-pass stats: ``var = max(0, E[x^2] - E[x]^2)``, fp32), written as a
    single expression so XLA emits one fused dual-reduce for the stats and
    one fused normalize+relu.

    In the depth form the unit batch axis is reduced with ``(H, W)``: the
    TPU compiler computes a small-batch conv with the width split into the
    batch, and keeps a reduction in that split only if it takes the batch
    and the split axis together (or neither) — then the sums leave the
    conv's own fusion as ``(B, C)`` and the normalisation joins the next
    one, with no relayout of the activation (PERF.md, PR 31).
    """
    frames, chan = x.ndim - 4, x.ndim - 1
    axes = tuple(a for a in range(x.ndim) if a not in (frames, chan))
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=axes, keepdims=True)
    m2 = jnp.mean(xf * xf, axis=axes, keepdims=True)
    var = jnp.maximum(m2 - mu * mu, 0.0)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)

# He/Kaiming-normal (fan_out) — the torchvision RAFT initializer.
kaiming_normal_init = nn.initializers.variance_scaling(
    2.0, "fan_out", "truncated_normal"
)

KernelT = Union[int, Tuple[int, int]]


def _pair(k: KernelT) -> Tuple[int, int]:
    return (k, k) if isinstance(k, int) else tuple(k)


def frames_conv(x, kernel, strides, padding):
    """2-D convolution by an HWIO ``kernel`` of ``(B, H, W, C)``, or of
    ``(1, B, H, W, C)``: frames on a depth axis that the kernel does not
    span, batch 1 — the same sums, frame by frame. Which of the two a
    caller wants is :class:`~raft_tpu.models.encoders.FeatureEncoder`'s
    to say, and why (PERF.md, PR 31); here the rank of ``x`` decides."""
    if x.ndim == 5:
        return jax.lax.conv_general_dilated(
            x, kernel[None], (1,) + tuple(strides), ((0, 0),) + tuple(padding),
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        )
    return jax.lax.conv_general_dilated(
        x, kernel, tuple(strides), tuple(padding),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


class Conv(nn.Module):
    """``nn.Conv``'s 2-D convolution — the same ``kernel`` / ``bias``
    parameters, dtype promotion and sums — for both of
    :func:`frames_conv`'s forms (``nn.Conv`` itself folds every leading
    axis back into one batch)."""

    features: int
    kernel_size: Tuple[int, int]
    strides: Tuple[int, int]
    padding: Tuple[int, int]
    use_bias: bool = True
    dtype: Optional[Dtype] = None  # computation dtype; params stay fp32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", kaiming_normal_init,
            tuple(self.kernel_size) + (x.shape[-1], self.features),
        )
        bias = (
            self.param("bias", nn.initializers.zeros, (self.features,))
            if self.use_bias else None
        )
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias, dtype=self.dtype)
        y = frames_conv(x, kernel, self.strides, [(p, p) for p in self.padding])
        return y if bias is None else y + bias


def conv(
    features: int,
    kernel: KernelT = 3,
    stride: KernelT = 1,
    padding=None,
    use_bias: bool = True,
    dtype: Optional[Dtype] = None,
    name: Optional[str] = None,
) -> Conv:
    """:class:`Conv` with kaiming-normal init and torch-style default padding.

    Default padding is ``(k-1)//2`` per spatial dim (symmetric), matching
    ``torch.nn.Conv2d(padding=k//2)`` for the odd kernels RAFT uses.
    """
    kernel = _pair(kernel)
    if padding is None:
        padding = tuple((k - 1) // 2 for k in kernel)
    return Conv(
        features, kernel, _pair(stride), _pair(padding),
        use_bias=use_bias, dtype=dtype, name=name,
    )


def make_norm(spec: Optional[str], *, train: bool, axis_name: Optional[str], name: str):
    """Instantiate a norm layer from a string spec: 'batch' | 'instance' | None.

    Returns a callable ``x -> x`` (identity for None). BatchNorm uses
    ``momentum=0.9`` (torch's 0.1 decay convention) and syncs batch statistics
    across ``axis_name`` when provided — the TPU data-parallel replacement for
    SyncBatchNorm.
    """
    if spec is None:
        return lambda x: x
    if spec == "batch":
        bn = nn.BatchNorm(
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            axis_name=axis_name,
            name=name,
        )
        return bn
    if spec == "instance":
        # parameter-free; the canonical fused form (ConvNormAct routes its
        # own instance branch through instance_norm directly to fold relu)
        return lambda x: instance_norm(x)
    raise ValueError(f"unknown norm spec: {spec!r}")


class _S2DConv7x2(nn.Module):
    """7x7 stride-2 conv computed as a 4x4 stride-1 conv on 2x2
    space-to-depth input.

    Tiny input channel counts (the RGB stem) starve the MXU: the measured
    stem conv ran ~8x over compute roofline at Sintel scale. Folding each
    2x2 pixel block into channels quadruples the contraction depth and
    quarters the spatial extent; the kernel is re-indexed on the fly from
    the checkpoint's ``(7, 7, C, F)`` layout (zero-padded to 8x8, split
    into the four stride phases), so parameters, initializer, and the
    variable tree are byte-identical to the plain conv (``kernel``/``bias``
    under the same module name) and the sums are the same numbers.
    """

    features: int
    use_bias: bool = True
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x):
        *lead, h, w, c = x.shape  # (B,) or (1, B): frames_conv's two forms
        if h % 2 or w % 2:
            raise ValueError("space-to-depth stem needs even H and W")
        kernel = self.param(
            "kernel", kaiming_normal_init, (7, 7, c, self.features)
        )
        bias = (
            self.param("bias", nn.initializers.zeros, (self.features,))
            if self.use_bias
            else None
        )
        # x2[p, q, (du, dv, c)] = x[2p+du, 2q+dv, c]
        x2 = x.reshape(-1, h // 2, 2, w // 2, 2, c)
        x2 = x2.transpose(0, 1, 3, 2, 4, 5).reshape(*lead, h // 2, w // 2, 4 * c)
        # y[i,j] = sum_k W[k,l] x[2i+k-3, 2j+l-3]; with k = 2t+du-1 the
        # phase decomposition is W2[t, tj, (du, dv, c)] = Wp[2t+du, 2tj+dv]
        # over the zero-padded Wp[1:8] = W
        kp = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        k2 = kp.reshape(4, 2, 4, 2, c, self.features)
        k2 = k2.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, self.features)
        if self.dtype is not None:
            x2 = x2.astype(self.dtype)
            k2 = k2.astype(self.dtype)
        y = frames_conv(x2, k2, (1, 1), ((2, 1), (2, 1)))
        if bias is not None:
            y = y + (bias.astype(self.dtype) if self.dtype is not None else bias)
        return y


class ConvNormAct(nn.Module):
    """Conv -> (norm) -> (relu), named ``layers_0`` / ``layers_1`` for
    checkpoint-tree compatibility (reference ``jax_raft/model.py:120-159``).

    ``s2d=True`` (7x7 stride-2 convs only) computes the conv via
    :class:`_S2DConv7x2` — same parameters, same sums, MXU-shaped.
    """

    features: int
    kernel: KernelT = 3
    stride: KernelT = 1
    norm: Optional[str] = "batch"
    act: bool = True
    use_bias: Optional[bool] = None
    axis_name: Optional[str] = None
    dtype: Optional[Dtype] = None
    s2d: bool = False

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        use_bias = self.use_bias if self.use_bias is not None else self.norm is None
        if self.s2d:
            if _pair(self.kernel) != (7, 7) or _pair(self.stride) != (2, 2):
                raise ValueError("s2d is specific to 7x7 stride-2 stems")
            x = _S2DConv7x2(
                self.features, use_bias=use_bias, dtype=self.dtype,
                name="layers_0",
            )(x)
        else:
            x = conv(self.features, self.kernel, self.stride, use_bias=use_bias,
                     dtype=self.dtype, name="layers_0")(x)
        if self.norm == "instance":
            # parameter-free, so skipping the ``layers_1`` module keeps the
            # checkpoint tree identical; the fused form folds the relu
            return instance_norm(x, relu=self.act)
        x = make_norm(self.norm, train=train, axis_name=self.axis_name, name="layers_1")(x)
        if self.act:
            x = nn.relu(x)
        return x


class ResidualBlock(nn.Module):
    """Two 3x3 conv-norm-relu stages with an identity / strided-1x1 skip.

    All convs carry biases and a trailing relu is applied to the sum — the
    torchvision-RAFT deviation from vanilla ResNet (reference
    ``jax_raft/model.py:162-184``).
    """

    features: int
    norm: Optional[str]
    stride: int = 1
    axis_name: Optional[str] = None
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        y = ConvNormAct(
            self.features, 3, self.stride, self.norm, use_bias=True,
            axis_name=self.axis_name, dtype=self.dtype, name="convnormrelu1",
        )(x, train=train)
        y = ConvNormAct(
            self.features, 3, 1, self.norm, use_bias=True,
            axis_name=self.axis_name, dtype=self.dtype, name="convnormrelu2",
        )(y, train=train)
        if self.stride != 1:
            x = ConvNormAct(
                self.features, 1, self.stride, self.norm, act=False, use_bias=True,
                axis_name=self.axis_name, dtype=self.dtype, name="downsample",
            )(x, train=train)
        return nn.relu(x + y)


class BottleneckBlock(nn.Module):
    """1x1(C/4) -> 3x3(C/4, stride) -> 1x1(C) bottleneck with skip
    (reference ``jax_raft/model.py:187-216``); used by raft_small."""

    features: int
    norm: Optional[str]
    stride: int = 1
    axis_name: Optional[str] = None
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x, *, train: bool = False):
        mid = self.features // 4
        y = ConvNormAct(
            mid, 1, 1, self.norm, use_bias=True,
            axis_name=self.axis_name, dtype=self.dtype, name="convnormrelu1",
        )(x, train=train)
        y = ConvNormAct(
            mid, 3, self.stride, self.norm, use_bias=True,
            axis_name=self.axis_name, dtype=self.dtype, name="convnormrelu2",
        )(y, train=train)
        y = ConvNormAct(
            self.features, 1, 1, self.norm, use_bias=True,
            axis_name=self.axis_name, dtype=self.dtype, name="convnormrelu3",
        )(y, train=train)
        if self.stride != 1:
            x = ConvNormAct(
                self.features, 1, self.stride, self.norm, act=False, use_bias=True,
                axis_name=self.axis_name, dtype=self.dtype, name="downsample",
            )(x, train=train)
        return nn.relu(x + y)
