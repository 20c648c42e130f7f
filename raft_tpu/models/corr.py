"""Correlation engine: all-pairs volume, pooled pyramid, multi-scale lookup.

Duck-typed interface (kept from the reference's component contract,
``jax_raft/model.py:530-539``): a correlation block exposes
``build_pyramid(fmap1, fmap2)``, ``index_pyramid(pyramid, centroids)`` and
``out_channels``, so dense / fused-Pallas / on-the-fly variants are
swappable. What a block builds is its own format: only its own methods
read it, and ``resident_pyramid(pyramid)`` says whether, and in what
shapes, it can be held across steps (the serve pool).

TPU-first notes:
  * The volume matmul runs in fp32 accumulation (``preferred_element_type``)
    regardless of input dtype — bf16 feature maps still correlate to fp32,
    which is required to hold EPE parity (SURVEY.md §7.3 item 2).
  * The dense path mirrors reference semantics exactly
    (``jax_raft/model.py:403-481``) and serves as the correctness oracle for
    the Pallas kernels in ``raft_tpu.kernels``.
"""

from __future__ import annotations

import math
from typing import List, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.ops.sampling import bilinear_sample

__all__ = [
    "CorrBlock",
    "LazyCorrFeatures",
    "correlation_volume",
    "pool_pyramid",
    "lookup_pyramid",
    "lookup_pyramid_gather",
    "project_taps",
]


def correlation_volume(fmap1: jax.Array, fmap2: jax.Array) -> jax.Array:
    """All-pairs dot-product volume, scaled by 1/sqrt(C).

    Args:
        fmap1, fmap2: ``(B, h, w, C)`` feature maps.

    Returns:
        ``(B, h*w, h, w)`` volume: correlation of each query pixel (flattened
        second axis) against every target pixel.
    """
    b, h, w, c = fmap1.shape
    q = fmap1.reshape(b, h * w, c)
    t = fmap2.reshape(b, h * w, c)
    vol = jax.lax.dot_general(
        q,
        t,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    vol = vol * (1.0 / math.sqrt(c))
    return vol.reshape(b, h * w, h, w)


def pool_pyramid(volume: jax.Array, num_levels: int) -> List[jax.Array]:
    """Average-pool the target dims of ``(B, Q, h, w)`` into a pyramid.

    Level l has target resolution ``(h / 2**l, w / 2**l)``. Pooling is done in
    ``(B*Q, h, w, 1)`` layout (NHWC with singleton channel) to reuse XLA's
    reduce-window; the fused Pallas path pools in-kernel instead.
    """
    b, q, h, w = volume.shape
    lvl = volume.reshape(b * q, h, w, 1)
    pyramid = [lvl]
    for _ in range(num_levels - 1):
        lvl = nn.avg_pool(lvl, (2, 2), strides=(2, 2))
        pyramid.append(lvl)
    return pyramid


def _offset_grid(radius: int, dtype=jnp.float32) -> jax.Array:
    """(S, S, 2) integer offsets in (x, y) order, S = 2*radius+1.

    Offsets enumerate (dy, dx) row-major to match the reference's
    ``meshgrid(di, dj, indexing='ij')`` channel ordering
    (``jax_raft/model.py:451-455``) — required for checkpoint-compatible
    ``convcorr1`` weights.
    """
    r = jnp.arange(-radius, radius + 1, dtype=dtype)
    # Tap (i, j) offsets x by r[i] and y by r[j]: the x offset varies along the
    # *first* tap axis. This transposed enumeration matches the reference's
    # meshgrid(di, dj, indexing='ij') added to (x, y)-ordered centroids and is
    # what converted `convcorr1` weights expect.
    off_x, off_y = jnp.meshgrid(r, r, indexing="ij")
    return jnp.stack([off_x, off_y], axis=-1)


def separable_taps(
    vol: jax.Array,
    cx: jax.Array,
    cy: jax.Array,
    radius: int,
    *,
    weight_dtype=None,
) -> jax.Array:
    """Bilinear (2r+1)^2 taps around per-item centers, as two batched matmuls.

        out[..., i, j] = sum_{y,x} Wx[..., i, x] * Wy[..., j, y] * vol[..., y, x]

    ``i`` indexes x-offsets and ``j`` y-offsets — the reference's transposed
    tap enumeration (see ``_offset_grid``). Out-of-range taps receive zero
    weight rows (exact torch ``padding_mode='zeros'`` parity). Shared by the
    dense and on-the-fly correlation paths so the parity-critical tap math
    exists exactly once.

    Args:
        vol: ``(*batch, hl, wl)`` values.
        cx, cy: ``(*batch,)`` tap-center coordinates (pixel units of vol).
    Returns:
        ``(*batch, S, S)`` taps, S = 2*radius+1, fp32.
    """
    hl, wl = vol.shape[-2], vol.shape[-1]
    r = jnp.arange(-radius, radius + 1, dtype=cx.dtype)
    wx = _bilinear_weights(cx[..., None] + r, wl)  # (*batch, S, wl)
    wy = _bilinear_weights(cy[..., None] + r, hl)  # (*batch, S, hl)
    if weight_dtype is not None:
        # Carrying weights and the row intermediate in bf16 halves the HBM
        # traffic of the volume-reading contraction; accumulation below is
        # fp32 either way.
        wx = wx.astype(weight_dtype)
        wy = wy.astype(weight_dtype)
    # y-contraction as a matmul: it reads the whole volume row-block, is
    # bandwidth-bound, and the MXU runs it at roofline.
    t = jnp.einsum(
        "...jy,...yx->...jx",
        wy,
        vol,
        preferred_element_type=weight_dtype or jnp.float32,
    )
    # x-contraction as multiply + lane-reduce on the VPU: the batched-matmul
    # form has M = N = 2r+1 = 9, which pads both dims to the 128-wide MXU
    # tile and wastes >99% of the array (measured slower than the
    # volume-reading contraction above at Sintel scale).
    return jnp.sum(
        wx[..., :, None, :] * t[..., None, :, :], axis=-1, dtype=jnp.float32
    )


def _bilinear_weights(pos: jax.Array, size: int) -> jax.Array:
    """Dense separable bilinear-interpolation weights.

    ``W[..., k] = relu(1 - |pos - k|)`` for grid index ``k in [0, size)`` —
    exactly the two-corner bilinear weights of ``pos`` with zero padding
    (out-of-range corners simply address no row, reproducing torch
    ``padding_mode='zeros'`` / ndimage ``mode='constant'``).

    Args:
        pos: ``(..., S)`` fractional positions.
    Returns:
        ``(..., S, size)`` weights (rows sum to <= 1; < 1 near borders).
    """
    grid = jnp.arange(size, dtype=pos.dtype)
    return nn.relu(1.0 - jnp.abs(pos[..., None] - grid))


def lookup_pyramid(
    pyramid: Sequence[jax.Array],
    centroids: jax.Array,
    radius: int,
    *,
    weight_dtype=None,
) -> jax.Array:
    """(2r+1)^2 bilinear taps around each centroid at every level — as
    separable batched matmuls, not gathers.

    TPU-first design note: a per-pixel scattered bilinear gather (the
    reference's formulation via ``map_coordinates``,
    ``jax_raft/model.py:448-470``) lowers to millions of scalar gathers and
    runs ~100 ms/iteration on TPU. Bilinear interpolation is separable
    (weight(y,x) = wy * wx), so the whole lookup is instead computed as two
    dense contractions per level with the bilinear weight matrices

        out[q, i, j] = sum_{y, x} Wx[q, i, x] * Wy[q, j, y] * vol[q, y, x]

    which XLA maps onto the MXU as batched matmuls. Out-of-range taps get
    zero weight rows => exact zero-padding parity with the gather oracle
    (covered by tests).

    Args:
        pyramid: list of ``(B*Q, hl, wl, 1)`` levels.
        centroids: ``(B, h, w, 2)`` level-0 (x, y) coordinates per query pixel.

    Returns:
        ``(B, h, w, L*(2r+1)^2)`` correlation features.
    """
    b, h, w, _ = centroids.shape
    q = b * h * w
    s = 2 * radius + 1
    cent = centroids.reshape(q, 2)

    features = []
    for level, vol in enumerate(pyramid):
        hl, wl = vol.shape[1], vol.shape[2]
        taps = separable_taps(
            vol.reshape(q, hl, wl),
            cent[:, 0] / (2.0**level),
            cent[:, 1] / (2.0**level),
            radius,
            weight_dtype=weight_dtype,
        )
        features.append(taps.reshape(b, h, w, s * s))
    return jnp.concatenate(features, axis=-1)


def lookup_pyramid_window(
    pyramid: Sequence[jax.Array],
    centroids: jax.Array,
    radius: int,
) -> jax.Array:
    """Row-window variant: gather only the (S+1) volume rows each query can
    touch, then 2-tap combine in y and dense multiply+reduce in x.

    All S taps in y share one fractional part (tap j sits at cy + j - r, so
    ``floor`` differs by exactly j), so the y-interpolation needs just the
    ``S+1`` consecutive rows starting at ``floor(cy) - r``: an 18%-of-volume
    read instead of 100%. Zero padding comes from physically padding the row
    axis by r+2 zeros; centroids are pre-clamped so fully out-of-range
    windows land inside the zero margin (exact parity with the gather
    oracle, covered by tests).
    """
    b, h, w, _ = centroids.shape
    q = b * h * w
    s = 2 * radius + 1
    cent = centroids.reshape(q, 2)

    features = []
    for level, vol in enumerate(pyramid):
        hl, wl = vol.shape[1], vol.shape[2]
        v = vol.reshape(q, hl, wl)
        # 2r+2 so the window start stays in-bounds (and over zero rows) even
        # for the fully-out-of-range clamped centroids at either end
        pad = 2 * radius + 2
        vp = jnp.pad(v, ((0, 0), (pad, pad), (0, 0)))

        cx = cent[:, 0] / (2.0**level)
        cy = cent[:, 1] / (2.0**level)
        # beyond these bounds every tap reads zero; clamping keeps the window
        # start inside the zero margin without changing any in-range result
        cy = jnp.clip(cy, -(radius + 1.5), hl + radius + 0.5)
        y0 = jnp.floor(cy - radius)
        fy = (cy - radius - y0).astype(v.dtype)
        start = (y0 + pad).astype(jnp.int32)

        rows = jax.vmap(
            lambda m, s0: jax.lax.dynamic_slice(m, (s0, 0), (s + 1, wl))
        )(vp, start)  # (q, S+1, wl)
        # 2-tap y interpolation: t[j] = (1-fy) rows[j] + fy rows[j+1]
        t = (1.0 - fy)[:, None, None] * rows[:, :s] + fy[:, None, None] * rows[:, 1:]

        r = jnp.arange(-radius, radius + 1, dtype=cx.dtype)
        wx = _bilinear_weights(cx[..., None] + r, wl)  # (q, S, wl)
        taps = (wx[:, :, None, :] * t[:, None, :, :]).sum(-1)
        features.append(taps.astype(jnp.float32).reshape(b, h, w, s * s))
    return jnp.concatenate(features, axis=-1)


def lookup_pyramid_gather(
    pyramid: Sequence[jax.Array],
    centroids: jax.Array,
    radius: int,
) -> jax.Array:
    """Gather-based reference lookup (the oracle for :func:`lookup_pyramid`;
    reference semantics ``jax_raft/model.py:448-470``). Slow on TPU — used
    in tests only."""
    b, h, w, _ = centroids.shape
    s = 2 * radius + 1
    delta = _offset_grid(radius)[None]  # (1, S, S, 2)
    centers = centroids.reshape(b * h * w, 1, 1, 2)

    features = []
    for level, vol in enumerate(pyramid):
        coords = centers / (2.0 ** level) + delta  # (B*Q, S, S, 2)
        taps = bilinear_sample(vol, coords)  # (B*Q, S, S, 1)
        features.append(taps.reshape(b, h, w, s * s))
    return jnp.concatenate(features, axis=-1)


def project_taps(taps: jax.Array, kernel: jax.Array, bias: jax.Array,
                 dtype=None) -> jax.Array:
    """``relu(taps @ kernel + bias)`` — the motion encoder's ``convcorr1``
    1x1 conv expressed as a matmul over the channel dim.

    Semantically identical to ``nn.Conv(features, (1, 1))`` + relu on the
    correlation features (a 1x1 stride-1 conv IS this matmul); pulled out
    so correlation blocks can fuse the projection into the lookup itself
    (``index_project``) without the (.., L*(2r+1)^2) tap tensor ever
    materializing in HBM.

    Args:
        taps: ``(..., C_in)`` correlation features.
        kernel: ``(1, 1, C_in, C_out)`` conv kernel (checkpoint layout).
        bias: ``(C_out,)``.
        dtype: compute dtype mirroring ``nn.Conv(dtype=...)`` promotion.
    """
    w = kernel.reshape(kernel.shape[-2], kernel.shape[-1])
    if dtype is not None:
        taps, w, bias = taps.astype(dtype), w.astype(dtype), bias.astype(dtype)
    else:
        taps = taps.astype(jnp.float32)
    return nn.relu(taps @ w + bias)


class LazyCorrFeatures:
    """Deferred correlation lookup, passed to the update block in place of
    the materialized ``(B, h, w, L*(2r+1)^2)`` tap tensor.

    The motion encoder calls :meth:`project` with its ``convcorr1``
    weights: blocks that support it (``FusedLookupCorrBlock``) run the
    lookup AND the projection in one Pallas kernel; every other block
    materializes the taps and applies the mathematically identical
    matmul+bias+relu (:func:`project_taps`). :meth:`materialize` keeps the
    plain-tensor contract for callers that want raw correlation features.

    Injected custom blocks only need the reference's documented contract
    (``build_pyramid`` / ``index_pyramid`` / ``out_channels``,
    ``jax_raft/model.py:530-539``) — ``index_project`` is an optional
    extension; :meth:`project` falls back to materialize + ``project_taps``
    when a block does not define it.
    """

    def __init__(self, block, pyramid: Sequence[jax.Array], centroids: jax.Array):
        self.block = block
        self.pyramid = pyramid
        self.centroids = centroids

    @property
    def out_channels(self) -> int:
        return self.block.out_channels

    def materialize(self) -> jax.Array:
        return self.block.index_pyramid(self.pyramid, self.centroids)

    def project(self, kernel: jax.Array, bias: jax.Array, dtype=None) -> jax.Array:
        index_project = getattr(self.block, "index_project", None)
        if index_project is None:
            return project_taps(self.materialize(), kernel, bias, dtype=dtype)
        return index_project(
            self.pyramid, self.centroids, kernel, bias, dtype=dtype
        )


class CorrBlock:
    """Dense correlation block (reference semantics; parameter-free).

    The constructor enforces the minimum feature-map size needed so the
    coarsest pyramid level still has >= 2 px per side (reference
    ``jax_raft/model.py:428-436``).
    """

    def __init__(self, num_levels: int = 4, radius: int = 4, dtype=None):
        """``dtype`` (e.g. ``jnp.bfloat16``): storage dtype for the pooled
        pyramid and lookup intermediates. The volume matmul always
        accumulates fp32 and the returned correlation features are fp32;
        bf16 storage halves the dominant per-iteration HBM traffic at ~3
        decimal digits of correlation precision. None = pure fp32."""
        self.num_levels = num_levels
        self.radius = radius
        self.dtype = dtype
        self.out_channels = num_levels * (2 * radius + 1) ** 2

    def min_fmap_size(self) -> int:
        return 2 * 2 ** (self.num_levels - 1)

    def build_pyramid(self, fmap1: jax.Array, fmap2: jax.Array) -> List[jax.Array]:
        if fmap1.shape != fmap2.shape:
            raise ValueError("feature maps must have identical shapes")
        min_hw = self.min_fmap_size()
        if min(fmap1.shape[1:3]) < min_hw:
            raise ValueError(
                f"feature maps {fmap1.shape[1:3]} too small for a "
                f"{self.num_levels}-level pyramid; need >= {min_hw} per side "
                f"(inputs are downsampled 8x, so images must be >= {8 * min_hw} px)"
            )
        vol = correlation_volume(fmap1, fmap2)
        if self.dtype is not None:
            vol = vol.astype(self.dtype)
        return pool_pyramid(vol, self.num_levels)

    def resident_pyramid(self, pyramid):
        """The built ``pyramid`` in the form to HOLD across many lookups
        (``RAFT.begin_refinement``, for the serve pool's slot state): a
        pytree of arrays, each with the ``B*Q`` query rows leading, that
        ``index_pyramid`` / ``index_project`` take as they take the built
        one. A block whose pyramid cannot be held by slot raises a
        ``ValueError``. Here: the levels as built."""
        return tuple(pyramid)

    def index_pyramid(self, pyramid: Sequence[jax.Array], centroids: jax.Array) -> jax.Array:
        feats = lookup_pyramid(
            pyramid, centroids, self.radius, weight_dtype=self.dtype
        )
        b, h, w, _ = centroids.shape
        assert feats.shape == (b, h, w, self.out_channels)
        return feats

    def index_project(
        self,
        pyramid: Sequence[jax.Array],
        centroids: jax.Array,
        kernel: jax.Array,
        bias: jax.Array,
        *,
        dtype=None,
    ) -> jax.Array:
        """Lookup + ``convcorr1`` projection (see :func:`project_taps`).
        Subclasses may fuse the two; this base form is the semantics."""
        return project_taps(
            self.index_pyramid(pyramid, centroids), kernel, bias, dtype=dtype
        )
