"""Pallas TPU kernel: fused multi-scale correlation lookup.

The lookup runs once per refinement iteration (32x per pair at validation,
reference semantics ``jax_raft/model.py:448-470``) and bounds raft_large
inference. The XLA separable formulation (``corr.lookup_pyramid``) computes
per level

    t   = wy @ vol          (reads the whole pooled volume -> HBM-bound, ok)
    out = reduce(wx * t)    (VPU)

but materializes ``wx``/``wy``/``t`` in HBM every iteration (~100 MB per
lookup), pays a layout copy for the ``(Q, S, S) -> (B, h, w, S*S)`` reshape,
and a 4-way concat. This kernel fuses the whole lookup: weights are built
in-registers from ``broadcasted_iota``, both contractions run from VMEM, and
all levels write one ``(Q, L*S*S)`` output block — per iteration the only
HBM traffic is the pooled volume (read once) and the 9 MB feature output.

Zero-padding parity: taps outside the volume get all-zero bilinear weight
rows (``relu(1 - |pos - k|)`` touches no valid grid index), exactly the
gather oracle's ``padding_mode='zeros'`` semantics — same scheme as the XLA
path, tested against the oracle in interpret mode and on-chip.

Status: SUPERSEDED by ``lookup_xtap`` (the benched flagship) for every
config path. Kept deliberately as (a) the A/B baseline kernel that
``scripts/lookup_bench.py`` measures the flagship against, and (b) the
readable single-kernel statement of the fused-lookup algorithm that
``lookup_xtap``'s layout tricks (run-layout flat levels, lane-roll
corners, in-kernel projection) obscure — it is the document you read
first when touching the flagship.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["lookup_pyramid_pallas"]


def _kernel(cents_ref, *refs, radius: int, num_levels: int):
    out_ref = refs[-1]
    vol_refs = refs[:-1]
    s = 2 * radius + 1
    cents = cents_ref[...]  # (T, 2) fp32
    t_q = cents.shape[0]

    for level in range(num_levels):
        vol = vol_refs[level][...].astype(jnp.float32)  # (T, hl, wl)
        hl, wl = vol.shape[1], vol.shape[2]
        inv = 1.0 / (2.0**level)
        cx = cents[:, 0] * inv  # (T,)
        cy = cents[:, 1] * inv

        # integer iota (Mosaic requirement), cast to float for the weights
        ygrid = jax.lax.broadcasted_iota(jnp.int32, (t_q, s, hl), 2).astype(
            jnp.float32
        )
        joff = (
            jax.lax.broadcasted_iota(jnp.int32, (t_q, s, hl), 1).astype(
                jnp.float32
            )
            - radius
        )
        # wy[t, j, y] = bilinear weight of tap (cy + j - r) at grid row y
        wy = jnp.maximum(0.0, 1.0 - jnp.abs(cy[:, None, None] + joff - ygrid))
        # y-contraction on the MXU (it reads the whole volume tile and is
        # the bandwidth-heavy half; a VPU multiply+reduce loop here measured
        # ~2.5x slower than the XLA baseline)
        t = jax.lax.dot_general(
            wy,
            vol,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # (T, S, wl)

        xgrid = jax.lax.broadcasted_iota(jnp.int32, (t_q, wl), 1).astype(
            jnp.float32
        )
        # out[t, i, j] = sum_x wx_i[t, x] * t[t, j, x] — looped over i to keep
        # the VMEM temporaries at (T, S, wl) instead of (T, S, S, wl) (the
        # one-shot form blows the 16 MB scoped-VMEM stack at useful tiles)
        cols = []
        for i in range(s):
            wx_i = jnp.maximum(
                0.0, 1.0 - jnp.abs(cx[:, None] + (i - radius) - xgrid)
            )  # (T, wl)
            cols.append(jnp.sum(t * wx_i[:, None, :], axis=-1))  # (T, S)
        taps = jnp.concatenate(cols, axis=1)  # (T, S*S): i-major, j-minor
        out_ref[:, level * s * s : (level + 1) * s * s] = taps


def lookup_pyramid_pallas(
    pyramid: Sequence[jax.Array],
    centroids: jax.Array,
    radius: int,
    *,
    query_tile: int = 64,
    interpret: bool = False,
) -> jax.Array:
    """Fused multi-scale (2r+1)^2 bilinear lookup over a pooled pyramid.

    Args:
        pyramid: list of ``(Q, hl, wl, 1)`` (or ``(Q, hl, wl)``) levels,
            as produced by ``corr.pool_pyramid`` / ``fused_volume_pyramid``.
        centroids: ``(B, h, w, 2)`` level-0 (x, y) coordinates, Q = B*h*w.
    Returns:
        ``(B, h, w, L*(2r+1)^2)`` fp32 correlation features (same channel
        order as ``corr.lookup_pyramid``: levels outer, x-offset, y-offset).
    """
    b, h, w, _ = centroids.shape
    q = b * h * w
    s = 2 * radius + 1
    num_levels = len(pyramid)
    vols = [v.reshape(q, v.shape[1], v.shape[2]) for v in pyramid]
    cents = centroids.reshape(q, 2).astype(jnp.float32)

    tq = min(query_tile, q)
    pad = (-q) % tq
    if pad:
        cents = jnp.pad(cents, ((0, pad), (0, 0)))
        vols = [jnp.pad(v, ((0, pad), (0, 0), (0, 0))) for v in vols]
    qp = q + pad
    n_tiles = qp // tq

    kernel = functools.partial(_kernel, radius=radius, num_levels=num_levels)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((qp, num_levels * s * s), jnp.float32),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tq, 2), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ]
        + [
            pl.BlockSpec(
                (tq, v.shape[1], v.shape[2]),
                lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM,
            )
            for v in vols
        ],
        out_specs=pl.BlockSpec(
            (tq, num_levels * s * s), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # the unrolled per-tap loop keeps ~S volume-tile temporaries on
            # the VMEM stack; the 16 MB default is too tight at useful tiles
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * qp * s * sum(v.shape[1] * v.shape[2] for v in vols),
            bytes_accessed=sum(v.size * v.dtype.itemsize for v in vols)
            + qp * num_levels * s * s * 4,
            transcendentals=0,
        ),
    )(cents, *vols)
    if pad:
        out = out[:q]
    return out.reshape(b, h, w, num_levels * s * s)
