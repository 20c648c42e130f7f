"""Pallas TPU kernel: fused all-pairs correlation volume + pooled pyramid.

Replaces the XLA three-op chain (batched matmul -> avg_pool x3) of
``corr.CorrBlock.build_pyramid`` with ONE kernel pass:

  * ``fmap2`` is VMEM-resident across the whole grid (its BlockSpec index is
    constant per batch element, so Pallas fetches it once, not per tile) —
    the MXU streams query tiles against it;
  * the (TQ, h*w) correlation tile is pooled into all pyramid levels while
    still in VMEM — the XLA path writes the 198 MB level-0 volume to HBM and
    reads it back for each pooling step, this kernel writes each level
    exactly once and reads the volume zero times;
  * accumulation is fp32 on the MXU regardless of input dtype
    (``preferred_element_type``), preserving the EPE-critical precision
    contract (SURVEY.md §7.3).

Pooling runs as matmuls against constant 2x-average matrices (built from
``broadcasted_iota`` at trace time) — always Mosaic-lowerable, MXU-friendly,
and exactly equal to ``nn.avg_pool`` VALID semantics including odd-size tail
dropping (the h-pool contraction is arranged to need one sublane/lane
transpose, which the TPU transpose unit handles).

Numerics vs the XLA oracle are exact to fp32 reassociation; covered by
interpret-mode tests in ``tests/test_pallas.py`` plus on-chip parity checks.
"""

from __future__ import annotations

import functools
import math
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


from raft_tpu.models.corr import CorrBlock

__all__ = ["fused_volume_pyramid", "PallasCorrBlock"]


def _level_dims(h: int, w: int, num_levels: int) -> List[Tuple[int, int]]:
    dims = [(h, w)]
    for _ in range(num_levels - 1):
        h, w = h // 2, w // 2
        dims.append((h, w))
    return dims


def _pool_matrix(n_in: int, n_out: int, dtype) -> jax.Array:
    """(n_in, n_out) constant: column j averages input rows 2j, 2j+1."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_in, n_out), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_in, n_out), 1)
    hit = (rows == 2 * cols) | (rows == 2 * cols + 1)
    return jnp.where(hit, jnp.asarray(0.5, dtype), jnp.asarray(0.0, dtype))


def _kernel(f1_ref, f2_ref, *out_refs, dims, scale, out_dtype):
    f1 = f1_ref[0]  # (TQ, C)
    f2 = f2_ref[0]  # (Q, C), VMEM-resident across tiles
    tq = f1.shape[0]
    h, w = dims[0]

    corr = jax.lax.dot_general(
        f1,
        f2,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (TQ, Q)
    v = corr.reshape(tq, h, w)
    out_refs[0][:] = v.astype(out_dtype)

    for level in range(1, len(dims)):
        hl, wl = dims[level]
        hp, wp = dims[level - 1]
        # w-pool: contract last dim with the averaging matrix -> (TQ, hp, wl)
        v = jax.lax.dot_general(
            v,
            _pool_matrix(wp, wl, v.dtype),
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # h-pool: contract middle dim -> (TQ, wl, hl), then restore (TQ, hl, wl)
        v = jax.lax.dot_general(
            v,
            _pool_matrix(hp, hl, v.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        v = jnp.swapaxes(v, 1, 2)
        out_refs[level][:] = v.astype(out_dtype)


def fused_volume_pyramid(
    fmap1: jax.Array,
    fmap2: jax.Array,
    num_levels: int = 4,
    *,
    query_tile: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> List[jax.Array]:
    """All-pairs correlation pyramid in one Pallas pass.

    Args:
        fmap1, fmap2: ``(B, h, w, C)`` feature maps.
    Returns:
        List of ``(B*h*w, hl, wl, 1)`` levels — same contract as
        ``corr.pool_pyramid`` (the correctness oracle).
    """
    b, h, w, c = fmap1.shape
    q = h * w
    scale = 1.0 / math.sqrt(c)
    dims = _level_dims(h, w, num_levels)

    tq = min(query_tile, q)
    pad = (-q) % tq
    f1 = fmap1.reshape(b, q, c)
    if pad:
        f1 = jnp.pad(f1, ((0, 0), (0, pad), (0, 0)))
    qp = q + pad
    n_tiles = qp // tq
    f2 = fmap2.reshape(b, q, c)

    kernel = functools.partial(
        _kernel, dims=dims, scale=scale, out_dtype=out_dtype
    )
    out_shapes = [
        jax.ShapeDtypeStruct((b * qp, hl, wl), out_dtype) for hl, wl in dims
    ]
    out_specs = [
        pl.BlockSpec(
            (tq, hl, wl),
            # row-block index: tile `qi` of batch `b` starts at row b*qp+qi*tq
            functools.partial(
                lambda bi, qi, nt: (bi * nt + qi, 0, 0), nt=n_tiles
            ),
            memory_space=pltpu.VMEM,
        )
        for hl, wl in dims
    ]
    grid_spec = pl.GridSpec(
        grid=(b, n_tiles),
        in_specs=[
            pl.BlockSpec(
                (1, tq, c), lambda bi, qi: (bi, qi, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, q, c), lambda bi, qi: (bi, 0, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=out_specs,
    )
    levels = pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid_spec=grid_spec,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # the VMEM-resident fmap2 plus double-buffered level-0 output
            # blocks exceed the 16 MB default at Sintel scale
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * qp * q * c,
            bytes_accessed=(f1.size + f2.size) * 4
            + sum(4 * b * qp * hl * wl for hl, wl in dims),
            transcendentals=0,
        ),
    )(f1, f2)

    if pad:
        # drop padded query rows: (B*qp, ...) -> (B, qp, ...) -> slice -> merge
        levels = [
            lvl.reshape(b, qp, *lvl.shape[1:])[:, :q].reshape(b * q, *lvl.shape[1:])
            for lvl in levels
        ]
    return [lvl[..., None] for lvl in levels]


class PallasCorrBlock(CorrBlock):
    """CorrBlock whose pyramid build runs in the fused Pallas kernel.

    Lookup (``index_pyramid``) is inherited — the separable-matmul
    formulation is already MXU-native.
    """

    def __init__(
        self,
        num_levels: int = 4,
        radius: int = 4,
        dtype=None,
        *,
        query_tile: int = 128,
        interpret: bool = False,
    ):
        super().__init__(num_levels=num_levels, radius=radius, dtype=dtype)
        self.query_tile = query_tile
        self.interpret = interpret

    def build_pyramid(self, fmap1: jax.Array, fmap2: jax.Array):
        if fmap1.shape != fmap2.shape:
            raise ValueError("feature maps must have identical shapes")
        min_hw = self.min_fmap_size()
        if min(fmap1.shape[1:3]) < min_hw:
            raise ValueError(
                f"feature maps {fmap1.shape[1:3]} too small for a "
                f"{self.num_levels}-level pyramid; need >= {min_hw} per side"
            )
        # Mosaic can only lower the in-kernel (TQ, h*w) -> (TQ, h, w)
        # reshape when the minor dim stays lane-aligned; for other widths
        # (e.g. the small shapes `init_variables` probes with) fall back to
        # the XLA oracle rather than fail to compile.
        if not self.interpret and fmap1.shape[2] % 128 != 0:
            return super().build_pyramid(fmap1, fmap2)
        return fused_volume_pyramid(
            fmap1,
            fmap2,
            self.num_levels,
            query_tile=self.query_tile,
            out_dtype=self.dtype or jnp.float32,
            interpret=self.interpret,
        )
