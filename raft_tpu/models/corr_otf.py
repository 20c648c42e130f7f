"""Memory-free on-the-fly correlation: the blockwise / "flash" variant.

Mathematical identity this rests on (the TPU-native answer to the
reference's 198 MB materialized volume, SURVEY.md §2.2/§5.7): average
pooling is linear, and the correlation volume is linear in the target
features, so pooling the volume over its *target* dims commutes with the
correlation itself:

    avgpool_l(fmap1[q] . fmap2^T) == fmap1[q] . (avgpool_l fmap2)^T

and likewise bilinear interpolation of pooled correlations equals
correlation against bilinearly-interpolated pooled features. Hence the
per-iteration lookup

    corr_feat(q, tap, l) = <fmap1[q], bilerp(pool_l(fmap2), c_q/2^l + d_tap)>
                           / sqrt(C)

needs only the L pooled copies of ``fmap2`` (~KBs) instead of the
``(h*w)^2`` volume (~198 MB fp32 at Sintel): O(Q * C) memory instead of
O(Q^2), exactly like blockwise attention avoids the score matrix.

Execution: per query chunk, the correlation rows are *recomputed* on the
MXU (an honest (chunk, C) x (C, hl*wl) matmul) and the bilinear taps are
applied as separable weight matmuls (see ``corr.lookup_pyramid``) — there
is not a single gather in the iteration loop. Cost ~2*Q*C*sum_l(hl*wl)
FLOPs per iteration (~34 GFLOP at Sintel scale): milliseconds on the MXU,
in exchange for never touching HBM with the volume.

Exactness: identical pooling windows to the dense pyramid (successive 2x2
VALID pooling drops the same tail rows), so results match the dense oracle
to float reassociation; covered by tests against ``CorrBlock``.

Same duck-typed interface as ``CorrBlock`` (reference contract,
``jax_raft/model.py:530-539``) — swappable via ``RAFTConfig.corr_impl``.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from raft_tpu.models.corr import separable_taps

__all__ = ["OnTheFlyCorrBlock"]


class OnTheFlyCorrBlock:
    """Correlation block that never materializes the all-pairs volume.

    Args:
        num_levels, radius: as in ``CorrBlock``.
        query_chunk: queries processed per blockwise step (bounds the
            transient ``(B, chunk, S^2, C)`` gather buffer).
    """

    def __init__(self, num_levels: int = 4, radius: int = 4, query_chunk: int = 1024):
        self.num_levels = num_levels
        self.radius = radius
        self.query_chunk = query_chunk
        self.out_channels = num_levels * (2 * radius + 1) ** 2

    def min_fmap_size(self) -> int:
        return 2 * 2 ** (self.num_levels - 1)

    def build_pyramid(self, fmap1: jax.Array, fmap2: jax.Array) -> Dict:
        """O(Q*C) 'pyramid': fmap1 + successively pooled fmap2 levels."""
        if fmap1.shape != fmap2.shape:
            raise ValueError("feature maps must have identical shapes")
        if min(fmap1.shape[1:3]) < self.min_fmap_size():
            raise ValueError(
                f"feature maps {fmap1.shape[1:3]} too small for "
                f"{self.num_levels} levels; need >= {self.min_fmap_size()}"
            )
        levels = [fmap2]
        for _ in range(self.num_levels - 1):
            levels.append(nn.avg_pool(levels[-1], (2, 2), strides=(2, 2)))
        return {"fmap1": fmap1, "fmap2_levels": levels}

    def resident_pyramid(self, pyramid: Dict):
        """Nothing to hold by slot: this block's 'pyramid' is feature
        maps, not one row a query. Held as built it ran the 1080p cell
        once at 46% of fused's rate (PR 30): no cell."""
        raise ValueError(
            "corr_impl='onthefly' cannot live in the resident slot "
            "pool (its pyramid is feature maps, not per-query rows); "
            "serve it with pool_capacity=0"
        )

    def index_project(
        self, pyramid: Dict, centroids: jax.Array, kernel, bias, *, dtype=None
    ) -> jax.Array:
        """Lookup + ``convcorr1`` projection (same contract as
        ``CorrBlock.index_project``; unfused here)."""
        from raft_tpu.models.corr import project_taps

        return project_taps(
            self.index_pyramid(pyramid, centroids), kernel, bias, dtype=dtype
        )

    def index_pyramid(self, pyramid: Dict, centroids: jax.Array) -> jax.Array:
        fmap1 = pyramid["fmap1"]
        levels: Sequence[jax.Array] = pyramid["fmap2_levels"]
        b, h, w, c = fmap1.shape
        q = h * w
        s = 2 * self.radius + 1
        scale = 1.0 / math.sqrt(c)
        f1 = fmap1.reshape(b, q, c)
        cent = centroids.reshape(b, q, 2)

        chunk = min(self.query_chunk, q)
        pad = (-q) % chunk
        if pad:
            f1 = jnp.pad(f1, ((0, 0), (0, pad), (0, 0)))
            cent = jnp.pad(cent, ((0, 0), (0, pad), (0, 0)))
        n_chunks = (q + pad) // chunk
        f1 = f1.reshape(b, n_chunks, chunk, c).transpose(1, 0, 2, 3)
        cent = cent.reshape(b, n_chunks, chunk, 2).transpose(1, 0, 2, 3)

        def one_chunk(carry, inputs):
            f1_c, cent_c = inputs  # (B, chunk, C), (B, chunk, 2)
            feats = []
            for level, f2l in enumerate(levels):
                # Recompute this chunk's correlation rows on the MXU
                # (blockwise: never more than (B, chunk, hl*wl) live).
                vol = jnp.einsum(
                    "bqc,byxc->bqyx",
                    f1_c,
                    f2l,
                    preferred_element_type=jnp.float32,
                )
                taps = separable_taps(
                    vol,
                    cent_c[..., 0] / (2.0**level),
                    cent_c[..., 1] / (2.0**level),
                    self.radius,
                )
                feats.append(taps.reshape(taps.shape[0], taps.shape[1], s * s))
            return carry, jnp.concatenate(feats, axis=-1) * scale

        _, out = jax.lax.scan(one_chunk, None, (f1, cent))
        # (n_chunks, B, chunk, L*S2) -> (B, Q, L*S2)
        out = out.transpose(1, 0, 2, 3).reshape(b, q + pad, -1)[:, :q]
        # Stays fp32 like the dense CorrBlock regardless of input dtype —
        # correlation features in low precision cost EPE (SURVEY.md §7.3).
        return out.reshape(b, h, w, self.out_channels)
