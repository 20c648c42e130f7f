"""On-the-fly correlation vs the dense oracle (must match to float noise)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.models.corr import CorrBlock
from raft_tpu.models.corr_otf import OnTheFlyCorrBlock
from raft_tpu.models import RAFT_SMALL, build_raft, init_variables


def _fmaps(rng, b=2, h=20, w=24, c=32):
    f1 = jnp.asarray(rng.normal(size=(b, h, w, c)).astype(np.float32))
    f2 = jnp.asarray(rng.normal(size=(b, h, w, c)).astype(np.float32))
    return f1, f2


@pytest.mark.parametrize("radius", [3, 4])
@pytest.mark.parametrize("chunk", [64, 1024])
def test_matches_dense_oracle(rng, radius, chunk):
    dense = CorrBlock(num_levels=3, radius=radius)
    otf = OnTheFlyCorrBlock(num_levels=3, radius=radius, query_chunk=chunk)
    f1, f2 = _fmaps(rng)

    centroids = jnp.asarray(
        rng.uniform(-2, 26, (2, 20, 24, 2)).astype(np.float32)
    )  # includes out-of-range taps -> zero-padding parity

    want = dense.index_pyramid(dense.build_pyramid(f1, f2), centroids)
    got = otf.index_pyramid(otf.build_pyramid(f1, f2), centroids)
    assert got.shape == want.shape == (2, 20, 24, otf.out_channels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_odd_sizes_match(rng):
    """Odd spatial dims: successive pooling must drop identical tail rows."""
    dense = CorrBlock(num_levels=4, radius=2)
    otf = OnTheFlyCorrBlock(num_levels=4, radius=2, query_chunk=128)
    f1, f2 = _fmaps(rng, b=1, h=19, w=21, c=16)
    centroids = jnp.asarray(rng.uniform(0, 19, (1, 19, 21, 2)).astype(np.float32))
    want = dense.index_pyramid(dense.build_pyramid(f1, f2), centroids)
    got = otf.index_pyramid(otf.build_pyramid(f1, f2), centroids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_full_model_with_onthefly_matches_dense(rng):
    cfg = RAFT_SMALL.replace(
        feature_encoder_widths=(8, 8, 12, 16, 24),
        context_encoder_widths=(8, 8, 12, 16, 40),
        motion_corr_widths=(16,),
        motion_flow_widths=(16, 8),
        motion_out_channels=20,
        gru_hidden=24,
        flow_head_hidden=16,
    )
    dense_model = build_raft(cfg)
    otf_model = build_raft(cfg.replace(corr_impl="onthefly"))
    variables = init_variables(dense_model)

    im1 = jnp.asarray(rng.uniform(-1, 1, (1, 128, 160, 3)).astype(np.float32))
    im2 = jnp.asarray(rng.uniform(-1, 1, (1, 128, 160, 3)).astype(np.float32))

    want = dense_model.apply(variables, im1, im2, train=False, num_flow_updates=3)
    got = otf_model.apply(variables, im1, im2, train=False, num_flow_updates=3)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-3, atol=5e-3
    )


def test_gradients_flow(rng):
    """The blockwise lookup must be differentiable end to end."""
    otf = OnTheFlyCorrBlock(num_levels=2, radius=2, query_chunk=64)
    f1, f2 = _fmaps(rng, b=1, h=8, w=8, c=8)
    centroids = jnp.asarray(rng.uniform(0, 8, (1, 8, 8, 2)).astype(np.float32))

    def loss(f1, f2, cent):
        feats = otf.index_pyramid(otf.build_pyramid(f1, f2), cent)
        return jnp.sum(feats**2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(f1, f2, centroids)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).max()) > 0


def test_matmul_lookup_matches_gather_oracle(rng):
    """The separable-matmul lookup == the gather formulation exactly."""
    from raft_tpu.models.corr import (
        CorrBlock,
        lookup_pyramid,
        lookup_pyramid_gather,
    )

    dense = CorrBlock(num_levels=3, radius=4)
    f1, f2 = _fmaps(rng, b=2, h=17, w=23, c=16)
    pyr = dense.build_pyramid(f1, f2)
    cent = jnp.asarray(rng.uniform(-3, 26, (2, 17, 23, 2)).astype(np.float32))
    got = lookup_pyramid(pyr, cent, 4)
    want = lookup_pyramid_gather(pyr, cent, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("radius", [3, 4])
def test_window_lookup_matches_gather_oracle(rng, radius):
    """The row-window variant == the gather oracle, including far
    out-of-range centroids that exercise the clamp + zero-pad margin."""
    from raft_tpu.models.corr import (
        CorrBlock,
        lookup_pyramid_gather,
        lookup_pyramid_window,
    )

    dense = CorrBlock(num_levels=3, radius=radius)
    f1, f2 = _fmaps(rng, b=2, h=17, w=23, c=16)
    pyr = dense.build_pyramid(f1, f2)
    # includes centroids far outside the map on both sides
    cent = jnp.asarray(rng.uniform(-40, 60, (2, 17, 23, 2)).astype(np.float32))
    cent = cent.at[0, 0, 0].set(jnp.array([0.0, 0.0]))
    cent = cent.at[0, 0, 1].set(jnp.array([22.0, 16.0]))
    got = lookup_pyramid_window(pyr, cent, radius)
    want = lookup_pyramid_gather(pyr, cent, radius)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_onthefly_refuses_the_slot_pool(rng):
    """The slot pool's entry point on the on-the-fly block: its 'pyramid'
    is feature maps, not one row a query, so there is nothing to hold by
    slot. ``begin_pair`` says so with a typed error (the block's own
    ``resident_pyramid``) and not an ``AttributeError`` from inside the
    fold; the scan serves
    it (``pool_capacity=0``). Held as built it ran the 1080p cell once at
    46% of the fused block's rate (PERF.md, PR 30) and got no cell."""
    cfg = RAFT_SMALL.replace(
        feature_encoder_widths=(8, 8, 12, 16, 24),
        context_encoder_widths=(8, 8, 12, 16, 40),
        motion_corr_widths=(16,),
        motion_flow_widths=(16, 8),
        motion_out_channels=20,
        gru_hidden=24,
        flow_head_hidden=16,
        corr_impl="onthefly",
    )
    model = build_raft(cfg)
    variables = init_variables(model)
    im = jnp.asarray(rng.uniform(-1, 1, (1, 128, 160, 3)).astype(np.float32))
    flow = model.apply(variables, im, im, train=False, num_flow_updates=1,
                       emit_all=False)
    assert flow.shape == (1, 128, 160, 2)
    with pytest.raises(ValueError, match="onthefly.*pool_capacity=0"):
        model.apply(variables, im, im, train=False, method="begin_pair")
