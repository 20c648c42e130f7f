"""The fused lookup kernel (``kernels/lookup_xtap.py``) vs the XLA oracle
(interpret mode on CPU), in fp32 and in the bf16 storage the cells run."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu.models.corr import CorrBlock


def _fmaps(rng, b=2, h=16, w=24, c=32):
    f1 = jnp.asarray(rng.normal(size=(b, h, w, c)).astype(np.float32))
    f2 = jnp.asarray(rng.normal(size=(b, h, w, c)).astype(np.float32))
    return f1, f2


def _pyramid_and_cents(rng, b=1, h=12, w=20, c=16, levels=3, spread=6.0):
    f1, f2 = _fmaps(rng, b=b, h=h, w=w, c=c)
    pyramid = CorrBlock(num_levels=levels, radius=3).build_pyramid(f1, f2)
    cents = jnp.asarray(
        rng.uniform(-spread, w + spread, (b, h, w, 2)).astype(np.float32)
    )
    return pyramid, cents


DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"]
)

# bf16 storage (what every benchmark cell runs): the kernel and the dense
# block's XLA form round the y-contracted rows to bf16 alike, then differ
# in where the x side rounds — XLA rounds the x-weights and each product
# to bf16 and sums in fp32, the kernel combines in fp32 and rounds the
# tap once. Read over the cases below (CPU, PR 32): taps differ by at
# most 0.0205 on |values| up to 3.9, projected features by at most 0.031
# on values up to 5.6 — one or two bf16 steps (2^-8 relative). The limit
# is about twice that; a tap taken one cell off is wrong by O(1).
BF16_TOL = dict(rtol=1e-2, atol=3e-2)


def _fused_vs_oracle(pyramid, cents, radius, dtype, fp32_tol):
    """``lookup_pyramid_fused`` against the oracle at ``dtype`` storage:
    fp32 against the gather oracle; bf16 against the dense block's own
    lookup (``corr.lookup_pyramid``) on the same bf16 pyramid."""
    from raft_tpu.kernels.lookup_xtap import lookup_pyramid_fused
    from raft_tpu.models.corr import lookup_pyramid, lookup_pyramid_gather

    if dtype == jnp.float32:
        want = lookup_pyramid_gather(pyramid, cents, radius)
        got = lookup_pyramid_fused(pyramid, cents, radius, interpret=True)
        tol = fp32_tol
    else:
        pyramid = [v.astype(dtype) for v in pyramid]
        want = lookup_pyramid(pyramid, cents, radius, weight_dtype=dtype)
        got = lookup_pyramid_fused(
            pyramid, cents, radius, weight_dtype=dtype, interpret=True
        )
        assert got.dtype == dtype
        tol = BF16_TOL
    assert got.shape == want.shape
    assert np.abs(np.asarray(want, np.float32)).max() > 1.0, "degenerate taps"
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


@DTYPES
@pytest.mark.parametrize("radius,levels,w", [(4, 4, 128), (3, 3, 64), (1, 2, 32)])
def test_lookup_fused_matches_oracle(rng, radius, levels, w, dtype):
    """The kernel (batched MXU y-dot + lane-gather x-tap, flat levels by
    4-corner gathers) matches the oracle, in both storage dtypes."""
    pyramid, _ = _pyramid_and_cents(rng, h=16, w=w, levels=levels)
    cents = jnp.asarray(
        rng.uniform(-9.0, w + 9.0, (1, 16, w, 2)).astype(np.float32)
    )
    _fused_vs_oracle(pyramid, cents, radius, dtype, dict(rtol=1e-5, atol=1e-5))


def test_lookup_fused_radius5_all_ydot(rng):
    """radius >= 5 overflows the flat run layout (S*(S+1) > 128 lanes);
    every level must route to the y-dot path instead of crashing."""
    from raft_tpu.kernels.lookup_xtap import _split_levels, lookup_pyramid_fused
    from raft_tpu.models.corr import lookup_pyramid_gather

    radius = 5
    pyramid, _ = _pyramid_and_cents(rng, h=16, w=64, levels=3)
    assert _split_levels(pyramid, 2 * radius + 1) == ([0, 1, 2], [])
    cents = jnp.asarray(
        rng.uniform(-9.0, 73.0, (1, 16, 64, 2)).astype(np.float32)
    )
    want = lookup_pyramid_gather(pyramid, cents, radius)
    got = lookup_pyramid_fused(pyramid, cents, radius, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


NONPOW2 = pytest.mark.parametrize(
    "h,w,levels",
    [(40, 62, 4), (16, 90, 4), (16, 96, 4), (16, 156, 4), (9, 156, 3)],
    ids=["chairs-62", "things-90", "sintel-stage-96", "kitti-156-chunked",
         "masked-tail-q1404"],
)


@DTYPES
@NONPOW2
def test_lookup_fused_nonpow2_matches_oracle(rng, h, w, levels, dtype):
    """Every standard training/eval /8 geometry engages the kernel and
    matches the oracle — non-pow2 widths via the clamped gather (Chairs
    62, Things 90, Sintel-stage 96), >128 widths via the chunked gather
    (KITTI 156), and q with no 8-aligned divisor (9*156=1404) via the
    masked-tail cdiv grid — in both storage dtypes."""
    from raft_tpu.kernels.lookup_xtap import _fusable

    pyramid, _ = _pyramid_and_cents(rng, h=h, w=w, levels=levels)
    assert _fusable(pyramid, 9)
    cents = jnp.asarray(
        rng.uniform(-9.0, w + 9.0, (1, h, w, 2)).astype(np.float32)
    )
    # atol 2e-5: one element in ~5e5 lands at 1.25e-5 from fp32
    # reassociation between the two-corner combine and the oracle
    _fused_vs_oracle(pyramid, cents, 4, dtype, dict(rtol=1e-4, atol=2e-5))


def test_fused_lookup_grad_nonpow2_padded_width(rng):
    """Gradients through the fused block at a >128-wide level (the
    build-time lane pad must backprop through its pad slice) match the
    dense path."""
    from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock

    f1, f2 = _fmaps(rng, b=1, h=8, w=156, c=8)
    cents = jnp.asarray(
        rng.uniform(0, 150, (1, 8, 156, 2)).astype(np.float32)
    )
    weights = jnp.asarray(
        rng.normal(size=(1, 8, 156, 2 * 49)).astype(np.float32)
    )

    def make_loss(blk):
        def loss(f1, f2):
            taps = blk.index_pyramid(blk.build_pyramid(f1, f2), cents)
            return jnp.sum(taps * weights)
        return loss

    dense = CorrBlock(num_levels=2, radius=3)
    fused = FusedLookupCorrBlock(num_levels=2, radius=3, interpret=True)
    assert isinstance(fused.build_pyramid(f1, f2), dict)
    g_dense = jax.grad(make_loss(dense), argnums=(0, 1))(f1, f2)
    g_fused = jax.grad(make_loss(fused), argnums=(0, 1))(f1, f2)
    for gd, gf in zip(g_dense, g_fused):
        assert gf.shape == gd.shape
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-5
        )


def test_lookup_fused_far_out_of_range(rng):
    """Centroids far outside the volume read all-zero taps (torch
    padding_mode='zeros' parity)."""
    from raft_tpu.kernels.lookup_xtap import lookup_pyramid_fused
    from raft_tpu.models.corr import lookup_pyramid_gather

    pyramid, _ = _pyramid_and_cents(rng, h=12, w=32, levels=2)
    cents = jnp.asarray(
        rng.uniform(-200, 250, (1, 12, 32, 2)).astype(np.float32)
    )
    want = lookup_pyramid_gather(pyramid, cents, 4)
    got = lookup_pyramid_fused(pyramid, cents, 4, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_fused_corr_block_matches_dense(rng):
    """FusedLookupCorrBlock == CorrBlock through build+index, at a pow2
    and a non-pow2 width (both engage the kernel since the round-5 width
    generalization)."""
    from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock

    for w in (64, 24):  # 24 -> levels 24/12: non-pow2, engages since r5
        f1, f2 = _fmaps(rng, b=1, h=16, w=w, c=16)
        cents = jnp.asarray(
            rng.uniform(-2, w + 2, (1, 16, w, 2)).astype(np.float32)
        )
        dense = CorrBlock(num_levels=2, radius=3)
        fused = FusedLookupCorrBlock(num_levels=2, radius=3, interpret=True)
        want = dense.index_pyramid(dense.build_pyramid(f1, f2), cents)
        got = fused.index_pyramid(fused.build_pyramid(f1, f2), cents)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
        )


@DTYPES
@NONPOW2
def test_lookup_project_fused_matches_oracle(rng, h, w, levels, dtype):
    """Fused lookup+convcorr1 kernel == project_taps(lookup_pyramid(...)):
    the call every pool step makes, at the geometries and storage dtypes
    of the lookup's own oracle cases (bf16: storage, rows and the
    projection's matmul in bf16, as ``index_project(dtype=bfloat16)``
    on a bf16 block runs it)."""
    from raft_tpu.kernels.lookup_xtap import lookup_project_fused
    from raft_tpu.models.corr import lookup_pyramid, project_taps

    radius = 4
    pyramid, _ = _pyramid_and_cents(rng, h=h, w=w, levels=levels)
    cents = jnp.asarray(
        rng.uniform(-9.0, w + 9.0, (1, h, w, 2)).astype(np.float32)
    )
    c_in = levels * (2 * radius + 1) ** 2
    kernel = jnp.asarray(rng.normal(size=(1, 1, c_in, 32)).astype(np.float32)) * 0.1
    bias = jnp.asarray(rng.normal(size=(32,)).astype(np.float32))

    if dtype == jnp.float32:
        wd, tol = None, dict(rtol=1e-4, atol=1e-4)
    else:
        # the taps' own rounding (BF16_TOL) averaged by the matmul, and
        # the output rounded to bf16 (2^-9 relative)
        pyramid = [v.astype(dtype) for v in pyramid]
        wd, tol = dtype, BF16_TOL
    want = project_taps(
        lookup_pyramid(pyramid, cents, radius, weight_dtype=wd),
        kernel, bias, dtype=wd,
    )
    got = lookup_project_fused(
        pyramid, cents, kernel, bias, radius,
        weight_dtype=wd, proj_dtype=wd, interpret=True,
    )
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.asarray(want, np.float32).max() > 1.0, "degenerate features"
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), **tol
    )


def test_fused_block_index_project_and_fallback(rng):
    """FusedLookupCorrBlock.index_project == base CorrBlock.index_project,
    on the kernel path at a pow2 and a non-pow2 width."""
    from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock

    for w in (64, 24):
        f1, f2 = _fmaps(rng, b=1, h=16, w=w, c=16)
        cents = jnp.asarray(
            rng.uniform(-2, w + 2, (1, 16, w, 2)).astype(np.float32)
        )
        dense = CorrBlock(num_levels=2, radius=3)
        fused = FusedLookupCorrBlock(num_levels=2, radius=3, interpret=True)
        c_in = 2 * 7 * 7
        kernel = jnp.asarray(rng.normal(size=(1, 1, c_in, 24)).astype(np.float32)) * 0.1
        bias = jnp.asarray(rng.normal(size=(24,)).astype(np.float32))
        want = dense.index_project(
            dense.build_pyramid(f1, f2), cents, kernel, bias
        )
        got = fused.index_project(
            fused.build_pyramid(f1, f2), cents, kernel, bias
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
        )

    # a genuinely non-fusable shape (y-dot level 0 narrower than S+1)
    # still routes index_project through the exact XLA fallback
    f1, f2 = _fmaps(rng, b=1, h=32, w=6, c=16)
    cents = jnp.asarray(rng.uniform(-2, 8, (1, 32, 6, 2)).astype(np.float32))
    dense = CorrBlock(num_levels=2, radius=3)
    fused = FusedLookupCorrBlock(num_levels=2, radius=3, interpret=True)
    pyr = fused.build_pyramid(f1, f2)
    assert not isinstance(pyr, dict), "w=6 < S+1 must not fuse"
    kernel = jnp.asarray(rng.normal(size=(1, 1, 2 * 49, 24)).astype(np.float32)) * 0.1
    bias = jnp.asarray(rng.normal(size=(24,)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(fused.index_project(pyr, cents, kernel, bias)),
        np.asarray(
            dense.index_project(dense.build_pyramid(f1, f2), cents, kernel, bias)
        ),
        rtol=1e-5, atol=1e-5,
    )


def test_fused_lookup_grad_matches_dense(rng):
    """custom_vjp: gradients through the fused kernel == gradients through
    the XLA path (training with corr_impl='fused' is exact)."""
    from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock

    f1, f2 = _fmaps(rng, b=1, h=16, w=64, c=16)
    cents = jnp.asarray(rng.uniform(0, 60, (1, 16, 64, 2)).astype(np.float32))
    weights = jnp.asarray(
        rng.normal(size=(1, 16, 64, 2 * 49)).astype(np.float32)
    )

    def make_loss(blk):
        def loss(f1, f2):
            taps = blk.index_pyramid(blk.build_pyramid(f1, f2), cents)
            return jnp.sum(taps * weights)
        return loss

    dense = CorrBlock(num_levels=2, radius=3)
    fused = FusedLookupCorrBlock(num_levels=2, radius=3, interpret=True)
    g_dense = jax.grad(make_loss(dense), argnums=(0, 1))(f1, f2)
    g_fused = jax.grad(make_loss(fused), argnums=(0, 1))(f1, f2)
    for gd, gf in zip(g_dense, g_fused):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-5
        )


def test_fused_project_grad(rng):
    """Gradients through index_project's custom_vjp match the base path
    (incl. d/dkernel, d/dbias)."""
    from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock

    f1, f2 = _fmaps(rng, b=1, h=16, w=64, c=16)
    cents = jnp.asarray(rng.uniform(0, 60, (1, 16, 64, 2)).astype(np.float32))
    c_in = 2 * 49
    kernel = jnp.asarray(rng.normal(size=(1, 1, c_in, 16)).astype(np.float32)) * 0.1
    bias = jnp.asarray(rng.normal(size=(16,)).astype(np.float32)) * 0.1

    def make_loss(blk):
        def loss(f1, k, b):
            out = blk.index_project(blk.build_pyramid(f1, f2), cents, k, b)
            return jnp.sum(out * out)
        return loss

    dense = CorrBlock(num_levels=2, radius=3)
    fused = FusedLookupCorrBlock(num_levels=2, radius=3, interpret=True)
    g_dense = jax.grad(make_loss(dense), argnums=(0, 1, 2))(f1, kernel, bias)
    g_fused = jax.grad(make_loss(fused), argnums=(0, 1, 2))(f1, kernel, bias)
    for gd, gf in zip(g_dense, g_fused):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


def test_fused_model_nonpow2_width_engages(rng):
    """A full fused-impl model at a KITTI-like width (fmap width not a
    power of two) ENGAGES the kernel since the round-5 width
    generalization — and still matches dense."""
    from raft_tpu.models import build_raft, init_variables
    from tests.test_train import tiny_cfg

    cfg = tiny_cfg()
    m_dense = build_raft(cfg)
    m_fused = build_raft(cfg.replace(corr_impl="fused"))
    variables = init_variables(m_dense)
    # width 312 -> fmap 39 wide: levels 39/19/9/4, non-pow2 — engages now
    im = lambda s: jnp.asarray(
        np.random.default_rng(s).uniform(-1, 1, (1, 136, 312, 3)).astype(np.float32)
    )
    fmaps = jnp.concatenate([im(0), im(1)], axis=0)
    f = m_fused.feature_encoder.apply(
        {"params": variables["params"]["feature_encoder"]}, fmaps
    )
    f1, f2 = jnp.split(f, 2, axis=0)
    assert isinstance(m_fused.corr_block.build_pyramid(f1, f2), dict), (
        "non-pow2 width must engage the fused path since round 5"
    )
    fd = m_dense.apply(variables, im(0), im(1), train=False,
                       num_flow_updates=2, emit_all=False)
    ff = m_fused.apply(variables, im(0), im(1), train=False,
                       num_flow_updates=2, emit_all=False)
    # kernel-vs-XLA fp32 reassociation (~1e-5 per tap) amplifies through
    # two refinement iterations on untrained random weights: 0.3% of
    # elements land near 1.2e-3 on |flow| ~ 70
    np.testing.assert_allclose(np.asarray(ff), np.asarray(fd), rtol=1e-4, atol=5e-3)


def test_sintel_geometry_engages_fused_paths(rng):
    """The flagship protocol's /8-scale geometry must take the packed
    fused path — not the silent XLA fallback — with the swept level
    split (levels 0-1 on the y-dot, levels 2-3 flat for raft_large's
    S=9; levels 1-3 flat for raft_small's S=7). The split depends on
    BOTH the tap width and each level's packed row count, so the exact
    Sintel 440x1024 level dims (55x128 down to 6x16) are asserted via
    shape shells; the packed form, in the bf16 storage the cells run,
    is built on a real (16, 128) pyramid."""
    from raft_tpu.kernels.lookup_xtap import (
        FusedLookupCorrBlock,
        _fusable,
        _split_levels,
    )

    sintel_levels = [
        jnp.zeros((1, hl, wl, 1), jnp.float32)
        for hl, wl in ((55, 128), (27, 64), (13, 32), (6, 16))
    ]
    assert _fusable(sintel_levels, 9)
    assert _split_levels(sintel_levels, 9) == ([0, 1], [2, 3])  # raft_large
    assert _split_levels(sintel_levels, 7) == ([0], [1, 2, 3])  # raft_small

    f1, f2 = _fmaps(rng, b=1, h=16, w=128, c=8)
    for radius in (4, 3):
        blk = FusedLookupCorrBlock(num_levels=4, radius=radius, interpret=True)
        pyr = blk.build_pyramid(f1, f2)
        assert isinstance(pyr, dict), "width-128 pyramids must be fusable"

        blk16 = FusedLookupCorrBlock(
            num_levels=4, radius=radius, dtype=jnp.bfloat16, interpret=True
        )
        pyr16 = blk16.build_pyramid(f1, f2)
        assert set(pyr16) == {"levels", "flats"}
        assert all(v.dtype == jnp.bfloat16 for v in pyr16["levels"])
        assert all(v.dtype == jnp.bfloat16 for v in pyr16["flats"])
