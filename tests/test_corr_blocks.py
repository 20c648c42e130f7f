"""The seam between ``models/`` and the correlation blocks.

Three blocks remain (``corr_impl``): ``dense`` (``CorrBlock``: the XLA
form, the oracle), ``fused`` (``FusedLookupCorrBlock``: what the chip
runs) and ``onthefly`` (``OnTheFlyCorrBlock``: no volume). The model
reads none of their pyramid formats: it builds, asks the block how the
pyramid is held across steps (``resident_pyramid``), and hands it back
to the block's own lookups. Held here:

  * every block's ``index_project`` is ``project_taps`` of its
    ``index_pyramid``;
  * the resident form gives bitwise the lookup the built form gives, or
    the block refuses by slot with a typed error;
  * ``RAFT.begin_refinement`` holds whatever the block answers, every
    leaf with ``(B, Q)`` leading;
  * the fused block's resident form (levels padded to whole tiles) is
    bitwise the built one through ``index_project`` at each benchmark
    cell's level geometry, cut to a CPU size, in bf16;
  * what left the tree in PR 32 is refused by the checks that were
    there, in words that name what remains.

CPU, interpret mode for the kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock
from raft_tpu.models.corr import CorrBlock, project_taps
from raft_tpu.models.corr_otf import OnTheFlyCorrBlock

LEVELS, RADIUS = 3, 3
TAPS = LEVELS * (2 * RADIUS + 1) ** 2

BLOCKS = pytest.mark.parametrize("impl", ["dense", "fused", "onthefly"])


def _block(impl, dtype=None, levels=LEVELS, radius=RADIUS):
    if impl == "dense":
        return CorrBlock(levels, radius, dtype=dtype)
    if impl == "fused":
        return FusedLookupCorrBlock(levels, radius, dtype=dtype, interpret=True)
    return OnTheFlyCorrBlock(levels, radius, query_chunk=128)


def _case(rng, b, h8, w8, taps, c=16, c_out=24):
    f1 = jnp.asarray(rng.normal(size=(b, h8, w8, c)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(b, h8, w8, c)), jnp.float32)
    xs, ys = np.meshgrid(np.arange(w8), np.arange(h8))
    cents = np.stack([xs, ys], -1)[None] + rng.uniform(-6, 6, (b, h8, w8, 2))
    kernel = jnp.asarray(rng.normal(size=(1, 1, taps, c_out)) * 0.1, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(c_out,)) * 0.1, jnp.float32)
    return f1, f2, jnp.asarray(cents, jnp.float32), kernel, bias


@BLOCKS
def test_index_project_is_the_projection_of_index_pyramid(rng, impl):
    block = _block(impl)
    f1, f2, cents, kernel, bias = _case(rng, 2, 12, 20, TAPS)
    pyramid = block.build_pyramid(f1, f2)
    taps = block.index_pyramid(pyramid, cents)
    assert block.out_channels == TAPS and taps.shape == (2, 12, 20, TAPS)
    want = project_taps(taps, kernel, bias)
    got = block.index_project(pyramid, cents, kernel, bias)
    assert np.abs(np.asarray(want)).max() > 0.5
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
    )


@BLOCKS
def test_resident_pyramid_looks_up_what_the_built_one_does(rng, impl):
    """Held across steps or as built, the block's lookups read the same
    cells: bitwise, in the bf16 storage the cells run (a bilinear y-row
    has two non-zero weights and a bf16 product is exact in fp32, so
    rows of zeros appended to a level cannot move the sum; in fp32 the
    CPU's fused multiply-add makes it depend on the order, by 2e-7).
    The on-the-fly block has nothing to hold by slot and says so."""
    block = _block(impl, jnp.bfloat16)
    # 12x20: level 0 pads 12 -> 16 rows in the fused block's resident form
    f1, f2, cents, kernel, bias = _case(rng, 2, 12, 20, TAPS)
    pyramid = block.build_pyramid(f1, f2)
    if impl == "onthefly":
        with pytest.raises(ValueError, match="onthefly.*pool_capacity=0"):
            block.resident_pyramid(pyramid)
        return
    held = block.resident_pyramid(pyramid)
    if impl == "fused":
        assert held["levels"][0].shape[1:3] == (16, 128)
        assert pyramid["levels"][0].shape[1:3] == (12, 20)
    for leaf in jax.tree.leaves(held):
        assert leaf.shape[0] == 2 * 12 * 20  # query rows leading
    want = np.asarray(block.index_pyramid(pyramid, cents), np.float32)
    assert np.abs(want).max() > 1.0
    np.testing.assert_array_equal(
        np.asarray(block.index_pyramid(held, cents), np.float32), want
    )
    project = lambda p: np.asarray(
        block.index_project(p, cents, kernel, bias, dtype=jnp.bfloat16),
        np.float32,
    )
    np.testing.assert_array_equal(project(held), project(pyramid))


@BLOCKS
def test_begin_refinement_holds_what_the_block_answers(rng, impl):
    """The model folds whatever ``resident_pyramid`` returns to ``(B, Q,
    ...)`` leaves and reads no format; a block that cannot be held by
    slot refuses through it, and ``iterate_step`` takes the state back."""
    from raft_tpu.models import build_raft, init_variables
    from tests.test_train import tiny_cfg

    cfg = tiny_cfg()
    model = build_raft(
        cfg,
        corr_block=_block(impl, levels=cfg.corr_levels, radius=cfg.corr_radius),
    )
    variables = init_variables(build_raft(cfg))
    b, h, w = 2, 128, 160
    im = jnp.asarray(rng.uniform(-1, 1, (b, h, w, 3)), jnp.float32)
    if impl == "onthefly":
        with pytest.raises(ValueError, match="onthefly.*pool_capacity=0"):
            model.apply(variables, im, im, train=False, method="begin_pair")
        return
    state = model.apply(variables, im, im, train=False, method="begin_pair")
    q = (h // 8) * (w // 8)
    leaves = jax.tree.leaves(state["pyramid"])
    assert len(leaves) >= cfg.corr_levels
    assert all(leaf.shape[:2] == (b, q) for leaf in leaves)
    nxt = model.apply(variables, state, train=False, method="iterate_step")
    assert jax.tree.structure(nxt) == jax.tree.structure(state)
    assert np.isfinite(np.asarray(nxt["coords1"])).all()
    assert np.abs(np.asarray(nxt["coords1"] - state["coords1"])).max() > 0


@pytest.mark.parametrize(
    "radius,h8,w8,built,held",
    [
        # raft_large at 440x1024 ([55,128] -> [56,128], [27,64] -> [32,128]):
        # level 0 pads rows, level 1 rows and lanes, levels 2-3 flat
        (4, 19, 128, [(19, 128), (9, 64)], [(24, 128), (16, 128)]),
        # raft_small at 440x1024: level 0 alone is raw, levels 1-3 flat
        (3, 19, 128, [(19, 128)], [(24, 128)]),
        # raft_large at 1088x1920 ([136,240] -> [136,256]): a level wider
        # than 128 lanes (lane-padded at build), chunked gathers
        (4, 17, 136, [(17, 256), (8, 68)], [(24, 256), (8, 128)]),
        # 656 query rows have no 8-aligned divisor <= 640: masked tail
        (4, 16, 41, [(16, 41)], [(16, 128)]),
    ],
    ids=["large-sintel", "small-sintel", "large-hd1080-wide", "masked-tail"],
)
def test_fused_resident_form_is_the_built_form_bitwise(
    rng, radius, h8, w8, built, held
):
    """What the pool's step program reads (``index_project`` on the
    resident form, bf16, projected in bf16) is what the built pyramid
    gives: the tile padding is zero data past the grid, an out-of-range
    tap."""
    levels = 4
    taps = levels * (2 * radius + 1) ** 2
    block = FusedLookupCorrBlock(
        levels, radius, dtype=jnp.bfloat16, interpret=True
    )
    f1, f2, cents, kernel, bias = _case(rng, 1, h8, w8, taps)
    pyramid = block.build_pyramid(f1, f2)
    resident = block.resident_pyramid(pyramid)
    raw = len(built)
    assert [v.shape[1:3] for v in pyramid["levels"][:raw]] == built
    assert [v.shape[1:3] for v in resident["levels"][:raw]] == held
    assert [r.shape[1:] for r in block.kernel_rows(resident)[:raw]] == held
    assert len(resident["flats"]) == levels - raw

    def run(p):
        return np.asarray(
            block.index_project(p, cents, kernel, bias, dtype=jnp.bfloat16),
            np.float32,
        )

    want = run(pyramid)
    assert np.isfinite(want).all() and want.max() > 0.5
    np.testing.assert_array_equal(run(resident), want)


def _pallas_model():
    from raft_tpu.models import build_raft
    from tests.test_train import tiny_cfg

    return build_raft(tiny_cfg().replace(corr_impl="pallas"))


def _int8_config():
    from raft_tpu.serve import ServeConfig

    return ServeConfig(corr_dtype="int8")


def _edge_preset():
    from raft_tpu.serve import ServeConfig

    return ServeConfig.preset("edge")


@pytest.mark.parametrize(
    "make,names",
    [
        (_pallas_model, "'dense', 'fused', 'onthefly'"),
        (_int8_config, "None or 'bfloat16'"),
        (_edge_preset, r"\['quality', 'throughput'\]"),
    ],
    ids=["corr_impl-pallas", "corr_dtype-int8", "preset-edge"],
)
def test_what_is_refused_says_what_is_left(make, names):
    """The values that left the tree are unknown values like any other:
    a ``ValueError`` that names the ones that remain."""
    with pytest.raises(ValueError, match=names):
        make()
