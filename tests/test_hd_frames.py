"""Frames past the Sintel size through the fused lookup and the slot pool
(PR 30: raft_large on whole 1080p frames, bucket 1088x1920).

What 1080p frames change is shapes, so the rules under test read shapes:

  * ``_plan_tile``: the kernel's query tile comes from the bytes of one
    tile's level blocks against the VMEM limit, and the coordinate
    operand is blocked by tile where whole it would not fit beside them
    — 640 rows and whole coordinates at every Sintel shape, as before;
  * the blocked-coordinate kernel computes what the whole-coordinate
    kernel does, bit for bit;
  * ``ServeEngine``'s pool on a bucket whose level 0 is wider than 128
    lanes (chunked gathers, lane padding) and whose rows are no multiple
    of 8 (row padding in the resident form) agrees with the benchmark's
    plain reference;
  * ``stats()`` says what a slot holds and how the kernel reads it.

CPU, interpret mode, seeded random weights; the real widths are compiled
for a described v5e in ``tests/test_chip_compile.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.kernels import lookup_xtap as lx
from raft_tpu.kernels.lookup_xtap import FusedLookupCorrBlock

# raft_large's correlation geometry: 4 levels, radius 4
LEVELS, RADIUS = 4, 4
S = 2 * RADIUS + 1


def _resident_spec(block, slots, h8, w8):
    """The packed pyramid as the slot pool holds ``slots`` pairs at the
    (h8, w8) grid, rows folded (shape specs)."""
    fmap = jax.ShapeDtypeStruct((slots, h8, w8, 32), jnp.float32)
    return jax.eval_shape(
        lambda a, b: block.resident_pyramid(block.build_pyramid(a, b)),
        fmap, fmap,
    )


@pytest.mark.parametrize(
    "h8,w8,slots,tile,blocked",
    [
        (55, 128, 1, 640, False),    # Sintel 440x1024, one pair
        (55, 128, 16, 640, False),   # the Sintel cells' pool: as before PR 30
        (55, 128, 32, 640, True),    # 110 MiB of coordinates: blocked
        (47, 156, 1, 616, False),    # KITTI 376x1248: cdiv grid, as before
        (46, 96, 8, 552, False),     # the training crop, batch 8: as before
        (136, 240, 1, 408, True),    # one 1088x1920 pair
        (136, 240, 2, 408, True),    # the 1080p cell's pool
        (136, 240, 4, 408, True),
    ],
)
def test_tile_follows_the_blocks_bytes(h8, w8, slots, tile, blocked):
    """The plan for the pool's resident pyramid — ``lookup_plan``, which
    is ``_plan_tile`` on the operands ``_FusedPrep`` hands the call, the
    same two functions ``_invoke_xtap`` runs and ``stats()`` reports —
    is the expected tile, and what the tile needs fits the VMEM limit."""
    block = FusedLookupCorrBlock(LEVELS, RADIUS, dtype=jnp.bfloat16)
    pyramid = _resident_spec(block, slots, h8, w8)
    rows = block.kernel_rows(pyramid)
    q = slots * h8 * w8
    tq, is_blocked = block.lookup_plan(pyramid)
    assert (tq, is_blocked) == (tile, blocked)
    assert tq % 8 == 0 and tq <= lx.DEFAULT_QUERY_TILE
    if q % 8 == 0 and h8 * w8 % tq == 0:
        assert q % tq == 0  # a divisor where one exists: no masked tail
    # double-buffered level blocks, the body's scratch, and the
    # coordinates where whole, fit
    need = tq * (2 * lx._row_bytes(rows) + lx._SCRATCH_LANE_BYTES * rows[0].shape[2])
    if not is_blocked:
        need += -(-q // tq) * tq * lx.MAX_LANES * 4
    assert need <= lx._VMEM_LIMIT


def test_the_1080p_slot_holds_three_raw_levels():
    """At 1088x1920 level 2 (34x60: 16 packed rows, over the 4 a flat
    level may have at radius 4) stays a raw y-dot level: three levels in
    whole (8, 128) tiles, one flat."""
    block = FusedLookupCorrBlock(LEVELS, RADIUS, dtype=jnp.bfloat16)
    pyramid = _resident_spec(block, 2, 136, 240)
    assert [v.shape[1:3] for v in pyramid["levels"]] == [
        (136, 256), (72, 128), (40, 128), (17, 30)
    ]
    assert [r.shape[1:] for r in block.kernel_rows(pyramid)] == [
        (136, 256), (72, 128), (40, 128), (512,)
    ]


def _lookup_case(rng, b, h8, w8, c=16):
    f1 = jnp.asarray(rng.normal(size=(b, h8, w8, c)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(b, h8, w8, c)), jnp.float32)
    xs, ys = np.meshgrid(np.arange(w8), np.arange(h8))
    cents = np.stack([xs, ys], -1)[None] + rng.uniform(-6, 6, (b, h8, w8, 2))
    kernel = jnp.asarray(rng.normal(size=(1, 1, LEVELS * S * S, 24)) * 0.1,
                         jnp.float32)
    bias = jnp.asarray(rng.normal(size=(24,)) * 0.1, jnp.float32)
    return f1, f2, jnp.asarray(cents, jnp.float32), kernel, bias


@pytest.mark.parametrize(
    "h8,w8",
    [(17, 136),   # a level wider than 128 lanes: the chunked gathers
     (16, 41)],   # 656 rows have no 8-aligned divisor <= 640: masked tail
    ids=["wide-17x136", "tail-16x41"],
)
def test_blocked_coordinates_equal_whole_bitwise(monkeypatch, rng, h8, w8):
    """The same operands through the kernel with its coordinate operand
    whole in VMEM (what these small shapes plan) and blocked by tile
    (forced: the plan's second answer is replaced, nothing else)."""
    f1, f2, cents, kernel, bias = _lookup_case(rng, 1, h8, w8)
    block = FusedLookupCorrBlock(
        LEVELS, RADIUS, dtype=jnp.bfloat16, interpret=True
    )
    pyramid = block.resident_pyramid(block.build_pyramid(f1, f2))
    assert block.lookup_plan(pyramid)[1] is False

    def run():
        return np.asarray(
            block.index_project(pyramid, cents, kernel, bias, dtype=jnp.bfloat16)
        )

    whole = run()
    real_plan = lx._plan_tile

    def blocked_plan(q, query_tile, operands):
        tq, _ = real_plan(q, query_tile, operands)
        return tq, True

    monkeypatch.setattr(lx, "_plan_tile", blocked_plan)
    lx._partitioned_xtap.cache_clear()
    jax.clear_caches()
    blocked = run()
    lx._partitioned_xtap.cache_clear()
    jax.clear_caches()
    assert np.isfinite(whole).all() and np.abs(whole).max() > 0
    np.testing.assert_array_equal(whole, blocked)


# -- the pool on a wide, odd bucket against the plain reference ----------------

BUCKET = (136, 1088)   # a 17 x 136 grid: level 0 pads 136 -> 256 lanes and
IMAGE_HW = (130, 1085)  # 17 -> 24 rows, level 1 (8 x 68) 68 -> 128 lanes
ITERS = 4


def _small_arch():
    """raft_large's structure (residual encoders, instance + batch norm,
    two separable ConvGRUs, convex upsampling, 4 levels, radius 4) at
    widths the CPU runs in seconds; the pyramid's shapes depend on the
    bucket alone. In the form of a configuration file's ``arch``."""
    from raft_tpu.models import zoo

    cfg = zoo.CONFIGS["raft_large"].replace(
        feature_encoder_widths=(16, 16, 24, 32, 32),
        context_encoder_widths=(16, 16, 24, 32, 48),
        motion_corr_widths=(32, 24),
        motion_flow_widths=(16, 8),
        motion_out_channels=24,
        gru_hidden=24,
        flow_head_hidden=32,
        mask_predictor_hidden=32,
    )
    keys = (
        "feature_encoder_widths", "feature_encoder_block",
        "feature_encoder_norm", "context_encoder_widths",
        "context_encoder_block", "context_encoder_norm", "corr_levels",
        "corr_radius", "motion_corr_widths", "motion_flow_widths",
        "motion_out_channels", "gru_hidden", "gru_kernels", "gru_pads",
        "flow_head_hidden", "use_mask_predictor", "mask_predictor_hidden",
    )
    have = dataclasses.asdict(cfg)
    return cfg, {k: have[k] for k in keys}


@pytest.fixture(scope="module")
def hd_engine():
    """A started 2-slot pool engine at the throughput preset on BUCKET,
    with the benchmark's seeded weights and the reference's copy."""
    from benchmarks import weights
    from benchmarks.reference import raft as ref
    from raft_tpu.models import build_raft
    from raft_tpu.serve import ServeConfig, ServeEngine

    cfg, arch = _small_arch()
    serve = ServeConfig.preset(
        "throughput", buckets=(BUCKET,), ladder=(ITERS, 2), max_batch=1,
        pool_capacity=2, queue_capacity=16, default_deadline_ms=600000.0,
        high_watermark=1.0, warmup=False, stream_cache_size=0,
    )
    model = build_raft(cfg.replace(**serve.model_overrides()))
    variables = weights.make_variables(ref.param_shapes(arch), 2147483777, 0.01)
    with ServeEngine(model, variables, serve) as eng:
        yield eng, arch, jax.device_get(variables)


def test_pool_on_a_wide_odd_bucket_matches_the_reference(hd_engine):
    """Three clients on the 2-slot pool: every pair goes through
    ``pool_begin_pair`` (lane- and row-padded resident levels), ``ITERS``
    ``pool_step``s (chunked gathers on level 0) and ``pool_final``, and is
    held against ``benchmarks.reference.raft.forward`` (fp32, HIGHEST).

    Tolerance: the engine computes convs and the volume in bf16, the
    reference in fp32; over three seeds x three pairs that reads
    0.020-0.037 px of mean endpoint error on fields of 0.6-0.9 px, and
    the same reference computed in fp8 — the nearest precision below —
    reads 0.17-0.26 px (measured on the CPU, PR 30). 0.08 px lies between
    with 2x of room either side; a padded column or row read as data, or
    a tap dropped at a chunk boundary, moves whole rows of the field by
    more than that."""
    from benchmarks import inputs
    from benchmarks.reference import compare as cmp

    eng, arch, host_vars = hd_engine
    pairs = inputs.serve_pairs(2147483777, 3, IMAGE_HW)
    reqs = eng.submit_many([{"image1": a, "image2": b} for a, b in pairs])
    for r in reqs:
        assert r.wait(600.0) and r.error is None, r.error
    for (a, b), r in zip(pairs, reqs):
        assert r.result.num_flow_updates == ITERS and not r.result.degraded
        assert r.result.flow.shape == IMAGE_HW + (2,)
        want = cmp.reference_flow(arch, host_vars, (a, b), bucket=BUCKET,
                                  iters=ITERS)
        stats = cmp.flow_stats(r.result.flow, want)
        assert stats["finite"] == 1.0
        assert np.abs(want).mean() > 0.1, "degenerate field"
        assert stats["flow_epe_mean_px"] < 0.08, stats


def test_stats_report_the_slot_and_the_fetch(hd_engine):
    """``stats()``: per live bucket the bytes a slot and the state hold,
    the kernel's tile and whether its coordinates are blocked; and the
    host bytes retirements fetched, beside ``completed``."""
    eng, _, _ = hd_engine
    s = eng.stats()
    layout = s["pool"]["buckets"][f"{BUCKET[0]}x{BUCKET[1]}"]
    q = (BUCKET[0] // 8) * (BUCKET[1] // 8)
    # the resident levels: [24, 256] and [8, 128] raw, two flats
    assert layout["slot_bytes"] > q * (24 * 256 + 8 * 128) * 2
    assert layout["state_bytes"] == pytest.approx(
        2 * layout["slot_bytes"], abs=2
    )
    assert layout["query_tile"] % 8 == 0 and layout["coords_blocked"] is False
    flow_bytes = BUCKET[0] * BUCKET[1] * 2 * 4
    assert s["completed"] >= 3
    assert s["fetched_bytes"] >= s["completed"] * flow_bytes
