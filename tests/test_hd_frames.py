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
  * ``stats()`` says what a slot holds and how the kernel reads it;
  * (PR 34) a raw-volume level is read by a window of rows a query tile
    where that is clearly less than the level: the windowed kernel is
    the whole-level kernel bit for bit, at every border and where a
    tile's taps spread over several windows, and the two row counters
    say what was read.

CPU, interpret mode, seeded random weights; the real widths are compiled
for a described v5e in the ``tests/test_chip_compile*.py`` files.
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
    "h8,w8,slots,tile,blocked,heights",
    [
        # Sintel 440x1024, one pair; the Sintel cells' pool; 110 MiB of
        # coordinates at 32 slots: blocked. Whole levels: a window of 32
        # of level 0's 56 rows is over half of it, and measured no gain
        (55, 128, 1, 640, False, (56, 32)),
        (55, 128, 16, 640, False, (56, 32)),
        (55, 128, 32, 640, True, (56, 32)),
        # KITTI 376x1248: cdiv grid with a masked tail, whole levels
        (47, 156, 1, 616, False, (48, 24)),
        # the training crop in the pool's resident form, batch 8
        (46, 96, 8, 552, False, (48, 24)),
        # 1088x1920: levels 0 and 1 by window, 33 KB of blocks a row
        # where whole levels took 97 KB and a 408-row tile
        (136, 240, 1, 640, False, (32, 24, 40)),
        (136, 240, 2, 640, True, (32, 24, 40)),   # the 1080p cell's pool
        (136, 240, 4, 640, True, (32, 24, 40)),
    ],
)
def test_tile_follows_the_blocks_bytes(h8, w8, slots, tile, blocked, heights):
    """The plan for the pool's resident pyramid — ``lookup_plan``, which
    is ``_plan_tile`` on the operands ``_FusedPrep`` hands the call, the
    same two functions ``_invoke_xtap`` runs and ``stats()`` reports —
    is the expected tile and windows, and what the tile needs fits the
    VMEM limit with the window blocks counted."""
    block = FusedLookupCorrBlock(LEVELS, RADIUS, dtype=jnp.bfloat16)
    pyramid = _resident_spec(block, slots, h8, w8)
    rows = block.kernel_rows(pyramid)
    q = slots * h8 * w8
    plan = block.lookup_plan(pyramid, w8)
    tq = plan.tile
    assert plan[:3] == (tile, blocked, heights)
    assert plan.rows == tuple(r.shape[1] for r in rows[:len(heights)])
    assert all(h % 8 == 0 or h == r for h, r in zip(heights, plan.rows))
    assert tq % 8 == 0 and tq <= lx.DEFAULT_QUERY_TILE
    if q % 8 == 0 and h8 * w8 % tq == 0:
        assert q % tq == 0  # a divisor where one exists: no masked tail
    # double-buffered blocks (a windowed level's once more), the body's
    # scratch, and the coordinates where whole, fit
    need = tq * (
        2 * lx._row_bytes(rows, heights)
        + sum(h * r.shape[2] * 2 for h, r in zip(heights, rows) if h < r.shape[1])
        + lx._SCRATCH_LANE_BYTES * rows[0].shape[2]
    )
    assert need == tq * lx._tile_row_bytes(rows, heights)
    if not plan.coords_blocked:
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
    assert block.lookup_plan(pyramid, w8).coords_blocked is False

    def run():
        return np.asarray(
            block.index_project(pyramid, cents, kernel, bias, dtype=jnp.bfloat16)
        )

    whole = run()
    real_plan = lx._plan_tile

    def blocked_plan(*args):
        return real_plan(*args)._replace(coords_blocked=True)

    monkeypatch.setattr(lx, "_plan_tile", blocked_plan)
    lx._partitioned_xtap.cache_clear()
    jax.clear_caches()
    blocked = run()
    lx._partitioned_xtap.cache_clear()
    jax.clear_caches()
    assert np.isfinite(whole).all() and np.abs(whole).max() > 0
    np.testing.assert_array_equal(whole, blocked)


# -- levels read by window (PR 34) ---------------------------------------------
# A grid step brings in only the rows of a raw-volume level its query
# tile's taps can reach: H rows from a start the call computes from the
# coordinates, more windows where a tile's taps spread past one. The
# pool's resident form (rows in whole tiles of 8) is what windows.

# The plan windows a level only under half its rows (1088x1920: 32 of
# 136, 24 of 72). These grids are cut to what the CPU interprets in
# seconds, so the cases lift that one threshold (``WINDOWED``: every
# level whose window is shorter than it) and compare with the same call
# under the threshold at 0 (every level whole); nothing else differs.
WINDOWED = 0.99

WINDOW_GRIDS = {
    # name: (h8, w8, radius, (tile, heights) at bf16 storage and WINDOWED)
    # Sintel 440x1024: 32 of level 0's 56 rows, 24 of level 1's 32
    "sintel": (55, 128, 4, (640, (32, 24))),
    # KITTI 376x1248: 7332 rows have no 8-aligned divisor, the grid has a
    # masked tail, and an element-indexed block may not run past the
    # array: every level whole, whatever the threshold
    "kitti-tail": (47, 156, 4, (616, (48, 24))),
    # one row more: a divisor (624), 2 lane chunks, level 0 by window
    "kitti-2chunks": (48, 156, 4, (624, (32, 24))),
    # a cut of the 1088x1920 bucket: >128 lanes, three y-dot levels
    # (level 2's 18x34 is 5 packed rows, over the 4 a flat level may
    # have), levels 0 and 1 by window — by the plan's own threshold too
    "hd-cut": (72, 136, 4, (576, (32, 24, 24))),
    # raft_small's geometry: radius 3, level 0 the only y-dot level
    "sintel-r3": (55, 128, 3, (640, (32,))),
}

WINDOW_COORDS = {
    # the cells' case: small flows, one window a tile
    "near": lambda rng, ys, xs: (
        xs + rng.uniform(-3, 3, xs.shape), ys + rng.uniform(-3, 3, ys.shape)
    ),
    # at and far beyond every border: clamped starts, zero taps
    "borders": lambda rng, ys, xs: (
        rng.choice([-300.0, -4.5, -0.5, 0.0, xs.max() + 0.5, xs.max() + 5, 900.0], xs.shape)
        + rng.uniform(-1, 1, xs.shape),
        rng.choice([-250.0, -5.0, -1.0, 0.0, ys.max(), ys.max() + 4.5, 700.0], ys.shape)
        + rng.uniform(-1, 1, ys.shape),
    ),
    # vertical flow that varies across a tile by more than a window holds
    "spread": lambda rng, ys, xs: (
        xs + rng.uniform(-2, 2, xs.shape),
        ys + 0.45 * ys.max() * np.sin(xs / 5.0) + rng.uniform(-2, 2, ys.shape),
    ),
}


def _window_case(rng, grid, coords, dtype):
    h8, w8, radius, _ = WINDOW_GRIDS[grid]
    f1 = jnp.asarray(rng.normal(size=(1, h8, w8, 8)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(1, h8, w8, 8)), jnp.float32)
    block = FusedLookupCorrBlock(
        4, radius, dtype=None if dtype == jnp.float32 else dtype,
        interpret=True,
    )
    pyramid = block.resident_pyramid(block.build_pyramid(f1, f2))
    xs, ys = np.meshgrid(np.arange(w8, dtype=np.float64), np.arange(h8, dtype=np.float64))
    cx, cy = WINDOW_COORDS[coords](rng, ys, xs)
    cents = jnp.asarray(np.stack([cx, cy], -1)[None], jnp.float32)
    return block, pyramid, cents


def _count_rows_by_hand(plan, cents, levels, radius, lanes):
    """128-lane rows a lookup reads, counted tile by tile from the
    coordinates with nothing of the kernel's: a window covers ``H`` rows
    from the row tile at or before the tile's first tap row (clamped into
    the level), and as many more follow as reach its last."""
    cy = np.asarray(cents, np.float64).reshape(-1, 2)[:, 1]
    q = cy.size
    read = whole = 0
    for level, rows, h, n_lanes in zip(levels, plan.rows, plan.heights, lanes):
        whole += q * rows * n_lanes
        if h == rows:
            read += q * rows * n_lanes
            continue
        for t in range(q // plan.tile):
            y = np.floor(cy[t * plan.tile:(t + 1) * plan.tile] / 2**level)
            lo = int(np.clip(y.min() - radius, 0, rows - 1))
            hi = int(np.clip(y.max() + radius + 1, 0, rows - 1))
            start = min(lo - lo % 8, rows - h)
            windows = 1
            while start + windows * h <= hi:
                windows += 1
            read += windows * h * plan.tile * n_lanes
    return read, whole


WINDOW_CASES = (
    # the storage every cell runs, at every grid and kind of coordinates
    [(g, c, jnp.bfloat16) for g in WINDOW_GRIDS if g != "kitti-tail"
     for c in WINDOW_COORDS]
    # fp32 storage (the quality preset): the oracle at the borders, and
    # several windows a tile
    + [("sintel", "borders", jnp.float32), ("sintel", "spread", jnp.float32)]
    + [("kitti-tail", "near", jnp.bfloat16), ("kitti-tail", "spread", jnp.float32)]
)


@pytest.mark.parametrize(
    "grid,coords,dtype", WINDOW_CASES,
    ids=[f"{g}-{c}-{jnp.dtype(d).name}" for g, c, d in WINDOW_CASES],
)
def test_windowed_lookup_equals_whole_levels(monkeypatch, rng, grid, coords, dtype):
    """The lookup + projection with its levels read by window is, bit
    for bit (to an ulp in fp32 storage where a tile takes several
    windows), the one that reads them whole (the plan's windows replaced
    by whole levels, nothing else), and agrees with the XLA oracle —
    at every border, and where a tile takes several windows; and
    ``lookup_rows`` counts what a count by hand from the coordinates
    gives."""
    from raft_tpu.models.corr import lookup_pyramid, project_taps
    from tests.test_pallas import BF16_TOL

    h8, w8, radius, (tile, heights) = WINDOW_GRIDS[grid]
    block, pyramid, cents = _window_case(rng, grid, coords, dtype)
    monkeypatch.setattr(lx, "_WINDOW_SHARE", WINDOWED)
    plan = block.lookup_plan(pyramid, w8)
    if dtype == jnp.bfloat16:
        assert (plan.tile, plan.heights) == (tile, heights)
    windowed = [h < r for h, r in zip(plan.heights, plan.rows)]
    assert any(windowed) == (grid != "kitti-tail")
    s = 2 * radius + 1
    kernel = jnp.asarray(rng.normal(size=(1, 1, 4 * s * s, 24)) * 0.1, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(24,)) * 0.1, jnp.float32)
    proj = None if dtype == jnp.float32 else dtype

    def run():
        lx._partitioned_xtap.cache_clear()
        jax.clear_caches()
        return np.asarray(
            block.index_project(pyramid, cents, kernel, bias, dtype=proj),
            np.float32,
        )

    got = run()
    read, whole = (int(x) for x in block.lookup_rows(pyramid, cents))
    rows = block.kernel_rows(pyramid)
    levels = lx._split_levels(pyramid["levels"], s)[0]
    assert (read, whole) == _count_rows_by_hand(
        plan, cents, levels, radius,
        [-(-r.shape[2] // 128) for r in rows[:len(plan.rows)]],
    )
    if coords == "near" and any(windowed):
        assert read < whole  # one window a tile
    if coords == "spread" and any(windowed):
        one_each = whole - sum(
            h8 * w8 * (r - h) * -(-x.shape[2] // 128)
            for h, r, x in zip(plan.heights, plan.rows, rows)
        )
        assert read > one_each  # some tile took more than one

    monkeypatch.setattr(lx, "_WINDOW_SHARE", 0.0)  # every level whole
    assert block.lookup_plan(pyramid, w8).heights == plan.rows
    want = run()
    monkeypatch.undo()
    lx._partitioned_xtap.cache_clear()
    jax.clear_caches()
    assert np.isfinite(want).all() and np.abs(want).max() > 0.5
    if dtype == jnp.bfloat16 or coords == "near":
        np.testing.assert_array_equal(got, want)
    else:
        # fp32 storage, a tap's two rows in different windows: the sum
        # of two rounded products where one dot fuses the second into
        # the first (bf16 products are exact in fp32: no difference)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    if coords != "borders" and grid != "kitti-tail":
        return  # the oracle once a grid and dtype, at the hardest taps
    levels_held = [v.astype(dtype) for v in pyramid["levels"]]
    wd = None if dtype == jnp.float32 else dtype
    oracle = project_taps(
        lookup_pyramid(levels_held, cents, radius, weight_dtype=wd),
        kernel, bias, dtype=wd,
    )
    tol = dict(rtol=1e-4, atol=1e-4) if wd is None else BF16_TOL
    np.testing.assert_allclose(got, np.asarray(oracle, np.float32), **tol)


def test_window_plan_stays_in_range_whatever_the_coordinates():
    """Starts are row tiles inside ``[0, rows - H]`` and counts lie in
    ``[1, ceil(rows / H)]`` for coordinates far outside the frame,
    infinite or not a number: the DMA never leaves the level."""
    cy = jnp.asarray([
        [0.0, 1.0, 2.0, 3.0], [-1e9, 5.0, 6.0, 7.0], [1e9, 1e9, 1e9, 1e9],
        [-jnp.inf, 0.0, 50.0, jnp.inf], [jnp.nan, 3.0, 4.0, 5.0],
        [130.0, 131.0, 135.9, 136.5], [-40.0, -30.0, -20.0, -10.0],
    ], jnp.float32)
    windows = [
        lx._Window(0, 0, 136, 32), lx._Window(1, 1, 72, 24),
        lx._Window(2, 2, 40, 24),
    ]
    starts, counts = lx._window_plan(cy, windows, 4)
    starts, counts = np.asarray(starts), np.asarray(counts)
    for (_, level, rows, h), s, n in zip(windows, starts, counts):
        assert (s % 8 == 0).all() and (s >= 0).all() and (s <= rows - h).all()
        assert (n >= 1).all() and (n <= -(-rows // h)).all()
    # a tile inside the frame: its first tap row's tile, one window
    assert starts[0, 0] == 0 and counts[0, 0] == 1
    assert starts[0, 5] == 104 and counts[0, 5] == 1   # 126 -> 120, clamped
    # from row 0 to the last, or not a number: every window of the level
    for tile in (3, 4):
        assert starts[:, tile].tolist() == [0, 0, 0]
        assert counts[:, tile].tolist() == [5, 3, 2]


def test_lookup_rows_ride_the_pacing_token():
    """The step program appends the tick's two row counts to the packed
    converged mask; the host takes them off the same fetch."""
    from raft_tpu.serve.pool import unpack_converged, unpack_lookup_rows

    mask = np.packbits(np.array([1, 0, 1] + [0] * 14, np.uint8))  # 17 slots
    rows = np.array([14_336 * 65_280, 2_000_000_000], "<i4")
    token = np.concatenate([mask, rows.view(np.uint8)])
    assert unpack_lookup_rows(token, 17) == (14_336 * 65_280, 2_000_000_000)
    assert unpack_converged(token, 17).tolist() == [True, False, True] + [False] * 14
    assert unpack_lookup_rows(mask, 17) is None  # a block with no windows


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


def test_one_admission_row_on_the_device_at_a_time(hd_engine, monkeypatch):
    """Two admissions a tick apart (both slots free, two pairs queued:
    clients still joining) do not hold two rows: when ``pool_begin_pair``
    is dispatched — its output row is allocated then, not when it runs —
    the rows of the admission before are in their slots. At 1088x1920 a
    row is 3.3 GB and the second one took the pool to 13.4 GB of the
    chip's 16.9 (PERF.md, PR 34)."""
    eng, _, _ = hd_engine
    pool = eng._pools[BUCKET]
    begun = []
    real = eng._run_pool_begin

    def begin(p1, p2):
        leaves = jax.tree_util.tree_leaves(pool.state["pyramid"])
        begun.append((pool.free_count(), all(x.is_ready() for x in leaves)))
        return real(p1, p2)

    monkeypatch.setattr(eng, "_run_pool_begin", begin)
    from benchmarks import inputs

    pairs = inputs.serve_pairs(2147483779, 4, IMAGE_HW)
    reqs = eng.submit_many([{"image1": a, "image2": b} for a, b in pairs])
    for r in reqs:
        assert r.wait(600.0) and r.error is None, r.error
    assert len(begun) == 4
    assert [free for free, _ in begun[:2]] == [2, 1]  # consecutive loops
    assert all(landed for _, landed in begun)


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
    # level 0 (17 -> 24 rows) and level 1 (8 rows) are far too short for
    # a window to save anything: read whole, and the two row counts the
    # ticks' tokens carried are equal — a whole number of ticks' worth
    # (a 128-lane row a query: 24 x 2 + 8)
    assert layout["level_rows"] == layout["window_rows"] == [24, 8]
    a_tick = 2 * q * (24 * 2 + 8)
    assert layout["lookup_rows_read"] == layout["lookup_rows_whole"] > 0
    assert layout["lookup_rows_whole"] % a_tick == 0
    assert layout["lookup_rows_whole"] // a_tick <= s["pool"]["ticks"]
    flow_bytes = BUCKET[0] * BUCKET[1] * 2 * 4
    assert s["completed"] >= 3
    assert s["fetched_bytes"] >= s["completed"] * flow_bytes
