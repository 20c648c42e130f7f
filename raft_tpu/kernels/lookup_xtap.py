"""Pallas TPU kernel: the multi-scale correlation lookup and its projection.

The lookup (reference semantics ``jax_raft/model.py:448-470``) runs once
per refinement step and pair: (2r+1)^2 bilinear taps around each query's
current match, on every level of the pooled all-pairs volume, then the
motion encoder's ``convcorr1`` 1x1 projection of those taps. In the
serve pool's step program it is one Mosaic call, and the largest part of
the tick: of 8.25 / 5.39 / 12.36 ms a tick (raft_large and raft_small at
440x1024 on 16 slots, raft_large at 1088x1920 on 2; ledger, PR 29-32)
the call took 4.05 / 3.15 / 8.68 ms while it read whole levels. Since
PR 34 a grid step reads a y-window of each large level (below): at
1088x1920 the call alone takes 4.19 ms against 8.87 and the tick 7.65
ms against 12.36 (builder's chip runs, PR 34), which is 2.66% of the
bytes-bound least time for the cells its taps touch
(``lookup_xtap_roofline.offline``) against 1.20%. At 440x1024 the body
— the lane gathers — outlasts the DMA of whole levels (a window of 32
of level 0's 56 rows left the call at 4.32 ms against 4.30 on 16
slots), so those levels are read whole and the call is the one it was.

How the work is split, and why:

  * x-contraction. The bilinear weight matrix has shift structure
    ``wx[q, i, x] = f_q(x - i)`` with ``f_q`` 2-sparse (the two bilinear
    corners), so the whole contraction collapses to

        out[q, i, j] = (1-fx_q) * t[q, j, u0_q + i] + fx_q * t[q, j, u0_q+i+1]

    i.e. a per-query 10-wide window read at dynamic lane offset ``u0``.
    Mosaic supports exactly one scattered primitive that vectorizes over
    queries: the lane-dim gather (``take_along_axis`` axis=-1, index shape
    == source shape). Per (level, j) the kernel issues one gather per
    query tile; the second corner is a static roll of the first.
  * y-contraction of the large levels (``ydot_levels``: level 0, and
    whichever others are too big to pack). The kernel takes the RAW
    ``(q, hl, wl)`` volume as double-buffered blocks and contracts it
    against bilinear y-weights built from iotas, as one batched MXU
    ``dot_general``: the ``(q, S, wl)`` rows never exist in HBM, and the
    volume is read once a step.
  * ... and of that volume only the rows a query tile's taps can reach
    (PR 34). A tile is ``tq`` consecutive queries — 2.7 image rows at
    1088x1920, 5 at 440x1024 — so at level ``l`` its taps lie in rows
    ``floor(min y / 2^l) - r .. floor(max y / 2^l) + r + 1``. The call
    computes each tile's first row from the coordinates it already
    takes (:func:`_window_plan`: a reduce over ``(tiles, tq)`` in XLA),
    hands it to the pipeline as a scalar-prefetch operand, and the
    level's block is ``H`` rows from there (an element-indexed
    ``BlockSpec``, on a row tile of 8), ``H`` from shapes
    (:func:`_window_heights`: 32 / 24 of 136 / 72 rows at 1088x1920);
    the y-weights count rows from the window's first, so rows outside
    it have the zero weight they had. A level whose window would be no
    clear saving (under half its rows: level 2's 24 of 40 there, 32 of
    56 at 440x1024), or whose rows are no whole row tiles (every level
    outside the pool's resident form), is read whole by the same code: ``H`` is its rows, the Mosaic module
    is the one it was. A tile whose taps do not fit one window
    (vertical flow varying across the tile by more than ``H`` leaves)
    sums the y-dots of as many windows as its span needs, in fp32,
    before the one rounding to the storage dtype
    (:func:`_ydot_windows`): same program, same result — bit for bit in
    bf16 storage, on the chip too; to an ulp in fp32 storage, where a
    tap's two rows in different windows are two rounded products and
    not a fused multiply-add — at a cost in proportion to the span (at
    1088x1920 with half the tiles on two or more windows the call takes
    8.84 ms, the whole-level read 8.97).
  * the small pooled levels (``flat_levels``) skip the y-dot entirely:
    their whole volumes are packed at build time into lane-dense rows
    and both bilinear axes run as 4-corner lane gathers. A separate
    contraction of a lane-padded ``(q, hl, wl <= 64)`` level reads mostly
    padding.
  * the projection (+bias+relu) runs in the same call
    (``lookup_project_fused``): the ``(Q, L*S*S)`` tap tensor lives only
    in a VMEM scratch and one MXU matmul emits the motion features, so
    the taps are never laid out in reference channel order.

Out-of-range taps: the y side is exact by construction (dense weights
vanish outside the grid); the x side masks each corner by its in-range
predicate, folded into the corner coefficients, reproducing torch
``padding_mode='zeros'`` (tested against the gather oracle in
``tests/test_pallas.py``).

The pyramid is stored in the block's dtype (``corr_dtype``: fp32, or
bf16 rounding-only storage, which every benchmark cell runs; PARITY.md
bounds what the rounding does to trained weights). The kernel is the
forward pass only: gradients are those of ``models/corr.py``'s XLA form
(``lookup_fused_diff`` / ``project_fused_diff``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from raft_tpu.models.corr import CorrBlock, lookup_pyramid, project_taps

__all__ = [
    "FusedLookupCorrBlock",
    "lookup_pyramid_fused",
    "lookup_project_fused",
    "MAX_LANES",
]

# lane-dim gathers address at most one 128-lane register row
MAX_LANES = 128

# widest y-dot level the kernel accepts: wider levels would need more than
# 4 chunked gathers per tap row and fall back to the XLA separable path
# (KITTI-pad 156 needs 2 chunks; full-HD /8 = 240 also 2; 4K /8 = 480 -> 4)
MAX_WIDTH = 4 * MAX_LANES


def _pad_width_to_lanes(wl: int) -> int:
    """Operand width the kernel sees: widths past one register row are
    padded (with zero DATA — zero-pad lookup semantics make the padded
    columns indistinguishable from out-of-range taps) to a multiple of
    MAX_LANES so every chunk of the chunked gather is a full row."""
    return wl if wl <= MAX_LANES else -(-wl // MAX_LANES) * MAX_LANES


def _pad_width(vol: jax.Array) -> jax.Array:
    """Zero-pad a ``(q, hl, wl[, 1])`` level volume on its width axis 2
    to :func:`_pad_width_to_lanes`. No-op at wl <= MAX_LANES. Call once
    per pyramid build where possible — inside the update scan XLA refuses
    to hoist size-increasing ops."""
    wl = vol.shape[2]
    wp = _pad_width_to_lanes(wl)
    if wp == wl:
        return vol
    pads = [(0, 0)] * vol.ndim
    pads[2] = (0, wp - wl)
    return jnp.pad(vol, pads)

# most queries per kernel grid step. _plan_tile lowers it where a tile's
# level blocks would not fit VMEM (408 rows at 1088x1920, where tiles
# 320 / 384 / 408 timed within 0.15% on the chip: builder's, PR 30);
# _pick_tile rounds to a divisor of Q. 640 itself predates the ledger
DEFAULT_QUERY_TILE = 640

# VMEM the kernel body takes beside its double-buffered blocks, per query
# row and per lane of the widest blocked level: the in-kernel y-dot's
# result rows (S -> 16 sublanes: fp32, the storage dtype, fp32 again for
# the gathers), the tap windows, the output block. Read off compiles for
# a described v5e with the tile forced until VMEM ran out (PR 30): 141 B
# at Sintel (18.0 KB a row, 128 lanes), 113 B at 1088x1920 (29.0 KB, 256
# lanes), 145 B at KITTI (37.2 KB, 256 lanes, masked tail).
_SCRATCH_LANE_BYTES = 160


# Scoped-VMEM limit of the call, of the chip's 128 MiB. The default is
# 16 MiB; a tile's raw level blocks (double-buffered), the batched
# y-dot's operands and the coordinates take 29 + 12.5 + 55 MiB in the
# 16-slot Sintel cells (compiled for a described v5e, PR 30). _plan_tile
# fits the tile to this.
_VMEM_LIMIT = 100 << 20


# Rows of a raw-volume level one DMA can start at: the (8, 128) tile of
# the operand's HBM layout (bf16 is T(8,128)(2,1): eight rows are one
# contiguous 2 KiB tile a 128-lane column, like fp32's eight).
_ROW_TILE = 8

# Level-0 rows (1/8 resolution: 8 px each) a window leaves, beside the
# rows its tile spans itself, for how much the vertical flow VARIES
# across one tile. A tile whose taps spread further takes more windows
# (exact, slower). Once the window is cut to whole row tiles, 8 leaves
# 12-19 rows of room at 1088x1920 and 11-18 at 440x1024, by where the
# tile's first tap row falls in its row tile.
_WINDOW_SPREAD = 8

# A level is read by window only where the window is clearly smaller
# than the level: under this share of its rows. Set once from what
# Sintel's level 0 at 32 of 56 rows (0.57) measured on the chip (PR 34):
# the kernel alone 4.30 -> 4.32 ms for raft_large on 16 slots, the tick
# 8.2533 -> 8.2250 ms, and for raft_small 5.3895 -> 5.4991 — at 440x1024
# the body outlasts the DMA of whole levels, so a window saves HBM
# traffic and no time, and its accumulator costs a little. Under half,
# the levels are those whose whole read was the bound: 32 of 136 and 24
# of 72 rows at 1088x1920 (8.87 -> 4.19 ms); 24 of 40 stays whole.
_WINDOW_SHARE = 0.5


class _Plan(NamedTuple):
    """How one call reads its blocked operands: ``tile`` query rows a
    grid step; the coordinate operand ``coords_blocked`` by tile or whole
    in VMEM; of the ``rows[k]`` that raw-volume operand ``k`` holds, a
    step brings ``heights[k]`` into VMEM at a time (all of them where
    the level is read whole)."""

    tile: int
    coords_blocked: bool
    heights: tuple
    rows: tuple


def _lanes(x) -> int:
    return -(-x.shape[-1] // MAX_LANES) * MAX_LANES


def _row_bytes(operands, heights) -> int:
    """VMEM bytes ONE query row of the blocked operands takes:
    ``heights[k]`` rows of the ``k``-th ``(q, a, b)`` operand's ``(a,
    b)`` slab in whole (8, 128) tiles, a ``(q, n)`` flat row in whole
    lanes."""
    total, k = 0, 0
    for x in operands:
        rows = 1
        if len(x.shape) == 3:
            rows = -(-heights[k] // 8) * 8
            k += 1
        total += rows * _lanes(x) * jnp.dtype(x.dtype).itemsize
    return total


def _tile_row_bytes(operands, heights) -> int:
    """VMEM bytes a tile takes a query row: its blocks twice
    (double-buffered), a windowed level's a third time (the buffer that
    windows past a tile's first are copied into), the body's scratch by
    the widest raw level's lanes."""
    raws = [x for x in operands if len(x.shape) == 3]
    lanes = max([_lanes(x) for x in raws] or [MAX_LANES])
    third = sum(
        h * _lanes(x) * jnp.dtype(x.dtype).itemsize
        for x, h in zip(raws, heights) if h < x.shape[1]
    )
    return (
        2 * _row_bytes(operands, heights) + third
        + _SCRATCH_LANE_BYTES * lanes
    )


def _window_heights(raws, levels, q, tq, width, radius):
    """Rows of each raw-volume operand a ``tq``-query tile brings in at
    a time, from shapes. A tile of consecutive queries spans
    ``ceil(tq / width)`` rows of the ``width``-wide query grid (one more
    where it starts mid-row); at level ``l`` its taps reach ``r`` rows
    above and ``r + 1`` below that span halved ``l`` times, and the
    window starts on a row tile, so up to ``_ROW_TILE - 1`` rows before
    the first tap come along. The level is read whole where that is no
    clear saving (``_WINDOW_SHARE``), where its rows are no whole row
    tiles (a window at the bottom edge could not start on one: the
    unpadded levels of ``RAFT.__call__`` and the trainer), where the
    grid has a masked tail (an element-indexed block cannot run past the
    array)."""
    if q % tq:
        return tuple(x.shape[1] for x in raws)
    span = -(-tq // width) - (0 if tq % width else 1) + _WINDOW_SPREAD
    heights = []
    for x, level in zip(raws, levels):
        reach = -(-span // 2**level) + 2 * radius + 2 + _ROW_TILE - 1
        h = -(-reach // _ROW_TILE) * _ROW_TILE
        rows = x.shape[1]
        windowed = rows % _ROW_TILE == 0 and h < _WINDOW_SHARE * rows
        heights.append(h if windowed else rows)
    return tuple(heights)


def _plan_tile(q: int, query_tile: int, operands, levels, width: int,
               radius: int) -> _Plan:
    """The :class:`_Plan` for ``q`` query rows of the blocked
    ``operands`` (arrays or shape specs; ``levels`` are the pyramid
    levels of the raw-volume ones, ``width`` the queries an image row),
    from shapes alone. One rule: what a tile needs — its level blocks
    twice (double-buffered; a windowed level a third time, for windows
    past a tile's first), the body's scratch (``_SCRATCH_LANE_BYTES``),
    and the coordinate operand where it lies whole in VMEM (512 B a row,
    lane-padded) — fits the call's VMEM limit (``_VMEM_LIMIT``).

    The tile is the largest :func:`_pick_tile` gives under ``query_tile``
    that fits with the coordinates blocked, its windows
    (:func:`_window_heights`) counted at that tile: 640 at every
    Sintel, KITTI and training shape (a row of whole levels 0-1 is 23 KB
    at 440x1024) and, since PR 34, at 1088x1920, where a row of the
    blocks is 33 KB with levels 0-1 by window and whole levels 0-2 took
    97 KB and a 408-row tile. ``coords_blocked`` says whether the
    coordinate operand is blocked by tile like the levels: only where
    whole it would not fit beside them — a 16-slot Sintel pool keeps it
    whole, a 32-slot one (110 MiB) or any 1088x1920 pool does not."""
    raws = [x for x in operands if len(x.shape) == 3]
    cap = query_tile
    while True:
        tq = _pick_tile(q, max(8, cap))
        heights = _window_heights(raws, levels, q, tq, width, radius)
        per_row = _tile_row_bytes(operands, heights)
        if tq * per_row <= _VMEM_LIMIT or tq <= 8:
            break
        cap = min(tq - 8, _VMEM_LIMIT // per_row)
    cents_bytes = -(-q // tq) * tq * MAX_LANES * 4
    return _Plan(
        tq, tq * per_row + cents_bytes > _VMEM_LIMIT, heights,
        tuple(x.shape[1] for x in raws),
    )


class _Window(NamedTuple):
    """One windowed level of a call (static): ``index`` among the call's
    windowed levels (its row of the prefetched starts and counts),
    pyramid ``level``, the resident ``rows`` of its map and the
    ``height`` a window reads of them."""

    index: int
    level: int
    rows: int
    height: int


def _windows(vols, levels, heights):
    """The :class:`_Window` of each raw-volume operand (``None`` where it
    is read whole), per operand."""
    out, n = [], 0
    for v, level, h in zip(vols, levels, heights):
        windowed = h < v.shape[1]
        out.append(_Window(n, level, v.shape[1], h) if windowed else None)
        n += windowed
    return tuple(out)


def _window_plan(cy, windows, radius: int):
    """Where each tile's window of each windowed level starts, and how
    many windows the tile needs: ``(starts, counts)``, int32
    ``(len(windows), tiles)``, from the tiles' level-0 ``y`` coordinates
    ``cy`` ``(tiles, tq)``, for the :class:`_Window` s ``windows``.

    At level ``l`` a query's taps have weight on rows ``floor(y / 2^l) -
    r .. floor(y / 2^l) + r + 1``, so a tile needs ``lo .. hi`` from its
    least and largest ``y``, cut to the rows there are (the weights
    vanish outside them). The first window starts on the row tile at or
    before ``lo``, clamped so that it ends inside the level; ``counts``
    windows of ``height`` rows from there reach ``hi``. Whatever the
    coordinates hold, both stay in range: far outside the frame a window
    misses every tap and multiplies by zero weights; a tile with a
    coordinate that is not a number reads every window, so its other
    queries get their taps."""
    lo0, hi0 = jnp.min(cy, axis=1), jnp.max(cy, axis=1)
    nan = jnp.isnan(lo0) | jnp.isnan(hi0)  # min and max carry a NaN
    lo0, hi0 = jnp.where(nan, -jnp.inf, lo0), jnp.where(nan, jnp.inf, hi0)
    starts, counts = [], []
    for _, level, rows, h in windows:
        inv = 1.0 / (2.0**level)

        def row(v, off):
            v = jnp.clip(jnp.floor(v * inv) + off, 0, rows - 1)
            return jnp.clip(v.astype(jnp.int32), 0, rows - 1)

        lo, hi = row(lo0, -radius), row(hi0, radius + 1)
        start = jnp.minimum(lo // _ROW_TILE * _ROW_TILE, rows - h)
        starts.append(start)
        counts.append(jnp.clip((hi - start) // h + 1, 1, -(-rows // h)))
    return jnp.stack(starts), jnp.stack(counts)


def _corner_gather(src, idx_a, coef_a, coef_b):
    """Two-corner bilinear combine from ONE lane gather; fp32 out.

    Corner b's value at lane i is ``src[u0+i+1]`` — exactly corner a's
    value at lane i+1 — so instead of a second dynamic gather it is a
    static left-roll of the first (dynamic gathers are the expensive VPU
    op here; a constant-shift roll is near-free). Lane wl-1 wraps to
    lane 0 garbage, but only lanes < S << wl are ever consumed and
    ``coef_b`` zeroes any out-of-range column either way."""
    g_a = jnp.take_along_axis(src, idx_a, axis=1)
    g_b = jnp.roll(g_a, -1, axis=1)
    return g_a * coef_a + g_b * coef_b


def _rows_read(cents, operands, levels, width, radius: int, query_tile: int):
    """``(read, whole)`` 128-lane rows of the raw-volume ``operands`` a
    lookup at ``cents`` ``(q, 2)`` reads: :func:`_plan_tile` and
    :func:`_window_plan` as :func:`_invoke_xtap` runs them — per shard
    under an ambient mesh that divides ``q``, as ``_partitioned_xtap``
    splits the call."""
    q = cents.shape[0]
    mesh = jax.sharding.get_abstract_mesh()
    shards = 1
    if not mesh.empty and mesh.size > 1 and q % mesh.size == 0:
        shards = mesh.size
    raws = [x for x in operands if len(x.shape) == 3]
    tq, _, heights, _ = _plan_tile(
        q // shards, query_tile, operands, levels, width, radius
    )
    lane_rows = [_lanes(x) // MAX_LANES for x in raws]
    whole = q * sum(x.shape[1] * n for x, n in zip(raws, lane_rows))
    live = [
        (w, n) for w, n in zip(_windows(raws, levels, heights), lane_rows)
        if w is not None
    ]
    read = jnp.int32(whole)
    if live:
        # a windowed level: its windows' rows in place of its own
        _, counts = _window_plan(
            cents[:, 1].reshape(-1, tq), [w for w, _ in live], radius
        )
        a_window = jnp.asarray([tq * w.height * n for w, n in live], jnp.int32)
        read = read - q * sum(w.rows * n for w, n in live) + jnp.sum(
            counts * a_window[:, None]
        )
    return read, jnp.int32(whole)


class _WindowRefs(NamedTuple):
    """What a grid step needs to read windowed levels: the prefetched
    ``starts`` and ``counts`` (``(windowed level, tile)``, flat; ``tiles``
    a level), each windowed level once more in HBM (``anys``) with a
    VMEM buffer one window large (``bufs``) and a DMA semaphore for the
    windows past a tile's first, and the fp32 ``(T, S, lanes)``
    accumulator ``t`` the windows' y-dots are summed in."""

    starts: object
    counts: object
    anys: Sequence
    t: object
    bufs: Sequence
    sem: object
    tiles: int


def _ydot_windows(ydot, vol_ref, win: _Window, refs: _WindowRefs, tq: int):
    """The y-dot of a windowed level for this tile, fp32 ``(T, S, wl)``.

    ``vol_ref`` is the tile's first window, brought in by the call's
    pipeline like any block. The y-dot is linear in the rows, so a tile
    whose taps reach past it (``counts > 1``: vertical flow varying
    across the tile by more than the window leaves) adds the y-dots of
    the windows that follow, each copied here from the level's alias in
    HBM — not double-buffered: the cost of the rare case is in
    proportion to its span, the whole level at worst. Window ``w`` owns
    rows ``start + w * H`` on; the last is clamped to end inside the
    level and gives the rows before its own no weight, so no row counts
    twice. One window a tile is the cells' case and costs the store and
    reload of the accumulator alone."""
    i = pl.program_id(0)
    at = win.index * refs.tiles + i
    start, h = refs.starts[at], win.height
    acc = refs.t.at[:, :, : vol_ref.shape[2]]
    acc[...] = ydot(vol_ref, start)
    any_ref, buf_ref = refs.anys[win.index], refs.bufs[win.index]

    @pl.when(refs.counts[at] > 1)
    def _():
        def more(w, carry):
            first = start + w * h
            row0 = pl.multiple_of(jnp.minimum(first, win.rows - h), _ROW_TILE)
            copy = pltpu.make_async_copy(
                any_ref.at[pl.ds(i * tq, tq), pl.ds(row0, h), :], buf_ref,
                refs.sem,
            )
            copy.start()
            copy.wait()
            acc[...] += ydot(buf_ref, row0, first)
            return carry

        jax.lax.fori_loop(1, refs.counts[at], more, 0)

    return acc[...]


def _write_taps(
    cents_ref, vol_refs, flat_refs, dst_ref, *,
    radius: int, ydot_levels, widths, flat_levels, flat_dims,
    ydot_offsets, flat_offsets, tq: int, cents_blocked: bool = False,
    windows=(), win_refs=None,
):
    """Write one query tile of taps into ``dst_ref`` (the out ref, or the
    fp32 scratch of the projecting kernel), at the per-level column offsets
    of :func:`_scratch_layout`.

    Two in-kernel paths, chosen per pyramid level by the wrapper:

      * y-dot levels (``vol_refs``, the large levels): the block is the
        RAW ``(T, hl, wl)`` volume; the y-contraction runs here as one
        batched MXU dot against bilinear y-weights built from iotas, then
        the 2-tap x-combine via lane gathers.
        Block layout: j-major, ``off + j*S + i``. A WINDOWED level
        (``windows[k]`` rows, not ``None``) comes as the ``H`` rows from
        this tile's first row (``win_refs``: the scalar-prefetched
        starts and window counts) instead of the whole map: the
        y-weights count rows from that start, and a tile whose taps
        reach past one window adds the y-dots of as many more as it
        needs, fetched here (:func:`_ydot_windows`).
      * flat levels (``flat_refs``, the small pooled levels): the level's
        whole (hl, wl) volume is packed as dense 128-lane rows and BOTH
        bilinear axes run here as lane gathers — no y-dot at all (a
        lane-padded ``(hl, wl <= 64)`` level's y-dot reads mostly
        padding). Taps are laid out in RUNS of ``S+1`` lanes
        (``off + j*(S+1) + i``, lane ``i == S`` dead): within a run the
        flat volume index is affine in the lane, so the x+1 bilinear
        corner is a static left-roll of the x corner's gather instead of a
        second dynamic gather. When ``S*(S+1) <= 64`` both y corners ride
        ONE gather (dy=0 in lanes [0, S*(S+1)), dy=1 at lane+64) — for
        S=7 that is 1 dynamic gather per packed row where the first
        version of this kernel issued 4.
    """
    s = 2 * radius + 1
    # cents stay resident in VMEM unblocked where they fit (a blocked
    # operand is a VMEM->HBM round trip of the coords carry every
    # iteration, on the critical path); slice this tile's rows here. The
    # tile size is 8-aligned so the dynamic start is provably aligned.
    # Where whole they would not fit beside the level blocks (_plan_tile)
    # the ref IS this tile's rows, blocked like the levels.
    rows = (
        slice(None) if cents_blocked
        else pl.dslice(pl.program_id(0) * tq, tq)
    )
    cx = cents_ref[rows, 0]  # (T,) f32 level-0 x
    cy = cents_ref[rows, 1]  # (T,) f32 level-0 y

    for k, (level, vol_ref, wl, off) in enumerate(zip(
        ydot_levels, vol_refs, widths, ydot_offsets
    )):
        cxl = cx * (1.0 / (2.0**level))
        x0 = jnp.floor(cxl)
        fx = (cxl - x0).astype(jnp.float32)
        u0 = x0.astype(jnp.int32) - radius  # leftmost tap's grid column

        # index/coefficient rows are j-independent: build once per level,
        # reuse across all S gathers below. Lane i reads grid column u0+i
        # (corner a) / u0+i+1 (corner b); only lanes < S are consumed.
        # Widths > MAX_LANES run the chunked path: the gather shape is one
        # 128-lane register row and the tap window (S+1 wide) is summed
        # over per-chunk hit masks, the same scheme as the flat path below
        # (the 1080p cell's level 0, 240 wide, runs it on the chip).
        chunked = wl > MAX_LANES
        nl = MAX_LANES if chunked else wl
        lane = jax.lax.broadcasted_iota(jnp.int32, (tq, nl), 1)
        col_a = u0[:, None] + lane
        col_b = col_a + 1
        # corners outside the grid get zero coefficients => exact
        # zero-padding parity with the gather oracle
        coef_a = jnp.where((col_a >= 0) & (col_a < wl), 1.0 - fx[:, None], 0.0)
        coef_b = jnp.where((col_b >= 0) & (col_b < wl), fx[:, None], 0.0)
        # clamp keeps gather indices in-bounds for the masked lanes (their
        # products are zeroed by the coefficients); unlike the former
        # power-of-two bitwise mask this works at ANY width. The corner-b
        # roll stays exact: idx is affine in the lane wherever a corner-b
        # coefficient is nonzero (requires wl >= S+1, see _fusable)
        idx_a = jnp.clip(col_a, 0, wl - 1)
        if chunked:
            # j-invariant per-chunk index/hit rows, hoisted like idx_a
            chunk_rows = [
                (
                    c * MAX_LANES,
                    jnp.clip(col_a - c * MAX_LANES, 0, MAX_LANES - 1),
                    (col_a >= c * MAX_LANES) & (col_a < (c + 1) * MAX_LANES),
                    (col_b >= c * MAX_LANES) & (col_b < (c + 1) * MAX_LANES),
                )
                for c in range(wl // MAX_LANES)
            ]

        # the y-contraction, as one batched MXU dot over the RAW
        # (T, hl, wl) block: no (q, S, wl) rows in HBM. The weights are
        # rounded to the storage dtype and the fp32 accumulation is
        # rounded back to it, as the XLA form (corr.lookup_pyramid with
        # this weight_dtype) rounds its rows
        cyl = (cy * (1.0 / (2.0**level))).astype(jnp.float32)

        def ydot(ref, row0=None, first=None, cyl=cyl):
            """``ref``'s rows are the level's from ``row0``; rows before
            ``first`` belong to an earlier window and get no weight."""
            hl = ref.shape[1]
            jj = jax.lax.broadcasted_iota(
                jnp.int32, (tq, s, hl), 1
            ).astype(jnp.float32)
            yi = jax.lax.broadcasted_iota(jnp.int32, (tq, s, hl), 2)
            if row0 is not None:
                yi = yi + row0
            yy = yi.astype(jnp.float32)
            wy = jnp.maximum(
                1.0 - jnp.abs(cyl[:, None, None] + (jj - radius) - yy), 0.0
            )
            if first is not None:
                wy = jnp.where(yi >= first, wy, 0.0)
            vol = ref[...]
            return jax.lax.dot_general(
                wy.astype(vol.dtype), vol,
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )

        if windows and windows[k] is not None:
            t = _ydot_windows(ydot, vol_ref, windows[k], win_refs, tq)
        else:
            t = ydot(vol_ref)
        t = t.astype(vol_ref.dtype)

        for j in range(s):
            # fp32 before the gather (Mosaic's tpu.dynamic_gather has no
            # bf16 lowering here)
            src = t[:, j, :].astype(jnp.float32)  # (T, wl)
            if not chunked:
                taps = _corner_gather(src, idx_a, coef_a, coef_b)
            else:
                # wl > 128 (prepare pads it to a 128 multiple, with zero
                # data in the pad — zero-pad lookup semantics make the
                # padded columns indistinguishable from out-of-range):
                # gather each 128-lane chunk at chunk-local clamped
                # indices; hit masks pick the chunk that owns each corner
                # (a tap window straddles at most two chunks)
                taps = jnp.zeros((tq, nl), jnp.float32)
                for base, idx, hit_a, hit_b in chunk_rows:
                    chunk = src[:, base : base + MAX_LANES]
                    g = jnp.take_along_axis(chunk, idx, axis=1)
                    gb = jnp.roll(g, -1, axis=1)
                    taps = (
                        taps
                        + jnp.where(hit_a, g * coef_a, 0.0)
                        + jnp.where(hit_b, gb * coef_b, 0.0)
                    )
            dst = off + j * s  # j-major within the level block
            dst_ref[:, dst : dst + s] = taps[:, :s].astype(dst_ref.dtype)

    rl = s + 1  # run length: S consumed taps + 1 roll slack lane
    nlanes = s * rl
    dual = nlanes <= 64  # both dy corners fit one 128-lane gather
    k = jax.lax.broadcasted_iota(jnp.int32, (tq, MAX_LANES), 1)
    if dual:
        blk = k // 64  # 0 => dy=0 half, 1 => dy=1 half
        k0 = k - blk * 64
    else:
        blk = None
        k0 = k
    kj = k0 // rl  # tap y-offset index
    ki = k0 - kj * rl  # tap x-offset index
    alive = (kj < s) & (ki < s)

    for level, flat_ref, (hl, wl), off in zip(
        flat_levels, flat_refs, flat_dims, flat_offsets
    ):
        inv = 1.0 / (2.0**level)
        cxl, cyl = cx * inv, cy * inv
        x0 = jnp.floor(cxl)
        y0 = jnp.floor(cyl)
        fx = (cxl - x0).astype(jnp.float32)
        fy = (cyl - y0).astype(jnp.float32)
        gx = (x0.astype(jnp.int32) - radius)[:, None] + ki  # corner-a grid x

        n_rows = flat_ref.shape[1] // MAX_LANES
        acc = jnp.zeros((tq, MAX_LANES), jnp.float32)
        for dy in ((None,) if dual else (0, 1)):
            gy = (y0.astype(jnp.int32) - radius)[:, None] + kj
            gy = gy + (blk if dual else dy)
            f = gy * wl + gx  # flat volume index of corner (dy, dx=0)
            idx = jax.lax.bitwise_and(f, MAX_LANES - 1)
            if dual:
                wy_frac = jnp.where(blk == 1, fy[:, None], 1.0 - fy[:, None])
            else:
                wy_frac = fy[:, None] if dy else 1.0 - fy[:, None]
            wy = jnp.where((gy >= 0) & (gy < hl), wy_frac, 0.0)
            coef_a = jnp.where(
                alive & (gx >= 0) & (gx < wl), wy * (1.0 - fx[:, None]), 0.0
            )
            coef_b = jnp.where(
                alive & (gx + 1 >= 0) & (gx + 1 < wl), wy * fx[:, None], 0.0
            )
            for r in range(n_rows):
                src = flat_ref[:, r * MAX_LANES : (r + 1) * MAX_LANES].astype(
                    jnp.float32
                )  # (T, 128)
                # one dynamic gather per (row, dy-pass); the dx+1 corner is
                # its static left-roll (f is affine in the lane within a
                # run; the run's slack lane makes i+1 <= S always valid)
                g = jnp.take_along_axis(src, idx, axis=1)
                gb = jnp.roll(g, -1, axis=1)
                base = r * MAX_LANES
                hit_a = (f >= base) & (f < base + MAX_LANES)
                hit_b = (f + 1 >= base) & (f + 1 < base + MAX_LANES)
                acc = (
                    acc
                    + jnp.where(hit_a, g * coef_a, 0.0)
                    + jnp.where(hit_b, gb * coef_b, 0.0)
                )
        if dual:
            # fold the dy=1 half (lanes 64+) onto the dy=0 half
            acc = acc + jnp.roll(acc, -64, axis=1)
        dst_ref[:, off : off + nlanes] = acc[:, :nlanes].astype(dst_ref.dtype)


def _tile_taps(refs, *, project: bool, tiles: int, **static):
    """Sort one grid step's ``refs`` and write the tile's taps.

    ``refs`` come as ``[starts, counts,] cents, [w, b,] vol_*, flat_*,
    [any_*,] out, [acc,] [t, buf_*, sem]``: the bracketed window refs
    only where a level is windowed, ``w``, ``b`` and the fp32 tap scratch
    ``acc`` only in the projecting kernel. The taps go to ``acc`` there
    and to ``out`` otherwise, in :func:`_scratch_layout`'s columns.
    Returns ``(out, acc, w, b)``, ``(out, None)`` without the projection."""
    nv, nf = len(static["widths"]), len(static["flat_levels"])
    nw = sum(w is not None for w in static["windows"])
    refs = list(refs)
    pre = [refs.pop(0) for _ in range(2 if nw else 0)]
    cents_ref = refs.pop(0)
    wb = [refs.pop(0) for _ in range(2 if project else 0)]
    vols = [refs.pop(0) for _ in range(nv)]
    flats = [refs.pop(0) for _ in range(nf)]
    anys = [refs.pop(0) for _ in range(nw)]
    out_ref = refs.pop(0)
    acc_ref = refs.pop(0) if project else None
    win_refs = None
    if nw:
        t_ref, *bufs, sem = refs
        win_refs = _WindowRefs(*pre, anys, t_ref, bufs, sem, tiles)
    _write_taps(
        cents_ref, vols, flats, acc_ref if project else out_ref,
        tq=out_ref.shape[0], win_refs=win_refs, **static,
    )
    return (out_ref, acc_ref, *wb)


def _xtap_kernel(*refs, **static):
    """One query tile of taps: out is (T, c_scratch) taps in the
    :func:`_scratch_layout` column order. vol_l is the RAW (T, hl, wl)
    volume block of a y-dot level — or its first (T, H, wl) window — (the
    y-contraction runs here as a batched MXU dot); flat_l is (T,
    rows*128) packed volume for the flat levels."""
    _tile_taps(refs, **static)


def _xtap_project_kernel(*refs, mxu_dtype, **static):
    """x-tap + ``convcorr1`` projection in one pass: the j-major taps land
    in an fp32 VMEM scratch, one (T, L*S*S) @ (L*S*S, C_out) MXU matmul +
    bias + relu emits the motion-encoder input directly — the tap tensor
    never reaches HBM in reference layout. ``w`` is the row-permuted
    (j-major) projection matrix, ``b`` the (1, C_out) bias."""
    out_ref, acc_ref, w_ref, b_ref = _tile_taps(refs, **static)
    taps = acc_ref[...].astype(mxu_dtype)
    w = w_ref[...].astype(mxu_dtype)
    y = jax.lax.dot_general(
        taps, w,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y = y + b_ref[...].astype(jnp.float32)
    out_ref[...] = jnp.maximum(y, 0.0).astype(out_ref.dtype)


class _XtapStatic(NamedTuple):
    """Hashable static config of one x-tap pallas_call: everything the
    kernel needs besides the operand arrays themselves. One instance keys
    one :func:`_partitioned_xtap` mesh-aware call (lru-cached), and
    :func:`_invoke_xtap` rebuilds the pallas_call from it at ANY query
    count — the global q in a single-device trace, the per-shard q under
    a mesh."""

    radius: int
    ydot_levels: tuple
    widths: tuple
    flat_levels: tuple
    flat_dims: tuple
    ydot_offsets: tuple
    flat_offsets: tuple
    c_scratch: int
    out_dtype: Optional[str]  # dtype *name* (dtype objects don't hash stably)
    query_tile: int
    interpret: bool
    q_width: int  # queries an image row (the windows' rule)
    project: bool = False
    c_out: int = 0
    mxu_dtype: Optional[str] = None


def _invoke_xtap(st: _XtapStatic, *arrays) -> jax.Array:
    """Build and run the x-tap pallas_call for this operand set's q.

    ``arrays`` order: ``cents, [w_mat, bias (project),] *vols, *flats``.
    Shape-polymorphic in q only: the query tile, grid, block specs and
    the windows' starts (:func:`_window_plan`, on THESE coordinates) are
    derived here so the same static config serves both the global trace
    and the per-shard call under a mesh (``_partitioned_xtap`` hands this
    q/n-row operands)."""
    cents = arrays[0]
    head = arrays[1:3] if st.project else ()
    nv = len(st.widths)
    vols = arrays[1 + len(head) : 1 + len(head) + nv]
    flats = arrays[1 + len(head) + nv :]

    q = cents.shape[0]
    tq, cents_blocked, heights, _ = _plan_tile(
        q, st.query_tile, [*vols, *flats], st.ydot_levels, st.q_width,
        st.radius,
    )
    grid = -(-q // tq)
    windows = _windows(vols, st.ydot_levels, heights)
    live = [(v, w) for v, w in zip(vols, windows) if w is not None]
    prefetch = ()
    if live:
        # microseconds of XLA before the call: a reduce over (grid, tq)
        starts, counts = _window_plan(
            cents[:, 1].reshape(grid, tq), [w for _, w in live], st.radius
        )
        prefetch = (starts.reshape(-1), counts.reshape(-1))
    if grid * tq != q:
        # non-divisible q (no 8-aligned divisor <= the tile): the last
        # block is masked by Pallas (OOB stores dropped, OOB operand rows
        # padded); only cents needs real rows, its tile is sliced manually
        cents = jnp.pad(cents, ((0, grid * tq - q), (0, 0)))
    static = dict(
        radius=st.radius, ydot_levels=st.ydot_levels, widths=st.widths,
        flat_levels=st.flat_levels, flat_dims=st.flat_dims,
        ydot_offsets=st.ydot_offsets, flat_offsets=st.flat_offsets,
        cents_blocked=cents_blocked, windows=windows, tiles=grid,
        project=st.project,
    )
    # index maps take the grid index, then the prefetched scalars
    cents_spec = (
        pl.BlockSpec((tq, 2), lambda i, *_: (i, 0)) if cents_blocked
        else pl.BlockSpec(memory_space=pltpu.VMEM)
    )

    def vol_spec(v, w):
        if w is None:  # the whole (hl, wl) map, blocked on dim 0
            return pl.BlockSpec(
                (tq, v.shape[1], v.shape[2]), lambda i, *_: (i, 0, 0)
            )
        # H rows from the tile's first: element-indexed, so the row the
        # block starts at is a scalar the pipeline reads before the step
        return pl.BlockSpec(
            (pl.Element(tq), pl.Element(w.height), pl.Element(v.shape[2])),
            lambda i, starts, _, at=w.index * grid: (
                i * tq, pl.multiple_of(starts[at + i], _ROW_TILE), 0
            ),
        )

    in_specs = (
        [cents_spec]
        # w_mat and bias whole in VMEM, unblocked
        + [pl.BlockSpec(memory_space=pltpu.VMEM) for _ in head]
        + [vol_spec(v, w) for v, w in zip(vols, windows)]
        + [pl.BlockSpec((tq, f.shape[1]), lambda i, *_: (i, 0)) for f in flats]
        # a windowed level once more, left in HBM: windows past a tile's
        # first are copied from it in the body
        + [pl.BlockSpec(memory_space=pl.ANY) for _ in live]
    )
    scratch = []
    if st.project:
        scratch.append(pltpu.VMEM((tq, st.c_scratch), jnp.float32))
    if live:
        wide = max(v.shape[2] for v, _ in live)
        scratch.append(pltpu.VMEM((tq, 2 * st.radius + 1, wide), jnp.float32))
        scratch += [
            pltpu.VMEM((tq, w.height, v.shape[2]), v.dtype) for v, w in live
        ]
        scratch.append(pltpu.SemaphoreType.DMA(()))
    out_dtype = jnp.dtype(st.out_dtype) if st.out_dtype else jnp.float32
    c_out = st.c_out if st.project else st.c_scratch
    kernel = functools.partial(_xtap_kernel, **static)
    if st.project:
        kernel = functools.partial(
            _xtap_project_kernel,
            mxu_dtype=jnp.dtype(st.mxu_dtype) if st.mxu_dtype else jnp.float32,
            **static,
        )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((q, c_out), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(grid,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tq, c_out), lambda i, *_: (i, 0)),
            scratch_shapes=scratch,
        ),
        interpret=st.interpret,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
    )(*prefetch, cents, *head, *vols, *flats, *[v for v, _ in live])


def _partition_dim0(mesh, dim0, q: int):
    """The q-axis sharding the kernel will actually use under ``mesh``:
    ``dim0`` (mesh axis name or tuple of names) when q divides evenly
    over it, else ``None`` — run the kernel whole rather than let it see
    padded rows (correctness over parallelism for odd shapes)."""
    if dim0 is None:
        return None
    axes = dim0 if isinstance(dim0, tuple) else (dim0,)
    n = 1
    for a in axes:
        n *= dict(mesh.shape)[a]
    return None if q % n else dim0


@functools.lru_cache(maxsize=None)
def _partitioned_xtap(st: _XtapStatic):
    """The x-tap pallas_call, ``shard_map``-ped over the ambient mesh.

    The SPMD partitioner cannot see inside a TPU custom call, so under a
    mesh it would replicate the kernel (all-gathering its operands). What
    is true of the kernel: every query row is independent, all
    q-carrying operands (cents, vols, flats) shard identically on dim 0,
    everything else (projection weights, bias, the tap/lane dims) is
    replicated. So when the program is traced under an
    ambient mesh (``parallel.mesh.traced_under`` — the sharded step and
    serve programs enter it), the call is a ``shard_map`` of
    :func:`_invoke_xtap` over ALL mesh axes on the q dim: same kernel,
    local q, smaller grid. (``custom_partitioning`` expressed the same
    rule without needing the mesh, but the TPU compiler has no
    partitioner for it: "Custom emitter for CustomSPMDPartitioning not
    found".)

    With no ambient mesh this is the bare kernel. When q does not divide
    evenly over the mesh the kernel also runs whole (the partitioner then
    inserts the reshards), so odd shapes stay correct, merely
    unpartitioned."""
    n_pre = 3 if st.project else 1
    n_args = n_pre + len(st.widths) + len(st.flat_levels)
    q_positions = (0,) + tuple(range(n_pre, n_args))

    def call(*arrays):
        mesh = jax.sharding.get_abstract_mesh()
        dim0 = None
        if not mesh.empty and mesh.size > 1:
            dim0 = _partition_dim0(
                mesh, tuple(mesh.axis_names), arrays[0].shape[0]
            )
        if dim0 is None:
            return _invoke_xtap(st, *arrays)
        return jax.shard_map(
            functools.partial(_invoke_xtap, st),
            mesh=mesh,
            in_specs=tuple(
                P(dim0) if pos in q_positions else P()
                for pos in range(n_args)
            ),
            out_specs=P(dim0),
            check_vma=False,  # pallas_call outputs carry no vma
        )(*arrays)

    return call


def lookup_pyramid_fused(
    pyramid: Sequence[jax.Array],
    centroids: jax.Array,
    radius: int,
    *,
    weight_dtype=None,
    query_tile: int = DEFAULT_QUERY_TILE,
    interpret: bool = False,
    flats=None,
) -> jax.Array:
    """Multi-scale (2r+1)^2 bilinear lookup in one Pallas call: batched
    MXU y-dot over double-buffered raw volume blocks + lane-gather x-tap
    for the large levels, 4-corner lane gathers for the small flat-packed
    ones.

    Semantically equal to ``corr.lookup_pyramid`` (reference channel order,
    zero-padding; oracle-tested). Requires every y-dot-path level width in
    ``[2r+2, MAX_WIDTH]`` (see :func:`_fusable`) — any standard crop or
    eval geometry qualifies, including non-power-of-two widths (Chairs 62,
    Things 90, Sintel-stage 96) and >128 widths (KITTI 156, chunked
    gathers); ``FusedLookupCorrBlock`` falls back to the XLA path
    otherwise.

    Args:
        pyramid: list of ``(B*Q, hl, wl, 1)`` (or 3D) pooled volume levels.
        centroids: ``(B, h, w, 2)`` level-0 (x, y) tap centers.
        weight_dtype: dtype the volume blocks are read in, of the
            y-contraction's weights and rows, and of the emitted taps
            (``jnp.bfloat16`` halves the HBM+VMEM traffic; the bf16
            compute path converts taps right after anyway). ``None``
            keeps fp32 end to end.
    Returns:
        ``(B, h, w, L*(2r+1)^2)`` correlation features.
    """
    b, h, w, _ = centroids.shape
    q = b * h * w
    s = 2 * radius + 1
    rl = s + 1
    num_levels = len(pyramid)
    _check_fusable(pyramid, s, "lookup_pyramid_fused")
    prep = _FusedPrep(pyramid, radius, weight_dtype, flats)
    c_out = num_levels * s * s

    st = _XtapStatic(
        c_scratch=prep.c_scratch,
        out_dtype=jnp.dtype(weight_dtype).name if weight_dtype else None,
        query_tile=query_tile,
        interpret=interpret,
        q_width=w,
        **prep.static,
    )
    out = _partitioned_xtap(st)(_flat_cents(centroids), *prep.operands)

    # kernel layouts -> reference i-major channel order per level
    feats = []
    for level in range(num_levels):
        off = prep.offsets[level]
        if level in prep.ydot_levels:
            blk = out[:, off : off + s * s].reshape(q, s, s)  # [j, i]
        else:
            blk = out[:, off : off + s * rl].reshape(q, s, rl)[:, :, :s]  # [j, i]
        feats.append(jnp.transpose(blk, (0, 2, 1)).reshape(q, s * s))
    out = jnp.concatenate(feats, axis=-1)
    return out.reshape(b, h, w, c_out)


def _flat_cents(centroids: jax.Array) -> jax.Array:
    """``(B, h, w, 2)`` tap centers as the kernel's ``(q, 2)`` fp32 rows."""
    return centroids.reshape(-1, 2).astype(jnp.float32)


def _flat_max_rows(s: int) -> int:
    """Largest packed-row count a level may have and still take the
    in-kernel 4-corner flat-gather path instead of the y-dot. A flat
    level costs one masked gather per packed 128-lane row, a y-dot level
    one batched dot and S gathers whatever its size, so the cut follows
    the tap width: raft_large (S=9) packs levels of at most 4 rows
    (levels 2-3 at 440x1024, level 3 alone at 1088x1920, where level 2
    has 16), raft_small (S=7, fewer gathers a row) of at most 16 (levels
    1-3 at 440x1024). Level 0 always takes the y-dot. The two thresholds
    predate the ledger; no cell has timed their neighbours."""
    return 4 if s >= 9 else 16


def _split_levels(pyramid, s: int):
    """Partition level indices into (ydot_levels, flat_levels)."""
    max_rows = _flat_max_rows(s)
    if s * (s + 1) > MAX_LANES:
        # the run layout needs S*(S+1) lanes per level block; radii >= 5
        # overflow a 128-lane register row, so every level stays on the
        # y-dot path
        max_rows = -1
    ydot, flat = [], []
    for level, v in enumerate(pyramid):
        rows = -(-(v.shape[1] * v.shape[2]) // MAX_LANES)
        (flat if level > 0 and rows <= max_rows else ydot).append(level)
    return ydot, flat


def _scratch_layout(num_levels, ydot_levels, s: int):
    """Per-level column layout of the kernel's tap scratch/output.

    y-dot levels occupy ``S*S`` columns (j-major); flat levels occupy
    ``S*(S+1)`` columns (runs of S+1 lanes, last lane of each run dead —
    the roll slack, see ``_write_taps``). Returns
    ``(offsets, widths, total)`` indexed by level.
    """
    rl = s + 1
    offsets, widths = [], []
    col = 0
    for level in range(num_levels):
        w = s * s if level in ydot_levels else s * rl
        offsets.append(col)
        widths.append(w)
        col += w
    return tuple(offsets), tuple(widths), col


def _flat_pack(vol, q):
    """(q, hl, wl[, 1]) volume -> (q, rows*128) lane-dense packing.

    Kept 2D: the last two dims of a 3D (q, rows, 128) array get sublane
    tiling, which pads small row counts (to 16 rows for bf16); a
    (q, rows*128) layout is dense for every dtype and the kernel
    addresses row r as the static lane slice [r*128, (r+1)*128).

    Call at build_pyramid time, not per lookup: XLA's while-loop invariant
    code motion refuses to hoist size-increasing ops, so packing inside
    the refinement scan would run every iteration.
    """
    hl, wl = vol.shape[1], vol.shape[2]
    flat = vol.reshape(q, hl * wl)
    rows = -(-(hl * wl) // MAX_LANES)
    pad = rows * MAX_LANES - hl * wl
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat


def _pick_tile(q: int, query_tile: int) -> int:
    """Largest 8-aligned divisor of q <= query_tile when one exists (no
    masked tail, no padding copy of any operand: Sintel's q=7040 and every
    other geometry with such a divisor); q itself is the degenerate
    single-tile fallback. Otherwise
    (e.g. KITTI's q=47*156=7332, which has no 8-aligned divisor) return
    an 8-aligned tile and let :func:`_invoke_xtap` run a cdiv grid whose
    masked last block covers the tail — only the small cents operand is
    padded, never the volumes."""
    for d in range(min(query_tile, q), 0, -1):
        if q % d == 0 and d % 8 == 0:
            return d
    if q <= query_tile:
        return q  # one tile, start 0: no alignment or masking concerns
    # balance tiles across the cdiv grid: the maximal tile could waste up
    # to a whole tile of masked compute (q=641 -> 640+639 garbage rows);
    # ceil-dividing q over the same grid count caps waste at 7 rows/step
    # (KITTI 7332: tq=616 x 12, 60 masked rows vs 348)
    grid = -(-q // max(8, query_tile - query_tile % 8))
    rows_per_tile = -(-q // grid)
    return -(-rows_per_tile // 8) * 8


class _FusedPrep:
    """The kernel's blocked operands for a pyramid, and the static
    level-layout kwargs that go with them: level split, the y-dot levels'
    raw ``(q, hl, wl)`` volumes (lane-padded, in ``weight_dtype``), the
    flat levels' packed rows (packed here when not prepacked). The one
    place either is decided, so the lookup and lookup+project variants
    and the plan ``stats()`` reports (:meth:`FusedLookupCorrBlock.
    kernel_rows`) can never disagree on what the kernel is handed.
    (Tile choice and block specs live in :func:`_invoke_xtap`, which
    rebuilds them per shard under a mesh.)"""

    def __init__(self, pyramid, radius, weight_dtype, flats):
        q = pyramid[0].shape[0]
        s = 2 * radius + 1
        ydot_levels, flat_levels = _split_levels(pyramid, s)
        offsets, _, self.c_scratch = _scratch_layout(len(pyramid), ydot_levels, s)
        self.offsets = offsets
        self.ydot_levels, self.flat_levels = ydot_levels, flat_levels
        # the kernel sees lane-padded widths for >128-wide levels (zero
        # data in the pad == out-of-range taps); FusedLookupCorrBlock
        # prepads at build_pyramid time so _pad_width is a no-op on that
        # path — direct callers pay the pad per call
        vols = [
            _pad_width(pyramid[l].reshape((q,) + pyramid[l].shape[1:3]))
            for l in ydot_levels
        ]
        if weight_dtype is not None:
            vols = [v.astype(weight_dtype) for v in vols]
        if not flats:
            # direct-call convenience; FusedLookupCorrBlock prepacks at
            # build_pyramid time (see _flat_pack)
            flats = [_flat_pack(pyramid[l], q) for l in flat_levels]
        self.operands = [*vols, *flats]
        self.static = dict(
            radius=radius, ydot_levels=tuple(ydot_levels),
            widths=tuple(v.shape[2] for v in vols),
            flat_levels=tuple(flat_levels),
            flat_dims=tuple(pyramid[l].shape[1:3] for l in flat_levels),
            ydot_offsets=tuple(offsets[l] for l in ydot_levels),
            flat_offsets=tuple(offsets[l] for l in flat_levels),
        )


def _check_fusable(pyramid, s, who):
    if not _fusable(pyramid, s):
        raise ValueError(
            f"{who} needs every y-dot-path level width in "
            f"[{s + 1}, {MAX_WIDTH}], got {[v.shape[2] for v in pyramid]}; "
            f"use corr.lookup_pyramid"
        )


def lookup_project_fused(
    pyramid: Sequence[jax.Array],
    centroids: jax.Array,
    kernel: jax.Array,
    bias: jax.Array,
    radius: int,
    *,
    weight_dtype=None,
    proj_dtype=None,
    query_tile: int = DEFAULT_QUERY_TILE,
    interpret: bool = False,
    flats=None,
) -> jax.Array:
    """Multi-scale lookup + ``convcorr1`` 1x1 projection in one kernel.

    Semantically equal to ``project_taps(lookup_pyramid(...), kernel,
    bias)`` (oracle-tested). The projection matrix's rows are permuted
    once per call from the reference i-major tap order into the kernel's
    j-major order, so the in-VMEM taps multiply directly — no transpose,
    no reference-layout materialization.

    Args:
        kernel: ``(1, 1, L*(2r+1)^2, C_out)`` conv kernel.
        bias: ``(C_out,)``.
        proj_dtype: matmul/output dtype of the projection, mirroring the
            motion encoder's compute dtype (``project_taps(dtype=...)``).
    Returns:
        ``(B, h, w, C_out)`` projected (relu'd) motion features.
    """
    b, h, w, _ = centroids.shape
    s = 2 * radius + 1
    rl = s + 1
    num_levels = len(pyramid)
    _check_fusable(pyramid, s, "lookup_project_fused")
    c_in = num_levels * s * s
    c_out = kernel.shape[-1]
    if kernel.shape[-2] != c_in:
        raise ValueError(f"kernel expects {kernel.shape[-2]} taps, lookup makes {c_in}")

    prep = _FusedPrep(pyramid, radius, weight_dtype, flats)

    # Permute the projection rows from the reference tap channel order
    # (row l*S*S + i*S + j) into the kernel's scratch layout: j-major
    # ``off + j*S + i`` for y-dot levels, (S+1)-runs ``off + j*(S+1) + i``
    # for flat levels — the dead roll-slack lanes (i == S) get zero rows.
    perm = np.zeros(prep.c_scratch, np.int64)
    live = np.zeros(prep.c_scratch, np.float32)
    for level in range(num_levels):
        off = prep.offsets[level]
        run = s if level in prep.ydot_levels else rl
        for j in range(s):
            for i in range(s):
                col = off + j * run + i
                perm[col] = level * s * s + i * s + j
                live[col] = 1.0
    w_mat = (kernel.reshape(c_in, c_out)[perm] * live[:, None]).astype(kernel.dtype)

    st = _XtapStatic(
        c_scratch=prep.c_scratch,
        out_dtype=jnp.dtype(proj_dtype).name if proj_dtype else None,
        query_tile=query_tile,
        interpret=interpret,
        project=True,
        c_out=c_out,
        mxu_dtype=jnp.dtype(proj_dtype).name if proj_dtype else None,
        q_width=w,
        **prep.static,
    )
    out = _partitioned_xtap(st)(
        _flat_cents(centroids), w_mat, bias.reshape(1, c_out), *prep.operands
    )

    return out.reshape(b, h, w, c_out)


def _fusable(pyramid: Sequence[jax.Array], s: int) -> bool:
    """Whether the kernel can run this pyramid.

    Flat-path levels (small, lane-dense packed) have no width constraint;
    y-dot-path levels need ``S+1 <= wl <= MAX_WIDTH``: the corner-b roll
    needs one slack lane past the S consumed taps, and widths beyond
    MAX_WIDTH would spend more than 4 chunked gathers per tap row (they
    fall back to the XLA separable path instead). Any width in range
    works — non-power-of-two level widths (every standard training crop:
    Chairs 62, Things 90, the Sintel stage 96) and >128 widths (KITTI's
    156) included."""
    ydot, _ = _split_levels(pyramid, s)
    return all(s + 1 <= pyramid[l].shape[2] <= MAX_WIDTH for l in ydot)


# ---------------------------------------------------------------------------
# Differentiable wrappers. pallas_call has no autodiff rule, but both fused
# functions are output-identical to their XLA formulations (oracle-tested),
# so: forward = Pallas kernel, backward = VJP of the XLA path. Gradients are
# exactly those of the reference semantics; training through
# corr_impl='fused' works (tested in tests/test_pallas.py).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def lookup_fused_diff(pyramid, flats, centroids, radius, weight_dtype,
                      query_tile, interpret):
    """``flats`` are the prepacked small levels (derived from ``pyramid``
    at build time; empty tuple = pack inside). Their cotangent is zero by
    construction: the forward's value equals the XLA path applied to
    ``pyramid`` alone, so the pyramid cotangent already carries the full
    dependence and the packing branch contributes nothing extra."""
    return lookup_pyramid_fused(
        list(pyramid), centroids, radius,
        weight_dtype=weight_dtype, query_tile=query_tile, interpret=interpret,
        flats=flats,
    )


def _lookup_fwd(pyramid, flats, centroids, radius, weight_dtype, query_tile,
                interpret):
    out = lookup_fused_diff(
        pyramid, flats, centroids, radius, weight_dtype, query_tile, interpret
    )
    return out, (pyramid, flats, centroids)


def _lookup_bwd(radius, weight_dtype, query_tile, interpret, res, g):
    pyramid, flats, centroids = res
    _, vjp = jax.vjp(
        lambda p, c: lookup_pyramid(p, c, radius, weight_dtype=weight_dtype),
        list(pyramid),
        centroids,
    )
    dp, dc = vjp(g)
    return type(pyramid)(dp), jax.tree.map(jnp.zeros_like, flats), dc


lookup_fused_diff.defvjp(_lookup_fwd, _lookup_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def project_fused_diff(
    pyramid, flats, centroids, kernel, bias, radius, weight_dtype, query_tile,
    interpret, proj_dtype,
):
    return lookup_project_fused(
        list(pyramid), centroids, kernel, bias, radius,
        weight_dtype=weight_dtype, proj_dtype=proj_dtype,
        query_tile=query_tile, interpret=interpret, flats=flats,
    )


def _project_fwd(
    pyramid, flats, centroids, kernel, bias, radius, weight_dtype, query_tile,
    interpret, proj_dtype,
):
    out = project_fused_diff(
        pyramid, flats, centroids, kernel, bias, radius, weight_dtype,
        query_tile, interpret, proj_dtype,
    )
    return out, (pyramid, flats, centroids, kernel, bias)


def _project_bwd(radius, weight_dtype, query_tile, interpret, proj_dtype,
                 res, g):
    pyramid, flats, centroids, kernel, bias = res

    def xla_path(p, c, k, b):
        taps = lookup_pyramid(p, c, radius, weight_dtype=weight_dtype)
        return project_taps(taps, k, b, dtype=proj_dtype)

    _, vjp = jax.vjp(xla_path, list(pyramid), centroids, kernel, bias)
    dp, dc, dk, db = vjp(g)
    return (
        type(pyramid)(dp),
        jax.tree.map(jnp.zeros_like, flats),
        dc,
        dk,
        db,
    )


project_fused_diff.defvjp(_project_fwd, _project_bwd)


class FusedLookupCorrBlock(CorrBlock):
    """Dense correlation block whose per-iteration lookup (and optionally
    the motion encoder's ``convcorr1`` projection, via ``index_project``)
    runs in the Pallas kernel (``corr_impl='fused'``).

    Numeric semantics are identical to :class:`CorrBlock` (parameter-free,
    oracle-tested), but ``build_pyramid`` returns this block's own pyramid
    structure: the standard pooled levels (>128-wide levels zero-padded to
    a lane multiple — equivalent data under zero-pad lookup semantics)
    plus lane-dense prepacked copies of the small levels for the kernel's
    flat path. The structure is opaque to the model (it only flows back
    into this block's methods). Every standard training/eval geometry is
    fusable (see :func:`_fusable`); the rare shape the kernel cannot
    handle (a y-dot level narrower than S+1 or wider than MAX_WIDTH)
    silently falls back to the XLA separable path, which is semantically
    identical, on the plain list of levels :class:`CorrBlock` builds.
    """

    def __init__(
        self,
        num_levels: int = 4,
        radius: int = 4,
        dtype=None,
        *,
        interpret: bool | None = None,
    ):
        super().__init__(num_levels=num_levels, radius=radius, dtype=dtype)
        self.interpret = interpret

    def _interpret(self) -> bool:
        if self.interpret is None:
            return jax.default_backend() == "cpu"
        return self.interpret

    def build_pyramid(self, fmap1: jax.Array, fmap2: jax.Array):
        """Standard pooled pyramid, plus — when the shapes are fusable —
        the small levels prepacked into lane-dense rows for the kernel's
        flat path. Packing here (once per pair) instead of in the lookup
        matters: XLA's while-loop invariant code motion refuses to hoist
        the size-increasing pad out of the refinement scan."""
        s = 2 * self.radius + 1
        levels = super().build_pyramid(fmap1, fmap2)
        if not _fusable(levels, s):
            return levels
        # lane-pad >128-wide levels ONCE here (outside the update scan —
        # XLA loop-ICM refuses size-increasing ops); zero pad data is
        # exactly out-of-range-tap semantics, so the XLA oracle/VJP paths
        # see an equivalent pyramid and every consumer splits identically
        levels = [_pad_width(v) for v in levels]
        _, flat_levels = _split_levels(levels, s)
        flats = tuple(
            _flat_pack(levels[l], levels[l].shape[0]) for l in flat_levels
        )
        return {"levels": levels, "flats": flats}

    @staticmethod
    def _unwrap(pyramid):
        if isinstance(pyramid, dict):
            return pyramid["levels"], pyramid["flats"]
        return pyramid, ()

    def resident_pyramid(self, pyramid):
        """The built ``pyramid`` in the shapes to HOLD it in across many
        lookups (the serve pool's slot state): each level the kernel
        takes as a raw ``(q, hl, wl)`` volume block — the y-dot levels,
        contracted in the kernel — zero-padded to whole ``(8, 128)``
        tiles. Zero data past the grid is exactly an out-of-range tap,
        so the lookup is unchanged; what changes is how the array lies
        in memory. The Mosaic call wants those operands row-major,
        tiled over ``(hl, wl)``. For a resident ``(slots, Q, 55, 128,
        1)`` leaf the TPU's default layout tiles over ``Q`` instead
        (55 rows would pad to 56; a 64-lane level gets ``Q`` on the
        lanes), and every lookup then begins with a relayout ``copy``
        of the whole level. A shape with no tile padding left to save
        gets the row-major default — the operand's own layout — and
        the buffers go to the kernel as they are
        (``tests/test_chip_compile.py`` holds the compiler to that).
        It costs the padding the operand carried anyway: ``[55, 128]``
        -> ``[56, 128]``, ``[27, 64]`` -> ``[32, 128]``. The small
        levels' raw copies (they reach the kernel as ``flats``) stay
        as built, and so does the plain list of levels built for a
        shape the kernel cannot take."""
        if not isinstance(pyramid, dict):
            return super().resident_pyramid(pyramid)
        levels = list(pyramid["levels"])
        for l in _split_levels(levels, 2 * self.radius + 1)[0]:
            hl, wl = levels[l].shape[1:3]
            pads = [(0, 0)] * levels[l].ndim
            pads[1:3] = (0, -hl % 8), (0, -wl % MAX_LANES)
            levels[l] = jnp.pad(levels[l], pads)
        return dict(pyramid, levels=levels)

    def kernel_rows(self, pyramid):
        """Shape specs of the kernel's blocked operands for the packed
        ``pyramid`` (arrays or specs, query rows leading): what
        :class:`_FusedPrep` hands :func:`_invoke_xtap` for it, asked of
        ``_FusedPrep`` itself."""
        levels, flats = self._unwrap(pyramid)
        return jax.eval_shape(
            lambda lv, fl: _FusedPrep(lv, self.radius, self.dtype, fl).operands,
            list(levels), tuple(flats),
        )

    def lookup_plan(self, pyramid, width: int):
        """The :class:`_Plan` the kernel makes for one lookup of
        ``pyramid`` by a query grid ``width`` wide — tile, coordinates
        blocked?, rows of each raw-volume level a grid step reads at a
        time: :func:`_plan_tile`, as :func:`_invoke_xtap` calls it, on
        :meth:`kernel_rows`; ``None`` for the plain levels of a shape the
        kernel does not run. Under a mesh the kernel runs per shard: hand
        this the rows one device holds (``serve.pool.state_layout``
        does, for ``ServeEngine.stats()``)."""
        if not isinstance(pyramid, dict):
            return None
        rows = self.kernel_rows(pyramid)
        ydot_levels, _ = _split_levels(pyramid["levels"], 2 * self.radius + 1)
        return _plan_tile(
            rows[0].shape[0], DEFAULT_QUERY_TILE, rows, ydot_levels, width,
            self.radius,
        )

    def lookup_rows(self, pyramid, centroids: jax.Array):
        """``(read, whole)``, int32 scalars: the 128-lane rows of the
        raw-volume levels ONE lookup of ``pyramid`` at ``centroids``
        brings into VMEM, and what reading every level whole would take
        — the same plan and the same window counts the call makes,
        computed beside it (no kernel runs). ``read / whole`` is the
        share of the y-dot levels the kernel read: the windows' heights
        over the levels' rows where every tile fits one window, more
        where the vertical flow spreads a tile's taps over several.
        ``None`` for a pyramid the kernel does not run. Under a mesh the
        call plans per shard, and so does this."""
        levels, _ = self._unwrap(pyramid)
        if not isinstance(pyramid, dict):
            return None
        rows = self.kernel_rows(pyramid)
        ydot_levels, _ = _split_levels(levels, 2 * self.radius + 1)
        return _rows_read(
            _flat_cents(centroids), rows, ydot_levels, centroids.shape[2],
            self.radius, DEFAULT_QUERY_TILE,
        )

    def index_pyramid(self, pyramid, centroids: jax.Array) -> jax.Array:
        levels, flats = self._unwrap(pyramid)
        if _fusable(levels, 2 * self.radius + 1):
            feats = lookup_fused_diff(
                tuple(levels),
                flats,
                centroids,
                self.radius,
                self.dtype,
                DEFAULT_QUERY_TILE,
                self._interpret(),
            )
        else:
            feats = lookup_pyramid(
                levels, centroids, self.radius, weight_dtype=self.dtype
            )
        b, h, w, _ = centroids.shape
        assert feats.shape == (b, h, w, self.out_channels)
        return feats

    def index_project(
        self,
        pyramid,
        centroids: jax.Array,
        kernel: jax.Array,
        bias: jax.Array,
        *,
        dtype=None,
    ) -> jax.Array:
        """Lookup + ``convcorr1`` in one Pallas kernel (the tap tensor
        never reaches HBM); XLA fallback for non-fusable shapes."""
        levels, flats = self._unwrap(pyramid)
        if not _fusable(levels, 2 * self.radius + 1):
            return super().index_project(
                levels, centroids, kernel, bias, dtype=dtype
            )
        out = project_fused_diff(
            tuple(levels),
            flats,
            centroids,
            kernel,
            bias,
            self.radius,
            self.dtype,
            DEFAULT_QUERY_TILE,
            self._interpret(),
            dtype,
        )
        b, h, w, _ = centroids.shape
        assert out.shape == (b, h, w, kernel.shape[-1])
        return out
