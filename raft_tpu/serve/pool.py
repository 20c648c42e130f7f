"""Resident GRU-iteration pool: iteration-level continuous batching.

RAFT's refinement loop is an *anytime* ladder — every GRU iteration emits
a valid flow — which makes the whole-request dispatch unit wrong for
serving: a request that wants 12 iterations should not hold a batch slot
while its neighbors run to 32. This module holds the state machinery for
the serve engine's iteration pool (the LLM continuous-batching idea, Yu
et al., OSDI '22, applied to RAFT's recurrence): a fixed-capacity
on-device slot array of per-request recurrent state, advanced one
``RAFT.iterate_step`` per dispatch. Requests join a free slot when
admitted, leave the moment their own iteration target is met (per-request
``num_flow_updates``, a degradation target, or a deadline-driven early
exit), and late arrivals fill freed slots mid-flight — so admission-to-
first-dispatch latency is one iteration time and padding waste under
mixed iteration counts goes to ~0.

The compiled-program set stays closed and warmable, per bucket:

  * ``begin_pair`` / ``begin_refinement`` — admission encode + state init,
    one program per admission rung (``ServeConfig.resolved_admit_ladder``);
  * ``insert`` — write the whole admission cohort's rows into their
    slots in ONE dispatch, with the slot-index and validity-mask vectors
    *traced* (one program per rung, not per slot or per request);
  * ``step`` — ONE refinement iteration across all ``pool_capacity``
    slots (one program total);
  * ``gather`` + ``final`` — pull finished slots' carry and run the final
    convex upsample, one program per retirement rung.

Convergence telemetry (ISSUE 11): the step program additionally reduces
each slot's **flow-update residual** on device — the per-slot RMS of
``delta_flow = coords1' - coords1`` over the 1/8-resolution grid, RAFT's
natural convergence signal — into a rolling ``(capacity, resid_len)``
history (``state['resid_hist']``) that rides the state pytree. One fused
reduce inside the existing step dispatch, fetched by the existing
retirement gather: zero extra host syncs, zero extra programs. The flow
math is untouched (the residual is a pure *observer* of the coords the
step already computes — pinned bitwise in tests).

Residual-driven early exit (ISSUE 12) *spends* that signal: the step
program compares each slot's latest residuals — a streak of
``converge_streak`` consecutive entries of ``resid_hist`` all below
``converge_thresh`` — and maintains a per-slot ``state['converged']``
bitmask. A slot that was already converged at dispatch time is **frozen**
via ``jnp.where``: its coords/hidden/history pass through bitwise
unchanged (no state churn), so the flow a converged request eventually
finalizes is exactly the flow at its freeze iteration. The mask, packed
to bytes (``jnp.packbits``), IS the tick pacing token — the host learns
about convergence on the pacing-token fetch it already pays, zero new
host syncs. Both knobs are *traced* scalars (``thresh <= 0`` disables),
so the program set is unchanged by enabling/disabling convergence and
one compiled step program serves any threshold. Admission seeds the
residual history with a large sentinel (``RESID_SENTINEL``) so a fresh
slot can never look converged before it has run ``streak`` real
iterations; the host-side trajectory read only ever touches the last
``min(done, resid_len)`` entries, so the sentinel is invisible there.

Memory note: slot state is dominated by the correlation pyramid — the
same footprint the fallback engine pays for a ``max_batch`` whole-request
batch. ``insert`` donates the pool state (single-device; see the
in-class note for the mesh exception) so slot writes are in-place
scatters, never a pool-sized copy; ``step`` returns only the recurrent
carry (coords + hidden) plus a scalar pacing token, so the pyramid is
never copied per tick.

That last clause also needs the state to hold the pyramid the way the
lookup kernel reads it: a ``step`` lowered against a level whose
default layout is not the kernel operand's begins with a relayout
``copy`` of the whole level — 7.6 of raft_large's 16.0 ms tick at 16
slots, until PR 29. It is kept true by shape, at admission:
``RAFT.begin_refinement`` holds the fused block's packed pyramid in
``FusedLookupCorrBlock.resident_pyramid``'s shapes (raw-volume levels
zero-padded to whole ``(8, 128)`` tiles, for which the TPU's default
layout is row-major, the operand's), ``state_spec`` / ``zero_state``
derive the pool's leaves from that row, and ``insert`` writes like into
like. No layout is named anywhere, so the programs, their specs, the
compile cache and the warm-up artifact carry nothing new (a pinned
``jax.experimental.layout.Format`` did the same on the chip until a
program came back from the persistent cache expecting the default
layout again: PERF.md, PR 29). ``tests/test_chip_compile.py`` compiles
the lookup from ``state_spec``'s leaves for a described v5e and fails
on a ``copy`` of a level.
"""

from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "PoolPrograms", "BucketPool", "state_spec", "zero_state", "state_layout",
    "RESID_HISTORY", "RESID_SENTINEL", "unpack_converged",
    "unpack_lookup_rows",
]

# Default length of the rolling per-slot residual history. The engine
# passes its full-quality iteration target (``ladder[0]``) instead, so a
# request's whole trajectory fits; direct callers get a sane bound.
RESID_HISTORY = 32

# Admission seed for the residual history: any value comfortably above
# every plausible convergence threshold, so the streak test over a fresh
# slot's not-yet-written history positions can never read "converged".
# (Finite rather than inf: the history leaf must stay safely arithmetic-
# friendly under future reductions.)
RESID_SENTINEL = 1e30


def unpack_converged(packed, capacity: int):
    """Host-side inverse of the step program's ``jnp.packbits`` pacing
    token: the per-slot converged bool vector for ``capacity`` slots
    (the mask's bytes lead the token; what may follow them is
    :func:`unpack_lookup_rows`')."""
    import numpy as np

    return np.unpackbits(np.asarray(packed, np.uint8))[:capacity].astype(bool)


def unpack_lookup_rows(packed, capacity: int):
    """``(read, whole)`` off a fetched pacing token, or ``None`` where the
    step program appended none (a correlation block with no windowed
    lookup): the two int32 counts of ``lookup_rows`` for that tick, as
    eight little-endian bytes after the ``ceil(capacity / 8)`` bytes of
    the converged mask."""
    import numpy as np

    tail = np.asarray(packed, np.uint8)[-(-capacity // 8):]
    if tail.size != 8:
        return None
    read, whole = tail.view("<i4")
    return int(read), int(whole)


@dataclasses.dataclass
class _SlotMeta:
    """Host-side bookkeeping for one resident request."""

    req: Any                 # serve.queue.Request
    target: int              # iterations this request runs (admission-time)
    level: int               # degradation level it was admitted at
    done: int = 0            # iterate_step dispatches applied so far
    admitted_t: float = 0.0  # time.monotonic() at admission
    warm: bool = False       # admitted with a warm-start initial flow
    # residual-driven early exit (ISSUE 12): set when a fetched pacing
    # token reports this slot's flow converged on device. The device
    # froze the slot from the tick AFTER detection, so `converged_done`
    # (the slot's done count at the detecting tick) is the number of
    # iterations the frozen flow actually reflects — later ticks changed
    # nothing (bitwise) and are accounted as idle slot-iterations.
    converged: bool = False
    converged_done: int = 0
    # a stream pair's new frame: (its admission cohort's device array of
    # the encoders' finite flags, its lane), read at retirement
    frame_ok: Optional[Tuple[Any, int]] = None

    def frame_finite(self) -> bool:
        """Whether the encoders made finite features of this slot's new
        frame (True for a pair admitted whole: its flow says so). A read
        of a few bytes computed an admission ago."""
        if self.frame_ok is None:
            return True
        import numpy as np

        flags, lane = self.frame_ok
        return bool(np.asarray(flags)[lane])


def _insert_rows(state, rows, idx, mask):
    """Write every admitted row of ``rows`` into its pool slot, in ONE
    program (per admission-rung shape of ``rows``).

    ``idx[j]`` is the slot row ``j`` lands in and ``mask[j]`` whether
    row ``j`` is a real admission (padding lanes carry ``False`` and
    touch nothing) — both traced vectors, so one compiled program per
    rung covers every (rows, slots) assignment. The scan applies writes
    in row order with an in-place carry; ISSUE 8 batched what was one
    dispatch per admitted request into one dispatch per admission
    cohort (the per-request inserts dominated mesh admission cost).
    The caller jits this with ``donate_argnums=(0,)`` on a single
    device so the writes scatter into the donated pool state in place
    (donation is withheld under a mesh — see :class:`PoolPrograms`).
    """

    def body(st, xs):
        row, i, m = xs
        upd = jax.tree_util.tree_map(
            lambda s, r: jax.lax.dynamic_update_index_in_dim(s, r, i, 0),
            st,
            row,
        )
        st = jax.tree_util.tree_map(
            lambda u, s: jnp.where(m, u, s), upd, st
        )
        return st, ()

    state, _ = jax.lax.scan(body, state, (rows, idx, mask))
    return state


def _gather_carry(coords1, hidden, resid_hist, idx):
    """Pull the recurrent carry + residual history of the slots in
    ``idx`` (one program per retirement-rung ``idx`` length)."""
    return coords1[idx], hidden[idx], resid_hist[idx]


class PoolPrograms:
    """The closed jitted program set of the iteration pool.

    With ``mesh`` (ISSUE 8) every program carries explicit
    ``in_shardings`` — weights replicated, slot/batch-leading trees
    sharded over the mesh ``data`` axis, scalar/index args replicated —
    so the jit path and the AOT ``.lower(specs).compile()`` path both
    produce SPMD-partitioned executables, and dispatching host numpy
    buffers shards them automatically. ``mesh=None`` is byte-for-byte
    the single-device program set.
    """

    def __init__(self, model, mesh=None, resid_len: int = RESID_HISTORY):
        self.resid_len = int(resid_len)
        if self.resid_len < 1:
            raise ValueError(f"resid_len must be >= 1, got {resid_len}")

        def sh(ins, out):
            """in/out sharding kwargs from 'row'/'rep' spec strings.

            Outputs are PINNED, not left to GSPMD inference: the pool
            programs chain into each other (begin -> insert -> step ->
            gather -> final), so every slot/batch-leading tree must come
            out row-sharded or the next program's ``in_shardings`` would
            reject the committed array."""
            if mesh is None:
                return {}
            from raft_tpu.parallel.serve_shard import replicated, row_sharding

            table = {"row": row_sharding(mesh), "rep": replicated(mesh)}
            kw = {"in_shardings": tuple(table[s] for s in ins)}
            kw["out_shardings"] = (
                table[out] if isinstance(out, str)
                else tuple(table[s] for s in out)
            )
            return kw

        R = self.resid_len
        # traced under the serve mesh so the fused lookup kernel
        # shard_maps itself over the slot rows (identity off-mesh)
        from raft_tpu.parallel.mesh import traced_under

        apply = traced_under(mesh, model.apply)
        lookup_rows = getattr(
            getattr(model, "corr_block", None), "lookup_rows", None
        )
        if lookup_rows is not None:
            block_rows = traced_under(mesh, lookup_rows)

            def lookup_rows(pyramid, coords):
                # iterate_step's view of the state: slots folded into rows
                return block_rows(
                    jax.tree.map(
                        lambda v: v.reshape(
                            (v.shape[0] * v.shape[1],) + v.shape[2:]
                        ),
                        pyramid,
                    ),
                    coords,
                )

        def _with_hist(rows):
            # admission rows start with a sentinel-seeded residual
            # history (so a fresh slot cannot satisfy a convergence
            # streak before running `streak` real iterations) and a
            # cleared converged bit, keeping the state tree the insert
            # scatters shape-congruent
            rows = dict(rows)
            rows["resid_hist"] = jnp.full(
                (rows["coords1"].shape[0], R), RESID_SENTINEL, jnp.float32
            )
            rows["converged"] = jnp.zeros(
                (rows["coords1"].shape[0],), jnp.bool_
            )
            return rows

        # Each program is a function of this instance with a name of its
        # own: the name is the program's in a device trace (``jit_<name>``;
        # lambdas all read ``jit__lambda``), and a fresh function object
        # per instance keeps jax's compiled-program cache — keyed on the
        # FUNCTION OBJECT — per engine, which ``program_counts()`` counts.
        # ``_step`` keeps its name: the benchmark reads ``jit__step``.
        def pool_begin_pair(variables, image1, image2):
            return _with_hist(
                apply(
                    variables, image1, image2, train=False,
                    method="begin_pair",
                )
            )

        self.begin_pair = jax.jit(
            pool_begin_pair, **sh(("rep", "row", "row"), "row")
        )
        # Stream admission takes the warm-start initial flow as a TRACED
        # input (ISSUE 12): zeros reproduce the cold start bitwise, a
        # forward-interpolated previous-pair flow seeds coords1 near the fixed
        # point — one compiled program either way.
        def pool_begin_features(variables, fmap1, fmap2, context_out,
                                init_flow):
            return _with_hist(
                apply(
                    variables, fmap1, fmap2, context_out,
                    init_flow=init_flow, train=False,
                    method="begin_refinement",
                )
            )

        self.begin_features = jax.jit(
            pool_begin_features,
            **sh(("rep", "row", "row", "row", "row"), "row"),
        )

        def _step(variables, state, thresh, streak, min_iters):
            out = apply(variables, state, train=False,
                              method="iterate_step")
            # Convergence telemetry (ISSUE 11): per-slot RMS of this
            # iteration's flow update (1/8-grid pixels), rolled into the
            # bounded residual history. A pure observer of coords the
            # step already computes — the flow output stays bitwise
            # identical to the uninstrumented step (pinned in tests).
            delta = out["coords1"] - state["coords1"]
            resid = jnp.sqrt(
                jnp.mean(jnp.sum(delta * delta, axis=-1), axis=(1, 2))
            )
            hist = jnp.concatenate(
                [state["resid_hist"][:, 1:], resid[:, None]], axis=1
            )
            # Residual-driven early exit (ISSUE 12): a slot already
            # converged at dispatch time FREEZES — coords/hidden/history
            # pass through bitwise unchanged, so the finalized flow is
            # exactly the flow at the freeze iteration. Unconverged
            # slots' outputs are the jnp.where pass-through of the very
            # values computed above — bitwise identical to the
            # convergence-free step (pinned in tests).
            frozen = state["converged"]
            coords1 = jnp.where(
                frozen[:, None, None, None], state["coords1"], out["coords1"]
            )
            hidden = jnp.where(
                frozen[:, None, None, None], state["hidden"], out["hidden"]
            )
            hist = jnp.where(frozen[:, None], state["resid_hist"], hist)
            # streak test over the history tail: positions
            # [R - streak, R) all below thresh. All three knobs are
            # traced scalars — thresh <= 0 disables without a recompile.
            tail = jnp.arange(R) >= (R - streak)
            streak_ok = jnp.all(
                jnp.where(tail[None, :], hist < thresh, True), axis=1
            )
            # age gate: a slot may only freeze once it has run at least
            # `min_iters` REAL iterations — the m-th-newest history
            # position still holds the admission sentinel otherwise.
            # Enforced ON DEVICE so a frozen slot always satisfies the
            # host's pool_min_iters retirement floor (no freeze-below-
            # floor deadlock, no wasted frozen ticks waiting to age).
            m = jnp.clip(jnp.maximum(streak, min_iters), 1, R)
            age_ok = (
                jnp.take_along_axis(
                    hist, jnp.full((hist.shape[0], 1), R, jnp.int32) - m,
                    axis=1,
                )[:, 0]
                < RESID_SENTINEL * 0.5
            )
            converged = frozen | (streak_ok & age_ok & (thresh > 0.0))
            # The packed converged mask IS the pacing token: the worker
            # paces the dispatch pipeline on its fetch (as before) and
            # now ALSO learns which slots froze — on the same fetch,
            # zero new host syncs. (A token also keeps the worker from
            # holding a buffer a later insert might donate.)
            token = jnp.packbits(converged.astype(jnp.uint8))
            # ... and where the lookup kernel reads its levels by window,
            # how many rows this tick's lookup read and what whole
            # levels would have taken (unpack_lookup_rows): eight bytes
            # more on the same fetch, summed on the host
            if lookup_rows is not None:
                rows = lookup_rows(state["pyramid"], state["coords1"])
                if rows is not None:
                    token = jnp.concatenate([
                        token,
                        jax.lax.bitcast_convert_type(
                            jnp.stack(rows), jnp.uint8
                        ).reshape(-1),
                    ])
            return coords1, hidden, hist, converged, token

        self.step = jax.jit(
            _step,
            **sh(
                ("rep", "row", "rep", "rep", "rep"),
                ("row", "row", "row", "row", "rep"),
            ),
        )
        def pool_final(variables, coords1, hidden):
            return apply(
                variables, coords1, hidden, train=False,
                method="finalize_flow",
            )

        self.final = jax.jit(pool_final, **sh(("rep", "row", "row"), "row"))

        # Donation is single-device only: deserializing an SPMD
        # executable that carries input-output aliasing segfaults on
        # this jaxlib (serialize_executable + donate_argnums +
        # multi-device CPU, reproduced 2/3 runs; isolated in ISSUE 8).
        # A mesh insert therefore pays one pool-state copy per admission
        # dispatch — admissions are rare next to ticks — and the whole
        # insert pipeline (jit fallback, AOT warmup, artifact) stays one
        # consistent non-donating program. Revisit on a jaxlib where
        # aliased deserialization holds, and on real-TPU bringup.
        def pool_insert(state, rows, idx, mask):
            return _insert_rows(state, rows, idx, mask)

        self.insert = jax.jit(
            pool_insert,
            **({"donate_argnums": (0,)} if mesh is None else {}),
            **sh(("row", "row", "rep", "rep"), "row"),
        )
        # the retiring-slot index vector stays replicated: every device
        # must see which (sharded) slots the gather pulls. Since ISSUE 11
        # the gather also pulls the retiring slots' residual histories —
        # the trajectories ride the fetch the finalize already pays.
        def pool_gather(coords1, hidden, resid_hist, idx):
            return _gather_carry(coords1, hidden, resid_hist, idx)

        self.gather = jax.jit(
            pool_gather,
            **sh(("row", "row", "row", "rep"), ("row", "row", "row")),
        )

    def counts(self) -> Dict[str, int]:
        """Compiled-program count per pool program (-1 if unsupported)."""

        def n(f) -> int:
            try:
                return int(f._cache_size())
            except Exception:  # pragma: no cover - jax internals moved
                return -1

        return {
            "pool_begin_pair": n(self.begin_pair),
            "pool_begin_features": n(self.begin_features),
            "pool_step": n(self.step),
            "pool_final": n(self.final),
            "pool_insert": n(self.insert),
            "pool_gather": n(self.gather),
        }


def state_spec(model, variables, capacity: int, bucket: Tuple[int, int],
               resid_len: int = RESID_HISTORY):
    """Shape/dtype spec of a ``capacity``-slot pool state for ``bucket``
    (``jax.eval_shape`` only — no compute, no allocation). ``variables``
    may itself be a spec tree; this is what AOT warmup lowers the pool
    programs against (:mod:`raft_tpu.serve.aot`). ``resid_len`` must
    match the owning :class:`PoolPrograms` — the residual history rides
    the state tree."""
    bh, bw = bucket
    spec = jax.ShapeDtypeStruct((1, bh, bw, 3), jnp.float32)
    row = jax.eval_shape(
        partial(model.apply, train=False, method="begin_pair"),
        variables, spec, spec,
    )
    st = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((capacity,) + s.shape[1:], s.dtype),
        row,
    )
    st["resid_hist"] = jax.ShapeDtypeStruct(
        (capacity, int(resid_len)), jnp.float32
    )
    st["converged"] = jax.ShapeDtypeStruct((capacity,), jnp.bool_)
    return st


def zero_state(model, variables, capacity: int, bucket: Tuple[int, int],
               sharding=None, resid_len: int = RESID_HISTORY):
    """Allocate an all-zeros pool state for ``capacity`` slots of
    ``bucket`` (shapes derived via ``jax.eval_shape`` — no compute).

    ``sharding`` (a slot-dim ``NamedSharding``) places the slot table
    sharded over the serve mesh in ONE host-zeros ``jax.device_put`` of
    the whole tree — a transfer, not a compile, so a sharded pool
    allocation adds zero backend-compile events to an artifact boot."""
    spec = state_spec(model, variables, capacity, bucket, resid_len)
    if sharding is None:
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), spec
        )
    import numpy as np

    host = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), spec
    )
    return jax.device_put(
        host, jax.tree_util.tree_map(lambda _: sharding, spec)
    )


def state_layout(model, state) -> Dict[str, Any]:
    """What a pool's ``state`` costs and how the lookup kernel reads it,
    from shapes alone: bytes resident (``state_bytes``; ``slot_bytes`` a
    slot) and, where the state holds the fused block's packed pyramid,
    the kernel's query tile, whether its coordinate operand is blocked
    by tile, and per raw-volume level the rows it holds resident
    (``level_rows``) and the rows a grid step reads of them at a time
    (``window_rows``; equal where the level is read whole) — ``None``
    otherwise: the block's own ``lookup_plan``, which is the plan the
    kernel call makes. The kernel plans from the rows ONE device holds
    (under a mesh it runs per shard), so the plan is asked for each
    leaf's shard shape. ``lookup_rows_read`` / ``lookup_rows_whole``
    start at 0: :meth:`BucketPool.note_drain` sums what the ticks'
    tokens report."""
    leaves = jax.tree_util.tree_leaves(state)
    capacity = int(leaves[0].shape[0])
    state_bytes = sum(
        int(x.size) * jnp.dtype(x.dtype).itemsize for x in leaves
    )
    plan = None
    ask = getattr(getattr(model, "corr_block", None), "lookup_plan", None)
    if ask is not None:
        def rows(v):
            shape = v.sharding.shard_shape(v.shape)
            return jax.ShapeDtypeStruct(
                (shape[0] * shape[1],) + shape[2:], v.dtype
            )

        pyramid = jax.tree_util.tree_map(rows, state["pyramid"])
        plan = ask(pyramid, state["coords1"].shape[2])
    layout = {
        "state_bytes": state_bytes,
        "slot_bytes": state_bytes // capacity,
        "query_tile": plan and plan.tile,
        "coords_blocked": plan and plan.coords_blocked,
        "level_rows": plan and list(plan.rows),
        "window_rows": plan and list(plan.heights),
    }
    if plan:
        layout.update(lookup_rows_read=0, lookup_rows_whole=0)
    return layout


class BucketPool:
    """One bucket's resident slot array + host-side slot table."""

    def __init__(self, bucket: Tuple[int, int], capacity: int, state,
                 layout: Optional[Dict[str, Any]] = None):
        self.bucket = bucket
        self.capacity = int(capacity)
        self.state = state                     # device pytree, lead dim = capacity
        self.layout = layout or {}             # state_layout(), for stats()
        self.slots: List[Optional[_SlotMeta]] = [None] * self.capacity
        self._free: List[int] = list(range(self.capacity - 1, -1, -1))
        # dispatched-but-unfetched tick tokens (the pacing window):
        # (dispatch time, packed-converged-mask token, occupants) where
        # occupants snapshots (slot, rid, done-after-tick) at dispatch —
        # a fetched mask bit is only believed for the same (slot, rid)
        # it was dispatched for, so a freed-and-reused slot can never
        # inherit the previous occupant's convergence (ISSUE 12)
        self.pending: "collections.deque[Tuple[float, Any, Tuple]]" = (
            collections.deque()
        )
        self.tick_ewma_ms = 50.0               # device time per tick (est.)
        self.last_drain_t: Optional[float] = None

    def occupied(self) -> List[Tuple[int, _SlotMeta]]:
        return [(i, m) for i, m in enumerate(self.slots) if m is not None]

    def occupied_count(self) -> int:
        return self.capacity - len(self._free)

    def free_count(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        return self._free.pop()

    def release(self, i: int) -> None:
        self.slots[i] = None
        self._free.append(i)
        if len(self._free) == self.capacity:
            # pool went idle: drop pacing state so the next burst doesn't
            # inherit a stale tick-time sample or hold dead tokens
            self.pending.clear()
            self.last_drain_t = None

    def clear(self) -> List[_SlotMeta]:
        """Empty every slot (callers fail/finish the requests); returns
        the evicted metas."""
        metas = [m for m in self.slots if m is not None]
        self.slots = [None] * self.capacity
        self._free = list(range(self.capacity - 1, -1, -1))
        self.pending.clear()
        self.last_drain_t = None
        return metas

    def await_rows(self) -> None:
        """Block until the last admission's rows are in their slots, so
        that the buffer they were computed into is free before the next
        admission's is allocated (a dispatch allocates its outputs when
        it is enqueued, not when it runs). A row is the size of a slot:
        3.3 GB at 1088x1920, where two admissions enqueued a tick apart
        — both slots free while clients are still joining — held 13.4 GB
        where 10.0 is the pool's peak, 92.7% of the chip with the
        programs' reservation (builder's chip runs, PR 34; the same race
        read as 16.3 GB in PR 33's). The pyramid leaves are ``insert``'s
        own outputs, and no tick replaces them, so this waits for the
        last admission's ``insert`` and for nothing dispatched after it.
        Admission runs before the loop reads its retirement, so the wait
        can be real; but that ``insert`` was dispatched a loop ago or
        more, and the tick and the retirement dispatched since are queued
        behind it: the device stays busy while the host waits."""
        jax.block_until_ready(self.state["pyramid"])

    def note_drain(self, now: float, token=None) -> None:
        """One pipeline drain completed: fold the drain-to-drain gap into
        the tick-time estimate (host loop rate == device tick rate at
        steady state; the clamp keeps a scheduling stall from blowing up
        the EWMA), and add the lookup's row counts the fetched ``token``
        carries to the layout ``stats()`` reports."""
        rows = None if token is None else unpack_lookup_rows(
            token, self.capacity
        )
        if rows is not None:
            self.layout["lookup_rows_read"] += rows[0]
            self.layout["lookup_rows_whole"] += rows[1]
        if self.last_drain_t is not None:
            dt = (now - self.last_drain_t) * 1e3
            dt = min(dt, 10.0 * self.tick_ewma_ms)
            self.tick_ewma_ms += 0.25 * (dt - self.tick_ewma_ms)
        self.last_drain_t = now
