"""Device-resident stream-session cache: a video frame's features never
leave the device between the program that encodes it and the program
that pairs it.

A stream session (:meth:`ServeEngine.open_stream`) pairs every frame with
the one before it, so frame ``t``'s feature map and context output are
needed twice: as pair ``(t-1, t)``'s second frame and as pair
``(t, t+1)``'s first. This module holds them where both uses are — one
table a bucket on the device, ``stream_cache_size`` rows of

  * ``fmap`` / ``ctx`` — the ``encode_frame`` program's outputs, in the
    dtype it computes them in (bf16 at the ``throughput`` preset), so a
    stream pair's ``pool_begin_features`` reads bit for bit what
    ``pool_begin_pair`` computes for the same two frames;
  * ``flow`` — the session's last pair's final 1/8-grid flow
    (``coords1 - coords0``, fp32), written by the retirement's
    ``stream_store_flow`` program, for the warm start

— and the host's index into it: which session holds which row
(:class:`StreamCache`, LRU-bounded; a row is 7.3 MB at 440x1024 with
raft_large's widths in bf16, 33 MB at 1088x1920). The host sends frames
and row indices and fetches nothing: an admission is ``encode_frame`` ->
``stream_swap`` (gather the sessions' previous rows, write the new ones
in place, interpolate the warm-start seeds) -> ``pool_begin_features``
-> ``insert``, every operand but the frames and the index vectors a
device array. Both of the engine's stream paths (the pool's admission
and the ``pool_capacity=0`` worker) share the one cache.

Warm start is upstream's (princeton-vl/RAFT ``core/utils/utils.py::
forward_interpolate``, as ``evaluate.py::create_sintel_submission`` uses
it): :func:`forward_interpolate`, computed on the device inside
``stream_swap``.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "forward_interpolate", "encode_frame_program", "StreamCache",
    "StreamPrograms", "Cohort",
]

# the interpolation's distance block, (targets, Q) fp32: the one temporary
# that grows with the frame (Q^2 whole: 198 MB at 440x1024, 4.3 GB at
# 1088x1920)
_BLOCK_BYTES = 8 << 20


def _block_targets(q: int) -> int:
    """Target cells a distance block holds: the largest power of two
    whose ``(targets, q)`` fp32 block fits ``_BLOCK_BYTES`` (256 at
    440x1024, 64 at 1088x1920), at least 8."""
    t = max(8, _BLOCK_BYTES // (4 * q))
    return 1 << (t.bit_length() - 1)


def forward_interpolate(flow):
    """Upstream's video warm start for one ``(h8, w8, 2)`` flow field:
    every source cell ``p`` lands at ``p + flow[p]``; landing points
    strictly inside ``(0, w8) x (0, h8)`` are kept; every grid cell takes
    the flow of the nearest kept point (squared Euclidean distance in
    fp32, ties to the lowest source index); zeros if none is kept.

    Upstream scatters with ``scipy.interpolate.griddata(...,
    method='nearest')``; this is the same nearest search by brute force,
    in blocks of target cells so that no ``Q x Q`` array exists.
    """
    h, w = int(flow.shape[0]), int(flow.shape[1])
    q = h * w
    flow = flow.astype(jnp.float32)
    ys, xs = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32), jnp.arange(w, dtype=jnp.float32),
        indexing="ij",
    )
    px = (xs + flow[..., 0]).reshape(q)
    py = (ys + flow[..., 1]).reshape(q)
    keep = (px > 0) & (px < w) & (py > 0) & (py < h)
    vec = flow.reshape(q, 2)
    t = _block_targets(q)
    blocks = -(-q // t)

    def nearest(b):
        cell = b * t + jnp.arange(t, dtype=jnp.int32)
        tx = (cell % w).astype(jnp.float32)
        ty = (cell // w).astype(jnp.float32)
        dx = tx[:, None] - px[None, :]
        dy = ty[:, None] - py[None, :]
        d2 = jnp.where(keep[None, :], dx * dx + dy * dy, jnp.inf)
        return jnp.argmin(d2, axis=1).astype(jnp.int32)

    src = jax.lax.map(nearest, jnp.arange(blocks, dtype=jnp.int32))
    out = vec[src.reshape(blocks * t)[:q]].reshape(h, w, 2)
    return jnp.where(keep.any(), out, 0.0)


def encode_frame_program(apply):
    """The stream path's encode program over ``apply`` (the model's, as
    the engine traces it): one frame batch through both encoders, and
    whether each frame's features came out finite — the poisoned-frame
    check, computed where the features are."""

    def encode_frame(variables, frames):
        fmap, ctx = apply(
            variables, frames, train=False, method="encode_frame"
        )
        finite = jnp.isfinite(fmap).all(axis=(1, 2, 3)) & (
            jnp.isfinite(ctx).all(axis=(1, 2, 3))
        )
        return fmap, ctx, finite

    return encode_frame


def _write_rows(leaf, rows, idx, mask):
    """``leaf[idx[j]] = rows[j]`` where ``mask[j]``, in lane order, as
    in-place row updates of a donated table (a lane that is masked off
    rewrites its target row with itself)."""

    def body(acc, xs):
        row, i, m = xs
        old = jax.lax.dynamic_index_in_dim(acc, i, 0, keepdims=False)
        new = jnp.where(m, row.astype(acc.dtype), old)
        return jax.lax.dynamic_update_index_in_dim(acc, new, i, 0), ()

    leaf, _ = jax.lax.scan(body, leaf, (rows, idx, mask))
    return leaf


class StreamPrograms:
    """The two programs that touch the session table. ``stream_swap`` is
    an admission's (one a cohort of frames), ``stream_store_flow`` a
    retirement's (only compiled with warm start on). Both take the table
    donated, so rows are written in place — off a mesh: as for the pool's
    ``insert``, an aliased SPMD executable does not survive this jaxlib's
    serialisation, and under a mesh the table is replicated and copied."""

    def __init__(self, warm_start: bool, mesh=None):
        self.warm_start = bool(warm_start)
        donate = {"donate_argnums": (0,)} if mesh is None else {}

        def sh(ins, outs):
            if mesh is None:
                return {}
            from raft_tpu.parallel.serve_shard import replicated, row_sharding

            table = {"row": row_sharding(mesh), "rep": replicated(mesh)}
            return {
                "in_shardings": tuple(table[s] for s in ins),
                "out_shardings": (
                    table[outs] if isinstance(outs, str)
                    else tuple(table[s] for s in outs)
                ),
            }

        def stream_swap(table, fmap, ctx, idx, put, warm):
            """Gather the sessions' previous rows, then write the new
            frame's. ``idx[j]`` is lane ``j``'s row, ``put[j]`` whether
            the lane is a real frame, ``warm[j]`` whether its pair starts
            from the interpolated flow of the session's last pair (zeros
            otherwise: the cold start, bit for bit)."""
            row = lambda leaf, i: jax.lax.dynamic_index_in_dim(
                leaf, i, 0, keepdims=False
            )
            put_row = jax.lax.dynamic_update_index_in_dim

            def lane(tab, xs):
                # one lane at a time, rows read and written where they
                # lie: a batched gather of whole rows costs the compiler
                # three rows of temporaries a lane
                f_new, c_new, i, m, w = xs
                f_old, c_old = row(tab["fmap"], i), row(tab["ctx"], i)
                if self.warm_start:
                    init = jax.lax.cond(
                        w, forward_interpolate, jnp.zeros_like,
                        row(tab["flow"], i),
                    )
                else:
                    init = jnp.zeros(tab["flow"].shape[1:], jnp.float32)
                tab = {
                    **tab,
                    "fmap": put_row(
                        tab["fmap"], jnp.where(m, f_new, f_old), i, 0
                    ),
                    "ctx": put_row(
                        tab["ctx"], jnp.where(m, c_new, c_old), i, 0
                    ),
                }
                return tab, (f_old, c_old, init)

            table, (prev_f, prev_c, init) = jax.lax.scan(
                lane, table, (fmap, ctx, idx, put, warm)
            )
            return table, prev_f, prev_c, init

        self.swap = jax.jit(
            stream_swap, **donate,
            **sh(("rep", "row", "row", "rep", "rep", "rep"),
                 ("rep", "row", "row", "row")),
        )

        def stream_store_flow(table, coords1, idx, mask):
            """A retiring pair's final ``coords1 - coords0`` into its
            session's row, for the next pair's warm start."""
            from raft_tpu.ops.sampling import coords_grid

            b, h8, w8, _ = coords1.shape
            flow = coords1.astype(jnp.float32) - coords_grid(b, h8, w8)
            return {
                **table, "flow": _write_rows(table["flow"], flow, idx, mask),
            }

        self.store_flow = jax.jit(
            stream_store_flow, **donate,
            **sh(("rep", "row", "rep", "rep"), "rep"),
        ) if self.warm_start else None

    def counts(self) -> Dict[str, int]:
        def n(f) -> int:
            if f is None:
                return 0
            try:
                return int(f._cache_size())
            except Exception:  # pragma: no cover - jax internals moved
                return -1

        return {"stream_swap": n(self.swap),
                "stream_store_flow": n(self.store_flow)}


class _Session:
    """The host's side of one session: where its row is, and what the
    row holds."""

    __slots__ = ("sid", "bucket", "hw", "busy", "row", "has_flow")

    def __init__(self, sid: int, bucket: Tuple[int, int], hw: Tuple[int, int]):
        self.sid = sid
        self.bucket = bucket
        self.hw = hw
        self.busy = False        # one frame in flight a session
        # the row of its bucket's table that holds the last frame's
        # features; None until a frame primes it (and again after an
        # invalidation, an eviction of the row, a change of resolution)
        self.row: Optional[int] = None
        # the row's ``flow`` is the last pair's: set when that pair
        # retires, consumed by the next admission. Cleared with the row —
        # a session never warm-starts across a gap
        self.has_flow = False


class Cohort:
    """What one admission cohort does to the table, lane by lane:
    ``idx`` / ``put`` / ``warm`` are ``stream_swap``'s index vectors,
    ``pairs`` and ``primes`` the ``(lane, request)`` of the frames that
    had a previous frame to pair with and of those that opened one."""

    __slots__ = ("idx", "put", "warm", "pairs", "primes")

    def __init__(self, rung: int):
        self.idx = np.zeros((rung,), np.int32)
        self.put = np.zeros((rung,), bool)
        self.warm = np.zeros((rung,), bool)
        self.pairs: List[Tuple[int, Any]] = []
        self.primes: List[Tuple[int, Any]] = []


class StreamCache:
    """Sessions (host, LRU) and the tables their rows live in (device).

    ``capacity`` (``ServeConfig.stream_cache_size``) bounds both: at most
    that many idle sessions are remembered, and each bucket's table has
    that many rows, allocated once (at boot, on a warmed engine). Every
    method but :meth:`table` / :meth:`set_table` is host bookkeeping
    under :attr:`lock`; the tables are touched by the engine's worker
    thread alone, which is what makes donating them safe.
    ``count(name)`` is the engine's counter hook.
    """

    def __init__(self, capacity: int, warm_start: bool,
                 row_spec: Callable[[Tuple[int, int]], Tuple[Any, Any]],
                 count: Callable[[str], None], mesh=None):
        self.capacity = int(capacity)
        self.warm_start = bool(warm_start)
        self.programs = StreamPrograms(warm_start, mesh)
        self.sessions: "collections.OrderedDict[int, _Session]" = (
            collections.OrderedDict()
        )
        self.lock = threading.Lock()
        self._row_spec = row_spec
        self._count = count
        self._mesh = mesh
        self._specs: Dict[Tuple[int, int], Dict[str, Any]] = {}
        self._tables: Dict[Tuple[int, int], Dict[str, Any]] = {}
        self._free: Dict[Tuple[int, int], List[int]] = {}
        self._next_sid = 0

    # -- the device side ---------------------------------------------------

    def table_spec(self, bucket: Tuple[int, int]) -> Dict[str, Any]:
        """Shape/dtype of ``bucket``'s table (what AOT warm-up lowers the
        stream programs against)."""
        spec = self._specs.get(bucket)
        if spec is None:
            fm, cx = self._row_spec(bucket)
            rows = lambda s: jax.ShapeDtypeStruct(
                (self.capacity,) + tuple(s.shape[1:]), s.dtype
            )
            flow = jax.ShapeDtypeStruct(
                tuple(fm.shape[:3]) + (2,), jnp.float32
            )
            spec = self._specs[bucket] = {
                "fmap": rows(fm), "ctx": rows(cx), "flow": rows(flow),
            }
        return spec

    def table(self, bucket: Tuple[int, int]) -> Dict[str, Any]:
        """``bucket``'s table, allocated (zeros) on first use."""
        tab = self._tables.get(bucket)
        if tab is None:
            spec = self.table_spec(bucket)
            if self._mesh is None:
                tab = jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), spec
                )
            else:
                from raft_tpu.parallel.serve_shard import replicated

                tab = jax.device_put(
                    jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), spec),
                    replicated(self._mesh),
                )
            self._tables[bucket] = tab
        return tab

    def set_table(self, bucket: Tuple[int, int], table) -> None:
        self._tables[bucket] = table

    def row_bytes(self, bucket: Tuple[int, int]) -> int:
        return sum(
            int(np.prod(s.shape[1:])) * jnp.dtype(s.dtype).itemsize
            for s in self.table_spec(bucket).values()
        )

    # -- sessions ----------------------------------------------------------

    def open(self) -> int:
        with self.lock:
            sid = self._next_sid
            self._next_sid += 1
        return sid

    def begin_frame(self, sid: int, bucket, hw) -> Optional[str]:
        """A frame of session ``sid`` arrives: remember the session, mark
        it busy. Returns a refusal (the session already has a frame in
        flight) or None."""
        with self.lock:
            st = self._session(sid, bucket, hw)
            if st.busy:
                return (
                    f"stream {sid} already has a frame in flight; "
                    f"streams are strictly ordered — submit sequentially"
                )
            if st.bucket != bucket or st.hw != hw:
                # resolution change mid-stream: re-prime rather than pair
                # frames across different buckets
                self._drop_row(st)
                st.bucket, st.hw = bucket, hw
            st.busy = True
        return None

    def end_frame(self, sid: int) -> None:
        with self.lock:
            st = self.sessions.get(sid)
            if st is not None:
                st.busy = False

    def close(self, sid: int) -> None:
        with self.lock:
            st = self.sessions.pop(sid, None)
            if st is not None:
                self._drop_row(st)

    def invalidate(self, sid: Optional[int]) -> None:
        """A frame of the session was dropped, expired or failed: forget
        its features, so the next frame primes instead of pairing across
        the gap."""
        if sid is None:
            return
        with self.lock:
            st = self.sessions.get(sid)
            if st is not None and st.row is not None:
                self._drop_row(st)
                self._count("stream_invalidations")

    def plan(self, live: List[Any], rung: int) -> Cohort:
        """Decide, for a cohort of frames in lane order, which pair with
        their session's cached frame and which prime it, and give each a
        row to leave its features in."""
        co = Cohort(rung)
        with self.lock:
            for lane, r in enumerate(live):
                st = self._session(r.stream_id, r.bucket, r.orig_hw)
                primed = st.row is not None
                if not primed:
                    st.row = self._take_row(st)
                if st.row is None:
                    # every row is held by a session with a frame in
                    # flight: this frame opens a pair nobody remembers
                    co.primes.append((lane, r))
                    continue
                co.idx[lane], co.put[lane] = st.row, True
                if primed:
                    co.warm[lane] = self.warm_start and st.has_flow
                    co.pairs.append((lane, r))
                else:
                    co.primes.append((lane, r))
                st.has_flow = False   # consumed, or stale
        return co

    def flow_rows(self, bucket, reqs: List[Any], rung: int):
        """``stream_store_flow``'s index vectors for a retirement cohort
        (``reqs`` in lane order, None where the lane is no stream pair):
        the lanes whose session still holds the row it was admitted
        from. None when no lane does."""
        idx = np.zeros((rung,), np.int32)
        mask = np.zeros((rung,), bool)
        with self.lock:
            for lane, r in enumerate(reqs):
                st = None if r is None else self.sessions.get(r.stream_id)
                if (st is not None and st.row is not None
                        and st.bucket == bucket):
                    idx[lane], mask[lane] = st.row, True
        return (idx, mask) if mask.any() else None

    def mark_flow(self, sid: Optional[int]) -> None:
        """The session's pair retired with a finite flow, and the row
        holds it."""
        with self.lock:
            st = self.sessions.get(sid)
            if st is not None and st.row is not None:
                st.has_flow = True

    def stats(self) -> Dict[str, int]:
        with self.lock:
            held = [st.bucket for st in self.sessions.values()
                    if st.row is not None]
            n = len(self.sessions)
        return {
            "stream_sessions": n,
            "stream_cache_bytes": sum(self.row_bytes(b) for b in held),
        }

    # -- under the lock ----------------------------------------------------

    def _session(self, sid: int, bucket, hw) -> _Session:
        st = self.sessions.get(sid)
        if st is None:
            st = self.sessions[sid] = _Session(sid, bucket, hw)
            self._evict()
        self.sessions.move_to_end(sid)
        return st

    def _evict(self) -> None:
        """LRU-evict sessions beyond the bound (never a busy one)."""
        excess = len(self.sessions) - self.capacity
        if excess <= 0:
            return
        idle = [s for s, st in self.sessions.items() if not st.busy]
        for sid in idle[:excess]:
            self._drop_row(self.sessions.pop(sid))
            self._count("stream_evictions")

    def _take_row(self, st: _Session) -> Optional[int]:
        free = self._free.setdefault(
            st.bucket, list(range(self.capacity - 1, -1, -1))
        )
        if not free:
            # the rows are all held (sessions of another resolution count
            # against the bound too): the least recently used idle holder
            # gives its up
            for other in self.sessions.values():
                if (other.row is not None and not other.busy
                        and other.bucket == st.bucket and other is not st):
                    self._drop_row(other)
                    self._count("stream_evictions")
                    break
        return free.pop() if free else None

    def _drop_row(self, st: _Session) -> None:
        if st.row is not None:
            self._free[st.bucket].append(st.row)
        st.row, st.has_flow = None, False
