"""Fault-isolated serving engine for RAFT optical flow.

``FlowEstimator`` is a correct synchronous wrapper; this module is what
stands between it and "heavy traffic from millions of users" (ROADMAP
north star). One worker thread owns the device; callers interact only
through a bounded deadline-aware queue. The ladder of defenses, outermost
first (docs/failure_model.md, serving ladder):

  1. **validate** — shape/dtype/nonfinite checked at admission
     (:class:`~raft_tpu.serve.InvalidInput`); malformed bytes never reach
     the batch thread.
  2. **bucket** — resolutions are closed over a configured bucket set
     (:mod:`raft_tpu.serve.bucketing`); a novel shape is rejected or rate-
     limited onto the caller's own thread, so a compile stampede cannot
     form behind the batcher.
  3. **shed** — the queue is bounded; excess load fails fast with a
     retryable :class:`~raft_tpu.serve.Overloaded` carrying a backoff
     hint, instead of serving everyone late.
  4. **degrade** — under sustained pressure the controller steps
     ``num_flow_updates`` down the anytime ladder (everyone gets slightly
     softer flow, nobody gets shed), recovering when drained; every
     response reports the level it was served at.
  5. **isolate** — each dispatched batch runs under a device-execution
     deadline (``Watchdog`` in worker-thread callback mode), and a batch
     that comes back non-finite is retried as singles so exactly the
     poisoned request fails (:class:`~raft_tpu.serve.PoisonedInput`) —
     the inference mirror of training's data quarantine. The worker
     thread survives any per-batch failure.

The hot path dispatches *iterations*, not requests (the resident
GRU-iteration pool — iteration-level continuous batching):

  * **Resident iteration pool** (``pool_capacity > 0``, the default) —
    RAFT's refinement loop is anytime, so the dispatch unit is one GRU
    iteration across a fixed on-device slot array of per-request
    recurrent state (correlation pyramid, hidden state, context, current
    flow — ``RAFT.begin_pair`` / ``iterate_step`` / ``finalize_flow``).
    Each tick, requests that hit their own iteration target (per-request
    ``num_flow_updates``, a degradation target, or a deadline-driven
    early exit) leave the pool and queued requests fill the freed slots
    mid-flight. Under mixed iteration counts nobody waits for a
    neighbor's tail iterations: ``padding_waste`` (now idle-slot-
    iterations / dispatched-slot-iterations) goes to ~0 and admission-to-
    first-dispatch latency drops to about one iteration time. Degradation
    levels become per-request iteration *targets* assigned at admission
    instead of a compile-time ladder; the compiled-program set stays
    closed (per bucket: admission rungs x {begin, insert, gather, final}
    + ONE capacity-wide step program) and fully warmable.

The whole-request fallback path (``pool_capacity=0``) keeps the PR 4
throughput rework:

  * **Batch-size ladder** — a formed batch is zero-padded to the next
    rung of ``config.batch_ladder`` (default powers of two up to
    ``max_batch``), not blindly to ``max_batch``; under light load up to
    ``(max_batch-1)/max_batch`` of dispatched FLOPs disappear. The
    compiled-program set stays closed — ``buckets x iter-ladder x
    batch-ladder`` — and fully warmable; ``stats()['padding_waste']``
    reports the padded-row fraction actually paid.
  * **Pipelined dispatch** — JAX dispatch is asynchronous: the worker
    keeps up to ``pipeline_depth`` batches in flight, assembling and
    staging batch N+1 (into preallocated rotating host buffers — no
    per-batch ``np.zeros``/``np.concatenate``) while batch N computes.
    The window is pressure-adaptive: past the degradation
    high-watermark the worker drains the oldest batch before
    dispatching ahead, so under flood the window never extends
    effective residence (measured +~1 batch of p99 otherwise) — flood
    latency and shed behavior match the pre-pipeline engine. Deadline,
    shed, degradation, and quarantine semantics are depth-independent
    (the chaos suite runs them at depth 2).
  * **Shared-frame feature cache** — stream sessions
    (:meth:`ServeEngine.open_stream`) encode each video frame once and
    reuse frame t's feature/context maps as pair (t, t+1)'s first-frame
    inputs (``RAFT.encode_frame`` / ``RAFT.iterate``), roughly halving
    encoder FLOPs on streams. The cache is on the device
    (:mod:`raft_tpu.serve.stream_cache`): a table of ``stream_cache_size``
    rows a bucket holds each session's last frame's features in the
    dtype the encoders compute them in, and its last pair's 1/8-grid
    flow; the host keeps the index (which session holds which row, LRU)
    and sends frames and row numbers only — between a frame's arrival
    and its pair's ``insert`` nothing is fetched. Any dropped/failed
    frame invalidates its session so the next frame re-primes rather
    than pairing across a gap; with ``stream_warm_start`` a pair starts
    from upstream's ``forward_interpolate`` of the session's last flow,
    computed on the device. Both engines share the cache (this one's
    stream batches run ``encode`` -> ``stream_swap`` -> ``iterate``).

Boot pays as little as possible (ISSUE 7, :mod:`raft_tpu.serve.aot`):
warmup is compile-only AOT lowering (concurrent, no forward passes on
zeros) behind two faster tiers — a fingerprinted **warmup artifact**
(``warmup_artifact``) that loads the whole compiled program set instead
of compiling it, and the JAX **persistent compilation cache**
(``compilation_cache_dir``). ``stats()['boot']`` reports boot-to-ready
time, programs loaded vs compiled, and the raw backend-compile event
count, so cold-start cost is measured, not guessed.

Everything above narrates itself through the observability spine
(ISSUE 10, :mod:`raft_tpu.obs`, docs/observability.md): sampled
per-request traces (``ServeConfig.trace_sample_rate``; span chain
admit -> queue_wait -> batch_form -> dispatch -> fetch, ``refine`` in
pool mode; ``trace_id`` on every :class:`ServeResult`), a unified
metrics registry behind the unchanged ``stats()`` keys (plus
:meth:`ServeEngine.prometheus`), and a flight recorder whose bounded
event ring (shed, degradation step, drain phases, quarantine, boot
outcome, pool reset) is dumped as a postmortem bundle whenever the
device-deadline watchdog trips.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.inference import FlowEstimator
from raft_tpu.obs import (
    RESIDUAL_BUCKETS, AlertEngine, AlertRule, DeviceTimeLedger,
    FlightRecorder, MetricsRegistry, TraceContext, Tracer, gauge_value,
    logger_sink, profile, rate, ratio_rate,
)
from raft_tpu.serve import aot
from raft_tpu.serve.bucketing import BucketRouter, TokenBucket
from raft_tpu.serve.config import ServeConfig
from raft_tpu.serve.degradation import DegradationController
from raft_tpu.serve.errors import (
    DeadlineExceeded,
    Draining,
    EngineStopped,
    InvalidInput,
    Overloaded,
    PoisonedInput,
    QuotaExceeded,
    ServeError,
    ShapeRejected,
)
from raft_tpu.serve.pool import (
    RESID_SENTINEL,
    BucketPool,
    PoolPrograms,
    _SlotMeta,
    state_layout,
    zero_state,
)
from raft_tpu.serve.stream_cache import StreamCache, encode_frame_program
from raft_tpu.serve.qos import (
    QosPolicy,
    QosStats,
    brownout_level,
    qos_stats_block,
    validate_priority,
)
from raft_tpu.serve.queue import MicroBatchQueue, Request
from raft_tpu.serve.tiler import TilePlanner, blend_tiles, nearest_bucket

__all__ = ["ServeEngine", "ServeResult", "StreamSession"]


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """One served request: the flow plus how it was served.

    ``num_flow_updates``/``level`` report the degradation state the
    request actually ran at (``degraded`` is their boolean shadow), so
    callers can tell full-quality flow from load-shed-quality flow.
    ``flow`` is ``None`` exactly when ``primed`` is True: the frame
    opened (or re-opened, after an invalidation) a stream pair and there
    was nothing to pair it with yet.
    """

    flow: Optional[np.ndarray]       # (H, W, 2) float32, caller resolution
    rid: int
    bucket: Tuple[int, int]
    num_flow_updates: int
    level: int
    degraded: bool
    latency_ms: float
    slow_path: bool = False
    retried_single: bool = False
    primed: bool = False
    # why refinement stopped where it did (ISSUE 12):
    #   'target'    — the request ran to its own iteration target (the
    #                 per-request ask or the degradation level's);
    #   'deadline'  — the deadline would have expired before the full
    #                 target, so the pool finalized early at
    #                 num_flow_updates iterations (anytime flow) instead
    #                 of expiring worthlessly;
    #   'converged' — the flow-update residual stayed below
    #                 pool_converge_thresh for the configured streak:
    #                 further iterations would not have moved the flow.
    exit_reason: str = "target"
    # observability (ISSUE 10): the id of this request's sampled trace
    # (None when tracing is off or the request was not sampled); look it
    # up in ``engine.tracer`` / the flight recorder's last-N ring
    trace_id: Optional[str] = None
    # convergence telemetry (ISSUE 11, pool mode, traced requests only):
    # this request's per-iteration flow-update residual trajectory
    # (RMS ||delta flow|| in 1/8-grid pixels, oldest first, the last
    # min(iters, resid-history) iterations) — the measured evidence the
    # residual-driven early-exit threshold is calibrated from
    residuals: Optional[Tuple[float, ...]] = None
    # stream warm start (ISSUE 12, pool mode): this request's refinement
    # was seeded from the previous pair's forward-warped flow
    warm_started: bool = False
    # tiled inference (ISSUE 20): this off-bucket request was fanned into
    # ``tiles`` bucket-shaped sub-requests and blended host-side; the
    # frontend prices these under their own ``tiled`` req_class and the
    # edge cache never caches them
    tiled: bool = False
    tiles: int = 0
    # the 1/8-grid flow ``flow`` was upsampled from (``coords1 - coords0``
    # on the bucket's grid, ``(bh/8, bw/8, 2)``; upstream's ``flow_low``),
    # for callers that asked (``StreamSession.submit(return_flow8=True)``,
    # pool mode): what the session's next pair warm-starts from
    flow8: Optional[np.ndarray] = None

    @property
    def early_exit(self) -> bool:
        """Back-compat shadow of :attr:`exit_reason`: True when the
        request stopped before its own target (deadline- or
        convergence-driven)."""
        return self.exit_reason in ("deadline", "converged")


class StreamSession:
    """Caller-facing handle for one served video stream.

    Feed frames in order via :meth:`submit`; each returns a
    :class:`ServeResult` whose ``flow`` is the flow from the previous
    frame to this one, or ``None`` (``primed=True``) when this frame
    opens a fresh pair. One outstanding frame per session (``submit``
    blocks); open several sessions for concurrency.
    """

    def __init__(self, engine: "ServeEngine", stream_id: int):
        self._engine = engine
        self.stream_id = stream_id

    def submit(
        self,
        frame,
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
        return_flow8: bool = False,
    ) -> ServeResult:
        kw = {} if trace_ctx is None else {"trace_ctx": trace_ctx}
        if priority is not None:
            kw["priority"] = priority
        if tenant is not None:
            kw["tenant"] = tenant
        if return_flow8:
            kw["return_flow8"] = True
        return self._engine.submit_frame(
            self.stream_id, frame, deadline_ms=deadline_ms,
            num_flow_updates=num_flow_updates, **kw,
        )

    def close(self) -> None:
        self._engine.close_stream(self.stream_id)

    def __enter__(self) -> "StreamSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-unfetched batch in the pipeline window."""

    live: List[Request]
    iters: int
    level: int
    t0: float
    flow_dev: Any
    kind: str                                   # 'pair' | 'stream'
    # stream only: ``live[j]``'s row of ``flow_dev`` (a stream batch keeps
    # its primes' lanes), and the batch's (fmap1, fmap2, ctx) device
    # arrays for the singles retry
    lanes: Optional[List[int]] = None
    retry_rows: Optional[Tuple[Any, Any, Any]] = None


def _coords0(shape) -> np.ndarray:
    """The identity coordinates of an ``(h8, w8, 2)`` grid, (x, y) last:
    what ``coords1`` starts from, so ``coords1 - _coords0`` is the flow."""
    h8, w8 = shape[0], shape[1]
    ys, xs = np.meshgrid(
        np.arange(h8, dtype=np.float32), np.arange(w8, dtype=np.float32),
        indexing="ij",
    )
    return np.stack([xs, ys], axis=-1)


# engine trace ring: a 6 s traced window of the busiest cell finishes
# ~160 request records and ~310 scheduler-loop records
_TRACE_RING = 4096


class _SchedLoop:
    """One sampled iteration of the pool scheduler's loop: the phases it
    went through (``(name, t0, t1)``, appended by ``_phase``) and what it
    did, sealed into a ``"sched"`` trace record when the next iteration
    starts."""

    __slots__ = (
        "trace", "cpu0", "t_end", "phases", "ticked", "admitted", "retired",
        "deferred",
    )

    def __init__(self, trace):
        self.trace = trace
        self.cpu0 = self.t_end = 0.0  # set by _sched_seal / _sched_turn
        self.phases: List[Tuple[str, float, float]] = []
        self.ticked = 0
        self.admitted: List[int] = []
        self.retired: List[int] = []
        self.deferred = 0  # retirements settled after this loop's tick


class _Retiring:
    """A retirement cohort between its dispatch and its settle: its
    ``pool_gather`` / ``pool_final`` (and ``stream_store_flow``) are
    enqueued, the host copies of what its requests get are started, and
    its slots are free for the loop's admission."""

    __slots__ = ("due", "live", "t_f", "flow", "res", "c1", "flags")

    def __init__(self, due, live, t_f, flow, res, c1):
        self.due, self.live, self.t_f = due, live, t_f
        self.flow, self.res, self.c1 = flow, res, c1
        # the retiring stream pairs' frame-finite flag arrays, one each
        # admission cohort they came from
        self.flags = list({
            id(m.frame_ok[0]): m.frame_ok[0]
            for _, m, _ in due if m.frame_ok is not None
        }.values())

    def arrays(self) -> list:
        return [self.flow, self.res] + (
            [] if self.c1 is None else [self.c1]
        ) + self.flags

    def holds(self, sessions) -> bool:
        return any(
            r.kind == "stream" and r.stream_id in sessions for r in self.live
        )

    def fetch(self):
        """The flows, residual histories, coordinates and frame flags on
        the host: a wait on the device only where the copies are not in
        yet."""
        return (
            np.asarray(self.flow),
            np.asarray(self.res),
            None if self.c1 is None else np.asarray(self.c1),
            [m.frame_finite() for _, m, _ in self.due],
        )


class _StagingPool:
    """Rotating preallocated host buffers, keyed by (role, bucket).

    ``pipeline_depth + 1`` slots per key guarantee a buffer is never
    rewritten while a previous dispatch could still be copying from it;
    rows are written in place and pad rows zeroed, replacing the old
    per-batch ``np.zeros`` + ``np.concatenate`` allocations.
    """

    def __init__(self, slots: int):
        self._slots = max(2, int(slots))
        self._rings: Dict[Any, List[np.ndarray]] = {}
        self._idx: Dict[Any, int] = {}

    def fill(self, key, shape, rows: List[np.ndarray], rung: int) -> np.ndarray:
        """Copy ``rows`` (each ``(1, ...)``) in, zero the pad tail, and
        return the ``rung``-row slice of a rotating ``shape`` buffer."""
        ring = self._rings.get(key)
        if ring is None or ring[0].shape != shape:
            ring = [np.zeros(shape, np.float32) for _ in range(self._slots)]
            self._rings[key] = ring
            self._idx[key] = 0
        i = self._idx[key]
        self._idx[key] = (i + 1) % len(ring)
        buf = ring[i]
        for j, row in enumerate(rows):
            buf[j] = row[0]
        if rung > len(rows):
            buf[len(rows):rung] = 0.0
        return buf[:rung]


class ServeEngine:
    """Deadline-aware, load-shedding, degradation-capable RAFT server."""

    def __init__(
        self,
        model,
        variables,
        config: Optional[ServeConfig] = None,
        *,
        logger=None,
    ):
        self.config = cfg = config or ServeConfig()
        self.model = model
        self._logger = logger
        if cfg.compilation_cache_dir:
            # the fallback boot tier: wire the JAX persistent compile
            # cache before anything here can compile (process-global;
            # JAX_COMPILATION_CACHE_DIR, when set, wins over this path)
            aot.enable_persistent_cache(cfg.compilation_cache_dir)
        self._router = BucketRouter(cfg.buckets)
        self._queue = MicroBatchQueue(
            cfg.queue_capacity, qos=cfg.qos_enabled,
            aging_ms=cfg.qos_aging_ms,
        )
        # QoS spine (ISSUE 17): per-class accounting always runs (stable
        # stats schema); the enforcement policy exists only when enabled,
        # so the default-off engine takes zero new hot-path branches that
        # change behavior.
        self._qos_stats = QosStats(cfg.latency_window)
        self._qos_policy = (
            QosPolicy(cfg.qos_tenant_quotas) if cfg.qos_enabled else None
        )
        self._controller = DegradationController(
            cfg.ladder,
            slo_p99_ms=cfg.slo_p99_ms,
            high_watermark=cfg.high_watermark,
            low_watermark=cfg.low_watermark,
            cooldown=cfg.cooldown_batches,
            recover_after=cfg.recover_after,
        )
        self._slow_tokens = TokenBucket(cfg.slow_path_per_s, cfg.slow_path_burst)
        self._slow_lock = threading.Lock()  # one novel-shape compile at a time
        # tiled inference (ISSUE 20): the waste-aware plan/blend layer
        # above the batch path. Always constructed (cheap, no device
        # state) — submit_tiled is callable on any engine; the
        # unknown_shape='tiled' arm only controls automatic routing.
        self._tiler = TilePlanner(
            cfg.buckets,
            overlap_px=cfg.tile_overlap_px,
            pad_penalty=cfg.tile_pad_penalty,
            max_tiles=cfg.tile_max_tiles,
        )
        self._tiler_counters = {
            "requests": 0, "completed": 0, "failures": 0,
            "tiles_submitted": 0, "tiles_retried": 0,
            "admission_acquisitions": 0,
        }
        self._tiler_blend_ms: List[float] = []
        self._tiler_px = [0, 0]  # [useful canvas px, dispatched px]
        # Serve mesh (ISSUE 8): with mesh_devices > 1 every dispatch unit
        # is sharded over the mesh `data` axis (weights replicated) and
        # sizing knobs scale per-device -> global. mesh=None is the
        # single-device engine, byte-for-byte the pre-mesh behavior.
        self._mesh = None
        self._row_sharding = None
        if cfg.mesh_devices > 1:
            from raft_tpu.parallel.serve_shard import (
                make_serve_mesh, replicated, row_sharding,
            )

            self._mesh = make_serve_mesh(cfg.mesh_devices)
            self._row_sharding = row_sharding(self._mesh)
            self._dev_vars = jax.device_put(variables, replicated(self._mesh))
        else:
            self._dev_vars = jax.device_put(variables)
        # serving-weights identity (ISSUE 18): lazily computed and cached
        # by the variables_hash property — stats()/fleet views expose
        # which checkpoint this engine actually serves
        self._variables_hash_cache: Optional[str] = None

        def _sh(*specs):
            """in/out sharding kwargs: 'rep' (weights/scalars) or 'row'
            (batch-leading trees); empty off-mesh so jit signatures are
            unchanged for the single-device engine. Outputs are pinned
            row-sharded (every engine program emits batch-leading
            arrays), matching the pool programs' convention."""
            if self._mesh is None:
                return {}
            from raft_tpu.parallel.serve_shard import replicated

            table = {"row": self._row_sharding,
                     "rep": replicated(self._mesh)}
            return {
                "in_shardings": tuple(table[s] for s in specs),
                "out_shardings": self._row_sharding,
            }

        # on the serve mesh every program body is traced under it, so the
        # fused lookup kernel shard_maps itself over the mesh's rows
        from raft_tpu.parallel.mesh import traced_under

        apply = traced_under(self._mesh, model.apply)

        def _pair_fwd(variables, p1, p2, num_flow_updates):
            # positional static arg: pjit rejects kwargs once explicit
            # in_shardings are given (the mesh path), and the AOT lowering
            # passes the iteration count as a plain value either way
            return apply(
                variables, p1, p2, train=False, emit_all=False,
                num_flow_updates=num_flow_updates,
            )

        self._apply = jax.jit(
            _pair_fwd, static_argnums=(3,), **_sh("rep", "row", "row")
        )
        n_dev = cfg.mesh_devices
        self._batch_ladder: Tuple[int, ...] = tuple(
            r * n_dev for r in cfg.resolved_batch_ladder()
        )
        self._max_batch = cfg.max_batch * n_dev
        self._staging = _StagingPool(cfg.pipeline_depth + 1)
        # resident iteration pool (the default engine); 0 = whole-request
        # batch-ladder fallback, which compiles none of the pool programs
        self._pool_progs: Optional[PoolPrograms] = None
        self._pools: Dict[Tuple[int, int], BucketPool] = {}
        self._admit_ladder: Tuple[int, ...] = ()
        self._admit_cap = 0
        self._pool_cap = cfg.pool_capacity * n_dev
        # residual-history length = the full-quality iteration target, so
        # any admitted request's whole trajectory fits the rolling window
        self._resid_len = cfg.ladder[0]
        # convergence-adaptive compute (ISSUE 12): both knobs are TRACED
        # step-program inputs (thresh <= 0 disables on device), built
        # once here so the hot loop passes the same host scalars every
        # tick; warm start is a host-side admission decision.
        self._conv_thresh = np.float32(cfg.pool_converge_thresh or 0.0)
        self._conv_streak = np.int32(
            min(cfg.pool_converge_streak, self._resid_len)
        )
        self._conv_min = np.int32(
            min(max(cfg.pool_min_iters, 1), self._resid_len)
        )
        self._warm_start = bool(
            cfg.stream_warm_start and cfg.pool_capacity > 0
        )
        if cfg.pool_capacity > 0:
            self._pool_progs = PoolPrograms(
                model, mesh=self._mesh, resid_len=self._resid_len
            )
            self._admit_ladder = tuple(
                r * n_dev for r in cfg.resolved_admit_ladder()
            )
            self._admit_cap = self._admit_ladder[-1]
        # stream-mode programs (encode-once feature caching); None when
        # stream serving is disabled so no extra programs ever compile.
        # The whole-request iterate program only exists in fallback mode —
        # pooled stream pairs refine through the slot-wise step program.
        self._encode = self._iterate = None
        self._stream_cache: Optional[StreamCache] = None
        if cfg.stream_cache_size > 0:
            self._encode = jax.jit(
                encode_frame_program(apply), **_sh("rep", "row")
            )
            if cfg.pool_capacity == 0:
                def _iterate_fwd(variables, f1, f2, ctx, num_flow_updates):
                    return apply(
                        variables, f1, f2, ctx, train=False, emit_all=False,
                        method="iterate", num_flow_updates=num_flow_updates,
                    )

                self._iterate = jax.jit(
                    _iterate_fwd, static_argnums=(4,),
                    **_sh("rep", "row", "row", "row"),
                )
            self._stream_cache = StreamCache(
                cfg.stream_cache_size, self._warm_start,
                row_spec=self._stream_row_spec, count=self._count,
                mesh=self._mesh,
            )
        # stream primes wait here for their frame's finite flag: (flag
        # array, [(lane, request)], iters, level) an admission cohort,
        # settled by the pool worker once the encode that computes the
        # flag has run (_stream_settle)
        self._stream_checks: "collections.deque[Tuple]" = collections.deque()
        # retirements between their dispatch and their settle, oldest
        # first (_pool_finalize parks, _pool_settle reads and completes)
        self._retiring: "collections.deque[_Retiring]" = collections.deque()
        self._lock = threading.Lock()
        # Observability spine (ISSUE 10): the unified metrics registry,
        # the per-request tracer, and the fault flight recorder. The
        # counter "dict" below is a registry-backed CounterGroup — same
        # keys, same hot-path `+= 1` under the engine lock, but now one
        # snapshot feeds stats(), Prometheus text, and the JSONL logger.
        self.metrics = MetricsRegistry("serve")
        self.recorder = FlightRecorder(proc="engine")
        self.tracer = Tracer(
            cfg.trace_sample_rate,
            prefix="srv",
            # holds a traced window whole: requests plus one "sched"
            # record per pool-scheduler loop (a deque: memory only as
            # records arrive); Tracer.dropped counts what it overwrote
            capacity=_TRACE_RING,
            on_finish=self.recorder.add_trace,
        )
        # the pool scheduler's current loop record, when that loop is
        # sampled (see _sched_turn); _phase appends to it
        self._loop: Optional[_SchedLoop] = None
        if logger is not None:
            # postmortem bundles persist through the logger's structured
            # events file (MetricLogger.log_event)
            self.recorder.add_sink(logger_sink(logger))
        self._counters = self.metrics.counter_group(
            "counters",
            (
                "submitted", "completed", "shed", "shed_slow_path", "rejected",
                "invalid", "expired", "quarantined", "retried_singles",
                "nonfinite_batches", "batches", "slow_path", "watchdog_trips",
                "worker_errors", "padded_rows", "dispatched_rows",
                "encode_cache_hits", "encode_cache_misses", "stream_primes",
                "stream_invalidations", "stream_evictions", "inflight_peak",
                "pool_ticks", "pool_admitted", "pool_resets",
                # host bytes of what retirements fetched (flow, residual
                # history, warm-start coords): beside `completed`, the
                # size of the blocking fetch a completion pays
                "fetched_bytes",
                # retirements read after a later tick was dispatched, and
                # of those, how many had all their arrays on the host
                # when read (_pool_settle)
                "retire_deferred", "retire_ready_at_settle",
                "idle_slot_iters", "dispatched_slot_iters",
                "early_exit_iters_saved", "early_exits_deadline",
                "early_exits_converged", "early_exit_iters_saved_deadline",
                "early_exit_iters_saved_converged", "stream_warm_starts",
                "drained",
                # mirrored rollout traffic (ISSUE 18): shadow submits are
                # accounted HERE, never under submitted/completed/shed/
                # expired — the autoscaler, QoS, and alert signals those
                # feed must be blind to mirrored load by construction
                "shadow_submitted", "shadow_completed", "shadow_shed",
                "shadow_expired",
            ),
        )
        self._latency_hist = self.metrics.histogram("latency_ms")
        # Device-time ledger (ISSUE 11): counter-sampled timed dispatches
        # per program family; registry-backed so every family's sub-ms
        # histogram reaches Prometheus with no extra wiring.
        self.ledger = DeviceTimeLedger(
            cfg.ledger_sample_every, registry=self.metrics
        )
        # Convergence telemetry (ISSUE 11, pool mode): final-residual
        # distribution + the iters-vs-residual table (per-iteration sums
        # and counts, host-side, a few floats per retirement).
        self._resid_final = self.metrics.histogram(
            "final_residual", bounds=RESIDUAL_BUCKETS
        )
        self._resid_iter_sum = np.zeros(self._resid_len)
        self._resid_iter_cnt = np.zeros(self._resid_len, np.int64)
        # Burn-rate alerting (ISSUE 11): multi-window rules over the
        # engine's own counters, evaluated from the worker loop; a
        # page-severity fire auto-dumps a postmortem and every bundle
        # carries the alerts active at dump time.
        s_w, l_w = cfg.alert_short_window_s, cfg.alert_long_window_s
        self._alerts = AlertEngine(
            (
                AlertRule(
                    "slo_burn", ratio_rate(("expired", "shed"), "submitted"),
                    0.1, s_w, l_w, severity="page",
                ),
                AlertRule(
                    "quarantine_burn",
                    ratio_rate("quarantined", "submitted"), 0.05, s_w, l_w,
                ),
                AlertRule(
                    "watchdog_trips", rate("watchdog_trips"), 0.0, s_w, l_w,
                    severity="page",
                ),
                AlertRule(
                    "device_time_drift", gauge_value("device_time_drift"),
                    1.5, s_w, l_w,
                ),
            ),
            snapshot_fn=self._alert_snapshot,
            recorder=self.recorder,
        )
        self._alerts.register_gauges(self.metrics)
        self.recorder.alerts_provider = self._alerts.active
        self.metrics.gauge("queue_depth", self._queue.depth)
        self.metrics.gauge("queue_forming", self._queue.forming)
        self.metrics.gauge(
            "degradation_level", lambda: self._controller.level
        )
        self.metrics.gauge(
            "num_flow_updates", lambda: self._controller.num_flow_updates
        )
        self.metrics.gauge(
            "pool_occupied",
            lambda: sum(p.occupied_count() for p in self._pools.values()),
        )
        self._last_level = 0  # degradation level at the last observe
        self._next_rid = 0
        # AOT executable overlay: program-key -> Compiled, installed by
        # warmup (compile-only AOT, or deserialized from a warmup
        # artifact). Hot-path seams consult it before the jit fallback;
        # it is written once before the worker thread starts.
        self._aot_execs: Dict[Tuple, Any] = {}
        self._boot: Dict[str, Any] = {
            "source": "none",
            "boot_to_ready_ms": None,
            "programs_total": 0,
            "programs_loaded": 0,
            "programs_compiled": 0,
            "backend_compiles": 0,
            "smoke_runs": 0,
            "artifact_error": None,
        }
        self._ttfd: List[float] = []   # admission-wait samples, pool mode
        self._latency: Dict[Tuple[int, int], List[float]] = {}
        self._batch_ms_ewma = 50.0
        self._quarantined_rids: List[int] = []
        self._stop = threading.Event()
        self._draining = threading.Event()
        # dispatched-but-unfetched batches (fallback worker); written only
        # by the worker thread, read by drain()'s quiesce poll
        self._inflight_n = 0
        self._ready = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._watchdog = None

    @classmethod
    def from_estimator(cls, estimator: FlowEstimator, **kw) -> "ServeEngine":
        """Wrap an existing :class:`FlowEstimator`'s model and weights."""
        return cls(estimator.model, estimator.variables, **kw)

    @property
    def num_devices(self) -> int:
        """Devices this engine's programs dispatch to (the serve mesh's
        ``data`` extent; 1 for the single-device engine). The warmup-
        artifact fingerprint keys on this, so an artifact built at one
        mesh size refuses — typed, degrading to compile — at another."""
        return self.config.mesh_devices

    @property
    def dispatch_devices(self) -> list:
        """The devices this engine's programs execute on, in assignment
        order: the serve mesh's, or the one device that holds the
        weights. A warmup artifact's executables are loaded for exactly
        these (``aot.load_programs``) — not for every device the process
        can see."""
        if self._mesh is not None:
            return list(self._mesh.devices.flat)
        return list(jax.tree.leaves(self._dev_vars)[0].devices())

    def _pad_rows(self, x: np.ndarray) -> np.ndarray:
        """Pad a (1, ...) single-row dispatch to the smallest mesh rung.

        Off-mesh this is the identity (rung 1 exists). On a mesh the
        leading dim must stay mesh-divisible, so singles-isolation
        retries and the slow path pad to ``mesh_devices`` rows — row 0
        still carries the request, the program key stays in the warmed
        ladder."""
        n = self._batch_ladder[0]
        if x.shape[0] >= n:
            return x
        pad = np.zeros((n - x.shape[0],) + x.shape[1:], x.dtype)
        return np.concatenate([np.asarray(x), pad], axis=0)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ServeEngine":
        """Warm up (optional), then start the batch worker. Idempotent.

        Boot is measured: ``stats()['boot']`` reports boot-to-ready time,
        how many programs were loaded from the warmup artifact vs
        compiled, the cache tier that served them (``artifact`` /
        ``persistent_cache`` / ``cold``), and the raw XLA
        backend-compile events observed during the boot window.
        """
        if self._thread is not None and self._thread.is_alive():
            return self
        if self._stop.is_set():
            raise EngineStopped("engine was stopped; build a new one")
        t0 = time.monotonic()
        ev0 = aot.compile_events()
        if self.config.apply_timeout_s is not None:
            from raft_tpu.utils.faults import Watchdog

            # callback-mode sections only: never interrupts the main
            # thread; a trip records + dumps through the flight recorder
            self._watchdog = Watchdog(
                self.config.apply_timeout_s, install_handler=False,
                recorder=self.recorder,
            )
        if self.config.warmup:
            self._warmup()
        worker = (
            self._worker_pool if self.config.pool_capacity > 0 else self._worker
        )
        self._thread = threading.Thread(
            target=worker, name="raft-serve-worker", daemon=True
        )
        self._thread.start()
        self._ready.set()
        self._boot["boot_to_ready_ms"] = (time.monotonic() - t0) * 1e3
        self._boot["backend_compiles"] = aot.compile_events() - ev0
        # the artifact-boot outcome is a flight-recorder event: a
        # degrade-to-compile boot shows up in the next postmortem bundle
        self.recorder.record("boot", **self._boot)
        return self

    def stop(self) -> None:
        self._stop.set()
        for req in self._queue.close():
            req.finish(error=EngineStopped("engine stopping"))
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        if self._watchdog is not None:
            self._watchdog.close()
        self._ready.clear()
        self._log_counters(force=True)

    @property
    def is_draining(self) -> bool:
        """True between :meth:`drain` and :meth:`stop` — the engine is
        quiescing and admits nothing (new work gets a typed, retryable
        :class:`~raft_tpu.serve.Draining`)."""
        return self._draining.is_set()

    def drain(self, *, timeout: Optional[float] = 30.0) -> bool:
        """Quiesce without dropping accepted work (the draining-restart
        seam the :class:`~raft_tpu.serve.router.ServeRouter` depends on).

        Three-phase, in order:

        1. **stop admitting** — from this point ``submit``/``submit_frame``
           raise :class:`~raft_tpu.serve.Draining` (retryable, carrying
           ``config.drain_retry_after_ms``), so callers back off or a
           router re-routes.
        2. **fail queued** — requests accepted but not yet dispatched are
           finished with the same typed ``Draining`` (they are exactly the
           work a router can still re-route losslessly; serving them here
           would stretch the drain window unboundedly under load).
        3. **finish in-flight** — dispatched batches complete and the
           iteration pool retires every resident at its own target; the
           worker thread keeps running until the engine is idle.

        Returns True once quiesced (queue empty, no popped-but-unacked
        batch in formation on the worker, no dispatched-but-unfetched
        batches, no pool residents) within ``timeout`` seconds
        (``None`` waits forever), False on timeout — the engine is still
        draining either way; ``stop()``/``close()`` remain the terminal
        calls. Idempotent.
        """
        if not self._draining.is_set():
            self.recorder.record("drain_begin", timeout=timeout)
        self._draining.set()
        retry_ms = self.config.drain_retry_after_ms
        n_failed = 0
        for req in self._queue.drain():
            if req.finish(
                error=Draining(
                    f"engine draining for restart; retry in "
                    f"~{retry_ms:.0f}ms",
                    retry_after_ms=retry_ms,
                )
            ):
                self._count("drained")
                n_failed += 1
                if req.kind == "stream":
                    self._invalidate_stream(req.stream_id)
        if n_failed:
            self.recorder.record("drain_queued_failed", n=n_failed)
        deadline = None if timeout is None else time.monotonic() + timeout
        ok = True
        while not self._quiesced():
            if not (self._thread is not None and self._thread.is_alive()):
                # no worker to finish in-flight work (never started, or
                # stopped under us): nothing more will quiesce
                ok = self._quiesced()
                break
            if deadline is not None and time.monotonic() > deadline:
                ok = False
                break
            time.sleep(0.005)
        self.recorder.record(
            "drain_quiesced" if ok else "drain_timeout", ok=ok
        )
        return ok

    def _quiesced(self) -> bool:
        """Idle check for :meth:`drain`: nothing queued, no batch popped
        from the queue but not yet reflected in dispatch bookkeeping
        (``queue.forming()``), nothing dispatched-but-unfetched, no pool
        residents, no retirement parked between dispatch and settle (its
        slots are free already)."""
        if self._queue.depth() or self._queue.forming():
            return False
        if self._retiring:
            return False  # flows dispatched, not yet read and handed over
        if self.config.pool_capacity > 0:
            return all(
                p.occupied_count() == 0 for p in self._pools.values()
            )
        return self._inflight_n == 0

    def close(self, graceful: bool = False, *, timeout: Optional[float] = 30.0) -> None:
        """Stop the engine; ``graceful=True`` drains first.

        Graceful mode finishes in-flight dispatches (pool residents
        retire at their own targets) and fails queued requests with the
        typed, retryable :class:`~raft_tpu.serve.Draining` — instead of
        the blunt :class:`~raft_tpu.serve.EngineStopped` every pending
        request gets from a bare :meth:`stop`. The seam a draining
        restart (router replica swap) is built on.
        """
        if graceful:
            self.drain(timeout=timeout)
        self.stop()

    def __enter__(self) -> "ServeEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def _warmup(self) -> None:
        """Build the worker thread's whole program set so readiness
        implies it never compiles — *without executing it*.

        Since ISSUE 7 warmup is compile-only: :mod:`raft_tpu.serve.aot`
        loads the warmup artifact when one matches (zero programs
        compiled), else AOT-compiles every program concurrently from
        shape/dtype specs (``jit(...).lower(specs).compile()`` — no
        zeros batches, no forward passes). A single tiny smoke execution
        per program family (:meth:`_smoke` / :meth:`_smoke_pool`) then
        validates the set is actually runnable — so warmup cost ~=
        compile cost, and an artifact boot costs ~the smoke alone.

        Coverage is unchanged from the execute-to-warm era. Pool mode:
        per bucket, admission programs at every admit rung (begin_pair +
        insert + gather + final, plus encode + begin_refinement when
        stream serving is enabled) and the ONE capacity-wide step
        program. Fallback mode: every (bucket, iters, rung)
        whole-request program — pairwise and, when stream serving is
        enabled, encode + iterate too.
        """
        self._boot.update(aot.warm_engine(self))
        try:
            self._smoke_boot()
        except Exception as e:
            if not self._boot.get("programs_loaded"):
                raise
            # artifact executables that load but cannot RUN (e.g. an
            # artifact whose executables were round-tripped through the
            # persistent compilation cache and lost their backend symbol
            # tables): drop the overlay and degrade to compiling — the
            # smoke check exists exactly so a bad artifact costs boot
            # time, never readiness (docs/failure_model.md)
            self._aot_execs = {}
            # the failed smoke may have left a resident slot table whose
            # buffers carry the failed dispatch's error: allocate anew
            self._pools.clear()
            specs = aot.program_specs(self)
            self._aot_execs = aot.compile_programs(
                specs, self.config.warmup_workers
            )
            self._boot.update({
                "source": (
                    "persistent_cache"
                    if self.config.compilation_cache_dir else "cold"
                ),
                "programs_loaded": 0,
                "programs_compiled": len(specs),
                "artifact_error": (
                    f"loaded programs failed to execute: {e!r}"
                ),
            })
            self._smoke_boot()

    def _smoke_boot(self) -> None:
        """One tiny execution per program family: proves the overlay
        (AOT-compiled or artifact-loaded) actually runs."""
        if self._pool_progs is not None:
            # allocate every bucket's resident slot state during boot so
            # first-traffic admission never pays an allocation (or its
            # fill-program compile) on the worker thread
            for bucket in self._router.buckets:
                self._pool_for(bucket)
            self._smoke_pool()
        else:
            self._smoke()

    def _smoke(self) -> None:
        """One tiny execution per fallback program family per bucket
        (smallest rung, ladder floor): proves the AOT-built/loaded
        executables run, without re-paying the old full warmup grid's
        FLOPs. The smallest rung is 1 off-mesh and ``mesh_devices`` on
        a serve mesh (rungs stay mesh-divisible)."""
        iters = self.config.ladder[-1]
        r0 = self._batch_ladder[0]
        for bucket in self._router.buckets:
            bh, bw = bucket
            z = np.zeros((r0, bh, bw, 3), np.float32)
            np.asarray(self._run_batch(z, z, iters))
            self._boot["smoke_runs"] += 1
            if self._encode is not None:
                fm, cx, _ = self._run_encode(z)
                f1, c1, _ = self._smoke_swap(bucket, fm, cx)
                np.asarray(self._run_iterate(f1, fm, c1, iters))
                self._boot["smoke_runs"] += 1

    def _smoke_pool(self) -> None:
        """One admission -> step -> retirement chain per bucket at the
        smallest admit rung: the pool-mode smoke check."""
        r = self._admit_ladder[0]
        for bucket in self._router.buckets:
            bh, bw = bucket
            pool = self._pool_for(bucket)
            z = np.zeros((r, bh, bw, 3), np.float32)
            rows = self._run_pool_begin(z, z)
            pool.state = self._pool_insert(
                pool.state, rows,
                np.zeros((r,), np.int32),
                np.asarray([True] + [False] * (r - 1), bool),
            )
            *_, token = self._run_pool_step(pool.state)
            np.asarray(token)
            c1, hid, _ = self._pool_gather(
                pool.state["coords1"], pool.state["hidden"],
                pool.state["resid_hist"], np.zeros((r,), np.int32),
            )
            np.asarray(self._run_pool_final(c1, hid))
            self._boot["smoke_runs"] += 1
            if self._encode is not None:
                # the stream admission's chain, on the operands it runs
                # on: the encoders' own outputs, in their own dtype
                fm, cx, _ = self._run_encode(z)
                f1, c1, ifl = self._smoke_swap(bucket, fm, cx)
                srows = self._run_pool_begin_features(f1, fm, c1, ifl)
                pool.state = self._pool_insert(
                    pool.state, srows,
                    np.zeros((r,), np.int32),
                    np.asarray([True] + [False] * (r - 1), bool),
                )
                if self._warm_start:
                    self._run_stream_store_flow(
                        bucket, srows["coords1"], np.zeros((r,), np.int32),
                        np.zeros((r,), bool),
                    )
                self._boot["smoke_runs"] += 1

    def _smoke_swap(self, bucket, fm, cx):
        """The smoke run's ``stream_swap``: every lane masked off, so the
        table (allocated here, at boot) stays zeros."""
        r = int(fm.shape[0])
        return self._run_stream_swap(
            bucket, fm, cx, np.zeros((r,), np.int32), np.zeros((r,), bool),
            np.zeros((r,), bool),
        )

    # -- public API --------------------------------------------------------

    def submit(
        self,
        image1,
        image2,
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
        shadow: bool = False,
        init_flow=None,
    ):
        """Serve one raw [0, 255] ``(H, W, 3)`` pair; returns :class:`ServeResult`.

        ``num_flow_updates`` caps this request's refinement iterations
        (validated against the configured full-quality ``ladder[0]``) —
        the anytime accuracy/latency dial per request. The iteration pool
        honors it exactly (the request leaves its slot at that
        iteration); the ``pool_capacity=0`` fallback engine honors it at
        ladder-rung granularity (the batch runs at the max of its
        members' rungs, so nobody's quality is cut below their ask).

        ``trace_ctx`` (ISSUE 15) joins this request to an externally-
        sampled trace: the engine's spans record under the propagated
        ``trace_id`` (the edge made the sampling decision — the engine's
        own rate is bypassed) and, when the context carries a live edge
        trace, the sealed record is stitched into it before this call
        returns.

        ``priority`` / ``tenant`` (ISSUE 17) classify the request for the
        QoS spine (``'interactive'`` | ``'standard'`` | ``'batch'``;
        ``None`` takes the config defaults). With ``qos_enabled`` the
        tenant's admission quota is charged (a retryable
        :class:`~raft_tpu.serve.QuotaExceeded` on breach) and the class
        drives shedding/brownout; off, they are annotations only.

        ``shadow`` (ISSUE 18) marks this request as mirrored rollout
        traffic: it is served normally but accounted under the
        ``shadow_*`` counters only — no tenant quota is charged and the
        submitted/completed/shed/expired counters the autoscaler, QoS
        stats, and burn-rate alerts read never move.

        ``init_flow`` (ISSUE 19) is a best-effort warm-start *hint*: a
        ``(h, w, 2)`` flow field on the caller's 1/8 refinement grid
        (1/8-pixel units — :func:`~raft_tpu.serve.edge_cache.
        seed_from_flow` builds one from a cached neighbor's flow) that
        seeds this pair's refinement through the PR 12 warm-start
        machinery, so a near-duplicate of recent traffic converges in a
        fraction of the iterations. Honored only when the engine can
        seed (iteration pool + stream encode programs available —
        :attr:`supports_init_flow`); otherwise silently ignored — a
        seed changes convergence speed, never correctness, so a tier
        that cannot seed just serves the request cold.

        Blocks the calling thread until the result, the deadline, or a
        typed :class:`~raft_tpu.serve.ServeError` — never an undocumented
        exception, never unboundedly.
        """
        if self.config.unknown_shape == "tiled":
            a1 = np.asarray(image1)
            if a1.ndim == 3 and self._router.route(
                int(a1.shape[0]), int(a1.shape[1])
            ) is None:
                # off-bucket under the tiled arm (ISSUE 20): fan out
                # before any accounting so the request is charged and
                # counted exactly once, by submit_tiled (init_flow is
                # dropped — there is no per-tile warm-start seed)
                return self.submit_tiled(
                    image1, image2, deadline_ms=deadline_ms,
                    num_flow_updates=num_flow_updates, trace_ctx=trace_ctx,
                    priority=priority, tenant=tenant, shadow=shadow,
                )
        t_sub = time.monotonic()
        deadline_ms = self._check_live(deadline_ms)
        pr, ten = self._qos_resolve(priority, tenant)
        iters = self._validate_iters(num_flow_updates)
        p1, p2, hw = self._admit(image1, image2)
        rel = None if shadow else self._qos_charge(pr, ten)
        t_adm = time.monotonic()
        bucket = self._router.route(*hw)
        rid = self._new_rid(shadow=shadow)
        if not shadow:
            self._qos_stats.count(pr, "submitted")
        trace = self.tracer.start(
            "pair", rid, t_start=t_sub,
            trace_id=None if trace_ctx is None else trace_ctx.trace_id,
        )
        if trace is not None:
            trace.add_span("admit", t_sub, t_adm)
            trace.annotate(priority=pr, tenant=ten)
        deadline = time.monotonic() + deadline_ms / 1e3
        try:
            if bucket is None:
                return self._submit_slow(
                    rid, p1, p2, hw, deadline, iters, trace=trace,
                    priority=pr, tenant=ten,
                )
            req = Request(
                rid, bucket, self._router.pad_to(p1, bucket),
                self._router.pad_to(p2, bucket), hw, deadline, iters=iters,
                priority=pr, tenant=ten, shadow=shadow,
            )
            if init_flow is not None:
                req.init8 = self._prepare_init_flow(init_flow, bucket)
                req.warm = req.init8 is not None
            req.trace = trace
            if rel is not None:
                req.add_done_callback(rel)
            return self._enqueue_and_wait(req, deadline_ms)
        finally:
            # quota release is one-shot: the done-callback covers the
            # async completion paths, this covers a queue shed (the
            # request object is abandoned unfinished) — submit blocks,
            # so returning at all means the lifecycle is over
            if rel is not None:
                rel()
            # in-process stitch: the engine's sealed record joins the
            # edge trace on every exit path (success, shed, deadline)
            if trace_ctx is not None and trace is not None:
                trace_ctx.absorb(trace.record, proc="engine")

    def submit_many(self, items: List[Dict[str, Any]]) -> List[Request]:
        """Coalesced pairwise admission (ISSUE 14): validate and admit a
        burst, enqueueing every admissible request under ONE queue lock
        acquisition (:meth:`MicroBatchQueue.put_many`) instead of one
        per request — the engine-side half of the transport's
        multi-submit frames.

        Each item is a dict: ``image1``, ``image2``, optional
        ``deadline_ms`` / ``num_flow_updates`` / ``trace_ctx`` (a
        propagated :class:`~raft_tpu.obs.TraceContext` — ISSUE 15) /
        ``priority`` / ``tenant`` (the QoS class markers — ISSUE 17), and
        an optional ``on_done`` callable invoked with the request handle
        on completion (the process worker's response coalescer rides it,
        so no thread parks per request). Returns one :class:`Request`
        handle per item, in order. Error-in-batch isolation: an item
        that fails validation, admission, quota, or queue shed comes back
        as an already-finished handle carrying its typed error — the
        rest of the burst is unaffected. Un-bucketed shapes take the slow
        path inline, exactly as :meth:`submit` would.

        Two internal item extensions (ISSUE 20) ride the tiler fan-out:
        ``shadow`` accounts the item under the ``shadow_*`` twins exactly
        as :meth:`submit` would, and an item carrying ``p1``/``p2``/
        ``hw`` (already-admitted [0, 1] slices) skips re-admission —
        ``skip_quota`` additionally skips the tenant charge, because the
        parent tiled request was charged once for all its tiles.
        """
        prepared: List[Optional[Request]] = []
        handles: List[Request] = []
        for it in items:
            cb = it.get("on_done")
            ctx = it.get("trace_ctx")
            sh = bool(it.get("shadow", False))
            t_sub = time.monotonic()
            try:
                deadline_ms = self._check_live(it.get("deadline_ms"))
                pr, ten = self._qos_resolve(
                    it.get("priority"), it.get("tenant")
                )
                iters = self._validate_iters(it.get("num_flow_updates"))
                if "p1" in it:
                    # tiler fan-out item: slices were admitted with the
                    # parent request; re-admitting would re-scale pixels
                    p1, p2 = it["p1"], it["p2"]
                    hw = (int(it["hw"][0]), int(it["hw"][1]))
                else:
                    p1, p2, hw = self._admit(it["image1"], it["image2"])
                rel = (
                    None if sh or it.get("skip_quota")
                    else self._qos_charge(pr, ten)
                )
            except BaseException as e:
                handles.append(self._finished_handle(error=e, on_done=cb))
                prepared.append(None)
                continue
            bucket = self._router.route(*hw)
            rid = self._new_rid(shadow=sh)
            if not sh:
                self._qos_stats.count(pr, "submitted")
            trace = self.tracer.start(
                "pair", rid, t_start=t_sub,
                trace_id=None if ctx is None else ctx.trace_id,
            )
            if trace is not None:
                trace.add_span("admit", t_sub, time.monotonic())
                trace.annotate(priority=pr, tenant=ten)
            deadline = time.monotonic() + deadline_ms / 1e3
            if bucket is None:
                # rare (un-bucketed shape): the slow path compiles and
                # runs on this thread either way, so it cannot coalesce
                req = Request(
                    rid, hw, None, None, hw, deadline, iters=iters,
                    priority=pr, tenant=ten, shadow=sh,
                )
                if rel is not None:
                    req.add_done_callback(rel)
                if cb is not None:
                    req.add_done_callback(cb)
                try:
                    res = self._submit_slow(
                        rid, p1, p2, hw, deadline, iters, trace=trace,
                        priority=pr, tenant=ten, shadow=sh,
                    )
                    req.finish(result=res)
                except BaseException as e:
                    req.finish(error=e)
                handles.append(req)
                prepared.append(None)
                continue
            req = Request(
                rid, bucket, self._router.pad_to(p1, bucket),
                self._router.pad_to(p2, bucket), hw, deadline, iters=iters,
                priority=pr, tenant=ten, shadow=sh,
            )
            req.trace = trace
            if rel is not None:
                req.add_done_callback(rel)
            if cb is not None:
                req.add_done_callback(cb)
            prepared.append(req)
            handles.append(req)
        live = [r for r in prepared if r is not None]
        if live:
            preempted: List[Request] = []
            outcomes = self._queue.put_many(
                live, retry_after_ms=self._retry_after_ms(),
                preempted=preempted,
            )
            for req, err in zip(live, outcomes):
                if err is None:
                    continue
                if isinstance(err, Overloaded):
                    self._count_outcome(req, "shed")
                    if not req.shadow:
                        self._qos_stats.count(req.priority, "shed")
                    self.recorder.record(
                        "shed", rid=req.rid, req_kind=req.kind,
                        retry_after_ms=err.retry_after_ms,
                    )
                    if self.config.qos_enabled and not req.shadow:
                        self.recorder.record(
                            "qos_shed", rid=req.rid, priority=req.priority,
                            tenant=req.tenant,
                            retry_after_ms=err.retry_after_ms,
                        )
                req.finish(error=err)
            if preempted:
                # the burst may displace queued lower-class work; every
                # victim is finished with the typed retryable shed
                self._qos_preempted(preempted, live[0])
        return handles

    def _finished_handle(self, *, error, on_done=None) -> Request:
        """A pre-failed Request handle for a multi-submit item that never
        reached the queue (validation/admission error)."""
        req = Request(-1, (0, 0), None, None, (0, 0), time.monotonic())
        if on_done is not None:
            req.add_done_callback(on_done)
        req.finish(error=error)
        return req

    def submit_tiled(
        self,
        image1,
        image2,
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
        shadow: bool = False,
    ) -> ServeResult:
        """Serve an off-bucket pair by tiling it into bucket-shaped
        sub-requests (ISSUE 20): degraded-but-served, at batch speed.

        The waste-aware :class:`~raft_tpu.serve.tiler.TilePlanner` picks
        the cheapest (bucket, overlap-stride) tiling for ``(H, W)``; both
        images are sliced at identical offsets into the planned tiles and
        pushed through :meth:`submit_many` under ONE
        :meth:`MicroBatchQueue.put_many` lock acquisition, so a tiled
        request costs the admission path one acquisition no matter how
        many tiles it fans into. Per-tile flows are blended host-side
        under feathered linear-ramp weights (cached per plan) — no new
        device programs, no new host syncs beyond the per-tile result
        fetches the batch path already pays.

        Failure semantics: a tile that fails terminally fails the whole
        request with that tile's typed error; a shed tile (retryable,
        carrying ``retry_after_ms``) is retried within the *request's*
        deadline. The tenant quota is charged once for the whole request
        (tiles inherit its QoS class but ride ``skip_quota`` items).
        On-bucket shapes fall through to :meth:`submit` — tiling never
        taxes a shape a bucket already admits. Works regardless of
        ``config.unknown_shape``; the ``'tiled'`` arm only controls
        whether :meth:`submit` routes here automatically.

        Returns a :class:`ServeResult` with ``tiled=True`` and
        ``tiles=N``; ``num_flow_updates``/``level``/``degraded`` report
        the most conservative tile (min iterations, max brownout level).
        """
        a1 = np.asarray(image1)
        if a1.ndim == 3 and self._router.route(
            int(a1.shape[0]), int(a1.shape[1])
        ) is not None:
            return self.submit(
                image1, image2, deadline_ms=deadline_ms,
                num_flow_updates=num_flow_updates, trace_ctx=trace_ctx,
                priority=priority, tenant=tenant, shadow=shadow,
            )
        t_sub = time.monotonic()
        deadline_ms = self._check_live(deadline_ms)
        pr, ten = self._qos_resolve(priority, tenant)
        iters = self._validate_iters(num_flow_updates)
        p1, p2, hw = self._admit(image1, image2)
        rel = None if shadow else self._qos_charge(pr, ten)
        t_adm = time.monotonic()
        # the parent is an envelope: its tiles carry the engine-level
        # submitted/completed/shed accounting (they are real queue
        # citizens), the ``tiler`` stats block counts the envelope — so
        # the rid is allocated without touching the submitted counter
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
        trace = self.tracer.start(
            "tiled", rid, t_start=t_sub,
            trace_id=None if trace_ctx is None else trace_ctx.trace_id,
        )
        if trace is not None:
            trace.add_span("admit", t_sub, t_adm)
            trace.annotate(priority=pr, tenant=ten)
        deadline = time.monotonic() + deadline_ms / 1e3
        try:
            return self._run_tiled(
                rid, p1, p2, hw, deadline, iters, trace=trace,
                priority=pr, tenant=ten, shadow=shadow, t_sub=t_sub,
            )
        finally:
            # one-shot, mirrors submit(): covers every exit path
            if rel is not None:
                rel()
            if trace_ctx is not None and trace is not None:
                trace_ctx.absorb(trace.record, proc="engine")

    def _run_tiled(
        self, rid, p1, p2, hw, deadline, req_iters=None, *,
        trace=None, priority="standard", tenant="default",
        shadow=False, t_sub=None,
    ) -> ServeResult:
        """Tiled fan-out core: plan -> slice -> one put_many -> blend.

        ``p1``/``p2`` are already-admitted ``(1, H, W, 3)`` arrays;
        tile slices are zero-copy views into them.
        """
        t0 = t_sub if t_sub is not None else time.monotonic()
        try:
            plan = self._tiler.plan(hw)
        except ShapeRejected:
            self._count("rejected")
            with self._lock:
                self._tiler_counters["failures"] += 1
            if trace is not None:
                trace.finish(ok=False, error="ShapeRejected")
            raise
        with self._lock:
            self._tiler_counters["requests"] += 1
            self._tiler_px[0] += plan.hw[0] * plan.hw[1]
            self._tiler_px[1] += plan.dispatched_px
        t_fan = time.monotonic()
        acq0 = self._queue.put_many_calls
        items: List[Dict[str, Any]] = [
            {
                "p1": p1[:, t.y0:t.y0 + t.h, t.x0:t.x0 + t.w],
                "p2": p2[:, t.y0:t.y0 + t.h, t.x0:t.x0 + t.w],
                "hw": (t.h, t.w),
                "deadline_ms": max(1.0, (deadline - time.monotonic()) * 1e3),
                "num_flow_updates": req_iters,
                "priority": priority, "tenant": tenant,
                "shadow": shadow, "skip_quota": True,
            }
            for t in plan.tiles
        ]
        handles = self.submit_many(items)
        # the one-batch admission pin: the whole fan-out rides a single
        # put_many acquisition (retries below re-acquire, and are
        # counted separately as tiles_retried)
        acq = self._queue.put_many_calls - acq0
        with self._lock:
            self._tiler_counters["tiles_submitted"] += len(items)
            self._tiler_counters["admission_acquisitions"] += acq
        if trace is not None:
            trace.add_span(
                "tiled_submit", t_fan, tiles=len(items),
                bucket=f"{plan.bucket[0]}x{plan.bucket[1]}",
                put_many_acquisitions=acq,
            )
        try:
            results: List[ServeResult] = []
            for i, h in enumerate(handles):
                while True:
                    if not h.wait(
                        max(0.0, deadline - time.monotonic()) + 0.05
                    ):
                        h.finish(error=DeadlineExceeded(
                            f"tiled request {rid} missed its deadline "
                            f"waiting on tile {i + 1}/{len(handles)}"
                        ))
                    if h.error is None:
                        break
                    err = h.error
                    retry_ms = getattr(err, "retry_after_ms", None)
                    if (
                        retry_ms is not None
                        and deadline - time.monotonic() > retry_ms / 1e3
                    ):
                        # shed tile: back off and retry within the
                        # request's own deadline; terminal tile errors
                        # fall through and fail the whole request typed
                        time.sleep(retry_ms / 1e3)
                        with self._lock:
                            self._tiler_counters["tiles_retried"] += 1
                        it = dict(items[i])
                        it["deadline_ms"] = max(
                            1.0, (deadline - time.monotonic()) * 1e3
                        )
                        h = self.submit_many([it])[0]
                        continue
                    raise err
                results.append(h.result)
            t_blend = time.monotonic()
            weights = self._tiler.weights(plan)
            flow = blend_tiles(plan, weights, [r.flow for r in results])
            now = time.monotonic()
            blend_ms = (now - t_blend) * 1e3
            with self._lock:
                self._tiler_counters["completed"] += 1
                self._tiler_blend_ms.append(blend_ms)
                del self._tiler_blend_ms[: -self.config.latency_window]
            reasons = {r.exit_reason for r in results}
            res = ServeResult(
                flow=flow,
                rid=rid,
                bucket=plan.bucket,
                num_flow_updates=min(r.num_flow_updates for r in results),
                level=max(r.level for r in results),
                degraded=any(r.degraded for r in results),
                latency_ms=(now - t0) * 1e3,
                exit_reason=reasons.pop() if len(reasons) == 1 else "target",
                trace_id=None if trace is None else trace.trace_id,
                tiled=True,
                tiles=plan.n_tiles,
            )
            if trace is not None:
                trace.add_span("tiled_blend", t_blend, now)
                trace.annotate(
                    tiled=True, tiles=plan.n_tiles,
                    bucket=f"{plan.bucket[0]}x{plan.bucket[1]}",
                    waste_frac=round(plan.waste_frac, 4),
                    blend_ms=round(blend_ms, 3),
                    latency_ms=round(res.latency_ms, 3),
                )
                trace.finish(ok=True)
            return res
        except BaseException as e:
            with self._lock:
                self._tiler_counters["failures"] += 1
            if trace is not None:
                trace.finish(ok=False, error=type(e).__name__)
            raise

    def open_stream(self) -> StreamSession:
        """Start a stream session: encode-once feature caching per frame.

        Consecutive frames of a video share a frame per pair; the session
        caches each frame's feature/context maps so pair (t, t+1) pays
        the encoder only for frame t+1 — ``stats()`` reports the hit rate
        as ``encoder_cache_hit_rate``. Sessions are LRU-bounded
        (``config.stream_cache_size``); an evicted or invalidated session
        transparently re-primes (``flow=None`` for that one frame).
        """
        if self._encode is None:
            raise InvalidInput(
                "stream serving is disabled (stream_cache_size=0)"
            )
        sid = self._stream_cache.open()
        return StreamSession(self, sid)

    def submit_frame(
        self,
        stream_id: int,
        frame,
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
        shadow: bool = False,
        return_flow8: bool = False,
    ) -> ServeResult:
        """Advance stream ``stream_id`` by one frame.

        Returns flow(previous frame -> this frame) at the caller's
        resolution, or a ``primed=True`` result (``flow=None``) when this
        frame opens a fresh pair (first frame, or first after an
        invalidation/eviction). One outstanding frame per stream.
        ``trace_ctx`` joins an externally-sampled trace, and ``priority``
        / ``tenant`` classify the request for QoS, exactly as in
        :meth:`submit`. ``return_flow8`` asks for the pair's 1/8-grid flow
        beside the full one (``ServeResult.flow8``: 56 KB more on the
        retirement's fetch at 440x1024; the iteration pool only).
        """
        if self._encode is None:
            raise InvalidInput(
                "stream serving is disabled (stream_cache_size=0)"
            )
        t_sub = time.monotonic()
        deadline_ms = self._check_live(deadline_ms)
        pr, ten = self._qos_resolve(priority, tenant)
        iters = self._validate_iters(num_flow_updates)
        p, hw = self._admit_frame(frame)
        t_adm = time.monotonic()
        bucket = self._router.route(*hw)
        if bucket is None:
            self._count("rejected")
            raise ShapeRejected(
                f"no bucket admits stream frame shape {hw} (buckets: "
                f"{list(self._router.buckets)}); streams have no slow path "
                f"— resize or reconfigure"
            )
        refusal = self._stream_cache.begin_frame(stream_id, bucket, hw)
        if refusal is not None:
            raise InvalidInput(refusal)
        req = None
        rel = None
        try:
            rel = None if shadow else self._qos_charge(pr, ten)
            rid = self._new_rid(shadow=shadow)
            if not shadow:
                self._qos_stats.count(pr, "submitted")
            deadline = time.monotonic() + deadline_ms / 1e3
            req = Request(
                rid, bucket, None, self._router.pad_to(p, bucket), hw,
                deadline, kind="stream", stream_id=stream_id, iters=iters,
                priority=pr, tenant=ten, shadow=shadow,
            )
            req.want_flow8 = bool(return_flow8)
            req.trace = self.tracer.start(
                "stream", rid, t_start=t_sub,
                trace_id=None if trace_ctx is None else trace_ctx.trace_id,
            )
            if req.trace is not None:
                req.trace.add_span("admit", t_sub, t_adm)
                req.trace.annotate(stream_id=stream_id, priority=pr,
                                   tenant=ten)
            if rel is not None:
                req.add_done_callback(rel)
            return self._enqueue_and_wait(req, deadline_ms)
        finally:
            if rel is not None:
                rel()  # one-shot: covers the shed path (req unfinished)
            self._stream_cache.end_frame(stream_id)
            if (
                trace_ctx is not None
                and req is not None
                and req.trace is not None
            ):
                trace_ctx.absorb(req.trace.record, proc="engine")

    def close_stream(self, stream_id: int) -> None:
        """Drop a stream session and its cached features."""
        if self._stream_cache is not None:
            self._stream_cache.close(stream_id)

    def health(self) -> dict:
        """Liveness/readiness for an external supervisor or LB probe."""
        with self._lock:
            trips = self._counters["watchdog_trips"]
            quarantined = self._counters["quarantined"]
        return {
            "ready": self._ready.is_set(),
            "healthy": (
                self._thread is not None
                and self._thread.is_alive()
                and not self._stop.is_set()
            ),
            "draining": self._draining.is_set(),
            "queue_depth": self._queue.depth(),
            "queue_capacity": self.config.queue_capacity,
            "level": self._controller.level,
            "num_flow_updates": self._controller.num_flow_updates,
            "watchdog_trips": trips,
            "quarantined": quarantined,
        }

    @property
    def variables_hash(self) -> str:
        """The serving-weights identity (ISSUE 18): sha256 over the
        flattened weight tree — paths, shapes, dtypes AND values. Unlike
        the aot artifact fingerprint (value-independent on purpose:
        executables survive checkpoint updates), this hash must tell two
        checkpoints of the same architecture apart — it is what a
        promoted fleet converges to, and what a rollback restores.
        Cached: the value walk runs once per engine."""
        h = self._variables_hash_cache
        if h is None:
            import hashlib

            digest = hashlib.sha256()
            leaves = jax.tree_util.tree_flatten_with_path(self._dev_vars)[0]
            for path, leaf in leaves:
                arr = np.asarray(leaf)
                digest.update(
                    f"{jax.tree_util.keystr(path)}:{arr.shape}:"
                    f"{arr.dtype}".encode()
                )
                digest.update(np.ascontiguousarray(arr).tobytes())
            h = self._variables_hash_cache = digest.hexdigest()
        return h

    @property
    def supports_init_flow(self) -> bool:
        """Whether pair submits can honor an ``init_flow`` seed (ISSUE
        19): seeded admission runs encode + ``begin_features`` — both the
        iteration pool and the stream encode program must exist. The
        edge's near-dup layer checks this before building a seed; a tier
        that cannot seed serves the near-dup cold instead."""
        return self._pool_progs is not None and self._encode is not None

    def _prepare_init_flow(self, init_flow, bucket) -> Optional[np.ndarray]:
        """Validate + pad a caller-grid ``(h8, w8, 2)`` seed to the
        bucket's 1/8 grid (``(1, bh/8, bw/8, 2)``, zeros beyond the
        caller's extent — a zero seed IS the cold start, so padding adds
        nothing). ``None`` when this engine cannot seed (best-effort
        hint, never an error path of its own); malformed seeds raise
        typed ``InvalidInput`` like any other bad input."""
        if not self.supports_init_flow:
            return None
        arr = np.asarray(init_flow, np.float32)
        if arr.ndim != 3 or arr.shape[-1] != 2:
            raise InvalidInput(
                f"init_flow must be (h/8, w/8, 2), got {arr.shape}"
            )
        if not np.all(np.isfinite(arr)):
            raise InvalidInput("init_flow contains non-finite values")
        bh8, bw8 = bucket[0] // 8, bucket[1] // 8
        out = np.zeros((1, bh8, bw8, 2), np.float32)
        h = min(arr.shape[0], bh8)
        w = min(arr.shape[1], bw8)
        out[0, :h, :w] = arr[:h, :w]
        return out

    def _tiler_block(self) -> dict:
        """The ``stats()['tiler']`` block (ISSUE 20): envelope-level
        tiled-request accounting. Schema pinned by
        ``tests/test_observability.py::TILER_STATS_KEYS``."""
        with self._lock:
            c = dict(self._tiler_counters)
            blend = list(self._tiler_blend_ms)
            useful, dispatched = self._tiler_px
        # traffic-weighted dispatched-pixel overhead across every tiled
        # request served (None until the first one)
        waste = 1.0 - useful / dispatched if dispatched else None
        return {
            "enabled": self.config.unknown_shape == "tiled",
            "overlap_px": self.config.tile_overlap_px,
            "plans_built": self._tiler.plans_built,
            "plan_cache_hits": self._tiler.plan_cache_hits,
            "requests": c["requests"],
            "completed": c["completed"],
            "failures": c["failures"],
            "tiles_submitted": c["tiles_submitted"],
            "tiles_retried": c["tiles_retried"],
            "admission_acquisitions": c["admission_acquisitions"],
            "waste_frac": waste,
            "blend_ms": {
                "n": len(blend),
                "p50_ms": float(np.percentile(blend, 50)) if blend else None,
                "p99_ms": float(np.percentile(blend, 99)) if blend else None,
            },
        }

    def stats(self) -> dict:
        """Serving counters + degradation + per-bucket latency quantiles +
        hot-path efficiency (padding waste, encoder cache hit rate,
        compiled-program counts).

        ``["pool"]["buckets"]`` says, per live bucket, what the slot
        state holds and how the lookup kernel reads it
        (``pool.state_layout``): ``state_bytes`` / ``slot_bytes``,
        ``query_tile``, ``coords_blocked``, and per raw-volume level the
        rows held resident (``level_rows``) and the rows a grid step
        brings into VMEM at a time (``window_rows``; equal where the
        level is read whole). ``lookup_rows_read`` /
        ``lookup_rows_whole`` sum, over the ticks whose pacing token was
        fetched, the 128-lane rows of those levels the kernel read and
        what reading every level whole would have taken: their ratio is
        the share of the y-dot levels the kernel read — the windows'
        share of the levels' rows where every query tile's taps fit one
        window, more where vertical flow spreads a tile's taps over
        several (docs/observability.md, section 1)."""
        with self._lock:
            counters = dict(self._counters)
            latency = {
                f"{bh}x{bw}": {
                    "n": len(v),
                    "p50_ms": float(np.percentile(v, 50)) if v else None,
                    "p99_ms": float(np.percentile(v, 99)) if v else None,
                }
                for (bh, bw), v in self._latency.items()
            }
            quarantined = list(self._quarantined_rids)
        counters["queue_depth"] = self._queue.depth()
        dispatched = counters["dispatched_rows"]
        hits = counters["encode_cache_hits"]
        misses = counters["encode_cache_misses"]
        pool_mode = self.config.pool_capacity > 0
        if pool_mode:
            # pool definition: idle-slot-iterations / dispatched-slot-
            # iterations — the fraction of dispatched refinement work that
            # advanced nobody (docs/perf_notes.md). The fallback engine
            # keeps the whole-request definition (padded/dispatched rows).
            disp_si = counters["dispatched_slot_iters"]
            padding_waste = (
                counters["idle_slot_iters"] / disp_si if disp_si else 0.0
            )
        else:
            padding_waste = (
                counters["padded_rows"] / dispatched if dispatched else 0.0
            )
        with self._lock:
            ttfd = list(self._ttfd)
        # Per-device slot occupancy (ISSUE 8): with the slot table row-
        # sharded over the mesh `data` axis, slot i lives on device
        # i // (capacity / mesh_devices) — contiguous blocks. The list is
        # the occupied fraction of each device's slots across buckets
        # (length mesh_devices; [overall] for the 1-device engine).
        n_dev = self.config.mesh_devices
        per_dev = [0] * n_dev
        slots_per_dev = max(1, self._pool_cap // n_dev) if pool_mode else 1
        for p in self._pools.values():
            for i, _ in p.occupied():
                per_dev[min(n_dev - 1, i // slots_per_dev)] += 1
        dev_denom = slots_per_dev * max(1, len(self._pools))
        pool_stats = {
            "capacity": self._pool_cap,
            "mesh_devices": n_dev,
            "per_device_occupancy": [
                c / dev_denom for c in per_dev
            ] if pool_mode else [],
            "occupied": sum(
                p.occupied_count() for p in self._pools.values()
            ),
            "ticks": counters["pool_ticks"],
            "occupancy": (
                1.0 - counters["idle_slot_iters"]
                / counters["dispatched_slot_iters"]
                if counters["dispatched_slot_iters"]
                else 0.0
            ),
            "ttfd_p50_ms": (
                float(np.percentile(ttfd, 50)) if ttfd else None
            ),
            "tick_ms_ewma": (
                float(
                    np.mean([p.tick_ewma_ms for p in self._pools.values()])
                )
                if self._pools
                else None
            ),
            # per live bucket: what its slot state holds on the device
            # and how the lookup kernel reads it (pool.state_layout)
            "buckets": {
                f"{bh}x{bw}": dict(p.layout)
                for (bh, bw), p in self._pools.items()
            },
        }
        with self._lock:
            r_sum = self._resid_iter_sum.copy()
            r_cnt = self._resid_iter_cnt.copy()
        return {
            **counters,
            "padding_waste": padding_waste,
            "mesh_devices": self.config.mesh_devices,
            # weights identity (ISSUE 18): a string, so the router's
            # numeric aggregate skips it while per-engine views carry it
            "variables_hash": self.variables_hash,
            "boot": dict(self._boot),
            # observability spine (ISSUE 10): tracing + flight-recorder
            # accounting; the raw rings live on engine.tracer /
            # engine.recorder, Prometheus text on engine.prometheus()
            "obs": {
                "trace_sample_rate": self.config.trace_sample_rate,
                "traces_started": self.tracer.started,
                "traces_finished": self.tracer.finished,
                "traces_dropped": self.tracer.dropped,
                "events_recorded": self.recorder.events_recorded,
                "postmortem_dumps": self.recorder.dumps,
            },
            # device-time ledger (ISSUE 11): slot-iter cost priced in
            # milliseconds — the full per-family table lives on
            # engine.device_time_breakdown()
            "ledger": self.ledger.breakdown(),
            # burn-rate alerting (ISSUE 11)
            "alerts": self._alerts.snapshot(),
            # convergence telemetry (ISSUE 11, pool mode): final-residual
            # quantiles + mean residual per iteration number (the
            # residual-vs-iters table behind serve_bench's
            # serve_convergence BENCH line and the threshold-calibration
            # evidence for scripts/calibrate_convergence.py), plus the
            # live adaptive-compute knobs (ISSUE 12)
            "convergence": {
                "enabled": pool_mode,
                "threshold": self.config.pool_converge_thresh,
                "streak": self.config.pool_converge_streak,
                "warm_start": self._warm_start,
                "n": self._resid_final.count,
                "final_residual_p50": self._resid_final.quantile(0.50),
                "final_residual_p99": self._resid_final.quantile(0.99),
                "resid_by_iter": [
                    round(float(s / c), 6) if c else None
                    for s, c in zip(r_sum, r_cnt)
                ],
            },
            "pool": pool_stats,
            # QoS spine (ISSUE 17): per-class counters/latency + the
            # per-tenant quota state; "enabled" pins the enforcement arm
            "qos": qos_stats_block(
                self.config.qos_enabled, self.config.qos_aging_ms,
                self._qos_stats, self._qos_policy,
            ),
            # waste-aware tile fan-out (ISSUE 20): the envelope-level
            # view — tiles themselves ride the ordinary counters above
            "tiler": self._tiler_block(),
            "encoder_cache_hit_rate": (
                hits / (hits + misses) if (hits + misses) else None
            ),
            # the device-resident session cache: frames admitted through
            # it, sessions remembered, bytes of table rows they hold
            "stream_frames": hits + misses,
            **(
                self._stream_cache.stats() if self._stream_cache is not None
                else {"stream_sessions": 0, "stream_cache_bytes": 0}
            ),
            "batch_ladder": list(self._batch_ladder),
            "programs": self.program_counts(),
            "degradation": self._controller.snapshot(),
            "latency": latency,
            "quarantined_rids": quarantined,
        }

    def prometheus(self) -> str:
        """Prometheus text exposition of this engine's metrics registry
        (counters, queue/degradation/pool gauges, latency + device-time
        histograms, per-alert-rule gauges), plus the QoS series: per-class
        counters labeled ``class=`` and per-tenant quota state labeled
        ``tenant=`` (ISSUE 17) — dashboards slice overload by who paid
        for it, not just how much of it there was."""
        text = self.metrics.prometheus_text()

        def esc(s: str) -> str:
            return s.replace("\\", "\\\\").replace('"', '\\"')

        lines = ["# TYPE serve_qos_class counter"]
        for cls, cstats in sorted(self._qos_stats.snapshot().items()):
            for k in QosStats.COUNTER_KEYS:
                lines.append(
                    f'serve_qos_class{{class="{esc(cls)}",key="{k}"}} '
                    f"{int(cstats.get(k, 0))}"
                )
        tenants = (
            self._qos_policy.snapshot() if self._qos_policy is not None
            else {}
        )
        if tenants:
            lines.append("# TYPE serve_qos_tenant gauge")
            for ten, tstats in sorted(tenants.items()):
                for k in ("inflight", "quota_refused"):
                    lines.append(
                        f'serve_qos_tenant{{tenant="{esc(ten)}",key="{k}"}} '
                        f"{int(tstats.get(k, 0))}"
                    )
        return text + "\n".join(lines) + "\n"

    def device_time_breakdown(self) -> Dict[str, Any]:
        """Per-program-family device-time attribution (ISSUE 11).

        Each family the ledger has sampled reports executions, sampled
        count, mean/EWMA/p50/p99 device ms, the extrapolated total, and
        its ``share`` of estimated device time — milliseconds, not row
        counts. Empty (``families == 0``) when
        ``config.ledger_sample_every == 0``.
        """
        return self.ledger.breakdown()

    def alerts(self) -> Dict[str, Any]:
        """The burn-rate alert surface: active alerts (rule, severity,
        live burn), fire/resolve counters, and the configured rules."""
        snap = self._alerts.snapshot()
        snap["active"] = self._alerts.active()
        return snap

    def _alert_snapshot(self) -> Dict[str, float]:
        """What the alert rules see: the engine counters plus the
        device-time drift gauge, one flat dict."""
        with self._lock:
            snap: Dict[str, float] = dict(self._counters)
        snap["device_time_drift"] = self.ledger.drift()
        return snap

    def program_counts(self) -> Dict[str, int]:
        """Compiled-program count per program family (-1 if unsupported).

        Counts merge the jit caches (programs compiled on demand) with
        the AOT executable overlay (programs warmup compiled or loaded
        from the warmup artifact — jit caches stay empty for those by
        design). The bound the warmup path promises: after
        ``warmup=True`` these stay constant under any admitted traffic —
        the worker thread never compiles.
        """

        def n(f) -> int:
            if f is None:
                return 0
            try:
                return int(f._cache_size())
            except Exception:  # pragma: no cover - jax internals moved
                return -1

        overlay: Dict[str, int] = {}
        for key in self._aot_execs:
            overlay[key[0]] = overlay.get(key[0], 0) + 1
        counts = {
            "pairwise": n(self._apply) + overlay.get("pairwise", 0),
            "encode": n(self._encode) + overlay.get("encode", 0),
            "iterate": n(self._iterate) + overlay.get("iterate", 0),
        }
        families = {}
        if self._pool_progs is not None:
            families.update(self._pool_progs.counts())
        if self._stream_cache is not None:
            families.update(self._stream_cache.programs.counts())
        counts.update(
            {name: cnt + overlay.get(name, 0)
             for name, cnt in families.items()}
        )
        return counts

    # -- admission ---------------------------------------------------------

    def _check_live(self, deadline_ms: Optional[float]) -> float:
        if not self._ready.is_set() or self._stop.is_set():
            raise EngineStopped("serve engine is not running")
        if self._draining.is_set():
            retry_ms = self.config.drain_retry_after_ms
            raise Draining(
                f"engine draining for restart; retry in ~{retry_ms:.0f}ms",
                retry_after_ms=retry_ms,
            )
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        if deadline_ms <= 0:
            raise InvalidInput(f"deadline_ms must be positive, got {deadline_ms}")
        return deadline_ms

    def _new_rid(self, shadow: bool = False) -> int:
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            self._counters["shadow_submitted" if shadow else "submitted"] += 1
        return rid

    def _count_outcome(self, r: Request, key: str) -> None:
        """Count a per-request outcome, diverted to the ``shadow_*``
        twin for mirrored rollout traffic (ISSUE 18) so every signal
        derived from the live counters stays blind to shadow load."""
        self._count(f"shadow_{key}" if r.shadow else key)

    # -- QoS (ISSUE 17) ----------------------------------------------------

    def _qos_resolve(
        self, priority: Optional[str], tenant: Optional[str]
    ) -> Tuple[str, str]:
        """Resolve/validate the request's class and tenant (config
        defaults when unspecified; unknown class -> ``InvalidInput``)."""
        cfg = self.config
        pr = validate_priority(
            priority if priority is not None else cfg.qos_default_priority
        )
        return pr, (tenant if tenant else cfg.qos_default_tenant)

    def _qos_charge(self, priority: str, tenant: str):
        """Charge one admission against the tenant's quota.

        Returns a one-shot releaser (attach it as a done callback AND
        call it on abandonment paths — only the first call releases), or
        ``None`` when QoS enforcement is off. Raises the retryable
        :class:`~raft_tpu.serve.QuotaExceeded` on breach.
        """
        policy = self._qos_policy
        if policy is None:
            return None
        try:
            policy.admit(tenant, priority)
        except QuotaExceeded as e:
            self._qos_stats.count(priority, "quota_refused")
            self.recorder.record(
                "quota_breach", tenant=tenant, priority=priority,
                retry_after_ms=e.retry_after_ms,
            )
            raise
        lock = threading.Lock()
        done = [False]

        def rel(_req=None):
            with lock:
                if done[0]:
                    return
                done[0] = True
            policy.release(tenant)

        return rel

    def _qos_preempted(self, preempted: List[Request], by: Request) -> None:
        """Finish queue-displaced lower-class victims with the typed
        retryable shed — a preempted request is never silently lost
        (zero-loss accounting: it counts exactly once, as a shed)."""
        if not preempted:
            return
        retry_ms = self._retry_after_ms()
        for v in preempted:
            err = Overloaded(
                f"request {v.rid} ({v.priority}) preempted by a "
                f"higher-class arrival; retry in ~{retry_ms:.0f}ms",
                retry_after_ms=retry_ms,
            )
            if v.finish(error=err):
                self._count_outcome(v, "shed")
                if v.shadow:
                    continue
                self._qos_stats.count(v.priority, "preempted")
                self.recorder.record(
                    "qos_preempt", rid=v.rid, priority=v.priority,
                    tenant=v.tenant, by_rid=by.rid,
                    by_priority=by.priority, retry_after_ms=retry_ms,
                )

    def _qos_levels(
        self, live: List[Request], iters: int, level: int
    ) -> Tuple[int, int]:
        """Class-aware brownout for a whole-request batch: under
        pressure the batch runs at the *highest* class present's
        effective level (nobody's quality is cut below their class's
        entitlement); a pure batch-class batch browns out first."""
        if not self.config.qos_enabled or level <= 0:
            return iters, level
        min_rank = min(r.rank for r in live)
        eff = brownout_level(level, min_rank, len(self._controller.ladder))
        return self._controller.ladder[eff], eff

    def _qos_forecast_slack(self, r: Request) -> float:
        """Deadline-forecast retirement preference: under pressure a
        lower-class slot forecasts with extra slack, so it cashes in the
        anytime ladder earlier and frees its slot for high-class work."""
        if not self.config.qos_enabled or self._controller.level <= 0:
            return 1.0
        return 1.0 + 0.5 * r.rank

    def _validate_iters(self, n: Optional[int]) -> Optional[int]:
        """Validate a per-request ``num_flow_updates`` against the
        configured full-quality top of the ladder."""
        if n is None:
            return None
        full = self.config.ladder[0]
        if int(n) != n or not (1 <= int(n) <= full):
            raise InvalidInput(
                f"num_flow_updates must be an int in [1, {full}] (the "
                f"configured full-quality ladder top), got {n!r}"
            )
        return int(n)

    def _iter_rung(self, n: Optional[int]) -> int:
        """Fallback-engine granularity for a per-request iteration cap:
        the largest compiled ladder entry <= n (floor at the ladder's
        last entry — the compiled-program set stays closed)."""
        if n is None:
            return self.config.ladder[0]
        for it in self.config.ladder:          # strictly descending
            if it <= n:
                return it
        return self.config.ladder[-1]

    def _honor_iters(self, live: List[Request], ctrl_iters: int) -> int:
        """Fallback-engine honoring of per-request ``num_flow_updates``:
        the batch runs at the max of its members' rungs (nobody's quality
        is cut below their ask) capped by the degradation target; the
        iterations that saves are counted as ``early_exit_iters_saved``.
        """
        want = max(self._iter_rung(r.iters) for r in live)
        iters = min(ctrl_iters, want)
        if iters < ctrl_iters:
            with self._lock:
                self._counters["early_exit_iters_saved"] += (
                    (ctrl_iters - iters) * len(live)
                )
        return iters

    def _admit(self, image1, image2):
        """Validate one raw pair; returns normalized (1,H,W,3) + (H, W)."""
        a1, a2 = np.asarray(image1), np.asarray(image2)
        if a1.ndim != 3 or a2.ndim != 3:
            raise InvalidInput(
                f"serve requests are single (H, W, 3) pairs, got shapes "
                f"{a1.shape} / {a2.shape}; submit batch members individually "
                f"(the engine micro-batches internally)"
            )
        if a1.shape != a2.shape:
            raise InvalidInput(
                f"image shapes differ: {a1.shape} vs {a2.shape}"
            )
        try:
            # owns the [0,255] -> [-1,1] contract AND the nonfinite reject
            p1 = FlowEstimator._normalize(a1)
            p2 = FlowEstimator._normalize(a2)
        except ValueError as e:
            self._count("invalid")
            raise InvalidInput(str(e)) from e
        return p1, p2, (int(a1.shape[0]), int(a1.shape[1]))

    def _admit_frame(self, frame):
        """Validate one raw stream frame; returns (1, H, W, 3) + (H, W)."""
        a = np.asarray(frame)
        if a.ndim != 3:
            raise InvalidInput(
                f"stream frames are single (H, W, 3) images, got {a.shape}"
            )
        try:
            p = FlowEstimator._normalize(a)
        except ValueError as e:
            self._count("invalid")
            raise InvalidInput(str(e)) from e
        return p, (int(a.shape[0]), int(a.shape[1]))

    def _enqueue_and_wait(self, req: Request, deadline_ms: float):
        preempted: List[Request] = []
        try:
            self._queue.put(
                req, retry_after_ms=self._retry_after_ms(),
                preempted=preempted,
            )
        except Overloaded as e:
            self._count_outcome(req, "shed")
            if not req.shadow:
                self._qos_stats.count(req.priority, "shed")
            self.recorder.record(
                "shed", rid=req.rid, req_kind=req.kind,
                retry_after_ms=e.retry_after_ms,
            )
            if self.config.qos_enabled and not req.shadow:
                self.recorder.record(
                    "qos_shed", rid=req.rid, priority=req.priority,
                    tenant=req.tenant, retry_after_ms=e.retry_after_ms,
                )
            if req.trace is not None:
                req.trace.finish(ok=False, error="Overloaded")
            raise
        self._qos_preempted(preempted, req)
        if not req.wait(max(0.0, req.remaining) + 0.05):
            # worker still busy past our deadline: fail caller-side (set-once
            # means a simultaneous worker finish wins harmlessly)
            if req.finish(
                error=DeadlineExceeded(
                    f"request {req.rid} missed its {deadline_ms:.0f}ms deadline"
                )
            ) and not req.shadow:
                self._qos_stats.count(req.priority, "expired")
            self._count_outcome(req, "expired")
        if req.error is not None:
            raise req.error
        return req.result

    def _submit_slow(self, rid, p1, p2, hw, deadline, req_iters=None,
                     trace=None, priority="standard", tenant="default",
                     shadow=False):
        """Un-bucketed shape: reject, tile, or run rate-limited on *this*
        thread."""
        if self.config.unknown_shape == "reject":
            self._count("rejected")
            if trace is not None:
                trace.finish(ok=False, error="ShapeRejected")
            buckets = tuple(self._router.buckets)
            raise ShapeRejected(
                f"no bucket admits shape {hw} (buckets: "
                f"{list(buckets)}); resize, reconfigure, or set "
                f"unknown_shape='slow_path' or 'tiled'",
                supported_buckets=buckets,
                nearest=nearest_bucket(hw, buckets),
            )
        if self.config.unknown_shape == "tiled":
            # only multi-submit items land here under 'tiled' (submit()
            # delegates to submit_tiled before any accounting); their rid
            # was already counted submitted, so balance it on success
            res = self._run_tiled(
                rid, p1, p2, hw, deadline, req_iters, trace=trace,
                priority=priority, tenant=tenant, shadow=shadow,
            )
            self._count("shadow_completed" if shadow else "completed")
            return res
        if not self._slow_tokens.try_take():
            self._count("shed_slow_path")
            self._qos_stats.count(priority, "shed")
            self.recorder.record("shed", rid=rid, req_kind="slow_path")
            if trace is not None:
                trace.finish(ok=False, error="Overloaded")
            raise Overloaded(
                f"slow path over its {self.config.slow_path_per_s}/s rate",
                retry_after_ms=self._slow_tokens.retry_after_ms(),
            )
        shape = self._router.natural_shape(*hw)
        req = Request(
            rid, shape, self._router.pad_to(p1, shape),
            self._router.pad_to(p2, shape), hw, deadline, slow_path=True,
            iters=req_iters, priority=priority, tenant=tenant,
        )
        req.trace = trace
        # honored exactly: the slow path compiles per shape on the
        # caller's thread anyway, so per-request iters add no program
        # pressure on the batch thread
        iters = self._controller.num_flow_updates
        if req_iters is not None:
            iters = min(iters, req_iters)
        with self._slow_lock:  # one novel-shape compile at a time
            t0 = time.monotonic()
            flow = np.asarray(
                self._run_batch(
                    self._pad_rows(req.p1), self._pad_rows(req.p2), iters
                )
            )
        if trace is not None:
            trace.add_span("dispatch", t0, iters=iters, slow_path=True)
        flow = self._request_flow(req, flow[0])
        if not np.isfinite(flow).all():
            self._quarantine(req)
            raise req.error
        self._count("slow_path")
        return self._finish_ok(req, flow, iters, t0=t0)

    # -- worker ------------------------------------------------------------

    def _worker(self) -> None:
        """The batch thread: survives any per-batch failure by contract.

        Runs a bounded dispatch pipeline: up to ``pipeline_depth`` batches
        are dispatched-but-unfetched at once, so batch N+1 is assembled,
        staged, and dispatched while batch N computes (JAX async
        dispatch). Completion order is dispatch order; a full window or an
        idle queue drains the oldest in-flight batch first.
        """
        cfg = self.config
        inflight: "collections.deque[_Inflight]" = collections.deque()
        last_sheds = self._shed_count()

        def complete_oldest() -> None:
            inf = inflight.popleft()
            try:
                self._complete(inf)
            except Exception as e:  # isolation: fail the batch, not the worker
                self._count("worker_errors")
                err = ServeError(f"batch execution failed: {e!r}")
                for r in inf.live:
                    r.finish(error=err)
            finally:
                self._inflight_n = len(inflight)

        while not self._stop.is_set():
            sheds = self._shed_count()
            shedding, last_sheds = sheds > last_sheds, sheds
            if inflight and (
                len(inflight) >= cfg.pipeline_depth
                or self._queue.depth() == 0
                # saturation guard: when load is being shed or the queue
                # is past the degradation high-watermark, the window must
                # not extend effective residence (it would trade p99 for
                # buffering under flood) — drain the oldest batch before
                # dispatching further ahead. Pipelining is a light-load
                # overlap optimization; flood behavior stays PR 3's.
                or shedding
                or self._queue.depth()
                >= cfg.high_watermark * self._queue.capacity
            ):
                complete_oldest()
                continue
            batch: List[Request] = []
            try:
                batch = self._queue.next_batch(
                    self._max_batch,
                    cfg.max_wait_ms / 1e3,
                    poll=0.0 if inflight else 0.05,
                )
                live = self._filter_live(batch)
                if live:
                    if live[0].kind == "stream":
                        inf = self._dispatch_stream(live)
                    else:
                        inf = self._dispatch_pair(live)
                    if inf is not None:
                        inflight.append(inf)
                        self._inflight_n = len(inflight)
                        with self._lock:
                            self._counters["inflight_peak"] = max(
                                self._counters["inflight_peak"], len(inflight)
                            )
            except Exception as e:  # isolation: fail the batch, not the worker
                self._count("worker_errors")
                err = ServeError(f"batch execution failed: {e!r}")
                for r in batch:
                    r.finish(error=err)
            finally:
                if batch:
                    # ack only once the batch is visible downstream
                    # (in the inflight window, or its requests finished)
                    # so drain()'s quiesce check never races the pop
                    self._queue.task_done()
            self._log_counters()
            self._alerts.maybe_observe()
        # drain the pipeline, then anything admitted during shutdown
        while inflight:
            complete_oldest()
        for r in self._queue.close():
            r.finish(error=EngineStopped("engine stopping"))

    def _filter_live(self, batch: List[Request]) -> List[Request]:
        """Fail queue-expired requests; invalidate streams with a dropped
        frame (pairing across a gap would be flow between non-consecutive
        frames)."""
        live: List[Request] = []
        for r in batch:
            if r.done or r.remaining <= 0:
                if r.finish(
                    error=DeadlineExceeded(f"request {r.rid} expired in queue")
                ):
                    self._count_outcome(r, "expired")
                    if not r.shadow:
                        self._qos_stats.count(r.priority, "expired")
                if r.kind == "stream":
                    self._invalidate_stream(r.stream_id)
            else:
                live.append(r)
        return live

    def _rung(self, k: int) -> int:
        """Smallest batch-ladder rung >= k (k <= max_batch by formation)."""
        for b in self._batch_ladder:
            if b >= k:
                return b
        return self._batch_ladder[-1]

    def _observe(self, live: List[Request]) -> Tuple[int, int]:
        depth_now = self._queue.depth() + len(live)
        iters = self._controller.observe(
            min(1.0, depth_now / self._queue.capacity),
            self._p99(live[0].bucket),
        )
        level = self._controller.level
        if level != self._last_level:
            # each controller move is a fault-ladder event: the 5 s of
            # context before an incident should show the pressure ramp
            self.recorder.record(
                "degradation_step", frm=self._last_level, to=level,
                num_flow_updates=iters, queue_depth=depth_now,
            )
            self._last_level = level
        return iters, level

    def _note_padding(self, rung: int, k: int) -> None:
        with self._lock:
            self._counters["dispatched_rows"] += rung
            self._counters["padded_rows"] += rung - k

    def _guarded_dispatch(self, live: List[Request], fn):
        """Run one dispatch under the per-batch device deadline.

        Returns ``(result, tripped)``; on a trip the in-flight requests
        are already failed by the watcher-thread callback and the result
        must be discarded.
        """
        if self._watchdog is None:
            return fn(), False
        tripped: List[str] = []

        def on_timeout(name, _live=live, _tripped=tripped):
            # watcher-thread callback: fail the in-flight requests and
            # count the trip now (the stuck dispatch may hold the worker
            # for a while yet; it is abandoned when it finally returns)
            _tripped.append(name)
            self._count("watchdog_trips")
            for r in _live:
                r.finish(
                    error=DeadlineExceeded(
                        f"device execution exceeded "
                        f"{self.config.apply_timeout_s:g}s"
                    )
                )

        with self._watchdog.section("serve/apply", on_timeout=on_timeout):
            out = fn()
        return out, bool(tripped)

    # -- trace span helpers (no-ops for unsampled requests) ----------------

    def _trace_queue_wait(self, live: List[Request], now: float) -> None:
        """Per-request span from submission to batch formation."""
        for r in live:
            if r.trace is not None:
                r.trace.add_span("queue_wait", r.t_submit, now)

    def _trace_span(
        self, live: List[Request], name: str, t0: float,
        t1: Optional[float] = None, **attrs,
    ) -> None:
        """One shared-timestamp span recorded on every sampled request."""
        if t1 is None:
            t1 = time.monotonic()
        for r in live:
            if r.trace is not None:
                r.trace.add_span(name, t0, t1, **attrs)

    def _dispatch_pair(self, live: List[Request]) -> Optional[_Inflight]:
        bucket = live[0].bucket
        iters, level = self._observe(live)
        iters, level = self._qos_levels(live, iters, level)
        iters = self._honor_iters(live, iters)
        bh, bw = bucket
        rung = self._rung(len(live))
        shape = (self._max_batch, bh, bw, 3)
        t_form = time.monotonic()
        self._trace_queue_wait(live, t_form)
        p1 = self._staging.fill(("p1", bucket), shape, [r.p1 for r in live], rung)
        p2 = self._staging.fill(("p2", bucket), shape, [r.p2 for r in live], rung)
        self._note_padding(rung, len(live))
        t0 = time.monotonic()
        self._trace_span(live, "batch_form", t_form, t0, rung=rung)
        flow_dev, tripped = self._guarded_dispatch(
            live, lambda: self._run_batch(p1, p2, iters)
        )
        if tripped:
            return None  # requests already failed (and the trip counted)
        self._trace_span(live, "dispatch", t0, iters=iters)
        return _Inflight(live, iters, level, t0, flow_dev, "pair")

    def _dispatch_stream(self, live: List[Request]) -> Optional[_Inflight]:
        """Stream batch: encode the new frames (one program per rung),
        swap them into the session cache against the sessions' previous
        frames (``stream_swap``, on the device), then dispatch the
        iterate stage for the requests that had a cached previous frame.

        Only the frames' finite flags come back to the host here (a few
        bytes; this worker has no later fetch to put a prime's answer
        on); the iterate stage — the dominant FLOPs, 12-32 GRU
        refinements — is what pipelines against the next batch. It runs
        at the encode's rung, prime lanes included: their rows are not
        read."""
        bucket = live[0].bucket
        iters, level = self._observe(live)
        iters, level = self._qos_levels(live, iters, level)
        iters = self._honor_iters(live, iters)
        bh, bw = bucket
        rung = self._rung(len(live))
        shape = (self._max_batch, bh, bw, 3)
        t_form = time.monotonic()
        self._trace_queue_wait(live, t_form)
        frames = self._staging.fill(
            ("frames", bucket), shape, [r.p2 for r in live], rung
        )
        self._note_padding(rung, len(live))
        t0 = time.monotonic()

        def run_encode():
            fm, cx, finite = self._run_encode(frames)
            co = self._stream_cache.plan(live, rung)
            f1, c1, _ = self._run_stream_swap(
                bucket, fm, cx, co.idx, co.put, co.warm
            )
            return fm, f1, c1, co, np.asarray(finite)

        out, tripped = self._guarded_dispatch(live, run_encode)
        if tripped:
            for r in live:
                self._invalidate_stream(r.stream_id)
            return None
        fm, f1, c1, co, finite = out
        self._trace_span(live, "encode", t0, rung=rung)
        self._count_stream_frames(co)
        self._settle_primes(co.primes, finite, iters, level)
        pairs = []
        for lane, r in co.pairs:
            if finite[lane]:
                pairs.append((lane, r))
            else:
                self._poisoned_frame(r)
        if not pairs:
            return None
        flow_reqs = [r for _, r in pairs]
        t_d = time.monotonic()
        flow_dev, tripped = self._guarded_dispatch(
            flow_reqs, lambda: self._run_iterate(f1, fm, c1, iters)
        )
        if tripped:
            return None
        self._trace_span(flow_reqs, "dispatch", t_d, iters=iters)
        return _Inflight(
            flow_reqs, iters, level, t0, flow_dev, "stream",
            lanes=[lane for lane, _ in pairs], retry_rows=(f1, fm, c1),
        )

    def _count_stream_frames(self, co) -> None:
        with self._lock:
            self._counters["encode_cache_hits"] += len(co.pairs)
            self._counters["encode_cache_misses"] += len(co.primes)
            self._counters["stream_primes"] += len(co.primes)
            self._counters["stream_warm_starts"] += int(co.warm.sum())
        for lane, r in co.pairs:
            r.warm = bool(co.warm[lane])

    def _settle_primes(self, primes, finite, iters, level) -> None:
        """Answer a cohort's primes once their frames' finite flags are
        on the host: ``primed``, or poisoned."""
        for lane, r in primes:
            if finite[lane]:
                self._finish_ok(r, None, iters, level=level, primed=True)
            else:
                self._poisoned_frame(r)

    def _poisoned_frame(self, r: Request) -> None:
        """The encoders made non-finite features of this frame: it is
        quarantined and its session forgets it — never paired with the
        next frame."""
        self._quarantine(r)
        self._invalidate_stream(r.stream_id)

    def _complete(self, inf: _Inflight) -> None:
        """Fetch one in-flight batch's flow and finish its requests."""
        t_f = time.monotonic()
        flow, tripped = self._guarded_dispatch(
            inf.live, lambda: np.asarray(inf.flow_dev)
        )
        self._trace_span(inf.live, "fetch", t_f)
        batch_ms = (time.monotonic() - inf.t0) * 1e3
        with self._lock:
            self._counters["batches"] += 1
            self._batch_ms_ewma += 0.2 * (batch_ms - self._batch_ms_ewma)
        if tripped:
            return  # requests already failed (and the trip counted)
        lanes = inf.lanes if inf.lanes is not None else range(len(inf.live))
        flows = [
            self._request_flow(r, flow[i]) for i, r in zip(lanes, inf.live)
        ]
        if all(np.isfinite(f).all() for f in flows):
            for r, f in zip(inf.live, flows):
                self._finish_ok(r, f, inf.iters, level=inf.level)
        else:
            # non-finite output: retry the batch as singles so exactly the
            # poisoned request is quarantined (PR 1's data quarantine, for
            # inference)
            self._count("nonfinite_batches")
            if inf.kind == "stream":
                self._retry_singles_stream(inf)
            else:
                self._retry_singles(inf.live, inf.iters, inf.level)

    def _retry_singles(self, live: List[Request], iters: int, level: int) -> None:
        for r in live:
            if r.done:
                continue
            t_r = time.monotonic()
            try:
                f = np.asarray(
                    self._run_batch(
                        self._pad_rows(r.p1), self._pad_rows(r.p2), iters
                    )
                )
                f = self._request_flow(r, f[0])
                if r.trace is not None:
                    r.trace.add_span("retry_single", t_r, iters=iters)
            except Exception as e:
                r.finish(error=ServeError(f"single retry failed: {e!r}"))
                self._count("worker_errors")
                continue
            if np.isfinite(f).all():
                self._count("retried_singles")
                self._finish_ok(r, f, iters, level=level, retried=True)
            else:
                self._quarantine(r)

    def _retry_singles_stream(self, inf: _Inflight) -> None:
        """Stream mirror of the singles retry, from the saved feature rows.

        A frame that is non-finite even alone is quarantined AND its
        session invalidated: its features are already cached (they were
        finite — the poison appeared in the flow), but a stream that just
        failed a frame should re-prime, not pair across the failure.
        """
        for r, lane in zip(inf.live, inf.lanes):
            if r.done:
                continue
            t_r = time.monotonic()
            try:
                # a fault path: the lane's rows cross the host on their
                # way to the smallest rung
                f1, f2, cx = (
                    self._pad_rows(np.asarray(a[lane:lane + 1]))
                    for a in inf.retry_rows
                )
                f = np.asarray(self._run_iterate(f1, f2, cx, inf.iters))
                f = self._request_flow(r, f[0])
                if r.trace is not None:
                    r.trace.add_span("retry_single", t_r, iters=inf.iters)
            except Exception as e:
                r.finish(error=ServeError(f"single retry failed: {e!r}"))
                self._count("worker_errors")
                self._invalidate_stream(r.stream_id)
                continue
            if np.isfinite(f).all():
                self._count("retried_singles")
                self._finish_ok(r, f, inf.iters, level=inf.level, retried=True)
            else:
                self._quarantine(r)
                self._invalidate_stream(r.stream_id)

    # -- iteration-pool worker (iteration-level continuous batching) -------

    def _pool_for(self, bucket: Tuple[int, int]) -> BucketPool:
        pool = self._pools.get(bucket)
        if pool is None:
            state = zero_state(
                self.model, self._dev_vars, self._pool_cap, bucket,
                sharding=self._row_sharding, resid_len=self._resid_len,
            )
            pool = BucketPool(
                bucket, self._pool_cap, state,
                layout=state_layout(self.model, state),
            )
            self._pools[bucket] = pool
        return pool

    def _rung_admit(self, k: int) -> int:
        """Smallest admission rung >= k (k <= admit cap by formation)."""
        for r in self._admit_ladder:
            if r >= k:
                return r
        return self._admit_ladder[-1]

    def _worker_pool(self) -> None:
        """The iteration-pool worker: one GRU iteration per dispatch.

        Each loop: retire slots whose requests are done (target reached,
        deadline-driven early exit, or expired), admit queued requests
        into the freed slots, then advance every occupied pool by ONE
        ``iterate_step`` dispatch. Ticks pipeline like the fallback
        engine's batches: up to ``pipeline_depth`` ticks stay
        dispatched-but-unfetched, so the host stages admissions and
        retirements while the device refines. Survives any per-dispatch
        failure by contract — an admission failure costs that admission
        batch, a tick failure costs the residents of that pool, never the
        worker thread.

        A retirement is dispatched before admission and read after the
        tick (``_pool_finalize`` parks it, ``_pool_settle`` reads it), so
        the wait for a flow and its transfer run while the device has
        the loop's admission and tick queued behind them.

        Every step of the loop runs inside one ``_phase`` (flat: never
        two open at once), so a profiler capture and a sampled loop's
        ``"sched"`` record both show where an iteration went
        (docs/observability.md lists the phases).
        """
        while not self._stop.is_set():
            closed = self._sched_turn()
            loop = self._loop
            with self._phase("serve/sched/upkeep"):
                self._sched_seal(closed, loop)
                self._log_counters()
                self._alerts.maybe_observe()
                self._stream_settle()
            try:
                for pool in list(self._pools.values()):
                    self._pool_retire(pool)
                self._pool_admit()
                ticked = False
                for pool in list(self._pools.values()):
                    if pool.occupied_count():
                        self._pool_tick(pool)
                        ticked = True
                        if loop is not None:
                            loop.ticked += 1
                # with no resident nothing else would run: read at once
                self._pool_settle(deferred=ticked)
            except Exception as e:  # isolation: fail residents, not the worker
                self._count("worker_errors")
                self._pool_fail_all(ServeError(f"pool tick failed: {e!r}"))
        self._sched_seal(self._sched_turn(last=True), None)
        # shutdown: fail whatever is still resident, then drain the queue
        self._pool_fail_all(EngineStopped("engine stopping"))
        for r in self._queue.close():
            r.finish(error=EngineStopped("engine stopping"))

    def _pool_fail_all(self, err: ServeError) -> None:
        while self._retiring:
            for r in self._retiring.popleft().live:
                r.finish(error=err)
                if r.kind == "stream":
                    self._invalidate_stream(r.stream_id)
        for pool in self._pools.values():
            metas = pool.clear()
            for m in metas:
                m.req.finish(error=err)
                if m.req.kind == "stream":
                    self._invalidate_stream(m.req.stream_id)
            if metas:
                with self._lock:
                    self._counters["pool_resets"] += 1
                self.recorder.record(
                    "pool_reset", bucket=f"{pool.bucket[0]}x{pool.bucket[1]}",
                    residents=len(metas), error=repr(err),
                )

    def _phase(self, name: str):
        """The one phase helper of the pool scheduler's loop: a profiler
        region when ``obs.profile`` is on, and ``(name, t0, t1)`` on the
        current loop's record when that loop is sampled. Both off: two
        truth tests, no clock read, no allocation."""
        loop = self._loop
        return profile.phase(name, None if loop is None else loop.phases)

    def _sched_turn(self, last: bool = False) -> Optional[_SchedLoop]:
        """Top of a scheduler-loop iteration: close the previous
        iteration's record, if it was sampled, and open this one's, if it
        is. One clock reading serves as the end of one ``loop`` span and
        the start of the next, so sampled loops tile the thread's time.
        Reads no clock when neither iteration is sampled. Returns the
        closed record, for ``_sched_seal`` (which the loop calls inside
        its first phase: the turn itself stays a few microseconds)."""
        prev = self._loop
        trace = None if last else self.tracer.start_loop("sched")
        if prev is None and trace is None:
            return None
        if prev is not None:
            prev.t_end = time.monotonic() if trace is None else trace.t_start
        self._loop = None if trace is None else _SchedLoop(trace)
        return prev

    def _sched_seal(
        self, closed: Optional[_SchedLoop], opened: Optional[_SchedLoop]
    ) -> None:
        """Read the thread's CPU clock once, for the loop that ended and
        the one that began, and finish the ended loop's ``"sched"``
        record: the ``loop`` span, its phases as children, and what the
        iteration did. Nothing sampled: nothing read."""
        if closed is None and opened is None:
            return
        cpu = time.thread_time()
        if opened is not None:
            opened.cpu0 = cpu
        if closed is None or not (
            closed.ticked or closed.admitted or closed.retired
        ):
            # nothing ended, or an idle poll of an empty queue did: 20 a
            # second would wash the request traces out of the ring
            return
        tr = closed.trace
        tr.add_span("loop", tr.t_start, closed.t_end)
        for name, t0, t1 in closed.phases:
            tr.add_span(name, t0, t1, parent="loop")
        pools = list(self._pools.values())
        tr.finish(
            t_end=closed.t_end, ticked=closed.ticked,
            occupied=sum(p.occupied_count() for p in pools),
            pending=sum(len(p.pending) for p in pools),
            admitted=len(closed.admitted), retired=len(closed.retired),
            admitted_rids=closed.admitted, retired_rids=closed.retired,
            deferred=closed.deferred, cpu_ms=(cpu - closed.cpu0) * 1e3,
        )

    def _pool_retire(self, pool: BucketPool) -> None:
        """Scan the slots (``_pool_due``), then finalize what is due."""
        with self._phase("serve/sched/retire"):
            due = self._pool_due(pool)
        if due:
            self._pool_finalize(pool, due)

    def _pool_due(
        self, pool: BucketPool
    ) -> List[Tuple[int, _SlotMeta, str]]:
        """Free slots whose requests are finished or expired; return
        the ones due for finalization: target reached OR converged
        (residual-driven, once past ``pool_min_iters``) OR a
        deadline-driven early exit.

        Precedence per slot, strictest first: a caller-side finish or a
        hard deadline expiry always wins (the slot is dead weight either
        way); then the request's own target; then convergence (the flow
        stopped moving — paying more ticks buys nothing); then the
        deadline *forecast* early exit (softer flow beats no flow).
        Convergence state arrives on the tick pacing-token fetch, so a
        converged slot is retired at most one pipeline window after its
        flow froze on device.
        """
        cfg = self.config
        due: List[Tuple[int, _SlotMeta, str]] = []
        for i, meta in pool.occupied():
            r = meta.req
            if r.done:
                # caller side already finished it (its deadline tripped)
                pool.release(i)
                if r.kind == "stream":
                    self._invalidate_stream(r.stream_id)
                continue
            remaining_ms = r.remaining * 1e3
            if remaining_ms <= 0:
                if r.finish(
                    error=DeadlineExceeded(
                        f"request {r.rid} expired after {meta.done} pool "
                        f"iterations"
                    )
                ):
                    self._count_outcome(r, "expired")
                    if not r.shadow:
                        self._qos_stats.count(r.priority, "expired")
                pool.release(i)
                if r.kind == "stream":
                    self._invalidate_stream(r.stream_id)
                continue
            need = meta.target - meta.done
            if need <= 0:
                due.append((i, meta, "target"))
            elif meta.converged and meta.done >= cfg.pool_min_iters:
                # the flow converged on device (and froze there):
                # retire now, spend the saved ticks on queued work
                due.append((i, meta, "converged"))
            elif (
                cfg.pool_early_exit
                and meta.done >= cfg.pool_min_iters
                and remaining_ms
                < (need + 1) * pool.tick_ewma_ms
                * self._qos_forecast_slack(r)
            ):
                # the deadline would expire before the remaining
                # iterations finish: cash in the anytime ladder now
                due.append((i, meta, "deadline"))
        return due

    def _pool_finalize(
        self, pool: BucketPool, due: List[Tuple[int, _SlotMeta, str]]
    ) -> None:
        """Dispatch a retirement: gather the finished slots' carry, run
        the final upsample, start the host copies of what the requests
        get, free the slots and park the cohort; ``_pool_settle`` reads
        it once the loop's tick is dispatched. The slots can be refilled
        by this loop's admission: their carry is in the gathered arrays,
        the device runs programs in dispatch order, and a later
        ``insert`` (or tick) donating the state waits for the gather's
        read of it.

        Retirement runs at the warmed admission rungs: more due slots
        than the top rung (possible when ``pool_capacity > max_batch``)
        finalize in chunks, keeping the program set closed."""
        while len(due) > self._admit_cap:
            self._pool_finalize(pool, due[: self._admit_cap])
            due = due[self._admit_cap:]
        with self._phase("serve/sched/gather"):
            rung = self._rung_admit(len(due))
            idx = np.asarray(
                [i for i, _, _ in due] + [due[0][0]] * (rung - len(due)),
                np.int32,
            )
            live = [m.req for _, m, _ in due]
            # warm start: which retiring pairs' sessions still hold the
            # row they were admitted from (their final 1/8-grid flow is
            # written there, on the device, by stream_store_flow)
            flow_rows = self._warm_start and self._stream_cache.flow_rows(
                pool.bucket,
                [r if r.kind == "stream" else None for r in live], rung,
            )
            fetch_c1 = any(r.want_flow8 for r in live)
            t_f = time.monotonic()
            for _, meta, _ in due:
                r = meta.req
                if r.trace is not None:
                    # the pool's per-iteration refinement window,
                    # admission insert -> finalize gather
                    r.trace.add_span(
                        "refine", meta.admitted_t, t_f, iters=meta.done,
                    )

        def run():
            with self._phase("serve/sched/gather"):
                c1, hid, res = self._pool_gather(
                    pool.state["coords1"], pool.state["hidden"],
                    pool.state["resid_hist"], idx,
                )
            flow = self._run_pool_final(c1, hid)
            if flow_rows:
                self._run_stream_store_flow(pool.bucket, c1, *flow_rows)
            return flow, res, c1 if fetch_c1 else None

        out, tripped = self._guarded_dispatch(live, run)
        with self._phase("serve/sched/gather"):
            if not tripped:
                # the residual trajectories (and the finite flags of the
                # retiring stream pairs' frames, computed by their
                # encode; and the 1/8-grid coordinates where a caller
                # asked for its flow8) travel with the flow: each copy
                # starts as soon as its array is computed. Parked before
                # the slots free up, so that drain() never sees the
                # cohort in neither place.
                co = _Retiring(due, live, t_f, *out)
                for a in co.arrays():
                    a.copy_to_host_async()
                self._retiring.append(co)
            for i, _, _ in due:
                pool.release(i)
        if tripped:
            with self._phase("serve/sched/complete"):
                self._pool_complete(due, live, t_f, None, True)

    def _pool_settle(self, deferred: bool, sessions=None) -> None:
        """Read the parked retirements, oldest first, and complete their
        requests. After the loop's tick (``deferred``) the wait for a
        flow runs while the device has that tick and the admission before
        it queued. With ``sessions``, only as far as the newest cohort
        that holds a pair of one of them: a session's next frame is
        planned only once its last pair is settled (quarantine,
        invalidation and ``mark_flow`` first)."""
        n = len(self._retiring)
        if sessions is not None:
            n = max(
                (k + 1 for k, co in enumerate(self._retiring)
                 if co.holds(sessions)),
                default=0,
            )
        for _ in range(n):
            co = self._retiring[0]
            with self._phase("serve/sched/fetch"):
                ready = deferred and all(a.is_ready() for a in co.arrays())
                out, tripped = self._guarded_dispatch(co.live, co.fetch)
            with self._phase("serve/sched/complete"):
                self._pool_complete(co.due, co.live, co.t_f, out, tripped)
                self._retiring.popleft()
                if deferred:
                    with self._lock:
                        self._counters["retire_deferred"] += 1
                        self._counters["retire_ready_at_settle"] += ready
                    if self._loop is not None:
                        self._loop.deferred += 1

    def _pool_complete(self, due, live, t_f, out, tripped) -> None:
        """Hand the fetched flows to their requests (crop, ``finish``,
        callbacks); their slots were freed at dispatch. A non-finite flow
        quarantines exactly its own request — slots are isolated by
        construction (inference is per-sample end to end), so no singles
        retry is needed."""
        self._trace_span(live, "fetch", t_f)
        with self._lock:
            self._counters["batches"] += 1
            if not tripped:
                self._counters["fetched_bytes"] += sum(
                    a.nbytes for a in out[:3] if a is not None
                )
        if tripped:
            # requests already failed by the watchdog callback
            for _, meta, _ in due:
                if meta.req.kind == "stream":
                    self._invalidate_stream(meta.req.stream_id)
            return
        if self._loop is not None:
            self._loop.retired.extend(r.rid for r in live)
        flows, resids, c1_rows, frame_ok = out
        for pos, (_, meta, reason) in enumerate(due):
            r = meta.req
            if r.done:
                # its caller's deadline finished it while it retired: as
                # in _pool_due, the session never pairs across it
                if r.kind == "stream":
                    self._invalidate_stream(r.stream_id)
                continue
            f = self._request_flow(r, flows[pos])
            # a converged slot froze on device at converged_done
            # iterations — ticks dispatched after that changed nothing
            # (bitwise) and were accounted as idle, so the effective
            # iteration count (trajectory tail, saved-iters math, the
            # result's num_flow_updates) is the freeze point
            eff = meta.converged_done if meta.converged else meta.done
            # convergence telemetry: the rolling history's tail holds the
            # last min(eff, resid_len) iterations' residuals, oldest
            # first (positions before that are the admission sentinel)
            k = min(eff, self._resid_len)
            traj = resids[pos, self._resid_len - k:] if k else resids[pos, :0]
            # a slot can freeze on device and still retire by target
            # before the host sees the mask (pipeline lag): the frozen
            # history stopped rolling, so the tail's oldest entries may
            # be the admission sentinel. Trim them — they are iterations
            # the flow never ran — and shrink eff to the real count.
            n_sent = int((traj >= RESID_SENTINEL * 0.5).sum())
            if n_sent:
                traj = traj[n_sent:]
                eff -= n_sent
                k = len(traj)
            if not frame_ok[pos]:
                # the pair's new frame came out of the encoders non-finite
                # (its flow may be finite all the same: only the context
                # would have been, and the NEXT pair reads that)
                self._poisoned_frame(r)
            elif np.isfinite(f).all():
                saved = max(0, self._controller.ladder[meta.level] - eff)
                with self._lock:
                    self._counters["early_exit_iters_saved"] += saved
                    if reason == "deadline":
                        self._counters["early_exits_deadline"] += 1
                        self._counters[
                            "early_exit_iters_saved_deadline"
                        ] += saved
                    elif reason == "converged":
                        self._counters["early_exits_converged"] += 1
                        self._counters[
                            "early_exit_iters_saved_converged"
                        ] += saved
                    if k:
                        # iters-vs-residual table: traj[j] was iteration
                        # (eff - k + j + 1); index 0-based into the table
                        i0 = eff - k
                        self._resid_iter_sum[i0:eff] += traj
                        self._resid_iter_cnt[i0:eff] += 1
                if k:
                    self._resid_final.observe(float(traj[-1]))
                    if r.trace is not None:
                        r.trace.annotate(
                            final_residual=round(float(traj[-1]), 6)
                        )
                if self._warm_start and r.kind == "stream":
                    # warm start: the session's row holds this pair's
                    # final 1/8-grid flow now
                    self._stream_cache.mark_flow(r.stream_id)
                self._finish_ok(
                    r, f, eff, level=meta.level, exit_reason=reason,
                    warm_started=meta.warm,
                    flow8=(
                        c1_rows[pos] - _coords0(c1_rows[pos].shape)
                        if r.want_flow8 else None
                    ),
                    residuals=(
                        tuple(float(x) for x in traj)
                        if (k and r.trace is not None) else None
                    ),
                )
            else:
                self._quarantine(r)
                if r.kind == "stream":
                    self._invalidate_stream(r.stream_id)

    def _pool_admit(self) -> None:
        """Fill free slots from the queue (slot-granularity admission).

        Admission is one encode + state-init dispatch at the next
        admission rung, then per-slot in-place inserts — so a late
        arrival's first refinement iteration is the very next tick.
        """
        cfg = self.config

        def cap(bucket, kind):
            pool = self._pools.get(bucket)
            return self._pool_cap if pool is None else pool.free_count()

        with self._phase("serve/sched/poll"):
            busy = bool(self._stream_checks or self._retiring) or any(
                p.occupied_count() or p.pending for p in self._pools.values()
            )
            batch = self._queue.next_batch(
                self._admit_cap,
                0.0,                  # admission never dawdles for stragglers
                poll=0.0 if busy else 0.05,
                cap=cap,
            )
        if not batch:
            return
        live: List[Request] = []
        try:
            with self._phase("serve/sched/poll"):
                live = self._filter_live(batch)
                if live:
                    pool = self._pool_for(live[0].bucket)
                    ctrl_iters, level = self._observe(live)
            if live:
                if live[0].kind == "stream":
                    self._pool_admit_stream(pool, live, ctrl_iters, level)
                else:
                    self._pool_admit_pairs(pool, live, ctrl_iters, level)
        except Exception as e:  # isolation: fail the admission, not the worker
            self._count("worker_errors")
            err = ServeError(f"pool admission failed: {e!r}")
            for r in live:
                if r.finish(error=err) and r.kind == "stream":
                    self._invalidate_stream(r.stream_id)
        finally:
            # ack only once the cohort is visible downstream (inserted
            # into pool slots, or its requests finished) so drain()'s
            # quiesce check never races the pop
            self._queue.task_done()

    def _pool_admit_pairs(
        self, pool: BucketPool, live: List[Request], ctrl_iters: int,
        level: int,
    ) -> None:
        seeded = [r for r in live if r.init8 is not None]
        if seeded:
            # warm-started pairs (ISSUE 19) admit through the stream-
            # style encode + begin_features programs (the only begin
            # path that takes a traced init_flow); the unseeded rest of
            # the cohort keeps the fused one-dispatch path below
            plain = [r for r in live if r.init8 is None]
            self._pool_admit_pairs_seeded(pool, seeded, ctrl_iters, level)
            if not plain:
                return
            live = plain
        p1, p2, rung, t0 = self._pool_stage_pairs(pool, live)
        rows, tripped = self._guarded_dispatch(
            live, lambda: self._run_pool_begin(p1, p2)
        )
        if tripped:
            return
        self._trace_span(live, "dispatch", t0, rung=rung)
        self._pool_insert_live(pool, rows, live, ctrl_iters, level)

    def _pool_stage_pairs(self, pool: BucketPool, live: List[Request]):
        """Copy the cohort's frames into the rotating staging buffers
        (the ``batch_form`` span). Returns ``(p1, p2, rung, t)`` with
        ``t`` the moment staging ended and the dispatch begins."""
        with self._phase("serve/sched/stage"):
            bh, bw = pool.bucket
            rung = self._rung_admit(len(live))
            shape = (self._admit_cap, bh, bw, 3)
            t_form = time.monotonic()
            self._trace_queue_wait(live, t_form)
            p1 = self._staging.fill(
                ("pool_p1", pool.bucket), shape, [r.p1 for r in live], rung
            )
            p2 = self._staging.fill(
                ("pool_p2", pool.bucket), shape, [r.p2 for r in live], rung
            )
            # one admission's rows on the device at a time
            pool.await_rows()
            t0 = time.monotonic()
            self._trace_span(live, "batch_form", t_form, t0, rung=rung)
        return p1, p2, rung, t0

    def _pool_admit_pairs_seeded(
        self, pool: BucketPool, live: List[Request], ctrl_iters: int,
        level: int,
    ) -> None:
        """Admit seeded pairs: encode both frames, then init the slot
        state from features with the traced ``init_flow`` seed.

        Three dispatches instead of one, but every program is one the
        stream path already compiled/warmed at the same admission rungs
        (``encode_frame`` twice, ``pool_begin_features`` once) — seeding
        adds zero new program families and zero AOT artifact churn. The
        encode outputs are already rung-batched in cohort order, so they
        feed ``begin_features`` directly without re-staging; pad lanes
        carry encode(0) garbage that the insert mask discards, exactly
        like the stream path's.
        """
        p1, p2, rung, t_e = self._pool_stage_pairs(pool, live)
        out, tripped = self._guarded_dispatch(
            live, lambda: (self._run_encode(p1), self._run_encode(p2))
        )
        if tripped:
            return
        (f1, c1, _), (f2, _c2, _) = out
        self._trace_span(live, "encode", t_e, rung=rung)
        with self._phase("serve/sched/stage"):
            ishape = (self._admit_cap,) + tuple(f1.shape[1:3]) + (2,)
            ifl = self._staging.fill(
                ("pool_init", pool.bucket), ishape,
                [r.init8 for r in live], rung,
            )
            t0 = time.monotonic()
        rows, tripped = self._guarded_dispatch(
            live, lambda: self._run_pool_begin_features(f1, f2, c1, ifl)
        )
        if tripped:
            return
        self._trace_span(live, "dispatch", t0, rung=rung)
        self._pool_insert_live(pool, rows, live, ctrl_iters, level)

    def _pool_admit_stream(
        self, pool: BucketPool, live: List[Request], ctrl_iters: int,
        level: int,
    ) -> None:
        """Admit a cohort of stream frames: ``encode_frame`` ->
        ``stream_swap`` -> ``pool_begin_features`` -> ``insert``, each
        fed the last one's device arrays. The host sends the frames and
        the sessions' row numbers and fetches nothing; a prime's answer
        waits for its frame's finite flag (``_stream_settle``), a pair's
        flag is read at its retirement — which is settled before the
        session's next frame is planned."""
        self._pool_settle(deferred=False, sessions={r.stream_id for r in live})
        with self._phase("serve/sched/stage"):
            bh, bw = pool.bucket
            rung = self._rung_admit(len(live))
            shape = (self._admit_cap, bh, bw, 3)
            t_form = time.monotonic()
            self._trace_queue_wait(live, t_form)
            frames = self._staging.fill(
                ("pool_frames", pool.bucket), shape,
                [r.p2 for r in live], rung,
            )
            co = self._stream_cache.plan(live, rung)
            self._count_stream_frames(co)
            # one admission's rows on the device at a time
            pool.await_rows()
            t_e = time.monotonic()

        def run():
            fm, cx, finite = self._run_encode(frames)
            f1, c1, ifl = self._run_stream_swap(
                pool.bucket, fm, cx, co.idx, co.put, co.warm
            )
            t0 = time.monotonic()
            if not co.pairs:
                return finite, None, t0
            rows = self._run_pool_begin_features(f1, fm, c1, ifl)
            return finite, rows, t0

        (finite, rows, t0), tripped = self._guarded_dispatch(live, run)
        if tripped:
            for r in live:
                self._invalidate_stream(r.stream_id)
            return
        self._trace_span(live, "encode", t_e, t0, rung=rung)
        self._trace_span([r for _, r in co.pairs], "dispatch", t0, rung=rung)
        if co.primes:
            self._stream_checks.append((finite, co.primes, ctrl_iters, level))
        if co.pairs:
            self._pool_insert_live(
                pool, rows, [r for _, r in co.pairs], ctrl_iters, level,
                lanes=[lane for lane, _ in co.pairs], frame_ok=finite,
            )

    def _stream_settle(self) -> None:
        """Answer the stream primes whose frames' finite flags have been
        computed. The flag of a cohort is read once its ``encode_frame``
        has run — which the loop learns without waiting (``is_ready``)
        while the pool ticks, and by waiting where nothing else would
        run on the device anyway (no resident, so no tick, drain or
        retirement fetch this loop)."""
        if not self._stream_checks:
            return
        idle = not any(p.occupied_count() for p in self._pools.values())
        while self._stream_checks:
            finite, primes, iters, level = self._stream_checks[0]
            if not (idle or finite.is_ready()):
                return
            self._stream_checks.popleft()
            self._settle_primes(primes, np.asarray(finite), iters, level)

    def _pool_insert_live(
        self, pool: BucketPool, rows, live: List[Request], ctrl_iters: int,
        level: int, lanes: Optional[List[int]] = None, frame_ok=None,
    ) -> None:
        """Write each admitted request's state row into a free slot.

        The per-request iteration target is fixed here: the request's own
        ``num_flow_updates`` capped by the degradation level's target —
        degradation under the pool is a per-request admission decision,
        not a compile-time ladder. The whole cohort's slot writes go
        through ONE insert dispatch (rows beyond ``len(live)`` are
        padding lanes, masked out). ``lanes`` names the rows of ``rows``
        that are ``live``'s where they are not its first ones (a stream
        cohort's primes keep their lanes and take no slot);
        ``frame_ok`` is the cohort's device array of finite flags, kept
        on each slot for its retirement to read.
        """
        with self._phase("serve/sched/insert"):
            self._pool_insert_slots(
                pool, rows, live, ctrl_iters, level,
                range(len(live)) if lanes is None else lanes, frame_ok,
            )

    def _pool_insert_slots(
        self, pool, rows, live, ctrl_iters, level, lanes, frame_ok
    ) -> None:
        now = time.monotonic()
        rung = int(rows["coords1"].shape[0])
        slots = [pool.alloc() for _ in live]
        lanes = list(lanes)
        idx = np.zeros((rung,), np.int32)
        mask = np.zeros((rung,), bool)
        idx[lanes], mask[lanes] = slots, True
        pool.state = self._pool_insert(pool.state, rows, idx, mask)
        if self._loop is not None:
            self._loop.admitted.extend(r.rid for r in live)
        qos_on = self.config.qos_enabled
        ladder = self._controller.ladder
        for i, r, lane in zip(slots, live, lanes):
            requested = r.iters if r.iters is not None else self.config.ladder[0]
            # class-aware brownout (ISSUE 17): under pressure each slot's
            # iteration target browns out by its class's extra levels —
            # a per-request admission decision, exactly like the level
            eff_level, eff_iters = level, ctrl_iters
            if qos_on and level > 0:
                eff_level = brownout_level(level, r.rank, len(ladder))
                eff_iters = ladder[eff_level]
            pool.slots[i] = _SlotMeta(
                req=r,
                target=max(1, min(requested, eff_iters)),
                level=eff_level,
                admitted_t=now,
                warm=r.warm,
                frame_ok=None if frame_ok is None else (frame_ok, lane),
            )
            with self._lock:
                self._counters["pool_admitted"] += 1
                self._ttfd.append((now - r.t_submit) * 1e3)
                del self._ttfd[:-self.config.latency_window]

    def _pool_tick(self, pool: BucketPool) -> None:
        """Advance every slot of ``pool`` by ONE refinement iteration.

        Already-converged slots are frozen on device (their dispatched
        slot-iteration advances nobody — accounted as idle until the
        retire loop frees them, at most one pipeline window later). The
        pacing token fetched when the window is full is the PACKED
        converged mask of its tick — one ``np.asarray`` in place of the
        old ``block_until_ready``, so convergence costs zero new host
        syncs (tripwire-asserted in tests)."""
        with self._phase("serve/pool_step"):
            live = self._pool_tick_dispatch(pool)
        while (
            live is not None
            and len(pool.pending) > self.config.pipeline_depth
        ):
            # the wait on the device (the oldest tick's pacing token)
            # has a phase to itself, apart from the dispatch above
            with self._phase("serve/sched/drain"):
                if not self._pool_tick_drain(pool, live):
                    return

    def _pool_tick_dispatch(
        self, pool: BucketPool
    ) -> Optional[List[Request]]:
        """Dispatch one ``pool_step`` and do the tick's bookkeeping;
        returns the residents it advanced (``None`` after a watchdog
        trip: the pool was reset)."""
        occupied = pool.occupied()
        live = [m.req for _, m in occupied]
        frozen_n = sum(1 for _, m in occupied if m.converged)
        out, tripped = self._guarded_dispatch(
            live, lambda: self._run_pool_step(pool.state)
        )
        if tripped:
            # residents already failed by the watchdog callback
            self._pool_reset_tripped(pool, "watchdog trip")
            return None
        coords1, hidden, resid_hist, converged, token = out
        pool.state = {
            **pool.state, "coords1": coords1, "hidden": hidden,
            "resid_hist": resid_hist, "converged": converged,
        }
        for _, m in pool.occupied():
            if not m.converged:
                m.done += 1
        # snapshot (slot, rid, done-after-tick) for this tick so the
        # fetched mask is only ever believed for the occupant it was
        # computed for (a freed slot may be reused before the fetch)
        occupants = tuple(
            (i, m.req.rid, m.done)
            for i, m in pool.occupied()
            if not m.converged
        )
        with self._lock:
            self._counters["pool_ticks"] += 1
            self._counters["batches"] += 1
            self._counters["dispatched_slot_iters"] += pool.capacity
            self._counters["idle_slot_iters"] += (
                pool.capacity - len(live) + frozen_n
            )
            self._counters["inflight_peak"] = max(
                self._counters["inflight_peak"], len(pool.pending) + 1
            )
        pool.pending.append((time.monotonic(), token, occupants))
        return live

    def _pool_tick_drain(self, pool: BucketPool, live: List[Request]) -> bool:
        """Fetch the oldest pending tick's pacing token (blocks until
        that tick is done on the device) and believe its converged mask.
        False after a watchdog trip: the pool was reset."""
        _, tok, occ = pool.pending.popleft()
        mask, tripped = self._guarded_dispatch(live, lambda: np.asarray(tok))
        now = time.monotonic()
        pool.note_drain(now, mask)
        with self._lock:
            self._batch_ms_ewma += 0.2 * (
                pool.tick_ewma_ms - self._batch_ms_ewma
            )
        if tripped:
            self._pool_reset_tripped(pool, "watchdog trip (drain)")
            return False
        self._apply_converged_mask(pool, mask, occ)
        return True

    def _pool_reset_tripped(self, pool: BucketPool, error: str) -> None:
        cleared = pool.clear()
        for m in cleared:
            if m.req.kind == "stream":
                self._invalidate_stream(m.req.stream_id)
        with self._lock:
            self._counters["pool_resets"] += 1
        self.recorder.record(
            "pool_reset", bucket=f"{pool.bucket[0]}x{pool.bucket[1]}",
            residents=len(cleared), error=error,
        )

    def _apply_converged_mask(self, pool: BucketPool, mask, occupants) -> None:
        """Mark slots the fetched pacing token reports converged.

        ``occupants`` is the (slot, rid, done-after-tick) snapshot taken
        when the token's tick was dispatched: a bit is honored only if
        the same request still holds the slot, so slot reuse can never
        inherit convergence. ``done-after-tick`` becomes the request's
        effective iteration count — the device froze the slot from the
        NEXT tick on, so the flow it finalizes reflects exactly that many
        refinements."""
        if self._conv_thresh <= 0.0 or mask is None:
            return
        from raft_tpu.serve.pool import unpack_converged

        bits = unpack_converged(mask, pool.capacity)
        for slot, rid, done_after in occupants:
            if not bits[slot]:
                continue
            m = pool.slots[slot]
            if m is not None and m.req.rid == rid and not m.converged:
                m.converged = True
                m.converged_done = done_after

    # -- seams (FaultInjector.patch_engine wraps these) --------------------
    # Every dispatch consults the AOT executable overlay first (warmed or
    # artifact-loaded Compiled objects, keyed on program family + shape
    # dims); the jit fallback only compiles for signatures outside the
    # warmed set (warmup=False engines, and the rate-limited slow path).

    def _run_pool_begin(self, p1: np.ndarray, p2: np.ndarray):
        """Dispatch one pool admission (pair encode + state init); seam."""
        key = ("pool_begin_pair", p1.shape[0], p1.shape[1], p1.shape[2])
        ex = self._aot_execs.get(key)
        with self._phase("serve/pool_begin"):
            if ex is not None:
                return self.ledger.run(key, lambda: ex(self._dev_vars, p1, p2))
            return self.ledger.run(
                key,
                lambda: self._pool_progs.begin_pair(self._dev_vars, p1, p2),
            )

    def _run_pool_begin_features(self, f1, f2, ctx, init_flow):
        """Dispatch one pool admission from cached stream features (with
        the traced warm-start seed, zeros for a cold start); seam."""
        key = ("pool_begin_features", f1.shape[0], f1.shape[1], f1.shape[2])
        ex = self._aot_execs.get(key)
        with self._phase("serve/pool_begin_features"):
            if ex is not None:
                return self.ledger.run(
                    key, lambda: ex(self._dev_vars, f1, f2, ctx, init_flow)
                )
            return self.ledger.run(
                key,
                lambda: self._pool_progs.begin_features(
                    self._dev_vars, f1, f2, ctx, init_flow
                ),
            )

    def _run_pool_step(self, state):
        """Dispatch ONE refinement iteration across all pool slots; seam.

        The convergence knobs ride along as traced scalars (thresh <= 0
        disables on device) — one compiled program for any setting."""
        c = state["coords1"]
        key = ("pool_step", c.shape[0], c.shape[1], c.shape[2])
        ex = self._aot_execs.get(key)
        th, sk, mi = self._conv_thresh, self._conv_streak, self._conv_min
        # no region of its own: _pool_tick opens "serve/pool_step" around
        # this call and the tick's bookkeeping
        if ex is not None:
            return self.ledger.run(
                key, lambda: ex(self._dev_vars, state, th, sk, mi)
            )
        return self.ledger.run(
            key,
            lambda: self._pool_progs.step(self._dev_vars, state, th, sk, mi),
        )

    def _run_pool_final(self, coords1, hidden):
        """Dispatch the final-upsample stage for retiring slots; seam."""
        key = (
            "pool_final", coords1.shape[0], coords1.shape[1],
            coords1.shape[2],
        )
        ex = self._aot_execs.get(key)
        with self._phase("serve/pool_final"):
            if ex is not None:
                return self.ledger.run(
                    key, lambda: ex(self._dev_vars, coords1, hidden)
                )
            return self.ledger.run(
                key,
                lambda: self._pool_progs.final(
                    self._dev_vars, coords1, hidden
                ),
            )

    def _pool_insert(self, state, rows, idx, mask):
        """Write the admission cohort's rows into their slots — one
        dispatch for the whole cohort (``idx``/``mask`` are traced
        vectors; padding lanes carry ``mask=False``)."""
        c = rows["coords1"]
        key = ("pool_insert", c.shape[0], c.shape[1], c.shape[2])
        ex = self._aot_execs.get(key)
        idx = np.asarray(idx, np.int32)
        mask = np.asarray(mask, bool)
        if ex is not None:
            return self.ledger.run(key, lambda: ex(state, rows, idx, mask))
        return self.ledger.run(
            key, lambda: self._pool_progs.insert(state, rows, idx, mask)
        )

    def _pool_gather(self, coords1, hidden, resid_hist, idx):
        """Pull the recurrent carry + residual history of the slots in
        ``idx``."""
        key = ("pool_gather", len(idx), coords1.shape[1], coords1.shape[2])
        ex = self._aot_execs.get(key)
        if ex is not None:
            return self.ledger.run(
                key, lambda: ex(coords1, hidden, resid_hist, idx)
            )
        return self.ledger.run(
            key,
            lambda: self._pool_progs.gather(coords1, hidden, resid_hist, idx),
        )

    def _run_stream_swap(self, bucket, fmap, ctx, idx, put, warm):
        """Dispatch one cohort's cache transaction: the sessions' previous
        rows (and warm-start seeds) out, the new frames' rows in."""
        cache = self._stream_cache
        key = ("stream_swap", fmap.shape[0], fmap.shape[1], fmap.shape[2])
        ex = self._aot_execs.get(key) or cache.programs.swap
        with self._phase("serve/stream_swap"):
            table, f1, c1, init = self.ledger.run(
                key,
                lambda: ex(cache.table(bucket), fmap, ctx, idx, put, warm),
            )
        cache.set_table(bucket, table)
        return f1, c1, init

    def _run_stream_store_flow(self, bucket, coords1, idx, mask) -> None:
        """Dispatch a retirement's write of its stream pairs' final
        1/8-grid flows into their sessions' rows (warm start)."""
        cache = self._stream_cache
        key = (
            "stream_store_flow", coords1.shape[0], coords1.shape[1],
            coords1.shape[2],
        )
        ex = self._aot_execs.get(key) or cache.programs.store_flow
        with self._phase("serve/stream_store_flow"):
            cache.set_table(bucket, self.ledger.run(
                key, lambda: ex(cache.table(bucket), coords1, idx, mask)
            ))

    def _stream_row_spec(self, bucket):
        """Shape/dtype of ``encode_frame``'s feature map and context
        output for one frame of ``bucket``: a row of the session table."""
        bh, bw = bucket
        fm, cx, _ = jax.eval_shape(
            self._encode, self._dev_vars,
            jax.ShapeDtypeStruct((self._batch_ladder[0], bh, bw, 3),
                                 jnp.float32),
        )
        return fm, cx

    @property
    def _streams(self):
        """The live sessions, by id (empty with stream serving off)."""
        cache = self._stream_cache
        return {} if cache is None else cache.sessions

    def _invalidate_stream(self, stream_id: Optional[int]) -> None:
        if self._stream_cache is not None:
            self._stream_cache.invalidate(stream_id)

    def _quarantine(self, r: Request) -> None:
        r.finish(
            error=PoisonedInput(
                f"request {r.rid} produced non-finite flow even when executed "
                f"alone; quarantined (co-batched requests were unaffected)"
            )
        )
        with self._lock:
            self._counters["quarantined"] += 1
            self._quarantined_rids.append(r.rid)
            del self._quarantined_rids[:-100]
        self.recorder.record("quarantine", rid=r.rid, req_kind=r.kind)

    def _finish_ok(
        self,
        r: Request,
        flow: Optional[np.ndarray],
        iters: int,
        *,
        level: Optional[int] = None,
        retried: bool = False,
        primed: bool = False,
        exit_reason: str = "target",
        t0: Optional[float] = None,
        residuals: Optional[Tuple[float, ...]] = None,
        warm_started: bool = False,
        flow8: Optional[np.ndarray] = None,
    ) -> ServeResult:
        level = self._controller.level if level is None else level
        latency_ms = (time.monotonic() - (t0 if t0 is not None else r.t_submit)) * 1e3
        if r.trace is not None:
            r.trace.annotate(
                bucket=f"{r.bucket[0]}x{r.bucket[1]}", level=level,
                num_flow_updates=iters, retried_single=retried,
                primed=primed, exit_reason=exit_reason,
                warm_started=warm_started,
                latency_ms=round(latency_ms, 3),
            )
        result = ServeResult(
            flow=None if flow is None else self._router.crop(flow, r.orig_hw),
            rid=r.rid,
            bucket=r.bucket,
            num_flow_updates=iters,
            level=level,
            degraded=level > 0,
            latency_ms=latency_ms,
            slow_path=r.slow_path,
            retried_single=retried,
            primed=primed,
            exit_reason=exit_reason,
            trace_id=None if r.trace is None else r.trace.trace_id,
            residuals=residuals,
            warm_started=warm_started,
            flow8=flow8,
        )
        def _account(r_: Request) -> None:
            # rides finish(on_first=...): counted BEFORE the waiter wakes
            # or the transport reply fires, so a stats read issued after
            # the caller observed this result always sees it counted
            self._latency_hist.observe(latency_ms)
            if not r_.shadow:
                self._qos_stats.count(r_.priority, "completed")
                self._qos_stats.observe_latency(r_.priority, latency_ms)
            with self._lock:
                self._counters[
                    "shadow_completed" if r_.shadow else "completed"
                ] += 1
                self._latency.setdefault(r_.bucket, []).append(latency_ms)
                del self._latency[r_.bucket][: -self.config.latency_window]

        r.finish(result=result, on_first=_account)
        return result

    # -- seams (FaultInjector.patch_engine wraps these) --------------------

    def _run_batch(self, p1: np.ndarray, p2: np.ndarray, iters: int):
        """Dispatch one padded pair batch; the ``infer.slow_apply`` seam."""
        key = ("pairwise", p1.shape[0], p1.shape[1], p1.shape[2], int(iters))
        ex = self._aot_execs.get(key)
        with profile.annotate("serve/pairwise"):
            if ex is not None:
                return self.ledger.run(key, lambda: ex(self._dev_vars, p1, p2))
            return self.ledger.run(
                key, lambda: self._apply(self._dev_vars, p1, p2, int(iters))
            )

    def _run_encode(self, frames: np.ndarray):
        """Dispatch one frame-encode batch (stream path); seam."""
        key = ("encode", frames.shape[0], frames.shape[1], frames.shape[2])
        ex = self._aot_execs.get(key)
        with self._phase("serve/encode"):
            if ex is not None:
                return self.ledger.run(key, lambda: ex(self._dev_vars, frames))
            return self.ledger.run(
                key, lambda: self._encode(self._dev_vars, frames)
            )

    def _run_iterate(self, f1, f2, ctx, iters: int):
        """Dispatch one refinement batch from encoded features; seam."""
        key = ("iterate", f1.shape[0], f1.shape[1], f1.shape[2], int(iters))
        ex = self._aot_execs.get(key)
        with profile.annotate("serve/iterate"):
            if ex is not None:
                return self.ledger.run(
                    key, lambda: ex(self._dev_vars, f1, f2, ctx)
                )
            return self.ledger.run(
                key,
                lambda: self._iterate(self._dev_vars, f1, f2, ctx, int(iters)),
            )

    def _request_flow(self, req: Request, flow: np.ndarray) -> np.ndarray:
        """Per-request output hook; the ``infer.nan_flow`` seam."""
        return flow

    # -- accounting --------------------------------------------------------

    def _count(self, key: str) -> None:
        with self._lock:
            self._counters[key] += 1

    def _shed_count(self) -> int:
        with self._lock:
            return self._counters["shed"]

    def _p99(self, bucket) -> Optional[float]:
        with self._lock:
            v = self._latency.get(bucket)
            if not v or len(v) < 8:
                return None
            return float(np.percentile(v, 99))

    def _retry_after_ms(self) -> float:
        import math

        with self._lock:
            ewma = self._batch_ms_ewma
        if self.config.pool_capacity > 0:
            # a queued request needs roughly (depth / capacity) cohorts of
            # ~full-target iterations, each iteration one tick (the ewma
            # tracks tick time in pool mode)
            cohorts = math.ceil(
                max(1, self._queue.depth()) / self._pool_cap
            )
            return max(1.0, cohorts * self.config.ladder[0] * ewma)
        batches_queued = math.ceil(
            max(1, self._queue.depth()) / self._max_batch
        )
        return max(1.0, batches_queued * ewma)

    def _log_counters(self, force: bool = False) -> None:
        if self._logger is None:
            return
        with self._lock:
            step = self._counters["batches"]
            if not force and (
                step == 0 or step % self.config.log_every_batches
            ):
                return
            scalars = {f"serve/{k}": float(v) for k, v in self._counters.items()}
        scalars["serve/queue_depth"] = float(self._queue.depth())
        scalars["serve/level"] = float(self._controller.level)
        scalars["serve/num_flow_updates"] = float(
            self._controller.num_flow_updates
        )
        self._logger.log(step, scalars)
