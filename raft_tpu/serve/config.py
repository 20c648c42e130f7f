"""Serving configuration: every robustness knob in one validated dataclass.

The defaults encode the paper-native operating point: ``ladder=(32, 20,
12)`` spans the published 32-iteration protocol down to the common fast
setting (RAFT is an *anytime* algorithm — ``num_flow_updates`` is a runtime
accuracy/latency dial, which is what makes degradation under load a
first-class mechanism here rather than a bolt-on). Buckets are **padded**
``(H, W)`` shapes (each divisible by 8, the model contract); a constant-
resolution fleet configures exactly its resolutions and never compiles
after warmup.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

__all__ = ["ServeConfig", "PRESETS"]

# Named deployment presets. Each maps to RAFTConfig precision knobs that
# change activation/storage casts only — never the parameter tree — and
# each is gated by the trained-weight golden-EPE bounds in
# tests/test_epe_golden.py:
#
#   quality     fp32 everywhere — the paper-native reference point.
#   throughput  bf16 convs + bf16 corr storage on the fused kernel — the
#               default serving preset, and what every benchmark cell
#               runs (PERF.md §4).
PRESETS: Dict[str, Dict[str, Optional[str]]] = {
    "quality": dict(
        compute_dtype="float32", corr_dtype=None, corr_impl=None,
    ),
    "throughput": dict(
        compute_dtype="bfloat16", corr_dtype="bfloat16", corr_impl="fused",
    ),
}


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Knobs for :class:`raft_tpu.serve.ServeEngine`.

    Args:
        buckets: admitted padded shapes, each ``(H, W)`` divisible by 8.
            An input is routed to the smallest-area bucket that contains
            its %8-padded shape.
        pool_capacity: slots per bucket in the resident iteration pool —
            the engine's default dispatch unit is one GRU *iteration*
            across all slots (LLM-style continuous batching over RAFT's
            anytime refinement loop), not one whole request. Requests
            join a slot when admitted, advance one ``iterate_step`` per
            tick, and leave as soon as their own iteration target (the
            per-request ``num_flow_updates``, a degradation target, or a
            deadline-driven early exit) is met, freeing the slot for the
            next queued request mid-flight. ``0`` falls back to the
            whole-request batch-ladder engine (the PR 3/4 worker).
        pool_min_iters: floor on refinement iterations a pooled request
            runs before a deadline-driven early exit may finalize it
            (anytime flow below this is considered not worth returning).
        pool_early_exit: when True (default) a pooled request whose
            deadline would expire before its remaining iterations finish
            is finalized early at its current iteration count instead of
            expiring worthlessly — RAFT's anytime ladder cashed in
            mid-flight.
        pool_converge_thresh: residual-driven early exit (ISSUE 12) —
            retire a pooled request once its flow-update residual (the
            per-slot RMS ||delta flow|| the step program already reduces
            on device, 1/8-grid pixels) has stayed below this threshold
            for ``pool_converge_streak`` consecutive iterations and at
            least ``pool_min_iters`` iterations have run. Converged
            slots freeze on device (bitwise-stable flow) and the
            converged mask rides the existing tick pacing-token fetch —
            zero new host syncs. ``None`` (default) disables: adaptive
            compute is opt-in and must be golden-EPE-gated like the
            precision presets — pick the threshold with
            ``scripts/calibrate_convergence.py`` (the largest value
            whose EPE delta on the golden fixture stays under
            tolerance). The knob is a *traced* program input, so any
            threshold runs on the one compiled step program.
        pool_converge_streak: consecutive sub-threshold residuals
            required before a slot counts as converged (default 2 — a
            single small update can be a plateau, not a fixed point).
            Must fit the residual history (``<= ladder[0]``).
        stream_warm_start: start each stream pair's refinement from the
            session's previous pair, as upstream's video mode does
            (princeton-vl/RAFT ``forward_interpolate``): the points
            ``p + flow[p]`` of the last pair's final 1/8-grid flow that
            land strictly inside the frame, and every cell the flow of
            the nearest of them (zeros if none lands). The flow is
            written into the session's row of the device-resident cache
            by the program that retires the pair and interpolated on the
            device by the next admission (``stream_swap``); nothing of it
            crosses the host. Warm-started requests enter near the fixed
            point, so with ``pool_converge_thresh`` set they retire in a
            fraction of the iteration ladder. The start is a traced input
            of the admission program — zeros when off or un-primed, so
            the cold path is bitwise identical. Default off (gated like
            the threshold); pool mode only (the fallback engine ignores
            it). A session never warm-starts across a dropped, expired
            or failed frame.
        max_batch: micro-batch size cap — for the ``pool_capacity=0``
            fallback engine this is the whole-request micro-batch bound;
            for the pool it bounds how many queued requests are encoded
            and admitted per tick. A formed batch is zero-padded up
            to the next rung of ``batch_ladder`` (never beyond
            ``max_batch``), so batch-size jitter never triggers a compile
            while a half-full queue no longer pays full-batch FLOPs.
        bucket_pools: per-bucket pool plan, for buckets whose slots cost
            very different amounts (a 1088x1920 slot holds 18x the bytes
            of a 440x1024 one): a mapping ``{(H, W): (pool_capacity,
            max_batch)}`` or the same as ``((H, W), pool_capacity,
            max_batch)`` rows (kept as rows, sorted, so the config
            survives the JSON control channel). A listed bucket gets a
            pool of its own capacity and an admission ladder up to its
            own ``max_batch``; an unlisted one takes ``pool_capacity``
            and ``max_batch``. Pool mode only. Every pool is allocated at
            boot under one HBM budget (``stats()['pool']['budget']``): a
            plan that cannot fit the device is refused with
            :class:`~raft_tpu.serve.MemoryBudgetExceeded`.
        batch_ladder: ascending padded batch sizes the engine compiles and
            dispatches at; a batch of ``k`` live rows pads to the smallest
            rung ``>= k``. Must start at 1 (the singles-isolation retry
            size) and end at ``max_batch``. ``None`` (default) derives the
            powers-of-two ladder ``(1, 2, 4, ..., max_batch)``. The
            compiled-program set is ``buckets x iter-ladder x
            batch_ladder`` — still closed, still fully warmable.
        mesh_devices: devices on the serve mesh's ``data`` axis (ISSUE 8).
            ``1`` (default) is the single-device engine. With ``N > 1``
            every dispatch unit — padded batch rungs in the fallback
            engine, the resident slot table in the iteration pool — is
            placed with a ``NamedSharding`` over an N-way ``data`` mesh
            and XLA SPMD-partitions the programs across the chips.
            Sizing knobs (``max_batch``, ``batch_ladder``,
            ``pool_capacity``) are **per-device**: the engine multiplies
            them by ``mesh_devices``, so ladder rungs stay
            mesh-divisible by construction and an N-device engine runs
            the same per-device configuration as the 1-device engine it
            A/Bs against (``scripts/serve_bench.py --mesh-devices``).
            AOT warmup, warmup artifacts, and the no-compile-after-
            warmup pins cover the sharded program set; the artifact
            fingerprint keys on the dispatch device count, so an
            artifact built at one mesh size refuses (typed, degrading
            to compile) at another. ``stats()['pool']`` adds per-device
            slot occupancy.
        pipeline_depth: bound on dispatched-but-unfetched batches. At the
            default 2 the worker assembles, normalizes, and stages batch
            N+1 while batch N computes on the device (JAX async dispatch);
            1 restores strictly synchronous dispatch. The window is
            pressure-adaptive: once the queue passes ``high_watermark``
            the worker drains before dispatching ahead, so flood p99 and
            shed behavior are depth-independent (as are deadline,
            degradation, and quarantine semantics).
        stream_cache_size: rows of the device-resident stream cache
            (:mod:`raft_tpu.serve.stream_cache`), and the LRU bound on
            remembered sessions: one table a bucket, allocated at boot,
            a row a session — its last frame's feature map and context
            output in the encoders' dtype and its last pair's 1/8-grid
            flow (7.3 MB at 440x1024 with raft_large in bf16, 33 MB at
            1088x1920). Size it to the live sessions: one beyond the
            bound loses its row and primes again. ``stats()`` reports
            ``stream_sessions`` and the bytes their rows hold
            (``stream_cache_bytes``). 0 disables stream serving entirely
            (stream programs are then neither compiled nor warmed).
        max_wait_ms: how long the batch thread waits for stragglers after
            the first request of a batch arrives (capped by that request's
            own deadline slack — the queue never dawdles past a deadline).
        queue_capacity: bound on queued requests; an arrival beyond it is
            shed with a retryable :class:`~raft_tpu.serve.Overloaded`
            instead of adding unbounded latency.
        default_deadline_ms: deadline applied when a request carries none.
        ladder: descending ``num_flow_updates`` degradation ladder;
            ``ladder[0]`` is full quality, the last entry the floor.
        slo_p99_ms: p99 latency objective; ``None`` disables the latency
            trigger (queue pressure still degrades).
        high_watermark / low_watermark: queue-fullness fractions that
            trigger a degradation step down / allow a step back up.
        cooldown_batches: minimum batches between controller level moves.
        recover_after: consecutive calm batches required per step back up.
        unknown_shape: ``'reject'`` (default) fails un-bucketed shapes at
            admission with :class:`~raft_tpu.serve.ShapeRejected`;
            ``'slow_path'`` routes them to a rate-limited single-request
            path executed on the *caller's* thread (a novel shape costs
            its caller a compile, never the batch thread); ``'tiled'``
            (ISSUE 20) fans them into overlapping bucket-shaped tiles
            through the existing batch path — zero new compiles — and
            blends the per-tile flows host-side (results carry
            ``tiled=True``).
        slow_path_per_s: sustained slow-path admission rate (token
            bucket, burst of ``slow_path_burst``).
        tile_overlap_px: per-seam overlap floor for the tile planner
            (ISSUE 20); must be >= the 8 px 1/8-grid receptive margin.
        tile_pad_penalty: cost-model weight on the replicate-padded
            fraction of dispatched tile pixels (0 = tile count only).
        tile_max_tiles: upper bound on tiles per request; a shape whose
            cheapest plan exceeds it is ``ShapeRejected`` even under
            ``'tiled'``.
        apply_timeout_s: device-execution deadline per dispatched batch,
            armed via :class:`~raft_tpu.utils.faults.Watchdog` in callback
            mode (worker-thread-safe); ``None`` disables.
        warmup: build the worker's whole program set inside ``start()``,
            so readiness implies the worker thread never compiles. Since
            ISSUE 7 warmup is *compile-only*: every program is lowered
            from shape/dtype specs and AOT-compiled (concurrently, on
            ``warmup_workers`` threads) without executing the model, then
            one tiny smoke execution per program family validates
            runnability — warmup cost ~= compile cost. Pool mode: per
            bucket, admission rungs x {begin, insert, gather, final}
            (+ encode/begin_refinement for streams) plus ONE
            capacity-wide step program — per-request iteration counts add
            nothing. Fallback mode: every ``(bucket, iters, rung)``
            whole-request program.
        warmup_artifact: path to an AOT warmup artifact built by
            ``scripts/build_warmup_artifact.py`` (serialized compiled
            program set + fingerprint). When it matches the engine's
            fingerprint the boot *loads* executables instead of compiling
            them (``stats()['boot']`` reports the split); on any
            mismatch or corruption the engine logs the typed
            :class:`~raft_tpu.serve.ArtifactMismatch` reason and degrades
            to compiling — an artifact can make boot fast, never make it
            fail.
        compilation_cache_dir: wire the JAX persistent compilation cache
            (``jax_compilation_cache_dir``) at this path before any
            program compiles — the fallback tier below the artifact: a
            replica that must compile (first boot, artifact mismatch)
            pays XLA compilation only once per (program, jaxlib,
            backend) across process restarts. Process-global JAX config;
            ``None`` leaves the cache untouched. When the environment
            sets ``JAX_COMPILATION_CACHE_DIR`` that directory is used
            and a differing path here is ignored with one log line
            (``aot.enable_persistent_cache``).
        warmup_workers: thread-pool width for concurrent AOT compilation
            during warmup/artifact build (independent programs compile in
            parallel); 0 = auto (``min(8, cpu_count)``).
        precision / compute_dtype / corr_dtype / corr_impl: the
            deployment precision of the *model this engine serves* —
            see :meth:`preset` and :meth:`model_overrides`. The engine
            itself never casts; these fields thread the validated
            precision configs through the zoo into the engine (and into
            the warmup-artifact fingerprint, so an artifact built for
            bf16 convs can never warm an fp32 replica).
        drain_retry_after_ms: the backoff hint carried by the typed
            :class:`~raft_tpu.serve.Draining` error a draining engine
            returns for queued/new requests — the operator's estimate of
            the drain + re-boot window (artifact boots make the default
            realistic). Behind a :class:`~raft_tpu.serve.router.
            ServeRouter` callers never see it (drained work is re-routed).
        trace_sample_rate: fraction of requests recorded as observability
            traces (:mod:`raft_tpu.obs.trace` — per-request spans for
            admit / queue wait / dispatch / fetch and the pool's refine
            path, carried as ``trace_id`` on the
            :class:`~raft_tpu.serve.ServeResult`). Sampling is
            deterministic (counter-based, no RNG on the hot path); 0
            (default) disables tracing entirely, 1.0 traces every
            request. Sampled traces feed ``stats()['obs']``, the flight
            recorder's last-N ring, and ``serve_bench
            --trace-sample``'s per-phase latency breakdown.
        alert_short_window_s / alert_long_window_s: the two windows of
            the burn-rate alert engine (:mod:`raft_tpu.obs.alerts`). A
            rule fires only when its burn exceeds threshold over BOTH
            windows (fast detection + blip rejection) and resolves with
            hysteresis. Engine rules: SLO burn (expired+shed fraction of
            submissions, page severity — fires the postmortem dump),
            quarantine fraction, watchdog-trip rate. Exposed via
            ``engine.alerts()`` / the ``alerts`` stats block / per-rule
            Prometheus gauges.
        latency_window: per-bucket ring-buffer size for p50/p99 tracking.
        log_every_batches: serving-counter cadence through ``MetricLogger``.
        qos_enabled: multi-tenant QoS enforcement (ISSUE 17). Off
            (default) the serve path is byte-identical to the priority-
            blind engine: priority/tenant ride along as annotations only.
            On, admission charges per-tenant quotas
            (``qos_tenant_quotas``), a full queue sheds lowest-class-
            first (an interactive arrival preempts a queued batch
            request — the victim gets a retryable ``Overloaded``), batch
            formation seeds highest-class-first with the
            ``qos_aging_ms`` starvation guard, and degradation /
            deadline-forecast retirement brown out low classes first.
        qos_default_priority: class assumed when a request carries none
            (``'interactive'`` | ``'standard'`` | ``'batch'``).
        qos_default_tenant: tenant assumed when a request carries none.
        qos_tenant_quotas: per-tenant admission quotas, a tuple of
            ``(tenant, rate_rps, burst, max_concurrent)`` rows (tuple-of-
            tuples so the config survives the JSON control channel).
            ``rate_rps <= 0`` disables the rate arm, ``max_concurrent <=
            0`` the concurrency arm; an unlisted tenant is unlimited. An
            over-quota request is refused with the retryable
            :class:`~raft_tpu.serve.QuotaExceeded` (HTTP 429 at the
            frontend) — quota refusal protects *other* tenants' capacity
            before the queue ever sees the request.
        qos_aging_ms: starvation guard — a queued request older than
            this competes at interactive rank: it can no longer be
            preempted and it seeds batches first, so a saturating
            high-class flood cannot starve batch-class work forever.
    """

    buckets: Tuple[Tuple[int, int], ...] = ((440, 1024),)
    pool_capacity: int = 8
    pool_min_iters: int = 1
    pool_early_exit: bool = True
    pool_converge_thresh: Optional[float] = None
    pool_converge_streak: int = 2
    stream_warm_start: bool = False
    max_batch: int = 8
    bucket_pools: Tuple[Tuple[Tuple[int, int], int, int], ...] = ()
    batch_ladder: Optional[Tuple[int, ...]] = None
    mesh_devices: int = 1
    pipeline_depth: int = 2
    stream_cache_size: int = 16
    max_wait_ms: float = 5.0
    queue_capacity: int = 64
    default_deadline_ms: float = 1000.0
    ladder: Tuple[int, ...] = (32, 20, 12)
    slo_p99_ms: Optional[float] = None
    high_watermark: float = 0.75
    low_watermark: float = 0.25
    cooldown_batches: int = 2
    recover_after: int = 2
    unknown_shape: str = "reject"
    slow_path_per_s: float = 1.0
    slow_path_burst: int = 2
    tile_overlap_px: int = 16
    tile_pad_penalty: float = 1.0
    tile_max_tiles: int = 64
    apply_timeout_s: Optional[float] = None
    warmup: bool = False
    warmup_artifact: Optional[str] = None
    compilation_cache_dir: Optional[str] = None
    warmup_workers: int = 0
    precision: Optional[str] = None
    compute_dtype: str = "float32"
    corr_dtype: Optional[str] = None
    corr_impl: Optional[str] = None
    drain_retry_after_ms: float = 2000.0
    trace_sample_rate: float = 0.0
    alert_short_window_s: float = 5.0
    alert_long_window_s: float = 60.0
    latency_window: int = 256
    log_every_batches: int = 50
    qos_enabled: bool = False
    qos_default_priority: str = "standard"
    qos_default_tenant: str = "default"
    qos_tenant_quotas: Tuple[Tuple[str, float, float, int], ...] = ()
    qos_aging_ms: float = 500.0

    @classmethod
    def preset(cls, name: str = "throughput", **overrides) -> "ServeConfig":
        """A named deployment preset (default: ``'throughput'`` — the
        fastest golden-EPE-validated config is the default serving
        config, not a bench footnote).

        ``preset('quality')`` is fp32 everywhere; ``'throughput'`` is
        bf16 convs + bf16 correlation storage on the fused kernel. Any
        other :class:`ServeConfig` field can be overridden::

            cfg = ServeConfig.preset("quality", buckets=((440, 1024),),
                                     warmup=True)
            model, variables = zoo.raft_for_serving(cfg, pretrained=True)
            engine = ServeEngine(model, variables, cfg)
        """
        if name not in PRESETS:
            raise ValueError(
                f"unknown precision preset {name!r}; choose from "
                f"{sorted(PRESETS)}"
            )
        kw = dict(PRESETS[name], precision=name)
        kw.update(overrides)
        return cls(**kw)

    def model_overrides(self) -> Dict[str, Optional[str]]:
        """The :class:`~raft_tpu.models.zoo.RAFTConfig` override dict
        this config's precision fields imply (only non-default knobs, so
        it composes with any base architecture)."""
        kw: Dict[str, Optional[str]] = {}
        if self.compute_dtype != "float32":
            kw["compute_dtype"] = self.compute_dtype
        if self.corr_dtype is not None:
            kw["corr_dtype"] = self.corr_dtype
        if self.corr_impl is not None:
            kw["corr_impl"] = self.corr_impl
        return kw

    def resolved_batch_ladder(self) -> Tuple[int, ...]:
        """The effective ascending rung set (defaults to powers of two)."""
        if self.batch_ladder is not None:
            return tuple(self.batch_ladder)
        rungs = [1]
        while rungs[-1] * 2 < self.max_batch:
            rungs.append(rungs[-1] * 2)
        if rungs[-1] != self.max_batch:
            rungs.append(self.max_batch)
        return tuple(rungs)

    def pool_plan(self, bucket: Tuple[int, int]) -> Tuple[int, int]:
        """``(pool_capacity, max_batch)`` of ``bucket``'s pool: its row
        of ``bucket_pools``, else the engine-wide values."""
        for b, capacity, max_batch in self.bucket_pools:
            if b == tuple(bucket):
                return capacity, max_batch
        return self.pool_capacity, self.max_batch

    def resolved_admit_ladder(
        self, bucket: Optional[Tuple[int, int]] = None
    ) -> Tuple[int, ...]:
        """Admission rungs for the iteration pool (of ``bucket``'s pool,
        where given): the batch ladder capped at ``min(max_batch,
        pool_capacity)`` (a tick never admits more requests than it has
        free slots or encode bandwidth for)."""
        capacity, max_batch = (
            (self.pool_capacity, self.max_batch) if bucket is None
            else self.pool_plan(bucket)
        )
        cap = min(max_batch, max(1, capacity))
        rungs = [r for r in self.resolved_batch_ladder() if r < cap]
        return tuple(rungs) + (cap,)

    def _check_bucket_pools(self) -> None:
        """Normalize ``bucket_pools`` to sorted ``((H, W), capacity,
        max_batch)`` rows (from a mapping, or from the lists JSON gives
        back) and validate them against ``buckets``."""
        plans = self.bucket_pools
        if isinstance(plans, dict):
            plans = [(b, *v) for b, v in plans.items()]
        try:
            rows = tuple(sorted(
                ((int(b[0]), int(b[1])), int(c), int(m)) for b, c, m in plans
            ))
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"bucket_pools must map (H, W) to (pool_capacity, "
                f"max_batch), got {self.bucket_pools!r}"
            ) from e
        object.__setattr__(self, "bucket_pools", rows)
        if rows and self.pool_capacity == 0:
            raise ValueError(
                "bucket_pools plans slot pools: it needs pool_capacity > 0 "
                "(0 selects the whole-request fallback engine)"
            )
        seen = set()
        buckets = [tuple(b) for b in self.buckets]
        for bucket, capacity, max_batch in rows:
            if bucket not in buckets:
                raise ValueError(
                    f"bucket_pools names {bucket!r}, which is not in "
                    f"buckets {self.buckets!r}"
                )
            if bucket in seen:
                raise ValueError(f"duplicate bucket_pools row for {bucket!r}")
            seen.add(bucket)
            if capacity < 1 or max_batch < 1:
                raise ValueError(
                    f"bucket_pools row for {bucket!r} needs pool_capacity "
                    f">= 1 and max_batch >= 1, got ({capacity}, {max_batch})"
                )

    def __post_init__(self):
        if not self.buckets:
            raise ValueError("at least one shape bucket is required")
        for b in self.buckets:
            if len(b) != 2 or b[0] <= 0 or b[1] <= 0:
                raise ValueError(f"bucket must be positive (H, W), got {b!r}")
            if b[0] % 8 or b[1] % 8:
                raise ValueError(
                    f"bucket {b!r} violates the %8 model contract; configure "
                    f"padded shapes (H and W divisible by 8)"
                )
        if len(set(self.buckets)) != len(self.buckets):
            raise ValueError(f"duplicate buckets in {self.buckets!r}")
        if not self.ladder or any(i <= 0 for i in self.ladder):
            raise ValueError(f"ladder must be positive iters, got {self.ladder!r}")
        if list(self.ladder) != sorted(self.ladder, reverse=True) or len(
            set(self.ladder)
        ) != len(self.ladder):
            raise ValueError(
                f"ladder must be strictly descending (full -> floor), got "
                f"{self.ladder!r}"
            )
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.batch_ladder is not None:
            bl = tuple(self.batch_ladder)
            if not bl or any(int(b) != b or b < 1 for b in bl):
                raise ValueError(
                    f"batch_ladder must be positive ints, got {bl!r}"
                )
            if list(bl) != sorted(set(bl)):
                raise ValueError(
                    f"batch_ladder must be strictly ascending, got {bl!r}"
                )
            if bl[0] != 1:
                raise ValueError(
                    f"batch_ladder must start at 1 (the singles-isolation "
                    f"retry size), got {bl!r}"
                )
            if bl[-1] != self.max_batch:
                raise ValueError(
                    f"batch_ladder must end at max_batch={self.max_batch}, "
                    f"got {bl!r}"
                )
        self._check_bucket_pools()
        if self.mesh_devices < 1:
            raise ValueError(
                f"mesh_devices must be >= 1 (1 = single-device engine), "
                f"got {self.mesh_devices}"
            )
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {self.pipeline_depth}"
            )
        if self.pool_capacity < 0:
            raise ValueError(
                f"pool_capacity must be >= 0 (0 = whole-request batch "
                f"fallback), got {self.pool_capacity}"
            )
        if self.pool_min_iters < 1:
            raise ValueError(
                f"pool_min_iters must be >= 1, got {self.pool_min_iters}"
            )
        if self.pool_converge_thresh is not None and not (
            self.pool_converge_thresh > 0.0
        ):
            raise ValueError(
                f"pool_converge_thresh must be positive or None (off), "
                f"got {self.pool_converge_thresh}"
            )
        if self.pool_converge_streak < 1:
            raise ValueError(
                f"pool_converge_streak must be >= 1, got "
                f"{self.pool_converge_streak}"
            )
        if (
            self.pool_converge_thresh is not None
            and self.pool_converge_streak > self.ladder[0]
        ):
            # only enforced when the feature is ON: the default streak
            # must not invalidate existing short-ladder configs
            raise ValueError(
                f"pool_converge_streak ({self.pool_converge_streak}) must "
                f"fit the residual history (ladder[0]={self.ladder[0]}): a "
                f"streak longer than the full-quality target can never fire"
            )
        if self.stream_cache_size < 0:
            raise ValueError(
                f"stream_cache_size must be >= 0, got {self.stream_cache_size}"
            )
        if self.queue_capacity < 1:
            raise ValueError(
                f"queue_capacity must be >= 1, got {self.queue_capacity}"
            )
        if self.unknown_shape not in ("reject", "slow_path", "tiled"):
            raise ValueError(
                f"unknown_shape must be 'reject', 'slow_path', or "
                f"'tiled', got {self.unknown_shape!r}"
            )
        # tiler knobs (ISSUE 20) — validated even under 'reject', so a
        # config later flipped to 'tiled' cannot carry a latent bad plan
        if self.tile_overlap_px < 8:
            raise ValueError(
                f"tile_overlap_px must be >= 8 (the 1/8-grid receptive "
                f"margin), got {self.tile_overlap_px}"
            )
        if self.tile_pad_penalty < 0:
            raise ValueError(
                f"tile_pad_penalty must be >= 0, got "
                f"{self.tile_pad_penalty}"
            )
        if self.tile_max_tiles < 1:
            raise ValueError(
                f"tile_max_tiles must be >= 1, got {self.tile_max_tiles}"
            )
        if not (0.0 <= self.low_watermark <= self.high_watermark <= 1.0):
            raise ValueError(
                f"need 0 <= low_watermark <= high_watermark <= 1, got "
                f"{self.low_watermark} / {self.high_watermark}"
            )
        if self.max_wait_ms < 0 or self.default_deadline_ms <= 0:
            raise ValueError("max_wait_ms must be >= 0 and default_deadline_ms > 0")
        if self.apply_timeout_s is not None and self.apply_timeout_s <= 0:
            raise ValueError(
                f"apply_timeout_s must be positive or None, got "
                f"{self.apply_timeout_s}"
            )
        if self.drain_retry_after_ms <= 0:
            raise ValueError(
                f"drain_retry_after_ms must be positive, got "
                f"{self.drain_retry_after_ms}"
            )
        if not (0.0 <= self.trace_sample_rate <= 1.0):
            raise ValueError(
                f"trace_sample_rate must be in [0, 1], got "
                f"{self.trace_sample_rate}"
            )
        if not (0 < self.alert_short_window_s <= self.alert_long_window_s):
            raise ValueError(
                f"need 0 < alert_short_window_s <= alert_long_window_s, "
                f"got {self.alert_short_window_s} / "
                f"{self.alert_long_window_s}"
            )
        if self.warmup_workers < 0:
            raise ValueError(
                f"warmup_workers must be >= 0 (0 = auto), got "
                f"{self.warmup_workers}"
            )
        if self.precision is not None and self.precision not in PRESETS:
            raise ValueError(
                f"unknown precision preset {self.precision!r}; choose "
                f"from {sorted(PRESETS)}"
            )
        if self.compute_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"compute_dtype must be 'float32' or 'bfloat16', got "
                f"{self.compute_dtype!r}"
            )
        if self.corr_dtype not in (None, "bfloat16"):
            raise ValueError(
                f"corr_dtype must be None or 'bfloat16', got "
                f"{self.corr_dtype!r}"
            )
        # QoS (ISSUE 17) — validated even when disabled, so a config that
        # will later be flipped on cannot carry a latent bad quota table
        _qos_classes = ("interactive", "standard", "batch")
        if self.qos_default_priority not in _qos_classes:
            raise ValueError(
                f"qos_default_priority must be one of {_qos_classes}, got "
                f"{self.qos_default_priority!r}"
            )
        if not self.qos_default_tenant:
            raise ValueError("qos_default_tenant must be a non-empty string")
        if self.qos_aging_ms <= 0:
            raise ValueError(
                f"qos_aging_ms must be positive, got {self.qos_aging_ms}"
            )
        seen_tenants = set()
        for row in self.qos_tenant_quotas:
            if len(row) != 4:
                raise ValueError(
                    f"each qos_tenant_quotas row must be (tenant, rate_rps, "
                    f"burst, max_concurrent), got {row!r}"
                )
            tenant, rate_rps, burst, max_conc = row
            if not tenant or not isinstance(tenant, str):
                raise ValueError(
                    f"quota tenant must be a non-empty string, got {tenant!r}"
                )
            if tenant in seen_tenants:
                raise ValueError(f"duplicate quota row for tenant {tenant!r}")
            seen_tenants.add(tenant)
            if rate_rps > 0 and burst < 1:
                raise ValueError(
                    f"quota burst must be >= 1 when rate_rps > 0, got "
                    f"{burst!r} for tenant {tenant!r}"
                )
            if int(max_conc) != max_conc:
                raise ValueError(
                    f"quota max_concurrent must be an int, got {max_conc!r} "
                    f"for tenant {tenant!r}"
                )
