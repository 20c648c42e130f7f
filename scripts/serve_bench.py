#!/usr/bin/env python
"""Serving load generator: p50/p99, throughput, shed rate, degradation occupancy.

Floods a :class:`raft_tpu.serve.ServeEngine` with concurrent clients for a
fixed duration and emits BENCH-style JSON lines (the repo's bench
trajectory format), so serving robustness joins fps on the perf record:

    {"metric": "serve_p99_ms", "value": ..., "unit": "ms", "config": ...}

Clients behave like a real fleet: each submits back-to-back requests with a
deadline, treats `Overloaded` as a shed (backs off by the engine's
`retry_after_ms` hint), and counts outcomes. Degradation occupancy is the
fraction of completed requests served at each ladder level — the measure of
how much anytime-iteration headroom the load actually consumed.

Hot-path efficiency joins the report: `padding_waste` (pool mode:
idle-slot-iterations / dispatched-slot-iterations — the refinement work
that advanced nobody; fallback mode: padded rows / dispatched rows) and
`encoder_cache_hit_rate` (stream sessions' encode-once reuse). `--streams N`
runs N of the clients as video-stream sessions (`engine.open_stream()`);
`--batch-ladder 1,<max>` approximates the pre-ladder pad-to-max engine for
A/B runs; `--pipeline-depth 1` disables dispatch pipelining likewise.

Iteration-level continuous batching (ISSUE 6): the default engine is the
resident GRU-iteration pool (`--pool-capacity N`, 0 = the whole-request
batch-ladder engine for A/B). `--iters-mix a,b,c` makes each client draw
its per-request `num_flow_updates` uniformly from the list — the mixed
iteration-count traffic the pool exists for. Pool runs additionally
report occupancy, slot waste, and time-to-first-dispatch.

Cold start (ISSUE 7): `--boot-report` A/Bs boot-to-ready across the
three tiers — cold compile, JAX persistent compilation cache (miss then
hit), and AOT warmup artifact (`scripts/build_warmup_artifact.py`) —
emitting `serve_boot_*_ms` BENCH lines with programs compiled vs loaded
per tier. `--preset quality|throughput|edge` serves a named deployment
precision preset (`ServeConfig.preset`, golden-EPE-gated);
`--warmup-artifact` / `--compilation-cache-dir` wire the boot tiers into
the regular load bench.

Mesh sharding (ISSUE 8): `--mesh-devices N` shards every dispatch over
an N-way serve-mesh `data` axis (sizing knobs are per-device) and runs
a built-in 1-vs-N A/B at the same per-device config, emitting a
`serve_mesh_ab` BENCH line (throughput, slot-iterations/s,
padding_waste, per-device occupancy). CPU hosts get virtual devices
provisioned automatically.

Horizontal tier (ISSUE 9): `--replicas N` serves through a `ServeRouter`
over N engine replicas (least-loaded dispatch, stream affinity,
health-driven eviction); with warmup enabled, ONE warmup artifact is
built and shared by every replica boot. N > 1 runs a built-in 1-vs-N
A/B at equal per-replica config and emits a `serve_replica_ab` BENCH
line (throughput, per-replica completion split, router counters).

Realistic load model (ISSUE 9): `--arrival steady|bursty|diurnal` with
`--arrival-rate R` drives each client as an arrival process instead of
a closed loop (bursty = geometric on-bursts with compensating idle
gaps; diurnal = one sinusoidal "day" over the run). `--class-mix P,S,B`
splits clients into pairwise / stream / second-bucket traffic classes
(`--bucket2` sets the alternate resolution), each with its own SLO
deadline (`--class-deadline-ms`), and the report gains a per-class SLO
block — p99 vs deadline, SLO miss rate, shed rate — emitted as a
`serve_slo_report` BENCH line.

Observability (ISSUE 10): `--trace-sample RATE` turns on per-request
tracing (`ServeConfig.trace_sample_rate`) and emits a
`serve_phase_breakdown` BENCH line — the *measured* per-phase latency
split (admit / queue_wait / batch_form / dispatch / fetch p50/p99 from
the collected traces), replacing the hand-estimated phase split in
docs/perf_notes.md.

Edge SLO (ISSUE 15): `--frontend` drives the whole load through the
HTTP front door — every client speaks `FrontendClient`, latency is
measured at the EDGE, and a `serve_edge_slo` BENCH line reports
per-class edge p50/p99 alongside the engine-side quantiles of the SAME
completed requests, with the wire-tax delta (edge minus engine — the
HTTP + transport cost the engine-side SLOs undercount). Combined with
`--trace-sample`, edge traces stitch across
frontend/router/transport/worker and the phase breakdown covers all
lanes.

Device time + convergence (ISSUE 11): `--ledger-sample K` turns on the
device-time ledger (`ServeConfig.ledger_sample_every` — every Kth
execution per program family is a timed, blocked dispatch) and emits a
`serve_device_time` BENCH line: per-family device-ms p50/p99/EWMA and
each family's share of estimated device time. Pool runs additionally
emit `serve_convergence`: final-residual p50/p99 plus the
residual-vs-iters table (mean RMS ||delta flow|| per iteration number)
— the measured evidence base for residual-driven early exit.
`scripts/perf_ledger.py` gates both on the BENCH trajectory.

Convergence-adaptive compute (ISSUE 12): `--converge-thresh T` (with
`--converge-streak K`) turns on residual-driven early exit
(`ServeConfig.pool_converge_thresh` — pick T with
`scripts/calibrate_convergence.py`), `--warm-start` seeds each stream
pair from the previous pair's forward-warped flow
(`ServeConfig.stream_warm_start`), and the report gains mean
iters/request plus exit-reason occupancy (target / deadline /
converged fractions of completed requests). `--adaptive-ab` runs the
built-in adaptive-vs-fixed A/B on a deterministic smooth-motion
synthetic stream with known ground truth — same frames both arms,
trained golden-fixture weights when the fixture is present — and emits
a `serve_adaptive_ab` BENCH line: mean iters/request and throughput
per arm, the iters-reduction fraction, and the EPE cost
(`epe_delta_px` = max(0, adaptive - fixed) against ground truth:
measured quality degradation, zero when adaptive lands the better
EPE). `scripts/perf_ledger.py` gates the line's reduction/speedup/
delta series from BENCH_r07 onward.

Process fleet (ISSUE 13): `--backend process` promotes every replica to
a spawned worker **process** — its own interpreter, GIL, and JAX runtime
— behind the same router surface (socket control channel, shared-memory
tensor rings, typed errors over the wire). With `--replicas N > 1` the
built-in A/B runs three arms at equal config (one in-process engine, N
thread replicas, N process replicas) and emits a `serve_process_ab`
BENCH line with the structural pins (worker PIDs, per-replica request
split); `scripts/perf_ledger.py` gates its throughput/speedup/p99
series. `--autoscale-max N` attaches the signal-driven Autoscaler
(shed/SLO-miss/occupancy with hysteresis) to the router and emits a
`serve_autoscale` BENCH line — pair it with `--arrival diurnal` for the
scale-into-the-peak scenario.

Run (TPU, real model):      python scripts/serve_bench.py --arch raft_small
Run (CPU smoke, tiny net):  python scripts/serve_bench.py --tiny --duration 3
(without --tiny the bench refuses to run off a TPU; every line it prints
carries the `device` — platform, kind, count — it ran on. On a TPU the
`--backend process` arms refuse: this parent holds the chip once it has
run an in-process arm, and a chip belongs to one process.)
Boot A/B (CPU smoke):       python scripts/serve_bench.py --tiny \
    --ladder 2,1 --max-batch 2 --pool-capacity 2 --boot-report
Mixed-iteration A/B (the pool win):
    python scripts/serve_bench.py --tiny --clients 8 --duration 6 \
        --ladder 8,5,3 --iters-mix 8,5,3
    python scripts/serve_bench.py --tiny --clients 8 --duration 6 \
        --ladder 8,5,3 --iters-mix 8,5,3 --pool-capacity 0
Replica A/B + SLO classes (CPU smoke):
    python scripts/serve_bench.py --tiny --replicas 3 --duration 4 \
        --pool-capacity 0 --class-mix 0.5,0.25,0.25 \
        --arrival bursty --arrival-rate 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

# the device every printed result ran on (platform, kind, count) — set once
# in main() before anything is measured
_DEVICE = None


def _line(result: dict) -> str:
    return json.dumps(dict(result, device=_DEVICE))


def tiny_config():
    """A CPU-sized RAFT for smoke runs (mirrors the test suite's tiny cfg)."""
    from raft_tpu.models import RAFT_SMALL

    return RAFT_SMALL.replace(
        feature_encoder_widths=(8, 8, 12, 16, 24),
        context_encoder_widths=(8, 8, 12, 16, 40),
        motion_corr_widths=(16,),
        motion_flow_widths=(16, 8),
        motion_out_channels=20,
        gru_hidden=24,
        flow_head_hidden=16,
        corr_levels=2,
    )


def class_mix(args):
    """(pairwise, stream, bucket2) client fractions. `--class-mix` wins;
    otherwise the legacy `--streams N` knob maps to the stream class."""
    if args.class_mix:
        fr = [float(x) for x in args.class_mix.split(",")]
        if len(fr) != 3 or any(f < 0 for f in fr) or sum(fr) <= 0:
            raise SystemExit(
                f"--class-mix needs 3 nonnegative fractions, got "
                f"{args.class_mix!r}"
            )
        s = sum(fr)
        return tuple(f / s for f in fr)
    n_stream = min(args.streams, args.clients)
    return (1.0 - n_stream / max(1, args.clients),
            n_stream / max(1, args.clients), 0.0)


def build_config(args, **extra):
    from raft_tpu.serve import ServeConfig

    bucket = tuple(int(x) for x in args.bucket.split("x"))
    buckets = (bucket,)
    if class_mix(args)[2] > 0:
        buckets = buckets + (tuple(int(x) for x in args.bucket2.split("x")),)
    ladder = tuple(int(x) for x in args.ladder.split(","))
    batch_ladder = (
        tuple(int(x) for x in args.batch_ladder.split(","))
        if args.batch_ladder
        else None
    )
    kw = dict(
        buckets=buckets,
        max_batch=args.max_batch,
        batch_ladder=batch_ladder,
        mesh_devices=getattr(args, "_mesh_override", None)
        or args.mesh_devices,
        pool_capacity=args.pool_capacity,
        pipeline_depth=args.pipeline_depth,
        stream_cache_size=max(args.stream_cache_size, args.streams),
        max_wait_ms=args.max_wait_ms,
        queue_capacity=args.queue_capacity,
        default_deadline_ms=args.deadline_ms,
        ladder=ladder,
        slo_p99_ms=args.slo_ms,
        cooldown_batches=1,
        recover_after=2,
        warmup=not args.no_warmup,
        warmup_artifact=args.warmup_artifact,
        compilation_cache_dir=args.compilation_cache_dir,
        trace_sample_rate=args.trace_sample,
        ledger_sample_every=args.ledger_sample,
        pool_converge_thresh=args.converge_thresh,
        pool_converge_streak=args.converge_streak,
        stream_warm_start=args.warm_start,
    )
    n_tenants = int(getattr(args, "tenants", 0) or 0)
    if n_tenants > 0:
        kw["qos_enabled"] = True
        rps = float(getattr(args, "tenant_rps", 0.0) or 0.0)
        if rps > 0:
            # one identical token-bucket row per synthetic tenant; no
            # concurrency cap (the rate arm is what the bench exercises)
            kw["qos_tenant_quotas"] = tuple(
                (f"tenant{i}", rps, max(1.0, 2 * rps), 0)
                for i in range(n_tenants)
            )
    kw.update(extra)
    if args.preset:
        return ServeConfig.preset(args.preset, **kw)
    return ServeConfig(**kw)


def _build_model(tiny, arch, random_init, cfg):
    from raft_tpu.models import build_raft, init_variables

    if tiny:
        # precision presets compose with the tiny net: build_raft derives
        # the corr block from the config's corr_impl/corr_dtype knobs
        model = build_raft(tiny_config().replace(**cfg.model_overrides()))
        return model, init_variables(model)
    from raft_tpu.models import zoo

    return zoo.raft_for_serving(cfg, arch=arch, pretrained=not random_init)


def build_model(args, cfg):
    return _build_model(args.tiny, args.arch, args.random_init, cfg)


class ProcessEngineFactory:
    """Picklable engine factory for ``--backend process`` workers.

    Spawned workers cannot inherit the parent's model/weights (spawn,
    not fork — ISSUE 13), so each child rebuilds them: the tiny net's
    deterministic random init, or the zoo path for a real arch. Every
    worker therefore serves identical weights, and with a shared warmup
    artifact in the config the rebuild boots by loading, not compiling.
    """

    def __init__(self, tiny, arch, random_init, cfg):
        self.tiny = bool(tiny)
        self.arch = arch
        self.random_init = bool(random_init)
        self.cfg = cfg

    def __call__(self, **overrides):
        import dataclasses

        from raft_tpu.serve import ServeEngine

        cfg = (
            dataclasses.replace(self.cfg, **overrides)
            if overrides
            else self.cfg
        )
        model, variables = _build_model(
            self.tiny, self.arch, self.random_init, cfg
        )
        return ServeEngine(model, variables, cfg)


def effective_transport(args) -> str:
    """The control-channel codec this run's process workers speak: the
    ``--transport`` choice, with ``ab`` resolved per arm through the
    override the A/B driver sets."""
    t = getattr(args, "_transport_override", None) or args.transport
    return "binary" if t == "ab" else t


def collect_transport(server, n_ok: int) -> dict:
    """Aggregate the process fleet's transport ledgers (client + worker
    side, per replica) into the bench's cross-process-tax numbers:
    copies/request, control bytes/request, coalescing ratios, and the
    pack/ring_wait/rpc/unpack span quantiles. Empty for thread tiers."""
    blocks = []
    for rep in getattr(server, "replicas", []):
        ts = getattr(rep.engine, "transport_stats", None)
        if ts is None:
            continue
        try:
            blocks.append(ts(include_worker=True))
        except Exception:
            pass
    if not blocks:
        return {}
    copies = 0
    ctrl_bytes = 0
    msgs = frames = 0
    health_hits = health_misses = 0
    remote_blocks = reconnects = disconnects = keepalive_misses = 0
    spans: dict = {}
    for b in blocks:
        r = b.get("remote")
        if r:
            # TCP links (ISSUE 16): the supervisor's fault ledger — a
            # clean bench run pins reconnects == 0 from here
            remote_blocks += 1
            reconnects += r.get("reconnects", 0)
            disconnects += r.get("disconnects", 0)
            keepalive_misses += r.get("keepalive_misses_total", 0)
        rings = b.get("rings") or {}
        for r in rings.values():
            copies += r.get("copies_in", 0) + r.get("copies_out", 0)
        w = b.get("worker") or {}
        for r in (w.get("rings") or {}).values():
            copies += r.get("copies_in", 0) + r.get("copies_out", 0)
        # both directions, counted once: bytes the client wrote plus
        # bytes it read (everything the worker wrote)
        snd = b.get("sender") or {}
        ctrl_bytes += snd.get("bytes_sent", 0) + b.get("bytes_received", 0)
        msgs += snd.get("msgs_sent", 0) + b.get("msgs_received", 0)
        frames += snd.get("frames_sent", 0) + b.get("frames_received", 0)
        health_hits += b.get("health_cache_hits", 0)
        health_misses += b.get("health_cache_misses", 0)
        for name, q in (b.get("spans") or {}).items():
            if q.get("n"):
                spans.setdefault(name, []).append(q)
    span_agg = {
        name: {
            "n": sum(q["n"] for q in qs),
            "p50_ms": round(
                float(np.mean([q["p50_ms"] for q in qs])), 4
            ),
            "p99_ms": round(float(max(q["p99_ms"] for q in qs)), 4),
        }
        for name, qs in spans.items()
    }
    net = {} if not remote_blocks else {
        "remote_links": remote_blocks,
        "reconnects": reconnects,
        "disconnects": disconnects,
        "keepalive_misses": keepalive_misses,
    }
    return {
        "transport": blocks[0].get("transport"),
        "replica_blocks": len(blocks),
        **net,
        "copies_total": copies,
        "copies_per_req": round(copies / max(1, n_ok), 3),
        "control_bytes_total": ctrl_bytes,
        "control_bytes_per_req": round(ctrl_bytes / max(1, n_ok), 1),
        "control_msgs": msgs,
        "control_frames": frames,
        "coalesce_ratio": round(msgs / max(1, frames), 3),
        "health_cache_hits": health_hits,
        "health_cache_misses": health_misses,
        "spans": span_agg,
    }


def build_server(args):
    """The serving tier under test: a bare engine, or (--replicas N > 1,
    --backend process, or autoscaling on) a ServeRouter over N engine
    replicas sharing ONE warmup artifact (built here when warmup is on
    and no artifact was given) — the production boot path for a
    homogeneous fleet. ``--backend process`` runs every replica's engine
    in a spawned worker process (ISSUE 13); ``--autoscale-max N``
    attaches a signal-driven Autoscaler to the router."""
    from raft_tpu.serve import ServeEngine

    cfg = build_config(args)
    n_rep = getattr(args, "_replicas_override", None) or args.replicas
    backend = getattr(args, "_backend_override", None) or args.backend
    autoscale = args.autoscale_max > 0
    if n_rep <= 1 and backend == "thread" and not autoscale:
        model, variables = build_model(args, cfg)
        return ServeEngine(model, variables, cfg), cfg
    import dataclasses
    import tempfile

    from raft_tpu.serve import (
        AutoscaleConfig, Autoscaler, RouterConfig, ServeRouter, aot,
    )

    model = variables = None
    rep_cfg = cfg
    if cfg.warmup and not cfg.warmup_artifact:
        model, variables = build_model(args, cfg)
        path = os.path.join(
            tempfile.mkdtemp(prefix="raft_router_aot_"), "shared.raftaot"
        )
        aot.save_artifact(
            ServeEngine(model, variables, cfg), path,
            workers=cfg.warmup_workers,
        )
        rep_cfg = dataclasses.replace(cfg, warmup_artifact=path)

    if backend == "remote":
        # the TCP arm (ISSUE 16): N remote workers over loopback, each
        # booted here with the SAME pickled factory (and shared warmup
        # artifact) as the process arm, then routed as backend="remote"
        # replicas — supervised links, framed tensor bodies, no shm.
        # The workers outlive router.close() (a remote engine is
        # externally owned); handles land on args for driver teardown.
        from raft_tpu.serve import Replica
        from raft_tpu.serve.worker import start_remote_worker

        factory = ProcessEngineFactory(
            args.tiny, args.arch, args.random_init, rep_cfg
        )
        handles = []
        try:
            for _ in range(n_rep):
                handles.append(start_remote_worker(
                    factory, idle_timeout_s=600.0,
                ))
        except Exception:
            for h in handles:
                h.terminate()
            raise
        args._remote_handles = (
            getattr(args, "_remote_handles", None) or []
        ) + handles
        rcfg = RouterConfig()
        router = ServeRouter([
            Replica(
                f"r{i}", factory, error_window=rcfg.error_window,
                backend="remote", endpoint=h.endpoint,
            )
            for i, h in enumerate(handles)
        ], rcfg)
    elif backend == "process":
        # workers rebuild model + weights in their own interpreters; the
        # factory must cross the spawn boundary as a pickle
        factory = ProcessEngineFactory(
            args.tiny, args.arch, args.random_init, rep_cfg
        )
        worker_options = dict(
            ring_slots=args.worker_ring_slots,
            transport=effective_transport(args),
        )
        if args.tiny:
            worker_options["slot_bytes"] = 1 << 20
        router = ServeRouter.from_factory(
            factory, n_rep, RouterConfig(),
            backend="process", worker_options=worker_options,
        )
    else:
        if model is None:
            model, variables = build_model(args, cfg)

        def factory(**kw):
            return ServeEngine(
                model, variables,
                dataclasses.replace(rep_cfg, **kw) if kw else rep_cfg,
            )

        router = ServeRouter.from_factory(factory, n_rep, RouterConfig())
    if autoscale:
        Autoscaler(router, AutoscaleConfig(
            min_replicas=args.autoscale_min,
            max_replicas=args.autoscale_max,
            eval_interval_s=args.autoscale_interval,
            cooldown_s=args.autoscale_cooldown,
        ))
    return router, cfg


def assign_classes(args):
    """One traffic class per client thread, honoring the mix fractions."""
    mix = class_mix(args)
    names = ("pairwise", "stream", "bucket")
    counts = [int(round(f * args.clients)) for f in mix]
    while sum(counts) > args.clients:
        counts[counts.index(max(counts))] -= 1
    while sum(counts) < args.clients:
        counts[0] += 1
    return [n for n, c in zip(names, counts) for _ in range(c)]


def class_deadlines(args):
    base = args.deadline_ms
    if not args.class_deadline_ms:
        return {"pairwise": base, "stream": base, "bucket": base}
    ds = [float(x) for x in args.class_deadline_ms.split(",")]
    if len(ds) != 3 or any(d <= 0 for d in ds):
        raise SystemExit(
            f"--class-deadline-ms needs 3 positive values, got "
            f"{args.class_deadline_ms!r}"
        )
    return {"pairwise": ds[0], "stream": ds[1], "bucket": ds[2]}


def priority_mix(args):
    """(interactive, standard, batch) client fractions for --tenants."""
    raw = getattr(args, "priority_mix", None)
    if not raw:
        return (0.34, 0.33, 0.33)
    fr = [float(x) for x in raw.split(",")]
    if len(fr) != 3 or any(f < 0 for f in fr) or sum(fr) <= 0:
        raise SystemExit(
            f"--priority-mix needs 3 nonnegative fractions "
            f"(interactive,standard,batch), got {raw!r}"
        )
    s = sum(fr)
    return tuple(f / s for f in fr)


def assign_qos(args):
    """Per-client (priority, tenant) for the multi-tenant arm; all-None
    when --tenants is 0 so the legacy load is byte-identical (no QoS
    kwargs ride the submits at all)."""
    n_tenants = int(getattr(args, "tenants", 0) or 0)
    if n_tenants <= 0:
        return [(None, None)] * args.clients
    from raft_tpu.serve import PRIORITIES

    mix = priority_mix(args)
    counts = [int(round(f * args.clients)) for f in mix]
    while sum(counts) > args.clients:
        counts[counts.index(max(counts))] -= 1
    while sum(counts) < args.clients:
        counts[1] += 1  # spill into standard
    prios = [p for p, c in zip(PRIORITIES, counts) for _ in range(c)]
    return [
        (p, f"tenant{i % n_tenants}") for i, p in enumerate(prios)
    ]


def make_gap_fn(args, duration):
    """Per-client inter-arrival sampler: fresh closure per client (bursty
    carries per-client state). Returns gap seconds given (rng, elapsed).

    steady  — Poisson arrivals at --arrival-rate.
    bursty  — geometric on-bursts of back-to-back arrivals separated by
              idle gaps sized to keep the mean rate ~= --arrival-rate.
    diurnal — one sinusoidal "day" across the run (10x peak-to-trough),
              Poisson within the instantaneous rate.
    A rate of 0 keeps the legacy closed loop (back-to-back submits).
    """
    rate = args.arrival_rate
    if rate <= 0:
        return lambda rng, t: 0.0
    if args.arrival == "steady":
        return lambda rng, t: float(rng.exponential(1.0 / rate))
    if args.arrival == "diurnal":
        import math

        def gap(rng, t):
            r = rate * max(
                0.1,
                1.0 + 0.9 * math.sin(2.0 * math.pi * t / duration
                                     - math.pi / 2.0),
            )
            return float(rng.exponential(1.0 / r))

        return gap
    # bursty
    mean_burst = 8.0
    state = {"left": 0}

    def gap(rng, t):
        if state["left"] > 0:
            state["left"] -= 1
            return 0.0
        state["left"] = int(rng.geometric(1.0 / mean_burst))
        return float(rng.exponential(mean_burst / rate))

    return gap


def collect_traces(server, frontend=None) -> list:
    """Completed observability traces from the tier under test: the bare
    engine's tracer ring, or every replica engine's ring behind a
    router — plus, with ``--frontend``, the front door's stitched edge
    traces. Deduplicated by trace_id (ISSUE 15): under propagation a
    sampled request exists both as the stitched edge record and as the
    worker engine's own record; ``serve_phase_breakdown`` must count
    each phase once (the richer, stitched record wins)."""
    from raft_tpu.obs import dedupe_traces

    engines = []
    if hasattr(server, "replicas"):
        engines = [
            rep.engine for rep in server.replicas if rep.engine is not None
        ]
    elif hasattr(server, "tracer"):
        engines = [server]
    traces = []
    if frontend is not None:
        try:
            traces.extend(frontend.tracer.snapshot())
        except Exception:
            pass
    for eng in engines:
        try:
            traces.extend(eng.tracer.snapshot())
        except Exception:
            pass
    return dedupe_traces(traces)


def phase_breakdown(traces: list) -> dict:
    """Per-phase latency split measured from spans (ISSUE 10): the
    queue/admit/dispatch/fetch p50/p99 that used to be hand-estimated in
    docs/perf_notes.md now comes out of the traces themselves."""
    phases = {}
    for tr in traces:
        for sp in tr.get("spans", []):
            phases.setdefault(sp["name"], []).append(sp["dur_ms"])
    # canonical request phases first, extras (encode/refine/retry) after
    order = ["admit", "queue_wait", "batch_form", "dispatch", "fetch"]
    names = [n for n in order if n in phases] + sorted(
        n for n in phases if n not in order
    )
    return {
        n: {
            "n": len(phases[n]),
            "p50_ms": round(float(np.percentile(phases[n], 50)), 3),
            "p99_ms": round(float(np.percentile(phases[n], 99)), 3),
            "mean_ms": round(float(np.mean(phases[n])), 3),
        }
        for n in names
    }


def boot_report(args) -> dict:
    """A/B boot-to-ready across the three cold-start tiers (ISSUE 7):
    cold compile, persistent compilation cache (miss then hit), and
    warmup artifact. One report dict, BENCH lines per tier."""
    import tempfile

    from raft_tpu.serve import ServeEngine, aot

    cfg = build_config(args, warmup=True, warmup_artifact=None,
                       compilation_cache_dir=None)
    model, variables = build_model(args, cfg)
    report = {"programs": None}

    def boot_once(tag, **cfg_kw):
        import dataclasses

        eng = ServeEngine(
            model, variables, dataclasses.replace(cfg, **cfg_kw)
        )
        with eng:
            boot = eng.stats()["boot"]
        report[f"{tag}_ms"] = round(boot["boot_to_ready_ms"], 1)
        report[f"{tag}_programs_compiled"] = boot["programs_compiled"]
        report[f"{tag}_programs_loaded"] = boot["programs_loaded"]
        # raw XLA backend-compile events: distinguishes a persistent-cache
        # hit (trace+lower paid, backend compile skipped) from cold
        report[f"{tag}_backend_compiles"] = boot["backend_compiles"]
        report["programs"] = boot["programs_total"]
        return boot

    # 1) cold: no cache, no artifact (must run before the cache is wired
    #    — the persistent-cache config is process-global)
    boot_once("boot_cold")
    # 2) persistent cache: first boot populates (a miss only while the
    #    directory holds nothing for these programs — the path is fixed,
    #    never a temp name, because the path is part of the cache key),
    #    second hits. JAX_COMPILATION_CACHE_DIR, when set, wins inside
    #    the engine (runtime.enable_persistent_cache).
    from raft_tpu.utils.runtime import DEFAULT_CACHE_DIR

    cache_dir = args.compilation_cache_dir or DEFAULT_CACHE_DIR
    boot_once("boot_cache_miss", compilation_cache_dir=cache_dir)
    boot_once("boot_cache_hit", compilation_cache_dir=cache_dir)
    # 3) artifact: build it once (offline cost, reported), then boot
    art_path = args.warmup_artifact or os.path.join(
        tempfile.mkdtemp(prefix="raft_warmup_"), "warm.raftaot"
    )
    eng = ServeEngine(model, variables, cfg)
    build = aot.save_artifact(eng, art_path, workers=cfg.warmup_workers)
    report["artifact_build_s"] = build["build_s"]
    report["artifact_bytes"] = build["bytes"]
    boot_once("boot_artifact", warmup_artifact=art_path)
    report["boot_speedup_artifact_vs_cold"] = (
        round(report["boot_cold_ms"] / report["boot_artifact_ms"], 2)
        if report["boot_artifact_ms"]
        else None
    )
    config = (
        f"bucket={args.bucket}, ladder={args.ladder}, "
        f"max_batch={args.max_batch}, pool_capacity={args.pool_capacity}, "
        f"preset={args.preset}"
    )
    for metric, value, unit in [
        ("serve_boot_cold_ms", report["boot_cold_ms"], "ms"),
        ("serve_boot_cache_hit_ms", report["boot_cache_hit_ms"], "ms"),
        ("serve_boot_artifact_ms", report["boot_artifact_ms"], "ms"),
        ("serve_boot_speedup_artifact_vs_cold",
         report["boot_speedup_artifact_vs_cold"], "x"),
    ]:
        print(_line(
            {"metric": metric, "value": value, "unit": unit, "config": config}
        ), flush=True)
    print(_line({"metric": "serve_boot_report", **report}), flush=True)
    return report


def _smooth_stream_frames(hw, n_frames, shift=2, seed=0):
    """Deterministic smooth-motion synthetic stream with exact ground
    truth: a blurred low-frequency pattern viewed through a window that
    pans ``shift`` px/frame — content moves ``-shift`` px in x between
    consecutive frames. Low-frequency texture survives the encoder's 8x
    downsample, so the matching problem is well-posed (per-pixel noise
    is not trackable at the 1/8 grid)."""
    from numpy.lib.stride_tricks import sliding_window_view

    h, w = hw
    rng = np.random.default_rng(seed)
    pad = 16 + shift * n_frames
    coarse = rng.random(((h + 2 * pad) // 8 + 2, (w + 2 * pad) // 8 + 2, 3))
    big = np.kron(coarse.astype(np.float32), np.ones((8, 8, 1), np.float32))
    p = np.pad(big, ((3, 3), (3, 3), (0, 0)), mode="edge")
    smooth = sliding_window_view(p, (7, 7), axis=(0, 1)).mean(
        axis=(-2, -1)
    ) * 255.0
    frames = [
        smooth[16:16 + h, 16 + shift * t:16 + shift * t + w].astype(
            np.float32
        )
        for t in range(n_frames)
    ]
    gt = np.zeros((h, w, 2), np.float32)
    gt[..., 0] = -float(shift)
    return frames, gt


def _fixture_model(args):
    """The trained golden-fixture model when the fixture is present (the
    contractive refinement the adaptive A/B needs — random-init weights
    never converge), else the tiny random net (machinery smoke only)."""
    fixture = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "fixtures", "epe_golden",
    )
    if args.ab_model == "tiny" or (
        args.ab_model == "auto" and not os.path.isdir(fixture)
    ):
        from raft_tpu.models import build_raft, init_variables

        model = build_raft(tiny_config())
        return model, init_variables(model), "tiny-random"
    import flax.serialization
    import jax

    from raft_tpu.models.zoo import build_raft, init_variables
    from scripts.make_epe_fixture import fixture_arch

    model = build_raft(fixture_arch())
    tmpl = jax.tree.map(
        np.zeros_like, jax.device_get(init_variables(model))
    )
    with open(os.path.join(fixture, "weights.msgpack"), "rb") as f:
        trained = flax.serialization.from_bytes(tmpl, f.read())
    return model, trained, "fixture-trained"


def _ab_scenes(args, model_tag):
    """The A/B's stream workload: the golden fixture's real scenes
    (frames + ground-truth flows) under the trained model — real motion
    is what makes warm start and convergence behave like the paper's —
    or one synthetic smooth-motion scene for the tiny machinery smoke.
    Returns [(frames, gts)], gts aligned with pairs (t-1, t)."""
    if model_tag != "fixture-trained":
        frames, gt = _smooth_stream_frames((96, 128), 4)
        return [(frames, [gt] * (len(frames) - 1))], (96, 128)
    import glob as _glob

    from raft_tpu.data.io import read_flow, read_image

    fixture = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tests", "fixtures", "epe_golden",
    )
    scenes = []
    hw = None
    for scene_dir in sorted(
        _glob.glob(os.path.join(fixture, "training", "clean", "*"))
    ):
        frames = [
            read_image(p).astype(np.float32)
            for p in sorted(_glob.glob(os.path.join(scene_dir, "*.png")))
        ]
        gts = [
            read_flow(p)[0]
            for p in sorted(_glob.glob(os.path.join(
                fixture, "training", "flow",
                os.path.basename(scene_dir), "*.flo",
            )))
        ]
        if len(frames) >= 2 and len(gts) >= len(frames) - 1:
            scenes.append((frames, gts))
            h, w = frames[0].shape[:2]
            hw = ((h + 7) // 8 * 8, (w + 7) // 8 * 8)
    return scenes, hw


def adaptive_ab(args) -> dict:
    """Built-in adaptive-vs-fixed A/B (ISSUE 12): the same deterministic
    stream workload through two engines — fixed iteration target vs
    residual-driven early exit + warm start — measuring mean
    iters/request, throughput, and the EPE cost against ground truth.

    The workload is the golden fixture's real scenes (trained weights,
    real motion, real GT) streamed in laps — each lap re-opens the
    stream per scene, so the first pair of a lap is always cold and the
    rest warm-start, exactly the video serving pattern. ``epe_delta_px``
    is **measured quality degradation**: ``max(0, epe_adaptive -
    epe_fixed)``. Over-iterating RAFT past its EPE optimum slowly
    degrades (the calibration sweep shows it), so an adaptive arm that
    lands a BETTER EPE costs zero — both raw EPEs are reported for the
    record.
    """
    from raft_tpu.serve import ServeConfig, ServeEngine

    model, variables, model_tag = _fixture_model(args)
    n_iters = args.ab_iters
    thresh = (
        args.converge_thresh if args.converge_thresh is not None else 0.03
    )
    scenes, bucket = _ab_scenes(args, model_tag)
    pairs_per_lap = sum(len(f) - 1 for f, _ in scenes)
    laps = max(1, int(np.ceil(args.ab_frames / pairs_per_lap)))

    base_kw = dict(
        buckets=(bucket,),
        ladder=(n_iters,),
        pool_capacity=2,
        max_batch=2,
        stream_cache_size=4,
        queue_capacity=16,
        default_deadline_ms=600000.0,
        pool_min_iters=2,
        warmup=False,
    )

    def run_lap(eng, record):
        iters, epes, reasons, warm, n = [], [], {}, 0, 0
        for frames, gts in scenes:
            with eng.open_stream() as stream:
                for t, f in enumerate(frames):
                    res = stream.submit(f)
                    if res.primed:
                        continue
                    n += 1
                    if record:
                        iters.append(res.num_flow_updates)
                        reasons[res.exit_reason] = (
                            reasons.get(res.exit_reason, 0) + 1
                        )
                        warm += int(res.warm_started)
                        gt = gts[t - 1]
                        err = np.sqrt((
                            (res.flow[: gt.shape[0], : gt.shape[1]] - gt)
                            ** 2
                        ).sum(-1))
                        epes.append(float(err.mean()))
        return iters, epes, reasons, warm, n

    def run_arm(**kw):
        eng = ServeEngine(model, variables, ServeConfig(**base_kw, **kw))
        with eng:
            # warm lap outside the timed window (first traffic compiles
            # the pool programs — warmup=False keeps the A/B boot cheap)
            run_lap(eng, record=False)
            iters, epes, reasons, warm = [], [], {}, 0
            t0 = time.monotonic()
            n_timed = 0
            for _ in range(laps):
                li, le, lr, lw, n = run_lap(eng, record=True)
                iters += li
                epes += le
                warm += lw
                n_timed += n
                for k, v in lr.items():
                    reasons[k] = reasons.get(k, 0) + v
            elapsed = time.monotonic() - t0
        return {
            "iters_per_req": round(float(np.mean(iters)), 3),
            "throughput_rps": round(n_timed / elapsed, 3),
            "epe_px": round(float(np.mean(epes)), 5),
            "exit_reasons": reasons,
            "warm_starts": warm,
            "pairs": len(iters),
        }

    fixed = run_arm()
    adaptive = run_arm(
        pool_converge_thresh=thresh,
        pool_converge_streak=args.converge_streak,
        stream_warm_start=True,
    )
    config = (
        f"adaptive_ab bucket={bucket[0]}x{bucket[1]}, iters={n_iters}, "
        f"pairs={fixed['pairs']}, thresh={thresh}, "
        f"streak={args.converge_streak}, model={model_tag}"
    )
    report = {
        "metric": "serve_adaptive_ab",
        "model": model_tag,
        "ab_iters": n_iters,
        "converge_thresh": thresh,
        "converge_streak": args.converge_streak,
        "pairs": fixed["pairs"],
        "iters_per_req_fixed": fixed["iters_per_req"],
        "iters_per_req_adaptive": adaptive["iters_per_req"],
        "iters_reduction_frac": round(
            1.0 - adaptive["iters_per_req"] / max(
                fixed["iters_per_req"], 1e-9
            ), 4,
        ),
        "throughput_rps_fixed": fixed["throughput_rps"],
        "throughput_rps_adaptive": adaptive["throughput_rps"],
        "speedup": round(
            adaptive["throughput_rps"]
            / max(fixed["throughput_rps"], 1e-9), 3,
        ),
        "epe_fixed_px": fixed["epe_px"],
        "epe_adaptive_px": adaptive["epe_px"],
        # degradation only: better-EPE-than-fixed clamps to zero
        "epe_delta_px": round(
            max(0.0, adaptive["epe_px"] - fixed["epe_px"]), 5
        ),
        "exit_reasons_adaptive": adaptive["exit_reasons"],
        "warm_starts_adaptive": adaptive["warm_starts"],
        "config": config,
    }
    print(_line(report), flush=True)
    return report


def rollout_bench(args) -> dict:
    """Guarded-rollout scenario (ISSUE 18): three arms over thread
    fleets sharing one warmup artifact, one ``serve_rollout`` BENCH
    line.

    1. **mirror tax** — interleaved best-of-rounds A/B through the
       tier's front door: the same request loop against a plain fleet
       and against fleets with a candidate parked in shadow (gate
       floor unreachably high so the ladder never advances), in two
       flavors. ``mirror_overhead_pct`` is the **hot-path machinery
       tax** — the candidate's deadline is set so mirrors shed at
       admission without running inference, isolating what the caller
       pays for the stride counter + bounded hand-off (the "caller
       latency untouched" claim; on production hardware candidate
       compute runs on the candidate's own device). The full-compute
       flavor rides along as ``mirror_capacity_tax_pct`` — what
       mirroring costs when candidate inference shares this host's
       cores (on a 1-core CI box that is mostly raw compute
       contention, reported, not the acceptance number).
    2. **happy ladder** — an identical-weights candidate walks shadow
       -> canary -> promoted under flood; the line carries the stage
       timeline and the gate's measured flow diff (px).
    3. **bad candidate** — a perturbed-weights candidate against a
       tight flow gate: the ladder must auto-rollback (rollback_count,
       reason ride the line).
    """
    import dataclasses
    import tempfile

    from raft_tpu.serve import (
        RolloutAborted, RolloutConfig, RolloutStage, RouterConfig,
        ServeEngine, ServeRouter, aot,
    )

    cfg = build_config(args)
    model, variables = build_model(args, cfg)
    path = os.path.join(
        tempfile.mkdtemp(prefix="raft_rollout_aot_"), "shared.raftaot"
    )
    aot.save_artifact(
        ServeEngine(model, variables, cfg), path, workers=cfg.warmup_workers,
    )
    rep_cfg = dataclasses.replace(cfg, warmup=True, warmup_artifact=path)

    def factory(**kw):
        return ServeEngine(
            model, variables,
            dataclasses.replace(rep_cfg, **kw) if kw else rep_cfg,
        )

    n_rep = max(2, args.replicas)
    rng = np.random.default_rng(11)
    bh, bw = cfg.buckets[0]
    im1 = rng.integers(0, 255, (bh - 3, bw - 4, 3), dtype=np.uint8)
    im2 = rng.integers(0, 255, (bh - 3, bw - 4, 3), dtype=np.uint8)
    deadline = args.deadline_ms
    # the CPU bench box makes candidate queue-wait a meaningless
    # promotion signal (one candidate absorbs a whole fleet's mirrors);
    # quality gates judge, latency/iters gates stand down
    lax = dict(latency_ratio=1000.0, iters_delta=1000.0)

    def _router():
        return ServeRouter.from_factory(
            factory, n_rep,
            RouterConfig(heartbeat_interval_s=0.1, cooldown_s=0.5),
        )

    def run_round(router, n_req):
        lats = []
        t0 = time.monotonic()
        for _ in range(n_req):
            t1 = time.monotonic()
            try:
                router.submit(im1, im2, deadline_ms=deadline)
            except Exception:
                continue
            lats.append((time.monotonic() - t1) * 1e3)
        elapsed = time.monotonic() - t0
        return len(lats) / max(elapsed, 1e-9), lats

    def flood_until_terminal(router, ctrl, timeout_s=120.0):
        t0 = time.monotonic()
        n = 0
        while (
            ctrl.stage not in RolloutStage.TERMINAL
            and time.monotonic() - t0 < timeout_s
        ):
            try:
                router.submit(im1, im2, deadline_ms=deadline)
                n += 1
            except Exception:
                time.sleep(0.02)
        return n

    # -- arm 1: mirror tax, interleaved best-of-rounds ---------------------
    reqs = max(24, int(args.duration * 4))
    rounds = 3
    best = {"off": 0.0, "on": 0.0, "on_full": 0.0}
    p99 = {"off": None, "on": None, "on_full": None}
    r_off, r_on, r_full = _router(), _router(), _router()
    with r_off, r_on, r_full:
        # the acceptance arm: mirrors sampled + handed off for real, but
        # the candidate's deadline sheds them at admission — no inference
        # ever runs, so the delta vs "off" is pure mirroring machinery
        r_on.add_candidate(rollout_config=RolloutConfig(
            min_samples=10**6,  # gate floor unreachable: parked in shadow
            candidate_deadline_ms=1e-4,
            **lax,
        ))
        # the capacity arm: same ladder, mirrors run real inference on
        # this host's (shared) cores
        r_full.add_candidate(rollout_config=RolloutConfig(
            min_samples=10**6, **lax,
        ))
        mirror_fraction = r_on.rollout.config.mirror_fraction
        for router in (r_off, r_on, r_full):
            run_round(router, reqs // 2)  # warm outside the clock
        for _ in range(rounds):
            for arm, router in (
                ("off", r_off), ("on", r_on), ("on_full", r_full),
            ):
                rps, lats = run_round(router, reqs)
                if rps > best[arm]:
                    best[arm] = rps
                    p99[arm] = round(float(np.percentile(lats, 99)), 3)
        tax_snap = r_full.rollout.snapshot()
    overhead_pct = max(
        0.0, (1.0 - best["on"] / max(best["off"], 1e-9)) * 100.0
    )
    capacity_tax_pct = max(
        0.0, (1.0 - best["on_full"] / max(best["off"], 1e-9)) * 100.0
    )

    # -- arm 2: happy ladder to promotion ----------------------------------
    router = _router()
    flow_diff = {"flow_mean_px": None, "flow_p99_px": None}
    with router:
        ctrl = router.add_candidate(rollout_config=RolloutConfig(
            mirror_fraction=0.5, canary_fraction=0.5, min_samples=8,
            shadow_hold_s=1.0, canary_hold_s=1.0,
            short_window_s=0.5, long_window_s=2.0, **lax,
        ))
        t0 = time.monotonic()
        n = 0
        while (
            ctrl.stage not in RolloutStage.TERMINAL
            and time.monotonic() - t0 < 120.0
        ):
            try:
                router.submit(im1, im2, deadline_ms=deadline)
            except Exception:
                time.sleep(0.02)
            n += 1
            if n % 16 == 0:
                # the gate's window empties during the promoting drain:
                # sample the measured diff while mirrors still flow
                g = ctrl.gate.evaluate()["long"]
                if g.get("flow_mean_px") is not None:
                    flow_diff = {
                        "flow_mean_px": round(g["flow_mean_px"], 5),
                        "flow_p99_px": round(g["flow_p99_px"], 5),
                    }
        happy = ctrl.wait(timeout=60.0)

    # -- arm 3: bad candidate must roll back -------------------------------
    import jax

    noise = np.random.default_rng(13)
    perturbed = jax.tree_util.tree_map(
        lambda a: a + np.asarray(
            noise.normal(0.0, 0.5, np.shape(a)), np.result_type(a)
        ),
        variables,
    )

    def bad_factory(**kw):
        return ServeEngine(
            model, perturbed,
            dataclasses.replace(rep_cfg, **kw) if kw else rep_cfg,
        )

    rollback_count, rollback_reason = 0, None
    router = _router()
    with router:
        ctrl = router.add_candidate(
            factory=bad_factory,
            rollout_config=RolloutConfig(
                mirror_fraction=1.0, canary_fraction=0.5, min_samples=8,
                shadow_hold_s=2.0, canary_hold_s=2.0,
                short_window_s=0.5, long_window_s=2.0,
                # identical weights diff to exactly 0: any persistent
                # disagreement is the regression signal
                flow_diff_mean_px=0.01, flow_diff_p99_px=0.05,
                error_rate=0.5, **lax,
            ),
        )
        flood_until_terminal(router, ctrl)
        try:
            ctrl.wait(timeout=60.0)
        except RolloutAborted as e:
            rollback_count, rollback_reason = 1, e.reason
        bad_snap = ctrl.snapshot()

    config = (
        f"rollout bucket={bh}x{bw}, replicas={n_rep}, "
        f"rounds={rounds}, reqs_per_round={reqs}, "
        f"mirror_fraction={mirror_fraction}, ladder={args.ladder}"
    )
    report = {
        "metric": "serve_rollout",
        "throughput_rps_off": round(best["off"], 3),
        "throughput_rps_on": round(best["on"], 3),
        "rps_ratio_mirror_vs_off": round(
            best["on"] / max(best["off"], 1e-9), 4
        ),
        "mirror_overhead_pct": round(overhead_pct, 2),
        "throughput_rps_on_full": round(best["on_full"], 3),
        "mirror_capacity_tax_pct": round(capacity_tax_pct, 2),
        "p99_ms_off": p99["off"],
        "p99_ms_on": p99["on"],
        "p99_ms_on_full": p99["on_full"],
        "mirrored_tax_arm": tax_snap["mirrored"],
        "mirror_shed_tax_arm": tax_snap["mirror_shed"],
        "flow_diff_mean_px": flow_diff["flow_mean_px"],
        "flow_diff_p99_px": flow_diff["flow_p99_px"],
        "stage_timeline": happy["stage_history"],
        "promoted_replicas": happy["promoted_replicas"],
        "mirrored": happy["mirrored"],
        "canary_routed": happy["canary_routed"],
        "rollback_count": rollback_count,
        "rollback_reason": rollback_reason,
        "rollback_stage_timeline": bad_snap["stage_history"],
        "config": config,
    }
    print(_line(report), flush=True)
    return report


def edge_ab(args) -> dict:
    """Front-door A/B + redundancy-layer measurement (ISSUE 19).

    Phase 1 — ONE engine behind both front doors in turn (the stdlib
    threading server, then the selectors event loop) at equal
    closed-loop load. Edge latency is measured at the CLIENT and the
    engine's own ``latency_ms`` subtracted per request: the
    distribution of that delta IS the wire tax each front door charges,
    independent of how busy the engine underneath happens to be.

    Phase 2 (with any cache knob on) — the chosen arm with the
    redundancy layer enabled, driven with traffic over a SMALL set of
    repeating pairs (plus sensor-noise near-duplicates when
    ``--edge-near-dup`` is set), so exact hits, coalesces and near-dups
    arise the way production redundancy does. The block reports
    hit/coalesce/near-dup rates, the refinement iterations the cache
    absorbed, and a zero-engine-submit pin on an exact hit.

    One ``serve_edge_cache`` BENCH line carries both phases.
    """
    from raft_tpu.serve import ServeEngine, ServeError
    from raft_tpu.serve.frontend import FrontendClient, ServeFrontend

    cfg = build_config(args)
    model, variables = build_model(args, cfg)
    bucket = cfg.buckets[0]
    hw = (bucket[0] - 3, bucket[1] - 4)
    rng = np.random.default_rng(7)
    uniq = [
        (rng.integers(0, 255, hw + (3,), dtype=np.uint8),
         rng.integers(0, 255, hw + (3,), dtype=np.uint8))
        for _ in range(max(2, args.edge_unique_pairs))
    ]
    arms = ("thread", "async") if args.edge == "ab" else (args.edge,)
    half = max(2.0, args.duration / 2.0)
    eng = ServeEngine(model, variables, cfg)
    eng.start()
    report: dict = {"metric": "serve_edge_cache", "arms": {}}
    try:
        eng.submit(uniq[0][0], uniq[0][1])  # compile outside the clock

        # per-client think time: below engine capacity the front door's
        # OWN overhead is what the tax measures (closed-loop saturation
        # would bury both arms under the same engine queue)
        gap_s = (
            1.0 / args.arrival_rate if args.arrival_rate > 0 else 0.0
        )

        def drive(fe, duration, pick, record):
            stop = threading.Event()

            def worker(seed):
                c = FrontendClient(fe.address)
                c_rng = np.random.default_rng(300 + seed)
                try:
                    while not stop.is_set():
                        if gap_s > 0 and stop.wait(
                            c_rng.exponential(gap_s)
                        ):
                            return
                        im1, im2 = pick(c_rng, seed)
                        t0 = time.monotonic()
                        try:
                            if args.edge_fresh_conns:
                                # connection setup is part of the tax:
                                # the clock starts before connect
                                c.close_connection()
                            r = c.submit(
                                im1, im2, deadline_ms=args.deadline_ms
                            )
                        except ServeError:
                            continue
                        record((time.monotonic() - t0) * 1e3, r)
                finally:
                    c.close_connection()

            threads = [
                threading.Thread(target=worker, args=(i,), daemon=True)
                for i in range(args.clients)
            ]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            time.sleep(duration)
            stop.set()
            for t in threads:
                t.join(timeout=30.0)
            return time.monotonic() - t0

        def q(xs, p):
            return round(float(np.percentile(xs, p)), 3) if xs else None

        # interleaved best-of-rounds (the rollout mirror-tax idiom):
        # alternate the arms in short segments and keep each arm's best
        # round per stat — scheduler noise hits whichever arm is
        # running, best-of keeps the measurement, not the noise
        rounds = max(1, args.edge_rounds)
        segment = max(2.0, half / rounds)
        samples = {arm: [] for arm in arms}
        for _ in range(rounds):
            for arm in arms:
                lock = threading.Lock()
                edge_ms: list = []
                taxes: list = []

                def record(lat, r, _e=edge_ms, _t=taxes, _l=lock):
                    with _l:
                        _e.append(lat)
                        if r.get("latency_ms") is not None:
                            _t.append(lat - float(r["latency_ms"]))

                with ServeFrontend(
                    eng, edge=arm, handler_pool=args.edge_handler_pool,
                ) as fe:
                    elapsed = drive(
                        fe, segment,
                        lambda c_rng, seed: uniq[seed % len(uniq)],
                        record,
                    )
                samples[arm].append({
                    "requests": len(edge_ms),
                    "throughput_rps": round(len(edge_ms) / elapsed, 3),
                    "edge_p50_ms": q(edge_ms, 50),
                    "edge_p99_ms": q(edge_ms, 99),
                    "wire_tax_p50_ms": q(taxes, 50),
                    "wire_tax_p99_ms": q(taxes, 99),
                })
        for arm in arms:
            rs = samples[arm]
            best = {
                "requests": sum(r["requests"] for r in rs),
                "rounds": len(rs),
                "throughput_rps": max(
                    r["throughput_rps"] for r in rs
                ),
            }
            for stat in ("edge_p50_ms", "edge_p99_ms",
                         "wire_tax_p50_ms", "wire_tax_p99_ms"):
                vals = [r[stat] for r in rs if r[stat] is not None]
                best[stat] = min(vals) if vals else None
            report["arms"][arm] = best

        th = report["arms"].get("thread")
        an = report["arms"].get("async")
        if th and an and th.get("wire_tax_p50_ms"):
            report["wire_tax_p50_ratio_async_vs_thread"] = round(
                an["wire_tax_p50_ms"] / max(th["wire_tax_p50_ms"], 1e-9),
                3,
            )

        cache_on = (
            args.edge_cache > 0 or args.edge_coalesce
            or args.edge_near_dup is not None
        )
        if cache_on:
            arm = "async" if args.edge == "ab" else args.edge
            fe = ServeFrontend(
                eng, edge=arm, handler_pool=args.edge_handler_pool,
                flow_cache_entries=args.edge_cache,
                coalesce=args.edge_coalesce,
                near_dup_threshold=args.edge_near_dup,
            ).start()
            lock = threading.Lock()
            tally = {"n": 0, "iters_saved": 0}

            def record2(lat, r, _l=lock):
                with _l:
                    tally["n"] += 1
                    if r.get("edge_cached") or r.get("edge_coalesced"):
                        tally["iters_saved"] += int(
                            r.get("num_flow_updates") or 0
                        )

            def pick2(c_rng, seed):
                im1, im2 = uniq[int(c_rng.integers(0, len(uniq)))]
                if (
                    args.edge_near_dup is not None
                    and c_rng.random() < 0.3
                ):
                    # a near-duplicate: the same scene plus faint
                    # sensor noise — close in signature space,
                    # different content hash
                    im1 = np.clip(
                        im1.astype(np.int16)
                        + c_rng.integers(-2, 3, im1.shape),
                        0, 255,
                    ).astype(np.uint8)
                return im1, im2

            s_before = eng.stats()["submitted"]
            drive(fe, half, pick2, record2)
            snap = fe.edge_cache.snapshot()
            s_after = eng.stats()["submitted"]
            # the exact-hit pin: a cached pair completes with ZERO new
            # engine submits — the whole point of the flow cache
            c = FrontendClient(fe.address)
            c.submit(uniq[0][0], uniq[0][1], deadline_ms=args.deadline_ms)
            s0 = eng.stats()["submitted"]
            r = c.submit(
                uniq[0][0], uniq[0][1], deadline_ms=args.deadline_ms
            )
            s1 = eng.stats()["submitted"]
            c.close_connection()
            fe.close()
            n = max(tally["n"], 1)
            report["cache"] = {
                "arm": arm,
                "requests": tally["n"],
                "unique_pairs": len(uniq),
                "engine_submits": int(s_after - s_before),
                "hit_rate": round(snap["hits"] / n, 4),
                "coalesce_rate": round(snap["coalesced"] / n, 4),
                "near_dup_rate": round(
                    snap["near_dup_hits"] / max(snap["misses"], 1), 4
                ),
                "iters_saved": int(tally["iters_saved"]),
                "zero_engine_submits_on_hit": bool(
                    r.get("edge_cached") and s1 == s0
                ),
                "entries": snap["entries"],
                "evictions": snap["evictions"],
                "invalidations": snap["invalidations"],
            }
    finally:
        eng.stop()
    report["config"] = (
        f"edge_ab bucket={bucket[0]}x{bucket[1]}, clients={args.clients}, "
        f"fresh_conns={args.edge_fresh_conns}, "
        f"ladder={args.ladder}, max_batch={args.max_batch}, "
        f"pool_capacity={cfg.pool_capacity}, "
        f"unique_pairs={len(uniq)}, cache={args.edge_cache}, "
        f"coalesce={args.edge_coalesce}, near_dup={args.edge_near_dup}"
    )
    print(_line(report), flush=True)
    return report


def _seam_p99_px(plan, flow) -> float:
    """p99 step discontinuity (px) across every interior tile-boundary
    line of one blended flow — the gauge that a feather regression
    (or a placement bug) cannot hide behind mean EPE."""
    H, W = plan.hw
    xs, ys = set(), set()
    for t in plan.tiles:
        if t.x0 > 0:
            xs.add(t.x0)
        if t.x0 + t.w < W:
            xs.add(t.x0 + t.w)
        if t.y0 > 0:
            ys.add(t.y0)
        if t.y0 + t.h < H:
            ys.add(t.y0 + t.h)
    diffs = [np.abs(flow[:, x] - flow[:, x - 1]).ravel() for x in xs]
    diffs += [np.abs(flow[y] - flow[y - 1]).ravel() for y in ys]
    if not diffs:
        return 0.0
    return float(np.percentile(np.concatenate(diffs), 99))


def tiled_bench(args) -> dict:
    """Off-bucket tiled serving (ISSUE 20): closed-loop clients submit
    shapes NO bucket admits through the ``unknown_shape='tiled'`` arm.

    One ``serve_tiled`` BENCH line carries the degraded-but-served
    rung's whole economy: request throughput and latency quantiles,
    tiles and queue acquisitions per request (the one-``put_many`` pin:
    acquisitions/request stays 1.0 while plans fit the queue), the
    planner's dispatched-pixel waste fraction, the host-side blend cost,
    and the p99 seam discontinuity of a served flow (feather health,
    model-free).
    """
    from raft_tpu.serve import ServeEngine

    cfg = build_config(args, unknown_shape="tiled")
    model, variables = build_model(args, cfg)
    eng = ServeEngine(model, variables, cfg)
    bh, bw = cfg.buckets[0]
    if args.tiled_shapes:
        shapes = [
            tuple(int(x) for x in s.split("x"))
            for s in args.tiled_shapes.split(",")
        ]
    else:
        # one multi-tile canvas (~2x the bucket each way, off the %8
        # grid like real uploads) + one short-and-wide shape whose rows
        # ride a single replicate-padded tile (the pad-penalty arm)
        shapes = [(2 * bh - 4, 2 * bw + 4), (bh - 8, 2 * bw + 4)]
    rng = np.random.default_rng(0)
    pairs = [
        (
            rng.integers(0, 255, (*hw, 3), dtype=np.uint8),
            rng.integers(0, 255, (*hw, 3), dtype=np.uint8),
        )
        for hw in shapes
    ]
    lat: list = []
    errors = [0]
    lock = threading.Lock()
    state = {"stop_at": 0.0}

    def client(ci):
        r = np.random.default_rng(1000 + ci)
        while time.monotonic() < state["stop_at"]:
            im1, im2 = pairs[int(r.integers(len(pairs)))]
            t0 = time.monotonic()
            try:
                res = eng.submit(im1, im2, deadline_ms=args.deadline_ms)
                assert res.tiled or res.bucket == (bh, bw)
            except Exception:
                with lock:
                    errors[0] += 1
                continue
            dt = (time.monotonic() - t0) * 1e3
            with lock:
                lat.append(dt)

    with eng:
        # warm every shape's plan + program rungs outside the timed
        # window, and grade the feather on the multi-tile canvas
        warm = [
            eng.submit(im1, im2, deadline_ms=args.deadline_ms)
            for im1, im2 in pairs
        ]
        seam_p99 = 0.0
        for hw, res in zip(shapes, warm):
            if res.tiled:
                seam_p99 = max(
                    seam_p99, _seam_p99_px(eng._tiler.plan(hw), res.flow)
                )
        base = eng.stats()["tiler"]
        t_start = time.monotonic()
        state["stop_at"] = t_start + args.duration
        threads = [
            threading.Thread(target=client, args=(ci,), daemon=True)
            for ci in range(args.clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t_start
        st = eng.stats()["tiler"]
    n_req = st["requests"] - base["requests"]
    n_acq = st["admission_acquisitions"] - base["admission_acquisitions"]
    n_tiles = st["tiles_submitted"] - base["tiles_submitted"]
    report = {
        "metric": "serve_tiled",
        "value": round(len(lat) / max(wall, 1e-9), 3),
        "unit": "req/s",
        "throughput_rps": round(len(lat) / max(wall, 1e-9), 3),
        "p50_ms": round(float(np.percentile(lat, 50)), 3) if lat else None,
        "p99_ms": round(float(np.percentile(lat, 99)), 3) if lat else None,
        "requests": len(lat),
        "errors": errors[0],
        "tiles_per_request": round(n_tiles / max(n_req, 1), 3),
        "acquisitions_per_request": round(n_acq / max(n_req, 1), 3),
        "tiles_retried": st["tiles_retried"] - base["tiles_retried"],
        "waste_frac": st["waste_frac"],
        "seam_p99_px": round(seam_p99, 4),
        "blend_p50_ms": (st["blend_ms"] or {}).get("p50_ms"),
        "blend_p99_ms": (st["blend_ms"] or {}).get("p99_ms"),
        "plans_built": st["plans_built"],
        "plan_cache_hits": st["plan_cache_hits"],
        "shapes": [f"{h}x{w}" for h, w in shapes],
        "config": (
            f"tiled bucket={bh}x{bw}, clients={args.clients}, "
            f"shapes={','.join(f'{h}x{w}' for h, w in shapes)}, "
            f"ladder={args.ladder}, max_batch={args.max_batch}, "
            f"pool_capacity={cfg.pool_capacity}, "
            f"queue_capacity={cfg.queue_capacity}, "
            f"overlap={cfg.tile_overlap_px}"
        ),
    }
    print(_line(report), flush=True)
    return report


def transport_parity(args) -> bool:
    """One fixed pair served through a binary-transport worker and a
    legacy-transport worker (same pickled factory, same deterministic
    weights, one shared warmup artifact): the flows must be bitwise
    identical — the codec/coalescing change moves bytes, it must never
    touch math. The pinned half of the ``serve_transport`` A/B."""
    import dataclasses
    import tempfile

    from raft_tpu.serve import ServeEngine, aot
    from raft_tpu.serve.worker import ProcessEngineClient

    cfg = build_config(args)
    if cfg.warmup_artifact:
        # reuse the caller's artifact: building a fresh one inside a
        # persistent-cache-enabled process can serialize cache-restored
        # executables whose symbol tables are gone (the PR 9 failure
        # mode save_artifact guards cold processes against)
        path = cfg.warmup_artifact
    else:
        model, variables = build_model(args, cfg)
        path = os.path.join(
            tempfile.mkdtemp(prefix="raft_xport_aot_"), "shared.raftaot"
        )
        aot.save_artifact(
            ServeEngine(model, variables, cfg), path,
            workers=cfg.warmup_workers,
        )
    rep_cfg = dataclasses.replace(cfg, warmup=True, warmup_artifact=path)
    factory = ProcessEngineFactory(
        args.tiny, args.arch, args.random_init, rep_cfg
    )
    rng = np.random.default_rng(7)
    bh, bw = cfg.buckets[0]
    im1 = rng.integers(0, 255, (bh - 3, bw - 4, 3), dtype=np.uint8)
    im2 = rng.integers(0, 255, (bh - 3, bw - 4, 3), dtype=np.uint8)
    wopts = dict(ring_slots=args.worker_ring_slots)
    if args.tiny:
        wopts["slot_bytes"] = 1 << 20
    flows = {}
    for mode in ("binary", "legacy"):
        client = ProcessEngineClient(factory, transport=mode, **wopts)
        with client:
            flows[mode] = np.asarray(client.submit(im1, im2).flow)
    return bool(np.array_equal(flows["binary"], flows["legacy"]))


def run_bench(args) -> dict:
    server, cfg = build_server(args)
    buckets = cfg.buckets
    bucket = buckets[0]
    bucket2 = buckets[1] if len(buckets) > 1 else buckets[0]
    # odd sizes: exercise bucket padding
    hw_for = {
        "pairwise": (bucket[0] - 3, bucket[1] - 4),
        "stream": (bucket[0] - 3, bucket[1] - 4),
        "bucket": (bucket2[0] - 3, bucket2[1] - 4),
    }
    deadlines = class_deadlines(args)
    assignments = assign_classes(args)
    n_stream = sum(1 for c in assignments if c == "stream")
    qos_assign = assign_qos(args)
    qos_on = any(p is not None for p, _ in qos_assign)

    from raft_tpu.serve import Overloaded, QuotaExceeded, ServeError

    iters_mix = (
        [int(x) for x in args.iters_mix.split(",")] if args.iters_mix else None
    )

    use_frontend = bool(getattr(args, "frontend", False))
    frontend_box = [None]  # the ServeFrontend, set inside the with block

    lock = threading.Lock()
    levels = []
    iters_served = []
    exit_reasons = {"target": 0, "deadline": 0, "converged": 0}
    per_class = {
        c: {"latencies": [], "engine_latencies": [], "ok": 0, "shed": 0,
            "failed": 0, "primed": 0, "slo_miss": 0}
        for c in ("pairwise", "stream", "bucket")
    }
    # the multi-tenant ledger (ISSUE 17): same counters keyed by QoS
    # class — the serve_qos BENCH line is cut from this
    per_qos = {
        p: {"latencies": [], "ok": 0, "shed": 0, "quota_refused": 0,
            "failed": 0, "slo_miss": 0}
        for p in ("interactive", "standard", "batch")
    }
    stop = threading.Event()
    t_start_box = [0.0]

    def qos_note(pr, key, latency_ms=None, deadline=None):
        if pr is None:
            return
        with lock:
            q = per_qos[pr]
            q[key] += 1
            if latency_ms is not None:
                q["latencies"].append(latency_ms)
                if deadline is not None and latency_ms > deadline:
                    q["slo_miss"] += 1

    def record_ok(cls, latency_ms, res):
        with lock:
            pc = per_class[cls]
            pc["ok"] += 1
            pc["latencies"].append(latency_ms)
            # the engine's own measure of the same request: with
            # --frontend the delta between the two IS the HTTP+wire tax
            pc["engine_latencies"].append(res.latency_ms)
            if latency_ms > deadlines[cls]:
                pc["slo_miss"] += 1
            levels.append(res.level)
            # adaptive compute (ISSUE 12): what the requests actually
            # paid, and why each one stopped where it did
            iters_served.append(res.num_flow_updates)
            exit_reasons[res.exit_reason] = (
                exit_reasons.get(res.exit_reason, 0) + 1
            )

    def client(cls, seed):
        from types import SimpleNamespace

        c_rng = np.random.default_rng(1000 + seed)
        gap = make_gap_fn(args, args.duration)
        h, w = hw_for[cls]
        im1 = c_rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
        im2 = c_rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
        deadline = deadlines[cls]
        pr, ten = qos_assign[seed % len(qos_assign)]
        qkw = {} if pr is None else {"priority": pr, "tenant": ten}
        fc = None
        if use_frontend:
            from raft_tpu.serve.frontend import FrontendClient

            fc = FrontendClient(frontend_box[0].address)
        while not stop.is_set():
            g = gap(c_rng, time.monotonic() - t_start_box[0])
            if g > 0 and stop.wait(g):
                return
            n = int(c_rng.choice(iters_mix)) if iters_mix else None
            t0 = time.monotonic()
            try:
                if fc is not None:
                    # through the front door: the measured latency is
                    # the EDGE latency the user actually pays
                    res = SimpleNamespace(**fc.submit(
                        im1, im2, deadline_ms=deadline,
                        num_flow_updates=n, **qkw,
                    ))
                else:
                    res = server.submit(
                        im1, im2, deadline_ms=deadline, num_flow_updates=n,
                        **qkw,
                    )
            except QuotaExceeded as e:
                qos_note(pr, "quota_refused")
                stop.wait(min(e.retry_after_ms, 200.0) / 1e3)
                continue
            except Overloaded as e:
                with lock:
                    per_class[cls]["shed"] += 1
                qos_note(pr, "shed")
                stop.wait(min(e.retry_after_ms, 200.0) / 1e3)
                continue
            except ServeError:
                with lock:
                    per_class[cls]["failed"] += 1
                qos_note(pr, "failed")
                continue
            lat = (time.monotonic() - t0) * 1e3
            record_ok(cls, lat, res)
            qos_note(pr, "ok", lat, deadline)

    def stream_client(seed):
        """A video feed: one session, consecutive frames, frame t pairs
        with frame t-1 on the server's feature cache (sticky to one
        replica through the router's consistent-hash ring)."""
        from types import SimpleNamespace

        s_rng = np.random.default_rng(seed)
        gap = make_gap_fn(args, args.duration)
        h, w = hw_for["stream"]
        deadline = deadlines["stream"]
        pr, ten = qos_assign[seed % len(qos_assign)]
        qkw = {} if pr is None else {"priority": pr, "tenant": ten}
        fc = sid = None
        if use_frontend:
            from raft_tpu.serve.frontend import FrontendClient

            fc = FrontendClient(frontend_box[0].address)
            sid = fc.open_stream()
            stream = None
        else:
            stream = server.open_stream()
        try:
            while not stop.is_set():
                g = gap(s_rng, time.monotonic() - t_start_box[0])
                if g > 0 and stop.wait(g):
                    return
                frame = s_rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
                t0 = time.monotonic()
                try:
                    if fc is not None:
                        res = SimpleNamespace(**fc.submit_frame(
                            sid, frame, deadline_ms=deadline, **qkw,
                        ))
                    else:
                        res = stream.submit(
                            frame, deadline_ms=deadline, **qkw
                        )
                except QuotaExceeded as e:
                    qos_note(pr, "quota_refused")
                    stop.wait(min(e.retry_after_ms, 200.0) / 1e3)
                    continue
                except Overloaded as e:
                    with lock:
                        per_class["stream"]["shed"] += 1
                    qos_note(pr, "shed")
                    stop.wait(min(e.retry_after_ms, 200.0) / 1e3)
                    continue
                except ServeError:
                    with lock:
                        per_class["stream"]["failed"] += 1
                    qos_note(pr, "failed")
                    continue
                if res.primed:
                    with lock:
                        per_class["stream"]["primed"] += 1
                else:
                    lat = (time.monotonic() - t0) * 1e3
                    record_ok("stream", lat, res)
                    qos_note(pr, "ok", lat, deadline)
        finally:
            if fc is not None:
                try:
                    fc.close_stream(sid)
                except Exception:
                    pass
            elif stream is not None:
                stream.close()

    with server:
        frontend_snapshot = None
        if use_frontend:
            # the HTTP front door arm (ISSUE 15): the whole load rides
            # FrontendClient connections, latency is measured at the
            # edge, and edge traces stitch across the tier
            from raft_tpu.serve.frontend import ServeFrontend

            frontend_box[0] = ServeFrontend(
                server, trace_sample_rate=args.trace_sample,
                max_inflight=max(64, 2 * args.clients),
            ).start()
        try:
            threads = []
            for i, cls in enumerate(assignments):
                if cls == "stream":
                    threads.append(threading.Thread(
                        target=stream_client, args=(i,), daemon=True,
                    ))
                else:
                    threads.append(threading.Thread(
                        target=client, args=(cls, i), daemon=True,
                    ))
            t_start = time.monotonic()
            t_start_box[0] = t_start
            for t in threads:
                t.start()
            # per-device occupancy is only meaningful under live load:
            # sample it mid-run (the final stats() below runs after
            # clients stop)
            time.sleep(args.duration / 2)
            live_stats = server.stats()
            time.sleep(args.duration / 2)
            stop.set()
            for t in threads:
                t.join(timeout=max(deadlines.values()) / 1e3 + 5.0)
            elapsed = time.monotonic() - t_start
            stats = server.stats()
            traces = (
                collect_traces(server, frontend=frontend_box[0])
                if args.trace_sample > 0 else []
            )
            # the cross-process-tax ledger (ISSUE 14), while workers live
            n_ok_live = sum(pc["ok"] for pc in per_class.values())
            transport_block = collect_transport(server, n_ok_live)
            if frontend_box[0] is not None:
                frontend_snapshot = frontend_box[0].snapshot()
        finally:
            if frontend_box[0] is not None:
                frontend_box[0].close()

    # a router reports {"aggregate": summed engine counters, ...}; a bare
    # engine reports the counters at top level — read through one view
    agg = stats.get("aggregate", stats)
    live_agg = live_stats.get("aggregate", live_stats)
    is_router = "router" in stats
    engines = stats.get("engines", {})
    one_engine = next(iter(engines.values())) if engines else stats

    latencies = [
        x for pc in per_class.values() for x in pc["latencies"]
    ]
    n_ok = sum(pc["ok"] for pc in per_class.values())
    n_shed = sum(pc["shed"] for pc in per_class.values())
    n_failed = sum(pc["failed"] for pc in per_class.values())
    n_primed = sum(pc["primed"] for pc in per_class.values())
    total = n_ok + n_shed + n_failed + n_primed
    ladder = tuple(int(x) for x in args.ladder.split(","))
    occupancy = {
        str(it): (sum(1 for l in levels if ladder[l] == it) / max(1, n_ok))
        for it in ladder
    }
    hit_rate = agg.get("encoder_cache_hit_rate")

    def pctl(values, q):
        return round(float(np.percentile(values, q)), 3) if values else None

    classes = {}
    for cls, pc in per_class.items():
        n_cls = pc["ok"] + pc["shed"] + pc["failed"] + pc["primed"]
        if n_cls == 0:
            continue
        p99 = pctl(pc["latencies"], 99)
        classes[cls] = {
            "requests": n_cls,
            "completed": pc["ok"],
            "primed": pc["primed"],
            "failed": pc["failed"],
            "deadline_ms": deadlines[cls],
            "p50_ms": pctl(pc["latencies"], 50),
            "p99_ms": p99,
            "slo_p99_met": (p99 is not None and p99 <= deadlines[cls]),
            "slo_miss_rate": round(pc["slo_miss"] / max(1, pc["ok"]), 4),
            "shed_rate": round(pc["shed"] / max(1, n_cls), 4),
        }

    qos_report = None
    if qos_on:
        # the engine-side view rides along: a bare engine reports its
        # own qos block, a router the fleet-aggregated one
        qos_classes = {}
        for p, q in per_qos.items():
            n_cls = q["ok"] + q["shed"] + q["quota_refused"] + q["failed"]
            if n_cls == 0:
                continue
            p99 = pctl(q["latencies"], 99)
            qos_classes[p] = {
                "requests": n_cls,
                "completed": q["ok"],
                "failed": q["failed"],
                "p50_ms": pctl(q["latencies"], 50),
                "p99_ms": p99,
                "slo_p99_met": (p99 is not None and p99 <= args.deadline_ms),
                "slo_miss_rate": round(q["slo_miss"] / max(1, q["ok"]), 4),
                "shed_rate": round(q["shed"] / max(1, n_cls), 4),
                "quota_rate": round(q["quota_refused"] / max(1, n_cls), 4),
            }
        qos_report = {
            "tenants": int(getattr(args, "tenants", 0) or 0),
            "priority_mix": [round(f, 4) for f in priority_mix(args)],
            "tenant_rps": float(getattr(args, "tenant_rps", 0.0) or 0.0),
            "classes": qos_classes,
            "engine": stats.get("qos") or one_engine.get("qos") or {},
        }

    edge_slo = None
    if use_frontend:
        # the edge-vs-engine SLO view (ISSUE 15): per class, what the
        # user paid at the HTTP edge next to what the engine measured
        # for the SAME completed requests — the delta IS the wire tax
        edge_slo = {}
        for cls, pc in per_class.items():
            if not pc["latencies"]:
                continue
            e50, e99 = pctl(pc["latencies"], 50), pctl(pc["latencies"], 99)
            g50 = pctl(pc["engine_latencies"], 50)
            g99 = pctl(pc["engine_latencies"], 99)
            edge_slo[cls] = {
                "deadline_ms": deadlines[cls],
                "edge_p50_ms": e50,
                "edge_p99_ms": e99,
                "engine_p50_ms": g50,
                "engine_p99_ms": g99,
                "wire_tax_p50_ms": (
                    round(e50 - g50, 3)
                    if e50 is not None and g50 is not None else None
                ),
                "wire_tax_p99_ms": (
                    round(e99 - g99, 3)
                    if e99 is not None and g99 is not None else None
                ),
                "slo_miss_rate": round(
                    pc["slo_miss"] / max(1, pc["ok"]), 4
                ),
            }

    pool_stats = one_engine.get("pool", {})
    report = {
        "clients": args.clients,
        "streams": n_stream,
        "duration_s": round(elapsed, 2),
        "bucket": f"{bucket[0]}x{bucket[1]}",
        "ladder": list(ladder),
        "batch_ladder": one_engine.get("batch_ladder", []),
        "pipeline_depth": args.pipeline_depth,
        "requests": total,
        "completed": n_ok,
        "primed": n_primed,
        "throughput_rps": round(n_ok / elapsed, 3) if elapsed else 0.0,
        "p50_ms": pctl(latencies, 50),
        "p99_ms": pctl(latencies, 99),
        "shed_rate": round(n_shed / max(1, total), 4),
        "failed": n_failed,
        "degradation_occupancy": occupancy,
        "steps_down": one_engine.get("degradation", {}).get("steps_down", 0),
        "steps_up": one_engine.get("degradation", {}).get("steps_up", 0),
        "quarantined": agg.get("quarantined", 0),
        "batches": agg.get("batches", 0),
        "padding_waste": round(agg.get("padding_waste", 0.0), 4),
        "dispatched_rows": agg.get("dispatched_rows", 0),
        "padded_rows": agg.get("padded_rows", 0),
        "encoder_cache_hit_rate": (
            round(hit_rate, 4) if hit_rate is not None else None
        ),
        "inflight_peak": agg.get("inflight_peak", 0),
        "programs": one_engine.get("programs", {}),
        # realistic load model (ISSUE 9): arrivals + per-class SLOs
        "arrival": args.arrival,
        "arrival_rate": args.arrival_rate,
        "class_mix": list(class_mix(args)),
        "classes": classes,
        # multi-tenant QoS (ISSUE 17): per-priority-class client view +
        # the engine's enforcement counters; None when --tenants is 0
        "qos": qos_report,
        # iteration pool (ISSUE 6): occupancy, slot waste, admission wait
        "pool_capacity": args.pool_capacity,
        "iters_mix": iters_mix,
        "pool_ticks": agg.get("pool_ticks", 0),
        "pool_occupancy": round(
            1.0 - agg.get("idle_slot_iters", 0)
            / agg["dispatched_slot_iters"], 4,
        ) if agg.get("dispatched_slot_iters") else 0.0,
        "idle_slot_iters": agg.get("idle_slot_iters", 0),
        "dispatched_slot_iters": agg.get("dispatched_slot_iters", 0),
        "ttfd_p50_ms": (
            round(pool_stats["ttfd_p50_ms"], 3)
            if pool_stats.get("ttfd_p50_ms") is not None
            else None
        ),
        "early_exit_iters_saved": agg.get("early_exit_iters_saved", 0),
        "early_exits_deadline": agg.get("early_exits_deadline", 0),
        # convergence-adaptive compute (ISSUE 12): what requests paid
        # and why they stopped; the client-side view (iters_served /
        # exit reasons of COMPLETED requests) plus the engine counters
        "converge_thresh": args.converge_thresh,
        "converge_streak": args.converge_streak,
        "warm_start": args.warm_start,
        "iters_per_request_mean": (
            round(float(np.mean(iters_served)), 3) if iters_served else None
        ),
        "exit_reason_occupancy": {
            k: round(v / max(1, n_ok), 4) for k, v in exit_reasons.items()
        },
        "early_exits_converged": agg.get("early_exits_converged", 0),
        "early_exit_iters_saved_converged": agg.get(
            "early_exit_iters_saved_converged", 0
        ),
        "early_exit_iters_saved_deadline": agg.get(
            "early_exit_iters_saved_deadline", 0
        ),
        "stream_warm_starts": agg.get("stream_warm_starts", 0),
        # mesh-sharded dispatch (ISSUE 8): the serve `data` axis
        "mesh_devices": one_engine.get(
            "mesh_devices", args.mesh_devices
        ),
        "pool_capacity_total": pool_stats.get("capacity", 0),
        "per_device_occupancy": [
            round(x, 4)
            for x in (
                [] if is_router else
                live_agg.get("pool", {}).get("per_device_occupancy", [])
            )
        ],
        "slot_iters_per_s": (
            round(agg.get("dispatched_slot_iters", 0) / elapsed, 1)
            if elapsed else 0.0
        ),
        # cold-start accounting (ISSUE 7): how this engine became ready
        "preset": args.preset,
        "boot": (
            stats["boot"] if not is_router else {
                rid: st.get("boot", {}).get("source")
                for rid, st in engines.items()
            }
        ),
        # horizontal tier (ISSUE 9)
        "replicas": (
            getattr(args, "_replicas_override", None) or args.replicas
        ),
        # observability (ISSUE 10): measured per-phase latency split
        "trace_sample": args.trace_sample,
        "traces_collected": len(traces),
        "phase_breakdown": phase_breakdown(traces) if traces else {},
        # device-time ledger + convergence telemetry (ISSUE 11). Behind
        # a router these are the FIRST replica's view (per-replica device
        # time; the aggregate would average away a slow replica)
        "ledger_sample": args.ledger_sample,
        "ledger": one_engine.get("ledger", {}),
        "convergence": one_engine.get("convergence", {}),
        "alerts": (
            stats.get("alerts", {}) if is_router
            else one_engine.get("alerts", {})
        ),
    }
    report["backend"] = (
        getattr(args, "_backend_override", None) or args.backend
    )
    report["transport"] = transport_block
    report["frontend"] = frontend_snapshot
    report["edge_slo"] = edge_slo
    if is_router:
        report["router"] = stats["router"]
        report["per_replica_completed"] = [
            st.get("completed", 0) for st in engines.values()
        ]
        # process fleet (ISSUE 13): the structural pins — real worker
        # PIDs (None for thread replicas), one per live replica
        report["worker_pids"] = [
            snap.get("pid") for snap in stats["replicas"].values()
        ]
        scaler = getattr(server, "_autoscaler", None)
        if scaler is not None:
            report["autoscale"] = scaler.snapshot()
            report["final_replica_count"] = stats["replica_count"]
    return report


def emit(report: dict, args) -> None:
    config = (
        f"bucket={report['bucket']}, clients={report['clients']}, "
        f"max_batch={args.max_batch}, ladder={args.ladder}, "
        f"batch_ladder={report['batch_ladder']}, "
        f"pool_capacity={report['pool_capacity']}, "
        f"mesh_devices={report['mesh_devices']}, "
        f"iters_mix={report['iters_mix']}, "
        f"pipeline_depth={report['pipeline_depth']}, "
        f"streams={report['streams']}"
    )
    for metric, value, unit in [
        ("serve_throughput", report["throughput_rps"], "req/s"),
        ("serve_p50_ms", report["p50_ms"], "ms"),
        ("serve_p99_ms", report["p99_ms"], "ms"),
        ("serve_shed_rate", report["shed_rate"], "frac"),
        ("serve_padding_waste", report["padding_waste"], "frac"),
        ("serve_pool_occupancy", report["pool_occupancy"], "frac"),
        ("serve_iters_per_request", report["iters_per_request_mean"],
         "iters"),
        ("serve_ttfd_p50_ms", report["ttfd_p50_ms"], "ms"),
        ("serve_encoder_cache_hit_rate",
         report["encoder_cache_hit_rate"], "frac"),
    ]:
        if value is None:
            continue
        print(_line(
            {"metric": metric, "value": value, "unit": unit, "config": config}
        ), flush=True)
    if report.get("phase_breakdown"):
        print(_line({
            "metric": "serve_phase_breakdown",
            "trace_sample": report["trace_sample"],
            "traces": report["traces_collected"],
            "phases": report["phase_breakdown"],
            "config": config,
        }), flush=True)
    ledger = report.get("ledger") or {}
    if ledger.get("sampled_dispatches"):
        print(_line({
            "metric": "serve_device_time",
            "sample_every": ledger.get("sample_every"),
            "est_total_device_ms": ledger.get("est_total_device_ms"),
            "families": {
                name: {
                    k: fam.get(k)
                    for k in ("p50_ms", "p99_ms", "ewma_ms", "executions",
                              "est_total_ms", "share")
                }
                for name, fam in (ledger.get("by_family") or {}).items()
            },
            "config": config,
        }), flush=True)
    conv = report.get("convergence") or {}
    if conv.get("n"):
        print(_line({
            "metric": "serve_convergence",
            "n": conv["n"],
            "final_residual_p50": conv.get("final_residual_p50"),
            "final_residual_p99": conv.get("final_residual_p99"),
            # the residual-vs-iters table: mean RMS ||delta flow|| at
            # iteration k (1-based), None rows (never reached) dropped
            "resid_vs_iters": [
                [i + 1, v]
                for i, v in enumerate(conv.get("resid_by_iter") or [])
                if v is not None
            ],
            "config": config,
        }), flush=True)
    if report.get("autoscale"):
        asc = report["autoscale"]
        print(_line({
            "metric": "serve_autoscale",
            "min_replicas": asc["min_replicas"],
            "max_replicas": asc["max_replicas"],
            "scale_ups": asc["scale_ups"],
            "scale_downs": asc["scale_downs"],
            "evaluations": asc["evaluations"],
            "final_replica_count": report.get("final_replica_count"),
            "actions": asc["actions"],
            "config": config,
        }), flush=True)
    if report.get("edge_slo"):
        fe_snap = report.get("frontend") or {}
        print(_line({
            "metric": "serve_edge_slo",
            "classes": report["edge_slo"],
            "http_requests": fe_snap.get("http_requests"),
            "http_shed": fe_snap.get("http_shed"),
            "http_slo_miss": fe_snap.get("http_slo_miss"),
            "config": config,
        }), flush=True)
    if report.get("qos"):
        q = report["qos"]
        eng_classes = (q.get("engine") or {}).get("classes") or {}
        print(_line({
            "metric": "serve_qos",
            "tenants": q["tenants"],
            "priority_mix": q["priority_mix"],
            "tenant_rps": q["tenant_rps"],
            "classes": q["classes"],
            "preempted": {
                cls: cs.get("preempted", 0)
                for cls, cs in eng_classes.items()
            },
            "config": config,
        }), flush=True)
    if report["classes"]:
        print(_line({
            "metric": "serve_slo_report",
            "arrival": report["arrival"],
            "arrival_rate": report["arrival_rate"],
            "replicas": report["replicas"],
            "classes": report["classes"],
            "config": config,
        }), flush=True)
    print(_line({"metric": "serve_report", **report}), flush=True)


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="raft_small",
                    choices=["raft_small", "raft_large"])
    ap.add_argument("--tiny", action="store_true",
                    help="CPU-sized random-init model (smoke/chaos runs)")
    ap.add_argument("--random-init", action="store_true",
                    help="skip the pretrained-weight fetch")
    ap.add_argument("--bucket", default=None,
                    help="HxW padded bucket (default: 440x1024, tiny: 48x64)")
    ap.add_argument("--ladder", default=None,
                    help="degradation ladder (default: 32,20,12, tiny: 2,1)")
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--duration", type=float, default=20.0, help="seconds")
    ap.add_argument("--deadline-ms", type=float, default=2000.0)
    ap.add_argument("--slo-ms", type=float, default=None)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--batch-ladder", default=None,
                    help="comma list of padded batch rungs, e.g. 1,2,4,8 "
                         "(default: powers of two up to max-batch; "
                         "'1,<max>' approximates the pre-ladder "
                         "pad-to-max engine for A/B runs)")
    ap.add_argument("--pool-capacity", type=int, default=8,
                    help="resident iteration-pool slots per bucket "
                         "(0 = whole-request batch-ladder engine for A/B); "
                         "per DEVICE when --mesh-devices > 1")
    ap.add_argument("--mesh-devices", type=int, default=1,
                    help="shard every dispatch over an N-way serve mesh "
                         "`data` axis (ISSUE 8); sizing knobs are "
                         "per-device. N > 1 runs a built-in 1-vs-N A/B "
                         "(same per-device config both sides) and emits "
                         "serve_mesh_* BENCH lines. On CPU, virtual "
                         "devices are provisioned automatically")
    ap.add_argument("--backend", default="thread",
                    choices=["thread", "process"],
                    help="replica backend (ISSUE 13): 'process' runs "
                         "every replica engine in its own spawned "
                         "worker process (socket control channel + "
                         "shared-memory tensor rings). With --replicas "
                         "N > 1 runs the built-in thread-vs-process "
                         "1-vs-N A/B and emits a serve_process_ab "
                         "BENCH line")
    ap.add_argument("--worker-ring-slots", type=int, default=32,
                    help="shm tensor-ring slots per direction per "
                         "process worker (flow control: a full ring "
                         "sheds retryably with a live occupancy x "
                         "EWMA-hold retry hint)")
    ap.add_argument("--transport", default="binary",
                    choices=["binary", "legacy", "ab", "tcp"],
                    help="process-worker control-channel wire (ISSUE "
                         "14): 'binary' = struct-packed codec + RPC "
                         "coalescing (default), 'legacy' = the PR 13 "
                         "JSON-per-message wire, 'ab' = run BOTH arms "
                         "at equal config and emit a serve_transport "
                         "BENCH line (throughput ratio, copies/req, "
                         "control-bytes/req, span p50/p99, bitwise "
                         "flow parity). 'tcp' (ISSUE 16) A/Bs the "
                         "unix-socket+shm fleet against the SAME fleet "
                         "served by remote workers over loopback TCP "
                         "(framed tensor bodies, supervised links) and "
                         "emits a serve_tcp_ab BENCH line (rps ratio, "
                         "control-bytes/req per arm, reconnects pinned "
                         "0 on a clean run)")
    ap.add_argument("--frontend", action="store_true",
                    help="drive the whole load through the HTTP front "
                         "door (ISSUE 15): every client is a "
                         "FrontendClient, latencies are measured at the "
                         "EDGE, and a serve_edge_slo BENCH line reports "
                         "per-class edge p50/p99 next to the engine-side "
                         "numbers with the wire-tax delta; with "
                         "--trace-sample > 0 edge traces stitch across "
                         "frontend/router/transport/worker")
    ap.add_argument("--autoscale-max", type=int, default=0,
                    help="attach a signal-driven Autoscaler to the "
                         "router with this max replica count (0 = "
                         "off); scale-up/down events join the report "
                         "and a serve_autoscale BENCH line")
    ap.add_argument("--autoscale-min", type=int, default=1)
    ap.add_argument("--autoscale-interval", type=float, default=2.0,
                    help="autoscaler evaluation interval (s)")
    ap.add_argument("--autoscale-cooldown", type=float, default=15.0,
                    help="cooldown after any scale action (s)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a ServeRouter over N engine "
                         "replicas (ISSUE 9); with warmup on, one warmup "
                         "artifact is built and shared by every replica. "
                         "N > 1 runs a built-in 1-vs-N A/B at equal "
                         "per-replica config and emits a "
                         "serve_replica_ab BENCH line")
    ap.add_argument("--arrival", default="steady",
                    choices=["steady", "bursty", "diurnal"],
                    help="client arrival process (with --arrival-rate): "
                         "Poisson, geometric on-bursts, or one "
                         "sinusoidal 'day' across the run")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean per-client request rate (req/s) for the "
                         "arrival process; 0 = legacy closed loop")
    ap.add_argument("--class-mix", default=None,
                    help="pairwise,stream,bucket2 client fractions, e.g. "
                         "0.6,0.3,0.1 (default: all pairwise, or "
                         "--streams N legacy split)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="N synthetic tenants (round-robin over client "
                         "threads); > 0 turns QoS enforcement on "
                         "(qos_enabled=True, priority classes on every "
                         "submit) and emits a serve_qos BENCH line")
    ap.add_argument("--priority-mix", default=None,
                    help="interactive,standard,batch client fractions "
                         "for --tenants (default 0.34,0.33,0.33)")
    ap.add_argument("--tenant-rps", type=float, default=0.0,
                    help="per-tenant token-bucket admission quota "
                         "(requests/s, burst 2x; 0 = no rate quota)")
    ap.add_argument("--class-deadline-ms", default=None,
                    help="per-class SLO deadlines "
                         "pairwise,stream,bucket2 (default: "
                         "--deadline-ms for every class)")
    ap.add_argument("--bucket2", default=None,
                    help="HxW padded bucket of the 'bucket' traffic "
                         "class (default: 64x80, tiny; 544x1280 "
                         "otherwise)")
    ap.add_argument("--iters-mix", default=None,
                    help="comma list of per-request num_flow_updates each "
                         "client draws from uniformly (mixed-iteration "
                         "traffic; entries must be <= ladder[0])")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="dispatched-but-unfetched batch window "
                         "(1 = synchronous dispatch)")
    ap.add_argument("--streams", type=int, default=0,
                    help="run this many clients as video-stream sessions "
                         "(encode-once feature cache)")
    ap.add_argument("--stream-cache-size", type=int, default=16)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--preset", default=None,
                    choices=["quality", "throughput"],
                    help="deployment precision preset (ServeConfig.preset): "
                         "threads corr_dtype/compute_dtype through the zoo "
                         "into the engine")
    ap.add_argument("--warmup-artifact", default=None,
                    help="boot from this AOT warmup artifact "
                         "(scripts/build_warmup_artifact.py)")
    ap.add_argument("--compilation-cache-dir", default=None,
                    help="wire the JAX persistent compilation cache here "
                         "(the fallback boot tier)")
    ap.add_argument("--boot-report", action="store_true",
                    help="A/B boot-to-ready for cold / persistent-cache / "
                         "artifact boots instead of the load bench")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    help="observability trace sample rate in [0, 1] "
                         "(ServeConfig.trace_sample_rate); > 0 emits a "
                         "serve_phase_breakdown BENCH line with the "
                         "measured queue/admit/dispatch/fetch p50/p99 "
                         "from the collected traces")
    ap.add_argument("--converge-thresh", type=float, default=None,
                    help="residual-driven early exit threshold "
                         "(ServeConfig.pool_converge_thresh, 1/8-grid "
                         "px): retire a pooled request once its "
                         "flow-update residual stays below this for "
                         "--converge-streak iterations; pick it with "
                         "scripts/calibrate_convergence.py (default: "
                         "off)")
    ap.add_argument("--converge-streak", type=int, default=2,
                    help="consecutive sub-threshold residuals required "
                         "(ServeConfig.pool_converge_streak)")
    ap.add_argument("--warm-start", action="store_true",
                    help="seed each stream pair from the previous "
                         "pair's forward-warped flow "
                         "(ServeConfig.stream_warm_start)")
    ap.add_argument("--adaptive-ab", action="store_true",
                    help="run the built-in adaptive-vs-fixed A/B on a "
                         "deterministic smooth-motion synthetic stream "
                         "(trained fixture weights when present) and "
                         "emit a serve_adaptive_ab BENCH line instead "
                         "of the load bench")
    ap.add_argument("--ab-iters", type=int, default=32,
                    help="fixed-arm iteration target for --adaptive-ab "
                         "(default 32, the published protocol)")
    ap.add_argument("--ab-frames", type=int, default=12,
                    help="minimum timed stream pairs per arm for "
                         "--adaptive-ab (rounded up to whole laps over "
                         "the fixture scenes)")
    ap.add_argument("--ab-model", default="auto",
                    choices=["auto", "tiny", "fixture"],
                    help="--adaptive-ab model: trained fixture weights "
                         "(contractive refinement — the measurement "
                         "that matters), tiny random net (machinery "
                         "smoke), or auto (fixture when present)")
    ap.add_argument("--edge", default=None,
                    choices=["thread", "async", "ab"],
                    help="run the front-door edge scenario (ISSUE 19) "
                         "instead of the load bench: 'thread' / 'async' "
                         "measures one arm's edge latency and wire tax "
                         "through a ServeFrontend; 'ab' runs BOTH arms "
                         "at equal closed-loop load. With any cache "
                         "knob on, a second phase drives repeating "
                         "traffic through the redundancy layer. Emits "
                         "one serve_edge_cache BENCH line")
    ap.add_argument("--edge-cache", type=int, default=0,
                    help="content-addressed flow-cache entries for the "
                         "--edge scenario's cache phase "
                         "(ServeFrontend flow_cache_entries; 0 = off)")
    ap.add_argument("--edge-coalesce", action="store_true",
                    help="coalesce concurrent identical in-flight "
                         "requests in the --edge scenario "
                         "(ServeFrontend coalesce)")
    ap.add_argument("--edge-near-dup", type=float, default=None,
                    help="near-duplicate signature distance threshold "
                         "(mean abs pixel units) for the --edge "
                         "scenario's warm-start seeding; requires "
                         "--edge-cache > 0")
    ap.add_argument("--edge-unique-pairs", type=int, default=8,
                    help="distinct request pairs the --edge cache phase "
                         "cycles over (smaller = more redundancy)")
    ap.add_argument("--edge-handler-pool", type=int, default=8,
                    help="async-edge handler pool size for the --edge "
                         "scenario (ServeFrontend handler_pool)")
    ap.add_argument("--edge-rounds", type=int, default=3,
                    help="interleaved measurement rounds per arm for "
                         "the --edge A/B (best-of per stat — the "
                         "mirror-tax idiom for noisy CPU hosts)")
    ap.add_argument("--edge-fresh-conns", action="store_true",
                    help="open a fresh connection per request in the "
                         "--edge A/B instead of keep-alive (the "
                         "no-LB-pooling edge pattern: the threading "
                         "arm pays a thread spawn per connection, the "
                         "event loop accepts into a warm pool)")
    ap.add_argument("--tiled", action="store_true",
                    help="run the off-bucket tiled-serving scenario "
                         "(ISSUE 20) instead of the load bench: closed-"
                         "loop clients submit shapes no bucket admits "
                         "through the unknown_shape='tiled' arm and one "
                         "serve_tiled BENCH line reports throughput, "
                         "tiles and put_many acquisitions per request, "
                         "the planner's waste fraction, the host blend "
                         "cost, and the p99 seam discontinuity")
    ap.add_argument("--tiled-shapes", default=None,
                    help="comma list of HxW request shapes for --tiled "
                         "(default: one ~2x-bucket multi-tile canvas + "
                         "one single-padded-tile shape, both off the "
                         "%%8 grid)")
    ap.add_argument("--rollout", action="store_true",
                    help="run the guarded-rollout scenario (ISSUE 18) "
                         "instead of the load bench: mirror-tax "
                         "interleaved A/B, shadow->canary->promote "
                         "ladder, and a bad-candidate auto-rollback "
                         "arm, emitted as one serve_rollout BENCH line")
    ap.add_argument("--ledger-sample", type=int, default=0,
                    help="device-time ledger cadence K "
                         "(ServeConfig.ledger_sample_every): every Kth "
                         "execution per program family is a timed "
                         "blocked dispatch; > 0 emits a "
                         "serve_device_time BENCH line (and "
                         "serve_convergence in pool mode) — the inputs "
                         "scripts/perf_ledger.py gates on")
    args = ap.parse_args(argv)
    if args.bucket is None:
        args.bucket = "48x64" if args.tiny else "440x1024"
    if args.bucket2 is None:
        args.bucket2 = "64x80" if args.tiny else "544x1280"
    if args.ladder is None:
        args.ladder = "2,1" if args.tiny else "32,20,12"
    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.tiny and args.deadline_ms == 2000.0:
        args.deadline_ms = 30000.0  # CPU compiles ride inside the deadline
    if args.mesh_devices > 1:
        # must precede the first jax import in the process: the --tiny
        # CPU smoke provisions a virtual mesh via XLA_FLAGS (a TPU host
        # exposes its own chips; a non---tiny run without one refuses)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags and args.tiny:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh_devices}"
            ).strip()
    from raft_tpu.utils.runtime import (
        device_info, enable_persistent_cache, require_tpu,
    )

    global _DEVICE
    if args.tiny:
        # the CPU counts/correctness smoke: named for what it is
        _DEVICE = device_info()
    else:
        # a measurement: needs the chip, never falls back to host devices
        _DEVICE = require_tpu("serve_bench.py")
        enable_persistent_cache(args.compilation_cache_dir)
    if args.adaptive_ab:
        return adaptive_ab(args)
    if args.boot_report:
        return boot_report(args)
    if args.rollout:
        return rollout_bench(args)
    if args.tiled:
        return tiled_bench(args)
    if args.edge:
        return edge_ab(args)
    if args.backend == "process" and args.transport == "tcp":
        # 2-arm wire A/B (ISSUE 16): the same fleet at the same config,
        # once on the unix-socket + shm-ring transport (binary wire),
        # once as remote workers dialed over loopback TCP (framed tensor
        # bodies, ConnectionSupervisor links). The BENCH line carries the
        # rps ratio, control-bytes/request per arm, and the supervisor's
        # reconnect count — pinned 0 on a clean (fault-free) run.
        args._transport_override = "binary"
        unix = run_bench(args)
        emit(unix, args)
        args._transport_override = None
        args._backend_override = "remote"
        args._remote_handles = []
        try:
            report = run_bench(args)
            emit(report, args)
        finally:
            for h in args._remote_handles:
                h.terminate()
            args._backend_override = None
        tu = unix.get("transport") or {}
        tt = report.get("transport") or {}
        ab = {
            "replicas": args.replicas,
            "throughput_rps_unix": unix["throughput_rps"],
            "throughput_rps_tcp": report["throughput_rps"],
            "rps_ratio_tcp_vs_unix": round(
                report["throughput_rps"]
                / max(unix["throughput_rps"], 1e-9), 3,
            ),
            "p99_ms_unix": unix["p99_ms"],
            "p99_ms_tcp": report["p99_ms"],
            "control_bytes_per_req_unix": tu.get(
                "control_bytes_per_req"
            ),
            "control_bytes_per_req_tcp": tt.get(
                "control_bytes_per_req"
            ),
            "copies_per_req_unix": tu.get("copies_per_req"),
            "remote_links": tt.get("remote_links"),
            "reconnects": tt.get("reconnects"),
            "disconnects": tt.get("disconnects"),
            "keepalive_misses": tt.get("keepalive_misses"),
            "worker_pids_tcp": report.get("worker_pids", []),
            "config": (
                f"bucket={report['bucket']}, clients={args.clients}, "
                f"replicas={args.replicas}, max_batch={args.max_batch}, "
                f"ladder={args.ladder}, "
                f"pool_capacity={report['pool_capacity']}, "
                f"queue_capacity={args.queue_capacity}"
            ),
        }
        print(_line({"metric": "serve_tcp_ab", **ab}), flush=True)
        report["tcp_ab"] = ab
        return report
    if args.backend == "process" and args.transport == "ab":
        # 2-arm transport A/B (ISSUE 14): the same process fleet at the
        # same config, once on the legacy JSON-per-message wire, once on
        # the binary+coalesced one — throughput ratio, copies/request,
        # control-bytes/request, span quantiles, and a bitwise flow
        # parity pin ride one serve_transport BENCH line
        args._transport_override = "legacy"
        legacy = run_bench(args)
        emit(legacy, args)
        args._transport_override = "binary"
        report = run_bench(args)
        emit(report, args)
        args._transport_override = None
        parity = transport_parity(args)
        tb = report.get("transport") or {}
        tl = legacy.get("transport") or {}
        ab = {
            "replicas": args.replicas,
            "throughput_rps_legacy": legacy["throughput_rps"],
            "throughput_rps_binary": report["throughput_rps"],
            "speedup_binary_vs_legacy": round(
                report["throughput_rps"]
                / max(legacy["throughput_rps"], 1e-9), 3,
            ),
            "p99_ms_legacy": legacy["p99_ms"],
            "p99_ms_binary": report["p99_ms"],
            "copies_per_req_legacy": tl.get("copies_per_req"),
            "copies_per_req_binary": tb.get("copies_per_req"),
            "control_bytes_per_req_legacy": tl.get(
                "control_bytes_per_req"
            ),
            "control_bytes_per_req_binary": tb.get(
                "control_bytes_per_req"
            ),
            "coalesce_ratio_legacy": tl.get("coalesce_ratio"),
            "coalesce_ratio_binary": tb.get("coalesce_ratio"),
            "spans_binary": tb.get("spans", {}),
            "flow_bitwise_equal": parity,
            "config": (
                f"bucket={report['bucket']}, clients={args.clients}, "
                f"replicas={args.replicas}, max_batch={args.max_batch}, "
                f"ladder={args.ladder}, "
                f"pool_capacity={report['pool_capacity']}, "
                f"queue_capacity={args.queue_capacity}"
            ),
        }
        print(_line({"metric": "serve_transport", **ab}), flush=True)
        report["transport_ab"] = ab
        return report
    if args.backend == "process" and args.replicas > 1:
        # thread-vs-process 1-vs-N A/B at equal config (ISSUE 13): one
        # in-process engine, N thread replicas, N process replicas — the
        # measurement that turns the parity-bounded scale-out claim into
        # a wall-clock one wherever the host has cores
        args._replicas_override, args._backend_override = 1, "thread"
        base = run_bench(args)
        emit(base, args)
        args._replicas_override, args._backend_override = None, "thread"
        thread_rep = run_bench(args)
        emit(thread_rep, args)
        args._backend_override = None
        report = run_bench(args)
        emit(report, args)
        ab = {
            "replicas": args.replicas,
            "throughput_rps_1": base["throughput_rps"],
            "throughput_rps_thread": thread_rep["throughput_rps"],
            "throughput_rps_process": report["throughput_rps"],
            "speedup_process_vs_thread": round(
                report["throughput_rps"]
                / max(thread_rep["throughput_rps"], 1e-9), 3,
            ),
            "speedup_process_vs_1": round(
                report["throughput_rps"]
                / max(base["throughput_rps"], 1e-9), 3,
            ),
            "thread_p99_ms": thread_rep["p99_ms"],
            "process_p99_ms": report["p99_ms"],
            "shed_rate_thread": thread_rep["shed_rate"],
            "shed_rate_process": report["shed_rate"],
            "per_replica_completed_process": report.get(
                "per_replica_completed", []
            ),
            "worker_pids": report.get("worker_pids", []),
            "config": (
                f"bucket={report['bucket']}, clients={args.clients}, "
                f"replicas={args.replicas}, max_batch={args.max_batch}, "
                f"ladder={args.ladder}, "
                f"pool_capacity={report['pool_capacity']}, "
                f"queue_capacity={args.queue_capacity}"
            ),
        }
        print(_line({"metric": "serve_process_ab", **ab}), flush=True)
        report["process_ab"] = ab
        return report
    if args.replicas > 1:
        # built-in 1-vs-N A/B at the same per-replica config: the
        # horizontal-scaling claim is measured, not asserted
        args._replicas_override = 1
        base = run_bench(args)
        emit(base, args)
        args._replicas_override = None
        report = run_bench(args)
        emit(report, args)
        ab = {
            "replicas": args.replicas,
            "throughput_rps_1": base["throughput_rps"],
            "throughput_rps_n": report["throughput_rps"],
            "speedup": round(
                report["throughput_rps"]
                / max(base["throughput_rps"], 1e-9), 3,
            ),
            "p99_ms_1": base["p99_ms"],
            "p99_ms_n": report["p99_ms"],
            "shed_rate_1": base["shed_rate"],
            "shed_rate_n": report["shed_rate"],
            "per_replica_completed": report.get(
                "per_replica_completed", []
            ),
            "router": report.get("router", {}),
        }
        print(_line({"metric": "serve_replica_ab", **ab}), flush=True)
        report["replica_ab"] = ab
        return report
    if args.mesh_devices > 1:
        # built-in 1-vs-N A/B at the same per-device config: the scaling
        # claim is measured the way padding_waste already is, not asserted
        args._mesh_override = 1
        base = run_bench(args)
        emit(base, args)
        args._mesh_override = None
        report = run_bench(args)
        emit(report, args)
        print(_line({
            "metric": "serve_mesh_ab",
            "mesh_devices": args.mesh_devices,
            "throughput_rps_1dev": base["throughput_rps"],
            "throughput_rps_mesh": report["throughput_rps"],
            "speedup": round(
                report["throughput_rps"]
                / max(base["throughput_rps"], 1e-9), 3,
            ),
            "slot_iters_per_s_1dev": base["slot_iters_per_s"],
            "slot_iters_per_s_mesh": report["slot_iters_per_s"],
            "padding_waste_1dev": base["padding_waste"],
            "padding_waste_mesh": report["padding_waste"],
            "per_device_occupancy": report["per_device_occupancy"],
        }), flush=True)
        return report
    report = run_bench(args)
    emit(report, args)
    return report


if __name__ == "__main__":
    main()
