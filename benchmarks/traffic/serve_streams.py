"""Serve driver, closed loop over video: ``streams`` clients, each a
``StreamSession`` (``ServeEngine.open_stream``) with one frame in flight —
the next frame is submitted when the last one's answer arrives, as a
video pipeline that needs flow between every pair of consecutive frames
walks a clip. A session's first frame primes it (no flow); every later one
is a pair. When a clip ends the client closes its session and opens a new
one on another clip. Each client's *first* clip starts at a seeded offset,
as if the window opened mid-stream, so session starts are spread over the
window; clients start one by one over the ramp.

A client is a thread here because ``submit_frame`` blocks until its
answer, which is the closed loop itself; the threads wait on the engine
and do nothing else.

``correct`` walks chains, a link at a time: a seeded sample of sessions,
first those that primed inside the window and served ``compare_pairs``
pairs in it (pair 1 cold, the later ones warm-started from the program's
own chain), and for each its first pairs served in the window. Every pair
is held against one link of upstream's loop in plain ``jax.numpy``
(``reference/raft_video.py::forward_step``) started where the program
started: from the reference's own ``forward_interpolate`` of the 1/8-grid
flow the *program* returned for the session's pair before
(``StreamSession.submit(return_flow8=True)``, upstream's ``flow_low``), or
cold for a session's first pair. A whole chain of the reference against a
whole chain of the program compares two trajectories of a map that
random weights make expansive (PERF.md §4: a pair's 0.3-0.5 px of rounding
reads 3 px one pair on and 8-9 px two pairs on); a link at a time holds
the program's interpolation just the same — a start that is off moves the
pair by as much.

Cell file keys (``workloads/<cell>.json``): ``image_hw``, ``bucket``,
``iters``, ``serve`` (ServeConfig overrides, ``stream_cache_size`` and
``stream_warm_start`` among them), ``streams``, ``distinct_clips``,
``clip_frames`` ([shortest, longest]), ``ramp_s``, ``compare_sessions``,
``compare_pairs``, ``limits``.
"""

from __future__ import annotations

import threading
import time


from benchmarks import inputs, inputs_video, weights
from benchmarks.reference import compare as cmp, raft as ref, raft_video
from benchmarks.traffic.serve_closed import check_arch

# -- set-up --------------------------------------------------------------------

def build_engine(ctx):
    import jax

    from raft_tpu.models import build_raft, zoo
    from raft_tpu.obs import profile
    from raft_tpu.serve import ServeConfig, ServeEngine

    cell, config = ctx.cell, ctx.config
    arch = config["program_arch"]
    check_arch(config, zoo.CONFIGS[arch])
    kw = dict(cell["serve"])
    if kw["stream_cache_size"] < cell["streams"]:
        raise ValueError("stream_cache_size must cover the live streams")
    kw.update(
        buckets=(tuple(cell["bucket"]),), warmup=True,
        compilation_cache_dir=ctx.jax_cache_dir,
        trace_sample_rate=1.0 if ctx.trace else 0.0,
        ladder=tuple(kw["ladder"]),
    )
    cfg = ServeConfig.preset(config["precision"]["serve"]["preset"], **kw)
    if cfg.ladder[0] != cell["iters"]:
        raise ValueError("cell iters must be the ladder's full-quality rung")
    if ctx.trace:
        profile.enable()
    model = build_raft(zoo.CONFIGS[arch].replace(**cfg.model_overrides()))
    variables = weights.make_variables(
        ref.param_shapes(config["arch"]), ctx.seed,
        config["assumed"]["flow_head_scale"])
    host_vars = jax.device_get(variables)  # the reference's copy, off-chip
    engine = ServeEngine(model, variables, cfg).start()
    return engine, host_vars


def setup(ctx):
    """Engine, weights, clips and the clients, ramped up."""
    engine, host_vars = build_engine(ctx)
    cell = ctx.cell
    state = {"engine": engine, "host_vars": host_vars,
             "clips": inputs_video.clips(ctx.seed, cell["distinct_clips"],
                                         cell["image_hw"],
                                         tuple(cell["clip_frames"]))}
    state["loop"] = Loop(state, ctx)
    state["loop"].ramp(cell["ramp_s"])
    return state


# -- the clients ---------------------------------------------------------------

class Session:
    """One session's record: which clip it walks from which frame, when it
    primed, and its first pairs served in the window — each with the
    1/8-grid flow its start was interpolated from (for the comparison)."""

    __slots__ = ("clip", "start", "t_primed", "prev8", "pairs")

    def __init__(self, clip, start):
        self.clip, self.start = clip, start
        self.t_primed = None
        self.prev8 = None   # the last pair's flow8, as the program returned it
        self.pairs = []     # (frame index, t_done, flow, the flow8 before)


class Loop:
    """``streams`` client threads; every answer is a record."""

    def __init__(self, state, ctx):
        self.engine = state["engine"]
        self.clips = state["clips"]
        self.cell = ctx.cell
        self.memory = ctx.memory
        self.keep = ctx.cell["compare_pairs"]
        self.lock = threading.Lock()
        self.records = []      # (t_sent, t_done, ok, kind, detail)
        self.sessions = []
        self.inflight = 0
        self.stop = threading.Event()
        self.measure_from = None   # pairs are kept from here on
        self.threads = [
            threading.Thread(target=self._client, daemon=True,
                             args=(i, inputs.seeded_rng(ctx.seed, 100 + i)))
            for i in range(ctx.cell["streams"])
        ]

    def _client(self, i, rng):
        time.sleep(max(0.0, self.t_ramp0 + i * self.ramp_s / len(self.threads)
                            - time.monotonic()))
        first = True
        while not self.stop.is_set():
            clip = int(rng.integers(len(self.clips)))
            frames = self.clips[clip]
            # the first clip is joined mid-way, with at least a few pairs left
            start = int(rng.integers(0, len(frames) - 4)) if first else 0
            first = False
            sess = Session(clip, start)
            with self.lock:
                self.sessions.append(sess)
            with self.engine.open_stream() as stream:
                for k in range(start, len(frames)):
                    if self.stop.is_set():
                        return
                    self._frame(stream, sess, k, frames[k], primes=(k == start))

    def _frame(self, stream, sess, k, frame, primes):
        t_sent = time.monotonic()
        with self.lock:
            self.inflight += 1
        res = err = None
        try:
            res = stream.submit(frame, return_flow8=True)
        except Exception as e:  # a typed ServeError: the record says which
            err = e
        t_done = time.monotonic()
        kind = "prime" if primes else "pair"
        if err is not None:
            ok, detail = False, repr(err)
        elif primes:
            ok, detail = bool(res.primed), "a first frame that did not prime"
        else:
            ok = (res.flow is not None and not res.degraded
                  and res.num_flow_updates == self.cell["iters"]
                  and res.flow.shape == tuple(self.cell["image_hw"]) + (2,))
            detail = (f"primed={res.primed} degraded={res.degraded} "
                      f"iters={res.num_flow_updates}")
        with self.lock:
            self.inflight -= 1
            self.records.append(
                (t_sent, t_done, ok, kind, None if ok else detail))
            if not ok:
                return
            if primes:
                sess.t_primed = t_done
            else:
                kept = (self.measure_from is not None
                        and t_done >= self.measure_from
                        and len(sess.pairs) < self.keep)
                if kept:
                    sess.pairs.append((k, t_done, res.flow, sess.prev8))
                sess.prev8 = res.flow8

    def ramp(self, seconds):
        """Start the clients one by one; counted as set-up."""
        self.t_ramp0, self.ramp_s = time.monotonic(), seconds
        for t in self.threads:
            t.start()
        self.run(self.t_ramp0 + seconds)

    def run(self, t_end):
        while time.monotonic() < t_end:
            if self.memory is not None:
                self.memory.sample(force=False)
            time.sleep(0.05)

    def finish(self, limit_s):
        """Let every client take its answer and stop."""
        self.stop.set()
        t_stop = time.monotonic() + limit_s
        for t in self.threads:
            t.join(max(0.0, t_stop - time.monotonic()))


def engine_counters(engine):
    s = engine.stats()
    keys = ("pool_ticks", "dispatched_slot_iters", "idle_slot_iters",
            "completed", "shed", "expired", "pool_admitted",
            "encode_cache_hits", "encode_cache_misses", "stream_warm_starts",
            "stream_invalidations", "stream_evictions")
    out = {k: s.get(k, 0) for k in keys}
    out["stream_frames"] = out["encode_cache_hits"] + out["encode_cache_misses"]
    return out


def window(ctx, state, seconds):
    """Let the clients run for ``seconds``, then let each take the answer
    it is waiting for (a late answer is late, not wrong). The rate is every
    pair answered in the window over the whole window; a prime is not a
    pair."""
    loop, engine = state["loop"], state["engine"]
    c0 = engine_counters(engine)
    with loop.lock:
        n0 = len(loop.records)
    with ctx.window_region():
        t0 = time.monotonic()
        loop.measure_from = t0
        loop.run(t0 + seconds)
        t1 = time.monotonic()
    c1 = engine_counters(engine)
    stats = engine.stats()
    spans = engine.tracer.snapshot() if ctx.trace else []
    loop.finish(60.0)
    with loop.lock:
        recs = loop.records[n0:]
        left = loop.inflight
    for r in recs:
        if not r[2]:
            ctx.log(phase="request_failed", kind=r[3], detail=r[4])
    done = [r[1] for r in recs if r[2] and r[3] == "pair" and t0 <= r[1] <= t1]
    rate = len(done) / (t1 - t0)
    sixths = [0] * 6
    for t in done:
        sixths[min(5, int((t - t0) / (t1 - t0) * 6))] += 1
    return {
        "window_s": t1 - t0, "t0": t0, "t1": t1,
        "counters": {k: c1[k] - c0[k] for k in c1},
        "spans": [s for s in spans if s.get("t_start", 0) >= t0],
        "metrics": {"serve_pairs_per_s": rate},
        "attempted": len(recs) + left,
        "failed": sum(1 for r in recs if not r[2]) + left,
        "rates": {"serve_pairs_per_s": rate},
        "notes": {"completed": len(done), "elapsed_s": t1 - t0,
                  "completed_by_sixth": sixths,
                  "primes": sum(1 for r in recs
                                if r[2] and r[3] == "prime" and t0 <= r[1] <= t1),
                  "stream_sessions": stats.get("stream_sessions"),
                  "stream_cache_bytes": stats.get("stream_cache_bytes"),
                  "pool": stats["pool"]["buckets"], "boot": stats["boot"]},
    }


def release(ctx, state):
    loop = state.pop("loop")
    loop.finish(5.0)
    state.pop("engine").stop()
    state["sessions"] = loop.sessions
    import gc

    gc.collect()


# -- the comparison ---------------------------------------------------------------

def sample_sessions(sessions, t0, t1, n_sessions, n_pairs, rng):
    """Sessions that primed inside the window and served ``n_pairs`` pairs
    in it come first (their first pair is a cold start); where a window
    is too short for ``n_sessions`` of them (a traced one), sessions that
    served any pair in it make up the number. Each comes with its kept
    pairs that were answered inside the window."""
    inside = lambda s: [p for p in s.pairs if t0 <= p[1] <= t1]
    whole = [s for s in sessions if s.t_primed is not None
             and t0 <= s.t_primed <= t1 and len(inside(s)) >= n_pairs]
    rest = [s for s in sessions if s not in whole and inside(s)]
    out = []
    for group in (whole, rest):
        n = min(n_sessions - len(out), len(group))
        out += [group[i] for i in rng.choice(len(group), size=n, replace=False)]
    return [(s, inside(s)) for s in out]


def compare(ctx, state, window_result):
    cell, config = ctx.cell, ctx.config
    out = {"failed": (float(window_result["failed"]), 0.0)}
    sample = sample_sessions(
        state["sessions"], window_result["t0"], window_result["t1"],
        cell["compare_sessions"], cell["compare_pairs"],
        inputs.seeded_rng(ctx.seed, 5))
    if not sample:
        out["pairs_compared"] = (float("nan"), 0.0)  # nothing served: not correct
        return out
    rows = [
        link_stats(config["arch"], state["host_vars"], state["clips"][s.clip],
                   pairs, bucket=cell["bucket"], iters=cell["iters"],
                   warm_start=cell["serve"]["stream_warm_start"],
                   precision=config["precision"]["serve"]["reference"])
        for s, pairs in sample
    ]
    stats = worst(rows)
    ctx.log(phase="compare", by_pair=rows, **stats, sessions=[
        (s.clip, s.start, [p[0] for p in pairs]) for s, pairs in sample])
    for name, limit in cell["limits"].items():
        out[name] = (stats[name], limit)
    return out


def reference_link(arch, variables, frames, pair, *, bucket, iters, warm_start,
                   precision="fp32"):
    """The reference's answer to one served pair ``(k, t, flow, prev8)`` of
    a clip: frames ``k-1 -> k``, started from its own interpolation of
    the flow8 the program returned for the pair before (cold where there
    was none, or the cell has warm start off)."""
    k, _, _, prev8 = pair
    h, w = frames[k].shape[:2]
    want, _ = raft_video.forward_step(
        arch, variables, cmp.preprocess(frames[k - 1], bucket),
        cmp.preprocess(frames[k], bucket), iters=iters,
        prev_flow8=prev8 if warm_start else None, precision=precision)
    return want[:h, :w]


def link_stats(arch, variables, frames, pairs, **kw):
    """Per pair of one sampled session: how far the served flow lies from
    the reference's link."""
    return [cmp.flow_stats(p[2], reference_link(arch, variables, frames, p, **kw))
            for p in pairs]


def worst(rows):
    """Each statistic's worst reading over every pair of every sampled
    session; NaN where any served value is not finite."""
    flat = [r for chain in rows for r in chain]
    finite = all(r["finite"] for r in flat)
    return {k: max(r[k] for r in flat) if finite else float("nan")
            for k in flat[0] if k != "finite"}
