"""Serve driver, closed loop: ``clients`` requests always in flight through
``ServeEngine.submit_many`` (one driver thread; completions arrive on the
engine's done-callbacks), each completion replaced at once. Callers that
wait for their answer before sending the next — an offline batch job.

Building the engine from a cell file, the measured loop and the comparison
with the plain reference are plain functions, so that a later open-loop
driver can share them.

Cell file keys (``workloads/<cell>.json``): ``image_hw``, ``bucket``,
``iters``, ``serve`` (ServeConfig overrides), ``distinct_pairs``,
``ramp_s``, ``compare_pairs``, ``limits``, and for this driver ``clients``
(started one by one over the ramp).
"""

from __future__ import annotations

import queue
import time

from benchmarks import inputs, weights
from benchmarks.reference import compare as cmp, raft as ref


# -- set-up --------------------------------------------------------------------

def build_engine(ctx):
    import jax

    from raft_tpu.models import build_raft, zoo
    from raft_tpu.obs import profile
    from raft_tpu.serve import ServeConfig, ServeEngine

    cell, config = ctx.cell, ctx.config
    arch = config["program_arch"]
    check_arch(config, zoo.CONFIGS[arch])
    bucket = tuple(cell["bucket"])
    kw = dict(cell.get("serve", {}))
    kw.update(
        buckets=(bucket,), warmup=True, stream_cache_size=0,
        compilation_cache_dir=ctx.jax_cache_dir,
        trace_sample_rate=1.0 if ctx.trace else 0.0,
    )
    if "ladder" in kw:
        kw["ladder"] = tuple(kw["ladder"])
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


def check_arch(config, program_cfg) -> None:
    """The configuration file's sizes are the ones the program runs."""
    import dataclasses

    have = dataclasses.asdict(program_cfg)
    for key, want in config["arch"].items():
        if json_like(have[key]) != json_like(want):
            raise ValueError(f"configuration {config['name']}: {key} is {want} "
                             f"in its file, {have[key]} in the program")


def json_like(v):
    return [json_like(x) for x in v] if isinstance(v, (list, tuple)) else v


def setup(ctx):
    """Engine, weights, inputs and a ramped-up loop."""
    engine, host_vars = build_engine(ctx)
    cell = ctx.cell
    state = {"engine": engine, "host_vars": host_vars,
             "pairs": inputs.serve_pairs(ctx.seed, cell["distinct_pairs"],
                                         cell["image_hw"]),
             "order": inputs.seeded_rng(ctx.seed, 4)}
    state["loop"] = Loop(state, ctx)
    state["loop"].ramp(cell["ramp_s"])
    return state


# -- the measured loop ------------------------------------------------------------

class Loop:
    """Keeps ``clients`` requests in flight (a completion releases the
    next request) and records every completion."""

    def __init__(self, state, ctx):
        self.engine = state["engine"]
        self.pairs = state["pairs"]
        self.rng = state["order"]
        self.cell = ctx.cell
        self.done = queue.SimpleQueue()
        self.inflight = 0
        self.sent = 0
        self.records = []      # (t_sent, t_done, ok, pair index, detail)
        self.flows = {}        # pair index -> last in-window flow
        self.bag = []
        self.ramp_from = self.ramp_s = None
        self.memory = ctx.memory

    def _next_pair(self):
        if not self.bag:  # every pair once per round, in a seeded order
            self.bag = list(self.rng.permutation(len(self.pairs)))
        return int(self.bag.pop())

    def _submit(self, t_sent, n):
        items = []
        for _ in range(n):
            idx = self._next_pair()
            a, b = self.pairs[idx]
            tag = (t_sent, idx)
            items.append({
                "image1": a, "image2": b,
                "on_done": lambda req, tag=tag: self.done.put(
                    (time.monotonic(), tag, req)),
            })
        self.inflight += len(items)
        self.sent += len(items)
        self.engine.submit_many(items)

    def _collect(self, timeout, t_from, t_to):
        try:
            t_done, (t_sent, idx), req = self.done.get(timeout=max(timeout, 0.0))
        except queue.Empty:
            return False
        self.inflight -= 1
        res, err = req.result, req.error
        ok = (err is None and res is not None and res.flow is not None
              and not res.degraded
              and res.num_flow_updates == self.cell["iters"]
              and res.flow.shape == tuple(self.cell["image_hw"]) + (2,))
        detail = None if ok else repr(err) if err is not None else (
            f"degraded={res.degraded} iters={res.num_flow_updates}")
        self.records.append((t_sent, t_done, ok, idx, detail))
        if ok and t_from <= t_done <= t_to:
            self.flows[idx] = res.flow
        return True

    def run(self, t_end, measure_from=None):
        """Drive until ``t_end`` (monotonic). Completions inside
        ``[measure_from, t_end]`` keep their flow for the comparison."""
        t_from = t_end if measure_from is None else measure_from
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            if self.memory is not None:
                self.memory.sample(force=False)
            want = self._clients_started(now) - self.inflight
            if want > 0:
                self._submit(now, want)
            self._collect(min(0.05, t_end - now), t_from, t_end)

    def _clients_started(self, now):
        """Clients start one by one over the ramp, so their requests stay
        spread over the pool's ticks instead of finishing in lockstep
        cohorts (which would count completions in steps of a whole pool)."""
        n = self.cell["clients"]
        if self.ramp_from is None or now >= self.ramp_from + self.ramp_s:
            return n
        return min(n, 1 + int((now - self.ramp_from) / self.ramp_s * n))

    def ramp(self, seconds):
        """Fill the pool before the window; counted as set-up."""
        self.ramp_from, self.ramp_s = time.monotonic(), seconds
        self.run(self.ramp_from + seconds)

    def drain(self, t_from, t_to, limit_s):
        """Wait for what is still in flight (a late answer is late, not
        wrong); what never comes stays counted as sent."""
        t_stop = time.monotonic() + limit_s
        while self.inflight > 0 and time.monotonic() < t_stop:
            self._collect(0.25, t_from, t_to)


def engine_counters(engine):
    s = engine.stats()
    keys = ("pool_ticks", "dispatched_slot_iters", "idle_slot_iters",
            "completed", "shed", "expired", "pool_admitted")
    return {k: s.get(k, 0) for k in keys}


def window(ctx, state, seconds):
    """Drive the loop for ``seconds``, then wait for what is in flight (a
    late answer is late, not wrong). The rate is every pair completed in
    the window over the whole window."""
    loop, engine = state["loop"], state["engine"]
    c0 = engine_counters(engine)
    n0 = len(loop.records)
    with ctx.window_region():
        t0 = time.monotonic()
        loop.run(t0 + seconds, measure_from=t0)
        t1 = time.monotonic()
    c1 = engine_counters(engine)
    spans = engine.tracer.snapshot() if ctx.trace else []
    loop.drain(t0, t1, 60.0)
    recs = loop.records[n0:]
    for r in recs:
        if not r[2]:
            ctx.log(phase="request_failed", detail=r[4])
    done = [r[1] for r in recs if r[2] and t0 <= r[1] <= t1]
    rate = len(done) / (t1 - t0)
    sixths = [0] * 6  # completions by sixth of the window: is a run steady in itself?
    for t in done:
        sixths[min(5, int((t - t0) / (t1 - t0) * 6))] += 1
    return {
        "window_s": t1 - t0, "t0": t0, "t1": t1,
        "counters": {k: c1[k] - c0[k] for k in c1},
        "spans": [s for s in spans if s.get("t_start", 0) >= t0],
        "metrics": {"serve_pairs_per_s": rate},
        "attempted": len(recs) + loop.inflight,
        "failed": sum(1 for r in recs if not r[2]) + loop.inflight,
        "rates": {"serve_pairs_per_s": rate},
        "notes": {"completed": len(done), "elapsed_s": t1 - t0,
                  "completed_by_sixth": sixths, "boot": engine.stats()["boot"]},
    }


def release(ctx, state):
    state["loop"].drain(0.0, 0.0, 5.0)
    state["engine"].stop()
    state["served"] = dict(state["loop"].flows)
    for k in ("engine", "loop"):
        del state[k]
    import gc

    gc.collect()


# -- the comparison ---------------------------------------------------------------

def compare(ctx, state, window_result):
    """A seeded sample of the pairs the window served, each run once
    through the plain reference at the bucket's size."""
    cell, config = ctx.cell, ctx.config
    served = state["served"]
    out = {"failed": (float(window_result["failed"]), 0.0)}
    rng = inputs.seeded_rng(ctx.seed, 5)
    have = sorted(served)
    n = min(cell["compare_pairs"], len(have))
    if n == 0:
        out["pairs_compared"] = (float("nan"), 0.0)  # nothing served: not correct
        return out
    sample = [have[i] for i in rng.choice(len(have), size=n, replace=False)]
    stats = cmp.serve_stats(
        config["arch"], state["host_vars"],
        [state["pairs"][i] for i in sample], [served[i] for i in sample],
        bucket=cell["bucket"], iters=cell["iters"],
        precision=config["precision"]["serve"]["reference"],
    )
    ctx.log(phase="compare", pairs=sample, **{k: v for k, v in stats.items()})
    for name, limit in cell["limits"].items():
        out[name] = (stats[name], limit)
    return out
