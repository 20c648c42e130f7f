"""The pool scheduler's loop on the trace's clock, and its
phase totals at full pace.

``ServeEngine._worker_pool`` runs every step of an iteration inside one
``_phase`` (``raft_tpu.obs.profile.PhaseTable.phase``): its duration and
count go to the always-on totals (``stats()["sched"]``), it is a profiler
region when profiling is on, and ``(name, t0, t1)`` on the loop's
``"sched"`` trace record when the loop is sampled. On the tiny CPU pool
engine:

  * a sampled loop's phases are disjoint, ordered children of its
    ``loop`` span; its meta says what the iteration did and names the
    requests;
  * on a clock that only the loop's own operations advance, the phases
    cover the whole of ``loop`` and each operation's time falls under
    the phase that names it; every blocking read of the device falls
    under one of the three wait phases (``WAIT_PHASES``), the rows guard
    under ``serve/sched/await_rows`` and never under ``stage``, on the
    pair and the stream admission paths;
  * the totals equal the sampled records' phase sums, and count with
    sampling and profiling off at two clock reads a phase;
  * the profiler regions never nest on the scheduler thread;
  * loop records are sampled on a counter of their own (the same
    requests are traced with and without them) and stay out of the
    flight recorder's last-N ring;
  * ``Tracer.dropped`` counts what the ring overwrote;
  * the parked per-layer metrics of the waits
    (``benchmarks/parked/sched_waits``) split the phases the way the
    engine's constant does.

How much of a real loop the phases cover is a wall-clock ratio, and the
chip's to give (PERF.md §6, PR 27: 98.4-99.3%); nothing here asserts one.
"""

import collections
import contextlib
import json
import os
import re
import threading
import time

import jax
import numpy as np
import pytest

from raft_tpu.obs import Tracer, profile
from raft_tpu.serve import ServeConfig, ServeEngine
from raft_tpu.serve import engine as engine_mod
from raft_tpu.serve.engine import WAIT_PHASES
from tests.test_serve_pool import _image, _tiny_model

pytestmark = pytest.mark.chaos

ADMIT = {"serve/sched/poll", "serve/sched/stage", "serve/sched/await_rows",
         "serve/pool_begin", "serve/sched/insert"}
RETIRE = {"serve/sched/retire", "serve/sched/gather", "serve/pool_final",
          "serve/sched/fetch", "serve/sched/complete"}
TICK = {"serve/pool_step", "serve/sched/drain", "serve/sched/upkeep"}
# what a stream admission and retirement add
STREAM = {"serve/encode", "serve/stream_swap", "serve/pool_begin_features"}
WAITS = frozenset(WAIT_PHASES)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


@contextlib.contextmanager
def _pool_engine(tiny_model, **kw):
    """A started tiny pool engine: one bucket, two slots."""
    model, variables = tiny_model
    base = dict(
        buckets=((48, 64),), ladder=(3, 2, 1), max_batch=2, pool_capacity=2,
        queue_capacity=16, default_deadline_ms=60000.0, high_watermark=1.0,
        low_watermark=0.25, warmup=False,
    )
    base.update(kw)
    with ServeEngine(model, variables, ServeConfig(**base)) as eng:
        yield eng


def _serve(eng, n, seed=0):
    """``n`` pairs, all queued at once (the callers then sleep on their
    events, so nothing contends with the scheduler thread)."""
    rng = np.random.default_rng(seed)
    reqs = eng.submit_many(
        [{"image1": _image(rng), "image2": _image(rng)} for _ in range(n)]
    )
    for r in reqs:
        assert r.wait(120.0) and r.error is None, r.error
    return reqs


def _sched(eng):
    return [r for r in eng.tracer.snapshot() if r["kind"] == "sched"]


def _quiesce(eng):
    """Let the loop that retired the last request close its record: it
    does at the top of the next iteration, one 50 ms idle poll later."""
    time.sleep(0.2)


# the loop's operations, and the phase each one's time belongs under:
# host work of the engine's own, and its dispatch seams (``_run_*``, the
# slot programs' ``_pool_insert`` / ``_pool_gather``)
OPS = {
    "_log_counters": "serve/sched/upkeep",
    "_pool_due": "serve/sched/retire",
    "_pool_complete": "serve/sched/complete",
    "_settle_primes": "serve/sched/complete",
    "_pool_insert_slots": "serve/sched/insert",
    "_pool_tick_drain": "serve/sched/drain",
    "_run_pool_begin": "serve/pool_begin",
    "_run_pool_begin_features": "serve/pool_begin_features",
    "_run_encode": "serve/encode",
    "_run_stream_swap": "serve/stream_swap",
    "_run_pool_step": "serve/pool_step",
    "_run_pool_final": "serve/pool_final",
    "_run_stream_store_flow": "serve/stream_store_flow",
    "_pool_insert": "serve/sched/insert",
    "_pool_gather": "serve/sched/gather",
}


class _Numpy:
    """The engine module's ``np``, except that ``asarray`` of a device
    array is a blocking read of the device: an operation of the clock's."""

    def __init__(self, clock):
        self._clock = clock

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self._clock.tick("np.asarray", WAITS)
        return np.asarray(a, *args, **kw)


class _WorkClock:
    """``time.monotonic`` for the scheduler thread of one engine: it
    stands still except that every operation of the loop (``OPS``, the
    queue poll, a staging fill, the alert pass) and every blocking read
    of the device (``jax.block_until_ready``, the engine's ``np.asarray``
    of a device array) takes 1 ms of it. Other threads keep the real
    clock. ``log`` holds ``(t, operation, phases it may lie under)``,
    ``t`` the middle of the operation's millisecond: one phase for an
    operation, any of ``WAIT_PHASES`` for a read."""

    def __init__(self, eng, monkeypatch):
        self.eng, self.real = eng, time.monotonic
        self.t0, self.ms, self.log = time.monotonic(), 0, []
        monkeypatch.setattr(time, "monotonic", self)
        for name, phase in OPS.items():
            self._wrap(eng, name, {phase})
        self._wrap(eng._queue, "next_batch", {"serve/sched/poll"})
        self._wrap(eng._staging, "fill", {"serve/sched/stage"})
        self._wrap(eng._alerts, "maybe_observe", {"serve/sched/upkeep"})
        block = jax.block_until_ready

        def blocking(x):
            self.tick("block_until_ready", WAITS)
            return block(x)

        monkeypatch.setattr(jax, "block_until_ready", blocking)
        monkeypatch.setattr(engine_mod, "np", _Numpy(self))

    def _mine(self):
        return threading.current_thread() is self.eng._thread

    def __call__(self):
        return self.t0 + self.ms / 1e3 if self._mine() else self.real()

    def tick(self, op, phases):
        if self._mine():
            self.log.append((self() + 0.0005, op, frozenset(phases)))
            self.ms += 1

    def _wrap(self, obj, name, phases):
        fn = getattr(obj, name)

        def timed(*a, **k):
            self.tick(name, phases)
            return fn(*a, **k)

        setattr(obj, name, timed)


def _work_run(tiny_model, stream):
    """One engine on the work clock, every loop sampled: six pairs on two
    slots, or one session of five frames (a prime, then four pairs).
    Returns ``(records, loops, log, stats()["sched"])``, read once the
    engine has stopped (its last loop counted): ``loops`` are every
    loop's phase lists, the idle ones' too (they keep no record)."""
    model, variables = tiny_model
    loops = []

    class _Kept(engine_mod._SchedLoop):
        def __init__(self, trace):
            super().__init__(trace)
            loops.append(self.phases)

    eng = ServeEngine(model, variables, ServeConfig(
        buckets=((48, 64),), ladder=(3, 2, 1), max_batch=2,
        pool_capacity=2, queue_capacity=16, warmup=False,
        default_deadline_ms=60000.0, trace_sample_rate=1.0,
        stream_cache_size=2 if stream else 0,
    ))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "_SchedLoop", _Kept)
        clock = _WorkClock(eng, mp)
        with eng:
            if stream:
                rng = np.random.default_rng(1)
                with eng.open_stream() as s:
                    for _ in range(5):
                        s.submit(_image(rng))
            else:
                _serve(eng, 6)
            _quiesce(eng)
        recs = _sched(eng)
        totals = eng.stats()["sched"]
    return recs, loops, clock.log, totals


@pytest.fixture(scope="module")
def pair_run(tiny_model):
    return _work_run(tiny_model, stream=False)


@pytest.fixture(scope="module")
def stream_run(tiny_model):
    return _work_run(tiny_model, stream=True)


def _under(loops, log):
    """``(t, op, allowed, [phases whose span holds t])`` for every logged
    operation, in order."""
    spans = [(t0, t1, name) for phases in loops for name, t0, t1, *_ in phases]
    return [
        (t, op, allowed, [n for t0, t1, n in spans if t0 <= t < t1])
        for t, op, allowed in log
    ]


class TestLoopRecords:
    @staticmethod
    def _check_tiling(recs):
        """Every record's phases are ordered, disjoint children of its
        ``loop`` span; returns their cover of it, per record."""
        assert len(recs) >= 9  # 6 pairs x 3 iterations on 2 slots
        cover = []
        for rec in recs:
            loop, phases = rec["spans"][0], rec["spans"][1:]
            assert loop["name"] == "loop" and loop["t0_ms"] == 0.0
            assert rec["rid"] is None and rec["ok"] is True
            assert rec["dur_ms"] == pytest.approx(loop["dur_ms"])
            end, covered = 0.0, 0.0
            for s in phases:
                assert s["parent"] == "loop"
                assert s["name"].startswith("serve/")
                # each starts where or after the last one ended
                assert s["t0_ms"] >= end - 1e-6, (s, end)
                end = s["t0_ms"] + s["dur_ms"]
                covered += s["dur_ms"]
            assert end <= loop["dur_ms"] + 1e-6
            cover.append(covered / loop["dur_ms"])
        return cover

    def test_phases_tile_the_loop(self, tiny_model):
        with _pool_engine(tiny_model, trace_sample_rate=1.0) as eng:
            reqs = _serve(eng, 6)
            _quiesce(eng)
            snap = eng.tracer.snapshot()
            stats = eng.stats()
        recs = [r for r in snap if r["kind"] == "sched"]
        self._check_tiling(recs)
        names = {s["name"] for rec in recs for s in rec["spans"][1:]}
        # every group of the loop showed up under its own names
        assert ADMIT <= names and RETIRE <= names and TICK <= names
        assert names <= ADMIT | RETIRE | TICK
        # the meta says what each loop did and names the requests
        ticked = sum(r["ticked"] for r in recs)
        assert ticked == stats["pool_ticks"] >= 9
        rids = sorted(r.rid for r in reqs)
        assert sorted(x for r in recs for x in r["admitted_rids"]) == rids
        assert sorted(x for r in recs for x in r["retired_rids"]) == rids
        for rec in recs:
            assert rec["admitted"] == len(rec["admitted_rids"])
            assert rec["retired"] == len(rec["retired_rids"])
            assert 0 <= rec["occupied"] <= 2 and rec["pending"] >= 0
            assert rec["cpu_ms"] >= 0.0
        assert stats["obs"]["traces_dropped"] == 0
        # consecutive sampled loops share their boundary's clock reading
        for a, b in zip(recs, recs[1:]):
            gap_ms = (b["t_start"] - a["t_start"]) * 1e3 - a["dur_ms"]
            assert gap_ms >= -1e-6
        # a request's refine span lies inside the loops that advanced it
        by_rid = {r["rid"]: r for r in snap if r["kind"] == "pair"}
        for rid in rids:
            admit = next(r for r in recs if rid in r["admitted_rids"])
            retire = next(r for r in recs if rid in r["retired_rids"])
            refine = next(
                s for s in by_rid[rid]["spans"] if s["name"] == "refine"
            )
            t0 = by_rid[rid]["t_start"] + refine["t0_ms"] / 1e3
            t1 = t0 + refine["dur_ms"] / 1e3
            assert admit["t_start"] <= t0
            assert t1 <= retire["t_start"] + retire["dur_ms"] / 1e3

    def test_phases_cover_the_loops_work(self, pair_run):
        """The >= 95% cover, without a stopwatch: on a clock that only
        the loop's operations advance, a hole between two phases shows
        as cover under 1, and an operation under the wrong phase by
        name."""
        recs, loops, log, _ = pair_run
        cover = self._check_tiling(recs)
        assert min(cover) >= 0.95, cover
        assert cover == pytest.approx([1.0] * len(recs))
        # every operation's millisecond lies in a phase of its own name
        seen = set()
        for t, op, allowed, got in _under(loops, log):
            assert len(got) == 1 and got[0] in allowed, (op, allowed, got)
            seen.add(got[0])
        assert seen == ADMIT | RETIRE | TICK

    @pytest.mark.parametrize("path", ["pair", "stream"])
    def test_await_rows_is_a_wait_of_its_own(self, path, pair_run, stream_run):
        """The rows guard (``_await_rows``: ``block_until_ready`` on the
        last admission's rows) is timed under ``serve/sched/await_rows``,
        at every admission but the first, and no millisecond of it under
        ``serve/sched/stage``; ``stage`` holds the staging fills only."""
        recs, loops, log, _ = pair_run if path == "pair" else stream_run
        under = _under(loops, log)
        guards = [got for _, op, _, got in under if op == "block_until_ready"]
        assert len(guards) >= 2
        assert all(got == ["serve/sched/await_rows"] for got in guards)
        in_stage = {op for _, op, _, got in under if got == ["serve/sched/stage"]}
        assert in_stage == {"fill"}
        # an admission enters the guard's phase once, right after stage
        for rec in recs:
            names = [s["name"] for s in rec["spans"][1:]]
            for i, n in enumerate(names):
                if n == "serve/sched/stage":
                    assert names[i + 1] == "serve/sched/await_rows", names

    @pytest.mark.parametrize("path", ["pair", "stream"])
    def test_every_blocking_read_is_in_a_wait_phase(
        self, path, pair_run, stream_run
    ):
        """Every read that blocks on the device lies under one of
        ``WAIT_PHASES``, and those phases hold nothing else of the
        loop's but the drain's own bookkeeping."""
        recs, loops, log, _ = pair_run if path == "pair" else stream_run
        under = _under(loops, log)
        reads = [(op, got) for _, op, allowed, got in under if allowed == WAITS]
        assert {got[0] for _, got in reads} == WAITS
        assert all(len(got) == 1 for _, got in reads)
        others = {op for _, op, allowed, got in under
                  if allowed != WAITS and got[0] in WAITS}
        assert others <= {"_pool_tick_drain"}
        names = {name for phases in loops for name, *_ in phases}
        if path == "stream":
            want = (ADMIT - {"serve/pool_begin"}) | STREAM | RETIRE | TICK
        else:
            want = ADMIT | RETIRE | TICK
        assert names == want

    def test_a_primes_flag_is_read_in_fetch(self, stream_run):
        """A stream prime's answer waits for its frame's finite flag:
        ``_stream_settle`` reads it under ``serve/sched/fetch`` and
        answers under ``complete``, never inside ``upkeep``."""
        _, loops, log, _ = stream_run
        under = _under(loops, log)
        settles = [i for i, (_, op, _, _) in enumerate(under)
                   if op == "_settle_primes"]
        assert settles  # the session's first frame is a prime
        for i in settles:
            assert under[i][3] == ["serve/sched/complete"]
            op, got = under[i - 1][1], under[i - 1][3]
            assert (op, got) == ("np.asarray", ["serve/sched/fetch"])
        upkeep = {op for _, op, _, got in under if got == ["serve/sched/upkeep"]}
        assert upkeep == {"_log_counters", "maybe_observe"}

    def test_totals_equal_the_sampled_records(self, pair_run):
        """At ``trace_sample_rate`` 1.0 every loop is sampled: the totals
        are the sum of every loop's phases, to the microsecond and the
        entry, and each sealed record carries its loop's phases (a loop
        that did nothing, an idle poll, keeps no record but is counted);
        the loops' wall is every operation's millisecond."""
        recs, loops, log, totals = pair_run
        ms, n = collections.Counter(), collections.Counter()
        for phases in loops:
            for name, t0, t1, *args in phases:
                key = name + (f"@{args[0]['bucket']}" if args else "")
                ms[key] += (t1 - t0) * 1e3
                n[key] += 1
        got = totals["phases"]
        assert set(got) == set(ms)
        for key in got:
            assert got[key]["ms"] == pytest.approx(ms[key], abs=1e-6), key
            assert got[key]["n"] == n[key], key
        assert totals["loops"] == len(loops) > len(recs) >= 9
        assert totals["loop_ms"] == pytest.approx(float(len(log)), abs=1e-6)
        assert sum(p["ms"] for p in got.values()) == pytest.approx(
            totals["loop_ms"], abs=1e-6
        )
        assert totals["cpu_ms"] > 0.0
        # the records are the loops that ticked, admitted or retired, in
        # order, each with its loop's phases
        seqs = iter([(p[0], round((p[2] - p[1]) * 1e3, 3)) for p in phases]
                    for phases in loops)
        for rec in recs:
            want = [(s["name"], round(s["dur_ms"], 3)) for s in rec["spans"][1:]]
            assert want in seqs

    def test_pool_phases_are_totalled_per_bucket(self, pair_run, stream_run):
        """A phase that carries a bucket (the tick, a pair admission) is
        totalled under ``<phase>@<bucket>``, and only there."""
        for *_, totals in (pair_run, stream_run):
            keys = set(totals["phases"])
            assert "serve/pool_step@48x64" in keys
            assert "serve/pool_step" not in keys
            assert "serve/pool_begin" not in keys
        assert "serve/pool_begin@48x64" in pair_run[-1]["phases"]
        # the stream path admits from features, a phase with no bucket
        assert "serve/pool_begin_features" in stream_run[-1]["phases"]

    def test_a_retirement_is_read_after_the_loops_tick(self, tiny_model):
        """A loop that retires and ticks opens ``serve/pool_step`` before
        ``serve/sched/fetch`` (PR 37): the retirement is dispatched before
        admission and read after the tick, ``complete`` after the read.
        A loop that retires and has no resident left to tick reads at
        once. The phase set is the one the tests above pin."""
        with _pool_engine(tiny_model, trace_sample_rate=1.0) as eng:
            _serve(eng, 6)
            _quiesce(eng)
            recs = _sched(eng)
            stats = eng.stats()
        retiring = [r for r in recs if r["retired"]]
        assert retiring
        for rec in retiring:
            names = [s["name"] for s in rec["spans"][1:]]
            assert set(names) <= ADMIT | RETIRE | TICK
            # dispatched first, read last
            assert names.index("serve/pool_final") < names.index(
                "serve/sched/fetch"
            ) < names.index("serve/sched/complete")
            if rec["ticked"]:
                assert names.index("serve/pool_step") < names.index(
                    "serve/sched/fetch"
                )
                assert rec["deferred"] >= 1
            else:
                assert rec["deferred"] == 0
        assert any(r["ticked"] for r in retiring)
        assert sum(r["deferred"] for r in recs) == stats["retire_deferred"]
        assert 0 <= stats["retire_ready_at_settle"] <= stats["retire_deferred"]

    def test_loop_records_stay_out_of_the_flight_recorder(self, tiny_model):
        with _pool_engine(tiny_model, trace_sample_rate=1.0) as eng:
            _serve(eng, 2)
            _quiesce(eng)
            assert _sched(eng)
            kept = eng.recorder.traces()
            assert kept and all(t["kind"] != "sched" for t in kept)
            obs = eng.stats()["obs"]
            # started / finished count requests, as before
            assert obs["traces_started"] == obs["traces_finished"] == 2

    def test_idle_polls_leave_no_record(self, tiny_model):
        with _pool_engine(tiny_model, trace_sample_rate=1.0) as eng:
            time.sleep(0.3)  # ~6 polls of an empty queue
            assert eng.tracer.snapshot() == []


class TestProfilerRegions:
    def test_regions_never_nest_on_the_scheduler_thread(
        self, tiny_model, monkeypatch
    ):
        events = []  # (thread, name, +1 | -1)

        class Region:
            def __init__(self, name, **args):  # args: the region's bucket
                self.name = name

            def __enter__(self):
                events.append((threading.get_ident(), self.name, +1))

            def __exit__(self, *exc):
                events.append((threading.get_ident(), self.name, -1))

        monkeypatch.setattr(profile, "annotate", Region)
        monkeypatch.setattr(profile, "_on", True)
        for rate in (0.0, 1.0):  # regions alone, and with loop records
            del events[:]
            with _pool_engine(tiny_model, trace_sample_rate=rate) as eng:
                _serve(eng, 4)
                worker = eng._thread.ident
            mine = [e for e in events if e[0] == worker]
            depth, seen = 0, set()
            for _, name, step in mine:
                depth += step
                assert 0 <= depth <= 1, (name, depth)
                seen.add(name)
            assert depth == 0
            assert ADMIT | RETIRE | TICK <= seen


class TestOffPath:
    def test_helper_reads_no_clock_when_both_are_off(
        self, tiny_model, monkeypatch
    ):
        """Sampling and profiling off, the helper reads no clock beyond
        the totals' own — which are always on: two ``time.monotonic``
        reads a phase, one a loop and one ``time.thread_time`` a loop —
        opens no region, keeps no record and allocates no phase (the
        one built on first use is returned every time)."""
        assert not profile.enabled()
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            ServeConfig(buckets=((48, 64),), pool_capacity=2, warmup=False),
        )
        reads = collections.Counter()
        real_mono, real_cpu = time.monotonic, time.thread_time

        def mono():
            reads["monotonic"] += 1
            return real_mono()

        def cpu():
            reads["thread_time"] += 1
            return real_cpu()

        monkeypatch.setattr(time, "monotonic", mono)
        monkeypatch.setattr(time, "thread_time", cpu)
        phases = {}
        for _ in range(100):  # N loops of the helper, nothing sampled
            assert eng._sched_turn() is None
            eng._sched_seal(None, eng._loop)
            for name, bucket in (("serve/sched/upkeep", None),
                                 ("serve/pool_step", "48x64")):
                region = eng._phase(name, bucket)
                assert phases.setdefault(name, region) is region
                with region:
                    pass
        assert eng._sched_turn(last=True) is None
        monkeypatch.undo()
        assert reads == {"monotonic": 101 + 100 * 2 * 2, "thread_time": 100}
        assert eng._loop is None and eng._phases.sink is None
        sched = eng.stats()["sched"]
        assert sched["loops"] == 100
        assert {k: v["n"] for k, v in sched["phases"].items()} == {
            "serve/sched/upkeep": 100, "serve/pool_step@48x64": 100,
        }
        # and a served request leaves no loop record behind
        with _pool_engine(tiny_model) as eng:
            _serve(eng, 2)
            assert eng.tracer.snapshot() == []
            assert eng._loop is None

    def test_phase_helper_with_a_sink(self):
        table = profile.PhaseTable()
        table.sink = sink = []
        with table.phase("a"):
            time.sleep(0.002)
        with table.phase("b", "48x64"):
            pass
        (a, a0, a1), (b, b0, b1, args) = sink
        assert (a, b, args) == ("a", "b", {"bucket": "48x64"})
        assert a1 - a0 >= 0.002 and a1 <= b0 <= b1
        table.sink = None
        with table.phase("a"):
            pass
        assert len(sink) == 2  # no sink: totals only
        got = table.report()["phases"]
        assert got["a"]["n"] == 2 and got["a"]["ms"] >= 2.0
        assert got["b@48x64"] == {"ms": pytest.approx((b1 - b0) * 1e3), "n": 1}
        assert table.phase("b", "48x64") is table.phase("b", "48x64")

    def test_totals_count_at_full_pace(self, tiny_model):
        """Sampling and profiling off, a served run's totals hold every
        loop: the loop's phases cover its wall time, every phase of the
        pair path shows up, and the loop count matches the ticks."""
        assert not profile.enabled()
        with _pool_engine(tiny_model) as eng:
            _serve(eng, 6)
            _quiesce(eng)
            assert eng.tracer.snapshot() == []
            sched = eng.stats()["sched"]
            ticks = eng.stats()["pool_ticks"]
        names = {k.split("@")[0] for k in sched["phases"]}
        assert names == ADMIT | RETIRE | TICK
        assert sched["phases"]["serve/pool_step@48x64"]["n"] == ticks >= 9
        assert sched["loops"] > ticks
        covered = sum(p["ms"] for p in sched["phases"].values())
        assert 0.0 < covered <= sched["loop_ms"]
        assert 0.0 < sched["cpu_ms"]


class TestParkedWaits:
    """The parked per-layer metrics of the scheduler's waits
    (``benchmarks/parked/sched_waits``) read the same three phases as
    the engine's ``WAIT_PHASES``; with the parked complement of
    ``sched_phases`` they split the device's idle time whole."""

    PARK = os.path.join(ROOT, "benchmarks", "parked")
    CELLS = ("raft_large.sintel_offline", "raft_small.sintel_offline",
             "raft_large_hd1080.davis_offline",
             "raft_large_video.sintel_streams",
             "raft_large_resmix.mixed_offline")

    def _spec(self, park, name):
        with open(os.path.join(self.PARK, park, "layer_metrics",
                               f"{name}.json")) as f:
            return json.load(f)

    def test_wait_phases_partition_the_docs_table(self):
        """Every phase ``docs/observability.md`` lists is under exactly
        one of the two idle shares' regions, under the waiting one iff
        it is one of ``WAIT_PHASES``, and the rows-guard share reads the
        guard alone."""
        with open(os.path.join(ROOT, "docs", "observability.md")) as f:
            doc = f.read()
        table = doc[doc.index("| group | phase |"):]
        table = table[:table.index("\n\n")]
        listed = set(re.findall(r"`(serve/[a-z_/]+)`", table))
        assert set(WAIT_PHASES) | ADMIT | RETIRE | TICK | STREAM <= listed
        waiting = re.compile(
            self._spec("sched_waits", "idle_waiting_share.sched")
            ["params"]["regions"])
        working = re.compile(
            self._spec("sched_waits", "idle_working_share.sched")
            ["params"]["regions"])
        nobody = self._spec("sched_phases", "idle_unattributed_share.offline")
        assert nobody["params"] == {"regions": "^serve/", "complement": True}
        for name in listed:
            w, k = bool(waiting.search(name)), bool(working.search(name))
            assert w != k, name
            assert w == (name in WAIT_PHASES), name
        rows = self._spec("sched_waits", "await_rows_share.sched")
        assert rows["reader"] == "phase_share"
        assert rows["params"] == {"phases": ["serve/sched/await_rows"]}

    def test_the_idle_shares_add_up(self):
        """On a hand-made plane: 11 ms of idle, under ``drain`` (2 ms),
        ``stage`` (3), ``await_rows`` (4) and no region (2), splits 6 ms
        waiting, 3 working and 2 to the complement, and the three shares
        add up to ``idle_share``."""
        from types import SimpleNamespace as NS

        from benchmarks.reduce.readers import idle_share, idle_under

        host = [("serve/sched/stage", 0.020, 0.003),
                ("serve/sched/await_rows", 0.023, 0.004),
                ("serve/sched/drain", 0.001, 0.002),
                ("serve/pool_step", 0.031, 0.002)]
        obs = {"trace": NS(window_s=0.040, busy_s=0.029, host=host,
                           gaps=[(0.000, 0.003), (0.020, 0.028)])}
        read = lambda park, name: idle_under.read(
            obs, **self._spec(park, name)["params"])
        wait = read("sched_waits", "idle_waiting_share.sched")
        work = read("sched_waits", "idle_working_share.sched")
        rest = read("sched_phases", "idle_unattributed_share.offline")
        assert (wait, work, rest) == pytest.approx((15.0, 7.5, 5.0))
        assert wait + work + rest == pytest.approx(idle_share.read(obs), abs=1e-9)

    @pytest.mark.parametrize("cell", CELLS)
    def test_each_cell_loads_with_the_three(self, cell, tmp_path):
        """``run_parked.unpark_metrics`` brings the set back for each of
        the five one-chip cells, beside the benchmark's own metrics."""
        from benchmarks import loader
        from benchmarks.tools import run_parked

        with open(os.path.join(self.PARK, "sched_waits", "metrics.json")) as f:
            entries = json.load(f)["per_layer"]
        names = [m["name"] for m in entries]
        assert names == ["idle_waiting_share.sched", "idle_working_share.sched",
                         "await_rows_share.sched"]
        for m in entries:
            assert tuple(m["workloads"]) == self.CELLS
            assert (m["layer"], m["moves"]) == ("serve host", "serve_pairs_per_s")
        root = run_parked.unpark_metrics("sched_waits", str(tmp_path))
        got = {s["name"]: s for s in
               loader.load_cell(cell, root=root)["per_layer_specs"]}
        assert set(names) <= set(got)
        assert "device_idle_share.offline" in got
        for name in names:
            loader.reader(got[name]["reader"])


class TestSampling:
    def test_loop_sampling_does_not_move_request_sampling(self, tiny_model):
        """Same ``rid``s traced at rate 0.5 with the scheduler's loop
        records (pool mode) and without them (the fallback worker keeps
        none): the loops draw from a counter of their own."""
        traced = {}
        for mode, pool in (("loops", 2), ("no_loops", 0)):
            model, variables = tiny_model
            cfg = ServeConfig(
                buckets=((48, 64),), ladder=(2, 1), max_batch=2,
                pool_capacity=pool, queue_capacity=16, warmup=False,
                default_deadline_ms=60000.0, trace_sample_rate=0.5,
            )
            with ServeEngine(model, variables, cfg) as eng:
                reqs = _serve(eng, 8)
                _quiesce(eng)
                snap = eng.tracer.snapshot()
            traced[mode] = sorted(r.rid for r in reqs if r.trace is not None)
            have_loops = any(r["kind"] == "sched" for r in snap)
            assert have_loops == (mode == "loops")
            assert sorted(
                r["rid"] for r in snap if r["kind"] != "sched"
            ) == traced[mode]
        assert traced["loops"] == traced["no_loops"]
        assert len(traced["loops"]) == 4

    def test_the_two_counters_are_independent(self):
        t = Tracer(0.5)
        first = [t.start("pair", i) is not None for i in range(6)]
        t = Tracer(0.5)
        mixed = []
        for i in range(6):
            t.start_loop("sched")
            t.start_loop("sched")
            mixed.append(t.start("pair", i) is not None)
        assert mixed == first and sum(first) == 3
        assert Tracer(0.0).start_loop("sched") is None


class TestRing:
    def test_dropped_counts_overwrites(self):
        t = Tracer(1.0, capacity=4)
        kept = []
        for i in range(6):
            t.start("pair", i).finish()
        assert (t.dropped, t.finished) == (2, 6)
        loop = t.start_loop("sched")
        loop.add_span("loop", loop.t_start, loop.t_start + 0.01)
        loop.finish(t_end=loop.t_start + 0.01, ticked=1)
        assert t.dropped == 3
        # loop records: in the ring, not counted as requests, and not
        # handed to on_finish
        assert (t.started, t.finished) == (6, 6)
        snap = t.snapshot()
        assert [r["kind"] for r in snap] == ["pair"] * 3 + ["sched"]
        assert snap[-1]["dur_ms"] == pytest.approx(10.0)
        t2 = Tracer(1.0, on_finish=kept.append)
        t2.start_loop("sched").finish()
        t2.start("pair", 0).finish()
        assert [r["kind"] for r in kept] == ["pair"]

    def test_engine_ring_holds_a_traced_window(self, tiny_model):
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            ServeConfig(buckets=((48, 64),), pool_capacity=2, warmup=False),
        )
        assert eng.tracer._ring.maxlen >= 2048
        assert eng.stats()["obs"]["traces_dropped"] == 0
