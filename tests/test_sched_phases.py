"""The pool scheduler's loop on the trace's clock (ISSUE 27).

``ServeEngine._worker_pool`` runs every step of an iteration inside one
``_phase`` (``raft_tpu.obs.profile.phase``): a profiler region when
profiling is on, and ``(name, t0, t1)`` on the loop's ``"sched"`` trace
record when the loop is sampled. On the tiny CPU pool engine:

  * a sampled loop's phases are disjoint, ordered children of its
    ``loop`` span; its meta says what the iteration did and names the
    requests;
  * on a clock that only the loop's own operations advance, the phases
    cover the whole of ``loop`` and each operation's time falls under
    the phase that names it;
  * the profiler regions never nest on the scheduler thread;
  * with sampling and profiling off the helper reads no clock and no
    loop record exists;
  * loop records are sampled on a counter of their own (the same
    requests are traced with and without them) and stay out of the
    flight recorder's last-N ring;
  * ``Tracer.dropped`` counts what the ring overwrote.

How much of a real loop the phases cover is a wall-clock ratio, and the
chip's to give (PERF.md §6, PR 27: 98.4-99.3%); nothing here asserts one.
"""

import contextlib
import threading
import time

import numpy as np
import pytest

from raft_tpu.obs import Tracer, profile
from raft_tpu.serve import ServeConfig, ServeEngine
from tests.test_serve_pool import _image, _tiny_model

pytestmark = pytest.mark.chaos

ADMIT = {"serve/sched/poll", "serve/sched/stage", "serve/pool_begin",
         "serve/sched/insert"}
RETIRE = {"serve/sched/retire", "serve/sched/gather", "serve/pool_final",
          "serve/sched/fetch", "serve/sched/complete"}
TICK = {"serve/pool_step", "serve/sched/drain", "serve/sched/upkeep"}


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


# the loop's operations, and the phase each one's time belongs under;
# dispatches go through ``ledger.run`` and are told apart by family
OPS = {
    "_log_counters": "serve/sched/upkeep",
    "_pool_due": "serve/sched/retire",
    "_pool_gather": "serve/sched/gather",
    "_pool_complete": "serve/sched/complete",
    "_pool_insert_slots": "serve/sched/insert",
    "_pool_tick_drain": "serve/sched/drain",
}
FAMILIES = {
    "pool_begin_pair": "serve/pool_begin",
    "pool_step": "serve/pool_step",
    "pool_final": "serve/pool_final",
    "pool_insert": "serve/sched/insert",
    "pool_gather": "serve/sched/gather",
}


class _WorkClock:
    """``time.monotonic`` for the scheduler thread of one engine: it
    stands still except that every operation of the loop (``OPS``,
    ``FAMILIES``, the queue poll, a staging fill, the alert pass) takes
    1 ms of it. Other threads keep the real clock. ``log`` holds
    ``(t, phase the operation belongs under)``, ``t`` the middle of the
    operation's millisecond."""

    def __init__(self, eng, monkeypatch):
        self.eng, self.real = eng, time.monotonic
        self.t0, self.ms, self.log = time.monotonic(), 0, []
        monkeypatch.setattr(time, "monotonic", self)
        for name, phase in OPS.items():
            self._wrap(eng, name, lambda *a, _p=phase: _p)
        self._wrap(eng._queue, "next_batch", lambda *a, **k: "serve/sched/poll")
        self._wrap(eng._staging, "fill", lambda *a: "serve/sched/stage")
        self._wrap(eng._alerts, "maybe_observe", lambda: "serve/sched/upkeep")
        self._wrap(eng.ledger, "run", lambda key, fn: FAMILIES[key[0]])

    def _mine(self):
        return threading.current_thread() is self.eng._thread

    def __call__(self):
        return self.t0 + self.ms / 1e3 if self._mine() else self.real()

    def _wrap(self, obj, name, phase_of):
        fn = getattr(obj, name)

        def timed(*a, **k):
            if self._mine():
                self.log.append((self() + 0.0005, phase_of(*a, **k)))
                self.ms += 1
            return fn(*a, **k)

        setattr(obj, name, timed)


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

    def test_phases_cover_the_loops_work(self, tiny_model, monkeypatch):
        """The >= 95% cover, without a stopwatch: on a clock that only
        the loop's operations advance, a hole between two phases shows
        as cover under 1, and an operation under the wrong phase by
        name."""
        model, variables = tiny_model
        eng = ServeEngine(model, variables, ServeConfig(
            buckets=((48, 64),), ladder=(3, 2, 1), max_batch=2,
            pool_capacity=2, queue_capacity=16, warmup=False,
            default_deadline_ms=60000.0, trace_sample_rate=1.0,
        ))
        clock = _WorkClock(eng, monkeypatch)
        with eng:
            _serve(eng, 6)
            _quiesce(eng)
            recs = _sched(eng)
        monkeypatch.undo()
        cover = self._check_tiling(recs)
        assert min(cover) >= 0.95, cover
        assert cover == pytest.approx([1.0] * len(recs))
        # every operation's millisecond lies in a phase of its own name
        spans = [
            (rec["t_start"] + s["t0_ms"] / 1e3, s["dur_ms"] / 1e3, s["name"])
            for rec in recs for s in rec["spans"][1:]
        ]
        t_first = recs[0]["t_start"]
        t_last = recs[-1]["t_start"] + recs[-1]["dur_ms"] / 1e3
        seen = set()
        for t, want in clock.log:
            if not t_first <= t < t_last:
                continue  # an idle poll's: those loops keep no record
            got = [n for s0, d, n in spans if s0 <= t < s0 + d]
            assert got == [want], (t - t_first, want, got)
            seen.add(want)
        # (the fetch is a bare ``np.asarray``: nothing of the engine's to time)
        assert seen == (ADMIT | RETIRE | TICK) - {"serve/sched/fetch"}

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
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                events.append((threading.get_ident(), self.name, +1))

            def __exit__(self, *exc):
                events.append((threading.get_ident(), self.name, -1))

        monkeypatch.setattr(profile, "annotate", Region)
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
        assert not profile.enabled()
        model, variables = tiny_model
        eng = ServeEngine(
            model, variables,
            ServeConfig(buckets=((48, 64),), pool_capacity=2, warmup=False),
        )
        reads = []
        real = time.monotonic

        def counting():
            reads.append(1)
            return real()

        monkeypatch.setattr(time, "monotonic", counting)
        null = profile.annotate("x")
        for _ in range(100):  # N loops of the helper, nothing sampled
            assert eng._sched_turn() is None
            for name in ("serve/sched/upkeep", "serve/pool_step"):
                region = eng._phase(name)
                assert region is null  # the shared no-op: no allocation
                with region:
                    pass
        assert eng._sched_turn(last=True) is None
        assert reads == []
        monkeypatch.undo()
        # and a served request leaves no loop record behind
        with _pool_engine(tiny_model) as eng:
            _serve(eng, 2)
            assert eng.tracer.snapshot() == []
            assert eng._loop is None

    def test_phase_helper_with_a_sink(self):
        sink = []
        with profile.phase("a", sink):
            time.sleep(0.002)
        with profile.phase("b", sink):
            pass
        (a, a0, a1), (b, b0, b1) = sink
        assert (a, b) == ("a", "b")
        assert a1 - a0 >= 0.002 and a1 <= b0 <= b1
        assert profile.phase("c") is profile.annotate("c")


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
