"""The readers of the pool scheduler's phases (PR 27) on hand-made planes
and records: ``idle_under`` splits a gap between the regions it lies
under by time and its groups add up to ``idle_share``; ``phase_share``
reads ``"sched"`` records only; the two clocks meet at the window's
anchor; both cells load with the six new names once the parked set
``benchmarks/parked/sched_phases`` is brought back (a cell's own file
lists its per-layer metrics, so the six cannot join the manifest without
an edit to the two cell files: PERF.md, Open questions)."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from benchmarks import loader
from benchmarks.reduce import layer_metrics, trace
from benchmarks.reduce.readers import idle_share, idle_under, phase_share
from benchmarks.tools import run_parked

CELLS = ("raft_large.sintel_offline", "raft_small.sintel_offline")
NEW = ("tick_period_ms.offline", "sched_wait_share.offline",
       "idle_admit_share.offline", "idle_retire_share.offline",
       "idle_tick_share.offline", "idle_unattributed_share.offline")


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * 1e6, duration_ns=dur_ms * 1e6)


def planes():
    """Window 0..40 ms. The device runs 10..20 and 30..40: idle 0..10 and
    20..30. The second gap lies under 3 ms of ``stage``, 6 ms of
    ``pool_begin`` and 1 ms of nothing; the first under ``drain`` (4 ms,
    tick group), ``complete`` (2 ms, retire group) and 4 ms of nothing."""
    ops = [ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 10),
           ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 30, 10)]
    host = [ev("bench/window", 0, 40),
            ev("serve/sched/drain", 1, 4), ev("serve/sched/complete", 5, 2),
            ev("serve/sched/stage", 20, 3), ev("serve/pool_begin", 23, 6),
            # a dispatch under which the device is busy gives no idle time
            ev("serve/pool_step", 31, 2)]
    return [NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=[]),
                                            NS(name="XLA Ops", events=ops)]),
            NS(name="/host:CPU", lines=[NS(name="python3", events=host)])]


PARKED = "sched_phases"


@pytest.fixture(scope="module")
def unparked(tmp_path_factory):
    return run_parked.unpark_metrics(PARKED, str(tmp_path_factory.mktemp("unparked")))


@pytest.fixture(scope="module")
def specs(unparked):
    return lambda cell: {s["name"]: s for s in
                         loader.load_cell(cell, root=unparked)["per_layer_specs"]}


@pytest.mark.parametrize("cell", CELLS)
def test_cells_load_with_the_six_names(cell, specs, unparked):
    got = specs(cell)
    assert set(NEW) <= set(got)
    for name in NEW:
        assert got[name]["layer"] == "serve host"
        assert got[name]["moves"] == "serve_pairs_per_s"
        loader.reader(got[name]["reader"])  # the reader's file is there
    # the copy's cell file is the cell's file plus the six names, at the end
    live = json.load(open(os.path.join(loader.HERE, "workloads", f"{cell}.json")))
    copy = json.load(open(os.path.join(unparked, "benchmarks", "workloads", f"{cell}.json")))
    assert copy == {**live, "per_layer": live["per_layer"] + list(NEW)}
    # and the benchmark as it stands is left as it was: the cell loads without them
    assert not set(NEW) & {s["name"] for s in loader.load_cell(cell)["per_layer_specs"]}


def test_idle_under_splits_a_gap_by_time_and_adds_up(specs):
    summary = trace.summarize(planes(), chips=1)
    obs = {"trace": summary}
    sp = specs(CELLS[0])
    share = {n: idle_under.read(obs, **sp[n]["params"]) for n in NEW[2:]}
    ms = {n.split(".")[0]: v * summary.window_s * 10 for n, v in share.items()}
    # the 20..30 ms gap: 9 ms to the admit group, 1 ms to nobody; the
    # 0..10 ms gap: 4 to the tick group, 2 to retire, 4 to nobody
    assert ms == pytest.approx({"idle_admit_share": 9.0, "idle_retire_share": 2.0,
                                "idle_tick_share": 4.0,
                                "idle_unattributed_share": 5.0})
    assert sum(share.values()) == pytest.approx(idle_share.read(obs))
    # winner-takes-all, for contrast: the whole second gap goes to pool_begin
    assert dict(trace.breakdown(summary)["idle_gaps"])["serve/pool_begin"] \
        == pytest.approx(0.010)


def test_idle_under_one_gap_alone(specs):
    """The issue's case by itself: 10 ms under 3 of stage + 6 of
    pool_begin + 1 of nothing splits 9 / 0 / 0 / 1."""
    host = [("serve/sched/stage", 0.020, 0.003), ("serve/pool_begin", 0.023, 0.006)]
    obs = {"trace": NS(window_s=0.040, busy_s=0.030, host=host,
                       gaps=[(0.020, 0.030)])}
    sp = specs(CELLS[1])
    got = [idle_under.read(obs, **sp[n]["params"]) for n in NEW[2:]]
    assert got == pytest.approx([22.5, 0.0, 0.0, 2.5])
    assert sum(got) == pytest.approx(idle_share.read(obs))


def test_idle_under_without_regions_reads_nothing(specs):
    obs = {"trace": NS(window_s=0.040, busy_s=0.030, gaps=[(0.020, 0.030)],
                       host=[("bench/window", 0.0, 0.040)])}
    for n in NEW[2:]:
        assert idle_under.read(obs, **specs(CELLS[0])[n]["params"]) is None


def sched(t_start, phases, **meta):
    """A ``"sched"`` record as ``Trace.finish`` writes it; ``phases`` are
    ``(name, t0_ms, dur_ms)`` on the loop's own timeline."""
    dur = max(t0 + d for _, t0, d in phases)
    spans = [{"name": "loop", "t0_ms": 0.0, "dur_ms": dur}]
    spans += [{"name": n, "t0_ms": t0, "dur_ms": d, "parent": "loop"}
              for n, t0, d in phases]
    return {"kind": "sched", "rid": None, "t_start": t_start, "dur_ms": dur,
            "spans": spans, **meta}


def records():
    request = {"kind": "pair", "rid": 7, "t_start": 100.0, "dur_ms": 640.0,
               "spans": [{"name": "dispatch", "t0_ms": 1.0, "dur_ms": 4.0},
                         # a request span that shares a phase's name must not count
                         {"name": "serve/sched/drain", "t0_ms": 5.0, "dur_ms": 600.0}]}
    return [
        sched(100.000, [("serve/sched/upkeep", 0.0, 1.0), ("serve/pool_step", 1.0, 3.0),
                        ("serve/sched/drain", 4.0, 16.0)], ticked=1),
        request,
        sched(100.020, [("serve/sched/fetch", 0.0, 2.0), ("serve/sched/stage", 2.0, 4.0),
                        ("serve/pool_step", 6.0, 2.0), ("serve/sched/drain", 8.0, 2.0)],
              ticked=1),
    ]


def test_phase_share_reads_sched_records_only(specs):
    obs = {"window": {"spans": records()}}
    sp = specs(CELLS[0])
    # (16 + 2 + 2) ms waiting of 20 + 10 ms of loops
    assert phase_share.read(obs, **sp["sched_wait_share.offline"]["params"]) \
        == pytest.approx(100.0 * 20.0 / 30.0)
    no_sched = {"window": {"spans": [r for r in records() if r["kind"] != "sched"]}}
    assert phase_share.read(no_sched, **sp["sched_wait_share.offline"]["params"]) is None


def test_tick_period_is_the_mean_loop_span(unparked):
    cell = loader.load_cell(CELLS[0], root=unparked)
    cell["per_layer_specs"] = [s for s in cell["per_layer_specs"]
                               if s["name"] in NEW[:2]]
    got = layer_metrics.read_all(cell, {"spans": records()}, None, None)
    assert got["tick_period_ms.offline"] == pytest.approx(15.0)  # loops of 20 and 10 ms
    # a program without the loop records (the parent): both are left out
    assert layer_metrics.read_all(cell, {"spans": [records()[1]]}, None, None) == {}


def test_spans_map_onto_the_trace_at_the_window_anchor(specs):
    """PERF.md §3: ``window["t0"]`` is read just inside the ``bench/window``
    region, so ``trace_t = window_region.start + (span_t - t0)``. Laid on
    the trace that way, a loop's phases are the profiler's regions."""
    t0 = 100.0                       # monotonic, as the driver read it
    region_start = 0.0               # the bench/window region on the trace's clock
    rec = sched(t0 + 0.020, [("serve/sched/stage", 0.0, 3.0),
                             ("serve/pool_begin", 3.0, 6.0)])
    mapped = [(s["name"],
               region_start + (rec["t_start"] + s["t0_ms"] / 1e3 - t0),
               s["dur_ms"] / 1e3)
              for s in rec["spans"] if s["name"] != "loop"]
    on_trace = [e for e in trace.summarize(planes(), chips=1).host
                if e[0] in ("serve/sched/stage", "serve/pool_begin")]
    assert [m[0] for m in mapped] == [e[0] for e in on_trace]
    for m, e in zip(mapped, on_trace):
        assert m[1:] == pytest.approx(e[1:])
    # so the records give the same idle attribution as the regions do
    obs = {"trace": NS(window_s=0.040, busy_s=0.020, host=mapped,
                       gaps=[(0.0, 0.010), (0.020, 0.030)])}
    admit = specs(CELLS[0])["idle_admit_share.offline"]["params"]
    assert idle_under.read(obs, **admit) == pytest.approx(22.5)
