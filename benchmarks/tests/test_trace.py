"""The trace reduction and the per-layer readers on a small hand-made
trace, laid out as the v5e's traces are (PR 25: plane ``/device:TPU:0``
with lines ``XLA Modules`` / ``XLA Ops``, annotations on the host plane's
``python3`` lines, runtime threads beside them)."""

from types import SimpleNamespace as NS

import pytest

from benchmarks.reduce import layer_metrics, trace


def ev(name, start_ms, dur_ms, **stats):
    return NS(name=name, start_ns=start_ms * 1e6, duration_ns=dur_ms * 1e6,
              stats=list(stats.items()))


def planes():
    ops = [ev("%copy.41 = bf16[16,7040,55,128,1]{3,2,1,0,4:T(8,128)(2,1)} copy(bf16[16,7040,55,128,1]{3,1,4,2,0} %p)", 10, 5),
           ev("%motion_encoder.1 = bf16[112640,256]{1,0:T(8,128)(2,1)} custom-call(f32[112640,2]{1,0:T(8,128)} %bitcast.18)", 15, 4),
           ev("%fusion.15 = bf16[16,55,128,192]{3,0,2,1:T(8,128)(2,1)S(1)} fusion(bf16[16,55,128,256]{3,2,1,0} %bitcast.18)", 19, 1),
           # second step after a 10 ms gap
           ev("%copy.41 = bf16[16,7040,55,128,1]{3,2,1,0,4:T(8,128)(2,1)} copy(bf16[16,7040,55,128,1]{3,1,4,2,0} %p)", 30, 5),
           ev("%motion_encoder.1 = bf16[112640,256]{1,0:T(8,128)(2,1)} custom-call(f32[112640,2]{1,0:T(8,128)} %bitcast.18)", 35, 4),
           ev("%while = (s32[]{:T(128)}, bf16[16,55,128,128]{3,2,1,0}) while(%tuple.1)", 39, 1),
           # before the window: must be clipped away
           ev("%fusion.1 = early", 0, 2)]
    mods = [ev("jit__step(123)", 10, 10), ev("jit__step(123)", 30, 10),
            ev("jit__lambda(9)", 0, 2)]
    host = [ev("bench/window", 5, 45), ev("serve/pool_begin", 21, 8),
            ev("serve/pool_step", 29, 1), ev("$profiler.py:1 noise", 0, 50)]
    return [
        NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=mods),
                                        NS(name="XLA Ops", events=ops),
                                        NS(name="Async XLA Ops", events=[ev("x", 0, 100)])]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=host),
                                    NS(name="pjrt-tpu-tasks/606", events=[ev("a/b", 0, 100)])]),
    ]


def test_union_length_and_gaps():
    total, gaps = trace.union_length([(0, 2), (1, 3), (5, 6)], lo=0, hi=8)
    assert total == 4 and gaps == [(3, 5), (6, 8)]


def test_summary_busy_window_and_gaps():
    s = trace.summarize(planes(), chips=1)
    assert s.window_s == pytest.approx(0.045)        # the bench/window region
    assert s.busy_s == pytest.approx(0.020)          # two 10 ms steps, early op clipped
    assert [round(b - a, 6) for a, b in s.gaps] == [0.005, 0.010, 0.010]
    assert trace.module_time(s, r"^jit__step\(") == (2, pytest.approx(0.020))
    assert trace.parse_op(planes()[0].lines[1].events[2].name) == ("fusion.15", "fusion")
    n, kernel_s = trace.op_time(s, r"custom-call$")
    assert (n, kernel_s) == (2, pytest.approx(0.008))


def test_breakdown_names_ops_and_gaps():
    b = trace.breakdown(trace.summarize(planes(), chips=1))
    ops = dict(b["device_ops"])
    assert ops["data movement"] == pytest.approx(0.010)
    assert ops["fused lookup kernel"] == pytest.approx(0.008)
    # a fusion that READS a bitcast is a fusion; a while is a container
    assert ops["fusion (convs, GRU, elementwise)"] == pytest.approx(0.001)
    assert not any("while" in k for k in ops)
    gaps = dict(b["idle_gaps"])
    assert gaps["serve/pool_begin"] == pytest.approx(0.010)  # the 20..30 ms gap
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_device_operation_is_an_error():
    with pytest.raises(RuntimeError):
        trace.summarize([NS(name="/host:CPU", lines=[])], chips=1)


def test_unmarked_window_is_an_error():
    """Without the driver's ``bench/window`` region the idle share would
    be taken over the extent of the device's work, and read too low."""
    device, host = planes()
    python3 = host.lines[0]
    python3.events = [e for e in python3.events if e.name != "bench/window"]
    with pytest.raises(RuntimeError, match="bench/window"):
        trace.summarize([device, host], chips=1)


def _cell():
    arch = {"corr_levels": 4, "corr_radius": 4, "motion_corr_widths": [256, 192]}
    specs = [
        {"name": "pool_step_ms.offline", "reader": "program_time", "params": {"module": r"^jit__step\("}},
        {"name": "lookup_xtap_roofline.offline", "reader": "lookup_roofline",
         "params": {"kernel": r"custom-call$", "step_module": r"^jit__step\("}},
        {"name": "device_idle_share.offline", "reader": "idle_share"},
        {"name": "pool_occupancy.offline", "reader": "counter_ratio",
         "params": {"num": "idle_slot_iters", "den": "dispatched_slot_iters",
                    "scale": 100.0, "complement": True}},
        {"name": "dispatch_ms.offline", "reader": "span_stat", "params": {"span": "dispatch", "stat": "mean"}},
        {"name": "queue_wait_ms.absent", "reader": "span_stat", "params": {"span": "queue_wait", "stat": "median"}},
        {"name": "data_wait_share.train", "reader": "span_stat", "params": {"span": "data_wait", "stat": "share"}},
    ]
    return {"per_layer_specs": specs, "config": {"arch": arch}, "bucket": [440, 1024],
            "serve": {"pool_capacity": 16}, "chips": 1, "iters": 32}


def test_readers_on_the_small_trace():
    window = {
        "counters": {"idle_slot_iters": 8, "dispatched_slot_iters": 32},
        "spans": [{"dur_ms": 100.0, "spans": [{"name": "dispatch", "dur_ms": 4.0},
                                              {"name": "data_wait", "dur_ms": 25.0}]},
                  {"dur_ms": 100.0, "spans": [{"name": "dispatch", "dur_ms": 6.0}]}],
        "rates": {},
    }
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    got = layer_metrics.read_all(_cell(), window, trace.summarize(planes(), 1), peaks)
    assert got["pool_step_ms.offline"] == pytest.approx(10.0)
    assert got["device_idle_share.offline"] == pytest.approx(100 * (1 - 20 / 45))
    assert got["pool_occupancy.offline"] == pytest.approx(75.0)
    assert got["dispatch_ms.offline"] == pytest.approx(5.0)
    assert got["data_wait_share.train"] == pytest.approx(12.5)
    # nothing to read -> left out, never 0
    assert "queue_wait_ms.absent" not in got
    # bytes-bound least time of one 16-slot step over 4 ms of kernel time
    q = 16 * 55 * 128
    least = (q * 400 * 2 + 324 * 256 * 2 + q * 256 * 2) / 819e9
    assert got["lookup_xtap_roofline.offline"] == pytest.approx(100 * least / 0.004)
    assert got["lookup_xtap_roofline.offline"] < 100
