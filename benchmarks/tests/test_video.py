"""PR 36's additions to the benchmark: the configuration
``raft_large_video``, the cell ``raft_large_video.sintel_streams`` on a
driver of its own (``traffic/serve_streams.py``, clips from
``inputs_video.py``), the plain reference for video
(``reference/raft_video.py``), five per-layer metrics and one reader
(``readers/mfu_video.py`` over ``reduce/work_video.py``). Everything is
new files and appended entries; the cell is rehearsed on the CPU at a small
size, and an answer altered underneath it has to come out as not correct.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import inputs, inputs_video, loader, run as runmod
from benchmarks.reduce import work, work_video

CELL = "raft_large_video.sintel_streams"
SHARED = [
    "dispatch_ms.offline", "pool_occupancy.offline", "pool_step_ms.offline",
    "lookup_xtap_roofline.offline", "device_idle_share.offline",
]
OWN = [
    "encode_ms.video", "stream_begin_ms.video", "stream_hit_share.video",
    "sched_wait_share.video", "serve_mfu.video",
]
PEAKS = {"flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9}
SEED = 2**31 + 77


def test_the_cell_is_the_deployment():
    cell = loader.load_cell(CELL)
    cfg, dep = cell["config"], cell["config"]["deployment"]
    assert cell["driver"] == "serve_streams" and cell["chips"] == 1
    assert cell["image_hw"] == dep["frame_hw"] == [436, 1024]
    assert cell["bucket"] == dep["bucket_hw"] == [440, 1024]
    assert cell["iters"] == dep["iterations"] == cell["serve"]["ladder"][0] == 32
    serve = cell["serve"]
    assert serve["pool_capacity"] == dep["pool_slots"] == 32
    assert serve["stream_warm_start"] is True
    assert serve["stream_cache_size"] >= cell["streams"] == 40
    assert cell["distinct_clips"] == 8 and cell["clip_frames"] == [20, 50]
    assert cell["compare_sessions"] == 3 and cell["compare_pairs"] == 3
    # the model is whole: raft_large's own sizes and precision
    large = loader.load_cell("raft_large.sintel_offline")["config"]
    assert cfg["reduced"] == [] and cfg["program_arch"] == "raft_large"
    assert cfg["arch"] == large["arch"]
    assert cfg["precision"]["serve"] == large["precision"]["serve"]
    for key in ("weights", "flow_head_scale", "clips", "clip_lengths",
                "forward_interpolate", "iterations"):
        assert key in cfg["assumed"]
    assert {"serve_pairs_per_s", "setup_s"} == {
        m["name"] for m in cell["end_to_end"]}
    assert set(cell["limits"]) == {"flow_epe_mean_px", "flow_epe_p99_px"}


@pytest.mark.parametrize("name", SHARED + OWN)
def test_the_cell_reports_the_metric(name):
    man = loader.manifest()
    entry = [m for m in man["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and CELL in entry[0]["workloads"]
    assert entry[0]["moves"] == "serve_pairs_per_s"
    if name in OWN:
        assert entry[0]["workloads"] == [CELL]
    with open(os.path.join(loader.HERE, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in entry[0] if k != "workloads"} == {
        k: v for k, v in entry[0].items() if k != "workloads"}
    assert callable(loader.reader(spec["reader"]))
    assert name in loader.load_cell(CELL)["per_layer"]


def test_the_manifest_only_grew():
    man = loader.manifest()
    assert [c["name"] for c in man["configs"]][:3] == [
        "raft_large", "raft_small", "raft_large_hd1080"]
    assert [w["name"] for w in man["workloads"]][-1] == CELL
    assert [m["name"] for m in man["per_layer"]][-5:] == OWN
    mfu = next(m for m in man["per_layer"] if m["name"] == "serve_mfu.offline")
    assert CELL not in mfu["workloads"]   # it counts two feature passes a pair
    for w in man["workloads"] + man["configs"]:
        assert len(w["why"]) <= 200


def test_clips_are_seeded_video():
    a = inputs_video.clips(SEED, 3, (60, 96), (5, 9))
    b = inputs_video.clips(SEED, 3, (60, 96), (5, 9))
    assert [len(c) for c in a] == [len(c) for c in b]
    assert all(5 <= len(c) <= 9 for c in a)
    for ca, cb in zip(a, b):
        assert all(np.array_equal(x, y) for x, y in zip(ca, cb))
        assert ca[0].dtype == np.uint8 and ca[0].shape == (60, 96, 3)
        # consecutive frames share their content (noise and a small shift
        # apart) and no two are the same array
        for x, y in zip(ca, ca[1:]):
            assert not np.array_equal(x, y)
    other = inputs_video.clips(SEED + 1, 3, (60, 96), (5, 9))
    assert not np.array_equal(a[0][0], other[0][0])
    # a frame moves by at most 6 px an axis: the best roll that maps one
    # frame onto the next is small
    x, y = (f.astype(np.float32) for f in a[0][:2])
    errs = {(dy, dx): np.abs(np.roll(x, (dy, dx), (0, 1)) - y).mean()
            for dy in range(-7, 8) for dx in range(-7, 8)}
    dy, dx = min(errs, key=errs.get)
    assert max(abs(dy), abs(dx)) <= 6 and errs[(dy, dx)] < 8.0


def test_video_work_counts_one_encode_a_frame():
    arch = loader.load_cell(CELL)["config"]["arch"]
    h, w, iters = 440, 1024, 32
    frame = work_video.frame_flops(arch, h, w)
    refine = work_video.refine_flops(arch, h, w, iters)
    # an unrelated pair is a session pair plus one more feature pass
    feat = work.encoder_flops(arch["feature_encoder_widths"],
                              arch["feature_encoder_block"], h, w)
    assert work.pair_flops(arch, h, w, iters) == pytest.approx(
        refine + frame + feat)
    obs = {"cell": {"bucket": [h, w], "iters": iters, "chips": 1},
           "config": {"arch": arch}, "peaks": PEAKS,
           "window": {"rates": {"serve_pairs_per_s": 28.0}, "window_s": 30.0,
                      "counters": {"stream_frames": 870}}}
    read = loader.reader("mfu_video")
    kw = dict(rate="serve_pairs_per_s", frames="stream_frames", shape_key="bucket")
    want = 100.0 * (29.0 * frame + 28.0 * refine) / 197e12
    assert read(obs, **kw) == pytest.approx(want) and 0 < want < 100
    # a program with no such counter: the metric is left out, not raised
    obs["window"]["counters"] = {}
    assert read(obs, **kw) is None


def test_video_readers_on_a_window():
    """The two readers that were there, on the new metrics' parameters."""
    spec = lambda n: json.load(open(os.path.join(
        loader.HERE, "layer_metrics", f"{n}.json")))
    hit = spec("stream_hit_share.video")
    obs = {"window": {"counters": {"encode_cache_hits": 97, "stream_frames": 100}}}
    assert loader.reader(hit["reader"])(obs, **hit["params"]) == pytest.approx(97.0)
    assert loader.reader(hit["reader"])({"window": {"counters": {}}}, **hit["params"]) is None
    wait = spec("sched_wait_share.video")
    loop = lambda **phases: {"kind": "sched", "spans": [
        {"name": "loop", "dur_ms": 10.0}] + [
        {"name": f"serve/sched/{k}", "dur_ms": v} for k, v in phases.items()]}
    obs = {"window": {"spans": [loop(drain=4.0, fetch=2.0, stage=1.0),
                                loop(drain=3.0, encode_fetch=1.0),
                                {"kind": "stream", "spans": []}]}}
    assert loader.reader(wait["reader"])(obs, **wait["params"]) == pytest.approx(50.0)


def tiny():
    """The cell as committed (its limits too), at a size a test can hold."""
    cell = loader.load_cell(CELL)
    cell.update(image_hw=[120, 152], bucket=[128, 160], iters=4, streams=3,
                distinct_clips=2, clip_frames=[5, 6], ramp_s=0.5,
                compare_sessions=2, compare_pairs=2)
    cell["serve"] = dict(cell["serve"], pool_capacity=4, max_batch=2,
                         ladder=[4, 3, 2], stream_cache_size=4)
    return cell


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    cell = tiny()
    root = tmp_path_factory.mktemp("video")
    return cell, runmod.run_cell(cell, SEED, 8.0, 0, PEAKS,
                                 cache_root=str(root / "cache"))


def test_stream_rehearsal(rehearsal):
    """raft_large whole, bf16, through ``open_stream`` on the CPU: sessions
    prime, pair, warm-start and retire; the sampled chains agree with
    upstream's loop inside the cell's own limits."""
    cell, result = rehearsal
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"serve_pairs_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"   # never a device number
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > result["notes"]["completed"] > 0
    assert result["notes"]["primes"] > 0
    assert set(cell["limits"]) <= set(result["compared"])
    assert result["compared"]["window_compiles"]["value"] == 0.0
    json.dumps(result)


def test_stream_altered_answer_is_not_correct(tmp_path, monkeypatch):
    from raft_tpu.serve.engine import ServeEngine

    monkeypatch.setattr(ServeEngine, "_request_flow",
                        lambda self, req, flow: 8.0 + flow[:, ::-1])
    result = runmod.run_cell(tiny(), SEED, 8.0, 0, PEAKS,
                             cache_root=str(tmp_path / "cache"))
    assert not result["correct"]
    assert any(not c["ok"] for k, c in result["compared"].items()
               if k.startswith("flow"))


def test_no_session_to_compare_is_not_correct():
    """A window in which no session served a pair compares nothing, and
    says so with a NaN."""
    from benchmarks.traffic import serve_streams

    ctx = type("Ctx", (), {"cell": tiny(), "config": tiny()["config"],
                           "seed": SEED, "log": staticmethod(lambda **kw: None)})
    out = serve_streams.compare(
        ctx, {"sessions": [], "clips": [], "host_vars": None},
        {"t0": 0.0, "t1": 1.0, "failed": 0})
    compared, ok = runmod.judge(out)
    assert not ok and np.isnan(compared["pairs_compared"]["value"])


def test_sampled_sessions_prime_inside_the_window_where_any_did():
    """Sessions that primed inside the window and served the cell's pairs
    in it are sampled first (their first link is a cold start); a window
    too short for that — the traced run's — compares sessions that served
    any pair in it, each link from the flow8 the program returned for
    the pair before, which may lie outside the window."""
    from benchmarks.traffic.serve_streams import Session, sample_sessions

    def session(t_primed, times):
        s = Session(clip=0, start=0)
        s.t_primed = t_primed
        s.pairs = [(k + 1, t, None, None) for k, t in enumerate(times)]
        return s

    whole = [session(10.5, [11.0, 12.0, 13.0]), session(11.0, [12.0, 13.0, 14.0])]
    late = session(9.0, [10.5, 11.5, 25.0])      # primed before the window
    short = session(18.0, [19.0, 19.5])          # too few pairs in it
    rng = lambda: inputs.seeded_rng(SEED, 5)
    got = sample_sessions(whole + [late, short], 10.0, 20.0, 2, 3, rng())
    assert {id(s) for s, _ in got} == {id(s) for s in whole}
    assert all(len(pairs) == 3 for _, pairs in got)
    got = sample_sessions(whole + [late, short], 10.0, 20.0, 4, 3, rng())
    assert [s for s, _ in got][:2] != [] and len(got) == 4
    by_id = {id(s): pairs for s, pairs in got}
    assert [p[1] for p in by_id[id(late)]] == [10.5, 11.5]   # in the window only
    assert len(by_id[id(short)]) == 2
    # a traced window: nobody primed and served three pairs inside it
    got = sample_sessions([late, short], 10.0, 12.0, 3, 3, rng())
    assert [id(s) for s, _ in got] == [id(late)]
    assert sample_sessions([late], 30.0, 40.0, 3, 3, rng()) == []
