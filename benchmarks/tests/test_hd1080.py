"""PR 30's additions are data beside what was there: the configuration
``raft_large_hd1080``, the cell ``raft_large_hd1080.davis_offline``, its
name appended to the ``workloads`` of the six accepted ``.offline`` metrics
(one series a layer across cells) and two per-layer metrics of its own, on
readers the benchmark already had. They load through ``loader.load_cell``
with nothing edited, and the seed study's stand-in runs on the cell at a
small size on the CPU.

The tests hold what PR 30 owns and that what came before stands first: a
later PR that appends a configuration, a cell or a metric passes them."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import loader

CELL = "raft_large_hd1080.davis_offline"
# the accepted series that continue onto the cell, and the cell's own two
ACCEPTED = [
    "dispatch_ms.offline", "pool_occupancy.offline", "pool_step_ms.offline",
    "serve_mfu.offline", "lookup_xtap_roofline.offline",
    "device_idle_share.offline",
]
OWN = ["pool_begin_ms.hd1080", "sched_wait_share.hd1080"]


def test_the_cell_is_the_deployment():
    cell = loader.load_cell(CELL)
    cfg = cell["config"]
    assert cell["driver"] == "serve_closed" and cell["chips"] == 1
    assert cell["image_hw"] == cfg["deployment"]["frame_hw"] == [1080, 1920]
    assert cell["bucket"] == cfg["deployment"]["bucket_hw"] == [1088, 1920]
    assert cell["iters"] == cfg["deployment"]["iterations"] == 20
    assert cell["serve"]["ladder"][0] == cell["iters"]
    assert cell["serve"]["pool_capacity"] >= 2
    assert cell["clients"] == cell["serve"]["pool_capacity"] + 2
    # the frame is whole and the model is whole
    assert cfg["reduced"] == [] and cfg["program_arch"] == "raft_large"
    large = loader.load_cell("raft_large.sintel_offline")["config"]
    assert cfg["arch"] == large["arch"]
    assert cfg["precision"]["serve"] == large["precision"]["serve"]
    assert cfg["precision"]["serve"]["control"] == "fp8"
    for key in ("weights", "flow_head_scale", "frames", "iterations"):
        assert key in cfg["assumed"]
    # the two end-to-end metrics a serve cell reports, under their bounds
    assert {"serve_pairs_per_s", "setup_s"} <= {
        m["name"] for m in cell["end_to_end"]}
    assert set(cell["limits"]) == {"flow_epe_mean_px", "flow_epe_p99_px"}


def test_the_program_runs_the_files_sizes():
    """``check_arch``: the configuration's sizes are raft_large's own."""
    from benchmarks.traffic import serve_closed
    from raft_tpu.models import zoo

    cfg = loader.load_cell(CELL)["config"]
    serve_closed.check_arch(cfg, zoo.CONFIGS[cfg["program_arch"]])


@pytest.mark.parametrize("name", ACCEPTED + OWN)
def test_the_cell_reports_the_metric_through_a_reader_that_was_there(name):
    man = loader.manifest()
    entry = [m for m in man["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and CELL in entry[0]["workloads"]
    assert entry[0]["moves"] == "serve_pairs_per_s"
    with open(os.path.join(loader.HERE, "layer_metrics", f"{name}.json")) as f:
        spec = json.load(f)
    assert {k: spec[k] for k in entry[0] if k != "workloads"} == {
        k: v for k, v in entry[0].items() if k != "workloads"}
    assert callable(loader.reader(spec["reader"]))
    cell = loader.load_cell(CELL)
    assert name in cell["per_layer"]
    assert name in [m["name"] for m in cell["per_layer_specs"]]


def test_the_manifest_only_grew():
    """What PR 25 and PR 29 left stands first and as it was; PR 30's
    entries are present, wherever later PRs' entries come to stand."""
    man = loader.manifest()
    configs = [c["name"] for c in man["configs"]]
    cells = [w["name"] for w in man["workloads"]]
    metrics = [m["name"] for m in man["per_layer"]]
    before = ["raft_large.sintel_offline", "raft_small.sintel_offline"]
    assert configs[:2] == ["raft_large", "raft_small"]
    assert cells[:2] == before and metrics[:6] == ACCEPTED
    assert "raft_large_hd1080" in configs[2:] and CELL in cells[2:]
    assert set(OWN) <= set(metrics[6:])
    rate = next(m for m in man["end_to_end"] if m["name"] == "serve_pairs_per_s")
    for m in [rate] + man["per_layer"][:6]:
        assert m["workloads"][:2] == before and CELL in m["workloads"][2:]


def test_seed_study_standin_rehearsal():
    """``tools/seed_study.py --standin`` on the new cell at 136x1088 (a
    17x136 grid: a level wider than 128 lanes), one seed, one pair: the
    stated precision sits well under the fp8 control, as the limits need."""
    out = subprocess.run(
        [sys.executable, os.path.join(loader.HERE, "tools", "seed_study.py"),
         "--workload", CELL, "--standin", "--hw", "136", "1088",
         "--seeds", "1", "--pairs", "1"],
        capture_output=True, text=True, timeout=900, cwd=loader.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["workload"] == CELL and row["hw"] == [136, 1088]
    prog, ctrl = row["program"][0], row["fp8"][0]
    assert prog["finite"] == ctrl["finite"] == 1.0
    assert 0 < prog["flow_epe_mean_px"] < ctrl["flow_epe_mean_px"] / 3
    assert 0 < prog["flow_epe_p99_px"] < ctrl["flow_epe_p99_px"] / 3
