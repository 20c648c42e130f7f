"""Every cell, configuration and per-layer metric file loads and names only
things that exist; the manifest keeps to the contract's limits; a later PR
can add a cell, a configuration and a metric as new files plus entries."""

import json
import os
import re
import shutil

import pytest

from benchmarks import loader

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(_dim|_rank|width|hidden|intermediate|head)")


def test_manifest_keeps_to_the_contract():
    man = loader.manifest()
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["benchmarks"] and man["command"][1].startswith("benchmarks/")
    assert 1 <= man["run_seconds"] <= 51
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1 and "workloads" not in e2e["setup_s"]
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in man["workloads"]}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e
        for c in m["workloads"]:  # each cell reports the metric it moves
            assert c in cells
            assert "workloads" not in e2e[m["moves"]] or c in e2e[m["moves"]]["workloads"]
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(names) == len(set(names))
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and not any(WIDTHS.search(k) for k in c["reduced"])
    # a roofline that moves a metric has the whole step's mfu beside it
    moved = {m["moves"] for m in man["per_layer"] if m["name"].split(".")[0].endswith("_roofline")}
    mfus = {m["moves"] for m in man["per_layer"] if "mfu" in re.split(r"[._]", m["name"])}
    assert moved <= mfus
    assert len(json.dumps(man)) < 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in loader.manifest()["workloads"]])
def test_cell_loads_and_names_what_exists(cell):
    c = loader.load_cell(cell)
    assert loader.driver(c["driver"]).window
    for spec in c["per_layer_specs"]:
        assert callable(loader.reader(spec["reader"]))
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and c["per_layer_specs"]
    assert c["config"]["reduced"] == [] and c["limits"]
    for limit in c["limits"].values():
        assert limit > 0


def test_every_file_is_named_by_the_manifest():
    man = loader.manifest()
    cells = {w["name"] for w in man["workloads"]}
    metrics = {m["name"] for m in man["per_layer"]}
    stem = lambda d: {f[:-5] for f in os.listdir(os.path.join(loader.HERE, d)) if f.endswith(".json")}
    assert stem("workloads") == cells and stem("layer_metrics") == metrics
    assert stem("configs") == {c["name"] for c in man["configs"]}


def test_unknown_device_is_an_error():
    assert loader.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(loader.SpecError):
        loader.peaks("TPU v99")


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """One new configuration, cell and per-layer metric: new files, new
    manifest entries, no edit to any existing file."""
    root = tmp_path / "checkout"
    shutil.copytree(loader.HERE, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    man = loader.manifest()
    before = {p: open(os.path.join(dp, p)).read()
              for dp, _, fs in os.walk(root / "benchmarks") for p in fs if p.endswith(".json")}
    bench = root / "benchmarks"
    cfg = json.loads((bench / "configs" / "raft_small.json").read_text())
    cfg["name"] = "raft_small_b"
    (bench / "configs" / "raft_small_b.json").write_text(json.dumps(cfg))
    cell = json.loads((bench / "workloads" / "raft_small.sintel_offline.json").read_text())
    cell.update(config="raft_small_b", clients=4,
                per_layer=cell["per_layer"] + ["fetch_ms.offline"])
    (bench / "workloads" / "raft_small_b.burst.json").write_text(json.dumps(cell))
    (bench / "layer_metrics" / "fetch_ms.offline.json").write_text(json.dumps({
        "name": "fetch_ms.offline", "reader": "span_stat",
        "params": {"span": "fetch", "stat": "median"}}))
    man["configs"].append({"name": "raft_small_b", "source": "x", "reduced": [], "why": "y",
                           "file": "benchmarks/configs/raft_small_b.json"})
    man["workloads"].append({"name": "raft_small_b.burst", "config": "raft_small_b",
                             "traffic": "burst", "chips": 1, "why": "z"})
    for m in man["per_layer"]:
        if "raft_small.sintel_offline" in m["workloads"]:
            m["workloads"].append("raft_small_b.burst")
    for m in man["end_to_end"]:
        if "raft_small.sintel_offline" in m.get("workloads", []):
            m["workloads"].append("raft_small_b.burst")
    man["per_layer"].append({"name": "fetch_ms.offline", "unit": "ms", "better": "lower",
                             "source": "program_span", "layer": "serve host",
                             "moves": "serve_pairs_per_s", "workloads": ["raft_small_b.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    got = loader.load_cell("raft_small_b.burst", root=str(root))
    assert got["config"]["name"] == "raft_small_b" and got["clients"] == 4
    assert "fetch_ms.offline" in [s["name"] for s in got["per_layer_specs"]]
    assert loader.load_cell("raft_small.sintel_offline", root=str(root))["clients"] == 20
    for p, text in before.items():  # nothing that was there changed
        found = [os.path.join(dp, p) for dp, _, fs in os.walk(bench) if p in fs]
        assert open(found[0]).read() == text


def test_disagreeing_files_are_refused(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(loader.HERE, root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    man = loader.manifest()
    man["per_layer"] = [m for m in man["per_layer"] if m["name"] != "dispatch_ms.offline"]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    with pytest.raises(loader.SpecError):
        loader.load_cell("raft_large.sintel_offline", root=str(root))
    with pytest.raises(loader.SpecError):
        loader.load_cell("no_such.cell", root=str(root))
