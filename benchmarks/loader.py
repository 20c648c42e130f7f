"""Everything is data, found by name: ``BENCHMARK.json`` lists cells,
configurations and metrics; each has a file of its own under this
directory, and nothing here needs an edit when a later PR adds one.

* cell ``<name>``        -> ``workloads/<name>.json`` (driver, traffic
  parameters, the per-layer metrics it reports, the limits ``correct`` holds)
* configuration ``<c>``  -> the ``file`` its ``BENCHMARK.json`` entry names
* per-layer metric ``<m>`` -> ``layer_metrics/<m>.json`` (reader + parameters)
* driver ``<d>``         -> ``traffic/<d>.py``; reader ``<r>`` -> ``reduce/readers/<r>.py``

A *parked* cell (``parked/<name>/``) is whole — its files and its manifest
entries — but not in ``BENCHMARK.json``, because ``correct`` cannot hold it
yet (PERF.md, Open questions). ``unpark`` shows what bringing it back takes:
new files and new entries, nothing edited.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
from typing import Any, Dict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    """A name in the manifest that has no file, or a file that names
    something that does not exist."""


def _read(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def manifest(root: str = ROOT) -> Dict[str, Any]:
    return _read(os.path.join(root, "BENCHMARK.json"))


def _entry(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise SpecError(f"{what} {name!r} is not in BENCHMARK.json")


def load_cell(name: str, root: str = ROOT) -> Dict[str, Any]:
    """The cell's manifest entry merged over its own file, with its
    configuration and its metrics resolved."""
    man = manifest(root)
    entry = _entry(man["workloads"], name, "workload")
    bench = os.path.join(root, man["paths"][0])
    cell = _read(os.path.join(bench, "workloads", f"{name}.json"))
    if cell.get("config") != entry["config"] or cell.get("chips") != entry["chips"]:
        raise SpecError(f"workloads/{name}.json disagrees with BENCHMARK.json "
                        "on config or chips")
    cfg_entry = _entry(man["configs"], entry["config"], "configuration")
    config = _read(os.path.join(root, cfg_entry["file"]))
    reports = lambda m: "workloads" not in m or name in m["workloads"]
    e2e = [m for m in man["end_to_end"] if reports(m)]
    per_layer = []
    for m in man["per_layer"]:
        if not reports(m):
            continue
        spec = _read(os.path.join(bench, "layer_metrics", f"{m['name']}.json"))
        per_layer.append({**spec, **m})
    listed = set(cell.get("per_layer", []))
    named = {m["name"] for m in per_layer}
    if listed != named:
        raise SpecError(f"cell {name}: its file lists per-layer metrics "
                        f"{sorted(listed)}, BENCHMARK.json gives it {sorted(named)}")
    return {**cell, "name": name, "config_name": entry["config"],
            "config": config, "end_to_end": e2e, "per_layer_specs": per_layer,
            "run_seconds": man["run_seconds"]}


def unpark(name: str, dest: str) -> str:
    """A copy of the benchmark under ``dest`` in which the parked cell
    ``name`` is a cell: its files copied into place, its entries appended
    to the manifest. Returns the copy's root, for ``load_cell(name, root)``."""
    park = os.path.join(HERE, "parked", name)
    entries = _read(os.path.join(park, "entries.json"))
    bench = os.path.join(dest, "benchmarks")
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "parked", "*.py", "*.sh"))
    for sub in ("workloads", "layer_metrics"):
        shutil.copytree(os.path.join(park, sub), os.path.join(bench, sub),
                        dirs_exist_ok=True)
    man = manifest()
    for key, items in entries.items():
        man[key] = man[key] + items
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return dest


def driver(name: str):
    try:
        return importlib.import_module(f"benchmarks.traffic.{name}")
    except ModuleNotFoundError as e:
        raise SpecError(f"no driver benchmarks/traffic/{name}.py") from e


def reader(name: str):
    try:
        return importlib.import_module(f"benchmarks.reduce.readers.{name}").read
    except ModuleNotFoundError as e:
        raise SpecError(f"no reader benchmarks/reduce/readers/{name}.py") from e


def peaks(device_kind: str) -> Dict[str, float]:
    table = _read(os.path.join(HERE, "reduce", "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        "benchmarks/reduce/peaks.json; add it with its source")
    return table[device_kind]
