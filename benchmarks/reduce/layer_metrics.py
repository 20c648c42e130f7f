"""Read every per-layer metric a cell lists, each through the reader its
own file names. A reader that finds nothing to read returns ``None`` and
the metric is left out of the line (never 0 for a share)."""

from __future__ import annotations

from benchmarks import loader


def read_all(cell, window, summary, peaks):
    obs = {"cell": cell, "config": cell["config"], "window": window,
           "trace": summary, "peaks": peaks}
    out = {}
    for spec in cell["per_layer_specs"]:
        value = loader.reader(spec["reader"])(obs, **spec.get("params", {}))
        if value is not None:
            out[spec["name"]] = value
    return out
