"""A statistic of one named host span over the window's traced requests
(``raft_tpu/obs/trace.py`` records): ``mean`` or ``median`` of its
duration in ms, or ``share`` — its summed duration over the summed
duration of the traces, in %."""

import statistics


def read(obs, span, stat):
    durs, total = [], 0.0
    for rec in obs["window"]["spans"]:
        total += rec.get("dur_ms", 0.0)
        durs += [s["dur_ms"] for s in rec.get("spans", ()) if s["name"] == span]
    if not durs:
        return None
    if stat == "mean":
        return statistics.fmean(durs)
    if stat == "median":
        return statistics.median(durs)
    if stat == "share":
        return 100.0 * sum(durs) / total if total > 0 else None
    raise ValueError(f"span_stat: unknown stat {stat!r}")
