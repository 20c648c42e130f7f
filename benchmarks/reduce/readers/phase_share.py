"""The share of the pool scheduler's time spent in some of its phases:
over the window's ``"sched"`` records (``raft_tpu/obs/trace.py``; one a
scheduler loop, request records in the same list are skipped), the summed
duration of the spans named in ``phases`` over the summed ``loop`` spans
(the whole iteration), in %."""


def read(obs, phases):
    num = den = 0.0
    for rec in obs["window"]["spans"]:
        if rec.get("kind") != "sched":
            continue
        for s in rec.get("spans", ()):
            if s["name"] == "loop":
                den += s["dur_ms"]
            elif s["name"] in phases:
                num += s["dur_ms"]
    return 100.0 * num / den if den > 0 else None
