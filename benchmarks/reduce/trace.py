"""From a ``jax.profiler`` trace (``*.xplane.pb``) to what the metrics need,
with nothing but ``jax.profiler.ProfileData``.

A TPU plane (``/device:TPU:<n>``) carries a line of whole-program
executions (``XLA Modules``) and a line of device operations (``XLA
Ops``); host planes carry the ``TraceAnnotation`` regions the program and
the harness open (``serve/pool_step``, ``train/window_dispatch``,
``bench/window``). Times are in seconds on the trace's own clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]  # name, start_s, duration_s

WINDOW_ANNOTATION = "bench/window"
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)

# Device operations by what they are. The trace names an operation by its
# whole HLO line (``%copy.41 = bf16[...]{...} copy(bf16[...] %x)``); only the
# instruction's own name and opcode say what it is — its operand list would
# file every fusion that reads a bitcast under data movement. XLA names
# fusions ``fusion.N``, so the model's stages (scripts/profile_stats.py
# bucketed them by the framework-op path, which this trace does not carry)
# show only where a name keeps a hint.
BUCKETS = [
    ("fused lookup kernel", r"custom-call$"),
    ("data movement", r"^copy|copy$|transpose|bitcast$|copy-start$|copy-done$|slice"),
    ("pyramid pooling", r"reduce[-_]window"),
    ("feature encoder", r"feature_encoder"),
    ("context encoder", r"context_encoder"),
    ("motion encoder", r"motion_encoder|convcorr|convflow"),
    ("GRU", r"convgru|recurrent_block"),
    ("flow head / mask", r"flow_head|mask_predictor"),
    ("convolution", r"convolution"),
    ("fusion (convs, GRU, elementwise)", r"fusion"),
]
CONTAINERS = ("while", "conditional", "call")  # their bodies' ops are traced too

_NAME = re.compile(r"^%?([^\s=]+) = ")
_OPCODE = re.compile(r"[\]\}\)] ([a-z][\w\-]*)\(")


def parse_op(text: str):
    """``(instruction name, opcode)`` of a traced HLO line."""
    name = _NAME.match(text)
    opcode = _OPCODE.search(text)
    return (name.group(1) if name else text,
            opcode.group(1) if opcode else "")


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over the chips used
    modules: List[List[Event]] = field(default_factory=list)   # per chip
    ops: List[List[Tuple[str, str, float, float]]] = field(default_factory=list)  # name, opcode, start, dur
    host: List[Event] = field(default_factory=list)
    gaps: List[Tuple[float, float]] = field(default_factory=list)  # chip 0


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9


def union_length(intervals, lo=None, hi=None):
    """Total length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]``, and the gaps between them inside it."""
    ivs = sorted((max(s, lo) if lo is not None else s,
                  min(e, hi) if hi is not None else e) for s, e in intervals)
    ivs = [(s, e) for s, e in ivs if e > s]
    total, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in ivs:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e + 1e-9:  # touching ops leave no gap
            cur_e = max(cur_e, e)
        else:
            total += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        total += cur_e - cur_s
        if lo is not None and ivs[0][0] > lo:
            gaps.insert(0, (lo, ivs[0][0]))
        if hi is not None and cur_e < hi:
            gaps.append((cur_e, hi))
    return total, gaps


def load(trace_dir: str, chips: int) -> Summary:
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(max(paths, key=os.path.getmtime))
    return summarize(data.planes, chips)


def summarize(planes, chips: int) -> Summary:
    modules, ops, host = [], [], []
    for plane in planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            mods, devops = [], []
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    mods.extend(_events(line))
                elif line.name in OP_LINES:
                    devops.extend(parse_op(ev.name) + (ev.start_ns * 1e-9,
                                                       ev.duration_ns * 1e-9)
                                  for ev in line.events)
            modules.append(mods)
            ops.append(devops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue  # runtime threads: millions of events, none ours
                for name, s, d in _events(line):
                    if "/" in name and not name.startswith("$"):
                        host.append((name, s, d))
    busy_ops = [o for o in ops if o][:chips]
    if not busy_ops:
        raise RuntimeError("the trace holds no device operation")
    marks = [(s, s + d) for n, s, d in host if n == WINDOW_ANNOTATION]
    if not marks:  # the extent of the device's work would hide idle time at both ends
        raise RuntimeError(f"the trace holds no {WINDOW_ANNOTATION!r} region: "
                           "the driver's window is not marked")
    lo, hi = marks[0]
    # only what ran inside the window counts, for every reader alike
    inside = lambda s, d: s < hi and s + d > lo
    busy_ops = [[o for o in dev if inside(o[2], o[3])] for dev in busy_ops]
    modules = [[m for m in dev if inside(m[1], m[2])] for dev in modules]
    busy, gaps0 = [], []
    for i, dev in enumerate(busy_ops):
        b, gaps = union_length([(s, s + d) for _, _, s, d in dev], lo, hi)
        busy.append(b)
        if i == 0:
            gaps0 = gaps
    return Summary(window_s=hi - lo, busy_s=sum(busy) / len(busy),
                   modules=modules[:chips], ops=busy_ops, host=host, gaps=gaps0)


def module_time(summary: Summary, pattern: str):
    """(executions, total seconds) of the programs whose name matches."""
    rx = re.compile(pattern)
    n, total = 0, 0.0
    for dev in summary.modules:
        for name, _, d in dev:
            if rx.search(name):
                n, total = n + 1, total + d
    return n, total


def op_time(summary: Summary, pattern: str):
    """(events, total seconds) of the device operations whose
    ``"<name> <opcode>"`` matches."""
    rx = re.compile(pattern)
    n, total = 0, 0.0
    for dev in summary.ops:
        for name, opcode, _, d in dev:
            if rx.search(f"{name} {opcode}"):
                n, total = n + 1, total + d
    return n, total


def bucket_of(name: str, opcode: str) -> str:
    text = f"{name} {opcode}"
    for bucket, pat in BUCKETS:
        if re.search(pat, text):
            return bucket
    return "other: " + (opcode or re.sub(r"[.\d]+$", "", name)[:40])


def breakdown(summary: Summary, top: int = 10) -> Dict[str, list]:
    """The contract's ``breakdown``: device operations that took most time
    (by bucket, chip 0) and the longest idle gaps by what the host was in."""
    per: Dict[str, float] = {}
    for name, opcode, _, d in summary.ops[0]:
        if opcode in CONTAINERS:
            continue
        b = bucket_of(name, opcode)
        per[b] = per.get(b, 0.0) + d
    idle: Dict[str, float] = {}
    host = sorted(summary.host, key=lambda e: e[1])
    for g0, g1 in summary.gaps:
        best, cover = "host: no annotated region", 0.0
        for name, s, d in host:
            if s >= g1:
                break
            if name == WINDOW_ANNOTATION:
                continue
            c = min(g1, s + d) - max(g0, s)
            if c > cover:
                best, cover = name, c
        idle[best] = idle.get(best, 0.0) + (g1 - g0)
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(per), "idle_gaps": rank(idle)}
