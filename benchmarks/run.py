#!/usr/bin/env python3
"""The benchmark's command: one cell, one process, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses off a TPU (or with fewer chips than the cell asks for) before
anything compiles; makes weights and inputs from ``--seed``; builds and
warms the cell's own programs (set-up, ``setup_s``); measures for
``--seconds``; reads memory; frees the program; compares what the window
produced with the plain reference; prints the contract's last line.
With ``--trace 1`` the window is traced (``jax.profiler``) and the
per-layer metrics are reported instead of the end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # "process start": before any heavy import

import argparse
import contextlib
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# a traced run measures at most this long: traces grow by ~20 MB a second
TRACE_WINDOW_CAP_S = 6.0


def log(**kw) -> None:
    print(json.dumps(kw), file=sys.stderr, flush=True)


class Context:
    """What a driver gets: the cell, its configuration, the seed, where
    caches go, and whether this is the traced run."""

    def __init__(self, cell, seed, seconds, trace, cache_root):
        self.cell = cell
        self.config = cell["config"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.cache_root = cache_root
        self.jax_cache_dir = None
        self.memory = None  # a MemoryWatch: drivers call .sample(force=False) as they go
        self.t_start = T_START
        self.log = log

    def window_region(self):
        """What a driver wraps its measured loop in: a profiler region in
        the traced run (it marks the window on the trace's clock)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation("bench/window")


class CompileCounter:
    """Backend compilations, so a run can show none fell in its window."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, _secs: float, **kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.n += 1


class MemoryWatch:
    """What the fullest chip holds, read at instants. On this runtime
    ``bytes_in_use`` counts live arrays and ``bytes_reserved`` the arena
    that programs' temporaries live in (PERF.md, PR 25: an array moves
    only the first, a program's temporaries only the second). One
    ``memory_stats()`` call reads both at the same moment, so their sum
    at that moment is what the chip held then. The peak reported is the
    largest such sum seen, or ``peak_bytes_in_use`` where that is larger:
    never the sum of two peaks that need not coincide."""

    EVERY_S = 0.25

    def __init__(self, devices):
        self.devices = list(devices)
        self.best = {"occupied": 0, "bytes_in_use": 0, "bytes_reserved": 0}
        self.samples = 0
        self._next = 0.0

    def sample(self, force=True):
        now = time.monotonic()
        if not force and now < self._next:
            return
        self._next = now + self.EVERY_S
        self.samples += 1
        for d in self.devices:
            stats = d.memory_stats() or {}
            a = int(stats.get("bytes_in_use", 0))
            r = int(stats.get("bytes_reserved", 0))
            if a + r > self.best["occupied"]:
                self.best = {"occupied": a + r, "bytes_in_use": a, "bytes_reserved": r}

    def block(self):
        self.sample()
        peaks = [d.memory_stats() or {} for d in self.devices]
        in_use = max(int(p.get("peak_bytes_in_use", 0)) for p in peaks)
        reserved = max(int(p.get("peak_bytes_reserved", 0)) for p in peaks)
        return {
            "memory_peak_bytes": max(self.best["occupied"], in_use),
            "memory_at_peak": dict(self.best, samples=self.samples),
            "peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved,
        }


def device_block(jax, memory):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), **memory.block()}


def judge(compared):
    """``{name: (value, limit)}`` -> the result line's ``compared`` block
    and whether every number is within its limit (NaN is not)."""
    out, ok = {}, True
    for name, (value, limit) in compared.items():
        good = bool(value <= limit)  # False for NaN
        out[name] = {"value": float(value), "limit": float(limit), "ok": good}
        ok = ok and good
    return out, ok


def measure(ctx, driver, jax, chips, compiles):
    """Set-up, window, memory, release, comparison. Split from ``main`` so
    the tests can drive it without a chip."""
    ctx.memory = MemoryWatch(jax.devices()[:chips])
    state = driver.setup(ctx)
    ctx.memory.sample()
    setup_s = time.monotonic() - ctx.t_start
    seconds = min(ctx.seconds, TRACE_WINDOW_CAP_S) if ctx.trace else ctx.seconds
    trace_dir = None
    n0 = compiles.n if compiles else 0
    if ctx.trace:
        trace_dir = os.path.join(ctx.cache_root, "trace", ctx.cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            window = driver.window(ctx, state, seconds)
        finally:
            jax.profiler.stop_trace()
    else:
        window = driver.window(ctx, state, seconds)
    window_compiles = (compiles.n - n0) if compiles else 0
    device = device_block(jax, ctx.memory)
    log(phase="memory_stats", at="window_end", stats=jax.devices()[0].memory_stats())
    driver.release(ctx, state)
    compared = {"window_compiles": (float(window_compiles), 0.0)}
    compared.update(driver.compare(ctx, state, window))
    return setup_s, window, device, compared, trace_dir


def run_cell(cell, seed, seconds, trace, peaks, *, cache_root,
             jax_cache_dir=None, compiles=None):
    """Everything after the look for a chip; returns the result line."""
    import jax

    from benchmarks import loader

    chips = int(cell["chips"])
    ctx = Context(cell, seed, seconds, trace, cache_root)
    ctx.jax_cache_dir = jax_cache_dir
    ctx.peaks = peaks
    driver = loader.driver(cell["driver"])
    log(phase="start", workload=cell["name"], seed=seed, cache_dir=jax_cache_dir)

    setup_s, window, device, compared, trace_dir = measure(
        ctx, driver, jax, chips, compiles)

    result = {"correct": False, "attempted": int(window["attempted"]),
              "failed": int(window["failed"]), "metrics": {}, "device": device}
    units = {m["name"]: m["unit"] for m in
             cell["end_to_end"] + cell["per_layer_specs"]}
    if trace:
        from benchmarks.reduce import layer_metrics, trace as trace_mod

        summary = trace_mod.load(trace_dir, chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
        values = layer_metrics.read_all(cell, window, summary, peaks)
        result["breakdown"] = trace_mod.breakdown(summary)
    else:
        values = dict(window["metrics"])
        values["setup_s"] = setup_s
        missing = [m["name"] for m in cell["end_to_end"] if m["name"] not in values]
        if missing:
            raise RuntimeError(f"driver reported no {missing}")
    for name, value in values.items():
        result["metrics"][name] = {"value": float(value), "unit": units[name]}
    result["notes"] = window.get("notes")  # not the driver's: for whoever reads a far-off run
    result["compared"], result["correct"] = judge(compared)
    log(phase="done", setup_s=setup_s, wall_s=time.monotonic() - T_START,
        window=window.get("notes"))
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import loader

    cell = loader.load_cell(args.workload)
    chips = int(cell["chips"])

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"benchmarks/run.py: cell {args.workload} needs {chips} TPU "
              f"chip(s); JAX reports {len(devs)} x {devs[0].platform!r}. "
              "Refusing before compiling anything.", file=sys.stderr)
        return 2
    peaks = loader.peaks(devs[0].device_kind)  # unknown device: an error

    from raft_tpu.utils.runtime import enable_persistent_cache

    cache_root = os.path.join(ROOT, ".bench_cache")
    cache_dir = enable_persistent_cache(os.path.join(cache_root, "jax"))
    result = run_cell(cell, args.seed, args.seconds, args.trace, peaks,
                      cache_root=cache_root, jax_cache_dir=cache_dir,
                      compiles=CompileCounter())
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
