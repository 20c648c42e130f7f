"""Opt-in ``jax.profiler`` trace annotations, and the pool scheduler's
always-on phase totals.

The spans in :mod:`raft_tpu.obs.trace` time the *host's* view of a
request; correlating them with what the device actually executed needs
``jax.profiler`` annotations in the profiler timeline. Annotating every
dispatch unconditionally would put a profiler call on the hot path, so
this module is a process-wide toggle:

    from raft_tpu.obs import profile
    profile.enable()                      # or RAFT_OBS_PROFILE=1
    ...
    with profile.annotate("serve/pool_step"):
        exec(...)                          # shows up as a named region

Disabled (the default), :func:`annotate` returns a shared no-op context
manager — the cost is one attribute read and a truth test per dispatch.
The annotations pair with ``jax.profiler.trace`` / the TensorBoard
profiler capture (``TrainConfig.profile_port``); nothing here starts a
profiler by itself.

:class:`PhaseTable` is the one phase helper of a loop that keeps its own
timeline (the pool scheduler's): ``table.phase(name)`` is a
:class:`Phase` built once and entered every loop. Each entry adds its
duration and a count to the phase's totals, always; it is a profiler
region when profiling is on, and it appends ``(name, t0, t1)`` to the
table's ``sink`` (the sampled loop's record) when one is set — so one
``with`` names the step on the profiler's clock, on the trace records'
clock and in the totals.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, Optional

__all__ = ["enable", "disable", "enabled", "annotate", "Phase", "PhaseTable"]

_NULL = contextlib.nullcontext()
_on = os.environ.get("RAFT_OBS_PROFILE", "") not in ("", "0", "false")


def enable(on: bool = True) -> None:
    """Turn dispatch-window profiler annotations on (process-wide)."""
    global _on
    _on = bool(on)


def disable() -> None:
    enable(False)


def enabled() -> bool:
    return _on


def annotate(name: str, **args):
    """A named profiler region when enabled, a shared no-op otherwise.
    ``args`` ride the region as arguments (the trace keeps ``name`` as
    the region's name and each argument as a stat of the event)."""
    if not _on:
        return _NULL
    try:
        import jax

        return jax.profiler.TraceAnnotation(name, **args)
    except Exception:  # profiler unavailable: degrade to no-op, never raise
        return _NULL


class Phase:
    """One named step of a loop, built once and entered every time the
    step runs. Entering reads ``time.monotonic()``, leaving reads it again
    and adds the difference to ``total_s`` and one to ``n`` — whatever is
    on or off. With profiling on it is also a region (with ``args``, such
    as the bucket a tick is for); with its table's ``sink`` set it also
    appends ``(name, t0, t1)`` — ``(name, t0, t1, args)`` where it has
    arguments. Neither on: two clock reads and no allocation of its own.
    Not reentrant: a table's phases are flat and entered by one thread
    at a time."""

    __slots__ = ("name", "args", "total_s", "n", "_table", "_t0", "_region")

    def __init__(self, name: str, args: dict, table: "PhaseTable"):
        self.name, self.args, self._table = name, args, table
        self.total_s, self.n = 0.0, 0
        self._t0, self._region = 0.0, None

    def __enter__(self):
        if _on:
            self._region = annotate(self.name, **self.args)
            self._region.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, typ, val, tb):
        t1 = time.monotonic()
        t0 = self._t0
        self.total_s += t1 - t0
        self.n += 1
        sink = self._table.sink
        if sink is not None:
            sink.append(
                (self.name, t0, t1, self.args) if self.args
                else (self.name, t0, t1)
            )
        region = self._region
        if region is None:
            return False
        self._region = None
        return region.__exit__(typ, val, tb)


class PhaseTable:
    """A loop's phases and its totals, counted at full pace: per phase —
    per phase and bucket where it has one — the seconds spent and the
    entries (:class:`Phase`); per loop, the count, the wall time and the
    thread's CPU time (:meth:`turn`, :meth:`cpu`). ``sink`` is the list
    the phases append their spans to while the current loop is sampled,
    ``None`` otherwise. Registered in a :class:`~raft_tpu.obs.metrics
    .MetricsRegistry` as the counter group ``name``
    (``registry.add_group``): :meth:`snapshot` is its flat view."""

    def __init__(self, name: str = "sched"):
        self.name = name
        self.sink: Optional[list] = None
        self.loops = 0
        self.loop_s = self.cpu_s = 0.0
        self._t_loop: Optional[float] = None
        self._cpu_loop: Optional[float] = None
        # name -> bucket (None where it has none) -> Phase
        self._phases: Dict[str, Dict[Optional[str], Phase]] = {}
        self._lock = threading.Lock()  # phase creation vs. snapshots

    def phase(self, name: str, bucket: Optional[str] = None) -> Phase:
        """The phase ``name`` (of ``bucket``), built on first use."""
        by_bucket = self._phases.get(name)
        p = None if by_bucket is None else by_bucket.get(bucket)
        if p is None:
            args = {} if bucket is None else {"bucket": bucket}
            with self._lock:
                p = self._phases.setdefault(name, {}).setdefault(
                    bucket, Phase(name, args, self)
                )
        return p

    def turn(self, now: float) -> None:
        """The top of a loop at ``now`` (``time.monotonic()``): the loop
        that ran since the last turn is counted."""
        if self._t_loop is not None:
            self.loops += 1
            self.loop_s += now - self._t_loop
        self._t_loop = now

    def cpu(self, now: float) -> None:
        """The thread's CPU clock (``time.thread_time()``) read once a
        loop: what it advanced since the last reading is the loop's."""
        if self._cpu_loop is not None:
            self.cpu_s += now - self._cpu_loop
        self._cpu_loop = now

    def _entries(self):
        with self._lock:
            return [
                (name if b is None else f"{name}@{b}", p)
                for name, by_bucket in self._phases.items()
                for b, p in by_bucket.items()
            ]

    def report(self) -> dict:
        """``{"loops", "loop_ms", "cpu_ms", "phases": {name[@bucket]:
        {"ms", "n"}}}`` — ``stats()["sched"]``."""
        return {
            "loops": self.loops,
            "loop_ms": self.loop_s * 1e3,
            "cpu_ms": self.cpu_s * 1e3,
            "phases": {
                key: {"ms": p.total_s * 1e3, "n": p.n}
                for key, p in self._entries()
            },
        }

    def snapshot(self) -> Dict[str, float]:
        """The same numbers flat, as a counter group: ``loops``,
        ``loop_ms``, ``cpu_ms``, and ``<phase>[@bucket]/ms`` and ``/n``."""
        out: Dict[str, float] = {
            "loops": self.loops, "loop_ms": self.loop_s * 1e3,
            "cpu_ms": self.cpu_s * 1e3,
        }
        for key, p in self._entries():
            out[f"{key}/ms"] = p.total_s * 1e3
            out[f"{key}/n"] = p.n
        return out
