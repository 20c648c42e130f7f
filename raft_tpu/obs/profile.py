"""Opt-in ``jax.profiler`` trace annotations around dispatch windows.

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

:func:`phase` is the same region for code that also keeps its own
timeline (the pool scheduler's loop records, ISSUE 27): given a list it
appends ``(name, t0, t1)`` from ``time.monotonic()`` on exit, inside the
profiler region, so one ``with`` names the step on the profiler's clock
and on the trace records' clock. Given ``None`` it is :func:`annotate`.
"""

from __future__ import annotations

import contextlib
import os
import time

__all__ = ["enable", "disable", "enabled", "annotate", "phase"]

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


def annotate(name: str):
    """A named profiler region when enabled, a shared no-op otherwise."""
    if not _on:
        return _NULL
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except Exception:  # profiler unavailable: degrade to no-op, never raise
        return _NULL


class _Phase:
    """A profiler region (when enabled) around a ``(name, t0, t1)``
    tuple appended to ``sink`` on exit."""

    __slots__ = ("_name", "_sink", "_region", "_t0")

    def __init__(self, name: str, sink: list):
        self._name = name
        self._sink = sink

    def __enter__(self):
        self._region = annotate(self._name)
        self._region.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self._sink.append((self._name, self._t0, time.monotonic()))
        return self._region.__exit__(*exc)


def phase(name: str, sink=None):
    """:func:`annotate`, plus a ``(name, t0, t1)`` tuple appended to the
    list ``sink`` when one is given. With ``sink=None`` and profiling off
    this is two truth tests: no clock read, no allocation."""
    if sink is None:
        return annotate(name)
    return _Phase(name, sink)
