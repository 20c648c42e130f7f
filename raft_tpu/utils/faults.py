"""Fault-tolerance primitives: the repo's answer to infrastructure faults.

A 100k-step curriculum stage (train/trainer.py STAGES) spans many hours on
preemptible TPU slices, where the dominant failures are not model bugs but
infra faults: a torn checkpoint after a hard kill, one corrupt sample at
step 80k, a hung collective, a flaky network fetch. This module holds the
shared machinery (docs/failure_model.md maps each fault to its owner):

  * :class:`Watchdog` — heartbeat stall detector armed around blocking
    regions (``step_fn``, ``next(data_iter)``, checkpoint waits); on
    timeout it dumps all-thread stacks via :mod:`faulthandler` and raises
    :class:`StallError` in the main thread, turning a silent infinite hang
    into a diagnosable failure.
  * :class:`DataFaultPolicy` — what the input pipeline does with a sample
    that fails to load: retry transient ``OSError``s with capped
    exponential backoff, quarantine-and-skip deterministic parse errors,
    bounded by a bad-sample budget (``data.pipeline.TrainPipeline``).
  * :func:`retry_transient` — the one backoff loop shared by the data
    pipeline and the pretrained-weights fetch (``models.zoo``).
  * :class:`FaultInjector` / :func:`tear_checkpoint` — deterministic fault
    injection for the chaos suite (``tests/test_faults.py``); every
    recovery path above is exercised by a CPU-only tier-1 test, not just
    claimed.

Nothing here touches the fault-free hot path: the watchdog costs two
attribute writes per guarded region, the data policy engages only on
exceptions, and the injector is never installed outside tests.
"""

from __future__ import annotations

import collections
import dataclasses
import faulthandler
import os
import signal
import socket as _socket
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = [
    "StallError",
    "BadSampleBudgetError",
    "CheckpointRestoreError",
    "DataFaultPolicy",
    "Watchdog",
    "FaultInjector",
    "NetworkFaultInjector",
    "retry_transient",
    "tear_checkpoint",
]


class StallError(RuntimeError):
    """A guarded region stayed blocked past the watchdog timeout."""


class BadSampleBudgetError(RuntimeError):
    """The data pipeline quarantined more distinct samples than allowed."""


class CheckpointRestoreError(RuntimeError):
    """No retained checkpoint restored and validated.

    ``attempts`` is the ``[(step, repr(error)), ...]`` trail of every step
    tried (newest first) so the failure is diagnosable from the message
    alone.
    """

    def __init__(self, msg: str, attempts: Tuple = ()):
        super().__init__(msg)
        self.attempts = tuple(attempts)


# Golden-ratio conjugate: frac(k * phi) is a low-discrepancy sequence in
# [0, 1) — successive retry attempts get well-spread jitter fractions from
# the attempt counter alone, no RNG (ISSUE 16: the same no-RNG-on-hot-paths
# discipline as trace sampling; reconnect storms still decorrelate because
# each retry loop walks the sequence from its own attempt index).
_JITTER_PHI = 0.6180339887498949


def retry_transient(
    fn: Callable[[], Any],
    *,
    attempts: int = 3,
    base_delay: float = 0.5,
    max_delay: float = 8.0,
    transient: Tuple[type, ...] = (OSError, TimeoutError),
    jitter: float = 0.25,
    max_elapsed: Optional[float] = None,
    on_retry: Optional[Callable[[int, BaseException], None]] = None,
    sleep: Callable[[float], None] = time.sleep,
) -> Any:
    """Call ``fn()``, retrying ``transient`` errors with capped exponential
    backoff plus multiplicative jitter. The last failure re-raises; anything
    outside ``transient`` (deterministic parse errors, real bugs) propagates
    immediately.

    The jitter is **deterministic**: attempt ``k`` sleeps
    ``min(base * 2^k, max_delay) * (1 + jitter * frac((k + 1) * phi))`` —
    a counter-derived golden-ratio fraction instead of ``random()``, so
    retry schedules are reproducible in tests and the hot reconnect path
    never touches an RNG. ``max_elapsed`` is a wall-budget on the whole
    loop (connect/reconnect supervision, ISSUE 16): once the elapsed time
    plus the next backoff would cross it, the current failure re-raises
    instead of sleeping — the budget bounds *time*, ``attempts`` bounds
    *tries*, and whichever is hit first ends the loop. This is the one
    backoff implementation for the zoo fetch, the data pipeline, and the
    TCP connect/reconnect path.
    """
    delay = base_delay
    t0 = time.monotonic()
    for attempt in range(attempts):
        try:
            return fn()
        except transient as e:
            if attempt == attempts - 1:
                raise
            pause = min(delay, max_delay) * (
                1.0 + jitter * ((attempt + 1) * _JITTER_PHI % 1.0)
            )
            if max_elapsed is not None and (
                time.monotonic() - t0 + pause > max_elapsed
            ):
                raise
            if on_retry is not None:
                on_retry(attempt, e)
            sleep(pause)
            delay *= 2.0
    raise AssertionError("unreachable")  # pragma: no cover


@dataclasses.dataclass(frozen=True)
class DataFaultPolicy:
    """What the input pipeline does when ``dataset[idx]`` raises.

    * ``transient`` errors (network/filesystem flakes — ``OSError`` and
      subclasses) are retried up to ``max_retries`` extra times with capped
      exponential backoff.
    * ``deterministic`` errors (parse failures — ``ValueError``: bad magic,
      corrupt header, truncated payload) are never retried; the bytes on
      disk will not change.
    * After retries are exhausted (or immediately, for deterministic
      errors): ``mode='skip'`` quarantines the index — it is skipped
      without re-reading on every future draw — and refills the batch slot
      from the index stream; ``mode='raise'`` propagates (fail-fast, the
      pre-fault-policy behavior, still with transient retries).
    * The run fails with :class:`BadSampleBudgetError` once more than
      ``max_bad_samples`` *distinct* samples are quarantined: mass
      corruption is a storage incident, not something to skip through.

    Counters (``data/skipped`` = skipped draws, ``data/retries`` = transient
    retries) surface through the trainer's log boundary.
    """

    mode: str = "skip"  # 'skip' | 'raise'
    max_bad_samples: int = 64
    max_retries: int = 2
    base_delay: float = 0.1
    max_delay: float = 5.0
    transient: Tuple[type, ...] = (OSError,)
    deterministic: Tuple[type, ...] = (ValueError,)

    def __post_init__(self):
        if self.mode not in ("skip", "raise"):
            raise ValueError(
                f"DataFaultPolicy.mode must be 'skip' or 'raise', got {self.mode!r}"
            )


class Watchdog:
    """Heartbeat stall watchdog for blocking host-side regions.

    Usage::

        wd = Watchdog(timeout=300, dump_path="stalls.log")
        with wd.section("train/step"):
            state, metrics = step_fn(state, batch)   # may hang
        ...
        wd.close()

    A daemon thread polls the armed section's deadline. On expiry it dumps
    all-thread stacks via :func:`faulthandler.dump_traceback` (to
    ``dump_path`` when given, else stderr) and interrupts the main thread —
    via a dedicated signal (``SIGUSR1``) whose handler raises
    :class:`StallError` — so an interruptible hang (queue wait, sleep,
    retry loop) becomes a raised, diagnosable error at the stalled call
    site. A hang inside a C extension that never returns to the
    interpreter cannot be unwound from Python; the stack dump (the
    diagnosis) still happens, which is the difference between "the job
    said nothing for six hours" and a pointed bug report.

    Arming/disarming is two attribute writes under a lock — safe to wrap
    around every step. Construct on the main thread (signal handler
    installation); elsewhere it degrades to ``_thread.interrupt_main``.

    **Callback mode** (multi-threaded servers): interrupting the main
    thread is the right escalation for a single-threaded trainer, but in a
    server it would kill the wrong thread. ``section(name,
    on_timeout=cb)`` instead invokes ``cb(name)`` on the watcher thread
    after the stack dump — the serve engine uses this to fail the in-flight
    batch's requests with a typed deadline error while the worker thread
    survives. Pass ``install_handler=False`` to skip signal-handler
    installation entirely for a callback-only watchdog (safe to construct
    off the main thread; plain sections then fall back to
    ``interrupt_main``).
    """

    def __init__(
        self,
        timeout: float,
        *,
        poll: Optional[float] = None,
        dump_path: Optional[str] = None,
        signum: int = signal.SIGUSR1,
        install_handler: bool = True,
        recorder=None,
    ):
        if timeout <= 0:
            raise ValueError(f"timeout must be positive, got {timeout}")
        # optional obs.FlightRecorder (ISSUE 10): every trip records a
        # structured watchdog_trip event AND dumps a postmortem bundle —
        # the 5 s of fault-ladder context before the stall, captured at
        # the moment it still exists
        self.recorder = recorder
        self.timeout = float(timeout)
        self.poll = poll if poll is not None else max(0.05, min(self.timeout / 4.0, 1.0))
        self.dump_path = dump_path
        self.stall_count = 0
        self.last_stall: Optional[str] = None
        self._pending: Optional[str] = None  # stalled-section name, set pre-interrupt
        # (name, deadline, on_timeout-or-None)
        self._armed: Optional[Tuple[str, float, Optional[Callable]]] = None
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._signum = signum
        self._main = threading.main_thread()
        self._old_handler = None
        self._handler_installed = False
        if install_handler:
            try:
                self._old_handler = signal.signal(signum, self._on_signal)
                self._handler_installed = True
            except ValueError:  # not on the main thread
                pass
        self._thread = threading.Thread(
            target=self._watch, name="raft-watchdog", daemon=True
        )
        self._thread.start()

    # -- main-thread side -------------------------------------------------

    def _on_signal(self, signum, frame):
        name = self._pending
        self._pending = None
        if name is None:
            # not our interrupt (external SIGUSR1): defer to the previous
            # handler instead of swallowing it
            if callable(self._old_handler):
                self._old_handler(signum, frame)
            return
        raise StallError(self._message(name))

    def _message(self, name: str) -> str:
        where = self.dump_path or "stderr"
        return (
            f"watchdog: {name!r} stalled for more than {self.timeout:g}s; "
            f"all-thread stacks dumped to {where}"
        )

    @contextmanager
    def section(self, name: str, *, scale: float = 1.0, on_timeout=None):
        """Arm the watchdog around a blocking region.

        ``scale`` stretches the deadline for regions that are legitimately
        slow once (first-step jit compilation, first eval) without loosening
        the steady-state timeout. ``on_timeout`` (callback mode) is invoked
        as ``on_timeout(name)`` on the *watcher* thread instead of
        interrupting the main thread — the worker-thread-safe escalation for
        servers; trainer sections (no callback) behave exactly as before.
        """
        self.beat(name, scale=scale, on_timeout=on_timeout)
        try:
            yield self
        except KeyboardInterrupt:
            # interrupt_main fallback path (no handler installed): convert
            # our own interrupt to the typed error, pass real Ctrl+C through
            pending, self._pending = self._pending, None
            if pending is not None:
                raise StallError(self._message(pending)) from None
            raise
        finally:
            self.disarm()

    def beat(
        self, name: Optional[str] = None, *, scale: float = 1.0, on_timeout=None
    ) -> None:
        """(Re-)arm: push the deadline ``timeout * scale`` seconds out.

        A bare ``beat()`` inside an armed section keeps the section's name
        *and* its callback.
        """
        with self._lock:
            if name is None and self._armed is not None:
                name = self._armed[0]
                if on_timeout is None:
                    on_timeout = self._armed[2]
            self._armed = (
                name or "<unnamed>",
                time.monotonic() + self.timeout * scale,
                on_timeout,
            )

    def disarm(self) -> None:
        with self._lock:
            self._armed = None

    def close(self) -> None:
        """Stop the watcher thread and restore the signal handler."""
        self._stop.set()
        self._thread.join(timeout=5.0)
        if self._handler_installed:
            try:
                signal.signal(self._signum, self._old_handler or signal.SIG_DFL)
            except ValueError:  # pragma: no cover - close() off-main-thread
                pass
            self._handler_installed = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- watcher-thread side ----------------------------------------------

    def _watch(self):
        while not self._stop.wait(self.poll):
            with self._lock:
                armed = self._armed
            if armed is None:
                continue
            name, deadline, on_timeout = armed
            if time.monotonic() < deadline:
                continue
            self.stall_count += 1
            self.last_stall = name
            self._dump_stacks(name)
            if self.recorder is not None:
                try:
                    self.recorder.record(
                        "watchdog_trip", section=name,
                        timeout_s=self.timeout, stalls=self.stall_count,
                    )
                    self.recorder.dump(f"watchdog_trip:{name}")
                except Exception:  # telemetry never masks the stall
                    pass
            if on_timeout is not None:
                # callback mode: escalate on the watcher thread, never
                # interrupt the main thread (it is not the stalled one)
                try:
                    on_timeout(name)
                except Exception:  # a broken callback must not kill the watcher
                    pass
            else:
                self._pending = name
                self._interrupt_main()
            with self._lock:
                # fire once per arm; the next section()/beat() re-arms
                if self._armed is armed:
                    self._armed = None

    def _dump_stacks(self, name: str) -> None:
        header = (
            f"\n=== watchdog: {name!r} exceeded {self.timeout:g}s at "
            f"{time.strftime('%Y-%m-%d %H:%M:%S')}; all-thread stacks ===\n"
        )
        try:
            if self.dump_path:
                os.makedirs(os.path.dirname(self.dump_path) or ".", exist_ok=True)
                with open(self.dump_path, "a") as f:
                    f.write(header)
                    f.flush()
                    faulthandler.dump_traceback(file=f, all_threads=True)
            else:
                sys.stderr.write(header)
                faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        except Exception:  # the dump must never mask the stall itself
            pass

    def _interrupt_main(self) -> None:
        if self._handler_installed and self._main.ident is not None:
            try:
                signal.pthread_kill(self._main.ident, self._signum)
                return
            except (AttributeError, ValueError, OSError):  # pragma: no cover
                pass
        import _thread  # pragma: no cover - non-main-thread fallback

        _thread.interrupt_main()  # pragma: no cover


# ---------------------------------------------------------------------------
# Fault injection (chaos tests)
# ---------------------------------------------------------------------------


class FaultInjector:
    """Deterministic fault injection for the chaos suite.

    Faults are *planned* against named sites keyed by 0-based call index,
    then *installed* with monkeypatch-style ``patch_*`` context managers
    (originals restored on exit — never active outside the ``with``)::

        inj = FaultInjector()
        inj.on("io.read", when=lambda i, path: i % 100 == 7,
               action=ValueError("injected: corrupt sample"))
        inj.on("train.step", when=3, action=0.5)           # 0.5s stall
        inj.on("ckpt.commit", when=2, action=FaultInjector.tear)
        with inj.patch_reads(), inj.patch_step(trainer):
            trainer.run()

    ``action`` may be an exception instance/class (raised), a number
    (seconds slept — latency injection), or a callable taking the site
    context. ``counts``/``fired`` record observed traffic per site.
    """

    def __init__(self):
        self.counts: collections.Counter = collections.Counter()
        self.fired: collections.Counter = collections.Counter()
        self._plans = collections.defaultdict(list)
        self._lock = threading.Lock()

    def on(self, site: str, when, action) -> "FaultInjector":
        """Schedule ``action`` at the matching calls of ``site``.

        ``when``: an int call index, a container of indices, or a
        predicate ``(index, context) -> bool``.
        """
        with self._lock:
            self._plans[site].append((when, action))
        return self

    def fire(self, site: str, ctx: Any = None) -> None:
        """Instrumentation point: count the call, apply any matching plan."""
        with self._lock:
            idx = self.counts[site]
            self.counts[site] = idx + 1
            plans = list(self._plans.get(site, ()))
        for when, action in plans:
            if self._matches(when, idx, ctx):
                with self._lock:
                    self.fired[site] += 1
                self._apply(action, ctx)

    @staticmethod
    def _matches(when, idx: int, ctx) -> bool:
        if callable(when):
            return bool(when(idx, ctx))
        if isinstance(when, int):
            return idx == when
        return idx in when

    @staticmethod
    def _apply(action, ctx) -> None:
        if isinstance(action, BaseException):
            raise action
        if isinstance(action, type) and issubclass(action, BaseException):
            raise action("injected fault")
        if isinstance(action, (int, float)):
            time.sleep(float(action))
            return
        action(ctx)

    @staticmethod
    def tear(ctx) -> None:
        """``ckpt.commit`` action: tear the just-committed checkpoint."""
        manager, step = ctx
        tear_checkpoint(manager.directory, step)

    @staticmethod
    def nan_grads(ctx) -> None:
        """``step.nan_grads`` action: poison the batch so the backward pass
        produces NaN gradients (what a bf16 overflow burst looks like from
        the optimizer's side). Mutates the host-side batch in place."""
        import numpy as np

        img = np.asarray(ctx["image1"], np.float32).copy()
        img[..., :] = np.nan
        ctx["image1"] = img

    @staticmethod
    def nan_flow(ctx) -> None:
        """``infer.nan_flow`` action: poison one serve request's output flow
        (what a numerically pathological input looks like from the engine's
        side). Mutates the per-request flow array in place; pair with a
        ``when`` predicate keyed on ``ctx['rid']`` so the same request stays
        poisoned across the batch pass *and* its single-isolation retry."""
        import numpy as np

        ctx["flow"][...] = np.nan

    @staticmethod
    def replica_dead(ctx) -> None:
        """``router.heartbeat`` action: make one replica's probe report a
        dead worker (``healthy=False``) without touching the engine —
        what a crashed serving process looks like from the router's
        health loop. Mutates the probe's health dict in place; pair with
        a ``when`` predicate keyed on ``ctx['replica']``."""
        ctx["health"]["healthy"] = False

    @staticmethod
    def loss_spike(ctx, keep: int = 1) -> None:
        """``step.loss_spike`` action: concentrate the batch's loss on
        ``keep`` pixels (every other pixel marked invalid) so the
        gradient global-norm jumps while staying FINITE — the grad-norm
        spike the EMA detector must catch.

        Why this stimulus: the masked-mean L1 loss averages per-pixel
        gradients of mixed sign, which largely cancel over a full frame;
        one surviving pixel carries the whole unit weight uncancelled
        (measured on the tiny test model: 7.5x the warmed EMA, 11x at
        init). Blowing the input images out of [-1, 1] does NOT spike the
        gradient — the feature encoder's instance norm makes the network
        nearly scale-invariant and the un-normed context path saturates
        the GRU gates (measured: images x1e4 move the norm < 2x) — and
        neither does scaling the ground-truth flow: the L1 gradient
        magnitude is scale-invariant in the flow error."""
        import numpy as np

        b, h, w = np.shape(ctx["flow"])[:3]
        like = np.asarray(ctx["valid"]) if "valid" in ctx else np.zeros(
            (b, h, w), np.float32
        )
        valid = np.zeros_like(like)
        for i in range(int(keep)):
            valid[i % b, h // 2, (w // 2 + i // b) % w] = 1
        ctx["valid"] = valid

    # -- installation -----------------------------------------------------

    @contextmanager
    def patch_reads(self):
        """Route data-file reads through site ``'io.read'`` (ctx = path).

        Patches both ``data.io`` and the names ``data.datasets`` imported
        from it, so reads through either module are seen.
        """
        from raft_tpu.data import datasets as ds_mod
        from raft_tpu.data import io as io_mod

        def wrap(fn):
            def inner(path, *a, **kw):
                self.fire("io.read", path)
                return fn(path, *a, **kw)

            return inner

        targets = [
            (io_mod, "read_image"), (io_mod, "read_flow"),
            (ds_mod, "read_image"), (ds_mod, "read_flow"),
        ]
        originals = [(mod, name, getattr(mod, name)) for mod, name in targets]
        try:
            for mod, name, orig in originals:
                setattr(mod, name, wrap(orig))
            yield self
        finally:
            for mod, name, orig in originals:
                setattr(mod, name, orig)

    @contextmanager
    def patch_step(self, trainer):
        """Route ``trainer.step_fn`` dispatches through site
        ``'train.step'`` (latency injection: a numeric action stalls the
        host before dispatch, exactly what a hung collective looks like
        from the driver's side)."""
        orig = trainer.step_fn

        def wrapped(state, batch):
            self.fire("train.step")
            return orig(state, batch)

        trainer.step_fn = wrapped
        try:
            yield self
        finally:
            trainer.step_fn = orig

    @contextmanager
    def patch_batches(self, trainer):
        """Route every batch entering ``trainer.step_fn`` through the
        model-fault sites ``'step.nan_grads'`` and ``'step.loss_spike'``
        (ctx = the mutable host batch dict), so NaN-grad bursts and
        grad-norm spikes are injectable without touching device code —
        pair with the :meth:`nan_grads` / :meth:`loss_spike` actions.
        Both sites see every step; plans pick the steps that fault.

        Also wraps ``trainer._make_step_fn`` so the sites survive a
        rollback that re-jits the step (``rollback_lr_scale < 1``) —
        persistent-divergence scenarios keep faulting across rollbacks.

        Fused window dispatch (``TrainConfig.window_size=k > 1``): the
        stacked batch window is split host-side, the sites fire once per
        STEP of the window (same call-index numbering as the per-step
        loop, so one injection plan drives both), and the window is
        restacked — a host round trip that only the injection path (tests)
        ever pays. ``trainer.window_fn`` / ``_make_window_fn`` are wrapped
        the same way as their per-step twins.
        """
        import numpy as np

        orig_step = trainer.step_fn
        orig_make = trainer._make_step_fn

        def fire_sites(batch):
            batch = dict(batch)
            self.fire("step.nan_grads", batch)
            self.fire("step.loss_spike", batch)
            return batch

        def wrap(fn):
            def wrapped(state, batch):
                return fn(state, fire_sites(batch))

            return wrapped

        def wrap_window(fn):
            def wrapped(state, window):
                keys = list(window)
                host = {k: np.asarray(v) for k, v in window.items()}
                k_steps = host[keys[0]].shape[0]
                subs = [
                    fire_sites({k: host[k][i] for k in keys})
                    for i in range(k_steps)
                ]
                window = {
                    k: np.stack([np.asarray(s[k]) for s in subs]) for k in keys
                }
                return fn(state, window)

            return wrapped

        trainer.step_fn = wrap(orig_step)
        trainer._make_step_fn = lambda: wrap(orig_make())
        orig_window = getattr(trainer, "window_fn", None)
        orig_make_window = getattr(trainer, "_make_window_fn", None)
        if orig_window is not None:
            trainer.window_fn = wrap_window(orig_window)
            trainer._make_window_fn = lambda: wrap_window(orig_make_window())
        try:
            yield self
        finally:
            trainer.step_fn = orig_step
            del trainer._make_step_fn  # restore the class method
            if orig_window is not None:
                trainer.window_fn = orig_window
                del trainer._make_window_fn

    @contextmanager
    def patch_engine(self, engine):
        """Route a serve engine's execution seams through the inference
        fault sites:

        * ``'infer.slow_apply'`` — fired before every batch dispatch
          (ctx = ``{'batch': B, 'iters': n, 'stage': s}`` with ``stage``
          one of ``'pair'``/``'encode'``/``'iterate'`` — the pairwise
          fused program and the stream path's two stages — or, for the
          iteration pool, ``'pool_begin'``/``'pool_begin_features'``/
          ``'pool_step'``/``'pool_final'`` — admission, per-tick
          refinement, and retirement dispatches); a
          numeric action stalls the batch thread pre-dispatch (a slow
          compile / contended device from the queue's point of view), an
          exception action models a failed dispatch the worker must
          survive.
        * ``'infer.nan_flow'`` — fired on every per-request output
          (ctx = ``{'rid': id, 'flow': mutable (H, W, 2) array}``); pair
          with the :meth:`nan_flow` action and an rid-keyed ``when`` to
          poison exactly one request through batch pass and single retry.
        """
        import numpy as np

        orig_run = engine._run_batch
        orig_encode = engine._run_encode
        orig_iterate = engine._run_iterate
        orig_req = engine._request_flow
        orig_pool_begin = engine._run_pool_begin
        orig_pool_begin_features = engine._run_pool_begin_features
        orig_pool_step = engine._run_pool_step
        orig_pool_final = engine._run_pool_final

        def run(p1, p2, iters):
            self.fire(
                "infer.slow_apply",
                {"batch": int(p1.shape[0]), "iters": int(iters),
                 "stage": "pair"},
            )
            return orig_run(p1, p2, iters)

        def run_encode(frames):
            self.fire(
                "infer.slow_apply",
                {"batch": int(frames.shape[0]), "iters": 0,
                 "stage": "encode"},
            )
            return orig_encode(frames)

        def run_iterate(f1, f2, ctx, iters):
            self.fire(
                "infer.slow_apply",
                {"batch": int(f1.shape[0]), "iters": int(iters),
                 "stage": "iterate"},
            )
            return orig_iterate(f1, f2, ctx, iters)

        def request_flow(req, flow):
            flow = np.array(flow)  # mutable copy so actions can poison it
            self.fire("infer.nan_flow", {"rid": req.rid, "flow": flow})
            return orig_req(req, flow)

        def run_pool_begin(p1, p2):
            self.fire(
                "infer.slow_apply",
                {"batch": int(p1.shape[0]), "iters": 0,
                 "stage": "pool_begin"},
            )
            return orig_pool_begin(p1, p2)

        def run_pool_begin_features(f1, f2, ctx, init_flow):
            self.fire(
                "infer.slow_apply",
                {"batch": int(f1.shape[0]), "iters": 0,
                 "stage": "pool_begin_features"},
            )
            return orig_pool_begin_features(f1, f2, ctx, init_flow)

        def run_pool_step(state):
            self.fire(
                "infer.slow_apply",
                {"batch": int(state["coords1"].shape[0]), "iters": 1,
                 "stage": "pool_step"},
            )
            return orig_pool_step(state)

        def run_pool_final(coords1, hidden):
            self.fire(
                "infer.slow_apply",
                {"batch": int(coords1.shape[0]), "iters": 0,
                 "stage": "pool_final"},
            )
            return orig_pool_final(coords1, hidden)

        engine._run_batch = run
        engine._run_encode = run_encode
        engine._run_iterate = run_iterate
        engine._request_flow = request_flow
        engine._run_pool_begin = run_pool_begin
        engine._run_pool_begin_features = run_pool_begin_features
        engine._run_pool_step = run_pool_step
        engine._run_pool_final = run_pool_final
        try:
            yield self
        finally:
            engine._run_batch = orig_run
            engine._run_encode = orig_encode
            engine._run_iterate = orig_iterate
            engine._request_flow = orig_req
            engine._run_pool_begin = orig_pool_begin
            engine._run_pool_begin_features = orig_pool_begin_features
            engine._run_pool_step = orig_pool_step
            engine._run_pool_final = orig_pool_final

    @contextmanager
    def patch_router(self, router):
        """Route a :class:`~raft_tpu.serve.ServeRouter`'s seams through
        the horizontal-tier fault sites (ISSUE 9):

        * ``'router.heartbeat'`` — fired per monitor probe, *after* the
          replica's ``health()`` returns (ctx = ``{'replica': id,
          'health': mutable dict}``). Actions: mutate the health dict
          (:meth:`replica_dead` models a crashed worker the router must
          evict), raise (a failing probe), or a number (seconds slept —
          a stalled heartbeat; past ``heartbeat_timeout_s`` the router
          evicts).
        * ``'router.dispatch'`` — fired on the caller's thread just
          before each replica dispatch (ctx = ``{'replica': id, 'kind':
          'pair'|'stream', 'attempt_inflight': n}``). A numeric action
          is a slow replica; an exception models a replica-side dispatch
          failure the router must re-route (counted against the
          replica's error-rate budget).

        The per-engine seams (:meth:`patch_engine`) still compose: patch
        an individual replica's engine to poison flows or stall batches
        *inside* one replica while the router sites watch the tier.
        """
        orig_probe = router._probe_health
        orig_before = router._before_dispatch

        def probe(rep):
            h = orig_probe(rep)
            ctx = {"replica": rep.replica_id, "health": h}
            self.fire("router.heartbeat", ctx)
            return ctx["health"]

        def before_dispatch(rep, kind):
            self.fire(
                "router.dispatch",
                {"replica": rep.replica_id, "kind": kind,
                 "attempt_inflight": rep.inflight},
            )
            return orig_before(rep, kind)

        router._probe_health = probe
        router._before_dispatch = before_dispatch
        try:
            yield self
        finally:
            router._probe_health = orig_probe
            router._before_dispatch = orig_before

    @contextmanager
    def patch_checkpoint_commits(self, manager):
        """Route durable saves through site ``'ckpt.commit'``
        (ctx = ``(manager, step)``). Each save is awaited before firing so
        a ``tear`` action corrupts a fully committed checkpoint — the
        bitrot/partial-flush case Orbax's atomic-commit marker cannot
        catch."""
        orig = manager.save

        def wrapped(step, state, **kw):
            saved = orig(step, state, **kw)
            if saved:
                manager.wait()
                self.fire("ckpt.commit", (manager, step))
            return saved

        manager.save = wrapped
        try:
            yield self
        finally:
            manager.save = orig


class NetworkFaultInjector:
    """An in-process TCP relay with per-direction fault controls (ISSUE 16).

    The network arm of the chaos suite: a client that should be talking to
    ``upstream`` dials the relay's :attr:`endpoint` instead, and every byte
    chunk pumped in either direction passes through a fault gate::

        relay = NetworkFaultInjector("127.0.0.1:9001").start()
        client.connect(relay.endpoint)      # instead of the worker directly
        relay.partition()                   # black-hole both directions
        ...
        relay.heal()                        # bytes flow again

    Controls, per direction (``"c2s"`` client->server, ``"s2c"``
    server->client) via :meth:`set_faults`:

    * ``blackhole`` — swallow chunks silently, **keeping the connection
      open**: the partition the OS will not report. Neither peer sees EOF
      or RST; only application-level keepalives (or a reader deadline) can
      notice. :meth:`partition` / :meth:`heal` toggle it on both
      directions at once.
    * ``delay_s`` — sleep before forwarding each chunk (a slow peer /
      congested path).
    * ``throttle_bps`` — pace forwarding to a byte rate (a thin pipe; a
      large frame arrives, slowly, which is what stalls a mid-frame read).
    * ``duplicate`` — forward each chunk twice (the duplicate-delivery
      case idempotent resubmission must tolerate).
    * ``drop_conn_after`` — hard-close both sockets once this many chunks
      have passed (a mid-flight connection reset — the *loud* failure, for
      contrast with the black hole).

    Faults apply to live connections immediately (the pump checks the
    control block per chunk, under a lock), and every chunk additionally
    fires the ``net.c2s`` / ``net.s2c`` sites of an attached
    :class:`FaultInjector` (ctx = ``{"nbytes": n, "conn": i}``), so
    index-keyed chaos plans compose with the declarative controls: a
    numeric action delays that chunk, an exception action kills the
    connection. Counters (:meth:`stats`) record connections, chunks, and
    bytes forwarded/swallowed per direction — the assertions the partition
    acceptance pins.
    """

    _CHUNK = 1 << 16

    def __init__(
        self,
        upstream: str,
        *,
        injector: Optional["FaultInjector"] = None,
        site: str = "net",
    ):
        host, _, port = str(upstream).rpartition(":")
        self._upstream = (host or "127.0.0.1", int(port))
        self.injector = injector
        self.site = site
        self.endpoint: Optional[str] = None
        self._listener: Optional[_socket.socket] = None
        self._accept_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._faults: Dict[str, Dict[str, Any]] = {
            "c2s": {}, "s2c": {},
        }
        self._conns: list = []  # live (client, server) socket pairs
        self.stats_counters: collections.Counter = collections.Counter()

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "NetworkFaultInjector":
        ls = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
        ls.setsockopt(_socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1)
        ls.bind(("127.0.0.1", 0))
        ls.listen(8)
        ls.settimeout(0.2)
        self._listener = ls
        self.endpoint = "127.0.0.1:%d" % ls.getsockname()[1]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="raft-netfault-accept", daemon=True
        )
        self._accept_thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
        with self._lock:
            conns = list(self._conns)
        for pair in conns:
            self._kill_pair(pair)
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=2.0)

    def __enter__(self) -> "NetworkFaultInjector":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- controls ----------------------------------------------------------

    def set_faults(self, direction: str, **controls) -> None:
        """Replace one direction's fault block (empty = clean relay)."""
        if direction not in ("c2s", "s2c"):
            raise ValueError(
                f"direction must be 'c2s' or 's2c', got {direction!r}"
            )
        with self._lock:
            self._faults[direction] = dict(controls)

    def partition(self) -> None:
        """Black-hole both directions: the connection stays open, bytes
        vanish — what a network partition looks like to both peers."""
        with self._lock:
            for d in ("c2s", "s2c"):
                self._faults[d]["blackhole"] = True
        self.stats_counters["partitions"] += 1

    def heal(self) -> None:
        with self._lock:
            for d in ("c2s", "s2c"):
                self._faults[d].pop("blackhole", None)
        self.stats_counters["heals"] += 1

    def drop_connections(self) -> None:
        """Hard-close every live relayed connection (reset, not
        partition: both peers see the break immediately)."""
        with self._lock:
            conns = list(self._conns)
        for pair in conns:
            self._kill_pair(pair)

    def stats(self) -> Dict[str, int]:
        return {k: int(v) for k, v in self.stats_counters.items()}

    # -- relay machinery ---------------------------------------------------

    def _kill_pair(self, pair) -> None:
        for s in pair:
            try:
                s.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                s.close()
            except OSError:
                pass

    def _accept_loop(self) -> None:
        conn_idx = 0
        while not self._stop.is_set():
            try:
                client, _ = self._listener.accept()
            except (OSError, TypeError):
                if self._stop.is_set():
                    return
                continue
            try:
                server = _socket.create_connection(self._upstream, timeout=5.0)
            except OSError:
                try:
                    client.close()
                except OSError:
                    pass
                self.stats_counters["upstream_refused"] += 1
                continue
            for s in (client, server):
                try:
                    s.setsockopt(
                        _socket.IPPROTO_TCP, _socket.TCP_NODELAY, 1
                    )
                except OSError:
                    pass
            pair = (client, server)
            with self._lock:
                self._conns.append(pair)
            self.stats_counters["conns_accepted"] += 1
            i = conn_idx
            conn_idx += 1
            for direction, src, dst in (
                ("c2s", client, server), ("s2c", server, client),
            ):
                threading.Thread(
                    target=self._pump, args=(direction, src, dst, pair, i),
                    name=f"raft-netfault-{direction}-{i}", daemon=True,
                ).start()

    def _pump(self, direction, src, dst, pair, conn_idx) -> None:
        chunks = 0
        try:
            while not self._stop.is_set():
                try:
                    data = src.recv(self._CHUNK)
                except OSError:
                    break
                if not data:
                    break
                chunks += 1
                with self._lock:
                    faults = dict(self._faults[direction])
                if self.injector is not None:
                    try:
                        self.injector.fire(
                            f"{self.site}.{direction}",
                            {"nbytes": len(data), "conn": conn_idx},
                        )
                    except BaseException:
                        break  # an exception action kills the connection
                if faults.get("blackhole"):
                    self.stats_counters[f"{direction}_swallowed_bytes"] += (
                        len(data)
                    )
                    self.stats_counters[f"{direction}_swallowed_chunks"] += 1
                    continue
                delay = float(faults.get("delay_s", 0.0))
                bps = faults.get("throttle_bps")
                if bps:
                    delay += len(data) / float(bps)
                if delay > 0:
                    time.sleep(delay)
                try:
                    dst.sendall(data)
                    if faults.get("duplicate"):
                        dst.sendall(data)
                        self.stats_counters[
                            f"{direction}_duplicated_chunks"
                        ] += 1
                except OSError:
                    break
                self.stats_counters[f"{direction}_bytes"] += len(data)
                self.stats_counters[f"{direction}_chunks"] += 1
                cap = faults.get("drop_conn_after")
                if cap is not None and chunks >= int(cap):
                    self.stats_counters["conns_dropped"] += 1
                    break
        finally:
            # one side breaking tears down the pair: half-open relays are
            # a *fault to inject deliberately* (blackhole), never a leak
            self._kill_pair(pair)
            with self._lock:
                if pair in self._conns:
                    self._conns.remove(pair)


def tear_checkpoint(directory: str, step: int) -> str:
    """Simulate a torn write: truncate the largest file under the committed
    ``step`` directory to half its size. Returns the mangled path.

    This models the failure Orbax's atomic rename cannot protect against —
    a committed checkpoint whose payload is damaged (lost page-cache flush
    on hard power-off, storage bitrot) — and is what the restore-validation
    fallback chain exists to survive.
    """
    step_dir = os.path.join(str(directory), str(step))
    if not os.path.isdir(step_dir):
        raise FileNotFoundError(step_dir)
    victim, size = None, -1
    for root, _, files in os.walk(step_dir):
        for fn in files:
            p = os.path.join(root, fn)
            s = os.path.getsize(p)
            if s > size:
                victim, size = p, s
    if victim is None:
        raise FileNotFoundError(f"no files under {step_dir}")
    with open(victim, "r+b") as f:
        f.truncate(max(1, size // 2))
    return victim
