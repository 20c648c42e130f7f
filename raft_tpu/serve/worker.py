"""Process-per-replica serving: one ServeEngine per worker process.

The thread-replica tier (ISSUE 9) shares one GIL and one device across
all N replicas — which is why its 1-vs-N A/B reads as overhead-bounded
parity on a single core instead of a multiply. This module crosses the
process boundary: a :class:`ProcessEngineClient` in the router's process
speaks the exact :class:`~raft_tpu.serve.ServeEngine` surface
(``submit`` / ``submit_frame`` / ``open_stream`` / ``close_stream`` /
``health`` / ``stats`` / ``alerts`` / ``prometheus`` / ``drain`` /
``close``), while the engine itself — model, weights, compiled programs,
worker thread, slot pool — lives in a child **worker process** with its
own interpreter, its own GIL, and its own JAX runtime.

Mechanics:

* **spawn, never fork** — a forked child would inherit the parent's JAX
  state (live XLA client, compiled-program caches, locked runtime
  threads) mid-flight; ``multiprocessing.get_context("spawn")`` gives
  each worker a fresh interpreter that imports JAX itself. The cost of
  re-importing is paid once per worker boot and amortized exactly like a
  replica rebuild already is: the engine factory is pickled into the
  child and boots from the same fleet-shared warmup artifact as a thread
  replica (the fingerprint keys on config + weights, not on process
  identity), so a worker boot is artifact-load + smoke, not a compile
  storm.
* **control channel** — a Unix-domain socket carries length-prefixed
  control messages (:mod:`raft_tpu.serve.ipc`), multiplexed by id, so
  any number of router dispatch threads share one connection. Since
  ISSUE 14 the codec and write discipline are negotiated at the ready
  handshake: ``transport="binary"`` (the default) speaks the compact
  struct-packed binary codec and **coalesces RPCs** — the client drains
  every pending submit into one multi-submit frame per socket write,
  the worker feeds that burst to the engine queue under ONE lock
  acquisition (:meth:`~raft_tpu.serve.ServeEngine.submit_many`) and acks
  completions in batched wakeup frames from a single responder thread;
  ``transport="legacy"`` keeps the PR 13 one-JSON-frame-per-message
  wire behavior (old peers interop — both sides always *decode* both).
  Typed serving errors round-trip by name with their payload
  (``Overloaded``/``Draining`` keep ``retry_after_ms``), so the router's
  shed/migrate/re-route classification is backend-blind.
* **shared-memory tensor transport** — frame tensors cross through
  :class:`~raft_tpu.serve.ipc.ShmRing` slot pools (one per direction),
  referenced from the control messages by ``{slot, shape, dtype}``; the
  sockets never carry pixels. A full ring sheds with the retryable
  ``Overloaded`` carrying an occupancy x EWMA-hold ``retry_after_ms``
  hint — flow control, not failure. On the binary transport the worker
  borrows request tensors as zero-copy ring views just long enough for
  admission to normalize them (then frees the slots in one batched
  message), and the parent exposes :meth:`ProcessEngineClient.submit_refs`
  / :meth:`ProcessEngineClient.reserve_request_slot` so the HTTP front
  door can ``recv_into`` request bodies straight into ring slots.
  Every copy the transport does pay is counted
  (:meth:`ProcessEngineClient.transport_stats`, ``serve_bench``'s
  copies/request) and span-timed (pack / ring_wait / rpc / unpack ride
  the ISSUE 10 tracer when sampling is on).
* **death is a first-class outcome** — the reader thread turns a broken
  control channel (SIGKILL, OOM-kill, a crashed runtime) into
  ``EngineStopped`` for every pending and future call, which is exactly
  the signal the router's dispatch-fault path evicts on immediately;
  respawn goes through the same factory rebuild as any readmission, with
  a brand-new PID, rings, and socket.
* **postmortems cross the boundary** — pass ``dump_dir`` and the worker
  wires a :func:`~raft_tpu.obs.recorder.file_sink` into its engine's
  flight recorder, so watchdog/alert auto-dumps land in the *parent's*
  dump directory; :meth:`ProcessEngineClient.dump_postmortem` pulls a
  bundle on demand (the router calls it best-effort on eviction).

The engine factory must be **picklable** (a module-level function or
class instance, not a closure): spawn re-imports its defining module in
the child and calls it there.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import os
import socket
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from raft_tpu.obs.trace import TraceContext
from raft_tpu.serve import ipc
from raft_tpu.serve.config import ServeConfig
from raft_tpu.serve.errors import EngineStopped, Overloaded, ServeError
from raft_tpu.utils.faults import retry_transient

__all__ = [
    "ProcessEngineClient",
    "RemoteEngineClient",
    "ConnectionSupervisor",
    "RemoteWorkerHandle",
    "start_remote_worker",
    "config_from_wire",
    "serve_result_to_wire",
    "serve_result_to_body",
]

# RPC grace on top of the request's own deadline: the engine enforces
# deadlines itself; the client timeout is only the wedged-worker backstop
# (and surfaces as a replica fault, never as the caller's deadline).
_RPC_GRACE_S = 15.0


def config_from_wire(d: Dict[str, Any]) -> ServeConfig:
    """Rebuild the worker engine's ServeConfig from its JSON form (the
    handshake payload): tuple-typed fields come back from JSON as lists
    and are re-tupled so the parent-side config is a real, validated
    :class:`~raft_tpu.serve.ServeConfig` — not a lookalike namespace."""
    kw = dict(d)
    kw["buckets"] = tuple(tuple(b) for b in kw.get("buckets", ()))
    for f in ("ladder", "batch_ladder"):
        if kw.get(f) is not None:
            kw[f] = tuple(kw[f])
    return ServeConfig(**kw)


def _result_fields(res) -> Dict[str, Any]:
    """The tensor-free half of a ServeResult as a control-message dict —
    shared between the shm-ring wire form (:func:`serve_result_to_wire`)
    and the framed-body remote form (:func:`serve_result_to_body`)."""
    return {
        "rid": res.rid,
        "bucket": list(res.bucket),
        "num_flow_updates": res.num_flow_updates,
        "level": res.level,
        "degraded": res.degraded,
        "latency_ms": res.latency_ms,
        "slow_path": res.slow_path,
        "retried_single": res.retried_single,
        "primed": res.primed,
        "exit_reason": res.exit_reason,
        "trace_id": res.trace_id,
        "residuals": (
            None if res.residuals is None else [float(x) for x in res.residuals]
        ),
        "warm_started": res.warm_started,
        "flow": None,
    }


def serve_result_to_wire(
    res, resp_ring: ipc.ShmRing, *, timeout: float = 5.0,
    trace_rec: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """A ServeResult as a control-message dict, flow via the shm ring.

    ``trace_rec`` (ISSUE 15) piggybacks the worker's sealed trace record
    on the reply — only for requests that arrived with a propagated
    ``trace_id``, so the hot-path result shape (and its struct-packed
    wire fast path) is untouched for everything else.
    """
    d = _result_fields(res)
    if trace_rec is not None:
        d["trace"] = trace_rec
    if res.flow is not None:
        # the response ring tolerates a slow parent for a few seconds
        # before shedding (the parent frees a slot per response it reads)
        d["flow"] = resp_ring.put(
            np.asarray(res.flow, np.float32), timeout=timeout
        )
    return d


def serve_result_to_body(
    res, *, trace_rec: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    """The remote (TCP) form of :func:`serve_result_to_wire`: no shm ring
    crosses a machine boundary, so a tensor-carrying result degrades to a
    framed tensor section (:func:`~raft_tpu.serve.ipc.pack_frames`) under
    the ``body`` key — the same layout the HTTP front door speaks. The
    extra key also keeps the message off the struct-packed record fast
    path, so the binary codec's generic packer carries the bytes."""
    d = _result_fields(res)
    if trace_rec is not None:
        d["trace"] = trace_rec
    if res.flow is not None:
        d["body"] = ipc.pack_frames(
            {}, [np.asarray(res.flow, np.float32)]
        )
    return d


def _serve_result_from_wire(d: Dict[str, Any], flow):
    from raft_tpu.serve.engine import ServeResult

    return ServeResult(
        flow=flow,
        rid=int(d["rid"]),
        bucket=tuple(d["bucket"]),
        num_flow_updates=int(d["num_flow_updates"]),
        level=int(d["level"]),
        degraded=bool(d["degraded"]),
        latency_ms=float(d["latency_ms"]),
        slow_path=bool(d["slow_path"]),
        retried_single=bool(d["retried_single"]),
        primed=bool(d["primed"]),
        exit_reason=str(d["exit_reason"]),
        trace_id=d.get("trace_id"),
        residuals=(
            None if d.get("residuals") is None
            else tuple(d["residuals"])
        ),
        warm_started=bool(d.get("warm_started", False)),
    )


# ---------------------------------------------------------------------------
# Worker process (child side)
# ---------------------------------------------------------------------------


def _ref_slots(msg: Dict[str, Any]) -> List[int]:
    """Slot numbers out of a free message (singular ``slot`` — the
    legacy wire form — or the batched ``slots`` list)."""
    if "slots" in msg:
        return [int(s) for s in msg["slots"]]
    return [int(msg["slot"])]


class _Responder:
    """The worker's completion coalescer (ISSUE 14, binary transport):
    engine done-callbacks post ``(mid, req)`` here from whatever thread
    finished the request; one responder thread drains everything pending
    per wakeup, encodes the results (response tensors into the shm
    ring), and acks the whole burst through the coalescing sender — one
    batched wakeup frame for the parent instead of one write per
    completion. The (possibly blocking) response-ring ``put`` runs HERE,
    never on the engine's batch thread.
    """

    def __init__(
        self,
        sender: ipc.FrameCoalescer,
        resp_ring: ipc.ShmRing,
        *,
        free_flush: int = 8,
    ):
        self._sender = sender
        self._resp_ring = resp_ring
        self._done: List = []
        self._frees: List[int] = []
        self._free_flush = max(1, int(free_flush))
        self._cond = threading.Condition()
        self._stop = False
        self.batches = 0
        self.acks = 0
        self._thread = threading.Thread(
            target=self._run, name="raft-worker-responder", daemon=True
        )
        self._thread.start()

    @staticmethod
    def _trace_rec(req, include_trace: bool):
        """The request's sealed trace record, iff the submit carried a
        propagated trace_id (sealed before done-callbacks fire, so this
        is a plain attribute read on the completion path)."""
        if not include_trace or req.trace is None:
            return None
        return req.trace.record

    def complete(self, mid: int, req, *, include_trace: bool = False) -> None:
        with self._cond:
            self._done.append((mid, req, include_trace))
            self._cond.notify()

    def complete_inline(
        self, mid: int, req, *, include_trace: bool = False
    ) -> None:
        """Encode + ack on the COMPLETING thread — one fewer wakeup on
        the hot path (on one core, thread handoffs are the expensive
        part of the tax). The response-ring put runs with timeout=0:
        when the parent is behind and the ring is full, the completion
        falls back to :meth:`complete`, whose responder thread owns the
        blocking wait — the engine's thread never stalls on a slow
        parent. Pending request-slot frees ride the same frame."""
        if req.error is not None:
            reply = {"id": mid, "error": ipc.encode_error(req.error)}
        else:
            try:
                reply = {
                    "id": mid, "ok": True,
                    "result": serve_result_to_wire(
                        req.result, self._resp_ring, timeout=0.0,
                        trace_rec=self._trace_rec(req, include_trace),
                    ),
                }
            except Overloaded:
                # backpressure: the slow path
                self.complete(mid, req, include_trace=include_trace)
                return
            except BaseException as e:
                reply = {"id": mid, "error": ipc.encode_error(e)}
        with self._cond:
            frees, self._frees = self._frees, []
        msgs: List[Dict[str, Any]] = []
        if frees:
            msgs.append({"op": "free_req", "slots": frees})
        msgs.append(reply)
        try:
            self._sender.send_many(msgs)
        except Exception:
            pass  # a vanished parent is handled by the recv loop
        self.acks += 1

    def add_frees(self, slots: List[int]) -> None:
        """Queue request-ring slots to free — piggybacked onto the next
        reply frame instead of costing their own write + parent wakeup.
        Past ``free_flush`` pending, flush immediately: deferral must
        never starve the parent's allocator under a deep queue."""
        flush = None
        with self._cond:
            self._frees.extend(slots)
            if len(self._frees) >= self._free_flush:
                flush, self._frees = self._frees, []
        if flush is not None:
            try:
                self._sender.send({"op": "free_req", "slots": flush})
            except Exception:
                pass

    def stop(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify()

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._done and not self._stop:
                    self._cond.wait()
                if self._stop and not self._done:
                    return
                batch, self._done = self._done, []
                frees, self._frees = self._frees, []
            replies = []
            if frees:
                replies.append({"op": "free_req", "slots": frees})
            for mid, req, include_trace in batch:
                if req.error is not None:
                    replies.append(
                        {"id": mid, "error": ipc.encode_error(req.error)}
                    )
                else:
                    try:
                        replies.append({
                            "id": mid, "ok": True,
                            "result": serve_result_to_wire(
                                req.result, self._resp_ring,
                                trace_rec=self._trace_rec(
                                    req, include_trace
                                ),
                            ),
                        })
                    except BaseException as e:
                        # a full response ring sheds THIS reply typed and
                        # retryable; the parent re-routes or backs off
                        replies.append(
                            {"id": mid, "error": ipc.encode_error(e)}
                        )
            try:
                self._sender.send_many(replies)
            except Exception:
                pass  # a vanished parent is handled by the recv loop
            self.batches += 1
            self.acks += len(replies)


def _worker_main(spec: Dict[str, Any]) -> None:
    """Child entry point: build + boot the engine, then serve the
    control protocol until the parent hangs up.

    Runs under ``spawn`` in a fresh interpreter; connects *before*
    booting so the parent can distinguish "alive and compiling" from
    "died at import". The parent closing the socket (or dying — the
    socket dies with it) is the worker's shutdown signal, so an orphaned
    worker always exits rather than squatting on a device.
    """
    from concurrent.futures import ThreadPoolExecutor

    sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    sock.connect(spec["socket_path"])
    # the transport the parent asked for; a spec without the key is an
    # old parent, which gets the legacy JSON-per-message wire unchanged
    binary = spec.get("transport") == "binary"
    sender = ipc.FrameCoalescer(sock, binary=binary, batch=binary)

    def send(msg: Dict[str, Any]) -> None:
        try:
            sender.send(msg)
        except Exception:
            pass  # a vanished parent is handled by the recv loop

    engine = None
    try:
        engine = spec["factory"](**(spec.get("overrides") or {}))
        if spec.get("dump_dir"):
            # worker flight-recorder bundles (watchdog trips, page
            # alerts, on-demand eviction dumps) land in the PARENT's
            # dump directory — the postmortem trail survives the worker
            from raft_tpu.obs import file_sink

            engine.recorder.add_sink(file_sink(spec["dump_dir"]))
        engine.start()
    except BaseException as e:  # the parent needs the reason, then die
        send({"op": "ready", "error": repr(e)})
        sock.close()
        os._exit(1)

    req_ring = ipc.ShmRing.attach(**spec["req_ring"])
    resp_ring = ipc.ShmRing.attach(**spec["resp_ring"])
    responder = (
        _Responder(
            sender, resp_ring,
            free_flush=max(4, int(spec["req_ring"]["slots"]) // 4),
        )
        if binary else None
    )
    # trace-propagation negotiation (ISSUE 15): echoed only when the
    # parent requested it — the same zero-negotiation shape as the
    # transport echo. An old parent never asks, an old worker never
    # echoes, and either side missing the key degrades to the PR 14
    # wire: no trace field, no clock handshake, nothing raises.
    propagate = bool(spec.get("trace_propagation", False))
    # qos-propagation negotiation (ISSUE 17): identical shape — the
    # parent asks, this worker echoes, and either side missing the key
    # means submits arrive without priority/tenant fields (PR 16 wire)
    # and the engine serves them at the configured defaults.
    qos_propagate = bool(spec.get("qos_propagation", False))
    ready: Dict[str, Any] = {
        "op": "ready",
        "pid": os.getpid(),
        "transport": "binary" if binary else "legacy",
        "config": dataclasses.asdict(engine.config),
        "boot": engine.stats()["boot"],
    }
    if propagate:
        ready["trace_propagation"] = True
    if qos_propagate:
        ready["qos_propagation"] = True
    send(ready)

    stopping = threading.Event()
    pool = ThreadPoolExecutor(
        max_workers=int(spec.get("rpc_workers", 16)),
        thread_name_prefix="raft-worker-rpc",
    )

    def reply(mid: int, fn: Callable[[], Dict[str, Any]]) -> None:
        try:
            send({"id": mid, "ok": True, "result": fn()})
        except BaseException as e:
            send({"id": mid, "error": ipc.encode_error(e)})

    def _msg_ctx(msg) -> Optional[TraceContext]:
        """The propagated trace context of one submit message (None on
        the PR 14 wire — the field simply never arrives)."""
        tid = msg.get("trace_id")
        return None if tid is None else TraceContext(tid)

    def _traced_wire(res, msg) -> Dict[str, Any]:
        """Result to wire; a propagated request's sealed trace record
        rides the reply (looked up by the id the edge chose)."""
        rec = None
        if msg.get("trace_id") is not None and res.trace_id is not None:
            rec = engine.tracer.find(res.trace_id)
        return serve_result_to_wire(res, resp_ring, trace_rec=rec)

    def h_submit(msg):
        # legacy path: copy out, recycle the request slots immediately,
        # park this pool thread on the result
        im1 = req_ring.get(msg["im1"])
        im2 = req_ring.get(msg["im2"])
        send({"op": "free_req", "slot": msg["im1"]["slot"]})
        send({"op": "free_req", "slot": msg["im2"]["slot"]})
        res = engine.submit(
            im1, im2,
            deadline_ms=msg.get("deadline_ms"),
            num_flow_updates=msg.get("num_flow_updates"),
            trace_ctx=_msg_ctx(msg),
            priority=msg.get("priority"),
            tenant=msg.get("tenant"),
        )
        return _traced_wire(res, msg)

    def h_submit_frame(msg):
        frame = req_ring.get(msg["frame"])
        send({"op": "free_req", "slot": msg["frame"]["slot"]})
        res = engine.submit_frame(
            int(msg["stream_id"]), frame,
            deadline_ms=msg.get("deadline_ms"),
            num_flow_updates=msg.get("num_flow_updates"),
            trace_ctx=_msg_ctx(msg),
            priority=msg.get("priority"),
            tenant=msg.get("tenant"),
        )
        return _traced_wire(res, msg)

    def h_submits_coalesced(msgs: List[Dict[str, Any]]) -> None:
        """Binary transport: one received frame's submit burst, handled
        INLINE on the recv loop (``submit_many`` only admits and
        enqueues — it never blocks on the model — so the hot path pays
        no pool handoff).

        Pairwise submits borrow their tensors as zero-copy ring views,
        feed the engine queue under ONE lock acquisition
        (``engine.submit_many``) — admission normalizes into the
        engine's own buffers, so every borrowed slot is returned in one
        batched free message the moment ``submit_many`` returns, not
        after the model runs. Completions flow through the responder's
        batched acks via done-callbacks: no parked thread per request.
        Stream frames keep per-stream ordering state in the engine and
        ride the pool individually (copied out, slot freed at once).
        """
        items, free_slots = [], []
        for m in msgs:
            if m.get("op") != "submit":
                continue
            mid = m.get("id", -1)
            try:
                im1 = req_ring.get(m["im1"], copy=False)
                im2 = req_ring.get(m["im2"], copy=False)
            except BaseException as e:
                send({"id": mid, "error": ipc.encode_error(e)})
                continue
            free_slots += [int(m["im1"]["slot"]), int(m["im2"]["slot"])]
            traced = m.get("trace_id") is not None
            items.append({
                "image1": im1, "image2": im2,
                "deadline_ms": m.get("deadline_ms"),
                "num_flow_updates": m.get("num_flow_updates"),
                "priority": m.get("priority"),
                "tenant": m.get("tenant"),
                "trace_ctx": _msg_ctx(m),
                "on_done": (
                    lambda req, _mid=mid, _tr=traced:
                    responder.complete_inline(_mid, req, include_trace=_tr)
                ),
            })
        if items:
            try:
                engine.submit_many(items)
            except BaseException as e:  # belt and braces: never silent
                for m in msgs:
                    if m.get("op") == "submit":
                        send({
                            "id": m.get("id", -1),
                            "error": ipc.encode_error(e),
                        })
        if free_slots:
            # admission copied everything; the slots are recyclable NOW
            # — but the message rides the next reply frame (or a bulk
            # flush) instead of buying its own write + parent wakeup
            responder.add_frees(free_slots)
        for m in msgs:
            if m.get("op") == "submit_frame":
                pool.submit(
                    reply, m.get("id", -1), lambda _m=m: h_submit_frame(_m)
                )

    def h_shutdown(msg):
        engine.close(
            graceful=bool(msg.get("graceful", False)),
            timeout=msg.get("timeout", 30.0),
        )
        stopping.set()
        return {"stopped": True}

    handlers: Dict[str, Callable[[Dict[str, Any]], Dict[str, Any]]] = {
        "submit": h_submit,
        "submit_frame": h_submit_frame,
        "open_stream": lambda m: {
            "stream_id": engine.open_stream().stream_id
        },
        "close_stream": lambda m: (
            engine.close_stream(int(m["stream_id"])) or {}
        ),
        "drain": lambda m: {
            "quiesced": engine.drain(timeout=m.get("timeout", 30.0))
        },
        "shutdown": h_shutdown,
        "health": lambda m: engine.health(),
        # clock-offset estimation (ISSUE 15): the parent reads this
        # worker's monotonic clock, brackets it with its own, and takes
        # the RPC round-trip midpoint — the offset that aligns stitched
        # cross-process span timestamps (error bound: +-rtt/2)
        "clock": lambda m: {"t": time.monotonic()},
        "stats": lambda m: engine.stats(),
        "alerts": lambda m: engine.alerts(),
        "prometheus": lambda m: {"text": engine.prometheus()},
        "transport": lambda m: {
            "copies": ipc.copies_snapshot(),
            "rings": {"req": req_ring.stats(), "resp": resp_ring.stats()},
            "sender": sender.stats(),
            "responder_batches": responder.batches if responder else 0,
            "responder_acks": responder.acks if responder else 0,
        },
        "events": lambda m: {
            "events": engine.recorder.events(m.get("kind"))[
                -int(m.get("n", 64)):
            ]
        },
        "traces": lambda m: {"traces": engine.tracer.snapshot()},
        "trace_find": lambda m: {
            "trace": engine.tracer.find(m["trace_id"])
        },
        "dump": lambda m: {
            "reason": engine.recorder.dump(
                m.get("reason", "parent-request")
            )["reason"]
        },
    }
    # blocking ops ride the RPC pool so a slow submit never starves a
    # health probe; introspection runs inline on the recv loop
    _POOLED = {"submit", "submit_frame", "drain", "shutdown"}

    reader = ipc.FrameReader(sock)  # buffered: ~1 syscall per burst
    try:
        while not stopping.is_set():
            try:
                frame = reader.read_msg()
            except ipc.ConnectionClosed:
                break  # parent hung up (or died): shut down with it
            msgs = ipc.iter_messages(frame)
            submits = []
            for msg in msgs:
                op = msg.get("op")
                if op == "free_resp":
                    for s in _ref_slots(msg):
                        resp_ring.free(s)
                    continue
                if binary and op in ("submit", "submit_frame"):
                    submits.append(msg)
                    continue
                fn = handlers.get(op)
                mid = msg.get("id", -1)
                if fn is None:
                    send({"id": mid, "error": ipc.encode_error(
                        ServeError(f"unknown worker op {op!r}")
                    )})
                elif op in _POOLED:
                    pool.submit(reply, mid, lambda m=msg, f=fn: f(m))
                else:
                    reply(mid, lambda m=msg, f=fn: f(m))
            if submits:
                if engine.config.unknown_shape == "reject":
                    # admission + enqueue only — nothing here can block
                    # on the model, so the burst is handled inline with
                    # zero pool handoff (the hot-path default); the
                    # 'slow_path' and 'tiled' arms both run model work
                    # on the submitting thread, so they take the pool
                    h_submits_coalesced(submits)
                else:
                    # a slow_path config may compile/execute inline in
                    # submit_many; keep that off the recv loop
                    pool.submit(h_submits_coalesced, submits)
    finally:
        stopping.set()
        if responder is not None:
            responder.stop()
        try:
            engine.close(graceful=False)
        except Exception:
            pass
        pool.shutdown(wait=False)
        try:
            sock.close()
        except Exception:
            pass
        req_ring.close()
        resp_ring.close()


# ---------------------------------------------------------------------------
# Remote worker (TCP child side, ISSUE 16)
# ---------------------------------------------------------------------------

# Handshakes ride recv_msg under a socket timeout (FrameReader is for the
# steady state only — a mid-frame timeout would lose the partial read).
_REMOTE_HANDSHAKE_TIMEOUT_S = 10.0


class _DedupeTable:
    """Worker-side idempotent-resubmission ledger (ISSUE 16).

    A retry after an ambiguous timeout — the client never learned whether
    its request was executed — is only safe if re-executing is impossible:
    completed replies are cached by request id and **resent verbatim**; an
    id still in flight is dropped (its completion will send). The table is
    scoped to one client *session* (the token minted per
    :class:`RemoteEngineClient`): a reconnect of the same session keeps the
    table (that is the whole point), a new session — a rebuilt client after
    readmission — clears it, so ids restarting from zero can never collide
    with a dead predecessor's.
    """

    def __init__(self, capacity: int = 1024):
        self._capacity = int(capacity)
        self._done: "collections.OrderedDict[int, Dict[str, Any]]" = (
            collections.OrderedDict()
        )
        self._inflight: set = set()
        self._lock = threading.Lock()
        self.session: Optional[str] = None
        self.hits = 0

    def reset(self, session: Optional[str]) -> bool:
        """Bind to a (possibly new) client session; returns True when the
        session resumed (same token — the dedupe history survives)."""
        with self._lock:
            resumed = session is not None and session == self.session
            if not resumed:
                self._done.clear()
                self._inflight.clear()
            self.session = session
            return resumed

    def begin(self, mid: int) -> Tuple[str, Optional[Dict[str, Any]]]:
        """Admit one request id: ``("new", None)`` to execute,
        ``("done", reply)`` to resend the cached reply, or
        ``("inflight", None)`` to drop (the original completion sends)."""
        if mid < 0:
            return "new", None
        with self._lock:
            reply = self._done.get(mid)
            if reply is not None:
                self.hits += 1
                return "done", reply
            if mid in self._inflight:
                self.hits += 1
                return "inflight", None
            self._inflight.add(mid)
            return "new", None

    def finish(self, mid: int, reply: Dict[str, Any]) -> None:
        if mid < 0:
            return
        with self._lock:
            self._inflight.discard(mid)
            self._done[mid] = reply
            while len(self._done) > self._capacity:
                self._done.popitem(last=False)


class _RemoteLink:
    """The remote worker's *current* client connection — a mutable slot
    handlers and completion callbacks send through, so a reconnect swaps
    the socket under them without re-wiring anything. Sends are
    best-effort by the same contract as the unix worker's: a vanished
    (or partitioned) peer re-pulls every reply it missed through the
    dedupe table on resubmission."""

    def __init__(self):
        self._lock = threading.Lock()
        self.conn: Optional[socket.socket] = None
        self.sender: Optional[ipc.FrameCoalescer] = None

    def install(
        self, conn: socket.socket, sender: ipc.FrameCoalescer
    ) -> Optional[socket.socket]:
        """Swap in a new connection; returns the displaced one (the
        caller kills it — its serve thread unblocks on the shutdown)."""
        with self._lock:
            old, self.conn, self.sender = self.conn, conn, sender
        return old if old is not conn else None

    def send(self, msg: Dict[str, Any]) -> None:
        self.send_many((msg,))

    def send_many(self, msgs) -> None:
        with self._lock:
            sender = self.sender
        if sender is None:
            return
        try:
            sender.send_many(msgs)
        except Exception:
            pass

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            sender = self.sender
        return sender.stats() if sender is not None else {}


def _remote_worker_main(spec: Dict[str, Any]) -> None:
    """Remote worker entry point: boot the engine, bind a TCP listener,
    report the endpoint through ``spec["endpoint_file"]``, then serve
    clients — **surviving disconnects**. Unlike the unix worker, whose
    parent-EOF is its death signal, a remote worker's link can drop and
    come back (that is what a partition looks like from here), so the
    engine persists across connections and only two things end the
    process: an explicit ``shutdown`` RPC, or the idle watchdog — no
    inbound traffic (keepalives included) for ``idle_timeout_s`` means
    the peer is gone for good, and self-terminating is what keeps a
    partition from leaking orphan processes squatting on a device.
    """
    from concurrent.futures import ThreadPoolExecutor

    endpoint_file = spec["endpoint_file"]

    def _report(text: str) -> None:
        tmp = endpoint_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(text)
        os.replace(tmp, endpoint_file)  # atomic: never a half-written read

    engine = None
    try:
        engine = spec["factory"](**(spec.get("overrides") or {}))
        if spec.get("dump_dir"):
            from raft_tpu.obs import file_sink

            engine.recorder.add_sink(file_sink(spec["dump_dir"]))
        engine.start()
        listener, endpoint = ipc.listen_tcp(spec.get("host", "127.0.0.1"))
    except BaseException as e:  # the launcher needs the reason, then die
        try:
            _report("ERROR:" + repr(e))
        except Exception:
            pass
        os._exit(1)
    # this worker's bundles carry the wire identity (schema /4): --fleet
    # uses it to tell remote lanes apart and place partition windows
    engine.recorder.transport = "tcp"
    engine.recorder.endpoint = endpoint
    _report(endpoint)

    stopping = threading.Event()
    link = _RemoteLink()
    dedupe = _DedupeTable()
    pool = ThreadPoolExecutor(
        max_workers=int(spec.get("rpc_workers", 16)),
        thread_name_prefix="raft-remote-rpc",
    )
    last_rx = [time.monotonic()]
    idle_timeout = float(spec.get("idle_timeout_s", 60.0))

    def _reply(mid: int, fn: Callable[[], Dict[str, Any]]) -> None:
        verdict, cached = dedupe.begin(mid)
        if verdict == "done":
            link.send(cached)
            return
        if verdict == "inflight":
            return
        try:
            r: Dict[str, Any] = {"id": mid, "ok": True, "result": fn()}
        except BaseException as e:
            r = {"id": mid, "error": ipc.encode_error(e)}
        dedupe.finish(mid, r)
        link.send(r)

    def _msg_ctx(msg) -> Optional[TraceContext]:
        tid = msg.get("trace_id")
        return None if tid is None else TraceContext(tid)

    def _complete(mid: int, req, include_trace: bool) -> None:
        """Engine done-callback: encode (flow into a framed body), cache
        for resubmission, send through whatever link is live NOW. Caching
        before sending closes the loss window — a completion racing a
        disconnect is recoverable the moment the client resubmits."""
        if req.error is not None:
            reply = {"id": mid, "error": ipc.encode_error(req.error)}
        else:
            try:
                rec = (
                    req.trace.record
                    if include_trace and req.trace is not None else None
                )
                reply = {
                    "id": mid, "ok": True,
                    "result": serve_result_to_body(req.result, trace_rec=rec),
                }
            except BaseException as e:
                reply = {"id": mid, "error": ipc.encode_error(e)}
        dedupe.finish(mid, reply)
        link.send(reply)

    def h_submits(msgs: List[Dict[str, Any]]) -> None:
        """One frame's submit burst: dedupe-gate each id, unpack the
        framed tensor bodies as zero-copy views, feed the engine queue
        under one lock acquisition (``submit_many``) — the remote mirror
        of the unix worker's coalesced path, minus the rings."""
        items: List[Dict[str, Any]] = []
        mids: List[int] = []
        for m in msgs:
            if m.get("op") != "submit":
                continue
            mid = m.get("id", -1)
            verdict, cached = dedupe.begin(mid)
            if verdict == "done":
                link.send(cached)
                continue
            if verdict == "inflight":
                continue
            try:
                _, arrays = ipc.unpack_frames(m["body"], copy=False)
                im1, im2 = arrays
            except BaseException as e:
                r = {"id": mid, "error": ipc.encode_error(e)}
                dedupe.finish(mid, r)
                link.send(r)
                continue
            traced = m.get("trace_id") is not None
            mids.append(mid)
            items.append({
                "image1": im1, "image2": im2,
                "deadline_ms": m.get("deadline_ms"),
                "num_flow_updates": m.get("num_flow_updates"),
                "priority": m.get("priority"),
                "tenant": m.get("tenant"),
                "trace_ctx": _msg_ctx(m),
                "on_done": (
                    lambda req, _mid=mid, _tr=traced:
                    _complete(_mid, req, _tr)
                ),
            })
        if items:
            try:
                engine.submit_many(items)
            except BaseException as e:  # belt and braces: never silent
                for mid in mids:
                    r = {"id": mid, "error": ipc.encode_error(e)}
                    dedupe.finish(mid, r)
                    link.send(r)

    def h_submit_frame(msg):
        _, arrays = ipc.unpack_frames(msg["body"], copy=False)
        res = engine.submit_frame(
            int(msg["stream_id"]), arrays[0],
            deadline_ms=msg.get("deadline_ms"),
            num_flow_updates=msg.get("num_flow_updates"),
            trace_ctx=_msg_ctx(msg),
            priority=msg.get("priority"),
            tenant=msg.get("tenant"),
        )
        rec = None
        if msg.get("trace_id") is not None and res.trace_id is not None:
            rec = engine.tracer.find(res.trace_id)
        return serve_result_to_body(res, trace_rec=rec)

    def h_shutdown(msg):
        engine.close(
            graceful=bool(msg.get("graceful", False)),
            timeout=msg.get("timeout", 30.0),
        )
        stopping.set()
        try:
            listener.close()  # breaks the accept loop
        except Exception:
            pass
        return {"stopped": True}

    handlers: Dict[str, Callable[[Dict[str, Any]], Dict[str, Any]]] = {
        "submit_frame": h_submit_frame,
        "open_stream": lambda m: {
            "stream_id": engine.open_stream().stream_id
        },
        "close_stream": lambda m: (
            engine.close_stream(int(m["stream_id"])) or {}
        ),
        "drain": lambda m: {
            "quiesced": engine.drain(timeout=m.get("timeout", 30.0))
        },
        "shutdown": h_shutdown,
        "health": lambda m: engine.health(),
        "clock": lambda m: {"t": time.monotonic()},
        "stats": lambda m: engine.stats(),
        "alerts": lambda m: engine.alerts(),
        "prometheus": lambda m: {"text": engine.prometheus()},
        "transport": lambda m: {
            "copies": ipc.copies_snapshot(),
            "rings": {},
            "sender": link.stats(),
            "dedupe_hits": dedupe.hits,
        },
        "events": lambda m: {
            "events": engine.recorder.events(m.get("kind"))[
                -int(m.get("n", 64)):
            ]
        },
        "traces": lambda m: {"traces": engine.tracer.snapshot()},
        "trace_find": lambda m: {
            "trace": engine.tracer.find(m["trace_id"])
        },
        "dump": lambda m: {
            "reason": engine.recorder.dump(
                m.get("reason", "parent-request")
            )["reason"]
        },
    }
    _POOLED_REMOTE = {"submit_frame", "drain", "shutdown"}

    def _serve_conn(conn: socket.socket) -> None:
        """One client connection: handshake, then the frame loop. A drop
        returns to the accept loop with the engine intact — server-side
        reconnect-and-resume."""
        conn.settimeout(_REMOTE_HANDSHAKE_TIMEOUT_S)
        try:
            hello = ipc.recv_msg(conn)
        except Exception:
            try:
                conn.close()
            except OSError:
                pass
            return
        if hello.get("op") != "hello" or hello.get("transport") != "binary":
            # the remote wire mandates the binary codec: the JSON
            # fallback's default=repr would corrupt raw tensor bodies
            try:
                ipc.send_msg(conn, {
                    "op": "ready",
                    "error": "remote transport requires the binary codec "
                             "hello (got %r)" % (hello.get("op"),),
                })
                conn.close()
            except Exception:
                pass
            return
        conn.settimeout(None)
        last_rx[0] = time.monotonic()
        resumed = dedupe.reset(hello.get("session"))
        propagate = bool(hello.get("trace_propagation", False))
        qos_propagate = bool(hello.get("qos_propagation", False))
        ready: Dict[str, Any] = {
            "op": "ready",
            "pid": os.getpid(),
            "transport": "binary",
            "config": dataclasses.asdict(engine.config),
            "boot": engine.stats()["boot"],
            "endpoint": endpoint,
            "resumed": resumed,
        }
        if propagate:
            ready["trace_propagation"] = True
        if qos_propagate:
            ready["qos_propagation"] = True
        try:
            ipc.send_msg(conn, ready)
        except Exception:
            try:
                conn.close()
            except OSError:
                pass
            return
        # install only AFTER the ready is on the wire, so a completion
        # racing the handshake can never interleave with it; the
        # displaced connection (a half-open victim the OS never closed)
        # is shut down here, which also unblocks its serve thread
        sender = ipc.FrameCoalescer(conn, binary=True, batch=True)
        old = link.install(conn, sender)
        if old is not None:
            try:
                old.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                old.close()
            except OSError:
                pass
        engine.recorder.record(
            "net_connect", endpoint=endpoint, resumed=resumed
        )
        reader = ipc.FrameReader(conn)
        try:
            while not stopping.is_set():
                try:
                    frame = reader.read_msg()
                except Exception:
                    return  # link dropped; the engine persists
                last_rx[0] = time.monotonic()
                submits: List[Dict[str, Any]] = []
                for msg in ipc.iter_messages(frame):
                    op = msg.get("op")
                    if op == "submit":
                        submits.append(msg)
                        continue
                    fn = handlers.get(op)
                    mid = msg.get("id", -1)
                    if fn is None:
                        link.send({"id": mid, "error": ipc.encode_error(
                            ServeError(f"unknown worker op {op!r}")
                        )})
                    elif op in _POOLED_REMOTE:
                        pool.submit(_reply, mid, lambda m=msg, f=fn: f(m))
                    else:
                        _reply(mid, lambda m=msg, f=fn: f(m))
                if submits:
                    if engine.config.unknown_shape == "reject":
                        h_submits(submits)
                    else:
                        # 'slow_path'/'tiled' can block on model work:
                        # keep the recv loop free
                        pool.submit(h_submits, submits)
        finally:
            engine.recorder.record("net_disconnect", endpoint=endpoint)

    def _idle_watch() -> None:
        """Self-termination on sustained keepalive loss: every inbound
        frame (keepalive pings included) refreshes ``last_rx``; silence
        past the budget means the peer is partitioned away or dead, and
        an unreachable worker must die rather than orphan a device."""
        while not stopping.wait(min(1.0, idle_timeout / 4.0)):
            if time.monotonic() - last_rx[0] > idle_timeout:
                engine.recorder.record(
                    "net_idle_exit", idle_timeout_s=idle_timeout
                )
                try:
                    engine.recorder.dump("remote-idle-exit")
                except Exception:
                    pass
                try:
                    engine.close(graceful=False)
                except Exception:
                    pass
                os._exit(0)

    threading.Thread(
        target=_idle_watch, name="raft-remote-idle", daemon=True
    ).start()
    listener.settimeout(0.5)
    try:
        while not stopping.is_set():
            try:
                conn, _ = listener.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(
                target=_serve_conn, args=(conn,),
                name="raft-remote-serve", daemon=True,
            ).start()
    finally:
        stopping.set()
        try:
            listener.close()
        except Exception:
            pass
        try:
            engine.close(graceful=False)
        except Exception:
            pass
        pool.shutdown(wait=False)
        os._exit(0)


class RemoteWorkerHandle:
    """The launcher's ownership token for one remote worker process.

    A remote worker's lifetime belongs to whoever started it — NOT to the
    router (eviction only disconnects the link; readmission redials the
    same endpoint and finds the same engine). Terminate through this
    handle (or let the worker's idle watchdog do it)."""

    def __init__(self, proc, endpoint: str, tmpdir: str):
        self.proc = proc
        self.endpoint = endpoint
        self._tmpdir = tmpdir

    @property
    def pid(self) -> Optional[int]:
        return self.proc.pid

    def is_alive(self) -> bool:
        return self.proc.is_alive()

    def terminate(self) -> None:
        if self.proc.is_alive():
            self.proc.terminate()
            self.proc.join(timeout=5.0)
            if self.proc.is_alive():
                self.proc.kill()
                self.proc.join(timeout=5.0)
        if self._tmpdir:
            try:
                ep = os.path.join(self._tmpdir, "endpoint")
                if os.path.exists(ep):
                    os.remove(ep)
                os.rmdir(self._tmpdir)
            except OSError:
                pass
            self._tmpdir = ""

    def __enter__(self) -> "RemoteWorkerHandle":
        return self

    def __exit__(self, *exc) -> None:
        self.terminate()


def _refuse_if_parent_holds_tpu() -> None:
    """One process per chip: a parent that has initialised JAX on the TPU
    holds it, and a spawned worker that needs it would fail or hang —
    refuse up front with the reason. No-op on CPU and in a parent that
    has stayed off JAX."""
    from raft_tpu.utils.runtime import holds_tpu

    if holds_tpu():
        raise ServeError(
            "cannot spawn a worker process: this process has initialised "
            "JAX on the TPU and holds the chip, so a child that needs it "
            "would fail or hang (one process per chip) — spawn workers "
            "from a parent that stays off JAX, or use the thread backend"
        )


def start_remote_worker(
    factory: Callable[..., Any],
    overrides: Optional[Dict[str, Any]] = None,
    *,
    boot_timeout_s: float = 300.0,
    host: str = "127.0.0.1",
    rpc_workers: int = 16,
    dump_dir: Optional[str] = None,
    idle_timeout_s: float = 60.0,
) -> RemoteWorkerHandle:
    """Spawn a TCP remote worker and wait for its endpoint.

    The worker binds an ephemeral port and reports ``host:port`` through
    a file (atomic rename), the one channel that exists before the wire
    does. In a real multi-host deployment the worker runs under its own
    supervisor on the remote box and the endpoint travels out of band;
    this launcher is the loopback stand-in with identical semantics.
    """
    import multiprocessing as mp

    _refuse_if_parent_holds_tpu()
    tmpdir = tempfile.mkdtemp(prefix="raft-remote-")
    ep_file = os.path.join(tmpdir, "endpoint")
    spec = {
        "factory": factory,
        "overrides": dict(overrides or {}),
        "endpoint_file": ep_file,
        "host": host,
        "rpc_workers": int(rpc_workers),
        "dump_dir": dump_dir,
        "idle_timeout_s": float(idle_timeout_s),
    }
    ctx = mp.get_context("spawn")  # never fork a live JAX runtime
    try:
        proc = ctx.Process(
            target=_remote_worker_main, args=(spec,), daemon=True
        )
        proc.start()
    except Exception as e:
        raise ServeError(
            f"failed to spawn remote worker (the engine factory must be "
            f"picklable): {e!r}"
        ) from e
    deadline = time.monotonic() + float(boot_timeout_s)
    text = ""
    while True:
        if os.path.exists(ep_file):
            with open(ep_file) as f:
                text = f.read().strip()
            if text:
                break
        if not proc.is_alive():
            # one last read: the worker may have reported and exited
            if os.path.exists(ep_file):
                with open(ep_file) as f:
                    text = f.read().strip()
                if text:
                    break
            raise ServeError(
                f"remote worker exited during boot (code {proc.exitcode})"
            )
        if time.monotonic() > deadline:
            proc.terminate()
            raise ServeError(
                f"remote worker boot exceeded {boot_timeout_s}s"
            )
        time.sleep(0.05)
    if text.startswith("ERROR:"):
        proc.join(timeout=5.0)
        raise ServeError(f"remote worker engine boot failed: {text[6:]}")
    return RemoteWorkerHandle(proc, text, tmpdir)


# ---------------------------------------------------------------------------
# Parent side
# ---------------------------------------------------------------------------


class _RemoteTracer:
    """Read-only view of the worker engine's tracer (postmortem path:
    never raises — a dead worker simply contributes no traces)."""

    def __init__(self, client: "ProcessEngineClient"):
        self._client = client

    def snapshot(self):
        # the worker engine's request traces, plus this client's local
        # 'transport'-kind traces (pack/ring_wait/rpc spans, ISSUE 14) —
        # one stream, so phase breakdowns and postmortems see both.
        # Deduplicated by trace_id (ISSUE 15 fix): under propagation a
        # sampled request exists both as the worker's record and as a
        # stitched parent-side record under the SAME id — returning both
        # double-counted its phases in serve_phase_breakdown. The richer
        # record (more spans) wins.
        from raft_tpu.obs.trace import dedupe_traces

        tx = getattr(self._client, "_txtracer", None)
        local = tx.snapshot() if tx is not None else []
        try:
            worker = self._client._call("traces", timeout=10.0)["traces"]
        except Exception:
            worker = []
        return dedupe_traces(worker + local)

    def find(self, trace_id: str):
        try:
            return self._client._call(
                "trace_find", {"trace_id": trace_id}, timeout=10.0
            )["trace"]
        except Exception:
            return None


class _RemoteRecorder:
    """Read-only view of the worker engine's flight-recorder ring."""

    def __init__(self, client: "ProcessEngineClient"):
        self._client = client

    def events(self, kind: Optional[str] = None, n: int = 64):
        try:
            return self._client._call(
                "events", {"kind": kind, "n": n}, timeout=10.0
            )["events"]
        except Exception:
            return []


class ProcessEngineClient:
    """The parent-side half of one worker process, shaped like an engine.

    Drop-in for the surface :class:`~raft_tpu.serve.replica.Replica` and
    :class:`~raft_tpu.serve.router.ServeRouter` drive, so the router's
    dispatch/eviction/drain machinery is backend-blind. Lifecycle
    mirrors the engine: construct (cheap), :meth:`start` (spawn + boot +
    handshake), serve, :meth:`drain` / :meth:`close`. After the worker
    dies — for any reason — every call raises ``EngineStopped``; the
    recovery path is a rebuild through the replica factory, exactly like
    a wedged thread engine.
    """

    def __init__(
        self,
        factory: Callable[..., Any],
        overrides: Optional[Dict[str, Any]] = None,
        *,
        boot_timeout_s: float = 300.0,
        ring_slots: int = 32,
        slot_bytes: int = 16 * 1024 * 1024,
        rpc_workers: int = 16,
        dump_dir: Optional[str] = None,
        health_ttl_s: float = 0.02,
        transport: str = "binary",
        trace_propagation: bool = True,
        qos_propagation: bool = True,
    ):
        if transport not in ("binary", "legacy"):
            raise ValueError(
                f"transport must be 'binary' or 'legacy', got {transport!r}"
            )
        self._factory = factory
        self._overrides = dict(overrides or {})
        self._boot_timeout_s = float(boot_timeout_s)
        self._ring_slots = int(ring_slots)
        self._slot_bytes = int(slot_bytes)
        self._rpc_workers = int(rpc_workers)
        self._dump_dir = dump_dir
        # dispatch-scoring freshness vs control-channel traffic dial —
        # a worker_options knob since ISSUE 14 (hits/misses counted)
        self.health_ttl_s = float(health_ttl_s)
        self._requested_transport = transport
        self.transport = transport  # the negotiated one, post-handshake
        # trace propagation (ISSUE 15): requested in the worker spec,
        # echoed in the ready handshake; False until the worker confirms
        # (and the PR 14-wire A/B / back-compat arm when disabled here).
        self._requested_propagation = bool(trace_propagation)
        self.trace_propagation = False
        # qos propagation (ISSUE 17): same handshake shape — requested
        # in the spec, echoed in ready, False until confirmed; when off,
        # priority/tenant are stripped before the wire and the worker
        # serves at its configured defaults (PR 16 peers degrade clean).
        self._requested_qos = bool(qos_propagation)
        self.qos_propagation = False
        # worker monotonic clock minus ours, estimated from the clock
        # RPC round-trip midpoint post-handshake (re-estimated on every
        # start(), i.e. on reconnect); 0 until estimated. The stitcher
        # uses it to align absorbed worker spans; rtt/2 bounds its error.
        self.clock_offset_s = 0.0
        self.clock_rtt_s: Optional[float] = None
        self.config: Optional[ServeConfig] = None
        self.boot: Dict[str, Any] = {}
        self.pid: Optional[int] = None
        self.tracer = _RemoteTracer(self)
        self.recorder = _RemoteRecorder(self)
        self._proc = None
        self._sock: Optional[socket.socket] = None
        self._sender: Optional[ipc.FrameCoalescer] = None
        self._tmpdir: Optional[str] = None
        self._req_ring: Optional[ipc.ShmRing] = None
        self._resp_ring: Optional[ipc.ShmRing] = None
        self._pending: Dict[int, Dict[str, Any]] = {}
        self._plock = threading.Lock()
        self._ids = itertools.count()
        self._reader: Optional[threading.Thread] = None
        self._started = False
        self._dead = False
        self._dead_reason = "worker not started"
        self._health_cache: Optional[Dict[str, Any]] = None
        self._health_t = 0.0
        self.health_cache_hits = 0
        self.health_cache_misses = 0
        # transport spans (pack / ring_wait / rpc / unpack): bounded
        # per-span sample rings feeding transport_stats() quantiles
        self._span_ms: Dict[str, Any] = {
            name: collections.deque(maxlen=512)
            for name in ("pack", "ring_wait", "rpc", "unpack")
        }
        self._txtracer = None  # obs tracer, built once sampling is known
        self.msgs_received = 0
        self.frames_received = 0
        self.bytes_received = 0
        # response-ring frees piggyback on the next outgoing call frame
        # (binary transport) instead of buying their own socket write;
        # past the flush threshold they go out on their own anyway so
        # deferral never starves the worker's response allocator
        self._resp_frees: List[int] = []
        self._resp_free_lock = threading.Lock()
        self._resp_free_flush = max(4, self._ring_slots // 4)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ProcessEngineClient":
        """Spawn the worker, wait for its engine to boot, handshake."""
        if self._started and not self._dead:
            return self
        if self._dead and self._proc is not None:
            raise EngineStopped(
                f"worker died ({self._dead_reason}); build a new one"
            )
        import multiprocessing as mp

        _refuse_if_parent_holds_tpu()
        self._tmpdir = tempfile.mkdtemp(prefix="raft-worker-")
        path = os.path.join(self._tmpdir, "ctl.sock")
        listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        listener.bind(path)
        listener.listen(1)
        listener.settimeout(30.0)
        self._req_ring = ipc.ShmRing(self._slot_bytes, self._ring_slots)
        self._resp_ring = ipc.ShmRing(self._slot_bytes, self._ring_slots)
        spec = {
            "socket_path": path,
            "factory": self._factory,
            "overrides": self._overrides,
            "req_ring": self._req_ring.geometry(),
            "resp_ring": self._resp_ring.geometry(),
            "dump_dir": self._dump_dir,
            "rpc_workers": self._rpc_workers,
            "transport": self._requested_transport,
        }
        if self._requested_propagation:
            spec["trace_propagation"] = True
        if self._requested_qos:
            spec["qos_propagation"] = True
        ctx = mp.get_context("spawn")  # never fork a live JAX runtime
        try:
            self._proc = ctx.Process(
                target=_worker_main, args=(spec,), daemon=True
            )
            self._proc.start()
        except Exception as e:
            listener.close()
            self._teardown_transport()
            raise ServeError(
                f"failed to spawn worker process (the engine factory must "
                f"be picklable — a module-level function or class "
                f"instance, not a closure): {e!r}"
            ) from e
        try:
            conn, _ = listener.accept()
        except socket.timeout:
            self._kill_process()
            self._teardown_transport()
            raise ServeError(
                "worker process never connected (died at import?)"
            )
        finally:
            listener.close()
        self._sock = conn
        try:
            ready = self._wait_ready(conn)
        except Exception:
            self._kill_process()
            self._teardown_transport()
            raise
        if "error" in ready:
            self._kill_process()
            self._teardown_transport()
            raise ServeError(f"worker engine boot failed: {ready['error']}")
        self.pid = int(ready["pid"])
        # transport negotiation: the worker echoes what it will speak; a
        # ready without the key is an old worker — fall back to the
        # legacy JSON-per-message wire (both sides always decode both)
        self.transport = (
            ready.get("transport", "legacy")
            if self._requested_transport == "binary" else "legacy"
        )
        # a ready without the echo is a PR 14 worker: no trace field on
        # the wire, no clock handshake — spans degrade to the parent-
        # side (transport) view, nothing raises
        self.trace_propagation = self._requested_propagation and bool(
            ready.get("trace_propagation", False)
        )
        self.qos_propagation = self._requested_qos and bool(
            ready.get("qos_propagation", False)
        )
        self._sender = ipc.FrameCoalescer(
            conn, binary=self.transport == "binary",
            batch=self.transport == "binary",
        )
        self.config = config_from_wire(ready["config"])
        self.boot = dict(ready.get("boot", {}))
        # transport traces ride the same sampling dial as the engine's
        # own request traces (ISSUE 10); rate 0 = off, zero overhead
        from raft_tpu.obs import Tracer

        self._txtracer = Tracer(
            self.config.trace_sample_rate, prefix="x", capacity=128
        )
        self._dead = False
        self._started = True
        self._reader = threading.Thread(
            target=self._read_loop, name="raft-worker-client-reader",
            daemon=True,
        )
        self._reader.start()
        if self.trace_propagation:
            self._estimate_clock_offset()
        return self

    def _estimate_clock_offset(self) -> None:
        """Cross-process monotonic-clock alignment (ISSUE 15): read the
        worker's clock, bracket it with ours, take the round-trip
        midpoint. Best of 3 round trips (tightest rtt = tightest error
        bound); best-effort — an old worker without the RPC leaves the
        offset at 0 and stitching degrades gracefully."""
        best_rtt = None
        for _ in range(3):
            try:
                t0 = time.monotonic()
                tw = float(self._call("clock", timeout=5.0)["t"])
                t1 = time.monotonic()
            except Exception:
                return
            rtt = t1 - t0
            if best_rtt is None or rtt < best_rtt:
                best_rtt = rtt
                self.clock_offset_s = tw - (t0 + t1) / 2.0
        self.clock_rtt_s = best_rtt

    def _wait_ready(self, conn: socket.socket) -> Dict[str, Any]:
        """Poll for the ready message while watching the process: a boot
        can legitimately take minutes (compile fallback), but a dead
        child must fail fast, not eat the whole boot timeout."""
        deadline = time.monotonic() + self._boot_timeout_s
        conn.settimeout(1.0)
        try:
            while True:
                try:
                    msg = ipc.recv_msg(conn)
                except socket.timeout:
                    if not self._proc.is_alive():
                        raise ServeError(
                            f"worker process exited during boot (code "
                            f"{self._proc.exitcode})"
                        )
                    if time.monotonic() > deadline:
                        self._kill_process()
                        raise ServeError(
                            f"worker boot exceeded {self._boot_timeout_s}s"
                        )
                    continue
                except ipc.ConnectionClosed:
                    raise ServeError(
                        f"worker closed the channel during boot (code "
                        f"{self._proc.exitcode})"
                    )
                if msg.get("op") == "ready":
                    return msg
        finally:
            conn.settimeout(None)

    def is_alive(self) -> bool:
        return (
            self._proc is not None
            and self._proc.is_alive()
            and not self._dead
        )

    def drain(self, *, timeout: Optional[float] = 30.0) -> bool:
        res = self._call(
            "drain", {"timeout": timeout},
            timeout=(timeout or 30.0) + _RPC_GRACE_S,
        )
        # read-your-writes: the next health() must see draining=True,
        # not a pre-drain TTL-cached snapshot
        self._health_cache = None
        return bool(res["quiesced"])

    def stop(self) -> None:
        self.close(graceful=False)

    def close(
        self, graceful: bool = False, *, timeout: Optional[float] = 30.0
    ) -> None:
        """Shut the worker down (gracefully drains in the child when
        asked), then make sure the PID is really gone and the transport
        is reclaimed. Safe on an already-dead worker."""
        if self._started and not self._dead:
            try:
                self._call(
                    "shutdown", {"graceful": graceful, "timeout": timeout},
                    timeout=(timeout or 30.0) + _RPC_GRACE_S,
                )
            except Exception:
                pass  # a worker too broken to ack still gets killed below
        self._mark_dead("worker stopped")
        if self._proc is not None:
            self._proc.join(timeout=10.0)
            self._kill_process()
        if self._sock is not None:
            try:
                self._sock.close()
            except Exception:
                pass
            self._sock = None
        self._teardown_transport()

    def _kill_process(self) -> None:
        proc = self._proc
        if proc is None or not proc.is_alive():
            return
        proc.terminate()
        proc.join(timeout=5.0)
        if proc.is_alive():
            proc.kill()
            proc.join(timeout=5.0)

    def _teardown_transport(self) -> None:
        for ring in (self._req_ring, self._resp_ring):
            if ring is not None:
                ring.close()
        self._req_ring = self._resp_ring = None
        if self._tmpdir:
            try:
                sockpath = os.path.join(self._tmpdir, "ctl.sock")
                if os.path.exists(sockpath):
                    os.remove(sockpath)
                os.rmdir(self._tmpdir)
            except OSError:
                pass
            self._tmpdir = None

    def __enter__(self) -> "ProcessEngineClient":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- RPC plumbing ------------------------------------------------------

    def _mark_dead(self, reason: str) -> None:
        if self._dead:
            return
        self._dead = True
        self._dead_reason = reason
        self._health_cache = None
        with self._plock:
            pending = list(self._pending.values())
            self._pending.clear()
        for slot in pending:
            slot["error"] = {"type": "EngineStopped", "msg": reason}
            slot["ev"].set()

    def _read_loop(self) -> None:
        """Demultiplex worker responses to their waiting callers; copy
        response tensors out of the shm ring and recycle the slots (one
        batched free message per received frame — the read-side mirror
        of the send coalescer). A broken channel — the worker died —
        fails everything pending with ``EngineStopped`` (the router's
        immediate-eviction signal)."""
        reader = ipc.FrameReader(self._sock)
        try:
            while True:
                frame = reader.read_msg()
                self.frames_received = reader.frames
                self.bytes_received = reader.bytes
                free_slots: List[int] = []
                msgs = ipc.iter_messages(frame)
                self.msgs_received += len(msgs)
                for msg in msgs:
                    if msg.get("op") == "free_req":
                        if self._req_ring is not None:
                            for s in _ref_slots(msg):
                                self._req_ring.free(s)
                        continue
                    with self._plock:
                        slot = self._pending.pop(msg.get("id"), None)
                    if slot is None:
                        continue
                    if "error" in msg:
                        slot["error"] = msg["error"]
                    else:
                        result = msg.get("result") or {}
                        ref = result.get("flow")
                        if isinstance(ref, dict) and not slot.get("lease"):
                            t0 = time.monotonic()
                            result = dict(result)
                            result["flow"] = self._resp_ring.get(ref)
                            slot["unpack_s"] = time.monotonic() - t0
                            free_slots.append(int(ref["slot"]))
                        slot["result"] = result
                    slot["ev"].set()
                if free_slots:
                    self._queue_resp_frees(free_slots)
        except Exception:
            self._mark_dead("worker control channel lost")

    def _queue_resp_frees(self, slots: List[int]) -> None:
        """Defer response-slot frees onto the next outgoing call frame;
        flush standalone once enough accumulate (or immediately on the
        legacy transport, which has no piggyback discipline)."""
        if self.transport != "binary":
            try:
                self._sender.send({"op": "free_resp", "slots": slots})
            except Exception:
                pass
            return
        flush = None
        with self._resp_free_lock:
            self._resp_frees.extend(slots)
            if len(self._resp_frees) >= self._resp_free_flush:
                flush, self._resp_frees = self._resp_frees, []
        if flush is not None:
            try:
                self._sender.send({"op": "free_resp", "slots": flush})
            except Exception:
                pass

    def _take_resp_frees(self) -> List[Dict[str, Any]]:
        with self._resp_free_lock:
            if not self._resp_frees:
                return []
            frees, self._resp_frees = self._resp_frees, []
        return [{"op": "free_resp", "slots": frees}]

    def _free_resp_slot(self, slot: int) -> None:
        """Return a leased response slot to the worker (best-effort: a
        dead worker's ring died with it)."""
        try:
            self._queue_resp_frees([int(slot)])
        except Exception:
            pass

    def _call(
        self,
        op: str,
        payload: Optional[Dict[str, Any]] = None,
        *,
        timeout: float = 30.0,
        lease_flow: bool = False,
    ) -> Dict[str, Any]:
        """One multiplexed RPC. ``lease_flow`` leaves a tensor-carrying
        result's ``flow`` as the raw shm ref instead of copying it out —
        the caller maps the view and frees the slot itself (the front
        door's write-from-the-ring-view path)."""
        if not self._started:
            raise EngineStopped("worker is not running (call start())")
        if self._dead:
            raise EngineStopped(self._dead_reason)
        mid = next(self._ids)
        slot: Dict[str, Any] = {"ev": threading.Event()}
        if lease_flow:
            slot["lease"] = True
        with self._plock:
            self._pending[mid] = slot
        msg = dict(payload or {}, id=mid, op=op)
        try:
            # pending response-slot frees ride this same frame for free
            self._sender.send_many(self._take_resp_frees() + [msg])
        except Exception as e:
            with self._plock:
                self._pending.pop(mid, None)
            self._mark_dead(f"worker send failed: {e!r}")
            raise EngineStopped(self._dead_reason) from e
        if not slot["ev"].wait(timeout):
            with self._plock:
                self._pending.pop(mid, None)
            # NOT the caller's deadline (the engine raises that itself,
            # typed, over the wire): a silent worker is a replica fault
            # the router should re-route around and eventually evict
            raise ServeError(
                f"worker rpc {op!r} timed out after {timeout:.0f}s "
                f"(wedged worker?)"
            )
        if "error" in slot:
            raise ipc.decode_error(slot["error"])
        if "unpack_s" in slot:
            self._span_ms["unpack"].append(slot["unpack_s"] * 1e3)
        return slot["result"]

    # -- the engine surface ------------------------------------------------

    def _effective_deadline(self, deadline_ms: Optional[float]) -> float:
        return (
            deadline_ms
            if deadline_ms is not None
            else self.config.default_deadline_ms
        )

    def _record_spans(
        self, t0: float, t1: float, t2: float, spans: Dict[str, float],
        *, kind: str, ok: bool,
        trace_ctx: Optional[TraceContext] = None,
    ) -> None:
        """One request's transport spans into the sample rings and —
        when sampling is on — the local tracer, whose 'transport'-kind
        traces join :meth:`tracer.snapshot` next to the worker's own
        request traces (one phase-breakdown surface).

        A propagated request (``trace_ctx`` carrying the live edge
        trace, ISSUE 15) stitches its transport spans straight into the
        edge trace instead — under its ONE trace_id, so the request is
        never double-counted across the local and edge rings."""
        ring_wait_s = spans.get("ring_wait_s", 0.0)
        pack_s = max(0.0, (t1 - t0) - ring_wait_s)
        self._span_ms["pack"].append(pack_s * 1e3)
        self._span_ms["ring_wait"].append(ring_wait_s * 1e3)
        self._span_ms["rpc"].append((t2 - t1) * 1e3)
        if trace_ctx is not None and trace_ctx.trace is not None:
            tr = trace_ctx.trace
            tr.add_span("pack", t0, t0 + pack_s, proc="transport")
            if ring_wait_s:
                tr.add_span("ring_wait", t0 + pack_s, t1, proc="transport")
            tr.add_span("rpc", t1, t2, proc="transport")
            return
        tracer = self._txtracer
        if tracer is None:
            return
        tr = tracer.start(kind, t_start=t0)
        if tr is None:
            return
        tr.add_span("pack", t0, t0 + pack_s)
        if ring_wait_s:
            tr.add_span("ring_wait", t0 + pack_s, t1)
        tr.add_span("rpc", t1, t2)
        tr.finish(ok=ok)

    def _wire_trace_id(
        self, trace_ctx: Optional[TraceContext]
    ) -> Optional[str]:
        """The trace_id to put on the wire — only when the worker echoed
        trace_propagation (a PR 14 worker never sees the field)."""
        if trace_ctx is None or not self.trace_propagation:
            return None
        return trace_ctx.trace_id

    def _wire_qos(
        self, msg: Dict[str, Any],
        priority: Optional[str], tenant: Optional[str],
    ) -> None:
        """Put QoS identity on the wire — only when the worker echoed
        qos_propagation (a PR 16 worker never sees the fields; its
        engine serves everything at the configured defaults)."""
        if not self.qos_propagation:
            return
        if priority is not None:
            msg["priority"] = priority
        if tenant is not None:
            msg["tenant"] = tenant

    def _absorb_worker_trace(
        self, res: Dict[str, Any], trace_ctx: Optional[TraceContext]
    ) -> None:
        """Stitch the reply-piggybacked worker trace record into the
        edge trace, clock-aligned, under a worker-<pid> lane."""
        if trace_ctx is None:
            return
        rec = res.get("trace")
        if rec:
            trace_ctx.absorb(
                rec, proc=f"worker-{self.pid}",
                t_offset_s=self.clock_offset_s,
            )

    def submit(
        self,
        image1,
        image2,
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
    ):
        if self._dead:
            raise EngineStopped(self._dead_reason)
        eff = self._effective_deadline(deadline_ms)
        spans: Dict[str, float] = {}
        t0 = time.monotonic()
        r1 = self._req_ring.put(np.asarray(image1), spans=spans)
        try:
            r2 = self._req_ring.put(np.asarray(image2), spans=spans)
        except BaseException:
            self._req_ring.free(r1["slot"])
            raise
        t1 = time.monotonic()
        msg = {
            "im1": r1,
            "im2": r2,
            "deadline_ms": deadline_ms,
            "num_flow_updates": num_flow_updates,
        }
        tid = self._wire_trace_id(trace_ctx)
        if tid is not None:
            msg["trace_id"] = tid
        self._wire_qos(msg, priority, tenant)
        try:
            res = self._call(
                "submit", msg, timeout=eff / 1e3 + _RPC_GRACE_S,
            )
        except BaseException:
            self._record_spans(
                t0, t1, time.monotonic(), spans, kind="transport",
                ok=False, trace_ctx=trace_ctx,
            )
            raise
        self._record_spans(
            t0, t1, time.monotonic(), spans, kind="transport", ok=True,
            trace_ctx=trace_ctx,
        )
        self._absorb_worker_trace(res, trace_ctx)
        return _serve_result_from_wire(res, res.get("flow"))

    # -- zero-copy seams (ISSUE 14: the front door's socket->shm path) -----

    @property
    def transport_zero_copy(self) -> bool:
        """Whether callers may reserve request slots and submit by ref
        (the front door checks this before choosing its read path)."""
        return self._started and not self._dead

    def reserve_request_slot(self, nbytes: int) -> Tuple[int, memoryview]:
        """Claim one request-ring slot and hand back its writable view;
        the caller fills it (``recv_into``) and submits the ref with
        :meth:`submit_refs` — no intermediate bytes object ever exists.
        Sheds typed/retryable exactly like :meth:`ShmRing.put`."""
        if self._dead:
            raise EngineStopped(self._dead_reason)
        slot = self._req_ring.reserve(int(nbytes))
        return slot, self._req_ring.slot_view(slot, int(nbytes))

    def release_request_slot(self, slot: int) -> None:
        """Abandon a reserved slot (error paths only — a submitted ref
        is freed by the worker)."""
        if self._req_ring is not None:
            self._req_ring.free(int(slot))

    def submit_refs(
        self,
        ref1: Dict[str, Any],
        ref2: Dict[str, Any],
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        lease_flow: bool = False,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
    ):
        """Submit a pair whose tensors are ALREADY in the request ring
        (reserved + filled by the caller). With ``lease_flow`` the
        result's ``flow`` is a zero-copy view into the response ring and
        a ``release()`` callable is returned alongside — call it after
        the bytes leave (the front door writes the HTTP response from
        the ring view, then releases)."""
        if self._dead:
            raise EngineStopped(self._dead_reason)
        eff = self._effective_deadline(deadline_ms)
        t1 = time.monotonic()
        msg = {
            "im1": ref1,
            "im2": ref2,
            "deadline_ms": deadline_ms,
            "num_flow_updates": num_flow_updates,
        }
        tid = self._wire_trace_id(trace_ctx)
        if tid is not None:
            msg["trace_id"] = tid
        self._wire_qos(msg, priority, tenant)
        try:
            res = self._call(
                "submit", msg,
                timeout=eff / 1e3 + _RPC_GRACE_S,
                lease_flow=lease_flow,
            )
        except BaseException:
            self._record_spans(
                t1, t1, time.monotonic(), {}, kind="transport", ok=False,
                trace_ctx=trace_ctx,
            )
            raise
        self._record_spans(
            t1, t1, time.monotonic(), {}, kind="transport", ok=True,
            trace_ctx=trace_ctx,
        )
        self._absorb_worker_trace(res, trace_ctx)
        if not lease_flow:
            return _serve_result_from_wire(res, res.get("flow"))
        return self._leased_result(res)

    def submit_frame_ref(
        self,
        stream_id: int,
        ref: Dict[str, Any],
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        lease_flow: bool = False,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
    ):
        """Stream-frame mirror of :meth:`submit_refs`."""
        if self._dead:
            raise EngineStopped(self._dead_reason)
        eff = self._effective_deadline(deadline_ms)
        msg = {
            "stream_id": int(stream_id),
            "frame": ref,
            "deadline_ms": deadline_ms,
            "num_flow_updates": num_flow_updates,
        }
        tid = self._wire_trace_id(trace_ctx)
        if tid is not None:
            msg["trace_id"] = tid
        self._wire_qos(msg, priority, tenant)
        res = self._call(
            "submit_frame", msg,
            timeout=eff / 1e3 + _RPC_GRACE_S,
            lease_flow=lease_flow,
        )
        self._absorb_worker_trace(res, trace_ctx)
        if not lease_flow:
            return _serve_result_from_wire(res, res.get("flow"))
        return self._leased_result(res)

    def _leased_result(self, res: Dict[str, Any]):
        """(result, release) for a lease_flow call: flow stays a view
        into the response ring until release() sends the slot home."""
        ref = res.get("flow")
        if not isinstance(ref, dict):
            return _serve_result_from_wire(res, None), (lambda: None)
        view = self._resp_ring.get(ref, copy=False)
        released = []

        def release():
            if not released:
                released.append(True)
                self._free_resp_slot(ref["slot"])

        return _serve_result_from_wire(res, view), release

    def open_stream(self):
        from raft_tpu.serve.engine import StreamSession

        res = self._call("open_stream", timeout=10.0)
        return StreamSession(self, int(res["stream_id"]))

    def submit_frame(
        self,
        stream_id: int,
        frame,
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
    ):
        if self._dead:
            raise EngineStopped(self._dead_reason)
        eff = self._effective_deadline(deadline_ms)
        spans: Dict[str, float] = {}
        t0 = time.monotonic()
        ref = self._req_ring.put(np.asarray(frame), spans=spans)
        t1 = time.monotonic()
        msg = {
            "stream_id": int(stream_id),
            "frame": ref,
            "deadline_ms": deadline_ms,
            "num_flow_updates": num_flow_updates,
        }
        tid = self._wire_trace_id(trace_ctx)
        if tid is not None:
            msg["trace_id"] = tid
        self._wire_qos(msg, priority, tenant)
        try:
            res = self._call(
                "submit_frame", msg, timeout=eff / 1e3 + _RPC_GRACE_S,
            )
        except BaseException:
            self._record_spans(
                t0, t1, time.monotonic(), spans, kind="transport",
                ok=False, trace_ctx=trace_ctx,
            )
            raise
        self._record_spans(
            t0, t1, time.monotonic(), spans, kind="transport", ok=True,
            trace_ctx=trace_ctx,
        )
        self._absorb_worker_trace(res, trace_ctx)
        return _serve_result_from_wire(res, res.get("flow"))

    def close_stream(self, stream_id: int) -> None:
        self._call("close_stream", {"stream_id": int(stream_id)}, timeout=10.0)

    def health(self) -> dict:
        """The worker engine's own health dict, briefly cached
        (``health_ttl_s``, a worker_options knob): the router's monitor
        maintains its score vector from this, and one RPC per probe
        would put the control channel on the hot path. Cache hits and
        misses are counted through the transport stats block."""
        now = time.monotonic()
        cached = self._health_cache
        if cached is not None and now - self._health_t < self.health_ttl_s:
            self.health_cache_hits += 1
            return cached
        self.health_cache_misses += 1
        h = self._call("health", timeout=10.0)
        self._health_cache, self._health_t = h, time.monotonic()
        return h

    def transport_stats(self, *, include_worker: bool = False) -> dict:
        """The client-side transport ledger: negotiated codec, coalescer
        write stats, receive counts, ring stats (copies, occupancy, hold
        EWMA), health-cache hits/misses, and pack/ring_wait/rpc/unpack
        span quantiles. ``include_worker`` additionally RPCs the worker
        for its own side (best-effort; ``None`` when it cannot answer).
        """
        def q(name):
            xs = list(self._span_ms[name])
            if not xs:
                return {"n": 0, "p50_ms": None, "p99_ms": None}
            return {
                "n": len(xs),
                "p50_ms": round(float(np.percentile(xs, 50)), 4),
                "p99_ms": round(float(np.percentile(xs, 99)), 4),
            }

        out: Dict[str, Any] = {
            "transport": self.transport,
            # trace propagation + clock alignment (ISSUE 15): whether
            # the worker echoed the capability, and the handshake-
            # estimated cross-process monotonic offset with its rtt
            # (the stitching error bound is rtt/2)
            "trace_propagation": self.trace_propagation,
            "qos_propagation": self.qos_propagation,
            "clock_offset_ms": self.clock_offset_s * 1e3,
            "clock_rtt_ms": (
                None if self.clock_rtt_s is None else self.clock_rtt_s * 1e3
            ),
            "health_ttl_s": self.health_ttl_s,
            "health_cache_hits": self.health_cache_hits,
            "health_cache_misses": self.health_cache_misses,
            "sender": self._sender.stats() if self._sender else {},
            "msgs_received": self.msgs_received,
            "frames_received": self.frames_received,
            "bytes_received": self.bytes_received,
            "rings": {
                "req": self._req_ring.stats() if self._req_ring else {},
                "resp": self._resp_ring.stats() if self._resp_ring else {},
            },
            "spans": {n: q(n) for n in self._span_ms},
        }
        if include_worker:
            try:
                out["worker"] = self._call("transport", timeout=10.0)
            except Exception:
                out["worker"] = None
        return out

    def stats(self) -> dict:
        """The worker engine's stats tree — byte-identical key set to a
        thread engine's — plus one parent-side ``transport`` block (the
        ISSUE 14 ledger; tooling that wants the pure engine schema pops
        it, and the schema pins cover both)."""
        stats = self._call("stats", timeout=30.0)
        stats["transport"] = self.transport_stats()
        return stats

    def alerts(self) -> dict:
        return self._call("alerts", timeout=10.0)

    def prometheus(self) -> str:
        return self._call("prometheus", timeout=10.0)["text"]

    def dump_postmortem(self, reason: str) -> bool:
        """Ask the worker to dump its flight recorder through its sinks
        (with ``dump_dir`` set, that lands a bundle file in the parent's
        dump directory). Best-effort: False when the worker is gone."""
        try:
            self._call("dump", {"reason": reason}, timeout=5.0)
            return True
        except Exception:
            return False


# ---------------------------------------------------------------------------
# Remote link (TCP parent side, ISSUE 16)
# ---------------------------------------------------------------------------


class ConnectionSupervisor:
    """Owns one remote link end to end: dial, keepalive, reconnect.

    TCP's failure modes never all announce themselves — a black-holed
    partition drops packets without closing anything, so neither the
    reader's EOF nor the OS will report a half-open link. The supervisor
    closes that gap at the application layer:

    * **connect** — dial + handshake under a capped-exponential-backoff
      retry budget (:func:`~raft_tpu.utils.faults.retry_transient`, the
      fleet's one backoff implementation: deterministic counter-derived
      jitter, ``max_elapsed`` cap);
    * **keepalive** — periodic ``clock`` pings (zero new wire surface:
      the ISSUE 15 clock RPC doubles as liveness) with a consecutive-miss
      budget, the only reliable half-open detector;
    * **reconnect-and-resume** — on link loss, kill the socket (which
      unblocks the reader), redial under the retry budget, resend every
      pending RPC verbatim (the worker's dedupe table makes that safe),
      and only after the budget is spent mark the client dead — the typed
      ``EngineStopped`` the router evicts on immediately.

    Every transition lands in the client's link flight recorder
    (``net_connect`` / ``net_disconnect`` / ``net_keepalive_miss`` /
    ``net_reconnect`` / ``net_reconnect_failed``) so a postmortem bundle
    shows the partition window, not just its aftermath.
    """

    UP = "up"
    RECONNECTING = "reconnecting"
    DEAD = "dead"

    def __init__(
        self,
        client: "RemoteEngineClient",
        endpoint: str,
        *,
        connect_timeout_s: float = 5.0,
        keepalive_interval_s: float = 1.0,
        keepalive_timeout_s: float = 2.0,
        keepalive_misses: int = 3,
        reconnect_attempts: int = 6,
        reconnect_base_delay_s: float = 0.05,
        reconnect_max_delay_s: float = 1.0,
        reconnect_max_elapsed_s: float = 8.0,
    ):
        self._client = client
        self.endpoint = str(endpoint)
        self._connect_timeout_s = float(connect_timeout_s)
        self._keepalive_interval_s = float(keepalive_interval_s)
        self._keepalive_timeout_s = float(keepalive_timeout_s)
        self._keepalive_misses = max(1, int(keepalive_misses))
        self._reconnect_attempts = max(1, int(reconnect_attempts))
        self._reconnect_base_delay_s = float(reconnect_base_delay_s)
        self._reconnect_max_delay_s = float(reconnect_max_delay_s)
        self._reconnect_max_elapsed_s = float(reconnect_max_elapsed_s)
        self.state = self.UP
        self.generation = 0          # link generation: bumps per (re)connect
        self.connects = 0
        self.reconnects = 0
        self.disconnects = 0
        self.keepalive_misses_total = 0
        self._misses = 0
        self._lock = threading.Lock()
        self._nudge = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- dialing -----------------------------------------------------------

    def _dial_once(self) -> Tuple[socket.socket, Dict[str, Any]]:
        """One dial + hello/ready handshake (socket timeout scoped to the
        handshake; the steady-state socket is blocking, deadline-free —
        per-RPC deadlines live at the client's pending-event wait)."""
        sock = ipc.dial_tcp(self.endpoint, timeout=self._connect_timeout_s)
        try:
            sock.settimeout(self._connect_timeout_s)
            hello: Dict[str, Any] = {
                "op": "hello",
                "transport": "binary",
                "session": self._client._session,
            }
            if self._client._requested_propagation:
                hello["trace_propagation"] = True
            if self._client._requested_qos:
                hello["qos_propagation"] = True
            ipc.send_msg(sock, hello)
            deadline = time.monotonic() + self._connect_timeout_s
            while True:
                ready = ipc.recv_msg(sock)
                if ready.get("op") == "ready":
                    break
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"no ready from {self.endpoint} within "
                        f"{self._connect_timeout_s}s"
                    )
            if "error" in ready:
                raise ServeError(
                    f"remote worker refused the handshake: {ready['error']}"
                )
            sock.settimeout(None)
            return sock, ready
        except BaseException:
            try:
                sock.close()
            except OSError:
                pass
            raise

    def connect(self) -> Tuple[socket.socket, Dict[str, Any]]:
        """Initial connect under the retry budget (capped exponential
        backoff + deterministic jitter). Raises when the budget is spent;
        the caller (``start``) surfaces that as a failed replica boot."""
        sock, ready = retry_transient(
            self._dial_once,
            attempts=self._reconnect_attempts,
            base_delay=self._reconnect_base_delay_s,
            max_delay=self._reconnect_max_delay_s,
            max_elapsed=self._reconnect_max_elapsed_s,
            transient=(OSError, TimeoutError),
            on_retry=lambda k, e: self._client._link_event(
                "net_connect_retry", attempt=k, error=repr(e)
            ),
        )
        with self._lock:
            self.state = self.UP
            self.generation += 1
            self.connects += 1
            self._misses = 0
        return sock, ready

    # -- lifecycle ---------------------------------------------------------

    def start_loop(self) -> None:
        self._thread = threading.Thread(
            target=self._run, name="raft-link-supervisor", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._nudge.set()

    def link_lost(self, generation: int, reason: str) -> None:
        """Demote the link (reader thread, keepalive, or a failed send
        calls this). Generation-gated: a stale reader noticing its own
        long-dead socket cannot demote the healed link."""
        with self._lock:
            if (
                self._stop.is_set()
                or self.state != self.UP
                or generation != self.generation
            ):
                return
            self.state = self.RECONNECTING
            self.disconnects += 1
        self._client._on_link_down(reason)
        self._nudge.set()

    # -- the supervision loop ----------------------------------------------

    def _run(self) -> None:
        while not self._stop.is_set():
            if self.state == self.UP:
                self._nudge.wait(self._keepalive_interval_s)
                self._nudge.clear()
                if self._stop.is_set():
                    return
                if self.state == self.UP:
                    self._ping()
            elif self.state == self.RECONNECTING:
                self._reconnect()
            else:  # DEAD
                return

    def _ping(self) -> None:
        gen = self.generation
        try:
            self._client._call("clock", timeout=self._keepalive_timeout_s)
            self._misses = 0
        except EngineStopped:
            return  # closed/dead client: the loop exits via _stop
        except BaseException:
            self._misses += 1
            self.keepalive_misses_total += 1
            self._client._link_event(
                "net_keepalive_miss", misses=self._misses,
                budget=self._keepalive_misses,
            )
            if self._misses >= self._keepalive_misses:
                self.link_lost(
                    gen,
                    f"{self._misses} consecutive keepalive misses "
                    f"(half-open link?)",
                )

    def _reconnect(self) -> None:
        try:
            sock, ready = retry_transient(
                self._dial_once,
                attempts=self._reconnect_attempts,
                base_delay=self._reconnect_base_delay_s,
                max_delay=self._reconnect_max_delay_s,
                max_elapsed=self._reconnect_max_elapsed_s,
                transient=(OSError, TimeoutError),
                on_retry=lambda k, e: self._client._link_event(
                    "net_reconnect_retry", attempt=k, error=repr(e)
                ),
            )
        except BaseException as e:
            with self._lock:
                self.state = self.DEAD
            self._client._link_event(
                "net_reconnect_failed", endpoint=self.endpoint,
                error=repr(e),
            )
            # budget spent: NOW (and only now) the typed router signal
            self._client._mark_dead(
                f"remote link to {self.endpoint} lost and reconnect "
                f"budget spent: {e!r}"
            )
            return
        with self._lock:
            self.generation += 1
            gen = self.generation
            self.reconnects += 1
            self._misses = 0
            self.state = self.UP
        self._client._on_link_restored(sock, ready, gen)

    def stats(self) -> Dict[str, Any]:
        return {
            "endpoint": self.endpoint,
            "state": self.state,
            "generation": self.generation,
            "connects": self.connects,
            "reconnects": self.reconnects,
            "disconnects": self.disconnects,
            "keepalive_misses": self.keepalive_misses_total,
        }


class RemoteEngineClient(ProcessEngineClient):
    """A :class:`ProcessEngineClient` whose worker lives across a TCP
    link instead of a spawned child — the remote-replica backend.

    Same engine surface, three structural differences:

    * **no shared memory** — tensors degrade from shm rings to framed
      tensor sections (:func:`~raft_tpu.serve.ipc.pack_frames`) riding
      the binary control frames; ``transport_zero_copy`` is False, which
      is exactly the signal that makes the HTTP front door fall back to
      its buffered read path.
    * **the link can heal** — a broken socket is NOT worker death. Sends
      that fail leave the RPC pending; the :class:`ConnectionSupervisor`
      reconnects under its retry budget and resends everything pending
      (worker-side dedupe makes the resubmission idempotent). Only a
      spent budget surfaces as ``EngineStopped``.
    * **the worker is not owned** — :meth:`close` disconnects the link
      and leaves the remote worker running for the next generation of
      this replica to redial (readmission-after-heal); worker lifetime
      belongs to its :class:`RemoteWorkerHandle` and idle watchdog.
    """

    def __init__(
        self,
        factory: Optional[Callable[..., Any]] = None,
        overrides: Optional[Dict[str, Any]] = None,
        *,
        endpoint: str,
        connect_timeout_s: float = 5.0,
        keepalive_interval_s: float = 1.0,
        keepalive_timeout_s: float = 2.0,
        keepalive_misses: int = 3,
        reconnect_attempts: int = 6,
        reconnect_base_delay_s: float = 0.05,
        reconnect_max_delay_s: float = 1.0,
        reconnect_max_elapsed_s: float = 8.0,
        boot_timeout_s: float = 300.0,
        ring_slots: int = 32,            # accepted for worker_options
        slot_bytes: int = 16 * 1024 * 1024,  # compat; remote has no rings
        rpc_workers: int = 16,
        dump_dir: Optional[str] = None,
        health_ttl_s: float = 0.02,
        trace_propagation: bool = True,
        qos_propagation: bool = True,
    ):
        super().__init__(
            factory or _remote_noop_factory,
            overrides,
            boot_timeout_s=boot_timeout_s,
            ring_slots=ring_slots,
            slot_bytes=slot_bytes,
            rpc_workers=rpc_workers,
            dump_dir=dump_dir,
            health_ttl_s=health_ttl_s,
            transport="binary",
            trace_propagation=trace_propagation,
            qos_propagation=qos_propagation,
        )
        self.endpoint = str(endpoint)
        # the dedupe-table scope: a rebuilt client (readmission) mints a
        # fresh token, so its ids restarting from zero can never collide
        # with this one's history on the worker
        self._session = os.urandom(8).hex()
        self._closing = False
        self._supervisor = ConnectionSupervisor(
            self, self.endpoint,
            connect_timeout_s=connect_timeout_s,
            keepalive_interval_s=keepalive_interval_s,
            keepalive_timeout_s=keepalive_timeout_s,
            keepalive_misses=keepalive_misses,
            reconnect_attempts=reconnect_attempts,
            reconnect_base_delay_s=reconnect_base_delay_s,
            reconnect_max_delay_s=reconnect_max_delay_s,
            reconnect_max_elapsed_s=reconnect_max_elapsed_s,
        )
        # link flight recorder (schema /4: transport + endpoint): the
        # disconnect/reconnect record --fleet draws the partition window
        # from; with dump_dir it lands next to the worker bundles
        from raft_tpu.obs.recorder import FlightRecorder

        self.link_recorder = FlightRecorder(
            capacity=256, proc="link", transport="tcp",
            endpoint=self.endpoint,
        )
        if dump_dir:
            from raft_tpu.obs import file_sink

            self.link_recorder.add_sink(file_sink(dump_dir))
        self._rx_bytes_seen = 0

    def _link_event(self, kind: str, **fields) -> None:
        try:
            self.link_recorder.record(kind, **fields)
        except Exception:
            pass

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "RemoteEngineClient":
        """Dial + handshake (no spawn: the worker already exists)."""
        if self._started and not self._dead:
            return self
        if self._dead and self._sock is not None:
            raise EngineStopped(
                f"remote link died ({self._dead_reason}); build a new one"
            )
        sock, ready = self._supervisor.connect()
        self.pid = int(ready["pid"])
        self.transport = "binary"
        self.trace_propagation = self._requested_propagation and bool(
            ready.get("trace_propagation", False)
        )
        self.qos_propagation = self._requested_qos and bool(
            ready.get("qos_propagation", False)
        )
        self.config = config_from_wire(ready["config"])
        self.boot = dict(ready.get("boot", {}))
        from raft_tpu.obs import Tracer

        self._txtracer = Tracer(
            self.config.trace_sample_rate, prefix="x", capacity=128
        )
        self._dead = False
        self._started = True
        self._install_link(sock, self._supervisor.generation)
        self._link_event(
            "net_connect", endpoint=self.endpoint, pid=self.pid,
            resumed=bool(ready.get("resumed")),
        )
        if self.trace_propagation:
            self._estimate_clock_offset()
        self._supervisor.start_loop()
        return self

    def _install_link(self, sock: socket.socket, gen: int) -> None:
        """Swap in a live socket: sender first (so a concurrent
        ``_call`` that races the pending-resend snapshot lands on the
        new wire), then its reader thread."""
        self._sock = sock
        self._sender = ipc.FrameCoalescer(sock, binary=True, batch=True)
        self._rx_bytes_seen = 0
        self._reader = threading.Thread(
            target=self._remote_read_loop, args=(sock, gen),
            name="raft-remote-client-reader", daemon=True,
        )
        self._reader.start()

    def _on_link_down(self, reason: str) -> None:
        """The supervisor demoted the link. Read-your-writes: the health
        TTL cache is invalidated HERE, at the disconnect, so a
        cached-healthy snapshot can never shadow a dead remote during
        the eviction window (the PR 13 drain-fix mirror)."""
        self._health_cache = None
        self._link_event(
            "net_disconnect", endpoint=self.endpoint, reason=reason
        )
        sock = self._sock
        if sock is not None:
            # SHUT_RDWR reliably unblocks a reader parked in recv (a
            # plain close may not); the FrameReader then raises and its
            # thread exits through the generation gate
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass

    def _on_link_restored(
        self, sock: socket.socket, ready: Dict[str, Any], gen: int
    ) -> None:
        """Reconnect-and-resume: install the new wire, then resend every
        pending RPC verbatim — the worker's dedupe table resends cached
        replies for anything that actually completed during the outage
        and drops anything still in flight, so no request runs twice."""
        self._install_link(sock, gen)
        self._health_cache = None
        self.pid = int(ready.get("pid", self.pid or -1))
        self._link_event(
            "net_reconnect", endpoint=self.endpoint, pid=self.pid,
            resumed=bool(ready.get("resumed")),
        )
        with self._plock:
            msgs = [
                dict(slot["msg"]) for slot in self._pending.values()
                if "msg" in slot
            ]
        if msgs:
            try:
                self._sender.send_many(msgs)
            except Exception:
                pass  # the next link_lost cycle covers it
        if self.trace_propagation:
            self._estimate_clock_offset()

    def is_alive(self) -> bool:
        return self._started and not self._dead

    def close(
        self, graceful: bool = False, *, timeout: Optional[float] = 30.0
    ) -> None:
        """Close the LINK, not the worker: remote worker lifetime belongs
        to its launcher handle (and its own idle watchdog) — eviction and
        fleet shutdown only disconnect, which is what lets a readmitted
        replica generation redial the same endpoint after a heal."""
        if self._started and not self._dead and graceful:
            try:
                self.drain(timeout=timeout)
            except Exception:
                pass
        self._closing = True
        self._supervisor.stop()
        self._mark_dead("remote link closed")
        sock = self._sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                sock.close()
            except OSError:
                pass
        self._link_event("net_close", endpoint=self.endpoint)

    # -- RPC plumbing ------------------------------------------------------

    def _remote_read_loop(self, sock: socket.socket, gen: int) -> None:
        """Per-link reader: demultiplex replies, unpack framed tensor
        bodies. A broken channel is a LINK event, not worker death — the
        supervisor decides whether it becomes ``EngineStopped``."""
        reader = ipc.FrameReader(sock)
        try:
            while True:
                frame = reader.read_msg()
                self.frames_received += 1
                self.bytes_received += reader.bytes - self._rx_bytes_seen
                self._rx_bytes_seen = reader.bytes
                msgs = ipc.iter_messages(frame)
                self.msgs_received += len(msgs)
                for msg in msgs:
                    with self._plock:
                        slot = self._pending.pop(msg.get("id"), None)
                    if slot is None:
                        continue  # dedupe resend of an already-answered id
                    if "error" in msg:
                        slot["error"] = msg["error"]
                    else:
                        result = msg.get("result") or {}
                        body = result.get("body")
                        if body is not None:
                            t0 = time.monotonic()
                            result = dict(result)
                            _, arrays = ipc.unpack_frames(body, copy=True)
                            result["flow"] = arrays[0] if arrays else None
                            result.pop("body", None)
                            slot["unpack_s"] = time.monotonic() - t0
                        slot["result"] = result
                    slot["ev"].set()
        except BaseException:
            if self._dead or self._closing:
                return
            self._supervisor.link_lost(gen, "remote control channel lost")

    def _call(
        self,
        op: str,
        payload: Optional[Dict[str, Any]] = None,
        *,
        timeout: float = 30.0,
        lease_flow: bool = False,
    ) -> Dict[str, Any]:
        """One multiplexed RPC over the remote link. Differs from the
        unix parent in exactly one way: a failed send does NOT mark the
        worker dead — the RPC stays pending (its message is kept for the
        supervisor's reconnect resend) and the per-RPC deadline at the
        event wait below is the backstop, so a stalled read or a
        partitioned link can never wedge a dispatch thread."""
        if not self._started:
            raise EngineStopped("remote link is not running (call start())")
        if self._dead:
            raise EngineStopped(self._dead_reason)
        mid = next(self._ids)
        msg = dict(payload or {}, id=mid, op=op)
        slot: Dict[str, Any] = {"ev": threading.Event(), "msg": msg}
        if lease_flow:
            slot["lease"] = True
        with self._plock:
            self._pending[mid] = slot
        sender = self._sender
        try:
            sender.send_many([msg])
        except Exception as e:
            # link down, worker fate unknown: kick the supervisor (the
            # generation gate makes a stale kick harmless) and wait —
            # reconnect-and-resume completes this call transparently if
            # the link heals inside the RPC deadline
            self._supervisor.link_lost(
                self._supervisor.generation, f"send failed: {e!r}"
            )
        if not slot["ev"].wait(timeout):
            with self._plock:
                self._pending.pop(mid, None)
            raise ServeError(
                f"remote rpc {op!r} to {self.endpoint} timed out after "
                f"{timeout:.0f}s (partitioned link?)"
            )
        if self._dead and "error" not in slot and "result" not in slot:
            raise EngineStopped(self._dead_reason)
        if "error" in slot:
            raise ipc.decode_error(slot["error"])
        if "unpack_s" in slot:
            self._span_ms["unpack"].append(slot["unpack_s"] * 1e3)
        return slot["result"]

    # -- the engine surface (tensors ride framed bodies) -------------------

    @property
    def transport_zero_copy(self) -> bool:
        """Never: zero-copy means shm rings, and rings do not cross a
        machine boundary. The front door reads this and falls back to
        its buffered (pack_frames) path — by design, not by failure."""
        return False

    def reserve_request_slot(self, nbytes: int) -> Tuple[int, memoryview]:
        raise ServeError(
            "remote transport has no shared-memory rings "
            "(transport_zero_copy is False)"
        )

    def submit_refs(self, *a, **kw):
        raise ServeError(
            "remote transport has no shared-memory rings "
            "(transport_zero_copy is False)"
        )

    def submit_frame_ref(self, *a, **kw):
        raise ServeError(
            "remote transport has no shared-memory rings "
            "(transport_zero_copy is False)"
        )

    def submit(
        self,
        image1,
        image2,
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
    ):
        if self._dead:
            raise EngineStopped(self._dead_reason)
        eff = self._effective_deadline(deadline_ms)
        t0 = time.monotonic()
        body = ipc.pack_frames(
            {}, [np.asarray(image1), np.asarray(image2)]
        )
        t1 = time.monotonic()
        msg: Dict[str, Any] = {
            "body": body,
            "deadline_ms": deadline_ms,
            "num_flow_updates": num_flow_updates,
        }
        tid = self._wire_trace_id(trace_ctx)
        if tid is not None:
            msg["trace_id"] = tid
        self._wire_qos(msg, priority, tenant)
        try:
            res = self._call(
                "submit", msg, timeout=eff / 1e3 + _RPC_GRACE_S,
            )
        except BaseException:
            self._record_spans(
                t0, t1, time.monotonic(), {}, kind="transport",
                ok=False, trace_ctx=trace_ctx,
            )
            raise
        self._record_spans(
            t0, t1, time.monotonic(), {}, kind="transport", ok=True,
            trace_ctx=trace_ctx,
        )
        self._absorb_worker_trace(res, trace_ctx)
        return _serve_result_from_wire(res, res.get("flow"))

    def submit_frame(
        self,
        stream_id: int,
        frame,
        *,
        deadline_ms: Optional[float] = None,
        num_flow_updates: Optional[int] = None,
        trace_ctx: Optional[TraceContext] = None,
        priority: Optional[str] = None,
        tenant: Optional[str] = None,
    ):
        if self._dead:
            raise EngineStopped(self._dead_reason)
        eff = self._effective_deadline(deadline_ms)
        t0 = time.monotonic()
        body = ipc.pack_frames({}, [np.asarray(frame)])
        t1 = time.monotonic()
        msg: Dict[str, Any] = {
            "stream_id": int(stream_id),
            "body": body,
            "deadline_ms": deadline_ms,
            "num_flow_updates": num_flow_updates,
        }
        tid = self._wire_trace_id(trace_ctx)
        if tid is not None:
            msg["trace_id"] = tid
        self._wire_qos(msg, priority, tenant)
        try:
            res = self._call(
                "submit_frame", msg, timeout=eff / 1e3 + _RPC_GRACE_S,
            )
        except BaseException:
            self._record_spans(
                t0, t1, time.monotonic(), {}, kind="transport",
                ok=False, trace_ctx=trace_ctx,
            )
            raise
        self._record_spans(
            t0, t1, time.monotonic(), {}, kind="transport", ok=True,
            trace_ctx=trace_ctx,
        )
        self._absorb_worker_trace(res, trace_ctx)
        return _serve_result_from_wire(res, res.get("flow"))

    # -- introspection -----------------------------------------------------

    def link_stats(self) -> Dict[str, Any]:
        """The supervisor's ledger: connects/reconnects/disconnects,
        keepalive misses, link state — ``serve_bench --transport tcp``
        pins ``reconnects == 0`` on clean runs from here."""
        out = self._supervisor.stats()
        out["session"] = self._session
        return out

    def transport_stats(self, *, include_worker: bool = False) -> dict:
        out = super().transport_stats(include_worker=include_worker)
        out["remote"] = self.link_stats()
        return out

    def dump_postmortem(self, reason: str) -> bool:
        """Worker dump (best-effort RPC) *plus* the local link bundle —
        under a partition the worker is unreachable by definition, and
        the link recorder is the half that saw the disconnect ladder."""
        ok = False
        try:
            self._call("dump", {"reason": reason}, timeout=5.0)
            ok = True
        except Exception:
            pass
        try:
            self.link_recorder.dump(
                reason, extra={"supervisor": self._supervisor.stats()}
            )
            ok = True
        except Exception:
            pass
        return ok


def _remote_noop_factory(**_kw):  # pragma: no cover - never called
    """Placeholder factory for a RemoteEngineClient built without one
    (the engine lives in the remote worker; the local factory is only
    the Replica.build pass-through)."""
    raise ServeError("a remote replica's engine lives in the remote worker")
