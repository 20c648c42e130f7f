"""Cold-start elimination: AOT warmup, warmup artifacts, boot accounting.

Before ISSUE 7, ``ServeEngine`` warmup *executed* every program on zeros
(buckets x iter-ladder x batch-ladder, plus the pool's per-rung admission
programs) purely to trigger compilation — on every boot, serially, paying
real forward-pass FLOPs on top of every compile. At replica scale that
re-compilation is the availability bottleneck (ROADMAP): a restarted
replica is dark for the whole compile wall.

This module removes the wall in three tiers, fastest first:

1. **Warmup artifact** — the engine's whole compiled program set,
   serialized (``jax.experimental.serialize_executable``, the same
   executable-serialization layer ``jax.export`` rides on) together with
   a *fingerprint* (jax/jaxlib versions, backend, device kind/count,
   program-set-shaping config fields, precision preset, weight-tree
   hash). A booting replica that holds a matching artifact **loads**
   executables instead of compiling them — zero programs compiled,
   counter-verified. Built offline by ``scripts/build_warmup_artifact.py``
   or :func:`save_artifact`.
2. **Persistent compilation cache** — ``ServeConfig.
   compilation_cache_dir`` wires ``jax_compilation_cache_dir`` before
   anything compiles, so a replica that *must* compile (no artifact, or a
   fingerprint mismatch) pays each XLA backend-compile once per
   (program, jaxlib, backend) across restarts instead of once per boot.
3. **Compile-only AOT warmup** — the floor tier. Programs are lowered
   from ``jax.ShapeDtypeStruct`` specs and compiled via
   ``jit(...).lower(specs).compile()`` — no zeros batches, no forward
   passes — and independent programs compile concurrently on a thread
   pool. A single tiny smoke execution per program family (not per
   program) validates runnability, so warmup cost ~= compile cost.

Boot is *measured*, not guessed: ``engine.stats()['boot']`` reports
``boot_to_ready_ms``, ``programs_compiled`` vs ``programs_loaded``, the
cache source tier, and the number of raw XLA backend-compile events
observed during boot (via the :func:`jax.monitoring` hook below — the
tier-1-safe compile counter). ``scripts/serve_bench.py --boot-report``
A/Bs the three tiers.

Failure model (docs/failure_model.md): an artifact can make boot fast,
never make it fail. :func:`load_artifact` *refuses* a mismatched or
corrupt artifact with a typed :class:`~raft_tpu.serve.ArtifactMismatch`
naming the offending fingerprint field; the booting engine catches it,
records the reason in ``stats()['boot']['artifact_error']``, and degrades
to tier 2/3 compilation.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu.serve.errors import ArtifactMismatch
from raft_tpu.utils.runtime import enable_persistent_cache

__all__ = [
    "ProgramSpec",
    "program_specs",
    "compile_programs",
    "fingerprint",
    "check_fingerprint",
    "save_artifact",
    "load_artifact",
    "load_programs",
    "warm_engine",
    "compile_events",
    "enable_persistent_cache",
    "ARTIFACT_VERSION",
]

# v2 (ISSUE 11): the pool step/gather/begin programs gained the
# residual-history leaf — a v1 artifact's executables no longer match
# the live signatures, so it must refuse at load (typed, degrading to
# compile) rather than fail at the boot smoke run.
# v3 (ISSUE 12): convergence-adaptive compute — the pool state grew the
# per-slot `converged` bitmask, the step program takes the traced
# (thresh, streak) knobs and returns the packed converged mask as its
# pacing token, and stream admission (`pool_begin_features`) takes the
# traced warm-start initial flow. A pre-ISSUE-12 (v2) artifact refuses
# typed at load and the boot degrades to compile.
# v4 (ISSUE 36): stream sessions live in a device table — `encode`
# returns its frames' finite flags, `stream_swap` / `stream_store_flow`
# joined the set, and `pool_begin_features` / `iterate` take the
# encoders' outputs in the dtype they are computed in.
ARTIFACT_VERSION = 4

ProgramKey = Tuple[Any, ...]  # (family, *shape dims[, iters])

# Serializes every bulk-compile entry point with save_artifact's
# temporary disabling of the process-global persistent-cache config.
# Without it, a replica compiling concurrently with an artifact save
# (e.g. a router rebuild degrading to compile) could run with the cache
# unexpectedly off, or the save's finally-restore could re-enable the
# cache mid-way through the artifact's own compiles — reintroducing the
# symbol-table-loss failure the bypass exists to prevent. RLock because
# save_artifact calls compile_programs while holding it.
_cache_config_lock = threading.RLock()


# ---------------------------------------------------------------------------
# Compile counter: the tier-1-safe "did anything actually compile?" probe
# ---------------------------------------------------------------------------

_events_lock = threading.Lock()
_backend_compiles = 0
_listener_state = {"registered": False}


def _ensure_listener() -> None:
    """Register the (idempotent, process-global) jax.monitoring listener.

    The listener only increments an integer on
    ``/jax/core/compile/backend_compile_duration`` events, so it is safe
    to leave registered for the life of the process (jax.monitoring has
    no unregister API for a single callback). Registration is lazy — a
    process that never touches the serve AOT layer never installs it.
    """
    with _events_lock:
        if _listener_state["registered"]:
            return
        _listener_state["registered"] = True
    try:
        from jax import monitoring

        def _on_event(name: str, *args, **kw) -> None:
            if "backend_compile" in name:
                global _backend_compiles
                with _events_lock:
                    _backend_compiles += 1

        monitoring.register_event_duration_secs_listener(_on_event)
    except Exception:  # pragma: no cover - monitoring API moved
        pass  # counter degrades to constant-0 deltas; boot still works


def compile_events() -> int:
    """Monotonic count of XLA backend-compile events observed so far.

    Sample before and after a window; a zero delta proves nothing
    compiled inside it — the assertion behind the artifact-boot CI lane
    (``tests/test_serve_aot.py``) and ``stats()['boot']
    ['backend_compiles']``. First call installs the listener, so deltas
    are only meaningful between calls *after* the first.
    """
    _ensure_listener()
    with _events_lock:
        return _backend_compiles


# ---------------------------------------------------------------------------
# Program-set enumeration: every program the worker thread may dispatch
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One AOT-compilable program: key + jitted fn + argument specs.

    ``key`` doubles as the engine's dispatch-overlay key — the hot-path
    seams rebuild it from live argument shapes (``O(1)`` tuple build, no
    tracing), so a spec enumerated here and a dispatch at serve time
    agree by construction.
    """

    key: ProgramKey
    fn: Any                      # the engine's own jitted callable
    args: Tuple[Any, ...]        # pytrees of jax.ShapeDtypeStruct
    kwargs: Dict[str, Any]       # static kwargs for .lower()


def _sds(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(int(d) for d in shape), dtype)


def _spec_of(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
    )


def program_specs(engine) -> List[ProgramSpec]:
    """Enumerate the engine's *whole* closed program set as compile specs.

    Mirrors exactly what the worker thread can dispatch (the same grids
    the pre-ISSUE-7 execute-to-warm path walked): pool mode covers both
    admission/retirement rungs and the one capacity-wide step program per
    bucket; fallback mode covers buckets x iter-ladder x batch-ladder.
    The same artifact therefore covers both the pool-mode and
    ``pool_capacity=0`` program sets — whichever the config selects.
    """
    cfg = engine.config
    var_specs = _spec_of(engine._dev_vars)
    specs: List[ProgramSpec] = []
    stream = engine._encode is not None

    def encode_specs(x):
        fm, cx, _ = jax.eval_shape(engine._encode, var_specs, x)
        return _spec_of(fm), _spec_of(cx)

    def stream_specs(bucket, r, fm, cx, c1=None):
        """The session table's two programs at rung ``r``: operands are
        the encode program's own outputs (their dtype, not float32) and
        the host's index vectors."""
        cache = engine._stream_cache
        table = cache.table_spec(bucket)
        h8, w8 = int(fm.shape[1]), int(fm.shape[2])
        lanes = (_sds(r, dtype=jnp.int32), _sds(r, dtype=jnp.bool_))
        out = [ProgramSpec(
            ("stream_swap", r, h8, w8), cache.programs.swap,
            (table, fm, cx, *lanes, _sds(r, dtype=jnp.bool_)), {},
        )]
        if c1 is not None and cache.programs.store_flow is not None:
            out.append(ProgramSpec(
                ("stream_store_flow", r, h8, w8), cache.programs.store_flow,
                (table, c1, *lanes), {},
            ))
        return out

    if engine._pool_progs is not None:
        from raft_tpu.serve.pool import state_spec

        progs = engine._pool_progs
        # the engine's EFFECTIVE capacity (per-device config x mesh)
        cap = getattr(engine, "_pool_cap", cfg.pool_capacity)
        for bucket in engine._router.buckets:
            bh, bw = bucket
            st = state_spec(
                engine.model, var_specs, cap, bucket,
                resid_len=progs.resid_len,
            )
            c1 = st["coords1"]
            h8, w8 = int(c1.shape[1]), int(c1.shape[2])
            specs.append(ProgramSpec(
                ("pool_step", cap, h8, w8), progs.step,
                # the convergence knobs (thresh, streak, min-iters) are
                # traced scalar inputs (ISSUE 12): one compiled step
                # program covers every setting, including disabled
                (var_specs, st, _sds(dtype=jnp.float32),
                 _sds(dtype=jnp.int32), _sds(dtype=jnp.int32)),
                {},
            ))
            for r in engine._admit_ladder:
                x = _sds(r, bh, bw, 3)
                rows = jax.eval_shape(progs.begin_pair, var_specs, x, x)
                rows = _spec_of(rows)
                specs.append(ProgramSpec(
                    ("pool_begin_pair", r, bh, bw),
                    progs.begin_pair, (var_specs, x, x), {},
                ))
                specs.append(ProgramSpec(
                    ("pool_insert", r, h8, w8),
                    progs.insert,
                    (st, rows, _sds(r, dtype=jnp.int32),
                     _sds(r, dtype=jnp.bool_)),
                    {},
                ))
                specs.append(ProgramSpec(
                    ("pool_gather", r, h8, w8),
                    progs.gather,
                    (c1, st["hidden"], st["resid_hist"],
                     _sds(r, dtype=jnp.int32)),
                    {},
                ))
                row_c1 = _sds(r, *c1.shape[1:], dtype=c1.dtype)
                row_hid = _sds(r, *st["hidden"].shape[1:],
                               dtype=st["hidden"].dtype)
                specs.append(ProgramSpec(
                    ("pool_final", r, h8, w8),
                    progs.final, (var_specs, row_c1, row_hid), {},
                ))
                if stream:
                    specs.append(ProgramSpec(
                        ("encode", r, bh, bw), engine._encode,
                        (var_specs, x), {},
                    ))
                    fm, cx = encode_specs(x)
                    ifl = _sds(r, int(fm.shape[1]), int(fm.shape[2]), 2)
                    specs.append(ProgramSpec(
                        ("pool_begin_features", r, int(fm.shape[1]),
                         int(fm.shape[2])),
                        progs.begin_features,
                        (var_specs, fm, fm, cx, ifl), {},
                    ))
                    specs.extend(stream_specs(bucket, r, fm, cx, row_c1))
        return specs

    for bucket in engine._router.buckets:
        bh, bw = bucket
        for b in engine._batch_ladder:
            x = _sds(b, bh, bw, 3)
            for iters in cfg.ladder:
                # the iteration count is a positional static arg (pjit
                # rejects kwargs alongside the mesh path's in_shardings)
                specs.append(ProgramSpec(
                    ("pairwise", b, bh, bw, int(iters)),
                    engine._apply, (var_specs, x, x, int(iters)), {},
                ))
            if stream:
                specs.append(ProgramSpec(
                    ("encode", b, bh, bw), engine._encode, (var_specs, x), {},
                ))
                fm, cx = encode_specs(x)
                specs.extend(stream_specs(bucket, b, fm, cx))
                for iters in cfg.ladder:
                    specs.append(ProgramSpec(
                        ("iterate", b, int(fm.shape[1]), int(fm.shape[2]),
                         int(iters)),
                        engine._iterate, (var_specs, fm, fm, cx, int(iters)),
                        {},
                    ))
    return specs


def compile_programs(
    specs: List[ProgramSpec], workers: int = 0
) -> Dict[ProgramKey, Any]:
    """AOT-compile ``specs`` concurrently; returns key -> ``Compiled``.

    ``jit(...).lower(shape_specs).compile()`` — tracing + lowering + XLA
    compile, **no execution**. Independent programs compile in parallel
    (XLA releases the GIL during backend compile); ``workers=0`` picks
    ``min(8, cpu_count)``. Runs under the module cache-config lock so a
    concurrent :func:`save_artifact` cannot toggle the process-global
    persistent-cache dir mid-compile.
    """
    if not specs:
        return {}
    if workers <= 0:
        workers = min(8, os.cpu_count() or 1)

    def _one(spec: ProgramSpec):
        return spec.key, spec.fn.lower(*spec.args, **spec.kwargs).compile()

    with _cache_config_lock:
        if workers == 1 or len(specs) == 1:
            return dict(_one(s) for s in specs)
        with ThreadPoolExecutor(
            max_workers=min(workers, len(specs))
        ) as pool:
            return dict(pool.map(_one, specs))


# ---------------------------------------------------------------------------
# Fingerprint + artifact (save / load / verify)
# ---------------------------------------------------------------------------


def _variables_hash(variables) -> str:
    """sha256 over the flattened (path, shape, dtype) weight-tree spec —
    cheap (no value reads) yet catches architecture or checkpoint-width
    swaps. Weight *values* are intentionally excluded: executables are
    value-independent (weights are traced arguments), so a checkpoint
    update with the same tree keeps its artifact."""
    leaves = jax.tree_util.tree_flatten_with_path(variables)[0]
    h = hashlib.sha256()
    for path, leaf in leaves:
        h.update(
            f"{jax.tree_util.keystr(path)}:{tuple(np.shape(leaf))}:"
            f"{np.result_type(leaf) if not hasattr(leaf, 'dtype') else leaf.dtype}".encode()
        )
    return h.hexdigest()


def _model_hash(model) -> str:
    """Stable structural hash of a model instance: its repr with object
    addresses stripped (flax module reprs are otherwise deterministic)
    plus a sorted walk of plain-python component attributes (e.g. the
    non-flax corr blocks, whose default reprs are *only* an address —
    their radius/levels/dtype knobs shape the compiled programs)."""
    import re

    parts = [re.sub(r" object at 0x[0-9a-f]+", "", repr(model))]

    def walk(obj, seen) -> None:
        if id(obj) in seen:
            return
        seen.add(id(obj))
        d = getattr(obj, "__dict__", None)
        if not isinstance(d, dict):
            return
        for k in sorted(d):
            if k.startswith("_") or k in ("parent",):
                continue
            v = d[k]
            if isinstance(v, (int, float, str, bool, tuple, type, type(None))):
                parts.append(f"{type(obj).__name__}.{k}={v!r}")
            else:
                parts.append(f"{type(obj).__name__}.{k}:{type(v).__name__}")
                walk(v, seen)

    walk(model, set())
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()


def fingerprint(engine) -> Dict[str, Any]:
    """The compatibility contract between an artifact and a booting
    engine: every field that changes what the program set lowers or
    compiles to. Flat and JSON-able so a mismatch can name its field."""
    cfg = engine.config
    import jaxlib

    dev = jax.devices()[0]
    return {
        "format": ARTIFACT_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        # the devices the programs are COMPILED FOR (the serve mesh's
        # extent, 1 for a single-device engine) — not the host's device
        # inventory: an artifact built at one mesh size must refuse at
        # another even on the same machine (ISSUE 8)
        "device_count": getattr(engine, "num_devices", jax.device_count()),
        "buckets": tuple(engine._router.buckets),
        "ladder": tuple(cfg.ladder),
        "batch_ladder": tuple(engine._batch_ladder),
        "max_batch": cfg.max_batch,
        "pool_capacity": cfg.pool_capacity,
        "admit_ladder": tuple(engine._admit_ladder),
        "stream_enabled": engine._encode is not None,
        # with warm start the retirement's stream_store_flow is in the set
        "stream_warm_start": bool(
            engine._encode is not None and engine._warm_start
        ),
        "precision": cfg.precision,
        "compute_dtype": cfg.compute_dtype,
        "corr_dtype": cfg.corr_dtype,
        "corr_impl": cfg.corr_impl,
        "model_hash": _model_hash(engine.model),
        "variables_hash": _variables_hash(engine._dev_vars),
    }


def check_fingerprint(
    artifact_fp: Dict[str, Any], engine_fp: Dict[str, Any]
) -> None:
    """Field-by-field comparison; raises :class:`ArtifactMismatch` naming
    the first mismatched field (deterministic order: the engine
    fingerprint's key order, version first)."""
    for field in engine_fp:
        a, e = artifact_fp.get(field, "<absent>"), engine_fp[field]
        if a != e:
            raise ArtifactMismatch(
                f"warmup artifact mismatch on {field!r}: artifact has "
                f"{a!r}, this engine needs {e!r} — rebuild with "
                f"scripts/build_warmup_artifact.py",
                field=field,
            )


def save_artifact(engine, path: str, workers: int = 0) -> Dict[str, Any]:
    """Compile the engine's whole program set (reusing any executables
    the engine already holds) and serialize it + fingerprint to ``path``
    (atomic write). Returns a summary dict."""
    from jax.experimental import serialize_executable

    t0 = time.monotonic()
    specs = program_specs(engine)
    # the cache-dir toggle mutates process-global jax config: hold the
    # module lock for the whole window so concurrent compiles (a router
    # replica rebuilding, another save) serialize against it instead of
    # compiling with the cache unexpectedly off — or having the restore
    # re-enable it mid-way through this save's own compiles
    with _cache_config_lock:
        cache_dir = getattr(jax.config, "jax_compilation_cache_dir", None)
        if cache_dir:
            # an executable deserialized from the persistent compilation
            # cache can lose its backend symbol table when re-serialized
            # (observed on this jaxlib: the artifact loads, then the first
            # execution dies with 'Symbols not found') — bypass the cache
            # and compile the artifact's program set fresh so the
            # serialized set is always self-contained, whatever process
            # builds it
            jax.config.update("jax_compilation_cache_dir", None)
            have: Dict[ProgramKey, Any] = {}
        else:
            have = dict(getattr(engine, "_aot_execs", {}) or {})
        try:
            missing = [s for s in specs if s.key not in have]
            have.update(compile_programs(missing, workers))
        finally:
            if cache_dir:
                jax.config.update("jax_compilation_cache_dir", cache_dir)
    programs = {}
    for spec in specs:
        payload, in_tree, out_tree = serialize_executable.serialize(
            have[spec.key]
        )
        programs[spec.key] = (payload, in_tree, out_tree)
    blob = pickle.dumps(
        {"fingerprint": fingerprint(engine), "programs": programs},
        protocol=pickle.HIGHEST_PROTOCOL,
    )
    tmp = f"{path}.tmp.{os.getpid()}"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    return {
        "path": path,
        "programs": len(programs),
        "compiled": len(missing),
        "reused": len(specs) - len(missing),
        "bytes": len(blob),
        "build_s": round(time.monotonic() - t0, 3),
    }


def load_artifact(path: str, engine_fp: Optional[Dict[str, Any]] = None):
    """Read + validate an artifact file; returns the raw artifact dict.

    Refuses with a typed :class:`ArtifactMismatch` on a corrupt file
    (``field='format'``) or, when ``engine_fp`` is given, on the first
    mismatched fingerprint field. Never partially loads."""
    try:
        with open(path, "rb") as f:
            art = pickle.loads(f.read())
        fp = art["fingerprint"]
        programs = art["programs"]
        assert isinstance(fp, dict) and isinstance(programs, dict)
    except ArtifactMismatch:
        raise
    except Exception as e:
        raise ArtifactMismatch(
            f"warmup artifact at {path} is unreadable ({type(e).__name__}: "
            f"{e}) — rebuild with scripts/build_warmup_artifact.py",
            field="format",
        ) from e
    if fp.get("format") != ARTIFACT_VERSION:
        raise ArtifactMismatch(
            f"warmup artifact mismatch on 'format': artifact has "
            f"{fp.get('format')!r}, this build needs {ARTIFACT_VERSION!r}",
            field="format",
        )
    if engine_fp is not None:
        check_fingerprint(fp, engine_fp)
    return art


def load_programs(
    artifact: Dict[str, Any],
    devices: Sequence[Any],
    keys: Optional[List[ProgramKey]] = None,
) -> Dict[ProgramKey, Any]:
    """Deserialize executables from a loaded artifact (``keys=None``
    loads everything; passing the live spec keys skips stale extras).

    ``devices`` are the ones the programs execute on
    (``engine.dispatch_devices``). Left to JAX's default — every device
    of the backend — a one-device program loaded in a process that sees
    more (a 4-chip host, the 8-virtual-device test mesh) refuses its
    arguments at the first run ("expected ... 8 shards")."""
    from jax.experimental import serialize_executable

    programs = artifact["programs"]
    wanted = programs.keys() if keys is None else [
        k for k in keys if k in programs
    ]
    out = {}
    for k in wanted:
        payload, in_tree, out_tree = programs[k]
        out[k] = serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree, execution_devices=list(devices)
        )
    return out


# ---------------------------------------------------------------------------
# Boot orchestration (ServeEngine._warmup delegates here)
# ---------------------------------------------------------------------------


def warm_engine(engine) -> Dict[str, Any]:
    """Build the engine's executable overlay: artifact tier first, then
    concurrent AOT compilation of whatever the artifact didn't cover.
    Returns the ``stats()['boot']`` accounting (sans ready-time, which
    the engine stamps when the worker is actually up)."""
    cfg = engine.config
    specs = program_specs(engine)
    execs: Dict[ProgramKey, Any] = {}
    artifact_error: Optional[str] = None
    loaded = 0
    if cfg.warmup_artifact:
        try:
            art = load_artifact(cfg.warmup_artifact, fingerprint(engine))
            execs = load_programs(
                art, engine.dispatch_devices, [s.key for s in specs]
            )
            loaded = len(execs)
        except ArtifactMismatch as e:
            # degrade to compile, never refuse to boot
            artifact_error = str(e)
            execs = {}
    missing = [s for s in specs if s.key not in execs]
    execs.update(compile_programs(missing, cfg.warmup_workers))
    engine._aot_execs = execs
    if loaded:
        source = "artifact"
    elif cfg.compilation_cache_dir:
        source = "persistent_cache"
    else:
        source = "cold"
    return {
        "source": source,
        "programs_total": len(specs),
        "programs_loaded": loaded,
        "programs_compiled": len(missing),
        "artifact_error": artifact_error,
    }
