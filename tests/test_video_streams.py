"""Video as sessions (PR 36): the device-resident stream cache, upstream's
warm start, and the stream path at the ``throughput`` preset.

What is held here, on the CPU at a small size with seeded random weights
(the benchmark's own ``weights.py``: flow head x0.01, so the recurrence is
contractive and a warm start means something):

* ``stream_cache.forward_interpolate`` (the program's, in blocks, under
  ``jit``) equals ``benchmarks/reference/raft_video.py``'s brute-force one
  exactly, on the fields that matter: identity, a constant shift, a field
  that folds, one that leaves the frame, one that lands nowhere;
* a session of five frames through ``ServeEngine``'s pool against
  upstream's loop in the plain reference (``forward_clip``), warm start on
  and off, in fp32 and at the ``throughput`` preset (bf16 convs and volume:
  the configuration that could not boot before this PR), where the fp8
  control in the program's place fails the same tolerance;
* a stream pair is bit for bit the pair path's flow at the ``throughput``
  preset with warm start off;
* between a frame's arrival and its pair's ``insert`` nothing is fetched
  from the device;
* rows are freed by ``close_stream``, LRU eviction and invalidation, and a
  frame the encoders poison never pairs with the next one.

The described-v5e compiles of the stream programs live in
``tests/test_chip_compile_admission.py`` and
``tests/test_chip_compile_kernel.py`` (the 32-slot step).
"""

import threading

import jax
import numpy as np
import pytest

from raft_tpu.serve import PoisonedInput, ServeConfig, ServeEngine
from raft_tpu.serve import stream_cache
from raft_tpu.serve.stream_cache import forward_interpolate

BUCKET = (128, 160)    # a 16 x 20 grid: the least a 4-level pyramid takes
IMAGE_HW = (124, 156)
ITERS = 6
SEED = 2147483999


# -- the interpolation ---------------------------------------------------------

def _fields(h, w):
    rng = np.random.default_rng(h * 1000 + w)
    xs = np.arange(w, dtype=np.float32)[None, :] * np.ones((h, 1), np.float32)
    fold = np.stack([-(xs - w / 2) * 1.2, np.zeros((h, w), np.float32)], -1)
    return {
        "identity": np.zeros((h, w, 2), np.float32),
        "shift": np.tile(np.asarray([1.5, -0.5], np.float32), (h, w, 1)),
        "fold": fold.astype(np.float32),
        "leaves_the_frame": rng.normal(0, 0.6 * w, (h, w, 2)).astype(np.float32),
        "all_out": np.full((h, w, 2), 1000.0, np.float32),
        "random": rng.normal(0, 3.0, (h, w, 2)).astype(np.float32),
    }


@pytest.mark.parametrize(
    "kind", ["identity", "shift", "fold", "leaves_the_frame", "all_out", "random"]
)
def test_interpolation_equals_the_references(kind):
    """Exactly: both search the nearest kept point by the same fp32
    distances, and ties go to the lowest source index in both."""
    from benchmarks.reference import raft_video

    for h, w in ((7, 9), (16, 20), (21, 40)):
        flow = _fields(h, w)[kind]
        got = np.asarray(jax.jit(forward_interpolate)(flow))
        want = np.asarray(raft_video.forward_interpolate(flow))
        assert np.array_equal(got, want), (kind, h, w)
        if kind == "all_out":
            assert (got == 0).all()
        if kind == "shift":      # every kept point carries the same vector
            assert (got == flow[0, 0]).all()


def test_interpolation_in_blocks_is_the_whole_search(monkeypatch):
    """The distance blocks (8 MiB of fp32 at the real sizes) cut the
    search by target cell only: forced down to 8 cells a block on a grid
    whose 21 x 40 cells are no multiple of 8 x anything convenient, the
    answer is the one-block answer."""
    flow = _fields(21, 41)["random"]
    whole = np.asarray(jax.jit(forward_interpolate)(flow))
    monkeypatch.setattr(stream_cache, "_BLOCK_BYTES", 1)
    assert stream_cache._block_targets(21 * 41) == 8
    blocked = np.asarray(jax.jit(lambda f: forward_interpolate(f))(flow))
    assert np.array_equal(blocked, whole)
    assert stream_cache._block_targets(7040) == 8   # still patched
    monkeypatch.undo()
    assert stream_cache._block_targets(7040) == 256
    assert stream_cache._block_targets(32640) == 64


# -- sessions through the engine, against upstream's loop ----------------------

@pytest.fixture(scope="module")
def world():
    """Weights, the reference's copy, and one clip of five frames."""
    from benchmarks import inputs, inputs_video, weights
    from benchmarks.reference import compare as cmp, raft as ref
    from tests.test_hd_frames import _small_arch   # raft_large's structure, small

    cfg, arch = _small_arch()
    variables = weights.make_variables(ref.param_shapes(arch), SEED, 0.01)
    frames = inputs_video.clip(inputs.seeded_rng(SEED, 6), IMAGE_HW, 5)
    x = np.concatenate([cmp.preprocess(f, BUCKET) for f in frames], 0)
    return cfg, arch, variables, jax.device_get(variables), frames, x


def _engine(world, preset=None, **kw):
    from raft_tpu.models import build_raft

    cfg, _, variables = world[:3]
    base = dict(
        buckets=(BUCKET,), ladder=(ITERS, 2), max_batch=2, pool_capacity=2,
        queue_capacity=16, default_deadline_ms=600000.0, high_watermark=1.0,
        warmup=False, stream_cache_size=2,
    )
    base.update(kw)
    if preset is None:
        serve = ServeConfig(**base)
        model = build_raft(cfg)
    else:
        serve = ServeConfig.preset(preset, **base)
        model = build_raft(cfg.replace(**serve.model_overrides()))
    return ServeEngine(model, variables, serve)


def _walk(eng, frames):
    with eng.open_stream() as stream:
        first = stream.submit(frames[0])
        assert first.primed and first.flow is None
        return [stream.submit(f) for f in frames[1:]]


def _chain_stats(world, flows, warm, want=None):
    from benchmarks.reference import compare as cmp, raft_video

    _, arch, _, host_vars, _, x = world
    h, w = IMAGE_HW
    if want is None:
        want = raft_video.forward_clip(
            arch, host_vars, x, iters=ITERS, warm_start=warm
        )
    rows = [cmp.flow_stats(g, wt[:h, :w]) for g, wt in zip(flows, want)]
    assert all(r["finite"] for r in rows)
    return (max(r["flow_epe_mean_px"] for r in rows),
            max(r["flow_epe_p99_px"] for r in rows))


@pytest.mark.parametrize("warm", [True, False], ids=["warm", "cold"])
def test_session_in_fp32_walks_the_clip_as_upstream_does(world, warm):
    """Four pairs of one session through the pool, each against the
    reference's pair of the same index in ``forward_clip``: pair 1 cold,
    pairs 2-4 started from ``forward_interpolate`` of the program's own
    previous 1/8-grid flow (or cold, with warm start off).

    Tolerance 0.01 px of endpoint error, worst pair, mean and 99th
    percentile alike: the engine and the reference both compute in fp32
    and differ by the order of their sums (the CPU reads 0.0000 to four
    digits on three seeds, on fields of 1.2-8 px); a wrong start — the
    splat this PR removed, a flow row of another session, a chain that
    drops a link — moves a warm pair by tenths of a px at the least."""
    with _engine(world, stream_warm_start=warm) as eng:
        got = _walk(eng, world[4])
        stats = eng.stats()
    assert [g.warm_started for g in got] == [False] + [warm] * 3
    assert stats["stream_warm_starts"] == (3 if warm else 0)
    assert stats["stream_frames"] == 5 and stats["encode_cache_hits"] == 4
    mean, p99 = _chain_stats(world, [g.flow for g in got], warm)
    assert mean < 0.01 and p99 < 0.01, (mean, p99)
    if warm:   # and the warm chain is not the cold one in disguise
        cold_mean, _ = _chain_stats(world, [g.flow for g in got], False)
        assert cold_mean > 0.1, cold_mean


def test_a_pair_is_upstreams_link_from_the_flow8_the_program_returned(world):
    """``return_flow8`` hands the caller upstream's ``flow_low``: the
    1/8-grid flow the answer was upsampled from, on the bucket's grid.
    One link of the reference's loop started from the reference's own
    interpolation of the flow8 the *program* returned for the pair before
    is the program's next pair (fp32: to 0.01 px) — what the benchmark's
    ``correct`` holds a warm chain by, a link at a time — and nobody who
    does not ask gets one."""
    from benchmarks.reference import compare as cmp, raft_video

    _, arch, _, host_vars, frames, x = world
    with _engine(world, stream_warm_start=True) as eng:
        with eng.open_stream() as stream:
            assert stream.submit(frames[0], return_flow8=True).flow8 is None
            got = [stream.submit(f, return_flow8=True) for f in frames[1:4]]
            assert stream.submit(frames[4]).flow8 is None
    h, w = IMAGE_HW
    for k, g in enumerate(got):
        assert g.flow8.shape == (BUCKET[0] // 8, BUCKET[1] // 8, 2)
        assert g.flow8.dtype == np.float32
        want, want8 = raft_video.forward_step(
            arch, host_vars, x[k:k + 1], x[k + 1:k + 2], iters=ITERS,
            prev_flow8=got[k - 1].flow8 if k else None,
        )
        stats = cmp.flow_stats(g.flow, want[:h, :w])
        assert stats["flow_epe_mean_px"] < 0.01 and stats["flow_epe_p99_px"] < 0.01
        # in 1/8-grid px: the same field before the upsample
        assert np.abs(g.flow8 - np.asarray(want8)).max() < 0.01


@pytest.fixture(scope="module")
def throughput_engine(world):
    """The row-4 configuration: ``ServeConfig.preset("throughput")`` (bf16
    convs, bf16 volume on the fused kernel) with streams on. It has to
    boot; before PR 36 its first stream admission failed on dtypes."""
    with _engine(world, "throughput", stream_warm_start=True,
                 stream_cache_size=3) as eng:
        yield eng


def test_session_at_the_throughput_preset_and_its_fp8_control(
    world, throughput_engine
):
    """Pairs 1-3 of a warm-started session at bf16, against the fp32
    reference's walk. Rounding compounds along a warm chain (pair ``k``
    starts from the program's own pair ``k-1``): on three seeds the worst
    of pairs 1-3 reads 0.14-0.31 px mean / 0.68-1.79 px p99 for the
    program and 0.78-0.94 / 1.97-2.68 for the control — the same walk
    computed in fp8, the nearest precision below the stated bf16, in the
    program's place (CPU, PR 36; this seed: 0.19 / 0.70 against 0.78 /
    1.97). The limits sit between with 2x of room on the mean; the p99's
    ranges nearly touch, so the control has to fail by the mean."""
    from benchmarks.reference import raft_video

    _, arch, _, host_vars, frames, x = world
    got = _walk(throughput_engine, frames)[:3]
    assert [g.warm_started for g in got] == [False, True, True]
    want = raft_video.forward_clip(
        arch, host_vars, x[:4], iters=ITERS, warm_start=True
    )
    mean, p99 = _chain_stats(world, [g.flow for g in got], True, want=want)
    assert mean < 0.4 and p99 < 1.4, (mean, p99)
    h, w = IMAGE_HW
    control = [c[:h, :w] for c in raft_video.forward_clip(
        arch, host_vars, x[:4], iters=ITERS, warm_start=True, precision="fp8"
    )]
    c_mean, _ = _chain_stats(world, control, True, want=want)
    assert c_mean > 0.4, c_mean


def test_stream_pair_is_bitwise_the_pair_path_at_the_throughput_preset(world):
    """Warm start off: ``encode_frame`` -> table -> ``pool_begin_features``
    hands the volume the same bf16 feature maps ``pool_begin_pair``
    computes for the two frames, and zeros as the start are the cold
    start — so the flows are equal to the bit, as in fp32 they were only
    close (the encoders round differently on one frame and on two)."""
    frames = world[4]
    with _engine(world, "throughput", stream_warm_start=False) as eng:
        streamed = _walk(eng, frames[:3])
        paired = [eng.submit(a, b) for a, b in zip(frames[:2], frames[1:3])]
        counts = eng.program_counts()
    for s, p in zip(streamed, paired):
        assert not s.warm_started
        assert np.array_equal(s.flow, p.flow)
    # no warm start, no retirement program for it
    assert counts["stream_store_flow"] == 0 and counts["stream_swap"] >= 1


# -- what crosses to the host ----------------------------------------------------

class _CountingNumpy:
    """``numpy`` as the engine module sees it, with ``asarray`` counted
    where its argument lives on the device and the calling thread is
    inside an admission."""

    def __init__(self, inside):
        self.inside = inside
        self.fetched = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array) and getattr(self.inside, "on", False):
            self.fetched.append((a.shape, str(a.dtype)))
        return np.asarray(a, *args, **kw)


def test_admission_fetches_nothing_from_the_device(
    world, throughput_engine, monkeypatch
):
    """Three frames of two sessions admit and retire while every
    ``np.asarray`` of a device array inside ``_pool_admit`` is counted —
    and, on a backend that guards transfers, refused
    (``jax.transfer_guard_device_to_host``; the CPU's arrays are host
    memory and it lets them through, the counter does not): none. The
    frames' finite flags are read outside admission (the primes' by
    ``_stream_settle``, the pairs' with their retirement's fetch)."""
    from raft_tpu.serve import engine as engine_mod

    eng = throughput_engine
    inside = threading.local()
    counting = _CountingNumpy(inside)
    monkeypatch.setattr(engine_mod, "np", counting)
    admit = eng._pool_admit

    def guarded_admit():
        inside.on = True
        try:
            with jax.transfer_guard_device_to_host("disallow"):
                return admit()
        finally:
            inside.on = False

    monkeypatch.setattr(eng, "_pool_admit", guarded_admit)
    before = eng.stats()
    frames = world[4]
    a, b = eng.open_stream(), eng.open_stream()
    out = []
    for k in range(3):   # the two sessions' frames arrive together
        other = threading.Thread(
            target=lambda: out.append(a.submit(frames[k]))
        )
        other.start()
        out.append(b.submit(frames[k + 1]))
        other.join()
    a.close(), b.close()
    after = eng.stats()
    assert after["worker_errors"] == before["worker_errors"]
    assert sum(1 for r in out if r.primed) == 2
    assert sum(1 for r in out if r.flow is not None) == 4
    assert all(np.isfinite(r.flow).all() for r in out if r.flow is not None)
    assert counting.fetched == []


# -- rows, eviction, invalidation, poison ----------------------------------------

def test_rows_are_freed_by_close_and_by_lru(world):
    """``stream_cache_bytes`` is what the live sessions' rows hold: a row
    a primed session, none for a session that only opened; back to the
    floor after ``close_stream``; and with more sessions than
    ``stream_cache_size`` the least recently used one loses its row and
    primes again."""
    frames = world[4]
    with _engine(world, stream_cache_size=2, ladder=(2, 1)) as eng:
        row = eng._stream_cache.row_bytes(BUCKET)
        h8, w8 = BUCKET[0] // 8, BUCKET[1] // 8
        assert row == h8 * w8 * (32 * 4 + 48 * 4 + 2 * 4)   # fmap, ctx, flow
        assert eng.stats()["stream_cache_bytes"] == 0
        s1, s2, s3 = (eng.open_stream() for _ in range(3))
        assert eng.stats()["stream_sessions"] == 0           # nothing submitted
        assert s1.submit(frames[0]).primed
        assert s2.submit(frames[0]).primed
        st = eng.stats()
        assert (st["stream_sessions"], st["stream_cache_bytes"]) == (2, 2 * row)
        assert s3.submit(frames[0]).primed                   # evicts s1 (LRU)
        st = eng.stats()
        assert (st["stream_sessions"], st["stream_cache_bytes"]) == (2, 2 * row)
        assert st["stream_evictions"] == 1
        assert s2.submit(frames[1]).flow is not None         # s2 kept its row
        assert s1.submit(frames[1]).primed                   # s1 lost its own
        for s in (s1, s2, s3):
            s.close()
        st = eng.stats()
        assert (st["stream_sessions"], st["stream_cache_bytes"]) == (0, 0)


def _poison_encode(eng, monkeypatch, when):
    """``_run_encode`` whose ``when``-th call returns NaN features (and
    says so in its finite flags, as the program computes them)."""
    import jax.numpy as jnp

    real = eng._run_encode
    calls = {"n": 0}

    def run_encode(frames):
        fm, cx, ok = real(frames)
        calls["n"] += 1
        if calls["n"] == when:
            fm = jnp.full_like(fm, jnp.nan)
            ok = jnp.zeros_like(ok)
        return fm, cx, ok

    monkeypatch.setattr(eng, "_run_encode", run_encode)


@pytest.mark.parametrize("pool_capacity", [2, 0], ids=["pool", "fallback"])
def test_a_frame_the_encoders_poison_never_pairs(world, monkeypatch, pool_capacity):
    """The finite check is the ``encode_frame`` program's own. A poisoned
    prime is refused when its flag is read (no feature map crossed to the
    host for it); a poisoned pair is refused at its retirement; either
    way the session forgets the frame, the next one primes, and the pair
    after it is sound — in the pool and in the ``pool_capacity=0`` engine,
    which share the cache."""
    frames = world[4]
    with _engine(world, pool_capacity=pool_capacity, ladder=(2, 1)) as eng:
        with eng.open_stream() as stream:
            _poison_encode(eng, monkeypatch, when=1)
            with pytest.raises(PoisonedInput):
                stream.submit(frames[0])                     # a prime
            assert eng.stats()["stream_cache_bytes"] == 0
            assert stream.submit(frames[0]).primed
            assert np.isfinite(stream.submit(frames[1]).flow).all()
            _poison_encode(eng, monkeypatch, when=1)
            with pytest.raises(PoisonedInput):
                stream.submit(frames[2])                     # a pair
            assert stream.submit(frames[2]).primed           # never across the gap
            assert np.isfinite(stream.submit(frames[3]).flow).all()
        st = eng.stats()
    assert st["quarantined"] == 2 and st["stream_invalidations"] >= 2
    assert st["worker_errors"] == 0


def test_fallback_engine_serves_sessions_from_the_same_cache(world):
    """``pool_capacity=0`` at the ``throughput`` preset: ``encode`` ->
    ``stream_swap`` -> ``iterate`` on device arrays in bf16 (this path
    re-uploaded fp32 copies before). Against the pair path of the same
    engine: the whole-request program encodes two frames together, so
    close, not equal (2e-2 px on fields of 1-2 px)."""
    frames = world[4]
    with _engine(world, "throughput", pool_capacity=0, ladder=(ITERS,)) as eng:
        streamed = _walk(eng, frames[:3])
        paired = [eng.submit(a, b) for a, b in zip(frames[:2], frames[1:3])]
        assert eng.stats()["stream_cache_bytes"] == 0        # closed
    for s, p in zip(streamed, paired):
        assert np.abs(p.flow).mean() > 0.1, "degenerate field"
        np.testing.assert_allclose(s.flow, p.flow, atol=2e-2, rtol=0)


def test_sessions_on_a_serve_mesh(rng=np.random.default_rng(7)):
    """``mesh_devices=2``: the session table is replicated, the cohort's
    rows are sharded over the mesh, and both table programs carry explicit
    shardings (no donation there, as for the pool's ``insert``). A session
    primes, pairs and warm-starts, warmed (AOT) and not."""
    from raft_tpu.models import RAFT_SMALL, build_raft, init_variables
    from raft_tpu.models.corr import CorrBlock

    cfg = RAFT_SMALL.replace(
        feature_encoder_widths=(8, 8, 12, 16, 24),
        context_encoder_widths=(8, 8, 12, 16, 40),
        motion_corr_widths=(16,), motion_flow_widths=(16, 8),
        motion_out_channels=20, gru_hidden=24, flow_head_hidden=16,
        corr_levels=2,
    )
    model = build_raft(cfg, corr_block=CorrBlock(num_levels=2, radius=3))
    variables = init_variables(model)
    frames = [rng.integers(0, 255, (45, 60, 3), dtype=np.uint8)
              for _ in range(4)]
    for warmup in (False, True):
        serve = ServeConfig(
            buckets=((48, 64),), ladder=(3, 2, 1), max_batch=2,
            pool_capacity=2, queue_capacity=16, default_deadline_ms=60000.0,
            high_watermark=1.0, stream_cache_size=4, stream_warm_start=True,
            mesh_devices=2, warmup=warmup,
        )
        with ServeEngine(model, variables, serve) as eng:
            got = _walk(eng, frames)
            assert eng.stats()["worker_errors"] == 0
        assert [g.warm_started for g in got] == [False, True, True]
        assert all(np.isfinite(g.flow).all() for g in got)
