"""Cold-start elimination suite (ISSUE 7): AOT warmup artifacts,
persistent-cache wiring, compile counting, and precision presets.

The claims under test, CPU-only and tier-1-collected:

  * warmup is compile-only (AOT lowering from shape specs) — jit caches
    stay empty, the executable overlay carries the whole program set,
    and a smoke execution per program family proves runnability;
  * a warmup artifact round-trips: a replica booting from it compiles
    ZERO programs (our program-table counter AND the raw
    ``jax.monitoring`` backend-compile event counter agree) and serves
    flow identical to a freshly-compiled engine, in both the pool and
    ``pool_capacity=0`` fallback modes;
  * a mismatched or corrupt artifact is refused with a typed
    :class:`ArtifactMismatch` naming the offending fingerprint field —
    and a booting engine *degrades to compiling* instead of refusing to
    boot;
  * ``ServeConfig.preset`` names exactly the golden-EPE-gated precision
    configs (the bf16 combos pinned in tests/test_epe_golden.py) and a
    preset-built model runs the serve fault ladder unchanged.
"""

import os

import numpy as np
import pytest

from raft_tpu.serve import (
    ArtifactMismatch,
    PoisonedInput,
    ServeConfig,
    ServeEngine,
    aot,
)
from raft_tpu.utils.faults import FaultInjector

from tests.test_serve import _image, _tiny_model

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module", autouse=True)
def no_inherited_compile_cache():
    """This module's claims are about COLD boots and self-contained
    artifacts, so it runs with the process-global persistent compile
    cache off — whatever the worker ran before. (xdist's ``loadfile``
    hands files out largest first, not alphabetically: a serve module
    whose autouse fixture points the cache at its own tmp dir and leaves
    it there can come first, and then ``save_artifact`` recompiles
    instead of reusing — ``compiled == 10`` — and executables read back
    from that cache die with "Function ... not found".)"""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny_model()


def _cfg(**kw):
    base = dict(
        buckets=((48, 64),),
        ladder=(2, 1),
        max_batch=2,
        pool_capacity=0,
        queue_capacity=8,
        default_deadline_ms=30000.0,
        stream_cache_size=0,
        warmup=True,
    )
    base.update(kw)
    return ServeConfig(**base)


@pytest.fixture(scope="module")
def fallback_boot(tiny_model, tmp_path_factory):
    """One cold (compile-only) fallback-mode boot + its artifact + a
    reference flow, shared by the round-trip tests."""
    model, variables = tiny_model
    rng = np.random.default_rng(7)
    im1, im2 = _image(rng), _image(rng)
    path = str(tmp_path_factory.mktemp("aot") / "fallback.raftaot")
    eng = ServeEngine(model, variables, _cfg(stream_cache_size=2))
    with eng:
        boot = eng.stats()["boot"]
        counts = eng.program_counts()
        ref_flow = eng.submit(im1, im2).flow
        info = aot.save_artifact(eng, path)
        fp = aot.fingerprint(eng)
    return dict(
        model=model, variables=variables, im1=im1, im2=im2, path=path,
        boot=boot, counts=counts, ref_flow=ref_flow, info=info, fp=fp,
    )


class TestPresets:
    def test_default_preset_is_throughput(self):
        cfg = ServeConfig.preset()
        assert cfg.precision == "throughput"
        assert cfg.compute_dtype == "bfloat16"
        assert cfg.corr_dtype == "bfloat16"
        assert cfg.corr_impl == "fused"

    def test_quality_is_fp32(self):
        cfg = ServeConfig.preset("quality")
        assert cfg.compute_dtype == "float32"
        assert cfg.corr_dtype is None and cfg.corr_impl is None
        assert cfg.model_overrides() == {}

    def test_unknown_preset_raises(self):
        with pytest.raises(ValueError, match="unknown precision preset"):
            ServeConfig.preset("warp9")
        with pytest.raises(ValueError, match="unknown precision preset"):
            ServeConfig(precision="warp9")
        with pytest.raises(ValueError, match="compute_dtype"):
            ServeConfig(compute_dtype="float16")

    def test_preset_composes_with_overrides(self):
        cfg = ServeConfig.preset(
            "quality", buckets=((64, 80),), max_batch=4, warmup=True
        )
        assert cfg.buckets == ((64, 80),)
        assert cfg.max_batch == 4 and cfg.warmup
        assert cfg.compute_dtype == "float32" and cfg.corr_dtype is None

    def test_preset_threads_dtypes_into_model(self):
        """raft_for_serving / build_raft wire the preset's dtypes into
        the actual modules (no init needed — construction is enough)."""
        import jax.numpy as jnp

        from raft_tpu.models import build_raft
        from scripts.serve_bench import tiny_config

        m = build_raft(
            tiny_config().replace(
                **ServeConfig.preset("throughput").model_overrides()
            )
        )
        assert m.feature_encoder.dtype == jnp.bfloat16
        assert m.corr_block.dtype == jnp.bfloat16
        m = build_raft(
            tiny_config().replace(
                **ServeConfig.preset("quality").model_overrides()
            )
        )
        assert m.corr_block.dtype is None  # fp32 storage
        assert m.feature_encoder.dtype is None  # fp32 convs

    def test_preset_knobs_are_the_golden_gated_sets(self):
        """The presets must name exactly the knob combinations whose
        trained-weight EPE is pinned against the reference scalar in
        tests/test_epe_golden.py — a preset that drifts from its gate is
        an ungated deployment config."""
        from raft_tpu.serve.config import PRESETS

        assert PRESETS["throughput"] == dict(
            compute_dtype="bfloat16", corr_dtype="bfloat16",
            corr_impl="fused",
        )  # == the deploy-raft-small-knobs golden case
        assert PRESETS["quality"]["compute_dtype"] == "float32"
        assert set(PRESETS) == {"quality", "throughput"}


class TestCompileCounter:
    def test_backend_compile_events_counted(self):
        import jax
        import jax.numpy as jnp

        n0 = aot.compile_events()
        # a fresh lambda is never cached: must produce >= 1 event
        jax.jit(lambda x: jnp.sin(x) * 3.25071)(np.ones((5,), np.float32))
        assert aot.compile_events() - n0 >= 1


class TestAOTWarmup:
    def test_cold_boot_is_compile_only(self, fallback_boot):
        boot = fallback_boot["boot"]
        assert boot["source"] == "cold"
        assert boot["programs_loaded"] == 0
        assert boot["programs_total"] > 0
        assert boot["programs_compiled"] == boot["programs_total"]
        assert boot["boot_to_ready_ms"] > 0
        # one smoke execution per program family per bucket
        assert boot["smoke_runs"] == 2  # pairwise + stream chain
        # the overlay carries the whole grid; the jit caches carry the
        # rest (nothing): buckets x iters x rungs for pairwise/iterate,
        # buckets x rungs for encode
        assert fallback_boot["counts"]["pairwise"] == 1 * 2 * 2
        assert fallback_boot["counts"]["encode"] == 1 * 2
        assert fallback_boot["counts"]["iterate"] == 1 * 2 * 2

    def test_boot_block_present_without_warmup(self, tiny_model):
        model, variables = tiny_model
        eng = ServeEngine(model, variables, _cfg(warmup=False))
        with eng:
            boot = eng.stats()["boot"]
            assert boot["source"] == "none"
            assert boot["programs_compiled"] == 0
            assert boot["boot_to_ready_ms"] is not None

    def test_fingerprint_covers_program_set_and_weights(self, fallback_boot):
        fp = fallback_boot["fp"]
        for field in (
            "jax", "jaxlib", "backend", "buckets", "ladder", "batch_ladder",
            "pool_capacity", "precision", "variables_hash", "model_hash",
        ):
            assert field in fp, field
        # deterministic for the same engine inputs
        assert fp["buckets"] == ((48, 64),)


class TestArtifactRoundTrip:
    def test_artifact_build_reused_warm_executables(self, fallback_boot):
        info = fallback_boot["info"]
        assert info["programs"] == fallback_boot["boot"]["programs_total"]
        assert info["compiled"] == 0 and info["reused"] == info["programs"]
        assert os.path.exists(fallback_boot["path"])

    def test_artifact_boot_compiles_zero_and_matches(self, fallback_boot):
        """The headline: boot from the artifact, compile NOTHING (both
        counters), serve flow identical to the freshly-compiled engine,
        and stay compile-free under traffic (the CPU CI lane of the
        ISSUE 7 tooling satellite)."""
        eng = ServeEngine(
            fallback_boot["model"], fallback_boot["variables"],
            _cfg(
                stream_cache_size=2, warmup_artifact=fallback_boot["path"]
            ),
        )
        with eng:
            boot = eng.stats()["boot"]
            assert boot["source"] == "artifact"
            assert boot["artifact_error"] is None
            assert boot["programs_compiled"] == 0
            assert boot["programs_loaded"] == boot["programs_total"]
            # the artifact boot must be faster than the recorded cold
            # boot of the same program set (the >= 2x A/B lives in
            # serve_bench --boot-report; this bound is load-tolerant)
            assert (
                boot["boot_to_ready_ms"]
                < fallback_boot["boot"]["boot_to_ready_ms"]
            )
            ev0 = aot.compile_events()
            counts = eng.program_counts()
            res = eng.submit(fallback_boot["im1"], fallback_boot["im2"])
            np.testing.assert_array_equal(res.flow, fallback_boot["ref_flow"])
            with eng.open_stream() as stream:
                for _ in range(3):
                    sres = stream.submit(fallback_boot["im1"])
            assert sres.flow is not None and np.isfinite(sres.flow).all()
            # no compile after artifact load: program table frozen AND
            # zero raw backend-compile events under traffic
            assert eng.program_counts() == counts
            assert aot.compile_events() - ev0 == 0

    def test_mismatched_artifact_refused_with_field(self, fallback_boot):
        model, variables = fallback_boot["model"], fallback_boot["variables"]
        other = ServeEngine(model, variables, _cfg(buckets=((56, 72),)))
        with pytest.raises(ArtifactMismatch) as ei:
            aot.load_artifact(fallback_boot["path"], aot.fingerprint(other))
        assert ei.value.field == "buckets"
        assert "buckets" in str(ei.value)

    def test_corrupt_artifact_refused_as_format(self, fallback_boot, tmp_path):
        bad = tmp_path / "corrupt.raftaot"
        bad.write_bytes(b"not a pickle at all")
        with pytest.raises(ArtifactMismatch) as ei:
            aot.load_artifact(str(bad))
        assert ei.value.field == "format"

    def test_mismatch_degrades_to_compile_never_refuses_boot(
        self, fallback_boot, rng
    ):
        """failure_model: an artifact can make boot fast, never make it
        fail — a mismatched artifact logs its typed reason and the
        engine compiles instead."""
        eng = ServeEngine(
            fallback_boot["model"], fallback_boot["variables"],
            _cfg(
                ladder=(3, 1),  # program-set change: fingerprint mismatch
                warmup_artifact=fallback_boot["path"],
            ),
        )
        with eng:
            boot = eng.stats()["boot"]
            assert boot["source"] == "cold"
            assert boot["programs_loaded"] == 0
            assert boot["programs_compiled"] == boot["programs_total"]
            assert "ladder" in boot["artifact_error"]
            res = eng.submit(_image(rng), _image(rng))
            assert np.isfinite(res.flow).all()


class TestPoolArtifact:
    @pytest.fixture(scope="class")
    def pool_boot(self, tiny_model, tmp_path_factory):
        model, variables = tiny_model
        path = str(tmp_path_factory.mktemp("aot") / "pool.raftaot")
        cfg = _cfg(pool_capacity=2, ladder=(3, 1), stream_cache_size=2)
        eng = ServeEngine(model, variables, cfg)
        rng = np.random.default_rng(3)
        im1, im2 = _image(rng), _image(rng)
        with eng:
            boot = eng.stats()["boot"]
            counts = eng.program_counts()
            ref = {
                n: eng.submit(im1, im2, num_flow_updates=n).flow
                for n in (3, 1)
            }
            aot.save_artifact(eng, path)
        return dict(
            model=model, variables=variables, cfg=cfg, path=path, boot=boot,
            counts=counts, im1=im1, im2=im2, ref=ref,
        )

    def test_pool_cold_boot_covers_pool_programs(self, pool_boot):
        counts = pool_boot["counts"]
        assert counts["pool_step"] == 1
        assert counts["pool_begin_pair"] == 2   # admit rungs (1, 2)
        assert counts["pool_insert"] == 2
        assert counts["pool_gather"] == 2
        assert counts["pool_final"] == 2
        assert counts["pairwise"] == 0          # no whole-request programs
        assert pool_boot["boot"]["programs_compiled"] == (
            pool_boot["boot"]["programs_total"]
        )

    def test_pool_artifact_boot_zero_compiles_and_parity(self, pool_boot):
        import dataclasses

        eng = ServeEngine(
            pool_boot["model"], pool_boot["variables"],
            dataclasses.replace(
                pool_boot["cfg"], warmup_artifact=pool_boot["path"]
            ),
        )
        with eng:
            boot = eng.stats()["boot"]
            assert boot["source"] == "artifact"
            assert boot["programs_compiled"] == 0
            assert boot["programs_loaded"] == boot["programs_total"]
            ev0 = aot.compile_events()
            counts = eng.program_counts()
            # mixed per-request iteration targets: the pool's whole point
            for n in (3, 1, 2):
                res = eng.submit(
                    pool_boot["im1"], pool_boot["im2"], num_flow_updates=n
                )
                assert np.isfinite(res.flow).all()
                if n in pool_boot["ref"]:
                    np.testing.assert_allclose(
                        res.flow, pool_boot["ref"][n], atol=1e-5
                    )
            with eng.open_stream() as stream:
                for _ in range(3):
                    stream.submit(pool_boot["im1"])
            assert eng.program_counts() == counts
            assert aot.compile_events() - ev0 == 0

    def test_same_artifact_covers_only_its_mode(self, pool_boot):
        """A pool-mode artifact names pool_capacity in its fingerprint:
        booting the fallback engine from it must degrade to compile (the
        program sets are disjoint), not half-load."""
        eng = ServeEngine(
            pool_boot["model"], pool_boot["variables"],
            _cfg(
                pool_capacity=0, ladder=(3, 1),
                warmup_artifact=pool_boot["path"],
            ),
        )
        with eng:
            boot = eng.stats()["boot"]
            assert boot["source"] == "cold"
            assert "pool_capacity" in boot["artifact_error"]


class TestPresetChaos:
    def test_throughput_preset_runs_the_fault_ladder(self, rng):
        """A preset-built (bf16 convs + bf16 corr) tiny model runs the
        serve chaos ladder unchanged: concurrent traffic served finite,
        a poisoned request quarantined in isolation."""
        from raft_tpu.models import build_raft, init_variables
        from scripts.serve_bench import tiny_config

        cfg = ServeConfig.preset(
            "throughput",
            buckets=((48, 64),), ladder=(2, 1), max_batch=2,
            pool_capacity=0, queue_capacity=8,
            default_deadline_ms=30000.0, stream_cache_size=0,
        )
        model = build_raft(tiny_config().replace(**cfg.model_overrides()))
        variables = init_variables(model)
        eng = ServeEngine(model, variables, cfg)
        inj = FaultInjector()
        seen = {}

        def first_rid(i, ctx):
            seen.setdefault("rid", ctx["rid"])
            return ctx["rid"] == seen["rid"]

        inj.on("infer.nan_flow", when=first_rid, action=FaultInjector.nan_flow)
        with eng, inj.patch_engine(eng):
            with pytest.raises(PoisonedInput):
                eng.submit(_image(rng), _image(rng))
            res = eng.submit(_image(rng), _image(rng))
            assert np.isfinite(res.flow).all()
            assert res.flow.dtype == np.float32  # output contract is fp32
        assert eng.stats()["quarantined"] == 1


class TestBuildArtifactScript:
    def _mod(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "script_build_warmup_artifact",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts", "build_warmup_artifact.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_build_verify_and_check_refusal(self, tmp_path, capsys):
        mod = self._mod()
        out = str(tmp_path / "tiny.raftaot")
        base = [
            "--tiny", "--ladder", "2,1", "--max-batch", "2",
            "--pool-capacity", "0", "--stream-cache-size", "0",
        ]
        report = mod.main(base + ["--out", out])
        assert os.path.exists(out)
        assert report["programs"] == 1 * 2 * 2  # bucket x iters x rungs
        assert report["verified_programs"] == report["programs"]
        assert '"metric": "warmup_artifact_build"' in capsys.readouterr().out
        # same config checks clean
        ok = mod.main(base + ["--check", out])
        assert ok["ok"] is True
        # a mismatched config is refused with the offending field named
        with pytest.raises(SystemExit) as ei:
            mod.main(
                ["--tiny", "--ladder", "3,1", "--max-batch", "2",
                 "--pool-capacity", "0", "--stream-cache-size", "0",
                 "--check", out]
            )
        assert ei.value.code == 2
        assert '"field": "ladder"' in capsys.readouterr().out


@pytest.mark.slow
class TestBootReportBench:
    def test_boot_report_a_b(self, tmp_path):
        """The full three-tier boot A/B (cold / persistent-cache /
        artifact) on the tiny CPU config: artifact boot compiles zero
        programs and is >= 2x faster than cold (the ISSUE 7 acceptance
        numbers, emitted BENCH-style)."""
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "script_serve_bench_boot",
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts", "serve_bench.py",
            ),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        report = mod.main(
            ["--tiny", "--ladder", "2,1", "--max-batch", "2",
             "--pool-capacity", "2", "--queue-capacity", "8",
             "--boot-report",
             # a cold directory of the test's own: the bench's default is
             # the checkout's fixed .jax_cache, which a test must neither
             # fill nor find warm (its miss boot has to be a miss)
             "--compilation-cache-dir", str(tmp_path / "jax_cache")]
        )
        assert report["boot_artifact_programs_compiled"] == 0
        assert report["boot_artifact_programs_loaded"] == report["programs"]
        assert report["boot_artifact_backend_compiles"] == 0
        assert report["boot_speedup_artifact_vs_cold"] >= 2.0
