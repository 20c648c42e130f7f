"""Where the persistent compile cache lives, and which entry points need
the chip (``utils.runtime``).

One helper chooses the path for every entry point and for
``ServeEngine``: ``JAX_COMPILATION_CACHE_DIR`` wins when set (nothing in
code points JAX anywhere else), otherwise an explicit directory,
otherwise the fixed ``<checkout>/.jax_cache``. The path is part of the
cache key, so it never carries a pid, a time or a temp name.

The measuring/smoking entry points refuse to run off a TPU (no quiet
CPU numbers), and a process that holds the chip refuses to spawn a
worker that would need it. CPU-only, nothing compiles here.
"""

import logging
import os
import re

import jax
import pytest

from raft_tpu.utils import runtime

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CONFIG_KEYS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def cache_config():
    """Process-global JAX cache config, restored after the test (other
    modules in this worker must see the cache exactly as they left it)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in _CONFIG_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _tiny_engine(cfg_kw):
    from tests.test_serve_pool import _config, _tiny_model

    from raft_tpu.serve import ServeEngine

    model, variables = _tiny_model()
    return ServeEngine(model, variables, _config(**cfg_kw))


class TestEnvSet:
    def test_helper_uses_env_dir(self, cache_config, monkeypatch, tmp_path):
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv(runtime.CACHE_DIR_ENV, env_dir)
        assert runtime.enable_persistent_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir

    def test_differing_explicit_dir_is_ignored_with_one_log_line(
        self, cache_config, monkeypatch, tmp_path, caplog
    ):
        env_dir = str(tmp_path / "from_env")
        other = str(tmp_path / "explicit")
        monkeypatch.setenv(runtime.CACHE_DIR_ENV, env_dir)
        with caplog.at_level(logging.WARNING, logger=runtime.__name__):
            assert runtime.enable_persistent_cache(other) == env_dir
        assert jax.config.jax_compilation_cache_dir == env_dir
        assert not os.path.exists(other)  # never even created
        lines = [r for r in caplog.records if "ignored" in r.getMessage()]
        assert len(lines) == 1 and other in lines[0].getMessage()

    def test_same_explicit_dir_is_silent(
        self, cache_config, monkeypatch, tmp_path, caplog
    ):
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv(runtime.CACHE_DIR_ENV, env_dir)
        with caplog.at_level(logging.WARNING, logger=runtime.__name__):
            assert runtime.enable_persistent_cache(env_dir) == env_dir
        assert not caplog.records

    def test_engine_leaves_config_at_env_dir(
        self, cache_config, monkeypatch, tmp_path
    ):
        """``ServeConfig.compilation_cache_dir`` that differs from the
        environment's is ignored: the engine does not move the cache."""
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv(runtime.CACHE_DIR_ENV, env_dir)
        jax.config.update("jax_compilation_cache_dir", env_dir)  # as at import
        _tiny_engine({"compilation_cache_dir": str(tmp_path / "cfg")})
        assert jax.config.jax_compilation_cache_dir == env_dir
        _tiny_engine({})  # no dir configured: cache untouched
        assert jax.config.jax_compilation_cache_dir == env_dir


class TestEnvUnset:
    def test_default_is_checkout_jax_cache(self, cache_config, monkeypatch):
        monkeypatch.delenv(runtime.CACHE_DIR_ENV, raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert runtime.DEFAULT_CACHE_DIR == want
        assert runtime.enable_persistent_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_explicit_dir_is_honoured(self, cache_config, monkeypatch, tmp_path):
        monkeypatch.delenv(runtime.CACHE_DIR_ENV, raising=False)
        d = str(tmp_path / "explicit")
        assert runtime.enable_persistent_cache(d) == d
        assert jax.config.jax_compilation_cache_dir == d

    def test_engine_uses_its_configured_dir(
        self, cache_config, monkeypatch, tmp_path
    ):
        monkeypatch.delenv(runtime.CACHE_DIR_ENV, raising=False)
        d = str(tmp_path / "cfg")
        _tiny_engine({"compilation_cache_dir": d})
        assert jax.config.jax_compilation_cache_dir == d

    def test_default_path_is_stable(self):
        """No pid, no time, no temp name in the fixed path."""
        import tempfile

        d = runtime.DEFAULT_CACHE_DIR
        assert not d.startswith(tempfile.gettempdir() + os.sep)
        assert str(os.getpid()) not in os.path.basename(d)
        assert os.path.basename(d) == ".jax_cache"
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


class TestOnePlaceChoosesThePath:
    # save_artifact's temporary switch-off restores the same path
    ALLOWED = {
        os.path.join("raft_tpu", "utils", "runtime.py"),
        os.path.join("raft_tpu", "serve", "aot.py"),
    }

    def _sources(self):
        for top in ("raft_tpu", "scripts"):
            for root, _, files in os.walk(os.path.join(REPO, top)):
                for name in files:
                    if name.endswith(".py"):
                        yield os.path.join(root, name)
        for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
            yield os.path.join(REPO, name)

    def test_no_other_code_sets_the_cache_dir(self):
        setter = re.compile(
            r"jax_compilation_cache_dir[\"']\s*,|set_cache_dir\(|"
            r"initialize_cache\("
        )
        offenders = []
        for path in self._sources():
            with open(path) as f:
                if setter.search(f.read()):
                    offenders.append(os.path.relpath(path, REPO))
        assert set(offenders) <= self.ALLOWED, offenders

    @pytest.mark.parametrize(
        "rel",
        ["chip_smoke.py", "bench.py", "scripts/train.py",
         "scripts/serve_bench.py", "scripts/train_bench.py",
         "raft_tpu/serve/engine.py"],
    )
    def test_entry_point_uses_the_helper(self, rel):
        with open(os.path.join(REPO, rel)) as f:
            assert "enable_persistent_cache(" in f.read()

    def test_no_temp_dir_cache(self):
        """The cache dir is never a ``mkdtemp`` (the path is part of the
        key: a directory that moves never hits)."""
        for path in self._sources():
            with open(path) as f:
                src = f.read()
            for m in re.finditer(r"mkdtemp\(([^)]*)\)", src):
                assert "cache" not in m.group(1).lower(), (path, m.group(0))


def _load_script(rel):
    import importlib.util

    name = "guard_" + os.path.basename(rel).replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestNeedsTheChip:
    def test_require_tpu_refuses_on_cpu_and_names_the_device(self):
        with pytest.raises(SystemExit) as ei:
            runtime.require_tpu("some_bench.py")
        msg = str(ei.value)
        assert "some_bench.py" in msg and "'cpu'" in msg
        assert runtime.device_info() == {
            "platform": "cpu",
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }

    @pytest.mark.parametrize(
        "rel", ["bench.py", "scripts/serve_bench.py", "scripts/train_bench.py"]
    )
    def test_untiny_bench_refuses_off_tpu(self, rel, monkeypatch, capsys):
        """Without ``--tiny`` nothing is timed on host devices: the run
        ends at ``require_tpu`` before it builds or prints anything."""
        monkeypatch.setattr("sys.argv", [rel])
        mod = _load_script(rel)
        with pytest.raises(SystemExit) as ei:
            mod.main()
        assert "needs a TPU" in str(ei.value)
        assert capsys.readouterr().out == ""

    def test_chip_smoke_refuses_off_tpu_before_compiling(
        self, monkeypatch, capsys
    ):
        from raft_tpu.serve import aot

        monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
        mod = _load_script("chip_smoke.py")
        before = aot.compile_events()
        assert mod.main() == 1
        assert aot.compile_events() == before
        out = capsys.readouterr()
        assert out.out == "" and "needs a TPU" in out.err

    def test_holding_the_chip_refuses_to_spawn_workers(self, monkeypatch):
        """One process per chip. On CPU nothing is held and spawning is
        untouched; a parent that holds the TPU gets a typed refusal from
        both spawn doors instead of a child that fails or hangs."""
        from raft_tpu.serve import worker
        from raft_tpu.serve.errors import ServeError

        assert runtime.holds_tpu() is False
        worker._refuse_if_parent_holds_tpu()  # no-op on CPU
        monkeypatch.setattr(runtime, "holds_tpu", lambda: True)
        with pytest.raises(ServeError, match="one process per chip"):
            worker.start_remote_worker(_load_script)
        client = worker.ProcessEngineClient(_load_script)
        with pytest.raises(ServeError, match="one process per chip"):
            client.start()
