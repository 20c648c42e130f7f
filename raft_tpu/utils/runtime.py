"""Process-level runtime plumbing shared by the entry points: where the
persistent compile cache lives, which device a result came from, and
whether this process already holds the chip.

One chip belongs to one process at a time, and a number measured on the
CPU backend says nothing about the TPU — so the timed/smoked entry points
(``chip_smoke.py``, ``bench.py``, ``scripts/serve_bench.py``,
``scripts/train_bench.py``) call :func:`require_tpu` before they measure
anything and stamp :func:`device_info` on every result they print.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, Optional

import jax

__all__ = [
    "CACHE_DIR_ENV",
    "DEFAULT_CACHE_DIR",
    "enable_persistent_cache",
    "device_info",
    "require_tpu",
    "holds_tpu",
]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: the cache key includes the path, so it is fixed
# (no pid, time or temp name) — a directory that moves never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Wire the JAX persistent compilation cache; returns the directory.

    The one place this repo chooses a cache path (entry points, the
    trainer script, ``ServeEngine``). ``JAX_COMPILATION_CACHE_DIR``
    wins when set: JAX already points at it and nothing here moves it —
    a differing ``cache_dir`` is ignored with one log line. Otherwise
    ``cache_dir`` is used, defaulting to ``<checkout>/.jax_cache``.

    Process-global config (every jit in the process benefits); must run
    before the programs it should capture compile. Thresholds are
    dropped to zero because serve programs are exactly the thing worth
    caching — the default min-compile-time heuristic is tuned for
    notebooks, not replica boot.
    """
    env_dir = os.environ.get(CACHE_DIR_ENV)
    if env_dir:
        if cache_dir and os.path.abspath(cache_dir) != os.path.abspath(env_dir):
            logging.getLogger(__name__).warning(
                "compilation cache dir %s ignored: %s=%s is set",
                cache_dir, CACHE_DIR_ENV, env_dir,
            )
        cache_dir = env_dir
    elif not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def device_info() -> Dict[str, object]:
    """The device as JAX reports it: platform, device kind, count."""
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def require_tpu(what: str) -> Dict[str, object]:
    """Refuse (``SystemExit``) to time or smoke ``what`` off the chip;
    returns :func:`device_info` when the platform is ``tpu``."""
    dev = device_info()
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU, but JAX reports platform "
            f"{dev['platform']!r} ({dev['kind']}, {dev['count']} device(s)) "
            "— a CPU run measures the CPU backend and the Pallas "
            "interpreter, not the system; refusing"
        )
    return dev


def holds_tpu() -> bool:
    """True when THIS process has initialised JAX on a TPU — a child that
    needs the chip would then fail or hang. Never initialises a backend
    itself: a process that has not touched JAX answers False."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() and (
        jax.default_backend() == "tpu"
    )
