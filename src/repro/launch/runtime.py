"""Process-level JAX set-up shared by the entry points.

``device_summary()`` names the device a run used, in the form every entry
point prints as its last line. ``enable_compile_cache()`` turns on JAX's
persistent compilation cache; it is called first thing by ``chip_smoke.py``,
``launch/serve.py`` and the benchmark entry points, so processes that compile
the same programs (a second smoke run, a serve after a smoke) load them from
disk instead of recompiling. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already keeps the cache there and no other directory is set here. Otherwise
the cache lives at ``.jax_cache/`` in the checkout: a fixed path, because the
path is part of what a later process must find again.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileStats:
    """Persistent-cache hits and misses, and seconds spent in backend
    compiles (cache loads included), seen by this process since creation."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0
        self.by_name: dict[str, list] = {}   # name -> [compiles, seconds]
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_time)

    def _on_event(self, event: str, **kwargs) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1

    def _on_time(self, event: str, duration: float, **kwargs) -> None:
        if event == _COMPILE:
            self.compile_s += duration
            entry = self.by_name.setdefault(kwargs.get("fun_name", "?"),
                                            [0, 0.0])
            entry[0] += 1
            entry[1] += duration

    def top(self, n: int = 12) -> list:
        """The ``n`` program names that took the most compile seconds, as
        (name, compiles, seconds)."""
        rows = sorted(self.by_name.items(), key=lambda kv: -kv[1][1])
        return [(k, c, round(t, 3)) for k, (c, t) in rows[:n]]


def device_summary() -> dict:
    """The device as JAX reports it: platform, kind and device count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    Every compiled program is cached, however quick its compile: the smoke
    and serving programs are many small kernels as well as a few large
    loops."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
