"""Persistent XLA compilation cache — the cold-start killer.

The reference's cold start is dominated by dependency + weight fetch (tens of
seconds, SURVEY §3.1); ours would be dominated by XLA compilation.  JAX's
persistent compilation cache writes every compiled executable to disk keyed by
(HLO, flags, platform); a warm pool VM restarting the server hits the cache and
skips compilation entirely — the TPU-native analogue of Zappa keep-warm
(SURVEY §3.4).  Cold-start compile time is a first-class BASELINE metric, so
``timed_compile`` records per-bucket wall time for /metrics and the bench CLI.
"""

from __future__ import annotations

import os
import time
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

# Where the cache lives when neither JAX_COMPILATION_CACHE_DIR nor the config
# names a place: one fixed directory inside the checkout (.gitignore lists
# it).  The path is part of jax's cache key, so a directory that moves never
# hits — nothing here may derive it from a pid, a clock or a tempdir.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "xla"

_configured: str | None = None


def resolve_compile_cache_dir(configured: str | Path | None = None) -> str:
    """The one place a compile-cache path is decided.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (the operator placed the
    cache from outside); otherwise an explicit ``compile_cache_dir`` from
    the config; otherwise :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if configured:
        return str(Path(configured).expanduser())
    return str(DEFAULT_CACHE_DIR)


def setup_compile_cache(cache_dir: str | Path | None = None) -> str:
    """Enable the on-disk compilation cache (idempotent); returns the
    resolved directory (:func:`resolve_compile_cache_dir`).

    With ``JAX_COMPILATION_CACHE_DIR`` set jax already reads that directory
    itself, so no ``jax_compilation_cache_dir`` update is made here.
    Otherwise reconfiguration to a DIFFERENT directory mid-process works
    too: jax initializes its persistent-cache object lazily once and then
    ignores later ``jax_compilation_cache_dir`` updates, so the cache
    object is reset whenever the dir changes (tests re-pointing per case,
    the lifecycle bench's fresh-dir-per-cold-trial path).
    """
    global _configured
    cache_dir = resolve_compile_cache_dir(cache_dir)
    if _configured == cache_dir:
        return cache_dir
    Path(cache_dir).mkdir(parents=True, exist_ok=True)
    # Cache everything: serving executables are precious regardless of size or
    # how fast they compiled.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # Drop the lazily-initialized cache object so the next compile
        # re-reads the config; harmless when the cache was never touched.
        compilation_cache.reset_cache()
    _configured = cache_dir
    return cache_dir


class CompileClock:
    """Accumulates per-executable compile timings for observability."""

    def __init__(self):
        self.entries: list[dict] = []

    def record(self, model: str, bucket, seconds: float):
        self.entries.append({"model": model, "bucket": list(bucket), "seconds": round(seconds, 3)})

    @property
    def total_seconds(self) -> float:
        return sum(e["seconds"] for e in self.entries)

    def per_model(self) -> dict[str, dict]:
        """{model: {entries, seconds}} — the /metrics breakdown, and the
        CompileClock history the lifecycle manager's cold-activation
        estimate reads (serving/lifecycle.py)."""
        out: dict[str, dict] = {}
        for e in self.entries:
            m = out.setdefault(e["model"], {"entries": 0, "seconds": 0.0})
            m["entries"] += 1
            m["seconds"] = round(m["seconds"] + e["seconds"], 3)
        return out


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0
