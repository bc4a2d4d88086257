"""engine/cache.py: persistent-compile-cache setup + CompileClock accounting.

The cache is the cold-start killer (and the thing the lifecycle manager's
warm-activation estimate leans on), yet until this file nothing tier-1
asserted its contract: idempotent setup, live reconfiguration to a new
directory (the lifecycle bench switches dirs per cold trial), and an actual
warm-vs-cold ``build_engine`` wall-time win on the CPU harness.
"""

import jax
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine import cache as cache_mod
from pytorch_zappa_serverless_tpu.engine.cache import (
    DEFAULT_CACHE_DIR, CompileClock, resolve_compile_cache_dir,
    setup_compile_cache)
from pytorch_zappa_serverless_tpu.engine.loader import build_engine


def test_setup_compile_cache_idempotent(tmp_path):
    d = tmp_path / "cache-a"
    got = setup_compile_cache(d)
    assert got == str(d) and d.is_dir()
    assert jax.config.jax_compilation_cache_dir == str(d)
    # Serving executables are precious regardless of size/compile time.
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    # Same dir again: a no-op, not a reconfiguration.
    assert setup_compile_cache(d) == str(d)
    assert jax.config.jax_compilation_cache_dir == str(d)


def test_setup_compile_cache_reconfigures_to_new_dir(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    setup_compile_cache(a)
    # Live re-point (the lifecycle bench's fresh-dir-per-cold-trial path).
    assert setup_compile_cache(b) == str(b)
    assert jax.config.jax_compilation_cache_dir == str(b)
    assert b.is_dir()


# -- the resolver: one place decides where the cache lives --------------------

REPO = cache_mod.Path(__file__).resolve().parents[1]


def test_resolver_env_wins_and_no_directory_is_set_in_code(tmp_path,
                                                           monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the cache lives there (jax reads the
    variable itself) and the program makes no jax_compilation_cache_dir
    update of its own — explicit config or not."""
    placed = tmp_path / "placed-from-outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    monkeypatch.setattr(cache_mod, "_configured", None)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (updates.append(name), real_update(name, value)))
    assert resolve_compile_cache_dir(tmp_path / "from-config") == str(placed)
    assert setup_compile_cache(tmp_path / "from-config") == str(placed)
    assert placed.is_dir() and not (tmp_path / "from-config").exists()
    assert "jax_compilation_cache_dir" not in updates
    # The size/time floors are still lifted: every executable is cached.
    assert "jax_persistent_cache_min_compile_time_secs" in updates


def test_resolver_explicit_config_when_env_unset(tmp_path):
    assert resolve_compile_cache_dir(tmp_path / "cfg") == str(tmp_path / "cfg")
    assert resolve_compile_cache_dir("~/x").startswith(
        str(cache_mod.Path.home()))


@pytest.mark.parametrize("unset", [None, ""])
def test_resolver_default_lands_inside_the_checkout(unset):
    """No variable, no config: one fixed directory inside the checkout —
    what ServeConfig's default ("") resolves to."""
    got = cache_mod.Path(resolve_compile_cache_dir(unset))
    assert got == DEFAULT_CACHE_DIR == REPO / ".cache" / "xla"
    assert ServeConfig().compile_cache_dir == ""
    # .gitignore lists it: the cache is built at run time, never committed.
    assert ".cache/" in (REPO / ".gitignore").read_text().split()


def test_resolver_same_path_on_two_calls(tmp_path, monkeypatch):
    """The path is part of jax's cache key: nothing in it may come from a
    pid, a clock or a tempdir, so two resolutions agree — in this process
    and in a fresh one."""
    import subprocess
    import sys

    assert resolve_compile_cache_dir() == resolve_compile_cache_dir()
    code = ("from pytorch_zappa_serverless_tpu.engine.cache import "
            "resolve_compile_cache_dir as r; print(r())")
    fresh = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                           capture_output=True, text=True, timeout=120,
                           check=True).stdout.strip()
    assert fresh == resolve_compile_cache_dir()


def test_compile_clock_per_model_totals():
    clock = CompileClock()
    clock.record("resnet18", (1,), 1.0)
    clock.record("resnet18", (4,), 0.5)
    clock.record("gpt2", (1, 64), 2.25)
    per = clock.per_model()
    assert per["resnet18"] == {"entries": 2, "seconds": 1.5}
    assert per["gpt2"] == {"entries": 1, "seconds": 2.25}
    assert clock.total_seconds == pytest.approx(3.75)


def _cfg(cache_dir):
    return ServeConfig(
        compile_cache_dir=str(cache_dir), warmup_at_boot=True,
        models=[ModelConfig(name="resnet18", batch_buckets=(1, 4),
                            dtype="float32",
                            extra={"image_size": 64, "resize_to": 72})])


def test_warm_cache_build_is_faster_than_cold(tmp_path):
    """Two build_engine runs against the SAME cache dir: the second's
    compiles are persistent-cache deserializes and must be cheaper.

    Compares the CompileClock's compile seconds (not whole-boot wall time):
    weight synthesis is identical both runs and would only dilute the
    signal.  The margin is deliberately generous — CI boxes jitter — but a
    broken cache (every bucket recompiling) fails it by multiples.
    """
    import time

    cache = tmp_path / "xla"
    t0 = time.perf_counter()
    cold_engine = build_engine(_cfg(cache))
    cold_wall = time.perf_counter() - t0
    cold_compile = cold_engine.clock.total_seconds
    cold_engine.shutdown()
    assert cold_compile > 0
    assert any(cache.iterdir()), "persistent cache dir stayed empty"

    t0 = time.perf_counter()
    warm_engine = build_engine(_cfg(cache))
    warm_wall = time.perf_counter() - t0
    warm_compile = warm_engine.clock.total_seconds
    warm_engine.shutdown()

    assert warm_compile < cold_compile * 0.8 + 0.15, (
        f"warm compiles ({warm_compile:.2f}s) not meaningfully cheaper than "
        f"cold ({cold_compile:.2f}s); persistent cache not hitting")
    # Whole-boot sanity: warm boot never costs MORE than cold + weights
    # jitter headroom.
    assert warm_wall < cold_wall + 2.0, (warm_wall, cold_wall)
