"""engine/cache.py: persistent-compile-cache setup + CompileClock accounting.

The cache is the cold-start killer (and the thing the lifecycle manager's
warm-activation estimate leans on), yet until this file nothing tier-1
asserted its contract: idempotent setup, live reconfiguration to a new
directory (a measurement switches dirs per cold trial), and an actual
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
    # Live re-point (a fresh directory for each cold trial).
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
    clock, seen = CompileClock(), set()
    for model, bucket, launch, run in (("resnet18", (1,), 0.75, 0.25),
                                       ("resnet18", (4,), 0.5, None),
                                       ("gpt2", (1, 64), 2.0, 0.25)):
        clock.open(model, "predict", {"bucket": list(bucket)},
                   seen=seen).entry.update(launch_s=launch, first_run_s=run)
    per = clock.per_model()
    assert per["resnet18"] == {"entries": 2, "seconds": 1.5}
    assert per["gpt2"] == {"entries": 1, "seconds": 2.25}
    assert clock.total_seconds == pytest.approx(3.75)
    # The same entries as the ledger keeps every lane's: a kind's first key
    # is ``first``, a second key of it ``shape``.
    assert [(e["program"], e["key"], e["cause"]) for e in clock.snapshot()] == [
        ("predict", {"bucket": [1]}, "first"),
        ("predict", {"bucket": [4]}, "shape"),
        ("predict", {"bucket": [1, 64]}, "first")]
    assert clock.first_uses() == {("resnet18", "predict", "uncached"): 2,
                                  ("gpt2", "predict", "uncached"): 1}


# -- the ledger: a first use's stages, heard from inside jax -------------------

ENTRY_KEYS = {"model", "program", "key", "outcome", "restored", "cause",
              "compiles",
              "layer_traces", "trace_s", "lower_s", "cache_read_s",
              "backend_s", "launch_s", "first_run_s", "round"}


def _first_use(clock, fn, *args, key=None, seen=None):
    with clock.open("m", "step", key or {},
                    seen=set() if seen is None else seen) as use:
        out = fn(*args)
        use.launched()
        jax.block_until_ready(out)
    return clock.entries[-1]


def test_first_use_books_its_stages_inside_the_launch(tmp_path):
    import jax.numpy as jnp

    setup_compile_cache(tmp_path / "xla")
    clock = CompileClock()
    e = _first_use(clock, jax.jit(lambda x: jnp.tanh(x @ x).sum()),
                   jnp.ones((64, 64)))
    assert set(e) == ENTRY_KEYS  # nothing that only a dropped export needed
    assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["backend_s"] > 0
    assert e["compiles"] == 1 and e["outcome"] in ("miss", "hit")
    assert (e["trace_s"] + e["lower_s"] + e["cache_read_s"] + e["backend_s"]
            <= e["launch_s"])
    assert e["first_run_s"] >= 0 and e["cause"] == "first"
    assert clock.total_seconds == pytest.approx(
        e["launch_s"] + e["first_run_s"])


def test_an_inner_jit_counts_its_trace_once(tmp_path):
    import jax.numpy as jnp

    setup_compile_cache(tmp_path / "xla")
    inner = jax.jit(lambda x: jnp.sin(x) * 2.0)

    def outer(x):
        return inner(inner(x) + 1.0).sum()

    heard = []

    def raw(event, seconds, **kw):  # what jax itself reports, nested or not
        if heard is not None and event.endswith("/jaxpr_trace_duration"):
            heard.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(raw)
    clock, x = CompileClock(), jnp.ones((32, 32))
    try:
        e = _first_use(clock, jax.jit(outer), x)
    finally:
        traces, heard = list(heard), None
    # The inner function traces inside the outer trace and fires the same
    # event: the outermost alone is booked (it ends last and holds the
    # others), and the stages stay inside the launch.
    assert len(traces) >= 2 and e["trace_s"] == pytest.approx(traces[-1])
    assert e["trace_s"] < sum(traces)
    assert e["trace_s"] + e["lower_s"] + e["backend_s"] <= e["launch_s"]


def test_the_persistent_cache_answers_miss_then_hit(tmp_path):
    import jax.numpy as jnp

    setup_compile_cache(tmp_path / "xla-outcomes")
    clock, seen = CompileClock(), set()

    def fresh():  # one program in a new function: no process-local cache
        return jax.jit(lambda x: jnp.cos(x @ x.T).mean() * 41.0)

    x = jnp.ones((48, 24))
    first = _first_use(clock, fresh(), x, key={"rows": 48}, seen=seen)
    again = _first_use(clock, fresh(), x, key={"rows": 48}, seen=seen)
    assert first["outcome"] == "miss" and first["cache_read_s"] == 0
    assert again["outcome"] == "hit" and again["cache_read_s"] > 0
    assert (first["cause"], again["cause"]) == ("first", "retrace")
    sums = clock.programs("m")
    assert sums["first_uses"] == 2
    assert sums["backend_miss_s"] == pytest.approx(first["backend_s"], abs=1e-5)
    assert sums["backend_hit_s"] == pytest.approx(again["backend_s"], abs=1e-5)
    assert clock.first_uses() == {("m", "step", "miss"): 1,
                                  ("m", "step", "hit"): 1}


def test_a_compile_with_no_scope_open_is_not_booked(tmp_path):
    """A builder's eager operations compile with no first use open on their
    thread: the ledger keeps nothing of them (no ``other`` entry)."""
    import jax.numpy as jnp

    setup_compile_cache(tmp_path / "xla")
    clock, x = CompileClock(), jnp.ones(5)
    jax.block_until_ready(jax.jit(lambda x: x * 3.0 + 7.0)(x))
    assert clock.entries == [] and cache_mod.on_thread.scope is None
    assert clock.programs("builder")["first_uses"] == 0


def test_a_ledger_fault_never_fails_a_compile(tmp_path, monkeypatch):
    import jax.numpy as jnp

    setup_compile_cache(tmp_path / "xla")
    clock = CompileClock()
    monkeypatch.setattr(cache_mod.FirstUse, "book", lambda *a, **k: 1 / 0)
    with clock.open("m", "step", {}, seen=set()):
        out = jax.jit(lambda x: x - 11.0)(jnp.ones(3))
    assert float(out[0]) == -10.0


def test_boot_stamps_tile_the_process_own_start_to_the_last_point(monkeypatch):
    """``utils/boot.py``: the intervals run from the process's start, each
    from the point before it; a point's first stamp stands; the split ends at
    the first point boot has not passed (an in-process server stamps none)."""
    import time

    from pytorch_zappa_serverless_tpu.utils import boot

    monkeypatch.setattr(boot, "_stamps", {})
    assert boot.split() == {}
    assert boot._START <= time.perf_counter()  # this process began before now
    boot.stamp("import")
    boot.stamp("engine")  # ``backend`` not passed: the split stops before it
    first = boot.split()
    assert set(first) == {"import_s"} and first["import_s"] > 0
    boot.stamp("backend")
    boot.stamp("import")  # a second stamp of a point changes nothing
    assert boot.split()["import_s"] == first["import_s"]
    monkeypatch.setattr(boot, "_START", 100.0)
    monkeypatch.setattr(boot, "_stamps", {"import": 106.0, "backend": 112.5,
                                          "engine": 117.0, "http": 117.25})
    assert boot.split() == {"import_s": 6.0, "backend_s": 6.5,
                            "engine_s": 4.5, "http_s": 0.25}


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
