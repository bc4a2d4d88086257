"""Test harness: force the CPU backend with 8 virtual devices.

Unit/parity/sharding tests never need the real chip (SURVEY §4): numerics are
checked against torch-CPU, and multi-chip sharding is exercised on a virtual
8-device CPU mesh exactly as the driver's ``dryrun_multichip`` does.  Real-TPU
latency tests live behind ``-m tpu`` and are skipped here.
"""

import os

# TPUSERVE_TEST_PLATFORM=tpu runs the suite against the real chip (enabling
# the `-m tpu` latency tests); default is the hermetic CPU harness.
_platform = os.environ.get("TPUSERVE_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
# Tests place their compile caches themselves (ServeConfig.compile_cache_dir
# under tmp_path); a cache placed from outside would override every one of
# them (engine/cache.py resolve_compile_cache_dir).
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

# Runtime lock-order sanitizer (docs/ANALYSIS.md): on by default for the
# whole suite, so every tier-1 run doubles as a sanitizer run — the package
# enables it at import when the knob is set, and tests/test_analyze.py
# cross-checks the observed acquisition orders against the static lock
# graph at the end.  TPUSERVE_LOCKWATCH=0 opts out.
os.environ.setdefault("TPUSERVE_LOCKWATCH", "1")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu: needs the real TPU chip (skipped in CI)")
    config.addinivalue_line("markers", "slow: long-running (SD-1.5 scale) test")


def pytest_collection_modifyitems(config, items):
    import jax

    on_tpu = jax.default_backend() == "tpu"
    skip = pytest.mark.skip(reason="real TPU not available under test harness")
    for item in items:
        if "tpu" in item.keywords and not on_tpu:
            item.add_marker(skip)
