"""chip_smoke.py off the chip: it must fail, fast, and never say ``"ok": true``.

The driver runs the script first in a sandbox with no accelerator (where it
must fail) and then on the chip; these cases hold the sandbox half.  The
rehearsals (the same phases on the CPU at tiny widths) are ``slow``: they
boot the server three times.
"""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd=REPO, script=SMOKE, timeout=600):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, str(script), *args], cwd=str(cwd),
                          env=env, capture_output=True, text=True,
                          timeout=timeout)
    return proc, time.monotonic() - t0


@pytest.mark.parametrize("args", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_no_accelerator_fails_without_a_result(args):
    """JAX_PLATFORMS=cpu and no ``--rehearse``: non-zero within seconds, no
    result line, and no server was ever started."""
    proc, seconds = _run(args)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs 'tpu'" in proc.stderr
    assert seconds < 60, f"took {seconds:.0f}s to notice there is no chip"


def test_alone_in_a_directory_fails_without_a_result(tmp_path):
    """A directory that holds chip_smoke.py and nothing else of the repo."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc, _ = _run([], cwd=tmp_path, script=tmp_path / "chip_smoke.py")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_passes_and_names_the_cpu(chips):
    proc, _ = _run(["--rehearse", "--chips", str(chips)], timeout=1500)
    assert proc.returncode == 0, proc.stderr[-2000:] + proc.stdout[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": chips}}
