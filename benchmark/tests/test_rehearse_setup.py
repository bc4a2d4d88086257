"""``run.py --rehearse --trace 1``: set-up as the server itself booked it
(PR 42).  The eleven metrics of ``readers/programs.py`` (the cell's one of
the three ``first_uses_in_window*``) are on the last line of a traced
rehearsal, set-up's lie inside what the harness's own stopwatch saw, and no
program was first used inside a quiet window.  (A file of its own: ``test_rehearse.py`` is the accepted benchmark's.)"""

import json
import re
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SETUP_FROM_INSIDE = {"warmup_trace_s", "warmup_lower_s", "warmup_cache_read_s",
                     "warmup_backend_s", "warmup_first_run_s",
                     "warmup_ledger_pct", "boot_import_s", "boot_backend_s"}


def test_traced_rehearsal_prints_set_up_from_inside():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "gpt2large-int8-chat", "--seed", "3000000007", "--seconds", "4",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert SETUP_FROM_INSIDE <= set(got)
    assert got["first_uses_in_window"] == 0 == got["compiles_in_window"]
    assert not {"first_uses_in_window.xl", "first_uses_in_window.bulk"} \
        & set(got)
    assert 0 < got["warmup_ledger_pct"] <= 100
    assert got["warmup_trace_s"] > 0 and got["warmup_lower_s"] > 0
    split = dict(re.findall(r"(\w+) ([0-9.]+)", proc.stdout.split(
        "set-up split (s): ")[1].splitlines()[0]))
    stages = sum(got[k] for k in ("warmup_trace_s", "warmup_lower_s",
                                  "warmup_cache_read_s", "warmup_backend_s",
                                  "warmup_first_run_s"))
    assert stages <= got["warmup_s"] + float(split["reference_requests"]) \
        + 0.01
    assert got["boot_import_s"] > 0 and got["boot_backend_s"] >= 0
    assert got["boot_import_s"] + got["boot_backend_s"] \
        <= float(split["spawn_to_engine"]) + 0.5
