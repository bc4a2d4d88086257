"""``run.py --rehearse``: the whole path on the CPU at tiny widths, ending in
a well-formed last line that names platform ``cpu``."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def names(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("cell,trace", [("gpt2large-int8-chat", 0),
                                        ("gpt2xl-doc-bulk", 1)])
def test_rehearsal_last_line(cell, trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "3000000001", "--seconds", "4", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # never passes for a chip run
    assert "memory_peak_bytes" in line["device"]
    if trace:
        # No device plane on the CPU: trace-born metrics are left out, the
        # counters and the client's own numbers are there.
        assert set(line["metrics"]) <= names("per_layer", cell)
        assert {"prefill_batch", "compiles_in_window.bulk", "warmup_s"} \
            <= set(line["metrics"])
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == names("end_to_end", cell)
    for m in line["metrics"].values():
        assert isinstance(m["value"], (int, float)) and m["unit"]
    assert "set-up split" in proc.stdout


def test_unknown_workload_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "nope", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and not proc.stdout.strip()
