"""A second family enters as new files: the toy of ``benchmark/tests/toy``
(family module, plain reference, configuration) runs ``run.py --rehearse`` as
a cell of a temporary copy of ``BENCHMARK.json``, and no file of the harness
was edited for it."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import families
from benchmark.tests.toy import family as toy

ROOT = Path(__file__).resolve().parents[2]
FILE = "benchmark/tests/toy/toy-decoder.json"
CONFIG = json.loads((ROOT / FILE).read_text())


@pytest.fixture(scope="module")
def benchmark_file(tmp_path_factory):
    """The repo's benchmark with one more configuration and one more cell,
    which reports what the int8 chat cell reports."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-decoder", "source": "nobody's",
                             "file": FILE, "reduced": [], "why": "the seam"})
    bench["workloads"].append({"name": "toy-chat", "config": "toy-decoder",
                               "traffic": "chat-int8", "chips": 1,
                               "why": "a family the harness never heard of"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "gpt2large-int8-chat" in m.get("workloads", []):
                m["workloads"].append("toy-chat")
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


@pytest.mark.parametrize("trace", [0, 1])
def test_the_toy_rehearses_to_a_correct_last_line(benchmark_file, trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "toy-chat",
         "--seed", "2147483777", "--seconds", "4", "--trace", str(trace),
         "--rehearse", "--benchmark-file", str(benchmark_file)],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    if trace:
        assert {"tokens_per_round", "kv_live_share", "first_emit_p50_ms",
                "warmup_s"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"ttft_p90_ms", "tpot_p50_ms",
                                        "setup_s"}
    # The toy's own check ran, on its own reference, and said what it saw.
    assert "served tokens are the float32 reference's best" in proc.stdout
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "[bench] correct True: request errors 0 (limit 0)")


def test_the_repo_s_benchmark_has_no_toy():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "toy-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_the_toy_s_counts_by_hand():
    assert families.load(CONFIG) is toy
    serve = CONFIG["serve"]
    # 1 layer x (4 x 64² + 2 x 64 x 128) + 512 x 64 weights, two bytes each.
    weights = 2 * (32_768 + 32_768)
    # One stream decoding for half the window holds 20 + 16 / 2 positions:
    # 14 on average, K and V, one layer, 64 wide, two bytes each.
    assert toy.decode_step_bytes(CONFIG, serve, [(5.0, 20, 16)], 10.0) \
        == weights + 14 * 1 * 2 * 64 * 2
    assert toy.prefill_flops(CONFIG, serve, 16) \
        == 2 * 16 * 32_768 + 2 * 16 * 16 * 64 + 2 * 64 * 512
