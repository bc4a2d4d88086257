"""EvaByte's family module: byte and operation counts against sums worked by
hand, the check against its own controls, and ``--rehearse`` of the cell."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import families, stage_weights
from benchmark.families import evabyte
from benchmark.run import serve_fragment

ROOT = Path(__file__).resolve().parents[2]
PATH = ROOT / "benchmark" / "configs" / "evabyte-16l.json"
CONFIG = {**json.loads(PATH.read_text()), "file": str(PATH)}
SERVE = CONFIG["serve"]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "evabyte-16l-docqa"


def test_configuration_holds_the_catalog_s_numbers():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "EvaByte")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    arch = SERVE["extra"]["arch"]
    assert (arch["hidden_size"], arch["intermediate_size"], arch["heads"],
            arch["window_size"], arch["chunk_size"], arch["vocab_size"]) == (
        4096, 11008, 32, 2048, 16, 320)
    assert families.load(CONFIG) is evabyte


def test_a_query_at_5000_reads_905_exact_rows_and_256_summaries():
    exact, summaries = evabyte.span_rows(SERVE, 5000)
    assert (int(exact), int(summaries)) == (905, 256)
    exact, summaries = evabyte.span_rows(SERVE, np.asarray([0, 2047, 2048]))
    assert exact.tolist() == [1, 2048, 1]
    assert summaries.tolist() == [0, 0, 128]
    # A row: K and V, 16 layers, 4,096 wide, two bytes each.
    assert evabyte.row_bytes(SERVE) == 16 * 16384 == 262_144


def test_decode_step_bytes_by_hand():
    layer = 2 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4 * 4 * 4096
    weights = 16 * layer + 4 * 4096 + 2 * 4096 * 320
    assert layer == 404_815_872 and weights == 6_479_691_776
    assert evabyte.weight_bytes(SERVE) == weights
    # One stream that decoded all the window long, prompt 5,000, one byte:
    # 905 + 256 rows of every layer.
    assert evabyte.decode_step_bytes(CONFIG, SERVE, [(50.0, 5000, 1)], 50.0) \
        == weights + 1161 * 262_144
    # Two bytes from 2,047: positions 2,047 (2,048 exact) and 2,048 (1 exact
    # and 128 summaries), half the window: (2048 + 129) / 2 / 2 rows.
    assert evabyte.decode_step_bytes(CONFIG, SERVE, [(25.0, 2047, 2)], 50.0) \
        == weights + 544.25 * 262_144


def test_prefill_flops_by_hand():
    # 4,096 bytes: two windows.  Exact rows 2 x (1 + ... + 2048), summaries
    # 128 for each query of the second window.
    rows = 2 * (2048 * 2049 // 2) + 2048 * 128
    attend = 16 * (2 * 2 * 4096 * rows + 10 * 4096 * 4096)
    assert evabyte.attend_flops(SERVE, 4096) == attend
    matrices = 2 * 4096 * 16 * (4 * 4096 ** 2 + 3 * 4096 * 11008)
    assert matrices == 26_525_718_020_096  # 2 x 4,096 x 3,238,002,688
    assert evabyte.prefill_flops(CONFIG, SERVE, 4096) \
        == matrices + attend + 2 * 4096 * 320


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    serve, _ = serve_fragment(CONFIG, rehearse=True)
    ckpt = tmp_path_factory.mktemp("w") / "w.tpu.safetensors"
    stage_weights.main([str(ckpt), CONFIG["file"], json.dumps(serve)])
    return serve, ckpt


def test_check_passes_the_reference_s_own_greedy_and_fails_another(rehearsal):
    serve, ckpt = rehearsal
    ids = [int(t) for t in np.random.default_rng(0).integers(0, 320, 60)]
    toks = []
    for _ in range(8):  # across position 64: two finished windows
        logits = evabyte.reference_logits(CONFIG, serve, ckpt,
                                          [ids + toks])[0]
        toks.append(int(np.argmax(logits[-1])))
    run = {"ids": ids, "tokens": toks, "again": toks, "error": None}
    got = evabyte.check(CONFIG, serve, ckpt, [run])
    assert got["ok"] and got["worst"] == 0.0
    bad = {**run, "tokens": [(t + 1) % 320 for t in toks]}
    assert not evabyte.check(CONFIG, serve, ckpt, [bad])["ok"]


def names(kind):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "4", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert set(line["metrics"]) <= names("per_layer")
        assert {"span_share", "summary_row_share", "kv_live_share",
                "tokens_per_round"} <= set(line["metrics"])
        # Every prompt ends past the first window: summaries are read.
        assert 0 < line["metrics"]["summary_row_share"]["value"] < 1
        assert 0 < line["metrics"]["span_share"]["value"] < 1
    else:
        assert set(line["metrics"]) == names("end_to_end") == {
            "ttft_p75_ms", "tpot_p50_ms", "setup_s"}


def test_attend_roofline_reads_the_capture_s_own_rounds():
    """20 rounds in the capture held 3,500 rows each on average: 8 steps x
    3,500 rows x 262,144 B over 819 GB/s is 8.963 ms a run; the kernel took
    12 ms a run, so 74.7%.  Without the capture's counters (the parent's
    program) there is nothing to read."""
    from benchmark.readers import rows

    def counters(total, rounds):
        return {k: {"sum": total, "count": rounds}
                for k in ("span_rows", "summary_rows", "live_positions")}

    ctx = {
        "config": CONFIG, "serve": SERVE, "device": {"kind": "TPU v5 lite"},
        "peaks": json.loads((ROOT / "benchmark" / "peaks.json").read_text()),
        "trace": {"window_s": 2.0, "programs": {"segment": {
            "runs": 20, "seconds": 2.2,
            "ops": {"decode_attention": 0.24}}}},
        "run": {"gen_before": counters(0, 0), "gen_after": counters(9, 3),
                "profile": {"generation": {"evabyte16l": {
                    "before": counters(100_000, 40),
                    "after": counters(170_000, 60)}}}}}
    want = 100 * (8 * 3500 * 262_144 / 819e9) / 0.012
    assert rows.read(ctx, "attend_roofline") == pytest.approx(want)
    assert 74 < want < 75
    ctx["run"]["profile"] = {"dir": "x"}
    assert rows.read(ctx, "attend_roofline") is None
    ctx["run"]["gen_after"] = {"segment_rounds": 3}
    assert rows.read(ctx, "span_share") is None


def test_prefill_roofline_is_the_window_s_operations_over_its_prefill_spans():
    """Two prompts of 4,096 and 6,144 bytes need 26.98 + 40.65 TFLOP
    (``prefill_flops``: the hand count above); their prefills held the
    dispatch thread 0.4 + 0.6 s: 67.6 TFLOP in 1.0 s of 197 is 34.3%.  A
    failed request brings no operations; a device with no stated peak (the
    rehearsal's CPU) and a program with no such spans give nothing."""
    from benchmark.readers import prefill

    def phases(launch_ms, fetch_ms, n):
        return {"host_phases": {
            "prefill.launch": {"sum_ms": launch_ms, "count": n},
            "prefill.fetch": {"sum_ms": fetch_ms, "count": n}}}

    ctx = {
        "config": CONFIG, "serve": SERVE, "device": {"kind": "TPU v5 lite"},
        "peaks": json.loads((ROOT / "benchmark" / "peaks.json").read_text()),
        "run": {"gen_before": phases(5.0, 100.0, 2),
                "gen_after": phases(6.0, 1099.0, 4),
                "records": [{"error": None, "prompt_len": 4096},
                            {"error": None, "prompt_len": 6144},
                            {"error": "x", "prompt_len": 12288}]}}
    flops = sum(evabyte.prefill_flops(CONFIG, SERVE, n) for n in (4096, 6144))
    assert prefill.read(ctx, "span_peak_pct") == pytest.approx(
        100 * flops / 197e12 / 1.0)
    assert 33 < prefill.read(ctx, "span_peak_pct") < 36
    assert prefill.read({**ctx, "device": {"kind": "cpu"}}, "span_peak_pct") is None
    ctx["run"]["gen_before"] = ctx["run"]["gen_after"] = {}
    assert prefill.read(ctx, "span_peak_pct") is None


def test_docqa_mix_is_open_loop_and_every_prompt_passes_the_first_window():
    """The cell's traffic as ISSUE 35 fixed it: ``open_loop``, where the
    seed orders lengths and gaps (``benchmark/tests/test_traffic.py`` holds
    every mix to one multiset); every prompt ends past the first window and
    inside a bucket the configuration compiles, and no answer passes
    ``max_new_tokens``."""
    from benchmark import traffic
    from benchmark.generators import open_loop

    mix = traffic.load_mix("docqa-bytes")
    assert mix["generator"] == "open_loop" and mix["admit_max"] == 1
    assert set(mix) <= {"generator", "who", "rate_per_s", "rate_from",
                        "prompt_tokens", "answer_tokens", "admit_max",
                        "profile_seconds"}
    reqs = open_loop.plan(mix, 50.0, 3_000_000_001, 320, 1.0, 8)
    assert len(reqs) == round(mix["rate_per_s"] * 50.0)
    assert abs(reqs[-1]["due"] - 50.0) < 1e-9
    window = SERVE["extra"]["arch"]["window_size"]
    assert all(window < len(r["ids"]) <= max(SERVE["seq_buckets"])
               for r in reqs)
    assert all(64 <= r["max_new"] <= SERVE["extra"]["max_new_tokens"]
               for r in reqs)
    assert all(0 <= t < 320 for r in reqs for t in r["ids"])
    assert {traffic.bucket_for(len(r["ids"]), SERVE["seq_buckets"])
            for r in reqs} <= set(SERVE["seq_buckets"])


@pytest.mark.parametrize("seed", [1, 2, 3, 3_500_004_101, 3_500_004_203,
                                  3_500_004_401, 2**31 + 5])
def test_at_the_mix_s_rate_no_two_requests_meet(seed):
    """What makes the cell steady (PERF.md section 6, PR 35): at its rate a
    window holds so few requests that under every seed each has left before
    the next is due, at a byte a 12.5 ms (a quarter over the step the chip
    measured) after 1 s of prefill (its prompts fall in the 4,096 and 6,144
    buckets: 0.4 and 0.6 s); and a capture of ``profile_seconds`` in the
    middle of the window holds the first whole."""
    from benchmark import traffic
    from benchmark.generators import open_loop

    mix = traffic.load_mix("docqa-bytes")
    reqs = open_loop.plan(mix, 50.0, seed, 320, 1.0, 8)
    ends = [r["due"] + 1.0 + 0.0125 * r["max_new"] for r in reqs]
    assert all(end < nxt["due"] for end, nxt in zip(ends, reqs[1:]))
    lead = (50.0 - traffic.profile_seconds(mix)) / 2
    assert lead < reqs[0]["due"] and ends[0] < 50.0 - lead
