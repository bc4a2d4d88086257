"""Mellum 2's family module: the configuration against the catalog, byte and
operation counts against sums worked by hand, the check (the reference's own
greedy passes; another answer and each control do not), the three new
per-layer metrics on recorded numbers, and ``--rehearse`` of the cell."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import families, stage_weights
from benchmark.families import mellum
from benchmark.readers import kinds as kinds_reader
from benchmark.run import serve_fragment

ROOT = Path(__file__).resolve().parents[2]
PATH = ROOT / "benchmark" / "configs" / "mellum2-12b-8l.json"
CONFIG = {**json.loads(PATH.read_text()), "file": str(PATH)}
SERVE = CONFIG["serve"]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "mellum2-8l-repo-assist"
WINDOW, FULL = "sliding_attention", "full_attention"


def test_configuration_holds_the_catalog_s_numbers():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["published"] == {
        "num_hidden_layers": row["config"]["num_hidden_layers"],
        "layer_types": row["config"]["layer_types"]}
    arch = SERVE["extra"]["arch"]
    # The eight that run are the first eight published layers: two whole
    # periods of the pattern.
    assert arch["layer_types"] == row["config"]["layer_types"][:8] == [
        WINDOW, WINDOW, WINDOW, FULL] * 2
    assert len(arch["layer_types"]) == CONFIG["num_hidden_layers"] == 8
    # No width is cut, no expert, no row of the vocabulary.
    for ours, theirs in {
            "hidden_size": "hidden_size", "heads": "num_attention_heads",
            "kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "vocab_size": "vocab_size", "sliding_window": "sliding_window",
            "expert_width": "moe_intermediate_size",
            "experts_published": "num_experts", "experts_held": "num_experts",
            "top_k": "num_experts_per_tok", "norm_eps": "rms_norm_eps",
            "max_positions": "max_position_embeddings"}.items():
        assert arch[ours] == row["config"][theirs], ours
    # Both rotations as published.
    rope = row["config"]["rope_parameters"]
    assert rope[WINDOW] == {"rope_type": "default",
                            "rope_theta": arch["rope_theta"]}
    assert rope[FULL] == {
        "rope_type": "yarn", "rope_theta": arch["rope_theta"],
        "factor": arch["yarn_factor"],
        "original_max_position_embeddings": arch["yarn_original_positions"],
        "beta_fast": arch["yarn_beta_fast"],
        "beta_slow": arch["yarn_beta_slow"],
        "attention_factor": arch["yarn_attention_factor"]}
    assert SERVE["seq_buckets"][-1] + SERVE["extra"]["max_new_tokens"] \
        == 17152 and SERVE["extra"]["gen_slots"] == 32
    assert families.load(CONFIG) is mellum
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert set(CONFIG["assumed"]) >= {"attention", "rotation", "router",
                                      "norms", "head", "eos_id", "mtp"}


def test_the_issue_s_parameter_counts():
    per = mellum.layer_params(SERVE)
    assert per == {"attention": 2 * 2304 * 4096 + 2 * 2304 * 512,  # 21.23M
                   "E": 2304 * 64, "expert": 3 * 2304 * 896}       # 6.193M
    assert mellum.kinds(SERVE) == {WINDOW: 6, FULL: 2, "E": 8}
    assert mellum.expert_bytes(SERVE) == 12_386_304
    assert mellum.row_bytes(SERVE) == 2048
    assert mellum.experts_held(SERVE) == 64
    layer = per["attention"] + per["E"] + 64 * per["expert"]
    assert 417.7e6 < layer < 417.8e6
    total = 8 * layer + 2 * 98304 * 2304
    assert 3.794e9 < total < 3.796e9     # 7.59 GB in bfloat16
    # The pool: two full layers of 17,408 rows, six rings of 1,024.
    assert 2 * 2 * 32 * 17408 * 1024 == 2_281_701_376
    assert 2 * 6 * 32 * 1024 * 1024 == 402_653_184


def test_decode_step_bytes_by_hand():
    """32 streams decoding all the window long, prompts of 9,216, 512 tokens
    made: every slot live at 9,472 positions on average, each ring full."""
    got = mellum.decode_step_bytes(CONFIG, SERVE, [(50.0, 9216, 512)] * 32,
                                   50.0)
    plain = 2 * (8 * (21_233_664 + 147_456) + 2304 * 98304)
    reached = 64 * (1 - (1 - 8 / 64) ** 32)
    assert 63.1 < reached < 63.2
    rows = 32 * (2 * 9472 + 6 * 1024)
    want = plain + 8 * reached * 12_386_304 + rows * 2048
    assert got == pytest.approx(want, rel=1e-12)
    # Experts 6.25 GB (8 layers x 63.1 x 12.39 MB; ISSUE 52's 3.12 counted
    # four layers), attention and routers 0.34, head 0.45, rows 1.64.
    assert 8.6e9 < got < 8.8e9
    # Read whole, the window layers would cost 3.3 GB more.
    whole = want + 32 * 6 * (9472 - 1024) * 2048
    assert 3.2e9 < whole - want < 3.4e9
    # A stream inside its first window holds its positions in every layer.
    short = mellum.decode_step_bytes(CONFIG, SERVE, [(50.0, 500, 100)], 50.0)
    assert short == pytest.approx(
        plain + 8 * 8 * 12_386_304 + 8 * 550 * 2048, rel=1e-9)
    assert mellum.rows_read(SERVE, np.asarray([100, 1024, 5000])).tolist() \
        == [800, 8192, 2 * 5000 + 6 * 1024]


def test_prefill_and_attend_flops_by_hand():
    weights = 8 * (21_233_664 + 147_456 + 8 * 6_193_152)
    assert 1.13e9 < 2 * weights < 1.14e9      # 1.135 GFLOP a token
    P = 9216
    at = np.arange(1, P + 1, dtype=np.float64)
    attend = 16384 * (2 * at.sum() + 6 * np.minimum(at, 1024).sum())
    assert mellum.attend_flops(SERVE, P) == pytest.approx(attend)
    # 0.15 GFLOP a token in the full layers, 0.10 in the window layers.
    assert 0.150e9 < 16384 * 2 * at.sum() / P < 0.152e9
    assert 0.094e9 < 16384 * 6 * np.minimum(at, 1024).sum() / P < 0.101e9
    want = 2 * P * weights + attend + 2 * 2304 * 98304
    assert mellum.prefill_flops(CONFIG, SERVE, P) == pytest.approx(want)
    assert 12.5e12 < want < 13.1e12
    # As visited, in blocks of 1,024: the causal triangle's blocks whole,
    # and of the band the diagonal's block and the one before it.
    for bucket, blocks in ((4096, 4), (16384, 16)):
        want = 16384 * 1024 ** 2 * (2 * blocks * (blocks + 1) / 2
                                    + 6 * (2 * blocks - 1))
        assert mellum.attend_flops(SERVE, bucket, visited=True) == want
        assert want > mellum.attend_flops(SERVE, bucket)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    serve, _ = serve_fragment(CONFIG, rehearse=True)
    ckpt = tmp_path_factory.mktemp("w") / "w.tpu.safetensors"
    stage_weights.main([str(ckpt), CONFIG["file"], json.dumps(serve)])
    return serve, ckpt


def test_check_passes_the_reference_s_own_greedy_and_fails_another(rehearsal):
    serve, ckpt = rehearsal
    vocab = serve["extra"]["arch"]["vocab_size"]
    window = serve["extra"]["arch"]["sliding_window"]
    ids = [int(t) for t in np.random.default_rng(0).integers(
        0, vocab, 3 * window)]
    toks = []
    for _ in range(6):
        logits = mellum.reference_logits(serve, ckpt, [ids + toks], 1)[0]
        assert logits.shape == (1, vocab)
        toks.append(int(np.argmax(logits[-1])))
    run = {"ids": ids, "tokens": toks, "again": toks, "error": None}
    got = mellum.check(CONFIG, serve, ckpt, [run])
    assert got["ok"] and got["worst"] == 0.0
    bad = {**run, "tokens": [(t + 1) % vocab for t in toks]}
    assert mellum.check(CONFIG, serve, ckpt, [bad])["worst"] > 0.5
    # Each control computes something else: other logits for the same ids.
    plain = mellum.reference_logits(serve, ckpt, [ids + toks], 6)[0]
    for control in ("int8", "window_as_full", "no_yarn"):
        other = mellum.reference_logits(serve, ckpt, [ids + toks], 6,
                                        control)[0]
        assert plain.shape == other.shape == (6, vocab)
        assert np.abs(other - plain).max() > 1e-3, control
    tree = mellum.reference.load_tree(ckpt)
    assert tree["head"].shape == tree["embed"].shape[::-1]  # untied
    assert "expert_bias" not in tree["layer0"]  # nothing to balance


# -- the new metrics, on recorded numbers ----------------------------------------

def _ctx(trace, **run):
    return {"config": CONFIG, "serve": SERVE,
            "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((ROOT / "benchmark"
                                 / "peaks.json").read_text()),
            "trace": trace, "run": run}


def _held(full, ring, rounds):
    return {"span_rows_by_kind": {FULL: {"sum": full, "count": rounds},
                                  WINDOW: {"sum": ring, "count": rounds}}}


NO_TRACE = {"window_s": 0.0, "busy_s": 0.0, "programs": {}}


def test_ring_rows_share_weighs_a_kind_s_rows_by_its_layers():
    """32 streams at 9,400 positions: 6 x 1,024 of 6 x 1,024 + 2 x 9,400."""
    ctx = _ctx(NO_TRACE, gen_before=_held(0, 0, 0),
               gen_after=_held(100 * 32 * 9400, 100 * 32 * 1024, 100))
    got = kinds_reader.read(ctx, "rows_share", of=WINDOW)
    assert got == pytest.approx(6 * 1024 / (6 * 1024 + 2 * 9400))
    assert 0.24 < got < 0.25
    # A program that keeps no such counter: nothing to read.
    assert kinds_reader.read(_ctx(NO_TRACE, gen_before={}, gen_after={}),
                             "rows_share", of=WINDOW) is None
    with pytest.raises(ValueError):
        kinds_reader.read(_ctx({**NO_TRACE, "window_s": 1.0},
                               gen_before={}, gen_after={},
                               profile={"generation": {"mellum2a8l": {
                                   "before": {}, "after": {}}}}), "another")


def test_kinds_attend_roofline_reads_the_capture_s_own_rounds():
    """10 rounds in the capture held 32 spans of 9,400 rows in a full layer
    and 1,024 in a ring: 8 steps x 32 x (2 x 9,400 + 6 x 1,024) x 2,048 B
    over 819 GB/s is 15.97 ms a run; the kernel took 20 ms a run."""
    counters = {"before": _held(10**6, 10**5, 40),
                "after": _held(10**6 + 10 * 32 * 9400,
                               10**5 + 10 * 32 * 1024, 50)}
    trace = {"window_s": 2.0, "busy_s": 1.9, "programs": {"segment": {
        "runs": 10, "seconds": 0.7,
        "ops": {"decode_attention": 10 * 0.020, "expert_matmul": 0.3}}}}
    ctx = _ctx(trace, gen_before={}, gen_after={},
               profile={"generation": {"mellum2a8l": counters}})
    least = 8 * 32 * (2 * 9400 + 6 * 1024) * 2048 / 819e9
    got = kinds_reader.read(ctx, "attend_roofline", op="decode_attention")
    assert got == pytest.approx(100 * least / 0.020)
    assert 79 < got < 81
    # No device plane, or a program without the counters: nothing to read.
    assert kinds_reader.read(_ctx(NO_TRACE, gen_before={}, gen_after={}),
                             "attend_roofline", op="decode_attention") is None
    ctx["run"]["profile"] = {"generation": {"mellum2a8l": {
        "before": {"span_rows": {"sum": 1, "count": 1}},
        "after": {"span_rows": {"sum": 2, "count": 2}}}}}
    assert kinds_reader.read(ctx, "attend_roofline",
                             op="decode_attention") is None


def test_band_prompt_peak_pct_counts_the_prefills_launched_in_the_capture():
    """Three prefills launched inside the capture (two of bucket 8192, one
    of 16384), each at what the kernel visits, against the kernel's time."""
    counters = {"before": {"prefill_buckets": {"4096": 5, "8192": 7}},
                "after": {"prefill_buckets": {"4096": 5, "8192": 9,
                                              "16384": 1}}}
    flops = (2 * mellum.attend_flops(SERVE, 8192, visited=True)
             + mellum.attend_flops(SERVE, 16384, visited=True))
    trace = {"window_s": 2.0, "busy_s": 1.9, "programs": {"prefill": {
        "runs": 3, "seconds": 0.5, "ops": {"flash_attention": 0.2}}}}
    ctx = _ctx(trace, gen_before={}, gen_after={},
               profile={"generation": {"mellum2a8l": counters}})
    got = kinds_reader.read(ctx, "prompt_peak_pct", op="flash_attention")
    assert got == pytest.approx(100 * flops / 197e12 / 0.2)
    assert 30 < got < 40
    # A slice that held no prefill, or a program without the counter.
    del trace["programs"]["prefill"]
    assert kinds_reader.read(ctx, "prompt_peak_pct",
                             op="flash_attention") is None


def test_the_new_metrics_and_the_cell_s_lists_are_in_the_benchmark():
    """By name and by membership: a later cell that joins a list, or a later
    metric that lists this cell, leaves this test as it is."""
    for name in ("ring_rows_share", "kinds_attend_roofline",
                 "band_prompt_peak_pct"):
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
        assert spec["reader"] == "kinds"
        for key in ("layer", "unit", "better", "moves", "source"):
            assert entry[key] == spec[key], (name, key)
    joined = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined >= {
        "tokens_per_round", "decode_step_ms", "decode_roofline",
        "device_idle_pct", "sse_ms_per_round", "host_turnaround_ms",
        "segment_launch_ms", "pool_copy_slice_pct", "idle_attributed_pct",
        "kv_live_share", "expert_matmul_roofline", "experts_touched_share",
        "expert_load_max_over_mean", "prefill_stall_share",
        "ring_rows_share", "kinds_attend_roofline", "band_prompt_peak_pct"}
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        CONFIG["name"], 1, "repo-assist")
    assert {"tpot_p50_ms", "setup_s"} <= {
        m["name"] for m in BENCH["end_to_end"]
        if "workloads" not in m or CELL in m["workloads"]}


def test_every_why_and_source_fits_the_file_s_form():
    """The driver refuses the file before any run over one line of more than
    200 characters (PR 52's first check: the configuration's `why` had 206)."""
    for entry in BENCH["configs"] + BENCH["workloads"]:
        for key in ("why", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (
                entry["name"], key, len(text))


# -- the cell, rehearsed ----------------------------------------------------------

def names(kind):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell(trace):
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000023", "--seconds", "4", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert set(line["metrics"]) <= names("per_layer")
        assert {"experts_touched_share", "expert_load_max_over_mean",
                "kv_live_share", "tokens_per_round",
                "ring_rows_share"} <= set(line["metrics"])
        assert 0 < line["metrics"]["experts_touched_share"]["value"] <= 1
        assert 0 < line["metrics"]["ring_rows_share"]["value"] < 0.75
    else:
        assert set(line["metrics"]) == names("end_to_end") == {
            "tpot_p50_ms", "setup_s"}
