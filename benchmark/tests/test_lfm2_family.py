"""LFM2's family module: the configuration against the catalog, byte and
operation counts against sums worked by hand, the check (the reference's own
greedy passes, another answer and the int8 control do not), the new
per-layer metric and the decode kernel's share on recorded numbers, and
``--rehearse`` of the cell."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import families, stage_weights
from benchmark.families import lfm2
from benchmark.readers import rows, stall
from benchmark.run import serve_fragment

ROOT = Path(__file__).resolve().parents[2]
PATH = ROOT / "benchmark" / "configs" / "lfm2-24b-10l.json"
CONFIG = {**json.loads(PATH.read_text()), "file": str(PATH)}
SERVE = CONFIG["serve"]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "lfm2-10l-rag-fleet"


def test_configuration_holds_the_catalog_s_numbers():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "LFM2-24B-A2B")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"]) == {"num_hidden_layers"}
    assert CONFIG["published"] == {
        "num_hidden_layers": row["config"]["num_hidden_layers"],
        "layer_types": row["config"]["layer_types"]}
    arch = SERVE["extra"]["arch"]
    # The ten that run are the first ten published layers: the two dense
    # ones, then two whole periods of the pattern.
    assert arch["layer_types"] == row["config"]["layer_types"][:10] == [
        "conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    assert len(arch["layer_types"]) == CONFIG["num_hidden_layers"] == 10
    # No width is cut, no expert, no row of the vocabulary.
    for ours, theirs in {
            "hidden_size": "hidden_size", "heads": "num_attention_heads",
            "kv_heads": "num_key_value_heads", "vocab_size": "vocab_size",
            "dense_width": "intermediate_size",
            "dense_layers": "num_dense_layers",
            "expert_width": "moe_intermediate_size",
            "experts_published": "num_experts", "experts_held": "num_experts",
            "top_k": "num_experts_per_tok", "conv_kernel": "conv_L_cache",
            "routed_scale": "routed_scaling_factor", "norm_eps": "norm_eps",
            "max_positions": "max_position_embeddings"}.items():
        assert arch[ours] == row["config"][theirs], ours
    assert arch["rope_theta"] == row["config"]["rope_parameters"]["rope_theta"]
    assert arch["head_dim"] * arch["heads"] == arch["hidden_size"]
    assert families.load(CONFIG) is lfm2
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]


def test_the_issue_s_parameter_counts():
    per = lfm2.layer_params(SERVE)
    assert per == {"conv": 2048 * 6144 + 2048 * 2048 + 2048 * 3,   # 16.79M
                   "full_attention": 2 * 2048 * 2048 + 2 * 2048 * 512,
                   "D": 3 * 2048 * 11776,                          # 72.35M
                   "E": 2048 * 64, "expert": 3 * 2048 * 1536}      # 9.437M
    assert lfm2.kinds(SERVE) == {"conv": 8, "full_attention": 2, "D": 2,
                                 "E": 8}
    assert lfm2.expert_bytes(SERVE) == 18_874_368
    assert lfm2.row_bytes(SERVE) == 2 * 2048
    assert lfm2.experts_held(SERVE) == 64
    # 32 slots x 8 layers x two rows of 2,048 bfloat16.
    assert lfm2.state_bytes(SERVE) == 32 * 8 * 8192
    n = lfm2.kinds(SERVE)
    total = (sum(n[k] * per[k] for k in ("conv", "full_attention", "D", "E"))
             + n["E"] * 64 * per["expert"] + 65536 * 2048)
    assert 5.26e9 < total < 5.27e9    # 10.53 GB in bfloat16


def test_decode_step_bytes_by_hand():
    """32 streams decoding all the window long, prompts of 4,600, 256 tokens
    made: every slot live, 4,728 rows each on average."""
    got = lfm2.decode_step_bytes(CONFIG, SERVE, [(50.0, 4600, 256)] * 32,
                                 50.0)
    plain = 2 * (8 * 16_783_360 + 2 * 10_485_760 + 2 * 72_351_744
                 + 8 * 131_072 + 2048 * 65536)
    reached = 64 * (1 - (1 - 4 / 64) ** 32)
    assert 55.8 < reached < 55.9
    want = (plain + 8 * reached * 18_874_368 + 2 * 2_097_152
            + 32 * 4728 * 4096)
    assert got == pytest.approx(want, rel=1e-12)
    assert 9.8e9 < got < 10.0e9
    # One live stream reaches its four experts a layer.
    one = lfm2.decode_step_bytes(CONFIG, SERVE, [(50.0, 4600, 256)], 50.0)
    assert one == pytest.approx(
        plain + 8 * 4 * 18_874_368 + 2 * 2_097_152 + 4728 * 4096, rel=1e-9)


def test_prefill_flops_by_hand():
    weights = (8 * 16_783_360 + 2 * 10_485_760 + 2 * 72_351_744
               + 8 * (131_072 + 4 * 9_437_184))
    assert 1.2e9 < 2 * weights < 1.22e9      # 1.2 GFLOP a token
    want = (2 * 6144 * weights + 2 * 2 * 2 * 2048 * 6144 ** 2 / 2
            + 2 * 2048 * 65536)
    assert lfm2.prefill_flops(CONFIG, SERVE, 6144) == pytest.approx(want)
    assert 7.5e12 < want < 7.9e12


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    serve, _ = serve_fragment(CONFIG, rehearse=True)
    ckpt = tmp_path_factory.mktemp("w") / "w.tpu.safetensors"
    stage_weights.main([str(ckpt), CONFIG["file"], json.dumps(serve)])
    return serve, ckpt


def test_check_passes_the_reference_s_own_greedy_and_fails_another(rehearsal):
    serve, ckpt = rehearsal
    vocab = serve["extra"]["arch"]["vocab_size"]
    ids = [int(t) for t in np.random.default_rng(0).integers(0, vocab, 20)]
    toks = []
    for _ in range(6):
        logits = lfm2.reference_logits(serve, ckpt, [ids + toks], 1)[0]
        assert logits.shape == (1, vocab)
        toks.append(int(np.argmax(logits[-1])))
    run = {"ids": ids, "tokens": toks, "again": toks, "error": None}
    got = lfm2.check(CONFIG, serve, ckpt, [run])
    assert got["ok"] and got["worst"] == 0.0
    bad = {**run, "tokens": [(t + 1) % vocab for t in toks]}
    assert lfm2.check(CONFIG, serve, ckpt, [bad])["worst"] > 0.5
    # The control computes something else: other logits for the same ids.
    plain = lfm2.reference_logits(serve, ckpt, [ids + toks], 6)[0]
    other = lfm2.reference_logits(serve, ckpt, [ids + toks], 6, "int8")[0]
    assert plain.shape == other.shape == (6, vocab)
    assert np.abs(other - plain).max() > 1e-4


def test_staging_balances_every_expert_layer(rehearsal):
    serve, ckpt = rehearsal
    tree = lfm2.reference.load_tree(ckpt)
    arch = serve["extra"]["arch"]
    for i in range(len(arch["layer_types"])):
        layer = tree[f"layer{i}"]
        assert ("expert_bias" in layer) == (i >= arch["dense_layers"])
        if "expert_bias" in layer:
            bias = np.asarray(layer["expert_bias"])
            assert bias.shape == (arch["experts_published"],)
            assert np.abs(bias).max() > 0
    assert "head" not in tree  # tied: the embedding is the head


# -- the new metric and the kernel's share, on recorded numbers -----------------------------------

def _ctx(trace, **run):
    return {"config": CONFIG, "serve": SERVE,
            "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((ROOT / "benchmark"
                                 / "peaks.json").read_text()),
            "trace": trace, "run": run}


def test_prefill_stall_share_is_the_prefills_share_of_the_busy_time():
    trace = {"window_s": 2.0, "busy_s": 1.8, "programs": {
        "segment": {"runs": 9, "seconds": 1.0, "ops": {}},
        "prefill": {"runs": 8, "seconds": 0.72, "ops": {}}}}
    assert stall.read(_ctx(trace), "prefill_share") == pytest.approx(0.4)
    del trace["programs"]["prefill"]  # a slice that held no prefill
    assert stall.read(_ctx(trace), "prefill_share") == 0.0
    # No device plane in the capture (the CPU): nothing to read.
    assert stall.read(_ctx({"window_s": 0.0, "busy_s": 0.0, "programs": {}}),
                      "prefill_share") is None
    with pytest.raises(ValueError):
        stall.read(_ctx(trace), "another")


def test_the_decode_kernel_s_roofline_reads_the_capture_s_own_rounds():
    """10 rounds in the capture held 150,000 rows each (32 spans of about
    4,700): 8 steps x 150,000 x 4,096 B over 819 GB/s is 6.0 ms a run; the
    kernel took 8 ms a run, so 75%."""
    span = {"before": {"span_rows": {"sum": 1_000_000, "count": 40}},
            "after": {"span_rows": {"sum": 2_500_000, "count": 50}}}
    trace = {"window_s": 2.0, "busy_s": 1.9, "programs": {"segment": {
        "runs": 10, "seconds": 1.2,
        "ops": {"decode_attention": 10 * 0.008, "expert_matmul": 0.9}}}}
    ctx = _ctx(trace, gen_before={}, gen_after={},
               profile={"generation": {"lfm2a2b10l": span}})
    least = 8 * 150_000 * 4096 / 819e9
    assert rows.read(ctx, "attend_roofline") == pytest.approx(
        100 * least / 0.008)
    assert 74 < rows.read(ctx, "attend_roofline") < 76


def test_the_new_metric_and_the_cell_s_lists_are_in_the_benchmark():
    """By name and by membership: a later cell that joins a list, or a later
    metric that lists this cell, leaves this test as it is."""
    spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                       / "prefill_stall_share.json").read_text())
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == "prefill_stall_share")
    assert CELL in entry["workloads"]
    for key in ("layer", "unit", "better", "moves", "source"):
        assert entry[key] == spec[key], key
    joined = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined >= {
        "tokens_per_round", "decode_step_ms", "decode_roofline",
        "device_idle_pct", "sse_ms_per_round", "host_turnaround_ms",
        "segment_launch_ms", "pool_copy_slice_pct", "idle_attributed_pct",
        "kv_live_share", "expert_matmul_roofline", "experts_touched_share",
        "expert_load_max_over_mean", "eva_attend_roofline",
        "prefill_stall_share"}
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        CONFIG["name"], 1, "rag-fleet")
    assert {"tpot_p50_ms", "setup_s"} <= {
        m["name"] for m in BENCH["end_to_end"]
        if "workloads" not in m or CELL in m["workloads"]}


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
                "kv_live_share", "tokens_per_round"} <= set(line["metrics"])
        assert 0 < line["metrics"]["experts_touched_share"]["value"] <= 1
        assert line["metrics"]["expert_load_max_over_mean"]["value"] >= 1
    else:
        assert set(line["metrics"]) == names("end_to_end") == {
            "tpot_p50_ms", "setup_s"}
