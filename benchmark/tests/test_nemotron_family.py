"""Nemotron-H's family module: the configuration against the catalog, byte
and operation counts against sums worked by hand, the check and its rule
(how many served tokens lie far), the experts reader on recorded counters, and ``--rehearse`` of the
cell."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import families, stage_weights
from benchmark.families import nemotron_h
from benchmark.readers import experts
from benchmark.run import serve_fragment

ROOT = Path(__file__).resolve().parents[2]
PATH = ROOT / "benchmark" / "configs" / "nemotron3-super-11l.json"
CONFIG = {**json.loads(PATH.read_text()), "file": str(PATH)}
SERVE = CONFIG["serve"]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "nemotron3s-11l-fleet-decode"


def test_configuration_holds_the_catalog_s_numbers():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"]
               == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items()
              if CONFIG.get(k) != v and not isinstance(v, str)}
    assert differ == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differ} == {
        k: CONFIG["published"][k] for k in differ}
    # One whole period of the published pattern.
    assert row["config"]["hybrid_override_pattern"].startswith(
        CONFIG["hybrid_override_pattern"])
    arch = SERVE["extra"]["arch"]
    assert arch["pattern"] == CONFIG["hybrid_override_pattern"] \
        == "MEMEMEM*EME"
    # No width is cut: the program's are the published ones.
    for ours, theirs in {
            "hidden_size": "hidden_size", "heads": "num_attention_heads",
            "kv_heads": "num_key_value_heads", "head_dim": "head_dim",
            "mamba_heads": "mamba_num_heads",
            "mamba_head_dim": "mamba_head_dim", "ssm_state": "ssm_state_size",
            "n_groups": "n_groups", "conv_kernel": "conv_kernel",
            "chunk_size": "chunk_size", "top_k": "num_experts_per_tok",
            "latent_size": "moe_latent_size",
            "expert_width": "moe_intermediate_size",
            "shared_width": "moe_shared_expert_intermediate_size",
            "routed_scale": "routed_scaling_factor",
            "experts_published": "n_routed_experts",
            "vocab_published": "vocab_size",
            "max_positions": "max_position_embeddings"}.items():
        assert arch[ours] == row["config"][theirs], ours
    assert (arch["experts_held"], arch["vocab_size"]) == (
        CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == (128, 32768)
    assert families.load(CONFIG) is nemotron_h


def test_the_issue_s_parameter_counts():
    per = nemotron_h.layer_params(SERVE)
    assert per == {"M": 4096 * 18560 + 8192 * 4096,       # 109.6M
                   "*": 2 * 4096 * 4096 + 2 * 4096 * 256,  # 35.7M
                   "E": 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376,
                   "expert": 2 * 1024 * 2688}
    assert nemotron_h.kinds(SERVE) == {"M": 5, "*": 1, "E": 5}
    assert nemotron_h.expert_bytes(SERVE) == 11_010_048
    # 32 slots x 5 layers x (128 x 64 x 128 float32 + 3 x 10,240 bfloat16).
    assert nemotron_h.state_bytes(SERVE) == 32 * 5 * (4_194_304 + 61_440)


def test_decode_step_bytes_by_hand():
    """32 streams decoding all the window long, prompts of 300, 400 tokens
    made: every slot live, 500 rows each on average."""
    got = nemotron_h.decode_step_bytes(CONFIG, SERVE,
                                       [(50.0, 300, 400)] * 32, 50.0)
    plain = 2 * (5 * 109_576_192 + 35_651_584 + 5 * 54_525_952
                 + 4096 * 32768)
    reached = 128 * (1 - (1 - 22 / 512) ** 32)
    assert 96 < reached < 97
    want = (plain + 5 * reached * 11_010_048 + 2 * 680_919_040
            + 32 * 500 * 2 * 256 * 2)
    assert got == pytest.approx(want, rel=1e-12)
    assert 8.6e9 < got < 8.8e9
    # One live stream reaches 22 / 4 experts a layer, near enough.
    one = nemotron_h.decode_step_bytes(CONFIG, SERVE, [(50.0, 300, 400)],
                                       50.0)
    assert one == pytest.approx(
        plain + 5 * 5.5 * 11_010_048 + 2 * 680_919_040 + 500 * 1024,
        rel=1e-9)


def test_prefill_flops_by_hand():
    weights = (5 * 109_576_192 + 35_651_584
               + 5 * (54_525_952 + 5.5 * 5_505_024))
    want = (2 * 512 * weights + 2 * 2 * 4096 * 512 ** 2 / 2
            + 2 * 4096 * 32768)
    assert nemotron_h.prefill_flops(CONFIG, SERVE, 512) == pytest.approx(want)


@pytest.mark.parametrize("far, ok", [(0, True), (8, True), (9, False),
                                     (256, False)])
def test_judge_counts_the_tokens_that_lie_far_not_the_farthest(far, ok):
    """256 served tokens (the cell's 16 prompts of 16), ``far`` of them 0.4
    under the reference's best, one 0.03 under (near, not far): the limit
    is a share of 0.035, so 8 pass and 9 do not, however far they lie."""
    vocab, prompts, new = 12, 16, 16
    rng = np.random.default_rng(3)
    runs, logits, n = [], [], 0
    for i in range(prompts):
        ids = [int(t) for t in rng.integers(0, vocab, 5 + i)]
        toks = [int(t) for t in rng.integers(0, vocab, new)]
        lg = rng.standard_normal((len(ids) + new - 1, vocab)).astype(
            np.float32) - 5.0
        for j, tok in enumerate(toks):
            row = lg[len(ids) - 1 + j]
            row[tok] = 1.0
            if n < far:
                row[(tok + 1) % vocab] = 1.4
            elif n == far:
                row[(tok + 1) % vocab] = 1.03
            n += 1
        runs.append({"ids": ids, "tokens": toks})
        logits.append(lg)
    got = nemotron_h.judge(CONFIG, runs, logits)
    assert got["ok"] is ok and got["worst"] == pytest.approx(far / 256)
    assert f"{far} lie more than 0.05 under" in got["note"]
    assert f"{256 - far - (far < 256)} of 256" in got["note"]


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
        logits = nemotron_h.reference_logits(serve, ckpt, [ids + toks])[0]
        toks.append(int(np.argmax(logits[-1])))
    run = {"ids": ids, "tokens": toks, "again": toks, "error": None}
    got = nemotron_h.check(CONFIG, serve, ckpt, [run])
    assert got["ok"] and got["worst"] == 0.0
    bad = {**run, "tokens": [(t + 1) % vocab for t in toks]}
    assert nemotron_h.check(CONFIG, serve, ckpt, [bad])["worst"] > 0.5
    # The control computes something else: other logits for the same ids.
    plain = nemotron_h.reference_logits(serve, ckpt, [ids + toks])[0]
    other = nemotron_h.reference_logits(serve, ckpt, [ids + toks], "int8")[0]
    assert np.abs(other - plain).max() > 1e-4


# -- the reader, on recorded counters ------------------------------------------

def _counters(held, touched, most, rounds):
    return {"expert_assignments_held": {"sum": held, "count": rounds},
            "experts_touched": {"sum": touched, "count": rounds},
            "expert_load_max": {"sum": most, "count": rounds}}


def _ctx(**run):
    return {"config": CONFIG, "serve": SERVE,
            "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((ROOT / "benchmark"
                                 / "peaks.json").read_text()),
            "trace": {"window_s": 0.0, "programs": {}}, "run": run}


def test_counter_metrics_read_the_window_s_deltas():
    """100 rounds of 8 steps over 5 layers: 704,000 rows held (176 a layer a
    step), 384,000 experts touched (96 of 128), 16,000 in all on the
    busiest (4 a layer a step, against a mean of 176 / 128)."""
    ctx = _ctx(gen_before=_counters(1000, 500, 20, 10),
               gen_after=_counters(705_000, 384_500, 16_020, 110))
    assert experts.read(ctx, "touched_share") == pytest.approx(0.75)
    assert experts.read(ctx, "load_max_over_mean") == pytest.approx(
        4 / (176 / 128))
    assert experts.read(ctx, "matmul_roofline") is None  # no device plane


def test_matmul_roofline_reads_the_capture_s_own_rounds():
    """20 rounds in the capture touched 96 experts a layer a step: 8 steps x
    5 layers x 96 x 11,010,048 B over 819 GB/s is 51.62 ms a run; the kernel
    took 64 ms a run, so 80.7%."""
    ctx = _ctx(gen_before=_counters(0, 0, 0, 0),
               gen_after=_counters(1, 1, 1, 1),
               profile={"generation": {"nemotron3s11l": {
                   "before": _counters(0, 10_000, 0, 40),
                   "after": _counters(0, 10_000 + 20 * 3840, 0, 60)}}})
    ctx["trace"] = {"window_s": 2.0, "programs": {"segment": {
        "runs": 20, "seconds": 1.7, "ops": {"expert_matmul": 20 * 0.064,
                                            "fusion": 0.4}}}}
    least = 3840 * 11_010_048 / 819e9
    assert experts.read(ctx, "matmul_roofline") == pytest.approx(
        100 * least / 0.064)
    assert 80 < experts.read(ctx, "matmul_roofline") < 81


@pytest.mark.parametrize("kind", ["touched_share", "load_max_over_mean",
                                  "matmul_roofline"])
def test_a_program_without_the_counters_reads_as_nothing(kind):
    plain = {"segment_rounds": 5, "tokens_emitted": 40}
    ctx = _ctx(gen_before=plain, gen_after=plain,
               profile={"generation": {}})
    ctx["trace"] = {"window_s": 2.0, "programs": {"segment": {
        "runs": 20, "seconds": 1.7, "ops": {"fusion": 0.4}}}}
    assert experts.read(ctx, kind) is None


def test_every_experts_metric_is_in_the_benchmark():
    for path in (ROOT / "benchmark" / "layer_metrics").glob("*.json"):
        spec = json.loads(path.read_text())
        if spec["reader"] != "experts":
            continue
        entry = next(m for m in BENCH["per_layer"]
                     if m["name"] == spec["name"])
        assert entry["workloads"] == [CELL]
        for key in ("layer", "unit", "better", "moves", "source"):
            assert entry[key] == spec[key], (spec["name"], key)


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


def test_balanced_bias_spreads_the_choices_evenly():
    """Scores that share an offset an expert send 174 of 512 rows to the
    busiest expert; balanced, every expert is chosen by 20 to 24 of them
    (22 is even)."""
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((512, 512)) * 0.9 \
        + rng.standard_normal(512) * 0.6
    score = 1 / (1 + np.exp(-logits))

    def loads(bias):
        chosen = np.argpartition(-(score + bias), 21, axis=1)[:, :22]
        return np.bincount(chosen.ravel(), minlength=512)

    assert loads(0.0).max() > 100
    even = loads(nemotron_h.balanced_bias(score, 22))
    assert even.sum() == 512 * 22 and 18 <= even.min() <= even.max() <= 26


def test_staging_balances_every_expert_layer(rehearsal):
    serve, ckpt = rehearsal
    tree = nemotron_h.reference.load_tree(ckpt)
    pattern = serve["extra"]["arch"]["pattern"]
    for i, kind in enumerate(pattern):
        if kind == "E":
            bias = np.asarray(tree[f"layer{i}"]["router_bias"])
            assert bias.shape == (serve["extra"]["arch"]["experts_published"],)
            assert np.abs(bias).max() > 0
