"""JoyAI-LLM-Flash's family module: the configuration against the catalog,
byte and operation counts against sums worked by hand, the check (the
reference's own greedy passes; another answer and each control do not), the
new per-layer metrics on recorded numbers, and ``--rehearse`` of the cell."""

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import families, stage_weights, traffic
from benchmark.families import joyai
from benchmark.readers import kinds as kinds_reader
from benchmark.readers import latent as latent_reader
from benchmark.run import serve_fragment, warm_plan

ROOT = Path(__file__).resolve().parents[2]
PATH = ROOT / "benchmark" / "configs" / "joyai-flash-10l.json"
CONFIG = {**json.loads(PATH.read_text()), "file": str(PATH)}
SERVE = CONFIG["serve"]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = "joyai-flash-10l-latent-fleet"
MODEL = SERVE["model"]


def test_configuration_holds_the_catalog_s_numbers():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if not catalog.is_file():
        pytest.skip("no catalog here")
    row = next(json.loads(line) for line in catalog.read_text().splitlines()
               if json.loads(line)["name"] == "JoyAI-LLM-Flash")
    assert CONFIG["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differ == set(CONFIG["reduced"]) == {"num_hidden_layers",
                                                "n_routed_experts"}
    assert CONFIG["published"] == {
        "num_hidden_layers": row["config"]["num_hidden_layers"],
        "n_routed_experts": row["config"]["n_routed_experts"]} == {
            "num_hidden_layers": 40, "n_routed_experts": 256}
    arch = SERVE["extra"]["arch"]
    assert arch["layers"] == CONFIG["num_hidden_layers"] == 10
    assert arch["experts_held"] == CONFIG["n_routed_experts"] == 32
    assert arch["expert_offset"] == 0
    # No width is cut, no row of the vocabulary; the router keeps its 256
    # outputs and its 8 a token.
    for ours, theirs in {
            "hidden_size": "hidden_size", "heads": "num_attention_heads",
            "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
            "nope_dim": "qk_nope_head_dim", "rope_dim": "qk_rope_head_dim",
            "v_dim": "v_head_dim", "dense_layers": "first_k_dense_replace",
            "dense_width": "intermediate_size", "vocab_size": "vocab_size",
            "expert_width": "moe_intermediate_size",
            "top_k": "num_experts_per_tok", "rope_theta": "rope_theta",
            "routed_scale": "routed_scaling_factor",
            "norm_eps": "rms_norm_eps",
            "max_positions": "max_position_embeddings"}.items():
        assert arch[ours] == row["config"][theirs], ours
    assert arch["experts_published"] == 256
    assert arch["nope_dim"] + arch["rope_dim"] == row["config"]["qk_head_dim"]
    assert row["config"]["rope_scaling"] is None
    assert row["config"]["n_group"] == row["config"]["topk_group"] == 1
    assert SERVE["seq_buckets"][-1] + SERVE["extra"]["max_new_tokens"] \
        == 9216 and SERVE["extra"]["gen_slots"] == 64
    assert families.load(CONFIG) is joyai
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert "eight-chip" in CONFIG["deployment"]
    assert set(CONFIG["assumed"]) >= {
        "layers", "experts", "head", "attention", "rotation", "router",
        "prediction_module", "eos_id", "weights", "expert_bias"}


def test_the_issue_s_parameter_counts():
    per = joyai.layer_params(SERVE)
    assert per == {
        "attention": (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
                      + 4096 * 2048),                            # 26.35 M
        "D": 3 * 2048 * 7168,                                    # 44.04 M
        "E": 2048 * 256 + 3 * 2048 * 768,                        # 5.243 M
        "expert": 3 * 2048 * 768}                                # 4.719 M
    assert 26.34e6 < per["attention"] < 26.36e6
    assert joyai.kinds(SERVE) == {"latent": 10, "D": 1, "E": 9}
    assert joyai.expert_bytes(SERVE) == 9_437_184
    assert joyai.experts_held(SERVE) == 32
    # 576 values a row, stored in five lane tiles.
    assert joyai.row_bytes(SERVE) == 1280
    weights = (10 * per["attention"] + per["D"]
               + 9 * (per["E"] + 32 * per["expert"]) + 2 * 129280 * 2048)
    assert 2.243e9 < weights < 2.244e9      # 4.49 GB in bfloat16
    pool = 10 * 64 * 9216 * 1280
    assert pool == 7_549_747_200            # 7.55 GB (6.79 at 1,152 B a row)
    assert 12.0e9 < 2 * weights + pool < 12.1e9


def test_decode_step_bytes_by_hand():
    """64 streams decoding all the window long, prompts of 5,120, 768 tokens
    made: every slot live at 5,504 positions on average."""
    streams = [(50.0, 5120, 768)] * 64
    got = joyai.decode_step_bytes(CONFIG, SERVE, streams, 50.0)
    plain = 2 * (10 * 26_345_472 + 44_040_192 + 9 * 5_242_880
                 + 2048 * 129280)
    reached = 32 * (1 - (1 - 8 / 256) ** 64)
    assert 27.8 < reached < 27.9            # 86.9% of the 32 held
    rows = 64 * 5504 * 10 * 1280
    assert joyai.rows_bytes(SERVE, streams, 50.0) == pytest.approx(rows)
    want = plain + 9 * reached * 9_437_184 + rows
    assert got == pytest.approx(want, rel=1e-12)
    # Weights outside the experts 0.71 GB, the head 0.53, the experts
    # reached 2.36, the rows 4.51: the rows are over half.
    assert 8.0e9 < got < 8.2e9
    assert 0.55 < rows / got < 0.57
    # One stream alone: its 8 experts a layer at most, its own rows.
    alone = joyai.decode_step_bytes(CONFIG, SERVE, [(50.0, 2048, 512)], 50.0)
    assert alone == pytest.approx(
        plain + 9 * 32 * (8 / 256) * 9_437_184 + 2304 * 10 * 1280, rel=1e-9)


def test_prefill_and_attend_flops_by_hand():
    weights = (10 * 26_345_472 + 44_040_192
               + 9 * (5_242_880 + 8 * (32 / 256) * 4_718_592))
    assert 0.79e9 < 2 * weights < 0.80e9      # 0.79 GFLOP a token
    P = 8192
    at = np.arange(1, P + 1, dtype=np.float64)
    attend = 10 * 2 * 32 * (192 + 128) * at.sum()
    assert joyai.attend_flops(SERVE, P) == pytest.approx(attend)
    want = 2 * P * weights + attend + 2 * 2048 * 129280
    assert joyai.prefill_flops(CONFIG, SERVE, P) == pytest.approx(want)
    assert 13.3e12 < want < 13.5e12
    # As visited, in blocks of 1,024 queries by 1,024 keys, the causal
    # triangle's blocks whole, keys padded to 256 lanes and values to 128:
    # 36 blocks of 64 at 8,192, 1.35 times what the mask and widths leave.
    for bucket, blocks in ((2048, 2), (8192, 8)):
        want = 10 * 2 * 32 * (256 + 128) * 1024 ** 2 * blocks * (
            blocks + 1) / 2
        assert joyai.attend_flops(SERVE, bucket, visited=True) == want
        assert want > joyai.attend_flops(SERVE, bucket)
    assert 1.34 < joyai.attend_flops(SERVE, 8192, visited=True) / attend \
        < 1.36


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    serve, _ = serve_fragment(CONFIG, rehearse=True)
    ckpt = tmp_path_factory.mktemp("w") / "w.tpu.safetensors"
    stage_weights.main([str(ckpt), CONFIG["file"], json.dumps(serve)])
    return serve, ckpt


def test_check_passes_the_reference_s_own_greedy_and_fails_another(rehearsal):
    serve, ckpt = rehearsal
    vocab = serve["extra"]["arch"]["vocab_size"]
    ids = [int(t) for t in np.random.default_rng(0).integers(0, vocab, 40)]
    toks = []
    for _ in range(6):
        logits = joyai.reference_logits(serve, ckpt, [ids + toks], 1)[0]
        assert logits.shape == (1, vocab)
        toks.append(int(np.argmax(logits[-1])))
    run = {"ids": ids, "tokens": toks, "again": toks, "error": None}
    got = joyai.check(CONFIG, serve, ckpt, [run])
    assert got["ok"] and got["worst"] == 0.0
    bad = {**run, "tokens": [(t + 1) % vocab for t in toks]}
    assert joyai.check(CONFIG, serve, ckpt, [bad])["worst"] > 0.5
    # Each control computes something else: other logits for the same ids.
    plain = joyai.reference_logits(serve, ckpt, [ids + toks], 6)[0]
    for control in joyai.reference.CONTROLS:
        other = joyai.reference_logits(serve, ckpt, [ids + toks], 6,
                                       control)[0]
        assert plain.shape == other.shape == (6, vocab)
        assert np.abs(other - plain).max() > 1e-3, control
    tree = joyai.reference.load_tree(ckpt)
    assert tree["head"].shape == tree["embed"].shape[::-1]  # untied
    assert "router" not in tree["layer0"]  # the leading dense layer
    # Nothing is balanced at staging: the bias is the initializer's zeros.
    assert not tree["layer1"]["expert_bias"].any()
    assert tree["layer1"]["w1"].shape[0] == 4 \
        and tree["layer1"]["router"].shape[1] == 16


# -- the new metrics, on recorded numbers ----------------------------------------

def _ctx(trace, **run):
    return {"config": CONFIG, "serve": SERVE, "seconds": 50.0,
            "device": {"kind": "TPU v5 lite"},
            "peaks": json.loads((ROOT / "benchmark"
                                 / "peaks.json").read_text()),
            "trace": trace, "run": run}


NO_TRACE = {"window_s": 0.0, "busy_s": 0.0, "programs": {}}


def _spans(total, rounds):
    return {"span_rows": {"sum": total, "count": rounds}}


def test_latent_attend_roofline_reads_the_capture_s_own_rounds():
    """10 rounds in the capture held 64 spans of 5,504 rows: 8 steps x 10
    layers x 64 x 5,504 x 1,280 B over 819 GB/s is 44.04 ms a run; the
    kernel took 60 ms a run."""
    counters = {"before": _spans(10**6, 40),
                "after": _spans(10**6 + 10 * 64 * 5504, 50)}
    trace = {"window_s": 2.0, "busy_s": 1.9, "programs": {"segment": {
        "runs": 10, "seconds": 1.1,
        "ops": {"latent_attention": 10 * 0.060, "expert_matmul": 0.3}}}}
    ctx = _ctx(trace, profile={"generation": {MODEL: counters}})
    least = 8 * 10 * 64 * 5504 * 1280 / 819e9
    got = latent_reader.read(ctx, "attend_roofline", op="latent_attention")
    assert got == pytest.approx(100 * least / 0.060)
    assert 73 < got < 74
    # The parent of the PR that brought the kernel: no such operation in the
    # segment, no such counters, or no device plane: nothing to read.
    trace["programs"]["segment"]["ops"] = {"decode_attention": 0.6}
    assert latent_reader.read(ctx, "attend_roofline",
                              op="latent_attention") is None
    assert latent_reader.read(_ctx(NO_TRACE), "attend_roofline",
                              op="latent_attention") is None
    trace["programs"]["segment"]["ops"] = {"latent_attention": 0.6}
    ctx["run"]["profile"] = {"generation": {MODEL: {"before": {},
                                                    "after": {}}}}
    assert latent_reader.read(ctx, "attend_roofline",
                              op="latent_attention") is None
    with pytest.raises(ValueError):
        latent_reader.read(ctx, "another")


def test_latent_bytes_share_is_the_rows_of_a_step_s_bytes():
    recs = [{"error": None, "t_tokens": [1.0 + j * 1e-3, 48.0], "tokens":
             [0] * 768, "prompt_len": 5120} for j in range(64)]
    ctx = _ctx(NO_TRACE, records=recs, drain_s=0.0)
    got = latent_reader.read(ctx, "bytes_share")
    streams = [(r["t_tokens"][-1] - r["t_tokens"][0], 5120, 768)
               for r in recs]
    assert got == pytest.approx(
        joyai.rows_bytes(SERVE, streams, 50.0)
        / joyai.decode_step_bytes(CONFIG, SERVE, streams, 50.0))
    assert 0.5 < got < 0.6
    assert latent_reader.read(_ctx(NO_TRACE, records=[], drain_s=0.0),
                              "bytes_share") == 0.0


def test_mla_prompt_peak_pct_counts_the_prefills_launched_in_the_capture():
    """Three prefills launched inside the capture (two of bucket 4096, one
    of 8192), each at what the kernel visits, against the kernel's time."""
    counters = {"before": {"prefill_buckets": {"2048": 5, "4096": 7}},
                "after": {"prefill_buckets": {"2048": 5, "4096": 9,
                                              "8192": 1}}}
    flops = (2 * joyai.attend_flops(SERVE, 4096, visited=True)
             + joyai.attend_flops(SERVE, 8192, visited=True))
    trace = {"window_s": 2.0, "busy_s": 1.9, "programs": {"prefill": {
        "runs": 3, "seconds": 0.5, "ops": {"flash_attention": 0.15}}}}
    ctx = _ctx(trace, gen_before={}, gen_after={},
               profile={"generation": {MODEL: counters}})
    got = kinds_reader.read(ctx, "prompt_peak_pct", op="flash_attention")
    assert got == pytest.approx(100 * flops / 197e12 / 0.15)
    assert 40 < got < 50
    del trace["programs"]["prefill"]  # a slice that held no prefill
    assert kinds_reader.read(ctx, "prompt_peak_pct",
                             op="flash_attention") is None


def test_the_new_metrics_and_the_cell_s_lists_are_in_the_benchmark():
    """By name and by membership: a later cell that joins a list, or a later
    metric that lists this cell, leaves this test as it is."""
    readers = {"latent_attend_roofline": "latent",
               "latent_bytes_share": "latent", "mla_prompt_peak_pct": "kinds"}
    for name, reader in readers.items():
        spec = json.loads((ROOT / "benchmark" / "layer_metrics"
                           / f"{name}.json").read_text())
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
        assert spec["reader"] == reader
        for key in ("layer", "unit", "better", "moves", "source"):
            assert entry[key] == spec[key], (name, key)
    joined = {m["name"] for m in BENCH["per_layer"]
              if CELL in m.get("workloads", ())}
    assert joined >= {
        "tokens_per_round", "decode_step_ms", "decode_roofline",
        "device_idle_pct", "sse_ms_per_round", "host_turnaround_ms",
        "segment_launch_ms", "pool_copy_slice_pct", "idle_attributed_pct",
        "kv_live_share", "expert_matmul_roofline", "experts_touched_share",
        "expert_load_max_over_mean", "prefill_stall_share", *readers}
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        CONFIG["name"], 1, "latent-fleet")
    assert {"tpot_p50_ms", "setup_s"} <= {
        m["name"] for m in BENCH["end_to_end"]
        if "workloads" not in m or CELL in m["workloads"]}
    # New entries stand at the end of their lists.
    assert BENCH["configs"][-1]["name"] == CONFIG["name"]
    assert BENCH["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == list(readers)
    for entry in BENCH["configs"] + BENCH["workloads"]:
        for key in ("why", "source"):
            text = entry.get(key, "x")
            assert 1 <= len(text) <= 200 and text.isprintable(), (
                entry["name"], key, len(text))


def test_mix_is_the_issue_s_and_every_compared_prefill_is_a_timed_one():
    """ISSUE 55 fixed the traffic before any code: its mix with both of its
    step-downs, which keep the means (5,120 and 768), and no range of the
    builder's own.  The window drives the three prefill programs its
    prompts reach (the 2,048 bucket of the issue's ``seq_buckets`` is a
    shorter prompt's, and built at first use), and each reference prompt
    falls in a bucket the window times: no program is built in set-up for
    the comparison alone."""
    mix = traffic.load_mix("latent-fleet")
    assert mix["generator"] == "closed_loop"
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 3072,
                                    "max": 7168}
    assert mix["answer_tokens"] == {"dist": "uniform", "min": 640,
                                    "max": 896}
    assert (mix["clients_per_slot"], mix["block"], mix["admit_max"],
            mix["profile_seconds"]) == (1, 64, 1, 5.0)
    for dist, as_given in ((mix["prompt_tokens"], (2048, 8192)),
                           (mix["answer_tokens"], (512, 1024))):
        assert dist["min"] + dist["max"] == sum(as_given)
    buckets, sizes = warm_plan(mix, SERVE, 1.0)
    assert buckets == SERVE["seq_buckets"][1:] and sizes == [1]
    assert {traffic.bucket_for(n, SERVE["seq_buckets"])
            for n in CONFIG["reference_prompts"]} == {4096, 6144}
    assert (mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"]
            <= SERVE["seq_buckets"][-1] + SERVE["extra"]["max_new_tokens"])


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
                "latent_bytes_share"} <= set(line["metrics"])
        assert 0 < line["metrics"]["experts_touched_share"]["value"] <= 1
        assert 0 < line["metrics"]["latent_bytes_share"]["value"] < 1
    else:
        assert set(line["metrics"]) == names("end_to_end") == {
            "tpot_p50_ms", "setup_s"}
