"""The seam between the harness and a model family (``benchmark/families``):
GPT-2 through the contract gives what the harness computed before it had
one, and the harness's side of the contract takes what a family may hold."""

import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

from benchmark import families, refcheck, stage_weights, traffic
from benchmark.families import gpt2
from benchmark.reference import gpt2 as reference
from benchmark.run import serve_fragment

HERE = Path(__file__).resolve().parent
CONFIGS = HERE.parent / "configs"
# (seconds decoding, prompt length, tokens made), and the window they are
# averaged over: 2 x 192 + 1 x 708 position-seconds in 50 s is 21.84 live.
STREAMS, WINDOW_S = [(2.0, 160, 64), (1.0, 700, 16)], 50.0


def config_of(name):
    path = CONFIGS / f"{name}.json"
    return {**json.loads(path.read_text()), "file": str(path)}


@pytest.mark.parametrize("name,decode_bytes,prefill_flops", [
    ("gpt2-xl", 3_120_658_048.0, 1_550_375_580_800),
    ("gpt2-large-int8", 780_409_616.8, 749_063_580_160)])
def test_gpt2_counts_are_the_ones_from_before_the_seam(name, decode_bytes,
                                                       prefill_flops):
    config = config_of(name)
    serve = config["serve"]
    assert families.load(config) is gpt2
    assert gpt2.decode_step_bytes(config, serve, STREAMS, WINDOW_S) \
        == decode_bytes
    assert gpt2.prefill_flops(config, serve, 512) == prefill_flops
    # And they are the shape arithmetic's own, as the reader called it.
    from benchmark.roofline import gpt2 as shapes
    arch, int8 = serve["extra"]["arch"], name.endswith("int8")
    assert decode_bytes == shapes.decode_step_bytes(arch, int8, 21.84)
    assert prefill_flops == shapes.prefill_flops(arch, 512)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """The int8 lane at its rehearsal widths: configuration, serve fragment,
    the staged tree, and two reference runs recorded from ``--rehearse``."""
    config = config_of("gpt2-large-int8")
    serve, _ = serve_fragment(config, rehearse=True)
    ckpt = tmp_path_factory.mktemp("w") / "w.tpu.safetensors"
    stage_weights.main([str(ckpt), config["file"], json.dumps(serve)])
    runs = json.loads((HERE / "data" / "reference_runs.json").read_text())
    return config, serve, ckpt, runs["runs"]


def old_check(config, serve, ckpt, runs):
    """``check_reference`` as it stood before the seam, GPT-2's arguments
    and all."""
    arch = serve["extra"]["arch"]
    weights = reference.prepare(reference.load_tree(ckpt), arch["layers"],
                                serve["extra"]["params_dtype"] == "int8")
    worst = 0.0
    for r in runs:
        logits = reference.forward(weights, r["ids"] + r["tokens"][:-1],
                                   arch["layers"], arch["heads"],
                                   float(config["layer_norm_epsilon"]))
        for j, tok in enumerate(r["tokens"]):
            row = logits[len(r["ids"]) - 1 + j]
            worst = max(worst, float(np.max(row) - row[tok]))
    return worst


def test_check_on_recorded_runs_gives_the_old_verdict(rehearsal):
    config, serve, ckpt, runs = rehearsal
    got = refcheck.check_reference(config, serve, ckpt, runs)
    assert got == {
        "ok": True, "worst": 0.0,
        "note": "32 of 32 served tokens are the float32 reference's best; "
                "the farthest lies 0.0000 under it in the reference's logits "
                "(tolerance 0.05)"}
    assert runs[0]["done"]["stats"]["rounds_to_first_token"] == 2  # whole


def test_check_fails_a_token_altered_where_it_is_served(rehearsal):
    config, serve, ckpt, runs = rehearsal
    bad = json.loads(json.dumps(runs))
    for r in bad:
        r["tokens"][3] = r["again"][3] = (r["tokens"][3] + 1) % 512
    got = refcheck.check_reference(config, serve, ckpt, bad)
    assert got["ok"] is False
    assert got["worst"] == old_check(config, serve, ckpt, bad) > 0.05
    assert f"{got['worst']:.4f} under" in got["note"]


def test_what_every_family_shares_fails_before_any_reference(rehearsal):
    config, serve, ckpt, runs = rehearsal
    twice = [{**runs[0], "again": runs[0]["tokens"][::-1]}]
    assert "gave different greedy tokens" in refcheck.check_reference(
        config, serve, ckpt, twice)["note"]
    failed = [{**runs[0], "error": "HTTP 500: boom"}]
    assert refcheck.check_reference(config, serve, ckpt, failed) \
        == {"ok": False, "note": "reference request: HTTP 500: boom"}


def test_stage_weights_takes_an_arch_with_a_list_and_a_float(tmp_path,
                                                            monkeypatch):
    seen = {}

    def init_tree(seed, config, serve):
        seen.update(seed=seed, arch=serve["extra"]["arch"])
        return {"w": np.ones((4, 4), np.float32),
                "norm": {"scale": np.ones((4,), np.float32)}}

    monkeypatch.setitem(sys.modules, "benchmark.families.probe",
                        types.SimpleNamespace(init_tree=init_tree))
    config = tmp_path / "probe.json"
    config.write_text(json.dumps({"family": "probe", "weights": {
        "seed": 7, "dtype": "bfloat16"}}))
    arch = {"layer_types": ["full_attention", "sliding_attention"],
            "rope_theta": 500000.0, "gate": "per_head", "layers": 2}
    out = tmp_path / "w" / "probe.tpu.safetensors"
    assert stage_weights.main([str(out), str(config),
                               json.dumps({"extra": {"arch": arch}})]) == 0
    assert seen == {"seed": 7, "arch": arch}
    tree = reference.load_tree(out)
    assert tree["w"].dtype.name == "bfloat16"  # a matrix, in weights.dtype
    assert tree["norm"]["scale"].dtype == np.float32  # a vector stays


def test_capture_length_is_the_mix_s_own_or_the_default():
    assert traffic.profile_seconds({"generator": "open_loop"}) == 1.5
    assert traffic.profile_seconds({"profile_seconds": 0.75}) == 0.75
    for mix in ("chat", "chat-int8", "doc-bulk"):  # every mix has a length
        assert 0.05 <= traffic.profile_seconds(traffic.load_mix(mix)) <= 3.0
