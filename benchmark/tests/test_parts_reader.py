"""``readers/parts.py`` on a ``/admin/profile`` answer made by hand: the six
metrics of ISSUE 57, nothing from an answer without ``parts`` (a program
older than them, or the CPU), and each metric's file beside its entry in
``BENCHMARK.json`` with the cells it is read in."""

import json
from pathlib import Path

import pytest

from benchmark.readers import parts

ROOT = Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmark" / "layer_metrics"
SEVEN = ["gpt2xl-chat", "gpt2large-int8-chat", "evabyte-16l-docqa",
         "nemotron3s-11l-fleet-decode", "lfm2-10l-rag-fleet",
         "mellum2-8l-repo-assist", "joyai-flash-10l-latent-fleet"]
ROUTED = ["lfm2-10l-rag-fleet", "mellum2-8l-repo-assist",
          "joyai-flash-10l-latent-fleet"]
CELLS = {
    "parts_named_pct": ("tpot_p50_ms", SEVEN),
    "parts_named_pct.bulk": ("req_per_s", ["gpt2xl-doc-bulk"]),
    "prefill_matmul_share.bulk": ("req_per_s", ["gpt2xl-doc-bulk"]),
    "prefill_experts_share": ("tpot_p50_ms", ROUTED),
    "prefill_unsort_share": ("tpot_p50_ms", ROUTED),
    "prefill_attend_share": ("tpot_p50_ms", ROUTED),
}


def answer():
    """A prefill of 100 ms of operations, 4 in no part, and a segment of 50,
    1 in none."""
    return {"dir": "/nowhere", "programs": {
        "prefill": {
            "runs": 2, "device_ms": 101.0,
            "ops": {"expert_matmul": 30.0, "fusion": 40.0,
                    "flash_attention": 16.0, "expert_combine": 6.0,
                    "copy": 8.0},
            "parts": {"experts.matmul": 30.0, "attend": 16.0, "qkv": 12.0,
                      "experts.unsort": 11.0, "mlp": 9.0, "attend_out": 8.0,
                      "experts.sort": 5.0, "head": 3.0, "norm": 2.0},
            "unnamed_ms": 4.0, "unnamed_ops": {"copy": 4.0},
            "part_ops": {"experts.matmul": {"expert_matmul": 30.0},
                         "attend": {"flash_attention": 16.0},
                         "experts.unsort": {"expert_combine": 6.0,
                                            "fusion": 5.0}}},
        "segment": {
            "runs": 10, "device_ms": 52.0, "ops": {"fusion": 50.0},
            "parts": {"attend": 30.0, "mlp": 19.0}, "unnamed_ms": 1.0,
            "part_ops": {"attend": {"decode_attention": 30.0}}}}}


def ctx(profile):
    return {"run": {"profile": profile}}


def spec(name):
    return json.loads((METRICS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,want", [
    ("parts_named_pct", 100.0 * 145.0 / 150.0),
    ("parts_named_pct.bulk", 100.0 * 145.0 / 150.0),
    ("prefill_matmul_share.bulk", (12.0 + 8.0 + 9.0 + 3.0) / 100.0),
    ("prefill_experts_share", (5.0 + 30.0 + 11.0) / 100.0),
    ("prefill_unsort_share", 0.11),
    ("prefill_attend_share", 0.16),
])
def test_metric_from_a_hand_made_answer(name, want, capsys):
    one = ctx(answer())
    s = spec(name)
    assert s["reader"] == "parts" and s["layer"] == "model step"
    assert parts.read(one, **s["args"]) == pytest.approx(want)
    said = capsys.readouterr().out
    # The table, once a run, with each part's largest operations.
    assert "parts of prefill (2 runs, 100.0 ms of operations, 4.0 in no " \
        "part: copy 4.0)" in said and "1.0 in no part)" in said and "attend 16.0 (flash_attention 16.0)" in said
    assert parts.read(one, **s["args"]) == pytest.approx(want)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("profile", [
    None,                                           # not a traced run
    {"dir": "/nowhere", "idle": {}, "programs": {}},  # the CPU
    {"programs": {"prefill": {"runs": 1, "device_ms": 1.0,   # the parent
                              "ops": {"fusion": 1.0}}}},
])
@pytest.mark.parametrize("name", list(CELLS))
def test_nothing_to_read_gives_none(name, profile, capsys):
    assert parts.read(ctx(profile), **spec(name)["args"]) is None
    assert capsys.readouterr().out == ""


def test_a_capture_with_no_prefill_run_leaves_the_prefill_shares_out():
    profile = answer()
    del profile["programs"]["prefill"]
    assert parts.read(ctx(profile), "prefill_share", ["attend"]) is None
    assert parts.read(ctx(profile), "named_pct") == pytest.approx(98.0)
    with pytest.raises(ValueError):
        parts.read(ctx(profile), "no_such_kind")


@pytest.mark.parametrize("name", list(CELLS))
def test_file_and_entry_agree(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    s = spec(name)
    moves, cells = CELLS[name]
    assert entry == {"name": name, "unit": s["unit"], "better": s["better"],
                     "source": "device_trace", "layer": "model step",
                     "moves": moves, "workloads": cells}
    assert (s["moves"], s["source"]) == (moves, "device_trace")
    # The new entries stand at the end of the list, in the table's order.
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(CELLS)
    # Each of its cells reports the end-to-end metric it moves.
    (moved,) = [m for m in bench["end_to_end"] if m["name"] == moves]
    assert set(cells) <= set(moved["workloads"])
