"""The stratified generators: the same multiset under every seed, in another
order."""

import itertools
import json
from pathlib import Path

import pytest

from benchmark import traffic
from benchmark.generators import closed_loop, open_loop

MIXES = sorted(p.stem for p in (Path(traffic.HERE) / "traffic").glob("*.json"))


def summary(requests):
    return (sorted(len(r["ids"]) for r in requests),
            sorted(r["max_new"] for r in requests))


@pytest.mark.parametrize("mix_name", MIXES)
def test_same_multiset_other_order(mix_name):
    mix = traffic.load_mix(mix_name)
    plans = []
    for seed in (1, 3_000_000_001):
        if mix["generator"] == "open_loop":
            reqs = open_loop.plan(mix, 50.0, seed, 50257, 1.0, 16)
            dues = [0.0] + [r["due"] for r in reqs]
            gaps = [round(b - a, 9) for a, b in zip(dues, dues[1:])]
            plans.append((reqs, sorted(gaps), reqs[-1]["due"]))
        else:
            source = closed_loop.plan(mix, 50.0, seed, 50257, 1.0, 16)
            reqs = list(itertools.islice(source["requests"],
                                         2 * int(mix["block"])))
            plans.append((reqs, None, None))
    (a, gaps_a, end_a), (b, gaps_b, end_b) = plans
    assert summary(a) == summary(b)
    assert [len(r["ids"]) for r in a] != [len(r["ids"]) for r in b]
    assert [r["ids"] for r in a] != [r["ids"] for r in b]
    if gaps_a is not None:
        assert gaps_a == gaps_b
        assert abs(end_a - 50.0) < 1e-9 and abs(end_b - 50.0) < 1e-9


def test_closed_loop_blocks_are_each_the_whole_multiset():
    mix = traffic.load_mix("doc-bulk")
    n = int(mix["block"])
    source = closed_loop.plan(mix, 50.0, 9, 50257, 1.0, 16)["requests"]
    first, second = (list(itertools.islice(source, n)) for _ in range(2))
    assert summary(first) == summary(second)
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo < len(r["ids"]) < hi for r in first)


def test_extends_overrides_single_values():
    base, child = traffic.load_mix("chat"), traffic.load_mix("chat-int8")
    raw = json.loads((Path(traffic.HERE) / "traffic" / "chat-int8.json")
                     .read_text())
    assert set(raw) <= {"extends", "rate_per_s", "rate_from",
                        "profile_seconds"}
    assert child["prompt_tokens"] == base["prompt_tokens"]
    assert child["rate_per_s"] != base["rate_per_s"]


def test_stratified_midpoints_and_clip():
    dist = {"dist": "lognormal", "median": 160, "sigma": 0.8, "min": 16,
            "max": 768}
    values = traffic.lengths(dist, 200)
    assert min(values) >= 16 and max(values) == 768
    assert abs(sorted(values)[100] - 160) <= 2
    assert traffic.stratified({"dist": "uniform", "min": 0, "max": 4}, 4) \
        == [0.5, 1.5, 2.5, 3.5]
