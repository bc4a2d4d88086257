"""Admission plans a round's prefills by the rows they pad (ISSUE 53).

``plan_prefills`` is a pure function of the admitted lengths, the lane's
buckets and the family's ``prefill_batch``:

1. the nine ways 8 documents split between ``[512, 768]`` give the plans and
   the row counts of ISSUE 53's table;
2. over random admissions it never pads more rows, makes more dispatches or
   asks for a larger padded batch than the per-bucket rule, and every prompt
   lands in a bucket at least its own that holds a prompt of its own;
3. every round ``benchmark/run.py`` sends to warm a cell up comes out as the
   per-bucket plan, so each warms the program it was sent to warm.

Through the scheduler: a prompt that rides in a longer bucket's dispatch is
served the greedy tokens it is served alone in its own bucket, the counters
and ``prefill.launch`` say that it rode, and a prefill at a longer bucket
writes the slots it was given and no other.
"""

import asyncio
import json
import math
import types
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run as bench_run
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.models.nemotron_h import PREFILL_BATCH
from pytorch_zappa_serverless_tpu.serving.generation import (
    GenerationScheduler, _pow2, build_gen_kernels, plan_prefills)
from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder
from test_decoder_seam import _EVA_ARCH, _GPT2_ARCH, _NEMOTRON_ARCH
from test_lfm2 import ARCH as _LFM2_ARCH

pytest_plugins = "aiohttp.pytest_plugin"

ROOT = Path(__file__).resolve().parents[1]


def _own(n, buckets):
    return next(b for b in buckets if b >= n)


def _per_bucket(lengths, buckets, cap):
    """The rule the plan replaced: a dispatch a bucket, split by ``cap``."""
    groups: dict[int, list[int]] = {}
    for i, n in enumerate(lengths):
        groups.setdefault(_own(n, buckets), []).append(i)
    return [(b, g[i:i + n]) for b, g in groups.items()
            for n in [cap(b) or len(g)] for i in range(0, len(g), n)]


def _rows(plan):
    return sum(_pow2(len(ix)) * b for b, ix in plan)


# -- 1. ISSUE 53's table -------------------------------------------------------

TABLE = {  # prompts of bucket 512 of 8 -> [(bucket, prompts)], rows x bucket
    0: ([(768, 8)], 6144), 1: ([(768, 8)], 6144), 2: ([(768, 8)], 6144),
    3: ([(768, 8)], 6144), 4: ([(512, 4), (768, 4)], 5120),
    5: ([(512, 4), (768, 4)], 5120), 6: ([(512, 4), (768, 4)], 5120),
    7: ([(512, 7), (768, 1)], 4864), 8: ([(512, 8)], 4096)}
TODAY = {0: 6144, 1: 6656, 2: 7168, 3: 8192, 4: 5120, 5: 7168, 6: 5632,
         7: 4864, 8: 4096}


@pytest.mark.parametrize("k", range(9))
def test_a_bulk_round_s_split_gives_the_table_s_plan(k):
    rng = np.random.default_rng(k)
    lengths = [int(n) for n in rng.permutation(
        [*rng.integers(256, 513, k), *rng.integers(513, 769, 8 - k)])]
    plan = plan_prefills(lengths, (512, 768), lambda b: None)
    assert sorted((b, len(ix)) for b, ix in plan) == TABLE[k][0]
    assert _rows(plan) == TABLE[k][1]
    assert _rows(_per_bucket(lengths, (512, 768), lambda b: None)) == TODAY[k]
    # The prompts that ride are their bucket's longest.
    stayed = [lengths[i] for b, ix in plan if b == 512 for i in ix]
    rode = [lengths[i] for b, ix in plan if b == 768 for i in ix
            if lengths[i] <= 512]
    assert not stayed or not rode or max(stayed) <= min(rode)


def test_the_table_s_means_are_the_issue_s():
    """6,560 rows and 1.99 dispatches a round today, 5,480 and 1.63 planned,
    over the binomial split of 8 documents uniform in 256-768."""
    share = [math.comb(8, k) / 256 for k in range(9)]
    assert sum(s * TODAY[k] for k, s in enumerate(share)) == 6560
    assert sum(s * TABLE[k][1] for k, s in enumerate(share)) == 5480
    assert sum(s * len(TABLE[k][0]) for k, s in enumerate(share)) \
        == pytest.approx(1.633, abs=1e-3)


# -- 2. never worse than the per-bucket rule ---------------------------------------

LANES = {  # buckets, prompts a dispatch, slots
    "xl": ((512, 768), lambda b: None, 8),
    "int8": ((256, 512, 768), lambda b: None, 16),
    "nemotron": ((128, 256, 512), lambda b: 8, 32),
    "one_a_dispatch": ((2048, 4096, 6144, 8192), lambda b: 1, 32),
    "a_window_s_worth": ((8, 16, 32, 64), lambda b: max(1, 32 // b), 12),
}


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("lane", LANES)
def test_random_admissions_are_never_worse_than_a_dispatch_a_bucket(lane, seed):
    buckets, cap, slots = LANES[lane]
    rng = np.random.default_rng([seed, len(lane)])
    for _ in range(60):
        lengths = [int(n) for n in rng.integers(
            1, buckets[-1] + 1, rng.integers(1, slots + 1))]
        if rng.random() < 0.5:  # a round that leans on one bucket
            b = buckets[rng.integers(len(buckets))]
            lengths[::2] = [min(n, b) for n in lengths[::2]]
        plan = plan_prefills(lengths, buckets, cap)
        today = _per_bucket(lengths, buckets, cap)
        assert sorted(i for _, ix in plan for i in ix) \
            == list(range(len(lengths)))
        assert _rows(plan) <= _rows(today)
        assert len(plan) <= len(today)
        assert max(_pow2(len(ix)) for _, ix in plan) \
            <= max(_pow2(len(ix)) for _, ix in today)
        used = {_own(n, buckets) for n in lengths}
        for b, ix in plan:
            assert b in used and len(ix) <= (cap(b) or len(ix))
            assert all(_own(lengths[i], buckets) <= b for i in ix)
        if _rows(plan) == _rows(today):  # a tie keeps what there was
            assert plan == today
        assert plan == plan_prefills(lengths, buckets, cap)


@pytest.mark.parametrize("lengths, buckets, cap, want", [
    # A lone admission, a single bucket and powers of two pad nothing.
    ([300], (512, 768), None, [(512, [0])]),
    ([300, 400, 500], (512, 768), None, [(512, [0, 1, 2])]),
    ([300, 700], (512, 768), None, [(512, [0]), (768, [1])]),
    ([700, 300, 310, 710], (512, 768), None, [(768, [0, 3]), (512, [1, 2])]),
    # 3 + 1 over [128, 256, 512] is a tie (1,024 rows either way).
    ([200, 210, 220, 500], (128, 256, 512), 8,
     [(256, [0, 1, 2]), (512, [3])]),
    # One prompt a dispatch: a dispatch's rows are its bucket.
    ([1000, 3000, 3100], (2048, 4096), 1,
     [(2048, [0]), (4096, [1]), (4096, [2])]),
    # 5 + 1 + 1 on 16 slots: the longest of 256 rides beside the one of 512
    # (2,816 rows for 3,328), and no further.
    ([100, 110, 120, 130, 140, 400, 700], (256, 512, 768), None,
     [(256, [0, 1, 2, 3]), (512, [4, 5]), (768, [6])]),
    # 5 + 2 + 1: 512 is full, so it rides past it to 768.
    ([100, 110, 120, 130, 140, 400, 410, 700], (256, 512, 768), None,
     [(256, [0, 1, 2, 3]), (512, [5, 6]), (768, [4, 7])]),
    # 2 + 1: a batch of 4 x 768 would pad more than it saves.
    ([300, 310, 700], (512, 768), None, [(512, [0, 1]), (768, [2])]),
    # Rule 2: riding into a bucket that holds one prompt a dispatch would
    # make three dispatches of two.
    ([10, 11, 12, 13, 14, 40], (16, 64), -1, [(16, [0, 1, 2, 3, 4]),
                                               (64, [5])]),
])
def test_the_plan_keeps_inside_what_the_lane_does_today(lengths, buckets, cap,
                                                        want):
    caps = (lambda b: 8 if b == 16 else 1) if cap == -1 else (lambda b: cap)
    assert plan_prefills(lengths, buckets, caps) == want


def test_no_padded_batch_grows_past_the_per_bucket_plan_s_largest():
    """Rule 3 where it binds: three buckets of 5 (each pads to 8) under a
    full one of 8.  One prompt of each riding to the top would save 8,520
    rows for the 6,144 a batch of 16 adds; no program of 16 rows was ever
    asked for, so the riders stop a bucket up instead."""
    buckets = (700, 710, 720, 768)
    lengths = [700] * 5 + [710] * 5 + [720] * 5 + [768] * 8
    plan = plan_prefills(lengths, buckets, lambda b: None)
    assert max(_pow2(len(ix)) for _, ix in plan) == 8
    assert [(b, len(ix)) for b, ix in plan] == [(700, 4), (710, 4), (720, 7),
                                                (768, 8)]
    assert _rows(plan) == 17544 < _rows(_per_bucket(
        lengths, buckets, lambda b: None)) == 23184


def test_a_prompt_past_the_largest_bucket_is_refused():
    with pytest.raises(ValueError, match="exceeds the largest bucket 768"):
        plan_prefills([300, 769], (512, 768), lambda b: None)


# -- 3. the benchmark's warm-up rounds dispatch as they were planned -----------------

# ``Rows.prefill_batch`` of each family at its cell's widths (EvaByte: a
# window's worth of positions, window 2,048).
CAPS = {"gpt2": lambda b: None, "evabyte": lambda b: max(1, 2048 // b),
        "nemotron_h": lambda b: PREFILL_BATCH, "lfm2": lambda b: 1,
        "mellum": lambda b: 1, "joyai": lambda b: 1}
CONFIGS = sorted({(w["config"], w["traffic"]) for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]})


@pytest.mark.parametrize("config, mix", CONFIGS)
def test_every_warm_up_round_comes_out_as_the_per_bucket_plan(config, mix):
    cell = next(w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]
        if (w["config"], w["traffic"]) == (config, mix))
    _, _, cfg, traffic_mix = bench_run.load_cell(cell)
    serve, scale = bench_run.serve_fragment(cfg, False)
    buckets, sizes = bench_run.warm_plan(traffic_mix, serve, scale)
    slots = int(serve["extra"]["gen_slots"])
    cap = CAPS[serve["builder"]]
    rounds = bench_run.warm_rounds(buckets, sizes, slots)
    assert rounds
    for rnd in rounds:
        lengths = [b for b, n in rnd for _ in range(n)]
        want = [(b, ix[i:i + c])
                for b, n in rnd
                for ix in [[j for j, own in enumerate(lengths) if own == b]]
                for c in [cap(b) or n] for i in range(0, n, c)]
        assert plan_prefills(lengths, serve["seq_buckets"], cap) == want
        # The blocker admitted with them (a late round) changes nothing.
        assert len(plan_prefills([buckets[0]] + lengths, serve["seq_buckets"],
                                 cap)) == len(_per_bucket(
                                     [buckets[0]] + lengths,
                                     serve["seq_buckets"], cap))


# -- through the scheduler ---------------------------------------------------------

_TINY = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 128,
         "vocab_size": 500, "max_positions": 64}
SERVED = {
    "gpt2": ("gpt2", {"arch": _TINY}, 400),
    "nemotron_h": ("nemotron_h", {"arch": _NEMOTRON_ARCH}, 90),
}


@pytest.fixture()
def engine(request, tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    builder, extra, _ = SERVED[request.param]
    eng = build_engine(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(
            name="m", builder=builder, dtype="float32", batch_buckets=(1,),
            seq_buckets=(8, 16), coalesce_ms=1.0,
            extra={"max_new_tokens": 6, "gen_slots": 5, "segment_tokens": 3,
                   **extra})]))
    yield eng, request.param
    eng.shutdown()


@pytest.mark.parametrize("engine", SERVED, indirect=True)
async def test_a_prompt_that_rides_up_is_served_what_it_is_served_alone(engine):
    """1 prompt of bucket 8 and 3 of bucket 16 arrive together: one dispatch
    ``[4, 16]`` (64 rows) where a dispatch a bucket made ``[1, 8]`` and
    ``[4, 16]`` (72).  Every stream's greedy tokens are what it is served
    alone, in its own bucket."""
    eng, name = engine
    cm = eng.model("m")
    rng = np.random.default_rng(7)
    prompts = [[int(t) for t in rng.integers(1, SERVED[name][2], n)]
               for n in (5, 12, 9, 16)]
    samples = [cm.servable.preprocess({"input_ids": p}) for p in prompts]

    alone = []
    sched = GenerationScheduler(cm, eng.runner, cm.cfg).start()
    try:
        for s in samples:
            alone.append(await asyncio.wait_for(sched.submit(s).done, 120))
        snap = sched.gen_snapshot()
        assert snap["prefill_buckets"] == {"8": 1, "16": 3}
        assert snap["prompts_moved_up"] == 0
        assert snap["prefill_rows_padded"] == 8 + 3 * 16
        assert snap["prefill_rows_prompt"] == 5 + 12 + 9 + 16
    finally:
        await sched.stop()

    sched = GenerationScheduler(cm, eng.runner, cm.cfg).start()
    try:
        reqs = [sched.submit(s) for s in samples]  # one admission holds all
        together = await asyncio.wait_for(
            asyncio.gather(*[r.done for r in reqs]), 120)
        snap = sched.gen_snapshot()
        launches = [p for r in sched.timeline.recent(64) for p in r["phases"]
                    if p["phase"] == "prefill.launch"]
    finally:
        await sched.stop()
    assert together == alone and all(len(t) == 6 for t in together)
    assert snap["prefill_dispatches"] == 1
    assert snap["prefill_buckets"] == {"8": 0, "16": 4}
    assert (snap["prefill_rows_padded"], snap["prefill_rows_prompt"],
            snap["prompts_moved_up"]) == (64, 42, 1)
    assert [(p["batch"], p["bucket"], p["moved"]) for p in launches] \
        == [(4, 16, 1)]


@pytest.mark.parametrize("engine", ["gpt2"], indirect=True)
async def test_a_burst_past_the_round_s_bound_is_admitted_over_rounds(
        engine, monkeypatch):
    """Five prompts arrive together at a bound of three a round: three are
    prefilled, a segment runs for them, then the other two are prefilled.
    Every stream's greedy tokens are what it is served alone."""
    from pytorch_zappa_serverless_tpu.serving import generation

    eng, name = engine
    cm = eng.model("m")
    rng = np.random.default_rng(11)
    samples = [cm.servable.preprocess(
        {"input_ids": [int(t) for t in rng.integers(1, SERVED[name][2], n)]})
        for n in (5, 7, 3, 8, 6)]

    async def serve(together: bool):
        sched = GenerationScheduler(cm, eng.runner, cm.cfg).start()
        try:
            if not together:
                return [await asyncio.wait_for(sched.submit(s).done, 120)
                        for s in samples], None
            reqs = [sched.submit(s) for s in samples]
            out = await asyncio.wait_for(
                asyncio.gather(*[r.done for r in reqs]), 120)
            return out, [[p["phase"] for p in r["phases"]
                          if p["phase"] in ("prefill.launch",
                                            "segment.launch")]
                         + [p["batch"] for p in r["phases"]
                            if p["phase"] == "prefill.launch"]
                         for r in sched.timeline.recent(64)]
        finally:
            await sched.stop()

    alone, _ = await serve(False)
    whole, rounds = await serve(True)  # the bound unmet: one admission
    assert whole == alone
    admitting = [r for r in rounds if "prefill.launch" in r]
    assert [r[-1] for r in admitting] == [5]

    monkeypatch.setattr(generation, "ROUND_ADMITS", 3)
    split, rounds = await serve(True)
    assert split == alone and all(len(t) == 6 for t in split)
    admitting = [r for r in rounds if "prefill.launch" in r]
    assert [r[-1] for r in admitting] == [3, 2]
    # The first three streams got a segment before the other two's prefill.
    assert all("segment.launch" in r for r in admitting)


def test_the_round_s_bound_is_met_by_no_lane_of_at_most_its_slots():
    """The bound leaves every accepted cell's rounds as they were: only a
    configuration of more slots than it can ever meet it."""
    from pytorch_zappa_serverless_tpu.serving.generation import ROUND_ADMITS

    slots = {c: int(json.loads((ROOT / "benchmark" / "configs" / f"{c}.json")
                               .read_text())["serve"]["extra"]["gen_slots"])
             for c, _ in CONFIGS}
    assert {c for c, n in slots.items() if n > ROUND_ADMITS} \
        == {"joyai-flash-10l"}


# -- a prefill at a longer bucket, every family -------------------------------------

_SLOT = {"gen_slots": 5, "segment_tokens": 4, "max_new_tokens": 12}
FAMILIES = {  # builder, dtype, extra, buckets, the prompt, vocabulary
    "gpt2": ("gpt2", "bfloat16", {**_SLOT, "arch": _GPT2_ARCH,
                                  "params_dtype": "bfloat16"}, (8, 16), 6, 90),
    "evabyte": ("evabyte", "float32", {**_SLOT, "arch": _EVA_ARCH},
                (32, 64), 22, 47),
    "nemotron_h": ("nemotron_h", "float32",
                   {**_SLOT, "arch": _NEMOTRON_ARCH}, (8, 16), 7, 90),
    "lfm2": ("lfm2", "float32", {**_SLOT, "arch": _LFM2_ARCH}, (8, 16), 5,
             60),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_longer_bucket_writes_its_slots_and_serves_the_same_tokens(family):
    """The programs are ragged by their lengths: a prompt collated to the
    longer bucket, in a batch beside two of that bucket, leaves the slots it
    was not given as they were, bit for bit, and decodes the tokens it
    decodes from its own bucket (every leaf of its slot that a step reads:
    K and V rows, summaries, a convolution's tail, a state)."""
    builder, dtype, extra, buckets, n, vocab = FAMILIES[family]
    sv = get_model_builder(builder)(ModelConfig(
        name=builder, dtype=dtype, batch_buckets=(1,), seq_buckets=buckets,
        extra=extra))
    meta = sv.meta["continuous"]
    kern = build_gen_kernels(types.SimpleNamespace(servable=sv))
    rng = np.random.default_rng(11)
    short, long = buckets
    prompts = [[int(t) for t in rng.integers(1, vocab, m)]
               for m in (n, long, long - 3)]

    def payload(ids, bucket):
        rows = [meta["collate_admit"](sv.preprocess({"input_ids": p}), bucket)
                for p in ids]
        return {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}

    def garbage():
        g = np.random.default_rng(5)
        return tuple(jnp.asarray(g.standard_normal(shape) * 3.0, dt)
                     for shape, dt in meta["cache_leaves"])

    def decode(first, cache, slot, length):
        S = meta["slots"]
        tok = np.zeros((S,), np.int32)
        pos = np.zeros((S,), np.int32)
        fin = np.ones((S,), bool)
        tok[slot], pos[slot], fin[slot] = first, length, False
        zeros = np.zeros((S,), np.int32)
        out = []
        step = zeros
        for _ in range(2):
            packed, *cache = kern["segment"](
                sv.params, tuple(cache), tok, pos, step, fin,
                np.zeros((S,), np.float32), zeros, zeros,
                np.ones((S,), np.float32))
            packed = np.asarray(packed)
            seg = meta["segment_tokens"]
            out += [int(t) for t in packed[slot, :seg]]
            tok, pos, step = (packed[:, seg + k].copy() for k in range(3))
        return out

    before = [np.asarray(leaf) for leaf in garbage()]
    first, *own = kern["prefill"](sv.params, garbage(),
                                  np.asarray([3], np.int32),
                                  payload(prompts[:1], short))
    want = [int(np.asarray(first)[0])] + decode(int(np.asarray(first)[0]),
                                               own, 3, n)
    # In the longer bucket's dispatch, padded to four with its first row.
    first, *rode = kern["prefill"](
        sv.params, garbage(), np.asarray([3, 0, 4, 3], np.int32),
        payload(prompts + prompts[:1], long))
    for was, leaf in zip(before, rode):
        leaf = np.asarray(leaf)
        for slot in (1, 2):  # given to nobody
            np.testing.assert_array_equal(leaf[:, slot], was[:, slot])
    got = [int(np.asarray(first)[0])] + decode(int(np.asarray(first)[0]),
                                              rode, 3, n)
    assert got == want
