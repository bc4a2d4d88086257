"""Continuous batching + SSE streaming (VERDICT r2 #2).

Covers, on the CPU backend with a tiny arch:
- decode_segment chain parity: segment-sliced decode emits the exact token
  stream the one-shot ``generate`` scan produces (greedy and sampled);
- scheduler parity through the public API;
- continuous batching: request B admits and finishes while request A is
  still mid-generation; slots are reused across more requests than slots;
- the SSE endpoint streams per-token events and a final done event;
- backpressure and cancellation.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import gpt2 as G

pytest_plugins = "aiohttp.pytest_plugin"

TINY_ARCH = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 128,
             "vocab_size": 500, "max_positions": 64}


def _tiny_cfg():
    import dataclasses

    return dataclasses.replace(G.SMALL, **TINY_ARCH, eos_id=499)


def _model_cfg(**extra):
    return ModelConfig(
        name="gpt2", dtype="float32", batch_buckets=(1, 2), seq_buckets=(8,),
        coalesce_ms=1.0,
        extra={"max_new_tokens": 12, "arch": TINY_ARCH, "gen_slots": 2,
               "segment_tokens": 3, **extra})


# ---------------------------------------------------------------------------
# Kernel-level parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 4.0])
def test_segment_chain_matches_one_shot_generate(temperature):
    cfg = _tiny_cfg()
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(3, cfg))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, 400, (2, 6)).astype(np.int32))
    lens = jnp.asarray([6, 4], jnp.int32)
    temp = jnp.full((2,), temperature, jnp.float32)
    seeds = jnp.asarray([5, 9], jnp.int32)
    max_new = 9
    fam = G.family(cfg)
    want = np.asarray(D.generate(fam, params, toks, lens, temp, seeds,
                                 max_new, jnp.float32))

    total = 6 + max_new
    first, ck, cv = D.prefill_start(
        fam, params, toks, lens, temp, seeds,
        D.zero_cache(fam, 2, total, jnp.float32), jnp.arange(2), jnp.float32)
    tok, pos = first, lens
    step = jnp.zeros((2,), jnp.int32)
    fin = jnp.zeros((2,), bool)
    got = []
    for _ in range(3):  # 3 segments x 3 tokens = max_new
        emits, ck, cv, tok, pos, step, fin = D.decode_segment(
            fam, params, D.slot_pool(ck, cv), tok, pos, step, fin, temp,
            seeds, 3, jnp.float32)
        got.append(np.asarray(emits))
    np.testing.assert_array_equal(np.concatenate(got, axis=1), want)


def test_segment_frozen_rows_do_not_disturb_neighbors():
    """A finished/empty slot rides along without changing an active row's
    chain — the core slot-pool invariant."""
    cfg = _tiny_cfg()
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(3, cfg))
    toks = jnp.asarray([[7, 8, 9, 0]], jnp.int32)
    lens = jnp.asarray([3], jnp.int32)
    z1 = jnp.zeros((1,), jnp.float32)
    s1 = jnp.zeros((1,), jnp.int32)
    total = 4 + 6
    fam = G.family(cfg)
    first, ck, cv = D.prefill_start(
        fam, params, toks, lens, z1, s1,
        D.zero_cache(fam, 1, total, jnp.float32), s1, jnp.float32)
    # Solo row decode.
    solo, *_ = D.decode_segment(fam, params, D.slot_pool(ck, cv), first,
                                lens, s1, jnp.zeros((1,), bool), z1, s1, 6,
                                jnp.float32)
    # Same row in slot 0 of a 2-slot pool; slot 1 empty (finished, pos 0).
    L = cfg.layers
    ck2 = jnp.zeros((L, 2, total, cfg.d_model), jnp.float32).at[:, :1].set(ck)
    cv2 = jnp.zeros((L, 2, total, cfg.d_model), jnp.float32).at[:, :1].set(cv)
    pooled, *_ = D.decode_segment(
        fam, params, D.slot_pool(ck2, cv2),
        jnp.asarray([int(first[0]), cfg.eos_id], jnp.int32),
        jnp.asarray([int(lens[0]), 0], jnp.int32),
        jnp.zeros((2,), jnp.int32),
        jnp.asarray([False, True]),
        jnp.zeros((2,), jnp.float32), jnp.zeros((2,), jnp.int32),
        6, jnp.float32)
    np.testing.assert_array_equal(np.asarray(pooled)[0], np.asarray(solo)[0])
    assert (np.asarray(pooled)[1] == cfg.eos_id).all()


# ---------------------------------------------------------------------------
# Scheduler behavior (engine + scheduler, no HTTP)
# ---------------------------------------------------------------------------

@pytest.fixture()
def engine(tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    cfg = ServeConfig(compile_cache_dir=str(tmp_path / "xla"),
                      warmup_at_boot=False, models=[_model_cfg()])
    eng = build_engine(cfg)
    yield eng
    eng.shutdown()


def _scheduler(engine):
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    cm = engine.model("gpt2")
    return GenerationScheduler(cm, engine.runner, cm.cfg)


async def test_scheduler_matches_fixed_batch(engine):
    cm = engine.model("gpt2")
    sched = _scheduler(engine).start()
    try:
        sample = cm.servable.preprocess({"input_ids": [5, 6, 7]})
        got = await asyncio.wait_for(sched.submit(sample).done, 60)
        want = cm.run_batch([sample])[0][0]["tokens"]
        assert got == want
    finally:
        await sched.stop()


async def test_request_joins_mid_generation(engine):
    """B admits while A decodes (continuous batching), with 1 slot free;
    a third request C queues until a slot frees, then completes."""
    sched = _scheduler(engine).start()
    cm = engine.model("gpt2")
    try:
        mk = lambda *ids: cm.servable.preprocess({"input_ids": list(ids)})
        a = sched.submit(mk(5, 6, 7), max_new=12)
        # Wait until A is actively decoding (some tokens streamed, not done).
        first_a = await asyncio.wait_for(a.events.get(), 60)
        assert first_a is not None and not a.done.done()
        b = sched.submit(mk(9, 10), max_new=3)
        toks_b = await asyncio.wait_for(b.done, 60)
        assert len(toks_b) <= 3
        # B finished while A (12-token budget) was still in flight, OR A
        # finished via EOS first — assert the join actually happened.
        assert b.slot is not None and a.slot is not None
        assert b.slot != a.slot  # distinct slots: B did not wait for A
        c = sched.submit(mk(11, 12, 13), max_new=2)
        assert (await asyncio.wait_for(c.done, 60)) is not None
        await asyncio.wait_for(a.done, 60)
    finally:
        await sched.stop()


async def test_slots_reused_across_many_requests(engine):
    """More requests than slots: all complete, deterministically."""
    sched = _scheduler(engine).start()
    cm = engine.model("gpt2")
    try:
        samples = [cm.servable.preprocess({"input_ids": [3 + i, 4 + i]})
                   for i in range(5)]
        reqs = [sched.submit(s, max_new=4) for s in samples]
        outs = await asyncio.wait_for(
            asyncio.gather(*[r.done for r in reqs]), 120)
        # Same inputs through the fixed-batch path give the same chain; the
        # per-request max_new=4 budget truncates it (a knob the fixed path
        # doesn't have), so compare the prefix.
        for s, got in zip(samples, outs):
            want = cm.run_batch([s])[0][0]["tokens"]
            assert len(got) <= 4 and got == want[: len(got)]
            assert got, "empty generation"
    finally:
        await sched.stop()


async def test_slots_at_different_lengths_match_generate_alone(tmp_path):
    """Every slot of the pool at a different length, over three segments:
    decode attention stops at each slot's own last position, and each
    stream equals ``generate()`` run alone on its request."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    eng = build_engine(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[_model_cfg(gen_slots=4, max_new_tokens=9)]))
    sched = _scheduler(eng).start()
    cm = eng.model("gpt2")
    try:
        samples = [cm.servable.preprocess(
            {"input_ids": [7 + 3 * i + j for j in range(n)]})
            for i, n in enumerate((2, 4, 6, 8))]
        reqs = [sched.submit(s) for s in samples]
        outs = await asyncio.wait_for(
            asyncio.gather(*[r.done for r in reqs]), 120)
        assert len({r.slot for r in reqs}) == 4
        for s, got in zip(samples, outs):
            assert got and got == cm.run_batch([s])[0][0]["tokens"]
        assert sched.segment_rounds >= 3  # 9 tokens in segments of 3
        live = sched.gen_snapshot()["kv_live_share"]
        assert live["count"] == sched.segment_rounds
        # Four slots, prompts of 2..8 and up to 9 tokens each, of 17
        # positions: never empty, never the whole pool.
        assert 0.0 < live["sum"] / live["count"] < 1.0
    finally:
        await sched.stop()
        eng.shutdown()


async def test_kv_live_share_counts_the_generating_slots(engine):
    """``kv_live_share``: per segment round, the positions the generating
    slots read over slots x total — the share of the pool a length-bounded
    decode read touches."""
    sched = _scheduler(engine).start()
    cm = engine.model("gpt2")
    try:
        assert sched.gen_snapshot()["kv_live_share"] == {"sum": 0.0,
                                                         "count": 0}
        assert sched.gen_snapshot()["kv_read_share"] == {"sum": 0.0,
                                                         "count": 0}
        sample = cm.servable.preprocess({"input_ids": [5, 6, 7]})
        await asyncio.wait_for(sched.submit(sample, max_new=3).done, 60)
        live = sched.gen_snapshot()["kv_live_share"]
        # One slot of two at position 3 (it reads 0..3) in a pool of 8 + 12
        # positions a slot, 3 more positions a round; the empty slot reads
        # nothing.
        rounds = live["count"]
        assert rounds == sched.segment_rounds >= 1
        want = sum((3 + 3 * r + 1) / (2 * 20) for r in range(rounds))
        assert live["sum"] == pytest.approx(want, abs=1e-6)
        # ``kv_read_share``: the same positions, each slot's rounded up to
        # the block attention reads in.  The ``jax.numpy`` form serves here
        # and reads whole rows: one row of two a round.
        assert sched.read_block == 20
        assert sched.gen_snapshot()["kv_read_share"] == {
            "sum": pytest.approx(0.5 * rounds), "count": rounds}
    finally:
        await sched.stop()


async def test_a_scrape_during_the_wait_sees_sums_and_count_of_the_same_rounds(
        engine):
    """Every ``{sum, count}`` pair of ``gen_snapshot`` is booked together,
    after the round's one wait: a scrape that lands inside the wait (most of
    a round, on the chip) reads the rows of as many rounds as it counts.  A
    profile capture divides one by the other over a handful of rounds."""
    sched = _scheduler(engine).start()
    cm = engine.model("gpt2")
    scraped = []

    class Fetched:
        def __init__(self, packed):
            self.packed = packed

        def __array__(self, dtype=None, copy=None):
            scraped.append(sched.gen_snapshot())
            return np.asarray(self.packed)

    segment = sched._segment

    def watched(*args):
        packed, *cache = segment(*args)
        return (Fetched(packed), *cache)

    sched._segment = watched
    try:
        sample = cm.servable.preprocess({"input_ids": [5, 6, 7]})
        await asyncio.wait_for(sched.submit(sample, max_new=7).done, 60)
        assert len(scraped) >= 2
        for snap in scraped + [sched.gen_snapshot()]:
            rounds = snap["span_rows"]["count"]
            # One slot at position 3, 3 more positions a round.
            held = sum(3 + 3 * r + 1 for r in range(rounds))
            assert snap["span_rows"]["sum"] == held
            assert snap["live_positions"]["sum"] == held
            assert snap["kv_live_share"]["sum"] == pytest.approx(
                held / (2 * 20), abs=1e-6)
            assert snap["kv_read_share"]["sum"] == pytest.approx(0.5 * rounds)
    finally:
        await sched.stop()


async def test_boot_log_names_the_prompt_form_of_every_prefill(engine,
                                                               monkeypatch):
    """``generation lane ready`` says, per prefill bucket and admission
    batch (the powers of two up to the slots), which form the prompt
    attention takes, by calling the picker's own rule."""
    from pytorch_zappa_serverless_tpu.ops import flash_attention as F
    from pytorch_zappa_serverless_tpu.serving import generation

    lines = []
    monkeypatch.setattr(generation, "log_event",
                        lambda log, msg, **fields: lines.append((msg, fields)))

    def forms():
        del lines[:]
        sched = _scheduler(engine)
        fields, = [f for msg, f in lines if msg == "generation lane ready"]
        return sched, fields["prompt_forms"]

    sched, got = forms()
    batches = [str(1 << i) for i in range(sched.slots.bit_length())
               if 1 << i <= sched.slots]
    assert got == {str(b): dict.fromkeys(batches, "einsum")
                   for b in sched.prompt_buckets}
    calls = []
    monkeypatch.setattr(
        "pytorch_zappa_serverless_tpu.models.decoder.prompt_form",
        lambda *shape: calls.append(shape) or "kernel")
    _, got = forms()
    assert set(got[str(sched.prompt_buckets[0])].values()) == {"kernel"}
    # (batch, heads, bucket, head size), what the rule is written on
    assert calls == [(int(b), 2, 8, 16) for b in batches]
    assert F.prompt_form(*calls[0]) == "einsum"


async def test_boot_log_lists_the_experts_plan_by_program(engine,
                                                          monkeypatch):
    """``generation lane ready`` lists under ``expert_plans`` what a family
    with routed experts says of the segment's rows and of each bucket's
    largest prefill dispatch; a family without says nothing."""
    from pytorch_zappa_serverless_tpu.serving import generation

    lines = []
    monkeypatch.setattr(generation, "log_event",
                        lambda log, msg, **fields: lines.append((msg, fields)))
    sched = _scheduler(engine)
    assert lines[-1][1]["expert_plans"] == {}
    meta = engine.model("gpt2").servable.meta["continuous"]
    monkeypatch.setitem(meta, "expert_plan", lambda rows: {"rows": rows})
    assert sched._expert_plans() == {
        "segment": {"rows": sched.slots},
        **{str(b): {"rows": b * sched.slots} for b in sched.prompt_buckets}}


async def test_first_uses_of_the_lanes_programs_are_booked_once(engine):
    """The slot lane's first prefill and segment each leave one
    entry in the engine's ledger, with the stages heard from inside jax
    inside the launch's wall; the same shapes again leave none; a new padded
    batch is a first use of cause ``shape``; and the ``:predict`` lane's
    first dispatch writes an entry of the same shape into the same ledger."""
    cm = engine.model("gpt2")
    sched = _scheduler(engine).start()
    clock = engine.clock
    try:
        one = cm.servable.preprocess({"input_ids": [5, 6, 7]})
        await asyncio.wait_for(sched.submit(one, max_new=4).done, 120)
        first = clock.snapshot()
        assert [(e["program"], e["key"], e["cause"]) for e in first] == [
            ("prefill", {"batch": 1, "bucket": 8, "form": "einsum"}, "first"),
            ("segment", {}, "first")]
        for e in first:
            assert e["model"] == "gpt2" and e["outcome"] == "miss"
            assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["backend_s"] > 0
            assert (e["trace_s"] + e["lower_s"] + e["cache_read_s"]
                    + e["backend_s"]) <= e["launch_s"]
            assert e["round"] == 1
        prefill, segment = first
        # The pool's zeros compile inside the first prefill's launch and
        # fold into its entry (where this process has not made them before).
        assert 1 <= prefill["compiles"] <= 3 and segment["compiles"] == 1
        assert prefill["first_run_s"] > 0 and segment["first_run_s"] > 0
        await asyncio.wait_for(sched.submit(one, max_new=4).done, 120)
        assert len(clock.entries) == 2  # the same keys: nothing new
        pair = [cm.servable.preprocess({"input_ids": [3 + i, 4 + i]})
                for i in range(2)]
        before = sched.gen_snapshot()["programs"]
        await asyncio.wait_for(asyncio.gather(
            *[sched.submit(s, max_new=4).done for s in pair]), 120)
        # What the benchmark's ``first_uses_in_window`` reads: the counter
        # moves by exactly the programs a burst of a new batch size compiles.
        after = sched.gen_snapshot()["programs"]
        assert after["first_uses"] - before["first_uses"] == 1
        assert [(e["program"], e["key"].get("batch"), e["cause"])
                for e in clock.snapshot()[2:]] == [("prefill", 2, "shape")]
        snap = sched.gen_snapshot()["programs"]
        assert snap["first_uses"] == 3 and snap["backend_hit_s"] == 0
        assert clock.first_uses() == {("gpt2", "prefill", "miss"): 2,
                                      ("gpt2", "segment", "miss"): 1}
        assert snap["launch_s"] + snap["first_run_s"] == pytest.approx(
            clock.per_model()["gpt2"]["seconds"], abs=2e-3)
        cm.run_batch([one])
        predict = clock.snapshot()[-1]
        assert predict["program"] == "predict" \
            and predict["key"] == {"bucket": [1, 8]}
        assert set(predict) == set(prefill)  # one entry shape, every lane
        assert predict["trace_s"] > 0 and predict["launch_s"] > 0 \
            and predict["first_run_s"] >= 0
    finally:
        await sched.stop()


async def test_burst_admissions_coalesce_into_one_prefill(engine):
    """A burst of same-bucket requests admits with ONE batched prefill
    dispatch (VERDICT r3 #5) — and the chains still match the fixed-batch
    path exactly."""
    sched = _scheduler(engine).start()
    cm = engine.model("gpt2")
    try:
        samples = [cm.servable.preprocess({"input_ids": [3 + i, 4 + i]})
                   for i in range(2)]  # gen_slots=2: both admit in one wave
        reqs = [sched.submit(s, max_new=4) for s in samples]
        outs = await asyncio.wait_for(
            asyncio.gather(*[r.done for r in reqs]), 120)
        assert sched.prefill_dispatches == 1, sched.prefill_dispatches
        for s, got in zip(samples, outs):
            want = cm.run_batch([s])[0][0]["tokens"]
            assert got == want[: len(got)] and got
        # One admission round + one segment round to the first token —
        # pinned so a regression to per-request admission (2+N rounds)
        # fails here, not in a chip run.
        assert [r.rounds_to_first_token for r in reqs] == [2, 2]
    finally:
        await sched.stop()


async def test_mixed_bucket_burst_admits_per_bucket(tmp_path):
    """Requests landing in different prompt buckets coalesce per bucket."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"),
        warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", dtype="float32", batch_buckets=(1, 2),
            seq_buckets=(4, 8), coalesce_ms=1.0,
            extra={"max_new_tokens": 6, "arch": TINY_ARCH, "gen_slots": 4,
                   "segment_tokens": 3})])
    eng = build_engine(cfg)
    try:
        cm = eng.model("gpt2")
        sched = GenerationScheduler(cm, eng.runner, cm.cfg).start()
        try:
            short = [cm.servable.preprocess({"input_ids": [5 + i]})
                     for i in range(2)]               # bucket 4
            long = [cm.servable.preprocess({"input_ids": list(range(1, 7))})
                    for _ in range(2)]                # bucket 8
            reqs = [sched.submit(s, max_new=4) for s in short + long]
            await asyncio.wait_for(
                asyncio.gather(*[r.done for r in reqs]), 120)
            # 4 requests, 2 buckets -> exactly 2 prefill dispatches.
            assert sched.prefill_dispatches == 2, sched.prefill_dispatches
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


async def test_lockstep_admission_fault_fails_every_popped_request(engine):
    """A lockstep-leader admission fault must fail EVERY request popped in
    that round — including ones in groups the round never reached (ADVICE
    r4 medium #1: those were popped from _pending but never in _active, so
    _go_fatal's sweep missed them and their futures hung forever)."""
    sched = _scheduler(engine)

    class _FakeLockstep:
        def lead_gen_admit(self, *a, **k):
            pass

        def lead_gen_segment(self, *a, **k):
            pass

    sched.lockstep = _FakeLockstep()

    def _bad_prefill(params, payload):
        raise RuntimeError("injected prefill fault")

    sched._prefill = _bad_prefill
    sched.start()
    cm = engine.model("gpt2")
    try:
        mk = lambda *ids: cm.servable.preprocess({"input_ids": list(ids)})
        # gen_slots=2: both pop in ONE admission round; lockstep groups are
        # per-request, so request B sits in a not-yet-processed group when
        # A's admission faults.
        a = sched.submit(mk(5, 6), max_new=4)
        b = sched.submit(mk(7, 8), max_new=4)
        with pytest.raises(RuntimeError):
            await asyncio.wait_for(a.done, 60)
        with pytest.raises(RuntimeError):  # pre-fix: hung forever
            await asyncio.wait_for(b.done, 10)
        assert sched.fatal is not None
    finally:
        await sched.stop()


async def test_lockstep_contract_error_is_per_request_not_fatal(engine):
    """A pre-broadcast collate/spec drift (LockstepContractError) fails only
    the offending request: no broadcast went out, so the world is still in
    lockstep and the lane must NOT go fatal (else a deterministic payload
    bug becomes a crash-restart loop)."""
    from pytorch_zappa_serverless_tpu.parallel.lockstep import (
        LockstepContractError)

    sched = _scheduler(engine)
    state = {"raised": False}

    class _DriftingLockstep:
        def lead_gen_admit(self, *a, **k):
            if not state["raised"]:
                state["raised"] = True
                raise LockstepContractError("injected collate/spec drift")

        def lead_gen_segment(self, *a, **k):
            pass

    sched.lockstep = _DriftingLockstep()
    sched.start()
    cm = engine.model("gpt2")
    try:
        mk = lambda *ids: cm.servable.preprocess({"input_ids": list(ids)})
        a = sched.submit(mk(5, 6), max_new=4)
        with pytest.raises(RuntimeError, match="drift"):
            await asyncio.wait_for(a.done, 60)
        assert sched.fatal is None  # lane still alive
        b = sched.submit(mk(7, 8), max_new=4)
        assert await asyncio.wait_for(b.done, 60)
    finally:
        await sched.stop()


async def test_mid_round_pool_reset_requeues_unprocessed_groups(tmp_path):
    """A post-donation admission fault resets the pool mid-round; requests
    in later groups of the SAME round must re-queue and admit cleanly next
    round instead of keeping slots popped from the pre-reset free list
    (ADVICE r4 medium #2: stale assignments double-booked slots)."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"),
        warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", dtype="float32", batch_buckets=(1, 2),
            seq_buckets=(4, 8), coalesce_ms=1.0,
            extra={"max_new_tokens": 6, "arch": TINY_ARCH, "gen_slots": 4,
                   "segment_tokens": 3})])
    eng = build_engine(cfg)
    try:
        cm = eng.model("gpt2")
        sched = GenerationScheduler(cm, eng.runner, cm.cfg)
        real_prefill = sched._prefill
        state = {"faulted": False}

        def _bad_prefill(params, cache, slots, payload):
            if not state["faulted"]:
                state["faulted"] = True
                # Simulate a dispatch that faulted AFTER consuming its
                # donated operands: the pool buffers are gone.
                for leaf in jax.tree.leaves(cache):
                    leaf.delete()
                raise RuntimeError("injected post-donation fault")
            return real_prefill(params, cache, slots, payload)

        sched._prefill = _bad_prefill
        sched.start()
        try:
            # Two buckets -> two groups in one admission round; bucket-4
            # group (submitted first) faults, bucket-8 group is unprocessed.
            short = [sched.submit(
                cm.servable.preprocess({"input_ids": [5 + i]}), max_new=4)
                for i in range(2)]
            long = [sched.submit(
                cm.servable.preprocess({"input_ids": list(range(1, 7))}),
                max_new=4) for _ in range(2)]
            for r in short:
                with pytest.raises(RuntimeError, match="post-donation"):
                    await asyncio.wait_for(r.done, 60)
            outs = [await asyncio.wait_for(r.done, 60) for r in long]
            # The re-queued requests decode the exact fixed-batch chains on
            # the rebuilt pool, on distinct slots.
            want = cm.run_batch(
                [cm.servable.preprocess({"input_ids": list(range(1, 7))})]
            )[0][0]["tokens"]
            for got in outs:
                assert got and got == want[: len(got)]
            assert len({r.slot for r in long}) == 2
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("fault", [False, True])
async def test_a_round_s_next_prefill_is_launched_behind_the_one_before(
        tmp_path, fault):
    """Two buckets make two admission groups in one round: the second
    group's prefill is launched before the first group's tokens are fetched
    (no host turn between two prefills on the device), every stream is the
    fixed-batch chain, and a launch that raises fails its own group in its
    own turn and nobody else (the pool was not donated: the lane lives).
    A shape's first use is not launched so: the round that compiles both
    runs them one after the other, as the ledger of first uses books them."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", dtype="float32", batch_buckets=(1, 2),
            seq_buckets=(4, 8), coalesce_ms=1.0,
            extra={"max_new_tokens": 6, "arch": TINY_ARCH, "gen_slots": 4,
                   "segment_tokens": 3})])
    eng = build_engine(cfg)
    try:
        cm = eng.model("gpt2")
        sched = GenerationScheduler(cm, eng.runner, cm.cfg)
        events = []
        real_prefill, real_set_slot = sched._prefill, sched._set_slot

        def prefill(params, cache, slots, payload):
            events.append(("launch", payload["input_ids"].shape[1]))
            if fault and events == [("launch", 4), ("launch", 8)]:
                raise RuntimeError("injected launch fault")  # once
            return real_prefill(params, cache, slots, payload)

        def set_slot(slot, *rest):
            events.append(("fetched", slot))
            return real_set_slot(slot, *rest)

        sched._prefill, sched._set_slot = prefill, set_slot
        samples = [cm.servable.preprocess({"input_ids": ids}) for ids in (
            [5], [6], list(range(1, 7)), list(range(2, 8)))]
        cold = [sched.submit(s, max_new=2) for s in samples]
        sched.start()
        try:
            for req in cold:
                await asyncio.wait_for(req.done, 120)
            assert [e for e in events if e[0] == "launch"] == [
                ("launch", 4), ("launch", 8)]
            assert events[1][0] == "fetched"  # the first use: one at a time
            events.clear()
            reqs = [sched.submit(s, max_new=4) for s in samples]
            want = [cm.run_batch([s])[0][0]["tokens"][:4] for s in samples]
            for req, tokens in zip(reqs[:2], want[:2]):
                assert await asyncio.wait_for(req.done, 60) == tokens
            for req, tokens in zip(reqs[2:], want[2:]):
                if fault:
                    with pytest.raises(RuntimeError, match="injected"):
                        await asyncio.wait_for(req.done, 60)
                else:
                    assert await asyncio.wait_for(req.done, 60) == tokens
            assert events[:3] == [("launch", 4), ("launch", 8),
                                  ("fetched", reqs[0].slot)]
            assert sched.fatal is None and sched._ahead is None
            again = sched.submit(samples[2], max_new=4)
            assert await asyncio.wait_for(again.done, 60) == want[2]
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


async def test_sampled_top_k_top_p_stream_matches_fixed_batch(engine):
    """Sampled decoding with top-k/top-p (VERDICT r4 #7): the continuous
    lane's token chain equals the fixed-batch path bit-for-bit under a fixed
    (seed, step) key chain — the parity property extends to the new knobs
    (both are [B]/[S]-shaped jit inputs, ops/sampling.py)."""
    sched = _scheduler(engine).start()
    cm = engine.model("gpt2")
    try:
        sample = cm.servable.preprocess(
            {"input_ids": [5, 6, 7], "temperature": 1.3, "seed": 11,
             "top_k": 5, "top_p": 0.9})
        assert sample["top_k"] == 5 and abs(sample["top_p"] - 0.9) < 1e-6
        got = await asyncio.wait_for(sched.submit(sample).done, 60)
        want = cm.run_batch([sample])[0][0]["tokens"]
        assert got == want and got
        # And the knobs actually bind: a different seed diverges somewhere
        # on this sampled chain (temperature 1.3 over a 500-token vocab).
        other = cm.servable.preprocess(
            {"input_ids": [5, 6, 7], "temperature": 1.3, "seed": 12,
             "top_k": 5, "top_p": 0.9})
        got2 = await asyncio.wait_for(sched.submit(other).done, 60)
        assert got2 != got
    finally:
        await sched.stop()


async def test_backpressure_and_cancel(engine):
    sched = _scheduler(engine)
    sched._max_pending = 2
    sched.start()
    cm = engine.model("gpt2")
    try:
        mk = lambda seed: cm.servable.preprocess({"input_ids": [5, seed]})
        a = sched.submit(mk(1), max_new=12)
        b = sched.submit(mk(2), max_new=12)
        with pytest.raises(OverflowError):
            sched.submit(mk(3))
        sched.cancel(b)
        with pytest.raises(RuntimeError, match="cancelled"):
            await asyncio.wait_for(b.done, 60)
        await asyncio.wait_for(a.done, 60)
    finally:
        await sched.stop()


# ---------------------------------------------------------------------------
# The round: one wait, retirement where the fetch lands, launch before fan-out
# ---------------------------------------------------------------------------

_EOS = 499


def _surface(row, live, budget):
    """The stream's rule, one token at a time: an EOS is not surfaced and
    ends the stream; the budget ends it after the token that spent it."""
    if not live:
        return 0, False
    n = 0
    for tok in row:
        if tok == _EOS:
            return n, True
        n += 1
        if n >= budget:
            return n, True
    return n, False


@pytest.mark.parametrize("row,live,budget", [
    pytest.param([1, 2, 3, 4], True, 9, id="neither"),
    pytest.param([_EOS, _EOS, _EOS, _EOS], True, 9, id="eos-at-0"),
    pytest.param([1, _EOS, _EOS, _EOS], True, 9, id="eos-at-1"),
    pytest.param([1, 2, _EOS, _EOS], True, 9, id="eos-at-2"),
    pytest.param([1, 2, 3, _EOS], True, 9, id="eos-at-3"),
    pytest.param([1, 2, 3, 4], True, 1, id="budget-at-0"),
    pytest.param([1, 2, 3, 4], True, 2, id="budget-at-1"),
    pytest.param([1, 2, 3, 4], True, 3, id="budget-at-2"),
    pytest.param([1, 2, 3, 4], True, 4, id="budget-at-3"),
    pytest.param([1, 2, 3, 4], True, 5, id="budget-in-the-next-segment"),
    pytest.param([1, _EOS, _EOS, _EOS], True, 3, id="eos-before-budget"),
    pytest.param([1, 2, 3, _EOS], True, 2, id="budget-before-eos"),
    pytest.param([1, 2, _EOS, _EOS], True, 2, id="budget-then-eos"),
    pytest.param([_EOS, _EOS, _EOS, _EOS], False, 0, id="already-finished"),
    pytest.param([1, 2, 3, 4], False, 7, id="dead-slot-with-stale-budget"),
])
def test_retirement_decision_is_the_streams_rule(row, live, budget):
    """``_retire`` over a pool equals the token-by-token rule in every slot,
    whatever its neighbours hold."""
    from pytorch_zappa_serverless_tpu.serving.generation import _retire

    rows = [([7, 8, 9, 10], True, 6), (row, live, budget),
            ([7, _EOS, _EOS, _EOS], True, 1), ([_EOS] * 4, False, 0)]
    n, done = _retire(np.asarray([r for r, _, _ in rows], np.int32),
                      np.asarray([lv for _, lv, _ in rows]),
                      np.asarray([b for _, _, b in rows], np.int32), _EOS)
    want = [_surface(*r) for r in rows]
    assert [(int(a), bool(b)) for a, b in zip(n, done)] == want
    # The surfaced tokens are the row's first n, none of them an EOS.
    assert _EOS not in rows[1][0][: int(n[1])]


def _sampled(cm, i, **extra):
    """A request whose chain wanders (greedy chains of the random tiny model
    repeat one token): sampled under a fixed (seed, step) key chain, which
    the fixed-batch lane reproduces bit for bit."""
    return cm.servable.preprocess({"input_ids": [5 + i, 6, 7 + 2 * i],
                                   "temperature": 1.3, "seed": 11 + i,
                                   **extra})


async def test_mixed_run_streams_match_fixed_batch_with_chained_rounds(
        tmp_path):
    """More requests than slots, differing budgets, one stream that meets
    EOS, one cancelled mid-stream: every stream is the fixed-batch chain,
    and rounds chained on the way."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    def engine_with(**arch):
        return build_engine(ServeConfig(
            compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
            models=[_model_cfg(arch={**TINY_ARCH, **arch})]))

    # The EOS of this run: the fifth token of request 0's own chain, so its
    # stream ends after four tokens, in the middle of its second segment.
    eng = engine_with()
    try:
        cm = eng.model("gpt2")
        chain = cm.run_batch([_sampled(cm, 0)])[0][0]["tokens"]
    finally:
        eng.shutdown()
    eos = chain[4]
    assert eos not in chain[:4]
    eng = engine_with(eos_id=eos)
    sched = _scheduler(eng).start()
    cm = eng.model("gpt2")
    try:
        assert sched.eos_id == eos
        budgets = [12, 7, 2, 12, 5, 3]
        samples = [_sampled(cm, i) for i in range(len(budgets))]
        reqs = [sched.submit(s, max_new=b) for s, b in zip(samples, budgets)]
        doomed = sched.submit(_sampled(cm, 9), max_new=12)
        first = await asyncio.wait_for(doomed.events.get(), 120)
        sched.cancel(doomed)
        outs = await asyncio.wait_for(
            asyncio.gather(*[r.done for r in reqs]), 120)
        with pytest.raises(RuntimeError, match="cancelled"):
            await asyncio.wait_for(doomed.done, 60)
        for s, b, got in zip(samples, budgets, outs):
            assert got == cm.run_batch([s])[0][0]["tokens"][:b]
        assert outs[0] == chain[:4]  # EOS ended it, and was not surfaced
        want = cm.run_batch([_sampled(cm, 9)])[0][0]["tokens"]
        assert doomed.tokens[0] == first
        assert doomed.tokens == want[: len(doomed.tokens)]
        snap = sched.gen_snapshot()
        assert 0 < snap["chained_rounds"] <= snap["segment_rounds"]
        # A later request is served from the slots the others left.
        last = _sampled(cm, 4)
        assert await asyncio.wait_for(sched.submit(last).done, 60) \
            == cm.run_batch([last])[0][0]["tokens"]
    finally:
        await sched.stop()
        eng.shutdown()


async def test_arrival_during_a_chained_segment_is_admitted_before_the_next(
        engine):
    """A request submitted while a chained segment runs waits for that one
    segment, as it would have, and for no other: with it pending and a slot
    free the fetch launches nothing, and the next program is its prefill."""
    sched = _scheduler(engine)
    cm = engine.model("gpt2")
    events: list = []
    segment, prefill = sched._segment, sched._prefill

    def spy_segment(*a):  # dispatch thread
        events.append("launch")
        return segment(*a)

    def spy_prefill(*a):
        events.append(("prefill", sched.chained_rounds))
        return prefill(*a)

    sched._segment, sched._prefill = spy_segment, spy_prefill
    late: dict = {}
    distribute = sched._distribute

    def spy_distribute(emits, n, done):
        distribute(emits, n, done)
        if sched._inflight is not None and not late:
            # On the event loop, between a chained launch and its fetch.
            late["chained"] = sched.chained_rounds
            events.append("submit")
            late["req"] = sched.submit(_sampled(cm, 1), max_new=3)

    sched._distribute = spy_distribute
    sched.start()
    try:
        a = sched.submit(_sampled(cm, 0), max_new=12)
        await asyncio.wait_for(a.done, 120)
        b = late["req"]
        got = await asyncio.wait_for(b.done, 120)
        assert got == cm.run_batch([_sampled(cm, 1)])[0][0]["tokens"][:3]
        assert late["chained"] == 1  # the first fetch chained
        at = events.index("submit")
        assert events[:at] == [("prefill", 0), "launch", "launch"]
        # Nothing was launched between the arrival and its admission, and
        # the fetch that found it pending beside a free slot did not chain.
        assert events[at + 1] == ("prefill", late["chained"])
        assert events[at + 2] == "launch"
        # The running segment and its own: what an arrival during a segment
        # has always paid.
        assert b.segments_to_first_token == 2
        assert a.segments_to_first_token == 1
        # A slot was free: all of its wait was for the running round.
        assert b.timing_stats()["slot_wait_ms"] == 0.0
        assert b.timing_stats()["round_wait_ms"] > 0.0
    finally:
        await sched.stop()


async def test_arrival_with_no_slot_free_waits_for_a_slot_not_for_a_round(
        engine):
    """Rounds chain while a request waits for a slot (nothing is admissible);
    it is seen at the loop top after its arrival, so that wait is booked to
    ``slot_wait_ms`` and not to the round that happened to be running."""
    sched = _scheduler(engine)
    cm = engine.model("gpt2")
    late: dict = {}
    distribute = sched._distribute

    def spy_distribute(emits, n, done):
        distribute(emits, n, done)
        if sched._inflight is not None and not late:
            late["req"] = sched.submit(_sampled(cm, 2), max_new=3)

    sched._distribute = spy_distribute
    sched.start()
    try:
        holders = [sched.submit(_sampled(cm, i), max_new=12) for i in (0, 1)]
        await asyncio.wait_for(asyncio.gather(*[r.done for r in holders]), 120)
        c = late["req"]
        got = await asyncio.wait_for(c.done, 120)
        assert got == cm.run_batch([_sampled(cm, 2)])[0][0]["tokens"][:3]
        st = c.timing_stats()
        # Both slots were held for three more segments of the four.
        assert st["slot_wait_ms"] > st["round_wait_ms"] >= 0.0
        assert c.seen_at < c.slotted_at
        assert sched.chained_rounds >= 3  # waiting for a slot stops no chain
    finally:
        await sched.stop()


async def test_fault_in_a_chained_launch_delivers_the_fetched_tokens(engine):
    """The launch a fetch makes fails: the fetched segment's tokens still
    reach the stream, then the in-flight request fails with the error, the
    pool resets and the next request is served."""
    sched = _scheduler(engine)
    cm = engine.model("gpt2")
    segment = sched._segment
    calls = {"n": 0}

    def faulty(*a):
        calls["n"] += 1
        if calls["n"] == 2:  # the first chained launch
            raise RuntimeError("injected launch fault")
        return segment(*a)

    sched._segment = faulty
    sched.start()
    try:
        sample = _sampled(cm, 0)
        want = cm.run_batch([sample])[0][0]["tokens"]
        a = sched.submit(sample, max_new=12)
        with pytest.raises(RuntimeError, match="injected launch fault"):
            await asyncio.wait_for(a.done, 120)
        assert a.tokens == want[:3]  # one segment of three, delivered
        assert [a.events.get_nowait() for _ in range(4)] == want[:3] + [None]
        assert sched.chained_rounds == 0 and sched.segment_rounds == 1
        assert sched._inflight is None and sched._cache is None
        assert sorted(sched._free) == [0, 1] and not sched._active
        assert await asyncio.wait_for(sched.submit(sample).done, 120) == want
        assert sched.chained_rounds > 0
    finally:
        await sched.stop()


async def test_lockstep_leader_never_chains(engine):
    """Each launch of the lockstep leader is paired with a broadcast of the
    slot state the followers mirror: the round keeps its order."""
    sched = _scheduler(engine)
    cm = engine.model("gpt2")
    led: list = []

    class _Lockstep:
        def lead_gen_admit(self, *a, **k):
            pass

        def lead_gen_segment(self, name, state):
            led.append(state["fin"].copy())

    sched.lockstep = _Lockstep()
    sched.start()
    try:
        sample = _sampled(cm, 0)
        got = await asyncio.wait_for(sched.submit(sample).done, 120)
        assert got == cm.run_batch([sample])[0][0]["tokens"]
        assert sched.chained_rounds == 0
        assert len(led) == sched.segment_rounds == 4  # 12 tokens by 3
    finally:
        await sched.stop()


# ---------------------------------------------------------------------------
# HTTP surface
# ---------------------------------------------------------------------------

async def test_sse_streams_tokens(aiohttp_client, tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    cfg = ServeConfig(compile_cache_dir=str(tmp_path / "xla"),
                      warmup_at_boot=False, models=[_model_cfg()])
    engine = build_engine(cfg)
    try:
        client = await aiohttp_client(create_app(cfg, engine=engine))
        r = await client.post("/v1/models/gpt2:generate",
                              json={"input_ids": [5, 6, 7],
                                    "max_new_tokens": 6})
        assert r.status == 200
        assert r.content_type == "text/event-stream"
        events = []
        async for line in r.content:
            line = line.decode().strip()
            if line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
        assert events, "no SSE events received"
        final = events[-1]
        assert final.get("done") is True
        streamed = [e["token"] for e in events[:-1]]
        assert streamed == final["tokens"] and 1 <= len(streamed) <= 6

        # stream=false returns one JSON body with the same tokens.
        r = await client.post("/v1/models/gpt2:generate",
                              json={"input_ids": [5, 6, 7],
                                    "max_new_tokens": 6, "stream": False})
        body = await r.json()
        assert r.status == 200, body
        assert body["predictions"]["tokens"] == final["tokens"]

        # repetition_penalty is batch-API-only: declined loudly here.
        r = await client.post("/v1/models/gpt2:generate",
                              json={"input_ids": [5],
                                    "repetition_penalty": 1.5})
        assert r.status == 400
        assert "repetition_penalty" in (await r.json())["error"]

        # Non-generative model → 405 with guidance.
        r = await client.post("/v1/models/nope:generate", json={"text": "x"})
        assert r.status == 404
    finally:
        engine.shutdown()
