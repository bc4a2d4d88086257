"""Continuous batching v2 (ISSUE 9): paged KV blocks, chunked prefill,
speculative decoding.

Covers, on the CPU backend with a tiny arch:
- BlockManager allocation policy (all-or-nothing, trash padding,
  utilization accounting);
- speculative_verify unit semantics (greedy acceptance, full-acceptance
  sampled case);
- chunked prefill == monolithic prefill (first-token logits + the decode
  chain that follows);
- paged scheduler greedy/sampled parity with the fixed-batch path;
- speculation ON == OFF byte-identical greedy streams (same-params draft,
  int8 draft, spec_mismatch chaos, draft-cold fallback);
- KV-pool pressure: eviction + re-admission continues streams correctly,
  exhaustion sheds with a computed Retry-After;
- chunked prefill interleaves with decode (long prompt doesn't stall a
  live stream);
- HTTP surface: SSE with X-Spec-Draft evidence + spec stats;
- /metrics generation block.
"""

import asyncio
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import gpt2 as G
from pytorch_zappa_serverless_tpu.serving.kvcache import (
    TRASH_BLOCK, BlockManager, KVPoolExhausted)

pytest_plugins = "aiohttp.pytest_plugin"

TINY_ARCH = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 128,
             "vocab_size": 500, "max_positions": 96}


def _tiny_cfg():
    return dataclasses.replace(G.SMALL, **TINY_ARCH, eos_id=499)


def _model_cfg(**over):
    extra = {"max_new_tokens": 12, "arch": TINY_ARCH, "gen_slots": 2,
             "segment_tokens": 3}
    extra.update(over.pop("extra", {}))
    kw = dict(name="gpt2", dtype="float32", batch_buckets=(1, 2),
              seq_buckets=(16,), coalesce_ms=1.0, kv_cache="paged",
              kv_block_size=4, extra=extra)
    kw.update(over)
    return ModelConfig(**kw)


# ---------------------------------------------------------------------------
# BlockManager
# ---------------------------------------------------------------------------

def test_block_manager_alloc_free_roundtrip():
    m = BlockManager(num_blocks=8, block_size=4, max_blocks=6)
    assert m.blocks_for(1) == 1 and m.blocks_for(4) == 1
    assert m.blocks_for(5) == 2
    assert m.alloc("a", 9)          # 3 blocks
    assert m.used_blocks == 3 and m.free_blocks == 4
    row = m.table_row("a")
    assert len(row) == 6 and row[3:] == [TRASH_BLOCK] * 3
    assert TRASH_BLOCK not in row[:3]
    assert m.extend("a", 13)        # grows to 4 blocks
    assert m.used_blocks == 4
    assert m.extend("a", 2)         # never shrinks, no-op
    assert m.used_blocks == 4
    assert m.free("a") == 4
    assert m.used_blocks == 0 and m.free_blocks == 7


def test_block_manager_all_or_nothing_and_caps():
    m = BlockManager(num_blocks=6, block_size=4, max_blocks=5)
    assert m.alloc("a", 12)         # 3 of 5 allocatable
    assert not m.alloc("b", 12)     # needs 3, only 2 free → nothing taken
    assert m.free_blocks == 2 and not m.holds("b")
    assert m.alloc("b", 8)
    assert not m.extend("b", 16)    # would need 2 more, 0 free
    assert m.free("a") == 3
    assert m.extend("b", 16)
    # max_blocks also caps a single sequence.
    with pytest.raises(ValueError):
        BlockManager(num_blocks=4, block_size=4, max_blocks=8)


def test_block_manager_utilization_accounting():
    m = BlockManager(num_blocks=16, block_size=8, max_blocks=10)
    m.alloc("a", 9)                 # 2 blocks for 9 tokens
    snap = m.snapshot()
    assert snap["blocks_used"] == 2
    assert snap["utilization"] == round(9 / 16, 4)
    assert snap["fragmentation"] == round(1 - 9 / 16, 4)
    m.note_tokens("a", 12)
    assert m.snapshot()["utilization"] == round(12 / 16, 4)


# ---------------------------------------------------------------------------
# speculative_verify unit
# ---------------------------------------------------------------------------

def test_speculative_verify_greedy_accepts_matching_prefix():
    from pytorch_zappa_serverless_tpu.ops.sampling import speculative_verify

    V, K = 7, 3
    # Target argmax chain: 2, 4, 1, 5 (positions 0..3).
    tgt_chain = [2, 4, 1, 5]
    t_logits = np.full((1, K + 1, V), -5.0, np.float32)
    for i, t in enumerate(tgt_chain):
        t_logits[0, i, t] = 5.0
    d_logits = np.zeros((1, K, V), np.float32)
    zeros = jnp.zeros((1,), jnp.int32)
    zf = jnp.zeros((1,), jnp.float32)

    # Draft matches 2 then diverges: accept 2, correct with tgt[2].
    n, out = speculative_verify(
        jnp.asarray(t_logits), jnp.asarray(d_logits),
        jnp.asarray([[2, 4, 0]], jnp.int32), zf, zeros, zeros)
    assert int(n[0]) == 2
    assert np.asarray(out)[0].tolist() == tgt_chain

    # Full match: all K accepted, bonus token is tgt[3].
    n, out = speculative_verify(
        jnp.asarray(t_logits), jnp.asarray(d_logits),
        jnp.asarray([[2, 4, 1]], jnp.int32), zf, zeros, zeros)
    assert int(n[0]) == K and int(np.asarray(out)[0, K]) == 5


def test_speculative_verify_sampled_identical_dists_accept_all():
    from pytorch_zappa_serverless_tpu.ops.sampling import speculative_verify

    rng = np.random.default_rng(3)
    V, K, S = 11, 4, 3
    logits = rng.normal(size=(S, K + 1, V)).astype(np.float32)
    draft = jnp.asarray(logits[:, :K])
    toks = jnp.asarray(rng.integers(0, V, (S, K)).astype(np.int32))
    temp = jnp.ones((S,), jnp.float32)
    seeds = jnp.asarray([1, 2, 3], jnp.int32)
    step = jnp.zeros((S,), jnp.int32)
    # p == q at every position → accept probability 1 for any proposal.
    n, _ = speculative_verify(jnp.asarray(logits), draft, toks, temp,
                              seeds, step)
    assert np.asarray(n).tolist() == [K] * S


# ---------------------------------------------------------------------------
# Chunked prefill == monolithic prefill
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_monolithic_logits_and_chain():
    cfg = _tiny_cfg()
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(3, cfg))
    rng = np.random.default_rng(0)
    P, max_new, BS, C = 13, 9, 4, 4
    ids = rng.integers(1, 400, (P,)).astype(np.int32)
    toks = jnp.asarray(ids[None])
    lens = jnp.asarray([P], jnp.int32)
    z1, s1 = jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32)
    topk, topp = jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.float32)
    total = P + max_new
    MB = -(-total // BS)
    fam = G.family(cfg)
    first_ref, ck_ref, _ = D.prefill_start(
        fam, params, toks, lens, z1, s1,
        D.zero_cache(fam, 1, total, jnp.float32), s1, jnp.float32)
    want = np.asarray(D.generate(fam, params, toks, lens, z1, s1, max_new,
                                 jnp.float32))[0]

    ck = jnp.zeros((cfg.layers, MB + 2, BS, cfg.d_model), jnp.float32)
    cv = jnp.zeros_like(ck)
    table = np.full((1, MB), TRASH_BLOCK, np.int32)
    table[0] = np.arange(1, MB + 1)
    table = jnp.asarray(table)
    first = None
    for start in range(0, P, C):
        sl = ids[start:start + C]
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :sl.shape[0]] = sl
        first, ck, cv = D.prefill_chunk(
            fam, params, jnp.asarray(chunk), jnp.asarray([start], jnp.int32),
            lens, D.PagedPool(ck, cv, table, BS), z1, s1, topk, topp,
            jnp.float32)
    # Same first token AND bitwise-identical cache rows at every written
    # prompt position (gathered virtually).
    assert int(first[0]) == int(first_ref[0])
    virt = np.asarray(ck[0][np.asarray(table[0])]).reshape(-1,
                                                           cfg.d_model)[:P]
    np.testing.assert_array_equal(virt, np.asarray(ck_ref[0, 0, :P]))
    # And the decode chain off the chunked cache matches one-shot generate.
    tok, pos = first, lens
    step = jnp.zeros((1,), jnp.int32)
    fin = jnp.zeros((1,), bool)
    got = []
    for _ in range(3):
        emits, ck, cv, tok, pos, step, fin = D.decode_segment(
            fam, params, D.PagedPool(ck, cv, table, BS), tok, pos, step, fin,
            z1, s1, 3, jnp.float32, top_k=topk, top_p=topp)
        got.append(np.asarray(emits))
    np.testing.assert_array_equal(np.concatenate(got, axis=1)[0], want)


# ---------------------------------------------------------------------------
# Paged scheduler vs fixed batch (engine + scheduler, no HTTP)
# ---------------------------------------------------------------------------

def _build_engine(tmp_path, *models):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    cfg = ServeConfig(compile_cache_dir=str(tmp_path / "xla"),
                      warmup_at_boot=False, models=list(models))
    return build_engine(cfg)


def _paged(engine, mc=None, draft_cm=None, name="gpt2"):
    from pytorch_zappa_serverless_tpu.serving.generation import (
        DraftGate, PagedGenerationScheduler)

    cm = engine.model(name)
    gate = None
    if draft_cm is not None:
        gate = DraftGate(draft_cm.servable.name, lambda: draft_cm)
    return PagedGenerationScheduler(cm, engine.runner, mc or cm.cfg,
                                    draft=gate)


@pytest.fixture()
def engine(tmp_path):
    eng = _build_engine(tmp_path, _model_cfg())
    yield eng
    eng.shutdown()


async def test_paged_scheduler_matches_fixed_batch(engine):
    cm = engine.model("gpt2")
    sched = _paged(engine).start()
    try:
        for ids in ([5, 6, 7], [9, 10], [3]):
            sample = cm.servable.preprocess({"input_ids": ids})
            got = await asyncio.wait_for(sched.submit(sample).done, 60)
            want = cm.run_batch([sample])[0][0]["tokens"]
            assert got == want, ids
    finally:
        await sched.stop()


async def test_paged_sampled_stream_matches_fixed_batch(engine):
    cm = engine.model("gpt2")
    sched = _paged(engine).start()
    try:
        sample = cm.servable.preprocess(
            {"input_ids": [5, 6, 7], "temperature": 1.3, "seed": 11,
             "top_k": 5, "top_p": 0.9})
        got = await asyncio.wait_for(sched.submit(sample).done, 60)
        want = cm.run_batch([sample])[0][0]["tokens"]
        assert got == want and got
    finally:
        await sched.stop()


async def test_paged_slots_reused_and_kv_freed(engine):
    cm = engine.model("gpt2")
    sched = _paged(engine).start()
    try:
        samples = [cm.servable.preprocess({"input_ids": [3 + i, 4 + i]})
                   for i in range(5)]
        reqs = [sched.submit(s, max_new=4) for s in samples]
        outs = await asyncio.wait_for(
            asyncio.gather(*[r.done for r in reqs]), 120)
        for s, got in zip(samples, outs):
            want = cm.run_batch([s])[0][0]["tokens"]
            assert got and len(got) <= 4 and got == want[: len(got)]
        snap = sched.gen_snapshot()
        assert snap["kv"]["blocks_used"] == 0  # everything released
        assert snap["kv"]["high_water_blocks"] > 0
    finally:
        await sched.stop()


async def test_backpressure_cancel_and_overlength(engine):
    sched = _paged(engine)
    sched._max_pending = 2
    sched.start()
    cm = engine.model("gpt2")
    try:
        mk = lambda *ids: cm.servable.preprocess({"input_ids": list(ids)})
        a = sched.submit(mk(5, 1), max_new=12)
        b = sched.submit(mk(5, 2), max_new=12)
        with pytest.raises(OverflowError):
            sched.submit(mk(5, 3))
        with pytest.raises(ValueError, match="longest configured"):
            # over the largest seq bucket (16): rejected at submit
            sched._max_pending = 99
            sched.submit(mk(*range(1, 19)))
        sched.cancel(b)
        with pytest.raises(RuntimeError, match="cancelled"):
            await asyncio.wait_for(b.done, 60)
        await asyncio.wait_for(a.done, 60)
    finally:
        await sched.stop()


# ---------------------------------------------------------------------------
# Chunked prefill interleaves with decode
# ---------------------------------------------------------------------------

async def test_long_prompt_prefill_does_not_stall_live_stream(tmp_path):
    eng = _build_engine(tmp_path, _model_cfg(
        prefill_chunk_tokens=4, extra={"max_new_tokens": 16}))
    try:
        cm = eng.model("gpt2")
        sched = _paged(eng).start()
        try:
            a = sched.submit(cm.servable.preprocess({"input_ids": [5, 6]}),
                             max_new=16)
            first_a = await asyncio.wait_for(a.events.get(), 60)
            assert first_a is not None and not a.done.done()
            # 15-token prompt at chunk cap 4 → 4 chunks, each interleaved
            # with a decode segment for A.
            b = sched.submit(cm.servable.preprocess(
                {"input_ids": list(range(1, 16))}), max_new=3)
            await asyncio.wait_for(b.events.get(), 60)
            assert b.segments_to_first_token is not None
            # Decode ticks ran BETWEEN b's prefill chunks — with a stalling
            # monolithic prefill this would be 1.
            assert b.segments_to_first_token >= 3
            assert sched.prefill_chunks >= 5  # 1 (a) + 4 (b)
            await asyncio.wait_for(asyncio.gather(a.done, b.done), 120)
            # Chains still correct.
            want_b = cm.run_batch([cm.servable.preprocess(
                {"input_ids": list(range(1, 16))})])[0][0]["tokens"]
            assert b.tokens == want_b[: len(b.tokens)] and b.tokens
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# Speculative decoding
# ---------------------------------------------------------------------------

def _spec_engine(tmp_path, **target_over):
    """gpt2 target + gpt2_draft (same builder, same random-init params →
    a perfect draft) as two deploys of one family."""
    target = _model_cfg(spec_draft="gpt2_draft", spec_k=3, family="gpt2fam",
                        quality_rank=2, **target_over)
    draft = ModelConfig(name="gpt2_draft", builder="gpt2", dtype="float32",
                        batch_buckets=(1, 2), seq_buckets=(16,),
                        coalesce_ms=1.0, family="gpt2fam", quality_rank=1,
                        extra={"max_new_tokens": 12, "arch": TINY_ARCH,
                               "gen_slots": 2, "segment_tokens": 3})
    return _build_engine(tmp_path, target, draft)


async def _greedy_stream(sched, cm, ids, max_new=10):
    sample = cm.servable.preprocess({"input_ids": ids})
    return await asyncio.wait_for(sched.submit(sample, max_new).done, 60)


async def test_spec_on_matches_spec_off_greedy_byte_identical(tmp_path):
    eng = _spec_engine(tmp_path)
    try:
        cm = eng.model("gpt2")
        draft_cm = eng.model("gpt2_draft")
        plain = _paged(eng).start()
        spec = _paged(eng, draft_cm=draft_cm).start()
        try:
            for ids in ([5, 6, 7], [9, 10], [2, 3, 4, 5, 6]):
                a = await _greedy_stream(plain, cm, ids)
                b = await _greedy_stream(spec, cm, ids)
                assert a == b and a, ids
            # A perfect draft (identical params): every proposal accepted.
            assert spec.spec_proposed > 0
            assert spec.spec_accepted == spec.spec_proposed
            assert plain.spec_proposed == 0
        finally:
            await plain.stop()
            await spec.stop()
    finally:
        eng.shutdown()


async def test_spec_with_imperfect_draft_still_exact(tmp_path):
    """An int8-quantized draft proposes slightly-off tokens; verification
    must correct to the exact plain-greedy chain, with partial acceptance."""
    target = _model_cfg(spec_draft="gpt2_i8", spec_k=3, family="gpt2fam",
                        quality_rank=2)
    # The int8 Pallas lm head needs 128-aligned d_model: the draft is a
    # genuinely DIFFERENT model (width, weights, quantization) — only the
    # vocab is shared.  Verification must still emit the target's chain.
    draft = ModelConfig(name="gpt2_i8", builder="gpt2", dtype="float32",
                        batch_buckets=(1, 2), seq_buckets=(16,),
                        coalesce_ms=1.0, family="gpt2fam", quality_rank=1,
                        extra={"max_new_tokens": 12,
                               "arch": {**TINY_ARCH, "d_model": 128},
                               "gen_slots": 2, "segment_tokens": 3,
                               "params_dtype": "int8",
                               "quantize_min_size": 1024})
    eng = _build_engine(tmp_path, target, draft)
    try:
        cm = eng.model("gpt2")
        spec = _paged(eng, draft_cm=eng.model("gpt2_i8")).start()
        try:
            for ids in ([5, 6, 7], [11, 12]):
                got = await _greedy_stream(spec, cm, ids)
                sample = cm.servable.preprocess({"input_ids": ids})
                want = cm.run_batch([sample])[0][0]["tokens"]
                assert got == want[: len(got)] and got
            assert spec.spec_proposed > 0
            assert 0 <= spec.spec_accepted <= spec.spec_proposed
        finally:
            await spec.stop()
    finally:
        eng.shutdown()


async def test_spec_mismatch_chaos_exercises_rejection_path(tmp_path):
    eng = _spec_engine(tmp_path)
    try:
        cm = eng.model("gpt2")
        # Derail EVERY spec tick's proposals: acceptance must go to zero
        # while greedy output stays byte-identical to plain decode.
        eng.runner.faults.configure(model="gpt2", fail_every_n=1,
                                    kind="spec_mismatch")
        spec = _paged(eng, draft_cm=eng.model("gpt2_draft")).start()
        try:
            got = await _greedy_stream(spec, cm, [5, 6, 7])
            sample = cm.servable.preprocess({"input_ids": [5, 6, 7]})
            want = cm.run_batch([sample])[0][0]["tokens"]
            assert got == want[: len(got)] and got
            assert spec.spec_proposed > 0 and spec.spec_accepted == 0
            assert eng.runner.faults.snapshot()["injected"]["spec"] > 0
        finally:
            await spec.stop()
    finally:
        eng.shutdown()


async def test_spec_falls_back_to_plain_decode_when_draft_cold(tmp_path):
    from pytorch_zappa_serverless_tpu.serving.generation import (
        DraftGate, PagedGenerationScheduler)

    eng = _spec_engine(tmp_path)
    try:
        cm = eng.model("gpt2")
        live = {"on": True}
        draft_cm = eng.model("gpt2_draft")
        gate = DraftGate("gpt2_draft",
                         lambda: draft_cm if live["on"] else None)
        sched = PagedGenerationScheduler(cm, eng.runner, cm.cfg,
                                         draft=gate).start()
        try:
            a = await _greedy_stream(sched, cm, [5, 6, 7])
            assert sched.spec_proposed > 0
            live["on"] = False  # draft goes COLD/quarantined
            before = sched.spec_proposed
            b = await _greedy_stream(sched, cm, [5, 6, 7])
            assert b == a  # plain decode, same chain
            assert sched.spec_proposed == before  # no new proposals
            assert sched.spec_fallback_ticks > 0
            assert not sched.spec_live()
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# KV-pool pressure: eviction + exhaustion shed
# ---------------------------------------------------------------------------

async def test_eviction_requeues_newest_and_streams_stay_correct(tmp_path):
    # Pool of 7 allocatable blocks (block 4): one 16+12-token stream needs
    # up to 7 — two concurrent streams MUST collide and evict.
    # kv_migrate=False pins PR 9's evict+recompute FALLBACK path (the
    # default now migrates pages to host instead — tests/test_migration.py
    # covers that; this proves the ladder's last rung still works).
    eng = _build_engine(tmp_path, _model_cfg(
        kv_num_blocks=8, kv_migrate=False,
        extra={"gen_slots": 2, "max_new_tokens": 12}))
    try:
        cm = eng.model("gpt2")
        sched = _paged(eng).start()
        try:
            mk = lambda *ids: cm.servable.preprocess({"input_ids": list(ids)})
            a = sched.submit(mk(5, 6, 7, 8, 9, 10, 11, 12), max_new=12)
            b = sched.submit(mk(9, 10, 11, 12, 13, 14), max_new=12)
            outs = await asyncio.wait_for(
                asyncio.gather(a.done, b.done), 120)
            assert sched.gen_snapshot()["kv"]["evictions"] > 0
            assert a.evictions + b.evictions > 0
            for req, ids in ((a, [5, 6, 7, 8, 9, 10, 11, 12]),
                             (b, [9, 10, 11, 12, 13, 14])):
                want = cm.run_batch([mk(*ids)])[0][0]["tokens"]
                assert req.tokens == want[: len(req.tokens)] and req.tokens
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


async def test_kv_exhaustion_sheds_with_computed_retry(tmp_path):
    eng = _build_engine(tmp_path, _model_cfg(
        kv_num_blocks=8, extra={"gen_slots": 2, "max_new_tokens": 12}))
    try:
        cm = eng.model("gpt2")
        sched = _paged(eng)  # not started: admission never drains pending
        mk = lambda seed: cm.servable.preprocess(
            {"input_ids": [seed] * 12})
        sched.submit(mk(1))  # 4 blocks pending demand
        sched._mgr.alloc("squatter", 20)  # 5 of 7 blocks gone
        with pytest.raises(KVPoolExhausted) as ei:
            sched.submit(mk(2))
        assert ei.value.retry_after_s > 0
        assert ei.value.free_blocks == 2
        assert ei.value.needed_blocks == 4
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# HTTP surface + metrics
# ---------------------------------------------------------------------------

async def test_sse_paged_with_spec_evidence(aiohttp_client, tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[
            _model_cfg(spec_draft="auto", spec_k=3, family="gpt2fam",
                       quality_rank=2, prefill_chunk_tokens=8),
            ModelConfig(name="gpt2_draft", builder="gpt2", dtype="float32",
                        batch_buckets=(1, 2), seq_buckets=(16,),
                        coalesce_ms=1.0, family="gpt2fam", quality_rank=1,
                        extra={"max_new_tokens": 12, "arch": TINY_ARCH,
                               "gen_slots": 2, "segment_tokens": 3}),
        ])
    engine = build_engine(cfg)
    try:
        client = await aiohttp_client(create_app(cfg, engine=engine))
        r = await client.post("/v1/models/gpt2:generate",
                              json={"input_ids": [5, 6, 7],
                                    "max_new_tokens": 6})
        assert r.status == 200
        assert r.content_type == "text/event-stream"
        # spec_draft=auto resolved the family's low rung; evidence header.
        assert r.headers.get("X-Spec-Draft") == "gpt2_draft"
        events = []
        async for line in r.content:
            line = line.decode().strip()
            if line.startswith("data: "):
                events.append(json.loads(line[len("data: "):]))
        final = events[-1]
        assert final.get("done") is True
        assert [e["token"] for e in events[:-1]] == final["tokens"]
        stats = final.get("stats", {})
        assert stats.get("spec_draft") == "gpt2_draft"
        assert stats.get("spec_proposed", 0) > 0
        assert 0 <= stats["spec_accepted"] <= stats["spec_proposed"]

        # stream=false carries the same evidence on headers + stats.
        r = await client.post("/v1/models/gpt2:generate",
                              json={"input_ids": [5, 6, 7],
                                    "max_new_tokens": 6, "stream": False})
        body = await r.json()
        assert r.status == 200, body
        assert r.headers.get("X-Spec-Draft") == "gpt2_draft"
        assert body["predictions"]["tokens"] == final["tokens"]

        # /metrics exposes the generation block with KV + spec counters.
        m = await (await client.get("/metrics")).json()
        gen = m["generation"]["gpt2"]
        assert gen["mode"] == "paged"
        assert gen["spec"]["proposed"] > 0
        assert gen["kv"]["blocks_total"] > 0
        prom = await (await client.get(
            "/metrics", headers={"Accept": "text/plain"})).text()
        for fam in ("tpuserve_kv_blocks_used", "tpuserve_kv_blocks_total",
                    "tpuserve_prefill_chunks_total",
                    "tpuserve_spec_proposed_total",
                    "tpuserve_spec_accepted_total"):
            assert fam in prom, fam
    finally:
        engine.shutdown()


async def test_paged_lane_without_contract_is_loud(tmp_path):
    """kv_cache='paged' on a servable without the paged kernel contract is
    a config error, not a silent downgrade."""
    from pytorch_zappa_serverless_tpu.serving.generation import (
        PagedGenerationScheduler)

    eng = _build_engine(tmp_path, ModelConfig(
        name="whisper_tiny", dtype="float32", batch_buckets=(1,),
        kv_cache="paged",
        extra={"max_new_tokens": 8,
               "arch": {"d_model": 32, "encoder_layers": 2,
                        "decoder_layers": 2, "heads": 2, "ffn_dim": 64,
                        "vocab_size": 64, "source_positions": 1500,
                        "target_positions": 96}}))
    try:
        cm = eng.model("whisper_tiny")
        if "continuous" not in cm.servable.meta:
            pytest.skip("whisper has no continuous meta in this config")
        with pytest.raises(ValueError, match="paged"):
            PagedGenerationScheduler(cm, eng.runner, cm.cfg)
    finally:
        eng.shutdown()
