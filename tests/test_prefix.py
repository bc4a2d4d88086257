"""Prefix KV cache (ISSUE 11): radix-tree block reuse with copy-on-write.

Covers, on the CPU backend with a tiny arch:
- BlockManager refcount edges: incref/decref, double-free guarded,
  free-while-shared decrements without releasing, adopt/cow, and
  snapshot()/utilization counting shared pages once;
- PrefixCache units: radix walk, edge split on divergence, partial-page
  match, LRU leaf-first reclaim with path protection, TTL decay,
  capacity decay, adapter invalidation;
- the parity bar: warm-prefix generation == cold == fixed-batch,
  greedy AND sampled, with and without an adapter slot;
- CoW divergence never mutates a shared page another stream references
  (device page bytes pinned before/after);
- chaos kind="prefix": poisoned lookups fall back to uncached prefill
  with identical output; force-CoW hits stay byte-identical;
- spec-decode fallback: a warm (prefix-hit) stream decodes plain;
- pool pressure: decayed prefix pages yield before any live stream is
  evicted;
- HTTP surface: /admin/prefix, per-stream stats evidence, the
  tpuserve_prefix_* families + manifest, the CLI table.
"""

import asyncio
import dataclasses
import json

import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.models import gpt2 as G
from pytorch_zappa_serverless_tpu.serving.kvcache import BlockManager
from pytorch_zappa_serverless_tpu.serving.prefixcache import PrefixCache

pytest_plugins = "aiohttp.pytest_plugin"

TINY_ARCH = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 128,
             "vocab_size": 500, "max_positions": 96}


def _tiny_cfg():
    return dataclasses.replace(G.SMALL, **TINY_ARCH, eos_id=499)


def _model_cfg(**over):
    extra = {"max_new_tokens": 8, "arch": TINY_ARCH, "gen_slots": 2,
             "segment_tokens": 3}
    extra.update(over.pop("extra", {}))
    kw = dict(name="gpt2", dtype="float32", batch_buckets=(1, 2),
              seq_buckets=(16,), coalesce_ms=1.0, kv_cache="paged",
              kv_block_size=4, extra=extra)
    kw.update(over)
    return ModelConfig(**kw)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    # One compile cache for the whole module: every test serves the same
    # tiny arch, so later engine builds hit warm XLA compiles.
    return tmp_path_factory.mktemp("xla-prefix")


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


# ---------------------------------------------------------------------------
# BlockManager refcount edges
# ---------------------------------------------------------------------------

def test_refcount_share_free_and_double_free_guard():
    m = BlockManager(num_blocks=8, block_size=4, max_blocks=6)
    assert m.alloc("a", 8)                      # 2 blocks at ref 1
    blocks = m.blocks_of("a")
    assert [m.refcount(b) for b in blocks] == [1, 1]
    for b in blocks:
        m.incref(b)                             # the "prefix tree" holds on
    assert m.shared_blocks() == 2
    # free-while-shared decrements without releasing.
    assert m.free("a") == 0
    assert m.used_blocks == 2
    assert [m.refcount(b) for b in blocks] == [1, 1]
    # Last holder releases for real.
    assert m.decref(blocks[0]) and m.decref(blocks[1])
    assert m.used_blocks == 0
    # Double free is a loud bug, not a silent page giveaway.
    with pytest.raises(ValueError, match="double free"):
        m.decref(blocks[0])
    with pytest.raises(ValueError, match="unallocated"):
        m.incref(blocks[0])


def test_adopt_and_cow_semantics():
    m = BlockManager(num_blocks=10, block_size=4, max_blocks=8)
    assert m.alloc("owner", 8)
    shared = m.blocks_of("owner")
    assert m.adopt("reader", shared, 8)
    assert [m.refcount(b) for b in shared] == [2, 2]
    assert m.used_blocks == 2                   # shared pages count once
    # CoW: the reader gets a private slot; the source stays pinned until
    # the caller's device copy lands.
    src, dst = m.cow("reader", 1)
    assert src == shared[1] and dst not in shared
    assert m.refcount(src) == 2                 # owner + caller's pin
    assert m.refcount(dst) == 1
    assert m.blocks_of("reader") == [shared[0], dst]
    m.decref(src)                               # copy landed
    assert m.refcount(src) == 1
    assert m.free("reader") == 1                # dst released, shared[0] not
    assert m.free("owner") == 2


def test_utilization_counts_shared_pages_once():
    m = BlockManager(num_blocks=16, block_size=8, max_blocks=10)
    m.alloc("a", 16)                            # 2 full blocks
    m.adopt("b", m.blocks_of("a"), 16)          # fully shared
    m.extend("b", 24)                           # + 1 private block
    snap = m.snapshot()
    assert snap["blocks_used"] == 3             # not 5
    assert snap["shared_blocks"] == 2
    # 24 unique tokens over 3 blocks: utilization from unique coverage.
    assert snap["utilization"] == round(24 / 24, 4)
    assert m.free("b") == 1
    # Tree-only blocks (external ref, no seq) count as fully covered.
    blocks = m.blocks_of("a")
    for b in blocks:
        m.incref(b)
    m.free("a")
    assert m.snapshot()["utilization"] == 1.0
    assert m.snapshot()["blocks_used"] == 2


# ---------------------------------------------------------------------------
# PrefixCache units
# ---------------------------------------------------------------------------

def _ids(*toks):
    return np.asarray(toks, np.int32)


def _freeze(cache, mgr, aidx, ids, seq):
    """Alloc + insert the way the scheduler does at prefill completion."""
    assert mgr.alloc(seq, ids.shape[0] + 1)
    return cache.insert(aidx, ids, mgr.blocks_of(seq))


def test_radix_lookup_insert_split_and_partial_match():
    mgr = BlockManager(num_blocks=32, block_size=4, max_blocks=16)
    pc = PrefixCache(mgr, 4)
    ids_a = _ids(*range(1, 11))                    # 10 tokens -> 2 frozen
    assert _freeze(pc, mgr, 0, ids_a, "a") == 2
    assert pc.node_count == 1 and pc.page_count == 2
    # Full-page hit, capped at plen-1.
    n, blocks = pc.lookup(0, ids_a, max_tokens=9)
    assert n == 8 and len(blocks) == 2
    assert blocks == mgr.blocks_of("a")[:2]
    # Sub-page divergence: shares one full page + the partial second page.
    ids_b = _ids(1, 2, 3, 4, 5, 6, 90, 91, 92)
    n, blocks = pc.lookup(0, ids_b, max_tokens=8)
    assert n == 6 and len(blocks) == 2             # partial page rides along
    # Insert of the divergent prompt splits the 2-page edge at the page
    # boundary and hangs a sibling for the new second page.
    assert mgr.alloc("b", ids_b.shape[0] + 1)
    pc.insert(0, ids_b, mgr.blocks_of("b"))
    assert pc.node_count == 3                      # [p1] -> {[p2], [p2']}
    assert pc.page_count == 3
    # Both full prompts now resolve through the split tree.
    n, _ = pc.lookup(0, ids_b, max_tokens=8)
    assert n == 8
    # Unknown prefix: miss.
    n, blocks = pc.lookup(0, _ids(200, 201, 202, 203, 204), max_tokens=4)
    assert n == 0 and blocks == []
    snap = pc.snapshot()
    assert snap["hits"] == 3 and snap["misses"] == 1
    assert snap["nodes_total"] == 3 and snap["pages_total"] == 3
    assert snap["cached_tokens"]["count"] == 3


def test_adapter_keyed_roots_and_invalidate():
    mgr = BlockManager(num_blocks=16, block_size=4, max_blocks=8)
    pc = PrefixCache(mgr, 4)
    ids = _ids(*range(1, 9))
    _freeze(pc, mgr, 1, ids, "t1")
    # Another adapter slot never sees slot 1's KV.
    assert pc.lookup(0, ids, max_tokens=7)[0] == 0
    # Capped at 7: one full page + a partial ride-along page.
    assert pc.lookup(1, ids, max_tokens=7)[0] == 7
    mgr.free("t1")
    used_before = mgr.used_blocks
    assert pc.invalidate(1) == 1
    assert pc.lookup(1, ids, max_tokens=7)[0] == 0
    assert mgr.used_blocks == used_before - 2      # tree refs dropped
    assert pc.snapshot()["evictions"] == 1


def test_reclaim_is_lru_leaf_first_and_respects_refs_and_protect():
    mgr = BlockManager(num_blocks=32, block_size=4, max_blocks=16)
    clock = {"t": 0.0}
    pc = PrefixCache(mgr, 4, clock=lambda: clock["t"])
    old = _ids(*range(1, 9))
    hot = _ids(*range(50, 58))
    _freeze(pc, mgr, 0, old, "old")
    clock["t"] = 10.0
    _freeze(pc, mgr, 0, hot, "hot")
    mgr.free("old")
    mgr.free("hot")
    assert pc.reclaimable() == 4
    # LRU first: reclaiming 1 page takes the OLD leaf (both its pages go —
    # node granularity), leaving the hot path resolvable.
    freed = pc.reclaim(1)
    assert freed == 2
    assert pc.lookup(0, hot, max_tokens=7)[0] == 7
    assert pc.lookup(0, old, max_tokens=7)[0] == 0
    # A stream still sharing the hot pages blocks reclaim entirely.
    n, blocks = pc.lookup(0, hot, max_tokens=7)
    assert mgr.adopt("reader", blocks, n)
    assert pc.reclaim(99) == 0
    mgr.free("reader")
    # protect= pins a matched-but-not-yet-adopted path.
    assert pc.reclaim(99, protect=frozenset(blocks)) == 0
    assert pc.reclaim(99) == 2


def test_ttl_decay_and_capacity_cap():
    mgr = BlockManager(num_blocks=32, block_size=4, max_blocks=16)
    clock = {"t": 0.0}
    pc = PrefixCache(mgr, 4, max_pages=2, clock=lambda: clock["t"])
    a = _ids(*range(1, 9))
    _freeze(pc, mgr, 0, a, "a")
    mgr.free("a")
    assert pc.page_count == 2
    # Capacity cap: inserting a second 2-page prefix evicts the LRU leaf.
    clock["t"] = 1.0
    b = _ids(*range(30, 38))
    _freeze(pc, mgr, 0, b, "b")
    mgr.free("b")
    assert pc.page_count == 2
    assert pc.lookup(0, b, max_tokens=7)[0] == 7
    assert pc.lookup(0, a, max_tokens=7)[0] == 0   # decayed
    # TTL decay: idle leaves go once the clock passes the ttl.
    assert pc.decay(5.0) == 0
    clock["t"] = 100.0
    assert pc.decay(5.0) == 2
    assert pc.page_count == 0 and mgr.used_blocks == 0


# ---------------------------------------------------------------------------
# Scheduler parity: warm == cold == fixed batch (greedy + sampled)
# ---------------------------------------------------------------------------

@pytest.fixture()
def engine(cache_dir):
    eng = _build_engine(cache_dir, _model_cfg())
    yield eng
    eng.shutdown()


async def _run(sched, cm, payload, max_new=None):
    sample = cm.servable.preprocess(payload)
    req = sched.submit(sample, max_new)
    await asyncio.wait_for(req.done, 60)
    return req


async def test_warm_prefix_parity_greedy_and_sampled(engine):
    cm = engine.model("gpt2")
    sched = _paged(engine).start()
    try:
        # Distinct prompts per case: KV depends on tokens only, so the
        # sampled case would otherwise (correctly) hit the greedy run's
        # frozen pages and never exercise its own cold path.
        for payload in ({"input_ids": list(range(5, 15))},
                        {"input_ids": list(range(30, 40)),
                         "temperature": 1.3, "seed": 11,
                         "top_k": 5, "top_p": 0.9}):
            cold = await _run(sched, cm, payload)
            assert cold.cached_tokens == 0
            warm = await _run(sched, cm, payload)
            want = cm.run_batch([cm.servable.preprocess(payload)])[0][0][
                "tokens"]
            assert cold.tokens == want
            assert warm.tokens == want              # byte-identical
            assert warm.cached_tokens == 8          # 2 pages reused
        snap = sched.gen_snapshot()["prefix"]
        assert snap["hits"] == 2 and snap["misses"] == 2
        assert snap["pages"] >= 2
        # Warm TTFT in device rounds: one small chunk instead of the full
        # prompt — device work strictly shrinks (wall clocks are too noisy
        # for tier-1).
        assert snap["cached_tokens"]["count"] == 2
    finally:
        await sched.stop()


async def test_cow_divergence_never_mutates_shared_page(engine):
    cm = engine.model("gpt2")
    sched = _paged(engine).start()
    try:
        base = list(range(5, 14))                   # 9 tokens -> 2 frozen
        cold = await _run(sched, cm, {"input_ids": base})
        want_base = cm.run_batch([cm.servable.preprocess(
            {"input_ids": base})])[0][0]["tokens"]
        assert cold.tokens == want_base
        # Pin the frozen pages' device bytes.
        root = sched._prefix._roots[0]
        node = next(iter(root.children.values()))
        blocks = list(node.blocks)
        page_k = np.array(np.asarray(sched._cache_k)[:, blocks])
        page_v = np.array(np.asarray(sched._cache_v)[:, blocks])
        # Diverge INSIDE the second frozen page -> partial share + CoW.
        div = base[:6] + [90, 91, 92]
        dreq = await _run(sched, cm, {"input_ids": div})
        want_div = cm.run_batch([cm.servable.preprocess(
            {"input_ids": div})])[0][0]["tokens"]
        assert dreq.tokens == want_div
        assert dreq.cached_tokens == 6              # 1 full + half page
        snap = sched.gen_snapshot()["prefix"]
        assert snap["cow_copies"] == 1
        # The shared pages are bit-for-bit untouched...
        np.testing.assert_array_equal(
            np.asarray(sched._cache_k)[:, blocks], page_k)
        np.testing.assert_array_equal(
            np.asarray(sched._cache_v)[:, blocks], page_v)
        # ...and the original prompt still replays byte-identically.
        re = await _run(sched, cm, {"input_ids": base})
        assert re.tokens == want_base and re.cached_tokens == 8
    finally:
        await sched.stop()


async def test_eviction_reclaims_prefix_pages_before_live_streams(cache_dir):
    # 6 allocatable blocks; stream A retires leaving 2 frozen pages.  A
    # second long stream must then grow past the remaining free pages —
    # the tree yields (leaf-first) before any live stream is evicted.
    eng = _build_engine(cache_dir, _model_cfg(
        kv_num_blocks=7, extra={"gen_slots": 2, "max_new_tokens": 8}))
    try:
        cm = eng.model("gpt2")
        sched = _paged(eng).start()
        try:
            a = await _run(sched, cm,
                           {"input_ids": [5, 6, 7, 8, 9, 10, 11, 12]},
                           max_new=2)
            snap = sched.gen_snapshot()["prefix"]
            assert snap["pages"] == 2 and snap["reclaimable_pages"] == 2
            b = await _run(sched, cm,
                           {"input_ids": [20, 21, 22, 23, 24, 25, 26, 27]},
                           max_new=8)
            want = cm.run_batch([cm.servable.preprocess(
                {"input_ids": [20, 21, 22, 23, 24, 25, 26, 27]})])[0][0][
                "tokens"]
            assert b.tokens == want
            assert b.evictions == 0                  # never evicted
            snap = sched.gen_snapshot()["prefix"]
            assert snap["evictions"] >= 1            # the tree paid instead
            assert sched.gen_snapshot()["kv"]["evictions"] == 0
            assert a.tokens  # a finished normally earlier
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# Chaos: faults kind="prefix"
# ---------------------------------------------------------------------------

def test_prefix_fault_rule_validation_and_targeting():
    from pytorch_zappa_serverless_tpu.faults import FaultInjector

    inj = FaultInjector()
    with pytest.raises(ValueError, match="kind='prefix'"):
        inj.configure(kind="transient", mode="cow")
    with pytest.raises(ValueError, match="poison"):
        inj.configure(kind="prefix", mode="bogus")
    inj.configure(model="gpt2", fail_every_n=1, kind="prefix")
    assert inj.on_prefix("gpt2") == "poison"        # default mode
    inj.on_dispatch("gpt2")                         # own target class
    inj.configure(model="gpt2", fail_every_n=1, kind="prefix", mode="cow")
    assert inj.on_prefix("gpt2") == "cow"
    assert inj.on_prefix("other") == ""
    assert inj.snapshot()["injected"]["prefix"] == 2
    rule = inj.snapshot()["rules"][0]
    assert rule["kind"] == "prefix" and rule["mode"] == "cow"


async def test_prefix_poison_chaos_falls_back_to_uncached(engine):
    cm = engine.model("gpt2")
    sched = _paged(engine).start()
    try:
        ids = list(range(5, 15))
        cold = await _run(sched, cm, {"input_ids": ids})
        # Poison EVERY lookup: warm requests must serve cold prefills with
        # byte-identical output and count as misses.
        engine.runner.faults.configure(model="gpt2", fail_every_n=1,
                                       kind="prefix")
        warm = await _run(sched, cm, {"input_ids": ids})
        assert warm.tokens == cold.tokens
        assert warm.cached_tokens == 0              # clean fallback
        snap = sched.gen_snapshot()["prefix"]
        assert snap["hits"] == 0 and snap["misses"] == 2
        assert engine.runner.faults.snapshot()["injected"]["prefix"] > 0
        # Clear the rule: reuse resumes on the SAME frozen pages.
        engine.runner.faults.clear()
        again = await _run(sched, cm, {"input_ids": ids})
        assert again.tokens == cold.tokens and again.cached_tokens == 8
    finally:
        await sched.stop()


async def test_prefix_force_cow_chaos_stays_byte_identical(engine):
    cm = engine.model("gpt2")
    sched = _paged(engine).start()
    try:
        ids = list(range(5, 15))
        cold = await _run(sched, cm, {"input_ids": ids})
        engine.runner.faults.configure(model="gpt2", fail_every_n=1,
                                       kind="prefix", mode="cow")
        warm = await _run(sched, cm, {"input_ids": ids})
        assert warm.tokens == cold.tokens           # copies are pure
        assert warm.cached_tokens == 8              # still a hit
        snap = sched.gen_snapshot()["prefix"]
        assert snap["cow_copies"] == 2              # every shared page cloned
        assert snap["hits"] == 1
    finally:
        await sched.stop()


# ---------------------------------------------------------------------------
# Spec-decode fallback: warm streams decode plain
# ---------------------------------------------------------------------------

async def test_warm_prefix_stream_falls_back_from_speculation(cache_dir):
    target = _model_cfg(spec_draft="gpt2_draft", spec_k=3, family="gpt2fam",
                        quality_rank=2, extra={"max_new_tokens": 10})
    draft = ModelConfig(name="gpt2_draft", builder="gpt2", dtype="float32",
                        batch_buckets=(1, 2), seq_buckets=(16,),
                        coalesce_ms=1.0, family="gpt2fam", quality_rank=1,
                        extra={"max_new_tokens": 10, "arch": TINY_ARCH,
                               "gen_slots": 2, "segment_tokens": 3})
    eng = _build_engine(cache_dir, target, draft)
    try:
        cm = eng.model("gpt2")
        sched = _paged(eng, draft_cm=eng.model("gpt2_draft")).start()
        try:
            ids = list(range(5, 15))
            cold = await _run(sched, cm, {"input_ids": ids})
            assert cold.spec_proposed > 0           # cold stream speculated
            warm = await _run(sched, cm, {"input_ids": ids})
            assert warm.tokens == cold.tokens       # parity under fallback
            assert warm.cached_tokens == 8
            assert warm.spec_proposed == 0          # plain decode
            assert not warm.has_draft
            assert sched.spec_fallback_ticks > 0
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# Adapters: per-slot trees, parity, detach invalidation
# ---------------------------------------------------------------------------

def _adapter_cfg(cache_dir):
    return ServeConfig(
        compile_cache_dir=str(cache_dir), warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", dtype="float32", batch_buckets=(1, 2),
            seq_buckets=(16,), coalesce_ms=10.0, kv_cache="paged",
            kv_block_size=4, adapter_slots=2, adapter_rank=4,
            adapters={"tenant-a": {"seed": 1, "alpha": 128},
                      "tenant-b": {"seed": 2, "alpha": 128}},
            extra={"max_new_tokens": 4, "arch": TINY_ARCH,
                   "gen_slots": 2, "segment_tokens": 2})])


async def test_warm_prefix_parity_under_adapter_slot(aiohttp_client,
                                                     cache_dir):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    client = await aiohttp_client(create_app(_adapter_cfg(cache_dir / "a")))
    ids = list(range(5, 15))

    async def gen(adapter=None):
        h = {"X-Adapter": adapter} if adapter else {}
        r = await client.post("/v1/models/gpt2:generate",
                              json={"input_ids": ids, "stream": False,
                                    "max_new_tokens": 4}, headers=h)
        assert r.status == 200, await r.text()
        body = await r.json()
        pred = body["predictions"]
        return (pred["tokens"],
                pred.get("stats", {}).get("prefix_cached_tokens", 0))

    base_cold, c0 = await gen()
    a_cold, c1 = await gen("tenant-a")
    assert c0 == 0 and c1 == 0                      # per-slot trees: no leak
    assert a_cold != base_cold                      # the adapter does bite
    base_warm, cb = await gen()
    a_warm, ca = await gen("tenant-a")
    assert base_warm == base_cold and cb == 8       # byte-identical + hit
    assert a_warm == a_cold and ca == 8
    r = await client.get("/admin/prefix")
    pref = (await r.json())["models"]["gpt2"]
    assert pref["hits"] == 2 and sorted(pref["adapters"]) == [0, 1]


async def test_adapter_detach_invalidates_slot_prefixes(aiohttp_client,
                                                        cache_dir):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    client = await aiohttp_client(create_app(_adapter_cfg(cache_dir / "a")))
    ids = list(range(5, 15))

    async def gen(adapter):
        r = await client.post("/v1/models/gpt2:generate",
                              json={"input_ids": ids, "stream": False,
                                    "max_new_tokens": 4},
                              headers={"X-Adapter": adapter})
        assert r.status == 200, await r.text()
        body = await r.json()
        pred = body["predictions"]
        return (pred["tokens"],
                pred.get("stats", {}).get("prefix_cached_tokens", 0))

    a_toks, _ = await gen("tenant-a")               # slot 1, freezes pages
    r = await client.post("/admin/adapters/gpt2/tenant-a",
                          json={"action": "detach"})
    assert r.status == 200, await r.text()
    pref = (await (await client.get("/admin/prefix")).json())["models"][
        "gpt2"]
    assert 1 not in pref["adapters"]                # slot 1 tree dropped
    assert pref["evictions"] >= 1
    # tenant-b now takes slot 1: its first run must be COLD (no stale KV)
    # and equal its own reference chain.
    b_toks, cached = await gen("tenant-b")
    assert cached == 0
    b_again, cached2 = await gen("tenant-b")
    assert b_again == b_toks and cached2 == 8
    assert b_toks != a_toks


# ---------------------------------------------------------------------------
# HTTP surface: metrics families, manifest, CLI
# ---------------------------------------------------------------------------

async def test_prefix_metrics_families_admin_and_manifest(aiohttp_client,
                                                          cache_dir):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    cfg = ServeConfig(compile_cache_dir=str(cache_dir / "xla"),
                      warmup_at_boot=False, models=[_model_cfg()])
    engine = build_engine(cfg)
    try:
        client = await aiohttp_client(create_app(cfg, engine=engine))
        for _ in range(2):
            r = await client.post("/v1/models/gpt2:generate",
                                  json={"input_ids": list(range(5, 15)),
                                        "max_new_tokens": 4,
                                        "stream": False})
            assert r.status == 200, await r.text()
        body = await r.json()
        assert body["predictions"]["stats"]["prefix_cached_tokens"] == 8
        # JSON metrics block.
        m = await (await client.get("/metrics")).json()
        pref = m["generation"]["gpt2"]["prefix"]
        assert pref["hits"] == 1 and pref["pages"] >= 2
        # /admin/prefix mirrors it with pool context.
        a = await (await client.get("/admin/prefix")).json()
        assert a["models"]["gpt2"]["hits"] == 1
        assert "kv_shared_blocks" in a["models"]["gpt2"]
        # Prometheus families, manifest-pinned.
        prom = await (await client.get(
            "/metrics", headers={"Accept": "text/plain"})).text()
        for fam in ("tpuserve_prefix_hits_total",
                    "tpuserve_prefix_misses_total",
                    "tpuserve_prefix_nodes_total",
                    "tpuserve_prefix_pages_total",
                    "tpuserve_prefix_cow_copies_total",
                    "tpuserve_prefix_cached_tokens"):
            assert fam in prom, fam
        import importlib.util
        from pathlib import Path

        path = (Path(__file__).resolve().parents[1] / "tools"
                / "check_metrics.py")
        spec = importlib.util.spec_from_file_location("cm_prefix", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.check(prom, mod.load_manifest()) == []
    finally:
        engine.shutdown()


def test_cli_prefix_table_renders():
    from pytorch_zappa_serverless_tpu.cli import format_prefix_table

    table = format_prefix_table({"models": {"gpt2": {
        "nodes": 3, "pages": 7, "hits": 5, "misses": 2, "hit_rate": 0.714,
        "cow_copies": 1, "evictions": 2, "reclaimable_pages": 6,
        "kv_shared_blocks": 3}}})
    lines = table.splitlines()
    assert lines[0].split() == ["MODEL", "NODES", "PAGES", "HITS", "MISSES",
                                "HIT_RATE", "COW", "EVICTIONS",
                                "RECLAIMABLE", "SHARED_NOW"]
    assert lines[1].split() == ["gpt2", "3", "7", "5", "2", "0.714", "1",
                                "2", "6", "3"]
