"""Live KV migration + disaggregated serving primitives (ISSUE 13).

Covers, on the CPU backend with a tiny arch:
- kvmigrate wire-format units: pack/unpack round trip, integrity hash
  catches corruption, manifest version/field validation;
- faults kind="migration": rule validation, own target class, drop/
  corrupt/slow modes;
- the parity bar: a stream migrated mid-decode between two paged pools
  finishes byte-identical to the same stream left in place — greedy AND
  sampled, with a prefix-cache dedup hit on the target, and under an
  adapter slot (over HTTP);
- migrate-out under KV pressure: colliding streams swap to host and
  resume instead of evict+recompute — ZERO kv evictions, zero stream
  kills, byte-identical output;
- the HTTP protocol: snapshot → cutover → import → commit → attach with
  zero duplicate tokens; chaos mode="corrupt" caught by the integrity
  hash and cleanly retried through the pages phase; mode="drop" answers
  a retryable 503;
- metrics: tpuserve_migration* families + manifest lint, /admin/streams.
"""

import asyncio
import json

import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.models import gpt2 as G
from pytorch_zappa_serverless_tpu.serving import kvmigrate as KM

pytest_plugins = "aiohttp.pytest_plugin"

TINY_ARCH = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 128,
             "vocab_size": 500, "max_positions": 96}


def _model_cfg(**over):
    extra = {"max_new_tokens": 24, "arch": TINY_ARCH, "gen_slots": 2,
             "segment_tokens": 3}
    extra.update(over.pop("extra", {}))
    kw = dict(name="gpt2", dtype="float32", batch_buckets=(1, 2),
              seq_buckets=(16,), coalesce_ms=1.0, kv_cache="paged",
              kv_block_size=4, extra=extra)
    kw.update(over)
    return ModelConfig(**kw)


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("xla-migration")


def _build_engine(tmp_path, *models):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    cfg = ServeConfig(compile_cache_dir=str(tmp_path / "xla"),
                      warmup_at_boot=False, models=list(models))
    return build_engine(cfg)


def _paged(engine, mc=None, name="gpt2"):
    from pytorch_zappa_serverless_tpu.serving.generation import \
        PagedGenerationScheduler

    cm = engine.model(name)
    return PagedGenerationScheduler(cm, engine.runner, mc or cm.cfg)


def _pace_ticks(eng, latency_ms=25.0):
    """Slow every device dispatch (the latency half of a dispatch fault
    rule — no failures) so decode cannot outrun the migration handshake:
    each export/import command lands between two well-separated ticks."""
    eng.runner.faults.configure(model="gpt2", latency_ms=latency_ms)


async def _tokens_at_least(req, n, timeout_s=60.0):
    deadline = asyncio.get_running_loop().time() + timeout_s
    while len(req.tokens) < n:
        if asyncio.get_running_loop().time() > deadline:
            raise TimeoutError(f"stream stuck at {len(req.tokens)} tokens")
        await asyncio.sleep(0.005)


# ---------------------------------------------------------------------------
# kvmigrate wire-format units
# ---------------------------------------------------------------------------

def test_pack_unpack_round_trip_and_integrity():
    k = np.arange(2 * 4 * 8, dtype=np.float32).reshape(2, 4, 8)
    v = -k
    rec = KM.pack_page(5, k, v)
    i, k2, v2 = KM.unpack_page(rec, (2, 4, 8), "float32")
    assert i == 5
    np.testing.assert_array_equal(k2, k)
    np.testing.assert_array_equal(v2, v)
    # Corruption flips bytes AFTER the hash: the verify must catch it and
    # name the page to re-request.
    bad = KM.pack_page(5, k, v, corrupt=True)
    with pytest.raises(KM.PageIntegrityError) as ei:
        KM.unpack_page(bad, (2, 4, 8), "float32")
    assert ei.value.indices == [5]


def test_manifest_validation():
    good = {"version": KM.FORMAT_VERSION, "prompt": [1], "emitted": [],
            "state": {}, "npages": 1, "page_shape": [2, 4, 8],
            "dtype": "float32", "max_new": 8}
    KM.check_manifest(good)
    with pytest.raises(KM.MigrationError, match="version"):
        KM.check_manifest({**good, "version": 99})
    with pytest.raises(KM.MigrationError, match="missing field"):
        KM.check_manifest({k: v for k, v in good.items() if k != "state"})
    with pytest.raises(KM.MigrationError, match="JSON object"):
        KM.check_manifest(None)


def test_migration_fault_rule_validation_and_targeting():
    from pytorch_zappa_serverless_tpu.faults import FaultInjector

    inj = FaultInjector()
    with pytest.raises(ValueError, match="kind='prefix'/'migration'"):
        inj.configure(kind="transient", mode="drop")
    with pytest.raises(ValueError, match="drop"):
        inj.configure(kind="migration", mode="bogus")
    inj.configure(model="gpt2", fail_every_n=1, kind="migration")
    assert inj.on_migration("gpt2") == ("drop", 0.0)  # default mode
    inj.on_dispatch("gpt2")                           # own target class
    assert inj.on_migration("other") == ("", 0.0)
    inj.configure(model="gpt2", fail_every_n=1, kind="migration",
                  mode="slow", latency_ms=40.0)
    mode, lat = inj.on_migration("gpt2")
    assert mode == "slow" and lat == pytest.approx(0.04)
    assert inj.snapshot()["injected"]["migration"] == 2
    rule = inj.snapshot()["rules"][0]
    assert rule["kind"] == "migration" and rule["mode"] == "slow"


# ---------------------------------------------------------------------------
# Scheduler-level migration parity: migrated == left in place
# ---------------------------------------------------------------------------

async def _migrate_between(src, dst, req, cause="admin"):
    """Drive the full snapshot → cutover → import → commit protocol at the
    scheduler level; returns the imported request."""
    snap = await src.migrate_snapshot(req)
    cut = await src.migrate_cutover(req, have_idx=list(snap["pages"]))
    pages = {**snap["pages"], **cut["pages"]}
    new_req, hits, copied = await dst.migrate_import(
        cut["ids"], cut["emitted"], cut["state"], pages,
        aidx=cut["aidx"], max_new=cut["max_new"], cause=cause)
    await src.migrate_commit(req, cause)
    return new_req, cut, hits, copied


async def test_migrated_stream_parity_greedy_and_sampled(cache_dir):
    eng = _build_engine(cache_dir, _model_cfg())
    try:
        cm = eng.model("gpt2")
        _pace_ticks(eng)
        src = _paged(eng).start()
        dst = _paged(eng).start()
        try:
            for payload in ({"input_ids": list(range(5, 15))},
                            {"input_ids": list(range(30, 40)),
                             "temperature": 1.3, "seed": 11,
                             "top_k": 5, "top_p": 0.9}):
                want = cm.run_batch([cm.servable.preprocess(payload)])[0][0][
                    "tokens"]
                req = src.submit(cm.servable.preprocess(payload))
                await _tokens_at_least(req, 3)
                new_req, cut, hits, copied = await _migrate_between(
                    src, dst, req)
                assert copied > 0
                # The source stream ended with the migrated marker...
                assert req.migrated
                with pytest.raises(RuntimeError, match="migrated"):
                    await req.done
                # ...and the imported stream finishes the SAME chain.
                full = await asyncio.wait_for(new_req.done, 60)
                assert full == want                     # byte-identical
                assert new_req.emitted_base == len(cut["emitted"])
                # Zero duplicates: only post-import tokens entered the
                # event queue.
                fresh = 0
                while True:
                    ev = new_req.events.get_nowait()
                    if ev is None:
                        break
                    fresh += 1
                assert fresh == len(want) - new_req.emitted_base
            assert src.migration.snapshot()["by_cause"]["admin"] == 2
            assert dst.migration.snapshot()["by_cause"]["admin"] == 2
        finally:
            await src.stop()
            await dst.stop()
    finally:
        eng.shutdown()


async def test_migration_dedups_against_target_prefix_tree(cache_dir):
    eng = _build_engine(cache_dir, _model_cfg())
    try:
        cm = eng.model("gpt2")
        _pace_ticks(eng)
        src = _paged(eng).start()
        dst = _paged(eng).start()
        try:
            payload = {"input_ids": list(range(50, 60))}
            want = cm.run_batch([cm.servable.preprocess(payload)])[0][0][
                "tokens"]
            # Warm the TARGET's radix tree with the same prompt first.
            warm = dst.submit(cm.servable.preprocess(payload))
            assert (await asyncio.wait_for(warm.done, 60)) == want
            req = src.submit(cm.servable.preprocess(payload))
            await _tokens_at_least(req, 2)
            new_req, _, hits, copied = await _migrate_between(src, dst, req)
            assert hits >= 1          # frozen prompt pages adopted, not sent
            assert copied >= 1        # the decode tail still travels
            assert (await asyncio.wait_for(new_req.done, 60)) == want
            ms = dst.migration.snapshot()
            assert ms["pages"]["hit"] >= 1
        finally:
            await src.stop()
            await dst.stop()
    finally:
        eng.shutdown()


async def test_abort_resumes_stream_in_place(cache_dir):
    eng = _build_engine(cache_dir, _model_cfg())
    try:
        cm = eng.model("gpt2")
        _pace_ticks(eng)
        src = _paged(eng).start()
        try:
            payload = {"input_ids": list(range(70, 80))}
            want = cm.run_batch([cm.servable.preprocess(payload)])[0][0][
                "tokens"]
            req = src.submit(cm.servable.preprocess(payload))
            await _tokens_at_least(req, 2)
            await src.migrate_cutover(req, have_idx=())
            assert src.gen_snapshot()["migration"]["detached"] == 1
            await src.migrate_abort(req)
            assert (await asyncio.wait_for(req.done, 60)) == want
        finally:
            await src.stop()
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# Migrate-out under KV pressure: zero evictions, zero stream kills
# ---------------------------------------------------------------------------

async def test_pressure_migrates_out_before_eviction(cache_dir):
    # The PR 9 eviction scenario (pool of 7 blocks, two streams MUST
    # collide) — but with kv_migrate on (the default) the newest stream's
    # pages move to host and come back byte-identical: ZERO evictions,
    # zero recompute, both outputs exact.
    eng = _build_engine(cache_dir, _model_cfg(
        kv_num_blocks=8, extra={"gen_slots": 2, "max_new_tokens": 12}))
    try:
        cm = eng.model("gpt2")
        sched = _paged(eng).start()
        try:
            mk = lambda *ids: cm.servable.preprocess(
                {"input_ids": list(ids)})
            a = sched.submit(mk(5, 6, 7, 8, 9, 10, 11, 12), max_new=12)
            b = sched.submit(mk(9, 10, 11, 12, 13, 14), max_new=12)
            await asyncio.wait_for(asyncio.gather(a.done, b.done), 120)
            snap = sched.gen_snapshot()
            assert snap["kv"]["evictions"] == 0          # zero kills
            assert a.evictions + b.evictions == 0
            assert snap["migration"]["by_cause"]["pressure"] >= 1
            assert snap["migration"]["pages"]["copied"] >= 1
            assert a.migrations + b.migrations >= 1
            for req, ids in ((a, [5, 6, 7, 8, 9, 10, 11, 12]),
                             (b, [9, 10, 11, 12, 13, 14])):
                want = cm.run_batch([mk(*ids)])[0][0]["tokens"]
                assert req.tokens == want                # byte-identical
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


async def test_pressure_drop_chaos_falls_back_to_eviction(cache_dir):
    # mode="drop" on every migration: the pressure ladder must fall back
    # to PR 9's evict+recompute and still finish every stream.
    eng = _build_engine(cache_dir, _model_cfg(
        kv_num_blocks=8, extra={"gen_slots": 2, "max_new_tokens": 12}))
    try:
        cm = eng.model("gpt2")
        eng.runner.faults.configure(model="gpt2", fail_every_n=1,
                                    kind="migration", mode="drop")
        sched = _paged(eng).start()
        try:
            mk = lambda *ids: cm.servable.preprocess(
                {"input_ids": list(ids)})
            a = sched.submit(mk(5, 6, 7, 8, 9, 10, 11, 12), max_new=12)
            b = sched.submit(mk(9, 10, 11, 12, 13, 14), max_new=12)
            await asyncio.wait_for(asyncio.gather(a.done, b.done), 120)
            snap = sched.gen_snapshot()
            assert snap["kv"]["evictions"] > 0           # fallback fired
            assert snap["migration"]["by_cause"]["pressure"] == 0
            assert snap["migration"]["failed"] >= 1
            assert eng.runner.faults.snapshot()["injected"]["migration"] >= 1
            for req, ids in ((a, [5, 6, 7, 8, 9, 10, 11, 12]),
                             (b, [9, 10, 11, 12, 13, 14])):
                want = cm.run_batch([mk(*ids)])[0][0]["tokens"]
                assert req.tokens == want[: len(req.tokens)] and req.tokens
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# HTTP protocol: export → import → attach (+ chaos, metrics)
# ---------------------------------------------------------------------------

class _SSEReader:
    """Stateful SSE consumer: bytes buffered past an early return are kept
    for the next read (a chunk may carry more events than asked for)."""

    def __init__(self, resp):
        self.resp = resp
        self.buf = b""
        self.pending: list[dict] = []

    async def events(self, n=None, timeout_s=60.0):
        out = []

        def drain() -> bool:
            while self.pending:
                out.append(self.pending.pop(0))
                if n is not None and len(out) >= n:
                    return True
            return False

        if drain():
            return out
        deadline = asyncio.get_running_loop().time() + timeout_s
        while True:
            if asyncio.get_running_loop().time() > deadline:
                raise TimeoutError("SSE read timed out")
            chunk = await asyncio.wait_for(self.resp.content.readany(),
                                           timeout_s)
            if not chunk:
                return out
            self.buf += chunk
            while b"\n\n" in self.buf:
                raw, self.buf = self.buf.split(b"\n\n", 1)
                for line in raw.splitlines():
                    if line.startswith(b"data: "):
                        self.pending.append(json.loads(line[6:]))
            if drain():
                return out


def _serve_cfg(cache_dir, **model_over):
    return ServeConfig(compile_cache_dir=str(cache_dir),
                       warmup_at_boot=False,
                       models=[_model_cfg(**model_over)])


async def _pace_http(client, latency_ms=15.0):
    r = await client.post("/admin/faults",
                          json={"model": "gpt2",
                                "latency_ms": latency_ms})
    assert r.status == 200, await r.text()


async def _drive_http_migration(client, sid, new_sid, cause="admin"):
    """The router's import loop, inline: snapshot → cutover → import with
    need-list retries → commit.  Returns (watermark, import body)."""
    r = await client.post(f"/admin/streams/{sid}/export",
                          json={"phase": "snapshot"})
    assert r.status == 200, await r.text()
    snap = await r.json()
    pages = {p["i"]: p for p in snap["pages"]}
    r = await client.post(f"/admin/streams/{sid}/export",
                          json={"phase": "cutover",
                                "have": sorted(pages)})
    assert r.status == 200, await r.text()
    cut = await r.json()
    for p in cut["pages"]:
        pages[p["i"]] = p
    body = None
    for _ in range(3):
        r = await client.post(f"/admin/streams/{new_sid}/import",
                              json={"manifest": cut["manifest"],
                                    "pages": list(pages.values()),
                                    "cause": cause})
        body = await r.json()
        if r.status == 200:
            break
        assert r.status == 409 and body.get("need"), body
        rp = await client.post(f"/admin/streams/{sid}/export",
                               json={"phase": "pages",
                                     "indices": body["need"]})
        assert rp.status == 200, await rp.text()
        for p in (await rp.json())["pages"]:
            pages[p["i"]] = p
    else:
        raise AssertionError(f"import never succeeded: {body}")
    r = await client.post(f"/admin/streams/{sid}/export",
                          json={"phase": "commit", "cause": cause})
    assert r.status == 200, await r.text()
    commit = await r.json()
    return commit["watermark"], body


async def test_http_export_import_attach_zero_duplicates(aiohttp_client,
                                                         cache_dir):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    client = await aiohttp_client(create_app(_serve_cfg(cache_dir / "h")))
    payload = {"input_ids": list(range(5, 15)), "max_new_tokens": 16}
    await _pace_http(client)
    # Reference chain (fixed-batch lane, byte-identical contract).
    r = await client.post("/v1/models/gpt2:generate",
                          json={**payload, "stream": False})
    assert r.status == 200, await r.text()
    want = (await r.json())["predictions"]["tokens"]

    resp = await client.post("/v1/models/gpt2:generate", json=payload)
    assert resp.status == 200
    sid = resp.headers["X-Stream-Id"]
    reader = _SSEReader(resp)
    head = [ev["token"] for ev in await reader.events(n=3)]
    watermark, imp = await _drive_http_migration(client, sid, "mig-1")
    assert imp["imported"] and imp["watermark"] >= len(head)
    # The source stream ends with the migrated marker — tokens up to the
    # cutover, then the terminal event, never an error or a done.
    tail_src = await reader.events()
    src_tokens = head + [ev["token"] for ev in tail_src if "token" in ev]
    assert tail_src[-1].get("migrated") is True
    assert tail_src[-1]["watermark"] == watermark
    assert len(src_tokens) == watermark
    # Attach from the tokens WE have: the server replays the gap from the
    # imported history, then streams live — each token exactly once.
    r = await client.get("/admin/streams/mig-1/attach",
                         params={"from": str(len(src_tokens))})
    assert r.status == 200
    evs = await _SSEReader(r).events()
    rest = [ev["token"] for ev in evs if "token" in ev]
    assert evs[-1].get("done") is True
    assert src_tokens + rest == want            # zero loss, zero dup
    assert evs[-1]["tokens"] == want
    # Registry + metrics evidence.
    streams = (await (await client.get("/admin/streams")).json())["streams"]
    assert streams[sid]["state"] == "migrated"
    assert streams["mig-1"]["imported"] is True
    m = await (await client.get("/metrics")).json()
    mig = m["generation"]["gpt2"]["migration"]
    assert mig["by_cause"]["admin"] >= 2        # export + import counted
    assert mig["pages"]["copied"] >= 1
    prom = await (await client.get(
        "/metrics", headers={"Accept": "text/plain"})).text()
    for fam in ("tpuserve_migrations_total",
                "tpuserve_migration_pages_total",
                "tpuserve_migration_ms"):
        assert fam in prom, fam
    import importlib.util
    from pathlib import Path

    path = (Path(__file__).resolve().parents[1] / "tools"
            / "check_metrics.py")
    spec = importlib.util.spec_from_file_location("cm_migration", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.check(prom, mod.load_manifest()) == []


async def test_http_corrupt_chaos_caught_and_retried(aiohttp_client,
                                                     cache_dir):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    app = create_app(_serve_cfg(cache_dir / "h"))
    client = await aiohttp_client(app)
    payload = {"input_ids": list(range(30, 40)), "max_new_tokens": 16,
               "temperature": 1.1, "seed": 7, "top_k": 6}
    await _pace_http(client)
    r = await client.post("/v1/models/gpt2:generate",
                          json={**payload, "stream": False})
    want = (await r.json())["predictions"]["tokens"]
    resp = await client.post("/v1/models/gpt2:generate", json=payload)
    sid = resp.headers["X-Stream-Id"]
    reader = _SSEReader(resp)
    head = [ev["token"] for ev in await reader.events(n=2)]
    # Corrupt ONE export: the integrity hash must catch it; the retry
    # fetches exactly the bad pages by value and the stream survives.
    r = await client.post("/admin/faults",
                          json={"model": "gpt2", "fail_every_n": 1,
                                "count": 1, "kind": "migration",
                                "mode": "corrupt"})
    assert r.status == 200, await r.text()
    watermark, imp = await _drive_http_migration(client, sid, "mig-c")
    tail_src = await reader.events()
    src_tokens = head + [ev["token"] for ev in tail_src if "token" in ev]
    r = await client.get("/admin/streams/mig-c/attach",
                         params={"from": str(len(src_tokens))})
    evs = await _SSEReader(r).events()
    rest = [ev["token"] for ev in evs if "token" in ev]
    assert src_tokens + rest == want            # sampled chain exact too
    faults = await (await client.get("/admin/faults")).json()
    assert faults["faults"]["injected"]["migration"] >= 1


async def test_http_drop_chaos_answers_retryable_503(aiohttp_client,
                                                     cache_dir):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    client = await aiohttp_client(create_app(_serve_cfg(cache_dir / "h")))
    payload = {"input_ids": list(range(60, 70)), "max_new_tokens": 16}
    await _pace_http(client)
    resp = await client.post("/v1/models/gpt2:generate", json=payload)
    sid = resp.headers["X-Stream-Id"]
    await _SSEReader(resp).events(n=2)
    r = await client.post("/admin/faults",
                          json={"model": "gpt2", "fail_every_n": 1,
                                "count": 1, "kind": "migration",
                                "mode": "drop"})
    assert r.status == 200, await r.text()
    r = await client.post(f"/admin/streams/{sid}/export",
                          json={"phase": "snapshot"})
    assert r.status == 503
    assert r.headers.get("Retry-After")
    assert (await r.json()).get("retryable") is True
    # The rule is spent: the retry succeeds and the stream is unharmed.
    r = await client.post(f"/admin/streams/{sid}/export",
                          json={"phase": "snapshot"})
    assert r.status == 200, await r.text()
    resp.close()


async def test_http_adapter_stream_migration_parity(aiohttp_client,
                                                    cache_dir):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    cfg = ServeConfig(
        compile_cache_dir=str(cache_dir / "a"), warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", dtype="float32", batch_buckets=(1, 2),
            seq_buckets=(16,), coalesce_ms=10.0, kv_cache="paged",
            kv_block_size=4, adapter_slots=2, adapter_rank=4,
            adapters={"tenant-a": {"seed": 1, "alpha": 128}},
            extra={"max_new_tokens": 12, "arch": TINY_ARCH,
                   "gen_slots": 2, "segment_tokens": 2})])
    client = await aiohttp_client(create_app(cfg))
    payload = {"input_ids": list(range(5, 15)), "max_new_tokens": 12}
    await _pace_http(client)
    hdr = {"X-Adapter": "tenant-a"}
    r = await client.post("/v1/models/gpt2:generate",
                          json={**payload, "stream": False}, headers=hdr)
    assert r.status == 200, await r.text()
    want = (await r.json())["predictions"]["tokens"]
    resp = await client.post("/v1/models/gpt2:generate", json=payload,
                             headers=hdr)
    sid = resp.headers["X-Stream-Id"]
    reader = _SSEReader(resp)
    head = [ev["token"] for ev in await reader.events(n=2)]
    watermark, imp = await _drive_http_migration(client, sid, "mig-a")
    tail_src = await reader.events()
    src_tokens = head + [ev["token"] for ev in tail_src if "token" in ev]
    r = await client.get("/admin/streams/mig-a/attach",
                         params={"from": str(len(src_tokens))})
    evs = await _SSEReader(r).events()
    rest = [ev["token"] for ev in evs if "token" in ev]
    assert src_tokens + rest == want   # adapter chain survives migration
    assert evs[-1]["tokens"] == want


def test_cli_disagg_flags_exist():
    from pytorch_zappa_serverless_tpu.cli import build_parser

    args = build_parser().parse_args(
        ["fleet", "--replicas", "http://a,http://b", "--disagg",
         "--prefill-replicas", "http://a"])
    assert args.disagg and args.prefill_replicas == "http://a"
