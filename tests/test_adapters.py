"""Multi-tenant LoRA adapter serving (serving/adapters.py, ops/lora.py;
docs/ADAPTERS.md).

Kernel half: batched-vs-sequential multi-adapter matmul parity and the
rank-0/no-adapter == base byte-identity contract, plus the torch/PEFT
checkpoint conversion and the offline merge hook.  Unit half: the adapter
residency state machine (single-flight attach, idle scale-to-zero per
tenant, LRU slot eviction, HBM-budget shedding) against a fake engine.
HTTP half: the real serving stack with a tiny gpt2 — two tenants co-batched
into ONE dispatch (batch_mates evidence), 503 ``adapter_cold`` + Retry-After
on deadline-infeasible cold hits, idle detach + on-demand re-attach, the
``kind="adapter"`` chaos contract (one poisoned tenant never takes the base
or its neighbors down), per-stream adapters on the paged :generate lane,
(model, adapter)-keyed jobs, and the adapter metrics families against the
pinned manifest.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine import weights as W
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import gpt2 as G
from pytorch_zappa_serverless_tpu.ops import lora as L
from pytorch_zappa_serverless_tpu.serving.adapters import (
    ACTIVE, COLD, AdapterCold, AdapterManager, UnknownAdapter)
from pytorch_zappa_serverless_tpu.serving.server import create_app

pytest_plugins = "aiohttp.pytest_plugin"

TINY_ARCH = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 64,
             "vocab_size": 300, "max_positions": 64}


def _tiny_cfg():
    return dataclasses.replace(G.SMALL, **TINY_ARCH, eos_id=299)


DIMS = {"q": (32, 32), "v": (32, 32)}


# ---------------------------------------------------------------------------
# Kernel: batched multi-adapter parity + base passthrough
# ---------------------------------------------------------------------------

def _stacks(n_adapters=2, rank=4, layers=2):
    stacks = {f"layer{i}": L.zero_stacks(n_adapters + 1, rank, DIMS)
              for i in range(layers)}
    for slot in range(1, n_adapters + 1):
        L.install_adapter(stacks, slot,
                          W.init_lora(layers, DIMS, rank, seed=slot),
                          scaling=1.0 + slot)
    return stacks


def test_lora_batched_equals_sequential():
    """N adapters co-batched in ONE dispatch == N sequential single-adapter
    calls, bitwise (the acceptance parity contract)."""
    stacks = _stacks(3)
    node = jax.tree.map(jnp.asarray, stacks["layer0"]["q"])
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((6, 32)).astype(np.float32))
    y = x * 0.5
    idx = jnp.asarray([1, 3, 0, 2, 1, 0], jnp.int32)
    batched = np.asarray(L.lora_apply(y, x, node, idx))
    seq = np.concatenate([
        np.asarray(L.lora_apply(y[i:i + 1], x[i:i + 1], node, idx[i:i + 1]))
        for i in range(6)])
    np.testing.assert_array_equal(batched, seq)
    # 3-D (batch, positions, features) path too — the prefill shape.
    x3 = x.reshape(2, 3, 32)
    y3 = y.reshape(2, 3, 32)
    i3 = jnp.asarray([2, 0], jnp.int32)
    b3 = np.asarray(L.lora_apply(y3, x3, node, i3))
    s3 = np.concatenate([
        np.asarray(L.lora_apply(y3[i:i + 1], x3[i:i + 1], node, i3[i:i + 1]))
        for i in range(2)])
    np.testing.assert_array_equal(b3, s3)


def test_lora_slot0_passthrough_byte_identical():
    """Rows at slot 0 (no adapter) come back UNSELECTED — byte-identical
    base output, and a whole-batch slot-0 ``generate`` matches a plain
    adapter-less tree bit-for-bit."""
    stacks = _stacks(2)
    node = jax.tree.map(jnp.asarray, stacks["layer0"]["v"])
    x = jnp.asarray(np.random.default_rng(1)
                    .standard_normal((4, 32)).astype(np.float32))
    y = x @ x.T @ x  # arbitrary base output incl. negative zeros territory
    out = np.asarray(L.lora_apply(y, x, node,
                                  jnp.zeros((4,), jnp.int32)))
    np.testing.assert_array_equal(out, np.asarray(y))

    cfg = _tiny_cfg()
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(0, cfg))
    with_stacks = dict(params)
    with_stacks["__adapters__"] = jax.tree.map(jnp.asarray, stacks)
    toks = jnp.asarray([[7, 8, 9, 0], [3, 4, 0, 0]], jnp.int32)
    lens = jnp.asarray([3, 2], jnp.int32)
    z, s = jnp.zeros((2,), jnp.float32), jnp.zeros((2,), jnp.int32)
    fam = G.family(cfg)
    base = np.asarray(D.generate(fam, params, toks, lens, z, s, 6,
                                 jnp.float32))
    thru = np.asarray(D.generate(fam, with_stacks, toks, lens, z, s, 6,
                                 jnp.float32,
                                 adapter_idx=jnp.zeros((2,), jnp.int32)))
    np.testing.assert_array_equal(base, thru)


def test_gpt2_cobatched_generate_matches_solo():
    """Mixed-adapter co-batched generate reproduces each row's solo run,
    and distinct adapters actually produce distinct continuations."""
    cfg = _tiny_cfg()
    params = dict(jax.tree.map(jnp.asarray, G.init_gpt2_params(2, cfg)))
    params["__adapters__"] = jax.tree.map(jnp.asarray, _stacks(2))
    toks = jnp.asarray(np.random.default_rng(3).integers(1, 290, (3, 5)),
                       jnp.int32)
    lens = jnp.asarray([5, 5, 5], jnp.int32)
    z, s = jnp.zeros((3,), jnp.float32), jnp.zeros((3,), jnp.int32)
    aidx = jnp.asarray([1, 2, 0], jnp.int32)
    fam = G.family(cfg)
    mixed = np.asarray(D.generate(fam, params, toks, lens, z, s, 8,
                                  jnp.float32, adapter_idx=aidx))
    for i in range(3):
        solo = np.asarray(D.generate(fam, params, toks[i:i + 1],
                                     lens[i:i + 1], z[:1], s[:1], 8,
                                     jnp.float32,
                                     adapter_idx=aidx[i:i + 1]))
        np.testing.assert_array_equal(mixed[i], solo[0])


# ---------------------------------------------------------------------------
# Weights: torch/PEFT conversion, native round trip, offline merge
# ---------------------------------------------------------------------------

def test_convert_lora_peft_keys_and_fused_c_attn():
    g = np.random.default_rng(0)
    r, D = 4, 32
    sd = {}
    for i in range(2):
        pre = f"base_model.model.transformer.h.{i}.attn.c_attn"
        sd[f"{pre}.lora_A.weight"] = g.standard_normal((r, D)).astype(
            np.float32)
        sd[f"{pre}.lora_B.weight"] = g.standard_normal((3 * D, r)).astype(
            np.float32)
    tree = W.convert_lora(sd)
    for i in range(2):
        layer = tree[f"layer{i}"]
        assert set(layer) == {"q", "k", "v"}
        a = layer["q"]["a"]
        assert a.shape == (D, r) and layer["q"]["b"].shape == (r, D)
        # Shared A, B split into thirds: delta_W rows partition exactly.
        full_b = sd[f"base_model.model.transformer.h.{i}.attn.c_attn"
                    ".lora_B.weight"]
        np.testing.assert_array_equal(layer["v"]["b"], full_b.T[:, 2 * D:])
    assert L.validate_adapter(tree, {"q": (D, D), "k": (D, D),
                                     "v": (D, D)}, 8) == r
    with pytest.raises(ValueError, match="rank"):
        L.validate_adapter(tree, {"q": (D, D), "k": (D, D), "v": (D, D)}, 2)
    with pytest.raises(ValueError, match="adapter_targets"):
        L.validate_adapter(tree, {"q": (D, D)}, 8)


def test_adapter_native_round_trip(tmp_path):
    tree = W.init_lora(2, DIMS, 4, seed=7)
    path = tmp_path / "t.tpu.safetensors"
    W.save_adapter(tree, path)
    back = W.import_adapter(path)
    for lname, layer in tree.items():
        for t, node in layer.items():
            np.testing.assert_array_equal(node["a"], back[lname][t]["a"])
            np.testing.assert_array_equal(node["b"], back[lname][t]["b"])


def test_merge_adapter_equals_runtime_delta():
    """Offline merge (W + A@B*s) == the runtime per-row delta at slot 1."""
    cfg = _tiny_cfg()
    params = G.init_gpt2_params(1, cfg)
    adapter = W.init_lora(cfg.layers, DIMS, 4, seed=9)
    merged = W.merge_adapter(params, adapter, scaling=0.5)
    k0 = np.asarray(params["layer0"]["q"]["kernel"])
    np.testing.assert_allclose(
        merged["layer0"]["q"]["kernel"],
        k0 + np.asarray(adapter["layer0"]["q"]["a"])
        @ np.asarray(adapter["layer0"]["q"]["b"]) * 0.5, rtol=1e-6)
    # Base untouched.
    np.testing.assert_array_equal(params["layer0"]["q"]["kernel"], k0)


# ---------------------------------------------------------------------------
# Unit: residency state machine against a fake engine
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, s):
        self.now += s


def _adapter_cfg(tmp_path, n=3, slots=2, **kw):
    base = dict(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", dtype="float32", batch_buckets=(1, 4),
            seq_buckets=(8,), coalesce_ms=20.0,
            adapter_slots=slots, adapter_rank=4,
            adapters={f"t{i}": {"seed": i + 1, "tenants": [f"tenant-{i}"]}
                      for i in range(n)},
            extra={"max_new_tokens": 4, "arch": TINY_ARCH})])
    base.update(kw)
    return ServeConfig(**base)


def _fake_stack(tmp_path, **cfg_kw):
    """(manager, fake server, clock) over a REAL tiny gpt2 servable (the
    stacks must exist and device_put must work) and a fake runner ledger."""
    from types import SimpleNamespace

    cfg = _adapter_cfg(tmp_path, **cfg_kw)
    servable = G.make_gpt2_servable("gpt2", cfg.models[0])

    class FakeRunner:
        def __init__(self):
            from pytorch_zappa_serverless_tpu.faults import FaultInjector

            self.faults = FaultInjector()
            self._resident = {"gpt2": servable_nbytes}

        def track_model(self, name, nbytes):
            self._resident[name] = int(nbytes)

        def untrack_model(self, name):
            self._resident.pop(name, None)

        def resident_bytes(self):
            return dict(self._resident)

    servable_nbytes = 1000
    cm = SimpleNamespace(servable=servable, lockstep=None)
    runner = FakeRunner()
    engine = SimpleNamespace(models={"gpt2": cm}, runner=runner)
    server = SimpleNamespace(cfg=cfg, engine=engine, tracer=None)
    clock = _FakeClock()
    mgr = AdapterManager(server, cfg, clock=clock)
    return mgr, server, clock


def test_single_flight_attach_and_resolution(tmp_path):
    async def scenario():
        mgr, server, clock = _fake_stack(tmp_path)
        slots = await asyncio.gather(*[
            mgr.ensure_attached("gpt2", "t0") for _ in range(8)])
        rec = mgr.get("gpt2", "t0")
        assert rec.state == ACTIVE and rec.attaches == 1
        assert all(s == slots[0] for s in slots)
        assert server.engine.runner.resident_bytes()["gpt2:t0"] > 0
        # Resolution: explicit name, tenant indirection, unknowns.
        assert mgr.resolve("gpt2", "t1", None).name == "t1"
        assert mgr.resolve("gpt2", None, "tenant-2").name == "t2"
        assert mgr.resolve("gpt2", None, None) is None
        with pytest.raises(UnknownAdapter):
            mgr.resolve("gpt2", "nope", None)
        with pytest.raises(UnknownAdapter):
            mgr.resolve("gpt2", None, "stranger")
    asyncio.run(scenario())


def test_deadline_infeasible_attach_fast_fails(tmp_path):
    async def scenario():
        mgr, server, clock = _fake_stack(tmp_path)
        # Prior (500 ms) dwarfs a 5 ms deadline: AdapterCold, attach keeps
        # warming in the background (single-flight).
        with pytest.raises(AdapterCold) as ei:
            await mgr.ensure_attached("gpt2", "t0", deadline_ms=5.0)
        assert ei.value.estimated_attach_ms == 500.0
        assert ei.value.retry_after_s >= 1.0
        assert mgr.get("gpt2", "t0").cold_fast_fails == 1
        await mgr.ensure_attached("gpt2", "t0")
        assert mgr.get("gpt2", "t0").attaches == 1  # shared, not doubled
        # Learned history now rules: the same deadline is admitted warm,
        # and stays feasible after a detach (median attach ms << 5000).
        await mgr.ensure_attached("gpt2", "t0", deadline_ms=5000.0)
    asyncio.run(scenario())


def test_idle_detach_and_lru_slot_eviction(tmp_path):
    async def scenario():
        mgr, server, clock = _fake_stack(tmp_path, adapter_idle_unload_s=10.0)
        await mgr.ensure_attached("gpt2", "t0")
        clock.advance(1)
        await mgr.ensure_attached("gpt2", "t1")
        # Busy adapters never idle-detach.
        rec0 = mgr.get("gpt2", "t0")
        mgr.enter(rec0)
        clock.advance(50)
        await mgr.tick_once()
        assert rec0.state == ACTIVE
        assert mgr.get("gpt2", "t1").state == COLD  # t1 idled out
        assert "gpt2:t1" not in server.engine.runner.resident_bytes()
        mgr.exit(rec0)
        clock.advance(50)
        await mgr.tick_once()
        assert rec0.state == COLD

        # 2 slots, 3 tenants: the LRU idle tenant is evicted to make room.
        await mgr.ensure_attached("gpt2", "t0")
        clock.advance(1)
        await mgr.ensure_attached("gpt2", "t1")
        clock.advance(1)
        await mgr.ensure_attached("gpt2", "t2")
        assert mgr.get("gpt2", "t0").state == COLD
        assert mgr.get("gpt2", "t1").state == ACTIVE
        assert mgr.get("gpt2", "t2").state == ACTIVE
        assert (mgr.get("gpt2", "t2").slot
                != mgr.get("gpt2", "t1").slot)  # distinct live slots
    asyncio.run(scenario())


def test_hbm_budget_sheds_adapter_bytes(tmp_path):
    """Adapter bytes land in the runner ledger and the budget loop sheds
    them LRU-first — the acceptance criterion's bounded-by-budget half."""
    async def scenario():
        mgr, server, clock = _fake_stack(tmp_path)
        await mgr.ensure_attached("gpt2", "t0")
        nbytes = mgr.get("gpt2", "t0").nbytes
        assert nbytes > 0
        assert server.engine.runner.resident_bytes()["gpt2:t0"] == nbytes
        clock.advance(1)
        await mgr.ensure_attached("gpt2", "t1")
        # Budget admits base + ~1.5 adapters: t0 (LRU) must shed.
        server.cfg.hbm_budget_bytes = 1000 + nbytes + nbytes // 2
        await mgr.tick_once()
        resident = server.engine.runner.resident_bytes()
        assert "gpt2:t0" not in resident
        assert resident["gpt2:t1"] == nbytes
        assert sum(resident.values()) <= server.cfg.hbm_budget_bytes
        assert mgr.get("gpt2", "t0").state == COLD
        assert mgr.get("gpt2", "t1").state == ACTIVE
    asyncio.run(scenario())


def test_adapter_fault_rule_targets_attach_only(tmp_path):
    """faults.py kind="adapter": fires on on_adapter (keyed base:name or
    base-wide), never on dispatch, and coexists with dispatch rules."""
    from pytorch_zappa_serverless_tpu.faults import FaultInjector

    inj = FaultInjector()
    inj.configure(model="gpt2:t0", fail_every_n=1, count=1, kind="adapter")
    inj.configure(model="gpt2", fail_every_n=1, count=1, kind="transient")
    assert len(inj.snapshot()["rules"]) == 2
    with pytest.raises(RuntimeError, match="adapter"):
        inj.on_adapter("gpt2:t0")
    assert inj.injected["adapter"] == 1
    inj.on_adapter("gpt2:t0")   # count spent: inert
    inj.on_adapter("gpt2:t1")   # different tenant: never matched
    inj.on_dispatch("gpt2:t0")  # adapter rules never fire on dispatch
    # Base-wide adapter rule faults EVERY tenant's attach.
    inj.configure(model="gpt2", fail_every_n=1, count=2, kind="adapter")
    with pytest.raises(RuntimeError):
        inj.on_adapter("gpt2:t1")
    with pytest.raises(RuntimeError):
        inj.on_adapter("gpt2:t2")


# ---------------------------------------------------------------------------
# HTTP: the real serving stack
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("xla-adapters")


def _http_cfg(cache_dir, **kw):
    base = dict(
        compile_cache_dir=str(cache_dir), warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", dtype="float32", batch_buckets=(1, 2, 4),
            seq_buckets=(8,), coalesce_ms=25.0,
            adapter_slots=2, adapter_rank=4,
            # Random-init dev adapters on a random-init tiny base need a
            # large alpha before a rank-4 delta can move a greedy argmax
            # (measured: the token chains separate from alpha ~128).
            adapters={"tenant-a": {"seed": 1, "alpha": 128,
                                   "tenants": ["alice"]},
                      "tenant-b": {"seed": 2, "alpha": 128}},
            extra={"max_new_tokens": 4, "arch": TINY_ARCH,
                   "gen_slots": 2, "segment_tokens": 2})])
    base.update(kw)
    return ServeConfig(**base)


async def _predict(client, adapter=None, headers=None, ids=(5, 6, 7),
                   seed=0):
    h = dict(headers or {})
    if adapter:
        h["X-Adapter"] = adapter
    return await client.post("/v1/models/gpt2:predict",
                             json={"input_ids": list(ids), "seed": seed},
                             headers=h)


async def test_two_tenants_cobatch_one_dispatch(aiohttp_client, cache_dir):
    """The acceptance core: two tenants' adapters on ONE resident base
    serve concurrently from a single co-batched dispatch — proven by
    batch_mates trace linking + the adapter-mix annotation — and each
    tenant's output equals their solo run (and differs from base)."""
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    # Solo reference runs (also attach both adapters + warm the b=1 path).
    r = await _predict(client)
    assert r.status == 200, await r.text()
    base_toks = (await r.json())["predictions"]["tokens"]
    solo = {}
    for name in ("tenant-a", "tenant-b"):
        r = await _predict(client, adapter=name)
        assert r.status == 200, await r.text()
        assert r.headers["X-Adapter"] == name
        solo[name] = (await r.json())["predictions"]["tokens"]
    assert solo["tenant-a"] != solo["tenant-b"]
    assert solo["tenant-a"] != base_toks

    # Concurrent burst: both tenants inside one coalescing window.
    ra, rb = await asyncio.gather(_predict(client, adapter="tenant-a"),
                                  _predict(client, adapter="tenant-b"))
    assert ra.status == 200 and rb.status == 200
    ba, bb = await ra.json(), await rb.json()
    assert ba["predictions"]["tokens"] == solo["tenant-a"]
    assert bb["predictions"]["tokens"] == solo["tenant-b"]
    ta = ra.headers["X-Trace-Id"]
    tb = rb.headers["X-Trace-Id"]

    # Batch evidence: trace A's device span links trace B as a co-batched
    # mate, and the dispatch's head span names BOTH adapters (the
    # batcher's adapter-mix annotation rides one of the two trees).
    def spans(node):
        yield node
        for c in node.get("children", []):
            yield from spans(c)

    linked = mixed = False
    trees = []
    for tid in (ta, tb):
        r = await client.get(f"/admin/trace/{tid}")
        trees.append((await r.json())["trace"])
    for tree, mate in zip(trees, (tb, ta)):
        for sp in spans(tree["tree"]):
            attrs = sp.get("attrs", {})
            if mate in (attrs.get("batch_mates") or []):
                linked = True
            if set(attrs.get("adapters") or ()) == {"tenant-a", "tenant-b"}:
                mixed = True
    assert linked and mixed, trees

    # Counter evidence + per-tenant QoS rings on /metrics.
    r = await client.get("/metrics")
    m = await r.json()
    assert m["adapters"]["multi_adapter_batches"] >= 1
    assert m["models"]["gpt2:tenant-a"]["requests"] >= 2
    assert m["adapters"]["models"]["gpt2"]["tenant-a"]["served"] >= 2


async def test_idle_detach_cold_503_and_reattach(aiohttp_client, cache_dir):
    """Per-tenant scale-to-zero over HTTP: the idle adapter detaches (HBM
    ledger entry gone), a deadline-infeasible cold hit 503s
    ``adapter_cold`` + Retry-After, and a patient request re-attaches."""
    cfg = _http_cfg(cache_dir, adapter_idle_unload_s=0.15,
                    adapter_attach_estimate_ms=800.0)
    client = await aiohttp_client(create_app(cfg))
    r = await _predict(client, adapter="tenant-a")
    assert r.status == 200, await r.text()
    r = await client.get("/metrics")
    by_model = (await r.json())["hbm"]["by_model"]
    assert by_model.get("gpt2:tenant-a", 0) > 0  # adapter bytes in ledger

    for _ in range(100):  # idle reaper: ~0.15 s + tick cadence
        r = await client.get("/admin/adapters")
        snap = await r.json()
        if snap["models"]["gpt2"]["tenant-a"]["state"] == "cold":
            break
        await asyncio.sleep(0.05)
    else:
        pytest.fail("idle adapter never detached")
    r = await client.get("/metrics")
    assert "gpt2:tenant-a" not in (await r.json())["hbm"]["by_model"]

    # Cold + tight deadline: 503 adapter_cold with the retry contract.
    r = await _predict(client, adapter="tenant-b",
                       headers={"X-Deadline-Ms": "100"})
    body = await r.json()
    assert r.status == 503, body
    assert body["adapter_cold"] is True and body["adapter"] == "tenant-b"
    assert body["estimated_attach_ms"] > 100
    assert int(r.headers["Retry-After"]) >= 1
    assert body["request_id"] and body["trace_id"]

    # Patient request: re-attach on demand, then serve.
    r = await _predict(client, adapter="tenant-a")
    assert r.status == 200, await r.text()
    r = await client.get("/admin/adapters")
    snap = await r.json()
    assert snap["models"]["gpt2"]["tenant-a"]["state"] == "active"
    assert snap["models"]["gpt2"]["tenant-a"]["attaches"] >= 2


async def test_adapter_chaos_one_tenant_poisoned(aiohttp_client, cache_dir):
    """kind="adapter" chaos scenario: tenant-b's attach is poisoned — its
    requests 503 with Retry-After — while the base model and tenant-a keep
    serving; clearing the rule heals tenant-b on the next demand."""
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await client.post("/admin/faults",
                          json={"model": "gpt2:tenant-b", "fail_every_n": 1,
                                "kind": "adapter"})
    assert r.status == 200, await r.text()
    r = await _predict(client, adapter="tenant-b")
    body = await r.json()
    assert r.status == 503 and body.get("adapter_attach_failed"), body
    assert "Retry-After" in r.headers
    # Other tenants and the base keep serving through the poisoned attach.
    r = await _predict(client, adapter="tenant-a")
    assert r.status == 200, await r.text()
    r = await _predict(client)
    assert r.status == 200, await r.text()
    r = await client.get("/admin/adapters")
    assert (await r.json())["models"]["gpt2"]["tenant-b"]["state"] == "cold"
    # Heal: clear the rule, next demand attaches.
    r = await client.post("/admin/faults", json={"clear": True,
                                                 "model": "gpt2:tenant-b"})
    assert r.status == 200
    r = await _predict(client, adapter="tenant-b")
    assert r.status == 200, await r.text()


async def test_unknown_adapter_404_enumerates_ladder(aiohttp_client,
                                                     cache_dir):
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    for kwargs in ({"adapter": "nope"},
                   {"headers": {"X-Tenant": "stranger"}}):
        r = await _predict(client, **kwargs)
        body = await r.json()
        assert r.status == 404, body
        assert body["model"] == "gpt2"
        assert set(body["adapters"]) == {"tenant-a", "tenant-b"}
        assert body["adapters"]["tenant-a"]["tenants"] == ["alice"]
        assert "residency" in body["adapters"]["tenant-a"]
        assert body["request_id"] and body["trace_id"]
    # Body-field resolution + tenant indirection serve normally.
    r = await client.post("/v1/models/gpt2:predict",
                          json={"input_ids": [5, 6], "adapter": "tenant-a"})
    assert r.status == 200, await r.text()
    r = await _predict(client, headers={"X-Tenant": "alice"})
    assert r.status == 200, await r.text()
    assert r.headers["X-Adapter"] == "tenant-a"


async def test_discovery_lists_adapters(aiohttp_client, cache_dir):
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await client.get("/v1/models")
    models = (await r.json())["models"]
    assert models["gpt2"]["adapters"] == {"tenant-a": "cold",
                                          "tenant-b": "cold"}
    r = await _predict(client, adapter="tenant-a")
    assert r.status == 200
    r = await client.get("/v1/models")
    assert (await r.json())["models"]["gpt2"]["adapters"]["tenant-a"] \
        == "active"
    # /admin/models carries the same map (the fleet routing signal).
    r = await client.get("/admin/models/gpt2")
    assert (await r.json())["model"]["adapters"]["tenant-a"] == "active"


async def test_adapter_jobs_keyed_by_model_adapter(aiohttp_client,
                                                   cache_dir):
    """:submit with an adapter: instant 202 ack naming the tenant, the job
    worker attaches (cause="job") and the result matches the sync lane."""
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await _predict(client, adapter="tenant-a", ids=(9, 10, 11))
    want = (await r.json())["predictions"]["tokens"]
    r = await client.post("/v1/models/gpt2:submit",
                          json={"input_ids": [9, 10, 11],
                                "adapter": "tenant-a"})
    assert r.status == 202, await r.text()
    ack = await r.json()
    assert ack["adapter"] == "tenant-a"
    job_id = ack["job"]["id"]
    for _ in range(200):
        job = (await (await client.get(f"/v1/jobs/{job_id}")).json())["job"]
        if job["status"] in ("done", "error"):
            break
        await asyncio.sleep(0.05)
    assert job["status"] == "done", job
    assert job["result"]["tokens"] == want


async def test_paged_generate_per_stream_adapter(aiohttp_client, cache_dir):
    """kv_cache="paged" :generate with a per-stream adapter index: the
    adapter stream's tokens equal the fixed-batch lane's (the co-decode
    kernels gather the same slot), and the slot lane declines loudly."""
    cfg = _http_cfg(cache_dir)
    cfg.models[0].kv_cache = "paged"
    client = await aiohttp_client(create_app(cfg))
    r = await _predict(client, adapter="tenant-a", ids=(4, 5, 6))
    want = (await r.json())["predictions"]["tokens"]
    r = await client.post("/v1/models/gpt2:generate",
                          json={"input_ids": [4, 5, 6], "stream": False,
                                "max_new_tokens": 4},
                          headers={"X-Adapter": "tenant-a"})
    assert r.status == 200, await r.text()
    assert r.headers["X-Adapter"] == "tenant-a"
    got = (await r.json())["predictions"]["tokens"]
    assert got == want
    # Base stream co-decodes beside it unchanged.
    rb = await client.post("/v1/models/gpt2:generate",
                           json={"input_ids": [4, 5, 6], "stream": False,
                                 "max_new_tokens": 4})
    base_gen = (await rb.json())["predictions"]["tokens"]
    assert base_gen != got

    # Slot pool: adapter-addressed generation declines loudly.
    slot_client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await slot_client.post("/v1/models/gpt2:generate",
                               json={"input_ids": [4, 5], "stream": False},
                               headers={"X-Adapter": "tenant-a"})
    body = await r.json()
    assert r.status == 400 and "paged" in body["error"], body


async def test_adapter_metrics_families_and_manifest(aiohttp_client,
                                                     cache_dir):
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await _predict(client, adapter="tenant-a")
    assert r.status == 200
    r = await client.get("/metrics", params={"format": "prometheus"})
    text = await r.text()
    assert ('tpuserve_adapter_residency{adapter="tenant-a",model="gpt2"} 2'
            in text)
    assert ('tpuserve_adapter_served_total{adapter="tenant-a",'
            'model="gpt2"}' in text)
    assert "tpuserve_adapter_attach_ms_bucket" in text
    assert "tpuserve_adapter_multi_batches_total" in text
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "tools" / "check_metrics.py"
    spec = importlib.util.spec_from_file_location("tpuserve_cm_ad", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    problems = mod.check(text, mod.load_manifest())
    assert not problems, problems


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_adapters_cli_table():
    from pytorch_zappa_serverless_tpu import cli

    payload = {
        "multi_adapter_batches": 3,
        "models": {"gpt2": {
            "tenant-a": {"state": "active", "slot": 1,
                         "tenants": ["alice"], "hbm_bytes": 4096,
                         "last_used_s_ago": 0.5, "attaches": 2,
                         "served": 7, "estimated_attach_ms": 3.0},
            "tenant-b": {"state": "cold", "slot": None, "tenants": [],
                         "hbm_bytes": 0, "last_used_s_ago": 60.0,
                         "attaches": 1, "served": 2,
                         "estimated_attach_ms": 500.0}}}}
    table = cli.format_adapters_table(payload)
    lines = table.splitlines()
    assert lines[0].split()[:4] == ["MODEL", "ADAPTER", "STATE", "SLOT"]
    assert any("tenant-a" in l and "active" in l and "alice" in l
               for l in lines)
    assert any("tenant-b" in l and "cold" in l for l in lines)
    assert ">1 adapter: 3" in lines[-1]
