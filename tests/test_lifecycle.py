"""Serverless model lifecycle (serving/lifecycle.py; docs/LIFECYCLE.md).

Unit half: the residency state machine against a fake engine/builder —
single-flight activation, deadline-aware cold admission, idle scale-to-zero
through the warm tiers, LRU-under-budget eviction, PIN semantics, busy
protection, activation chaos.  HTTP half: the real serving stack with a lazy
ResNet-18 — cold 503 fast-fail, unload/reactivate with zero acknowledged
loss, the /admin/models surface with each tier's activation record, the
residency metrics, and the ``tpuserve models`` CLI.
"""

import asyncio
import io
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest
from PIL import Image

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.cache import CompileClock
from pytorch_zappa_serverless_tpu.faults import FaultInjector, TransientFault
from pytorch_zappa_serverless_tpu.serving.lifecycle import (
    ACTIVE, COLD, ColdStart, LifecycleManager)
from pytorch_zappa_serverless_tpu.serving.server import Server, create_app

pytest_plugins = "aiohttp.pytest_plugin"


# -- fakes for the unit half --------------------------------------------------

class FakeRunner:
    def __init__(self):
        self.faults = FaultInjector()
        self._resident = {}

    def track_model(self, name, nbytes):
        self._resident[name] = int(nbytes)

    def untrack_model(self, name):
        self._resident.pop(name, None)

    def resident_bytes(self):
        return dict(self._resident)


class FakeCM:
    def __init__(self, nbytes=100):
        self.nbytes = nbytes
        self.mesh = None
        self.lockstep = None
        self.offloads = 0
        self.restores = 0

    def param_nbytes(self):
        return self.nbytes

    def host_offload(self):
        self.offloads += 1

    def device_restore(self):
        self.restores += 1


class FakeEngine:
    def __init__(self):
        self.models = {}
        self.runner = FakeRunner()
        self.clock = CompileClock()
        self.build_seconds = {}
        self.mesh = None

    def attach(self, name, cm, nbytes=None):
        self.models[name] = cm
        self.runner.track_model(
            name, cm.param_nbytes() if nbytes is None else nbytes)

    def detach(self, name):
        self.runner.untrack_model(name)
        return self.models.pop(name, None)

    def model(self, name):
        return self.models[name]


class FakeServer:
    def __init__(self, cfg):
        self.cfg = cfg
        self.engine = FakeEngine()
        self.tracer = None
        self.batchers = {}
        self.schedulers = {}
        self.jobs = None
        self.resilience = SimpleNamespace(quarantined=set())
        self.lanes_started = []
        self.lanes_stopped = []

    def _start_model_lanes(self, name):
        self.lanes_started.append(name)

    async def _stop_model_lanes(self, name):
        self.lanes_stopped.append(name)


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, s):
        self.now += s


def _unit_cfg(tmp_path, names=("m",), **kw):
    base = dict(compile_cache_dir=str(tmp_path / "empty-cache"),
                models=[ModelConfig(name=n) for n in names])
    base.update(kw)
    return ServeConfig(**base)


def _mgr(tmp_path, names=("m",), builds=None, delay=0.0, nbytes=100,
         fail_first=False, **cfg_kw):
    """(manager, server, clock, builds-counter) against the fake stack."""
    cfg = _unit_cfg(tmp_path, names, **cfg_kw)
    server = FakeServer(cfg)
    clock = FakeClock()
    builds = builds if builds is not None else {}
    failed = {}

    def build(name, from_tier, host_cm, root):
        if delay:
            time.sleep(delay)
        builds[name] = builds.get(name, 0) + 1
        if fail_first and not failed.get(name):
            failed[name] = True
            raise RuntimeError("injected build failure")
        if from_tier == "host" and host_cm is not None:
            host_cm.device_restore()
            return host_cm
        return FakeCM(nbytes)

    mgr = LifecycleManager(server, cfg, build_fn=build, clock=clock)
    return mgr, server, clock, builds


# -- unit: state machine ------------------------------------------------------

def test_idle_cycle_through_warm_tiers(tmp_path):
    """ACTIVE → (idle) host tier → (more idle) compiled-cache-only, with
    re-activation cost tiered: host restore reuses the SAME CompiledModel."""
    async def scenario():
        mgr, server, clock, builds = _mgr(
            tmp_path, idle_unload_s=10.0, host_idle_drop_s=30.0)
        cm1 = await mgr.ensure_active("m")
        res = mgr.residency("m")
        assert res.state == ACTIVE and res.tier == "device"
        assert server.lanes_started == ["m"] and builds["m"] == 1
        assert server.engine.runner.resident_bytes() == {"m": 100}

        clock.advance(11)
        await mgr.tick_once()
        assert res.state == COLD and res.tier == "host"
        assert cm1.offloads == 1 and server.lanes_stopped == ["m"]
        assert server.engine.runner.resident_bytes() == {}

        cm2 = await mgr.ensure_active("m")
        assert cm2 is cm1 and cm1.restores == 1  # host tier: restore, no build
        assert res.state == ACTIVE and builds["m"] == 2

        clock.advance(11)
        await mgr.tick_once()           # active → host again
        assert res.tier == "host"
        clock.advance(35)
        await mgr.tick_once()           # host → compiled-cache-only
        assert res.tier == "none" and res.cm_host is None

        cm3 = await mgr.ensure_active("m")
        assert cm3 is not cm1           # full rebuild from the cold tier
        assert res.state == ACTIVE
    asyncio.run(scenario())


def test_single_flight_activation(tmp_path):
    """N concurrent cold requests share ONE activation (the acceptance
    check): one build, one lane start, identical CompiledModel back."""
    async def scenario():
        mgr, server, clock, builds = _mgr(tmp_path, delay=0.05)
        got = await asyncio.gather(
            *[mgr.ensure_active("m", cause="request") for _ in range(10)])
        assert builds == {"m": 1}
        assert all(g is got[0] for g in got)
        assert server.lanes_started == ["m"]
        assert mgr.activations_by_cause["m"] == {"request": 1}
    asyncio.run(scenario())


def test_deadline_aware_cold_admission(tmp_path):
    """A deadline below the activation estimate fast-fails ColdStart (503
    cold_start upstream) while the single-flight activation keeps warming;
    a deadline-less caller then finds it active with ONE total build."""
    async def scenario():
        mgr, server, clock, builds = _mgr(
            tmp_path, activation_estimate_ms=5000.0)
        est = mgr.estimate_warm_ms("m")
        assert est == 5000.0  # empty cache dir: the full prior
        with pytest.raises(ColdStart) as ei:
            await mgr.ensure_active("m", deadline_ms=10.0)
        assert ei.value.estimated_warm_ms == 5000.0
        assert ei.value.retry_after_s >= 1.0
        assert mgr.residency("m").cold_fast_fails == 1
        # The fast-fail started the activation anyway — demand is warmup.
        await mgr.ensure_active("m")
        assert builds == {"m": 1}
        assert mgr.residency("m").state == ACTIVE
        # Warm model + the same tight deadline: admitted without a blink.
        await mgr.ensure_active("m", deadline_ms=10.0)
    asyncio.run(scenario())


def test_estimate_reads_a_generation_lanes_first_uses(tmp_path):
    """A model whose generation programs have run is no longer estimated at
    the configured prior: the ledger the lanes write (engine/cache.py) is the
    history ``estimate_warm_ms`` reads, whichever lane wrote it."""
    mgr, server, _, _ = _mgr(tmp_path, activation_estimate_ms=5000.0)
    assert mgr.estimate_warm_ms("m") == 5000.0
    clock, seen = server.engine.clock, set()
    for program, launch, run in (("prefill", 1.5, 0.25), ("segment", 2.0,
                                                           None)):
        clock.open("m", program, {}, seen=seen, round=1).entry.update(
            launch_s=launch, first_run_s=run)
    assert clock.per_model()["m"] == {"entries": 2, "seconds": 3.75}
    assert mgr.estimate_warm_ms("m") == 3750.0 + 500.0


def test_lru_eviction_respects_budget_and_pinned(tmp_path):
    """hbm_budget_bytes evicts LRU-first, never PINNED, never the model
    whose activation triggered enforcement; all-pinned stays over budget."""
    async def scenario():
        mgr, server, clock, builds = _mgr(
            tmp_path, names=("a", "b", "c"), hbm_budget_bytes=250)
        await mgr.ensure_active("a")
        await mgr.pin("a")
        clock.advance(1)
        await mgr.ensure_active("b")
        clock.advance(1)
        await mgr.ensure_active("c")  # 300 bytes resident > 250 budget
        resident = server.engine.runner.resident_bytes()
        # LRU non-pinned victim is b: a is PINNED, c just activated.
        assert set(resident) == {"a", "c"}
        assert mgr.residency("b").state == COLD
        assert mgr.residency("b").tier == "host"
        assert mgr.residency("a").state == ACTIVE
        assert mgr.residency("c").state == ACTIVE

        # Pin c too: now nothing can evict — the budget stays exceeded
        # rather than evicting PINNED or the fresh activation.
        await mgr.pin("c")
        clock.advance(1)
        await mgr.ensure_active("b")
        assert set(server.engine.runner.resident_bytes()) == {"a", "b", "c"}
        assert all(mgr.residency(n).state == ACTIVE for n in "abc")
    asyncio.run(scenario())


def test_pin_semantics(tmp_path):
    """pin activates a COLD model and exempts it from idle unload; unpin
    re-arms the reaper."""
    async def scenario():
        mgr, server, clock, builds = _mgr(tmp_path, idle_unload_s=5.0)
        await mgr.pin("m")
        res = mgr.residency("m")
        assert res.state == ACTIVE and res.pinned
        assert mgr.activations_by_cause["m"] == {"pin": 1}
        assert mgr.state_code("m") == 4  # PINNED on the residency gauge
        clock.advance(60)
        await mgr.tick_once()
        assert res.state == ACTIVE  # pinned: idle reaper must not touch it
        mgr.unpin("m")
        await mgr.tick_once()
        assert res.state == COLD and res.tier == "host"
    asyncio.run(scenario())


def test_busy_model_never_demoted(tmp_path):
    """The in-flight guard (enter/exit) blocks idle demotion and explicit
    unload until the handler window closes."""
    async def scenario():
        mgr, server, clock, builds = _mgr(tmp_path, idle_unload_s=5.0)
        await mgr.ensure_active("m")
        mgr.enter("m")
        clock.advance(60)
        await mgr.tick_once()
        assert mgr.residency("m").state == ACTIVE
        assert not await mgr.unload("m")     # busy: refuse, 409 upstream
        mgr.exit("m")
        clock.advance(60)                    # exit() touched the LRU clock
        await mgr.tick_once()
        assert mgr.residency("m").state == COLD
    asyncio.run(scenario())


def test_activation_failure_returns_to_cold_and_retries(tmp_path):
    async def scenario():
        mgr, server, clock, builds = _mgr(tmp_path, fail_first=True)
        with pytest.raises(RuntimeError, match="injected build failure"):
            await mgr.ensure_active("m")
        res = mgr.residency("m")
        assert res.state == COLD and res.activations == 0
        await mgr.ensure_active("m")         # next demand retries the build
        assert res.state == ACTIVE and builds["m"] == 2
    asyncio.run(scenario())


def test_activation_fault_rule_targets_activation_only():
    """faults.py kind="activation": fires on on_activation, never on
    dispatch, and coexists with a dispatch rule for the same model."""
    inj = FaultInjector()
    inj.configure(model="m", fail_every_n=1, count=1, kind="activation")
    inj.configure(model="m", fail_every_n=1, count=1, kind="transient")
    assert len(inj.snapshot()["rules"]) == 2  # distinct targets, no replace
    with pytest.raises(RuntimeError, match="activation"):
        inj.on_activation("m")
    assert inj.injected["activation"] == 1
    inj.on_activation("m")  # count=1 spent: inert
    with pytest.raises(TransientFault):
        inj.on_dispatch("m")  # the dispatch rule, not the activation one
    assert inj.injected["dispatch"] == 1 and inj.injected["activation"] == 1


def test_rebind_records_recovery_activations(tmp_path):
    """An engine swap re-syncs residency: swapped-in models count as
    cause="recovery" activations, missing ones return to COLD."""
    async def scenario():
        mgr, server, clock, builds = _mgr(tmp_path, names=("a", "b"))
        await mgr.ensure_active("a")
        await mgr.ensure_active("b")
        # Simulate a watchdog rebuild that only brought back "a".
        server.engine = FakeEngine()
        server.engine.attach("a", FakeCM())
        server.engine.build_seconds["a"] = 1.5
        mgr.rebind(cause="recovery")
        assert mgr.residency("a").state == ACTIVE
        assert mgr.activations_by_cause["a"]["recovery"] == 1
        assert mgr.residency("b").state == COLD
        assert mgr.residency("b").tier == "none"
    asyncio.run(scenario())


# -- HTTP: the real serving stack --------------------------------------------

@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    # Shared persistent compile cache: the first activation compiles, every
    # later test re-activates against the warm cache (fast).
    return tmp_path_factory.mktemp("xla-lifecycle")


def _http_cfg(cache_dir, **kw):
    base = dict(
        compile_cache_dir=str(cache_dir), warmup_at_boot=True,
        lazy_load=True, activation_max_wait_s=120.0,
        models=[ModelConfig(name="resnet18", batch_buckets=(1, 2),
                            dtype="float32", coalesce_ms=2.0,
                            extra={"image_size": 48, "resize_to": 56})])
    base.update(kw)
    return ServeConfig(**base)


def _jpeg(seed=0) -> bytes:
    arr = np.random.default_rng(seed).integers(
        0, 255, (60, 70, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG")
    return buf.getvalue()


_IMG_HEADERS = {"Content-Type": "image/jpeg"}


async def test_lazy_boot_first_request_activates(aiohttp_client, cache_dir):
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await client.get("/admin/models")
    snap = await r.json()
    assert r.status == 200
    assert snap["models"]["resnet18"]["state"] == "cold"
    assert snap["models"]["resnet18"]["tier"] == "none"
    assert snap["hbm_bytes_total"] == 0
    # Discovery + health list the COLD model and stay healthy.
    r = await client.get("/v1/models")
    assert (await r.json())["models"]["resnet18"]["residency"] == "cold"
    r = await client.get("/healthz")
    body = await r.json()
    assert r.status == 200 and body["residency"]["resnet18"] == "cold"

    # First request: on-demand activation, then a normal 200.
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(),
                          headers=_IMG_HEADERS)
    assert r.status == 200, await r.text()
    r = await client.get("/admin/models/resnet18")
    m = (await r.json())["model"]
    assert m["state"] == "active" and m["tier"] == "device"
    assert m["hbm_bytes"] > 0
    assert m["activations_by_cause"].get("request") == 1
    assert m["last_activation_ms"] > 0

    # Unload to zero, then N concurrent cold requests → ONE activation.
    r = await client.post("/admin/models/resnet18",
                          json={"action": "unload"})
    assert r.status == 200, await r.text()
    rs = await asyncio.gather(*[
        client.post("/v1/models/resnet18:predict", data=_jpeg(i),
                    headers=_IMG_HEADERS) for i in range(6)])
    assert [r.status for r in rs] == [200] * 6
    r = await client.get("/admin/models/resnet18")
    m = (await r.json())["model"]
    assert m["activations_by_cause"]["request"] == 2  # +1, not +6

    # Residency metrics on both surfaces, and the manifest lint stays green.
    r = await client.get("/metrics")
    mjson = await r.json()
    assert mjson["lifecycle"]["models"]["resnet18"]["state"] == "active"
    assert mjson["hbm"]["total_bytes"] > 0
    assert "resnet18" in mjson["cold_start"]["compile_by_model"]
    r = await client.get("/metrics", params={"format": "prometheus"})
    text = await r.text()
    assert 'tpuserve_residency_state{model="resnet18"} 2' in text
    assert 'tpuserve_activations_total{cause="request",model="resnet18"}' in text
    assert 'tpuserve_hbm_bytes{model="resnet18"}' in text
    assert 'tpuserve_compile_entries{model="resnet18"}' in text
    assert "tpuserve_activation_ms_bucket" in text
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "tools" / "check_metrics.py"
    spec = importlib.util.spec_from_file_location("tpuserve_cm", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    problems = mod.check(text, mod.load_manifest())
    assert not problems, problems


async def test_cold_fast_fail_503_with_retry_after(aiohttp_client, cache_dir,
                                                   tmp_path):
    # Empty cache dir + huge prior: the estimate always dwarfs the deadline.
    cfg = _http_cfg(tmp_path / "cold-cache",
                    activation_estimate_ms=600000.0)
    client = await aiohttp_client(create_app(cfg))
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(),
                          headers={**_IMG_HEADERS, "X-Deadline-Ms": "40"})
    body = await r.json()
    assert r.status == 503, body
    assert body["cold_start"] is True
    assert body["estimated_warm_ms"] > 40
    assert int(r.headers["Retry-After"]) >= 1
    assert body["request_id"] and body["trace_id"]
    # Demand started the single-flight warmup in the background: wait for
    # ACTIVE, then the same tight deadline is admitted.
    for _ in range(600):
        rs = await client.get("/admin/models/resnet18")
        if (await rs.json())["model"]["state"] == "active":
            break
        await asyncio.sleep(0.1)
    else:
        pytest.fail("background activation never finished")
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(1),
                          headers=_IMG_HEADERS)
    assert r.status == 200, await r.text()


async def test_unload_reactivate_zero_acked_loss(aiohttp_client, cache_dir):
    """The acceptance cycle: burst → unload raced against live work (409
    while busy) → drained unload → reactivation — every acknowledged
    request answered 200, none lost."""
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))

    async def one(i):
        r = await client.post("/v1/models/resnet18:predict", data=_jpeg(i),
                              headers=_IMG_HEADERS)
        return r.status

    async def try_unload():
        await asyncio.sleep(0.001)  # land inside the burst
        r = await client.post("/admin/models/resnet18",
                              json={"action": "unload"})
        return r.status

    results = await asyncio.gather(*[one(i) for i in range(8)], try_unload())
    statuses, unload_status = results[:-1], results[-1]
    assert statuses == [200] * 8          # zero acked-request loss
    assert unload_status in (200, 409)    # busy → refused, quiet → unloaded

    # Drained unload always succeeds, then the next request reactivates.
    for _ in range(100):
        r = await client.post("/admin/models/resnet18",
                              json={"action": "unload"})
        if r.status == 200:
            break
        await asyncio.sleep(0.05)
    assert r.status == 200
    r = await client.get("/admin/models/resnet18")
    assert (await r.json())["model"]["state"] == "cold"
    assert await one(99) == 200           # reactivated from the warm cache
    r = await client.get("/admin/models/resnet18")
    assert (await r.json())["model"]["state"] == "active"


async def test_activation_from_each_tier_leaves_its_record(aiohttp_client,
                                                           cache_dir):
    """The ladder through the admin API on the real stack: an activation from
    the compiled-cache-only tier and one from the host tier each leave their
    ``load_ms``/``compile_ms`` split on the model's row and their wall time in
    the history ``estimate_warm_ms`` reads; activating a model that is on the
    device already is no activation, and the restored model serves the bytes
    it served before."""
    srv = Server(_http_cfg(cache_dir))
    client = await aiohttp_client(srv.app)

    async def action(act):
        r = await client.post("/admin/models/resnet18", json={"action": act})
        body = await r.json()
        assert r.status == 200, (act, body)
        return body["model"]

    async def predict():
        r = await client.post("/v1/models/resnet18:predict", data=_jpeg(),
                              headers=_IMG_HEADERS)
        assert r.status == 200, await r.text()
        return (await r.json())["predictions"]

    m = await action("activate")                    # none -> device
    assert (m["state"], m["tier"], m["activations"]) == ("active", "device", 1)
    built = m["last_activation_phases"]
    assert built["tier"] == "none"
    assert built["load_ms"] > 0 and built["compile_ms"] > 0
    before = await predict()

    again = await action("activate")                # device: nothing to do
    assert again["activations"] == 1
    assert again["last_activation_phases"] == built
    m = await action("demote")                      # device -> host
    assert (m["state"], m["tier"], m["hbm_bytes"]) == ("cold", "host", 0)
    m = await action("activate")                    # host -> device
    assert (m["state"], m["tier"], m["activations"]) == ("active", "device", 2)
    restored = m["last_activation_phases"]
    assert restored["tier"] == "host"
    assert restored["load_ms"] > 0 and restored["compile_ms"] == 0.0
    assert await predict() == before

    history = srv.lifecycle.residency("resnet18").history
    assert {tier: len(ms) for tier, ms in history.items()} \
        == {"none": 1, "host": 1}


async def test_pin_blocks_unload_and_budget(aiohttp_client, cache_dir):
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await client.post("/admin/models/resnet18", json={"action": "pin"})
    m = (await r.json())["model"]
    assert r.status == 200 and m["state"] == "active" and m["pinned"]
    r = await client.post("/admin/models/resnet18", json={"action": "unload"})
    assert r.status == 409
    r = await client.post("/admin/models/resnet18", json={"action": "demote"})
    assert r.status == 409
    r = await client.post("/admin/models/resnet18", json={"action": "unpin"})
    assert r.status == 200
    r = await client.post("/admin/models/resnet18", json={"action": "unload"})
    assert r.status == 200
    r = await client.post("/admin/models/resnet18", json={"action": "nope"})
    assert r.status == 400
    r = await client.post("/admin/models/ghost", json={"action": "pin"})
    assert r.status == 404


async def test_submit_acks_cold_model_job_activates(aiohttp_client,
                                                    cache_dir):
    """:submit never blocks on activation: instant 202 while COLD, the job
    worker activates (cause="job") and finishes."""
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await client.get("/admin/models/resnet18")
    assert (await r.json())["model"]["state"] == "cold"
    r = await client.post("/v1/models/resnet18:submit", data=_jpeg(7),
                          headers=_IMG_HEADERS)
    assert r.status == 202
    job_id = (await r.json())["job"]["id"]
    for _ in range(600):
        job = (await (await client.get(f"/v1/jobs/{job_id}")).json())["job"]
        if job["status"] in ("done", "error"):
            break
        await asyncio.sleep(0.05)
    assert job["status"] == "done", job
    r = await client.get("/admin/models/resnet18")
    m = (await r.json())["model"]
    assert m["state"] == "active"
    assert m["activations_by_cause"].get("job") == 1


async def test_unknown_model_404_lists_residency(aiohttp_client, cache_dir):
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    for route in ("/v1/models/nope:predict", "/v1/models/nope:submit",
                  "/v1/models/nope:generate"):
        r = await client.post(route, data=b"x")
        body = await r.json()
        assert r.status == 404, body
        assert "available" in body["error"]
        assert body["models"] == {"resnet18": "cold"}
        assert body["request_id"] and body["trace_id"]


async def test_activation_chaos_fault(aiohttp_client, cache_dir):
    """kind="activation" chaos: the first cold request fails 503 with the
    injected error, the model returns to COLD, and the next demand (rule
    spent) activates — recovery-under-cold-start, tier-1."""
    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    r = await client.post("/admin/faults",
                          json={"model": "resnet18", "fail_every_n": 1,
                                "count": 1, "kind": "activation"})
    assert r.status == 200, await r.text()
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(3),
                          headers=_IMG_HEADERS)
    body = await r.json()
    assert r.status == 503 and body.get("activation_failed"), body
    assert "Retry-After" in r.headers
    r = await client.get("/admin/models/resnet18")
    assert (await r.json())["model"]["state"] == "cold"
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(4),
                          headers=_IMG_HEADERS)
    assert r.status == 200, await r.text()


# -- CLI ----------------------------------------------------------------------

def test_models_cli_table(monkeypatch, capsys):
    from pytorch_zappa_serverless_tpu import cli

    payload = {
        "hbm_budget_bytes": 2 * 1024 * 1024, "hbm_bytes_total": 1048576,
        "models": {
            "resnet18": {"state": "active", "tier": "device", "pinned": True,
                         "last_used_s_ago": 1.25, "activations": 3,
                         "last_activation_ms": 812.0,
                         "estimated_warm_ms": 400.0,
                         "hbm_bytes": 1048576},
            "gpt2": {"state": "cold", "tier": "host", "pinned": False,
                     "last_used_s_ago": 73.0, "activations": 1,
                     "estimated_warm_ms": 250.0, "hbm_bytes": 0}}}
    table = cli.format_models_table(payload)
    lines = table.splitlines()
    # Family-grouped ladder view (docs/VARIANTS.md): FAMILY + quality rank
    # lead, then the per-model residency columns.
    assert lines[0].split()[:5] == ["FAMILY", "Q", "MODEL", "STATE", "TIER"]
    assert any("resnet18" in l and "pinned" in l and "1.0" in l
               for l in lines)
    assert any("gpt2" in l and "cold" in l and "host" in l
               for l in lines)
    assert "2.0 MB budget" in lines[-1]

    class FakeResp:
        def __init__(self, data):
            self._data = data

        def read(self):
            return json.dumps(self._data).encode()

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    import urllib.request
    monkeypatch.setattr(urllib.request, "urlopen",
                        lambda req, timeout=10: FakeResp(payload))
    assert cli.main(["models", "--url", "http://x:1"]) == 0
    out = capsys.readouterr().out
    assert "resnet18" in out and "MODEL" in out
    assert cli.main(["models", "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["hbm_bytes_total"] == 1048576
