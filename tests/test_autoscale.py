"""Predictive autoscaling plane (serving/autoscale.py; docs/AUTOSCALE.md).

Unit half: the demand model's gap histogram / forecaster, keep-warm
windows with the thin-history fallback, the DETERMINISTIC decision core
(same journal → same actions — the acceptance pin), single-flight
pre-warm dedupe, the HBM-budget shed, the misprediction degradation
ladder under ``kind="demand"`` chaos, the lifecycle/adapter reaper
integration, and the fleet-sizing core.  HTTP half: the real serving
stack — /admin/autoscale, the ``tpuserve autoscale`` table, prometheus
families, and the tier-1 chaos bar (phantom predictions must converge
back to reactive with zero acked loss and no activation stampede).
``tools/replay.py``'s ``policy_sweep`` over live servers is at the bottom.
"""

import asyncio
import io
import json
from types import SimpleNamespace

import numpy as np
import pytest
from PIL import Image

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.faults import FaultInjector
from pytorch_zappa_serverless_tpu.serving.autoscale import (
    AutoscalePlane, DemandModel, SingleFlight, desired_replicas,
    fleet_wait_ms)
from tools import replay

pytest_plugins = "aiohttp.pytest_plugin"


class FakeClock:
    def __init__(self, now=0.0):
        self.now = now

    def __call__(self):
        return self.now

    def advance(self, s):
        self.now += s


def _plane(clock=None, **cfg_kw) -> AutoscalePlane:
    cfg = ServeConfig(**cfg_kw)
    return AutoscalePlane(cfg, clock=clock or FakeClock())


# -- units: demand model ------------------------------------------------------

def test_demand_model_gaps_quantiles_and_next_arrival():
    clock = FakeClock()
    dm = DemandModel(clock=clock)
    assert dm.gap_quantile_s(0.5) is None
    assert dm.next_expected_in_s(0.0) is None
    for _ in range(10):
        clock.advance(1.0)
        dm.note_arrival()
    assert dm.arrivals == 10 and dm.gap_samples == 9
    # 1 s gaps land in the 1.0 bucket; median == p95 == that bound.
    assert dm.median_gap_s() == 1.0
    assert dm.gap_quantile_s(0.95) == 1.0
    # Next arrival predicted one median gap after the last one.
    assert dm.next_expected_in_s(clock.now) == pytest.approx(1.0)
    clock.advance(5.0)
    assert dm.next_expected_in_s(clock.now) == 0.0  # overdue clamps to 0


def test_demand_model_forecast_has_momentum():
    clock = FakeClock()
    dm = DemandModel(clock=clock, fast_s=10.0, slow_s=100.0)
    for _ in range(20):
        clock.advance(0.5)
        dm.note_arrival()
    fast = dm._rate(dm.fast)
    slow = dm._rate(dm.slow)
    assert fast > slow  # a 10 s burst reads hotter over 10 s than 100 s
    assert dm.forecast_rps() == pytest.approx(fast + (fast - slow), abs=1e-6)


def test_keepwarm_window_thin_history_falls_back():
    clock = FakeClock()
    plane = _plane(clock, autoscale_min_history=8, keepwarm_min_s=2.0,
                   keepwarm_max_s=60.0)
    assert plane.keepwarm_window_s("m") is None  # no model at all
    for _ in range(5):
        clock.advance(1.0)
        plane.note_arrival("m")
    assert plane.keepwarm_window_s("m") is None  # 4 gaps < min_history
    for _ in range(5):
        clock.advance(1.0)
        plane.note_arrival("m")
    # 9 gaps of 1 s → p95 bucket 1.0, clamped up to keepwarm_min_s.
    assert plane.keepwarm_window_s("m") == 2.0
    off = _plane(FakeClock(), autoscale="off")
    off.note_arrival("m")
    assert off.keepwarm_window_s("m") is None  # mode off never opines
    assert not off._models  # and records nothing


def test_tenant_keys_are_tracked_separately():
    clock = FakeClock()
    plane = _plane(clock, autoscale_min_history=2)
    for _ in range(4):
        clock.advance(1.0)
        plane.note_arrival("base")
        plane.note_arrival("base", adapter="t1")
    assert set(plane._models) == {"base", "base:t1"}
    assert plane.keepwarm_window_s("base:t1") is not None


# -- units: the deterministic decision core -----------------------------------

def _feed(plane, clock, key="m", n=10, gap=1.0):
    base, _, adapter = key.partition(":")
    for _ in range(n):
        clock.advance(gap)
        plane.note_arrival(base, adapter=adapter or None)


def test_plan_same_journal_same_actions():
    """The acceptance pin: the decision core is pure over (journal, clock,
    suppliers) — two planes fed the identical journal plan identically,
    and planning twice mutates nothing."""
    def build():
        clock = FakeClock()
        plane = _plane(clock, autoscale_min_history=4, prewarm_margin_s=1.0)
        plane.bind(residency_fn=lambda k: "cold",
                   estimate_warm_ms_fn=lambda k: 500.0,
                   resident_bytes_fn=lambda: 0)
        for _ in range(8):  # interleaved: both keys stay fresh
            clock.advance(1.0)
            plane.note_arrival("m")
            plane.note_arrival("base", adapter="t1")
        return plane, clock

    p1, c1 = build()
    p2, c2 = build()
    assert c1.now == c2.now
    a1, a2 = p1.plan(c1.now), p2.plan(c2.now)
    assert a1 == a2
    assert a1 == p1.plan(c1.now)  # planning is side-effect-free on actions
    # Both keys are due: next arrival in 1 s <= 0.5 s estimate + 1 s margin.
    assert [a["key"] for a in a1] == ["base:t1", "m"]  # sorted = stable
    assert all(a["cause"] == "predicted" for a in a1)
    # Staleness: a key long overdue (demand stream stopped) is NOT chased
    # — no pre-warm churn against dead history.
    c1.advance(5.0)  # > 2x the 1 s median past the predicted arrival
    assert p1.plan(c1.now) == []


def test_plan_gates_on_residency_history_and_eta():
    clock = FakeClock()
    plane = _plane(clock, autoscale_min_history=4, prewarm_margin_s=0.2)
    states = {"m": "active"}
    plane.bind(residency_fn=lambda k: states.get(k, "cold"),
               estimate_warm_ms_fn=lambda k: 100.0,
               resident_bytes_fn=lambda: 0)
    _feed(plane, clock, "m", n=10, gap=1.0)
    assert plane.plan(clock.now) == []  # resident: nothing to do
    states["m"] = "cold"
    # eta 1.0 > lead 0.3 → not yet due; advance so the arrival is near.
    assert plane.plan(clock.now) == []
    clock.advance(0.8)
    acts = plane.plan(clock.now)
    assert [a["key"] for a in acts] == ["m"]
    # Histogram mode never pre-warms, whatever the journal says.
    hclock = FakeClock()
    hist = _plane(hclock, autoscale="histogram", autoscale_min_history=4)
    hist.bind(residency_fn=lambda k: "cold",
              estimate_warm_ms_fn=lambda k: 100.0)
    _feed(hist, hclock, "m", n=10, gap=1.0)
    assert hist.plan(hclock.now) == []
    assert hist.keepwarm_window_s("m") is not None  # windows still learn


def test_plan_sheds_prewarms_over_hbm_budget():
    clock = FakeClock()
    plane = _plane(clock, autoscale_min_history=4, prewarm_margin_s=2.0,
                   hbm_budget_bytes=1000)
    plane.bind(residency_fn=lambda k: "cold",
               estimate_warm_ms_fn=lambda k: 100.0,
               resident_bytes_fn=lambda: 2000)  # over budget
    _feed(plane, clock, "m", n=10, gap=1.0)
    assert plane.plan(clock.now) == []
    assert plane.prewarm_shed_budget == 1
    # Budget pressure released → the same journal fires again.
    plane.resident_bytes_fn = lambda: 0
    assert [a["key"] for a in plane.plan(clock.now)] == ["m"]


def test_desired_replicas_sizing_core():
    # Over target → one step out; far under → one step in; else hold.
    assert desired_replicas([{"m": 900.0}], 1, target_wait_ms=250) == 2
    assert desired_replicas([{"m": 900.0}, {"m": 10.0}], 2,
                            target_wait_ms=500) == 2  # mean 455 under
    assert desired_replicas([{"m": 10.0}, {"m": 5.0}], 3,
                            target_wait_ms=250) == 2
    assert desired_replicas([{"m": 10.0}], 1, target_wait_ms=250) == 1
    # Clamps: never past max, never under min, hold with no forecasts.
    assert desired_replicas([{"m": 9999.0}], 4, target_wait_ms=250,
                            max_replicas=4) == 4
    assert desired_replicas([{}], 1, target_wait_ms=250) == 1
    assert desired_replicas([], 0, target_wait_ms=250,
                            min_replicas=2) == 2
    assert fleet_wait_ms([{"a": 100.0, "b": 300.0}, {"a": 100.0}]) == 200.0
    # Deterministic: same inputs, same answer.
    args = ([{"m": 900.0}, {}], 2)
    assert desired_replicas(*args, target_wait_ms=250) \
        == desired_replicas(*args, target_wait_ms=250)


# -- units: pre-warm execution ------------------------------------------------

async def test_prewarm_single_flight_and_draft_warmup():
    clock = FakeClock()
    plane = _plane(clock, autoscale_min_history=4, prewarm_margin_s=2.0)
    release = asyncio.Event()
    calls = []

    async def activate(name, cause):
        calls.append((name, cause))
        if name == "m":
            await release.wait()

    plane.bind(activate_fn=activate,
               draft_of=lambda m: "m_int8" if m == "m" else None,
               residency_fn=lambda k: "cold",
               estimate_warm_ms_fn=lambda k: 100.0,
               resident_bytes_fn=lambda: 0)
    _feed(plane, clock, "m", n=10, gap=1.0)
    plane.tick_once(clock.now)
    plane.tick_once(clock.now)  # second tick: activation still in flight
    await asyncio.sleep(0)
    assert calls == [("m", "prewarm")]  # ONE launch — no stampede
    assert plane.snapshot()["counters"]["prewarms"] == 1
    release.set()
    await asyncio.sleep(0.01)
    # The draft rung warmed right behind its target.
    assert calls == [("m", "prewarm"), ("m_int8", "prewarm_draft")]
    # A matching arrival scores the pre-warm as a hit.
    plane.note_arrival("m")
    assert plane.prewarm_hits == 1 and plane.mispredict_streak == 0


async def test_adapter_prewarm_routes_to_attach():
    clock = FakeClock()
    plane = _plane(clock, autoscale_min_history=4, prewarm_margin_s=2.0)
    attached = []

    async def attach(base, adapter, cause):
        attached.append((base, adapter, cause))

    plane.bind(attach_fn=attach, residency_fn=lambda k: "cold",
               estimate_warm_ms_fn=lambda k: 50.0,
               resident_bytes_fn=lambda: 0)
    _feed(plane, clock, "base:t1", n=10, gap=1.0)
    plane.tick_once(clock.now)
    await asyncio.sleep(0.01)
    assert attached == [("base", "t1", "prewarm")]


async def test_single_flight_gate_reuses_running_task():
    flight = SingleFlight()
    release = asyncio.Event()
    runs = []

    async def job():
        runs.append(1)
        await release.wait()

    t1 = flight.launch("k", job)
    t2 = flight.launch("k", job)
    assert t1 is t2 and flight.running("k")
    release.set()
    await t1
    assert runs == [1] and not flight.running("k")
    t3 = flight.launch("k", job)  # done → a new flight may start
    assert t3 is not t1
    release.set()
    await t3


# -- units: chaos + the degradation ladder ------------------------------------

def test_demand_fault_validation_and_hooks():
    inj = FaultInjector()
    with pytest.raises(ValueError):
        inj.configure(model="m", kind="demand", mode="nope", fail_every_n=1)
    with pytest.raises(ValueError):
        inj.configure(model="m", kind="transient", mode="spike",
                      fail_every_n=1)
    inj.configure(model="m", kind="demand", mode="starve", fail_every_n=1)
    assert inj.on_demand("m") == "starve"
    assert inj.on_demand("other") == ""
    # Demand rules are their own target: dispatch stays clean.
    inj.on_dispatch("m")
    assert inj.snapshot()["injected"]["demand"] == 1


def test_spike_fault_makes_burst_forecaster_invisible():
    clock = FakeClock()
    plane = _plane(clock, autoscale_min_history=2)
    inj = FaultInjector()
    inj.configure(model="m", kind="demand", mode="spike", fail_every_n=1)
    plane.bind(faults=inj, model_names=["m"])
    for _ in range(6):
        clock.advance(0.1)
        plane.note_arrival("m")
    assert "m" not in plane._models  # the burst happened; the model is blind
    assert inj.snapshot()["injected"]["demand"] == 6


async def test_phantom_predictions_degrade_to_reactive_then_recover():
    """The chaos bar: a mispredicting forecaster walks down to today's
    reactive behavior — no pre-warms, fixed timers — and never amplifies
    load (single-flight + bounded by the mispredict limit)."""
    clock = FakeClock()
    plane = _plane(clock, autoscale_min_history=4,
                   autoscale_mispredict_limit=3,
                   autoscale_reactive_hold_s=30.0, prewarm_margin_s=0.5)
    inj = FaultInjector()
    inj.configure(model="ghost", kind="demand", mode="starve",
                  fail_every_n=1)
    activations = []

    async def activate(name, cause):
        activations.append((name, cause))

    plane.bind(activate_fn=activate, faults=inj, model_names=["ghost"],
               residency_fn=lambda k: "cold",
               estimate_warm_ms_fn=lambda k: 100.0,
               resident_bytes_fn=lambda: 0)
    # Teach a keep-warm window on a REAL key so we can watch it vanish.
    _feed(plane, clock, "real", n=10, gap=1.0)
    assert plane.keepwarm_window_s("real") is not None
    misses = 0
    for _ in range(10):
        plane.tick_once(clock.now)
        await asyncio.sleep(0)
        clock.advance(5.0)  # let every phantom watch expire unmatched
        if plane.degraded(clock.now):
            break
        misses += 1
    snap = plane.snapshot()
    assert snap["degraded"] and snap["effective_mode"] == "reactive"
    assert plane.degradations == 1
    assert plane.prewarm_misses >= 3
    # Degraded = today's reactive behavior: no plans, fixed timers.
    assert plane.plan(clock.now) == []
    assert plane.keepwarm_window_s("real") is None
    before = len(activations)
    plane.tick_once(clock.now)
    await asyncio.sleep(0)
    assert len(activations) == before  # even phantoms stop firing
    # No stampede ever: one activation per phantom watch, single-flight.
    assert len(activations) <= plane.mispredict_limit + 1
    # The hold expires → the plane recovers and re-learns.
    clock.advance(31.0)
    assert not plane.degraded(clock.now)
    assert plane.mispredict_streak == 0
    assert plane.keepwarm_window_s("real") is not None


# -- units: reaper integration ------------------------------------------------

class _FakeRunner:
    def __init__(self):
        self.faults = FaultInjector()
        self._resident = {}

    def track_model(self, name, nbytes):
        self._resident[name] = int(nbytes)

    def untrack_model(self, name):
        self._resident.pop(name, None)

    def resident_bytes(self):
        return dict(self._resident)


class _FakeCM:
    mesh = None
    lockstep = None

    def param_nbytes(self):
        return 128

    def host_offload(self):
        pass

    def device_restore(self):
        pass


class _FakeEngine:
    def __init__(self):
        self.models = {}
        self.runner = _FakeRunner()
        self.build_seconds = {}
        self.mesh = None
        self.clock = SimpleNamespace(per_model=lambda: {})

    def attach(self, name, cm):
        self.models[name] = cm
        self.runner.track_model(name, cm.param_nbytes())

    def detach(self, name):
        self.runner.untrack_model(name)
        return self.models.pop(name, None)

    def model(self, name):
        return self.models[name]


class _FakeServer:
    def __init__(self, cfg):
        self.cfg = cfg
        self.engine = _FakeEngine()
        self.tracer = None
        self.batchers = {}
        self.schedulers = {}
        self.jobs = None
        self.resilience = SimpleNamespace(quarantined=set())

    def _start_model_lanes(self, name):
        pass

    async def _stop_model_lanes(self, name):
        pass


async def test_lifecycle_reaper_honors_learned_window(tmp_path):
    """The keep-warm actuator: a learned window replaces idle_unload_s
    per model; None (thin history / degraded) falls back to the timer."""
    from pytorch_zappa_serverless_tpu.serving.lifecycle import (
        ACTIVE, COLD, LifecycleManager)

    cfg = ServeConfig(compile_cache_dir=str(tmp_path / "c"),
                      idle_unload_s=1.0, host_idle_drop_s=100.0,
                      models=[ModelConfig(name="m")])
    server = _FakeServer(cfg)
    clock = FakeClock()
    mgr = LifecycleManager(server, cfg,
                           build_fn=lambda *a: _FakeCM(), clock=clock)
    await mgr.ensure_active("m")
    assert mgr.state_of("m") == ACTIVE
    windows = {"m": 10.0}
    mgr.keepwarm_fn = windows.get
    clock.advance(2.0)  # past the fixed timer, inside the learned window
    await mgr.tick_once()
    assert mgr.state_of("m") == ACTIVE
    clock.advance(9.0)  # past the learned window
    await mgr.tick_once()
    assert mgr.state_of("m") == COLD
    # Fallback: no opinion → the fixed timer rules again.
    await mgr.ensure_active("m")
    windows.clear()
    clock.advance(1.5)
    await mgr.tick_once()
    assert mgr.state_of("m") == COLD


def test_adapter_reaper_window_lookup():
    from pytorch_zappa_serverless_tpu.serving.adapters import (
        AdapterManager, AdapterResidency)

    cfg = ServeConfig(adapter_idle_unload_s=5.0, models=[])
    mgr = AdapterManager(SimpleNamespace(engine=None), cfg)
    rec = AdapterResidency(base="b", name="t", spec={})
    assert mgr.idle_window_s(rec) == 5.0  # unwired → fixed timer
    mgr.keepwarm_fn = lambda key: 42.0 if key == "b:t" else None
    assert mgr.idle_window_s(rec) == 42.0
    mgr.keepwarm_fn = lambda key: None
    assert mgr.idle_window_s(rec) == 5.0  # thin history → fixed timer


# -- HTTP: the real stack -----------------------------------------------------

def _jpeg(seed=0):
    rng = np.random.default_rng(seed)
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 256, (48, 48, 3), np.uint8)
                    ).save(buf, format="JPEG")
    return buf.getvalue()


_IMG_HEADERS = {"Content-Type": "image/jpeg"}


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("autoscale-xla"))


def _http_cfg(cache_dir, **kw):
    base = dict(
        compile_cache_dir=cache_dir, warmup_at_boot=True,
        autoscale="predictive", autoscale_tick_s=0.05,
        autoscale_min_history=3, autoscale_mispredict_limit=2,
        autoscale_reactive_hold_s=2.0, prewarm_margin_s=0.5,
        models=[ModelConfig(name="resnet18", batch_buckets=(1, 2),
                            dtype="float32", coalesce_ms=1.0,
                            extra={"image_size": 48, "resize_to": 56}),
                # Trafficless lazy deploy: the phantom-prediction chaos
                # target (same builder/shapes → compile-cache hits).
                ModelConfig(name="ghost", builder="resnet18",
                            batch_buckets=(1, 2), dtype="float32",
                            coalesce_ms=1.0, lazy_load=True,
                            extra={"image_size": 48, "resize_to": 56})])
    base.update(kw)
    return ServeConfig(**base)


async def test_http_surface_chaos_and_cli(aiohttp_client, cache_dir):
    """End-to-end over the real stack: demand shows on /admin/autoscale
    and the prometheus families; ``kind="demand"`` starve chaos walks the
    plane down to reactive with ZERO acked-request loss and NO activation
    stampede (single-flight pre-warm pinned); the plane recovers after
    the hold; the CLI table renders the payload."""
    from pytorch_zappa_serverless_tpu.cli import format_autoscale_table
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    client = await aiohttp_client(create_app(_http_cfg(cache_dir)))
    # Demand: a few predicts teach the model's demand journal.
    for i in range(4):
        r = await client.post("/v1/models/resnet18:predict", data=_jpeg(i),
                              headers=_IMG_HEADERS)
        assert r.status == 200, await r.text()
    snap = await (await client.get("/admin/autoscale")).json()
    assert snap["mode"] == "predictive" and not snap["degraded"]
    m = snap["models"]["resnet18"]
    assert m["arrivals"] == 4 and m["forecast_rps"] > 0
    # Prometheus families render and stay manifest-clean (the manifest
    # lint itself runs in test_metrics_prometheus.py over the loaded hub).
    r = await client.get("/metrics?format=prometheus")
    text = await r.text()
    assert 'tpuserve_autoscale_forecast_rps{model="resnet18"}' in text
    # Chaos: phantom predictions (starve) on the TRAFFICLESS lazy deploy —
    # demand that never comes.  Every pre-warm watch expires unmatched,
    # so the ladder must degrade the plane to reactive while the busy
    # model keeps serving untouched.
    r = await client.post("/admin/faults",
                          json={"model": "ghost", "kind": "demand",
                                "mode": "starve", "fail_every_n": 1})
    assert r.status == 200, await r.text()
    ok = 0
    for i in range(40):
        rr = await client.post("/v1/models/resnet18:predict",
                               data=_jpeg(i), headers=_IMG_HEADERS)
        ok += rr.status == 200
        snap = await (await client.get("/admin/autoscale")).json()
        if snap["degraded"]:
            break
        await asyncio.sleep(0.2)
    assert ok == i + 1  # ZERO acked-request loss under chaos
    assert snap["degraded"] and snap["effective_mode"] == "reactive"
    assert snap["counters"]["degradations"] >= 1
    assert snap["counters"]["prewarm_misses"] >= 2
    # No activation stampede: MANY phantom firings, at most ONE real
    # pre-warm activation of the ghost (single-flight + one open watch
    # per key), and at most one flight outstanding.
    models = await (await client.get("/admin/models")).json()
    acts = models["models"]["ghost"]["activations_by_cause"]
    assert acts.get("prewarm", 0) <= 1
    assert len(snap["in_flight"]) <= 1
    # Injected chaos is visible and clearable on the faults surface.
    fsnap = await (await client.get("/admin/faults")).json()
    assert fsnap["faults"]["injected"]["demand"] >= 1
    r = await client.post("/admin/faults", json={"clear": True})
    assert r.status == 200
    # The hold expires → reactive degradation lifts, serving never blinked.
    await asyncio.sleep(2.2)
    snap = await (await client.get("/admin/autoscale")).json()
    assert not snap["degraded"]
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(99),
                          headers=_IMG_HEADERS)
    assert r.status == 200
    # CLI table renders both the rows and the counter line.
    table = format_autoscale_table(snap)
    assert "resnet18" in table and "mode: predictive" in table
    assert "KEEPWARM_S" in table


# -- tools/replay.py policy_sweep ----------------------------------------------

@pytest.fixture(scope="module")
def sweep():
    """One bursty trace (6 s, 10 /s, seed 7) against the two ends of the
    policy ladder, fixed idle timers and the predictive plane, at equal
    ``hbm_budget_bytes``."""
    return replay.policy_sweep(duration_s=6.0, rps=10.0, seed=7,
                               policies=("fixed", "predictive"))


@pytest.mark.parametrize("store", [False, True],
                         ids=["fixed-vs-predictive", "fixed-with-store"])
def test_policy_sweep(sweep, store, tmp_path):
    fixed = sweep["policies"]["fixed"]
    # The fixed timer demoted between bursts and ate cold starts.
    assert fixed["demotions_idle"] >= 1
    assert fixed["cold_hits"] >= 1
    assert fixed["tier_end"] == "none"
    if not store:
        assert set(sweep["policies"]) == {"fixed", "predictive"}
        assert sweep["hbm_budget_bytes"] > 0  # equal for both: one config
        pred = sweep["policies"]["predictive"]
        for name, rep in sweep["policies"].items():
            assert rep["offered"] == fixed["offered"] > 0, name  # one trace
            assert rep["served"] > rep["offered"] * 0.5, (name, rep)
        # The plane learned a keep-warm window over the gaps between bursts
        # and held the model through them.
        assert pred["keepwarm_window_s"] is not None
        assert fixed["keepwarm_window_s"] is None
        assert pred["cold_hits"] <= fixed["cold_hits"]
        # The verdict is in the report, both halves of it.
        v = sweep["verdict"]
        assert isinstance(v["predictive_beats_fixed"], bool)
        for key in ("cold_hit_rate", "latency_p99_ms"):
            assert {"fixed", "predictive", "predictive_better"} <= set(v[key])
        return
    # Same trace and timers with the streaming checkpoint store on
    # (docs/LIFECYCLE.md): idle demotions land in the disk tier, and what
    # the plane has learned a re-activation from there costs undercuts what
    # it has learned a full rebuild costs.
    with_store = replay.policy_sweep(
        duration_s=6.0, rps=10.0, seed=7, policies=("fixed",),
        ckpt_store_dir=str(tmp_path / "ckpt"))
    assert with_store["ckpt_store"] is True
    stored = with_store["policies"]["fixed"]
    assert stored["demotions_idle"] >= 1
    assert stored["tier_end"] == "disk"
    assert stored["estimated_warm_ms"] < fixed["estimated_warm_ms"]
