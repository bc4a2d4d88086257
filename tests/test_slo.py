"""SLO & goodput plane (serving/slo.py; docs/OBSERVABILITY.md §6), tier-1.

Four layers, all CPU-runnable:

- **units** — rolling windows with an injectable clock, outcome
  classification, burn-rate math (a deliberately missed objective flips the
  fast-window alarm), the usage ledger, and the fleet merge semantics
  (window sums, histogram bucket-merge);
- **torn reads** — threaded observe/snapshot races over the windows, the
  ledger, and the fleet histogram-merge (the PR 8 ``Histogram.rows`` fix's
  invariant, re-proven on the new surfaces);
- **HTTP** — a real booted server: /admin/slo, the healthz burn summary,
  the Prometheus families, the usage ledger fed by real predicts, and the
  missed-objective alarm flip over the wire;
- **router** — a real :class:`FleetRouter` scraping two stub replicas'
  /metrics JSON: ``GET /admin/slo`` aggregates both replicas' goodput and
  burn state, /healthz and /admin/fleet carry the burn/quarantine summary,
  and shed responses under budget exhaustion still compute fleet-minimum
  Retry-After.

tools/replay.py (trace shapes, the replayer, and a replay against a live
two-deploy server) is covered at the bottom.
"""

import importlib.util
import io
import json
import threading
from pathlib import Path

import numpy as np
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer
from PIL import Image

from pytorch_zappa_serverless_tpu.config import (FleetConfig, ModelConfig,
                                                 ServeConfig)
from pytorch_zappa_serverless_tpu.serving.fleet import FleetRouter
from pytorch_zappa_serverless_tpu.serving.metrics import Histogram
from pytorch_zappa_serverless_tpu.serving.slo import (
    SLODef, SLOHub, RollingWindow, UsageLedger, merge_histogram_snapshots,
    merge_slo_snapshots, rollup_metrics)

pytest_plugins = "aiohttp.pytest_plugin"


def _load_tool(name: str):
    path = Path(__file__).resolve().parents[1] / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"tpuserve_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _hub(clock=None, **cfg_kw) -> SLOHub:
    cfg = ServeConfig(**cfg_kw)
    return SLOHub(cfg, **({"clock": clock} if clock is not None else {}))


# -- units: windows ------------------------------------------------------------

def test_rolling_window_expires_old_buckets():
    clk = [0.0]
    w = RollingWindow(60.0, buckets=6, clock=lambda: clk[0])
    w.note(True), w.note(False)
    assert w.counts() == (1, 2)
    clk[0] = 30.0
    w.note(True)
    assert w.counts() == (2, 3)
    clk[0] = 65.0  # first bucket (t=0) is now outside the window
    assert w.counts() == (1, 1)
    clk[0] = 300.0
    assert w.counts() == (0, 0)


def test_window_bucket_reuse_resets_stale_slot():
    clk = [0.0]
    w = RollingWindow(10.0, buckets=2, clock=lambda: clk[0])
    w.note(False)
    clk[0] = 10.0  # same ring slot, one full revolution later
    w.note(True)
    assert w.counts() == (1, 1)  # the stale miss did not leak in


# -- units: classification + burn ---------------------------------------------

def test_classification_matrix():
    hub = _hub(slo={"m": {"latency_objective_ms": 10.0,
                          "availability_target": 0.99}})
    assert hub.classify("m", 200, 5.0) == "good"
    assert hub.classify("m", 200, 5.0, degraded=True) == "degraded"
    assert hub.classify("m", 200, 11.0) == "late"
    for status in (429, 503, 504):
        assert hub.classify("m", status, 0.0) == "shed"
    assert hub.classify("m", 500, 0.0) == "error"
    assert hub.classify("m", 200, 5.0, errored=True) == "error"  # mid-SSE
    assert hub.classify("m", 400, 0.0) is None  # client errors don't burn
    assert hub.classify("m", 404, 0.0) is None
    # No latency objective → served == on time.
    assert hub.classify("other", 200, 1e9) == "good"


def test_definition_resolution_tenant_then_model_then_family():
    cfg = ServeConfig(
        slo={"m": {"latency_objective_ms": 50.0},
             "m:t1": {"latency_objective_ms": 5.0},
             "fam": {"latency_objective_ms": 99.0}},
        models=[ModelConfig(name="fm", family="fam")])
    hub = SLOHub(cfg)
    assert hub.definition("m:t1").latency_objective_ms == 5.0
    assert hub.definition("m:other").latency_objective_ms == 50.0
    assert hub.definition("m").latency_objective_ms == 50.0
    assert hub.definition("fm").latency_objective_ms == 99.0  # via family
    assert hub.definition("unknown").latency_objective_ms == 0.0


def test_missed_objective_flips_fast_window_alarm():
    """The acceptance bar: a deliberately missed latency objective burns
    the fast window past its alarm threshold."""
    clk = [100.0]
    hub = _hub(clock=lambda: clk[0],
               slo={"m": {"latency_objective_ms": 10.0,
                          "availability_target": 0.99}})
    for _ in range(20):
        assert hub.observe("m", "predict", 200, 5.0) == "good"
    snap = hub.snapshot()["models"]["m"]["predict"]
    assert snap["windows"]["fast"]["alarm"] is False
    assert snap["windows"]["fast"]["burn_rate"] == 0.0
    # Now miss the objective deliberately: 10 late serves out of 30 total
    # is a 33% bad fraction over a 1% budget — burn 33 >> the 14 alarm.
    for _ in range(10):
        assert hub.observe("m", "predict", 200, 50.0) == "late"
    snap = hub.snapshot()["models"]["m"]["predict"]
    fast = snap["windows"]["fast"]
    assert fast["alarm"] is True
    assert fast["burn_rate"] > 14.0
    assert fast["budget_remaining"] == 0.0
    assert "m|predict" in hub.health_summary()["fast_alarms"]
    # The fast window forgets; lifetime outcomes don't.
    clk[0] += hub.fast_window_s + 1
    snap = hub.snapshot()["models"]["m"]["predict"]
    assert snap["windows"]["fast"]["alarm"] is False
    assert snap["outcomes"]["late"] == 10
    # The slow window still remembers the burn.
    assert snap["windows"]["slow"]["total"] == 30


def test_tenant_tracked_under_both_keys():
    hub = _hub()
    hub.observe("m", "predict", 200, 1.0, adapter="t1")
    hub.observe("m", "predict", 429, 0.0, adapter="t1")
    hub.observe("m", "predict", 200, 1.0)
    snap = hub.snapshot()["models"]
    assert snap["m"]["predict"]["requests"] == 3       # base aggregates all
    assert snap["m:t1"]["predict"]["requests"] == 2    # tenant view apart
    assert snap["m:t1"]["predict"]["outcomes"]["shed"] == 1


# -- units: usage ledger -------------------------------------------------------

def test_usage_ledger_accumulates_per_tenant():
    led = UsageLedger()
    led.note_request("m", None, 2.5)
    led.note_request("m", "t1", 4.0)
    led.note_stream("m", "t1", 10.0, 3.25, 96)
    led.note_attach("m", "t1", 7.5)
    snap = led.snapshot()
    assert snap["m"]["requests"] == 1 and snap["m"]["device_ms"] == 2.5
    t1 = snap["m:t1"]
    assert t1["requests"] == 2
    assert t1["device_ms"] == 14.0
    assert t1["kv_block_seconds"] == 3.25
    assert t1["prefix_saved_tokens"] == 96
    assert t1["attaches"] == 1 and t1["attach_ms"] == 7.5


# -- units: fleet merge semantics ---------------------------------------------

def test_histogram_merge_sums_and_stays_monotonic():
    a = {"buckets": {"1": 2, "5": 3, "+Inf": 4}, "sum": 5.0, "count": 4}
    b = {"buckets": {"1": 1, "10": 2, "+Inf": 2}, "sum": 3.0, "count": 2}
    m = merge_histogram_snapshots([a, b])
    assert m["count"] == 6 and m["sum"] == 8.0
    accs = list(m["buckets"].values())
    assert accs == sorted(accs), "merged histogram must stay cumulative"
    assert m["buckets"]["+Inf"] == 6
    assert merge_histogram_snapshots([]) is None
    assert merge_histogram_snapshots([a])["buckets"] == {"1": 2, "5": 3,
                                                         "+Inf": 4}


def test_merge_slo_recomputes_burn_from_summed_windows():
    """An idle replica must not average away a burning one."""
    clk = [0.0]
    burning = _hub(clock=lambda: clk[0],
                   slo={"m": {"availability_target": 0.99}})
    idle = _hub(clock=lambda: clk[0],
                slo={"m": {"availability_target": 0.99}})
    for _ in range(10):
        burning.observe("m", "predict", 503, 0.0)
    idle.observe("m", "predict", 200, 1.0)
    merged = merge_slo_snapshots([burning.snapshot(), idle.snapshot()])
    lane = merged["models"]["m"]["predict"]
    assert lane["outcomes"]["shed"] == 10 and lane["outcomes"]["good"] == 1
    # 10/11 bad over a 1% budget ≈ 91x burn — alarmed fleet-wide.
    assert lane["windows"]["fast"]["burn_rate"] > 14.0
    assert lane["windows"]["fast"]["alarm"] is True
    assert merged["replicas_merged"] == 2


def test_rollup_metrics_sums_counters_and_merges_hists():
    h = Histogram(bounds=(1.0, 10.0))
    h.observe(0.5), h.observe(5.0)
    ring = {"requests": 4, "errors": 1, "req_per_s_lifetime": 2.0,
            "queue_hist": h.snapshot(), "device_hist": h.snapshot()}
    snap = {"models": {"m": ring},
            "generation": {"g": {"kv": {"blocks_used": 3, "blocks_total": 8,
                                        "evictions": 1}}},
            "hbm": {"total_bytes": 100},
            "slo": _hub().snapshot()}
    out = rollup_metrics([snap, snap])
    assert out["replicas_merged"] == 2
    assert out["models"]["m"]["requests"] == 8
    assert out["models"]["m"]["errors"] == 2
    assert out["models"]["m"]["queue_hist"]["count"] == 4
    assert out["kv"] == {"blocks_used": 6, "blocks_total": 16,
                         "evictions": 2}
    assert out["hbm_bytes_total"] == 200


# -- torn reads ---------------------------------------------------------------

def test_slo_snapshots_consistent_under_threaded_load():
    """Scrape-while-observe: every snapshot taken mid-hammer must be
    internally consistent (good <= total per window, no negative counts),
    and the final counts exact — the PR 8 torn-read bar on the new plane."""
    hub = _hub(slo={"m": {"availability_target": 0.9}})
    N, THREADS = 400, 4
    stop = threading.Event()
    problems: list[str] = []

    def hammer():
        for i in range(N):
            hub.observe("m", "predict", 200 if i % 3 else 503, 1.0,
                        adapter="t" if i % 2 else None)
            hub.usage.note_stream("m", "t", 1.0, 0.5, 4)

    def scrape():
        while not stop.is_set():
            snap = hub.snapshot()
            for key, lanes in snap["models"].items():
                for lane, t in lanes.items():
                    for w in t["windows"].values():
                        if w["good"] > w["total"]:
                            problems.append(f"{key}|{lane}: good>{w}")
                    if any(v < 0 for v in t["outcomes"].values()):
                        problems.append(f"{key}|{lane}: negative outcome")
            for row in snap["usage"].values():
                if any(v < 0 for v in row.values()):
                    problems.append("negative usage")

    threads = [threading.Thread(target=hammer) for _ in range(THREADS)]
    scraper = threading.Thread(target=scrape)
    scraper.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    scraper.join()
    assert problems == []
    snap = hub.snapshot()["models"]["m"]["predict"]
    assert sum(snap["outcomes"].values()) == N * THREADS
    assert snap["windows"]["slow"]["total"] == N * THREADS


def test_histogram_merge_consistent_under_concurrent_observe():
    """The fleet histogram-merge consumes snapshots taken while observes
    land: each merge must stay monotonic with +Inf == count (the exact
    invariant the pre-ISSUE-8 Histogram.rows violated)."""
    hists = [Histogram(bounds=(1.0, 5.0, 25.0)) for _ in range(2)]
    stop = threading.Event()
    problems: list[str] = []

    def observe(h):
        i = 0
        while not stop.is_set():
            h.observe(float(i % 40))
            i += 1

    def merge_loop():
        for _ in range(300):
            m = merge_histogram_snapshots([h.snapshot() for h in hists])
            if m is None:
                continue
            accs = list(m["buckets"].values())
            if accs != sorted(accs):
                problems.append(f"non-monotonic: {m}")
            if m["buckets"]["+Inf"] != m["count"]:
                problems.append(f"+Inf != count: {m}")

    obs = [threading.Thread(target=observe, args=(h,)) for h in hists]
    for t in obs:
        t.start()
    merge_loop()
    stop.set()
    for t in obs:
        t.join()
    assert problems == []


# -- HTTP: a real booted server -----------------------------------------------

def _slo_cfg(tmp_path, **kw):
    base = dict(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=True,
        slo={"resnet18": {"latency_objective_ms": 60000.0,
                          "availability_target": 0.9}},
        models=[ModelConfig(name="resnet18", batch_buckets=(1,),
                            dtype="float32", coalesce_ms=0.0,
                            extra={"image_size": 48, "resize_to": 56})])
    base.update(kw)
    return ServeConfig(**base)


def _png():
    rng = np.random.default_rng(0)
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 256, (64, 64, 3), np.uint8)
                    ).save(buf, format="PNG")
    return buf.getvalue()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    import asyncio

    from pytorch_zappa_serverless_tpu.serving.server import Server

    loop = asyncio.new_event_loop()
    srv = Server(_slo_cfg(tmp_path_factory.mktemp("slo")))

    async def _up():
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        return client
    client = loop.run_until_complete(_up())
    yield loop, srv, client
    loop.run_until_complete(client.close())
    loop.close()


def _reset(srv):
    srv.slo._trackers.clear()
    srv.slo._defs["resnet18"] = SLODef(60000.0, 0.9)


def test_http_good_predict_lands_in_slo_and_usage(served):
    loop, srv, client = served
    _reset(srv)

    async def go():
        r = await client.post("/v1/models/resnet18:predict", data=_png(),
                              headers={"Content-Type": "image/png"})
        assert r.status == 200, await r.text()
        return await (await client.get("/admin/slo")).json()
    snap = loop.run_until_complete(go())
    lane = snap["models"]["resnet18"]["predict"]
    assert lane["outcomes"]["good"] >= 1
    assert lane["goodput_ratio"] == 1.0
    assert lane["windows"]["fast"]["alarm"] is False
    # The usage ledger billed the device time.
    assert snap["usage"]["resnet18"]["requests"] >= 1
    assert snap["usage"]["resnet18"]["device_ms"] > 0


def test_http_missed_objective_flips_alarm_on_healthz(served):
    """Tier-1 acceptance over the wire: shrink the objective so a real
    serve misses it; the fast-window alarm flips on /admin/slo AND the
    /healthz burn summary (without flipping health)."""
    loop, srv, client = served
    _reset(srv)
    # Unmeetable objective over a 1% budget: 100% late = 100x burn.
    srv.slo._defs["resnet18"] = SLODef(0.0001, 0.99)

    async def go():
        for _ in range(3):
            r = await client.post("/v1/models/resnet18:predict",
                                  data=_png(),
                                  headers={"Content-Type": "image/png"})
            assert r.status == 200
        slo = await (await client.get("/admin/slo")).json()
        h = await client.get("/healthz")
        return slo, h.status, await h.json()
    slo, hstatus, health = loop.run_until_complete(go())
    lane = slo["models"]["resnet18"]["predict"]
    assert lane["outcomes"]["late"] >= 3
    assert lane["windows"]["fast"]["alarm"] is True
    assert lane["windows"]["fast"]["burn_rate"] >= 14.0  # 100% bad / 1%
    assert "resnet18|predict" in health["slo"]["fast_alarms"]
    assert hstatus == 200  # an SLO alarm is not a health failure


def test_http_sheds_and_client_errors_classified(served):
    loop, srv, client = served
    _reset(srv)

    async def go():
        # Expired deadline → 504 at admission → shed.
        r = await client.post("/v1/models/resnet18:predict", data=_png(),
                              headers={"Content-Type": "image/png",
                                       "X-Deadline-Ms": "0"})
        assert r.status == 504
        # Unknown model → 404 → a client error, not budget burn.
        r = await client.post("/v1/models/nope:predict", data=b"{}")
        assert r.status == 404
        return await (await client.get("/admin/slo")).json()
    snap = loop.run_until_complete(go())
    lane = snap["models"]["resnet18"]["predict"]
    assert lane["outcomes"]["shed"] == 1
    assert "nope" not in snap["models"]


def test_http_prometheus_families_and_json_block(served):
    loop, srv, client = served
    _reset(srv)

    async def go():
        await client.post("/v1/models/resnet18:predict", data=_png(),
                          headers={"Content-Type": "image/png"})
        text = await (await client.get(
            "/metrics", headers={"Accept": "text/plain"})).text()
        js = await (await client.get("/metrics")).json()
        return text, js
    text, js = loop.run_until_complete(go())
    for family in ("tpuserve_slo_requests_total", "tpuserve_slo_burn_rate",
                   "tpuserve_slo_burn_alarm", "tpuserve_slo_goodput_ratio",
                   "tpuserve_usage_device_ms_total"):
        assert f"# TYPE {family} " in text, family
    assert ('tpuserve_slo_requests_total{lane="predict",model="resnet18",'
            'outcome="good"}') in text
    assert "slo" in js and "resnet18" in js["slo"]["models"]


# -- router: fleet rollup ------------------------------------------------------

class SLOReplica:
    """Stub replica: a REAL SLOHub behind the three polled surfaces
    (/healthz with the burn summary, /admin/models, /metrics JSON) plus a
    scripted predict (ok | overloaded)."""

    def __init__(self, model="m", mode="ok", retry_after="3",
                 outcomes=((200, 1.0),)):
        self.model = model
        self.mode = mode
        self.retry_after = retry_after
        self.hub = SLOHub(ServeConfig(
            slo={model: {"latency_objective_ms": 100.0,
                         "availability_target": 0.99}}))
        for status, ms in outcomes:
            self.hub.observe(model, "predict", status, ms)
        self.app = web.Application()
        self.app.add_routes([
            web.get("/healthz", self._healthz),
            web.get("/admin/models", self._models),
            web.get("/metrics", self._metrics),
            web.post("/v1/models/{name:[^:/]+}:predict", self._predict),
        ])

    async def _healthz(self, request):
        return web.json_response({
            "device_ok": True, "draining": False, "quarantined": [],
            "forecast": {self.model: 1.0}, "jobs_backlog": 0,
            "slo": self.hub.health_summary()})

    async def _models(self, request):
        return web.json_response({"models": {
            self.model: {"state": "active", "estimated_warm_ms": 500.0}}})

    async def _metrics(self, request):
        return web.json_response({
            "models": {self.model: {"requests": 2, "errors": 0,
                                    "req_per_s_lifetime": 1.0}},
            "slo": self.hub.snapshot()})

    async def _predict(self, request):
        await request.read()
        if self.mode == "overloaded":
            return web.json_response(
                {"error": "overloaded: error budget exhausted",
                 "estimated_wait_ms": float(self.retry_after) * 1000},
                status=429, headers={"Retry-After": self.retry_after})
        return web.json_response({"model": self.model, "predictions": [1],
                                  "timing": {}})


class _Fleet:
    def __init__(self, fakes, **cfg_kw):
        self.fakes = fakes
        base = dict(poll_interval_s=0.0, failover_backoff_ms=0.0,
                    connect_timeout_s=1.0, quarantine_after=2)
        base.update(cfg_kw)
        self.cfg_kw = base
        self.servers = []
        self.router = None
        self.client = None

    async def __aenter__(self):
        urls = []
        for f in self.fakes:
            s = TestServer(f.app)
            await s.start_server()
            self.servers.append(s)
            urls.append(str(s.make_url("")).rstrip("/"))
        self.router = FleetRouter(FleetConfig(replicas=urls, **self.cfg_kw))
        self.client = TestClient(TestServer(self.router.app))
        await self.client.start_server()
        await self.router.poll_once()
        return self

    async def __aexit__(self, *exc):
        await self.client.close()
        for s in self.servers:
            await s.close()


async def test_router_admin_slo_aggregates_two_replicas():
    """The acceptance bar: GET /admin/slo on the router merges >= 2
    replicas' goodput and burn-rate state — counts summed, burn recomputed
    from the merged windows."""
    a = SLOReplica(outcomes=[(200, 1.0)] * 4)                # healthy
    b = SLOReplica(outcomes=[(200, 1.0)] + [(503, 0.0)] * 5)  # burning
    async with _Fleet([a, b]) as fl:
        r = await fl.client.get("/admin/slo")
        assert r.status == 200
        snap = await r.json()
        assert snap["replicas_merged"] == 2 and snap["fleet"] is True
        lane = snap["models"]["m"]["predict"]
        assert lane["outcomes"]["good"] == 5   # 4 + 1 across replicas
        assert lane["outcomes"]["shed"] == 5
        assert lane["goodput_ratio"] == 0.5
        # 5/10 bad over a 1% budget = 50x burn — alarmed fleet-wide even
        # though replica a alone is clean.
        assert lane["windows"]["fast"]["burn_rate"] > 14.0
        assert lane["windows"]["fast"]["alarm"] is True
        # Per-replica attribution rides along.
        assert len(snap["replicas"]) == 2
        assert any(rep["slo"]["fast_alarms"]
                   for rep in snap["replicas"].values())


async def test_router_healthz_and_fleet_carry_burn_summary():
    a = SLOReplica(outcomes=[(200, 1.0)] * 3)
    b = SLOReplica(outcomes=[(503, 0.0)] * 3)
    async with _Fleet([a, b]) as fl:
        h = await fl.client.get("/healthz")
        assert h.status == 200
        body = await h.json()
        assert body["slo"]["worst_fast_burn"] > 14.0
        assert any(x.endswith("m|predict") for x in
                   body["slo"]["fast_alarms"])
        fleet = await (await fl.client.get("/admin/fleet")).json()
        assert fleet["slo"]["fast_alarms"] == body["slo"]["fast_alarms"]
        assert fleet["quarantined"] == {"replicas": [], "models": {}}
        # The /metrics JSON rollup folds the replicas' scraped islands.
        m = await (await fl.client.get("/metrics")).json()
        roll = m["fleet"]["rollup"]
        assert roll["replicas_merged"] == 2
        assert roll["models"]["m"]["requests"] == 4  # 2 + 2
        assert roll["slo"]["models"]["m"]["predict"]["requests"] == 6


async def test_router_shed_under_budget_exhaustion_keeps_fleet_min_retry():
    """Regression (satellite): when every replica sheds because its budget
    is exhausted, the router's shed still computes the FLEET-minimum
    Retry-After — never a single replica's leaked value."""
    a = SLOReplica(mode="overloaded", retry_after="7",
                   outcomes=[(429, 0.0)] * 4)
    b = SLOReplica(mode="overloaded", retry_after="3",
                   outcomes=[(429, 0.0)] * 4)
    async with _Fleet([a, b]) as fl:
        r = await fl.client.post("/v1/models/m:predict", data=b"{}")
        assert r.status == 429
        body = await r.json()
        assert body["fleet_shed"] == "all_overloaded"
        assert int(r.headers["Retry-After"]) == 3  # min(7, 3)
        assert len(body["replicas_tried"]) == 2
        # The exhausted budget is visible on the same router's health.
        h = await (await fl.client.get("/healthz")).json()
        assert h["slo"]["worst_fast_burn"] > 14.0


# -- CLI table ----------------------------------------------------------------

def test_cli_slo_table_renders_models_and_usage():
    from pytorch_zappa_serverless_tpu.cli import format_slo_table

    hub = _hub(slo={"m": {"latency_objective_ms": 10.0,
                          "availability_target": 0.99}})
    hub.observe("m", "predict", 200, 5.0)
    hub.observe("m", "predict", 200, 50.0)
    hub.usage.note_stream("m", "t1", 12.0, 3.5, 96)
    hub.usage.note_attach("m", "t1", 7.0)
    out = format_slo_table(hub.snapshot())
    head, *rest = out.splitlines()
    assert head.split()[:4] == ["KEY", "LANE", "OBJ_MS", "TARGET"]
    row = next(line for line in rest if line.startswith("m "))
    assert "predict" in row and "fast" in row  # the alarm column
    assert any(line.startswith("m:t1") for line in rest)  # usage row
    assert "PREFIX_SAVED_TOK" in out
    # Fleet payloads render through the same table.
    merged = merge_slo_snapshots([hub.snapshot(), hub.snapshot()])
    assert "2 replicas merged" in format_slo_table(merged)


# -- tracedump substages (satellite) ------------------------------------------

def test_tracedump_surfaces_adapter_and_prefix_spans():
    from pytorch_zappa_serverless_tpu.serving.tracing import Tracer

    td = _load_tool("tracedump")
    tracer = Tracer()
    root = tracer.start("predict", model="gpt2")
    root.point("variant_select", family="g", variant="gpt2", degraded=False)
    adm = root.child("admission", start=root.t0)
    adm.point("adapter_gather", adapter="t1", slot=2)
    adm.end()
    root.point("adapter_attach", adapter="t1", waited_ms=12.5)
    q = root.child("queue", start=adm.t1)
    q.point("prefix_hit", cached_tokens=64, shared_pages=4, cow_copies=1)
    q.end()
    dev = root.child("device", start=q.t1)
    dev.child("prefill_chunk", batch=1, chunk=0, chunks=2).end()
    dev.point("prefix_insert", pages=5)
    dev.end()
    root.child("respond", start=dev.t1).end()
    tracer.finish(root.trace, "ok")

    tree = root.trace.tree()
    att = td.stage_attribution(tree)
    for name in ("adapter_gather", "adapter_attach", "prefix_hit",
                 "prefix_insert", "prefill_chunk", "variant_select"):
        assert name in att["substages"], name
    assert att["substages"]["prefix_hit"]["count"] == 1
    # The admission→queue→device→respond chain still tiles the wall.
    assert att["coverage_pct"] >= 95.0
    text = td.render(tree)
    assert "substages:" in text
    assert "adapter=t1" in text and "cached_tokens=64" in text
    assert "waited_ms=12.5" in text


# -- tools/replay.py -----------------------------------------------------------

def test_synth_trace_shapes_and_determinism():
    rp = _load_tool("replay")
    t1 = rp.synth_trace("bursty", 10.0, 20.0, ["a", "b", "c"], seed=3)
    t2 = rp.synth_trace("bursty", 10.0, 20.0, ["a", "b", "c"], seed=3)
    assert t1 == t2, "traces must be deterministic per seed"
    assert t1 and all(0 <= x["t"] <= 10.0 for x in t1)
    assert [x["t"] for x in t1] == sorted(x["t"] for x in t1)
    # Heavy-tailed skew: the head model dominates the bursty shape.
    counts = {m: sum(1 for x in t1 if x["model"] == m) for m in "abc"}
    assert counts["a"] > counts["c"]
    # Burstiness: some gaps are far tighter than the mean arrival gap.
    ts = [x["t"] for x in t1]
    gaps = [b - a for a, b in zip(ts, ts[1:])]
    assert min(gaps) < (10.0 / len(ts)) / 3
    d = rp.synth_trace("diurnal", 10.0, 20.0, ["a"], seed=1)
    assert d and all(x["model"] == "a" for x in d)
    with pytest.raises(ValueError):
        rp.synth_trace("square", 1.0, 1.0, ["a"])
    with pytest.raises(ValueError):
        rp.synth_trace("bursty", 1.0, 1.0, [])


def test_replay_summarize_goodput_vs_throughput():
    rp = _load_tool("replay")
    outcomes = (
        [{"status": 200, "latency_ms": 5.0, "cold": False,
          "degraded": False, "t": 0.0}] * 6
        + [{"status": 200, "latency_ms": 50.0, "cold": False,
            "degraded": True, "t": 0.1}] * 2    # served but late
        + [{"status": 503, "latency_ms": 1.0, "cold": True,
            "degraded": False, "t": 0.2}] * 2)  # cold sheds
    rep = rp.summarize(outcomes, duration_s=10.0, objective_ms=10.0)
    assert rep["offered"] == 10 and rep["served"] == 8 and rep["good"] == 6
    assert rep["slo_attainment"] == 0.6
    assert rep["cold_hit_rate"] == 0.2
    assert rep["goodput_rps"] == 0.6 and rep["throughput_rps"] == 0.8
    assert rep["goodput_vs_throughput"] == 0.75
    assert rep["degraded"] == 2 and rep["shed"] == 2


async def test_replay_async_is_open_loop():
    rp = _load_tool("replay")
    seen = []

    async def send(item):
        seen.append(item["model"])
        return {"status": 200, "latency_ms": 1.0, "cold": False,
                "degraded": False}

    trace = [{"t": 0.0, "model": "a"}, {"t": 0.02, "model": "b"},
             {"t": 0.04, "model": "c"}]
    outcomes = await rp.replay_async(send, trace, speedup=2.0)
    assert [o["model"] for o in outcomes] == ["a", "b", "c"]
    assert len(seen) == 3
    # A transport failure becomes an errored outcome, not a lost request.
    async def boom(item):
        raise ConnectionError("down")
    outcomes = await rp.replay_async(boom, trace[:1])
    assert outcomes[0]["status"] == 599


# -- the replayer against a live server ----------------------------------------

async def test_replay_against_a_live_server_burns_the_cold_deploys_fast_window(
        aiohttp_client, tmp_path):
    """A bursty trace over two deploys of one builder, one built at boot and
    one lazy, with deadlines under the activation estimate: the lazy deploy's
    requests fast-fail 503 ``cold_start``, the replayer counts them as cold
    hits, and the server's own /admin/slo tells the same story (sheds that
    burned the fast window).  The replayer and the SLO plane agree."""
    import asyncio

    from pytorch_zappa_serverless_tpu.serving.server import create_app

    rp = _load_tool("replay")

    def mk(name, lazy):
        return ModelConfig(name=name, builder="resnet18", batch_buckets=(1, 4),
                           dtype="float32", coalesce_ms=1.0, lazy_load=lazy,
                           extra={"image_size": 48, "resize_to": 56})

    slo = {"latency_objective_ms": 1500.0, "availability_target": 0.99}
    client = await aiohttp_client(create_app(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=True,
        # The cold deploy must fast-fail under the replay deadline, not
        # absorb it into a blocked activation.
        activation_estimate_ms=60000.0,
        slo={"rn_hot": slo, "rn_cold": slo},
        models=[mk("rn_hot", lazy=False), mk("rn_cold", lazy=True)])))
    send = rp.http_sender(client.session, str(client.make_url("")), _png(),
                          "image/png", deadline_ms=2000.0)
    trace = rp.synth_trace("bursty", 3.0, 8.0, ["rn_hot", "rn_cold"], seed=7)
    assert {t["model"] for t in trace} == {"rn_hot", "rn_cold"}
    report = rp.summarize(await rp.replay_async(send, trace), 3.0,
                          objective_ms=1500.0)
    assert report["offered"] == len(trace)
    assert report["cold_hits"] >= 1, report  # the lazy deploy fast-failed
    assert report["goodput_rps"] <= report["throughput_rps"] + 1e-9

    lanes = (await (await client.get("/admin/slo")).json())["models"]
    cold = lanes["rn_cold"]["predict"]
    assert cold["outcomes"]["shed"] >= 1
    assert cold["windows"]["fast"]["alarm"] is True
    # The fast-fails started rn_cold's activation in the background: let it
    # land before the compile cache's directory goes.
    for _ in range(600):
        models = (await (await client.get("/admin/models")).json())["models"]
        if models["rn_cold"]["state"] != "warming":
            break
        await asyncio.sleep(0.1)
