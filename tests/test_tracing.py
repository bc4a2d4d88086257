"""Request-trace lifecycle (ISSUE 4): span trees, correlation, reconstruction.

Two layers:

- Unit: the tracer alone — W3C ``traceparent`` round-trip, span parenting,
  span budgets, ring-buffer eviction vs flight-recorder pinning.
- Integration (aiohttp + real CPU engine): the acceptance criterion — a
  slow request made through the public API is fully reconstructable
  offline: its response yields a trace id, ``GET /admin/trace/{id}``
  returns a span tree whose stages tile the measured wall time, the same
  id appears in the structured logs and as an OpenMetrics exemplar, and
  ``tools/tracedump.py`` renders the waterfall.  Error responses on every
  work lane carry ``request_id``/``trace_id``, and the ``tpuserve tail``
  filters resolve them from a log file.
"""

import asyncio
import importlib.util
import io
import json
import logging
import time
from pathlib import Path

import numpy as np
import pytest
from PIL import Image

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.serving.server import Server, create_app
from pytorch_zappa_serverless_tpu.serving.tracing import (
    PHASES, Tracer, format_traceparent, parse_traceparent)

pytest_plugins = "aiohttp.pytest_plugin"


def _tracedump():
    path = Path(__file__).resolve().parents[1] / "tools" / "tracedump.py"
    spec = importlib.util.spec_from_file_location("tpuserve_tracedump", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- unit: traceparent ------------------------------------------------------

def test_traceparent_round_trip():
    tid, sid = "a" * 32, "b" * 16
    header = format_traceparent(tid, sid)
    assert header == f"00-{tid}-{sid}-01"
    assert parse_traceparent(header) == (tid, sid)
    # Case/whitespace tolerated; the id comes back lowercased.
    assert parse_traceparent(f"  00-{tid.upper()}-{sid}-01 ") == (tid, sid)


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-zz-bb-01",
    "00-" + "0" * 32 + "-" + "b" * 16 + "-01",   # all-zero trace id
    "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
    "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",   # reserved version
    "00-" + "a" * 31 + "-" + "b" * 16 + "-01",   # short trace id
])
def test_traceparent_invalid_headers_restart_the_trace(bad):
    assert parse_traceparent(bad) is None


def test_traceparent_ingest_joins_callers_trace():
    tracer = Tracer()
    tid, sid = "c" * 32, "d" * 16
    root = tracer.start("predict", model="m",
                        traceparent=format_traceparent(tid, sid))
    assert root.trace.trace_id == tid
    assert root.trace.remote_parent == sid
    tracer.finish(root.trace, "ok")
    tree = tracer.get(tid).tree()
    assert tree["remote_parent"] == sid
    # An invalid header mints a fresh id instead of failing the request.
    other = tracer.start("predict", traceparent="00-bogus")
    assert other.trace.trace_id != tid and other.trace.remote_parent is None


# -- unit: span parenting + budgets ----------------------------------------

def test_span_parenting_builds_the_tree():
    tracer = Tracer()
    root = tracer.start("predict", model="m", request_id="r1")
    adm = root.child("admission")
    adm.end()
    dev = root.child("device", batch_size=3)
    exec_sp = dev.child("exec", lane="latency")
    exec_sp.end()
    dev.end()
    root.point("retry", attempt=1)
    tracer.finish(root.trace, "ok")

    tree = tracer.get(root.trace.trace_id).tree()
    assert tree["status"] == "ok"
    top = tree["tree"]
    assert top["name"] == "predict"
    names = [c["name"] for c in top["children"]]
    assert names == ["admission", "device", "retry"]  # start-ordered
    device = top["children"][1]
    assert device["attrs"]["batch_size"] == 3
    assert device["children"][0]["name"] == "exec"
    retry = top["children"][2]
    assert retry["duration_ms"] == 0.0  # a decision, not a stage


def test_span_context_manager_records_errors():
    tracer = Tracer()
    root = tracer.start("predict", model="m")
    with pytest.raises(ValueError):
        with root.child("device"):
            raise ValueError("boom")
    tracer.finish(root.trace, "error")
    tree = tracer.get(root.trace.trace_id).tree()
    dev = tree["tree"]["children"][0]
    assert dev["status"] == "error" and "boom" in dev["attrs"]["error"]


def test_span_budget_drops_are_counted_not_raised():
    tracer = Tracer(max_spans=8)
    root = tracer.start("predict", model="m")
    for i in range(20):
        root.child(f"s{i}").end()
    tracer.finish(root.trace, "ok")
    trace = tracer.get(root.trace.trace_id)
    assert len(trace.spans) == 8
    assert trace.dropped_spans == 13  # 1 root + 7 children recorded
    assert tracer.snapshot()["dropped_spans"] == 13


def test_finish_closes_abandoned_spans():
    """An error return mid-stage leaves open spans; finish freezes them so
    the rendered tree stops growing."""
    tracer = Tracer()
    root = tracer.start("predict", model="m")
    root.child("device")  # never ended (e.g. an exception path)
    tracer.finish(root.trace, "error")
    tree1 = tracer.get(root.trace.trace_id).tree()
    time.sleep(0.02)
    tree2 = tracer.get(root.trace.trace_id).tree()
    assert tree1["tree"]["children"][0]["duration_ms"] == \
        tree2["tree"]["children"][0]["duration_ms"]
    assert tree1["duration_ms"] == tree2["duration_ms"]


# -- unit: ring eviction + flight recorder ---------------------------------

def _finished(tracer, model, status="ok", sleep=0.0):
    root = tracer.start("predict", model=model)
    if sleep:
        time.sleep(sleep)
    tracer.finish(root.trace, status)
    return root.trace


def test_ring_eviction_and_flight_recorder_pinning():
    tracer = Tracer(ring=4, flight_slow=1, flight_errors=2)
    slow = _finished(tracer, "m", sleep=0.03)       # slowest for model m
    errored = _finished(tracer, "m", status="error")
    churn = [_finished(tracer, "m") for _ in range(16)]
    # The ring (4 slots, 18 finishes) evicted both long ago, but the
    # flight recorder still resolves them.
    assert {t.trace_id for t in tracer._ring}.isdisjoint(
        {slow.trace_id, errored.trace_id})
    assert tracer.get(slow.trace_id) is slow
    assert tracer.get(errored.trace_id) is errored
    # Evicted AND unpinned healthy traces are genuinely gone.
    assert tracer.get(churn[0].trace_id) is None
    snap = tracer.snapshot()
    assert snap["ring"] == 4 and snap["finished"] == 18
    assert snap["pinned_slow"] == 1 and snap["pinned_errored"] == 1
    # Pin budgets hold: a third error rotates the oldest error out.
    e2 = _finished(tracer, "m", status="error")
    e3 = _finished(tracer, "m", status="error")
    assert tracer.snapshot()["pinned_errored"] == 2
    assert {t.trace_id for t in tracer._errored["m"]} == \
        {e2.trace_id, e3.trace_id}


def test_trace_list_filters():
    tracer = Tracer()
    _finished(tracer, "a")
    _finished(tracer, "b", status="error")
    slow = _finished(tracer, "a", sleep=0.03)
    assert {t["model"] for t in tracer.list()} == {"a", "b"}
    assert all(t["model"] == "a" for t in tracer.list(model="a"))
    errs = tracer.list(status="error")
    assert len(errs) == 1 and errs[0]["model"] == "b"
    by_dur = tracer.list(model="a", min_ms=20.0)
    assert [t["trace_id"] for t in by_dur] == [slow.trace_id]
    assert len(tracer.list(limit=2)) == 2


# -- integration: the public API -------------------------------------------

def _cfg(tmpdir):
    return ServeConfig(
        compile_cache_dir=str(tmpdir),
        trace_dir=str(Path(tmpdir) / "traces"),
        warmup_at_boot=True,
        models=[ModelConfig(name="resnet18", batch_buckets=(1, 4),
                            dtype="float32", coalesce_ms=5.0,
                            extra={"image_size": 64, "resize_to": 72})],
    )


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    eng = build_engine(_cfg(tmp_path_factory.mktemp("xla")))
    yield eng
    eng.shutdown()


@pytest.fixture
async def served(engine, aiohttp_client, tmp_path):
    app = create_app(_cfg(tmp_path), engine=engine)
    client = await aiohttp_client(app)
    yield client
    engine.runner.faults.clear()


class _Capture(logging.Handler):
    """Collect the JSON records the serving loggers emit."""

    def __init__(self):
        super().__init__()
        from pytorch_zappa_serverless_tpu.utils.logging import JsonFormatter

        self.setFormatter(JsonFormatter())
        self.records: list[dict] = []

    def emit(self, record):
        self.records.append(json.loads(self.format(record)))


@pytest.fixture
def server_logs():
    handler = _Capture()
    loggers = [logging.getLogger(n) for n in ("serving.server", "serving.jobs")]
    for lg in loggers:
        lg.addHandler(handler)
    yield handler.records
    for lg in loggers:
        lg.removeHandler(handler)


def _jpeg(seed=0) -> bytes:
    arr = np.random.default_rng(seed).integers(
        0, 255, (80, 100, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG")
    return buf.getvalue()


async def test_slow_request_reconstructs_offline(served):
    """The acceptance criterion, end to end: slow request → trace id on the
    response → span tree tiling the wall time → exemplar → waterfall."""
    client = served
    # Make the request honestly slow: 80 ms of injected dispatch-thread
    # latency (occupies the lane like a slow program would).
    r = await client.post("/admin/faults",
                          json={"model": "resnet18", "latency_ms": 80})
    assert r.status == 200, await r.text()

    t0 = time.perf_counter()
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(),
                          headers={"Content-Type": "image/jpeg"})
    wall_ms = (time.perf_counter() - t0) * 1000
    body = await r.json()
    assert r.status == 200, body
    trace_id = r.headers["X-Trace-Id"]
    assert r.headers["X-Request-Id"]

    # Full span tree via the admin API.
    r = await client.get(f"/admin/trace/{trace_id}")
    payload = await r.json()
    assert r.status == 200, payload
    trace = payload["trace"]
    assert trace["status"] == "ok" and trace["model"] == "resnet18"

    # Stage attribution: the root's direct children tile the request wall —
    # durations sum to within 5% (coverage >= 95%), and the trace total is
    # consistent with the client-measured wall.
    dump = _tracedump()
    att = dump.stage_attribution(payload)
    assert att["coverage_pct"] >= 95.0, att
    assert {"admission", "queue", "device", "respond"} <= set(att["stages"])
    assert att["stages"]["device"] >= 80.0  # the injected slowness is HERE
    assert att["total_ms"] <= wall_ms * 1.05
    assert att["total_ms"] >= body["timing"]["total_ms"] * 0.95

    # The device stage nests the dispatch-thread exec span.
    def find(node, name):
        if node["name"] == name:
            return node
        for c in node.get("children", []):
            hit = find(c, name)
            if hit is not None:
                return hit
        return None

    exec_span = find(trace["tree"], "exec")
    assert exec_span is not None and exec_span["attrs"]["lane"]

    # The waterfall renders and names every stage.
    text = dump.render(payload)
    for stage in ("admission", "queue", "device", "respond"):
        assert stage in text
    assert trace_id in text and "coverage=" in text

    # The same trace id rides the latency histograms as an exemplar.
    r = await client.get("/metrics", params={"format": "prometheus"})
    prom = await r.text()
    assert "tpuserve_device_ms_bucket" in prom
    assert 'trace_id="' in prom
    # /admin/trace lists it (and min_ms filters reach it).
    r = await client.get("/admin/trace", params={"min_ms": 50, "limit": 5})
    listed = await r.json()
    assert any(t["trace_id"] == trace_id for t in listed["traces"])


async def test_error_responses_carry_ids_and_log_them(served, server_logs):
    client = served
    # 404: model not served.
    r = await client.post("/v1/models/nope:predict", data=b"x")
    body = await r.json()
    assert r.status == 404
    assert body["request_id"] and body["trace_id"]
    assert r.headers["X-Trace-Id"] == body["trace_id"]
    # 400: bad payload on a served model.
    r = await client.post("/v1/models/resnet18:predict", data=b"not an image",
                          headers={"Content-Type": "image/jpeg"})
    bad = await r.json()
    assert r.status == 400 and bad["request_id"] and bad["trace_id"]
    # Both emitted a correlated structured log record.
    logged = {rec.get("trace_id") for rec in server_logs
              if rec.get("msg") == "request error"}
    assert {body["trace_id"], bad["trace_id"]} <= logged
    # The errored traces are pinned and queryable with status=error.
    for tid in (body["trace_id"], bad["trace_id"]):
        r = await client.get(f"/admin/trace/{tid}")
        assert r.status == 200
        assert (await r.json())["trace"]["status"] == "error"
    r = await client.get("/admin/trace", params={"status": "error"})
    errored = {t["trace_id"] for t in (await r.json())["traces"]}
    assert {body["trace_id"], bad["trace_id"]} <= errored


async def test_client_traceparent_round_trips_over_http(served):
    client = served
    tid, sid = "f" * 32, "1234567890abcdef"
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(1),
                          headers={"Content-Type": "image/jpeg",
                                   "traceparent": format_traceparent(tid, sid)})
    assert r.status == 200
    assert r.headers["X-Trace-Id"] == tid
    r = await client.get(f"/admin/trace/{tid}")
    trace = (await r.json())["trace"]
    assert trace["remote_parent"] == sid


async def test_job_trace_spans_submit_to_done(served, server_logs):
    """:submit detaches the trace to the job lane: ONE tree covers
    admission → job_queue → run → device/exec → journal, finished at the
    job's terminal state; polls carry the job's trace id."""
    client = served
    r = await client.post("/v1/models/resnet18:submit", data=_jpeg(2),
                          headers={"Content-Type": "image/jpeg"})
    sub = await r.json()
    assert r.status == 202, sub
    trace_id = r.headers["X-Trace-Id"]
    assert sub["job"]["trace_id"] == trace_id
    job_id = sub["job"]["id"]
    for _ in range(200):
        r = await client.get(f"/v1/jobs/{job_id}")
        poll = await r.json()
        if poll["job"]["status"] in ("done", "error"):
            break
        await asyncio.sleep(0.02)
    assert poll["job"]["status"] == "done", poll
    # The poll body correlates: its own request id + the job's trace id.
    assert poll["trace_id"] == trace_id and poll["request_id"]

    r = await client.get(f"/admin/trace/{trace_id}")
    payload = await r.json()
    assert r.status == 200, payload
    tree = payload["trace"]["tree"]
    names = [c["name"] for c in tree["children"]]
    assert "admission" in names and "job_queue" in names and "run" in names
    run = next(c for c in tree["children"] if c["name"] == "run")
    run_children = [c["name"] for c in run.get("children", [])]
    assert "device" in run_children
    assert payload["trace"]["status"] == "ok"
    # The worker's terminal log line carries the same trace id.
    assert any(rec.get("trace_id") == trace_id
               and rec.get("msg") == "job finished" for rec in server_logs)


async def test_generation_trace_spans(aiohttp_client, tmp_path):
    """Generation-lane parenting: queue → prefill → decode (+tick points)
    on the streaming scheduler's trace."""
    arch = {"d_model": 32, "layers": 1, "heads": 2, "ffn_dim": 64,
            "vocab_size": 512, "max_positions": 32}
    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"),
        models=[ModelConfig(name="gpt2", batch_buckets=(1, 2), seq_buckets=(8,),
                            dtype="float32", coalesce_ms=5.0,
                            extra={"max_new_tokens": 4, "arch": arch})])
    engine = build_engine(cfg)
    try:
        client = await aiohttp_client(create_app(cfg, engine=engine))
        r = await client.post("/v1/models/gpt2:generate",
                              json={"text": "hello tpu", "stream": False})
        body = await r.json()
        assert r.status == 200, body
        trace_id = r.headers["X-Trace-Id"]
        r = await client.get(f"/admin/trace/{trace_id}")
        payload = await r.json()
        assert r.status == 200, payload
        names = [c["name"] for c in payload["trace"]["tree"]["children"]]
        assert "queue" in names and "prefill" in names and "decode" in names
        assert payload["trace"]["status"] == "ok"
    finally:
        engine.shutdown()


# -- the scheduler-round timeline (ISSUE 24) ---------------------------------

# Wide and long enough that a round's work dwarfs the microseconds between
# its phases, which a 98% floor would otherwise trip over on a busy CPU.
_GEN_ARCH = {"d_model": 128, "layers": 2, "heads": 2, "ffn_dim": 512,
             "vocab_size": 512, "max_positions": 128}
_GEN_SLOTS = 2
_DISPATCH_PHASES = ("prefill.", "segment.", "round.lane_wait")


def _gen_cfg(tmp_path, kv_cache):
    return ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"),
        models=[ModelConfig(
            name="gpt2", batch_buckets=(1, 2), seq_buckets=(8,),
            dtype="float32", coalesce_ms=1.0, kv_cache=kv_cache,
            kv_block_size=4,
            extra={"max_new_tokens": 96, "gen_slots": _GEN_SLOTS,
                   "segment_tokens": 32, "arch": _GEN_ARCH})])


async def _generate(client, n, max_new=96):
    async def one(i):
        r = await client.post(
            "/v1/models/gpt2:generate",
            json={"input_ids": [3 + i, 5, 7, 11, 13], "stream": True,
                  "max_new_tokens": max_new})
        assert r.status == 200, await r.text()
        final = None
        async for raw in r.content:
            if raw.startswith(b"data: "):
                ev = json.loads(raw[6:])
                if ev.get("done"):
                    final = ev
        assert final is not None
        return r.headers["X-Trace-Id"], final["stats"]
    return await asyncio.gather(*(one(i) for i in range(n)))


async def _gen_counters(client):
    return (await (await client.get("/metrics")).json())["generation"]["gpt2"]


@pytest.mark.parametrize("kv_cache,floor", [("slot", 0.98), ("paged", 0.95)])
async def test_round_phases_tile_busy_rounds(aiohttp_client, tmp_path,
                                             kv_cache, floor):
    """(a) The ten phases tile a busy round and never overlap on one thread;
    (b) their counts are the scheduler's own dispatch counters."""
    cfg = _gen_cfg(tmp_path, kv_cache)
    engine = build_engine(cfg)
    try:
        client = await aiohttp_client(create_app(cfg, engine=engine))
        await _generate(client, 1, max_new=4)  # compiles, outside the count
        before = await _gen_counters(client)
        await _generate(client, 2 * _GEN_SLOTS)
        after = await _gen_counters(client)
        prom = await (await client.get(
            "/metrics", params={"format": "prometheus"})).text()
        r = await client.get("/admin/trace?rounds=256&model=gpt2")
        rounds = (await r.json())["rounds"]["gpt2"]
    finally:
        engine.shutdown()

    def delta(phase):
        return (after["host_phases"][phase]["count"]
                - before["host_phases"][phase]["count"])

    assert delta("segment.launch") == delta("segment.fetch") \
        == after["segment_rounds"] - before["segment_rounds"] > 0
    if kv_cache == "slot":
        # Twice the slots' worth of requests: with one pending and no slot
        # free, a fetch launches the next segment itself.
        assert 0 < after["chained_rounds"] - before["chained_rounds"] \
            <= after["segment_rounds"] - before["segment_rounds"]
        for key in ("chained_rounds", "segment_rounds"):
            assert f'tpuserve_{key}_total{{model="gpt2"}} {after[key]}' \
                in prom
    assert set(PHASES) == set(after["host_phases"])
    dispatches = "prefill_dispatches" if kv_cache == "slot" \
        else "device_rounds"
    prefills = delta("prefill.launch")
    assert prefills == delta("prefill.fetch") > 0
    if kv_cache == "slot":
        assert prefills == after[dispatches] - before[dispatches]
    else:  # every device round is a chunk or a segment here
        assert prefills + delta("segment.launch") \
            == after[dispatches] - before[dispatches]
    assert delta("round.lane_wait") == delta("round.wakeup") \
        == prefills + delta("segment.launch")
    assert sum(v["count"] for v in after["lane_wait"].values()) \
        == after["host_phases"]["round.lane_wait"]["count"]

    shares = []
    for this, nxt in zip(rounds, rounds[1:]):
        phases = this["phases"]
        names = [p["phase"] for p in phases]
        if "round.idle" in names or "segment.launch" not in names:
            continue  # the lane went quiet in this round
        t0 = next(p["t0_ns"] for p in phases
                  if p["phase"] == "round.admit_host")
        t1 = next(p["t0_ns"] for p in nxt["phases"]
                  if p["phase"] == "round.admit_host")
        for on_dispatch in (True, False):
            line = sorted((p["t0_ns"], p["t1_ns"]) for p in phases
                          if p["phase"].startswith(_DISPATCH_PHASES)
                          == on_dispatch)
            for (_, end), (start, _) in zip(line, line[1:]):
                assert start >= end, (this["round"], line)
        assert all(t0 <= p["t0_ns"] and p["t1_ns"] <= t1 for p in phases)
        shares.append(sum(p["t1_ns"] - p["t0_ns"] for p in phases)
                      / (t1 - t0))
    assert len(shares) >= 3, [r["round"] for r in rounds]
    # The median round: a thread the machine preempted between two phases
    # stretches the rounds it struck, in which nothing of the program ran,
    # and says nothing of what the phases leave uncovered in the others.
    shares.sort()
    assert shares[len(shares) // 2] >= floor, shares


@pytest.mark.parametrize("kv_cache", ["slot", "paged"])
async def test_a_first_use_carries_the_round_of_the_launch_that_compiled(
        aiohttp_client, tmp_path, kv_cache):
    """Both schedulers book a program's first use through the same scope, the
    launch phase that held the compile: the entry's ``round`` names a round
    of the ring with that launch in it, its ``launch_s`` is that phase's own
    interval, and ``/metrics`` sums the same entries.  The ring itself
    carries no copy of the entry (ISSUE 42: nothing reads one there)."""
    cfg = _gen_cfg(tmp_path, kv_cache)
    cfg.warmup_at_boot = False  # the lane's own programs alone
    engine = build_engine(cfg)
    try:
        client = await aiohttp_client(create_app(cfg, engine=engine))
        await _generate(client, 1, max_new=4)
        counters = await _gen_counters(client)
        r = await client.get("/admin/trace?rounds=256&model=gpt2")
        rounds = {rnd["round"]: rnd["phases"]
                  for rnd in (await r.json())["rounds"]["gpt2"]}
        entries = engine.clock.snapshot()
    finally:
        engine.shutdown()
    launch_of = {"prefill": "prefill.launch",
                 "prefill_chunk": "prefill.launch",
                 "segment": "segment.launch"}
    want = {"slot": ["prefill", "segment"],
            "paged": ["prefill_chunk", "segment"]}[kv_cache]
    assert [e["program"] for e in entries] == want
    for e in entries:
        assert (e["model"], e["outcome"], e["cause"]) == ("gpt2", "miss",
                                                          "first")
        assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["backend_s"] > 0
        assert (e["trace_s"] + e["lower_s"] + e["cache_read_s"]
                + e["backend_s"]) <= e["launch_s"]
        walls = [(p["t1_ns"] - p["t0_ns"]) / 1e9 for p in rounds[e["round"]]
                 if p["phase"] == launch_of[e["program"]]]
        assert e["launch_s"] in walls, (e, walls)
    assert not any("first_use" in p for ps in rounds.values() for p in ps)
    programs = counters["programs"]
    assert programs["first_uses"] == len(want)
    assert programs["backend_miss_s"] > 0 == programs["backend_hit_s"]
    assert programs["launch_s"] == pytest.approx(
        sum(e["launch_s"] for e in entries), abs=1e-5)


@pytest.mark.parametrize("kv_cache", ["slot", "paged"])
async def test_request_stamps_tile_time_to_first_token(aiohttp_client,
                                                       tmp_path, kv_cache):
    """(c) round_wait + slot_wait + prefill + first_emit is the server's
    whole time to the first token, and only the requests beyond the slot
    count waited for a slot; (d) ``queue`` ends where ``prefill`` starts."""
    cfg = _gen_cfg(tmp_path, kv_cache)
    engine = build_engine(cfg)
    try:
        server = Server(cfg, engine=engine)
        client = await aiohttp_client(server.app)
        await _generate(client, 1, max_new=4)
        sched = server.schedulers["gpt2"]
        seen = []
        submit = sched.submit

        def spy(*a, **kw):
            seen.append(submit(*a, **kw))
            return seen[-1]

        sched.submit = spy
        out = await _generate(client, 2 * _GEN_SLOTS)
        trees = [(await (await client.get(f"/admin/trace/{tid}")).json())
                 ["trace"]["tree"] for tid, _ in out]
    finally:
        engine.shutdown()
    assert len(seen) == 2 * _GEN_SLOTS
    for req in seen:
        st = req.timing_stats()
        legs = (st["round_wait_ms"] + st["slot_wait_ms"] + st["prefill_ms"]
                + st["first_emit_ms"])
        assert abs(legs - (req.first_token_at - req.submitted) * 1e3) < 1.0
        assert min(st.values()) >= 0.0, st
        assert st["ingest_ms"] > 0 and st["egress_ms"] > 0
    for _, stats in out:  # the done event carries the same six legs
        assert {"ingest_ms", "round_wait_ms", "slot_wait_ms", "prefill_ms",
                "first_emit_ms", "egress_ms"} <= set(stats), stats
    # Submitted together, they are seen at one loop top: the first
    # _GEN_SLOTS get a slot there, the others wait rounds for one.
    waits = sorted(req.timing_stats()["slot_wait_ms"] for req in seen)
    assert waits[:_GEN_SLOTS] == [0.0] * _GEN_SLOTS, waits
    assert all(w > 0 for w in waits[_GEN_SLOTS:]), waits
    for tree in trees:
        kids = {c["name"]: c for c in tree["children"]}
        queue, prefill = kids["queue"], kids["prefill"]
        assert abs(queue["start_ms"] + queue["duration_ms"]
                   - prefill["start_ms"]) < 0.01, (queue, prefill)
        assert prefill["attrs"]["round"] >= 1
        assert "decode" in kids


async def test_admin_profile_capture(served):
    """POST /admin/profile: a timed jax.profiler capture classified through
    utils/xplane.py — the device-level escalation of a slow trace.  On the
    CPU backend the capture may classify to zero ops; the endpoint still
    answers with the capture location instead of failing."""
    client = served
    r = await client.post("/admin/profile", json={"seconds": "nope"})
    assert r.status == 400
    r = await client.post("/admin/profile", json={"seconds": 1e9})
    assert r.status == 400

    async def load():
        for i in range(3):
            await client.post("/v1/models/resnet18:predict", data=_jpeg(i),
                              headers={"Content-Type": "image/jpeg"})

    task = asyncio.ensure_future(load())
    r = await client.post("/admin/profile", json={"seconds": 0.3, "top": 5})
    await task
    body = await r.json()
    assert r.status == 200, body
    assert body["seconds"] == 0.3 and "ops" in body
    assert Path(body["dir"]).is_dir()


# -- satellite: tpuserve tail --trace/--grep --------------------------------

def test_cli_tail_trace_and_grep_filters(tmp_path, capsys):
    from pytorch_zappa_serverless_tpu.cli import main as cli_main

    tid = "a1" * 16
    path = tmp_path / "serve.log"
    recs = [
        {"ts": 1700000000.0, "level": "info", "logger": "serving.server",
         "msg": "request error", "trace_id": tid, "status": 504},
        {"ts": 1700000001.0, "level": "info", "logger": "serving.jobs",
         "msg": "job finished", "trace_id": "ff" * 16},
        {"ts": 1700000002.0, "level": "info", "logger": "serving.server",
         "msg": "profile captured"},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")

    assert cli_main(["tail", str(path), "--trace", tid]) == 0
    out = capsys.readouterr().out
    assert "request error" in out and f'"{tid}"' in out
    assert "job finished" not in out and "profile captured" not in out

    assert cli_main(["tail", str(path), "--grep", "profile"]) == 0
    out = capsys.readouterr().out
    assert "profile captured" in out and "request error" not in out

    # Filters compose: --trace narrows a --grep stream.
    assert cli_main(["tail", str(path), "--grep", "finished",
                     "--trace", tid]) == 0
    assert "job finished" not in capsys.readouterr().out

    # Missing file is a clean exit code 2, not a traceback.
    assert cli_main(["tail", str(tmp_path / "nope.log")]) == 2


@pytest.mark.parametrize("module", ["serving.acceptors", "serving.fleet",
                                    "serving.tracing"])
def test_jax_free_processes_stay_jax_free(module):
    """The acceptor workers and the fleet router import ``serving/tracing``
    for its request ids; its scope for the compile listeners
    (``utils/scope.py``) must not bring jax or the engine with it."""
    import subprocess
    import sys

    code = (f"import sys, pytorch_zappa_serverless_tpu.{module}; "
            "print(sorted(m for m in sys.modules if m == 'jax' "
            "or m.endswith('.engine.cache')))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=Path(__file__).resolve().parents[1],
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "[]"
