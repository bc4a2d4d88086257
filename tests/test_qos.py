"""Mixed-workload QoS: priority dispatch lanes + preemptible chunked sd15.

The ISSUE-1 acceptance triplet, on the CPU harness:

1. a latency-class dispatch enqueued behind queued throughput work runs
   first (two-level pool ordering);
2. chunked sd15 (5x4 steps) matches the monolithic 20-step scan
   numerically;
3. a latency request submitted mid-sd15-image waits at most one chunk,
   not the full image (preemption points between device calls).

Plus the satellite surface: every registered model declares a latency
class, job coalescing is capped on mixed engines, lane stats reach
/metrics, and whisper's :predict lane declines sampling knobs loudly.
"""

import asyncio
import threading

import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.engine.runner import (LANE_LATENCY,
                                                        LANE_THROUGHPUT,
                                                        _DaemonDispatchPool)

pytest_plugins = "aiohttp.pytest_plugin"


def _tiny_sd15(**extra):
    return ModelConfig(
        name="sd15", dtype="float32", batch_buckets=(1,),
        extra={"variant": "tiny", "height": 64, "width": 64,
               "num_steps": 20, "chunk_steps": 4, **extra})


def _tiny_resnet(buckets=(1,)):
    return ModelConfig(name="resnet18", batch_buckets=buckets,
                       dtype="float32",
                       extra={"image_size": 64, "resize_to": 72})


@pytest.fixture(scope="module")
def qos_engine(tmp_path_factory):
    """One engine serving a latency model beside chunked tiny sd15: the
    mixed-workload co-residency of docs/QOS.md at tiny scale."""
    cfg = ServeConfig(compile_cache_dir=str(tmp_path_factory.mktemp("xla")),
                      warmup_at_boot=True,
                      models=[_tiny_sd15(), _tiny_resnet()])
    eng = build_engine(cfg)
    yield eng
    eng.shutdown()


# ---------------------------------------------------------------------------
# Latency-class declarations (satellite: every registered model declares one)
# ---------------------------------------------------------------------------

def test_every_registered_model_declares_latency_class():
    from pytorch_zappa_serverless_tpu import models as _zoo  # noqa: F401
    from pytorch_zappa_serverless_tpu.utils.registry import (
        LATENCY_CLASSES, get_latency_class, list_models)

    names = list_models()
    assert names, "registry is empty"
    for name in names:
        assert get_latency_class(name) in LATENCY_CLASSES, name
    # The BASELINE split: interactive endpoints are latency class, the async
    # job endpoint is throughput class.
    assert get_latency_class("sd15") == "throughput"
    for name in ("resnet50", "bert_base", "gpt2", "whisper_tiny"):
        assert get_latency_class(name) == "latency"


def test_config_override_and_validation(qos_engine, tmp_path):
    assert qos_engine.model("sd15").latency_class == "throughput"
    assert qos_engine.model("resnet18").latency_class == "latency"
    # Config override wins over the registered class; junk is rejected.
    from pytorch_zappa_serverless_tpu.engine.compiled import CompiledModel

    cm = qos_engine.model("resnet18")
    import dataclasses

    cfg = dataclasses.replace(cm.cfg, latency_class="throughput")
    assert CompiledModel(cm.servable, cfg).latency_class == "throughput"
    with pytest.raises(ValueError, match="latency_class"):
        CompiledModel(cm.servable, dataclasses.replace(cm.cfg,
                                                       latency_class="vip"))


# ---------------------------------------------------------------------------
# (1) Priority ordering on the dispatch pool
# ---------------------------------------------------------------------------

def _blocked_pool():
    """Pool whose dispatch thread is parked inside a gated item, so the test
    controls exactly what is queued when the gate opens."""
    pool = _DaemonDispatchPool("test-dispatch")
    running, gate = threading.Event(), threading.Event()

    def block():
        running.set()
        assert gate.wait(timeout=10)

    blocker = pool.submit_lane(LANE_THROUGHPUT, block)
    assert running.wait(timeout=10)
    return pool, gate, blocker


def test_latency_dispatch_jumps_queued_throughput_work():
    pool, gate, blocker = _blocked_pool()
    try:
        order = []
        t = pool.submit_lane(LANE_THROUGHPUT, order.append, "throughput")
        l = pool.submit_lane(LANE_LATENCY, order.append, "latency")
        stats = pool.stats_snapshot()
        assert stats[LANE_LATENCY]["depth"] == 1
        assert stats[LANE_THROUGHPUT]["depth"] == 1  # blocker already popped
        gate.set()
        blocker.result(timeout=10)
        l.result(timeout=10)
        t.result(timeout=10)
        # Enqueued AFTER the throughput item, ran BEFORE it.
        assert order == ["latency", "throughput"]
        stats = pool.stats_snapshot()
        assert stats[LANE_LATENCY]["dispatches"] == 1
        assert stats[LANE_LATENCY]["wait_ms_max"] > 0
    finally:
        pool.shutdown(cancel_futures=True)


def test_fifo_mode_preserves_arrival_order():
    """priority_dispatch: false is strict cross-lane FIFO by enqueue
    sequence."""
    pool, gate, blocker = _blocked_pool()
    try:
        pool.priority_enabled = False
        order = []
        t = pool.submit_lane(LANE_THROUGHPUT, order.append, "throughput")
        l = pool.submit_lane(LANE_LATENCY, order.append, "latency")
        gate.set()
        blocker.result(timeout=10)
        t.result(timeout=10)
        l.result(timeout=10)
        assert order == ["throughput", "latency"]
    finally:
        pool.shutdown(cancel_futures=True)


# ---------------------------------------------------------------------------
# (2) Chunked sd15 output parity
# ---------------------------------------------------------------------------

async def test_chunked_5x4_matches_monolithic_20_step_scan(qos_engine):
    cm = qos_engine.model("sd15")
    ch = cm.servable.meta["chunked"]
    assert ch["num_chunks"] == 5 and ch["steps_per_chunk"] == 4
    sample = cm.servable.preprocess({"prompt": "a red fox", "seed": 7})
    [mono] = qos_engine.runner.run_sync(cm, [sample])
    [chunked] = await qos_engine.runner.run_chunked(cm, [sample])
    # Same scan body run in slices with device-carried latents: at fp32 the
    # op sequence is identical, so allow at most off-by-one uint8 rounding.
    diff = np.abs(mono["pixels"].astype(int) - chunked["pixels"].astype(int))
    assert diff.max() <= 1, f"max pixel diff {diff.max()}"
    st = qos_engine.runner.stats["sd15"]
    assert st.chunks >= ch["num_chunks"]


# ---------------------------------------------------------------------------
# (3) Preemption: latency work waits at most one chunk, not the image
# ---------------------------------------------------------------------------

async def test_latency_request_preempts_between_chunks(qos_engine):
    cm = qos_engine.model("sd15")
    runner = qos_engine.runner
    ch = cm.servable.meta["chunked"]
    orig_chunk = ch["chunk"]
    order: list[str] = []           # appended only from the dispatch thread
    started, release = threading.Event(), threading.Event()

    def gated(p, state, rows):
        first = not started.is_set()
        started.set()
        if first:
            # Hold the dispatch thread INSIDE chunk 1 so the test submits
            # latency work mid-image deterministically.
            assert release.wait(timeout=30)
        out = orig_chunk(p, state, rows)
        order.append("chunk")
        return out

    ch["chunk"] = gated
    try:
        sample = cm.servable.preprocess({"prompt": "a tpu", "seed": 1})
        image_task = asyncio.ensure_future(runner.run_chunked(cm, [sample]))
        loop = asyncio.get_running_loop()
        await loop.run_in_executor(None, started.wait)
        # The image is mid-flight (chunk 1 of 5 on the device).  A latency
        # dispatch submitted NOW must run after that chunk, not after the
        # remaining four.
        latency_task = asyncio.ensure_future(
            runner.run_fn(lambda: order.append("latency")))
        await asyncio.sleep(0)  # let run_fn enqueue before opening the gate
        release.set()
        await latency_task
        assert not image_task.done(), \
            "latency work finished while the image still had chunks left"
        [result] = await image_task
        assert result["pixels"].shape == (64, 64, 3)
        # One chunk before the latency dispatch, the other four after.
        assert order.index("latency") == 1, order
        assert order.count("chunk") == 5, order
    finally:
        ch["chunk"] = orig_chunk


# ---------------------------------------------------------------------------
# Mixed-engine job coalescing cap
# ---------------------------------------------------------------------------

def test_job_coalescing_capped_when_latency_models_coresident(tmp_path):
    from pytorch_zappa_serverless_tpu.serving.server import Server

    mixed = ServeConfig(compile_cache_dir=str(tmp_path / "a"), models=[
        _tiny_sd15(num_steps=2, chunk_steps=0), _tiny_resnet()])
    mixed.models[0].batch_buckets = (1, 4)
    eng = build_engine(mixed, warmup=False)
    try:
        s = Server(mixed, engine=eng)
        # Co-resident latency models: coalescing off by default...
        assert s._job_batch_of("sd15") == 1
        # ...operator can trade tail latency back for job throughput...
        eng.model("sd15").cfg.extra["job_batch_mixed_cap"] = 3
        assert s._job_batch_of("sd15") == 3
        # ...and latency-class models are never capped.
        assert s._job_batch_of("resnet18") == 1  # its own max_batch
    finally:
        eng.shutdown()

    solo = ServeConfig(compile_cache_dir=str(tmp_path / "b"),
                       models=[_tiny_sd15(num_steps=2, chunk_steps=0)])
    solo.models[0].batch_buckets = (1, 4)
    eng2 = build_engine(solo, warmup=False)
    try:
        # Dedicated sd15 deployment: full coalescing as before.
        assert Server(solo, engine=eng2)._job_batch_of("sd15") == 4
    finally:
        eng2.shutdown()


# ---------------------------------------------------------------------------
# Lane stats on /metrics
# ---------------------------------------------------------------------------

def test_metrics_expose_dispatch_lanes(qos_engine):
    from pytorch_zappa_serverless_tpu.serving.metrics import MetricsHub

    hub = MetricsHub()
    m = hub.render(qos_engine)
    lanes = m["dispatch"]["lanes"]
    assert m["dispatch"]["priority_enabled"] is True
    for lane in ("latency", "throughput"):
        for key in ("depth", "dispatches", "wait_ms_total", "wait_ms_max",
                    "wait_ms_mean"):
            assert key in lanes[lane], (lane, key)
    # The qos_engine fixtures above dispatched on both lanes.
    assert lanes["throughput"]["dispatches"] >= 1
    text = hub.render_prometheus(qos_engine)
    assert 'tpuserve_dispatch_queue_depth{lane="latency"}' in text
    assert 'tpuserve_dispatch_total{lane="throughput"}' in text
    assert "tpuserve_chunk_dispatches_total" in text


# ---------------------------------------------------------------------------
# Mixed-load HTTP integration (heavier: full server + job stream)
# ---------------------------------------------------------------------------

@pytest.mark.slow
async def test_http_mixed_load_latency_beside_sd15_jobs(qos_engine,
                                                        aiohttp_client,
                                                        tmp_path):
    """Predicts stay green while a chunked sd15 job occupies the engine."""
    import io

    from PIL import Image

    from pytorch_zappa_serverless_tpu.serving.server import create_app

    cfg = ServeConfig(compile_cache_dir=str(tmp_path),
                      models=[_tiny_sd15(), _tiny_resnet()])
    client = await aiohttp_client(create_app(cfg, engine=qos_engine))
    buf = io.BytesIO()
    Image.fromarray(np.zeros((64, 64, 3), np.uint8)).save(buf, format="PNG")
    png = buf.getvalue()

    r = await client.post("/v1/models/sd15:submit", json={"prompt": "x"})
    assert r.status == 202
    job_id = (await r.json())["job"]["id"]
    for _ in range(8):
        r = await client.post("/v1/models/resnet18:predict", data=png,
                              headers={"Content-Type": "image/png"})
        assert r.status == 200, await r.text()
    for _ in range(400):
        r = await client.get(f"/v1/jobs/{job_id}")
        job = (await r.json())["job"]
        if job["status"] in ("done", "error"):
            break
        await asyncio.sleep(0.05)
    assert job["status"] == "done", job
    r = await client.get("/metrics")
    m = await r.json()
    assert m["dispatch"]["lanes"]["throughput"]["dispatches"] >= 1
    assert m["runner"]["sd15"]["chunks"] >= 5


# ---------------------------------------------------------------------------
# Whisper :predict declines sampling knobs (satellite, ADVICE r5)
# ---------------------------------------------------------------------------

async def test_whisper_predict_rejects_sampling_knobs(aiohttp_client,
                                                      tmp_path):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    arch = {"d_model": 32, "encoder_layers": 1, "decoder_layers": 1,
            "heads": 2, "ffn_dim": 64, "vocab_size": 64,
            "source_positions": 1500, "target_positions": 96}
    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path), warmup_at_boot=False,
        models=[ModelConfig(name="whisper_tiny", batch_buckets=(1,),
                            dtype="float32",
                            extra={"max_new_tokens": 4, "arch": arch})])
    eng = build_engine(cfg, warmup=False)
    try:
        client = await aiohttp_client(create_app(cfg, engine=eng))
        audio = [0.0] * 1600
        r = await client.post("/v1/models/whisper_tiny:predict",
                              json={"array": audio, "temperature": 0.7})
        assert r.status == 400
        err = (await r.json())["error"]
        assert "temperature" in err and ":generate" in err
        # The batch API declines per instance the same way.
        r = await client.post(
            "/v1/models/whisper_tiny:predict",
            json={"instances": [{"array": audio, "top_p": 0.9}]})
        assert r.status == 400
        assert "top_p" in (await r.json())["error"]
    finally:
        eng.shutdown()
