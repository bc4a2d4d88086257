"""Real-TPU latency lane (SURVEY §4 "TPU smoke/latency tests").

Runs only with ``TPUSERVE_TEST_PLATFORM=tpu`` — the conftest skips
``-m tpu`` tests when the session backend isn't the chip.  These measure the
BASELINE metrics *through the serving stack*: concurrent HTTP load → batcher
→ device → response, asserting the <30 ms p50 device-step targets and that
coalescing actually happens under load.
"""

import asyncio
import time

import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.serving.server import create_app

pytest_plugins = "aiohttp.pytest_plugin"

pytestmark = pytest.mark.tpu

TARGET_MS = 30.0


def _cfg(cache_dir):
    return ServeConfig(
        compile_cache_dir=str(cache_dir),
        warmup_at_boot=True,
        models=[
            ModelConfig(name="resnet50", batch_buckets=(1, 4, 8), coalesce_ms=3.0),
            ModelConfig(name="bert_base", batch_buckets=(1, 4, 8),
                        seq_buckets=(128,), coalesce_ms=3.0),
        ],
    )


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    eng = build_engine(_cfg(tmp_path_factory.mktemp("xla-tpu")))
    yield eng
    eng.shutdown()


@pytest.fixture
async def client(engine, aiohttp_client, tmp_path):
    app = create_app(_cfg(tmp_path), engine=engine)
    return await aiohttp_client(app)


async def _drive(client, route, payloads, concurrency=16):
    """Fire payloads with bounded concurrency; return per-request timing dicts."""
    sem = asyncio.Semaphore(concurrency)
    timings = []

    async def one(payload, headers):
        async with sem:
            t0 = time.perf_counter()
            r = await client.post(route, data=payload, headers=headers)
            body = await r.json()
            assert r.status == 200, body
            t = dict(body["timing"])
            t["wall_ms"] = (time.perf_counter() - t0) * 1000
            timings.append(t)

    await asyncio.gather(*[one(p, h) for p, h in payloads])
    return timings


async def test_resnet50_concurrent_load_meets_target(client):
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(48):
        arr = rng.integers(0, 256, (224, 224, 3), np.uint8)
        reqs.append((_raw_image(arr), {"Content-Type": "application/octet-stream"}))
    # warm the HTTP path once
    await _drive(client, "/v1/models/resnet50:predict", reqs[:2], concurrency=1)
    timings = await _drive(client, "/v1/models/resnet50:predict", reqs)
    device = [t["device_ms"] for t in timings]
    batches = [t["batch_size"] for t in timings]
    p50 = np.percentile(device, 50)
    assert p50 < TARGET_MS, f"device p50 {p50:.2f} ms >= {TARGET_MS} ms target"
    # Under 16-way concurrency the batcher must actually coalesce.
    assert max(batches) > 1, f"no coalescing observed: batches={set(batches)}"
    # e2e sanity: wall time is device + queue + host work.
    wall_p50 = np.percentile([t["wall_ms"] for t in timings], 50)
    assert wall_p50 < 30 * TARGET_MS, f"wall p50 {wall_p50:.1f} ms implausibly slow"


async def test_bert128_concurrent_load_meets_target(client):
    payloads = [(f'{{"text": "the quick brown fox {i} jumps over the lazy dog"}}',
                 {"Content-Type": "application/json"}) for i in range(48)]
    await _drive(client, "/v1/models/bert_base:predict", payloads[:2], concurrency=1)
    timings = await _drive(client, "/v1/models/bert_base:predict", payloads)
    device = [t["device_ms"] for t in timings]
    p50 = np.percentile(device, 50)
    assert p50 < TARGET_MS, (f"BERT device p50 {p50:.2f} ms >= "
                             f"{TARGET_MS} ms target")
    assert max(t["batch_size"] for t in timings) > 1


async def test_metrics_surface_after_load(client):
    r = await client.get("/metrics")
    body = await r.json()
    assert r.status == 200
    for model in ("resnet50", "bert_base"):
        assert model in body["models"]


def test_cold_start_recorded_on_chip(tmp_path):
    """Engine boot on the chip records real compile timings (BASELINE
    cold-start metric); the empty-vs-warm comparison is
    test_cache.py::test_warm_cache_build_is_faster_than_cold."""
    cfg = ServeConfig(compile_cache_dir=str(tmp_path / "xla"), models=[
        ModelConfig(name="resnet50", batch_buckets=(1,))])
    eng = build_engine(cfg, warmup=True)
    try:
        assert eng.cold_start_seconds > 0
        assert len(eng.clock.entries) == 1
        assert eng.clock.total_seconds > 0
    finally:
        eng.shutdown()


@pytest.mark.slow
async def test_sd15_full_job_through_server(aiohttp_client, tmp_path):
    """One FULL 512x512/20-step SD-1.5 image through the async job API on the
    chip (VERDICT r1 item 3): submit → poll → PNG comes back."""
    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"),
        warmup_at_boot=False,  # the one (1,) bucket compiles on first job
        models=[ModelConfig(name="sd15", batch_buckets=(1,),
                            extra={"num_steps": 20, "height": 512, "width": 512})],
    )
    engine = build_engine(cfg, warmup=False)
    try:
        client = await aiohttp_client(create_app(cfg, engine=engine))
        r = await client.post("/v1/models/sd15:submit",
                              json={"prompt": "a photo of a tpu", "seed": 3})
        assert r.status == 202
        job_id = (await r.json())["job"]["id"]
        deadline = time.monotonic() + 600  # param init + compile dominate
        while time.monotonic() < deadline:
            r = await client.get(f"/v1/jobs/{job_id}")
            job = (await r.json())["job"]
            if job["status"] in ("done", "failed"):
                break
            await asyncio.sleep(2.0)
        assert job["status"] == "done", job
        assert job["result"]["format"] == "png"
        assert len(job["result"]["image_b64"]) > 10000
    finally:
        engine.shutdown()


def _raw_image(arr: np.ndarray) -> bytes:
    import io

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="PNG")
    return buf.getvalue()
