"""End-to-end HTTP integration: aiohttp client → batcher → engine → response.

The fake-backend integration test from SURVEY §4: full request path on the CPU
backend with a tiny ResNet config, golden behavior checks, and the error
surface (404/400/429/503 paths).
"""

import asyncio
import io

import numpy as np
import pytest
from PIL import Image

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.serving.server import create_app

pytest_plugins = "aiohttp.pytest_plugin"


def _cfg(tmpdir):
    return ServeConfig(
        compile_cache_dir=str(tmpdir),
        warmup_at_boot=True,
        models=[ModelConfig(name="resnet18", batch_buckets=(1, 4), dtype="float32",
                            coalesce_ms=5.0,
                            extra={"image_size": 64, "resize_to": 72})],
    )


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    eng = build_engine(_cfg(tmp_path_factory.mktemp("xla")))
    yield eng
    eng.shutdown()


@pytest.fixture
async def client(engine, aiohttp_client, tmp_path):
    app = create_app(_cfg(tmp_path), engine=engine)
    return await aiohttp_client(app)


def _jpeg(seed=0) -> bytes:
    arr = np.random.default_rng(seed).integers(0, 255, (80, 100, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG")
    return buf.getvalue()


async def test_root_and_health(client):
    r = await client.get("/")
    body = await r.json()
    assert r.status == 200 and body["models"] == ["resnet18"]
    r = await client.get("/healthz")
    body = await r.json()
    assert r.status == 200 and body["device_ok"]
    assert body["models"]["resnet18"]["buckets_compiled"] == 2


async def test_healthz_names_the_device(client):
    """A host serving from the CPU must say so: the device block carries
    what JAX reports, next to (not instead of) ``device_ok``."""
    import jax

    body = await (await client.get("/healthz")).json()
    assert body["device"] == {"platform": "cpu",
                              "kind": jax.devices()[0].device_kind,
                              "count": len(jax.devices())}


async def test_predict_image_bytes(client):
    r = await client.post("/v1/models/resnet18:predict", data=_jpeg(),
                          headers={"Content-Type": "image/jpeg"})
    body = await r.json()
    assert r.status == 200, body
    top = body["predictions"]["top_k"]
    assert len(top) == 5 and top[0]["prob"] >= top[-1]["prob"]
    assert "queue_ms" in body["timing"] and "X-Device-Ms" in r.headers


async def test_reference_compatible_alias_routes(client):
    for route in ("/predict", "/classify"):
        r = await client.post(route, data=_jpeg(1),
                              headers={"Content-Type": "image/jpeg"})
        assert r.status == 200, await r.text()


async def test_concurrent_requests_coalesce_into_batches(client, engine):
    before = engine.runner.stats.get("resnet18")
    before_batches = before.batches if before else 0
    before_samples = before.samples if before else 0
    jpeg = _jpeg(2)

    async def one():
        r = await client.post("/v1/models/resnet18:predict", data=jpeg,
                              headers={"Content-Type": "image/jpeg"})
        assert r.status == 200
        return (await r.json())["timing"]["batch_size"]

    sizes = await asyncio.gather(*[one() for _ in range(8)])
    st = engine.runner.stats["resnet18"]
    assert st.samples - before_samples == 8
    # Coalescing must have produced at least one multi-request batch and
    # strictly fewer dispatches than requests.
    assert max(sizes) > 1
    assert st.batches - before_batches < 8


async def test_error_surface(client):
    r = await client.post("/v1/models/nope:predict", data=b"x")
    assert r.status == 404 and "available" in (await r.json())["error"]
    r = await client.post("/v1/models/resnet18:predict", data=b"not an image",
                          headers={"Content-Type": "image/jpeg"})
    assert r.status == 400
    r = await client.get("/v1/jobs/doesnotexist")
    assert r.status == 404


async def test_async_job_roundtrip(client):
    r = await client.post("/v1/models/resnet18:submit", data=_jpeg(3),
                          headers={"Content-Type": "image/jpeg"})
    assert r.status == 202
    job_id = (await r.json())["job"]["id"]
    for _ in range(100):
        r = await client.get(f"/v1/jobs/{job_id}")
        job = (await r.json())["job"]
        if job["status"] in ("done", "error"):
            break
        await asyncio.sleep(0.05)
    assert job["status"] == "done", job
    assert len(job["result"]["top_k"]) == 5


async def test_metrics_populated(client):
    await client.post("/v1/models/resnet18:predict", data=_jpeg(4),
                      headers={"Content-Type": "image/jpeg"})
    r = await client.get("/metrics")
    m = await r.json()
    ring = m["models"]["resnet18"]
    assert ring["requests"] >= 1 and "total_ms" in ring
    assert m["runner"]["resnet18"]["batches"] >= 1
    assert m["cold_start"]["seconds"] > 0


async def test_metrics_prometheus_text(client):
    """Content-negotiated Prometheus exposition: scrapeable text/plain with
    the same numbers; JSON default unchanged (VERDICT r2 #9)."""
    await client.post("/v1/models/resnet18:predict", data=_jpeg(5),
                      headers={"Content-Type": "image/jpeg"})
    r = await client.get("/metrics", headers={"Accept": "text/plain"})
    assert r.status == 200 and r.content_type == "text/plain"
    text = await r.text()
    assert '# TYPE tpuserve_requests_total counter' in text
    assert 'tpuserve_requests_total{model="resnet18"} ' in text
    assert 'tpuserve_total_latency_ms{model="resnet18",quantile="0.5"} ' in text
    assert 'tpuserve_compiled_buckets{model="resnet18",state="compiled"} 2' in text
    assert '# TYPE tpuserve_cold_start_seconds gauge' in text
    # Every non-comment line is NAME{labels} VALUE with a float-parsable value.
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            float(line.rsplit(" ", 1)[1])
    # ?format=prometheus works without the header; default stays JSON.
    r = await client.get("/metrics", params={"format": "prometheus"})
    assert r.content_type == "text/plain"
    r = await client.get("/metrics")
    assert r.content_type == "application/json"


async def test_instances_batch_predict(client):
    """{"instances": [...]} carries N inputs in one request: per-instance
    predictions in order, co-batched on the device."""
    import base64
    import json as _json

    body = _json.dumps({"instances": [{"b64": base64.b64encode(_jpeg(i)).decode()}
                                      for i in range(3)]})
    r = await client.post("/v1/models/resnet18:predict", data=body,
                          headers={"Content-Type": "application/json"})
    out = await r.json()
    assert r.status == 200, out
    preds = out["predictions"]
    assert isinstance(preds, list) and len(preds) == 3
    for p in preds:
        assert len(p["top_k"]) == 5
    assert out["timing"]["samples"] == 3
    # All three admitted atomically and arriving together: one device batch.
    assert out["timing"]["batch_size"] >= 3
    # Distinct images should not all produce identical top-1 rankings (they
    # are random noise through a random net, but routed per-instance).
    assert preds[0]["top_k"][0]["prob"] != preds[1]["top_k"][0]["prob"]


async def test_instances_empty_list_rejected(client):
    r = await client.post("/v1/models/resnet18:predict", json={"instances": []})
    assert r.status == 400
    r = await client.post("/v1/models/resnet18:predict",
                          json={"instances": "nope"})
    assert r.status == 400


async def test_gpt2_http_generation(aiohttp_client, tmp_path):
    """Text generation through the full HTTP stack: text in, tokens out,
    sampling knobs honored per request."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

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
        r = await client.post("/v1/models/gpt2:predict",
                              json={"text": "hello tpu world"})
        body = await r.json()
        assert r.status == 200, body
        greedy = body["predictions"]["tokens"]
        assert isinstance(greedy, list) and len(greedy) <= 4

        # Same text again: deterministic (greedy default).
        r = await client.post("/v1/models/gpt2:predict",
                              json={"text": "hello tpu world"})
        assert (await r.json())["predictions"]["tokens"] == greedy

        # Sampling knobs ride per request; same compiled program (no new
        # bucket compiles — warmup covered them all).
        r = await client.post("/v1/models/gpt2:predict",
                              json={"text": "hello tpu world",
                                    "temperature": 5.0, "seed": 11})
        body = await r.json()
        assert r.status == 200, body
        assert len(body["predictions"]["tokens"]) <= 4
    finally:
        engine.shutdown()


async def test_models_discovery_endpoint(client):
    r = await client.get("/v1/models")
    body = await r.json()
    assert r.status == 200
    m = body["models"]["resnet18"]
    assert m["buckets"] == [[1], [4]]
    assert m["buckets_compiled"] == 2
    assert m["endpoint"] == "/v1/models/resnet18:predict"
    assert m["async_only"] is False and m["checkpoint"] == "random-init"
