"""Perf plane (ISSUE 14): continuous profiling and ingest attribution.

Two layers:

- Unit: the loop-lag sampler with an injectable clock (deterministic lag
  detection), the stack sampler's top-K bounding/eviction with injected
  frames, the ingest histogram registry, the rolling gauge windows, and the
  peaks table a live MFU gauge is read against.
- Integration: a real booted CPU server — GET /admin/perf carries loop
  lag, ingest stages for a served request (which tile >= 95% of its trace
  beside the stage chain), and the split ttft/itl histograms ride
  gen_snapshot + /metrics; the `tpuserve perf` table renders the payload.
"""

import asyncio
import base64
import io
import json
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest
from PIL import Image

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.serving.perfplane import (
    INGEST_STAGES, LoopLagSampler, PerfPlane, StackSampler, hist_quantile)

pytest_plugins = "aiohttp.pytest_plugin"

REPO = Path(__file__).resolve().parents[1]


# -- unit: loop-lag sampler --------------------------------------------------

def test_loop_lag_sampler_detects_injected_lag():
    now = [100.0]
    lag = LoopLagSampler(interval_s=0.25, clock=lambda: now[0])
    lag.arm()
    now[0] += 0.25  # on time
    assert lag.note() == pytest.approx(0.0)
    now[0] += 0.25 + 0.180  # something held the loop 180 ms
    assert lag.note() == pytest.approx(180.0)
    now[0] += 0.25 + 0.030
    assert lag.note() == pytest.approx(30.0)
    snap = lag.snapshot()
    assert snap["ticks"] == 3
    assert snap["max_ms"] == pytest.approx(180.0)
    assert snap["last_ms"] == pytest.approx(30.0)
    assert snap["hist"]["count"] == 3
    # The histogram's p99 estimate lands in the right decade.
    assert 100.0 <= hist_quantile(snap["hist"], 0.99) <= 250.0
    # An early tick never records negative lag.
    now[0] += 0.01
    assert lag.note() == 0.0


async def test_loop_lag_sampler_ticks_on_a_real_loop():
    lag = LoopLagSampler(interval_s=0.02)
    lag.start(asyncio.get_running_loop())
    try:
        await asyncio.sleep(0.1)
    finally:
        lag.stop()
    assert lag.ticks >= 2
    assert lag.hist.count == lag.ticks


# -- unit: stack sampler -----------------------------------------------------

def _fake_frame(stack):
    """Innermost frame of a fake stack described outermost-first."""
    frame = None
    for fname, func in stack:
        frame = SimpleNamespace(
            f_code=SimpleNamespace(co_filename=fname, co_name=func),
            f_back=frame)
    return frame


def test_stack_sampler_aggregates_and_bounds_topk():
    frames = {"current": {}}
    sampler = StackSampler(topk=3, frames=lambda: frames["current"])
    hot = _fake_frame([("/srv/app.py", "loop"), ("/srv/app.py", "hot")])
    for i in range(10):
        frames["current"] = {1: hot}
        sampler.sample_once(0.1)
    # 9 distinct cold stacks overflow the 2*topk compaction threshold.
    for i in range(9):
        frames["current"] = {1: _fake_frame([("/srv/app.py", f"cold{i}")])}
        sampler.sample_once(0.01)
    snap = sampler.snapshot()
    assert snap["samples"] == 19
    assert len(snap["stacks"]) <= 3          # bounded top-K
    assert sampler.evictions > 0             # eviction actually happened
    top = snap["stacks"][0]
    assert top["stack"].endswith("app.py:loop;app.py:hot")
    assert top["seconds"] == pytest.approx(1.0)
    # Evicted weight is folded into (other), never silently dropped.
    total = sum(s["seconds"] for s in snap["stacks"]) + snap.get("other_s", 0)
    assert total == pytest.approx(19 * 0.1 - 9 * 0.09, abs=0.02)


def test_stack_sampler_skips_its_own_thread():
    frames = {1: _fake_frame([("a.py", "f")]), 2: _fake_frame([("b.py", "g")])}
    sampler = StackSampler(frames=lambda: frames)
    assert sampler.sample_once(0.1, skip_ident=2) == 1
    snap = sampler.snapshot()
    assert len(snap["stacks"]) == 1
    assert "a.py:f" in snap["stacks"][0]["stack"]


def test_stack_sampler_thread_runs_and_stops():
    sampler = StackSampler(hz=50.0).start()
    import time

    time.sleep(0.1)
    sampler.stop()
    assert sampler.samples >= 2
    before = sampler.samples
    time.sleep(0.05)
    assert sampler.samples == before  # genuinely stopped


# -- unit: ingest registry + gauges -----------------------------------------

def test_note_stage_histograms_and_disabled_noop():
    perf = PerfPlane(ServeConfig())
    for ms in (0.2, 0.4, 8.0):
        perf.note_stage("m", "json_decode", ms)
    perf.note_stage(None, "json_decode", 1.0)  # model-less: dropped
    snap = perf.ingest_snapshot()
    assert snap["m"]["json_decode"]["count"] == 3
    off = PerfPlane(ServeConfig(perfplane=False))
    off.note_stage("m", "json_decode", 1.0)
    assert off.ingest_snapshot() == {}
    assert off.start(loop=None) is off  # disabled start is a no-op


def test_rolling_gauges_difference_the_counters():
    perf = PerfPlane(ServeConfig(perf_window_s=30.0))
    stats = {"resnet18": SimpleNamespace(samples=0, batches=0,
                                         device_seconds=0.0)}
    gens = {"gpt2": {"tokens_emitted": 0, "segment_rounds": 0}}
    perf.runner_stats = lambda: stats
    perf.gen_snapshots = lambda: gens
    perf.observe_models(now=0.0)
    stats["resnet18"] = SimpleNamespace(samples=500, batches=100,
                                        device_seconds=2.0)
    gens["gpt2"] = {"tokens_emitted": 1200, "segment_rounds": 300}
    perf.observe_models(now=10.0)
    gauges = perf.model_gauges()
    assert gauges["resnet18"]["samples_per_s"] == pytest.approx(50.0)
    assert gauges["resnet18"]["step_ms"] == pytest.approx(20.0)  # 2s/100
    assert gauges["resnet18"]["device_util_pct"] == pytest.approx(20.0)
    assert gauges["gpt2:generate"]["tokens_per_s"] == pytest.approx(120.0)
    assert "mfu_pct" not in gauges["resnet18"]  # no flops hint -> no guess
    perf.flops_hint = lambda m: 1.0e9
    perf.peak_flops = 100e12
    # 50 samples/s * 1 GF = 50 GF/s against 100 TF peak = 0.05%.
    assert perf.model_gauges()["resnet18"]["mfu_pct"] == pytest.approx(0.05)


def test_hist_quantile_interpolates():
    assert hist_quantile({"buckets": {}, "count": 0}, 0.5) is None
    snap = {"buckets": {"1": 0, "2": 10, "4": 10, "+Inf": 10}, "count": 10}
    assert 1.0 < hist_quantile(snap, 0.5) <= 2.0


def test_peaks_table_is_keyed_by_reported_kind_and_unknown_is_an_error(
        monkeypatch):
    """A utilization against a guessed peak is not a measurement: a kind the
    table does not know gets no MFU gauge, and no exception either."""
    from pytorch_zappa_serverless_tpu.serving import perfplane
    from pytorch_zappa_serverless_tpu.utils.device import CHIP_PEAKS

    assert CHIP_PEAKS["TPU v5 lite"] == (197e12, 819e9)   # what a v5e reports
    assert "TPU v9 imaginary" not in CHIP_PEAKS

    def gauges(kind):
        monkeypatch.setattr(perfplane, "device_info", lambda: {
            "platform": "tpu", "kind": kind, "count": 1})
        perf = PerfPlane(ServeConfig(perf_window_s=30.0))
        stats = {"m": SimpleNamespace(samples=0, batches=0,
                                      device_seconds=0.0)}
        perf.runner_stats = lambda: stats
        perf.flops_hint = lambda m: 1.97e12
        perf.observe_models(now=0.0)
        stats["m"] = SimpleNamespace(samples=100, batches=100,
                                     device_seconds=2.0)
        perf.observe_models(now=10.0)
        return perf.model_gauges()["m"]

    # 10 samples/s * 1.97 TF = 19.7 TF/s against the v5e's 197 TF = 10%.
    assert gauges("TPU v5 lite")["mfu_pct"] == pytest.approx(10.0)
    unknown = gauges("TPU v9 imaginary")
    assert unknown["samples_per_s"] == pytest.approx(10.0)
    assert "mfu_pct" not in unknown


# -- integration: a real booted server ---------------------------------------

def _cfg(tmpdir):
    return ServeConfig(
        compile_cache_dir=str(tmpdir),
        warmup_at_boot=True,
        perf_loop_lag_interval_s=0.02,
        perf_stack_hz=50.0,
        models=[ModelConfig(name="resnet18", batch_buckets=(1, 4),
                            dtype="float32", coalesce_ms=2.0,
                            extra={"image_size": 64, "resize_to": 72,
                                   "flops_per_sample": 2.0e9})],
    )


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    eng = build_engine(_cfg(tmp_path_factory.mktemp("xla")))
    yield eng
    eng.shutdown()


@pytest.fixture
async def served(engine, aiohttp_client, tmp_path):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    app = create_app(_cfg(tmp_path), engine=engine)
    client = await aiohttp_client(app)
    yield client


def _json_b64_payload(seed=0) -> bytes:
    arr = np.random.default_rng(seed).integers(
        0, 255, (64, 64, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="PNG")
    return json.dumps({"b64": base64.b64encode(buf.getvalue()).decode()
                       }).encode()


async def test_admin_perf_over_a_real_server(served):
    client = served
    for i in range(3):
        r = await client.post(
            "/v1/models/resnet18:predict", data=_json_b64_payload(i),
            headers={"Content-Type": "application/json"})
        assert r.status == 200, await r.text()
    trace_id = r.headers["X-Trace-Id"]
    await asyncio.sleep(0.08)  # a few lag ticks + stack samples
    r = await client.get("/admin/perf")
    perf = await r.json()
    assert r.status == 200, perf
    assert perf["enabled"] is True
    assert perf["loop_lag"]["ticks"] >= 1
    assert perf["stacks"]["samples"] >= 1
    # Every ingest substage of the JSON lane recorded for the model.
    stages = perf["ingest"]["resnet18"]
    for stage in ("payload_read", "json_decode", "b64_decode", "validate",
                  "batch_form", "serialize", "respond"):
        assert stages[stage]["count"] >= 1, (stage, stages)
    # Stage order in the snapshot follows the pipeline.
    assert list(stages) == [s for s in INGEST_STAGES if s in stages]
    # Device memory, read at scrape time: the CPU keeps no such count, so
    # every device is there with nulls (and the gauge family is absent).
    assert perf["device_memory"] and all(
        set(row) == {"id", "bytes_in_use", "peak_bytes_in_use", "bytes_limit"}
        and row["peak_bytes_in_use"] is None for row in perf["device_memory"])
    text = await (await client.get("/metrics?format=prometheus")).text()
    assert "tpuserve_device_memory_bytes" not in text
    # ?top bounds the stack table; junk 400s.
    r = await client.get("/admin/perf", params={"top": 1})
    assert len((await r.json())["stacks"]["stacks"]) <= 1
    assert (await client.get("/admin/perf", params={"top": "x"})).status == 400

    # The same substages render on the trace waterfall and the attribution
    # table WITHOUT entering stage coverage (satellite: tracedump).
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tpuserve_tracedump", REPO / "tools" / "tracedump.py")
    dump = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(dump)
    r = await client.get(f"/admin/trace/{trace_id}")
    payload = await r.json()
    att = dump.stage_attribution(payload)
    assert att["coverage_pct"] >= 95.0, att
    assert "payload_read" not in att["stages"]
    assert {"payload_read", "json_decode", "b64_decode",
            "validate"} <= set(att["substages"])
    text = dump.render(payload)
    assert "payload_read" in text and "substages:" in text

    # The new families ride /metrics prometheus.
    r = await client.get("/metrics", params={"format": "prometheus"})
    prom = await r.text()
    assert "tpuserve_ingest_ms_bucket" in prom
    assert "tpuserve_loop_lag_ms_bucket" in prom

    # The CLI table renders the same payload (no server round trip).
    from pytorch_zappa_serverless_tpu.cli import format_perf_table

    table = format_perf_table(perf)
    assert "loop lag:" in table
    assert "payload_read" in table and "json_decode" in table
    assert "top stacks" in table


async def test_perfplane_off_disables_the_plane(engine, aiohttp_client,
                                                tmp_path):
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    cfg = _cfg(tmp_path)
    cfg.perfplane = False
    client = await aiohttp_client(create_app(cfg, engine=engine))
    r = await client.post(
        "/v1/models/resnet18:predict", data=_json_b64_payload(9),
        headers={"Content-Type": "application/json"})
    assert r.status == 200
    perf = await (await client.get("/admin/perf")).json()
    assert perf["enabled"] is False
    assert perf["ingest"] == {}
    assert perf["loop_lag"]["ticks"] == 0
    assert perf["stacks"]["samples"] == 0


# -- integration: split ttft/itl on a generation lane ------------------------

async def test_ttft_and_itl_split_histograms(aiohttp_client, tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.server import create_app

    arch = {"d_model": 32, "layers": 1, "heads": 2, "ffn_dim": 64,
            "vocab_size": 512, "max_positions": 32}
    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"),
        models=[ModelConfig(name="gpt2", batch_buckets=(1, 2),
                            seq_buckets=(8,), dtype="float32",
                            extra={"max_new_tokens": 6, "arch": arch})])
    engine = build_engine(cfg)
    try:
        client = await aiohttp_client(create_app(cfg, engine=engine))
        r = await client.post("/v1/models/gpt2:generate",
                              json={"text": "hello tpu", "stream": False})
        body = await r.json()
        assert r.status == 200, body
        n_tokens = len(body["predictions"]["tokens"])
        assert n_tokens >= 2
        r = await client.get("/metrics")
        gen = (await r.json())["generation"]["gpt2"]
        lat = gen["latency"]
        # Exactly one first token; every other token is an inter-token gap
        # — the split the conflated step ring could not make.
        assert lat["ttft_ms"]["count"] == 1
        assert lat["itl_ms"]["count"] == n_tokens - 1
        assert gen["tokens_emitted"] == n_tokens
        r = await client.get("/metrics", params={"format": "prometheus"})
        prom = await r.text()
        assert 'tpuserve_ttft_ms_count{model="gpt2"} 1' in prom
        assert f'tpuserve_itl_ms_count{{model="gpt2"}} {n_tokens - 1}' in prom
        assert f'tpuserve_tokens_streamed_total{{model="gpt2"}} {n_tokens}' \
            in prom
        # /admin/perf folds the quantiles into the gauge rows.
        perf = await (await client.get("/admin/perf")).json()
        assert "ttft_p50_ms" in perf["models"]["gpt2:generate"]
    finally:
        engine.shutdown()
