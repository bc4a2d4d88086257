"""Prometheus exposition-format regression (CI satellite, ISSUE 2).

Every family /metrics publishes must stay parseable by a scraper: each
non-comment line is ``name{labels} value`` (with an optional OpenMetrics
exemplar suffix on histogram buckets) with a float-parsable value, each
family carries HELP+TYPE exactly once, and label values survive escaping —
checked over a hub loaded with EVERY publishing subsystem (rings, gauges,
runner stats, lanes, resilience, faults, tracer) plus hostile names, so a
new counter can't silently break scrapers.  The manifest lint at the bottom
(tools/check_metrics.py, ISSUE 4) additionally pins family names + label
sets so renames are deliberate.
"""

import importlib.util
import re
from pathlib import Path
from types import SimpleNamespace

import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.runner import DeviceRunner
from pytorch_zappa_serverless_tpu.faults import FaultInjector
from pytorch_zappa_serverless_tpu.serving.metrics import MetricsHub
from pytorch_zappa_serverless_tpu.serving.resilience import ResilienceHub
from pytorch_zappa_serverless_tpu.serving.tracing import Tracer
from pytorch_zappa_serverless_tpu.serving.variants import VariantHub

# The exposition grammar (text format 0.0.4): metric name, optional label
# set, one float value.  Quoted label values may contain anything except a
# raw newline/unescaped quote.  Histogram bucket samples may carry an
# OpenMetrics exemplar: `` # {labels} value [timestamp]``.
_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL = rf'{_NAME}="(?:[^"\\\n]|\\.)*"'
_NUM = r"-?[0-9.e+-]+"
_EXEMPLAR = rf" # \{{{_LABEL}(?:,{_LABEL})*\}} {_NUM}( {_NUM})?"
_LINE = re.compile(
    rf"^{_NAME}(?:\{{{_LABEL}(?:,{_LABEL})*\}})? {_NUM}(?:{_EXEMPLAR})?$")
_HELP = re.compile(rf"^# HELP {_NAME} \S.*$")
_TYPE = re.compile(rf"^# TYPE {_NAME} (counter|gauge|summary|histogram)$")
# Component-series suffixes that roll up to their histogram family.
_HIST_SUFFIX = re.compile(r"_(bucket|sum|count)$")


def _loaded_hub():
    """A hub exercising every publishing subsystem, with hostile names."""
    hub = MetricsHub()
    tracer = Tracer()
    for model in ("resnet18", 'mo"del\\weird', "with\nnewline"):
        ring = hub.ring(model)
        for i in range(4):
            # Exemplars ride the histograms: hostile trace ids must escape.
            root = tracer.start("predict", model=model)
            tracer.finish(root.trace, "ok")
            ring.record(1.0 + i, 2.0 + i, 3.0 + i,
                        trace_id=root.trace.trace_id)
        ring.record_error()
    hub.tracer = tracer
    err = tracer.start("predict", model="resnet18")
    tracer.finish(err.trace, "error")  # populates the pinned-errored gauge
    hub.gauges["ok_gauge"] = 1.5
    hub.gauges["0bad name!"] = 2.0  # must be sanitized into the name charset

    cfg = ServeConfig(breaker_threshold=0.5, breaker_min_samples=1)
    hub.resilience = ResilienceHub(cfg)
    mr = hub.resilience.model('mo"del\\weird')
    mr.stats.retries, mr.stats.deadline_queue, mr.stats.shed_predicted = 3, 2, 1
    mr.breaker.record(False)  # trips open → breaker state/opens published
    hub.resilience.draining = True

    hub.resilience.quarantined.add('mo"del\\weird')

    hub.faults = FaultInjector()
    hub.faults.configure(model="*", fail_every_n=2, latency_ms=5)

    # Durability + recovery (ISSUE 3): duck-typed stand-ins for the JobQueue
    # and the Watchdog so the new families go through the grammar checks.
    hub.jobs = SimpleNamespace(durability_snapshot=lambda: {
        "journal": {"dir": "/tmp/j", "fsync": "always", "appended": 12},
        "recovered_jobs": 3, "restored_done": 2, "dropped_records": 1,
        "replay_ms": 4.2, "deduped_submits": 5})
    hub.watchdog = SimpleNamespace(snapshot=lambda: {
        "state": "recovering", "attempts": 1, "max_attempts": 3,
        "recoveries_total": 2, "requeued_jobs_total": 4,
        "last_reason": "device probe failed", "last_recovery_ts": None})

    # Variant serving (ISSUE 7): selections/degradations/sheds, brownout
    # state + transitions, selection-latency histogram — with a hostile
    # family name so label escaping is exercised there too.
    vcfg = ServeConfig(models=[
        ModelConfig(name="rn_full", builder="resnet18", family='fa"m\\ily',
                    quality_rank=2),
        ModelConfig(name="rn_lite", builder="resnet18", family='fa"m\\ily',
                    quality_rank=1)])
    hub.variants = VariantHub(vcfg)
    fam = 'fa"m\\ily'
    hub.variants.selections[fam] = {"rn_full": 3, "rn_lite": 2}
    hub.variants.degraded[fam] = {"rn_lite": 2}
    hub.variants.sheds[fam] = 1
    hub.variants.brownout.observe(fam, preferred_fits=False)
    from pytorch_zappa_serverless_tpu.serving.metrics import Histogram
    from pytorch_zappa_serverless_tpu.serving.variants import SELECT_BUCKETS_MS
    h = hub.variants.select_hists[fam] = Histogram(SELECT_BUCKETS_MS)
    h.observe(0.2)

    # Generation lanes (ISSUE 9): one slot lane + one paged lane with a
    # hostile model name, so the tpuserve_kv_*/prefill/spec families go
    # through the grammar + manifest checks.
    # Split ttft/itl per-token timing (ISSUE 14 satellite): both lanes
    # publish it, so both fakes carry a latency block + the token counter.
    _tok_lat = {"ttft_ms": {"buckets": {"1": 0, "2.5": 0, "5": 1, "10": 2,
                                        "25": 2, "50": 2, "100": 2,
                                        "250": 2, "500": 2, "1000": 2,
                                        "2500": 2, "5000": 2, "+Inf": 2},
                            "sum": 11.0, "count": 2},
                "itl_ms": {"buckets": {"1": 3, "2.5": 6, "5": 8, "10": 8,
                                       "25": 8, "50": 8, "100": 8,
                                       "250": 8, "500": 8, "1000": 8,
                                       "2500": 8, "5000": 8, "+Inf": 8},
                           "sum": 14.5, "count": 8}}
    hub.generation = lambda: {
        "gpt2": {"mode": "slot", "slots": 4, "active": 0, "pending": 0,
                 "device_rounds": 7, "segment_rounds": 5, "chained_rounds": 3,
                 "prefill_dispatches": 2, "prefill_kernel_dispatches": 1,
                 "tokens_emitted": 10,
                 "kv_live_share": {"sum": 0.93, "count": 5},
                 "kv_read_share": {"sum": 1.25, "count": 5},
                 "span_rows": {"sum": 3561, "count": 5},
                 "span_rows_by_kind": {
                     "full_attention": {"sum": 2537, "count": 5},
                     "sliding_attention": {"sum": 1024, "count": 5}},
                 "prefill_buckets": {"512": 3, "768": 1},
                 "prefill_rows_padded": 3584, "prefill_rows_prompt": 1700,
                 "prompts_moved_up": 1,
                 "summary_rows": {"sum": 768, "count": 5},
                 "live_positions": {"sum": 14900, "count": 5},
                 "expert_assignments_held": {"sum": 7040, "count": 5},
                 "experts_touched": {"sum": 3800, "count": 5},
                 "expert_load_max": {"sum": 160, "count": 5},
                 "step_counters": {
                     "expert_assignments_held": "Rows routed to the experts "
                                                "held here",
                     "experts_touched": "Held experts that a row reached",
                     "expert_load_max": "The most rows on one held expert"},
                 "window_rolls": 2,
                 "latency": _tok_lat},
        'pa"ged\\model': {
            "mode": "paged", "slots": 8, "active": 2, "prefilling": 1,
            "pending": 0, "prefill_chunks": 9, "chunk_cap": 64,
            "kv": {"block_size": 16, "blocks_total": 64, "blocks_used": 12,
                   "blocks_free": 52, "sequences": 2, "shared_blocks": 3,
                   "utilization": 0.86,
                   "fragmentation": 0.14, "high_water_blocks": 20,
                   "evictions": 1},
            "spec": {"draft": "gpt2_int8", "k": 4, "proposed": 40,
                     "accepted": 31, "fallback_ticks": 2},
            # Prefix KV cache (ISSUE 11): the tpuserve_prefix_* families
            # ride the grammar + manifest checks via the hostile lane name.
            "prefix": {"nodes": 3, "pages": 7, "hits": 5, "misses": 2,
                       "hit_rate": 0.7143, "cow_copies": 1, "evictions": 2,
                       "nodes_total": 4, "pages_total": 9,
                       "reclaimable_pages": 6, "adapters": [0],
                       "cached_tokens": {
                           "buckets": {"4": 0, "8": 2, "16": 4, "32": 5,
                                       "64": 5, "128": 5, "256": 5,
                                       "512": 5, "1024": 5, "2048": 5,
                                       "+Inf": 5},
                           "sum": 96.0, "count": 5}},
            # Live KV migration (ISSUE 13): the tpuserve_migration*
            # families ride the grammar + manifest checks via the hostile
            # lane name too.
            "migration": {"by_cause": {"pressure": 2, "failover": 1,
                                       "admin": 1},
                          "total": 4, "failed": 1,
                          "pages": {"hit": 3, "copied": 9},
                          "swapped": 1, "detached": 0, "enabled": True,
                          "ms": {"buckets": {"0.5": 0, "1.0": 1,
                                             "2.5": 2, "5.0": 4,
                                             "+Inf": 4},
                                 "sum": 11.5, "count": 4}},
            "device_rounds": 11, "segment_rounds": 6,
            "tokens_emitted": 23, "latency": _tok_lat}}

    # Multi-tenant adapters (ISSUE 10): hostile tenant name so the
    # tpuserve_adapter_* families ride the grammar + manifest checks.
    from pytorch_zappa_serverless_tpu.serving.adapters import \
        ATTACH_BUCKETS_MS
    ah = Histogram(ATTACH_BUCKETS_MS)
    ah.observe(3.0)
    hub.adapters = SimpleNamespace(
        enabled=True,
        attach_hists={'gpt2:ten"ant\\x': ah},
        snapshot=lambda: {
            "enabled": True, "idle_unload_s": 60.0,
            "multi_adapter_batches": 3,
            "models": {"gpt2": {
                'ten"ant\\x': {"state": "active", "slot": 1, "tenants": [],
                               "hbm_bytes": 4096, "last_used_s_ago": 0.1,
                               "inflight": 0, "attaches": 2, "detaches": 1,
                               "served": 5, "cold_fast_fails": 1,
                               "last_attach_ms": 3.0,
                               "estimated_attach_ms": 3.0},
                "t2": {"state": "cold", "slot": None, "tenants": ["a"],
                       "hbm_bytes": 0, "last_used_s_ago": 9.0,
                       "inflight": 0, "attaches": 0, "detaches": 0,
                       "served": 0, "cold_fast_fails": 0,
                       "last_attach_ms": None,
                       "estimated_attach_ms": 500.0}}}})

    # SLO & goodput plane (ISSUE 12): a real hub with a hostile model name
    # and a tenant key, every outcome class populated, plus usage-ledger
    # rows — so the tpuserve_slo_*/tpuserve_usage_* families ride the
    # grammar + manifest + escaping checks.
    from pytorch_zappa_serverless_tpu.serving.slo import SLOHub
    scfg = ServeConfig(slo={'mo"del\\weird': {"latency_objective_ms": 10.0,
                                              "availability_target": 0.99}})
    hub.slo = SLOHub(scfg)
    hub.slo.observe('mo"del\\weird', "predict", 200, 2.0)
    hub.slo.observe('mo"del\\weird', "predict", 200, 50.0)       # late
    hub.slo.observe('mo"del\\weird', "predict", 429, 1.0)        # shed
    hub.slo.observe('mo"del\\weird', "predict", 500, 1.0)        # error
    hub.slo.observe('mo"del\\weird', "generate", 200, 3.0,
                    degraded=True, adapter='ten"ant\\x')
    hub.slo.usage.note_request('mo"del\\weird', None, 4.5)
    hub.slo.usage.note_stream("gpt2", 'ten"ant\\x', 12.0, 3.5, 96)
    hub.slo.usage.note_attach("gpt2", 'ten"ant\\x', 3.0)

    # Predictive autoscaling (ISSUE 15): a real AutoscalePlane with a
    # hostile model name and a tenant key, arrivals + a fired pre-warm +
    # a phantom, so the tpuserve_autoscale_* families ride the grammar +
    # manifest + escaping checks.
    from pytorch_zappa_serverless_tpu.serving.autoscale import \
        AutoscalePlane

    class _Tick:
        def __init__(self):
            self.now = 0.0

        def __call__(self):
            return self.now

    atick = _Tick()
    aplane = AutoscalePlane(ServeConfig(autoscale_min_history=3),
                            clock=atick)
    for _ in range(6):
        atick.now += 0.5
        aplane.note_arrival('mo"del\\weird')
        aplane.note_arrival("gpt2", adapter='ten"ant\\x')
    aplane._note_prewarm('mo"del\\weird', "predicted")
    aplane._note_prewarm('mo"del\\weird', "phantom")
    hub.autoscale = aplane

    # Perf plane (ISSUE 14): a real PerfPlane with hostile model names so
    # the tpuserve_ingest_ms/tpuserve_loop_lag_*/tpuserve_perf_* families
    # ride the grammar + manifest + escaping checks.
    from pytorch_zappa_serverless_tpu.serving.perfplane import PerfPlane
    perf = PerfPlane(ServeConfig())
    for stage, ms in (("payload_read", 0.4), ("json_decode", 1.1),
                      ("b64_decode", 2.3), ("validate", 0.1),
                      ("batch_form", 2.9), ("serialize", 0.6),
                      ("respond", 0.2)):
        perf.note_stage('mo"del\\weird', stage, ms)
    perf.note_stage("resnet18", "payload_read", 0.3)
    perf.loop_lag.arm()
    perf.loop_lag.note()
    perf.stacks.sample_once(0.1)  # real frames: this test's own stack
    # Rolling gauges from two window samples, MFU via an explicit hint +
    # a pinned peak so the family renders on any backend.
    perf.flops_hint = lambda m: 2.0e9
    perf.peak_flops = 197e12
    perf._push(0.0, 'mo"del\\weird', {"samples": 0.0, "batches": 0.0,
                                      "device_seconds": 0.0})
    perf._push(10.0, 'mo"del\\weird', {"samples": 100.0, "batches": 25.0,
                                       "device_seconds": 5.0})
    perf._push(0.0, "gpt2:generate", {"tokens": 0.0, "ticks": 0.0})
    perf._push(10.0, "gpt2:generate", {"tokens": 500.0, "ticks": 100.0})
    hub.perf = perf

    # Server fast path + acceptor telemetry plane (ISSUES 16/19): the
    # serverpath snapshot shape with a hostile model on the binary-lane
    # counter and a hostile ring label, every per-worker counter, the
    # liveness/restart evidence and all three histogram families — so the
    # tpuserve_acceptor_*/tpuserve_shm_ring_* families ride the grammar +
    # manifest + escaping checks.
    _occ = {"buckets": {"1": 0, "5": 2, "10": 3, "25": 3, "50": 3, "75": 3,
                        "90": 3, "100": 3, "+Inf": 3},
            "sum": 17.0, "count": 3}
    hub.serverpath = lambda: {
        "ingest_workers": 2,
        "ring_depth": {"req:0": 1, 'ri"ng\\0': 0},
        "binary_requests": {'mo"del\\weird': 7, "resnet18": 3},
        "wire_pool": {"hits": 1, "misses": 1},
        "acceptor": {
            "workers": [
                {"worker": 0, "up": True, "accepts": 9, "shed_400": 1,
                 "shed_413": 2, "shed_415": 0, "shed_429": 1, "shed_504": 0,
                 "responses_ok": 5, "responses_err": 4, "bytes_in": 4096,
                 "bytes_out": 2048, "heartbeat_age_s": 0.12,
                 "inworker_ms": {"buckets": {"0.05": 0, "0.1": 1, "0.25": 3,
                                             "0.5": 5, "1": 5, "2.5": 5,
                                             "5": 5, "10": 5, "25": 5,
                                             "50": 5, "100": 5, "250": 5,
                                             "+Inf": 5},
                                 "sum": 1.4, "count": 5}},
                {"worker": 1, "up": False, "accepts": 0, "shed_400": 0,
                 "shed_413": 0, "shed_415": 0, "shed_429": 0, "shed_504": 0,
                 "responses_ok": 0, "responses_err": 0, "bytes_in": 0,
                 "bytes_out": 0, "heartbeat_age_s": None,
                 "inworker_ms": {"buckets": {"+Inf": 0}, "sum": 0.0,
                                 "count": 0}}],
            "restarts": 1,
            "ring_wait_ms": {"buckets": {"0.1": 0, "0.25": 1, "0.5": 2,
                                         "1": 4, "2.5": 4, "5": 4, "10": 4,
                                         "25": 4, "50": 4, "100": 4,
                                         "250": 4, "1000": 4, "+Inf": 4},
                             "sum": 2.6, "count": 4},
            "ring_occupancy_pct": {"req:0": _occ, 'ri"ng\\0': _occ},
        },
    }

    # Residency tiers + streaming checkpoint store (ISSUE 20): a lifecycle
    # stand-in with hostile model and store keys so the
    # tpuserve_residency_*/tpuserve_activation_*/tpuserve_ckpt_* families
    # ride the grammar + manifest + escaping checks — including the
    # adapter-delta store key ('base+adapter') on the chunk counters.
    from pytorch_zappa_serverless_tpu.serving.ckptstore import \
        CKPT_LOAD_BUCKETS_MS
    from pytorch_zappa_serverless_tpu.serving.lifecycle import \
        ACTIVATION_BUCKETS_MS
    lh = Histogram(ACTIVATION_BUCKETS_MS)
    lh.observe(812.0)
    ch = Histogram(CKPT_LOAD_BUCKETS_MS)
    ch.observe(42.0)
    hub.lifecycle = SimpleNamespace(
        state_code=lambda m: 2,
        activation_hists={'mo"del\\weird': lh},
        store=SimpleNamespace(
            load_hists_snapshot=lambda: {'mo"del\\weird+ten"ant\\x': ch}),
        snapshot=lambda: {
            "hbm_budget_bytes": 1 << 30, "hbm_bytes_total": 4096,
            "host_budget_bytes": 2048, "host_bytes_total": 1024,
            "ckpt_store": {
                "physical_bytes": 512,
                "chunks_streamed_total": {'mo"del\\weird': 7,
                                          'mo"del\\weird+ten"ant\\x': 2},
                "dedup_hits_total": {'mo"del\\weird': 3}},
            "models": {'mo"del\\weird': {
                "activations_by_cause": {"request": 2, "admin": 1},
                "demotions_by_cause": {"idle": 1, "host_budget": 1},
                "cold_fast_fails": 1}}})
    return hub


def test_every_published_line_is_scrapeable():
    runner = DeviceRunner()
    try:
        cm = SimpleNamespace(servable=SimpleNamespace(name="resnet18"),
                             run_batch=lambda samples, seq=None:
                             (["r"] * len(samples), (4,)))
        runner.run_sync(cm, [{}, {}])
        hub = _loaded_hub()
        engine = SimpleNamespace(
            runner=runner, cold_start_seconds=1.23,
            clock=SimpleNamespace(entries=[], total_seconds=0.5),
            models={})
        text = hub.render_prometheus(engine)
    finally:
        runner.shutdown()

    assert text.endswith("\n")
    seen_types: dict[str, str] = {}
    families_in_help = set()
    for line in text.strip().splitlines():
        if line.startswith("# HELP "):
            assert _HELP.match(line), f"bad HELP line: {line!r}"
            families_in_help.add(line.split()[2])
        elif line.startswith("# TYPE "):
            assert _TYPE.match(line), f"bad TYPE line: {line!r}"
            name = line.split()[2]
            assert name not in seen_types, f"duplicate TYPE for {name}"
            seen_types[name] = line.split()[3]
        else:
            assert _LINE.match(line), f"unscrapeable sample line: {line!r}"
            sample = line.split(" # ", 1)[0]  # strip OpenMetrics exemplar
            float(sample.rsplit(" ", 1)[1])  # value parses
            name = re.match(_NAME, sample).group(0)
            family = name  # summaries share the family name directly here
            if family not in seen_types and _HIST_SUFFIX.search(name):
                family = _HIST_SUFFIX.sub("", name)
            assert family in seen_types, f"sample before TYPE: {line!r}"
    assert families_in_help == set(seen_types)

    # The resilience/fault families made it out (new counters are covered
    # by the grammar checks above the moment they are added).
    for family in ("tpuserve_requests_total", "tpuserve_deadline_exceeded_total",
                   "tpuserve_load_shed_total", "tpuserve_dispatch_retries_total",
                   "tpuserve_breaker_state", "tpuserve_draining",
                   "tpuserve_faults_injected_total", "tpuserve_batches_total",
                   "tpuserve_quarantined", "tpuserve_recovered_jobs",
                   "tpuserve_journal_replay_ms", "tpuserve_recovery_state",
                   "tpuserve_recoveries_total",
                   "tpuserve_idempotent_dedupes_total",
                   "tpuserve_queue_ms", "tpuserve_device_ms",
                   "tpuserve_traces_finished_total"):
        assert f"# TYPE {family} " in text, f"missing family {family}"
    assert seen_types["tpuserve_queue_ms"] == "histogram"
    assert "tpuserve_draining 1" in text
    assert "tpuserve_recovery_state 1" in text  # "recovering" encodes as 1
    assert "tpuserve_recovered_jobs 3" in text


def test_label_escaping_round_trips():
    hub = _loaded_hub()
    text = hub.render_prometheus()
    # The hostile model names appear escaped, never raw.
    assert r'model="mo\"del\\weird"' in text
    assert "with\nnewline" not in text.replace(r"\n", "")  # no raw newline
    # Gauge names are sanitized into the metric-name charset.
    assert 'name="_0bad_name_"' in text


def test_histogram_exemplars_link_traces(tmp_path):
    """The queue/device histograms are real cumulative histograms whose
    buckets carry OpenMetrics exemplars with the trace_id a /admin/trace
    lookup resolves (ISSUE 4 tentpole: metric↔trace correlation)."""
    hub = _loaded_hub()
    text = hub.render_prometheus()
    ring = hub.models["resnet18"]
    # Exact cumulative counts: 4 observations, all <= 10 ms.
    assert 'tpuserve_queue_ms_bucket{model="resnet18",le="+Inf"} 4' in text
    assert 'tpuserve_queue_ms_count{model="resnet18"} 4' in text
    snap = ring.snapshot()
    assert snap["queue_hist"]["count"] == 4  # JSON twin stays additive
    assert {"queue_ms", "device_ms", "total_ms"} <= set(snap)  # compat keys
    # An exemplar rides a bucket line and names a trace the tracer can
    # still resolve (flight recorder / ring).
    m = re.search(r'tpuserve_device_ms_bucket\{model="resnet18",le="[^"]+"\} '
                  r'\d+ # \{trace_id="([0-9a-f]{32})"\}', text)
    assert m, "no exemplar on the resnet18 device histogram"
    assert hub.tracer.get(m.group(1)) is not None


def _check_metrics_mod():
    path = Path(__file__).resolve().parents[1] / "tools" / "check_metrics.py"
    spec = importlib.util.spec_from_file_location("tpuserve_check_metrics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_exposition_matches_checked_in_manifest():
    """Metrics-stability lint (ISSUE 4 satellite): every family name + label
    set a fully-loaded hub publishes is declared in
    tools/metrics_manifest.json — renaming a metric without updating the
    manifest fails CI before it breaks a dashboard."""
    mod = _check_metrics_mod()
    runner = DeviceRunner()
    try:
        cm = SimpleNamespace(servable=SimpleNamespace(name="resnet18"),
                             run_batch=lambda samples, seq=None:
                             (["r"] * len(samples), (4,)))
        runner.run_sync(cm, [{}, {}])
        hub = _loaded_hub()
        engine = SimpleNamespace(
            runner=runner, cold_start_seconds=1.23,
            clock=SimpleNamespace(entries=[], total_seconds=0.5),
            models={})
        text = hub.render_prometheus(engine)
    finally:
        runner.shutdown()
    problems = mod.check(text, mod.load_manifest())
    assert problems == [], "\n".join(problems)
    # The check actually bites: an undeclared family and a drifted label
    # set are both reported.
    manifest = mod.load_manifest()
    assert mod.check(text + "\n# TYPE tpuserve_rogue counter\n"
                            "tpuserve_rogue 1\n", manifest)
    mutated = text.replace('tpuserve_requests_total{model="resnet18"}',
                           'tpuserve_requests_total{rogue="x"}', 1)
    assert any("label set" in p for p in mod.check(mutated, manifest))


def test_a_lane_s_own_step_counters_render_under_the_names_it_gives():
    """The hub names no model's counters: a lane says what its model's decode
    step counts (``step_counters``: name -> what it counts) and the hub gives
    each pair a summary under that name (the manifest declares the names in
    use; this one is not in it and is not linted)."""
    hub = _loaded_hub()
    gen = hub.generation()
    gen["gpt2"]["rows_skipped"] = {"sum": 9, "count": 3}
    gen["gpt2"]["step_counters"]["rows_skipped"] = "Rows a step skipped"
    hub.generation = lambda: gen
    text = hub.render_prometheus()
    assert ("# HELP tpuserve_rows_skipped Rows a step skipped, per segment "
            "round") in text
    assert 'tpuserve_rows_skipped_sum{model="gpt2"} 9' in text
    assert 'tpuserve_experts_touched_count{model="gpt2"} 5' in text


@pytest.mark.parametrize("family, value", [
    ("tpuserve_prefill_rows_padded_total", 3584),
    ("tpuserve_prefill_rows_prompt_total", 1700),
    ("tpuserve_prompts_moved_up_total", 1)])
def test_a_round_s_prefill_plan_renders_beside_the_generation_counters(
        family, value):
    """What a slot lane's prefill dispatches multiplied, what their prompts
    hold and how many rode in a longer bucket (ISSUE 53), as counters a
    model beside ``tpuserve_prefill_dispatches_total``."""
    text = _loaded_hub().render_prometheus()
    assert f"# TYPE {family} counter" in text
    assert f'{family}{{model="gpt2"}} {value}' in text
    assert text.index("# TYPE tpuserve_prefill_dispatches_total") \
        < text.index(f"# TYPE {family}") \
        < text.index("# TYPE tpuserve_prefill_bucket_prompts_total")


def test_device_memory_gauge_renders_where_the_backend_counts(monkeypatch):
    """``tpuserve_device_memory_bytes{device,kind}``: read from
    ``memory_stats()`` at scrape time, one series per device and kind, in the
    manifest; a kind the backend does not report is left out."""
    from pytorch_zappa_serverless_tpu.utils import device

    monkeypatch.setattr(device, "device_memory", lambda: [
        {"id": 0, "bytes_in_use": 5, "peak_bytes_in_use": 9,
         "bytes_limit": 16},
        {"id": 1, "bytes_in_use": 7, "peak_bytes_in_use": None,
         "bytes_limit": 16}])
    mod = _check_metrics_mod()
    hub = _loaded_hub()
    engine = SimpleNamespace(
        runner=SimpleNamespace(stats={}, lane_stats=lambda: {}),
        cold_start_seconds=1.0,
        clock=SimpleNamespace(entries=[], total_seconds=0.0), models={})
    text = hub.render_prometheus(engine)
    assert 'tpuserve_device_memory_bytes{device="0",kind="peak"} 9' in text
    assert 'tpuserve_device_memory_bytes{device="1",kind="in_use"} 7' in text
    assert 'device="1",kind="peak"' not in text
    assert mod.check(text, mod.load_manifest()) == []


def test_the_first_use_ledger_renders_under_declared_names():
    """The ledger's family (ISSUE 42) is in the manifest, carries the label
    set it declares, and counts what the ledger holds: first uses by program
    and outcome.  The seconds by stage have no Prometheus family: their
    reader is the benchmark, over ``/metrics`` ``generation[model].programs``."""
    from pytorch_zappa_serverless_tpu.engine.cache import CompileClock

    mod = _check_metrics_mod()
    manifest = mod.load_manifest()["families"]
    assert manifest["tpuserve_program_first_uses_total"]["labels"] == [
        "model", "outcome", "program"]
    assert "tpuserve_program_stage_seconds_total" not in manifest
    clock, seen = CompileClock(), set()
    name = 'mo"del\\weird'
    for program, outcome in (("prefill", "hit"), ("prefill", "hit"),
                             ("segment", "miss")):
        clock.open(name, program, {"bucket": len(clock.entries)},
                   seen=seen).entry.update(outcome=outcome, launch_s=6.0)
    clock.open("resnet18", "predict", {"bucket": [4]},
               seen=seen).entry["launch_s"] = 2.0
    runner = DeviceRunner()
    try:
        engine = SimpleNamespace(runner=runner, cold_start_seconds=1.0,
                                 clock=clock, models={})
        text = MetricsHub().render_prometheus(engine)
    finally:
        runner.shutdown()
    assert mod.check(text, mod.load_manifest()) == []
    esc = name.replace("\\", "\\\\").replace('"', '\\"')
    assert (f'tpuserve_program_first_uses_total{{model="{esc}",'
            f'outcome="hit",program="prefill"}} 2') in text
    assert (f'tpuserve_program_first_uses_total{{model="{esc}",'
            f'outcome="miss",program="segment"}} 1') in text
    assert ('tpuserve_program_first_uses_total{model="resnet18",'
            'outcome="uncached",program="predict"} 1') in text
    assert "tpuserve_program_stage_seconds_total" not in text
    assert 'tpuserve_compile_entries{model="resnet18"} 1' in text
    assert f'tpuserve_model_compile_seconds_total{{model="{esc}"}} 18.0' in text
