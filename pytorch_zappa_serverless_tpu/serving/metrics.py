"""Serving metrics: the BASELINE numbers, live.

The reference gets duration/invocation/error counts for free from Lambda +
CloudWatch (SURVEY §5 "Metrics").  Here the serving layer records per-model
latency decompositions (queue wait / device / total) in ring buffers and
exposes p50/p99, req/s, batch occupancy, and compile-cache timings on
``GET /metrics`` — literally the BASELINE metric set
("p50/p99 request latency (ms) + req/s/chip; cold-start compile time").
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

# Explicit histogram bounds (ms) for the queue/device latency histograms:
# sub-ms batching wins through multi-second SD-1.5 denoise loops, log-ish
# spacing.  +Inf is implicit (the last cumulative bucket).
LATENCY_BUCKETS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0)


class Histogram:
    """Prometheus-style cumulative histogram with OpenMetrics exemplars.

    Fixed explicit bounds (no reservoir): O(1) observe, exact counts — the
    real thing, not the snapshot-only quantile gauges the summaries render.
    Each bucket remembers the LAST exemplar (trace_id, value, wall ts) that
    landed in it, which is how a scraped latency spike links back to
    ``GET /admin/trace/{id}`` (docs/OBSERVABILITY.md).  Lock-protected:
    observed from the event loop, rendered from a scrape.
    """

    def __init__(self, bounds: tuple[float, ...] = LATENCY_BUCKETS_MS):
        self.bounds = tuple(bounds)
        self._lock = threading.Lock()
        self._counts = [0] * (len(self.bounds) + 1)  # guarded-by: _lock
        self.sum = 0.0    # guarded-by: _lock
        self.count = 0    # guarded-by: _lock
        # guarded-by: _lock
        self._exemplars: list[tuple[str, float, float] | None] = \
            [None] * (len(self.bounds) + 1)

    def observe(self, value: float, trace_id: str | None = None):
        i = 0
        while i < len(self.bounds) and value > self.bounds[i]:
            i += 1
        with self._lock:
            self._counts[i] += 1
            self.sum += value
            self.count += 1
            if trace_id:
                self._exemplars[i] = (trace_id, value, time.time())

    def snapshot(self) -> dict:
        """Cumulative bucket counts keyed by upper bound (JSON surface)."""
        with self._lock:
            counts = list(self._counts)
            total, s = self.count, self.sum
        out, acc = {}, 0
        for bound, n in zip(self.bounds, counts):
            acc += n
            out[f"{bound:g}"] = acc
        out["+Inf"] = total
        return {"buckets": out, "sum": round(s, 3), "count": total}

    def rows(self) -> list[tuple[str, int, tuple[str, float, float] | None]]:
        """(le, cumulative count, exemplar) per bucket, +Inf last.

        The +Inf total comes from the SAME locked snapshot as the buckets:
        reading ``self.count`` after releasing the lock (the pre-ISSUE-8
        code) let a concurrent observe land between the two, rendering a
        +Inf row smaller than the sum of its buckets — a non-monotonic
        histogram a Prometheus scraper rightly rejects."""
        with self._lock:
            counts = list(self._counts)
            exemplars = list(self._exemplars)
            total = self.count
        rows, acc = [], 0
        for bound, n, ex in zip(self.bounds, counts, exemplars):
            acc += n
            rows.append((f"{bound:g}", acc, ex))
        rows.append(("+Inf", total, exemplars[-1]))
        return rows


class LatencyRing:
    """Lock-protected ring of recent (queue_ms, device_ms, total_ms) samples.

    Also feeds the real queue/device histograms (``tpuserve_queue_ms`` /
    ``tpuserve_device_ms``): the ring keeps the recent-window percentiles
    the JSON surface always had, the histograms keep exact lifetime
    distributions a scraper can aggregate — and, when the caller passes the
    request's ``trace_id``, exemplars linking buckets back to span trees.
    """

    def __init__(self, maxlen: int = 4096):
        # guarded-by: _lock
        self._samples: deque[tuple[float, float, float]] = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self.count = 0   # guarded-by: _lock
        self.errors = 0  # guarded-by: _lock
        self._t0 = time.monotonic()
        self.queue_hist = Histogram()
        self.device_hist = Histogram()

    def record(self, queue_ms: float, device_ms: float, total_ms: float,
               trace_id: str | None = None):
        with self._lock:
            self._samples.append((queue_ms, device_ms, total_ms))
            self.count += 1
        self.queue_hist.observe(queue_ms, trace_id)
        self.device_hist.observe(device_ms, trace_id)

    def record_error(self):
        with self._lock:
            self.errors += 1

    def device_p50(self) -> float | None:
        """Recent p50 device ms, or None before any sample — the signal the
        admission-time load shedder multiplies by queue depth."""
        with self._lock:
            if not self._samples:
                return None
            arr = np.asarray(self._samples, dtype=np.float64)
        return float(np.percentile(arr[:, 1], 50))

    def snapshot(self) -> dict:
        with self._lock:
            arr = np.asarray(self._samples, dtype=np.float64)
            count, errors = self.count, self.errors
        uptime = max(time.monotonic() - self._t0, 1e-9)
        out = {"requests": count, "errors": errors,
               "req_per_s_lifetime": round(count / uptime, 2)}
        if len(arr):
            for i, name in enumerate(("queue_ms", "device_ms", "total_ms")):
                col = arr[:, i]
                out[name] = {"p50": round(float(np.percentile(col, 50)), 3),
                             "p99": round(float(np.percentile(col, 99)), 3),
                             "mean": round(float(col.mean()), 3)}
        if self.queue_hist.count:
            # Additive keys only: the pre-histogram snapshot fields above
            # are a compatibility surface (tests, dashboards) and stay.
            out["queue_hist"] = self.queue_hist.snapshot()
            out["device_hist"] = self.device_hist.snapshot()
        return out


def _prom_name(name: str) -> str:
    """Prometheus metric-name charset: [a-zA-Z_:][a-zA-Z0-9_:]*."""
    out = "".join(c if c.isalnum() or c in "_:" else "_" for c in name)
    return out if out and not out[0].isdigit() else "_" + out


def _prom_label(value: str) -> str:
    return str(value).replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


class MetricsHub:
    """Registry of per-model rings + gauges, rendered for /metrics."""

    def __init__(self):
        # The hub itself is event-loop-confined (rings are handed out and
        # rendered from handlers); the rings/histograms inside are the
        # cross-thread objects and carry their own locks.
        self.models: dict[str, LatencyRing] = {}  # guarded-by: event-loop
        self.gauges: dict[str, float] = {}  # guarded-by: event-loop
        # Wired by the server: the ResilienceHub (sheds/retries/breaker/drain
        # counters, serving/resilience.py), the runner's FaultInjector, the
        # JobQueue (durability/replay stats, serving/durability.py), the
        # recovery Watchdog (serving/watchdog.py), and the request Tracer
        # (serving/tracing.py).  All optional so embedded/test hubs render
        # without a server.
        self.resilience = None
        self.faults = None
        self.jobs = None
        self.watchdog = None
        self.tracer = None
        # Residency manager (serving/lifecycle.py): states, activation
        # histograms, HBM budget — wired at server startup.
        self.lifecycle = None
        # Variant selector + brownout ladder (serving/variants.py;
        # docs/VARIANTS.md) — wired at server construction.
        self.variants = None
        # Generation lanes (serving/generation.py; docs/GENERATION.md): a
        # zero-arg callable returning {model: gen_snapshot()} — KV-pool
        # block accounting, prefill chunking, speculative acceptance.
        self.generation = None
        # Multi-tenant adapter manager (serving/adapters.py;
        # docs/ADAPTERS.md): per-tenant residency, attach latency, served
        # counters — wired at server construction.
        self.adapters = None
        # SLO & goodput plane (serving/slo.py; docs/OBSERVABILITY.md §6):
        # per-(model, tenant, lane) outcomes, burn-rate windows, usage
        # ledger — wired at server construction.  The JSON block below is
        # what the fleet router scrapes into its rollup.
        self.slo = None
        # Perf plane (serving/perfplane.py; docs/OBSERVABILITY.md §9):
        # ingest-stage histograms, loop-lag sampler, stack sampler, rolling
        # throughput gauges — wired at server construction.
        self.perf = None
        # Predictive autoscaling plane (serving/autoscale.py;
        # docs/AUTOSCALE.md): per-key demand forecasts, learned keep-warm
        # windows, pre-warm counters — wired at server construction.
        self.autoscale = None
        # Server fast path (docs/SERVERPATH.md): a zero-arg callable
        # returning {ingest_workers, ring_depth, binary_requests,
        # wire_pool} — acceptor topology + binary-lane evidence, wired at
        # server construction.
        self.serverpath = None

    def ring(self, model: str) -> LatencyRing:
        if model not in self.models:
            self.models[model] = LatencyRing()
        return self.models[model]

    def render(self, engine=None) -> dict:
        out = {"models": {k: r.snapshot() for k, r in self.models.items()},
               "gauges": dict(self.gauges)}
        if engine is not None:
            occ = {}
            for name, st in engine.runner.stats.items():
                total = st.samples + st.padded_samples
                by_bucket = {
                    b: {"batches": v["batches"], "samples": v["samples"],
                        "occupancy": round(v["samples"] / v["rows"], 3) if v["rows"] else 1.0}
                    for b, v in st.by_bucket.items()}
                occ[name] = {"batches": st.batches, "samples": st.samples,
                             "batch_occupancy": round(st.samples / total, 3) if total else 1.0,
                             "device_seconds": round(st.device_seconds, 3),
                             **({"chunks": st.chunks} if st.chunks else {}),
                             "by_bucket": by_bucket}
            out["runner"] = occ
            # QoS lane health (docs/QOS.md): per-class queue depth and wait
            # time — the numbers that show whether latency work is sitting
            # behind throughput programs.
            out["dispatch"] = {
                "priority_enabled": engine.runner.priority_enabled,
                "lanes": engine.runner.lane_stats(),
            }
            out["cold_start"] = {"seconds": round(engine.cold_start_seconds, 3),
                                 "compile_entries": list(engine.clock.entries),
                                 "compile_seconds_total": round(engine.clock.total_seconds, 3)}
            per_model = getattr(engine.clock, "per_model", None)
            if per_model is not None:
                # The first-use ledger's totals per model (every lane's):
                # how many programs each model has met this process and
                # their cumulative wall time (launch + first run) — the
                # cold-start cost the lifecycle estimate learns.
                out["cold_start"]["compile_by_model"] = per_model()
            resident = getattr(engine.runner, "resident_bytes", None)
            if resident is not None:
                # Live device-residency accounting (docs/LIFECYCLE.md).
                by_model = resident()
                out["hbm"] = {"by_model": by_model,
                              "total_bytes": sum(by_model.values())}
        if self.resilience is not None:
            out["resilience"] = self.resilience.snapshot()
        if self.faults is not None:
            out["faults"] = self.faults.snapshot()
        if self.jobs is not None:
            # Durability (docs/RESILIENCE.md): journal + replay/recovery
            # stats — recovered_jobs / replay_ms are the boot-recovery proof.
            snap = self.jobs.durability_snapshot()
            if snap is not None:
                out["durability"] = snap
        if self.watchdog is not None:
            out["recovery"] = self.watchdog.snapshot()
        if self.tracer is not None:
            out["tracing"] = self.tracer.snapshot()
        if self.lifecycle is not None:
            # Residency states, activation counts/costs, HBM budget
            # (serving/lifecycle.py; docs/LIFECYCLE.md).
            out["lifecycle"] = self.lifecycle.snapshot()
        if self.variants is not None:
            # Objective-driven variant serving (serving/variants.py;
            # docs/VARIANTS.md): ladders, selections, degradations, sheds,
            # and the per-family brownout state.
            out["variants"] = self.variants.snapshot()
        if self.generation is not None:
            # Generation lanes (docs/GENERATION.md): per-model scheduler
            # mode, KV-pool utilization/evictions (paged), prefill chunk
            # and speculative-acceptance counters.
            out["generation"] = self.generation()
        if self.adapters is not None and self.adapters.enabled:
            # Multi-tenant adapters (docs/ADAPTERS.md): per-tenant
            # residency, attach history, served counts, co-batch evidence.
            out["adapters"] = self.adapters.snapshot()
        if self.slo is not None:
            # SLO & goodput (serving/slo.py): objectives, outcome counts,
            # fast/slow burn rates + alarms, per-tenant usage ledger.
            out["slo"] = self.slo.snapshot()
        if self.perf is not None:
            # Perf plane (serving/perfplane.py; docs/OBSERVABILITY.md §9):
            # loop lag, stack census, rolling gauges, ingest stage tables.
            out["perf"] = self.perf.snapshot(top_stacks=10)
        if self.autoscale is not None:
            # Predictive autoscaling (serving/autoscale.py): per-key
            # forecasts, keep-warm windows, pre-warm hit/miss counters,
            # degradation state.
            out["autoscale"] = self.autoscale.snapshot()
        if self.serverpath is not None:
            # Server fast path (docs/SERVERPATH.md): acceptor worker
            # liveness, shm ring depths, binary-lane request counters,
            # response buffer pool hit rate.
            out["serverpath"] = self.serverpath()
        return out

    def render_prometheus(self, engine=None) -> str:
        """Prometheus text exposition (version 0.0.4) of the same numbers.

        The JSON render stays the primary/test surface; this is the
        ops-integration format — ``curl -H 'Accept: text/plain' /metrics``
        scrapes directly into Prometheus with no adapter.  Latency
        percentiles are emitted as summary-style quantile series (they are
        ring-buffer percentiles, not true streaming quantiles — same numbers
        the JSON reports).
        """
        lines: list[str] = []

        def metric(name, mtype, help_text, samples):
            """samples: [(labels_dict, value)]; skips the family if empty."""
            rows = [(lbl, v) for lbl, v in samples if v is not None]
            if not rows:
                return
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} {mtype}")
            for lbl, v in rows:
                label_s = ",".join(f'{k}="{_prom_label(val)}"'
                                   for k, val in sorted(lbl.items()))
                lines.append(f"{name}{{{label_s}}} {v}" if label_s else f"{name} {v}")

        def histogram(name, help_text, hists):
            """hists: [(labels_dict, Histogram)].  Cumulative buckets with
            OpenMetrics exemplars (``# {trace_id="..."} value ts``) linking
            a scraped bucket back to GET /admin/trace/{id}; _sum/_count
            close the family."""
            rows = [(lbl, h) for lbl, h in hists if h.count]
            if not rows:
                return
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} histogram")
            for lbl, h in rows:
                base = ",".join(f'{k}="{_prom_label(v)}"'
                                for k, v in sorted(lbl.items()))
                sep = "," if base else ""
                for le, acc, ex in h.rows():
                    line = f'{name}_bucket{{{base}{sep}le="{le}"}} {acc}'
                    if ex is not None:
                        tid, val, ts = ex
                        line += (f' # {{trace_id="{_prom_label(tid)}"}} '
                                 f"{round(val, 3)} {round(ts, 3)}")
                    lines.append(line)
                lines.append(f"{name}_sum{{{base}}} {round(h.sum, 3)}"
                             if base else f"{name}_sum {round(h.sum, 3)}")
                lines.append(f"{name}_count{{{base}}} {h.count}"
                             if base else f"{name}_count {h.count}")

        def snap_histogram(name, help_text, snaps_):
            """snaps_: [(labels_dict, Histogram.snapshot() dict)] — renders a
            histogram family from the JSON form (cumulative buckets keyed by
            upper bound).  Used where the publisher hands /metrics a
            JSON-safe snapshot (the generation lanes) rather than the live
            Histogram object; no exemplars on this path."""
            rows = [(lbl, s) for lbl, s in snaps_ if s and s.get("count")]
            if not rows:
                return
            lines.append(f"# HELP {name} {help_text}")
            lines.append(f"# TYPE {name} histogram")
            for lbl, s in rows:
                base = ",".join(f'{k}="{_prom_label(v)}"'
                                for k, v in sorted(lbl.items()))
                sep = "," if base else ""
                for le, acc in s["buckets"].items():
                    lines.append(f'{name}_bucket{{{base}{sep}le="{le}"}} '
                                 f"{acc}")
                lines.append(f"{name}_sum{{{base}}} {s['sum']}"
                             if base else f"{name}_sum {s['sum']}")
                lines.append(f"{name}_count{{{base}}} {s['count']}"
                             if base else f"{name}_count {s['count']}")

        snaps = {m: r.snapshot() for m, r in self.models.items()}
        metric("tpuserve_requests_total", "counter", "Requests recorded per model",
               [({"model": m}, s["requests"]) for m, s in snaps.items()])
        metric("tpuserve_request_errors_total", "counter", "Failed requests per model",
               [({"model": m}, s["errors"]) for m, s in snaps.items()])
        for stage in ("queue", "device", "total"):
            samples = []
            for m, s in snaps.items():
                col = s.get(f"{stage}_ms")
                if col:
                    samples += [({"model": m, "quantile": "0.5"}, col["p50"]),
                                ({"model": m, "quantile": "0.99"}, col["p99"])]
            metric(f"tpuserve_{stage}_latency_ms", "summary",
                   f"Recent {stage} latency percentiles (ring buffer)", samples)
        histogram("tpuserve_queue_ms",
                  "Batcher queue wait per request (ms, lifetime histogram)",
                  [({"model": m}, r.queue_hist)
                   for m, r in self.models.items()])
        histogram("tpuserve_device_ms",
                  "Device batch time per request (ms, lifetime histogram)",
                  [({"model": m}, r.device_hist)
                   for m, r in self.models.items()])
        metric("tpuserve_gauge", "gauge", "Free-form gauges",
               [({"name": _prom_name(k)}, v) for k, v in self.gauges.items()])
        if engine is not None:
            stats = engine.runner.stats
            metric("tpuserve_batches_total", "counter", "Device batches dispatched",
                   [({"model": m}, st.batches) for m, st in stats.items()])
            metric("tpuserve_batch_samples_total", "counter",
                   "Real (non-padding) samples dispatched",
                   [({"model": m}, st.samples) for m, st in stats.items()])
            metric("tpuserve_batch_occupancy", "gauge",
                   "Real samples / padded batch rows (lifetime)",
                   [({"model": m},
                     round(st.samples / (st.samples + st.padded_samples), 3)
                     if st.samples + st.padded_samples else 1.0)
                    for m, st in stats.items()])
            metric("tpuserve_device_seconds_total", "counter",
                   "Device-dispatch wall seconds per model",
                   [({"model": m}, round(st.device_seconds, 3))
                    for m, st in stats.items()])
            metric("tpuserve_chunk_dispatches_total", "counter",
                   "Chunked (preemptible) dispatches per model",
                   [({"model": m}, st.chunks)
                    for m, st in stats.items() if st.chunks])
            lanes = engine.runner.lane_stats()
            metric("tpuserve_dispatch_queue_depth", "gauge",
                   "Dispatch items queued per QoS lane",
                   [({"lane": l}, s["depth"]) for l, s in lanes.items()])
            metric("tpuserve_dispatch_total", "counter",
                   "Dispatches served per QoS lane",
                   [({"lane": l}, s["dispatches"]) for l, s in lanes.items()])
            metric("tpuserve_dispatch_wait_ms_total", "counter",
                   "Cumulative queue wait per QoS lane (ms)",
                   [({"lane": l}, s["wait_ms_total"]) for l, s in lanes.items()])
            metric("tpuserve_dispatch_wait_ms_max", "gauge",
                   "Worst queue wait per QoS lane (ms, lifetime)",
                   [({"lane": l}, s["wait_ms_max"]) for l, s in lanes.items()])
            metric("tpuserve_cold_start_seconds", "gauge",
                   "Engine boot (weights + warmup) seconds",
                   [({}, round(engine.cold_start_seconds, 3))])
            metric("tpuserve_compile_seconds_total", "counter",
                   "Cumulative XLA compile/warmup seconds",
                   [({}, round(engine.clock.total_seconds, 3))])
            metric("tpuserve_compiled_buckets", "gauge",
                   "Executables compiled vs configured per model",
                   [({"model": m, "state": s}, v)
                    for m, cm in engine.models.items()
                    for s, v in (("compiled", len(cm.warmed_buckets)),
                                 ("configured", len(cm.buckets)))])
            per_model = getattr(engine.clock, "per_model", None)
            if per_model is not None:
                clock_by_model = per_model()
                metric("tpuserve_compile_entries", "gauge",
                       "First uses of a program recorded per model",
                       [({"model": m}, v["entries"])
                        for m, v in clock_by_model.items()])
                metric("tpuserve_model_compile_seconds_total", "counter",
                       "Cumulative XLA compile/warmup seconds per model",
                       [({"model": m}, v["seconds"])
                        for m, v in clock_by_model.items()])
                # The ledger again, by what an operator alerts on: a first
                # use in serving hours is a compile on the request path.
                metric("tpuserve_program_first_uses_total", "counter",
                       "Programs a lane met for the first time, by the "
                       "persistent cache's answer (hit|miss|uncached)",
                       [({"model": m, "program": p, "outcome": o}, n)
                        for (m, p, o), n in engine.clock.first_uses().items()])
                metric("tpuserve_program_store_total", "counter",
                       "Generation programs the program store restored "
                       "(hits), compiled and stored (misses), failed to "
                       "load, or left for the jitted function at a launch",
                       [({"model": m, "outcome": o}, n)
                        for m, counts in engine.clock.store_snapshot().items()
                        for o, n in counts.items()])
            resident = getattr(engine.runner, "resident_bytes", None)
            if resident is not None:
                by_model = resident()
                metric("tpuserve_hbm_bytes", "gauge",
                       "Device-resident parameter bytes per model "
                       "(lifecycle budget accounting)",
                       [({"model": m}, v) for m, v in by_model.items()])
        if self.resilience is not None:
            # Resilience layer (docs/RESILIENCE.md): sheds, timeouts, retries,
            # breaker state, drain — per model, mirroring the JSON block.
            from .resilience import BREAKER_STATE_CODE

            snap = self.resilience.snapshot()
            per_model = snap["models"].items()
            metric("tpuserve_deadline_exceeded_total", "counter",
                   "Requests 504'd per model and stage (admission|queue|await)",
                   [({"model": m, "stage": stage}, v)
                    for m, s in per_model
                    for stage, v in s["deadline_exceeded"].items()
                    if stage != "total"])
            metric("tpuserve_load_shed_total", "counter",
                   "Requests 429'd by the queue-wait estimator per model",
                   [({"model": m}, s["shed"]) for m, s in per_model])
            metric("tpuserve_dispatch_retries_total", "counter",
                   "Transient dispatch retries attempted per model",
                   [({"model": m}, s["retries"]) for m, s in per_model])
            metric("tpuserve_dispatch_retry_success_total", "counter",
                   "Dispatches that succeeded after at least one retry",
                   [({"model": m}, s["retry_successes"]) for m, s in per_model])
            metric("tpuserve_breaker_fast_fails_total", "counter",
                   "Requests 503'd by an open circuit breaker per model",
                   [({"model": m}, s["breaker_fast_fails"]) for m, s in per_model])
            metric("tpuserve_breaker_state", "gauge",
                   "Circuit breaker state (0=closed, 1=half_open, 2=open)",
                   [({"model": m}, BREAKER_STATE_CODE[s["breaker"]["state"]])
                    for m, s in per_model if "breaker" in s])
            metric("tpuserve_breaker_opens_total", "counter",
                   "Circuit breaker closed->open transitions per model",
                   [({"model": m}, s["breaker"]["opens"])
                    for m, s in per_model if "breaker" in s])
            metric("tpuserve_draining", "gauge",
                   "1 while the server is draining (SIGTERM received)",
                   [({}, int(snap["draining"]))])
            metric("tpuserve_quarantined", "gauge",
                   "1 while a model is quarantined for engine recovery",
                   [({"model": m}, 1) for m in snap.get("quarantined", [])])
        if self.faults is not None:
            fsnap = self.faults.snapshot()
            metric("tpuserve_faults_injected_total", "counter",
                   "Chaos faults injected by target (dispatch|preprocess)",
                   [({"target": t}, v) for t, v in fsnap["injected"].items()
                    if t != "latency_ms"])
            metric("tpuserve_fault_rules_active", "gauge",
                   "Fault-injection rules currently installed",
                   [({}, len(fsnap["rules"]))])
        dsnap = (self.jobs.durability_snapshot()
                 if self.jobs is not None else None)
        if dsnap is not None:
            # Durability & crash recovery (docs/RESILIENCE.md): journal
            # volume plus what the last boot-time replay restored.
            metric("tpuserve_journal_records_appended_total", "counter",
                   "Job-journal records appended this process lifetime",
                   [({}, dsnap["journal"]["appended"])])
            metric("tpuserve_journal_dropped_records", "gauge",
                   "Corrupt/truncated journal records skipped at replay",
                   [({}, dsnap["dropped_records"])])
            metric("tpuserve_recovered_jobs", "gauge",
                   "Unfinished jobs re-enqueued by the boot-time replay",
                   [({}, dsnap["recovered_jobs"])])
            metric("tpuserve_restored_done_jobs", "gauge",
                   "Terminal jobs (results included) restored at replay",
                   [({}, dsnap["restored_done"])])
            metric("tpuserve_journal_replay_ms", "gauge",
                   "Wall milliseconds the boot-time journal replay took",
                   [({}, dsnap["replay_ms"])])
            metric("tpuserve_idempotent_dedupes_total", "counter",
                   "Submits answered with a prior job via Idempotency-Key",
                   [({}, dsnap["deduped_submits"])])
        if self.watchdog is not None:
            from .watchdog import RECOVERY_STATE_CODE

            wsnap = self.watchdog.snapshot()
            metric("tpuserve_recovery_state", "gauge",
                   "Watchdog state (0=healthy, 1=recovering, 2=gave_up)",
                   [({}, RECOVERY_STATE_CODE[wsnap["state"]])])
            metric("tpuserve_recoveries_total", "counter",
                   "Successful automatic/manual engine recoveries",
                   [({}, wsnap["recoveries_total"])])
            metric("tpuserve_recovery_attempts", "gauge",
                   "Consecutive failed rebuild attempts (resets on success)",
                   [({}, wsnap["attempts"])])
            metric("tpuserve_recovery_requeued_jobs_total", "counter",
                   "Outage-failed jobs requeued after an engine recovery",
                   [({}, wsnap["requeued_jobs_total"])])
        if self.lifecycle is not None:
            # Residency manager (serving/lifecycle.py; docs/LIFECYCLE.md):
            # per-model state gauge (PINNED = 4), activation counters by
            # cause, activation-latency histograms, demotions, cold
            # fast-fails, and the HBM budget gauge.
            lsnap = self.lifecycle.snapshot()
            lmodels = lsnap["models"].items()
            metric("tpuserve_residency_state", "gauge",
                   "Residency state (0=cold,1=warming,2=active,"
                   "3=draining_idle,4=pinned)",
                   [({"model": m}, self.lifecycle.state_code(m))
                    for m, _ in lmodels])
            metric("tpuserve_activations_total", "counter",
                   "Model activations by cause "
                   "(boot|request|job|pin|admin|recovery)",
                   [({"model": m, "cause": c}, n)
                    for m, s in lmodels
                    for c, n in s["activations_by_cause"].items()])
            metric("tpuserve_demotions_total", "counter",
                   "Residency demotions by cause (idle|budget|admin)",
                   [({"model": m, "cause": c}, n)
                    for m, s in lmodels
                    for c, n in s["demotions_by_cause"].items()])
            metric("tpuserve_cold_start_fast_fails_total", "counter",
                   "Requests 503'd cold_start (deadline below the "
                   "activation estimate)",
                   [({"model": m}, s["cold_fast_fails"]) for m, s in lmodels])
            metric("tpuserve_hbm_budget_bytes", "gauge",
                   "Configured device-residency budget (0 = unlimited)",
                   [({}, lsnap["hbm_budget_bytes"])])
            histogram("tpuserve_activation_ms",
                      "Model activation wall time (ms, lifetime histogram)",
                      [({"model": m}, h)
                       for m, h in self.lifecycle.activation_hists.items()])
            # Residency tier footprint (docs/LIFECYCLE.md ladder): device
            # (live HBM), host (host-RAM copies), disk (the store's
            # PHYSICAL post-dedup chunk bytes) — what each rung holds now.
            cs = lsnap.get("ckpt_store")
            metric("tpuserve_residency_tier_bytes", "gauge",
                   "Weight bytes resident per tier (disk = post-dedup "
                   "store bytes)",
                   [({"tier": "device"}, lsnap["hbm_bytes_total"]),
                    ({"tier": "host"}, lsnap["host_bytes_total"]),
                    ({"tier": "disk"},
                     cs["physical_bytes"] if cs is not None else 0)])
            store = getattr(self.lifecycle, "store", None)
            if cs is not None and store is not None:
                # Streaming checkpoint store (serving/ckptstore.py):
                # chunk/dedup counters keyed by the store's (base, adapter)
                # key and the streamed-load latency histogram.
                metric("tpuserve_ckpt_chunks_streamed_total", "counter",
                       "Chunks read through the streamed-load pipeline",
                       [({"model": k}, n)
                        for k, n in cs["chunks_streamed_total"].items()])
                metric("tpuserve_ckpt_dedup_hits_total", "counter",
                       "Staged chunks already content-present in the store",
                       [({"model": k}, n)
                        for k, n in cs["dedup_hits_total"].items()])
                histogram("tpuserve_ckpt_load_ms",
                          "Streamed checkpoint load wall time (ms)",
                          [({"model": k}, h)
                           for k, h in store.load_hists_snapshot().items()])
        if self.variants is not None:
            # Variant serving (serving/variants.py; docs/VARIANTS.md):
            # selections/degradations per (family, variant), family sheds,
            # brownout state + transitions, and the selection-latency
            # histogram — the proof the ladder serves instead of shedding
            # and costs microseconds doing it.
            vsnap = self.variants.snapshot()
            fams = vsnap["families"].items()
            metric("tpuserve_variant_selections_total", "counter",
                   "Family-addressed selections per (family, variant)",
                   [({"family": f, "variant": v}, n)
                    for f, s in fams for v, n in s["selections"].items()])
            metric("tpuserve_variant_degraded_total", "counter",
                   "Selections served below the family's ladder top",
                   [({"family": f, "variant": v}, n)
                    for f, s in fams for v, n in s["degraded"].items()])
            metric("tpuserve_variant_sheds_total", "counter",
                   "Family-addressed requests shed (no variant satisfied "
                   "the objective)",
                   [({"family": f}, s["sheds"]) for f, s in fams
                    if s["sheds"]])
            metric("tpuserve_variant_brownout_state", "gauge",
                   "Brownout state per family (0=off, 1=active, 2=forced)",
                   [({"family": f}, self.variants.brownout.state_code(f))
                    for f, _ in fams])
            metric("tpuserve_variant_brownout_transitions_total", "counter",
                   "Brownout enter/exit transitions per family",
                   [({"family": f, "direction": d}, n)
                    for f, t in self.variants.brownout.transitions.items()
                    for d, n in t.items() if n])
            histogram("tpuserve_variant_select_ms",
                      "Variant selection wall time per family (ms)",
                      [({"family": f}, h)
                       for f, h in self.variants.select_hists.items()])
        if self.generation is not None:
            # Continuous batching v2 (serving/generation.py;
            # docs/GENERATION.md): KV-block pool gauges + eviction counter
            # (paged lanes only), prefill-chunk and speculative
            # propose/accept counters — acceptance rate is
            # accepted/proposed, derivable in any scraper.
            gsnap = self.generation()
            paged = {m: s for m, s in gsnap.items() if "kv" in s}
            metric("tpuserve_kv_blocks_used", "gauge",
                   "KV-cache blocks currently allocated per model",
                   [({"model": m}, s["kv"]["blocks_used"])
                    for m, s in paged.items()])
            metric("tpuserve_kv_blocks_total", "gauge",
                   "Allocatable KV-cache blocks per model (pool size)",
                   [({"model": m}, s["kv"]["blocks_total"])
                    for m, s in paged.items()])
            metric("tpuserve_kv_block_evictions_total", "counter",
                   "Streams evicted + re-queued under KV-pool pressure",
                   [({"model": m}, s["kv"]["evictions"])
                    for m, s in paged.items()])
            metric("tpuserve_prefill_chunks_total", "counter",
                   "Prefill chunks dispatched per model (chunked prefill)",
                   [({"model": m}, s["prefill_chunks"])
                    for m, s in paged.items()])
            metric("tpuserve_spec_proposed_total", "counter",
                   "Draft tokens proposed per model (speculative decoding)",
                   [({"model": m}, s["spec"]["proposed"])
                    for m, s in paged.items()])
            metric("tpuserve_spec_accepted_total", "counter",
                   "Draft tokens accepted by verification per model",
                   [({"model": m}, s["spec"]["accepted"])
                    for m, s in paged.items()])
            # Prefix KV cache (serving/prefixcache.py; docs/PREFIX.md):
            # radix-tree reuse counters — hit rate is hits/(hits+misses),
            # derivable in any scraper; nodes/pages are cumulative
            # created/frozen totals (live counts ride the JSON snapshot).
            pref = {m: s["prefix"] for m, s in paged.items()
                    if s.get("prefix")}
            metric("tpuserve_prefix_hits_total", "counter",
                   "Admissions that reused frozen prefix pages per model",
                   [({"model": m}, p["hits"]) for m, p in pref.items()])
            metric("tpuserve_prefix_misses_total", "counter",
                   "Admissions that prefilled cold per model",
                   [({"model": m}, p["misses"]) for m, p in pref.items()])
            metric("tpuserve_prefix_nodes_total", "counter",
                   "Radix-tree nodes ever created per model",
                   [({"model": m}, p["nodes_total"])
                    for m, p in pref.items()])
            metric("tpuserve_prefix_pages_total", "counter",
                   "KV pages ever frozen into the prefix tree per model",
                   [({"model": m}, p["pages_total"])
                    for m, p in pref.items()])
            metric("tpuserve_prefix_cow_copies_total", "counter",
                   "Copy-on-write page clones on prefix divergence",
                   [({"model": m}, p["cow_copies"])
                    for m, p in pref.items()])
            metric("tpuserve_prefix_evictions_total", "counter",
                   "Prefix nodes evicted (LRU decay, reclaim, invalidation)",
                   [({"model": m}, p["evictions"])
                    for m, p in pref.items()])
            snap_histogram("tpuserve_prefix_cached_tokens",
                           "Prefix tokens served from frozen pages per hit",
                           [({"model": m}, p.get("cached_tokens"))
                            for m, p in pref.items()])
            # Live KV migration (serving/kvmigrate.py; docs/DISAGG.md):
            # migrations by cause (pressure = migrate-out under KV
            # pressure, failover = resumed after a replica death, admin =
            # operator/router driven), page counts by dedup outcome, and
            # the wall-time histogram.
            mig = {m: s["migration"] for m, s in paged.items()
                   if s.get("migration")}
            metric("tpuserve_migrations_total", "counter",
                   "Live stream migrations per model by cause "
                   "(pressure|failover|admin)",
                   [({"model": m, "cause": c}, n)
                    for m, g in mig.items()
                    for c, n in g["by_cause"].items() if n])
            metric("tpuserve_migration_pages_total", "counter",
                   "KV pages moved per model by dedup outcome "
                   "(hit = adopted from the local prefix tree, "
                   "copied = transferred by value)",
                   [({"model": m, "dedup": d}, n)
                    for m, g in mig.items()
                    for d, n in g["pages"].items() if n])
            snap_histogram("tpuserve_migration_ms",
                           "Stream migration wall time (ms)",
                           [({"model": m}, g.get("ms"))
                            for m, g in mig.items()])
            # Split per-token timing (docs/OBSERVABILITY.md §9): ttft =
            # submit → first token (admission + prefill), itl = steady-state
            # inter-token gap (decode cadence) — separated so a prefill
            # regression and a cadence regression are distinguishable; both
            # lanes (slot + paged) publish them.
            lat = {m: s["latency"] for m, s in gsnap.items()
                   if s.get("latency")}
            snap_histogram("tpuserve_ttft_ms",
                           "Time to first streamed token per request (ms)",
                           [({"model": m}, l.get("ttft_ms"))
                            for m, l in lat.items()])
            snap_histogram("tpuserve_itl_ms",
                           "Steady-state inter-token latency (ms)",
                           [({"model": m}, l.get("itl_ms"))
                            for m, l in lat.items()])
            metric("tpuserve_tokens_streamed_total", "counter",
                   "Tokens streamed to clients per model (:generate lanes)",
                   [({"model": m}, s["tokens_emitted"])
                    for m, s in gsnap.items()
                    if s.get("tokens_emitted") is not None])
            # Decode segments fetched, and those of them that the call which
            # fetched the segment before had already launched (slot lanes):
            # chained / segment is the share of rounds whose tokens were
            # fanned out while the device worked (docs/GENERATION.md).
            for key, what in (("segment_rounds", "Decode segments fetched "
                               "per model (:generate lanes)"),
                              ("chained_rounds", "Decode segments launched "
                               "by the call that fetched the one before"),
                              ("window_rolls", "Times a generating slot's "
                               "span moved its start during a segment (a "
                               "window of its cache completed)"),
                              ("prefill_dispatches", "Prefill programs "
                               "launched per model (slot lanes)"),
                              ("prefill_kernel_dispatches", "Those of them "
                               "whose prompt attention took the Pallas "
                               "kernel (ops/flash_attention.prompt_form)"),
                              ("prefill_rows_padded", "Rows those programs "
                               "multiplied: padded batch x bucket, summed"),
                              ("prefill_rows_prompt", "Positions the prompts "
                               "in them hold"),
                              ("prompts_moved_up", "Prompts prefilled in a "
                               "longer bucket's dispatch than their own "
                               "(serving/generation.plan_prefills)")):
                metric(f"tpuserve_{key}_total", "counter", what,
                       [({"model": m}, s[key]) for m, s in gsnap.items()
                        if s.get(key) is not None])
            metric("tpuserve_prefill_bucket_prompts_total", "counter",
                   "Prompts prefilled (a batch's padding with them) by the "
                   "bucket of their dispatch (slot lanes)",
                   [({"model": m, "bucket": b}, n) for m, s in gsnap.items()
                    for b, n in s.get("prefill_buckets", {}).items()])
            # How much of the slot pool decode attention has to read
            # (live), and how much its copies cover (read), per segment round
            # (slot lanes): _sum / _count is the mean share.
            # The same spans in rows (what they hold, the summary rows among
            # them, the positions they stand for), not divided by the pool.
            # A lane whose model counts (``step_counters``: name -> what it
            # counts) adds its own pairs, under its own names.
            counted = {key: what for s in gsnap.values()
                       for key, what in s.get("step_counters", {}).items()}
            for key, what in (
                    ("kv_live_share", "Rows the generating slots' spans "
                     "hold over slots x rows"),
                    ("kv_read_share", "Rows decode attention's copies cover "
                     "over slots x rows"),
                    ("span_rows", "Cache rows the generating slots' spans "
                     "hold"),
                    ("summary_rows", "Rows of those spans that stand for "
                     "more than one position"),
                    ("live_positions", "Positions the generating slots have "
                     "written"),
                    *counted.items()):
                held = {m: s[key] for m, s in gsnap.items()
                        if s.get(key, {}).get("count")}
                if not held:
                    continue
                lines.append(f"# HELP tpuserve_{key} {what}, per segment "
                             "round")
                lines.append(f"# TYPE tpuserve_{key} summary")
                for m, v in held.items():
                    label = f'{{model="{_prom_label(m)}"}}'
                    lines.append(f"tpuserve_{key}_sum{label} {v['sum']}")
                    lines.append(f"tpuserve_{key}_count{label} {v['count']}")
            # A lane whose model keeps more than one kind of K/V layer: the
            # rows its spans hold, a kind.
            by_kind = [(m, kind, v) for m, s in gsnap.items()
                       for kind, v in s.get("span_rows_by_kind", {}).items()]
            if by_kind:
                lines.append("# HELP tpuserve_span_rows_by_kind Cache rows "
                             "the generating slots' spans hold in one layer "
                             "of a kind, per segment round")
                lines.append("# TYPE tpuserve_span_rows_by_kind summary")
                for m, kind, v in by_kind:
                    label = (f'{{model="{_prom_label(m)}",'
                             f'kind="{_prom_label(kind)}"}}')
                    lines.append(
                        f"tpuserve_span_rows_by_kind_sum{label} {v['sum']}")
                    lines.append(
                        f"tpuserve_span_rows_by_kind_count{label} "
                        f"{v['count']}")
        if self.adapters is not None and self.adapters.enabled:
            # Multi-tenant adapters (serving/adapters.py; docs/ADAPTERS.md):
            # per-tenant residency gauge, attach-latency histograms, and the
            # per-tenant served counter — the "scale-to-zero per TENANT"
            # numbers beside the per-model lifecycle families above.
            asnap = self.adapters.snapshot()
            rows = [(b, a, s) for b, ads in asnap["models"].items()
                    for a, s in ads.items()]
            metric("tpuserve_adapter_residency", "gauge",
                   "Adapter residency (0=cold, 1=attaching, 2=active)",
                   [({"model": b, "adapter": a},
                     {"cold": 0, "attaching": 1, "active": 2}[s["state"]])
                    for b, a, s in rows])
            metric("tpuserve_adapter_served_total", "counter",
                   "Requests served per (model, adapter) tenant",
                   [({"model": b, "adapter": a}, s["served"])
                    for b, a, s in rows])
            metric("tpuserve_adapter_cold_fast_fails_total", "counter",
                   "Requests 503'd adapter_cold (deadline below the attach "
                   "estimate)",
                   [({"model": b, "adapter": a}, s["cold_fast_fails"])
                    for b, a, s in rows if s["cold_fast_fails"]])
            metric("tpuserve_adapter_multi_batches_total", "counter",
                   "Device dispatches that co-batched >1 distinct adapter",
                   [({}, asnap["multi_adapter_batches"])])
            histogram("tpuserve_adapter_attach_ms",
                      "Adapter attach wall time (ms, lifetime histogram)",
                      [(dict(zip(("model", "adapter"), key.split(":", 1))),
                        h)
                       for key, h in self.adapters.attach_hists.items()])
        if self.slo is not None:
            # SLO & goodput plane (serving/slo.py; docs/OBSERVABILITY.md
            # §6): outcome counters, goodput ratio, and the fast/slow
            # burn-rate pair with its alarm gauge — burn >= 1 means the
            # error budget exhausts exactly at the SLO horizon; the alarm
            # thresholds ride ServeConfig.slo_{fast,slow}_burn_alarm.
            ssnap = self.slo.snapshot()
            rows = [(key, lane, s)
                    for key, lanes in ssnap["models"].items()
                    for lane, s in lanes.items()]
            metric("tpuserve_slo_requests_total", "counter",
                   "SLO-classified requests per (model, lane, outcome: "
                   "good|degraded|late|shed|error)",
                   [({"model": k, "lane": ln, "outcome": o}, n)
                    for k, ln, s in rows
                    for o, n in s["outcomes"].items() if n])
            metric("tpuserve_slo_goodput_ratio", "gauge",
                   "Lifetime goodput fraction (good+degraded)/total",
                   [({"model": k, "lane": ln}, s["goodput_ratio"])
                    for k, ln, s in rows])
            metric("tpuserve_slo_burn_rate", "gauge",
                   "Error-budget burn rate per rolling window "
                   "(bad fraction / budget; 1 = exhausts at the horizon)",
                   [({"model": k, "lane": ln, "window": w},
                     s["windows"][w]["burn_rate"])
                    for k, ln, s in rows for w in ("fast", "slow")])
            metric("tpuserve_slo_burn_alarm", "gauge",
                   "1 while a window's burn rate is over its alarm "
                   "threshold",
                   [({"model": k, "lane": ln, "window": w},
                     int(s["windows"][w]["alarm"]))
                    for k, ln, s in rows for w in ("fast", "slow")])
            metric("tpuserve_slo_budget_remaining", "gauge",
                   "max(1 - burn_rate, 0) per rolling window",
                   [({"model": k, "lane": ln, "window": w},
                     s["windows"][w]["budget_remaining"])
                    for k, ln, s in rows for w in ("fast", "slow")])
            # Per-tenant usage ledger (docs/OBSERVABILITY.md §7): the
            # "at what cost" families, keyed like the HBM ledger.
            urows = [(dict(zip(("model", "adapter"),
                               (key.split(":", 1) + [""])[:2])), row)
                     for key, row in ssnap["usage"].items()]
            metric("tpuserve_usage_requests_total", "counter",
                   "Requests billed to a tenant's usage ledger row",
                   [(lbl, row["requests"]) for lbl, row in urows])
            metric("tpuserve_usage_device_ms_total", "counter",
                   "Device milliseconds consumed per tenant",
                   [(lbl, row["device_ms"]) for lbl, row in urows])
            metric("tpuserve_usage_kv_block_seconds_total", "counter",
                   "KV page-seconds held per tenant (paged :generate)",
                   [(lbl, row["kv_block_seconds"])
                    for lbl, row in urows if row["kv_block_seconds"]])
            metric("tpuserve_usage_prefix_saved_tokens_total", "counter",
                   "Prompt tokens served from frozen prefix pages per "
                   "tenant (the prefix cache's savings)",
                   [(lbl, row["prefix_saved_tokens"])
                    for lbl, row in urows if row["prefix_saved_tokens"]])
            metric("tpuserve_usage_adapter_attach_ms_total", "counter",
                   "Adapter attach wall milliseconds billed per tenant",
                   [(lbl, row["attach_ms"])
                    for lbl, row in urows if row["attach_ms"]])
        if self.perf is not None:
            # Perf plane (serving/perfplane.py; docs/OBSERVABILITY.md §9):
            # event-loop lag, stack-sampler census, per-(model, stage)
            # ingest/egress histograms, and the rolling throughput gauges.
            lag = self.perf.loop_lag
            histogram("tpuserve_loop_lag_ms",
                      "Event-loop callback lag: scheduled vs actual (ms)",
                      [({}, lag.hist)])
            metric("tpuserve_loop_lag_max_ms", "gauge",
                   "Worst event-loop lag observed this process (ms)",
                   [({}, round(lag.max_ms, 3)) if lag.ticks else ({}, None)])
            stacks = self.perf.stacks.snapshot(top=1)
            metric("tpuserve_stack_samples_total", "counter",
                   "Thread-stack sampler wakeups this process lifetime",
                   [({}, stacks["samples"]) if stacks["samples"] else
                    ({}, None)])
            histogram("tpuserve_ingest_ms",
                      "Host-side ingest/egress stage wall time per "
                      "(model, stage) — the http-to-device gap decomposition",
                      [({"model": m, "stage": st}, h)
                       for (m, st), h in list(self.perf.ingest.items())])
            rows = self.perf.model_gauges().items()
            metric("tpuserve_perf_samples_per_s", "gauge",
                   "Rolling-window samples/s per model (perf plane)",
                   [({"model": m}, r.get("samples_per_s")) for m, r in rows])
            metric("tpuserve_perf_tokens_per_s", "gauge",
                   "Rolling-window streamed tokens/s per generation lane",
                   [({"model": m}, r.get("tokens_per_s")) for m, r in rows])
            metric("tpuserve_perf_step_ms", "gauge",
                   "Rolling-window mean device step time per model (ms)",
                   [({"model": m}, r.get("step_ms")) for m, r in rows])
            metric("tpuserve_perf_device_util_pct", "gauge",
                   "Rolling-window device-lane occupancy per model (%)",
                   [({"model": m}, r.get("device_util_pct"))
                    for m, r in rows])
            metric("tpuserve_perf_mfu_pct", "gauge",
                   "Rolling-window MFU per model (needs a flops_per_sample "
                   "hint; absent otherwise)",
                   [({"model": m}, r.get("mfu_pct")) for m, r in rows])
            if engine is not None:
                # Read at scrape time; the CPU keeps no such count, and the
                # family is then absent.
                from ..utils.device import device_memory

                metric("tpuserve_device_memory_bytes", "gauge",
                       "Device memory from memory_stats(): in use, peak "
                       "since process start, and the limit",
                       [({"device": str(row["id"]), "kind": kind}, row[key])
                        for row in device_memory()
                        for kind, key in (("in_use", "bytes_in_use"),
                                          ("peak", "peak_bytes_in_use"),
                                          ("limit", "bytes_limit"))])
        if self.autoscale is not None:
            # Predictive autoscaling plane (serving/autoscale.py;
            # docs/AUTOSCALE.md): the demand forecast, the learned
            # keep-warm window each key currently earns, and the pre-warm
            # counter by cause (predicted vs phantom chaos).  The fleet
            # router renders the companion
            # tpuserve_autoscale_scale_events_total{direction} family.
            asnap = self.autoscale.snapshot()
            arows = list(asnap["models"].items())
            metric("tpuserve_autoscale_forecast_rps", "gauge",
                   "Short-horizon offered-rate forecast per demand key",
                   [({"model": k}, m["forecast_rps"]) for k, m in arows])
            metric("tpuserve_autoscale_keepwarm_window_s", "gauge",
                   "Learned keep-warm window per demand key (absent while "
                   "history is thin or the plane is degraded)",
                   [({"model": k}, m["keepwarm_window_s"])
                    for k, m in arows])
            metric("tpuserve_autoscale_prewarm_total", "counter",
                   "Pre-warm actions fired per (key, cause: "
                   "predicted|phantom)",
                   [({"model": k, "cause": c}, n)
                    for k, m in arows
                    for c, n in m["prewarms_by_cause"].items() if n])
        if self.serverpath is not None:
            # Server fast path (docs/SERVERPATH.md): acceptor topology +
            # binary tensor lane adoption.  Ring depth is labelled by ring
            # name (req / resp:<worker>) so a stuck consumer shows up as
            # one ring pinned at capacity rather than a blended average.
            spsnap = self.serverpath()
            metric("tpuserve_ingest_workers", "gauge",
                   "Live SO_REUSEPORT acceptor worker processes (0 = "
                   "single-process mode)",
                   [({}, spsnap["ingest_workers"])])
            metric("tpuserve_shm_ring_depth", "gauge",
                   "Occupied slots per shared-memory ring between acceptors "
                   "and the device-dispatch process",
                   [({"ring": r}, d)
                    for r, d in sorted(spsnap["ring_depth"].items())])
            metric("tpuserve_binary_lane_requests_total", "counter",
                   "Requests decoded on the zero-copy binary tensor lane, "
                   "per model",
                   [({"model": m}, n)
                    for m, n in sorted(spsnap["binary_requests"].items())])
            # Acceptor telemetry plane (docs/OBSERVABILITY.md §10): the
            # per-worker stats blocks crossed back from the worker
            # processes, plus the pump-side ring-wait / occupancy
            # histograms.  Families pinned in tools/metrics_manifest.json.
            acc = spsnap.get("acceptor") or {}
            arows = acc.get("workers") or []
            metric("tpuserve_acceptor_accepts_total", "counter",
                   "HTTP requests accepted per acceptor worker process",
                   [({"worker": str(r["worker"])}, r.get("accepts"))
                    for r in arows])
            metric("tpuserve_acceptor_sheds_total", "counter",
                   "Worker-local sheds per acceptor worker, by HTTP code",
                   [({"worker": str(r["worker"]), "code": code},
                     r.get(f"shed_{code}"))
                    for r in arows
                    for code in ("400", "413", "415", "429", "504")
                    if r.get(f"shed_{code}")])
            metric("tpuserve_acceptor_responses_total", "counter",
                   "Responses sent per acceptor worker, by outcome",
                   [({"worker": str(r["worker"]), "outcome": oc},
                     r.get(f"responses_{oc}"))
                    for r in arows for oc in ("ok", "err")])
            metric("tpuserve_acceptor_bytes_total", "counter",
                   "Bytes through each acceptor worker, by direction",
                   [({"worker": str(r["worker"]), "direction": d},
                     r.get(f"bytes_{d}"))
                    for r in arows for d in ("in", "out")])
            metric("tpuserve_acceptor_worker_up", "gauge",
                   "Acceptor worker liveness (0 = died, awaiting respawn)",
                   [({"worker": str(r["worker"])}, 1 if r.get("up") else 0)
                    for r in arows])
            metric("tpuserve_acceptor_heartbeat_age_s", "gauge",
                   "Seconds since each acceptor worker's liveness heartbeat",
                   [({"worker": str(r["worker"])}, r.get("heartbeat_age_s"))
                    for r in arows])
            if arows:
                metric("tpuserve_acceptor_restarts_total", "counter",
                       "Acceptor worker deaths detected (each is respawned)",
                       [({}, acc.get("restarts", 0))])
            snap_histogram("tpuserve_acceptor_inworker_ms",
                           "In-worker time accept→ring-push per acceptor "
                           "worker (ms)",
                           [({"worker": str(r["worker"])},
                             r.get("inworker_ms")) for r in arows])
            snap_histogram("tpuserve_acceptor_ring_wait_ms",
                           "Ring wait worker-push→pump-pop across all "
                           "workers (ms)",
                           [({}, acc.get("ring_wait_ms"))])
            snap_histogram("tpuserve_shm_ring_occupancy_pct",
                           "Ring occupancy (% of slots) sampled per busy "
                           "pump cycle",
                           [({"ring": rname}, s) for rname, s in
                            sorted((acc.get("ring_occupancy_pct")
                                    or {}).items())])
        if self.tracer is not None:
            tsnap = self.tracer.snapshot()
            metric("tpuserve_traces_finished_total", "counter",
                   "Request traces finished this process lifetime",
                   [({}, tsnap["finished"])])
            metric("tpuserve_trace_spans_dropped_total", "counter",
                   "Spans dropped by per-trace span budgets",
                   [({}, tsnap["dropped_spans"])])
            metric("tpuserve_traces_pinned", "gauge",
                   "Flight-recorder pins (slowest / recent errored traces)",
                   [({"kind": "slow"}, tsnap["pinned_slow"]),
                    ({"kind": "errored"}, tsnap["pinned_errored"])])
        return "\n".join(lines) + "\n"
