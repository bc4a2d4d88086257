"""The HTTP serving layer — Flask + Zappa shim, rebuilt for a TPU VM.

The reference's L3/L4 is a Flask app behind Zappa's WSGI→Lambda translation
(SURVEY §1): one request, one synchronous forward.  Here a single asyncio
process (aiohttp; Flask is not installed and WSGI's thread-per-request model
wastes a TPU host) owns the engine, per-model dynamic batchers, and the async
job queue.  Routes:

- ``GET  /``                                health + model list (reference's ``GET /``)
- ``GET  /healthz``                         device probe + per-model readiness
- ``GET  /metrics``                         BASELINE metrics (p50/p99, req/s, occupancy)
- ``GET  /v1/models``                       model discovery (buckets, endpoints)
- ``POST /v1/models/{name}:predict``        sync predict (batched); a JSON
  body ``{"instances": [...]}`` carries N inputs in one request (admitted
  atomically, co-batched, per-instance predictions list back)
- ``POST /predict``, ``POST /classify``     reference-compatible aliases → default model
- ``POST /v1/models/{name}:submit``         async job (latency-tolerant, e.g. sd15);
  ``Idempotency-Key`` header / ``idempotency_key`` body field dedupes
  resubmits to the original job — across restarts via the journal
- ``GET  /v1/jobs/{id}``                    job status/result
- ``POST /admin/recover``                   manual engine recovery (watchdog path)

Request bodies: raw image bytes (``image/*`` / ``application/octet-stream``),
JSON (``{"b64": ...}`` images, ``{"text": ...}`` token models), or — the
zero-copy fast lane (docs/SERVERPATH.md) — ``application/x-tpuserve-tensor``
frames carrying dtype+shape headers plus raw row-major bytes, decoded to
``np.frombuffer`` views with no base64, no JSON parse, and no per-instance
copy.  JSON/image payloads preprocess via the servable's hook in the default
executor so the event loop never blocks on PIL.
"""

from __future__ import annotations

import asyncio
import base64
import json
import math
import signal
import time
from typing import Any

import numpy as np
from aiohttp import web

from ..config import ServeConfig
from ..utils import boot
from ..utils.device import device_info, device_memory
from ..utils.logging import current_trace_id, get_logger, log_event
from ..engine.loader import Engine, build_engine
from .adapters import AdapterCold, AdapterManager, UnknownAdapter
from .autoscale import AutoscalePlane
from .batcher import DynamicBatcher, Overloaded
from .durability import JobJournal
from .generation import (DraftGate, GenerationScheduler,
                         PagedGenerationScheduler)
from .jobs import JobQueue
from .kvcache import KVPoolExhausted
from .kvmigrate import (CAUSES, FORMAT_VERSION, MigrationError,
                        MigrationNeedsPages, PageIntegrityError,
                        check_manifest, pack_page, unpack_page)
from .lifecycle import ColdStart, LifecycleManager
from .metrics import MetricsHub
from .perfplane import PerfPlane, hist_quantile
from .resilience import DeadlineExceeded, ResilienceHub, run_with_retry
from .slo import SLOHub
from .tracing import Tracer, new_request_id
from .variants import Objective, VariantHub
from .watchdog import Watchdog
from . import wire

log = get_logger("serving.server")


class _ReqCtx:
    """Per-request observability handle (docs/OBSERVABILITY.md).

    Opened by the lifecycle middleware for every work request: mints (or
    ingests, via ``X-Request-Id``) the request id, starts the trace (joining
    an inbound W3C ``traceparent`` when present), and stamps the trace id
    into the logging context so every record the handler emits correlates.
    The middleware closes it after the handler: response headers
    (``X-Request-Id`` / ``X-Trace-Id``), trace finish keyed off the HTTP
    status, contextvar reset.
    """

    def __init__(self, server: "Server", request: web.Request, kind: str,
                 model: str | None):
        self.server = server
        self.kind = kind
        self.model = model
        self.request_id = (request.headers.get("X-Request-Id")
                           or new_request_id())
        self.span = server.tracer.start(
            kind, model=model, traceparent=request.headers.get("traceparent"),
            request_id=self.request_id,
            **({"path": request.path} if model is None else {}))
        self.trace = self.span.trace
        self._cv_token = current_trace_id.set(self.trace.trace_id)
        # True once the trace's lifetime has been handed to the job lane
        # (:submit): the middleware then ends the root span but leaves the
        # trace open for the worker to finish at the job's terminal state.
        self.detached = False

    @property
    def trace_id(self) -> str:
        return self.trace.trace_id

    def detach(self):
        self.detached = True

    def close(self, resp: web.StreamResponse | None):
        status = resp.status if resp is not None else 500
        if resp is not None and not resp.prepared:
            # Streamed (SSE) responses were prepared mid-handler and set
            # their own correlation headers there.
            resp.headers.setdefault("X-Request-Id", self.request_id)
            resp.headers.setdefault("X-Trace-Id", self.trace_id)
        if self.detached:
            self.span.end()  # the job worker finishes the trace
        else:
            # Root-span status wins over the HTTP code: a mid-SSE failure
            # streams inside a 200 but must still pin as an errored trace.
            err = status >= 400 or self.span.status == "error"
            self.server.tracer.finish(self.trace, "error" if err else "ok")
        current_trace_id.reset(self._cv_token)


def _error(status: int, msg: str, ctx: _ReqCtx | None = None,
           **extra) -> web.Response:
    """Error envelope; with a request context it carries the correlation ids
    and emits the matching structured log record — no 4xx/5xx on the work
    surface leaves without a ``request_id``/``trace_id`` a client can quote
    and an operator can grep (``tpuserve tail --trace``)."""
    body = {"error": msg, **extra}
    if ctx is not None:
        body.setdefault("request_id", ctx.request_id)
        body.setdefault("trace_id", ctx.trace_id)
        ctx.span.annotate(http_status=status, error=msg)
        log_event(log, "request error", kind=ctx.kind, model=ctx.model,
                  status=status, error=msg, request_id=ctx.request_id,
                  trace_id=ctx.trace_id)
    return web.json_response(body, status=status)


def _error_retry(status: int, msg: str, retry_after_s: float,
                 ctx: _ReqCtx | None = None, **extra) -> web.Response:
    """Throttling/unavailability responses carry Retry-After (SURVEY §5:
    Lambda throttles with Retry-After; bare 429/503 strings teach clients
    nothing about when to come back)."""
    resp = _error(status, msg, ctx=ctx, **extra)
    resp.headers["Retry-After"] = str(max(int(math.ceil(retry_after_s)), 1))
    return resp


class _BinaryLaneDisabled(Exception):
    """A tensor frame arrived while ServeConfig.binary_lane is off (415)."""


def _payload_error(e: Exception, ctx: _ReqCtx | None) -> web.Response:
    """Map a payload-decode failure to its contract status
    (docs/SERVERPATH.md): an oversized DECLARED frame is 413, a frame on a
    disabled lane is 415, anything malformed is 400 — every one through the
    :func:`_error` envelope so the body carries the request/trace ids."""
    if isinstance(e, wire.FrameTooLarge):
        return _error(413, f"tensor frame too large: {e}", ctx=ctx)
    if isinstance(e, _BinaryLaneDisabled):
        return _error(415, str(e), ctx=ctx)
    return _error(400, f"bad request body: {type(e).__name__}: {e}", ctx=ctx)


# Compact separators + a direct-to-bytes body: web.json_response dumps with
# spaced separators into a str and the payload layer encodes that str AGAIN;
# the success path instead serializes the whole response (predictions list
# included) in ONE encoder walk straight to the wire bytes — the JSON lane's
# share of the ISSUE-16 batch-level serialization.
_JSON_SEPARATORS = (",", ":")


def _json_body_response(obj: Any, status: int = 200) -> web.Response:
    return web.Response(
        body=json.dumps(obj, separators=_JSON_SEPARATORS).encode(),
        status=status, content_type="application/json")


def _unwrap_b64(payload: Any) -> Any:
    """The wire convention for binary-in-JSON: {"b64": ...} → raw bytes.

    Shared by whole-body decode and the per-instance batch path so single and
    batch predict can never diverge on the envelope rule.
    """
    if isinstance(payload, dict) and "b64" in payload:
        return base64.b64decode(payload["b64"])
    return payload


def _substage(request: web.Request, stage: str, t0: float, t1: float,
              **attrs) -> None:
    """One ingest substage observation (docs/OBSERVABILITY.md §9): a
    per-(model, stage) histogram row on the perf plane plus a waterfall
    substage span on the request trace.  Substage spans overlap the
    admission/queue/device/respond chain, so the attribution table counts
    them beside — never inside — stage coverage (tools/tracedump.py)."""
    ctx = request.get("obs")
    if ctx is None:
        return
    ctx.server.perf.note_stage(ctx.model, stage, (t1 - t0) * 1000.0)
    ctx.span.child(stage, start=t0, **attrs).end(end=t1)


async def _decode_payload(request: web.Request,
                          extract: dict[str, Any] | None = None) -> Any:
    """Decode the request body; optionally pop envelope fields first.

    ``extract`` maps field names to default values: matching top-level keys
    of a JSON-object body are popped into it BEFORE the ``b64`` unwrap —
    ``{"b64": ..., "idempotency_key": ...}`` must surrender its key to the
    caller, not lose it when the envelope collapses to raw bytes.

    Instrumented end to end (docs/OBSERVABILITY.md §9): socket read, JSON
    parse, and b64 unwrap each stamp their own substage — the three host
    costs that tile most of the pre-queue http→device gap.
    """
    ctype = request.content_type or ""
    t0 = time.perf_counter()
    body = await request.read()
    _substage(request, "payload_read", t0, time.perf_counter(),
              bytes=len(body))
    if ctype.startswith("image/") or ctype == "application/octet-stream":
        return body
    if ctype == wire.TENSOR_CONTENT_TYPE:
        # Zero-copy binary tensor lane (docs/SERVERPATH.md): dtype+shape
        # header + raw row-major bytes, decoded to np.frombuffer views over
        # the request body — no base64, no JSON parse, no per-instance
        # Python loop.  Multi-block (or FLAG_LIST) frames collapse onto the
        # existing {"instances": [...]} batch contract so admission,
        # shedding, and co-batching behave identically across lanes.
        ctx = request.get("obs")
        cfg = ctx.server.cfg if ctx is not None else None
        if cfg is not None and not cfg.binary_lane:
            raise _BinaryLaneDisabled(
                "the binary tensor lane is disabled on this server "
                "(ServeConfig.binary_lane=false); send JSON or image bodies")
        cap = ((cfg.tensor_max_bytes or 64 * 1024 * 1024)
               if cfg is not None else 64 * 1024 * 1024)
        t1 = time.perf_counter()
        items, flags = wire.unpack(body, max_bytes=cap)
        _substage(request, "binary_decode", t1, time.perf_counter(),
                  blocks=len(items))
        if flags & wire.FLAG_META:
            raise wire.FrameError("FLAG_META frames are response-only")
        request["_binary_lane"] = True
        if ctx is not None:
            ctx.server.note_binary_request(ctx.model)
        if flags & wire.FLAG_LIST or len(items) > 1:
            return {"instances": items}
        return items[0]
    if ctype == "application/json" or (body[:1] in (b"{", b"[")):
        t1 = time.perf_counter()
        try:
            data = json.loads(body)
        except ValueError:
            if ctype == "application/json":
                raise
            return body  # sniffed wrong: binary payload that happens to start with { or [
        _substage(request, "json_decode", t1, time.perf_counter())
        if extract is not None and isinstance(data, dict):
            for field in list(extract):
                if field in data:
                    extract[field] = data.pop(field)
        if isinstance(data, dict) and "b64" in data:
            t2 = time.perf_counter()
            data = _unwrap_b64(data)
            _substage(request, "b64_decode", t2, time.perf_counter())
        return data
    return body


class Server:
    def __init__(self, cfg: ServeConfig, engine: Engine | None = None):
        self.cfg = cfg
        self.engine = engine
        self._owns_engine = engine is None
        self.metrics = MetricsHub()
        # Request tracer (serving/tracing.py): per-request span trees in a
        # bounded ring + flight recorder, queryable on /admin/trace.
        self.tracer = Tracer(ring=cfg.trace_ring,
                             flight_slow=cfg.trace_flight_slow,
                             flight_errors=cfg.trace_flight_errors,
                             max_spans=cfg.trace_max_spans)
        self.metrics.tracer = self.tracer
        # Perf plane (serving/perfplane.py; docs/OBSERVABILITY.md §9):
        # ingest-stage histograms, event-loop lag + thread-stack samplers,
        # rolling per-model throughput gauges.  Always constructed so
        # /admin/perf and the tpuserve_ingest_ms/tpuserve_perf_* families
        # exist; ServeConfig.perfplane=False makes every record a no-op.
        self.perf = PerfPlane(cfg)
        self.metrics.perf = self.perf
        self.batchers: dict[str, DynamicBatcher] = {}
        self.schedulers: dict[str, GenerationScheduler] = {}
        self.jobs: JobQueue | None = None
        self.watchdog: Watchdog | None = None
        # Serverless residency manager (serving/lifecycle.py): lazy
        # activation, scale-to-zero, HBM budget.  Built at startup once the
        # engine exists; always present so /admin/models and the residency
        # metrics work even when every lifecycle knob is off.
        self.lifecycle: LifecycleManager | None = None
        # Streaming checkpoint store (serving/ckptstore.py): built at
        # startup when ckpt_store_dir is set; None → disk tier off.
        self.ckpt_store = None
        self._supervisor: asyncio.Task | None = None
        self._heartbeat: asyncio.Task | None = None
        self._rebuild_lock = asyncio.Lock()
        self._tracing = False
        # Request-resilience state (docs/RESILIENCE.md): per-model breakers,
        # retry policy, shed/timeout counters, plus the drain flag.
        self.resilience = ResilienceHub(cfg)
        self.metrics.resilience = self.resilience
        # Objective-driven variant serving (serving/variants.py;
        # docs/VARIANTS.md): family ladders, the evidence-driven selector,
        # and the brownout controller — family-addressed requests degrade
        # down the quality ladder before they shed.
        self.variants = VariantHub(cfg)
        self.metrics.variants = self.variants
        # Generation-lane introspection (docs/GENERATION.md): KV-pool
        # utilization, prefill chunking, speculative acceptance — read live
        # off whatever schedulers exist at scrape time.
        self.metrics.generation = lambda: {
            n: s.gen_snapshot() for n, s in self.schedulers.items()}
        # Multi-tenant adapter residency (serving/adapters.py;
        # docs/ADAPTERS.md): per-tenant attach/detach, scale-to-zero, HBM
        # ledger entries under {base}:{adapter}.  Always constructed so the
        # discovery/metrics surfaces exist even with no adapters configured.
        self.adapters = AdapterManager(self, cfg)
        self.metrics.adapters = self.adapters
        # SLO & goodput plane (serving/slo.py; docs/OBSERVABILITY.md §6):
        # per-(model, tenant, lane) objectives, burn-rate windows, and the
        # usage ledger.  The lifecycle middleware below is its single
        # classification point; always constructed so /admin/slo and the
        # tpuserve_slo_* families exist with the default objectives.
        self.slo = SLOHub(cfg)
        self.metrics.slo = self.slo
        # Predictive autoscaling plane (serving/autoscale.py;
        # docs/AUTOSCALE.md): per-key demand models fitted from the request
        # journal, learned keep-warm windows for the lifecycle/adapter
        # reapers, and pre-warming ahead of forecast demand.  Always
        # constructed so /admin/autoscale and the tpuserve_autoscale_*
        # families exist; ``autoscale: off`` makes every hook a no-op.
        self.autoscale = AutoscalePlane(cfg)
        self.metrics.autoscale = self.autoscale
        # Prefix-cache ↔ adapter coupling (docs/PREFIX.md): a detached slot
        # index may be reused by a DIFFERENT tenant, so its frozen KV must
        # die with the detach — the manager calls back per (base, slot).
        self.adapters.prefix_invalidate = self._invalidate_prefix
        # Live-stream registry (docs/DISAGG.md): stream id → the :generate
        # request behind it, so the export/import/attach admin lanes can
        # address in-flight generations.  Bounded (oldest entries evicted);
        # finished streams linger until capacity so a just-migrated or
        # just-finished stream can still be attached/inspected.
        self.streams: dict[str, dict] = {}
        self._streams_cap = 1024
        # Server fast path (docs/SERVERPATH.md): the binary-lane request
        # counter behind tpuserve_binary_lane_requests_total, the pooled
        # serialization scratch (acceptor ring messages borrow it), and —
        # when ingest_workers > 0 — the SO_REUSEPORT acceptor supervisor.
        self.binary_requests: dict[str, int] = {}  # guarded-by: event-loop
        self.wire_pool = wire.BufferPool()
        self.acceptors = None
        self.metrics.serverpath = self._serverpath_snapshot
        self._inflight = 0          # work-bearing HTTP requests mid-handler
        self._drain_task: asyncio.Task | None = None
        self._handle_signals = False  # set by run(): SIGTERM → graceful drain
        self.default_model = cfg.models[0].name if cfg.models else None
        self.app = web.Application(client_max_size=64 * 1024 * 1024,
                                   middlewares=[self._lifecycle_mw])
        self.app.add_routes([
            web.get("/", self.handle_root),
            web.get("/healthz", self.handle_healthz),
            web.get("/metrics", self.handle_metrics),
            web.post("/admin/reload", self.handle_reload),
            web.post("/admin/drain", self.handle_drain),
            web.post("/admin/recover", self.handle_recover),
            web.get("/admin/faults", self.handle_faults_get),
            web.post("/admin/faults", self.handle_faults),
            web.get("/admin/trace", self.handle_trace_list),
            web.get("/admin/trace/{trace_id}", self.handle_trace_get),
            web.get("/admin/models", self.handle_admin_models),
            web.get("/admin/models/{name}", self.handle_admin_model_get),
            web.post("/admin/models/{name}", self.handle_admin_model_post),
            web.get("/admin/adapters", self.handle_admin_adapters),
            web.post("/admin/adapters/{name}/{adapter}",
                     self.handle_admin_adapter_post),
            web.get("/admin/prefix", self.handle_admin_prefix),
            web.get("/admin/streams", self.handle_admin_streams),
            web.post("/admin/streams/{stream_id}/export",
                     self.handle_stream_export),
            web.post("/admin/streams/{stream_id}/import",
                     self.handle_stream_import),
            web.get("/admin/streams/{stream_id}/attach",
                    self.handle_stream_attach),
            web.get("/admin/slo", self.handle_admin_slo),
            web.get("/admin/autoscale", self.handle_admin_autoscale),
            web.get("/admin/perf", self.handle_admin_perf),
            web.post("/admin/profile", self.handle_profile),
            web.get("/v1/models", self.handle_models),
            web.post("/v1/models/{name:[^:/]+}:predict", self.handle_predict),
            web.post("/v1/models/{name:[^:/]+}:generate", self.handle_generate),
            web.post("/v1/models/{name:[^:/]+}:submit", self.handle_submit),
            web.get("/v1/jobs/{job_id}", self.handle_job),
            web.post("/predict", self.handle_predict_default),
            web.post("/classify", self.handle_predict_default),
        ])
        self.app.on_startup.append(self._startup)
        self.app.on_cleanup.append(self._cleanup)

    @property
    def draining(self) -> bool:
        return self.resilience.draining

    @staticmethod
    def _is_work(request: web.Request) -> bool:
        """Work-bearing requests: what drain refuses and counts in-flight.

        Health/metrics/job polls and the admin surface keep answering during
        a drain — a client must be able to collect its async results while
        the server winds down.
        """
        return request.method == "POST" and (
            request.path in ("/predict", "/classify")
            or request.path.startswith("/v1/models/"))

    _KIND_BY_SUFFIX = ((":predict", "predict"), (":generate", "generate"),
                       (":submit", "submit"))

    def _open_ctx(self, request: web.Request) -> _ReqCtx:
        kind = "predict"  # the /predict and /classify aliases
        for suffix, k in self._KIND_BY_SUFFIX:
            if request.path.endswith(suffix):
                kind = k
                break
        model = request.match_info.get("name") or self.default_model
        return _ReqCtx(self, request, kind, model)

    @web.middleware
    async def _lifecycle_mw(self, request: web.Request, handler):
        """Drain gate + in-flight accounting + trace lifecycle for every
        work request.  The context opened here is what stamps request/trace
        ids on responses, logs, and exemplars; an unhandled handler
        exception becomes a correlated JSON 500 instead of a bare one."""
        if not self._is_work(request):
            return await handler(request)
        ctx = self._open_ctx(request)
        request["obs"] = ctx
        # Demand journal (serving/autoscale.py): every work arrival —
        # served, shed, or drained — is demand the forecaster should see.
        self.autoscale.note_arrival(ctx.model)
        resp = None
        try:
            if self.draining:
                resp = _error_retry(
                    503, "server is draining; retry against another replica",
                    self.cfg.drain_timeout_s or 1.0, ctx=ctx, draining=True)
                return resp
            self._inflight += 1
            try:
                resp = await handler(request)
            finally:
                self._inflight -= 1
            return resp
        except Exception as e:
            if isinstance(e, (web.HTTPException, asyncio.CancelledError)):
                raise
            log.exception("unhandled error serving %s", request.path)
            resp = _error(500, f"internal error: {type(e).__name__}", ctx=ctx)
            return resp
        finally:
            # Observe BEFORE close: close() flips the root span to "error"
            # for every 4xx, and a 400/404 is the CLIENT's mistake — only a
            # handler-set error status (mid-SSE failure) may count here.
            self._observe_slo(request, ctx, resp)
            ctx.close(resp)

    def _observe_slo(self, request: web.Request, ctx: _ReqCtx,
                     resp: web.StreamResponse | None):
        """The SLO plane's single classification point (serving/slo.py).

        Every work request exits through the middleware, so one observation
        here covers all three lanes AND every shed/degrade/error path —
        served-degraded via the variant selection, served-late against the
        key's latency objective, shed via the 429/503/504 statuses, and
        mid-SSE failures via the root span's error status (the 200 status
        line already left).  Never lets accounting fail a request.
        """
        try:
            status = resp.status if resp is not None else 500
            wall_ms = (time.perf_counter() - ctx.span.t0) * 1000.0
            sel = request.get("_variant")
            model = (sel.variant if sel is not None and sel.variant
                     else ctx.model)
            if model is None:
                return
            arec = request.get("_adapter_rec")
            if arec is not None:
                # Tenant-keyed demand (docs/AUTOSCALE.md): the adapter is
                # only resolved inside the handler, so the per-tenant
                # demand model is fed here, at the same choke point the
                # SLO plane uses.
                self.autoscale.note_arrival(model, adapter=arec.name)
            self.slo.observe(
                model, ctx.kind, status, wall_ms,
                degraded=bool(sel is not None and sel.degraded),
                adapter=arec.name if arec is not None else None,
                errored=ctx.span.status == "error")
        except Exception:  # noqa: BLE001 — accounting must not fail serving
            log.exception("slo observation failed")

    # -- lifecycle ----------------------------------------------------------
    def note_binary_request(self, model: str | None) -> None:
        """One binary-lane request decoded (event loop only) — the counter
        behind ``tpuserve_binary_lane_requests_total``."""
        key = model or "_default"
        self.binary_requests[key] = self.binary_requests.get(key, 0) + 1

    def _serverpath_snapshot(self) -> dict:
        """Fast-path evidence for /metrics (docs/SERVERPATH.md): live
        acceptor workers, shm-ring depths, binary-lane request counts, and
        the serialization pool's hit rate."""
        sup = self.acceptors
        out = {
            "ingest_workers": sup.alive_workers() if sup is not None else 0,
            "ring_depth": sup.ring_depths() if sup is not None else {},
            "binary_requests": dict(self.binary_requests),
            "wire_pool": self.wire_pool.snapshot(),
        }
        if sup is not None:
            # Pump-side degradation ladder: full-ring drops and over-slot
            # responses must be visible, not just logged.
            out["pump"] = {
                "served": sup.served,
                "resp_drops": sup.resp_drops,
                "resp_oversize": sup.resp_oversize,
                "resp_backlog": sum(len(d) for d in sup._resp_backlog),
                "degraded_reason": sup.degraded_reason,
            }
            # Per-worker stats blocks + ring-wait/occupancy histograms —
            # the tpuserve_acceptor_* families (docs/OBSERVABILITY.md §10).
            out["acceptor"] = sup.telemetry_snapshot()
        return out

    async def _startup(self, app):
        if self.engine is None:
            # Engine build blocks (weight import + AOT compile); do it in the
            # executor so health endpoints could come up first if wanted.
            loop = asyncio.get_running_loop()
            self.engine = await loop.run_in_executor(None, build_engine, self.cfg)
            boot.stamp("engine")
        if self.engine.lockstep is not None:
            import jax

            if jax.process_index() == 0:
                # Follower topology: this server is host 0 — every
                # run_batch dispatch broadcasts to the follower loops
                # (parallel/lockstep.py; `run()` routes non-zero processes
                # into engine.lockstep.follow() instead of serving).
                self.engine.enable_lockstep_lead()
        self._start_batchers()
        self.metrics.faults = self.engine.runner.faults
        # Perf-plane sources (docs/OBSERVABILITY.md §9): the gauge sampler
        # differences these live counters on the loop-lag tick.  Lambdas
        # re-read self.engine/self.schedulers per call so an engine rebuild
        # never leaves the plane reading a dead runner.
        self.perf.runner_stats = lambda: (
            self.engine.runner.stats if self.engine is not None else {})
        self.perf.gen_snapshots = lambda: {
            n: {"tokens_emitted": s.tokens_emitted,
                "segment_rounds": s.segment_rounds}
            for n, s in self.schedulers.items()}
        self.perf.flops_hint = self._flops_hint
        self.perf.start(asyncio.get_running_loop())
        # Streaming checkpoint store (serving/ckptstore.py;
        # docs/LIFECYCLE.md): chunked, content-addressed, dedup'd weights —
        # the disk residency tier and the stream-while-compile cold path.
        if self.cfg.ckpt_store_dir:
            from .ckptstore import CheckpointStore

            self.ckpt_store = CheckpointStore(
                self.cfg.ckpt_store_dir,
                chunk_bytes=self.cfg.ckpt_chunk_bytes,
                faults=self.engine.runner.faults)
        # Residency manager (docs/LIFECYCLE.md): tracks every configured
        # model COLD/WARMING/ACTIVE/DRAINING_IDLE (+PINNED), activates lazy
        # models on demand (single-flight), scales idle models to zero, and
        # enforces hbm_budget_bytes (and host_budget_bytes) LRU-first.
        self.lifecycle = LifecycleManager(self, self.cfg).start()
        self.metrics.lifecycle = self.lifecycle
        # Per-tenant reaper (idle detach + budget shed); no-op with no
        # adapters configured.
        self.adapters.start()
        # Predictive autoscaler (serving/autoscale.py; docs/AUTOSCALE.md):
        # actuators point at the SAME single-flight activation/attach paths
        # demand uses, so a pre-warm and a cold request can never race two
        # builds; the reapers consult the learned keep-warm windows with
        # their fixed timers as the thin-history fallback.
        self.autoscale.bind(
            activate_fn=self._autoscale_activate,
            attach_fn=self._autoscale_attach,
            draft_of=self._spec_draft_name,
            residency_fn=self._autoscale_residency,
            estimate_warm_ms_fn=self._autoscale_estimate_ms,
            resident_bytes_fn=lambda: sum(
                self.engine.runner.resident_bytes().values())
            if self.engine is not None else 0,
            faults=self.engine.runner.faults,
            model_names=[mc.name for mc in self.cfg.models])
        self.lifecycle.keepwarm_fn = self.autoscale.keepwarm_window_s
        self.adapters.keepwarm_fn = self.autoscale.keepwarm_window_s
        self.autoscale.start()
        if self.cfg.faults:
            # Boot-time chaos rules (the config twin of POST /admin/faults).
            self.engine.runner.faults.apply_config(self.cfg.faults)
            log_event(log, "fault rules installed from config",
                      models=sorted(self.cfg.faults))
        journal = None
        if self.cfg.journal_dir:
            # Durable job journal (serving/durability.py): acknowledged
            # submits survive a kill -9 — start() below replays it.
            journal = JobJournal(self.cfg.journal_dir,
                                 fsync=self.cfg.journal_fsync)
        self.jobs = JobQueue(self._run_job, run_jobs=self._run_jobs,
                             batch_of=self._job_batch_of,
                             max_backlog=self.cfg.job_max_backlog,
                             keep_done=self.cfg.job_keep_done,
                             max_result_mb=self.cfg.job_max_result_mb,
                             result_ttl_s=self.cfg.job_result_ttl_s,
                             journal=journal, tracer=self.tracer).start()
        self.metrics.jobs = self.jobs
        if journal is not None and (self.jobs.recovered_jobs
                                    or self.jobs.restored_done):
            log_event(log, "durable jobs recovered",
                      recovered=self.jobs.recovered_jobs,
                      restored_done=self.jobs.restored_done,
                      replay_ms=self.jobs.replay_ms)
        if self.cfg.watchdog_interval_s > 0:
            # Self-healing supervisor (serving/watchdog.py): quarantine +
            # background rebuild on fatal device faults, bounded attempts.
            self.watchdog = Watchdog(
                self, self.cfg.watchdog_interval_s,
                max_attempts=self.cfg.recover_max_attempts,
                backoff_s=self.cfg.recover_backoff_s).start()
        self.metrics.watchdog = self.watchdog
        if self._handle_signals and self.cfg.drain_timeout_s > 0:
            # SIGTERM → graceful drain (the Lambda SIGTERM-then-kill
            # lifecycle, SURVEY §5): finish in-flight work within the budget,
            # then exit.  Replaces aiohttp's immediate GracefulExit handler;
            # a second SIGTERM skips the drain.  Only installed by run() —
            # embedded/test apps must not touch process signal state.
            asyncio.get_running_loop().add_signal_handler(
                signal.SIGTERM, self._on_sigterm)
        if self.cfg.profiler_port:
            # jax.profiler trace server (SURVEY §5 tracing): point
            # TensorBoard's profile plugin / xprof at this port.
            import jax.profiler

            jax.profiler.start_server(self.cfg.profiler_port)
            log_event(log, "profiler server started", port=self.cfg.profiler_port)
        if self.cfg.supervise_interval_s > 0:
            self._supervisor = asyncio.get_running_loop().create_task(
                self._supervise(), name="supervisor")
        if (self.cfg.heartbeat_interval_s > 0
                and self.engine.lockstep is not None
                and self.engine.lockstep.lead_enabled):
            self._heartbeat = asyncio.get_running_loop().create_task(
                self._heartbeat_loop(), name="lockstep-heartbeat")
        if self.cfg.ingest_workers > 0:
            # SO_REUSEPORT acceptor pool (serving/acceptors.py; docs/
            # SERVERPATH.md): N worker processes accept + host-ingest the
            # binary fast lane on ingest_port and feed THIS process's
            # batchers over shared-memory rings.  Import is deferred so the
            # default (ingest_workers=0) path never touches multiprocessing.
            from .acceptors import AcceptorSupervisor

            # Share the server's pool so the /metrics wire_pool counters
            # reflect the ring pump's actual reuse.
            self.acceptors = AcceptorSupervisor(self.cfg, pool=self.wire_pool)
            await self.acceptors.start(self)
        log_event(log, "server ready", models=sorted(self.batchers),
                  cold_start_seconds=round(self.engine.cold_start_seconds, 3))

    def _start_batchers(self):
        for mc in self.cfg.models:
            if mc.name in self.engine.models:  # lazy models start COLD
                self._start_model_lanes(mc.name)

    def _start_model_lanes(self, name: str):
        """Start the serving lanes for ONE engine-resident model (idempotent).

        The per-model slice of the old boot loop, shared with the lifecycle
        manager's activation path so a model scaled back up from zero gets
        exactly the lanes a boot-built model would.
        """
        cm = self.engine.model(name)
        mc = cm.cfg
        if (not cm.servable.meta.get("async_only")
                and name not in self.batchers):
            # async_only models are served via the job queue only; no sync
            # batcher lane.
            self.batchers[name] = DynamicBatcher(
                cm, self.engine.runner, mc, self.metrics.ring(name),
                resilience=self.resilience.model(name),
                perf=self.perf).start()
            if self.adapters.enabled:
                # Co-batch evidence feed (docs/ADAPTERS.md): every dispatch
                # reports its adapter mix to the manager's counters.
                self.batchers[name].adapter_hook = self.adapters.note_batch
        if "continuous" in cm.servable.meta and name not in self.schedulers:
            import jax

            lockstep = mesh = None
            if jax.process_count() > 1:
                driver = self.engine.lockstep
                if driver is None or not driver.lead_enabled:
                    # Library-lockstep mode (every host drives its own
                    # dispatches): the scheduler's host-controlled loop
                    # cannot be mirrored — a clean 405 on :generate
                    # beats a collective deadlock.
                    log_event(log, "generation lane disabled "
                                   "(multi-host, no lead)", model=name)
                    return
                # Follower topology: every prefill and segment this
                # scheduler dispatches is broadcast to the follower
                # loops first (parallel/lockstep.py OP_GEN_*), so SSE
                # streaming + continuous batching serve cross-host too.
                lockstep, mesh = driver, self.engine.mesh
            # Streaming/continuous-batching lane (POST :generate) beside
            # the fixed-batch :predict lane; compiles lazily on first use.
            if mc.kv_cache == "paged" and lockstep is None:
                # Continuous batching v2 (docs/GENERATION.md): block-paged
                # KV pool + chunked prefill + optional speculative decoding.
                # Raises loudly on a servable without the paged contract —
                # a config error must fail the boot, not silently downgrade.
                self.schedulers[name] = PagedGenerationScheduler(
                    cm, self.engine.runner, mc,
                    self.metrics.ring(f"{name}:generate"),
                    draft=self._draft_gate(mc),
                    usage_hook=self._gen_usage_hook(name),
                    exit_on_fatal=self.cfg.exit_on_fatal).start()
                return
            if mc.kv_cache == "paged":
                # Lockstep worlds keep the proven slot pool: the follower
                # broadcast protocol mirrors its kernels only.
                log_event(log, "paged kv_cache ignored on a lockstep "
                               "world; serving the slot pool", model=name)
            self.schedulers[name] = GenerationScheduler(
                cm, self.engine.runner, mc,
                self.metrics.ring(f"{name}:generate"),
                lockstep=lockstep, mesh=mesh,
                exit_on_fatal=self.cfg.exit_on_fatal).start()

    def _draft_gate(self, mc) -> DraftGate | None:
        """The speculative draft rung for one paged lane (docs/GENERATION.md).

        ``spec_draft`` names a deploy directly, or ``"auto"`` asks the
        variant family ladder for its lowest rung (docs/VARIANTS.md — the
        cheap sibling, e.g. gpt2_int8 under gpt2).  The gate re-resolves on
        every tick against the LIVE engine/resilience/lifecycle state, so
        the scheduler falls back to plain decode while the draft is COLD,
        quarantined, or mid-rebuild, and enter/exit marks it busy so the
        lifecycle manager never demotes it under an in-flight tick.
        """
        draft = mc.spec_draft
        if not draft:
            return None
        if draft == "auto":
            ladder = self.variants.registry.ladder(mc.family or mc.name)
            below = [m.name for m in ladder if m.name != mc.name]
            if not below:
                log_event(log, "spec_draft auto found no family sibling; "
                               "speculation off", model=mc.name)
                return None
            draft = below[-1]  # ladder is quality-descending: cheapest rung
        if draft == mc.name:
            raise ValueError(f"{mc.name}: spec_draft must name a DIFFERENT "
                             "deploy (a model cannot draft for itself)")

        def resolve():
            eng = self.engine
            if eng is None or draft not in eng.models:
                return None
            if draft in self.resilience.quarantined:
                return None
            lc = self.lifecycle
            if lc is not None and lc.knows(draft) and lc.state_of(draft) in (
                    "cold", "warming"):
                return None
            return eng.model(draft)

        # Late-bound: the lifecycle manager is built AFTER the boot lanes
        # (serving startup order), so the hooks must read it per call.
        def lc_enter(name):
            if self.lifecycle is not None:
                self.lifecycle.enter(name)

        def lc_exit(name):
            if self.lifecycle is not None:
                self.lifecycle.exit(name)

        return DraftGate(draft, resolve, enter=lc_enter, exit=lc_exit)

    # -- autoscale actuators (serving/autoscale.py; docs/AUTOSCALE.md) -------
    def _spec_draft_name(self, model) -> str | None:
        """Resolve a model's speculative-draft rung to a deploy name (the
        non-raising twin of :meth:`_draft_gate`'s resolution): the
        autoscaler pre-warms it alongside its target so a predicted burst
        finds the whole draft/verify pair warm."""
        try:
            mc = model if not isinstance(model, str) else self.cfg.model(model)
        except KeyError:
            return None
        draft = mc.spec_draft
        if not draft:
            return None
        if draft == "auto":
            ladder = self.variants.registry.ladder(mc.family or mc.name)
            below = [m.name for m in ladder if m.name != mc.name]
            if not below:
                return None
            draft = below[-1]  # quality-descending: cheapest rung
        return None if draft == mc.name else draft

    async def _autoscale_activate(self, name: str, cause: str):
        """Pre-warm actuator: the lifecycle's single-flight activation."""
        if self.lifecycle is not None and self.lifecycle.knows(name):
            await self.lifecycle.ensure_active(name, cause=cause)

    async def _autoscale_attach(self, base: str, adapter: str, cause: str):
        """Pre-warm actuator: the adapter manager's single-flight attach
        (base first — a slot pool needs its base resident)."""
        if self.lifecycle is not None and self.lifecycle.knows(base):
            await self.lifecycle.ensure_active(base, cause=cause)
        await self.adapters.ensure_attached(base, adapter, cause=cause)

    def _autoscale_residency(self, key: str) -> str | None:
        """Current residency for a ``model`` or ``model:adapter`` key."""
        base, _, adapter = key.partition(":")
        if adapter:
            rec = self.adapters.get(base, adapter)
            return rec.state if rec is not None else None
        return (self.lifecycle.state_of(base)
                if self.lifecycle is not None else None)

    def _autoscale_estimate_ms(self, key: str) -> float:
        """Activation cost for a key — the pre-warm lead time's base."""
        base, _, adapter = key.partition(":")
        if adapter:
            rec = self.adapters.get(base, adapter)
            return (self.adapters.estimate_attach_ms(rec)
                    if rec is not None else 0.0)
        if self.lifecycle is not None and self.lifecycle.knows(base):
            return self.lifecycle.estimate_warm_ms(base)
        return 0.0

    def _gen_usage_hook(self, name: str):
        """Per-stream usage attribution for one paged :generate lane.

        Called by the scheduler at stream retire with the adapter SLOT the
        stream decoded through; resolved back to the tenant name here (the
        scheduler knows indices, not tenants) so the ledger rows land under
        the same ``{base}:{adapter}`` keys the HBM ledger prices.
        """
        def hook(aidx: int, device_ms: float, kv_block_seconds: float,
                 cached_tokens: int):
            adapter = None
            if aidx:
                for a in self.adapters.names_for(name):
                    rec = self.adapters.get(name, a)
                    if rec is not None and rec.slot == aidx:
                        adapter = a
                        break
            self.slo.usage.note_stream(name, adapter, device_ms,
                                       kv_block_seconds, cached_tokens)
        return hook

    async def _stop_model_lanes(self, name: str):
        """Stop + drop ONE model's lanes (scale-to-zero demotion path).

        The lifecycle manager only calls this for quiet models (no queued or
        in-flight work — its busy gate), so no request is stranded; stragglers
        racing the teardown get the batcher's stopped-429 and retry into the
        activation path.
        """
        b = self.batchers.pop(name, None)
        if b is not None:
            await b.stop()
        s = self.schedulers.pop(name, None)
        if s is not None:
            await s.stop()

    def _flops_hint(self, name: str) -> float | None:
        """Per-sample FLOP hint for the live MFU gauge (docs/OBSERVABILITY
        §9): ``ModelConfig.extra.flops_per_sample``, typically the program's
        FLOPs a sample by XLA's cost analysis.  None (the default) omits the
        gauge — an unhinted MFU would be a guess."""
        try:
            v = self.cfg.model(name).extra.get("flops_per_sample")
        except KeyError:
            return None
        try:
            return float(v) if v else None
        except (TypeError, ValueError):
            return None

    async def _cleanup(self, app):
        if self.acceptors is not None:
            await self.acceptors.stop()
            self.acceptors = None
        self.perf.stop()
        await self.autoscale.stop()
        await self.adapters.stop()
        if self.lifecycle is not None:
            await self.lifecycle.stop()
        if self.watchdog is not None:
            await self.watchdog.stop()
        for attr in ("_supervisor", "_heartbeat"):
            task = getattr(self, attr)
            if task is not None:
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
                setattr(self, attr, None)
        for b in self.batchers.values():
            await b.stop()
        for s in self.schedulers.values():
            await s.stop()
        if self.jobs:
            await self.jobs.stop()
        if self.engine and self._owns_engine:
            self.engine.shutdown()

    # -- graceful drain (docs/RESILIENCE.md) ---------------------------------
    def begin_drain(self):
        """Flip to draining: /healthz 503s, new work 503 + Retry-After.

        In-flight sync requests and queued jobs keep running; callers follow
        with :meth:`wait_drained` to give them the drain budget.  Idempotent.
        """
        if not self.draining:
            self.resilience.draining = True
            log_event(log, "drain started", inflight=self._inflight,
                      jobs_backlog=self.jobs.depth if self.jobs else 0)

    async def wait_drained(self, timeout_s: float) -> bool:
        """Wait for in-flight requests + queued/running jobs to finish.

        True = fully drained within the budget; False = budget expired with
        work still in flight (callers shut down anyway — the budget IS the
        contract).
        """
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout_s
        while True:
            jobs_idle = (self.jobs is None
                         or (self.jobs.depth == 0 and self.jobs.active == 0))
            if self._inflight == 0 and jobs_idle:
                return True
            if loop.time() >= deadline:
                log.warning("drain budget expired (inflight=%d jobs=%d)",
                            self._inflight,
                            self.jobs.depth if self.jobs else 0)
                return False
            await asyncio.sleep(0.02)

    def _on_sigterm(self):
        if self.draining:
            # Second SIGTERM: the operator means NOW.
            raise web.GracefulExit()
        self._drain_task = asyncio.get_running_loop().create_task(
            self._drain_then_exit(), name="drain")

    async def _drain_then_exit(self):
        self.begin_drain()
        ok = await self.wait_drained(self.cfg.drain_timeout_s)
        log_event(log, "drain finished; exiting", clean=ok)
        # Raised from a plain callback so it propagates out of run_forever
        # (GracefulExit is a SystemExit subclass) — aiohttp's run_app then
        # performs its normal cleanup, which stops batchers/jobs/engine.
        asyncio.get_running_loop().call_soon(self._raise_graceful_exit)

    @staticmethod
    def _raise_graceful_exit():
        raise web.GracefulExit()

    # -- failure recovery (SURVEY §5 failure detection) ----------------------
    async def _heartbeat_loop(self):
        """Periodic lockstep liveness tick (leader only).

        Rides the dispatch thread like every lead, so it serializes with
        real traffic and can never interleave inside another broadcast
        pair.  A failing tick means the world is already broken (a follower
        died mid-collective); log it — the dispatch-probe health check and
        the followers' own exit paths drive the restart.
        """
        while True:
            await asyncio.sleep(self.cfg.heartbeat_interval_s)
            try:
                await self.engine.runner.run_fn(
                    self.engine.lockstep.lead_heartbeat)
            except Exception:
                log.exception("lockstep heartbeat failed")

    async def _supervise(self):
        """Probe the device; rebuild the engine after consecutive failures.

        The in-process analogue of Lambda respawning a crashed container: the
        warm pool replaces failed VMs, this replaces a wedged device runtime.
        Rebuild is cheap on a warm persistent compile cache.
        """
        fails = 0
        loop = asyncio.get_running_loop()
        while True:
            await asyncio.sleep(self.cfg.supervise_interval_s)
            alive = await loop.run_in_executor(None, self._probe)
            fails = 0 if alive else fails + 1
            if fails >= self.cfg.supervise_fail_threshold:
                if self.engine is not None and self.engine.lockstep is not None:
                    # A one-host rebuild cannot help a lockstep world
                    # (rebuild_engine refuses anyway): keep /healthz honest
                    # (503) and leave recovery to the operator / process
                    # supervisor restarting every host.
                    log.error("device/dispatch probe failed %d consecutive "
                              "times on a multi-host deployment; restart "
                              "all hosts", fails)
                    fails = 0
                    continue
                log.error("device probe failed %d consecutive times; rebuilding engine",
                          fails)
                try:
                    await self.rebuild_engine()
                except Exception:
                    # Rebuild failed (device still wedged): keep supervising —
                    # the next interval retries instead of dying silently.
                    log.exception("engine rebuild failed; will retry")
                fails = 0

    async def rebuild_engine(self, cause: str = "reload"):
        """Tear down batchers + engine and build fresh ones.

        In-flight requests fail with 500 and requests racing the rebuild get
        429 (stopped batchers reject submits); new requests queue against the
        fresh engine.  Also reachable as ``POST /admin/reload`` for operators.
        Serialized: an /admin/reload overlapping a supervisor rebuild waits
        its turn rather than double-tearing-down.  If the build fails, the old
        engine stays live with fresh batchers, and the error propagates.

        Lifecycle integration (docs/LIFECYCLE.md): the swap is a residency
        transition, not a bespoke path — every model in the fresh engine is
        recorded as a re-activation under ``cause`` (the watchdog passes
        ``"recovery"``), lazy models return to COLD and re-activate on
        demand, host-tier copies survive the swap.
        """
        async with self._rebuild_lock:
            if self.engine is not None and self.engine.lockstep is not None:
                # A one-host rebuild cannot re-bootstrap the jax.distributed
                # world, and the followers' loops reference the old engine's
                # programs: restart ALL hosts instead (the warm compile
                # cache makes that cheap).  Refusing beats a silent
                # collective deadlock.
                raise RuntimeError(
                    "engine rebuild is single-host only; on a multi-host "
                    "deployment restart every host process instead")
            old_engine = self.engine
            for b in self.batchers.values():
                await b.stop()
            for s in self.schedulers.values():
                await s.stop()
            self.schedulers.clear()
            loop = asyncio.get_running_loop()
            try:
                new_engine = await loop.run_in_executor(None, build_engine, self.cfg)
            except Exception:
                # Roll back to the old engine so requests keep getting real
                # answers (or honest 500s from a wedged device) — never hangs.
                self.batchers.clear()
                self._start_batchers()
                raise
            self.engine = new_engine
            self.batchers.clear()
            self._start_batchers()
            # Re-point /metrics at the fresh injector: leaving it on the old
            # runner would report stale chaos counters (and hide new rules)
            # after a watchdog recovery.
            self.metrics.faults = new_engine.runner.faults
            if self.ckpt_store is not None:
                # Same for the store's ckpt chaos hook.
                self.ckpt_store.faults = new_engine.runner.faults
            if self.lifecycle is not None:
                # The rebuild IS a lifecycle transition: quarantine was the
                # forced demotion, this is the re-activation — counted per
                # model under `cause` on tpuserve_activations_total.
                self.lifecycle.rebind(cause=cause)
            if old_engine is not None and self._owns_engine:
                old_engine.shutdown()
            self._owns_engine = True  # the rebuilt engine is ours regardless
            log_event(log, "engine rebuilt", models=sorted(self.batchers),
                      cause=cause,
                      cold_start_seconds=round(new_engine.cold_start_seconds, 3))

    # -- helpers ------------------------------------------------------------
    def _servable(self, name: str):
        try:
            return self.engine.model(name)
        except KeyError:
            return None

    def _registered_models(self) -> dict[str, str]:
        """Every model this deployment knows about → its residency state
        (the 404 body contract: an unknown-model error teaches the caller
        what IS served, and whether it is warm)."""
        out: dict[str, str] = {}
        for mc in self.cfg.models:
            out[mc.name] = "active"
        for name in self.engine.models if self.engine is not None else ():
            out.setdefault(name, "active")
        if self.lifecycle is not None:
            for name in list(out):
                out[name] = self.lifecycle.state_of(name) or out[name]
        return out

    def _unknown_model_error(self, name: str, ctx: _ReqCtx | None):
        models = self._registered_models()
        # Family-grouped ladders (docs/VARIANTS.md): the 404 teaches the
        # caller not just what IS served but how to address it model-lessly
        # — each family's variants with rank + residency, quality-first.
        families: dict[str, list[dict]] = {}
        for fam in self.variants.registry.families():
            families[fam] = [
                {"variant": mc.name, "quality_rank": mc.quality_rank,
                 "residency": models.get(mc.name, "cold")}
                for mc in self.variants.registry.ladder(fam)]
        return _error(404, f"model {name!r} not served; available: "
                           f"{sorted(models)}", ctx=ctx, models=models,
                      families=families)

    async def _residency_gate(self, name: str, request: web.Request,
                              ctx: _ReqCtx | None):
        """Cold-admission gate (docs/LIFECYCLE.md): None = model ACTIVE,
        proceed; otherwise the error response to return.

        Uses the header/config deadline only (the body is not decoded yet —
        paying a payload decode for a model that may 503 ``cold_start``
        would hand cold models a free DoS amplifier): if the deadline can
        cover ``estimated_warm_ms`` the request blocks on the single-flight
        activation, else it fast-fails 503 + Retry-After while the
        activation keeps warming in the background.
        """
        lc = self.lifecycle
        if lc is None or not lc.knows(name):
            return self._unknown_model_error(name, ctx)
        try:
            deadline_ms = self._deadline_ms(request, None, self.cfg.model(name))
        except (ValueError, KeyError) as e:
            return _error(400, str(e), ctx=ctx)
        try:
            await lc.ensure_active(
                name, deadline_ms=deadline_ms, cause="request")
        except ColdStart as e:
            if ctx is not None:
                ctx.span.point("cold_start",
                               estimated_warm_ms=round(e.estimated_warm_ms, 1))
            return _error_retry(503, str(e), e.retry_after_s, ctx=ctx,
                                cold_start=True,
                                estimated_warm_ms=round(e.estimated_warm_ms, 1))
        except Exception as e:
            log.exception("activation failed for %s", name)
            return _error_retry(
                503, f"model {name!r} activation failed: "
                     f"{type(e).__name__}: {e}",
                self.cfg.recover_backoff_s or 1.0, ctx=ctx,
                activation_failed=True)
        return None

    # -- multi-tenant adapter admission (docs/ADAPTERS.md) -------------------
    def _unknown_adapter_error(self, base: str, requested: str,
                               ctx: _ReqCtx | None):
        """404 that teaches the caller the base's adapter ladder — the
        family-ladder 404 contract (docs/VARIANTS.md), one level down:
        each adapter with residency + tenants, plus correlation ids."""
        ladder = self.adapters.base_snapshot(base)
        adapters = {a: {"residency": s["state"], "tenants": s["tenants"]}
                    for a, s in sorted(ladder.items())}
        return _error(404, f"adapter {requested!r} not served on model "
                           f"{base!r}; available: {sorted(adapters)}",
                      ctx=ctx, model=base, adapters=adapters)

    async def _adapter_of(self, name: str, request: web.Request,
                          ctx: _ReqCtx | None):
        """Tenant→adapter resolution: (record | None, error | None).

        ``X-Adapter`` header wins, then the top-level ``adapter`` body
        field, then ``X-Tenant`` against the registry.  The body is only
        decoded when this base actually serves adapters (and the model is
        ACTIVE by the time this runs — the cold-gate's no-decode-for-cold
        DoS posture is preserved); the decoded payload is stashed so the
        handler never re-reads a consumed body.
        """
        mgr = self.adapters
        aname = request.headers.get("X-Adapter")
        tenant = request.headers.get("X-Tenant")
        if not mgr.enabled:
            return None, None
        if aname is None and mgr.names_for(name):
            extract: dict[str, Any] = {"objective": None,
                                       "idempotency_key": None,
                                       "adapter": None}
            fresh = "_payload" not in request
            try:
                payload = await self._read_payload(request, extract=extract)
            except Exception as e:
                return None, _error(400, f"bad request body: "
                                         f"{type(e).__name__}: {e}", ctx=ctx)
            if fresh:
                request["_payload"] = payload
                request["_extract"] = extract
                if extract["objective"] is not None:
                    # This decode now OWNS the envelope; keep the exact-
                    # variant body-objective contract loud (PR 7).
                    return None, _error(
                        400, "objective requires addressing the variant "
                             "family (or the X-Objective-* headers), not "
                             f"concrete variant {name!r}", ctx=ctx)
            if extract["adapter"] is not None:
                aname = str(extract["adapter"])
            elif isinstance(payload, dict) and "adapter" in payload:
                # Stashed payloads (family-addressed decode) did not pop
                # the field; surrender it here so preprocess never sees it.
                aname = str(payload.pop("adapter"))
        if aname is None and not tenant:
            return None, None
        try:
            rec = mgr.resolve(name, aname, tenant)
        except UnknownAdapter as e:
            return None, self._unknown_adapter_error(name, e.args[0], ctx)
        if rec is not None and ctx is not None:
            ctx.span.annotate(adapter=rec.name)
        return rec, None

    async def _adapter_gate(self, name: str, rec, request: web.Request,
                            ctx: _ReqCtx | None):
        """Cold-admission gate for one tenant's adapter: None = attached
        (``rec.slot`` valid), else the error response.  Mirrors the model
        residency gate one granularity down: a deadline below the learned
        attach estimate fast-fails 503 ``adapter_cold`` + Retry-After while
        the single-flight attach keeps warming."""
        try:
            deadline_ms = self._deadline_ms(request, None,
                                            self.cfg.model(name))
        except (ValueError, KeyError) as e:
            return _error(400, str(e), ctx=ctx)
        request["_deadline_ms_resolved"] = deadline_ms
        t0 = time.perf_counter()
        try:
            await self.adapters.ensure_attached(
                name, rec.name, deadline_ms=deadline_ms, cause="request")
            waited_ms = (time.perf_counter() - t0) * 1000.0
            if ctx is not None and waited_ms >= 1.0:
                # The request blocked on a cold tenant's single-flight
                # attach: mark it on the waterfall (tools/tracedump.py
                # surfaces it in the substage table) — the attach itself
                # runs under its own `adapter_attach` trace.
                ctx.span.point("adapter_attach", adapter=rec.name,
                               waited_ms=round(waited_ms, 1))
        except AdapterCold as e:
            if ctx is not None:
                ctx.span.point("adapter_cold", adapter=rec.name,
                               estimated_attach_ms=round(
                                   e.estimated_attach_ms, 1))
            return _error_retry(
                503, str(e), e.retry_after_s, ctx=ctx, adapter_cold=True,
                adapter=rec.name,
                estimated_attach_ms=round(e.estimated_attach_ms, 1))
        except Exception as e:
            log.exception("adapter attach failed for %s:%s", name, rec.name)
            return _error_retry(
                503, f"adapter {rec.name!r} attach failed: "
                     f"{type(e).__name__}: {e}",
                self.cfg.recover_backoff_s or 1.0, ctx=ctx,
                adapter_attach_failed=True, adapter=rec.name)
        return None

    @staticmethod
    def _stamp_adapter(samples, rec) -> None:
        """Route preprocessed samples through the tenant's slot: the
        per-row index the co-batched kernels gather by (ops/lora.py), plus
        the name for the batcher's adapter-mix evidence."""
        for s in samples:
            if isinstance(s, dict):
                s["adapter_idx"] = np.int32(rec.slot)
                s["_adapter"] = rec.name

    @staticmethod
    def _job_adapter_split(payload):
        """(adapter name | None, inner payload) — the :submit wrapper that
        keys journal-durable jobs by (model, adapter)."""
        if (isinstance(payload, dict) and "_adapter" in payload
                and "payload" in payload):
            return str(payload["_adapter"]), payload["payload"]
        return None, payload

    async def _job_model(self, model: str):
        """The job lane's engine lookup, residency-aware: a job for a COLD
        model activates it (cause="job", no deadline — the async lane is
        latency-tolerant by contract)."""
        if self.lifecycle is not None and self.lifecycle.knows(model):
            return await self.lifecycle.ensure_active(model, cause="job")
        return self.engine.model(model)

    async def _preprocess(self, cm, payload, span=None):
        # Chaos hook: injected preprocess faults fail THIS request on the
        # same path a malformed payload would (per-request isolation).
        sp = span.child("preprocess") if span is not None else None
        try:
            self.engine.runner.faults.on_preprocess(cm.servable.name)
            loop = asyncio.get_running_loop()
            result = await loop.run_in_executor(None, cm.servable.preprocess,
                                                payload)
        except BaseException as e:
            if sp is not None:
                sp.end(status="error", error=f"{type(e).__name__}: {e}")
            raise
        if sp is not None:
            sp.end()
        return result

    async def _run_device(self, cm, samples, deadline: float | None = None,
                          span=None):
        """One device batch via ``run_chunked`` with the retry contract.

        Transient dispatch faults retry with capped backoff (never past the
        deadline) and every outcome feeds the model's circuit breaker — the
        job lane gets the same resilience story as the sync batcher.
        """
        loop = asyncio.get_running_loop()
        sp = (span.child("device", batch_size=len(samples))
              if span is not None else None)
        try:
            results = await run_with_retry(
                lambda: self.engine.runner.run_chunked(cm, samples, span=sp),
                self.resilience.model(cm.servable.name), deadline,
                clock=loop.time, sleep=asyncio.sleep, span=sp)
        except BaseException as e:
            if sp is not None:
                sp.end(status="error", error=f"{type(e).__name__}: {e}")
            raise
        if sp is not None:
            sp.end()
        return results

    async def _execute(self, cm, sample, span=None):
        """Run one preprocessed sample (or multi-sample list) + finalize.

        Device work goes through ``run_chunked``: for models with a chunked
        contract (sd15) the program runs as K short dispatches so queued
        latency work preempts between chunks; everything else falls through
        to the monolithic ``run`` unchanged.
        """
        if isinstance(sample, list):
            # Multi-sample request (long-audio chunking): run in max_batch
            # slices and merge, same contract as the sync fan-out path.
            results = []
            for i in range(0, len(sample), cm.max_batch):
                results.extend(await self._run_device(
                    cm, sample[i: i + cm.max_batch], span=span))
            merge = cm.servable.meta.get("merge_results")
            result = merge(results) if merge else results
        else:
            results = await self._run_device(cm, [sample], span=span)
            result = results[0]
        finalize = cm.servable.meta.get("finalize")
        if finalize is not None:
            # Heavy host-side encoding (e.g. SD-1.5 PNG+base64) off the
            # dispatch thread AND off the event loop.
            sp = span.child("finalize") if span is not None else None
            loop = asyncio.get_running_loop()
            result = await loop.run_in_executor(None, finalize, result)
            if sp is not None:
                sp.end()
        return result

    async def _run_job(self, job):
        span = job.run_span or job.span
        aname, payload = self._job_adapter_split(job.payload)
        cm = await self._job_model(job.model)
        arec = None
        if aname is not None:
            # Journal-replayed or fresh, the job attaches its tenant's
            # adapter on demand — the async lane's cause="job" activation
            # contract, one granularity down (docs/ADAPTERS.md).
            await self.adapters.ensure_attached(job.model, aname,
                                                cause="job")
            arec = self.adapters.get(job.model, aname)
        lc = self.lifecycle
        if lc is not None:
            lc.enter(job.model)
        if arec is not None:
            self.adapters.enter(arec)
        try:
            sample = await self._preprocess(cm, payload, span=span)
            if arec is not None:
                self._stamp_adapter(
                    sample if isinstance(sample, list) else [sample], arec)
            result = await self._execute(cm, sample, span=span)
            if arec is not None:
                self.adapters.note_served(arec)
            return result
        finally:
            if arec is not None:
                self.adapters.exit(arec)
            if lc is not None:
                lc.exit(job.model)

    def _job_batch_of(self, model: str) -> int:
        """Max same-model jobs one device batch may carry (JobQueue coalesce).

        The largest configured batch bucket; 1 (off) for models whose
        preprocess can fan out to multi-sample lists (long-audio chunking) —
        their batch geometry is per-job already.

        QoS cap (docs/QOS.md): when latency-class models share the engine,
        a throughput model's coalescing is capped (default 1) — a coalesced
        ×4 sd15 batch makes every chunk ~4× longer, which is exactly the
        uninterruptible occupancy the chunked path exists to bound.  Raise
        ``extra.job_batch_mixed_cap`` to trade latency-lane tail for job
        throughput; dedicated sd15 deployments coalesce freely as before.
        """
        try:
            cm = self.engine.model(model)
        except Exception:
            return 1
        if cm.servable.meta.get("merge_results"):
            return 1
        cap = cm.max_batch
        if (cm.latency_class == "throughput"
                and any(m.latency_class == "latency"
                        for m in self.engine.models.values())):
            cap = min(cap, int(cm.cfg.extra.get("job_batch_mixed_cap", 1)))
        return max(cap, 1)

    async def _run_jobs(self, jobs):
        """Batched job lane: N single-sample jobs -> ONE engine batch.

        Returns one entry per job, in order; an Exception entry fails that
        job alone (jobs.py's worker contract) — one corrupt payload must not
        take down its batch-mates the way it couldn't in the per-job lane.
        Preprocess and finalize fan out concurrently on the executor; only
        the device batch is a single call.
        """
        if any(self._job_adapter_split(j.payload)[0] is not None
               for j in jobs):
            # Tenant-addressed jobs keep per-job isolation (a failed attach
            # must fail only ITS job); the sync batcher remains the adapter
            # co-batching lane (docs/ADAPTERS.md).
            out = []
            for j in jobs:
                try:
                    out.append(await self._run_job(j))
                except Exception as e:  # noqa: BLE001 — per-job isolation
                    out.append(e)
            return out
        cm = await self._job_model(jobs[0].model)
        lc = self.lifecycle
        if lc is not None:
            lc.enter(jobs[0].model)
        try:
            return await self._run_jobs_admitted(cm, jobs)
        finally:
            if lc is not None:
                lc.exit(jobs[0].model)

    async def _run_jobs_admitted(self, cm, jobs):
        samples = await asyncio.gather(
            *[self._preprocess(cm, j.payload, span=j.run_span or j.span)
              for j in jobs],
            return_exceptions=True)
        good = [i for i, s in enumerate(samples)
                if not isinstance(s, BaseException)]
        out: list = list(samples)  # failed slots already hold their Exception
        if any(isinstance(samples[i], list) for i in good):
            # Multi-sample fan-out (shouldn't happen given _job_batch_of,
            # but stay correct): run the already-preprocessed samples
            # sequentially — re-preprocessing via _run_job would double any
            # expensive decode work and its side effects.
            for i in good:
                try:
                    out[i] = await self._execute(
                        cm, samples[i], span=jobs[i].run_span or jobs[i].span)
                except Exception as e:  # noqa: BLE001 — per-job isolation
                    out[i] = e
            return out
        if good:
            # Device span on the head job's trace; batch-mates link the rest
            # (same convention as the batcher's coalesced dispatch).
            head = next((jobs[i] for i in good
                         if (jobs[i].run_span or jobs[i].span) is not None),
                        None)
            head_span = (head.run_span or head.span) if head else None
            if head_span is not None and len(good) > 1:
                head_span.annotate(batch_mates=[
                    jobs[i].trace_id for i in good
                    if jobs[i] is not head and jobs[i].trace_id][:8])
            results = await self._run_device(cm, [samples[i] for i in good],
                                             span=head_span)
            finalize = cm.servable.meta.get("finalize")
            if finalize is not None:
                # return_exceptions: a malformed result's finalize failure
                # lands on ITS job, not the whole batch (same isolation
                # contract as preprocess above).
                loop = asyncio.get_running_loop()
                results = await asyncio.gather(
                    *[loop.run_in_executor(None, finalize, r)
                      for r in results],
                    return_exceptions=True)
            for i, r in zip(good, results, strict=True):
                out[i] = r
        return out

    # -- handlers -----------------------------------------------------------
    async def handle_root(self, request):
        return web.json_response({
            "status": "ok",
            "framework": "pytorch-zappa-serverless-tpu",
            "profile": self.cfg.profile,
            # Registered models, resident or not — a scaled-to-zero model is
            # still served (it activates on demand, docs/LIFECYCLE.md).
            "models": sorted(self._registered_models()),
        })

    async def handle_models(self, request):
        """Model discovery: serving surface + bucket/compile state per model.

        Configured-but-COLD (lazy / scaled-to-zero) models are listed too —
        they serve the same endpoints, just with an activation on first
        demand — with their residency state alongside.
        """
        lc = self.lifecycle
        models = {}
        for name, cm in self.engine.models.items():
            mc = cm.cfg
            is_async = bool(cm.servable.meta.get("async_only"))
            models[name] = {
                "buckets": [list(b) for b in cm.buckets],
                "buckets_compiled": len(cm.warmed_buckets),
                "dtype": mc.dtype,
                "family": mc.family or name,
                "quality_rank": mc.quality_rank,
                "async_only": is_async,
                "endpoint": (f"/v1/models/{name}:submit" if is_async
                             else f"/v1/models/{name}:predict"),
                "max_new_tokens": cm.servable.meta.get("max_new_tokens"),
                "checkpoint": mc.checkpoint or "random-init",
            }
            if lc is not None and lc.knows(name):
                models[name]["residency"] = lc.state_of(name)
            if self.adapters.names_for(name):
                # Per-tenant ladder (docs/ADAPTERS.md): each adapter with
                # its residency — the discovery twin of the family ladder.
                models[name]["adapters"] = self.adapters.residency_of(name)
        for mc in self.cfg.models:
            if mc.name in models:
                continue
            models[mc.name] = {
                "buckets": [[int(b)] for b in mc.batch_buckets],
                "buckets_compiled": 0,
                "dtype": mc.dtype,
                "family": mc.family or mc.name,
                "quality_rank": mc.quality_rank,
                "async_only": False,
                "endpoint": f"/v1/models/{mc.name}:predict",
                "max_new_tokens": None,
                "checkpoint": mc.checkpoint or "random-init",
                "residency": (lc.state_of(mc.name) or "cold"
                              if lc is not None else "cold"),
            }
            if self.adapters.names_for(mc.name):
                models[mc.name]["adapters"] = \
                    self.adapters.residency_of(mc.name)
        return web.json_response({"models": models})

    def _probe(self) -> bool:
        """Device + (multi-host leader only) dispatch-thread liveness."""
        timeout = None
        if (self.engine.lockstep is not None
                and self.engine.lockstep.lead_enabled
                and self.cfg.dispatch_probe_timeout_s > 0):
            timeout = self.cfg.dispatch_probe_timeout_s
        return self.engine.runner.probe(dispatch_timeout_s=timeout)

    async def handle_healthz(self, request):
        loop = asyncio.get_running_loop()
        alive = await loop.run_in_executor(None, self._probe)
        # A permanently stopped :generate lane (multi-host fatal) must flip
        # health (ADVICE r3): a deployment that 503s every stream while
        # /healthz stays green never gets the world restart the lane's
        # fatal message asks for.
        gen_fatal = {n: s.fatal for n, s in self.schedulers.items() if s.fatal}
        quarantined = sorted(self.resilience.quarantined)
        body = {
            "device_ok": alive,
            # What JAX is serving from: a host where libtpu failed to
            # initialise must not look like a healthy chip.
            "device": device_info(),
            "generation_ok": not gen_fatal,
            # Draining flips health so the load balancer stops routing here
            # while in-flight work finishes (SIGTERM lifecycle, SURVEY §5).
            "draining": self.draining,
            # Mid-recovery (watchdog rebuild) also flips health: the LB
            # should back off until the quarantine lifts.
            "quarantined": quarantined,
            **({"recovery": self.watchdog.snapshot()}
               if self.watchdog is not None else {}),
            "models": {name: {"buckets_compiled": len(cm.warmed_buckets),
                              "buckets_total": len(cm.buckets)}
                       for name, cm in self.engine.models.items()},
            "queue_depths": {n: b.queue_depth for n, b in self.batchers.items()},
            # Per-model queue-wait forecast in ms (the admission-time load
            # shed signal, serving/resilience.py): the fleet router's
            # least-forecast-wait routing polls it from here (docs/FLEET.md).
            "forecast": self.resilience.queue_forecast(self.batchers),
            "jobs_backlog": self.jobs.depth if self.jobs else 0,
            "jobs_backlog_by_model": self.jobs.depths if self.jobs else {},
            # Residency states (docs/LIFECYCLE.md): COLD lazy models are
            # healthy — scale-to-zero must not flip the health check.
            **({"residency": {n: self.lifecycle.state_of(n)
                              for n in sorted(self.lifecycle.names)}}
               if self.lifecycle is not None else {}),
            "generation": {n: {"active": s.active, "pending": s.depth,
                               **({"fatal": s.fatal} if s.fatal else {})}
                           for n, s in self.schedulers.items()},
            # Burn-rate state (serving/slo.py; docs/OBSERVABILITY.md §6):
            # alarmed (key, lane) pairs + worst live burn per window.  The
            # fleet router folds this into its own /healthz so one poll
            # answers "is any replica burning its error budget".  Alarms do
            # NOT flip health — an SLO alarm means route AROUND pressure,
            # not take the replica out (that would burn the budget faster).
            "slo": self.slo.health_summary(),
        }
        ok = (alive and not gen_fatal and not self.draining
              and not quarantined)
        return web.json_response(body, status=200 if ok else 503)

    async def handle_metrics(self, request):
        """JSON by default; Prometheus text under content negotiation
        (``Accept: text/plain`` or ``?format=prometheus``) so a scraper
        needs no adapter while existing JSON consumers see no change."""
        accept = request.headers.get("Accept", "")
        if (request.query.get("format") == "prometheus"
                or ("text/plain" in accept and "application/json" not in accept)):
            return web.Response(
                text=self.metrics.render_prometheus(self.engine),
                content_type="text/plain", charset="utf-8")
        return web.json_response(self.metrics.render(self.engine))

    async def handle_reload(self, request):
        await self.rebuild_engine()
        return web.json_response({
            "status": "reloaded",
            "cold_start_seconds": round(self.engine.cold_start_seconds, 3),
        })

    # -- admin: request tracing + on-demand profiling ------------------------
    async def handle_trace_list(self, request):
        """``GET /admin/trace`` — finished/live trace summaries, filtered.

        Query params: ``model``, ``status`` (ok|error|open), ``min_ms``
        (minimum duration), ``limit`` (default 50).  Newest first; the
        flight recorder guarantees the slowest/errored traces per model
        survive ring churn (docs/OBSERVABILITY.md).  ``rounds=N`` adds the
        last N scheduler rounds of every generation lane (of ``model``, if
        given) with their host phases: the timeline a ``prefill`` span's
        ``round`` attribute points into.
        """
        q = request.query
        try:
            min_ms = float(q.get("min_ms", 0.0))
            limit = int(q.get("limit", 50))
            rounds = int(q.get("rounds", 0))
        except (TypeError, ValueError):
            return _error(400, "min_ms must be a number, limit and rounds "
                               "integers")
        out = {"traces": self.tracer.list(model=q.get("model"),
                                          status=q.get("status"),
                                          min_ms=min_ms, limit=limit),
               "pinned": self.tracer.pinned(),
               **self.tracer.snapshot()}
        if rounds > 0:
            out["rounds"] = {
                n: sched.timeline.recent(rounds)
                for n, sched in self.schedulers.items()
                if q.get("model") in (None, n)}
        return web.json_response(out)

    async def handle_trace_get(self, request):
        """``GET /admin/trace/{id}`` — the full span tree for one trace."""
        trace = self.tracer.get(request.match_info["trace_id"])
        if trace is None:
            return _error(404, "unknown trace id (evicted from the ring, or "
                               "never sampled); see GET /admin/trace")
        return web.json_response({"trace": trace.tree()})

    async def handle_profile(self, request):
        """``POST /admin/profile {"seconds": 2}`` — timed capture of live
        traffic, reduced by the program itself, in one call.

        The escalation path from a trace: a span tree says *which stage* is
        slow; this says *which device ops* (``ops``, classified through the
        ``utils/xplane.py`` rules ``tools/trace_ops.py`` uses;
        ``top`` bounds the list, default 15), *which program* runs them
        (``programs``: device runs named by the ``tpuserve.*.launch``
        annotation that launched them) and *what the host was doing while
        the device sat idle* (``idle``: every gap between device operations
        booked to the scheduler phase that covers it).  The capture stays
        under ``dir`` for xprof/TensorBoard or perfetto.  One capture at a
        time: a second request while one runs answers 409.
        """
        import time as _time
        import uuid as _uuid

        import jax.profiler

        from pathlib import Path

        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            body = {}
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        try:
            seconds = float(body.get("seconds", 2.0))
            top = int(body.get("top", 15))
        except (TypeError, ValueError):
            return _error(400, "seconds must be a number, top an integer")
        if not (0.05 <= seconds <= 60.0):  # also rejects NaN
            return _error(400, "seconds must be in [0.05, 60]")
        if self._tracing:
            return _error(409, "a trace capture is already running")
        out_dir = (Path(self.cfg.trace_dir).expanduser()
                   / f"profile-{_time.strftime('%Y%m%d-%H%M%S')}"
                     f"-{_uuid.uuid4().hex[:6]}")
        out_dir.mkdir(parents=True, exist_ok=True)
        self._tracing = True
        loop = asyncio.get_running_loop()

        def rows_held() -> dict:
            """What the slot lanes' spans held so far (``gen_snapshot``):
            taken as the capture begins and ends, so that a reader has the
            rows of the very rounds whose device time the capture holds."""
            keys = ("span_rows", "summary_rows", "live_positions")
            return {name: {k: snap[k] for k in keys
                           + tuple(sched.counter_sums)
                           + tuple({"span_rows_by_kind", "prefill_buckets"}
                                   & set(snap))}
                    for name, sched in self.schedulers.items()
                    for snap in [sched.gen_snapshot()] if keys[0] in snap}

        try:
            # start/stop serialize the capture buffer: keep them (and the
            # reduction below) off the event loop so /healthz and predicts
            # stay responsive; stop sits in a finally so a client that went
            # away mid-sleep can't leave the profiler session open (which
            # would 500 every later capture).
            await loop.run_in_executor(None, jax.profiler.start_trace,
                                       str(out_dir))
            held = rows_held()
            try:
                await asyncio.sleep(seconds)
            finally:
                held = {m: {"before": held[m], "after": after}
                        for m, after in rows_held().items() if m in held}
                t_stop = _time.monotonic()
                await loop.run_in_executor(None, jax.profiler.stop_trace)
                stop_s = _time.monotonic() - t_stop
        finally:
            self._tracing = False

        def classify():
            from ..utils.xplane import (attribute_idle, op_time_breakdown,
                                        read_capture)

            capture = read_capture(out_dir)  # a million events, read once
            compute, counts, overlap, envelope = op_time_breakdown(
                out_dir, capture)
            ops = [{"op": fam, "ms": round(ns / 1e6, 3),
                    "count": counts.get(fam, 0)}
                   for fam, ns in compute.most_common(max(top, 1))]
            return {"ops": ops,
                    "device_compute_ms": round(sum(compute.values()) / 1e6, 3),
                    "overlap_ms": round(sum(overlap.values()) / 1e6, 3),
                    "envelope_ms": round(sum(envelope.values()) / 1e6, 3),
                    **attribute_idle(out_dir, capture)}

        # A capture with no device plane (the CPU backend) classifies to
        # zero ops, an empty ``idle`` and no ``programs``; the answer still
        # carries the capture location.
        t_classify = _time.monotonic()
        breakdown = await loop.run_in_executor(None, classify)
        log_event(log, "profile captured", dir=str(out_dir), seconds=seconds,
                  ops=len(breakdown.get("ops", [])),
                  stop_trace_s=round(stop_s, 2),
                  classify_s=round(_time.monotonic() - t_classify, 2))
        return web.json_response({"dir": str(out_dir), "seconds": seconds,
                                  "generation": held, **breakdown})

    async def handle_predict(self, request):
        return await self._predict(request.match_info["name"], request)

    async def handle_predict_default(self, request):
        if self.default_model is None:
            # Work-surface 503s carry correlation ids + Retry-After like
            # every other unavailability answer (tools/analyze contracts
            # lint): a config with no models is an operator problem, so the
            # retry horizon is long — but a client behind a provisioning
            # fleet still learns when to probe again.
            return _error_retry(503, "no models configured", 30.0,
                                ctx=request.get("obs"))
        return await self._predict(self.default_model, request)

    def _deadline_ms(self, request, payload, mc) -> float | None:
        """Effective request deadline in ms, or None (no deadline).

        Client value (``X-Deadline-Ms`` header, else top-level
        ``deadline_ms`` body field — popped so preprocess never sees it)
        wins, capped by ``ServeConfig.deadline_max_ms``; otherwise an
        objective ``max_latency_ms`` (the variant resolver stashed it — a
        bound overrun must 504, never silently violate the objective);
        otherwise the model's ``deadline_ms``, otherwise
        ``deadline_default_ms``.  A client value <= 0 means "already
        expired" and is returned as-is for the admission check to 504.
        Raises ValueError on junk.  The variant resolver computes the
        deadline once for family-addressed requests and stashes it
        (``_deadline_ms_resolved``) so admission and selection can never
        disagree on the bound.
        """
        if "_deadline_ms_resolved" in request:
            return request["_deadline_ms_resolved"]
        raw = request.headers.get("X-Deadline-Ms")
        if raw is None and isinstance(payload, dict):
            raw = payload.pop("deadline_ms", None)
        if raw is None:
            raw = request.get("_objective_max_latency_ms")
        if raw is not None:
            try:
                ms = float(raw)
            except (TypeError, ValueError):
                raise ValueError("deadline_ms must be a number (milliseconds)")
            if math.isnan(ms):
                raise ValueError("deadline_ms must be a number (milliseconds)")
            if self.cfg.deadline_max_ms > 0:
                ms = min(ms, self.cfg.deadline_max_ms)
            return ms
        default = mc.deadline_ms or self.cfg.deadline_default_ms
        return default if default > 0 else None

    # -- objective-driven variant serving (docs/VARIANTS.md) -----------------
    _OBJECTIVE_HEADERS = ("X-Objective-Max-Latency-Ms",
                          "X-Objective-Min-Quality",
                          "X-Objective-Prefer-Cost")

    async def _read_payload(self, request, extract: dict[str, Any] | None = None):
        """Body decode with a per-request cache.

        The variant resolver decodes family-addressed requests early (the
        body may carry the objective); downstream handlers get the stashed
        payload and any extract fields it popped (except ``objective`` —
        the resolver owns that) instead of re-reading a consumed body.
        """
        if "_payload" in request:
            if extract is not None:
                stash = request.get("_extract") or {}
                for k in extract:
                    if k != "objective" and stash.get(k) is not None:
                        extract[k] = stash[k]
            return request["_payload"]
        return await _decode_payload(request, extract=extract)

    async def _resolve_variant(self, name: str, request: web.Request,
                               ctx: _ReqCtx | None):
        """Family-addressed admission: (concrete name, error response).

        A request is family-addressed when its name is a variant family
        that is not itself a configured model, or when it states an
        objective via the ``X-Objective-*`` headers (body objectives ride
        family names).  Everything else passes through untouched — except
        that exact-variant requests remember their (multi-variant) family
        so shed responses can report family-minimum retry evidence.

        For family-addressed requests: decode + stash the payload, parse
        the objective, snapshot per-variant evidence, run the brownout
        controller, and pick — recording a ``variant_select`` trace point
        with every candidate's score.  A pick below the ladder top serves
        with ``degraded``; no satisfying variant sheds with family-minimum
        ``Retry-After``/``estimated_wait_ms``/``estimated_warm_ms``.
        """
        reg = self.variants.registry
        family_only = reg.is_family(name) and not reg.is_model(name)
        header_obj = any(h in request.headers
                         for h in self._OBJECTIVE_HEADERS)
        if not family_only and not header_obj:
            fam = reg.family_of(name)
            if fam is not None and len(reg.ladder(fam)) > 1:
                request["_family"] = fam
            return name, None
        fam = name if family_only else reg.family_of(name)
        if fam is None:
            return name, self._unknown_model_error(name, ctx)
        extract: dict[str, Any] = {"objective": None, "idempotency_key": None}
        try:
            payload = await _decode_payload(request, extract=extract)
        except Exception as e:
            return name, _payload_error(e, ctx)
        request["_payload"] = payload
        request["_extract"] = extract
        try:
            objective = Objective.parse(request.headers, extract["objective"])
        except ValueError as e:
            return name, _error(400, str(e), ctx=ctx)
        if objective.max_latency_ms is not None:
            request["_objective_max_latency_ms"] = objective.max_latency_ms
        ladder = reg.ladder(fam)
        try:
            deadline_ms = self._deadline_ms(
                request, payload if isinstance(payload, dict) else None,
                ladder[0])
        except ValueError as e:
            return name, _error(400, str(e), ctx=ctx)
        request["_deadline_ms_resolved"] = deadline_ms
        bounds = [b for b in (objective.max_latency_ms, deadline_ms)
                  if b is not None and b > 0]
        sel = self.variants.resolve(self, fam, objective,
                                    min(bounds) if bounds else None)
        if ctx is not None:
            ctx.span.point("variant_select", family=fam,
                           variant=sel.variant, degraded=sel.degraded,
                           brownout=sel.brownout,
                           **({"shed": sel.shed_reason} if sel.shed_reason
                              else {}),
                           candidates=sel.candidates)
        if sel.variant is None:
            # Degrade-before-shed exhausted the whole ladder: the shed
            # carries the FAMILY's minimum evidence (PR 6 minima rule).
            status = 503 if sel.shed_reason == "all_blocked" else 429
            extra: dict[str, Any] = {"family": fam,
                                     "variant_shed": sel.shed_reason,
                                     "candidates": sel.candidates}
            if sel.estimated_wait_ms is not None:
                extra["estimated_wait_ms"] = sel.estimated_wait_ms
            if sel.estimated_warm_ms is not None:
                extra["estimated_warm_ms"] = sel.estimated_warm_ms
            return name, _error_retry(
                status, f"no variant of family {fam!r} satisfies the "
                        f"objective ({sel.shed_reason}); shedding",
                sel.retry_after_s, ctx=ctx, **extra)
        request["_variant"] = sel
        request["_family"] = fam
        if ctx is not None:
            ctx.span.annotate(variant=sel.variant, family=fam)
        return sel.variant, None

    def _overloaded_response(self, e: Overloaded, batcher, request,
                             ctx: _ReqCtx | None) -> web.Response:
        """429 for a full queue — with family-minimum retry evidence when
        the overloaded variant has siblings (docs/VARIANTS.md)."""
        retry_s = e.retry_after_s
        extra: dict[str, Any] = {"queue_depth": batcher.queue_depth,
                                 "in_flight": batcher.in_flight}
        floor = self._family_shed_floor(request)
        if floor is not None:
            extra["family"] = floor[0]
            retry_s = min(retry_s, floor[1])
            if floor[2] is not None:
                extra["estimated_wait_ms"] = floor[2]
        return _error_retry(429, str(e), retry_s, ctx=ctx, **extra)

    def _family_shed_floor(self, request) -> tuple[str, float, float | None] | None:
        """(family, retry_after_s, estimated_wait_ms) minima across the
        request's family, or None when the request has no (multi-variant)
        family context — exact-variant sheds report when the SOONEST
        sibling could serve, mirroring the fleet-minima rule."""
        fam = request.get("_family")
        if fam is None:
            return None
        retry_s, wait_ms = self.variants.family_floor(self, fam)
        return fam, retry_s, wait_ms

    def _decorate_variant(self, resp: web.StreamResponse, request,
                          name: str) -> None:
        """Stamp the served-variant evidence headers on a success response
        (family-addressed requests only)."""
        sel = request.get("_variant")
        if sel is None:
            return
        resp.headers["X-Served-Variant"] = name
        if sel.degraded:
            resp.headers["X-Degraded"] = "1"

    async def _predict(self, name: str, request):
        ctx: _ReqCtx | None = request.get("obs")
        name, verr = await self._resolve_variant(name, request, ctx)
        if verr is not None:
            return verr
        # Admission stage span: anchored to the root's start so the stage
        # chain (admission → queue → device → respond) tiles the request
        # wall time with no gaps (the acceptance check tools/tracedump.py
        # reports as coverage).
        adm = (ctx.span.child("admission", start=ctx.span.t0)
               if ctx is not None else None)
        cm = self._servable(name)
        if cm is None:
            # Not engine-resident: the residency gate either activates a
            # COLD/WARMING model (single-flight, deadline-aware; docs/
            # LIFECYCLE.md) or answers 404/503 itself.
            resp = await self._residency_gate(name, request, ctx)
            if resp is not None:
                return resp
            cm = self._servable(name)
            if cm is None:
                return self._unknown_model_error(name, ctx)
        if cm.servable.meta.get("async_only"):
            # Multi-second programs (SD-1.5's denoise loop) must not occupy
            # the latency-sensitive batcher lane; route them through jobs.
            return _error(405, f"model {name!r} is async-only; use "
                               f"POST /v1/models/{name}:submit and poll /v1/jobs/{{id}}",
                          ctx=ctx)
        # Tenant resolution + attach gate (docs/ADAPTERS.md): runs after
        # the model residency gate — the base is ACTIVE, so a tiny adapter
        # attach (not a model build) is all that can stand between this
        # request and its slot index.
        arec, aerr = await self._adapter_of(name, request, ctx)
        if aerr is not None:
            return aerr
        if arec is not None:
            resp = await self._adapter_gate(name, arec, request, ctx)
            if resp is not None:
                return resp
            request["_adapter_rec"] = arec
        lc = self.lifecycle
        if lc is not None:
            # In-flight guard: the model cannot be idle-unloaded or
            # budget-evicted while any request is inside its handler.
            lc.enter(name)
        if arec is not None:
            # Same guard one level down: the adapter's slot cannot be idle-
            # detached or budget-evicted mid-request.
            self.adapters.enter(arec)
        try:
            return await self._predict_admitted(name, request, ctx, adm)
        finally:
            if arec is not None:
                self.adapters.exit(arec)
            if lc is not None:
                lc.exit(name)

    async def _predict_admitted(self, name: str, request, ctx, adm):
        batcher = self.batchers.get(name)
        if batcher is None:
            return self._unknown_model_error(name, ctx)
        if name in self.resilience.quarantined:
            # Watchdog recovery in progress (serving/watchdog.py): the sick
            # engine is being rebuilt in the background — tell clients when
            # to come back instead of letting work land on it.
            if ctx is not None:
                ctx.span.point("quarantined")
            retry_s = self.cfg.recover_backoff_s or 1.0
            extra: dict[str, Any] = {"quarantined": True}
            floor = self._family_shed_floor(request)
            if floor is not None:
                # A healthy sibling variant may serve NOW: the shed's
                # Retry-After is the family minimum (docs/VARIANTS.md).
                extra["family"] = floor[0]
                retry_s = min(retry_s, floor[1])
            return _error_retry(
                503, f"model {name!r} is quarantined while the engine "
                     "recovers", retry_s, ctx=ctx, **extra)
        # Breaker fast-fail BEFORE any body/decode work: while the circuit is
        # open a sick model costs callers <10 ms and zero dispatch-lane time,
        # and co-resident models keep serving.
        mr = self.resilience.model(name)
        if mr.breaker is not None and not mr.breaker.allow():
            mr.stats.breaker_fast_fails += 1
            if ctx is not None:
                ctx.span.point("breaker_fast_fail", state=mr.breaker.state)
            retry_s = mr.breaker.retry_after_s()
            extra = {"breaker": mr.breaker.state}
            floor = self._family_shed_floor(request)
            if floor is not None:
                extra["family"] = floor[0]
                retry_s = min(retry_s, floor[1])
            return _error_retry(
                503, f"model {name!r} circuit breaker is {mr.breaker.state} "
                     f"(recent error rate {mr.breaker.error_rate():.0%}); "
                     "failing fast", retry_s, ctx=ctx, **extra)
        pextract: dict[str, Any] = {"objective": None}
        try:
            payload = await self._read_payload(request, extract=pextract)
        except Exception as e:
            return _payload_error(e, ctx)
        t_val0 = time.perf_counter()
        if pextract["objective"] is not None:
            # A body objective on an exact-variant request would be
            # silently ignored (selection already happened at the family
            # layer); decline loudly instead (docs/VARIANTS.md).
            return _error(400, "objective requires addressing the variant "
                               "family (or the X-Objective-* headers), not "
                               f"concrete variant {name!r}", ctx=ctx)
        cm = batcher.model
        try:
            deadline_ms = self._deadline_ms(request, payload, cm.cfg)
        except ValueError as e:
            return _error(400, str(e), ctx=ctx)
        loop = asyncio.get_running_loop()
        deadline = None
        if deadline_ms is not None:
            if deadline_ms <= 0:
                # Admission deadline check: the client's budget is already
                # spent (e.g. an upstream hop ate it) — never queue it.
                mr.stats.deadline_admission += 1
                return _error(504, f"deadline_ms={deadline_ms:g} already "
                                   "expired at admission", ctx=ctx,
                              stage="admission")
            deadline = loop.time() + deadline_ms / 1000.0
        instances = None
        if isinstance(payload, dict) and "instances" in payload:
            # Batch-predict API: one request carries N independent inputs
            # (the batched-classify surface of BASELINE config #2).  All
            # instances are admitted atomically and co-batch on the device;
            # predictions come back as a per-instance list.
            instances = payload["instances"]
            if not isinstance(instances, list) or not instances:
                return _error(400, '"instances" must be a non-empty list',
                              ctx=ctx)
            # Advisory early rejection BEFORE paying N preprocessing calls
            # (attacker-controlled decode work for a request that would 429
            # anyway); submit_many below re-checks atomically.
            try:
                batcher.check_capacity(len(instances))
            except Overloaded as e:
                return self._overloaded_response(e, batcher, request, ctx)
        if deadline_ms is not None:
            # Admission-time load shedding: if the queue-wait forecast
            # (depth × recent p50 device time) already exceeds the deadline,
            # reject NOW with 429 + Retry-After instead of queuing the
            # request to die a 504 after consuming a slot.
            est_ms = batcher.estimate_wait_ms(
                len(instances) if instances is not None else 1)
            if est_ms > deadline_ms:
                mr.stats.shed_predicted += 1
                if ctx is not None:
                    ctx.span.point("load_shed", estimated_wait_ms=round(est_ms, 1),
                                   deadline_ms=deadline_ms)
                retry_s, wait_ms = est_ms / 1000.0, round(est_ms, 1)
                extra = {"queue_depth": batcher.queue_depth}
                floor = self._family_shed_floor(request)
                if floor is not None:
                    # Family minima (docs/VARIANTS.md): a quieter sibling's
                    # forecast is the honest retry horizon, not this
                    # variant's own backlog.
                    extra["family"] = floor[0]
                    retry_s = min(retry_s, floor[1])
                    if floor[2] is not None:
                        wait_ms = min(wait_ms, floor[2])
                return _error_retry(
                    429, f"estimated queue wait {est_ms:.0f} ms exceeds "
                         f"deadline {deadline_ms:.0f} ms; shedding",
                    retry_s, ctx=ctx, estimated_wait_ms=wait_ms, **extra)
        ignored = cm.servable.meta.get("predict_ignores_sampling")
        if ignored:
            # Knobs this model's fixed-batch lane cannot honor (whisper's
            # :predict decode is always greedy) decline LOUDLY — the same
            # policy as repetition_penalty on the streaming lane — instead of
            # silently returning greedy output for a sampled request.
            bad = sorted({k for p in (instances if instances is not None
                                      else [payload])
                          if isinstance(p, dict) for k in ignored if k in p})
            if bad:
                return _error(400, f"model {name!r} ignores sampling knobs "
                                   f"{bad} on the :predict lane (greedy "
                                   f"decode); use POST /v1/models/{name}"
                                   f":generate for sampled output", ctx=ctx)
        # validate substage: everything between the payload decode and
        # preprocess — objective/deadline/instances/sampling-knob checks
        # plus the admission-time shed forecasting (docs/OBSERVABILITY §9).
        _substage(request, "validate", t_val0, time.perf_counter())
        try:
            if instances is not None:
                # Unwrap b64 envelopes BEFORE creating coroutines (a bad
                # instance must not leave sibling coroutines never-awaited),
                # then decode concurrently in the executor pool — instance
                # count must not multiply latency by sequential decode time.
                # ONE pass over the list (ISSUE 16 satellite: the old shape
                # walked it twice — an _unwrap_b64 call per instance plus an
                # any() probe for the substage stamp) and one stamp carrying
                # the envelope count; binary-lane instances are ndarray
                # views and fall straight through.
                t_b64 = time.perf_counter()
                decoded, n_b64 = [], 0
                for p in instances:
                    if isinstance(p, dict) and "b64" in p:
                        decoded.append(base64.b64decode(p["b64"]))
                        n_b64 += 1
                    else:
                        decoded.append(p)
                if n_b64:
                    _substage(request, "b64_decode", t_b64,
                              time.perf_counter(), instances=n_b64)
                per_inst = await asyncio.gather(*[
                    self._preprocess(cm, p, span=adm) for p in decoded])
            else:
                per_inst = [await self._preprocess(cm, payload, span=adm)]
        except Exception as e:
            return _error(400, f"preprocess failed: {type(e).__name__}: {e}",
                          ctx=ctx)
        # Each instance preprocesses to one sample or (long-audio chunking) a
        # list of sibling samples; flatten for atomic admission, regroup after.
        inst_spans = [len(s) if isinstance(s, list) else 1 for s in per_inst]
        flat = [s for inst in per_inst
                for s in (inst if isinstance(inst, list) else [inst])]
        arec = request.get("_adapter_rec")
        if arec is not None:
            # adapter_gather: the per-row slot routing that makes this
            # request co-batchable with other tenants' rows (ops/lora.py).
            self._stamp_adapter(flat, arec)
            if adm is not None:
                adm.point("adapter_gather", adapter=arec.name,
                          slot=arec.slot)
        seq_of = cm.servable.meta.get("seq_len_of")
        merge = cm.servable.meta.get("merge_results")
        if adm is not None:
            # Admission ends where the batcher queue begins; the batcher
            # records the queue/device stages on the same trace from here.
            adm.end()
        req_span = ctx.span if ctx is not None else None
        try:
            # The await on the device future is bounded by the remaining
            # deadline budget: a client contractually gone at T must get its
            # 504 at T, not whenever the batch lands.
            remaining = (max(deadline - loop.time(), 0.001)
                         if deadline is not None else None)
            if len(flat) == 1 and instances is None:
                result, timing = await asyncio.wait_for(
                    batcher.submit(flat[0], seq_of(flat[0]) if seq_of else None,
                                   deadline=deadline, span=req_span),
                    timeout=remaining)
            else:
                futs = batcher.submit_many(
                    flat, [seq_of(s) if seq_of else None for s in flat],
                    deadline=deadline, span=req_span)
                pairs = await asyncio.wait_for(asyncio.gather(*futs),
                                               timeout=remaining)
                grouped, i = [], 0
                for width in inst_spans:
                    chunk = [r for r, _ in pairs[i: i + width]]
                    grouped.append(merge(chunk) if (width > 1 and merge)
                                   else (chunk if width > 1 else chunk[0]))
                    i += width
                result = grouped if instances is not None else grouped[0]
                timing = {
                    "queue_ms": max(t["queue_ms"] for _, t in pairs),
                    "device_ms": max(t["device_ms"] for _, t in pairs),
                    "total_ms": max(t["total_ms"] for _, t in pairs),
                    "batch_size": max(t["batch_size"] for _, t in pairs),
                    "samples": len(pairs),
                    "t_done": max(t["t_done"] for _, t in pairs),
                }
        except Overloaded as e:
            return self._overloaded_response(e, batcher, request, ctx)
        except DeadlineExceeded as e:
            # Shed by the batcher before dispatch (counter already bumped).
            return _error(504, str(e), ctx=ctx, stage=e.stage)
        except (asyncio.TimeoutError, TimeoutError):
            mr.stats.deadline_await += 1
            self.metrics.ring(name).record_error()
            return _error(504, f"deadline ({deadline_ms:g} ms) expired while "
                               "awaiting the device", ctx=ctx, stage="await")
        except Exception as e:
            log.exception("predict failed for %s", name)
            return _error(500, f"inference failed: {type(e).__name__}",
                          ctx=ctx)
        # Respond stage: stitched to the device end (t_done) so the stage
        # chain stays gap-free; covers result grouping + JSON encode.
        t_done = timing.pop("t_done", None)
        rsp_span = (ctx.span.child("respond", start=t_done)
                    if ctx is not None else None)
        t_ser0 = time.perf_counter()
        sel = request.get("_variant")
        meta = {"model": name, "timing": timing}
        if sel is not None:
            # Family-addressed request (docs/VARIANTS.md): the body names
            # the family it asked for and whether the serve was degraded;
            # X-Served-Variant/X-Degraded carry the same on the headers.
            meta["family"] = sel.family
            meta["degraded"] = sel.degraded
        if request.get("_binary_lane") and \
                "application/json" not in request.headers.get("Accept", ""):
            # Binary-lane response (docs/SERVERPATH.md): ONE preserialized
            # frame — a JSON meta block ({"model", "timing", ...}) followed
            # by a block per prediction (tensor blocks for ndarray results,
            # compact-JSON blocks otherwise), sized up-front and filled
            # through a single memoryview.  Values byte-decode identically
            # to the JSON lane's (tier-1 pins it).  `Accept:
            # application/json` opts a binary request back into JSON.
            preds = result if instances is not None else [result]
            frame = wire.pack_response(meta, preds,
                                       list_frame=instances is not None)
            resp = web.Response(body=frame,
                                content_type=wire.TENSOR_CONTENT_TYPE)
        else:
            resp = _json_body_response({**meta, "predictions": result})
        # serialize substage: the response-body build + encode (one encoder
        # walk for the whole batch on either lane) — the egress twin of
        # json_decode/binary_decode.
        _substage(request, "serialize", t_ser0, time.perf_counter())
        self._decorate_variant(resp, request, name)
        if arec is not None:
            # Per-tenant evidence: the served header plus the tenant's own
            # QoS ring ({base}:{adapter} on /metrics — p50/p99/req counts
            # per adapter beside the base model's).
            resp.headers["X-Adapter"] = arec.name
            self.adapters.note_served(arec)
            self.metrics.ring(f"{name}:{arec.name}").record(
                timing["queue_ms"], timing["device_ms"], timing["total_ms"])
        resp.headers["X-Queue-Ms"] = str(timing["queue_ms"])
        resp.headers["X-Device-Ms"] = str(timing["device_ms"])
        # Usage ledger (docs/OBSERVABILITY.md §7): the device time this
        # request consumed, attributed to the tenant that spent it.
        self.slo.usage.note_request(
            name, arec.name if arec is not None else None,
            timing["device_ms"])
        if rsp_span is not None:
            rsp_span.end()
        if t_done is not None:
            self.perf.note_stage(name, "respond",
                                 (time.perf_counter() - t_done) * 1000.0)
        return resp

    async def handle_generate(self, request):
        """Streaming generation with continuous batching.

        ``POST /v1/models/{name}:generate`` with ``{"text"|"input_ids": ...,
        "temperature": t, "seed": s, "max_new_tokens": n, "stream": bool}``.
        ``stream: true`` (default) answers ``text/event-stream``: one
        ``data: {"token": id}`` event per generated token as each decode
        segment completes, then ``data: {"done": true, "tokens": [...]}``.
        ``stream: false`` waits and returns one JSON body.  Either way the
        request joins the slot pool immediately — mid-flight generations
        don't block admission (continuous batching).
        """
        name = request.match_info["name"]
        ctx: _ReqCtx | None = request.get("obs")
        name, verr = await self._resolve_variant(name, request, ctx)
        if verr is not None:
            return verr
        adm = (ctx.span.child("admission", start=ctx.span.t0)
               if ctx is not None else None)
        sched = self.schedulers.get(name)
        if sched is None:
            if self._servable(name) is None:
                # COLD model (or unknown): the residency gate activates or
                # errors; a successful activation starts the generation lane.
                resp = await self._residency_gate(name, request, ctx)
                if resp is not None:
                    return resp
                sched = self.schedulers.get(name)
            if sched is None:
                if self._servable(name) is None:
                    return self._unknown_model_error(name, ctx)
                return _error(405, f"model {name!r} has no generation lane; "
                                   f"use POST /v1/models/{name}:predict",
                              ctx=ctx)
        arec, aerr = await self._adapter_of(name, request, ctx)
        if aerr is not None:
            return aerr
        if arec is not None:
            if not isinstance(sched, PagedGenerationScheduler):
                # The slot pool's per-slot state carries no adapter index;
                # decline loudly rather than silently serve the base.
                return _error(400, f"adapter-addressed generation requires "
                                   f"kv_cache='paged' on model {name!r}",
                              ctx=ctx)
            resp = await self._adapter_gate(name, arec, request, ctx)
            if resp is not None:
                return resp
            request["_adapter_rec"] = arec
        lc = self.lifecycle
        if lc is not None:
            lc.enter(name)
        if arec is not None:
            # Held for the WHOLE stream: a mid-generation idle detach would
            # zero the slot this stream's rows gather from.
            self.adapters.enter(arec)
        try:
            return await self._generate_admitted(name, request, ctx, adm,
                                                 sched)
        finally:
            if arec is not None:
                self.adapters.exit(arec)
            if lc is not None:
                lc.exit(name)

    async def _generate_admitted(self, name: str, request, ctx, adm, sched):
        pextract: dict[str, Any] = {"objective": None}
        try:
            payload = await self._read_payload(request, extract=pextract)
        except Exception as e:
            return _payload_error(e, ctx)
        t_val0 = time.perf_counter()
        if pextract["objective"] is not None:
            return _error(400, "objective requires addressing the variant "
                               "family (or the X-Objective-* headers), not "
                               f"concrete variant {name!r}", ctx=ctx)
        stream, max_new = True, None
        if isinstance(payload, dict):
            stream = bool(payload.get("stream", True))
            if "max_new_tokens" in payload:
                try:
                    max_new = int(payload["max_new_tokens"])
                except (TypeError, ValueError):
                    return _error(400, "max_new_tokens must be an integer",
                                  ctx=ctx)
            try:
                rep = float(payload.get("repetition_penalty", 1.0))
            except (TypeError, ValueError):
                return _error(400, "repetition_penalty must be a number",
                              ctx=ctx)
            if rep != 1.0:
                # Supported on the fixed-batch lane only: the slot-pool
                # decode would need a [slots, vocab] presence buffer donated
                # across segments (and mirrored by lockstep followers).
                # Checked on the RAW payload so every generative model
                # declines loudly rather than silently ignoring the knob.
                return _error(400, "repetition_penalty is not supported on "
                                   "the streaming lane; use POST /v1/models/"
                                   f"{name}:predict (batch API)", ctx=ctx)
        _substage(request, "validate", t_val0, time.perf_counter())
        try:
            sample = await self._preprocess(sched.cm, payload, span=adm)
        except Exception as e:
            return _error(400, f"preprocess failed: {type(e).__name__}: {e}",
                          ctx=ctx)
        if isinstance(sample, list):
            # Multi-sample fan-out (whisper long-audio chunking) has no
            # single token stream to serve: that workload belongs to the
            # chunk-and-merge :predict lane.
            return _error(400, "input fans out to multiple windows; use "
                               f"POST /v1/models/{name}:predict for long "
                               "inputs", ctx=ctx)
        arec = request.get("_adapter_rec")
        if arec is not None and isinstance(sample, dict):
            # Per-STREAM adapter slot: the paged scheduler carries it per
            # slot so tenants co-decode in one program (docs/ADAPTERS.md).
            sample["adapter_idx"] = np.int32(arec.slot)
            if adm is not None:
                adm.point("adapter_gather", adapter=arec.name,
                          slot=arec.slot)
        if adm is not None:
            adm.end()
        try:
            gen = sched.submit(sample, max_new,
                               span=ctx.span if ctx is not None else None)
        except KVPoolExhausted as e:
            # KV page pool exhausted (docs/GENERATION.md "Exhaustion
            # policy"): Retry-After is the scheduler's expected block-
            # release horizon — the closest-to-done stream's remaining
            # tokens at the live decode pace — not a constant guess.
            retry_s = e.retry_after_s
            extra = {"kv_blocks_free": e.free_blocks,
                     "kv_blocks_needed": e.needed_blocks,
                     "estimated_wait_ms": round(e.retry_after_s * 1000, 1)}
            floor = self._family_shed_floor(request)
            if floor is not None:
                extra["family"] = floor[0]
                retry_s = min(retry_s, floor[1])
            return _error_retry(429, str(e), retry_s, ctx=ctx, **extra)
        except OverflowError as e:
            # Generation backlog full: the shed carries Retry-After and the
            # FAMILY minimum like the batcher/job 429s — this lane was the
            # one shed path PR 7's minima sweep missed (found by the
            # tools/analyze contracts lint, ISSUE 8).
            retry_s = 1.0
            extra: dict[str, Any] = {"backlog": sched.depth,
                                     "active": sched.active}
            floor = self._family_shed_floor(request)
            if floor is not None:
                extra["family"] = floor[0]
                retry_s = min(retry_s, floor[1])
                if floor[2] is not None:
                    extra["estimated_wait_ms"] = floor[2]
            return _error_retry(429, str(e), retry_s, ctx=ctx, **extra)
        except ValueError as e:  # over-length prompt, checked at submit
            return _error(400, str(e), ctx=ctx)
        except RuntimeError as e:
            # Lane stopped/fatal: unavailability answers carry Retry-After
            # like every other 503 on the work surface (docs/RESILIENCE.md),
            # and a healthy sibling variant caps the horizon.
            retry_s = 1.0
            extra = {}
            floor = self._family_shed_floor(request)
            if floor is not None:
                extra["family"] = floor[0]
                retry_s = min(retry_s, floor[1])
            return _error_retry(503, str(e), retry_s, ctx=ctx, **extra)
        # Stream registry (docs/DISAGG.md): every live :generate is
        # addressable by id so the export/import/attach admin lanes (and
        # the disaggregated router) can migrate it mid-flight.
        stream_id = ctx.request_id if ctx is not None else new_request_id()
        self._register_stream(stream_id, name, sched, gen, imported=False)
        if ctx is not None:
            gen.t_ingest0 = ctx.span.t0

        def final_body(tokens: list[int]) -> dict:
            out: dict = {"done": True, "tokens": tokens}
            if sched.detokenize is not None:
                out["text"] = sched.detokenize(tokens)
            if gen.rounds_to_first_token is not None:
                # Device round-trips before the first token (admission
                # prefills + decode segments): lets a client separate queue
                # effects from device time in its TTFT (the benchmark's
                # ``rounds_to_first_token``: benchmark/readers/client.py).
                # The *_ms keys tile the time to the first token by the
                # server's own stamps (GenRequest.timing_stats).
                out["stats"] = {
                    "rounds_to_first_token": gen.rounds_to_first_token,
                    "segments_to_first_token": gen.segments_to_first_token,
                    **({"prefill_windows": gen.prefill_windows}
                       if gen.prefill_windows is not None else {}),
                    **gen.timing_stats(),
                }
            if gen.spec_proposed:
                # Speculation evidence (docs/GENERATION.md): the draft rung
                # this stream verified against + its acceptance counts —
                # the body twin of the X-Spec-Draft header.
                out.setdefault("stats", {}).update(
                    spec_draft=sched.spec_draft_name,
                    spec_proposed=gen.spec_proposed,
                    spec_accepted=gen.spec_accepted)
            if gen.cached_tokens:
                # Prefix-cache evidence (docs/PREFIX.md): how many prompt
                # tokens this stream served from frozen pages instead of
                # prefilling — the per-request twin of /admin/prefix.
                out.setdefault("stats", {})[
                    "prefix_cached_tokens"] = gen.cached_tokens
            return out

        def spec_header(resp: web.StreamResponse) -> None:
            # X-Spec-Draft (satellite, docs/GENERATION.md): which draft rung
            # speculation runs with.  Decided at admission (SSE headers
            # freeze at prepare(), before any tick runs), so it attests the
            # lane's live configuration; per-stream acceptance numbers ride
            # the final body's stats.
            name = getattr(sched, "spec_draft_name", None)
            if name and sched.spec_live():
                resp.headers["X-Spec-Draft"] = name

        if not stream:
            try:
                tokens = await gen.done
            except RuntimeError as e:
                return _error(500, f"generation failed: {e}", ctx=ctx)
            except asyncio.CancelledError:
                # Client dropped while waiting: free the slot (the streaming
                # branch does the same) instead of decoding for nobody.
                sched.cancel(gen)
                raise
            body = final_body(tokens)
            body.pop("done")
            out = {"model": name, "predictions": body}
            sel = request.get("_variant")
            if sel is not None:
                out["family"] = sel.family
                out["degraded"] = sel.degraded
            resp = web.json_response(out)
            resp.headers["X-Stream-Id"] = stream_id
            self._decorate_variant(resp, request, name)
            spec_header(resp)
            if arec is not None:
                resp.headers["X-Adapter"] = arec.name
                self.adapters.note_served(arec)
            return resp

        resp = web.StreamResponse(
            headers={"Cache-Control": "no-cache", "X-Accel-Buffering": "no",
                     "X-Stream-Id": stream_id})
        if ctx is not None:
            # Correlation headers must land before prepare() freezes them —
            # the middleware can only decorate unprepared responses.
            resp.headers["X-Request-Id"] = ctx.request_id
            resp.headers["X-Trace-Id"] = ctx.trace_id
        # Served-variant evidence rides the SSE headers too (prepare()
        # freezes them, so it must land here).
        self._decorate_variant(resp, request, name)
        spec_header(resp)
        if arec is not None:
            resp.headers["X-Adapter"] = arec.name
            self.adapters.note_served(arec)
        resp.content_type = "text/event-stream"
        await resp.prepare(request)
        perf = self.perf

        async def send(obj) -> None:
            # Per-event egress attribution (docs/OBSERVABILITY.md §9):
            # serialize = the JSON encode, respond = the socket write.
            # Histogram-only — a span per token would blow the trace's
            # span budget for exactly the long streams worth inspecting.
            t0 = time.perf_counter()
            data = f"data: {json.dumps(obj)}\n\n".encode()
            t1 = time.perf_counter()
            await resp.write(data)
            perf.note_stage(name, "serialize", (t1 - t0) * 1000.0)
            perf.note_stage(name, "respond",
                            (time.perf_counter() - t1) * 1000.0)

        try:
            while True:
                ev = await gen.events.get()
                if ev is None:
                    break
                await send({"token": ev})
                if gen.first_write_at is None:
                    gen.first_write_at = time.perf_counter()
            if gen.done.done() and gen.done.exception() is not None:
                if gen.migrated:
                    # The stream left this replica via a committed
                    # migration: a terminal marker, not an error — the
                    # importer (router/operator) resumes it elsewhere from
                    # the watermark (docs/DISAGG.md "Cutover").
                    gen.done.exception()  # retrieved; not a failure here
                    await send({"migrated": True, "stream_id": stream_id,
                                "watermark": len(gen.tokens),
                                **({"request_id": ctx.request_id,
                                    "trace_id": ctx.trace_id}
                                   if ctx is not None else {})})
                    await resp.write_eof()
                    return resp
                err = str(gen.done.exception())
                body = {"error": err}
                if ctx is not None:
                    # Mid-stream failures can't change the (already sent)
                    # 200 status line: the error event itself carries the
                    # correlation ids, and the root span flips to error so
                    # the trace lands in the flight recorder's errored pin.
                    body.update(request_id=ctx.request_id,
                                trace_id=ctx.trace_id)
                    ctx.span.status = "error"
                    ctx.span.annotate(error=err)
                    log_event(log, "request error", kind=ctx.kind,
                              model=ctx.model, status=200, error=err,
                              request_id=ctx.request_id, trace_id=ctx.trace_id)
                await send(body)
            else:
                await send(final_body(await gen.done))
            await resp.write_eof()
        except (ConnectionResetError, asyncio.CancelledError):
            # Client went away mid-stream: release the slot so queued
            # requests admit instead of decoding for nobody.
            sched.cancel(gen)
            raise
        return resp

    async def handle_submit(self, request):
        name = request.match_info["name"]
        ctx: _ReqCtx | None = request.get("obs")
        name, verr = await self._resolve_variant(name, request, ctx)
        if verr is not None:
            return verr
        adm = (ctx.span.child("admission", start=ctx.span.t0)
               if ctx is not None else None)
        if self._servable(name) is None and (
                self.lifecycle is None or not self.lifecycle.knows(name)):
            return self._unknown_model_error(name, ctx)
        if self.lifecycle is not None:
            # A submit never blocks on activation: the 202 ack is immediate
            # and the job worker activates the COLD model when the job runs
            # (cause="job") — the async lane is latency-tolerant by contract.
            self.lifecycle.note_use(name)
        # Idempotent resubmit (docs/RESILIENCE.md "Durability"): a header
        # Idempotency-Key that matches a known job answers it BEFORE any
        # breaker/quarantine gate — the work already ran (or is running);
        # answering costs zero lane time even while the model is sick.
        idem_key = request.headers.get("Idempotency-Key")
        prior = self.jobs.dedupe(idem_key) if self.jobs else None
        if prior is not None:
            if ctx is not None:
                ctx.span.point("idempotent_dedupe", job=prior.id)
            return web.json_response({"job": prior.public(), "deduped": True,
                                      **self._poll_ids(ctx)})
        if name in self.resilience.quarantined:
            if ctx is not None:
                ctx.span.point("quarantined")
            retry_s = self.cfg.recover_backoff_s or 1.0
            extra: dict[str, Any] = {"quarantined": True}
            floor = self._family_shed_floor(request)
            if floor is not None:
                extra["family"] = floor[0]
                retry_s = min(retry_s, floor[1])
            return _error_retry(
                503, f"model {name!r} is quarantined while the engine "
                     "recovers", retry_s, ctx=ctx, **extra)
        # The job lane shares the dispatch lane: an open breaker fast-fails
        # submits too, so a sick model's backlog can't keep poisoning it.
        mr = self.resilience.model(name)
        if mr.breaker is not None and not mr.breaker.allow():
            mr.stats.breaker_fast_fails += 1
            if ctx is not None:
                ctx.span.point("breaker_fast_fail", state=mr.breaker.state)
            retry_s = mr.breaker.retry_after_s()
            extra = {"breaker": mr.breaker.state}
            floor = self._family_shed_floor(request)
            if floor is not None:
                extra["family"] = floor[0]
                retry_s = min(retry_s, floor[1])
            return _error_retry(
                503, f"model {name!r} circuit breaker is {mr.breaker.state}; "
                     "failing fast", retry_s, ctx=ctx, **extra)
        extract: dict[str, Any] = {"idempotency_key": None,
                                   "objective": None, "adapter": None}
        try:
            payload = await self._read_payload(request, extract=extract)
        except Exception as e:
            return _payload_error(e, ctx)
        if request.get("_binary_lane") and isinstance(payload, dict) \
                and "instances" in payload:
            # The job lane runs ONE payload per job (the journal replays it
            # whole); multi-instance tensor framing is predict-only
            # (docs/SERVERPATH.md).  Single-block frames submit fine — the
            # journal round-trips the decoded array via its __tensor__
            # wrapper (serving/durability.py).
            return _error(400, "multi-instance tensor frames are "
                               ":predict-only; submit one block per job",
                          ctx=ctx)
        if extract["objective"] is not None:
            return _error(400, "objective requires addressing the variant "
                               "family (or the X-Objective-* headers), not "
                               f"concrete variant {name!r}", ctx=ctx)
        # Tenant resolution (docs/ADAPTERS.md): the job is keyed (model,
        # adapter) via a payload wrapper, so the journal replays it onto
        # the right tenant and the worker attaches on demand (cause="job").
        arec = None
        aname = request.headers.get("X-Adapter") or extract.get("adapter")
        if aname is None and isinstance(payload, dict) \
                and "adapter" in payload:
            aname = payload.pop("adapter")
        tenant = request.headers.get("X-Tenant")
        if self.adapters.enabled and (aname or tenant):
            try:
                arec = self.adapters.resolve(
                    name, str(aname) if aname else None, tenant)
            except UnknownAdapter as e:
                return self._unknown_adapter_error(name, e.args[0], ctx)
        if arec is not None:
            if isinstance(payload, bytes):
                return _error(400, "adapter-addressed submits require a "
                                   "JSON (or text) body", ctx=ctx)
            payload = {"_adapter": arec.name, "payload": payload}
            if ctx is not None:
                ctx.span.annotate(adapter=arec.name)
        if extract["idempotency_key"]:
            # Body twin of the header (popped before the b64 unwrap so
            # preprocess never sees it).  Re-checked AFTER the decode await:
            # two same-key submits racing through decode must still collapse
            # to one job — dedupe+submit below run with no await between
            # them (single event loop).
            idem_key = str(extract["idempotency_key"])
        prior = self.jobs.dedupe(idem_key) if self.jobs else None
        if prior is not None:
            if ctx is not None:
                ctx.span.point("idempotent_dedupe", job=prior.id)
            return web.json_response({"job": prior.public(), "deduped": True,
                                      **self._poll_ids(ctx)})
        if adm is not None:
            adm.end()
        try:
            job = self.jobs.submit(
                name, payload, idempotency_key=idem_key,
                span=ctx.span if ctx is not None else None,
                request_id=ctx.request_id if ctx is not None else None)
        except OverflowError as e:
            retry_s = 1.0
            extra = {"backlog": self.jobs.depths.get(name, 0),
                     "max_backlog": self.jobs.max_backlog}
            floor = self._family_shed_floor(request)
            if floor is not None:
                extra["family"] = floor[0]
                retry_s = min(retry_s, floor[1])
            return _error_retry(429, str(e), retry_s, ctx=ctx, **extra)
        except RuntimeError as e:
            # Queue shut down: the client should fail over, but the 503
            # still carries Retry-After (contracts lint) — the fleet router
            # failover path keys off the status, and a direct client gets
            # an honest horizon for probing this process again.
            return _error_retry(503, str(e), 1.0, ctx=ctx)
        if ctx is not None:
            # The trace now belongs to the job: the worker adds queue/run/
            # device/journal spans and finishes it at the terminal state, so
            # GET /admin/trace/{id} shows submit→done as ONE tree.
            ctx.detach()
        ack = {"job": job.public()}
        sel = request.get("_variant")
        if sel is not None:
            ack["family"] = sel.family
            ack["degraded"] = sel.degraded
        if arec is not None:
            ack["adapter"] = arec.name
        resp = web.json_response(ack, status=202)
        self._decorate_variant(resp, request, name)
        if arec is not None:
            resp.headers["X-Adapter"] = arec.name
        return resp

    @staticmethod
    def _poll_ids(ctx: _ReqCtx | None, job=None) -> dict:
        """Correlation ids for job-surface bodies (docs/OBSERVABILITY.md):
        the poll's own request id plus the job's trace id when known."""
        out: dict[str, Any] = {}
        if ctx is not None:
            out["request_id"] = ctx.request_id
            out["trace_id"] = ctx.trace_id
        return out

    async def handle_job(self, request):
        # Job polls are not traced (they would churn the ring for no story)
        # but still correlate: every body carries the poll's request_id and
        # the job's trace_id, and error polls log the same ids.
        request_id = request.headers.get("X-Request-Id") or new_request_id()
        job = self.jobs.get(request.match_info["job_id"]) if self.jobs else None
        if job is None:
            log_event(log, "request error", kind="job_poll", status=404,
                      error="unknown job id", request_id=request_id,
                      trace_id=None)
            resp = _error(404, "unknown job id", request_id=request_id,
                          trace_id=None)
            resp.headers["X-Request-Id"] = request_id
            return resp
        body = {"job": job.public(), "request_id": request_id,
                "trace_id": job.trace_id}
        status = 200
        if job.status == "expired":
            # 410 Gone, not a 200 that looks like a live job: the record
            # exists but the result was evicted by the retention budget —
            # clients must distinguish "gone, resubmit" from "pending, poll".
            body["expired"] = {"finished": job.finished,
                               "result_ttl_s": self.jobs.result_ttl_s}
            status = 410
            log_event(log, "request error", kind="job_poll", status=410,
                      error="job result expired", request_id=request_id,
                      trace_id=job.trace_id)
        resp = web.json_response(body, status=status)
        resp.headers["X-Request-Id"] = request_id
        if job.trace_id:
            resp.headers["X-Trace-Id"] = job.trace_id
        return resp

    # -- admin: model lifecycle (docs/LIFECYCLE.md) --------------------------
    async def handle_admin_models(self, request):
        """``GET /admin/models`` — residency snapshot for every model."""
        if self.lifecycle is None:
            return _error(503, "lifecycle manager not started")
        return web.json_response(self.lifecycle.snapshot())

    async def handle_admin_model_get(self, request):
        """``GET /admin/models/{name}`` — one model's residency detail."""
        if self.lifecycle is None:
            return _error(503, "lifecycle manager not started")
        name = request.match_info["name"]
        snap = self.lifecycle.model_snapshot(name)
        if snap is None:
            return _error(404, f"model {name!r} not configured; available: "
                               f"{sorted(self.lifecycle.names)}")
        return web.json_response({"model": {"name": name, **snap}})

    async def handle_admin_model_post(self, request):
        """``POST /admin/models/{name} {"action": ...}`` — explicit
        lifecycle transitions:

        - ``activate`` — synchronous single-flight activation (shared with
          any concurrent cold requests); reports ``last_activation_ms``.
        - ``unload`` — scale to zero (compiled-cache-only tier); 409 if the
          model is PINNED or has in-flight work.
        - ``demote`` — one tier down (device → host-weights by default; an
          optional ``"to": "host"|"disk"|"none"`` picks the landing rung —
          ``disk`` needs ``ckpt_store_dir``); 409 if pinned/busy.
        - ``pin`` / ``unpin`` — PINNED residency (pin activates if COLD).
        """
        if self.lifecycle is None:
            return _error(503, "lifecycle manager not started")
        name = request.match_info["name"]
        lc = self.lifecycle
        if not lc.knows(name):
            return _error(404, f"model {name!r} not configured; available: "
                               f"{sorted(lc.names)}")
        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            return _error(400, "body must be a JSON object")
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        action = body.get("action")
        allowed = ("activate", "unload", "demote", "pin", "unpin")
        if action not in allowed:
            return _error(400, f"action must be one of {list(allowed)}, "
                               f"got {action!r}")
        try:
            if action == "activate":
                await lc.ensure_active(name, cause="admin")
            elif action == "unload":
                if not await lc.unload(name, cause="admin"):
                    return _error(409, f"model {name!r} cannot unload "
                                       "(pinned or busy)",
                                  **{"model": lc.model_snapshot(name)})
            elif action == "demote":
                to = body.get("to", "host")
                if to not in ("host", "disk", "none"):
                    return _error(400, "demote 'to' must be one of "
                                       "['host', 'disk', 'none'], "
                                       f"got {to!r}")
                if to == "disk" and lc.store is None:
                    return _error(409, "disk tier requires ckpt_store_dir")
                if not await lc.demote(name, to=to, cause="admin"):
                    return _error(409, f"model {name!r} cannot demote "
                                       "(pinned, busy, or not active)",
                                  **{"model": lc.model_snapshot(name)})
            elif action == "pin":
                await lc.pin(name)
            elif action == "unpin":
                lc.unpin(name)
        except ColdStart as e:
            return _error_retry(503, str(e), e.retry_after_s,
                                estimated_warm_ms=round(e.estimated_warm_ms, 1))
        except Exception as e:
            log.exception("admin lifecycle action %s failed for %s",
                          action, name)
            return _error(503, f"{action} failed for {name!r}: "
                               f"{type(e).__name__}: {e}")
        return web.json_response({"action": action,
                                  "model": {"name": name,
                                            **lc.model_snapshot(name)}})

    # -- admin: multi-tenant adapters (docs/ADAPTERS.md) ---------------------
    async def handle_admin_adapters(self, request):
        """``GET /admin/adapters`` — per-tenant residency snapshot."""
        return web.json_response(self.adapters.snapshot())

    async def handle_admin_adapter_post(self, request):
        """``POST /admin/adapters/{base}/{adapter} {"action": ...}`` —
        explicit ``attach`` (synchronous, shared with any concurrent cold
        requests) or ``detach`` (409 while the adapter has in-flight work).
        """
        base = request.match_info["name"]
        aname = request.match_info["adapter"]
        rec = self.adapters.get(base, aname)
        if rec is None:
            return self._unknown_adapter_error(base, aname, None)
        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            return _error(400, "body must be a JSON object")
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        action = body.get("action")
        if action not in ("attach", "detach"):
            return _error(400, f"action must be one of ['attach', "
                               f"'detach'], got {action!r}")
        try:
            if action == "attach":
                if self.lifecycle is not None and self.lifecycle.knows(base):
                    # The base must be resident to hold a slot pool.
                    await self.lifecycle.ensure_active(base, cause="admin")
                await self.adapters.ensure_attached(base, aname,
                                                    cause="admin")
            elif not await self.adapters.detach(base, aname, cause="admin"):
                return _error(
                    409, f"adapter {aname!r} on {base!r} cannot detach "
                         "(busy or not attached)",
                    adapter=self.adapters.adapter_snapshot(rec))
        except AdapterCold as e:
            return _error_retry(
                503, str(e), e.retry_after_s,
                estimated_attach_ms=round(e.estimated_attach_ms, 1))
        except Exception as e:
            log.exception("admin adapter action %s failed for %s:%s",
                          action, base, aname)
            return _error(503, f"{action} failed for {base}:{aname}: "
                               f"{type(e).__name__}: {e}")
        return web.json_response({
            "action": action,
            "adapter": {"model": base, "name": aname,
                        **self.adapters.adapter_snapshot(rec)}})

    # -- admin: prefix KV cache (docs/PREFIX.md) ------------------------------
    def _invalidate_prefix(self, base: str, slot: int):
        """AdapterManager detach hook: drop the slot's frozen prefixes."""
        sched = self.schedulers.get(base)
        if sched is not None and hasattr(sched, "invalidate_prefix"):
            sched.invalidate_prefix(slot)

    async def handle_admin_prefix(self, request):
        """``GET /admin/prefix`` — per-model radix-tree stats (nodes, pages,
        hit rate, CoW copies, evictions, cached-token histogram) for every
        paged lane with the prefix cache enabled."""
        models = {}
        for name, sched in self.schedulers.items():
            snap = sched.gen_snapshot()
            if "prefix" in snap:
                models[name] = {**snap["prefix"],
                                "kv_blocks_used": snap["kv"]["blocks_used"],
                                "kv_shared_blocks": snap["kv"].get(
                                    "shared_blocks", 0)}
        return web.json_response({"models": models})

    # -- admin: live KV migration (serving/kvmigrate.py; docs/DISAGG.md) -----
    def _register_stream(self, stream_id: str, model: str, sched, gen,
                         imported: bool):
        self.streams[stream_id] = {"model": model, "sched": sched,
                                   "gen": gen, "imported": imported,
                                   "attached": False,
                                   "created": time.time()}
        while len(self.streams) > self._streams_cap:
            self.streams.pop(next(iter(self.streams)))

    def _stream_entry(self, request):
        """(entry, error-response) for one /admin/streams/{id} call."""
        sid = request.match_info["stream_id"]
        entry = self.streams.get(sid)
        if entry is None:
            return None, _error(404, f"unknown stream {sid!r}",
                                streams=len(self.streams))
        sched = entry["sched"]
        if not isinstance(sched, PagedGenerationScheduler):
            return None, _error(409, "stream is not on a paged lane; "
                                     "migration requires kv_cache='paged'")
        if not sched.kv_migrate:
            return None, _error(409, "kv_migrate is disabled on model "
                                     f"{entry['model']!r}")
        return entry, None

    @staticmethod
    def _stream_state_of(gen) -> str:
        if gen.migrated:
            return "migrated"
        if gen.done.done():
            return "error" if gen.done.exception() is not None else "done"
        return "live"

    async def handle_admin_streams(self, request):
        """``GET /admin/streams`` — the live-stream registry: ids, model,
        token progress, migration evidence (docs/DISAGG.md)."""
        out = {}
        for sid, e in self.streams.items():
            gen = e["gen"]
            out[sid] = {"model": e["model"],
                        "state": self._stream_state_of(gen),
                        "tokens": len(gen.tokens),
                        "max_new": gen.max_new,
                        "emitted_base": gen.emitted_base,
                        "migrations": gen.migrations,
                        "imported": e["imported"]}
        return web.json_response({"streams": out})

    async def handle_stream_export(self, request):
        """``POST /admin/streams/{id}/export`` — the source half of a live
        migration, phased so decode barely stalls (docs/DISAGG.md):

        - ``{"phase": "snapshot"}`` — copy the stream's complete (frozen)
          pages while it KEEPS DECODING; returns packed pages + the
          frontier.  Idle-page-first: the hot page never travels here.
        - ``{"phase": "cutover", "have": [idx...]}`` — pause at a tick
          boundary and return the versioned manifest (prompt, emitted
          tokens, sampler state) plus only the delta pages the importer
          does not hold.  The stream stays detached until commit/abort.
        - ``{"phase": "pages", "indices": [...]}`` — re-read specific
          pages by value (the importer's integrity-failure retry).
        - ``{"phase": "commit", "cause": "admin"|"failover"|"pressure"}``
          — the importer confirmed: release pages, end the source stream
          with a terminal ``migrated`` SSE event (never a token loss).
        - ``{"phase": "abort"}`` — resume the stream in place.

        Every page record carries a sha256 integrity hash; the
        ``faults kind="migration"`` chaos rules fire here (drop → 503
        retryable, corrupt → caught by the importer's verify, slow →
        stretched copy).
        """
        entry, err = self._stream_entry(request)
        if err is not None:
            return err
        gen, sched, name = entry["gen"], entry["sched"], entry["model"]
        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            return _error(400, "body must be a JSON object")
        phase = body.get("phase", "cutover")
        if phase not in ("snapshot", "cutover", "pages", "commit", "abort"):
            return _error(400, f"phase must be snapshot|cutover|pages|"
                               f"commit|abort, got {phase!r}")
        mode, lat_s = self.engine.runner.faults.on_migration(name)
        if lat_s:
            await asyncio.sleep(lat_s)
        if mode == "drop":
            sched.migration.failed += 1
            return _error_retry(503, "injected migration fault "
                                     f"(drop, phase={phase})", 1.0,
                                retryable=True)

        def packed(pages: dict) -> list:
            # mode="corrupt": flip the first travelling page's bytes AFTER
            # its hash — the importer's verify must catch it and come back
            # through the "pages" retry lane.
            out = []
            for j, (i, (k, v)) in enumerate(sorted(pages.items())):
                out.append(pack_page(i, k, v,
                                     corrupt=(mode == "corrupt" and j == 0)))
            return out

        sid = request.match_info["stream_id"]
        try:
            if phase == "snapshot":
                res = await sched.migrate_snapshot(gen)
                return web.json_response({
                    "stream_id": sid, "model": name, "phase": phase,
                    "frontier": res["frontier"], "pos": res["pos"],
                    "pages": packed(res["pages"])})
            if phase == "cutover":
                have = [int(i) for i in (body.get("have") or ())]
                res = await sched.migrate_cutover(gen, have)
                adapter = self._adapter_name_of(name, res["aidx"])
                manifest = {
                    "version": FORMAT_VERSION, "stream_id": sid,
                    "model": name, "adapter": adapter,
                    "prompt": [int(t) for t in res["ids"]],
                    "emitted": res["emitted"],
                    "watermark": len(res["emitted"]),
                    "max_new": res["max_new"], "state": res["state"],
                    "npages": res["npages"],
                    "page_shape": list(sched.page_shape),
                    "dtype": str(np.dtype(sched.cache_dtype)),
                }
                return web.json_response({"manifest": manifest,
                                          "pages": packed(res["pages"])})
            if phase == "pages":
                indices = [int(i) for i in (body.get("indices") or ())]
                res = await sched.migrate_pages(gen, indices)
                return web.json_response({"stream_id": sid, "phase": phase,
                                          "pages": packed(res["pages"])})
            if phase == "commit":
                cause = body.get("cause", "admin")
                if cause not in CAUSES:
                    return _error(400, f"cause must be one of {CAUSES}, "
                                       f"got {cause!r}")
                wm = await sched.migrate_commit(gen, cause)
                return web.json_response({"committed": True,
                                          "stream_id": sid,
                                          "watermark": wm})
            await sched.migrate_abort(gen)
            return web.json_response({"aborted": True, "stream_id": sid})
        except MigrationError as e:
            return _error(409, str(e), stream_id=sid, phase=phase)

    def _adapter_name_of(self, model: str, aidx: int) -> str | None:
        """Reverse-resolve an adapter slot index to the tenant name (the
        wire carries names — slot indices are replica-local)."""
        if not aidx:
            return None
        for a in self.adapters.names_for(model):
            rec = self.adapters.get(model, a)
            if rec is not None and rec.slot == aidx:
                return a
        return None

    async def handle_stream_import(self, request):
        """``POST /admin/streams/{id}/import`` — the target half: verify
        page integrity, dedupe prompt pages through the LOCAL prefix radix
        tree (``dedup=hit`` — frozen pages are bitwise-portable), splice
        the rest by value, and resume decode from the imported sampler
        state.  Answers 409 ``{"need": [...]}`` for missing/corrupt pages
        (the caller re-fetches exactly those) and 503 retryable when the
        pool cannot take the stream right now.
        """
        sid = request.match_info["stream_id"]
        try:
            body = await request.json()
        except ValueError:
            return _error(400, "body must be a JSON object")
        manifest = body.get("manifest")
        try:
            check_manifest(manifest)
        except MigrationError as e:
            return _error(400, str(e))
        name = manifest.get("model")
        sched = self.schedulers.get(name)
        if not isinstance(sched, PagedGenerationScheduler):
            return _error(409, f"model {name!r} has no paged generation "
                               "lane on this replica")
        if not sched.kv_migrate:
            return _error(409, f"kv_migrate is disabled on model {name!r}")
        if (tuple(manifest["page_shape"]) != tuple(sched.page_shape)
                or str(np.dtype(manifest["dtype"]))
                != str(np.dtype(sched.cache_dtype))):
            return _error(409, "incompatible pool geometry: exporter page "
                               f"{manifest['page_shape']}/"
                               f"{manifest['dtype']} vs local "
                               f"{list(sched.page_shape)}/"
                               f"{np.dtype(sched.cache_dtype)}")
        cause = body.get("cause", "admin")
        if cause not in CAUSES:
            return _error(400, f"cause must be one of {CAUSES}, "
                               f"got {cause!r}")
        mode, lat_s = self.engine.runner.faults.on_migration(name)
        if lat_s:
            await asyncio.sleep(lat_s)
        if mode == "drop":
            sched.migration.failed += 1
            return _error_retry(503, "injected migration fault "
                                     "(drop, import)", 1.0, retryable=True)
        aidx = 0
        adapter = manifest.get("adapter")
        if adapter:
            rec = self.adapters.get(name, adapter)
            if rec is None or rec.slot is None:
                return _error_retry(
                    503, f"adapter {adapter!r} is not attached on this "
                         "replica; attach it and retry the import", 1.0,
                    adapter_cold=True)
            aidx = rec.slot
        page_map: dict = {}
        bad: list[int] = []
        shape = tuple(manifest["page_shape"])
        for rec_ in (body.get("pages") or ()):
            try:
                i, k, v = unpack_page(rec_, shape, manifest["dtype"])
                page_map[i] = (k, v)
            except PageIntegrityError as e:
                bad.extend(e.indices)
        if bad:
            return web.json_response(
                {"error": "page integrity check failed; re-fetch by value",
                 "need": sorted(bad), "stream_id": sid}, status=409)
        span = self.tracer.start("migrate_import", model=name,
                                 traceparent=request.headers.get(
                                     "traceparent"))
        try:
            gen, hits, copied = await sched.migrate_import(
                np.asarray(manifest["prompt"], np.int32),
                manifest["emitted"], manifest["state"], page_map,
                aidx=aidx, max_new=manifest["max_new"], cause=cause,
                span=span)
        except MigrationNeedsPages as e:
            self.tracer.finish(span.trace, "error")
            return web.json_response(
                {"error": str(e), "need": sorted(e.indices),
                 "stream_id": sid}, status=409)
        except MigrationError as e:
            self.tracer.finish(span.trace, "error")
            return _error_retry(503, str(e), 1.0, retryable=True)
        self.tracer.finish(span.trace, "ok")
        self._register_stream(sid, name, sched, gen, imported=True)
        return web.json_response({
            "imported": True, "stream_id": sid, "model": name,
            "watermark": gen.emitted_base, "dedup_pages": hits,
            "copied_pages": copied})

    async def handle_stream_attach(self, request):
        """``GET /admin/streams/{id}/attach?from=N`` — SSE of an IMPORTED
        stream from token watermark N: tokens the client already received
        are never re-sent (the zero-duplicate half of KV-aware failover),
        tokens it missed replay from the imported history, then the live
        tail streams as decode produces it."""
        sid = request.match_info["stream_id"]
        entry = self.streams.get(sid)
        if entry is None:
            return _error(404, f"unknown stream {sid!r}")
        if not entry["imported"]:
            return _error(409, "attach targets imported streams; the "
                               "original :generate response owns this one")
        if entry["attached"]:
            return _error(409, f"stream {sid!r} already has a consumer")
        entry["attached"] = True
        gen = entry["gen"]
        sched = entry["sched"]
        try:
            start = int(request.query.get("from", gen.emitted_base))
        except ValueError:
            return _error(400, "from must be an integer")
        start = max(0, start)
        resp = web.StreamResponse(headers={
            "Cache-Control": "no-cache", "X-Accel-Buffering": "no",
            "X-Stream-Id": sid})
        resp.content_type = "text/event-stream"
        await resp.prepare(request)

        async def send(obj) -> None:
            await resp.write(f"data: {json.dumps(obj)}\n\n".encode())

        try:
            # Imported history [start, emitted_base) lives only in the
            # tokens list (it never entered the event queue)...
            for t in gen.tokens[start:gen.emitted_base]:
                await send({"token": int(t)})
            # ...everything from emitted_base on flows through the queue —
            # skip what the caller already holds past the base.
            skip = max(0, start - gen.emitted_base)
            while True:
                ev = await gen.events.get()
                if ev is None:
                    break
                if skip > 0:
                    skip -= 1
                    continue
                await send({"token": ev})
            if gen.done.done() and gen.done.exception() is not None:
                if gen.migrated:
                    await send({"migrated": True, "stream_id": sid,
                                "watermark": len(gen.tokens)})
                else:
                    await send({"error": str(gen.done.exception()),
                                "stream_id": sid})
            else:
                body = {"done": True, "tokens": list(gen.tokens)}
                if sched.detokenize is not None:
                    body["text"] = sched.detokenize(gen.tokens)
                await send(body)
            await resp.write_eof()
        except (ConnectionResetError, asyncio.CancelledError):
            sched.cancel(gen)
            raise
        finally:
            entry["attached"] = False
        return resp

    # -- admin: SLO & goodput (docs/OBSERVABILITY.md §6) ----------------------
    async def handle_admin_slo(self, request):
        """``GET /admin/slo`` — per-(model, tenant, lane) goodput, outcome
        counts, fast/slow burn rates with alarm state, and the per-tenant
        usage ledger.  ``tpuserve slo`` renders this as the operator table;
        the fleet router serves the same path with every replica merged."""
        return web.json_response(self.slo.snapshot())

    async def handle_admin_autoscale(self, request):
        """``GET /admin/autoscale`` — the predictive autoscaling plane
        (docs/AUTOSCALE.md): per-key demand forecast, learned keep-warm
        window, next predicted arrival + planned pre-warm, the pre-warm
        hit/miss counters, and the misprediction degradation state.
        ``tpuserve autoscale`` renders this as the operator table."""
        return web.json_response(self.autoscale.snapshot())

    # -- admin: perf plane (docs/OBSERVABILITY.md §9) -------------------------
    async def handle_admin_perf(self, request):
        """``GET /admin/perf`` — the live perf plane: event-loop lag
        histogram + max, the top-K collapsed thread stacks by wall time,
        rolling per-model throughput gauges (samples/s, tok/s, step time,
        device utilization, MFU when hinted), and the per-(model, stage)
        ingest/egress histograms that decompose the http→device gap.
        ``?top=N`` bounds the stack table; ``tpuserve perf`` renders the
        operator table from this payload."""
        try:
            top = int(request.query.get("top", 20))
        except (TypeError, ValueError):
            return _error(400, "top must be an integer")
        snap = self.perf.snapshot(top_stacks=max(top, 1))
        # Fold the generation lanes' split ttft/itl quantiles into the
        # gauge rows (serving/generation.py): the perf table answers
        # "first token vs cadence" without a second endpoint.
        for n, s in self.schedulers.items():
            row = snap["models"].setdefault(f"{n}:generate", {})
            ttft = hist_quantile(s.ttft_hist.snapshot(), 0.5)
            itl = hist_quantile(s.itl_hist.snapshot(), 0.5)
            if ttft is not None:
                row["ttft_p50_ms"] = ttft
            if itl is not None:
                row["itl_p50_ms"] = itl
        # Read here, at scrape time, and never on the request path.
        snap["device_memory"] = device_memory()
        # Spawn to healthy in four intervals (utils/boot.py).
        snap["boot"] = boot.split()
        return web.json_response(snap)

    # -- admin: chaos + drain ------------------------------------------------
    async def handle_faults_get(self, request):
        return web.json_response({"faults": self.engine.runner.faults.snapshot()})

    async def handle_faults(self, request):
        """Configure the fault injector at runtime (docs/RESILIENCE.md).

        ``{"clear": true}`` removes every rule (and optional ``"model"``
        scopes the clear); otherwise the body is one rule:
        ``{"model": "*", "fail_every_n": 2, "count": 3, "kind": "transient",
        "latency_ms": 50, "preprocess": false}``.
        """
        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            return _error(400, "body must be a JSON object")
        if not isinstance(body, dict):
            return _error(400, "body must be a JSON object")
        faults = self.engine.runner.faults
        if body.get("clear"):
            # The clear path validates too: {"clear": true, "modle": "x"}
            # silently clearing EVERYTHING is exactly the typo'd-chaos-config
            # failure mode the rule path's 400 exists to prevent.
            unknown = set(body) - {"clear", "model"}
            if unknown:
                return _error(400, f"unknown fault fields {sorted(unknown)}; "
                                   f"allowed with clear: ['clear', 'model']")
            faults.clear(body.get("model"))
        else:
            allowed = {"model", "fail_every_n", "count", "kind",
                       "latency_ms", "preprocess", "mode"}
            unknown = set(body) - allowed
            if unknown:
                return _error(400, f"unknown fault fields {sorted(unknown)}; "
                                   f"allowed: {sorted(allowed)}")
            try:
                faults.configure(**body)
            except (TypeError, ValueError) as e:
                return _error(400, str(e))
        log_event(log, "fault rules updated", **faults.snapshot()["injected"])
        return web.json_response({"faults": faults.snapshot()})

    async def handle_recover(self, request):
        """Operator-triggered engine recovery (the watchdog path, over HTTP).

        Resets the watchdog's attempt budget (so it works after a
        ``gave_up``) and runs quarantine → rebuild → swap → requeue
        synchronously, reporting the resulting state.  Works even when the
        background watchdog is disabled — a one-shot supervisor is built on
        demand so the runbook is a single POST either way.
        """
        wd = self.watchdog
        if wd is None:
            wd = Watchdog(self, self.cfg.watchdog_interval_s or 1.0,
                          max_attempts=self.cfg.recover_max_attempts,
                          backoff_s=self.cfg.recover_backoff_s)
            self.watchdog = wd
            self.metrics.watchdog = wd
        try:
            snap = await wd.recover(reason="admin", manual=True)
        except Exception as e:
            log.exception("manual recovery failed")
            return _error(500, f"recovery failed: {type(e).__name__}: {e}",
                          recovery=wd.snapshot())
        status = 200 if snap["state"] == "healthy" else 503
        return web.json_response({"recovery": snap}, status=status)

    async def handle_drain(self, request):
        """Operator-initiated graceful drain (the SIGTERM path, over HTTP).

        Flips to draining, waits up to ``timeout_s`` (body override, default
        ``drain_timeout_s``) for in-flight work, and reports whether the
        drain completed.  Does NOT exit the process — the operator's
        supervisor owns that; this exists for load-balancer removal and
        for chaos tests.
        """
        try:
            body = await request.json() if request.can_read_body else {}
        except ValueError:
            body = {}
        timeout_s = float(body.get("timeout_s", self.cfg.drain_timeout_s or 5.0)) \
            if isinstance(body, dict) else 5.0
        self.begin_drain()
        drained = await self.wait_drained(timeout_s)
        return web.json_response({
            "draining": True, "drained": drained,
            "inflight": self._inflight,
            "jobs_backlog": self.jobs.depth if self.jobs else 0})


def create_app(cfg: ServeConfig, engine: Engine | None = None) -> web.Application:
    return Server(cfg, engine).app


def run(cfg: ServeConfig):
    """Serve HTTP — or, on a follower host of a multi-process world, mirror
    host 0's dispatches until it shuts down (parallel/lockstep.py).

    One ``tpuserve serve`` invocation per host with the same config: host 0
    (process_id 0) terminates requests, every other host builds the same
    engine and enters the follower loop — the load balancer needs exactly
    one backend.
    """
    if cfg.coordinator_address and cfg.num_processes > 1 and cfg.process_id != 0:
        from ..engine.loader import build_engine

        engine = build_engine(cfg)
        try:
            engine.lockstep.follow()  # blocks until host 0 leads a shutdown
        finally:
            engine.runner.shutdown()
        return
    server = Server(cfg)
    # Only the real process entrypoint owns signal state: with a drain
    # budget configured, SIGTERM flips to draining and exits after in-flight
    # work finishes (docs/RESILIENCE.md) instead of aiohttp's immediate stop.
    server._handle_signals = True

    def bound(banner: str) -> None:  # aiohttp's word that the socket listens
        boot.stamp("http")
        print(banner, flush=True)

    web.run_app(server.app, host=cfg.host, port=cfg.port, print=bound)
