"""Declarative configuration — the Zappa ``zappa_settings.json`` equivalent.

The reference configures stages (dev/prod), memory, timeouts and keep-warm in
``zappa_settings.json`` (SURVEY §2a, §5 "Config / flag system").  Here a single
dataclass tree covers per-model serving knobs and per-deploy profile knobs,
loadable from YAML/JSON with environment-variable overrides
(``TPUSERVE_<FIELD>``), and stages become named profiles.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

import yaml


@dataclass
class ModelConfig:
    """Per-model serving configuration.

    Mirrors what the reference hard-codes in ``app.py`` (checkpoint path,
    model builder) plus the batching/compile knobs the north star adds.
    """

    name: str
    # Checkpoint to import at cold start (torch .pth/.pt or .safetensors).
    # None → random-init with the real architecture (offline dev mode).
    checkpoint: str | None = None
    # Batch-size buckets precompiled at boot; requests are padded up to the
    # smallest bucket that fits (SURVEY §7 hard part 3).
    batch_buckets: tuple[int, ...] = (1, 4, 8, 16, 32)
    # Sequence-length buckets (token models only).
    seq_buckets: tuple[int, ...] = (128,)
    # Compute dtype on device; params stay fp32.
    dtype: str = "bfloat16"
    # Registered builder this deploy name instantiates ("" → the name
    # itself).  Lets one profile serve several *variants* of one builder
    # side by side — ``{name: gpt2_int8, builder: gpt2, extra:
    # {params_dtype: int8}}`` — each with its own lanes, metrics, and
    # residency (docs/VARIANTS.md).
    builder: str = ""
    # Variant family (docs/VARIANTS.md): variants sharing a family are
    # interchangeable implementations of one task at different
    # quality/cost points, and clients may address the FAMILY (plus an
    # objective) instead of a concrete variant — the server then picks.
    # "" → the model is its own single-member family (the pre-variant
    # behavior, unchanged).
    family: str = ""
    # Position on the family's quality ladder: higher = better output
    # quality (full-precision above int8, more denoise steps above fewer).
    # The brownout ladder degrades DOWN this rank before shedding.
    quality_rank: int = 0
    # Relative cost prior in ms (expected device time per request) used to
    # rank variants before any live latency evidence exists; live
    # LatencyRing p50 replaces it as soon as requests flow.  0 → unknown.
    cost_hint_ms: float = 0.0
    # Max concurrent requests admitted before 429 (backpressure).
    max_concurrency: int = 256
    # Batcher coalescing window in milliseconds: how long the head-of-line
    # request waits for co-batchable requests before dispatch.
    coalesce_ms: float = 2.0
    # Default request deadline in milliseconds (docs/RESILIENCE.md): applied
    # when the client sends none; checked at admission, re-checked when the
    # batcher pops the request (expired work is shed with 504, never
    # dispatched), and bounds the await on the device future.  0 → fall back
    # to ServeConfig.deadline_default_ms (0 there too → no deadline).
    deadline_ms: float = 0.0
    # QoS latency class for the priority dispatch lane (engine/runner.py):
    # "latency" dispatches jump ahead of queued "throughput" work between
    # device calls.  "" (default) defers to the class the model family
    # declared at registration (utils/registry.py) — resnet/bert/etc. are
    # "latency", sd15 is "throughput"; set explicitly to override per deploy.
    latency_class: str = ""
    # Serverless lifecycle (docs/LIFECYCLE.md): build this model lazily on
    # its first request instead of at boot.  None (default) defers to the
    # global ``ServeConfig.lazy_load``; True/False overrides per model.
    lazy_load: bool | None = None
    # PINNED residency: always device-resident — built at boot even under
    # lazy_load, never idle-unloaded, never evicted by the HBM budget.
    # Runtime twin: ``POST /admin/models/{name} {"action": "pin"}``.
    pinned: bool = False
    # -- continuous batching v2 (docs/GENERATION.md) ------------------------
    # KV-cache engine for the :generate lane: "slot" (the proven fixed slot
    # pool; default) or "paged" — a block-paged pool where sequences hold
    # only the pages their tokens need (PagedGenerationScheduler), enabling
    # chunked prefill and speculative decoding.  Requires the servable to
    # expose the paged kernel contract (gpt2 does); multi-host lockstep
    # worlds always serve the slot pool.
    kv_cache: str = "slot"
    # Token positions per KV page (paged only).
    kv_block_size: int = 16
    # Page-pool size (paged only).  0 → auto: slots x ceil(total/block) + 1
    # — the slot pool's worst-case capacity, so the default serves the same
    # load in the same HBM; size DOWN for utilization, raise gen_slots for
    # concurrency.
    kv_num_blocks: int = 0
    # Chunked prefill: max tokens per prefill dispatch, interleaved with
    # decode ticks so long prompts can't stall live streams.  0 → one
    # (bucketed) chunk per prompt.
    prefill_chunk_tokens: int = 0
    # Speculative decoding (paged only): the draft variant that proposes
    # spec_k tokens per tick, verified by this model in one forward with
    # distribution-preserving rejection sampling.  "" → off; "auto" → the
    # lowest-quality rung of this model's variant family (docs/VARIANTS.md);
    # any other value names a deploy directly (e.g. "gpt2_int8").  Falls
    # back to plain decode while the draft is COLD or quarantined.
    spec_draft: str = ""
    spec_k: int = 4
    # -- prefix KV cache (docs/PREFIX.md) -----------------------------------
    # Radix-tree reuse of frozen prompt pages across requests (paged lanes
    # only): matched (model, adapter, token-prefix) spans skip prefill
    # entirely, with copy-on-write on divergence — warm-prefix output is
    # byte-identical to cold.  On by default; costs nothing without repeats.
    prefix_cache: bool = True
    # Idle decay: frozen prefixes unreferenced for this long are evicted
    # (leaf-first, LRU).  0 = no time-based decay — pages still yield
    # on demand before any live stream is evicted.
    prefix_cache_ttl_s: float = 0.0
    # Cap on tree-held pages; inserts past it trigger LRU decay.
    # 0 = bounded only by the pool itself.
    prefix_cache_blocks: int = 0
    # -- live KV migration (docs/DISAGG.md) ---------------------------------
    # Under KV-pool pressure, migrate the newest stream's pages to host
    # memory and resume it byte-identically when blocks free (zero
    # recompute, zero stream kills) instead of PR 9's evict+recompute.
    # Also gates the export/import admin lanes this lane answers.  False
    # restores the pure eviction ladder.
    kv_migrate: bool = True
    # -- multi-tenant LoRA adapters (docs/ADAPTERS.md) ----------------------
    # Device slot pool for co-resident adapters on this base model: 0
    # disables adapters; N reserves N slots (plus the implicit slot 0 = the
    # zero adapter / base passthrough).  Requests for DIFFERENT adapters on
    # the same base co-batch into one dispatch — each row gathers its own
    # low-rank factors by slot index (ops/lora.py).  Single-device only
    # (like the int8 lane), and not combinable with params_dtype int8/auto.
    adapter_slots: int = 0
    # Uniform low-rank width of the slot pool (stack shapes are baked into
    # the compiled programs); adapter checkpoints of smaller rank zero-pad
    # up, larger ranks are a config error.
    adapter_rank: int = 8
    # Which projections carry deltas; every configured adapter must fit.
    adapter_targets: tuple[str, ...] = ("q", "v")
    # Registered adapters: {name: {checkpoint, alpha, rank, tenants, seed}}.
    # checkpoint None → deterministic random-init (dev mode, like models);
    # ``tenants`` lists the X-Tenant ids that resolve to this adapter.
    adapters: dict[str, dict] = field(default_factory=dict)
    # Free-form per-model extras (e.g. SD-1.5 num_steps, Whisper max tokens).
    extra: dict[str, Any] = field(default_factory=dict)


@dataclass
class FleetConfig:
    """Fleet control-plane profile (docs/FLEET.md): one router, N replicas.

    The router (``tpuserve fleet``; serving/fleet.py) polls every replica's
    ``/healthz`` + ``/admin/models`` and routes each request to a replica
    where the target model is ACTIVE — least forecast queue wait among them —
    spilling ``cold_start`` 503s to warm peers and failing over around dead
    or partitioned replicas with at most ``failover_retries`` extra
    attempts.
    """

    host: str = "127.0.0.1"
    port: int = 8080
    # Replica base URLs ("http://host:port").  Empty + spawn=0 → the fleet
    # CLI refuses to start (a router with nothing behind it serves nothing).
    replicas: list = field(default_factory=list)
    # Local replicas for `tpuserve fleet --spawn N`: subprocesses running
    # `tpuserve serve` on spawn_base_port + i, each with its own journal
    # subdirectory (journal_dir/replica-i) so durability stays per-replica.
    spawn: int = 0
    spawn_base_port: int = 8100
    # Registry poll cadence: healthz (liveness, drain flag, queue forecast)
    # and /admin/models (residency + estimated_warm_ms) per replica.
    poll_interval_s: float = 1.0
    # Outbound timeouts: connect is short (a dead host must fail fast into
    # the failover path), total is the per-attempt budget — a client
    # X-Deadline-Ms tightens it further per request.
    connect_timeout_s: float = 2.0
    request_timeout_s: float = 120.0
    # Failover: extra attempts against a DIFFERENT replica after the first
    # choice fails (connect error, timeout, cold_start spill, 429/503 shed).
    # 1 is the contract the crashtest asserts; 0 disables failover.
    failover_retries: int = 1
    failover_backoff_ms: float = 25.0
    # Quarantine: consecutive connect/poll failures before a replica is
    # pulled from routing (health polls keep probing it; a clean poll
    # re-admits).  The per-replica circuit breaker (same knobs as the
    # per-model one) covers request-level failures.
    quarantine_after: int = 3
    breaker_threshold: float = 0.5
    breaker_window: int = 20
    breaker_min_samples: int = 6
    breaker_open_s: float = 5.0
    # Bounded affinity maps: job id → replica (polls route home) and
    # Idempotency-Key → replica (resubmits dedupe against the journal that
    # acked the original; docs/FLEET.md "Cross-replica idempotency").
    affinity_capacity: int = 8192
    # Model for the /predict and /classify aliases; "" → the replica's own
    # default (first configured model).
    default_model: str = ""
    # -- disaggregated prefill/decode + KV-aware failover (docs/DISAGG.md) --
    # Disaggregated serving: prefill runs on a prefill-tagged replica, the
    # stream's KV pages migrate to a decode replica at the first token, and
    # decode continues there (DistServe/Splitwise lineage, PAPERS.md).
    # Requires paged lanes (ModelConfig.kv_cache="paged") on the replicas.
    disagg: bool = False
    # Replica base URLs tagged compute/prefill (must also appear in
    # ``replicas``); everything else is a decode candidate.  Empty →
    # role-less: the router picks any two distinct replicas.
    prefill_replicas: list = field(default_factory=list)
    # KV-aware failover for in-flight :generate streams (disagg mode): the
    # router journals each stream's migrated pages + the emitted-token
    # watermark; on decode-replica death it re-imports on a peer and
    # replays from the watermark — zero token loss, zero duplicates.
    kv_failover: bool = True
    # Bounded stream journal (entries; oldest evicted first).
    stream_journal_capacity: int = 1024
    # -- predictive replica scaling (docs/AUTOSCALE.md) ---------------------
    # POST /admin/fleet/scale sizes the fleet from the aggregated queue-wait
    # forecast each replica's /healthz exports (serving/resilience.py): out
    # while the fleet mean exceeds scale_target_wait_ms, in while it sits
    # under a quarter of it, one replica per step, clamped to
    # [scale_min_replicas, scale_max_replicas].
    scale_target_wait_ms: float = 250.0
    scale_min_replicas: int = 1
    scale_max_replicas: int = 8
    # Autonomous scaling cadence: every interval the router applies one
    # "auto" scale step (requires a spawn hook, i.e. a --spawn fleet).
    # 0 → manual only (the actuator still answers POST /admin/fleet/scale).
    autoscale_interval_s: float = 0.0


@dataclass
class ServeConfig:
    """Per-deploy profile — the stage (dev/prod) concept from Zappa."""

    profile: str = "dev"
    host: str = "127.0.0.1"
    port: int = 8000
    # Persistent XLA compilation cache directory (cold-start accelerator;
    # the TPU-native analogue of Lambda keep-warm, SURVEY §3.4).  "" → the
    # fixed default inside the checkout; JAX_COMPILATION_CACHE_DIR, when
    # set, wins over both (engine/cache.py resolve_compile_cache_dir).
    compile_cache_dir: str = ""
    # Precompile all (model × bucket) executables at boot rather than lazily.
    warmup_at_boot: bool = True
    # Two-level priority dispatch (engine/runner.py): latency-class dispatches
    # jump ahead of queued throughput work between device calls.  False
    # restores the single-FIFO lane (the pre-QoS behavior, kept as the
    # comparison point for head-of-line blocking on one engine).
    priority_dispatch: bool = True
    # Device mesh shape for multi-chip serving, e.g. {"data": 4, "model": 2}.
    # Empty → single-device (the v5e-1 target).
    mesh: dict[str, int] = field(default_factory=dict)
    # Multi-host (DCN) bootstrap (SURVEY §5 distributed backend): setting
    # coordinator_address ("host:port" of process 0) with num_processes > 1
    # joins jax.distributed before the engine builds — jax.devices() becomes
    # the GLOBAL pool, the mesh spans hosts, and XLA routes collectives over
    # ICI within a slice / DCN across slices.  Every process must run the
    # SAME profile (multi-controller SPMD); see README "Multi-host".
    coordinator_address: str = ""
    num_processes: int = 1
    process_id: int = 0
    # jax.profiler trace server port (SURVEY §5 tracing): connect
    # TensorBoard/XProf to this port for live profiling.  0 → disabled.
    profiler_port: int = 0
    # Where POST /admin/profile captures land (perfetto/xplane format).
    trace_dir: str = "~/.cache/tpuserve/traces"
    # Supervisor (SURVEY §5 failure detection): probe the device every
    # interval; after fail_threshold consecutive failures rebuild the engine
    # (the in-process Lambda-respawn analogue — cheap because the persistent
    # compile cache makes re-warmup a cache hit).  0 → disabled.
    supervise_interval_s: float = 0.0
    supervise_fail_threshold: int = 3
    # Multi-host leader only: how long the /healthz probe waits for a no-op
    # to clear the dispatch queue before declaring the lane wedged (a dead
    # follower strands the leader inside a collective).  Must sit ABOVE the
    # longest legitimate lane occupancy — lazy compiles included — or
    # health flips during a cold :generate compile.  0 disables.
    dispatch_probe_timeout_s: float = 300.0
    # Multi-host leader only: broadcast a no-op heartbeat to the followers
    # every interval, so an idle follower is never stranded inside a header
    # collective longer than this (the r3 "set a collective timeout
    # generously / run a cron ping" caveat, made a mechanism).  0 → off.
    heartbeat_interval_s: float = 0.0
    # Multi-host only: when a generation lane goes fatal (protocol
    # divergence — the lane cannot recover in place), SIGINT this process
    # (SIGTERM is pre-empted by jax's distributed runtime; README
    # "Multi-host") so the rendered warmpool.sh supervision loop restarts
    # the WORLD instead of serving 503s forever.  Single-host ignores it.
    exit_on_fatal: bool = True
    # -- request resilience (docs/RESILIENCE.md) ----------------------------
    # Every knob defaults to the pre-resilience behavior when unset (0/off).
    # Fleet-wide default deadline when neither the client nor the model's
    # ModelConfig.deadline_ms sets one.  0 → requests have no deadline.
    deadline_default_ms: float = 0.0
    # Cap on client-supplied deadlines (a client asking for 10 minutes on a
    # 30 ms model is lying to itself and pinning server state).  0 → no cap.
    deadline_max_ms: float = 0.0
    # Transient-fault retry (faults.is_transient): max retries per dispatch
    # after the first attempt (0 → off), capped exponential backoff base/max.
    # Retries never extend past the request's deadline.
    retry_max_attempts: int = 0
    retry_base_ms: float = 10.0
    retry_max_ms: float = 1000.0
    # Per-model circuit breaker: error-rate threshold in [0,1] that trips the
    # breaker OPEN once min_samples outcomes are in the sliding window
    # (0 → breaker disabled); open_s is the cooldown before half-open probes.
    breaker_threshold: float = 0.0
    breaker_window: int = 20
    breaker_min_samples: int = 10
    breaker_open_s: float = 5.0
    # Graceful drain: on SIGTERM flip to draining (healthz 503, new work
    # 503 + Retry-After), give in-flight requests and queued jobs this long
    # to finish, then exit cleanly.  0 → aiohttp's default immediate
    # GracefulExit (the pre-resilience behavior).
    drain_timeout_s: float = 0.0
    # -- durability & self-healing (docs/RESILIENCE.md "Durability") --------
    # Append-only job journal directory ("" = durability off): one JSONL
    # record per job state transition (submitted/running/done/failed).  On
    # boot the JobQueue replays it — acknowledged submits survive a kill -9,
    # done-job results are restored from disk (bounded by the job_* retention
    # knobs below), and Idempotency-Key dedupe works across restarts.
    journal_dir: str = ""
    # Journal fsync policy: "always" fsyncs every record (an acked submit is
    # on disk before the 202 leaves), "interval" fsyncs at most every ~250 ms
    # (bounded loss window, much cheaper), "never" leaves flushing to the OS
    # page cache (process crash safe, host crash may lose the tail).
    journal_fsync: str = "always"
    # Self-healing watchdog (serving/watchdog.py): probe the runner every
    # interval; a poisoned/fatally-faulted engine (dead device probe, or a
    # breaker open on a fatal cause) is quarantined and rebuilt in the
    # background — re-jit hits the persistent compile cache, so recovery is
    # a warm boot, not a cold one.  0 → disabled.
    watchdog_interval_s: float = 0.0
    # Bounded rebuild budget: after this many consecutive failed rebuild
    # attempts (with exponential backoff between them, base recover_backoff_s)
    # the watchdog gives up — a truly-dead device converges to breaker-open /
    # quarantined 503s instead of a rebuild loop.  POST /admin/recover resets
    # the budget and retries.
    recover_max_attempts: int = 3
    recover_backoff_s: float = 1.0
    # Async job queue retention (serving/jobs.py), previously constructor-only.
    job_max_backlog: int = 64
    job_keep_done: int = 256
    job_result_ttl_s: float = 900.0
    job_max_result_mb: float = 64.0
    # -- serverless model lifecycle (docs/LIFECYCLE.md) ---------------------
    # Global lazy-activation knob: models build on their first request (one
    # single-flight activation per model) instead of eagerly at boot.
    # Per-model ``ModelConfig.lazy_load`` overrides; PINNED models and SPMD
    # worlds (mesh / multi-process) always build eagerly.
    lazy_load: bool = False
    # Scale-to-zero: a model idle this long is demoted device → host-weights
    # (frees HBM; re-activation is a device_put), and after a further
    # ``host_idle_drop_s`` of idleness dropped to compiled-cache-only
    # (re-activation is a full build against the warm persistent compile
    # cache).  0 → never unload (the pre-lifecycle behavior).
    idle_unload_s: float = 0.0
    # Device-residency budget in bytes: while the live HBM accounting
    # (engine/runner.py resident_bytes) exceeds it, LRU non-PINNED idle
    # models are demoted to the host tier.  0 → unlimited.
    hbm_budget_bytes: int = 0
    # Host-tier retention before dropping to compiled-cache-only.
    # 0 → 4 x idle_unload_s.
    host_idle_drop_s: float = 0.0
    # Host-residency budget in bytes, mirroring hbm_budget_bytes one rung
    # down the ladder: while host-tier weight bytes exceed it, LRU host
    # copies demote to the disk tier (or drop to compiled-cache-only when
    # no checkpoint store is configured).  0 → unlimited.
    host_budget_bytes: int = 0
    # Streaming checkpoint store (serving/ckptstore.py, docs/LIFECYCLE.md):
    # a directory for chunked, content-addressed, dedup'd weights.  Set →
    # cold activations overlap disk read → host staging → h2d with the
    # compile, demotions gain the disk tier, and variant/adapter
    # activations stream only their delta chunks.  "" → store off (the
    # pre-store ladder device → host → none).
    ckpt_store_dir: str = ""
    # Chunk size for the store's content-addressed layout; the unit of
    # integrity hashing, dedup, and pipeline staging.
    ckpt_chunk_bytes: int = 1 << 20
    # Lifecycle reaper interval; 0 → auto (idle_unload_s / 4, clamped).
    lifecycle_tick_s: float = 0.0
    # Cold admission (serving/lifecycle.py): a request whose deadline cannot
    # cover the estimated activation time fast-fails 503 ``cold_start`` with
    # Retry-After + estimated_warm_ms; deadline-less requests block on the
    # single-flight activation up to activation_max_wait_s.
    # activation_estimate_ms is the prior used before any activation has
    # been observed for a model (history and CompileClock entries refine it;
    # a warm persistent compile cache quarters it).
    activation_max_wait_s: float = 120.0
    activation_estimate_ms: float = 15000.0
    # -- multi-tenant adapter serving (docs/ADAPTERS.md) --------------------
    # Scale-to-zero per TENANT: an adapter idle this long detaches from its
    # device slot (re-attach is a tiny device_put, single-flight).  0 →
    # follow ``idle_unload_s``; negative → never.
    adapter_idle_unload_s: float = 0.0
    # Cold-attach prior in ms before any attach has been observed for an
    # adapter (history refines it): the deadline-infeasibility bound behind
    # the 503 ``adapter_cold`` fast-fail.
    adapter_attach_estimate_ms: float = 500.0
    # -- predictive autoscaling (docs/AUTOSCALE.md) -------------------------
    # Demand-model policy (serving/autoscale.py): "predictive" (default)
    # learns per-key keep-warm windows from the inter-arrival histogram AND
    # pre-warms ahead of forecast demand; "histogram" learns the windows
    # only (Shahrad-style keep-warm, no pre-warming); "off" restores the
    # purely reactive fixed-timer behavior.  The fixed idle timers above
    # remain the fallback whenever a key's history is thin or the plane has
    # degraded after mispredictions.
    autoscale: str = "predictive"
    # Control-tick cadence; 0 → 1 s.
    autoscale_tick_s: float = 0.0
    # Keep-warm window = this quantile of the key's inter-arrival gaps
    # (Shahrad's histogram policy), clamped to [keepwarm_min_s,
    # keepwarm_max_s].
    keepwarm_quantile: float = 0.95
    keepwarm_min_s: float = 1.0
    keepwarm_max_s: float = 600.0
    # Gap observations required before the learned window/forecast applies
    # (below it the fixed timers rule — cheap keys never mistrain).
    autoscale_min_history: int = 8
    # Extra lead time added to estimated_warm_ms so a pre-warm COMPLETES
    # before the predicted burst.
    prewarm_margin_s: float = 1.0
    # Misprediction ladder: this many consecutive pre-warms that no arrival
    # matches degrade the plane to reactive (no pre-warms, fixed timers)
    # for autoscale_reactive_hold_s before it re-learns.
    autoscale_mispredict_limit: int = 3
    autoscale_reactive_hold_s: float = 30.0
    # -- request tracing (docs/OBSERVABILITY.md) ----------------------------
    # Bounded ring of finished per-request span trees (GET /admin/trace);
    # the flight recorder additionally pins, per model, the trace_flight_slow
    # slowest and the last trace_flight_errors errored traces so they survive
    # ring churn.  trace_max_spans caps one trace's span count (drops are
    # counted on /metrics, never raised).
    trace_ring: int = 256
    trace_flight_slow: int = 8
    trace_flight_errors: int = 32
    trace_max_spans: int = 512
    # -- perf plane (docs/OBSERVABILITY.md §9) ------------------------------
    # Always-on performance observability (serving/perfplane.py): ingest/
    # egress stage histograms, the event-loop lag sampler, the thread-stack
    # sampler, and the rolling per-model throughput gauges — all surfaced on
    # GET /admin/perf, `tpuserve perf`, and the tpuserve_ingest_ms/
    # tpuserve_loop_lag_*/tpuserve_perf_* metric families.  False turns the
    # whole plane off (no threads, no timers, no histogram writes).  What
    # the plane costs when on has no measurement in the tree (ROADMAP
    # Design 4).
    perfplane: bool = True
    # Event-loop lag probe cadence (also the gauge sampling cadence).
    perf_loop_lag_interval_s: float = 0.25
    # Thread-stack sampler rate in Hz (0 = stack sampling off; the lag
    # sampler and gauges stay on).
    perf_stack_hz: float = 7.0
    # Bounded top-K collapsed-stack table size (evicted weight folds into
    # an explicit "(other)" row).
    perf_stack_topk: int = 64
    # Rolling window for the per-model tok/s / samples/s / MFU gauges.
    perf_window_s: float = 30.0
    # -- server fast path (docs/SERVERPATH.md) ------------------------------
    # Zero-copy binary tensor lane: negotiate application/x-tpuserve-tensor
    # request/response bodies beside the JSON+b64 and raw-image lanes.
    # False answers binary frames 415 (the lane is an opt-out, not a
    # protocol removal — JSON clients never notice either way).
    binary_lane: bool = True
    # Per-frame byte cap for the binary lane, checked against the DECLARED
    # sizes before any allocation (413 over it).  0 inherits the HTTP
    # body cap (64 MiB).
    tensor_max_bytes: int = 0
    # SO_REUSEPORT multi-process acceptors (serving/acceptors.py): N worker
    # processes accept + host-ingest binary-lane traffic on ingest_port and
    # feed this process's device dispatch over shared-memory rings with
    # batch-level response fan-out.  0 (default) = single-process serving,
    # byte-identical to the pre-ISSUE-16 path.
    ingest_workers: int = 0
    # Fast-lane port the acceptor workers bind with SO_REUSEPORT
    # (0 = port + 1).  The main port keeps serving every lane unchanged.
    ingest_port: int = 0
    # Shared-memory ring geometry: slots per ring and the byte size of one
    # slot (a request or batch-response message must fit in one slot; a
    # bigger one is shed with 413 at the worker, never truncated).
    shm_ring_slots: int = 256
    shm_ring_slot_bytes: int = 1 << 20
    # -- objective-driven variant serving (docs/VARIANTS.md) ----------------
    # Brownout mode for family-addressed requests: "auto" degrades to a
    # cheaper variant when the preferred one would shed (forecast over the
    # latency bound, breaker open, quarantined) and recovers with
    # hysteresis; "forced" always serves the cheapest satisfying variant
    # (load-test / incident posture); "off" disables the ladder — the
    # selector still picks, but never *because* of pressure, and a
    # preferred variant that cannot serve sheds exactly as before.
    brownout: str = "auto"
    # Hysteresis: consecutive pressure-free selections required before a
    # family exits brownout (oscillating forecasts reset the count — no
    # flapping), and the minimum seconds a brownout holds once entered.
    brownout_exit_ticks: int = 3
    brownout_min_hold_s: float = 5.0
    # -- SLO / goodput accounting (docs/OBSERVABILITY.md §6) -----------------
    # Per-key objective overrides, keyed "model", "model:adapter" (one
    # tenant), or a variant family: {latency_objective_ms,
    # availability_target}.  File-only (structured).  Keys not listed
    # inherit the slo_* defaults below, so the plane is on for everything
    # the moment any objective matters.
    slo: dict[str, dict] = field(default_factory=dict)
    # Default latency objective in ms (0 = served == on time) and
    # availability target (0.999 → a 0.1% error budget) for unconfigured
    # keys.
    slo_latency_objective_ms: float = 0.0
    slo_availability_target: float = 0.999
    # Multi-window burn-rate alert (the SRE fast/slow pair): window lengths
    # and the burn-rate thresholds that flip each window's alarm (14 over
    # 5 m is the canonical page-now pace; 6 over 1 h the ticket pace).
    slo_fast_window_s: float = 300.0
    slo_slow_window_s: float = 3600.0
    slo_fast_burn_alarm: float = 14.0
    slo_slow_burn_alarm: float = 6.0
    # Boot-time fault injection rules ({model: {fail_every_n, kind, ...}});
    # the config twin of POST /admin/faults, for chaos soaks.  File-only.
    faults: dict[str, dict] = field(default_factory=dict)
    # Fleet control plane (docs/FLEET.md): the `tpuserve fleet` router's
    # knobs live beside the replica profile so one YAML file describes the
    # whole deployment.  File-only (structured, like models/faults).
    fleet: FleetConfig = field(default_factory=FleetConfig)
    models: list[ModelConfig] = field(default_factory=list)

    def model(self, name: str) -> ModelConfig:
        for m in self.models:
            if m.name == name:
                return m
        raise KeyError(f"model {name!r} not in profile {self.profile!r}")


_ENV_PREFIX = "TPUSERVE_"


def _coerce(value: str, target_type: Any) -> Any:
    if target_type is bool:
        return value.lower() in ("1", "true", "yes", "on")
    if target_type is int:
        return int(value)
    if target_type is float:
        return float(value)
    return value


def apply_env_overrides(cfg: ServeConfig, environ: dict[str, str] | None = None) -> ServeConfig:
    """Override top-level ServeConfig fields from TPUSERVE_* env vars.

    Mirrors the reference pattern of overriding Zappa stage settings with
    Lambda console env vars (SURVEY §5).  Coercion is driven by the field's
    *current value type* (robust to stringized annotations); ``mesh`` accepts
    JSON (``TPUSERVE_MESH='{"data": 4, "model": 2}'``), ``models`` is
    file-only (structured per-model config doesn't belong in an env var).
    """
    environ = os.environ if environ is None else environ
    for f in dataclasses.fields(ServeConfig):
        key = _ENV_PREFIX + f.name.upper()
        if key not in environ:
            continue
        if f.name in ("models", "faults", "fleet", "slo"):
            continue  # structured config is file-only
        if f.name == "mesh":
            try:
                mesh = json.loads(environ[key])
                if not isinstance(mesh, dict):
                    raise TypeError(f"expected JSON object, got {type(mesh).__name__}")
                cfg.mesh = {str(k): int(v) for k, v in mesh.items()}
            except (ValueError, TypeError) as e:
                raise ValueError(
                    f'{key} must be a JSON object like {{"data": 4, "model": 2}}: {e}'
                ) from None
            continue
        # Coerce by the field DEFAULT's type, not the current value's: a
        # float field loaded from YAML as an int (``drain_timeout_s: 20``)
        # must still accept a float override ("7.5").  Fields without a
        # literal default (mesh/models/faults) are handled above.
        current = getattr(cfg, f.name)
        target = (type(f.default) if f.default is not dataclasses.MISSING
                  else type(current))
        setattr(cfg, f.name, _coerce(environ[key], target))
    return cfg


def load_config(path: str | Path | None = None, profile: str | None = None) -> ServeConfig:
    """Load a ServeConfig from YAML/JSON; fall back to built-in defaults.

    The file may contain multiple named profiles (the Zappa stages idea):

    .. code-block:: yaml

        profiles:
          dev:  {port: 8000, models: [{name: resnet18}]}
          prod: {port: 80, warmup_at_boot: true, models: [...]}
    """
    if path is None:
        cfg = default_config()
        return apply_env_overrides(cfg)
    raw = Path(path).expanduser().read_text()
    data = json.loads(raw) if str(path).endswith(".json") else yaml.safe_load(raw)
    if not data:
        return apply_env_overrides(default_config())
    if "profiles" in data:
        profile = profile or data.get("default_profile", next(iter(data["profiles"])))
        data = dict(data["profiles"][profile], profile=profile)
    models = [ModelConfig(**{**m, "batch_buckets": tuple(m.get("batch_buckets", (1, 4, 8, 16, 32))),
                             "seq_buckets": tuple(m.get("seq_buckets", (128,))),
                             "adapter_targets": tuple(
                                 m.get("adapter_targets", ("q", "v")))})
              for m in data.pop("models", [])]
    fleet = data.pop("fleet", None)
    cfg = ServeConfig(models=models, **data)
    if fleet:
        cfg.fleet = FleetConfig(**fleet)
    return apply_env_overrides(cfg)


def _plain(value: Any) -> Any:
    """Recursively convert tuples → lists so yaml.safe_dump accepts the tree."""
    if isinstance(value, dict):
        return {k: _plain(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_plain(v) for v in value]
    return value


def dump_config(cfg: ServeConfig) -> str:
    """Serialize a ServeConfig to the profiles-style YAML ``load_config``
    reads back (round-trip tested) — what ``tpuserve deploy`` renders as the
    ``config.yaml`` its Dockerfile mounts, and ``stage`` emits pointing at
    the staged asset tree."""
    d = _plain(dataclasses.asdict(cfg))
    profile = d.pop("profile")
    return yaml.safe_dump({"default_profile": profile, "profiles": {profile: d}},
                          sort_keys=False)


def default_config() -> ServeConfig:
    """The built-in dev profile: every *implemented* zoo model, random-init.

    Filters against the registry so the zero-config path always boots even
    while the zoo is growing.
    """
    from .utils.registry import list_models
    from . import models as _zoo  # noqa: F401  (populates the registry)

    registered = set(list_models())
    cfg = ServeConfig(
        profile="dev",
        # Dev quickstart boots without compiling (~1.5 min of weight init
        # for the 8-model zoo); each bucket compiles lazily on its first
        # request — warming all (model x bucket) executables at boot would
        # otherwise cost many extra minutes (on CPU, tens) before the first
        # byte is served.  Production profiles set
        # warmup_at_boot: true (and the warm-pool script runs `tpuserve
        # warm`) so serving traffic never compiles.
        warmup_at_boot=False,
        models=[
            ModelConfig(name="resnet18", batch_buckets=(1, 4, 8)),
            ModelConfig(name="resnet50", batch_buckets=(1, 4, 8, 32)),
            ModelConfig(name="efficientnet_b0", batch_buckets=(1, 4, 8)),
            ModelConfig(name="vit_b16", batch_buckets=(1, 4, 8)),
            ModelConfig(name="bert_base", batch_buckets=(1, 4, 8), seq_buckets=(128,)),
            ModelConfig(name="whisper_tiny", batch_buckets=(1, 4),
                        extra={"max_new_tokens": 64}),
            ModelConfig(name="gpt2", batch_buckets=(1, 4), seq_buckets=(64, 128),
                        extra={"max_new_tokens": 32,
                               "params_dtype": "bfloat16"}),
            # The dev sd15 is the TINY variant at 64x64 (seconds to compile,
            # works on the CPU backend): txt2img smoke for the async-job
            # path.  Real 512x512 SD-1.5 belongs in a prod profile with a
            # checkpoint (see README).
            ModelConfig(name="sd15", batch_buckets=(1,),
                        extra={"variant": "tiny", "num_steps": 4,
                               "height": 64, "width": 64}),
        ],
    )
    cfg.models = [m for m in cfg.models
                  if (m.builder or m.name) in registered]
    return cfg
