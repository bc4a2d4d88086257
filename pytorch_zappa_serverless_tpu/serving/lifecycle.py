"""Serverless model lifecycle: scale-to-zero, on-demand activation, HBM budget.

The paper's core claim is *serverless* TPU serving, yet until this module the
repro built every configured model at boot and kept it device-resident
forever.  INFaaS (ATC '21) shows model-less serving needs a residency manager
moving models between cold and warm states under a resource budget;
ServerlessLLM (OSDI '24) shows activation latency is the make-or-break
metric.  This manager implements both, per model:

    COLD ──ensure_active──▶ WARMING ──build/restore──▶ ACTIVE
      ▲                                                  │ idle_unload_s
      └───────────── demote ◀── DRAINING_IDLE ◀──────────┘

plus **PINNED** (never demoted, built at boot even under ``lazy_load``).
Orthogonally, each non-active model sits on a residency *tier* that prices
its re-activation:

- ``device`` — ACTIVE: params in HBM, executables warm.  Cost: zero.
- ``host`` — weights fetched to host RAM, device buffers freed, jit
  executables still cached in-process.  Cost: one ``device_put``.
- ``disk`` — weights in the streaming checkpoint store
  (serving/ckptstore.py; requires ``ckpt_store_dir``), host copy freed,
  jit executables still cached.  Cost: one streamed read→h2d pipeline —
  no recompile, no rebuild.
- ``none`` — compiled-cache-only: nothing in memory; re-activation is a full
  build whose compiles hit the persistent XLA cache (engine/cache.py).
  When the store holds the model's chunks, the rebuild STREAMS the weights
  on a background thread while the servable builds and warms (jit keys on
  avals, not values), overlapping load with compile.

Mechanisms:

- **Lazy activation** (``lazy_load`` global + per-model): the engine skips
  the model at boot; the first request (or job, or ``/admin`` action, or
  pin) triggers ONE single-flight activation — N concurrent cold requests
  share the same build task.
- **Deadline-aware cold admission**: a request whose deadline cannot cover
  ``estimate_warm_ms`` fast-fails 503 ``cold_start`` + ``Retry-After`` +
  ``estimated_warm_ms`` (the activation keeps warming in the background —
  demand IS the warmup signal); deadline-less requests block on the
  activation up to ``activation_max_wait_s``.  The estimate is learned from
  this process's activation history per tier, falling back to the model's
  CompileClock entries, falling back to a prior that a warm persistent
  compile cache quarters.
- **Scale-to-zero**: models idle past ``idle_unload_s`` demote device→host;
  after ``host_idle_drop_s`` more they drop to ``none``.  A model with
  in-flight work (handler window, batcher queue, generation slots, job
  backlog) is never demoted, and arrivals during DRAINING_IDLE re-activate
  through the normal single-flight path.
- **HBM budget**: while ``engine/runner.py``'s live resident-bytes
  accounting exceeds ``hbm_budget_bytes``, LRU non-PINNED idle models are
  demoted to the host tier.  ``host_budget_bytes`` mirrors it one rung
  down: while host-tier bytes exceed it, LRU host copies demote to the
  disk tier (or drop to ``none`` without a store).
- **Observability**: every activation is a trace
  (``activate`` → ``load_weights``/``compile``/``warmup`` spans) plus
  Prometheus ``tpuserve_residency_state``, ``tpuserve_activations_total
  {model,cause}``, ``tpuserve_activation_ms`` histograms and
  ``tpuserve_hbm_bytes{model}`` (serving/metrics.py).  ``faults.py`` rules
  with ``kind="activation"`` inject chaos into the build path.

docs/LIFECYCLE.md is the operator story; ``GET/POST /admin/models/{name}``
the admin surface.
"""

from __future__ import annotations

import asyncio
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

from ..config import ServeConfig
from ..utils.logging import get_logger, log_event
from .metrics import Histogram

log = get_logger("serving.lifecycle")

COLD = "cold"
WARMING = "warming"
ACTIVE = "active"
DRAINING_IDLE = "draining_idle"

# Numeric encoding for the tpuserve_residency_state gauge; PINNED reports as
# its own code so a dashboard can tell "active because demanded" from
# "active because pinned" at a glance.
STATE_CODE = {COLD: 0, WARMING: 1, ACTIVE: 2, DRAINING_IDLE: 3, "pinned": 4}

# Activation latencies span device_put milliseconds to multi-minute cold
# compiles; wider log-ish bounds than the request-latency histograms.
ACTIVATION_BUCKETS_MS = (50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
                         10000.0, 30000.0, 60000.0, 120000.0, 300000.0)


class ColdStart(Exception):
    """The model is not resident and the request cannot (or will not) wait.

    Maps to HTTP 503 with ``Retry-After`` and ``estimated_warm_ms`` so the
    client knows when the single-flight activation (already running in the
    background) should have it warm.
    """

    def __init__(self, msg: str, estimated_warm_ms: float,
                 retry_after_s: float):
        super().__init__(msg)
        self.estimated_warm_ms = estimated_warm_ms
        self.retry_after_s = retry_after_s


@dataclass
class ModelResidency:
    """Per-model lifecycle record: state, tier, LRU clock, learned costs."""

    name: str
    # All residency fields are event-loop-confined: the manager (and the
    # server handlers) mutate them from the loop only; ``lock`` below
    # additionally serializes multi-step transitions, not thread access.
    state: str = COLD               # guarded-by: event-loop
    tier: str = "none"              # guarded-by: event-loop
    pinned: bool = False            # guarded-by: event-loop
    last_used: float = 0.0          # guarded-by: event-loop
    activations: int = 0            # guarded-by: event-loop
    last_activation_ms: float | None = None  # guarded-by: event-loop
    cold_fast_fails: int = 0        # guarded-by: event-loop
    # load_ms/compile_ms split of the last activation (on the model's row
    # in /admin/models); fake build_fns never set it.
    last_activation_phases: dict | None = None  # guarded-by: event-loop
    # Requests currently inside a handler for this model (the server's
    # enter/exit guard): the in-flight floor the demotion path respects even
    # before work reaches a queue.
    inflight: int = 0
    # Retained CompiledModel shell for the host AND disk tiers (host: params
    # on host RAM; disk: params in the ckpt store, shell keeps the cached
    # jit executables) awaiting restore.
    cm_host: Any = None
    # Recent activation wall-ms keyed by the tier activated FROM — the
    # learned half of estimate_warm_ms.
    history: dict[str, deque] = field(default_factory=dict)
    # Serializes activate/demote transitions for this model.
    lock: asyncio.Lock = field(default_factory=asyncio.Lock)

    def note_activation(self, from_tier: str, ms: float):
        self.activations += 1
        self.last_activation_ms = round(ms, 3)
        self.history.setdefault(from_tier, deque(maxlen=8)).append(ms)


class LifecycleManager:
    """The per-server residency manager (one instance, started at startup).

    ``build_fn(name, from_tier, host_cm, span) -> CompiledModel`` is the
    blocking activation body (runs in the default executor); tests inject a
    fake.  ``clock`` is the idle/LRU clock (monotonic seconds), injectable
    so idle-unload tests don't sleep.
    """

    def __init__(self, server, cfg: ServeConfig, *,
                 build_fn: Callable | None = None,
                 clock: Callable[[], float] = time.monotonic,
                 store: Any = None):
        self.server = server
        self.cfg = cfg
        self.clock = clock
        self._build_fn = build_fn or self._default_build
        # Streaming checkpoint store (serving/ckptstore.py): the disk tier
        # and the stream-while-compile cold path.  None (no ckpt_store_dir)
        # keeps the pre-store ladder: device → host → none.
        self.store = store if store is not None \
            else getattr(server, "ckpt_store", None)
        # load/compile phase split handed from the executor-thread build
        # (writes) to _activate on the event loop (pop).
        self._phases_lock = threading.Lock()
        self._build_phases: dict[str, dict] = {}  # guarded-by: _phases_lock
        self._models: dict[str, ModelResidency] = {}  # guarded-by: event-loop
        self._activating: dict[str, asyncio.Task] = {}  # guarded-by: event-loop
        self._activation_started: dict[str, float] = {}  # guarded-by: event-loop
        self.activation_hists: dict[str, Histogram] = {}  # guarded-by: event-loop
        self.activations_by_cause: dict[str, dict[str, int]] = {}  # guarded-by: event-loop
        self.demotions_by_cause: dict[str, dict[str, int]] = {}  # guarded-by: event-loop
        self._task: asyncio.Task | None = None  # guarded-by: event-loop
        self._over_budget_warned = False  # guarded-by: event-loop
        # Learned keep-warm window supplier (serving/autoscale.py;
        # docs/AUTOSCALE.md): ``fn(model) -> seconds | None``.  When wired
        # and the key has enough history, the reaper holds the model warm
        # for the learned window instead of the fixed ``idle_unload_s``;
        # None (thin history, plane off/degraded) falls back to the timer.
        self.keepwarm_fn: Callable | None = None  # guarded-by: event-loop
        now = self.clock()
        engine = server.engine
        for mc in cfg.models:
            res = self._models[mc.name] = ModelResidency(
                name=mc.name, pinned=mc.pinned, last_used=now)
            if engine is not None and mc.name in engine.models:
                res.state, res.tier = ACTIVE, "device"
                boot_s = engine.build_seconds.get(mc.name)
                if boot_s:
                    self._record_activation(mc.name, "boot", boot_s * 1000.0,
                                            "none")

    # -- plumbing ------------------------------------------------------------
    def start(self):
        if self._task is None and (self.cfg.idle_unload_s > 0
                                   or self.cfg.hbm_budget_bytes > 0
                                   or self.cfg.host_budget_bytes > 0):
            self._task = asyncio.get_running_loop().create_task(
                self._loop(), name="lifecycle")
        return self

    async def stop(self):
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    @property
    def names(self):
        return self._models.keys()

    def knows(self, name: str) -> bool:
        return name in self._models

    def residency(self, name: str) -> ModelResidency | None:
        return self._models.get(name)

    def state_of(self, name: str) -> str | None:
        res = self._models.get(name)
        return res.state if res is not None else None

    def note_use(self, name: str):
        """Touch the LRU clock (every work-surface request/submit)."""
        res = self._models.get(name)
        if res is not None:
            res.last_used = self.clock()

    def enter(self, name: str):
        """Open the handler in-flight window — demotion waits it out."""
        res = self._models.get(name)
        if res is not None:
            res.inflight += 1
            res.last_used = self.clock()

    def exit(self, name: str):
        res = self._models.get(name)
        if res is not None:
            res.inflight -= 1
            res.last_used = self.clock()

    def _busy(self, name: str) -> bool:
        """In-flight work anywhere for this model — the never-evict gate."""
        res = self._models[name]
        if res.inflight > 0:
            return True
        srv = self.server
        b = srv.batchers.get(name)
        if b is not None and (b.queue_depth or b.in_flight):
            return True
        s = srv.schedulers.get(name)
        if s is not None and (s.active or s.depth):
            return True
        jobs = getattr(srv, "jobs", None)
        if jobs is not None and jobs.depths.get(name):
            return True
        return False

    # -- activation cost model ----------------------------------------------
    def _cache_warm(self) -> bool:
        """Does the persistent compile cache plausibly cover this model set?
        (Any entries at all — the cache is keyed by HLO, so a populated dir
        means re-compiles are deserializes, not builds.)"""
        from ..engine.cache import resolve_compile_cache_dir

        try:
            d = Path(resolve_compile_cache_dir(self.cfg.compile_cache_dir))
            return d.is_dir() and any(d.iterdir())
        except OSError:
            return False

    def estimate_warm_ms(self, name: str) -> float:
        """Expected activation wall-ms from the model's CURRENT tier.

        Learned history per tier first; else the model's CompileClock
        entries from this process (a rebuilt model re-pays roughly its
        compile time against the warm cache); else the configured prior,
        quartered when the persistent compile cache is already populated.
        """
        res = self._models[name]
        tier = res.tier if res.tier in ("host", "disk", "none") else "none"
        hist = res.history.get(tier)
        if hist:
            ordered = sorted(hist)
            return float(ordered[len(ordered) // 2])
        if tier == "host":
            return 250.0  # one device_put; refined by the first observation
        if tier == "disk":
            # One streamed read→h2d, zero recompiles; a few device_puts'
            # worth until the first observation refines it.
            return 1000.0
        engine = self.server.engine
        if engine is not None:
            per = engine.clock.per_model().get(name)
            if per and per["seconds"]:
                return per["seconds"] * 1000.0 + 500.0
        est = float(self.cfg.activation_estimate_ms)
        return est / 4.0 if self._cache_warm() else est

    def _retry_after_s(self, name: str, est_ms: float) -> float:
        """Seconds until the in-flight (or about-to-run) activation should
        have the model warm."""
        started = self._activation_started.get(name)
        elapsed = (self.clock() - started) if started is not None else 0.0
        return max(est_ms / 1000.0 - elapsed, 1.0)

    # -- activation ----------------------------------------------------------
    async def ensure_active(self, name: str, *, deadline_ms: float | None = None,
                            cause: str = "request", wait: bool = True):
        """Admission: return the ACTIVE CompiledModel, activating on demand.

        Single-flight: concurrent callers share one activation task.  With a
        deadline the call either blocks within it (estimate fits) or raises
        :class:`ColdStart` (the activation continues in the background);
        without one it blocks up to ``activation_max_wait_s``.
        """
        res = self._models[name]  # KeyError = caller's 404
        res.last_used = self.clock()
        engine = self.server.engine
        if res.state == ACTIVE and name in engine.models:
            return engine.models[name]
        task = self._activating.get(name)
        if task is None or task.done():
            task = asyncio.get_running_loop().create_task(
                self._activate(name, cause), name=f"activate-{name}")
            # Fast-fail admitters never await this task; retrieve the
            # exception so an activation failure doesn't warn as unretrieved.
            task.add_done_callback(
                lambda t: t.exception() if not t.cancelled() else None)
            self._activating[name] = task
        est = self.estimate_warm_ms(name)
        if deadline_ms is not None and est > deadline_ms:
            res.cold_fast_fails += 1
            raise ColdStart(
                f"model {name!r} is {res.state} (activation estimated "
                f"{est:.0f} ms exceeds the {deadline_ms:.0f} ms deadline); "
                f"warming in the background",
                estimated_warm_ms=est,
                retry_after_s=self._retry_after_s(name, est))
        wait_s = (deadline_ms / 1000.0 if deadline_ms is not None
                  else self.cfg.activation_max_wait_s)
        if not wait or wait_s <= 0:
            res.cold_fast_fails += 1
            raise ColdStart(
                f"model {name!r} is {res.state}; warming in the background",
                estimated_warm_ms=est,
                retry_after_s=self._retry_after_s(name, est))
        try:
            await asyncio.wait_for(asyncio.shield(task), timeout=wait_s)
        except (asyncio.TimeoutError, TimeoutError):
            res.cold_fast_fails += 1
            est = self.estimate_warm_ms(name)
            raise ColdStart(
                f"model {name!r} still {res.state} after waiting "
                f"{wait_s:.1f} s for activation",
                estimated_warm_ms=est,
                retry_after_s=self._retry_after_s(name, max(est, 1000.0))
            ) from None
        return self.server.engine.model(name)

    async def _activate(self, name: str, cause: str):
        """The single-flight activation body: WARMING → build → ACTIVE."""
        res = self._models[name]
        loop = asyncio.get_running_loop()
        async with res.lock:  # waits out an in-progress demotion
            if res.state == ACTIVE and name in self.server.engine.models:
                self._activating.pop(name, None)
                return
            self._activation_started[name] = self.clock()
            from_tier = res.tier if res.tier in ("host", "disk") else "none"
            res.state = WARMING
            tracer = getattr(self.server, "tracer", None)
            root = (tracer.start("activate", model=name, cause=cause,
                                 tier=from_tier)
                    if tracer is not None else None)
            t0 = time.perf_counter()
            try:
                cm = await loop.run_in_executor(
                    None, self._build_fn, name, from_tier, res.cm_host, root)
            except BaseException as e:
                res.state = COLD
                self._activating.pop(name, None)
                self._activation_started.pop(name, None)
                with self._phases_lock:
                    self._build_phases.pop(name, None)
                if root is not None:
                    root.annotate(error=f"{type(e).__name__}: {e}")
                    root.end(status="error")
                    tracer.finish(root.trace, "error")
                log_event(log, "activation failed", model=name, cause=cause,
                          error=f"{type(e).__name__}: {e}")
                raise
            ms = (time.perf_counter() - t0) * 1000.0
            engine = self.server.engine
            engine.attach(name, cm)
            res.cm_host = None
            res.tier = "device"
            with self._phases_lock:
                phases = self._build_phases.pop(name, None)
            res.last_activation_phases = (
                {k: (round(v, 3) if isinstance(v, float) else v)
                 for k, v in phases.items()} if phases else None)
            self.server._start_model_lanes(name)
            res.state = ACTIVE
            res.last_used = self.clock()
            self._record_activation(name, cause, ms, from_tier)
            self._activating.pop(name, None)
            self._activation_started.pop(name, None)
            if root is not None:
                root.end()
                tracer.finish(root.trace, "ok")
            log_event(log, "model activated", model=name, cause=cause,
                      tier_from=from_tier, ms=round(ms, 1),
                      hbm_bytes=engine.runner.resident_bytes().get(name))
        await self.enforce_budget(exclude=name)
        # Device evictions above land on the host tier; cascade the rung
        # below so a budget squeeze walks the full ladder.
        await self.enforce_host_budget()

    def _default_build(self, name: str, from_tier: str, host_cm, root):
        """Blocking activation body (executor thread): restore or build.

        Spans mirror the issue's ladder: ``load_weights`` (builder / host
        restore / disk stream), ``compile`` (first-bucket warm), ``warmup``
        (remaining buckets + chunked programs).  The ``kind="activation"``
        chaos hook fires first — a failed activation leaves the model COLD.
        A broken disk stream (torn chunks past the re-read, missing
        manifest) degrades to the legacy whole-file build — never a dead
        activation.  Fills ``_build_phases[name]`` with the
        ``load_ms``/``compile_ms`` attribution the activation record
        reports.
        """
        server = self.server
        server.engine.runner.faults.on_activation(name)
        phases: dict[str, Any] = {"tier": from_tier}
        if from_tier == "host" and host_cm is not None:
            sp = root.child("load_weights", tier="host") if root else None
            t0 = time.perf_counter()
            host_cm.device_restore()
            phases["load_ms"] = (time.perf_counter() - t0) * 1000.0
            phases["compile_ms"] = 0.0
            with self._phases_lock:
                self._build_phases[name] = phases
            if sp is not None:
                sp.end()
            return host_cm
        store = self.store
        stream_failed = False  # a broken stream this activation stays broken
        if from_tier == "disk" and host_cm is not None and store is not None:
            import jax

            sp = root.child("load_weights", tier="disk") if root else None
            try:
                t0 = time.perf_counter()
                host_cm.disk_restore(
                    lambda: store.load(name, place_fn=jax.device_put)[0])
                phases["load_ms"] = (time.perf_counter() - t0) * 1000.0
                phases["compile_ms"] = 0.0
                phases["streamed"] = True
                with self._phases_lock:
                    self._build_phases[name] = phases
                if sp is not None:
                    sp.end()
                return host_cm
            except Exception as e:
                # Degrade to the legacy whole-file rebuild below — and
                # remember the stream is broken, so the rebuild's
                # stream-while-compile thread doesn't retry the same
                # broken store and double-count the degrade.
                stream_failed = True
                store.note_degraded()
                if sp is not None:
                    sp.annotate(error=f"{type(e).__name__}: {e}")
                    sp.end(status="error")
                log_event(log, "disk-tier stream failed; degrading to "
                          "full rebuild", model=name,
                          error=f"{type(e).__name__}: {e}")
                phases = {"tier": from_tier}
        from ..engine.loader import build_model

        from .ckptstore import checkpoint_fingerprint

        mc = self.cfg.model(name)
        clock = server.engine.clock
        mesh = server.engine.mesh
        # Source-checkpoint identity: a manifest staged from an OLDER
        # checkpoint file must read as a miss (stream skipped, store
        # re-seeded), or a restart after a checkpoint swap would stream
        # stale weights over the fresh build.
        ckpt_fp = checkpoint_fingerprint(getattr(mc, "checkpoint", None))

        # Stream-while-compile (docs/LIFECYCLE.md): when the store already
        # holds this model's chunks, the real weights stream on a
        # background thread while the servable builds AND the buckets warm
        # — jit executables key on avals, not values, so the builder's own
        # weights carry the compile and the streamed tree (identical
        # shapes) swaps in before the model serves.  A broken stream keeps
        # the legacy-built weights: the whole-file path already ran.
        stream_th = None
        stream_box: list = []
        if store is not None and mesh is None and not stream_failed \
                and store.has(name, fingerprint=ckpt_fp):
            import jax
            import threading

            def _pull():
                t = time.perf_counter()
                try:
                    params = store.load(name, place_fn=jax.device_put)[0]
                    stream_box.append(
                        ("ok", params, (time.perf_counter() - t) * 1000.0))
                except Exception as e:
                    stream_box.append(("err", e, 0.0))

            stream_th = threading.Thread(
                target=_pull, name=f"ckpt-stream-{name}", daemon=True)
            stream_th.start()

        sp = root.child("load_weights",
                        **({"tier": "stream"} if stream_th else {})) \
            if root else None
        t0 = time.perf_counter()
        cm = build_model(mc, clock, mesh, warmup=False)
        phases["load_ms"] = (time.perf_counter() - t0) * 1000.0
        if sp is not None:
            sp.end()
        t1 = time.perf_counter()
        if self.cfg.warmup_at_boot:
            sp = root.child("compile") if root else None
            cm._warm_bucket(cm.buckets[0])
            if sp is not None:
                sp.end()
            sp = root.child("warmup") if root else None
            cm.warmup()  # remaining buckets + chunked programs
            if sp is not None:
                sp.end()
        phases["compile_ms"] = (time.perf_counter() - t1) * 1000.0
        if stream_th is not None:
            stream_th.join()
            status, payload, stream_ms = stream_box[0]
            if status == "ok":
                cm.servable.params = payload
                # The stream ran concurrently with build+compile above, so
                # load_ms + compile_ms can exceed the activation wall
                # clock; that overlap IS the win of streaming.
                phases["load_ms"] = stream_ms
                phases["streamed"] = True
            else:
                store.note_degraded()
                phases["streamed"] = False
                log_event(log, "param stream failed; serving legacy-built "
                          "weights", model=name,
                          error=f"{type(payload).__name__}: {payload}")
        with self._phases_lock:
            self._build_phases[name] = phases
        if store is not None and mesh is None \
                and not store.has(name, fingerprint=ckpt_fp) \
                and self._can_host_tier(cm):
            # Write-once staging: the first cold build seeds the store so
            # every later activation of this model (and every byte-identical
            # sibling chunk across its variants) streams.  A stale-
            # fingerprint manifest (checkpoint swapped under the store)
            # lands here too and is re-staged from the fresh build.
            try:
                import jax

                store.put(name, jax.device_get(cm.servable.params),
                          fingerprint=ckpt_fp)
            except Exception:
                log.exception("seeding ckpt store for %s failed; streaming "
                              "stays off for this model", name)
        return cm

    def _record_activation(self, name: str, cause: str, ms: float,
                           from_tier: str):
        res = self._models[name]
        res.note_activation(from_tier, ms)
        self.activations_by_cause.setdefault(name, {})
        self.activations_by_cause[name][cause] = \
            self.activations_by_cause[name].get(cause, 0) + 1
        hist = self.activation_hists.get(name)
        if hist is None:
            hist = self.activation_hists[name] = Histogram(
                ACTIVATION_BUCKETS_MS)
        hist.observe(ms)

    # -- demotion / scale-to-zero -------------------------------------------
    def _can_host_tier(self, cm) -> bool:
        """Host tiering is single-device only (mesh placement / lockstep
        mirrors cannot be re-established by a bare device_put)."""
        return (getattr(cm, "mesh", None) is None
                and getattr(cm, "lockstep", None) is None)

    def _disk_save_fn(self, name: str):
        """The store hand-off :meth:`CompiledModel.disk_offload` calls with
        the host-fetched tree (write-once: an already-seeded manifest makes
        this a pure hash pass with zero chunk writes).  Records the source
        checkpoint's fingerprint so a later restart can tell these chunks
        from a swapped checkpoint's."""
        from .ckptstore import checkpoint_fingerprint

        store = self.store
        try:
            mc = self.cfg.model(name)
        except Exception:
            mc = None
        fp = checkpoint_fingerprint(getattr(mc, "checkpoint", None))
        return lambda params: store.put(name, params, fingerprint=fp)

    async def demote(self, name: str, *, to: str = "host",
                     cause: str = "idle") -> bool:
        """ACTIVE → DRAINING_IDLE → COLD (tier ``host``, ``disk`` or
        ``none``), or down the cold ladder host → disk → ``none``.
        Refuses (False) for pinned or busy models — the never-evict
        contract the budget loops and tests rely on.  ``to="disk"``
        requires the checkpoint store; without one it lands on the next
        rung that exists (host stays host, drops go to ``none``)."""
        res = self._models.get(name)
        if res is None:
            return False
        async with res.lock:
            if res.pinned:
                return False
            loop = asyncio.get_running_loop()
            if res.state == ACTIVE:
                if self._busy(name):
                    return False
                res.state = DRAINING_IDLE
                engine = self.server.engine
                cm = engine.detach(name)
                # Lanes are quiet (the busy gate above) — stopping them now
                # routes new arrivals through ensure_active, which serializes
                # on res.lock behind this demotion.
                await self.server._stop_model_lanes(name)
                tierable = cm is not None and self._can_host_tier(cm)
                if tierable and to == "host":
                    await loop.run_in_executor(None, cm.host_offload)
                    res.cm_host, res.tier = cm, "host"
                elif tierable and to == "disk" and self.store is not None:
                    try:
                        await loop.run_in_executor(
                            None, cm.disk_offload, self._disk_save_fn(name))
                        res.cm_host, res.tier = cm, "disk"
                    except Exception as e:
                        # A full/broken disk must not strand the model in
                        # DRAINING_IDLE with the CompiledModel dropped:
                        # disk_offload releases the params only AFTER
                        # save_fn returns, so the tree is still on the
                        # shell — land on the host rung instead.
                        await loop.run_in_executor(None, cm.host_offload)
                        res.cm_host, res.tier = cm, "host"
                        log_event(log, "disk offload failed; landing on "
                                  "host tier", model=name,
                                  error=f"{type(e).__name__}: {e}")
                else:
                    res.cm_host, res.tier = None, "none"
                res.state = COLD
                self._record_demotion(name, cause)
                log_event(log, "model demoted", model=name, cause=cause,
                          tier=res.tier)
                return True
            if res.state == COLD and res.tier == "host" and to == "disk" \
                    and self.store is not None and res.cm_host is not None:
                try:
                    await loop.run_in_executor(
                        None, res.cm_host.disk_offload,
                        self._disk_save_fn(name))
                except Exception as e:
                    # Host copy untouched (disk_offload drops it only
                    # after the store write succeeds) — stay on host.
                    log_event(log, "disk offload failed; staying on host "
                              "tier", model=name,
                              error=f"{type(e).__name__}: {e}")
                    return False
                res.tier = "disk"
                self._record_demotion(name, cause)
                log_event(log, "model demoted to disk tier", model=name,
                          cause=cause)
                return True
            if res.state == COLD and res.tier in ("host", "disk") \
                    and to == "none":
                res.cm_host, res.tier = None, "none"
                self._record_demotion(name, cause)
                log_event(log, "model dropped to compiled-cache-only",
                          model=name, cause=cause)
                return True
            return False

    async def unload(self, name: str, cause: str = "admin") -> bool:
        """Explicit scale-to-zero: all the way to compiled-cache-only."""
        res = self._models.get(name)
        if res is None:
            return False
        if res.state == ACTIVE:
            return await self.demote(name, to="none", cause=cause)
        if res.tier in ("host", "disk"):
            return await self.demote(name, to="none", cause=cause)
        return res.state == COLD  # already unloaded counts as success

    def _record_demotion(self, name: str, cause: str):
        self.demotions_by_cause.setdefault(name, {})
        self.demotions_by_cause[name][cause] = \
            self.demotions_by_cause[name].get(cause, 0) + 1

    async def pin(self, name: str):
        """PINNED: activate if needed and exempt from every demotion path."""
        res = self._models[name]
        res.pinned = True
        if res.state != ACTIVE:
            await self.ensure_active(name, cause="pin")

    def unpin(self, name: str):
        self._models[name].pinned = False

    # -- reaper --------------------------------------------------------------
    def _tick_interval(self) -> float:
        if self.cfg.lifecycle_tick_s > 0:
            return self.cfg.lifecycle_tick_s
        if self.cfg.idle_unload_s > 0:
            return min(max(self.cfg.idle_unload_s / 4.0, 0.05), 5.0)
        return 1.0

    def _host_drop_s(self) -> float:
        if self.cfg.host_idle_drop_s > 0:
            return self.cfg.host_idle_drop_s
        return 4.0 * self.cfg.idle_unload_s if self.cfg.idle_unload_s > 0 \
            else float("inf")

    async def _loop(self):
        while True:
            await asyncio.sleep(self._tick_interval())
            try:
                await self.tick_once()
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("lifecycle tick failed; next interval retries")

    def idle_window_s(self, name: str) -> float:
        """The demotion window for one model: the autoscaler's learned
        keep-warm window when available (docs/AUTOSCALE.md), else the fixed
        ``idle_unload_s`` timer — the pre-autoscale behavior, and the
        fallback whenever history is thin or the plane degraded."""
        idle = self.cfg.idle_unload_s
        if self.keepwarm_fn is None:
            return idle
        try:
            learned = self.keepwarm_fn(name)
        except Exception:
            log.exception("keepwarm window lookup failed for %s", name)
            return idle
        return float(learned) if learned is not None else idle

    async def tick_once(self):
        """One reaper pass: idle demotions, host-tier drops, budgets."""
        now = self.clock()
        if self.cfg.idle_unload_s > 0:
            # Host-tier retention AFTER the device demotion fires: with the
            # fixed timer this reproduces host_idle_drop_s exactly; with a
            # learned window it shifts out by the same amount, so a long
            # keep-warm window never skips the host tier.
            retention = max(self._host_drop_s() - self.cfg.idle_unload_s,
                            0.0)
            for name, res in list(self._models.items()):
                if res.pinned:
                    continue
                idle = self.idle_window_s(name)
                if (res.state == ACTIVE and now - res.last_used >= idle
                        and not self._busy(name)):
                    await self.demote(name, to="host", cause="idle")
                elif (res.state == COLD and res.tier == "host"
                      and now - res.last_used >= idle + retention):
                    # With a store the cold ladder lands on disk (cheap to
                    # keep, cheap to restream); without one this is the
                    # pre-store drop to compiled-cache-only.
                    await self.demote(
                        name, cause="idle",
                        to="disk" if self.store is not None else "none")
        await self.enforce_budget()
        await self.enforce_host_budget()

    async def enforce_budget(self, exclude: str | None = None):
        """Demote LRU-first until device-resident bytes fit the budget.

        ``exclude`` protects a just-activated model from evicting itself to
        make room for... itself.  PINNED and busy models never evict; if
        only those remain the budget stays exceeded (logged once) — serving
        live work always wins over the budget.
        """
        budget = self.cfg.hbm_budget_bytes
        if budget <= 0:
            return
        while True:
            resident = self.server.engine.runner.resident_bytes()
            total = sum(resident.values())
            if total <= budget:
                self._over_budget_warned = False
                return
            victims = sorted(
                (res.last_used, name)
                for name, res in self._models.items()
                if name in resident and res.state == ACTIVE
                and not res.pinned and name != exclude
                and not self._busy(name))
            evicted = False
            for _, name in victims:
                if await self.demote(name, to="host", cause="budget"):
                    evicted = True
                    break
            if not evicted:
                if not self._over_budget_warned:
                    self._over_budget_warned = True
                    log.warning(
                        "HBM budget exceeded (%d > %d bytes) with no "
                        "evictable model (all pinned/busy)", total, budget)
                return

    def host_bytes(self) -> dict[str, int]:
        """Per-model host-tier resident bytes (the host-budget ledger)."""
        return {name: int(res.cm_host.param_nbytes())
                for name, res in self._models.items()
                if res.tier == "host" and res.cm_host is not None}

    async def enforce_host_budget(self):
        """The ``hbm_budget_bytes`` loop one rung down: while host-tier
        bytes exceed ``host_budget_bytes``, LRU host copies demote to the
        disk tier (or drop to ``none`` without a store).  PINNED models
        never demote; host-tier models are never busy (they are COLD)."""
        budget = self.cfg.host_budget_bytes
        if budget <= 0:
            return
        to = "disk" if self.store is not None else "none"
        while True:
            held = self.host_bytes()
            if sum(held.values()) <= budget:
                return
            victims = sorted(
                (res.last_used, name)
                for name, res in self._models.items()
                if name in held and not res.pinned)
            evicted = False
            for _, name in victims:
                if await self.demote(name, to=to, cause="host_budget"):
                    evicted = True
                    break
            if not evicted:
                return

    # -- engine-rebuild integration (serving/watchdog.py) --------------------
    def rebind(self, cause: str = "recovery"):
        """Re-sync residency after an engine swap (watchdog recovery or
        ``/admin/reload``): the rebuild IS a lifecycle transition — every
        model in the fresh engine re-activated (counted under ``cause``),
        every lazy model back to COLD.  Host-tier copies survive (host
        arrays are runner-independent; restore device_puts onto the new
        runner)."""
        engine = self.server.engine
        now = self.clock()
        for name, res in self._models.items():
            if name in engine.models:
                was_cold = res.state != ACTIVE
                res.state, res.tier = ACTIVE, "device"
                res.cm_host = None
                res.last_used = now
                ms = (engine.build_seconds.get(name) or 0.0) * 1000.0
                self._record_activation(name, cause, ms, "none")
                if was_cold:
                    log_event(log, "model re-activated by rebuild",
                              model=name, cause=cause)
            else:
                if res.tier == "device":
                    res.tier = "none"
                if res.state in (ACTIVE, WARMING, DRAINING_IDLE):
                    res.state = COLD

    # -- introspection -------------------------------------------------------
    def model_snapshot(self, name: str) -> dict | None:
        res = self._models.get(name)
        if res is None:
            return None
        now = self.clock()
        quarantined = getattr(self.server.resilience, "quarantined", set())
        try:
            mc = self.cfg.model(name)
            family, quality = (mc.family or mc.name), mc.quality_rank
        except KeyError:
            family, quality = name, 0
        adapters = getattr(self.server, "adapters", None)
        store = self.store
        hbm = (self.server.engine.runner.resident_bytes().get(name, 0)
               if self.server.engine is not None else 0)
        host_b = (int(res.cm_host.param_nbytes())
                  if res.tier == "host" and res.cm_host is not None else 0)
        disk_b = store.manifest_nbytes(name) if store is not None else 0
        # The model's weight footprint wherever it currently lives: HBM
        # when ACTIVE, host RAM on the host tier, store bytes on disk/cold.
        param_nbytes = hbm if res.state == ACTIVE else (host_b or disk_b)
        return {
            "state": res.state,
            # Variant-family identity (docs/VARIANTS.md): the fleet router
            # polls this to route family-addressed requests to whichever
            # replica has ANY rung of the ladder warm.
            "family": family,
            # Per-tenant adapter residency (docs/ADAPTERS.md): the fleet
            # router treats an ACTIVE adapter as a routing signal — send
            # the tenant where their slot is already warm.
            **({"adapters": adapters.residency_of(name)}
               if adapters is not None and adapters.names_for(name)
               else {}),
            "quality_rank": quality,
            "tier": res.tier if res.state != ACTIVE else "device",
            "pinned": res.pinned,
            "quarantined": name in quarantined,
            "last_used_s_ago": round(max(now - res.last_used, 0.0), 3),
            "inflight": res.inflight,
            "activations": res.activations,
            "activations_by_cause": dict(
                self.activations_by_cause.get(name, {})),
            "demotions_by_cause": dict(self.demotions_by_cause.get(name, {})),
            "last_activation_ms": res.last_activation_ms,
            "last_activation_phases": res.last_activation_phases,
            "estimated_warm_ms": round(self.estimate_warm_ms(name), 1),
            "cold_fast_fails": res.cold_fast_fails,
            "hbm_bytes": hbm,
            "param_nbytes": param_nbytes,
            "host_bytes": host_b,
            "disk_bytes": disk_b,
        }

    def snapshot(self) -> dict:
        resident = (self.server.engine.runner.resident_bytes()
                    if self.server.engine is not None else {})
        held = self.host_bytes()
        return {
            "lazy_load": self.cfg.lazy_load,
            "idle_unload_s": self.cfg.idle_unload_s,
            "hbm_budget_bytes": self.cfg.hbm_budget_bytes,
            "hbm_bytes_total": sum(resident.values()),
            "host_budget_bytes": self.cfg.host_budget_bytes,
            "host_bytes_total": sum(held.values()),
            **({"ckpt_store": self.store.snapshot()}
               if self.store is not None else {}),
            "models": {name: self.model_snapshot(name)
                       for name in sorted(self._models)},
        }

    def state_code(self, name: str) -> int:
        """The tpuserve_residency_state gauge value (PINNED wins)."""
        res = self._models[name]
        if res.pinned:
            return STATE_CODE["pinned"]
        return STATE_CODE[res.state]
