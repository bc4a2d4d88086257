"""Device runner: the single dispatch lane to the TPU, with two QoS levels.

The reference is synchronous — one Lambda invocation, one CPU forward
(SURVEY §1).  Here many concurrent HTTP requests funnel into batches, and all
device work goes through ONE dispatch thread: the batcher's asyncio loop stays
free, and there is no shared mutable state across threads (the race-safety
story, SURVEY §5 "Race detection" — concurrency stays structured instead of
sanitized after the fact).  JAX's own dispatch is async; the worker blocks on
host transfer of results, which serializes device occupancy per model the way
a serving queue should.

QoS (docs/QOS.md): the lane is a TWO-LEVEL priority queue.  Every dispatch
carries its model's latency class ("latency" | "throughput",
utils/registry.py / ModelConfig.latency_class); a queued latency dispatch
always pops before queued throughput work.  TPU programs are uninterruptible,
so priority acts BETWEEN device calls — which is why throughput models with
long programs expose chunked kernels (``run_chunked``): sd15's 20-step denoise
becomes K short dispatches with the lane released between them, bounding how
long a <30 ms resnet/bert request can sit behind an in-flight image to one
chunk instead of the whole program.  Per-lane queue depth and wait time are
exported on /metrics.
"""

from __future__ import annotations

import asyncio
import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FuturesTimeout
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.profiler

from ..faults import FaultInjector
from ..utils.logging import get_logger, log_event
from .compiled import CompiledModel

log = get_logger("engine.runner")

# The two dispatch lanes; must mirror utils/registry.LATENCY_CLASSES (kept as
# plain strings here to avoid an import cycle through the model zoo).
LANE_LATENCY = "latency"
LANE_THROUGHPUT = "throughput"
LANES = (LANE_LATENCY, LANE_THROUGHPUT)


class _DaemonDispatchPool:
    """Single DAEMON dispatch thread over a two-level priority queue.

    Not a ThreadPoolExecutor: its workers are non-daemon and the interpreter
    joins them at exit, so a dispatch wedged inside a device call — e.g. a
    multi-host collective whose peer died (parallel/lockstep.py) — would hang
    process shutdown forever.  A daemon thread lets shutdown timeouts mean
    what they say: log, give up on the wedged call, exit.

    ``submit``/``submit_lane`` return ``concurrent.futures.Future`` so both
    ``asyncio.wrap_future`` and blocking ``.result(timeout=...)`` callers
    work.  ``submit`` (the Executor-compatible entry health probes use)
    routes to the latency lane — a liveness check must never sit behind a
    throughput backlog.  With ``priority_enabled`` False the pop order is
    strict cross-lane FIFO by enqueue sequence (the pre-QoS behavior, the
    comparison point for head-of-line blocking).
    """

    def __init__(self, thread_name: str = "tpu-dispatch"):
        # One Condition guards the lanes, the stats, and the down flag; the
        # dispatch thread holds it only to pop, never across a device call.
        self._cv = threading.Condition()
        self._lanes: dict[str, deque] = {lane: deque() for lane in LANES}  # guarded-by: _cv
        self._seq = itertools.count()  # guarded-by: _cv
        self._down = False  # guarded-by: _cv
        self._priority = True  # guarded-by: _cv
        self._stats = {lane: {"dispatches": 0, "wait_ms_total": 0.0,
                              "wait_ms_max": 0.0} for lane in LANES}  # guarded-by: _cv
        self._thread = threading.Thread(target=self._loop, name=thread_name,
                                        daemon=True)
        self._thread.start()

    def submit(self, fn, *args, **kwargs) -> Future:
        return self.submit_lane(LANE_LATENCY, fn, *args, **kwargs)

    def submit_lane(self, lane: str, fn, *args, **kwargs) -> Future:
        # Locked against shutdown(): an item enqueued after the down flag
        # would never run and its Future would hang a caller forever.
        with self._cv:
            if self._down:
                raise RuntimeError("dispatch pool is shut down")
            f: Future = Future()
            self._lanes[lane].append(
                (next(self._seq), time.perf_counter(), f, fn, args, kwargs))
            self._cv.notify()
            return f

    def set_priority(self, enabled: bool) -> None:
        """Toggle two-level vs FIFO pop order.  Under the cv: the flag is
        read by ``_pop`` on the dispatch thread, and an unguarded write was
        the race detector's first real finding (ISSUE 8) — benign on
        CPython today, but the annotation contract is the point."""
        with self._cv:
            self._priority = bool(enabled)

    @property
    def priority_enabled(self) -> bool:
        with self._cv:
            return self._priority

    @priority_enabled.setter
    def priority_enabled(self, enabled: bool) -> None:
        # Pre-ISSUE-8 callers assigned the flag directly; keep that surface
        # but route it through the guarded write.
        self.set_priority(enabled)

    def _pop(self):
        """Next (lane, item) under the cv lock; caller guarantees non-empty."""
        hi, lo = self._lanes[LANE_LATENCY], self._lanes[LANE_THROUGHPUT]
        if self._priority:
            lane = LANE_LATENCY if hi else LANE_THROUGHPUT
        elif hi and lo:
            # FIFO mode: strict arrival order across lanes (seq is the global
            # enqueue counter).
            lane = LANE_LATENCY if hi[0][0] < lo[0][0] else LANE_THROUGHPUT
        else:
            lane = LANE_LATENCY if hi else LANE_THROUGHPUT
        return lane, self._lanes[lane].popleft()

    def _loop(self):
        while True:
            with self._cv:
                while not any(self._lanes.values()) and not self._down:
                    self._cv.wait()
                if not any(self._lanes.values()):
                    return  # down and drained
                lane, (_, t_enq, f, fn, args, kwargs) = self._pop()
                st = self._stats[lane]
                wait_ms = (time.perf_counter() - t_enq) * 1000.0
                st["dispatches"] += 1
                st["wait_ms_total"] += wait_ms
                st["wait_ms_max"] = max(st["wait_ms_max"], wait_ms)
            if not f.set_running_or_notify_cancel():
                continue
            try:
                f.set_result(fn(*args, **kwargs))
            except BaseException as e:  # noqa: BLE001 — future carries it
                f.set_exception(e)

    def stats_snapshot(self) -> dict[str, dict]:
        """Per-lane depth + dispatch/wait counters (the /metrics numbers)."""
        with self._cv:
            return {lane: {"depth": len(self._lanes[lane]),
                           **{k: round(v, 3) if isinstance(v, float) else v
                              for k, v in self._stats[lane].items()}}
                    for lane in LANES}

    def shutdown(self, wait: bool = False, cancel_futures: bool = False):
        with self._cv:
            first = not self._down
            self._down = True
            if first and cancel_futures:
                # Drain queued-but-unstarted items so their futures resolve
                # (cancelled) instead of hanging awaiting callers; the worker
                # exits once the lanes are empty either way.
                for q in self._lanes.values():
                    while q:
                        q.popleft()[2].cancel()
            self._cv.notify_all()
        # Join OUTSIDE the lock: a wedged dispatch would otherwise hold it
        # forever and hang submit() callers that deserve the immediate
        # shut-down RuntimeError.  Applies to repeat calls too (idempotent,
        # but wait=True must still mean wait).
        if wait:
            self._thread.join()


@dataclass
class RunStats:
    batches: int = 0
    samples: int = 0
    padded_samples: int = 0
    device_seconds: float = 0.0
    # Chunked dispatches (run_chunked): how many preemption-point slices the
    # model's batches were served in.  chunks / batches ≈ chunks per image.
    chunks: int = 0
    by_bucket: dict = field(default_factory=dict)


class DeviceRunner:
    """Owns the dispatch thread; exposes an awaitable batch-run API."""

    def __init__(self):
        self._pool = _DaemonDispatchPool()
        self._lock = threading.Lock()
        self._closed = False  # guarded-by: _lock
        # Chaos surface (faults.py): per-model injection rules + the legacy
        # always-fatal poison hook, consulted at the head of every dispatch.
        self.faults = FaultInjector()
        self.stats: dict[str, RunStats] = {}  # guarded-by: _lock
        # Device-residency accounting (docs/LIFECYCLE.md): parameter bytes
        # per device-resident model, maintained by the engine builder and
        # the lifecycle manager on every activate/demote — the live number
        # the ``hbm_budget_bytes`` eviction loop and the
        # ``tpuserve_hbm_bytes`` gauge read.
        self._resident: dict[str, int] = {}  # guarded-by: _lock
        # Dispatch-probe sharing (ADVICE r3): concurrent /healthz hits during
        # a wedge must not each enqueue a no-op and block a full timeout.
        self._probe_lock = threading.Lock()
        self._probe_future: Future | None = None  # guarded-by: _probe_lock
        self._probe_verdict = True  # guarded-by: _probe_lock
        self._probe_deadline = 0.0  # guarded-by: _probe_lock

    def poison(self, exc: Exception | None):
        """Wedged-device hook (SURVEY §5 failure detection).

        While set, every dispatch raises ``exc`` and ``probe`` reports the
        device dead — simulating a fatal XLA/device error so tests can assert
        the 5xx path, the 503 health flip, and the supervisor rebuild.  Pass
        ``None`` to clear.  For *flaky* (transient/every-Nth/latency) faults
        use :attr:`faults` (FaultInjector) — those leave the probe green.
        """
        self.faults.poison_exc = exc

    def _run(self, model: CompiledModel, samples: Sequence[dict], seq: int | None,
             span=None):
        # Runs on the dispatch thread: injected latency occupies the lane
        # exactly like a slow program would.
        t_sub = getattr(span, "t0", None)
        t_exec = time.perf_counter()
        # Request-trace "exec" span (serving/tracing.py): execution window on
        # the dispatch thread; the gap back to the parent span's start is the
        # QoS-lane wait.  Created before the fault hook so injected faults
        # and latency land inside a recorded span.
        tspan = None
        if span is not None:
            tspan = span.child("exec", lane=self._lane_of(model),
                               batch=len(samples),
                               **({"seq": seq} if seq is not None else {}))
            if t_sub is not None:
                tspan.annotate(lane_wait_ms=round((t_exec - t_sub) * 1000, 3))
        try:
            self.faults.on_dispatch(model.servable.name)
            t0 = time.perf_counter()
            # Span shows the batcher→dispatch handoff in /admin/profile captures.
            with jax.profiler.TraceAnnotation(
                    f"dispatch:{model.servable.name}:b{len(samples)}"):
                results, bucket = model.run_batch(samples, seq=seq)
        except BaseException as e:
            if tspan is not None:
                tspan.end(status="error", error=f"{type(e).__name__}: {e}")
            raise
        dt = time.perf_counter() - t0
        if tspan is not None:
            tspan.end(bucket=list(bucket))
        with self._lock:
            st = self.stats.setdefault(model.servable.name, RunStats())
            st.batches += 1
            st.samples += len(samples)
            st.padded_samples += bucket[0] - len(samples)
            st.device_seconds += dt
            # Per-bucket occupancy: samples / (batches * bucket rows).  Exposes
            # padding waste per (batch[, seq]) bucket on /metrics — a batch of
            # shorts dragged into a long-seq bucket shows up here.
            bk = st.by_bucket.setdefault(str(bucket), {"batches": 0, "samples": 0, "rows": 0})
            bk["batches"] += 1
            bk["samples"] += len(samples)
            bk["rows"] += bucket[0]
        return results

    @staticmethod
    def _lane_of(model: CompiledModel) -> str:
        lane = getattr(model, "latency_class", LANE_LATENCY)
        return lane if lane in LANES else LANE_LATENCY

    async def run(self, model: CompiledModel, samples: Sequence[dict],
                  seq: int | None = None, span=None) -> list[Any]:
        return await asyncio.wrap_future(self._pool.submit_lane(
            self._lane_of(model), self._run, model, samples, seq, span))

    async def run_fn(self, fn, *args, lane: str = LANE_LATENCY,
                     model: str | None = None, trip=None) -> Any:
        """Run an arbitrary device callable on the dispatch thread.

        The generation scheduler's prefill/segment kernels go through here so
        ALL device work — batched predicts, jobs, continuous decode — stays
        serialized on the one lane (the structured-concurrency invariant).
        Defaults to the latency lane: streaming decode segments are
        interactive work.  Honors the poison hook like every dispatch, and —
        with ``model`` named — the LATENCY half of a matching dispatch rule
        (a slow device is slow for streaming too; the disagg crashtest
        leans on this to land its kill mid-stream).  Failure rules stay on
        the batch/chunk paths — a mid-stream generation has no retry
        story, so chaos failures target ``_run``/``run_chunked``.

        ``trip`` (a ``RoundTimeline.trip(kind)``, serving/tracing.py) is told
        when the dispatch thread picks the call up, when the callable has
        returned there, and when this coroutine resumes: the two hand-overs
        of a scheduler round, ``round.lane_wait`` and ``round.wakeup``, per
        program kind and on the profiler's timeline.
        """
        if self.faults.poison_exc is not None:
            raise self.faults.poison_exc
        delay_s = (self.faults.dispatch_latency_s(model)
                   if model is not None else 0.0)
        if delay_s:
            # Sleep ON the dispatch thread: injected slowness must occupy
            # the lane the way a slow program would, not just delay the
            # caller.
            run = fn

            def fn(*a, _run=run, _delay=delay_s):  # noqa: F811
                time.sleep(_delay)
                return _run(*a)
        if trip is None:
            return await asyncio.wrap_future(
                self._pool.submit_lane(lane, fn, *args))
        call = fn

        def fn(*a):  # noqa: F811
            trip.picked_up()
            try:
                return call(*a)
            finally:
                trip.returned()
        try:
            return await asyncio.wrap_future(
                self._pool.submit_lane(lane, fn, *args))
        finally:
            trip.resumed()

    async def run_chunked(self, model: CompiledModel, samples: Sequence[dict],
                          seq: int | None = None, span=None) -> list[Any]:
        """Run a chunked servable as K short dispatches (QoS preemption points).

        Models exposing ``meta['chunked']`` (models/sd15.py) split their
        program into prepare → K chunk steps → finalize; each slice is its own
        dispatch on the model's lane, blocked-until-ready on the dispatch
        thread so occupancy is real, with the lane RELEASED between slices —
        a queued latency dispatch runs after at most one chunk instead of the
        whole program.  State (latents + conditioning) stays device-resident
        between chunks; only Python control returns to the event loop.

        Falls back to the monolithic :meth:`run` when the model has no
        chunked contract or serves a lockstep/mesh world (the followers
        mirror ``run_batch`` dispatches only, and SPMD placement of the
        carried state is not wired).
        """
        ch = model.servable.meta.get("chunked")
        if (ch is None or model.lockstep is not None
                or getattr(model, "mesh", None) is not None):
            return await self.run(model, samples, seq, span=span)
        lane = self._lane_of(model)
        name = model.servable.name

        def timed(fn, *args, chunk=False, label=""):
            # Per-slice trace span (serving/tracing.py): each preemption-
            # point dispatch shows up on the request's waterfall, so a
            # latency request stuck behind ONE chunk is distinguishable from
            # one stuck behind the whole denoise loop.
            tspan = span.child(label, lane=lane) if span is not None else None
            try:
                self.faults.on_dispatch(name)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(
                        f"dispatch:{name}:{'chunk' if chunk else 'edge'}"):
                    out = fn(*args)
            except BaseException as e:
                if tspan is not None:
                    tspan.end(status="error", error=f"{type(e).__name__}: {e}")
                raise
            dt = time.perf_counter() - t0
            if tspan is not None:
                tspan.end()
            with self._lock:
                st = self.stats.setdefault(name, RunStats())
                st.device_seconds += dt
                if chunk:
                    st.chunks += 1
            return out

        async def dispatch(fn, *args, chunk=False, label=""):
            if self.faults.poison_exc is not None:
                raise self.faults.poison_exc
            return await asyncio.wrap_future(self._pool.submit_lane(
                lane, timed, fn, *args, chunk=chunk, label=label))

        bucket, state = await dispatch(model.chunk_prepare, samples,
                                       label="chunk_prepare")
        for i, rows in enumerate(ch["chunk_rows"]):
            state = await dispatch(model.chunk_step, state, rows, chunk=True,
                                   label=f"chunk[{i}]")
        results = await dispatch(model.chunk_finalize, state, samples,
                                 label="chunk_finalize")
        with self._lock:
            st = self.stats.setdefault(name, RunStats())
            st.batches += 1
            st.samples += len(samples)
            st.padded_samples += bucket[0] - len(samples)
            bk = st.by_bucket.setdefault(
                str(bucket), {"batches": 0, "samples": 0, "rows": 0})
            bk["batches"] += 1
            bk["samples"] += len(samples)
            bk["rows"] += bucket[0]
        return results

    def run_sync(self, model: CompiledModel, samples: Sequence[dict],
                 seq: int | None = None) -> list[Any]:
        return self._pool.submit_lane(self._lane_of(model), self._run,
                                      model, samples, seq).result()

    def run_fn_sync(self, fn, *args, timeout: float | None = None):
        """Run ``fn`` on the dispatch thread, blocking the caller.

        Shutdown-path device work (e.g. the lockstep leader's OP_SHUTDOWN
        broadcast) must serialize AFTER any in-flight dispatch's collectives
        — launching it from another thread could interleave between a
        lead()'s header and batch broadcasts and desync collective matching.
        """
        return self._pool.submit(fn, *args).result(timeout=timeout)

    # -- residency accounting (docs/LIFECYCLE.md) ----------------------------
    def track_model(self, name: str, nbytes: int) -> None:
        """Record a model as device-resident with ``nbytes`` of parameters."""
        with self._lock:
            self._resident[name] = int(nbytes)

    def untrack_model(self, name: str) -> None:
        with self._lock:
            self._resident.pop(name, None)

    def resident_bytes(self) -> dict[str, int]:
        """Per-model device-resident parameter bytes (live HBM accounting)."""
        with self._lock:
            return dict(self._resident)

    @property
    def hbm_bytes_total(self) -> int:
        with self._lock:
            return sum(self._resident.values())

    # -- QoS surface ---------------------------------------------------------
    def set_priority(self, enabled: bool) -> None:
        """Toggle the two-level lane (ServeConfig.priority_dispatch).

        False = strict cross-lane FIFO — the pre-QoS single queue, kept as a
        runtime toggle so head-of-line blocking can be measured on the same
        engine.
        """
        self._pool.set_priority(enabled)

    @property
    def priority_enabled(self) -> bool:
        return self._pool.priority_enabled

    def lane_stats(self) -> dict[str, dict]:
        """Per-class queue depth + dispatch/wait stats for /metrics."""
        out = self._pool.stats_snapshot()
        for st in out.values():
            n = st["dispatches"]
            st["wait_ms_mean"] = round(st["wait_ms_total"] / n, 3) if n else 0.0
        return out

    def probe(self, dispatch_timeout_s: float | None = None) -> bool:
        """Tiny device-liveness check for /healthz (SURVEY §5 failure detection).

        ``dispatch_timeout_s`` additionally asserts the DISPATCH THREAD is
        live: a no-op must clear the dispatch queue within the timeout.  The
        multi-host leader passes this (serving/server.py) because a follower
        dying mid-collective wedges the dispatch thread inside a broadcast
        while the local device stays perfectly healthy — without the queue
        probe, /healthz would smile through a black-holed deployment.
        Single-host serving leaves it off: a cold sd15 compile legitimately
        occupies the lane for minutes.
        """
        import jax
        import jax.numpy as jnp

        with self._lock:
            closed = self._closed
        if closed:
            # A shut-down runner (engine already swapped out) is not a live
            # device — answering True here would let a health check smile
            # through a stale reference during a watchdog recovery.
            return False
        if self.faults.poison_exc is not None:
            return False
        try:
            x = jax.jit(lambda a: a * 2)(jnp.ones((8,)))
            ok = bool(x.sum() == 16.0)
        except Exception:
            log.exception("device probe failed")
            return False
        if ok and dispatch_timeout_s is not None:
            ok = self._dispatch_alive(dispatch_timeout_s)
        return ok

    def _dispatch_alive(self, timeout_s: float, cache_s: float = 5.0) -> bool:
        """Shared, cached dispatch-thread liveness probe.

        One in-flight no-op future at a time: during a wedge, concurrent
        /healthz calls share the SAME pending future (no queue growth) and a
        resolved verdict is cached for ``cache_s`` so repeated checks don't
        each pay the full timeout (ADVICE r3, runner.py:198).  A timed-out
        future is deliberately kept: it resolves the moment the lane clears,
        making the next probe fast and truthful.
        """
        now = time.monotonic()
        with self._probe_lock:
            if now < self._probe_deadline:
                return self._probe_verdict
            fut = self._probe_future
            if fut is None or fut.done():
                try:
                    fut = self._pool.submit(lambda: True)
                except RuntimeError:  # pool shut down
                    return False
                self._probe_future = fut
        try:
            fut.result(timeout=timeout_s)
            verdict = True
        except FuturesTimeout:
            log.error("dispatch thread unresponsive for %.0fs (wedged "
                      "collective?)", timeout_s)
            verdict = False
        except Exception:
            verdict = False
        with self._probe_lock:
            self._probe_verdict = verdict
            self._probe_deadline = time.monotonic() + cache_s
            # Only clear OUR future: a racing caller may have already
            # installed a fresh pending probe after ours resolved, and
            # discarding theirs would let a third caller enqueue a second
            # no-op during a wedge — the exact pile-up this guards against.
            if self._probe_future is fut and fut.done():
                self._probe_future = None
        return verdict

    @property
    def closed(self) -> bool:
        with self._lock:
            return self._closed

    def shutdown(self):
        """Stop the dispatch pool.  Idempotent: the watchdog swap path and
        the server's normal cleanup may both shut the same runner down —
        the pool drains queued futures exactly once and repeat calls are
        no-ops rather than errors.  The closed flag is written under the
        lock: shutdown races the watchdog's executor-side probe, and the
        probe must never read a half-torn runner as live."""
        with self._lock:
            self._closed = True
        self._pool.shutdown(wait=False, cancel_futures=True)
