"""Perf plane: continuous in-process profiling + ingest-path attribution.

ROADMAP item 1 names the production bottleneck — 137 ms http→device p50
against a 1.9 ms device step — but nothing in the repo could say *where
inside* that gap the time goes: tracing (docs/OBSERVABILITY.md) stops at
admission/queue/device granularity, and the host-side work around the chip
(payload read, JSON/b64 decode, validation, batch formation, response
serialization) was unmeasured.  This module is the always-on layer that
closes that, three parts (Clipper treats the middle layer as a first-class
latency object; ORCA's iteration-level accounting is what makes scheduler
changes judgeable — PAPERS.md):

- **Ingest/egress attribution** (:meth:`PerfPlane.note_stage` +
  :data:`INGEST_STAGES`): the serving path stamps per-(model, stage)
  histograms for the substages that tile the http→device gap —
  ``payload_read`` / ``json_decode`` / ``b64_decode`` / ``binary_decode`` /
  ``validate`` / ``batch_form`` / ``serialize`` / ``respond`` — beside the
  trace substages
  the waterfall renders (tools/tracedump.py).
- **Continuous runtime profiler**: :class:`LoopLagSampler` (scheduled-vs-
  actual callback delta — the event-loop stall detector: a blocking call on
  the loop shows here before it shows as tail latency) and
  :class:`StackSampler` (a py-spy-style wall-clock sampler over
  ``sys._current_frames()``, aggregated by collapsed stack into a bounded
  top-K table — the "what is the host actually doing" answer without a
  redeploy).  Both are injectable-clock testable and meant to be cheap
  enough to stay on: a timer callback an interval and a frame walk a stack
  tick, off the serving path (what that costs a request has no measurement
  in the tree: ROADMAP Design 4).
- **Rolling per-model gauges**: tok/s, samples/s, step time and device
  utilization computed by differencing the counters the runner and the
  generation schedulers already keep (RunStats.device_seconds/samples,
  scheduler ``tokens_emitted``) over a sliding window — live MFU when a
  ``flops_per_sample`` hint is configured (``ModelConfig.extra``), against
  the public per-chip peak table.

Surfaces: ``GET /admin/perf``, the ``tpuserve perf`` CLI table, and the
manifest-pinned ``tpuserve_ingest_ms`` / ``tpuserve_loop_lag_*`` /
``tpuserve_perf_*`` Prometheus families (serving/metrics.py).  Every knob
rides ``ServeConfig.perfplane``/``perf_*``; ``perfplane: false`` makes the
whole module a no-op (no threads, no callbacks, no histogram writes).
"""

from __future__ import annotations

import sys
import threading
import time

from ..utils.device import CHIP_PEAKS, device_info
from .metrics import Histogram

# The http→device gap decomposition (docs/OBSERVABILITY.md §9).  These are
# SUBSTAGES: they overlap the admission/queue/device/respond chain that
# tiles a request's wall time, so the waterfall counts them beside — never
# inside — stage coverage (tools/tracedump.py).  The worker substages
# (docs/OBSERVABILITY.md §10) are stamped in the acceptor processes and
# stitched in by the RingPump: sock_read (accept→body read),
# frame_validate (the worker's validate-only wire.unpack) and ring_wait
# (ring push → pump pop) extend the same decomposition to the fast lane.
INGEST_STAGES = ("sock_read", "payload_read", "json_decode", "b64_decode",
                 "frame_validate", "binary_decode", "ring_wait", "validate",
                 "batch_form", "serialize", "respond")

# Sub-ms-to-ms bounds for host-side stage work (payload reads are µs-to-ms;
# a JSON decode of a big b64 body can reach tens of ms).
INGEST_BUCKETS_MS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
                     50.0, 100.0, 250.0)

# Event-loop lag: healthy loops sit under 1 ms; a blocking handler shows as
# a 10-1000 ms spike.
LAG_BUCKETS_MS = (0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                  500.0, 1000.0, 2500.0)

# First-token / inter-token latency bounds (serving/generation.py): ttft
# spans prefill (tens to hundreds of ms), itl is the per-tick cadence.
TOKEN_LATENCY_BUCKETS_MS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                            500.0, 1000.0, 2500.0, 5000.0)


def hist_quantile(snap: dict, q: float) -> float | None:
    """Approximate quantile from a ``Histogram.snapshot()`` dict (cumulative
    buckets keyed by upper bound): linear interpolation inside the bucket
    the rank lands in; the +Inf bucket answers its lower bound.  The same
    estimate a Prometheus ``histogram_quantile`` would make — good enough
    for tables, documented as approximate."""
    count = snap.get("count", 0)
    if not count:
        return None
    rank = q * count
    prev_bound, prev_cum = 0.0, 0
    for le, cum in snap["buckets"].items():
        if le == "+Inf":
            return prev_bound
        if cum >= rank:
            bound = float(le)
            width = cum - prev_cum
            frac = (rank - prev_cum) / width if width else 1.0
            return round(prev_bound + (bound - prev_bound) * frac, 3)
        prev_bound, prev_cum = float(le), cum
    return prev_bound


class LoopLagSampler:
    """Event-loop responsiveness probe: scheduled-vs-actual callback delta.

    Every ``interval_s`` a ``call_later`` callback fires; the difference
    between when it was due and when it actually ran is time something else
    held the loop (a blocking decode, an accidental sync syscall, GC).  The
    deltas feed a histogram + a lifetime max, so "the loop stalled 180 ms
    at 14:02" survives as evidence instead of folklore.

    Deterministically testable: ``clock`` is injectable and :meth:`note`
    is the measurement core — tests arm it and feed fake timestamps.
    """

    def __init__(self, interval_s: float = 0.25, clock=time.monotonic):
        self.interval_s = max(float(interval_s), 0.01)
        self._clock = clock
        self.hist = Histogram(LAG_BUCKETS_MS)
        self.ticks = 0        # guarded-by: event-loop
        self.max_ms = 0.0     # guarded-by: event-loop
        self.last_ms = 0.0    # guarded-by: event-loop
        self._due: float | None = None  # guarded-by: event-loop
        self._handle = None   # guarded-by: event-loop
        self._loop = None     # guarded-by: event-loop

    # -- measurement core (clock-injected, no event loop needed) -------------
    def arm(self, now: float | None = None) -> None:
        """Record when the next tick is due."""
        now = self._clock() if now is None else now
        self._due = now + self.interval_s

    def note(self, now: float | None = None) -> float:
        """One tick: lag = actual - due (clamped at 0); re-arms.  Returns
        the lag in ms."""
        now = self._clock() if now is None else now
        lag_ms = max(now - self._due, 0.0) * 1000.0 if self._due else 0.0
        self.ticks += 1
        self.last_ms = lag_ms
        if lag_ms > self.max_ms:
            self.max_ms = lag_ms
        self.hist.observe(lag_ms)
        self.arm(now)
        return lag_ms

    # -- asyncio wiring -------------------------------------------------------
    def start(self, loop) -> "LoopLagSampler":
        self._loop = loop
        self.arm(loop.time())
        self._handle = loop.call_later(self.interval_s, self._tick)
        return self

    def _tick(self):
        self.note(self._loop.time())
        self._handle = self._loop.call_later(self.interval_s, self._tick)

    def stop(self):
        if self._handle is not None:
            self._handle.cancel()
            self._handle = None

    def snapshot(self) -> dict:
        snap = self.hist.snapshot()
        return {"interval_s": self.interval_s, "ticks": self.ticks,
                "last_ms": round(self.last_ms, 3),
                "max_ms": round(self.max_ms, 3),
                "hist": snap}


def _collapse(frame, max_depth: int) -> str:
    """A py-spy-style collapsed stack: outermost;...;innermost frames as
    ``file:function`` (basenames — absolute paths would make every table
    row unreadably wide)."""
    parts: list[str] = []
    while frame is not None and len(parts) < max_depth:
        code = frame.f_code
        fname = code.co_filename.rsplit("/", 1)[-1]
        parts.append(f"{fname}:{code.co_name}")
        frame = frame.f_back
    parts.reverse()
    return ";".join(parts)


class StackSampler:
    """Wall-clock thread-stack sampler over ``sys._current_frames()``.

    A background thread wakes ``hz`` times a second, snapshots every
    thread's current frame, and charges the elapsed wall interval to each
    thread's collapsed stack.  The aggregate answers "where do the host
    threads actually spend their time" continuously — the in-process
    py-spy, minus the subprocess and the ptrace.

    The table is bounded: it compacts to the ``topk`` heaviest stacks when
    it doubles past the budget, folding evicted weight into an explicit
    ``(other)`` row so the snapshot never silently under-reports.

    ``frames``/``clock`` are injectable so tests drive deterministic
    samples without threads.
    """

    def __init__(self, hz: float = 7.0, topk: int = 64, max_depth: int = 24,
                 clock=time.monotonic, frames=sys._current_frames):
        self.hz = max(float(hz), 0.1)
        self.topk = max(int(topk), 1)
        self.max_depth = max(int(max_depth), 1)
        self._clock = clock
        self._frames = frames
        self._lock = threading.Lock()
        self._table: dict[str, float] = {}  # guarded-by: _lock
        self.other_s = 0.0                  # guarded-by: _lock
        self.samples = 0                    # guarded-by: _lock
        self.evictions = 0                  # guarded-by: _lock
        self._thread: threading.Thread | None = None  # guarded-by: _lock
        self._stop = threading.Event()

    def _thread_name(self, ident: int) -> str:
        for t in threading.enumerate():
            if t.ident == ident:
                return t.name
        return f"tid-{ident}"

    def sample_once(self, dt_s: float, skip_ident: int | None = None) -> int:
        """Charge ``dt_s`` wall seconds to every live thread's stack.
        Returns how many stacks were charged."""
        charged = 0
        rows = []
        for ident, frame in self._frames().items():
            if ident == skip_ident:  # never profile the profiler
                continue
            key = (f"{self._thread_name(ident)};"
                   f"{_collapse(frame, self.max_depth)}")
            rows.append(key)
        with self._lock:
            self.samples += 1
            for key in rows:
                self._table[key] = self._table.get(key, 0.0) + dt_s
                charged += 1
            if len(self._table) > 2 * self.topk:
                self._compact_locked()
        return charged

    def _compact_locked(self):
        keep = sorted(self._table.items(), key=lambda kv: -kv[1])[: self.topk]
        dropped = sum(self._table.values()) - sum(s for _, s in keep)
        self.evictions += len(self._table) - len(keep)
        self.other_s += dropped
        self._table = dict(keep)

    # -- thread wiring --------------------------------------------------------
    def start(self) -> "StackSampler":
        with self._lock:
            if self._thread is None:
                self._stop.clear()
                self._thread = threading.Thread(
                    target=self._run, name="perf-stack-sampler", daemon=True)
                self._thread.start()
        return self

    def _run(self):
        me = threading.get_ident()
        last = self._clock()
        while not self._stop.wait(1.0 / self.hz):
            now = self._clock()
            self.sample_once(now - last, skip_ident=me)
            last = now

    def stop(self):
        self._stop.set()
        with self._lock:
            thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=2.0)

    def snapshot(self, top: int | None = None) -> dict:
        with self._lock:
            rows = sorted(self._table.items(), key=lambda kv: -kv[1])
            other = self.other_s
            samples, evictions = self.samples, self.evictions
        # total covers the WHOLE table (+ evicted weight): rows truncated
        # out of the display still count, so pct never over-reports.
        total = sum(s for _, s in rows) + other
        other += sum(s for _, s in rows[(top or self.topk):])
        rows = rows[: (top or self.topk)]
        return {
            "hz": self.hz, "samples": samples, "evictions": evictions,
            "total_s": round(total, 3),
            "stacks": [{"stack": k, "seconds": round(s, 3),
                        "pct": round(100.0 * s / total, 1) if total else 0.0}
                       for k, s in rows],
            **({"other_s": round(other, 3)} if other else {}),
        }


class _Window:
    """Bounded ring of (t, cumulative-counters) samples per model; gauges
    are the difference quotient between the newest sample and the oldest
    one still inside the window."""

    def __init__(self, window_s: float):
        self.window_s = max(float(window_s), 1.0)
        self._rows: list[tuple[float, dict]] = []  # guarded-by: event-loop

    def push(self, now: float, counters: dict):
        self._rows.append((now, counters))
        floor = now - self.window_s
        while len(self._rows) > 2 and self._rows[1][0] <= floor:
            self._rows.pop(0)

    def rates(self) -> dict | None:
        if len(self._rows) < 2:
            return None
        (t0, a), (t1, b) = self._rows[0], self._rows[-1]
        dt = t1 - t0
        if dt <= 0:
            return None
        out = {f"{k}_per_s": (b.get(k, 0.0) - a.get(k, 0.0)) / dt
               for k in b}
        out["span_s"] = dt
        return out


class PerfPlane:
    """The per-server perf hub: ingest histograms, samplers, gauges.

    Constructed unconditionally (so /admin/perf and the metric families
    always exist); ``enabled=False`` short-circuits every record call and
    ``start()`` into no-ops.
    """

    def __init__(self, cfg=None):
        self.enabled = bool(getattr(cfg, "perfplane", True))
        self.window_s = float(getattr(cfg, "perf_window_s", 30.0))
        self.loop_lag = LoopLagSampler(
            interval_s=float(getattr(cfg, "perf_loop_lag_interval_s", 0.25)))
        self.stacks = StackSampler(
            hz=float(getattr(cfg, "perf_stack_hz", 7.0)),
            topk=int(getattr(cfg, "perf_stack_topk", 64)))
        self._stack_hz = float(getattr(cfg, "perf_stack_hz", 7.0))
        # Ingest/egress stage histograms, keyed (model, stage).  Written
        # from the event loop (server handlers) AND the batcher loop (same
        # loop) — but scraped from arbitrary render callers, which the
        # Histogram's own lock covers; the dict itself only grows from the
        # event loop.
        self.ingest: dict[tuple[str, str], Histogram] = {}  # guarded-by: event-loop
        self._windows: dict[str, _Window] = {}  # guarded-by: event-loop
        self._gauges: dict[str, dict] = {}      # guarded-by: event-loop
        # Wired by the server: zero-arg callables yielding live counter
        # sources (None-safe so an embedded hub renders without a server).
        self.runner_stats = None   # guarded-by: event-loop
        self.gen_snapshots = None  # guarded-by: event-loop
        self.flops_hint = None     # guarded-by: event-loop
        # Lazy (sentinel False = undetected): jax.devices() forces backend/
        # device acquisition, which must NOT happen at Server construction
        # — the engine build owns that; by first gauge read it is done.
        self.peak_flops: float | None | bool = False  # guarded-by: event-loop

    def _peak(self) -> float | None:
        if self.peak_flops is False:
            # An unknown kind (the CPU under --platform cpu) has no peak:
            # no live MFU gauge rather than a guessed one.
            peaks = CHIP_PEAKS.get(device_info()["kind"])
            self.peak_flops = peaks[0] if peaks else None
        return self.peak_flops

    # -- ingest attribution ---------------------------------------------------
    def note_stage(self, model: str | None, stage: str, ms: float) -> None:
        """One host-side stage observation (event loop only)."""
        if not self.enabled or model is None:
            return
        hist = self.ingest.get((model, stage))
        if hist is None:
            hist = self.ingest[(model, stage)] = Histogram(INGEST_BUCKETS_MS)
        hist.observe(ms)

    # -- rolling gauges -------------------------------------------------------
    def observe_models(self, now: float | None = None) -> None:
        """Sample the live counters into the rolling windows (called from
        the loop-lag tick, i.e. every ``perf_loop_lag_interval_s``)."""
        if not self.enabled:
            return
        now = time.monotonic() if now is None else now
        stats = self.runner_stats() if self.runner_stats is not None else {}
        gens = self.gen_snapshots() if self.gen_snapshots is not None else {}
        for model, st in (stats or {}).items():
            self._push(now, model, {
                "samples": float(st.samples), "batches": float(st.batches),
                "device_seconds": float(st.device_seconds)})
        for model, snap in (gens or {}).items():
            self._push(now, f"{model}:generate", {
                "tokens": float(snap.get("tokens_emitted", 0)),
                "ticks": float(snap.get("segment_rounds", 0))})

    def _push(self, now: float, key: str, counters: dict):
        win = self._windows.get(key)
        if win is None:
            win = self._windows[key] = _Window(self.window_s)
        win.push(now, counters)

    def model_gauges(self) -> dict[str, dict]:
        out: dict[str, dict] = {}
        for key, win in self._windows.items():
            rates = win.rates()
            if rates is None:
                continue
            row: dict = {"window_s": round(rates["span_s"], 1)}
            if "samples_per_s" in rates:
                row["samples_per_s"] = round(rates["samples_per_s"], 2)
                bps = rates.get("batches_per_s", 0.0)
                dps = rates.get("device_seconds_per_s", 0.0)
                if bps > 0:
                    row["step_ms"] = round(1000.0 * dps / bps, 3)
                row["device_util_pct"] = round(100.0 * dps, 1)
                flops = (self.flops_hint(key) if self.flops_hint is not None
                         else None)
                peak = self._peak() if flops else None
                if flops and peak and rates["samples_per_s"] > 0:
                    row["mfu_pct"] = round(
                        100.0 * flops * rates["samples_per_s"] / peak, 2)
            if "tokens_per_s" in rates:
                row["tokens_per_s"] = round(rates["tokens_per_s"], 2)
                if rates.get("ticks_per_s"):
                    row["tick_ms"] = round(1000.0 / rates["ticks_per_s"], 3)
            out[key] = row
        return out

    # -- lifecycle ------------------------------------------------------------
    def start(self, loop) -> "PerfPlane":
        if not self.enabled:
            return self
        # The gauge sampler rides the lag tick: one callback per interval
        # covers both jobs, so "always on" costs one timer and one O(models)
        # dict walk per quarter second.
        orig_note = self.loop_lag.note

        def note_and_sample(now=None):
            lag = orig_note(now)
            try:
                self.observe_models()
            except Exception:  # noqa: BLE001 — sampling must not kill the timer
                pass
            return lag

        self.loop_lag.note = note_and_sample
        self.loop_lag.start(loop)
        if self._stack_hz > 0:
            self.stacks.start()
        return self

    def stop(self):
        self.loop_lag.stop()
        self.stacks.stop()

    # -- export ---------------------------------------------------------------
    def ingest_snapshot(self) -> dict[str, dict[str, dict]]:
        """{model: {stage: histogram snapshot}} (stage order = pipeline)."""
        out: dict[str, dict[str, dict]] = {}
        for (model, stage), hist in list(self.ingest.items()):
            out.setdefault(model, {})[stage] = hist.snapshot()
        for model, stages in out.items():
            out[model] = {s: stages[s] for s in INGEST_STAGES if s in stages}
        return out

    def snapshot(self, top_stacks: int = 20) -> dict:
        return {
            "enabled": self.enabled,
            "loop_lag": self.loop_lag.snapshot(),
            "stacks": self.stacks.snapshot(top=top_stacks),
            "models": self.model_gauges(),
            "ingest": self.ingest_snapshot(),
        }
