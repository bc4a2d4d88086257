"""In-process request tracing — Dapper-style span trees for every hop.

The serving stack can say *that* it is slow (LatencyRing percentiles,
docs/RESILIENCE.md counters) but not *where one request* spent its time:
admission → queue (per QoS lane) → batch formation → device dispatch →
execution → postprocess, with shed/retry/breaker decisions interleaved.
This module is the missing layer (Sigelman et al., "Dapper", 2010; the
stage-latency attribution Clipper used to drive tail debugging) with zero
dependencies — spans are plain records in process memory, never exported
over the network:

- :class:`Span` — one timed stage, parented into a tree.  Timestamps are
  ``time.perf_counter()`` so stage durations line up exactly with the
  numbers the batcher/runner already record; the wall-clock anchor lives on
  the trace.
- :class:`Trace` — one request's span tree.  Spans append from the event
  loop AND the dispatch thread (device execution spans), so the append is
  lock-protected; the span budget (``max_spans``) bounds a pathological
  request (drops are counted, never raised).
- :class:`Tracer` — the per-server hub.  Finished traces land in a bounded
  ring buffer; a **flight recorder** additionally pins the N slowest and
  the recent errored traces *per model*, so the trace you need after a tail
  spike is still there after 10k healthy requests evicted the ring.

W3C Trace Context (``traceparent``) is ingested and propagated: a request
arriving with ``traceparent: 00-<trace>-<span>-01`` joins the caller's
trace id and parents its root span under the caller's span; responses
carry ``X-Trace-Id`` (and errors embed ``trace_id``) so the id round-trips
through logs (``utils/logging`` stamps it on every record via
``current_trace_id``), metrics (OpenMetrics exemplars on the queue/device
histograms, serving/metrics.py) and ``GET /admin/trace/{id}``.
``tools/tracedump.py`` renders the tree as a text waterfall.

:class:`RoundTimeline` is the scheduler-side twin: what a generation
scheduler's two threads were doing in each round of its loop, as cumulative
per-phase counters, a ring of the last rounds, and ``TraceAnnotation``s that
put the same phases on the profiler's clock (docs/OBSERVABILITY.md sections 1, 4).
"""

from __future__ import annotations

import re
import threading
import time
import uuid
from collections import deque

from ..utils.scope import on_thread

# 00-<16-byte trace id>-<8-byte span id>-<flags>, lowercase hex (W3C level 1).
_TRACEPARENT = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
    """``(trace_id, parent_span_id)`` from a ``traceparent`` header, or None.

    Invalid headers are treated as absent (the W3C-mandated behavior is to
    restart the trace, not to fail the request); the all-zero trace/span
    ids are explicitly invalid per spec.
    """
    if not header:
        return None
    m = _TRACEPARENT.match(header.strip().lower())
    if m is None or m.group(1) == "ff":
        return None
    trace_id, span_id = m.group(2), m.group(3)
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    """The outbound ``traceparent`` for (trace, span) — always sampled."""
    return f"00-{trace_id}-{span_id}-01"


def new_trace_id() -> str:
    return uuid.uuid4().hex


def new_span_id() -> str:
    return uuid.uuid4().hex[:16]


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


class Span:
    """One timed stage of a trace.  Usable as a context manager.

    ``start``/``end`` are ``perf_counter`` seconds; explicit values let
    instrumentation sites stitch spans to timestamps they already measured
    (``_Req.t_enq``, dispatch ``t_start``/``t_end``) so stage durations are
    contiguous and sum to the request wall time.
    """

    __slots__ = ("trace", "name", "span_id", "parent_id", "t0", "t1",
                 "status", "attrs", "recorded")

    def __init__(self, trace: "Trace", name: str, parent_id: str | None,
                 start: float | None = None, attrs: dict | None = None,
                 recorded: bool = True):
        self.trace = trace
        self.name = name
        self.span_id = new_span_id()
        self.parent_id = parent_id
        self.t0 = time.perf_counter() if start is None else start
        # One stage owns a span at a time (opened and ended by the same
        # instrumentation site, on whichever thread runs that stage); the
        # handoff between threads rides the awaited dispatch round-trip.
        self.t1: float | None = None  # guarded-by: dispatch-serialized
        self.status = "ok"            # guarded-by: dispatch-serialized
        self.attrs = dict(attrs) if attrs else {}
        self.recorded = recorded  # False once the trace's span budget is spent

    # -- lifecycle -----------------------------------------------------------
    def end(self, status: str | None = None, end: float | None = None,
            **attrs) -> "Span":
        if self.t1 is None:  # idempotent: first end wins
            self.t1 = time.perf_counter() if end is None else end
            if status is not None:
                self.status = status
            if attrs:
                self.attrs.update(attrs)
        return self

    def annotate(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def child(self, name: str, start: float | None = None, **attrs) -> "Span":
        """Open a child span (caller ends it)."""
        return self.trace.new_span(name, parent=self, start=start, attrs=attrs)

    def point(self, name: str, **attrs) -> "Span":
        """Zero-duration annotation span (a decision, not a stage)."""
        now = time.perf_counter()
        sp = self.trace.new_span(name, parent=self, start=now, attrs=attrs)
        sp.end(end=now)
        return sp

    @property
    def duration_ms(self) -> float:
        end = self.t1 if self.t1 is not None else time.perf_counter()
        return (end - self.t0) * 1000.0

    @property
    def traceparent(self) -> str:
        """Propagation header for work this span fans out."""
        return format_traceparent(self.trace.trace_id, self.span_id)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end(status="error" if exc_type is not None else None,
                 **({"error": f"{exc_type.__name__}: {exc}"}
                    if exc_type is not None else {}))


class Trace:
    """One request's span tree, with a wall-clock anchor and a span budget."""

    def __init__(self, trace_id: str, name: str, model: str | None = None,
                 max_spans: int = 512, parent_span_id: str | None = None,
                 attrs: dict | None = None, start: float | None = None):
        """``start`` back-dates the root span to a ``perf_counter`` stamp
        measured before the trace object existed — the acceptor fast lane
        anchors the trace at the worker process's accept time, so the
        waterfall covers the whole request, not just the pump's share
        (perf_counter is CLOCK_MONOTONIC on Linux: system-wide, hence
        comparable across processes; docs/OBSERVABILITY.md §10)."""
        self.trace_id = trace_id
        self.name = name
        self.model = model
        self.max_spans = max_spans
        self.started_wall = time.time()
        self._t0 = time.perf_counter() if start is None else start
        self.finished = False                 # guarded-by: event-loop
        self.status = "open"                  # guarded-by: event-loop
        self.duration_ms: float | None = None  # guarded-by: event-loop
        self.dropped_spans = 0                # guarded-by: _lock
        self._lock = threading.Lock()  # spans append from the dispatch thread
        self.spans: list[Span] = []           # guarded-by: _lock
        # The root: parented under the caller's traceparent span if one came
        # in (its id is foreign — not in self.spans — which marks it remote).
        self.remote_parent = parent_span_id
        self.root = self.new_span(name, parent=None, start=start, attrs=attrs)

    def new_span(self, name: str, parent: Span | None,
                 start: float | None = None, attrs: dict | None = None) -> Span:
        parent_id = (parent.span_id if parent is not None
                     else self.remote_parent)
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.dropped_spans += 1
                return Span(self, name, parent_id, start, attrs, recorded=False)
            sp = Span(self, name, parent_id, start, attrs)
            self.spans.append(sp)
            return sp

    def finish(self, status: str | None = None) -> "Trace":
        """Close the trace (idempotent): end the root, freeze the duration.

        Spans may still be appended afterwards (e.g. a watchdog requeue
        annotating a job trace post-mortem) — they show up in the tree but
        don't move the recorded duration.
        """
        if not self.finished:
            self.finished = True
            self.root.end(status=status)
            self.status = status or self.root.status
            with self._lock:
                # Close abandoned stage spans at the root's end (an error
                # return mid-stage): an open span must not keep "growing"
                # every time the tree is rendered.
                for s in self.spans:
                    if s.t1 is None:
                        s.t1 = max(self.root.t1, s.t0)
                last = max((s.t1 for s in self.spans if s.t1 is not None),
                           default=self.root.t1 or self._t0)
            self.duration_ms = round((last - self.root.t0) * 1000.0, 3)
        return self

    # -- export --------------------------------------------------------------
    def _span_dict(self, sp: Span) -> dict:
        out = {
            "name": sp.name,
            "span_id": sp.span_id,
            "start_ms": round((sp.t0 - self.root.t0) * 1000.0, 3),
            "duration_ms": round(sp.duration_ms, 3),
            "status": sp.status,
        }
        if sp.attrs:
            out["attrs"] = dict(sp.attrs)
        return out

    def tree(self) -> dict:
        """The nested span tree (children ordered by start time)."""
        with self._lock:
            spans = list(self.spans)
            dropped = self.dropped_spans
        nodes = {sp.span_id: self._span_dict(sp) for sp in spans}
        roots: list[dict] = []
        for sp in spans:
            node = nodes[sp.span_id]
            parent = nodes.get(sp.parent_id) if sp.parent_id else None
            if parent is None:
                roots.append(node)  # the root (or a remote-parented span)
            else:
                parent.setdefault("children", []).append(node)
        for node in nodes.values():
            if "children" in node:
                node["children"].sort(key=lambda n: n["start_ms"])
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "model": self.model,
            "status": self.status,
            "started": round(self.started_wall, 3),
            "duration_ms": (self.duration_ms if self.duration_ms is not None
                            else round((time.perf_counter() - self.root.t0)
                                       * 1000.0, 3)),
            "spans": len(spans),
            "dropped_spans": dropped,
            **({"remote_parent": self.remote_parent}
               if self.remote_parent else {}),
            "tree": roots[0] if len(roots) == 1 else {"name": "(forest)",
                                                      "children": roots},
        }

    def summary(self) -> dict:
        with self._lock:
            n_spans = len(self.spans)
        return {
            "trace_id": self.trace_id,
            "name": self.name,
            "model": self.model,
            "status": self.status,
            "started": round(self.started_wall, 3),
            "duration_ms": (self.duration_ms if self.duration_ms is not None
                            else round((time.perf_counter() - self.root.t0)
                                       * 1000.0, 3)),
            "spans": n_spans,
        }


class Tracer:
    """Per-server trace hub: live registry, ring buffer, flight recorder.

    - ``ring`` bounds the finished-trace history (FIFO eviction).
    - The flight recorder pins, per model: the ``flight_slow`` slowest
      traces (by duration) and the last ``flight_errors`` errored traces —
      the two populations a tail investigation actually needs, immune to
      ring churn from healthy traffic.
    - ``_live`` tracks open traces so an in-flight request is queryable;
      it is capped defensively (an abandoned trace must not leak forever).
    """

    def __init__(self, ring: int = 256, flight_slow: int = 8,
                 flight_errors: int = 32, max_spans: int = 512,
                 max_live: int = 4096):
        self._lock = threading.Lock()
        self._ring: deque[Trace] = deque(maxlen=max(int(ring), 1))  # guarded-by: _lock
        self.flight_slow = max(int(flight_slow), 0)
        self.flight_errors = max(int(flight_errors), 0)
        self.max_spans = max(int(max_spans), 8)
        self._max_live = max(int(max_live), 16)
        self._live: dict[str, Trace] = {}  # guarded-by: _lock
        self._slow: dict[str, list[Trace]] = {}      # guarded-by: _lock
        self._errored: dict[str, deque[Trace]] = {}  # guarded-by: _lock
        self.finished_total = 0      # guarded-by: _lock
        self.dropped_spans_total = 0  # guarded-by: _lock

    # -- lifecycle -----------------------------------------------------------
    def start(self, name: str, model: str | None = None,
              traceparent: str | None = None, start: float | None = None,
              **attrs) -> Span:
        """Open a trace; returns its root span (``span.trace`` is the trace).

        A valid ``traceparent`` joins the caller's trace id and parents the
        root under the caller's span; otherwise a fresh id is minted.
        ``start`` back-dates the root (see :class:`Trace`).
        """
        parsed = parse_traceparent(traceparent)
        trace_id, parent = parsed if parsed else (new_trace_id(), None)
        trace = Trace(trace_id, name, model=model, max_spans=self.max_spans,
                      parent_span_id=parent, attrs=attrs, start=start)
        with self._lock:
            if len(self._live) >= self._max_live:
                # Defensive: evict the oldest live trace (leaked = never
                # finished); finishing it keeps it inspectable in the ring.
                oldest = next(iter(self._live))
                self._record(self._live.pop(oldest).finish("abandoned"))
            self._live[trace.trace_id] = trace
        return trace.root

    def finish(self, trace: Trace, status: str | None = None) -> Trace:
        if trace.finished:  # idempotent: recorded exactly once
            return trace
        trace.finish(status)
        with self._lock:
            self._live.pop(trace.trace_id, None)
            self._record(trace)
        return trace

    def _record(self, trace: Trace):
        """Under the lock: ring append + flight-recorder pinning."""
        self.finished_total += 1
        self.dropped_spans_total += trace.dropped_spans
        self._ring.append(trace)
        model = trace.model or ""
        if trace.status == "error" and self.flight_errors:
            self._errored.setdefault(
                model, deque(maxlen=self.flight_errors)).append(trace)
        if self.flight_slow and trace.duration_ms is not None:
            slow = self._slow.setdefault(model, [])
            slow.append(trace)
            slow.sort(key=lambda t: -(t.duration_ms or 0.0))
            del slow[self.flight_slow:]

    # -- queries -------------------------------------------------------------
    def _all(self) -> list[Trace]:
        """Every known trace, deduped by id (live > ring > flight)."""
        seen: dict[str, Trace] = {}
        with self._lock:
            groups = [list(self._live.values()), list(self._ring),
                      *[list(d) for d in self._errored.values()],
                      *[list(v) for v in self._slow.values()]]
        for group in groups:
            for t in group:
                seen.setdefault(t.trace_id, t)
        return list(seen.values())

    def get(self, trace_id: str) -> Trace | None:
        with self._lock:
            t = self._live.get(trace_id)
        if t is not None:
            return t
        for t in self._all():
            if t.trace_id == trace_id:
                return t
        return None

    def list(self, model: str | None = None, status: str | None = None,
             min_ms: float = 0.0, limit: int = 50) -> list[dict]:
        """Finished+live trace summaries, newest first, filtered."""
        out = []
        for t in self._all():
            if model is not None and t.model != model:
                continue
            if status is not None and t.status != status:
                continue
            s = t.summary()
            if s["duration_ms"] is not None and s["duration_ms"] < min_ms:
                continue
            out.append(s)
        out.sort(key=lambda s: -s["started"])
        return out[: max(int(limit), 1)]

    def pinned(self) -> dict:
        """Flight-recorder census (for /metrics)."""
        with self._lock:
            return {"slow": {m: len(v) for m, v in self._slow.items() if v},
                    "errored": {m: len(v) for m, v in self._errored.items()
                                if v}}

    def snapshot(self) -> dict:
        with self._lock:
            live, ring = len(self._live), len(self._ring)
            finished = self.finished_total
            dropped = self.dropped_spans_total
        pins = self.pinned()
        return {"finished": finished,
                "live": live, "ring": ring,
                "dropped_spans": dropped,
                "pinned_slow": sum(pins["slow"].values()),
                "pinned_errored": sum(pins["errored"].values())}


# -- scheduler rounds -----------------------------------------------------------

# The host phases of one generation-scheduler round (one iteration of the
# scheduler's ``_loop``: zero or more admission groups, then one segment).
# The ``round.*`` phases run on the event loop, the others on the dispatch
# thread; ``round.lane_wait`` and ``round.wakeup`` are the two hand-overs
# between them.  PERF.md, the benchmark's readers and ``attribute_idle``
# (utils/xplane.py) share these names.
PHASES = ("round.idle", "round.admit_host", "round.lane_wait",
          "prefill.launch", "prefill.fetch", "segment.launch",
          "segment.fetch", "round.wakeup", "round.distribute")


class _Phase:
    """One timed phase: stamps outermost, the annotation inside them.  While
    it is open it is its thread's scope for the compile listeners
    (``engine/cache.py``): a program that compiles inside a launch phase is
    booked as that launch's first use."""

    __slots__ = ("tl", "name", "attrs", "ann", "t0")

    def __init__(self, tl: "RoundTimeline", name: str, attrs: dict):
        self.tl, self.name, self.attrs = tl, name, attrs
        # One thread opens and closes a phase; which one, the phase says.
        self.t0 = 0      # guarded-by: dispatch-serialized
        self.ann = None  # guarded-by: dispatch-serialized

    def __enter__(self) -> "_Phase":
        self.t0 = time.perf_counter_ns()
        self.ann = self.tl.annotation(self.name, **self.attrs)
        self.ann.__enter__()
        on_thread.scope = self
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        on_thread.scope = None
        self.ann.__exit__(exc_type, exc, tb)
        t1 = time.perf_counter_ns()
        if self.tl.open_uses:
            self.tl.settle(self, t1)
        self.tl.book(self.name, self.t0, t1, self.attrs)

    def first_use(self):
        """The ledger's open first use for a compile heard while this phase
        is open; None where the phase launches no program."""
        return self.tl.first_use(self)

    def program(self) -> tuple[str, dict] | None:
        """``(program, key)`` of the launch this phase is, as the lane names
        it for the ledger (and the program store keys its executables by);
        None where the phase launches no program."""
        of = self.tl.program_of
        return of(self.name, self.attrs) if of else None


class _Trip:
    """One awaited round-trip to the dispatch thread (``runner.run_fn``).

    ``round.lane_wait`` runs from the enqueue on the event loop to the
    pick-up on the dispatch thread, ``round.wakeup`` from the callable's
    return there to the scheduler coroutine's resumption.  Each annotation is
    entered on one thread and left on the other: the profiler records a
    ``TraceMe`` when it is left, with the start it was entered at, so the
    event lands whole on the timeline of the thread that ends it.
    """

    __slots__ = ("tl", "kind", "t_enq", "t_done", "ann")

    def __init__(self, tl: "RoundTimeline", kind: str):
        self.tl, self.kind = tl, kind
        # Handed from thread to thread with the call itself.
        self.t_done: int | None = None  # guarded-by: dispatch-serialized
        self.t_enq = time.perf_counter_ns()
        self.ann = tl.annotation("round.lane_wait", kind=kind)  # guarded-by: dispatch-serialized
        self.ann.__enter__()

    def picked_up(self) -> None:  # dispatch thread
        self.ann.__exit__(None, None, None)
        now = time.perf_counter_ns()
        self.tl.book("round.lane_wait", self.t_enq, now, {"kind": self.kind})
        split = self.tl.lane_wait_ns.setdefault(self.kind, [0, 0])
        split[0] += now - self.t_enq
        split[1] += 1

    def returned(self) -> None:  # dispatch thread
        self.t_done = time.perf_counter_ns()
        self.ann = self.tl.annotation("round.wakeup", kind=self.kind)
        self.ann.__enter__()

    def resumed(self) -> None:  # event loop
        if self.t_done is None:  # never picked up (cancelled, pool down)
            return
        self.ann.__exit__(None, None, None)
        self.tl.book("round.wakeup", self.t_done, time.perf_counter_ns(),
                     {"kind": self.kind})


class RoundTimeline:
    """What one scheduler's two threads did, round by round.

    Three views of the same stamps: cumulative ``sum_ns``/``count`` per phase
    (``gen_snapshot()["host_phases"]``, read as deltas), a ring of the last
    ``ring`` rounds with every phase interval in ``perf_counter_ns`` (``GET
    /admin/trace?rounds=N``), and a ``jax.profiler.TraceAnnotation`` named
    ``tpuserve.<phase>`` around each phase, which costs well under a
    microsecond without a profiler session and puts the phase on the
    capture's own clock with one (``POST /admin/profile``).

    No lock: every phase has exactly one writer thread, and the scheduler
    task and the dispatch thread alternate through awaited round-trips.

    A launch phase inside which a program compiled is that program's first
    use: ``clock`` (``engine/cache.CompileClock``) keeps its entry, named by
    ``program_of(phase name, attrs) -> (program, key) | None``, the lane's own
    account of which program a launch phase runs.
    """

    def __init__(self, model: str, ring: int = 256, clock=None,
                 program_of=None):
        from jax.profiler import TraceAnnotation

        self.model = model
        self.clock = clock
        self.program_of = program_of
        # First uses not yet whole, ``(launch phase, engine/cache.FirstUse)``:
        # kept from the first compile heard inside the launch until the fetch
        # of that kind returns.  Empty but for the rounds that compile.
        self.open_uses: list[tuple] = []  # guarded-by: dispatch-serialized
        self._seen: set = set()           # guarded-by: dispatch-serialized
        self._annotate = TraceAnnotation
        self.round = 0  # guarded-by: dispatch-serialized
        self.sum_ns = dict.fromkeys(PHASES, 0)  # guarded-by: dispatch-serialized
        self.count = dict.fromkeys(PHASES, 0)   # guarded-by: dispatch-serialized
        # round.lane_wait again, split by the program kind that waited.
        self.lane_wait_ns: dict[str, list[int]] = {}  # guarded-by: dispatch-serialized
        self._rounds: deque[dict] = deque(maxlen=max(int(ring), 1))  # guarded-by: dispatch-serialized
        self._cur: dict | None = None  # guarded-by: dispatch-serialized

    def annotation(self, phase: str, **attrs):
        return self._annotate(f"tpuserve.{phase}", round=self.round,
                              model=self.model, **attrs)

    def begin_round(self, **attrs) -> int:
        """Open the next round (scheduler task, loop top); ends the last."""
        now = time.perf_counter_ns()
        if self._cur is not None:
            self._cur["t1_ns"] = now
        self.round += 1
        self._cur = {"round": self.round, "t0_ns": now, "t1_ns": None,
                     **attrs, "phases": []}
        self._rounds.append(self._cur)
        return self.round

    def phase(self, name: str, **attrs) -> _Phase:
        return _Phase(self, name, attrs)

    def trip(self, kind: str) -> _Trip:
        return _Trip(self, kind)

    def first_use(self, phase: _Phase):
        """The open first use for a compile heard inside ``phase`` (begun at
        the first such compile), or None: not a launch, or no ledger."""
        for held, use in self.open_uses:
            if held is phase:
                return use
        named = phase.program() if self.clock is not None else None
        if named is None:
            return None
        use = self.clock.open(self.model, *named, seen=self._seen,
                              round=self.round, t0_ns=phase.t0)
        self.open_uses.append((phase, use))
        return use

    def settle(self, phase: _Phase, t1_ns: int) -> None:
        """``phase`` ends while first uses are open: the launch that compiled
        gets its wall, and the next fetch of its kind its first run."""
        for held, use in list(self.open_uses):
            fetch = held.name.replace(".launch", ".fetch")
            if held is phase:
                use.launched(t1_ns)
                whole = fetch not in PHASES  # no fetch of its own
            else:
                whole = use.launched_ns is not None and phase.name == fetch
                if whole:
                    use.ran(t1_ns)
            if whole:
                self.open_uses.remove((held, use))

    def book(self, name: str, t0_ns: int, t1_ns: int, attrs: dict) -> None:
        self.sum_ns[name] += t1_ns - t0_ns
        self.count[name] += 1
        if self._cur is not None:
            self._cur["phases"].append((name, t0_ns, t1_ns, attrs))

    def snapshot(self) -> dict:
        """``{phase: {"sum_ms", "count"}}``, cumulative since the start."""
        return {p: {"sum_ms": round(self.sum_ns[p] / 1e6, 3),
                    "count": self.count[p]} for p in PHASES}

    def lane_wait_snapshot(self) -> dict:
        return {kind: {"sum_ms": round(ns / 1e6, 3), "count": n}
                for kind, (ns, n) in list(self.lane_wait_ns.items())}

    def recent(self, limit: int = 32) -> list[dict]:
        """The newest ``limit`` rounds, oldest first."""
        rounds = list(self._rounds)[-max(int(limit), 1):]
        return [{**{k: v for k, v in r.items() if k != "phases"},
                 "phases": [{"phase": name, "t0_ns": t0, "t1_ns": t1, **attrs}
                            for name, t0, t1, attrs in list(r["phases"])]}
                for r in rounds]
