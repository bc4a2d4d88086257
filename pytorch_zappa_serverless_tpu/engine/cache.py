"""Persistent XLA compilation cache — the cold-start killer.

The reference's cold start is dominated by dependency + weight fetch (tens of
seconds, SURVEY §3.1); ours would be dominated by XLA compilation.  JAX's
persistent compilation cache writes every compiled executable to disk keyed by
(HLO, flags, platform); a warm pool VM restarting the server hits the cache and
skips compilation entirely — the TPU-native analogue of Zappa keep-warm
(SURVEY §3.4).  Cold-start compile time is a first-class BASELINE metric, so
:class:`CompileClock` keeps one entry for every first use of a jitted program,
whichever lane made it, with the stages ``jax.monitoring`` times from inside
(trace, lower, cache read, backend) beside the launch's own wall.
"""

from __future__ import annotations

import json
import os
import threading
import time
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

from ..utils.logging import get_logger
from ..utils.scope import on_thread

log = get_logger("engine.cache")

# Where the cache lives when neither JAX_COMPILATION_CACHE_DIR nor the config
# names a place: one fixed directory inside the checkout (.gitignore lists
# it).  The path is part of jax's cache key, so a directory that moves never
# hits — nothing here may derive it from a pid, a clock or a tempdir.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".cache" / "xla"

_configured: str | None = None


def resolve_compile_cache_dir(configured: str | Path | None = None) -> str:
    """The one place a compile-cache path is decided.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (the operator placed the
    cache from outside); otherwise an explicit ``compile_cache_dir`` from
    the config; otherwise :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if configured:
        return str(Path(configured).expanduser())
    return str(DEFAULT_CACHE_DIR)


def setup_compile_cache(cache_dir: str | Path | None = None) -> str:
    """Enable the on-disk compilation cache (idempotent); returns the
    resolved directory (:func:`resolve_compile_cache_dir`).

    With ``JAX_COMPILATION_CACHE_DIR`` set jax already reads that directory
    itself, so no ``jax_compilation_cache_dir`` update is made here.
    Otherwise reconfiguration to a DIFFERENT directory mid-process works
    too: jax initializes its persistent-cache object lazily once and then
    ignores later ``jax_compilation_cache_dir`` updates, so the cache
    object is reset whenever the dir changes (tests re-pointing per case,
    a fresh directory for each cold trial of a measurement).
    """
    global _configured
    _listen()
    cache_dir = resolve_compile_cache_dir(cache_dir)
    if _configured == cache_dir:
        return cache_dir
    Path(cache_dir).mkdir(parents=True, exist_ok=True)
    # Cache everything: serving executables are precious regardless of size or
    # how fast they compiled.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        # Drop the lazily-initialized cache object so the next compile
        # re-reads the config; harmless when the cache was never touched.
        compilation_cache.reset_cache()
    _configured = cache_dir
    return cache_dir


# -- the ledger of program first uses -------------------------------------------
#
# JAX times every stage of a compile and hands it to whoever listens
# (``jax.monitoring``): a scalar when a stage begins, a duration when it ends,
# plain events from the persistent cache in between.  None fires on the
# compiled fast path.  A listener books what it hears to the scope open on its
# thread (``utils/scope.py``): a scheduler's launch phase (``serving/tracing._Phase``) or a
# ``:predict`` bucket's first dispatch (:class:`FirstUse` itself).  A compile
# with no scope open on its thread (a builder's eager operations) is not
# booked.

_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
           "/jax/core/compile/backend_compile_duration": "backend_s"}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache": "miss",
                 "/jax/compilation_cache/cache_hits": "hit"}


_listening = False


def _listen() -> None:
    """Register the three listeners, once a process."""
    global _listening
    if _listening:
        return
    _listening = True
    jax.monitoring.register_scalar_listener(_stage_begins)
    jax.monitoring.register_event_listener(_cache_event)
    jax.monitoring.register_event_duration_secs_listener(_stage_ends)


def _stage_begins(event: str, value, **kw) -> None:
    if event in _STAGES:
        on_thread.depth += 1
        if on_thread.depth == 1 and _STAGES[event] == "backend_s":
            on_thread.request = ["uncached", 0.0]


def _cache_event(event: str, **kw) -> None:
    outcome = _CACHE_EVENTS.get(event)
    if outcome and on_thread.request is not None:
        on_thread.request[0] = outcome


def _stage_ends(event: str, seconds: float, **kw) -> None:
    if event == _CACHE_READ:
        if on_thread.request is not None:
            on_thread.request[1] += seconds
        return
    stage = _STAGES.get(event)
    if stage is None:
        return
    # A jit called inside a traced function traces inside the outer trace,
    # and a function lowered by tracing it traces inside the lowering: only
    # the outermost stage counts, so that the stages never sum past the wall.
    on_thread.depth = max(on_thread.depth - 1, 0)
    if on_thread.depth:
        return
    request, on_thread.request = on_thread.request, None
    try:
        use = _open_use()
        if use is not None:
            use.book(stage, seconds, request)
    except Exception:  # a fault of the ledger's must never fail a compile
        log.exception("program ledger: dropped a %s event", stage)


def _open_use() -> "FirstUse | None":
    """The first use open on this thread's scope (a scheduler's launch phase
    begins one at the first thing it hears), or None."""
    scope = on_thread.scope
    return scope.first_use() if scope is not None else None


def layer_traced() -> None:
    """The Python of a trunk's layer body ran (models/decoder.py ``_trunk``
    calls this from inside it, so only while a program is traced): one more
    ``layer_traces`` for the first use open on the tracing thread."""
    try:
        use = _open_use()
        if use is not None:
            use.entry["layer_traces"] += 1
    except Exception:  # as the listeners: never fail a trace
        log.exception("program ledger: dropped a layer trace")


class FirstUse:
    """One first use while it is open: its entry in the ledger, when its
    scope began and when its launch ended, and the longest compile heard so
    far (the entry answers for that one's ``outcome``).  A scheduler's
    timeline keeps it beside the launch phase that holds the compile; a lane
    with no timeline uses it as the scope itself::

        with clock.open(model, "predict", key, seen=seen) as use:
            launch; use.launched(); fetch
    """

    __slots__ = ("entry", "t0_ns", "launched_ns", "longest_s", "_outer")

    def __init__(self, entry: dict, t0_ns: int | None = None):
        self.entry = entry
        self.t0_ns = time.perf_counter_ns() if t0_ns is None else t0_ns
        self.launched_ns: int | None = None
        self.longest_s = -1.0
        self._outer = None

    def first_use(self) -> "FirstUse":
        return self

    def book(self, stage: str, seconds: float, request: list | None) -> None:
        """One outermost stage of one compile (the listeners call this)."""
        e = self.entry
        if request is not None:
            # The backend's stage holds the cache's read: book the two apart.
            outcome, read_s = request
            if seconds > self.longest_s:
                self.longest_s, e["outcome"] = seconds, outcome
            e["compiles"] += 1
            e["cache_read_s"] += read_s
            seconds = max(seconds - read_s, 0.0)
        e[stage] += seconds

    def launched(self, t1_ns: int | None = None) -> None:
        """The scope that held the compile has ended: its wall, and the one
        log line a first use leaves."""
        self.launched_ns = time.perf_counter_ns() if t1_ns is None else t1_ns
        e = self.entry
        e["launch_s"] = (self.launched_ns - self.t0_ns) / 1e9
        fields = {k: round(v, 4) if isinstance(v, float) else v
                  for k, v in e.items() if k != "first_run_s"}
        (log.warning if e["cause"] == "retrace" else log.info)(
            "program first use", extra={"fields": fields})

    def ran(self, t1_ns: int) -> None:
        """The first fetch of the program has returned."""
        self.entry["first_run_s"] = (t1_ns - self.launched_ns) / 1e9

    def __enter__(self) -> "FirstUse":
        self._outer, on_thread.scope = on_thread.scope, self
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        on_thread.scope = self._outer
        now = time.perf_counter_ns()
        if self.launched_ns is None:
            self.launched(now)
        else:
            self.ran(now)


class CompileClock:
    """The ledger of program first uses, for every lane.

    One entry for each first use of a jitted program: ``{model, program, key,
    outcome, cause, compiles, layer_traces, trace_s, lower_s, cache_read_s,
    backend_s, launch_s, first_run_s, round}``.  ``key`` is what made the
    program new (prompt bucket, padded batch, the prompt attention's form;
    for ``:predict`` the bucket).  ``outcome`` is the persistent cache's
    answer to the compile that took longest: ``hit``, ``miss``, or
    ``uncached`` where no request used the cache.  ``cause`` says why the
    lane met a new program:
    ``first`` (its first of that kind), ``shape`` (a key new to a kind in
    use: a new bucket or padded batch), ``retrace`` (a key the lane had
    compiled: a jit cache lost or a weak-type flip, never expected).
    ``layer_traces`` counts how often the Python of a decoder trunk's layer
    body ran while the program was traced (:func:`layer_traced`): 1 a trunk
    where every layer called the one traced body, the family's ``layers``
    where each was traced anew (a layer whose tree or dtypes differ from its
    neighbours'), 0 for a program with no trunk.
    ``backend_s`` is JAX's ``backend_compile_duration`` less ``cache_read_s``:
    on a hit the hashing of the module for its key and the bookkeeping round
    the read, on a miss XLA's compile.  ``launch_s`` is the wall of the scope
    that held the compile (small eager programs compiled inside it fold into
    the entry and count in ``compiles``), ``first_run_s`` runs from there to
    the return of the first fetch of that program (None where a program has
    no fetch of its own).  ``round`` is the scheduler's round (None on a lane
    that has none).  All on ``perf_counter_ns``, the clock of the scheduler's
    phases.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.entries: list[dict] = []  # guarded-by: _lock

    def open(self, model: str, program: str, key: dict, *, seen: set,
             round: int | None = None, t0_ns: int | None = None) -> FirstUse:
        """Begin the entry of a first use.  ``seen`` is the lane's own set of
        what it has compiled (a rebuilt lane starts anew)."""
        kind, exact = (model, program), (model, program,
                                         json.dumps(key, sort_keys=True))
        cause = ("retrace" if exact in seen
                 else "shape" if kind in seen else "first")
        seen.update((kind, exact))
        entry = {"model": model, "program": program, "key": dict(key),
                 "outcome": "uncached", "cause": cause, "compiles": 0,
                 "layer_traces": 0, "trace_s": 0.0, "lower_s": 0.0,
                 "cache_read_s": 0.0, "backend_s": 0.0, "launch_s": None,
                 "first_run_s": None, "round": round}
        with self._lock:
            self.entries.append(entry)
        return FirstUse(entry, t0_ns)

    @staticmethod
    def seconds_of(entry: dict) -> float:
        return (entry["launch_s"] or 0.0) + (entry["first_run_s"] or 0.0)

    def snapshot(self) -> list[dict]:
        """The entries so far, as they leave the process (copies)."""
        with self._lock:
            return [dict(e) for e in self.entries]

    @property
    def total_seconds(self) -> float:
        return sum(self.seconds_of(e) for e in self.snapshot())

    def per_model(self) -> dict[str, dict]:
        """{model: {entries, seconds}}: the /metrics breakdown, and the
        history the lifecycle manager's cold-activation estimate reads
        (serving/lifecycle.py)."""
        out: dict[str, dict] = {}
        for e in self.snapshot():
            m = out.setdefault(e["model"], {"entries": 0, "seconds": 0.0})
            m["entries"] += 1
            m["seconds"] = round(m["seconds"] + self.seconds_of(e), 3)
        return out

    def programs(self, model: str) -> dict:
        """One model's first uses since boot, summed: the ``programs`` block
        of ``/metrics`` ``generation[model]``."""
        mine = [e for e in self.snapshot() if e["model"] == model]
        out = {"first_uses": len(mine)}
        for k in ("trace_s", "lower_s", "cache_read_s"):
            out[k] = round(sum(e[k] for e in mine), 6)
        # A compile the cache did not serve is XLA's own, cached or not.
        out["backend_hit_s"] = round(sum(
            e["backend_s"] for e in mine if e["outcome"] == "hit"), 6)
        out["backend_miss_s"] = round(sum(
            e["backend_s"] for e in mine if e["outcome"] != "hit"), 6)
        for k in ("launch_s", "first_run_s"):
            out[k] = round(sum(e[k] or 0.0 for e in mine), 6)
        return out

    def first_uses(self) -> dict[tuple[str, str, str], int]:
        """``{(model, program, outcome): first uses}``, the Prometheus
        counter's samples."""
        out: dict[tuple[str, str, str], int] = {}
        for e in self.snapshot():
            k = (e["model"], e["program"], e["outcome"])
            out[k] = out.get(k, 0) + 1
        return out
