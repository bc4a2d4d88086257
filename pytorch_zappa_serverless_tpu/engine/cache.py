"""Persistent XLA compilation cache and the program store — the cold-start killers.

The reference's cold start is dominated by dependency + weight fetch (tens of
seconds, SURVEY §3.1); ours would be dominated by XLA compilation.  JAX's
persistent compilation cache writes every compiled executable to disk keyed by
the *lowered module* (with flags and platform), so a warm pool VM restarting
the server skips XLA's compile — the TPU-native analogue of Zappa keep-warm
(SURVEY §3.4) — but still runs the Python of every program and lowers it, only
to learn that the executable was already on disk.  The **program store**
beside it (:class:`ProgramStore`, a subdirectory of the same cache) keeps a
generation lane's executables by a digest of *what each program was built
from* (:func:`lane_basis`), so a warm boot of an unchanged tree traces and
lowers nothing: what it still pays for a program is the digest (milliseconds)
and the executable's load onto the device, the same ``deserialize_executable``
JAX's own retrieval ends in.  A first boot, and the first boot after any
change to the package's source, the configuration or the installation, pays
what it paid before and the serializing besides.  Cold-start compile time is a
first-class BASELINE metric, so :class:`CompileClock` keeps one entry for
every first use of a program, whichever lane made it and whichever store
answered, with the stages ``jax.monitoring`` times from inside (trace, lower,
cache read, backend) beside the launch's own wall.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import struct
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


# What the program store counts a model (:class:`StoredProgram`).
STORE_COUNTS = ("hits", "misses", "failed_loads", "fallbacks")

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

    def program(self) -> None:
        """No lane's launch phase: nothing for the program store to key."""
        return None

    def book(self, stage: str, seconds: float, request: list | None) -> None:
        """One outermost stage of one compile (the listeners call this)."""
        e = self.entry
        if request is not None:
            # The backend's stage holds the cache's read: book the two apart.
            outcome, read_s = request
            if seconds > self.longest_s:
                self.longest_s, e["outcome"] = seconds, outcome
                e["restored"] = "compile_cache" if outcome == "hit" else None
            e["compiles"] += 1
            e["cache_read_s"] += read_s
            seconds = max(seconds - read_s, 0.0)
        e[stage] += seconds

    def restored(self, lookup_s: float, load_s: float) -> None:
        """The program store answered (no compile, so no listener speaks):
        the digest and the look-up are the hit's ``backend_s``, the file's
        read and the executable's load its ``cache_read_s``."""
        e = self.entry
        e["outcome"], e["restored"] = "hit", "program_store"
        self.longest_s = max(self.longest_s, load_s)
        e["backend_s"] += lookup_s
        e["cache_read_s"] += load_s

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

    One entry for each first use of a program: ``{model, program, key,
    outcome, restored, cause, compiles, layer_traces, trace_s, lower_s,
    cache_read_s, backend_s, launch_s, first_run_s, round}``.  ``key`` is what made the
    program new (prompt bucket, padded batch, the prompt attention's form;
    for ``:predict`` the bucket).  ``outcome`` is the persistent cache's
    answer to the compile that took longest: ``hit``, ``miss``, or
    ``uncached`` where no request used the cache; a program the program store
    restored is a ``hit`` with no compile at all.  ``restored`` says which
    store answered: ``program_store`` (nothing traced or lowered),
    ``compile_cache`` (JAX's, after the trace and the lowering) or None (XLA
    compiled it).  ``cause`` says why the
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
    the read (for a program the store restored, its digest and look-up; its
    ``cache_read_s`` is the file's read and the executable's load), on a miss
    XLA's compile and the executable's serializing into the store.
    ``launch_s`` is the wall of the scope
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
        # {model: {hits, misses, failed_loads, fallbacks}} of the program
        # store (:class:`StoredProgram` counts them).
        self.store_counts: dict[str, dict[str, int]] = {}  # guarded-by: _lock

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
                 "outcome": "uncached", "restored": None, "cause": cause,
                 "compiles": 0,
                 "layer_traces": 0, "trace_s": 0.0, "lower_s": 0.0,
                 "cache_read_s": 0.0, "backend_s": 0.0, "launch_s": None,
                 "first_run_s": None, "round": round}
        with self._lock:
            self.entries.append(entry)
        return FirstUse(entry, t0_ns)

    def count_store(self, model: str, what: str) -> None:
        with self._lock:
            counts = self.store_counts.setdefault(
                model, dict.fromkeys(STORE_COUNTS, 0))
            counts[what] += 1

    def store_snapshot(self) -> dict[str, dict[str, int]]:
        """``{model: {hits, misses, failed_loads, fallbacks}}`` (copies)."""
        with self._lock:
            return {m: dict(c) for m, c in self.store_counts.items()}

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
        out["program_store"] = self.store_snapshot().get(
            model, dict.fromkeys(STORE_COUNTS, 0))
        return out

    def first_uses(self) -> dict[tuple[str, str, str], int]:
        """``{(model, program, outcome): first uses}``, the Prometheus
        counter's samples."""
        out: dict[tuple[str, str, str], int] = {}
        for e in self.snapshot():
            k = (e["model"], e["program"], e["outcome"])
            out[k] = out.get(k, 0) + 1
        return out


# -- the program store ----------------------------------------------------------
#
# JAX's cache is keyed by the lowered module, so finding an executable there
# costs the program's trace and lowering at every boot.  The store keeps the
# same executables (``jax.experimental.serialize_executable``: the client's
# own ``serialize_executable`` beside what a ``Compiled`` needs to be called)
# under a digest of what a program was built from, which a lane can compute
# without running any of the program's Python.  Nothing checks an entry
# against its program afterwards, so the digest holds everything the
# executable depends on: see :func:`lane_basis` and :meth:`StoredProgram._digest`.

_ENTRY_MAGIC = b"tpuserve-program-1\n"
PACKAGE_ROOT = Path(__file__).resolve().parents[1]


@functools.cache
def source_digest(root: Path = PACKAGE_ROOT) -> str:
    """sha256 of every ``.py`` file under ``root``, path and bytes: any change
    to the package makes every entry miss (what a kernel-bearing program's
    cache key did at every PR anyway: its lowered text carries the kernel's
    source locations).  Once a process."""
    h = hashlib.sha256()
    for path in sorted(root.rglob("*.py")):
        h.update(path.relative_to(root).as_posix().encode() + b"\0")
        h.update(path.read_bytes() + b"\0")
    return h.hexdigest()


def installation() -> dict:
    """What of the process, beside the package's source, decides what a
    program compiles to: the versions, the backend and its devices, the
    compiler's flags from the environment, and every ``jax.config`` value
    (x64, the default matmul precision, the PRNG: what a jit's own key
    holds, and the rest with them)."""
    import importlib.metadata

    def version(name: str) -> str | None:
        try:
            return importlib.metadata.version(name)
        except importlib.metadata.PackageNotFoundError:
            return None

    devices = jax.devices()
    return {"versions": {n: version(n) for n in (
                "jax", "jaxlib", "libtpu", "numpy", "flax")},
            "platform": devices[0].platform,
            "platform_version": devices[0].client.platform_version,
            "device_kind": devices[0].device_kind, "devices": len(devices),
            "processes": jax.process_count(),
            "XLA_FLAGS": os.environ.get("XLA_FLAGS", ""),
            "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS", ""),
            "jax_config": {k: str(v) for k, v in jax.config.values.items()}}


def lane_basis(config: dict, options: dict, *,
               source: str | None = None) -> str:
    """The digest every program of one lane shares: the package's source,
    the installation, the servable's whole configuration and the lane's
    build options, in canonical JSON.  The programs close over all four and
    take only the parameters and the pool as arguments."""
    text = json.dumps({"source": source or source_digest(),
                       "installation": installation(),
                       "config": config, "options": options},
                      sort_keys=True, default=str)
    return hashlib.sha256(text.encode()).hexdigest()


def _leaf_signature(x) -> str:
    aval = jax.typeof(x)
    return (f"{aval.dtype}{list(aval.shape)}"
            f"{'~' if getattr(aval, 'weak_type', False) else ''}"
            f"@{getattr(x, 'sharding', 'host')}")


def signature(args) -> tuple[str, set]:
    """A call's arguments as the executable sees them: the tree's structure
    and each leaf's shape, dtype, weak type and sharding; and the devices
    the leaves that live on one lie on."""
    leaves, tree = jax.tree.flatten(args)
    devices = set()
    for x in leaves:
        if isinstance(x, jax.Array):
            devices |= x.sharding.device_set
    return f"{tree}|" + ";".join(map(_leaf_signature, leaves)), devices


class ProgramStore:
    """One directory of serialized executables, a file an entry.

    An entry is written to a temporary name and renamed, so a reader finds a
    whole file or none; one that fails to load all the same (truncated,
    written by another jaxlib) is a miss, logged once, and overwritten by the
    compile that follows."""

    def __init__(self, directory: str | Path):
        self.directory = Path(directory)
        self._complained: set[tuple[str, str]] = set()  # guarded-by: dispatch-serialized

    def path(self, digest: str) -> Path:
        return self.directory / f"{digest}.program"

    def load(self, digest: str, args, devices):
        """The entry's ``Compiled``, or None where there is no such file;
        raises what a damaged entry raises."""
        import zstandard
        from jax.experimental.serialize_executable import deserialize_and_load
        from jax.tree_util import PyTreeDef, default_registry

        try:
            data = self.path(digest).read_bytes()
        except FileNotFoundError:
            return None
        head = len(_ENTRY_MAGIC)
        if data[:head] != _ENTRY_MAGIC:
            raise ValueError("not an entry of this store")
        (n,) = struct.unpack_from("<Q", data, head)
        out_tree = PyTreeDef.deserialize_using_proto(
            default_registry, data[head + 8:head + 8 + n])
        return deserialize_and_load(
            zstandard.ZstdDecompressor().decompress(data[head + 8 + n:]),
            jax.tree.structure((args, {})), out_tree,
            execution_devices=list(devices))

    def save(self, digest: str, compiled) -> int:
        """Serialize ``compiled`` into its entry -> the file's bytes.  Raises
        where the backend or the program's trees cannot be serialized."""
        import zstandard
        from jax.experimental.serialize_executable import serialize

        payload, _, out_tree = serialize(compiled)
        tree = out_tree.serialize_using_proto()
        data = b"".join((_ENTRY_MAGIC, struct.pack("<Q", len(tree)), tree,
                         zstandard.ZstdCompressor(level=3).compress(payload)))
        self.directory.mkdir(parents=True, exist_ok=True)
        path = self.path(digest)
        tmp = path.with_name(
            f".{digest}.{os.getpid()}.{threading.get_ident()}.tmp")
        try:
            tmp.write_bytes(data)
            os.replace(tmp, path)
        finally:
            tmp.unlink(missing_ok=True)
        return len(data)

    def complain(self, what: str, digest: str, **fields) -> None:
        """One warning a digest and kind of fault."""
        if (what, digest) not in self._complained:
            self._complained.add((what, digest))
            log.warning(what, extra={"fields": {"entry": digest, **fields}},
                        exc_info=True)


def program_store() -> ProgramStore | None:
    """The store beside the compile cache this process set up
    (``<cache dir>/programs``: ``JAX_COMPILATION_CACHE_DIR`` moves both), or
    None before :func:`setup_compile_cache`."""
    return ProgramStore(Path(_configured) / "programs") if _configured else None


class StoredProgram:
    """A lane's jitted function whose executables the program store keeps.

    Called as the jitted function is.  Inside a scheduler's launch phase
    (the scope open on the thread, which names the program's key: the
    ledger's own ``(model, program, key)``) a key's first call looks its
    executable up by digest, loads it on a hit, and on a miss does ahead of
    time what ``jax.jit`` would have done (``lower(*args).compile()``,
    through the persistent cache as before) and serializes the result into
    the store; every later call of that key calls the loaded ``Compiled``.
    With no store, no such scope, arguments on several devices or a backend
    that cannot serialize, the call is the jitted function's, as it was
    (on the CPU also an executable JAX's cache restored: see ``_first``).

    A restored ``Compiled`` checks its arguments' types and shardings and
    raises where a ``jax.jit`` would trace again; such a launch falls back
    to the jitted function and is counted, never served wrong nor raised.
    """

    def __init__(self, name: str, fn, *, basis: str | None = None,
                 model: str = "", clock: CompileClock | None = None,
                 **jit_options):
        self.name = name
        self.jitted = jax.jit(fn, **jit_options)
        self.store = program_store() if basis is not None else None
        self.model, self.clock = model, clock
        # Everything of the digest but the arguments.
        self._basis = hashlib.sha256(json.dumps(
            [basis, name, jit_options], sort_keys=True,
            default=str).encode()).hexdigest()
        self._by_key: dict[str, object] = {}  # guarded-by: dispatch-serialized

    def __call__(self, *args):
        scope = on_thread.scope
        named = scope.program() if self.store and scope is not None else None
        if named is None:
            return self.jitted(*args)
        key = json.dumps(named[1], sort_keys=True)
        run = self._by_key.get(key)
        if run is None:
            run = self._by_key[key] = self._first(args)
        if run is not self.jitted:
            try:
                return run(*args)
            except (TypeError, ValueError) as e:
                # The executable was made for other types (a weak-type flip,
                # a key that does not decide the shapes): the arguments are
                # checked before anything is donated or run.
                self._count("fallbacks")
                self.store.complain(
                    "program store: fell back to the jitted function",
                    self._basis, program=self.name, key=key,
                    error=str(e)[:400])
        return self.jitted(*args)

    def __getattr__(self, name: str):
        """``lower``, ``trace``, ``eval_shape``: the jitted function's."""
        return getattr(self.jitted, name)

    def _count(self, what: str) -> None:
        if self.clock is not None:
            self.clock.count_store(self.model, what)

    def _digest(self, args) -> tuple[str, set]:
        described, devices = signature(args)
        return hashlib.sha256(
            f"{self._basis}|{described}".encode()).hexdigest(), devices

    def _first(self, args):
        """The callable for a key met for the first time: restored, or
        compiled ahead of time and stored.  The first use is opened in the
        ledger here: a restore fires no listener."""
        use = _open_use()
        t0 = time.perf_counter()
        digest, devices = self._digest(args)
        if len(devices) > 1:  # one program across devices: as it was
            return self.jitted
        devices = devices or {jax.devices()[0]}
        t1 = time.perf_counter()
        try:
            run = self.store.load(digest, args, devices)
        except Exception:
            run = None
            self._count("failed_loads")
            self.store.complain("program store: an entry failed to load and "
                                "is rewritten", digest, program=self.name)
        if run is not None:
            if use is not None:
                use.restored(t1 - t0, time.perf_counter() - t1)
            self._count("hits")
            return run
        self._count("misses")
        run = self.jitted.lower(*args).compile()
        if next(iter(devices)).platform == "cpu" and (
                use is None or use.entry["restored"] == "compile_cache"):
            # An executable JAX's cache restored serializes again whole on
            # the TPU (PERF.md section 6, PR 59) and not on the CPU, where
            # the second copy loads and then misses a function at its first
            # run: only what XLA compiled in this process is stored there.
            return run
        t2 = time.perf_counter()
        try:
            size = self.store.save(digest, run)
        except Exception:
            self.store.complain("program store: an executable could not be "
                                "stored", digest, program=self.name)
            return run
        stored_s = time.perf_counter() - t2
        if use is not None:
            use.entry["backend_s"] += stored_s
        log.info("program stored", extra={"fields": {
            "model": self.model, "program": self.name, "entry": digest,
            "bytes": size, "seconds": round(stored_s, 4)}})
        return run
