"""Cold-start assembly: config → compiled, warm engine.

The reference's cold start imports ``app.py`` which loads one model as a
module side effect (SURVEY §3.1).  Here ``build_engine`` is the explicit
equivalent: enable the persistent compile cache, build every configured
servable (weight import or random-init), AOT-compile the bucket set, and
report cold-start timing — the BASELINE "cold-start compile time" metric.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from .. import models as _zoo  # noqa: F401  (imports register the model builders)
from ..config import ServeConfig
from ..utils.device import device_info
from ..utils.logging import get_logger, log_event
from ..utils.registry import get_model_builder
from .cache import CompileClock, setup_compile_cache
from .compiled import CompiledModel
from .runner import DeviceRunner

log = get_logger("engine.loader")


@dataclass
class Engine:
    models: dict[str, CompiledModel]
    runner: DeviceRunner
    clock: CompileClock
    cold_start_seconds: float = 0.0
    build_seconds: dict[str, float] = field(default_factory=dict)
    mesh: object | None = None  # jax.sharding.Mesh when ServeConfig.mesh is set
    # Multi-process worlds: the lockstep driver (parallel/lockstep.py).
    # Process 0 leads through CompiledModel.run_batch; other processes call
    # engine.lockstep.follow() instead of serving HTTP (cli serve does).
    lockstep: object | None = None
    # Set by shutdown(): makes teardown idempotent — the watchdog swap path
    # and the server's cleanup may both shut the same (old) engine down,
    # and a second lockstep shutdown broadcast would desync the followers.
    closed: bool = False

    def model(self, name: str) -> CompiledModel:
        try:
            return self.models[name]
        except KeyError:
            raise KeyError(f"model {name!r} not served; available: {sorted(self.models)}") from None

    # -- lifecycle attach/detach (serving/lifecycle.py) ----------------------
    def attach(self, name: str, cm: CompiledModel, nbytes: int | None = None):
        """Register an activated model (and its HBM accounting)."""
        self.models[name] = cm
        self.runner.track_model(name, cm.param_nbytes()
                                if nbytes is None else nbytes)

    def detach(self, name: str) -> CompiledModel | None:
        """Unregister a model (scale-to-zero / demotion); returns it so the
        caller can keep the host-tier copy."""
        self.runner.untrack_model(name)
        return self.models.pop(name, None)

    def enable_lockstep_lead(self):
        """Process 0, follower topology: mirror every run_batch dispatch.

        Opt-in (the HTTP server calls it) rather than automatic: the OTHER
        supported multi-host pattern — every host driving identical
        run_batch calls itself (tests/test_multihost.py's library surface)
        — must not have process 0 broadcasting to followers that are busy
        running their own dispatch.
        """
        import jax

        if jax.process_index() != 0 or self.lockstep is None:
            raise RuntimeError("lockstep lead is enabled on process 0 of a "
                               "multi-process world only")
        self.lockstep.lead_enabled = True
        for cm in self.models.values():
            cm.lockstep = self.lockstep

    def shutdown(self):
        if self.closed:
            return
        self.closed = True
        if self.lockstep is not None and self.lockstep.lead_enabled:
            import jax

            if jax.process_index() == 0:
                # On the dispatch thread: serializes after any in-flight
                # run_batch's collectives (an interleaved broadcast would
                # pair the followers' batch-zeros collective with the
                # shutdown header — structure mismatch or deadlock).
                try:
                    self.runner.run_fn_sync(self.lockstep.lead_shutdown,
                                            timeout=60.0)
                except Exception:
                    log.exception("lockstep shutdown broadcast failed; "
                                  "followers exit via their collective-"
                                  "failure path")
        self.runner.shutdown()


def lazy_effective(cfg: ServeConfig, mc) -> bool:
    """Whether this model defers its build to first request
    (docs/LIFECYCLE.md).  PINNED models and SPMD worlds (mesh /
    multi-process lockstep) always build eagerly — per-model attach/detach
    cannot be mirrored across hosts or re-sharded on the fly.
    """
    if mc.pinned:
        return False
    lazy = cfg.lazy_load if mc.lazy_load is None else bool(mc.lazy_load)
    if not lazy:
        return False
    if cfg.mesh or (cfg.coordinator_address and cfg.num_processes > 1):
        return False
    return True


def build_model(mc, clock: CompileClock, mesh=None, *,
                warmup: bool = True, params_stream=None,
                phases: dict | None = None) -> CompiledModel:
    """Build ONE servable + its compiled model (the per-model slice of
    :func:`build_engine`, shared with the lifecycle manager's on-demand
    activation path).

    ``params_stream`` is the streaming-checkpoint overlap hook
    (docs/LIFECYCLE.md): a zero-arg callable returning a device-resident
    param tree, started on a BACKGROUND thread before the servable builds.
    jit executables are keyed by avals, not values, so the builder's
    random-init params carry the warmup compile while the real weights
    stream off disk in parallel; the streamed tree (identical shapes) is
    swapped in before the model serves.  If the stream fails, the
    builder's own weight-import path already ran — the legacy whole-file
    fallback — so the model still activates.  ``phases``, when given, is
    filled with the ``load_ms``/``compile_ms`` split the activation
    record reports.
    """
    import threading

    stream_box: list = []
    stream_th = None
    t_load0 = time.perf_counter()
    if params_stream is not None:
        def _pull():
            t = time.perf_counter()
            try:
                params = params_stream()
                stream_box.append(("ok", params,
                                   (time.perf_counter() - t) * 1000.0))
            except Exception as e:  # degrade: keep the legacy-built params
                stream_box.append(("err", e, 0.0))

        stream_th = threading.Thread(target=_pull, name="ckpt-param-stream",
                                     daemon=True)
        stream_th.start()
    servable = get_model_builder(mc.builder or mc.name)(mc)
    if servable.name != mc.name:
        # Builder-aliased variant (``{name: gpt2_int8, builder: gpt2}``,
        # docs/VARIANTS.md): the deploy name owns the serving identity —
        # runner stats, metrics, and breaker state must never merge two
        # co-resident variants under the builder's hardcoded name.
        servable.name = mc.name
    t_built = time.perf_counter()
    cm = CompiledModel(servable, mc, clock, mesh=mesh)
    if warmup:
        cm.warmup()
    t_warm = time.perf_counter()
    if phases is not None:
        phases["compile_ms"] = (t_warm - t_built) * 1000.0
        phases["load_ms"] = (t_built - t_load0) * 1000.0
    if stream_th is not None:
        stream_th.join()
        status, payload, stream_ms = stream_box[0]
        if status == "ok":
            servable.params = payload
            if phases is not None:
                # Stream wall time, which ran CONCURRENTLY with the build
                # and compile above — load_ms + compile_ms may exceed the
                # activation wall clock; that overlap IS the win.
                phases["load_ms"] = stream_ms
                phases["streamed"] = True
        else:
            log.warning("param stream for %s failed (%s); serving the "
                        "legacy-built weights", mc.name, payload)
            if phases is not None:
                phases["streamed"] = False
    return cm


def build_engine(cfg: ServeConfig, *, warmup: bool | None = None) -> Engine:
    t0 = time.perf_counter()
    if cfg.coordinator_address and cfg.num_processes > 1:
        # Multi-host bootstrap BEFORE any device use: jax.devices() becomes
        # the global pool and the mesh below spans hosts (DCN).
        from ..parallel.mesh import init_distributed

        init_distributed(cfg.coordinator_address, cfg.num_processes,
                         cfg.process_id)
        import jax

        log_event(log, "distributed initialized",
                  process=jax.process_index(), processes=jax.process_count(),
                  global_devices=len(jax.devices()),
                  local_devices=len(jax.local_devices()))
    cache_dir = setup_compile_cache(cfg.compile_cache_dir)
    clock = CompileClock()
    runner = DeviceRunner()
    # QoS lane mode (docs/QOS.md): two-level priority unless the profile
    # opts back into the single FIFO.
    runner.set_priority(cfg.priority_dispatch)
    mesh = None
    if cfg.mesh:
        # ServeConfig.mesh, e.g. {"data": 4, "model": 2}: one mesh shared by
        # every servable; params go through the family TP rules, batches
        # shard over ``data`` (CompiledModel), XLA emits the collectives.
        from ..parallel.mesh import make_mesh

        mesh = make_mesh(dict(cfg.mesh))
        log_event(log, "mesh ready",
                  axes=dict(zip(mesh.axis_names, mesh.devices.shape)),
                  devices=int(mesh.devices.size))
    compiled: dict[str, CompiledModel] = {}
    build_seconds: dict[str, float] = {}
    warmup = cfg.warmup_at_boot if warmup is None else warmup
    for mc in cfg.models:
        if lazy_effective(cfg, mc):
            # Scale-to-zero boot (docs/LIFECYCLE.md): the model starts COLD;
            # the lifecycle manager activates it (single-flight) on first
            # demand, against the persistent compile cache.
            log_event(log, "model deferred (lazy_load)", model=mc.name)
            continue
        t1 = time.perf_counter()
        cm = build_model(mc, clock, mesh, warmup=warmup)
        compiled[mc.name] = cm
        build_seconds[mc.name] = round(time.perf_counter() - t1, 3)
        runner.track_model(mc.name, cm.param_nbytes())
        log_event(log, "model ready", model=mc.name, seconds=build_seconds[mc.name],
                  buckets=[list(b) for b in cm.buckets])
    cold = time.perf_counter() - t0
    log_event(log, "engine ready", cold_start_seconds=round(cold, 3),
              compile_seconds=round(clock.total_seconds, 3),
              models=sorted(compiled), device=device_info(),
              compile_cache_dir=cache_dir)
    engine = Engine(models=compiled, runner=runner, clock=clock,
                    cold_start_seconds=cold, build_seconds=build_seconds,
                    mesh=mesh)
    import jax

    if jax.process_count() > 1:
        # Multi-host world: the driver is built here; the follower TOPOLOGY
        # (process 0 leads every run_batch, others follow()) activates via
        # engine.enable_lockstep_lead() — the HTTP server does — so the
        # drive-run_batch-on-every-host library pattern keeps working.
        from ..parallel.lockstep import LockstepDriver

        engine.lockstep = LockstepDriver(engine)
    return engine
