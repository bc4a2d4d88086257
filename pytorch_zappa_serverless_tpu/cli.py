"""CLI — the operator surface that replaces the ``zappa`` command set.

Zappa gives the reference ``deploy / update / tail / undeploy`` plus local
``flask run`` (SURVEY §1 L5, §3.5).  The TPU-native equivalents:

- ``serve``        run the serving stack locally (== ``flask run``)
- ``fleet``        run the fleet router fronting N replicas (docs/FLEET.md)
- ``warm``         build + AOT-compile everything, populating the persistent
                   compile cache, then exit — the warm-pool primer that makes
                   the next boot near-instant (== ``keep_warm``)
- ``list-models``  show the registered zoo
- ``deploy``       render deploy artifacts (Cloud Run + warm pool; see deploy/)
- ``stage``        build the deployable asset tree: convert checkpoints once,
                   copy labels/tokenizers, emit the staged config.yaml
                   (== the reference's S3 weight-staging script)
- ``tail``         follow the structured-log file (== ``zappa tail``)
"""

from __future__ import annotations

import argparse
import json
import sys

from .config import load_config


def _force_platform(name: str | None):
    """Pin the JAX platform before first device use (``--platform cpu`` for
    dev serving on the host CPU; unset, JAX picks — the TPU where one is
    attached)."""
    if name:
        import jax

        jax.config.update("jax_platforms", name)


def _select_device(args, cfg, what: str) -> None:
    """``--platform`` handling for the commands that serve: pin the backend
    when asked, then refuse to start on anything but a TPU unless the CPU
    was asked for by name (utils/device.py)."""
    from .parallel.mesh import init_distributed
    from .utils.device import require_tpu

    _force_platform(args.platform)
    # The check initialises the backend, which a multi-host world may only
    # do after joining; build_engine's own call is then a no-op.
    init_distributed(cfg.coordinator_address, cfg.num_processes,
                     cfg.process_id)
    require_tpu(args.platform, f"tpuserve {what}")


def cmd_serve(args) -> int:
    from .serving.server import run
    from .utils import boot

    cfg = load_config(args.config, args.profile)
    boot.stamp("import")
    _select_device(args, cfg, "serve")
    boot.stamp("backend")
    if args.port:
        cfg.port = args.port
    if args.host:
        cfg.host = args.host
    if getattr(args, "ingest_workers", None) is not None:
        cfg.ingest_workers = args.ingest_workers
    run(cfg)
    return 0


def cmd_fleet(args) -> int:
    """Run the fleet control plane (docs/FLEET.md): a router fronting N
    replicas — pre-existing (``--replicas url,url``) or spawned locally
    (``--spawn N``, one ``tpuserve serve`` subprocess per replica on
    ``spawn_base_port + i`` with its own journal subdirectory).
    """
    import os
    import subprocess
    from pathlib import Path

    from aiohttp import web

    from .serving.fleet import FleetRouter

    cfg = load_config(args.config, args.profile)
    fc = cfg.fleet
    if args.port:
        fc.port = args.port
    if args.host:
        fc.host = args.host
    if args.replicas:
        fc.replicas = [u.strip() for u in args.replicas.split(",")
                       if u.strip()]
    if args.spawn is not None:
        fc.spawn = args.spawn
    if args.disagg:
        fc.disagg = True
    if args.prefill_replicas:
        fc.prefill_replicas = [u.strip()
                               for u in args.prefill_replicas.split(",")
                               if u.strip()]
    urls = [str(u) for u in fc.replicas]
    spawned: dict[str, subprocess.Popen] = {}  # url -> process
    next_replica = [0]  # next --spawn-style replica index (scale-out too)

    def _spawn_replica() -> str:
        """Start one `tpuserve serve` subprocess on the next port — the
        boot-time --spawn path AND the router's scale-out hook
        (POST /admin/fleet/scale; docs/AUTOSCALE.md)."""
        i = next_replica[0]
        next_replica[0] += 1
        port = fc.spawn_base_port + i
        env = dict(os.environ)
        env["TPUSERVE_PORT"] = str(port)
        if args.platform != "cpu":
            # libtpu gives a chip to ONE process: pin replica i to chip i
            # of this host, as a one-chip world of its own.
            env.update(TPU_VISIBLE_CHIPS=str(i),
                       TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
                       TPU_PROCESS_BOUNDS="1,1,1")
        if cfg.journal_dir:
            # Per-replica journal: durability is a replica-local contract
            # (each journal replays into the process that owns it).
            env["TPUSERVE_JOURNAL_DIR"] = str(
                Path(cfg.journal_dir).expanduser() / f"replica-{i}")
        cmd = [sys.executable, "-m", "pytorch_zappa_serverless_tpu.cli",
               "serve"]
        if args.config:
            cmd += ["--config", args.config]
        if args.profile:
            cmd += ["--profile", args.profile]
        if args.platform:
            cmd += ["--platform", args.platform]
        url = f"http://127.0.0.1:{port}"
        spawned[url] = subprocess.Popen(cmd, env=env)
        return url

    for _ in range(fc.spawn):
        urls.append(_spawn_replica())
    if not urls:
        print("fleet: no replicas (configure fleet.replicas, pass "
              "--replicas, or --spawn N)", file=sys.stderr)
        return 2
    fc.replicas = urls
    router_ref: list = []

    def _signal(replica_id: str, kill: bool) -> bool:
        # Resolve rid → url → process through the LIVE registry, so
        # replicas spawned later by the scale actuator are killable too.
        r = router_ref[0].registry.get(replica_id) if router_ref else None
        proc = spawned.get(r.url) if r is not None else None
        if proc is None or proc.poll() is not None:
            return False
        proc.kill() if kill else proc.terminate()
        return True

    router = FleetRouter(
        fc,
        kill_hook=lambda rid: _signal(rid, kill=True),
        terminate_hook=lambda rid: _signal(rid, kill=False),
        spawn_hook=_spawn_replica if (fc.spawn or args.spawn is not None)
        else None)
    router_ref.append(router)
    try:
        web.run_app(router.app, host=fc.host, port=fc.port)
    finally:
        for proc in spawned.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in spawned.values():
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
    return 0


def cmd_warm(args) -> int:
    from .engine.loader import build_engine

    cfg = load_config(args.config, args.profile)
    _select_device(args, cfg, "warm")
    engine = build_engine(cfg, warmup=True)
    print(json.dumps({
        "cold_start_seconds": round(engine.cold_start_seconds, 3),
        "compile_seconds": round(engine.clock.total_seconds, 3),
        "executables": len(engine.clock.entries),
        "models": {k: v for k, v in engine.build_seconds.items()},
    }))
    engine.shutdown()
    return 0


def cmd_list_models(args) -> int:
    from . import models as _zoo  # noqa: F401
    from .utils.registry import list_models

    for name in list_models():
        print(name)
    return 0


def cmd_profile(args) -> int:
    """Capture a profile of live traffic on a running server (POST
    /admin/profile): where the capture lies, the device's busiest
    operations, its programs, and its idle time by host phase."""
    import urllib.request

    req = urllib.request.Request(
        args.url.rstrip("/") + "/admin/profile",
        data=json.dumps({"seconds": args.seconds}).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=args.seconds + 30) as resp:
        print(resp.read().decode())
    return 0


def format_models_table(payload: dict) -> str:
    """Render the ``GET /admin/models`` snapshot as the ``tpuserve models``
    table (docs/LIFECYCLE.md): residency state, tier, pin, HBM, LRU age —
    grouped by variant family, quality-descending (docs/VARIANTS.md), so
    each family's degradation ladder reads top-to-bottom."""
    cols = ("FAMILY", "Q", "MODEL", "STATE", "TIER", "PIN", "HBM_MB",
            "HOST_MB", "DISK_MB", "LAST_USED_S", "ACTIVATIONS", "EST_WARM_MS")
    rows = [cols]
    models = payload.get("models", {})
    order = sorted(models,
                   key=lambda n: (models[n].get("family") or n,
                                  -(models[n].get("quality_rank") or 0), n))
    for name in order:
        m = models[name]
        rows.append((
            m.get("family") or name,
            str(m.get("quality_rank", 0)),
            name,
            ("pinned" if m.get("pinned") else m.get("state", "?")),
            m.get("tier", "?"),
            "yes" if m.get("pinned") else "-",
            f"{(m.get('hbm_bytes') or 0) / (1024 * 1024):.1f}",
            f"{(m.get('host_bytes') or 0) / (1024 * 1024):.1f}",
            f"{(m.get('disk_bytes') or 0) / (1024 * 1024):.1f}",
            f"{m.get('last_used_s_ago', 0):.1f}",
            str(m.get("activations", 0)),
            f"{m.get('estimated_warm_ms', 0):.0f}",
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
             for r in rows]
    store = payload.get("ckpt_store")
    if store:
        lines.append(
            f"ckpt store: {store.get('manifests', 0)} manifests, "
            f"{(store.get('physical_bytes') or 0) / (1024 * 1024):.1f} MB on"
            f" disk ({(store.get('logical_bytes') or 0) / (1024 * 1024):.1f}"
            f" MB logical, dedup {store.get('dedup_ratio', 1.0):.2f}x), "
            f"{store.get('degraded_loads_total', 0)} degraded loads")
    total = payload.get("hbm_bytes_total")
    budget = payload.get("hbm_budget_bytes")
    if total is not None:
        lines.append(f"hbm: {total / (1024 * 1024):.1f} MB resident"
                     + (f" / {budget / (1024 * 1024):.1f} MB budget"
                        if budget else " (no budget)"))
    return "\n".join(lines)


def cmd_models(args) -> int:
    """Tabular residency view of a running server (GET /admin/models)."""
    import urllib.request

    req = urllib.request.Request(args.url.rstrip("/") + "/admin/models")
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = json.loads(resp.read().decode())
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(format_models_table(payload))
    return 0


def format_adapters_table(payload: dict) -> str:
    """Render ``GET /admin/adapters`` as the ``tpuserve adapters`` table
    (docs/ADAPTERS.md): per-tenant residency, slot, attach cost, traffic."""
    cols = ("MODEL", "ADAPTER", "STATE", "SLOT", "TENANTS", "HBM_KB",
            "LAST_USED_S", "ATTACHES", "SERVED", "EST_ATTACH_MS")
    rows = [cols]
    for base, adapters in sorted((payload.get("models") or {}).items()):
        for aname, a in sorted(adapters.items()):
            rows.append((
                base, aname, a.get("state", "?"),
                str(a.get("slot")) if a.get("slot") is not None else "-",
                ",".join(a.get("tenants") or ()) or "-",
                f"{(a.get('hbm_bytes') or 0) / 1024:.1f}",
                f"{a.get('last_used_s_ago', 0):.1f}",
                str(a.get("attaches", 0)),
                str(a.get("served", 0)),
                f"{a.get('estimated_attach_ms', 0):.0f}",
            ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
             for r in rows]
    mixed = payload.get("multi_adapter_batches")
    if mixed is not None:
        lines.append(f"co-batched dispatches with >1 adapter: {mixed}")
    return "\n".join(lines)


def cmd_adapters(args) -> int:
    """Tabular per-tenant view of a running server (GET /admin/adapters)."""
    import urllib.request

    req = urllib.request.Request(args.url.rstrip("/") + "/admin/adapters")
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = json.loads(resp.read().decode())
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(format_adapters_table(payload))
    return 0


def format_prefix_table(payload: dict) -> str:
    """Render ``GET /admin/prefix`` as the ``tpuserve prefix`` table
    (docs/PREFIX.md): per-model radix-tree size, hit rate, CoW/eviction
    traffic — the one-look answer to "is prefix reuse earning its pages"."""
    cols = ("MODEL", "NODES", "PAGES", "HITS", "MISSES", "HIT_RATE",
            "COW", "EVICTIONS", "RECLAIMABLE", "SHARED_NOW")
    rows = [cols]
    for model, p in sorted((payload.get("models") or {}).items()):
        rows.append((
            model, str(p.get("nodes", 0)), str(p.get("pages", 0)),
            str(p.get("hits", 0)), str(p.get("misses", 0)),
            f"{p.get('hit_rate', 0.0):.3f}",
            str(p.get("cow_copies", 0)), str(p.get("evictions", 0)),
            str(p.get("reclaimable_pages", 0)),
            str(p.get("kv_shared_blocks", 0)),
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
    return "\n".join("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
                     for r in rows)


def cmd_prefix(args) -> int:
    """Tabular prefix-cache view of a running server (GET /admin/prefix)."""
    import urllib.request

    req = urllib.request.Request(args.url.rstrip("/") + "/admin/prefix")
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = json.loads(resp.read().decode())
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(format_prefix_table(payload))
    return 0


def format_slo_table(payload: dict) -> str:
    """Render ``GET /admin/slo`` as the ``tpuserve slo`` table
    (docs/OBSERVABILITY.md §6): per-(key, lane) goodput, outcome counts,
    fast/slow burn with alarm flags, then the per-tenant usage ledger —
    works against a replica or a fleet router (same payload shape, the
    router's is the merged fleet view)."""
    cols = ("KEY", "LANE", "OBJ_MS", "TARGET", "GOOD", "DEGR", "LATE",
            "SHED", "ERR", "GOODPUT", "BURN_FAST", "BURN_SLOW", "ALARM")
    rows = [cols]
    for key, lanes in sorted((payload.get("models") or {}).items()):
        for lane, t in sorted(lanes.items()):
            obj = t.get("objective", {})
            wins = t.get("windows", {})
            fast, slow = wins.get("fast", {}), wins.get("slow", {})
            alarm = ("fast" if fast.get("alarm")
                     else "slow" if slow.get("alarm") else "-")
            gp = t.get("goodput_ratio")
            outcomes = t.get("outcomes", {})
            rows.append((
                key, lane,
                f"{obj.get('latency_objective_ms', 0):g}",
                f"{obj.get('availability_target', 0):g}",
                str(outcomes.get("good", 0)),
                str(outcomes.get("degraded", 0)),
                str(outcomes.get("late", 0)),
                str(outcomes.get("shed", 0)),
                str(outcomes.get("error", 0)),
                f"{gp:.3f}" if gp is not None else "-",
                f"{fast.get('burn_rate', 0):g}",
                f"{slow.get('burn_rate', 0):g}",
                alarm,
            ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
             for r in rows]
    usage = payload.get("usage") or {}
    if usage:
        ucols = ("TENANT", "REQS", "DEVICE_MS", "KV_BLOCK_S",
                 "PREFIX_SAVED_TOK", "ATTACHES", "ATTACH_MS")
        urows = [ucols]
        for key, row in sorted(usage.items()):
            urows.append((
                key, str(row.get("requests", 0)),
                f"{row.get('device_ms', 0):.1f}",
                f"{row.get('kv_block_seconds', 0):.1f}",
                str(row.get("prefix_saved_tokens", 0)),
                str(row.get("attaches", 0)),
                f"{row.get('attach_ms', 0):.1f}",
            ))
        uw = [max(len(r[i]) for r in urows) for i in range(len(ucols))]
        lines.append("")
        lines += ["  ".join(c.ljust(w) for c, w in zip(r, uw)).rstrip()
                  for r in urows]
    if payload.get("replicas_merged"):
        lines.append(f"fleet view: {payload['replicas_merged']} replicas "
                     "merged (burn rates recomputed from summed windows)")
    return "\n".join(lines)


def cmd_slo(args) -> int:
    """Tabular SLO/goodput view of a running server or fleet router
    (GET /admin/slo)."""
    import urllib.request

    req = urllib.request.Request(args.url.rstrip("/") + "/admin/slo")
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = json.loads(resp.read().decode())
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(format_slo_table(payload))
    return 0


def format_autoscale_table(payload: dict) -> str:
    """Render ``GET /admin/autoscale`` as the ``tpuserve autoscale`` table
    (docs/AUTOSCALE.md): per-key demand forecast, learned keep-warm window,
    next predicted arrival, and the planned pre-warm — then the plane's
    mode/degradation line and the pre-warm hit/miss counters."""
    cols = ("KEY", "ARRIVALS", "FORECAST_RPS", "KEEPWARM_S", "NEXT_IN_S",
            "LAST_SEEN_S", "PREWARMS", "PLANNED")
    rows = [cols]
    for key, m in sorted((payload.get("models") or {}).items()):
        def num(v, fmt="{:.2f}"):
            return fmt.format(v) if v is not None else "-"

        prewarms = sum((m.get("prewarms_by_cause") or {}).values())
        rows.append((
            key, str(m.get("arrivals", 0)),
            num(m.get("forecast_rps")),
            num(m.get("keepwarm_window_s"), "{:.1f}"),
            num(m.get("next_expected_in_s")),
            num(m.get("last_arrival_s_ago"), "{:.1f}"),
            str(prewarms),
            m.get("planned") or "-",
        ))
    widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
             for r in rows]
    c = payload.get("counters") or {}
    lines.append(
        f"mode: {payload.get('mode', '?')}"
        + (f" (degraded to reactive for "
           f"{payload.get('degraded_for_s')}s)" if payload.get("degraded")
           else "")
        + f"  prewarms: {c.get('prewarms', 0)}"
          f" (hits {c.get('prewarm_hits', 0)},"
          f" misses {c.get('prewarm_misses', 0)},"
          f" shed-on-budget {c.get('prewarm_shed_budget', 0)})")
    return "\n".join(lines)


def cmd_autoscale(args) -> int:
    """Tabular autoscaler view of a running server (GET /admin/autoscale)."""
    import urllib.request

    req = urllib.request.Request(args.url.rstrip("/") + "/admin/autoscale")
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = json.loads(resp.read().decode())
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(format_autoscale_table(payload))
    return 0


def format_perf_table(payload: dict) -> str:
    """Render ``GET /admin/perf`` as the ``tpuserve perf`` table
    (docs/OBSERVABILITY.md §9): event-loop lag, per-model rolling gauges
    (tok/s, samples/s, step, device util, MFU, ttft/itl), the per-model
    ingest-stage p50/p99 decomposition, then the top collapsed stacks —
    the one-look answer to "where does the host spend the http→device
    gap"."""
    from .serving.perfplane import INGEST_STAGES, hist_quantile

    lines = []
    lag = payload.get("loop_lag") or {}
    hist = lag.get("hist") or {}
    p50 = hist_quantile(hist, 0.5)
    p99 = hist_quantile(hist, 0.99)
    lines.append(
        f"loop lag: p50 {p50 if p50 is not None else '-'} ms  "
        f"p99 {p99 if p99 is not None else '-'} ms  "
        f"max {lag.get('max_ms', '-')} ms  ticks {lag.get('ticks', 0)}  "
        f"interval {lag.get('interval_s', '-')}s")
    models = payload.get("models") or {}
    if models:
        cols = ("MODEL", "SAMPLES/S", "TOK/S", "STEP_MS", "UTIL%", "MFU%",
                "TTFT_P50", "ITL_P50")
        rows = [cols]
        for name, g in sorted(models.items()):
            def num(key, fmt="{:.2f}"):
                v = g.get(key)
                return fmt.format(v) if v is not None else "-"

            rows.append((name, num("samples_per_s"), num("tokens_per_s"),
                         num("step_ms", "{:.3f}"), num("device_util_pct",
                                                       "{:.1f}"),
                         num("mfu_pct"), num("ttft_p50_ms"),
                         num("itl_p50_ms")))
        widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
        lines.append("")
        lines += ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
                  for r in rows]
    ingest = payload.get("ingest") or {}
    if ingest:
        cols = ("MODEL", "STAGE", "P50_MS", "P99_MS", "COUNT")
        rows = [cols]
        for model, stages in sorted(ingest.items()):
            ordered = [s for s in INGEST_STAGES if s in stages] + \
                [s for s in stages if s not in INGEST_STAGES]
            for stage in ordered:
                snap = stages[stage]
                q50, q99 = (hist_quantile(snap, q) for q in (0.5, 0.99))
                rows.append((model, stage,
                             f"{q50:.3f}" if q50 is not None else "-",
                             f"{q99:.3f}" if q99 is not None else "-",
                             str(snap.get("count", 0))))
        widths = [max(len(r[i]) for r in rows) for i in range(len(cols))]
        lines.append("")
        lines += ["  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
                  for r in rows]
    stacks = payload.get("stacks") or {}
    if stacks.get("stacks"):
        lines.append("")
        lines.append(f"top stacks ({stacks.get('samples', 0)} samples @ "
                     f"{stacks.get('hz', '-')} Hz):")
        for row in stacks["stacks"][:10]:
            stack = row["stack"]
            if len(stack) > 100:
                stack = "..." + stack[-97:]
            lines.append(f"  {row['pct']:5.1f}%  {row['seconds']:8.2f}s  "
                         f"{stack}")
    return "\n".join(lines)


def cmd_perf(args) -> int:
    """Tabular perf-plane view of a running server (GET /admin/perf)."""
    import urllib.request

    req = urllib.request.Request(args.url.rstrip("/")
                                 + f"/admin/perf?top={args.top}")
    with urllib.request.urlopen(req, timeout=10) as resp:
        payload = json.loads(resp.read().decode())
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        print(format_perf_table(payload))
    return 0


def cmd_stage(args) -> int:
    from .deploy.stage import stage_assets

    _force_platform(args.platform)
    cfg = load_config(args.config, args.profile)
    out = stage_assets(cfg, out_dir=args.out, mount_root=args.mount_root)
    print(json.dumps(out, indent=2))
    return 0


def cmd_tail(args) -> int:
    """Follow the structured-log file — the ``zappa tail`` equivalent.

    Reads the JSON-lines file the server writes when ``TPUSERVE_LOG_FILE``
    is set, pretty-printing one line per record with optional level/substring
    filters; ``-f`` keeps following like ``tail -f``.
    """
    import os
    import time as _time

    path = args.file or os.environ.get("TPUSERVE_LOG_FILE")
    if not path:
        print("no log file: pass a path or set TPUSERVE_LOG_FILE", file=sys.stderr)
        return 2

    levels = {"debug": 10, "info": 20, "warning": 30, "error": 40}
    min_level = levels.get(args.level, 20)

    def render(line: str):
        line = line.strip()
        if not line:
            return
        try:
            rec = json.loads(line)
        except ValueError:
            print(line)
            return
        if levels.get(str(rec.get("level", "info")), 20) < min_level:
            return
        if args.grep and args.grep not in line:
            return
        if getattr(args, "trace", None) and rec.get("trace_id") != args.trace:
            # --trace <id>: only this request's records — the grep an
            # /admin/trace investigation actually runs (OBSERVABILITY.md).
            return
        raw_ts = rec.pop("ts", None)
        try:
            ts = _time.strftime("%H:%M:%S", _time.localtime(float(raw_ts)))
        except (TypeError, ValueError):
            # Foreign record with a non-epoch ts (ISO string etc.): show as-is.
            ts = str(raw_ts) if raw_ts is not None else "--:--:--"
        level = str(rec.pop("level", "info")).upper()
        logger = rec.pop("logger", "-")
        msg = rec.pop("msg", "")
        rest = " ".join(f"{k}={json.dumps(v)}" for k, v in rec.items())
        print(f"{ts} {level:<7} {logger:<18} {msg}" + (f"  {rest}" if rest else ""))

    try:
        f = open(os.path.expanduser(path))
    except FileNotFoundError:
        print(f"log file not found: {path} (the server writes it once "
              f"TPUSERVE_LOG_FILE is set)", file=sys.stderr)
        return 2
    with f:
        if args.follow and not args.from_start:
            f.seek(0, os.SEEK_END)
        try:
            while True:
                line = f.readline()
                if line:
                    render(line)
                elif args.follow:
                    _time.sleep(0.25)
                else:
                    return 0
        except KeyboardInterrupt:
            return 0


def cmd_deploy(args) -> int:
    from .deploy.render import render_deploy

    cfg = load_config(args.config, args.profile)
    out = render_deploy(cfg, target=args.target, out_dir=args.out)
    print(json.dumps(out, indent=2))
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="tpuserve", description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--config", default=None, help="YAML/JSON config path")
        sp.add_argument("--profile", default=None, help="named profile (Zappa stage)")

    def platform_flag(sp):  # only on commands that touch devices
        sp.add_argument("--platform", default=None, choices=["cpu", "tpu"],
                        help="pin the JAX backend; without it serve/warm "
                             "require a TPU (cpu = dev serving on the host)")

    sp = sub.add_parser("serve", help="run the HTTP serving stack")
    common(sp)
    platform_flag(sp)
    sp.add_argument("--port", type=int, default=None)
    sp.add_argument("--host", default=None, help="bind address (0.0.0.0 for containers)")
    sp.add_argument("--ingest-workers", type=int, default=None,
                    help="SO_REUSEPORT acceptor worker processes on the "
                         "binary-lane ingest port (docs/SERVERPATH.md; "
                         "0 = single-process)")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("fleet", help="run the fleet router fronting N "
                                      "replicas (docs/FLEET.md)")
    common(sp)
    platform_flag(sp)
    sp.add_argument("--port", type=int, default=None, help="router port")
    sp.add_argument("--host", default=None, help="router bind address")
    sp.add_argument("--replicas", default=None,
                    help="comma-separated replica base URLs")
    sp.add_argument("--spawn", type=int, default=None,
                    help="spawn N local replica subprocesses")
    sp.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode with live KV "
                         "migration + KV-aware failover (docs/DISAGG.md)")
    sp.add_argument("--prefill-replicas", default=None,
                    help="comma-separated replica urls tagged "
                         "compute/prefill (disagg mode)")
    sp.set_defaults(fn=cmd_fleet)

    sp = sub.add_parser("warm", help="precompile all executables, then exit")
    common(sp)
    platform_flag(sp)
    sp.set_defaults(fn=cmd_warm)

    sp = sub.add_parser("list-models", help="print the registered model zoo")
    sp.set_defaults(fn=cmd_list_models)

    sp = sub.add_parser("models", help="residency table of a running server "
                                       "(state/tier/pin/HBM; docs/LIFECYCLE.md)")
    sp.add_argument("--url", default="http://127.0.0.1:8000")
    sp.add_argument("--json", action="store_true",
                    help="raw /admin/models JSON instead of the table")
    sp.set_defaults(fn=cmd_models)

    sp = sub.add_parser("adapters", help="per-tenant adapter residency "
                                         "table of a running server")
    sp.add_argument("--url", default="http://127.0.0.1:8000")
    sp.add_argument("--json", action="store_true",
                    help="raw /admin/adapters JSON instead of the table")
    sp.set_defaults(fn=cmd_adapters)

    sp = sub.add_parser("prefix", help="prefix KV cache table of a running "
                                       "server (nodes/pages/hit rate; "
                                       "docs/PREFIX.md)")
    sp.add_argument("--url", default="http://127.0.0.1:8000")
    sp.add_argument("--json", action="store_true",
                    help="raw /admin/prefix JSON instead of the table")
    sp.set_defaults(fn=cmd_prefix)

    sp = sub.add_parser("slo", help="SLO/goodput + usage-ledger table of a "
                                    "running server or fleet router "
                                    "(docs/OBSERVABILITY.md §6)")
    sp.add_argument("--url", default="http://127.0.0.1:8000")
    sp.add_argument("--json", action="store_true",
                    help="raw /admin/slo JSON instead of the table")
    sp.set_defaults(fn=cmd_slo)

    sp = sub.add_parser("autoscale", help="predictive-autoscaler table of a "
                                          "running server (forecast/keep-warm"
                                          "/planned pre-warms; "
                                          "docs/AUTOSCALE.md)")
    sp.add_argument("--url", default="http://127.0.0.1:8000")
    sp.add_argument("--json", action="store_true",
                    help="raw /admin/autoscale JSON instead of the table")
    sp.set_defaults(fn=cmd_autoscale)

    sp = sub.add_parser("perf", help="perf-plane table of a running server "
                                     "(loop lag, gauges, ingest stages, "
                                     "stacks; docs/OBSERVABILITY.md §9)")
    sp.add_argument("--url", default="http://127.0.0.1:8000")
    sp.add_argument("--top", type=int, default=20,
                    help="stack-table depth (server-side bound)")
    sp.add_argument("--json", action="store_true",
                    help="raw /admin/perf JSON instead of the table")
    sp.set_defaults(fn=cmd_perf)

    sp = sub.add_parser("profile", help="capture a jax.profiler trace from a running server")
    sp.add_argument("--url", default="http://127.0.0.1:8000")
    sp.add_argument("--seconds", type=float, default=2.0)
    sp.set_defaults(fn=cmd_profile)

    sp = sub.add_parser("deploy", help="render deploy artifacts")
    common(sp)
    sp.add_argument("--target", default="cloudrun", choices=["cloudrun", "local"])
    sp.add_argument("--out", default="deploy_out")
    sp.set_defaults(fn=cmd_deploy)

    sp = sub.add_parser("stage", help="build the deployable asset tree "
                                      "(convert checkpoints, copy assets)")
    common(sp)
    platform_flag(sp)
    sp.add_argument("--out", default="stage_out")
    sp.add_argument("--mount-root", default="/srv/assets",
                    help="path where the asset tree is mounted on serving hosts")
    sp.set_defaults(fn=cmd_stage)

    sp = sub.add_parser("tail", help="follow the structured-log file")
    sp.add_argument("file", nargs="?", default=None,
                    help="log file (default: $TPUSERVE_LOG_FILE)")
    sp.add_argument("-f", "--follow", action="store_true")
    sp.add_argument("--from-start", action="store_true",
                    help="with -f, print existing lines before following")
    sp.add_argument("--level", default="info",
                    choices=["debug", "info", "warning", "error"])
    sp.add_argument("--grep", default=None, help="only lines containing this substring")
    sp.add_argument("--trace", default=None, metavar="TRACE_ID",
                    help="only records stamped with this trace_id")
    sp.set_defaults(fn=cmd_tail)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
