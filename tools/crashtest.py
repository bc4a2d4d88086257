"""Crash-safety harness: kill -9 a serving process mid-backlog, restart,
assert zero acknowledged-job loss and zero double-runs.

The durability contract (docs/RESILIENCE.md "Durability & recovery"): every
``:submit`` a client saw a 202 for must reach a terminal status across a
``kill -9`` + restart, and resubmitting with the same ``Idempotency-Key``
after the crash must return the original job id instead of running the work
twice.  This script proves it end to end against the real CLI entrypoint:

1. boot ``tpuserve serve`` (CPU backend) with a journal dir and an injected
   600 ms dispatch latency so a backlog forms;
2. submit N jobs with idempotency keys, wait for a non-empty backlog;
3. ``SIGKILL`` the server (no drain, no cleanup — the warm-pool preemption);
4. restart against the same journal (clean profile, warm compile cache);
5. assert every acknowledged job id reaches ``done``, resubmits dedupe to
   the original ids, and the replay metrics moved.

Usable two ways: CLI (``python tools/crashtest.py --workdir /tmp/ct``) and
the tier-1 pytest case (``tests/test_crash_recovery.py``).
"""

from __future__ import annotations

import argparse
import io
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[1]

CONFIG_TEMPLATE = """\
default_profile: boot
profiles:
  boot:
    host: 127.0.0.1
    port: {port}
    compile_cache_dir: {workdir}/xla
    warmup_at_boot: true
    journal_dir: {workdir}/journal
    journal_fsync: always
    job_max_backlog: 64
    # 600 ms of injected dispatch latency per job: a backlog forms fast,
    # so the SIGKILL reliably lands with acknowledged-but-unfinished work.
    faults:
      {model}: {{latency_ms: 600}}
    models: &models
      - name: {model}
        batch_buckets: [1]
        dtype: float32
        coalesce_ms: 0.0
        extra: {{image_size: 64, resize_to: 72}}
  restart:
    host: 127.0.0.1
    port: {port}
    compile_cache_dir: {workdir}/xla
    warmup_at_boot: true
    journal_dir: {workdir}/journal
    journal_fsync: always
    job_max_backlog: 64
    models: *models
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(method: str, url: str, body: dict | None = None,
          headers: dict | None = None, timeout: float = 10.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json",
                                          **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


def _wait_ready(port: int, proc: subprocess.Popen, timeout_s: float) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if proc.poll() is not None:
            raise RuntimeError(
                f"server exited with rc={proc.returncode} before ready")
        try:
            status, _ = _http("GET", f"http://127.0.0.1:{port}/", timeout=2.0)
            if status == 200:
                return time.monotonic() - t0
        except (urllib.error.URLError, OSError, ValueError):
            pass
        time.sleep(0.25)
    raise TimeoutError(f"server not ready within {timeout_s:.0f}s")


def _tiny_jpeg_b64() -> str:
    import base64

    import numpy as np
    from PIL import Image

    arr = np.random.default_rng(0).integers(
        0, 255, (80, 100, 3)).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG")
    return base64.b64encode(buf.getvalue()).decode("ascii")


def _lockwatch_env(workdir: Path, tag: str) -> dict[str, str]:
    """Chaos runs double as lock-order sanitizer runs (docs/ANALYSIS.md):
    every spawned process records its actual lock-acquisition orders and
    dumps them once a second, so even a SIGKILL'd phase leaves evidence."""
    return {"TPUSERVE_LOCKWATCH": "1",
            "TPUSERVE_LOCKWATCH_OUT": str(workdir / f"lockwatch-{tag}.json")}


def _check_lockwatch(workdir: Path, out: dict) -> None:
    """Fold the spawned processes' sanitizer reports into the evidence;
    any recorded violation fails the run like a lost job would."""
    edges = 0
    for path in sorted(workdir.glob("lockwatch-*.json")):
        try:
            rep = json.loads(path.read_text())
        except ValueError:
            continue  # torn mid-rewrite by the kill — the .tmp never landed
        bad = rep.get("violations", []) + rep.get("static_violations", [])
        assert not bad, f"lockwatch violations in {path.name}: {bad}"
        edges += len(rep.get("edges", []))
    out["lockwatch_edges_observed"] = edges
    out["lockwatch_violations"] = 0


def _spawn(cfg_path: Path, profile: str, workdir: Path) -> subprocess.Popen:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           **_lockwatch_env(workdir, profile)}
    logf = open(workdir / f"server-{profile}.log", "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "pytorch_zappa_serverless_tpu.cli", "serve",
         "--config", str(cfg_path), "--profile", profile, "--platform", "cpu"],
        env=env, cwd=str(REPO_ROOT), stdout=logf, stderr=logf)


def run_crashtest(workdir: str | Path, n_jobs: int = 6,
                  model: str = "resnet18", boot_timeout_s: float = 300.0,
                  finish_timeout_s: float = 120.0) -> dict:
    """Run the full kill-9 scenario; returns the evidence dict.

    Raises AssertionError on any acknowledged-job loss or double run —
    callers (pytest / CLI) treat a clean return as a pass.
    """
    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    port = _free_port()
    cfg_path = workdir / "crashtest.yaml"
    cfg_path.write_text(CONFIG_TEMPLATE.format(
        port=port, workdir=workdir, model=model))
    base = f"http://127.0.0.1:{port}"
    payload_b64 = _tiny_jpeg_b64()
    out: dict = {"n_jobs": n_jobs, "model": model}

    # -- phase 1: boot, submit, SIGKILL mid-backlog --------------------------
    p1 = _spawn(cfg_path, "boot", workdir)
    acked: dict[str, str] = {}  # idempotency key -> acked job id
    try:
        out["boot_ready_s"] = round(_wait_ready(port, p1, boot_timeout_s), 2)
        for i in range(n_jobs):
            key = f"crash-{i}"
            status, body = _http(
                "POST", f"{base}/v1/models/{model}:submit",
                body={"b64": payload_b64, "idempotency_key": key})
            assert status == 202, f"submit {i} not acknowledged: {status} {body}"
            acked[key] = body["job"]["id"]
        # Wait until the backlog is provably non-empty (jobs acknowledged
        # but not finished), then kill without ceremony.
        deadline = time.monotonic() + 30.0
        backlog = 0
        while time.monotonic() < deadline:
            _, health = _http("GET", f"{base}/healthz", timeout=5.0)
            backlog = health.get("jobs_backlog", 0)
            if backlog >= max(n_jobs // 2, 1):
                break
            time.sleep(0.1)
        assert backlog >= 1, "no backlog formed; SIGKILL would prove nothing"
        out["backlog_at_kill"] = backlog
    finally:
        if p1.poll() is None:
            os.kill(p1.pid, signal.SIGKILL)
        p1.wait(timeout=30)

    # -- phase 2: restart, recover, verify ----------------------------------
    p2 = _spawn(cfg_path, "restart", workdir)
    try:
        out["restart_ready_s"] = round(_wait_ready(port, p2, boot_timeout_s), 2)
        _, m = _http("GET", f"{base}/metrics")
        dur = m.get("durability", {})
        out["recovered_jobs"] = dur.get("recovered_jobs", 0)
        out["restored_done"] = dur.get("restored_done", 0)
        out["replay_ms"] = dur.get("replay_ms", 0.0)
        # Every acknowledged id must reach a terminal "done" — zero loss.
        pending = dict(acked)
        deadline = time.monotonic() + finish_timeout_s
        while pending and time.monotonic() < deadline:
            for key, jid in list(pending.items()):
                status, body = _http("GET", f"{base}/v1/jobs/{jid}")
                assert status != 404, \
                    f"acknowledged job {jid} (key={key}) LOST across restart"
                job = body["job"]
                if job["status"] == "done":
                    pending.pop(key)
                elif job["status"] == "error":
                    raise AssertionError(
                        f"job {jid} (key={key}) failed after restart: "
                        f"{job.get('error')}")
            if pending:
                time.sleep(0.25)
        assert not pending, \
            f"{len(pending)} acknowledged jobs never finished: {pending}"
        out["completed"] = n_jobs
        out["lost"] = 0
        # Idempotent resubmit across the restart: same key → original id,
        # deduped (no second run of already-done work).
        dedupes = 0
        for key, jid in acked.items():
            status, body = _http(
                "POST", f"{base}/v1/models/{model}:submit",
                body={"b64": payload_b64, "idempotency_key": key})
            assert body.get("deduped") is True, \
                f"resubmit of {key} was not deduped: {status} {body}"
            assert body["job"]["id"] == jid, \
                f"resubmit of {key} returned {body['job']['id']}, not {jid}"
            dedupes += 1
        out["deduped_resubmits"] = dedupes
        _, m = _http("GET", f"{base}/metrics")
        out["deduped_submits_metric"] = (
            m.get("durability", {}).get("deduped_submits", 0))
    finally:
        if p2.poll() is None:
            os.kill(p2.pid, signal.SIGKILL)
        p2.wait(timeout=30)
    _check_lockwatch(workdir, out)
    return out


FLEET_CONFIG_TEMPLATE = """\
default_profile: replica
profiles:
  replica:
    host: 127.0.0.1
    port: 8000
    compile_cache_dir: {workdir}/xla
    warmup_at_boot: true
    journal_dir: {workdir}/journal-default
    journal_fsync: always
    job_max_backlog: 64
    drain_timeout_s: 10.0
    # 600 ms of injected dispatch latency per job: a backlog forms fast,
    # so the SIGKILL reliably lands with acknowledged-but-unfinished work.
    faults:
      {model}: {{latency_ms: 600}}
    fleet:
      poll_interval_s: 0.4
      connect_timeout_s: 1.0
      quarantine_after: 2
      failover_retries: 1
      breaker_threshold: 0.5
      breaker_min_samples: 4
    models:
      - name: {model}
        batch_buckets: [1]
        dtype: float32
        coalesce_ms: 0.0
        extra: {{image_size: 64, resize_to: 72}}
"""


def _spawn_replica(cfg_path: Path, workdir: Path, port: int,
                   journal: Path, tag: str) -> subprocess.Popen:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "TPUSERVE_PORT": str(port),
           "TPUSERVE_JOURNAL_DIR": str(journal),
           **_lockwatch_env(workdir, f"replica-{tag}")}
    logf = open(workdir / f"replica-{tag}.log", "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "pytorch_zappa_serverless_tpu.cli", "serve",
         "--config", str(cfg_path), "--profile", "replica",
         "--platform", "cpu"],
        env=env, cwd=str(REPO_ROOT), stdout=logf, stderr=logf)


def _spawn_router(cfg_path: Path, workdir: Path, port: int,
                  replica_urls: list[str],
                  extra: tuple[str, ...] = ()) -> subprocess.Popen:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           **_lockwatch_env(workdir, "router")}
    logf = open(workdir / "router.log", "ab")
    return subprocess.Popen(
        [sys.executable, "-m", "pytorch_zappa_serverless_tpu.cli", "fleet",
         "--config", str(cfg_path), "--profile", "replica",
         "--port", str(port), "--replicas", ",".join(replica_urls),
         *extra],
        env=env, cwd=str(REPO_ROOT), stdout=logf, stderr=logf)


def _wait_fleet_state(base: str, rid: str, want: set[str],
                      timeout_s: float) -> str:
    """Poll the router's /admin/fleet until replica ``rid`` reaches one of
    the ``want`` states; returns the state."""
    deadline = time.monotonic() + timeout_s
    state = "?"
    while time.monotonic() < deadline:
        try:
            _, fleet = _http("GET", f"{base}/admin/fleet", timeout=5.0)
            state = fleet["replicas"].get(rid, {}).get("state", "?")
            if state in want:
                return state
        except (urllib.error.URLError, OSError, ValueError):
            pass
        time.sleep(0.15)
    raise TimeoutError(f"replica {rid} never reached {want} "
                       f"(last: {state}) within {timeout_s:.0f}s")


def run_fleet_crashtest(workdir: str | Path, n_jobs: int = 8,
                        model: str = "resnet18",
                        boot_timeout_s: float = 300.0,
                        finish_timeout_s: float = 180.0) -> dict:
    """Fleet kill -9 scenario (docs/FLEET.md "Failure matrix"):

    boot 2 journaled replicas behind the router, build a job backlog
    across them, SIGKILL one replica mid-backlog, then prove: sync traffic
    through the router keeps succeeding within one failover retry; the
    router quarantines the dead replica (visible in ``/admin/fleet``);
    after a restart on the same journal the router re-admits it, every
    acknowledged job reaches ``done`` (zero loss), and same-key resubmits
    dedupe to the original job ids (zero double runs).
    """
    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    p1, p2, pr = _free_port(), _free_port(), _free_port()
    cfg_path = workdir / "fleetcrash.yaml"
    cfg_path.write_text(FLEET_CONFIG_TEMPLATE.format(
        workdir=workdir, model=model))
    urls = [f"http://127.0.0.1:{p1}", f"http://127.0.0.1:{p2}"]
    base = f"http://127.0.0.1:{pr}"
    payload_b64 = _tiny_jpeg_b64()
    out: dict = {"n_jobs": n_jobs, "model": model, "replicas": 2}

    r1 = _spawn_replica(cfg_path, workdir, p1, workdir / "journal-1", "1")
    r2 = _spawn_replica(cfg_path, workdir, p2, workdir / "journal-2", "2")
    router = None
    r1b = None  # the restarted replica 1
    try:
        out["replica_ready_s"] = round(max(
            _wait_ready(p1, r1, boot_timeout_s),
            _wait_ready(p2, r2, boot_timeout_s)), 2)
        router = _spawn_router(cfg_path, workdir, pr, urls)
        _wait_ready(pr, router, 60.0)
        # The registry maps urls in order: r0 ↔ p1, r1 ↔ p2.
        _wait_fleet_state(base, "r0", {"healthy"}, 30.0)
        _wait_fleet_state(base, "r1", {"healthy"}, 30.0)

        # -- build a backlog through the router ------------------------------
        acked: dict[str, tuple[str, str]] = {}  # key -> (job id, replica)
        for i in range(n_jobs):
            key = f"fleet-crash-{i}"
            status, body, headers = _http_h(
                "POST", f"{base}/v1/models/{model}:submit",
                body={"b64": payload_b64},
                headers={"Idempotency-Key": key})
            assert status == 202, f"submit {i} not acked: {status} {body}"
            acked[key] = (body["job"]["id"], headers.get("X-Fleet-Replica"))
        by_replica: dict[str, int] = {}
        for _, (jid, rid) in acked.items():
            by_replica[rid] = by_replica.get(rid, 0) + 1
        out["acked_by_replica"] = by_replica
        # Kill whichever replica holds acknowledged work (prefer r0).
        victim_rid = max(by_replica, key=by_replica.get)
        victim_proc, victim_port, victim_journal, victim_tag = {
            "r0": (r1, p1, workdir / "journal-1", "1"),
            "r1": (r2, p2, workdir / "journal-2", "2")}[victim_rid]
        # Wait until the victim provably has an unfinished backlog.
        deadline = time.monotonic() + 30.0
        backlog = 0
        while time.monotonic() < deadline:
            _, health = _http(
                "GET", f"http://127.0.0.1:{victim_port}/healthz", timeout=5.0)
            backlog = health.get("jobs_backlog", 0)
            if backlog >= 1:
                break
            time.sleep(0.1)
        assert backlog >= 1, "no backlog on the victim; kill proves nothing"
        out["victim"] = victim_rid
        out["backlog_at_kill"] = backlog
        t_kill = time.monotonic()
        os.kill(victim_proc.pid, signal.SIGKILL)
        victim_proc.wait(timeout=30)

        # -- sync traffic fails over within one retry ------------------------
        failover_ok = 0
        for i in range(4):
            status, body, headers = _http_h(
                "POST", f"{base}/v1/models/{model}:predict",
                body={"b64": payload_b64}, timeout=60.0)
            assert status == 200, \
                f"predict after kill failed: {status} {body}"
            attempts = int(headers.get("X-Fleet-Attempts", "9"))
            assert attempts <= 2, \
                f"failover took {attempts} attempts (> 1 retry)"
            failover_ok += 1
        out["failover_predicts_ok"] = failover_ok
        out["first_failover_s"] = round(time.monotonic() - t_kill, 2)

        # -- the router quarantines the dead replica -------------------------
        out["quarantined_state"] = _wait_fleet_state(
            base, victim_rid, {"quarantined"}, 30.0)
        # Polling a job acked by the dead replica: 503 + Retry-After (the
        # journal owns it), NEVER a 404 that reads as data loss.
        victim_keys = [k for k, (jid, rid) in acked.items()
                       if rid == victim_rid]
        jid0 = acked[victim_keys[0]][0]
        status, body, headers = _http_h("GET", f"{base}/v1/jobs/{jid0}",
                                        timeout=30.0)
        assert status in (503, 200), \
            f"dead-replica job poll: {status} {body}"
        if status == 503:
            assert headers.get("Retry-After"), "503 job poll missing Retry-After"

        # -- restart the victim on its journal; router re-admits -------------
        r1b = _spawn_replica(cfg_path, workdir, victim_port, victim_journal,
                             victim_tag + "-restart")
        _wait_ready(victim_port, r1b, boot_timeout_s)
        out["readmitted_state"] = _wait_fleet_state(
            base, victim_rid, {"healthy"}, 60.0)
        out["kill_to_readmit_s"] = round(time.monotonic() - t_kill, 2)

        # -- zero acknowledged-job loss via the router ------------------------
        pending = {k: jid for k, (jid, _) in acked.items()}
        deadline = time.monotonic() + finish_timeout_s
        while pending and time.monotonic() < deadline:
            for key, jid in list(pending.items()):
                status, body, _h = _http_h("GET", f"{base}/v1/jobs/{jid}",
                                           timeout=10.0)
                assert status != 404, \
                    f"acked job {jid} (key={key}) LOST across the fleet kill"
                job = body.get("job", {})
                if job.get("status") == "done":
                    pending.pop(key)
                elif job.get("status") == "error":
                    raise AssertionError(
                        f"job {jid} (key={key}) failed: {job.get('error')}")
            if pending:
                time.sleep(0.25)
        assert not pending, \
            f"{len(pending)} acked jobs never finished: {sorted(pending)}"
        out["completed"] = n_jobs
        out["lost"] = 0

        # -- zero double runs: resubmits dedupe to the original ids ----------
        dedupes = 0
        for key, (jid, _) in acked.items():
            status, body, _h = _http_h(
                "POST", f"{base}/v1/models/{model}:submit",
                body={"b64": payload_b64},
                headers={"Idempotency-Key": key}, timeout=30.0)
            assert body.get("deduped") is True, \
                f"resubmit of {key} not deduped: {status} {body}"
            assert body["job"]["id"] == jid, \
                f"resubmit of {key} returned {body['job']['id']}, not {jid}"
            dedupes += 1
        out["deduped_resubmits"] = dedupes

        # -- fleet metrics recorded the story --------------------------------
        _, m = _http("GET", f"{base}/metrics")
        fleet = m.get("fleet", {})
        out["failovers"] = fleet.get("failovers", {})
        out["quarantines"] = {
            rid: r.get("quarantines", 0)
            for rid, r in fleet.get("replicas", {}).items()}
        assert sum(out["failovers"].values()) >= 1, "no failovers recorded"
        assert out["quarantines"].get(victim_rid, 0) >= 1, \
            "victim quarantine not recorded"
    finally:
        for proc in (router, r1, r2, r1b):
            if proc is not None and proc.poll() is None:
                os.kill(proc.pid, signal.SIGKILL)
        for proc in (router, r1, r2, r1b):
            if proc is not None:
                proc.wait(timeout=30)
    _check_lockwatch(workdir, out)
    return out


VARIANT_CONFIG_TEMPLATE = """\
default_profile: replica
profiles:
  replica:
    host: 127.0.0.1
    port: 8000
    compile_cache_dir: {workdir}/xla
    warmup_at_boot: true
    lazy_load: true
    journal_dir: {workdir}/journal-default
    journal_fsync: always
    job_max_backlog: 64
    brownout: auto
    # 600 ms of injected dispatch latency on the preferred rung: a backlog
    # forms fast on the replica where it is warm, so the SIGKILL lands with
    # acknowledged-but-unfinished work.
    faults:
      rn_full: {{latency_ms: 600}}
    fleet:
      poll_interval_s: 0.4
      connect_timeout_s: 1.0
      quarantine_after: 2
      failover_retries: 1
      breaker_threshold: 0.5
      breaker_min_samples: 4
    models:
      - name: rn_full
        builder: resnet18
        family: rn
        quality_rank: 2
        batch_buckets: [1]
        dtype: float32
        coalesce_ms: 0.0
        extra: {{image_size: 64, resize_to: 72}}
      - name: rn_lite
        builder: resnet18
        family: rn
        quality_rank: 1
        batch_buckets: [1]
        dtype: float32
        coalesce_ms: 0.0
        extra: {{image_size: 64, resize_to: 72}}
"""


def run_variant_crashtest(workdir: str | Path, n_jobs: int = 6,
                          boot_timeout_s: float = 300.0,
                          finish_timeout_s: float = 180.0) -> dict:
    """Variant-family kill -9 scenario (docs/VARIANTS.md "Chaos"):

    two lazy replicas behind the router; the preferred rung (``rn_full``)
    is activated ONLY on replica A, the cheap rung (``rn_lite``) only on
    replica B.  A backlog of acknowledged ``rn_full`` jobs builds on A,
    then A is SIGKILLed — the only replica with the preferred variant
    warm.  Family-addressed predicts with a ``max_latency_ms`` objective
    must KEEP SERVING through the router, answered by B's ``rn_lite``
    (``X-Served-Variant`` + ``X-Degraded`` prove the degrade); after A
    restarts on its journal every acknowledged job reaches ``done`` (zero
    loss) and same-key resubmits dedupe (zero double runs).
    """
    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    p1, p2, pr = _free_port(), _free_port(), _free_port()
    cfg_path = workdir / "variantcrash.yaml"
    cfg_path.write_text(VARIANT_CONFIG_TEMPLATE.format(workdir=workdir))
    urls = [f"http://127.0.0.1:{p1}", f"http://127.0.0.1:{p2}"]
    base = f"http://127.0.0.1:{pr}"
    payload_b64 = _tiny_jpeg_b64()
    objective = {"X-Objective-Max-Latency-Ms": "2000"}
    out: dict = {"n_jobs": n_jobs, "family": "rn", "replicas": 2}

    ra = _spawn_replica(cfg_path, workdir, p1, workdir / "journal-1", "1")
    rb = _spawn_replica(cfg_path, workdir, p2, workdir / "journal-2", "2")
    router = None
    rab = None  # the restarted replica A
    try:
        out["replica_ready_s"] = round(max(
            _wait_ready(p1, ra, boot_timeout_s),
            _wait_ready(p2, rb, boot_timeout_s)), 2)
        # Asymmetric warmth: A owns the preferred rung, B the cheap one.
        status, _ = _http("POST", f"http://127.0.0.1:{p1}"
                          "/admin/models/rn_full",
                          body={"action": "activate"}, timeout=300.0)
        assert status == 200, "rn_full activation on A failed"
        status, _ = _http("POST", f"http://127.0.0.1:{p2}"
                          "/admin/models/rn_lite",
                          body={"action": "activate"}, timeout=300.0)
        assert status == 200, "rn_lite activation on B failed"
        router = _spawn_router(cfg_path, workdir, pr, urls)
        _wait_ready(pr, router, 60.0)
        _wait_fleet_state(base, "r0", {"healthy"}, 30.0)
        _wait_fleet_state(base, "r1", {"healthy"}, 30.0)

        # -- backlog of acknowledged PREFERRED-rung jobs on A ----------------
        acked: dict[str, str] = {}
        for i in range(n_jobs):
            key = f"variant-crash-{i}"
            status, body, headers = _http_h(
                "POST", f"{base}/v1/models/rn_full:submit",
                body={"b64": payload_b64},
                headers={"Idempotency-Key": key})
            assert status == 202, f"submit {i} not acked: {status} {body}"
            acked[key] = body["job"]["id"]
        deadline = time.monotonic() + 30.0
        backlog = 0
        while time.monotonic() < deadline:
            _, health = _http("GET", f"http://127.0.0.1:{p1}/healthz",
                              timeout=5.0)
            backlog = health.get("jobs_backlog", 0)
            if backlog >= 1:
                break
            time.sleep(0.1)
        assert backlog >= 1, "no backlog on A; kill proves nothing"
        out["backlog_at_kill"] = backlog

        # -- kill the ONLY replica with the preferred variant warm -----------
        t_kill = time.monotonic()
        os.kill(ra.pid, signal.SIGKILL)
        ra.wait(timeout=30)

        # -- family-addressed traffic keeps serving, degraded ----------------
        degraded_served = 0
        for i in range(4):
            status, body, headers = _http_h(
                "POST", f"{base}/v1/models/rn:predict",
                body={"b64": payload_b64}, headers=objective, timeout=60.0)
            assert status == 200, \
                f"family predict after kill SHED: {status} {body}"
            assert headers.get("X-Served-Variant") == "rn_lite", \
                f"expected rn_lite to serve, got {headers}"
            if headers.get("X-Degraded"):
                degraded_served += 1
        assert degraded_served >= 1, "no degraded serve recorded"
        out["degraded_predicts_ok"] = degraded_served
        out["first_degraded_serve_s"] = round(time.monotonic() - t_kill, 2)
        out["quarantined_state"] = _wait_fleet_state(
            base, "r0", {"quarantined"}, 30.0)

        # -- restart A on its journal: zero acked loss, zero double runs -----
        rab = _spawn_replica(cfg_path, workdir, p1, workdir / "journal-1",
                             "1-restart")
        _wait_ready(p1, rab, boot_timeout_s)
        out["readmitted_state"] = _wait_fleet_state(
            base, "r0", {"healthy"}, 60.0)
        pending = dict(acked)
        deadline = time.monotonic() + finish_timeout_s
        while pending and time.monotonic() < deadline:
            for key, jid in list(pending.items()):
                status, body, _h = _http_h("GET", f"{base}/v1/jobs/{jid}",
                                           timeout=10.0)
                assert status != 404, \
                    f"acked job {jid} (key={key}) LOST across the kill"
                if body.get("job", {}).get("status") == "done":
                    pending.pop(key)
            if pending:
                time.sleep(0.25)
        assert not pending, \
            f"{len(pending)} acked jobs never finished: {sorted(pending)}"
        out["completed"] = n_jobs
        out["lost"] = 0
        dedupes = 0
        for key, jid in acked.items():
            status, body, _h = _http_h(
                "POST", f"{base}/v1/models/rn_full:submit",
                body={"b64": payload_b64},
                headers={"Idempotency-Key": key}, timeout=30.0)
            assert body.get("deduped") is True and body["job"]["id"] == jid, \
                f"resubmit of {key} not deduped: {status} {body}"
            dedupes += 1
        out["deduped_resubmits"] = dedupes
        _, m = _http("GET", f"{base}/metrics")
        out["fleet_degraded"] = m.get("fleet", {}).get("degraded", {})
        assert sum(out["fleet_degraded"].values()) >= 1, \
            "router recorded no degraded serves"
    finally:
        for proc in (router, ra, rb, rab):
            if proc is not None and proc.poll() is None:
                os.kill(proc.pid, signal.SIGKILL)
        for proc in (router, ra, rb, rab):
            if proc is not None:
                proc.wait(timeout=30)
    _check_lockwatch(workdir, out)
    return out


DISAGG_CONFIG_TEMPLATE = """\
default_profile: replica
profiles:
  replica:
    host: 127.0.0.1
    port: 8000
    compile_cache_dir: {workdir}/xla
    warmup_at_boot: true
    drain_timeout_s: 10.0
    # 150 ms of injected dispatch latency: every decode tick (and every
    # migration page copy) is slowed, so the SIGKILL reliably lands with
    # the stream mid-decode on the decode replica.
    faults:
      gpt2: {{latency_ms: 150}}
    fleet:
      poll_interval_s: 0.4
      connect_timeout_s: 1.0
      quarantine_after: 2
      failover_retries: 1
      breaker_threshold: 0.5
      breaker_min_samples: 4
    models:
      - name: gpt2
        dtype: float32
        batch_buckets: [1]
        seq_buckets: [16]
        coalesce_ms: 0.0
        kv_cache: paged
        kv_block_size: 4
        extra:
          max_new_tokens: 16
          gen_slots: 2
          segment_tokens: 2
          arch:
            d_model: 32
            layers: 2
            heads: 2
            ffn_dim: 128
            vocab_size: 500
            max_positions: 96
"""


class _SSEStream:
    """Incremental SSE reader over http.client (stdlib-only, like the rest
    of this harness)."""

    def __init__(self, port: int, path: str, body: dict,
                 timeout: float = 120.0):
        import http.client

        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)
        self.conn.request("POST", path, body=json.dumps(body),
                          headers={"Content-Type": "application/json"})
        self.resp = self.conn.getresponse()
        self.buf = b""

    def next_event(self) -> dict | None:
        """One parsed data event, or None at EOF/severed transport."""
        while True:
            while b"\n\n" in self.buf:
                raw, self.buf = self.buf.split(b"\n\n", 1)
                for line in raw.splitlines():
                    if line.startswith(b"data: "):
                        return json.loads(line[6:])
            try:
                chunk = self.resp.read1(65536)
            except Exception:
                return None
            if not chunk:
                return None
            self.buf += chunk

    def close(self):
        try:
            self.conn.close()
        except Exception:
            pass


def run_disagg_crashtest(workdir: str | Path,
                         boot_timeout_s: float = 300.0) -> dict:
    """Disaggregated kill -9 scenario (docs/DISAGG.md; ISSUE 13):

    three paged-gpt2 replicas behind the router in disagg mode (replica 1
    tagged prefill).  A greedy :generate stream prefills on the compute
    replica, live-migrates its KV pages to a decode replica at the first
    token, and streams from there; mid-stream the decode replica is
    SIGKILLed.  The router must resume the stream on a peer from the
    journaled pages and the emitted-token watermark — the client's full
    token sequence is byte-identical to an undisturbed reference run of
    the same prompt (zero token loss, zero duplicate SSE tokens).
    """
    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    p1, p2, p3, pr = (_free_port() for _ in range(4))
    cfg_path = workdir / "disaggcrash.yaml"
    cfg_path.write_text(DISAGG_CONFIG_TEMPLATE.format(workdir=workdir))
    urls = [f"http://127.0.0.1:{p}" for p in (p1, p2, p3)]
    base = f"http://127.0.0.1:{pr}"
    out: dict = {"replicas": 3, "model": "gpt2"}
    prompt = list(range(5, 15))
    gen_body = {"input_ids": prompt, "max_new_tokens": 16}

    procs = {
        "r0": _spawn_replica(cfg_path, workdir, p1, workdir / "journal-1",
                             "1"),
        "r1": _spawn_replica(cfg_path, workdir, p2, workdir / "journal-2",
                             "2"),
        "r2": _spawn_replica(cfg_path, workdir, p3, workdir / "journal-3",
                             "3"),
    }
    ports = {"r0": p1, "r1": p2, "r2": p3}
    router = None
    stream = None
    try:
        out["replica_ready_s"] = round(max(
            _wait_ready(p, proc, boot_timeout_s)
            for p, proc in ((p1, procs["r0"]), (p2, procs["r1"]),
                            (p3, procs["r2"]))), 2)
        router = _spawn_router(cfg_path, workdir, pr, urls,
                               extra=("--disagg",
                                      "--prefill-replicas", urls[0]))
        _wait_ready(pr, router, 60.0)
        for rid in ("r0", "r1", "r2"):
            _wait_fleet_state(base, rid, {"healthy"}, 30.0)

        # -- reference: the same prompt, undisturbed (it also proves the
        # prefill→decode migration itself streams correctly) -------------
        ref_stream = _SSEStream(pr, "/v1/models/gpt2:generate", gen_body)
        assert ref_stream.resp.status == 200, ref_stream.resp.status
        ref_tokens, ref_done = [], None
        while True:
            ev = ref_stream.next_event()
            assert ev is not None, "reference stream severed"
            if "token" in ev:
                ref_tokens.append(ev["token"])
            if ev.get("done"):
                ref_done = ev
                break
            assert "error" not in ev, f"reference stream errored: {ev}"
        ref_stream.close()
        assert len(ref_tokens) == 16, f"reference short: {len(ref_tokens)}"
        assert ref_done["tokens"] == ref_tokens
        out["reference_tokens"] = len(ref_tokens)
        _, fleet = _http("GET", f"{base}/admin/fleet", timeout=10.0)
        assert fleet["metrics"]["migrations"].get("prefill", 0) >= 1, \
            "reference run recorded no prefill→decode migration"

        # -- chaos stream: kill the decode replica mid-stream -------------
        stream = _SSEStream(pr, "/v1/models/gpt2:generate", gen_body)
        assert stream.resp.status == 200, stream.resp.status
        sid = stream.resp.headers.get("X-Stream-Id")
        assert sid, "router exposed no X-Stream-Id"
        tokens = []
        while len(tokens) < 4:
            ev = stream.next_event()
            assert ev is not None and "error" not in ev, f"early end: {ev}"
            if "token" in ev:
                tokens.append(ev["token"])
        # The journal names the decode replica that owns the stream now.
        deadline = time.monotonic() + 20.0
        decode_rid = None
        while time.monotonic() < deadline and decode_rid is None:
            _, fleet = _http("GET", f"{base}/admin/fleet", timeout=10.0)
            decode_rid = (fleet.get("streams", {}).get(sid) or {}).get(
                "replica")
            if decode_rid is None:
                time.sleep(0.1)
        assert decode_rid and decode_rid != "r0", \
            f"stream not on a decode replica: {decode_rid}"
        out["decode_replica"] = decode_rid
        t_kill = time.monotonic()
        os.kill(procs[decode_rid].pid, signal.SIGKILL)
        procs[decode_rid].wait(timeout=30)

        # -- the stream must finish elsewhere, byte-identical -------------
        done = None
        while True:
            ev = stream.next_event()
            assert ev is not None, \
                "stream severed after the kill (no resume, no error event)"
            assert "error" not in ev, f"stream errored after kill: {ev}"
            if "token" in ev:
                tokens.append(ev["token"])
            if ev.get("done"):
                done = ev
                break
        out["kill_to_done_s"] = round(time.monotonic() - t_kill, 2)
        assert tokens == ref_tokens, \
            (f"token sequence diverged after failover "
             f"(loss or duplicates): got {tokens} want {ref_tokens}")
        assert done["tokens"] == ref_tokens
        out["tokens_after_kill"] = len(tokens)
        out["lost"] = 0
        out["duplicates"] = 0

        # -- the router recorded the KV-aware failover --------------------
        _, fleet = _http("GET", f"{base}/admin/fleet", timeout=10.0)
        mig = fleet["metrics"]["migrations"]
        out["migrations"] = mig
        out["failovers"] = fleet["metrics"]["failovers"]
        assert mig.get("failover", 0) >= 1, "no failover migration recorded"
        assert out["failovers"].get("kv_failover", 0) >= 1, \
            "no kv_failover recorded"
        resumed_on = (fleet.get("streams", {}).get(sid) or {}).get("replica")
        assert resumed_on and resumed_on != decode_rid, \
            f"stream journal still points at the dead replica {resumed_on}"
        out["resumed_on"] = resumed_on
    finally:
        if stream is not None:
            stream.close()
        for proc in [router, *procs.values()]:
            if proc is not None and proc.poll() is None:
                os.kill(proc.pid, signal.SIGKILL)
        for proc in [router, *procs.values()]:
            if proc is not None:
                proc.wait(timeout=30)
    _check_lockwatch(workdir, out)
    return out


def _http_h(method: str, url: str, body: dict | None = None,
            headers: dict | None = None, timeout: float = 10.0):
    """Like _http but returns response headers too, and folds HTTP error
    statuses into the return value (the fleet scenario ASSERTS on 503s —
    they are evidence, not failures)."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json",
                                          **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode()), dict(
                resp.headers)
    except urllib.error.HTTPError as e:
        raw = e.read()
        try:
            parsed = json.loads(raw.decode())
        except (ValueError, UnicodeDecodeError):
            parsed = {"raw": raw.decode(errors="replace")}
        return e.code, parsed, dict(e.headers)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workdir", default=None,
                    help="scratch dir (default: a fresh tempdir)")
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--fleet", action="store_true",
                    help="fleet mode: 2 replicas + router, kill one replica "
                         "(docs/FLEET.md)")
    ap.add_argument("--variants", action="store_true",
                    help="variant mode: kill the only replica with the "
                         "preferred variant warm; the fleet must serve "
                         "degraded with zero acked loss (docs/VARIANTS.md)")
    ap.add_argument("--disagg", action="store_true",
                    help="disagg mode: prefill + decode replicas + router; "
                         "kill -9 the decode replica mid-stream — the "
                         "stream resumes elsewhere from migrated pages "
                         "with zero token loss (docs/DISAGG.md)")
    args = ap.parse_args(argv)
    workdir = args.workdir
    if workdir is None:
        import tempfile

        workdir = tempfile.mkdtemp(prefix="tpuserve-crashtest-")
    try:
        if args.disagg:
            result = run_disagg_crashtest(workdir)
        elif args.variants:
            result = run_variant_crashtest(workdir,
                                           n_jobs=max(args.jobs, 4))
        elif args.fleet:
            result = run_fleet_crashtest(workdir, n_jobs=max(args.jobs, 4),
                                         model=args.model)
        else:
            result = run_crashtest(workdir, n_jobs=args.jobs,
                                   model=args.model)
    except AssertionError as e:
        print(json.dumps({"ok": False, "error": str(e)}))
        return 1
    print(json.dumps({"ok": True, **result}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
