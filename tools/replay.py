#!/usr/bin/env python
"""Trace-driven load replay: production-shaped traffic against a live stack.

ROADMAP item 4 names this harness the thing "every later scale claim gets
measured on": synthetic traces with the two invocation shapes "Serverless in
the Wild" (PAPERS.md) documents for real serverless fleets —

- **diurnal**: a smooth day/night rate curve (sinusoidal modulation of a
  Poisson process) — the shape keep-warm policies are tuned against;
- **bursty**: the Azure-functions shape — most applications are nearly
  idle, a heavy-tailed few dominate invocations, and arrivals cluster into
  on/off bursts rather than spreading uniformly.  Modeled as per-model
  burst episodes (exponential gaps between episodes, geometric burst
  sizes, tight intra-burst spacing) over a thin Poisson background.

The replayer fires each request at its trace offset (open-loop: a slow
server does NOT slow the offered load — that is the point) against a server
or fleet router, then reports the SLO story (docs/OBSERVABILITY.md §6):

- **attainment** — fraction of offered requests that were served within the
  latency objective;
- **goodput vs throughput** — good req/s vs served req/s vs offered req/s
  (a stack can have high throughput and terrible goodput; only goodput
  pays);
- **cold-hit rate** — 503 ``cold_start`` / ``adapter_cold`` answers per
  offered request (the scale-to-zero tax the keep-warm policy should
  shrink);
- latency p50/p99 of served requests, shed/error counts, degraded serves.

Usage (CLI, against any running server/router)::

    python tools/replay.py --url http://localhost:8000 --model resnet18 \
        --shape bursty --duration 30 --rps 20

Importable: ``synth_trace`` and ``replay_async`` are used by the tier-1
replay against a live server (``tests/test_slo.py``); ``summarize`` turns
raw outcomes into the report.
Traces are deterministic per seed so reruns are comparable.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

SHAPES = ("diurnal", "bursty", "uniform")


def synth_trace(shape: str, duration_s: float, rps: float,
                models: list[str], seed: int = 0,
                period_s: float | None = None) -> list[dict]:
    """Deterministic arrival trace: ``[{"t": offset_s, "model": name}]``.

    ``rps`` is the MEAN offered rate over the whole trace; ``models`` are
    drawn per arrival (weighted toward the head of the list for the bursty
    shape — the heavy-tailed "few apps dominate" skew).  ``period_s``
    controls the diurnal cycle (default: one full cycle per trace).
    """
    if shape not in SHAPES:
        raise ValueError(f"shape must be one of {SHAPES}, got {shape!r}")
    if not models:
        raise ValueError("models must be non-empty")
    rng = np.random.default_rng(seed)
    n_total = max(int(duration_s * rps), 1)
    times: list[float] = []
    picks: list[str] = []
    if shape == "uniform":
        times = list(np.sort(rng.uniform(0.0, duration_s, n_total)))
        picks = [models[int(i)] for i in
                 rng.integers(0, len(models), len(times))]
    elif shape == "diurnal":
        # Thinned Poisson process: rate(t) = rps * (1 + 0.8 sin(2πt/T)).
        period = period_s or duration_s
        peak = rps * 1.8
        t, raw = 0.0, []
        while t < duration_s and len(raw) < n_total * 4:
            t += float(rng.exponential(1.0 / peak))
            if rng.random() < (1.0 + 0.8 * math.sin(
                    2.0 * math.pi * t / period)) * rps / peak:
                raw.append(t)
        times = [x for x in raw if x < duration_s]
        picks = [models[int(i)] for i in
                 rng.integers(0, len(models), len(times))]
    else:  # bursty — the Azure-functions shape
        # Zipf-ish model weights: the head model dominates, the tail is
        # nearly idle (exactly the skew that makes scale-to-zero pay and
        # cold hits hurt).
        weights = np.array([1.0 / (i + 1) ** 1.5
                            for i in range(len(models))])
        weights /= weights.sum()
        # Background trickle (20% of volume) + burst episodes (80%).
        n_bg = max(n_total // 5, 1)
        for t in np.sort(rng.uniform(0.0, duration_s, n_bg)):
            times.append(float(t))
            picks.append(models[int(rng.choice(len(models), p=weights))])
        budget = n_total - n_bg
        t = 0.0
        mean_gap = duration_s / max(budget / 8.0, 1.0)
        while budget > 0:
            t += float(rng.exponential(mean_gap))
            if t >= duration_s:
                break
            model = models[int(rng.choice(len(models), p=weights))]
            size = min(int(rng.geometric(1.0 / 8.0)), budget)
            for j in range(size):
                # Tight intra-burst spacing: the whole episode lands inside
                # a fraction of a second — concurrency, not a drizzle.
                times.append(min(t + j * float(rng.uniform(0.005, 0.05)),
                                 duration_s))
                picks.append(model)
            budget -= size
        order = np.argsort(times)
        times = [times[int(i)] for i in order]
        picks = [picks[int(i)] for i in order]
    return [{"t": round(float(t), 4), "model": m}
            for t, m in zip(times, picks)]


async def replay_async(send, trace: list[dict], speedup: float = 1.0,
                       clock=time.perf_counter, sleep=asyncio.sleep
                       ) -> list[dict]:
    """Fire the trace open-loop; returns one outcome dict per request.

    ``send(item) -> {"status": int, "latency_ms": float, "cold": bool,
    "degraded": bool, "retry_after_s": float | None}`` is the transport —
    the CLI wraps aiohttp against a URL, a test wraps a TestClient.
    Arrivals are scheduled at ``t / speedup``; a request whose slot has
    already passed fires immediately (open-loop lag is part of the story,
    not hidden by back-pressure).
    """
    t0 = clock()
    outcomes: list[dict] = []

    async def one(item: dict):
        delay = item["t"] / max(speedup, 1e-9) - (clock() - t0)
        if delay > 0:
            await sleep(delay)
        started = clock()
        try:
            out = await send(item)
        except Exception as e:  # transport failure = an errored request
            out = {"status": 599, "latency_ms": (clock() - started) * 1e3,
                   "cold": False, "degraded": False,
                   "error": f"{type(e).__name__}: {e}"}
        out["model"] = item["model"]
        out["t"] = item["t"]
        outcomes.append(out)

    await asyncio.gather(*[one(item) for item in trace])
    outcomes.sort(key=lambda o: o["t"])
    return outcomes


def summarize(outcomes: list[dict], duration_s: float,
              objective_ms: float | None = None) -> dict:
    """The replay report: attainment, goodput vs throughput, cold hits.

    A request is *good* when it was served (2xx) within ``objective_ms``
    (None → every served request is on time) — the same rule the server's
    SLO plane applies (serving/slo.py), so replay attainment and
    ``/admin/slo`` goodput agree on definitions.
    """
    offered = len(outcomes)
    served = [o for o in outcomes if 200 <= o["status"] < 300]
    shed = [o for o in outcomes if o["status"] in (429, 503, 504)]
    errors = [o for o in outcomes
              if o["status"] >= 500 and o["status"] != 503]
    cold = [o for o in outcomes if o.get("cold")]
    degraded = [o for o in served if o.get("degraded")]
    good = [o for o in served
            if objective_ms is None or o["latency_ms"] <= objective_ms]
    lat = sorted(o["latency_ms"] for o in served)

    def pctl(p):
        if not lat:
            return None
        return round(lat[min(int(len(lat) * p / 100), len(lat) - 1)], 2)

    dur = max(duration_s, 1e-9)
    return {
        "offered": offered,
        "served": len(served),
        "good": len(good),
        "degraded": len(degraded),
        "shed": len(shed),
        "errors": len(errors),
        "cold_hits": len(cold),
        "slo_attainment": round(len(good) / offered, 4) if offered else None,
        "cold_hit_rate": round(len(cold) / offered, 4) if offered else None,
        "offered_rps": round(offered / dur, 2),
        "throughput_rps": round(len(served) / dur, 2),
        "goodput_rps": round(len(good) / dur, 2),
        "goodput_vs_throughput": (round(len(good) / len(served), 4)
                                  if served else None),
        "latency_p50_ms": pctl(50),
        "latency_p99_ms": pctl(99),
        **({"objective_ms": objective_ms} if objective_ms else {}),
    }


def retrying_sender(send, *, max_attempts: int = 12,
                    wait_cap_s: float = 0.25, clock=time.perf_counter,
                    sleep=asyncio.sleep):
    """Client-perceived transport: retry sheds/colds per Retry-After.

    The raw open-loop outcome counts a cold 503 as one fast failure; a real
    client retries it, so the *time to an answer* at a burst head is the
    cold-start tax the keep-warm policy is supposed to remove.  This
    wrapper makes that tax measurable: ``latency_ms`` becomes first-send →
    final answer (retry waits included, capped at ``wait_cap_s`` per
    attempt), ``cold`` records whether the FIRST attempt hit a cold start,
    ``attempts`` how many sends it took.  Used by the ``--policy-sweep``
    mode so p99 reflects what clients feel under each policy.
    """
    async def retry_send(item: dict) -> dict:
        t0 = clock()
        out: dict = {}
        cold_first = False
        attempts = 0
        for attempt in range(max_attempts):
            out = await send(item)
            attempts = attempt + 1
            if attempt == 0:
                cold_first = bool(out.get("cold"))
            if out.get("status") not in (429, 503):
                break
            ra = out.get("retry_after_s")
            await sleep(min(float(ra), wait_cap_s) if ra else wait_cap_s)
        out = dict(out)
        out["latency_ms"] = round((clock() - t0) * 1000.0, 3)
        out["cold"] = cold_first
        out["attempts"] = attempts
        return out
    return retry_send


# -- policy sweep (docs/AUTOSCALE.md) -----------------------------------------

POLICIES = ("fixed", "histogram", "predictive")

# ServeConfig deltas per scaling policy — everything else (models, budget,
# timers, compile cache) is held identical so the comparison isolates the
# policy (serving/autoscale.py MODES).
POLICY_OVERRIDES = {
    "fixed": {"autoscale": "off"},
    "histogram": {"autoscale": "histogram"},
    "predictive": {"autoscale": "predictive"},
}


def sweep_verdict(per_policy: dict) -> dict:
    """The comparison the acceptance bar reads: does the predictive policy
    beat the fixed-timer baseline on cold-hit rate AND client p99?"""
    fixed = per_policy.get("fixed") or {}
    pred = per_policy.get("predictive") or {}

    def get(d, k):
        v = d.get(k)
        return float(v) if v is not None else None

    out: dict = {}
    for key, better_low in (("cold_hit_rate", True), ("latency_p99_ms", True),
                            ("goodput_rps", False)):
        f, p = get(fixed, key), get(pred, key)
        out[key] = {"fixed": f, "predictive": p,
                    "predictive_better": (None if f is None or p is None
                                          else (p < f if better_low
                                                else p > f))}
    chr_ok = out["cold_hit_rate"]["predictive_better"]
    p99_ok = out["latency_p99_ms"]["predictive_better"]
    out["predictive_beats_fixed"] = bool(chr_ok) and bool(p99_ok)
    return out


def policy_sweep(*, duration_s: float = 8.0, rps: float = 8.0,
                 seed: int = 7, shape: str = "bursty",
                 policies: tuple = POLICIES, deadline_ms: float = 1000.0,
                 objective_ms: float = 500.0, idle_unload_s: float = 0.35,
                 hbm_budget_bytes: int = 1 << 30,
                 retry_cap_s: float = 0.25,
                 compile_cache_dir: str | None = None,
                 ckpt_store_dir: str | None = None) -> dict:
    """Replay ONE trace against N scaling-policy variants of the same
    server config and emit the comparison table + verdict.

    Each variant boots a fresh in-process server (aiohttp TestServer) with
    a lazy scale-to-zero deploy on a SHORT fixed idle timer and an
    aggressive host-tier drop, at equal ``hbm_budget_bytes`` and a shared
    compile cache — so the only difference between variants is the policy:
    fixed timers demote between bursts and eat the cold-start tax at every
    burst head; the histogram policy learns a keep-warm window covering the
    inter-burst gap; the predictive policy additionally pre-warms ahead of
    the forecast.  The sender retries colds/sheds like a real client
    (:func:`retrying_sender`), so ``latency_p99_ms`` is the client-felt
    time-to-answer and ``cold_hit_rate`` the fraction of requests whose
    first attempt hit a cold start.

    ``ckpt_store_dir`` turns on the streaming checkpoint store
    (docs/LIFECYCLE.md): idle demotions land in the disk tier instead of a
    full unload, re-activations stream chunked weights, and the learned
    ``estimated_warm_ms`` falls — which makes mid-trace activations
    deadline-feasible and cuts ``cold_hit_rate``.
    """
    import shutil
    import sys as _sys
    import tempfile

    root = str(Path(__file__).resolve().parents[1])
    if root not in _sys.path:
        _sys.path.insert(0, root)
    from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
    from pytorch_zappa_serverless_tpu.serving.server import Server

    model = "rn_burst"
    trace = synth_trace(shape, duration_s, rps, [model], seed=seed)
    tmp = None
    if compile_cache_dir is None:
        tmp = tempfile.mkdtemp(prefix="tpuserve-policysweep-")
        compile_cache_dir = str(Path(tmp) / "xla")

    def mk_cfg(policy: str) -> ServeConfig:
        return ServeConfig(
            compile_cache_dir=compile_cache_dir, warmup_at_boot=True,
            idle_unload_s=idle_unload_s,
            # Drop straight through the host tier so a demotion costs a
            # real (deadline-infeasible) rebuild — the cold-start tax the
            # policies are being judged on, honest on the CPU backend.
            host_idle_drop_s=idle_unload_s,
            hbm_budget_bytes=hbm_budget_bytes,
            activation_estimate_ms=max(4.0 * deadline_ms, 1000.0),
            autoscale_tick_s=0.2, keepwarm_min_s=2.0,
            slo={model: {"latency_objective_ms": objective_ms,
                         "availability_target": 0.99}},
            models=[ModelConfig(
                name=model, builder="resnet18", batch_buckets=(1, 4),
                dtype="float32", coalesce_ms=1.0, lazy_load=True,
                extra={"image_size": 48, "resize_to": 56})],
            **({"ckpt_store_dir": ckpt_store_dir} if ckpt_store_dir else {}),
            **POLICY_OVERRIDES[policy])

    body, ctype = _default_payload()

    async def drive_one(policy: str) -> dict:
        from aiohttp.test_utils import TestClient, TestServer

        srv = Server(mk_cfg(policy))
        client = TestClient(TestServer(srv.app))
        await client.start_server()
        try:
            headers = {"Content-Type": ctype,
                       "X-Deadline-Ms": str(deadline_ms)}
            # Pre-phase, identical for every variant: one synchronous
            # activation takes the FIRST full build (weights + compiles)
            # out of the measured window and teaches the lifecycle's
            # activation estimate, so mid-trace cold hits are
            # deadline-infeasible fast-fails for every policy alike — the
            # sweep judges steady-state policy, not first-deploy cost.
            await (await client.post(f"/admin/models/{model}",
                                     json={"action": "activate"})).read()

            async def send(item):
                t0 = time.perf_counter()
                async with client.post(
                        f"/v1/models/{item['model']}:predict", data=body,
                        headers=headers) as resp:
                    raw = await resp.read()
                    cold = False
                    if resp.status == 503 and raw[:1] == b"{":
                        try:
                            j = json.loads(raw)
                            cold = bool(j.get("cold_start")
                                        or j.get("adapter_cold"))
                        except ValueError:
                            pass
                    ra = resp.headers.get("Retry-After")
                    return {"status": resp.status,
                            "latency_ms": (time.perf_counter() - t0) * 1e3,
                            "cold": cold, "degraded": False,
                            "retry_after_s": float(ra) if ra else None}

            outcomes = await replay_async(
                retrying_sender(send, max_attempts=20,
                                wait_cap_s=retry_cap_s), trace)
            report = summarize(outcomes, duration_s,
                               objective_ms=objective_ms)
            auto = await (await client.get("/admin/autoscale")).json()
            models_snap = await (await client.get("/admin/models")).json()
            mrow = (models_snap.get("models") or {}).get(model, {})
            report["activations"] = mrow.get("activations", 0)
            report["demotions_idle"] = (mrow.get("demotions_by_cause")
                                        or {}).get("idle", 0)
            # Let the sub-second idle timers walk the model fully down the
            # ladder, then record the warm-ms estimate the NEXT request
            # would see: the scale-to-zero floor is the disk tier when the
            # ckpt store is on, compiled-cache-only otherwise — so this is
            # the learned streamed-restore estimate vs the full-rebuild one.
            floor = "disk" if ckpt_store_dir else "none"
            mrow2 = mrow
            for _ in range(80):
                m = await (await client.get(f"/admin/models/{model}")).json()
                mrow2 = m["model"]
                if mrow2.get("tier") == floor and mrow2.get("state") == "cold":
                    break
                await asyncio.sleep(0.1)
            report["tier_end"] = mrow2.get("tier")
            report["estimated_warm_ms"] = mrow2.get("estimated_warm_ms")
            report["prewarms"] = auto["counters"]["prewarms"]
            report["keepwarm_window_s"] = (auto.get("models", {})
                                           .get(model, {})
                                           .get("keepwarm_window_s"))
            # Settle any in-flight background activation before teardown.
            for _ in range(100):
                m = await (await client.get("/admin/models")).json()
                if (m.get("models") or {}).get(model, {}).get("state") \
                        != "warming":
                    break
                await asyncio.sleep(0.1)
            return report
        finally:
            await client.close()

    per_policy: dict = {}
    try:
        for policy in policies:
            per_policy[policy] = asyncio.new_event_loop().run_until_complete(
                drive_one(policy))
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    return {
        "shape": shape, "duration_s": duration_s, "mean_rps": rps,
        "seed": seed, "deadline_ms": deadline_ms,
        "objective_ms": objective_ms, "idle_unload_s": idle_unload_s,
        "hbm_budget_bytes": hbm_budget_bytes,
        "ckpt_store": bool(ckpt_store_dir),
        "policies": per_policy,
        "verdict": sweep_verdict(per_policy),
        "note": ("one deterministic trace replayed against N scaling "
                 "policies at equal hbm_budget_bytes; latency is "
                 "client-felt time-to-answer (cold/shed retries included, "
                 "capped), cold_hit_rate the fraction of requests whose "
                 "first attempt hit a cold start"),
    }


def _default_payload() -> tuple[bytes, str]:
    """A 1-image PNG body — serves the vision zoo out of the box."""
    import io

    from PIL import Image

    rng = np.random.default_rng(0)
    buf = io.BytesIO()
    Image.fromarray(rng.integers(0, 256, (64, 64, 3), np.uint8)
                    ).save(buf, format="PNG")
    return buf.getvalue(), "image/png"


def http_sender(session, url: str, body: bytes, content_type: str,
                deadline_ms: float | None = None, clock=time.perf_counter):
    """An aiohttp ``send`` for :func:`replay_async` against a live stack."""
    headers = {"Content-Type": content_type}
    if deadline_ms:
        headers["X-Deadline-Ms"] = str(deadline_ms)

    async def send(item: dict) -> dict:
        t0 = clock()
        async with session.post(
                url.rstrip("/") + f"/v1/models/{item['model']}:predict",
                data=body, headers=headers) as resp:
            raw = await resp.read()
            latency_ms = (clock() - t0) * 1000.0
            cold = False
            if resp.status == 503 and raw[:1] == b"{":
                try:
                    j = json.loads(raw)
                    cold = bool(j.get("cold_start") or j.get("adapter_cold"))
                except ValueError:
                    pass
            ra = resp.headers.get("Retry-After")
            return {"status": resp.status, "latency_ms": latency_ms,
                    "cold": cold,
                    "degraded": bool(resp.headers.get("X-Degraded")),
                    "retry_after_s": float(ra) if ra else None}
    return send


async def _run_cli(args) -> dict:
    import aiohttp

    models = [m.strip() for m in args.model.split(",") if m.strip()]
    trace = synth_trace(args.shape, args.duration, args.rps, models,
                        seed=args.seed)
    if args.payload_file:
        body = open(args.payload_file, "rb").read()
        ctype = args.content_type or "application/json"
    else:
        body, ctype = _default_payload()
    async with aiohttp.ClientSession() as session:
        send = http_sender(session, args.url, body, ctype,
                           deadline_ms=args.deadline_ms or None)
        outcomes = await replay_async(send, trace, speedup=args.speedup)
        report = summarize(outcomes, args.duration / max(args.speedup, 1e-9),
                           objective_ms=args.objective_ms or None)
        try:
            # The server-side verdict on the same run: burn-rate state
            # from the stack's own SLO plane (replica or router — both
            # serve /admin/slo).
            async with session.get(args.url.rstrip("/")
                                   + "/admin/slo") as resp:
                if resp.status == 200:
                    slo = await resp.json()
                    alarms = {}
                    for key, lanes in (slo.get("models") or {}).items():
                        for lane, t in lanes.items():
                            for w, win in (t.get("windows") or {}).items():
                                if win.get("alarm"):
                                    alarms.setdefault(
                                        f"{key}|{lane}", []).append(w)
                    report["server_slo_alarms"] = alarms
        except Exception:
            pass
    return {"shape": args.shape, "duration_s": args.duration,
            "mean_rps": args.rps, "models": models, "seed": args.seed,
            **report}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--url", default="http://127.0.0.1:8000",
                   help="server or fleet-router base URL")
    p.add_argument("--model", default="resnet18",
                   help="comma-separated model/family names to address")
    p.add_argument("--shape", default="bursty", choices=list(SHAPES))
    p.add_argument("--duration", type=float, default=30.0,
                   help="trace length in seconds (before --speedup)")
    p.add_argument("--rps", type=float, default=20.0,
                   help="mean offered requests/second")
    p.add_argument("--speedup", type=float, default=1.0,
                   help="replay the trace this many times faster")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--deadline-ms", type=float, default=0.0,
                   help="X-Deadline-Ms per request (0 = none)")
    p.add_argument("--objective-ms", type=float, default=0.0,
                   help="latency objective for attainment (0 = served == "
                        "good)")
    p.add_argument("--payload-file", default=None,
                   help="request body file (default: a tiny PNG)")
    p.add_argument("--content-type", default=None)
    p.add_argument("--policy-sweep", action="store_true",
                   help="replay ONE trace against in-process servers under "
                        "each scaling policy (fixed | histogram | "
                        "predictive) and print the comparison table + "
                        "verdict (docs/AUTOSCALE.md) — ignores --url")
    p.add_argument("--policies", default=",".join(POLICIES),
                   help="comma-separated policy subset for --policy-sweep")
    args = p.parse_args(argv)
    if args.policy_sweep:
        policies = tuple(s.strip() for s in args.policies.split(",")
                         if s.strip())
        unknown = [s for s in policies if s not in POLICIES]
        if unknown:
            p.error(f"unknown policies {unknown}; choose from {POLICIES}")
        report = policy_sweep(
            duration_s=args.duration, rps=args.rps, seed=args.seed,
            shape=args.shape,
            policies=policies,
            **({"deadline_ms": args.deadline_ms} if args.deadline_ms
               else {}),
            **({"objective_ms": args.objective_ms} if args.objective_ms
               else {}))
        print(json.dumps(report, indent=2))
        return 0
    report = asyncio.new_event_loop().run_until_complete(_run_cli(args))
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
