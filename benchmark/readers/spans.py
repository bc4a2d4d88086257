"""Per-layer metrics from the program's own spans and stamps (ISSUE 24).

Three sources, each absent from a program older than the spans (or on the
CPU, which has no device plane); a reader then gives ``None`` and the line
leaves the metric out:

- the ``done`` event's ``stats``: the server's stamps tile a request's time
  to its first token into ``ingest_ms``, ``round_wait_ms``, ``slot_wait_ms``,
  ``prefill_ms``, ``first_emit_ms`` and ``egress_ms``;
- ``/metrics`` ``generation[model]["host_phases"]``: cumulative ``sum_ms`` and
  ``count`` per scheduler phase, read as deltas over the window, and the
  ``serialize``/``respond`` stage histograms of ``/admin/perf``;
- the ``idle`` block of the ``/admin/profile`` response: the traced slice's
  idle time by host phase.
"""

from __future__ import annotations

from benchmark.client import percentile

LEGS = ("ingest_ms", "round_wait_ms", "slot_wait_ms", "prefill_ms",
        "first_emit_ms", "egress_ms")
# A decode round's host share: everything between one segment's results
# reaching the dispatch thread and the next segment's launch.
TURNAROUND = ("round.wakeup", "round.distribute", "round.admit_host",
              "round.lane_wait")


def _answered(ctx) -> list[dict]:
    return [r for r in ctx["run"]["records"] if not r["error"]]


def _phase_deltas(ctx) -> dict | None:
    """``{phase: (delta sum_ms, delta count)}`` over the window."""
    run = ctx["run"]
    before = run["gen_before"].get("host_phases")
    after = run["gen_after"].get("host_phases")
    if not before or not after:
        return None
    return {p: (after[p]["sum_ms"] - before[p]["sum_ms"],
                after[p]["count"] - before[p]["count"]) for p in after}


def _rounds(ctx) -> int:
    run = ctx["run"]
    return run["gen_after"]["segment_rounds"] \
        - run["gen_before"]["segment_rounds"]


def _say(ctx) -> None:
    """The whole table, once a run: the log keeps what the metrics sum up."""
    if ctx.setdefault("_spans_said", False):
        return
    ctx["_spans_said"] = True
    deltas, rounds = _phase_deltas(ctx), _rounds(ctx)
    if deltas and rounds:
        wall = ctx["seconds"] + max(ctx["run"]["drain_s"], 0.0)
        total = sum(ms for ms, _ in deltas.values()) / 1e3
        print("[bench] host phases (ms/round): "
              + ", ".join(f"{p} {ms / rounds:.3f}"
                          for p, (ms, _) in deltas.items())
              + f"; {rounds} rounds; all phases {total:.2f} s of a window "
                f"(with its drain) of {wall:.2f} s", flush=True)
    idle = (ctx["run"].get("profile") or {}).get("idle")
    if idle:
        print(f"[bench] idle by phase: {idle['idle_ms']:.1f} ms idle of "
              f"{idle['window_ms']:.1f} ms; "
              + ", ".join(f"{p} {ms:.1f}"
                          for p, ms in idle["by_phase"].items())
              + f", unattributed {idle['unattributed_ms']:.1f}; gaps: "
              + "; ".join(
                  f"{g['before']}-{g['after']} {g['ms']:.1f} ms x{g['count']}"
                  f" (" + ", ".join(f"{p} {ms:.1f}" for p, ms in
                                    list(g["phases"].items())[:4]) + ")"
                  for g in idle["gaps"][:4])
              + f"; clock {idle['clock']}", flush=True)
    recs = [r for r in _answered(ctx) if all(k in r["stats"] for k in LEGS)]
    if recs:
        short = [(r["t_tokens"][0] - r["due"]) * 1e3
                 - sum(r["stats"][k] for k in LEGS) for r in recs]
        ttft = [(r["t_tokens"][0] - r["due"]) * 1e3 for r in recs]
        print("[bench] ttft tiling (ms, medians): "
              + ", ".join(f"{k[:-3]} "
                          f"{percentile([r['stats'][k] for r in recs], 0.5):.3f}"
                          for k in LEGS)
              + f"; client ttft {percentile(ttft, 0.5):.3f}; client minus "
                f"the six: min {min(short):.3f}, p50 "
                f"{percentile(short, 0.5):.3f}, p95 "
                f"{percentile(short, 0.95):.3f}, max {max(short):.3f}; "
                f"{len(recs)} requests", flush=True)


def read(ctx, kind: str, stats=(), q: float = 0.5, phases=()):
    _say(ctx)
    run = ctx["run"]
    if kind == "request_ms":
        # Per request the sum of these legs; then the nearest-rank quantile.
        values = [sum(r["stats"][k] for k in stats) for r in _answered(ctx)
                  if all(k in r["stats"] for k in stats)]
        return percentile(values, q) if values else None
    if kind == "sse_ms_per_round":
        model = ctx["serve"]["model"]
        before = run["perf_before"]["ingest"].get(model, {})
        after = run["perf_after"]["ingest"].get(model, {})
        rounds = _rounds(ctx)
        if not rounds or "respond" not in after:
            return None
        return sum(after[s]["sum"] - before.get(s, {"sum": 0.0})["sum"]
                   for s in ("serialize", "respond")) / rounds
    if kind == "phase_ms_per_round":
        deltas, rounds = _phase_deltas(ctx), _rounds(ctx)
        if not deltas or not rounds:
            return None
        return sum(deltas[p][0] for p in phases) / rounds
    if kind == "phase_mean_ms":
        deltas = _phase_deltas(ctx)
        if not deltas:
            return None
        ms, count = map(sum, zip(*(deltas[p] for p in phases)))
        return ms / count if count else None
    if kind == "idle_attributed_pct":
        idle = (run.get("profile") or {}).get("idle")
        if not idle or not idle["idle_ms"]:
            return None
        return 100.0 * (idle["idle_ms"] - idle["unattributed_ms"]) \
            / idle["idle_ms"]
    raise ValueError(f"spans reader has no kind {kind!r}")
