"""Per-layer metrics from the server's counters (``/metrics`` generation
block, ``/admin/perf``, the compile log), as deltas over the window."""

from __future__ import annotations


def read(ctx, kind: str):
    run = ctx["run"]
    before, after = run["gen_before"], run["gen_after"]

    def delta(key):
        return after[key] - before[key]

    if kind == "tokens_per_round":
        rounds = delta("segment_rounds")
        return delta("tokens_emitted") / rounds if rounds else None
    if kind == "prefill_batch":
        admitted = sum(1 for r in run["records"] if not r["error"])
        runs = delta("prefill_dispatches")
        return admitted / runs if runs else None
    if kind == "kv_live_share":
        # Per segment round, the positions the generating slots hold over
        # slots x pool length; the scheduler keeps the sum and the count.
        if "kv_live_share" not in after:
            return None
        live0, live1 = before["kv_live_share"], after["kv_live_share"]
        rounds = live1["count"] - live0["count"]
        return (live1["sum"] - live0["sum"]) / rounds if rounds else None
    if kind == "compiles_in_window":
        return run["compiles_in_window"]
    if kind == "loop_lag_mean_ms":
        # Mean lateness of the server's event-loop probe (every 0.25 s) over
        # the window: the sampler's histogram keeps the exact sum and count.
        h0 = run["perf_before"]["loop_lag"]["hist"]
        h1 = run["perf_after"]["loop_lag"]["hist"]
        n = h1["count"] - h0["count"]
        return (h1["sum"] - h0["sum"]) / n if n else None
    raise ValueError(f"counters reader has no kind {kind!r}")
