"""Per-layer metrics the load generator's own records hold."""

from __future__ import annotations

from benchmark.client import percentile


def _rank(values, q):
    return percentile(values, q) if values else None


def read(ctx, stat: str):
    recs = [r for r in ctx["run"]["records"] if not r["error"]]
    if stat == "late_p90_ms":  # actual send - due send
        return _rank([(r["sent"] - r["due"]) * 1e3 for r in recs], 0.9)
    if stat == "ttft_p90_ms":
        return _rank([(r["t_tokens"][0] - r["due"]) * 1e3 for r in recs], 0.9)
    if stat == "ttft_p50_ms":
        return _rank([(r["t_tokens"][0] - r["due"]) * 1e3 for r in recs], 0.5)
    if stat == "latency_p50_ms":
        return _rank([(r["t_end"] - r["due"]) * 1e3 for r in recs], 0.5)
    if stat == "rounds_to_first_token_mean":
        rounds = [r["stats"]["rounds_to_first_token"] for r in recs
                  if "rounds_to_first_token" in r["stats"]]
        return sum(rounds) / len(rounds) if rounds else None
    raise ValueError(f"client reader has no statistic {stat!r}")
