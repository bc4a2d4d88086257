"""Per-layer metrics of a cache whose rows are not a row a position: what
the generating slots' spans hold, from the scheduler's counters
(``/metrics`` ``generation[model]``: ``span_rows``, ``summary_rows``,
``live_positions``, each ``{sum, count}`` a segment round), and the decode
kernel's share of the time those rows' bytes need.

A program that keeps no such counters (the parent of the PR that brought
them) gives nothing to read, and the metric is left out of the line."""

from __future__ import annotations

from benchmark import families


def _delta(before: dict, after: dict, key: str):
    if key not in after or key not in before:
        return None
    return after[key]["sum"] - before[key]["sum"]


def read(ctx, kind: str):
    run = ctx["run"]
    before, after = run["gen_before"], run["gen_after"]
    rows = _delta(before, after, "span_rows")
    if kind == "span_share":
        # Rows held over positions written: 1 where a row is a position.
        positions = _delta(before, after, "live_positions")
        return rows / positions if rows and positions else None
    if kind == "summary_row_share":
        summaries = _delta(before, after, "summary_rows")
        return summaries / rows if rows and summaries is not None else None
    if kind == "attend_roofline":  # bound: bandwidth
        # Over the traced slice alone: the rows the live spans held in the
        # segment rounds the capture saw (the profile's own counters, taken
        # as the capture began and ended), K and V of every layer, eight
        # steps a round, against the kernel's device time in those runs.
        trace = ctx["trace"]
        seg = trace["programs"].get("segment") if trace["window_s"] else None
        counters = ((run.get("profile") or {}).get("generation") or {}).get(
            ctx["serve"]["model"])
        if not seg or not counters or not seg["ops"].get("decode_attention"):
            return None
        held = _delta(counters["before"], counters["after"], "span_rows")
        rounds = (counters["after"]["span_rows"]["count"]
                  - counters["before"]["span_rows"]["count"])
        if not held or not rounds:
            return None
        family = families.load(ctx["config"])
        peaks = ctx["peaks"][ctx["device"]["kind"]]
        steps = ctx["serve"]["extra"]["segment_tokens"]
        least_s = (held / rounds * steps * family.row_bytes(ctx["serve"])
                   / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / (seg["ops"]["decode_attention"]
                                  / seg["runs"])
    raise ValueError(f"rows reader has no kind {kind!r}")
