"""Per-layer metrics of a family whose K/V layers keep their rows in more
than one way (a ring of a window's rows beside a row a position): what the
generating slots' spans hold a kind, from the scheduler's counters
(``/metrics`` ``generation[model]``: ``span_rows_by_kind``, a kind's name ->
``{sum, count}`` a segment round of the rows one of its layers holds), the
decode kernel's share of the time the rows of every kind need, and the prompt
kernel's share of its compute (``prefill_buckets``: prompts prefilled by the
bucket of their dispatch).

The family (``benchmark/families``) says how many layers hold each kind
(``kinds(serve)``, by the program's own names), a layer's bytes a row
(``row_bytes``) and what a bucket's prompt attention computes
(``attend_flops(serve, bucket, visited=True)``).  A program that keeps no
such counters (the parent of the PR that brought them) gives nothing to
read, and the metric is left out of the line."""

from __future__ import annotations

from benchmark import families


def _rows_by_kind(before: dict, after: dict):
    """``({kind: rows gained}, rounds)`` or None where they are not kept."""
    a, b = after.get("span_rows_by_kind"), before.get("span_rows_by_kind")
    if not a or not b:
        return None
    rounds = {a[k]["count"] - b[k]["count"] for k in a}
    return {k: a[k]["sum"] - b[k]["sum"] for k in a}, max(rounds)


def _profile(ctx):
    """The capture's own counters, taken as it began and ended."""
    return ((ctx["run"].get("profile") or {}).get("generation") or {}).get(
        ctx["serve"]["model"])


def read(ctx, kind: str, of: str | None = None, op: str | None = None):
    run, serve = ctx["run"], ctx["serve"]
    family = families.load(ctx["config"])
    layers = family.kinds(serve)
    if kind == "rows_share":
        # Of the rows a step reads in all its layers, those of kind ``of``.
        held = _rows_by_kind(run["gen_before"], run["gen_after"])
        if not held:
            return None
        total = sum(layers[k] * rows for k, rows in held[0].items())
        return layers[of] * held[0][of] / total if total else None
    trace = ctx["trace"]
    counters = _profile(ctx)
    if not trace["window_s"] or not counters:
        return None
    peaks = ctx["peaks"][ctx["device"]["kind"]]
    if kind == "attend_roofline":  # bound: bandwidth
        # Over the traced slice alone: the rows the live spans held in the
        # capture's own rounds, a kind's rows in each of its layers, K and
        # V, every step of a round, against the decode kernel's device time
        # a segment run.
        seg = trace["programs"].get("segment")
        held = _rows_by_kind(counters["before"], counters["after"])
        if not seg or not seg["ops"].get(op) or not held or not held[1]:
            return None
        rows = sum(layers[k] * n for k, n in held[0].items()) / held[1]
        least_s = (rows * serve["extra"]["segment_tokens"]
                   * family.row_bytes(serve) / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / (seg["ops"][op] / seg["runs"])
    if kind == "prompt_peak_pct":  # bound: compute
        # The prompts whose prefill was launched inside the capture, each
        # at what the kernel computes for its bucket, against the kernel's
        # device time in the capture's prefill runs.
        pre = trace["programs"].get("prefill")
        a = counters["after"].get("prefill_buckets")
        b = counters["before"].get("prefill_buckets")
        if not pre or not pre["ops"].get(op) or a is None or b is None:
            return None
        flops = sum((n - b.get(bucket, 0))
                    * family.attend_flops(serve, int(bucket), visited=True)
                    for bucket, n in a.items())
        if not flops:
            return None
        return 100.0 * flops / peaks["bf16_flops_per_s"] / pre["ops"][op]
    raise ValueError(f"kinds reader has no kind {kind!r}")
