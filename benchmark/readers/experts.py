"""Per-layer metrics of routed experts, from what the program's decode step
counts (``/metrics`` ``generation[model]``: ``expert_assignments_held``,
``experts_touched``, ``expert_load_max``, each ``{sum, count}`` a segment
round) and the device time of the ``expert_matmul`` kernel.

A program that keeps no such counters (the parent of the PR that brought
them) gives nothing to read, and the metric is left out of the line."""

from __future__ import annotations

from benchmark import families


def _delta(before: dict, after: dict, key: str):
    """``(sum, rounds)`` a counter gained, or None where it is not kept."""
    if key not in after or key not in before:
        return None
    return (after[key]["sum"] - before[key]["sum"],
            after[key]["count"] - before[key]["count"])


def read(ctx, kind: str):
    run, serve = ctx["run"], ctx["serve"]
    family = families.load(ctx["config"])
    before, after = run["gen_before"], run["gen_after"]
    if kind == "touched_share":
        # Held experts a layer a step that at least one row reached.
        touched = _delta(before, after, "experts_touched")
        if not touched or not touched[1]:
            return None
        steps = touched[1] * serve["extra"]["segment_tokens"]
        return touched[0] / (family.experts_held(serve)
                             * family.kinds(serve)["E"] * steps)
    if kind == "load_max_over_mean":
        # The most rows on one held expert over the mean, a layer a step.
        most = _delta(before, after, "expert_load_max")
        held = _delta(before, after, "expert_assignments_held")
        if not most or not held or not held[0]:
            return None
        return most[0] / (held[0] / family.experts_held(serve))
    if kind == "matmul_roofline":  # bound: bandwidth
        # Over the traced slice alone: the experts the capture's own rounds
        # touched (the profile's counters, taken as it began and ended),
        # each read once, against the kernel's device time in those runs.
        trace = ctx["trace"]
        seg = trace["programs"].get("segment") if trace["window_s"] else None
        counters = ((run.get("profile") or {}).get("generation") or {}).get(
            serve["model"])
        if not seg or not counters or not seg["ops"].get("expert_matmul"):
            return None
        touched = _delta(counters["before"], counters["after"],
                         "experts_touched")
        if not touched or not touched[0] or not touched[1]:
            return None
        peaks = ctx["peaks"][ctx["device"]["kind"]]
        least_s = (touched[0] / touched[1] * family.expert_bytes(serve)
                   / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / (seg["ops"]["expert_matmul"] / seg["runs"])
    raise ValueError(f"experts reader has no kind {kind!r}")
