"""Per-layer metrics of a cache whose rows are one leaf (latent attention):
the latent kernel's share of the time its rows' bytes need, and the rows'
share of a decode step's bytes, from the scheduler's counters (``/metrics``
``generation[model]``: ``span_rows``, ``{sum, count}`` a segment round of the
rows the generating slots' spans hold in one layer).

The family (``benchmark/families``) says how many layers keep such rows
(``kinds(serve)["latent"]``), a layer's bytes a row (``row_bytes``) and what a
decode step moves (``decode_step_bytes``, ``rows_bytes``).  A program that
has no such kernel or keeps no such counters (the parent of the PR that
brought them) gives nothing to read, and the metric is left out of the
line."""

from __future__ import annotations

from benchmark import families


def read(ctx, kind: str, op: str | None = None):
    run, serve = ctx["run"], ctx["serve"]
    family = families.load(ctx["config"])
    if kind == "bytes_share":
        # Over the window: the rows the live streams held a step, in every
        # layer, of all the bytes the family says a step moves.
        recs = [r for r in run["records"] if not r["error"]]
        wall = ctx["seconds"] + max(run["drain_s"], 0.0)
        streams = [(r["t_tokens"][-1] - r["t_tokens"][0], r["prompt_len"],
                    len(r["tokens"])) for r in recs]
        total = family.decode_step_bytes(ctx["config"], serve, streams, wall)
        return family.rows_bytes(serve, streams, wall) / total if total \
            else None
    if kind == "attend_roofline":  # bound: bandwidth
        # Over the traced slice alone: the rows the live spans held in the
        # segment rounds the capture saw (the profile's own counters, taken
        # as the capture began and ended), in every layer, every step of a
        # round, against the kernel's device time in those runs.
        trace = ctx["trace"]
        seg = trace["programs"].get("segment") if trace["window_s"] else None
        counters = ((run.get("profile") or {}).get("generation") or {}).get(
            serve["model"])
        if not seg or not counters or not seg["ops"].get(op):
            return None
        before, after = (counters[k].get("span_rows")
                         for k in ("before", "after"))
        if not before or not after or after["count"] == before["count"]:
            return None
        held = (after["sum"] - before["sum"]) / (
            after["count"] - before["count"])
        peaks = ctx["peaks"][ctx["device"]["kind"]]
        least_s = (held * family.kinds(serve)["latent"]
                   * serve["extra"]["segment_tokens"]
                   * family.row_bytes(serve) / peaks["hbm_bytes_per_s"])
        return 100.0 * least_s / (seg["ops"][op] / seg["runs"])
    raise ValueError(f"latent reader has no kind {kind!r}")
