"""How much of the device's busy time in the traced slice went to prefill
programs: on a lane whose segments wait while a prompt is prefilled, the
share of a decoding stream's time that is another stream's prefill.

A capture with no device plane (the CPU) gives nothing to read, and the
metric is left out of the line."""

from __future__ import annotations


def read(ctx, kind: str):
    trace = ctx["trace"]
    if not trace["window_s"] or not trace["busy_s"]:
        return None
    if kind == "prefill_share":
        pre = trace["programs"].get("prefill")
        return (pre["seconds"] if pre else 0.0) / trace["busy_s"]
    raise ValueError(f"stall reader has no kind {kind!r}")
