"""Per-layer metrics from the reduced device trace of the traced slice."""

from __future__ import annotations

POOL_COPIES = ("copy", "slice", "slice-start", "slice-done")


def prompt_ktok_per_s(ctx) -> float:
    """Real prompt tokens admitted per second of the window, in thousands."""
    run = ctx["run"]
    tokens = sum(r["prompt_len"] for r in run["records"] if not r["error"])
    return tokens / 1e3 / (ctx["seconds"] + max(run["drain_s"], 0.0))


def read(ctx, kind: str):
    trace = ctx["trace"]
    if not trace["window_s"]:
        return None
    if kind == "device_idle_pct":
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if kind == "decode_step_ms":
        seg = trace["programs"].get("segment")
        if not seg:
            return None
        return seg["seconds"] / seg["runs"] * 1e3 \
            / ctx["serve"]["extra"]["segment_tokens"]
    if kind == "pool_copy_slice_pct":
        # The slot pool's own copies inside the segment program.
        # ``copy-start`` and ``copy-done`` are the compiler's prefetch of
        # weights into fast memory, not the pool.
        seg = trace["programs"].get("segment")
        if not seg or not seg["seconds"]:
            return None
        return 100.0 * sum(seg["ops"].get(fam, 0.0) for fam in POOL_COPIES) \
            / seg["seconds"]
    if kind == "prefill_ms_per_ktok":
        # Prefill's share of the traced slice over the prompt tokens the
        # window admitted per second: the slice stands for the window.
        pre = trace["programs"].get("prefill")
        rate = prompt_ktok_per_s(ctx)
        if not pre or not rate:
            return None
        return pre["seconds"] / trace["window_s"] * 1e3 / rate
    raise ValueError(f"trace reader has no kind {kind!r}")
