"""A program's share of its roofline: the least time the chip could take for
the bytes or operations the algorithm needs (the configuration's family,
``benchmark/families``; ``benchmark/peaks.json``) over the device time the
trace shows."""

from __future__ import annotations

from benchmark import families
from benchmark.readers import trace as trace_reader


def read(ctx, kind: str):
    if not ctx["trace"]["window_s"]:
        return None  # no device plane in the capture: nothing to read
    config, serve = ctx["config"], ctx["serve"]
    family = families.load(config)
    kind_of_device = ctx["device"]["kind"]
    if kind_of_device not in ctx["peaks"]:
        raise SystemExit(f"benchmark/peaks.json has no device kind "
                         f"{kind_of_device!r}: add it with its source")
    peaks = ctx["peaks"][kind_of_device]
    run = ctx["run"]
    recs = [r for r in run["records"] if not r["error"]]
    wall = ctx["seconds"] + max(run["drain_s"], 0.0)
    if kind == "decode":  # bound: bandwidth
        step_ms = trace_reader.read(ctx, "decode_step_ms")
        if not step_ms:
            return None
        # One entry a stream: how long it decoded, and what it held.
        streams = [(r["t_tokens"][-1] - r["t_tokens"][0], r["prompt_len"],
                    len(r["tokens"])) for r in recs]
        least_ms = family.decode_step_bytes(config, serve, streams, wall) \
            / peaks["hbm_bytes_per_s"] * 1e3
        return 100.0 * least_ms / step_ms
    if kind == "prefill":  # bound: compute (W8A16 computes in bf16)
        pre = ctx["trace"]["programs"].get("prefill")
        if not pre:
            return None
        flops_per_s = sum(family.prefill_flops(config, serve, r["prompt_len"])
                          for r in recs) / wall
        device_s_per_s = pre["seconds"] / ctx["trace"]["window_s"]
        return 100.0 * flops_per_s / peaks["bf16_flops_per_s"] / device_s_per_s
    raise ValueError(f"roofline reader has no kind {kind!r}")
