"""A program's share of its roofline: the least time the chip could take for
the bytes or operations the algorithm needs (``benchmark/roofline/<family>``,
``benchmark/peaks.json``) over the device time the trace shows."""

from __future__ import annotations

import importlib

from benchmark.readers import trace as trace_reader


def read(ctx, kind: str):
    if not ctx["trace"]["window_s"]:
        return None  # no device plane in the capture: nothing to read
    shapes = importlib.import_module(
        f"benchmark.roofline.{ctx['config']['family']}")
    kind_of_device = ctx["device"]["kind"]
    if kind_of_device not in ctx["peaks"]:
        raise SystemExit(f"benchmark/peaks.json has no device kind "
                         f"{kind_of_device!r}: add it with its source")
    peaks = ctx["peaks"][kind_of_device]
    arch = ctx["serve"]["extra"]["arch"]
    int8 = ctx["serve"]["extra"]["params_dtype"] == "int8"
    run = ctx["run"]
    recs = [r for r in run["records"] if not r["error"]]
    wall = ctx["seconds"] + max(run["drain_s"], 0.0)
    if kind == "decode":  # bound: bandwidth
        step_ms = trace_reader.read(ctx, "decode_step_ms")
        if not step_ms:
            return None
        # Live positions, averaged over the window: each stream holds its
        # prompt and the tokens so far (half of them on average) while it
        # decodes.
        live = sum((r["t_tokens"][-1] - r["t_tokens"][0])
                   * (r["prompt_len"] + len(r["tokens"]) / 2) for r in recs) \
            / wall
        least_ms = shapes.decode_step_bytes(arch, int8, live) \
            / peaks["hbm_bytes_per_s"] * 1e3
        return 100.0 * least_ms / step_ms
    if kind == "prefill":  # bound: compute (W8A16 computes in bf16)
        pre = ctx["trace"]["programs"].get("prefill")
        if not pre:
            return None
        flops_per_s = sum(shapes.prefill_flops(arch, r["prompt_len"])
                          for r in recs) / wall
        device_s_per_s = pre["seconds"] / ctx["trace"]["window_s"]
        return 100.0 * flops_per_s / peaks["bf16_flops_per_s"] / device_s_per_s
    raise ValueError(f"roofline reader has no kind {kind!r}")
