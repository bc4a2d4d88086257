"""The prompt pass over the whole window, from the program's spans: the
operations the answered requests' prompts need (``benchmark/families``) at
the chip's peak, over the time the dispatch thread spent between launching
each prefill and holding its result (``/metrics``
``generation[model]["host_phases"]``: ``prefill.launch`` and
``prefill.fetch``, as deltas over the window and its drain).

A metric of the model step as the scheduler sees it, not a kernel's share of
its roofline: the span also holds what was left of the segment in flight
when the prefill was launched (at most one run) and the host's part of the
launch, so it reads low and moves with the scheduler as well as with the
prefill program.  It is what a cell can read whose capture holds one prefill
or a part of one: ``roofline``'s kind ``prefill`` sets the whole window's
operations against the capture's share of prefill time, and two seconds that
happen to hold a tenth of a prefill read as 150% (PR 35's first traced run).
"""

from __future__ import annotations

from benchmark import families
from benchmark.readers.spans import _phase_deltas

PHASES = ("prefill.launch", "prefill.fetch")


def read(ctx, kind: str):
    if kind != "span_peak_pct":
        raise ValueError(f"prefill reader has no kind {kind!r}")
    peaks = ctx["peaks"].get(ctx["device"]["kind"])
    deltas = _phase_deltas(ctx)
    if not peaks or not deltas or not all(p in deltas for p in PHASES):
        return None
    span_s = sum(deltas[p][0] for p in PHASES) / 1e3
    config, serve = ctx["config"], ctx["serve"]
    family = families.load(config)
    flops = sum(family.prefill_flops(config, serve, r["prompt_len"])
                for r in ctx["run"]["records"] if not r["error"])
    if not span_s or not flops:
        return None
    return 100.0 * flops / peaks["bf16_flops_per_s"] / span_s
