#!/usr/bin/env python
"""Render a request trace as a text waterfall — the offline half of tracing.

Input is the JSON ``GET /admin/trace/{id}`` returns (or the ``tree`` object
inside it), from a file, stdin, or fetched live with ``--url``::

    python tools/tracedump.py trace.json
    curl -s localhost:8000/admin/trace/<id> | python tools/tracedump.py -
    python tools/tracedump.py --url http://localhost:8000 --id <trace_id>

Output: one row per span (indent = tree depth) with start offset, duration,
status, and a proportional bar, then a stage-attribution summary over the
root's direct children (docs/OBSERVABILITY.md)::

    predict resnet18 trace 1f3c... (ok, 212.4 ms)
      0.0ms  +-  212.4ms  predict                [##############################]
      0.0ms  |-    1.8ms  admission              [#                             ]
      ...

Importable: ``render(trace_dict)`` and ``stage_attribution(trace_dict)`` are
used by the tier-1 tests.
"""

from __future__ import annotations

import argparse
import json
import sys

BAR_WIDTH = 30

# Decision/sub-stage spans worth surfacing in the attribution summary even
# when they are not direct children of the root (adapter_gather rides the
# admission span; prefill_chunk/spec_* ride the generation ticks) or are
# zero-duration decision points.  These are the PR 7-11 spans a slow-request
# reconstruction needs beside the admission/queue/device/respond chain:
# variant selection, adapter slot routing + attach waits, prefix-cache
# hits/inserts, chunked prefill, and speculative draft/verify.
SUBSTAGES = ("variant_select", "adapter_gather", "adapter_attach",
             "prefix_hit", "prefix_insert", "prefill_chunk",
             "spec_draft", "spec_verify", "cold_start", "adapter_cold",
             "load_shed", "retry", "migrate_export", "migrate_import",
             "kv_failover",
             # Perf-plane ingest/egress attribution (docs/OBSERVABILITY.md
             # §9): the host-side substages that decompose the http→device
             # gap.  They overlap the admission/queue/device/respond chain
             # (payload_read/json_decode/b64_decode/validate ride inside
             # admission's window, batch_form inside queue's, serialize
             # inside respond's) so they are attribution rows, NEVER part
             # of stage coverage — stage_attribution below excludes them
             # from the direct-children sum wherever they are parented.
             "payload_read", "json_decode", "b64_decode", "validate",
             "batch_form", "serialize",
             # Acceptor fast lane (ISSUE 19, docs/OBSERVABILITY.md §10):
             # worker-stamped substages stitched over the shm ring —
             # sock_read/frame_validate happen in the worker process,
             # ring_wait is the cross-process hop, binary_decode is the
             # pump-side frame decode.  All four ride inside admission's
             # window on a fast-lane trace (the root is back-dated to the
             # worker's accept time), so they are substages like their
             # JSON-lane twins.
             "binary_decode", "sock_read", "frame_validate", "ring_wait")


def _tree_of(payload: dict) -> dict:
    """Accept the /admin/trace/{id} envelope, the trace dict, or a bare tree."""
    if "trace" in payload and isinstance(payload["trace"], dict):
        payload = payload["trace"]
    return payload


def _walk(node: dict, depth: int = 0):
    yield depth, node
    for child in node.get("children", []):
        yield from _walk(child, depth + 1)


def stage_attribution(payload: dict) -> dict:
    """Per-stage durations from the root's direct children.

    -> {"total_ms", "stages": {name: ms}, "coverage_pct"} — coverage is how
    much of the root's wall the stage chain tiles (100% ≈ no unaccounted
    gaps; the tier-1 acceptance asserts >= 95% on a served request).
    Repeated stages (retried device attempts, chunk slices) sum.
    """
    trace = _tree_of(payload)
    root = trace.get("tree", trace)
    total = float(root.get("duration_ms", 0.0))
    stages: dict[str, float] = {}
    for child in root.get("children", []):
        if child["name"] in SUBSTAGES:
            # Substages overlap the stage chain (a payload_read parented at
            # the root still happens inside admission's window): counting
            # them as stages would double-book coverage.
            continue
        stages[child["name"]] = (stages.get(child["name"], 0.0)
                                 + float(child.get("duration_ms", 0.0)))
    covered = sum(stages.values())
    # Sub-stage spans (SUBSTAGES): decision points and nested stages from
    # anywhere in the tree — counted and summed, but NOT part of coverage
    # (they overlap the direct-child chain that tiles the wall time).
    substages: dict[str, dict] = {}
    for _, node in _walk(root):
        if node is root or node["name"] not in SUBSTAGES:
            continue
        s = substages.setdefault(node["name"], {"count": 0, "ms": 0.0})
        s["count"] += 1
        s["ms"] = round(s["ms"] + float(node.get("duration_ms", 0.0)), 3)
    return {"total_ms": round(total, 3),
            "stages": {k: round(v, 3) for k, v in stages.items()},
            **({"substages": substages} if substages else {}),
            "coverage_pct": round(100.0 * covered / total, 1) if total else None}


def render(payload: dict, bar_width: int = BAR_WIDTH) -> str:
    """The waterfall text for one trace."""
    trace = _tree_of(payload)
    root = trace.get("tree", trace)
    total = max(float(root.get("duration_ms", 0.0)), 1e-9)
    lines = []
    head = (f"{trace.get('name', root.get('name', '?'))} "
            f"{trace.get('model') or ''} trace {trace.get('trace_id', '?')} "
            f"({trace.get('status', root.get('status', '?'))}, "
            f"{total:.1f} ms)")
    lines.append(" ".join(head.split()))
    rows = list(_walk(root))
    name_w = max(len("  " * d + n["name"]) for d, n in rows) + 2
    for depth, node in rows:
        start = float(node.get("start_ms", 0.0))
        dur = float(node.get("duration_ms", 0.0))
        lead = int(bar_width * max(start, 0.0) / total)
        fill = max(int(bar_width * dur / total), 1 if dur > 0 else 0)
        lead = min(lead, bar_width)
        fill = min(fill, bar_width - lead)
        bar = " " * lead + "#" * fill + " " * (bar_width - lead - fill)
        mark = "!" if node.get("status") == "error" else " "
        name = ("  " * depth + node["name"]).ljust(name_w)
        extra = ""
        attrs = node.get("attrs") or {}
        keys = [k for k in ("batch_size", "batch_mates", "attempt", "lane",
                            "tokens", "error", "shed", "variant", "adapter",
                            "slot", "round", "waited_ms", "cached_tokens",
                            "cow_copies", "prefix_cached", "chunk",
                            "degraded", "bytes", "instances") if k in attrs]
        if keys:
            extra = "  " + " ".join(f"{k}={attrs[k]}" for k in keys)
        lines.append(f"{start:9.1f}ms {mark}{dur:9.1f}ms  {name}"
                     f"[{bar}]{extra}")
    att = stage_attribution(payload)
    if att["stages"]:
        parts = [f"{k}={v:.1f}ms ({100 * v / max(att['total_ms'], 1e-9):.0f}%)"
                 for k, v in att["stages"].items()]
        lines.append("stages: " + "  ".join(parts)
                     + (f"  coverage={att['coverage_pct']:.1f}%"
                        if att["coverage_pct"] is not None else ""))
    if att.get("substages"):
        lines.append("substages: " + "  ".join(
            f"{k}={v['ms']:.1f}ms x{v['count']}"
            for k, v in att["substages"].items()))
    return "\n".join(lines)


def _fetch(url: str, trace_id: str) -> dict:
    import urllib.request

    full = url.rstrip("/") + f"/admin/trace/{trace_id}"
    with urllib.request.urlopen(full, timeout=10) as resp:
        return json.loads(resp.read().decode())


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("input", nargs="?", default=None,
                   help="trace JSON file, or - for stdin")
    p.add_argument("--url", default=None,
                   help="running server base URL (with --id)")
    p.add_argument("--id", default=None, help="trace id to fetch via --url")
    p.add_argument("--width", type=int, default=BAR_WIDTH)
    args = p.parse_args(argv)
    if args.url and args.id:
        payload = _fetch(args.url, args.id)
    elif args.input == "-":
        payload = json.loads(sys.stdin.read())
    elif args.input:
        with open(args.input) as f:
            payload = json.load(f)
    else:
        p.error("pass a file/- or --url + --id")
    print(render(payload, bar_width=args.width))
    return 0


if __name__ == "__main__":
    sys.exit(main())
