#!/usr/bin/env python
"""Bench regression sentinel: compare two bench rounds against a budget.

Bench rounds used to sit on disk with no automated comparison — a perf
regression shipped silently unless a human eyeballed two JSON blobs.  This
tool makes the comparison a checked contract (docs/OBSERVABILITY.md §9):

    python -m tools.benchdiff old_FULL.json new_FULL.json
    python -m tools.benchdiff old_FULL.json new_FULL.json --budget my.json
    python -m tools.benchdiff --check          # fixture self-test (CI)

Inputs are any two of: a driver round (``{"parsed": {...}}``), a compact
bench line (``{"metric", "value", "extra": ...}``), a full bench artifact
(``BENCH_FULL_PATH``), or any plain section dict — every numeric leaf is flattened to a
dotted key (``extra.server_path.achieved_rps``) and compared key by key.

The budget (``tools/perf_budget.json``, checked in) declares per-key
regression thresholds and directions; keys not listed fall back to the
defaults, with direction inferred from the name (``*_ms``/``*p99*`` lower
is better; ``*_rps``/``*tokens_per_s``/``*mfu*`` higher is better).  The
default thresholds date from the pre-round records (deleted in PR 21) and
have not been re-sized from repeat runs on the chip as installed today
(see the budget's note): wide enough that only a 2x regression fails.

Verdicts per key: ``pass`` / ``regress`` / ``improved`` / ``missing``
(key vanished from the new round) / ``new`` (key only in the new round).
Exit status is nonzero iff any key REGRESSES past its budget, or a key
marked ``"required": true`` in the budget goes missing — the tier-1 suite
runs the fixture self-test so later perf claims (ROADMAP items 1, 5) are
judged by this harness, not by eyeball.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BUDGET_PATH = Path(__file__).resolve().parent / "perf_budget.json"

# Name-suffix direction inference (used when the budget has no explicit
# per-key direction).  Checked in order; first hit wins.
_LOWER_BETTER = ("_ms", "_s", "p50", "p99", "p999", "max_ms", "n_429",
                 "latency", "evictions", "failed", "cold_hit_rate")
_HIGHER_BETTER = ("rps", "req_s_chip", "tokens_per_s", "images_per_s",
                  "mfu_pct", "speedup", "vs_baseline", "hit_rate",
                  "acceptance", "occupancy", "goodput", "attainment",
                  "coverage", "tflops", "gbps", "util_pct")

# Keys that are identities/counts, not performance: never judged.
_SKIP_KEYS = ("n", "rc", "unit", "seed", "iters", "trials", "n_requests",
              "n_traces", "concurrency", "batch", "count", "port")


def flatten(obj, prefix: str = "", out: dict | None = None) -> dict:
    """Every numeric leaf of a nested dict as {dotted.key: float} (bools
    and strings are skipped; lists are skipped — bench artifacts keep
    scalars in dicts)."""
    if out is None:
        out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            key = f"{prefix}{k}"
            if isinstance(v, dict):
                flatten(v, key + ".", out)
            elif (isinstance(v, (int, float)) and not isinstance(v, bool)
                  and k not in _SKIP_KEYS):
                out[key] = float(v)
    return out


def load_round(path: str | Path) -> dict:
    """Normalize any bench artifact into the comparable dict."""
    data = json.loads(Path(path).read_text())
    if isinstance(data, dict) and isinstance(data.get("parsed"), dict):
        data = data["parsed"]  # driver round envelope
    if data is None:
        raise SystemExit(f"{path}: round has no parsed payload")
    return data


def direction_of(key: str, spec: dict) -> str:
    if "direction" in spec:
        return spec["direction"]
    leaf = key.rsplit(".", 1)[-1].lower()
    for suf in _HIGHER_BETTER:
        if suf in leaf:
            return "higher_better"
    for suf in _LOWER_BETTER:
        if suf in leaf:
            return "lower_better"
    return "lower_better"  # conservative: unknown numbers read as costs


def _budget_for(key: str, budget: dict) -> dict:
    keys = budget.get("keys", {})
    if key in keys:
        return keys[key]
    # Longest matching suffix rule: "server_path.achieved_rps" matches the
    # same key under "extra." in a driver round.
    best: dict = {}
    best_len = 0
    for pat, spec in keys.items():
        if key.endswith(pat) and len(pat) > best_len:
            best, best_len = spec, len(pat)
    return best


def diff(old: dict, new: dict, budget: dict) -> list[dict]:
    """Key-by-key verdicts, sorted worst-first."""
    defaults = budget.get("defaults", {})
    min_abs = float(defaults.get("min_value", 0.0))
    o, n = flatten(old), flatten(new)
    rows: list[dict] = []
    for key in sorted(set(o) | set(n)):
        spec = _budget_for(key, budget)
        if spec.get("ignore"):
            continue
        if key not in n:
            rows.append({"key": key, "old": o[key], "new": None,
                         "verdict": ("regress" if spec.get("required")
                                     else "missing")})
            continue
        if key not in o:
            rows.append({"key": key, "old": None, "new": n[key],
                         "verdict": "new"})
            continue
        ov, nv = o[key], n[key]
        direction = direction_of(key, spec)
        limit = float(spec.get(
            "regress_pct",
            defaults.get("regress_pct", {}).get(direction, 50.0)
            if isinstance(defaults.get("regress_pct"), dict)
            else defaults.get("regress_pct", 50.0)))
        row = {"key": key, "old": ov, "new": nv, "direction": direction,
               "budget_pct": limit}
        if max(abs(ov), abs(nv)) < min_abs and not spec:
            # Sub-floor values (e.g. a 0.2 ms stage) jitter enormously in
            # relative terms; only an explicit budget entry judges them.
            row["verdict"] = "pass"
            row["note"] = "below min_value floor"
            rows.append(row)
            continue
        if ov == 0:
            delta_pct = 0.0 if nv == 0 else float("inf")
        else:
            delta_pct = 100.0 * (nv - ov) / abs(ov)
        worse = delta_pct if direction == "lower_better" else -delta_pct
        row["delta_pct"] = round(delta_pct, 1)
        if worse > limit:
            row["verdict"] = "regress"
        elif worse < -limit:
            row["verdict"] = "improved"
        else:
            row["verdict"] = "pass"
        rows.append(row)
    order = {"regress": 0, "missing": 1, "improved": 2, "new": 3, "pass": 4}
    rows.sort(key=lambda r: (order[r["verdict"]],
                             -(abs(r.get("delta_pct") or 0.0)
                               if r.get("delta_pct") not in (None,
                                                             float("inf"))
                               else 1e9)))
    return rows


def violations(rows: list[dict]) -> list[dict]:
    return [r for r in rows if r["verdict"] == "regress"]


def render(rows: list[dict], show_pass: bool = False) -> str:
    cols = ("KEY", "OLD", "NEW", "DELTA%", "BUDGET%", "VERDICT")
    table = [cols]
    shown = [r for r in rows if show_pass or r["verdict"] != "pass"]
    for r in shown:
        def num(v):
            return "-" if v is None else f"{v:g}"

        delta = r.get("delta_pct")
        table.append((r["key"], num(r["old"]), num(r["new"]),
                      "-" if delta is None else f"{delta:+.1f}",
                      num(r.get("budget_pct")), r["verdict"]))
    widths = [max(len(row[i]) for row in table) for i in range(len(cols))]
    lines = ["  ".join(c.ljust(w) for c, w in zip(row, widths)).rstrip()
             for row in table]
    counts: dict[str, int] = {}
    for r in rows:
        counts[r["verdict"]] = counts.get(r["verdict"], 0) + 1
    lines.append("summary: " + "  ".join(
        f"{k}={counts[k]}" for k in ("regress", "missing", "improved",
                                     "new", "pass") if k in counts))
    if not shown:
        lines.insert(1, "(no deltas outside budget; --show-pass for all)")
    return "\n".join(lines)


def load_budget(path: str | Path | None = None) -> dict:
    return json.loads(Path(path or BUDGET_PATH).read_text())


# -- fixture self-test (tier-1 / CI: needs no device, no bench run) ----------

_FIXTURE_OLD = {
    "metric": "resnet50_b8_p50_latency", "value": 1.2, "unit": "ms",
    "extra": {"req_s_chip": 6000.0, "mfu_pct": 40.0,
              "server_path": {"achieved_rps": 55.0,
                              "http_device_p50_ms": 120.0},
              "configs": {"gpt2": {"tokens_per_s": 15000.0}}}}

_FIXTURE_OK = {
    "metric": "resnet50_b8_p50_latency", "value": 1.4, "unit": "ms",
    "extra": {"req_s_chip": 5600.0, "mfu_pct": 38.0,
              "server_path": {"achieved_rps": 52.0,
                              "http_device_p50_ms": 131.0},
              "configs": {"gpt2": {"tokens_per_s": 14100.0}}}}

_FIXTURE_BAD = {
    "metric": "resnet50_b8_p50_latency", "value": 6.1, "unit": "ms",  # 5x
    "extra": {"req_s_chip": 900.0, "mfu_pct": 6.0,
              "server_path": {"achieved_rps": 8.0,   # collapsed
                              "http_device_p50_ms": 890.0},
              "configs": {"gpt2": {}}}}              # tokens_per_s vanished


def self_check(budget: dict) -> list[str]:
    """The sentinel must bite AND must not cry wolf; returns problems."""
    problems = []
    ok = diff(_FIXTURE_OLD, _FIXTURE_OK, budget)
    if violations(ok):
        problems.append("healthy fixture pair flagged as regression: "
                        + ", ".join(r["key"] for r in violations(ok)))
    bad = diff(_FIXTURE_OLD, _FIXTURE_BAD, budget)
    if not violations(bad):
        problems.append("5x-regressed fixture pair passed the budget")
    missing = [r for r in bad if r["verdict"] == "missing"]
    if not missing:
        problems.append("vanished fixture key not reported as missing")
    return problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("old", nargs="?", help="older round/artifact JSON")
    p.add_argument("new", nargs="?", help="newer round/artifact JSON")
    p.add_argument("--budget", default=None,
                   help=f"budget JSON (default {BUDGET_PATH.name})")
    p.add_argument("--json", action="store_true",
                   help="machine-readable verdict rows instead of the table")
    p.add_argument("--show-pass", action="store_true",
                   help="include in-budget keys in the table")
    p.add_argument("--check", action="store_true",
                   help="fixture self-test: the budget must fail a gross "
                        "regression and pass a healthy pair (CI mode)")
    args = p.parse_args(argv)
    budget = load_budget(args.budget)
    if args.check:
        problems = self_check(budget)
        for prob in problems:
            print(f"benchdiff --check: {prob}", file=sys.stderr)
        if not problems:
            print("benchdiff --check: sentinel bites and stays quiet (ok)")
        return 1 if problems else 0
    if not args.old or not args.new:
        p.error("pass OLD and NEW round files (or --check)")
    rows = diff(load_round(args.old), load_round(args.new), budget)
    if args.json:
        print(json.dumps({"rows": rows,
                          "violations": len(violations(rows))}, indent=1))
    else:
        print(render(rows, show_pass=args.show_pass))
    return 1 if violations(rows) else 0


if __name__ == "__main__":
    sys.exit(main())
