"""Per-layer metrics from the device time the program's own reduction of a
capture books to the named parts of the model (ISSUE 57): the ``programs``
block of the ``/admin/profile`` response, where every kind of program run
(``prefill``, ``segment``) has ``parts`` (``{part: ms}``, by the scope each
operation was traced in: ``pytorch_zappa_serverless_tpu/models/decoder.py``
``PARTS``), ``unnamed_ms`` and ``unnamed_ops`` (operations in no part) and
``part_ops`` (a part's three largest operation families).

A program older than the parts answers without them, and the CPU has no
device plane: the reader then gives ``None`` and the line leaves the metric
out."""

from __future__ import annotations


def _total(p: dict) -> float:
    """A kind's operations in ms, in a part or in none."""
    return sum(p["parts"].values()) + p["unnamed_ms"]


def _say(ctx, programs: dict) -> None:
    """The whole table, once a run: the log keeps what the metrics sum up."""
    if ctx.setdefault("_parts_said", False):
        return
    ctx["_parts_said"] = True
    for kind, p in programs.items():
        if "parts" not in p:
            continue
        total = _total(p)
        unnamed = ", ".join(f"{fam} {ms:.1f}" for fam, ms in
                            p.get("unnamed_ops", {}).items())
        print(f"[bench] parts of {kind} ({p['runs']} runs, {total:.1f} ms of "
              f"operations, {p['unnamed_ms']:.1f} in no part"
              + (f": {unnamed}" if unnamed else "") + "): "
              + "; ".join(
                  f"{part} {ms:.1f} ("
                  + ", ".join(f"{fam} {fam_ms:.1f}" for fam, fam_ms in
                              p["part_ops"].get(part, {}).items()) + ")"
                  for part, ms in p["parts"].items()), flush=True)


def read(ctx, kind: str, parts=()):
    programs = (ctx["run"].get("profile") or {}).get("programs") or {}
    booked = {k: p for k, p in programs.items() if "parts" in p}
    if not booked:
        return None
    _say(ctx, programs)
    if kind == "named_pct":
        total = sum(map(_total, booked.values()))
        unnamed = sum(p["unnamed_ms"] for p in booked.values())
        return 100.0 * (total - unnamed) / total if total else None
    if kind == "prefill_share":
        pre = booked.get("prefill")
        if not pre or not _total(pre):
            return None
        return sum(pre["parts"].get(part, 0.0) for part in parts) \
            / _total(pre)
    raise ValueError(f"parts reader has no kind {kind!r}")
