"""Served greedy tokens against the family's plain reference, after the
server has stopped (the HTTP API returns tokens, not logits).

What every family shares is here: a reference request that failed, or a
prompt that sent twice, alone, gave two answers, fails the check before any
reference runs; ``walk`` judges served tokens position by position.  What is
compared beyond that is the family's own (``check`` of
``benchmark/families/<family>.py``).
"""

from __future__ import annotations

import numpy as np

from benchmark import families


def check_reference(config: dict, serve: dict, checkpoint, runs: list) -> dict:
    for r in runs:
        if r["error"]:
            return {"ok": False, "note": f"reference request: {r['error']}"}
        if r["tokens"] != r["again"]:
            return {"ok": False, "note": "the same prompt sent twice, alone, "
                    f"gave different greedy tokens: {r['tokens']} then "
                    f"{r['again']}"}
    return families.load(config).check(config, serve, checkpoint, runs)


def walk(logits_of, runs: list, tol: float) -> dict:
    """For each reference sequence the served tokens are fed back to the
    reference (prompt + tokens served so far), so every position is judged
    on its own and one near-tie cannot spoil what follows.  ``logits_of(ids)``
    gives the reference's logits ``[len(ids), vocab]``; the number compared
    is the widest gap by which a served token lies under the reference's
    best."""
    worst, exact, total = 0.0, 0, 0
    for r in runs:
        ids, toks = r["ids"], r["tokens"]
        logits = logits_of(ids + toks[:-1])
        for j, tok in enumerate(toks):
            row = logits[len(ids) - 1 + j]
            deficit = float(np.max(row) - row[tok])
            worst = max(worst, deficit)
            exact += deficit == 0.0
            total += 1
    return {"ok": worst <= tol, "worst": worst,
            "note": f"{exact} of {total} served tokens are the float32 "
                    f"reference's best; the farthest lies {worst:.4f} under "
                    f"it in the reference's logits (tolerance {tol})"}
