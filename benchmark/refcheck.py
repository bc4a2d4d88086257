"""Served greedy tokens against the plain reference, after the server has
stopped (the HTTP API returns tokens, not logits).

For each reference sequence the served tokens are fed back to the reference
(prompt + tokens served so far), so every position is judged on its own and
one near-tie cannot spoil what follows.  The served token must be the
reference's best, or lie within ``reference_tolerance`` of it in the
reference's own logits: the server computes in bfloat16 and the reference in
float32, so where the reference's two best are closer than the rounding
error either is a right answer.
"""

from __future__ import annotations

import importlib

import numpy as np


def check_reference(config: dict, serve: dict, checkpoint, runs: list) -> dict:
    ref = importlib.import_module(f"benchmark.reference.{config['family']}")
    arch = serve["extra"]["arch"]
    tol = float(config["reference_tolerance"])
    for r in runs:
        if r["error"]:
            return {"ok": False, "note": f"reference request: {r['error']}"}
        if r["tokens"] != r["again"]:
            return {"ok": False, "note": "the same prompt sent twice, alone, "
                    f"gave different greedy tokens: {r['tokens']} then "
                    f"{r['again']}"}
    weights = ref.prepare(ref.load_tree(checkpoint), arch["layers"],
                          serve["extra"]["params_dtype"] == "int8")
    worst, exact, total = 0.0, 0, 0
    for r in runs:
        ids, toks = r["ids"], r["tokens"]
        logits = ref.forward(weights, ids + toks[:-1], arch["layers"],
                             arch["heads"], float(config["layer_norm_epsilon"]))
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
