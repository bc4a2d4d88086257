"""Stratified traffic: every seed offers the same multiset, in another order.

A mix (``benchmark/traffic/<mix>.json``) states distributions; ``stratified``
takes the N quantile midpoints of one, so N requests always carry the same
lengths and gaps, the same total work and the same span.  The seed permutes
them and draws the token ids, nothing else.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    """The mix's parameters, with those of the mix it ``extends`` beneath."""
    path = HERE / "traffic" / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no traffic mix {name!r}: {path} is missing")
    mix = json.loads(path.read_text())
    base = mix.pop("extends", None)
    return {**load_mix(base), **mix} if base else mix


def profile_seconds(mix: dict) -> float:
    """How long a ``--trace 1`` run's capture lasts in this mix.  A cell
    states a length that holds 20-30 runs of its main program: ``stop_trace``
    costs seconds for each, after the window and inside the run's limit."""
    return float(mix.get("profile_seconds", 1.5))


def quantile(dist: dict, q: float) -> float:
    kind = dist["dist"]
    if kind == "const":
        return float(dist["value"])
    if kind == "uniform":
        return dist["min"] + q * (dist["max"] - dist["min"])
    if kind == "lognormal":
        return dist["median"] * math.exp(dist["sigma"]
                                         * NormalDist().inv_cdf(q))
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-q)
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(dist: dict, n: int) -> list[float]:
    """The n quantile midpoints of ``dist``, clipped to its min and max."""
    lo, hi = dist.get("min", -math.inf), dist.get("max", math.inf)
    return [min(max(quantile(dist, (i + 0.5) / n), lo), hi) for i in range(n)]


def lengths(dist: dict, n: int, scale: float = 1.0) -> list[int]:
    """Stratified whole token counts; ``scale`` shrinks them for rehearsal."""
    return [max(2, round(v * scale)) for v in stratified(dist, n)]


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> list[int]:
    return [int(t) for t in rng.integers(0, vocab, n)]


def bucket_for(n: int, buckets: list[int]) -> int:
    for b in sorted(buckets):
        if b >= n:
            return b
    raise ValueError(f"a prompt of {n} tokens fits no bucket of {buckets}")
