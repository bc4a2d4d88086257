"""Open loop: requests are sent on a schedule, whether or not earlier ones
have finished, and each is timed from when it was due."""

from __future__ import annotations

import asyncio

import numpy as np

from benchmark import traffic
from benchmark.client import stream_request


def plan(mix: dict, seconds: float, seed: int, vocab: int, scale: float,
         slots: int) -> list[dict]:
    """``round(rate x seconds)`` requests: stratified prompt lengths, answer
    lengths and gaps, each permuted by the seed on its own."""
    n = max(1, round(mix["rate_per_s"] * seconds))
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(traffic.lengths(mix["prompt_tokens"], n, scale))
    answers = rng.permutation(traffic.lengths(mix["answer_tokens"], n, scale))
    gaps = rng.permutation(traffic.stratified(
        {"dist": "exponential", "mean": 1.0}, n))
    # The gaps fill the window exactly: the first request is due one gap in,
    # the last as the window ends, under every seed.
    due = np.cumsum(gaps) * (seconds / float(np.sum(gaps)))
    return [{"due": float(d), "ids": traffic.token_ids(rng, int(p), vocab),
             "max_new": int(a)} for d, p, a in zip(due, prompts, answers)]


async def drive(session, url: str, requests: list[dict], seconds: float,
                clock) -> list[dict]:
    """Send each request when it is due; returns one record per request."""
    t0 = clock()

    async def one(req):
        delay = t0 + req["due"] - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        return await stream_request(session, url, req["ids"], req["max_new"],
                                    due=t0 + req["due"], clock=clock)

    records = await asyncio.gather(*(one(r) for r in requests))
    for rec in records:
        rec["in_window"] = True  # every request is due inside the window
    return list(records)
