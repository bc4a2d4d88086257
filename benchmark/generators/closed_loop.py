"""Closed loop: a fixed number of clients, each sending its next request when
the last one has answered; counts what completes inside the window."""

from __future__ import annotations

import asyncio

import numpy as np

from benchmark import traffic
from benchmark.client import stream_request


def plan(mix: dict, seconds: float, seed: int, vocab: int, scale: float,
         slots: int):
    """An endless sequence of requests in blocks of ``block``: each block is
    the same stratified multiset of lengths, permuted by the seed, so any
    prefix of the sequence is the same work to within one block."""
    rng = np.random.default_rng(seed)
    n = int(mix["block"])
    prompts = traffic.lengths(mix["prompt_tokens"], n, scale)
    answers = traffic.lengths(mix["answer_tokens"], n, scale)

    def blocks():
        while True:
            for p, a in zip(rng.permutation(prompts), rng.permutation(answers)):
                yield {"ids": traffic.token_ids(rng, int(p), vocab),
                       "max_new": int(a)}

    return {"clients": int(mix["clients_per_slot"]) * slots,
            "requests": blocks()}


async def drive(session, url: str, planned: dict, seconds: float,
                clock) -> list[dict]:
    t0 = clock()
    source = planned["requests"]
    records: list[dict] = []

    async def client():
        while clock() - t0 < seconds:
            req = next(source)
            now = clock()
            rec = await stream_request(session, url, req["ids"],
                                       req["max_new"], due=now, clock=clock)
            rec["in_window"] = rec["t_end"] is not None \
                and rec["t_end"] - t0 <= seconds
            records.append(rec)

    await asyncio.gather(*(client() for _ in range(planned["clients"])))
    return records
