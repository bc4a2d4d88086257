"""One ``:generate`` SSE stream, timed on the client's clock."""

from __future__ import annotations

import json
import math

import aiohttp


def percentile(values: list[float], q: float) -> float:
    """Nearest rank: the smallest value with at least ``q`` of all at or
    under it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


async def stream_request(session: aiohttp.ClientSession, url: str,
                         ids: list[int], max_new: int, *, due: float,
                         clock) -> dict:
    """POST one greedy request and read its stream to the end.

    The record holds when it was due and sent, when each token arrived, the
    tokens, the ``done`` event whole (and its ``stats`` beside it), and
    ``error`` where the stream was refused, broke, or was not well formed
    (token events that differ from the ``done`` event's list, or another
    length than was asked for).
    """
    rec = {"due": due, "sent": None, "t_tokens": [], "tokens": [],
           "t_end": None, "stats": {}, "done": None, "error": None,
           "asked": max_new, "prompt_len": len(ids)}
    body = json.dumps({"input_ids": ids, "max_new_tokens": max_new}).encode()
    try:
        rec["sent"] = clock()
        async with session.post(
                url, data=body,
                headers={"Content-Type": "application/json"}) as resp:
            if resp.status != 200:
                rec["error"] = f"HTTP {resp.status}: " \
                               f"{(await resp.text())[:200]}"
                return rec
            final = None
            async for raw in resp.content:
                if not raw.startswith(b"data: "):
                    continue
                ev = json.loads(raw[6:])
                if "token" in ev:
                    rec["t_tokens"].append(clock())
                    rec["tokens"].append(int(ev["token"]))
                elif ev.get("done"):
                    final = ev
                elif "error" in ev:
                    rec["error"] = f"stream error: {ev['error']}"
            rec["t_end"] = clock()
    except (aiohttp.ClientError, ConnectionError, TimeoutError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        return rec
    if rec["error"] is None:
        if final is None:
            rec["error"] = "stream ended without a done event"
        elif final["tokens"] != rec["tokens"]:
            rec["error"] = "streamed tokens differ from the done event's"
        elif len(rec["tokens"]) != max_new:
            rec["error"] = f"{len(rec['tokens'])} tokens, asked {max_new}"
        else:
            rec["done"] = final
            rec["stats"] = final.get("stats", {})
    return rec
