#!/usr/bin/env python3
"""Find a chat cell's knee once, on the chip: one server boot, ascending
offered rates, the same open-loop generator as ``run.py``.

    python3 benchmark/sweep.py --workload gpt2xl-chat --rates 2,3,4,5,6 --seconds 40

For each rate it prints one JSON line: the offered rate, TTFT and token gap,
tokens per scheduler round and the share of slot-steps that emitted a token
(occupancy), how long the backlog took to drain after the last request was
due, and the mean TTFT of the second half of the requests over the first
(a backlog that grows shows as a ratio well above 1).  The knee is the
highest rate whose backlog does not grow; the rate written into the mix's
file follows the rule in PERF.md.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.client import percentile  # noqa: E402
from benchmark.run import (gen_counters, load_cell, serve_fragment,  # noqa: E402
                           warm_plan, warm_up)
from benchmark.server import Server, stage_weights  # noqa: E402


async def sweep(args, config, mix, serve, srv, scale):
    import aiohttp

    extra = serve["extra"]
    slots, seg = int(extra["gen_slots"]), int(extra["segment_tokens"])
    vocab = int(extra["arch"]["vocab_size"])
    model = serve["model"]
    url = f"{srv.url}/v1/models/{model}:generate"
    gen = importlib.import_module(f"benchmark.generators.{mix['generator']}")
    buckets, sizes = warm_plan(mix, serve, scale)
    async with aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=None, sock_read=300),
            connector=aiohttp.TCPConnector(limit=0)) as session:
        await warm_up(session, srv, model, url, buckets, sizes, slots, seg,
                      int(extra["max_new_tokens"]), vocab)
        for rate in args.rates:
            planned = gen.plan({**mix, "rate_per_s": rate}, args.seconds,
                               args.seed, vocab, scale, slots)
            srv.mark()
            before = await gen_counters(session, srv, model)
            t0 = time.perf_counter()
            recs = await gen.drive(session, url, planned, args.seconds,
                                   time.perf_counter)
            drain = time.perf_counter() - t0 - args.seconds
            after = await gen_counters(session, srv, model)
            ok = [r for r in recs if not r["error"]]
            ttft = [(r["t_tokens"][0] - r["due"]) * 1e3 for r in ok]
            half = len(ttft) // 2
            rounds = after["segment_rounds"] - before["segment_rounds"]
            tpr = (after["tokens_emitted"] - before["tokens_emitted"]) \
                / max(rounds, 1)
            print(json.dumps({
                "rate_per_s": rate, "requests": len(recs),
                "failed": len(recs) - len(ok),
                "ttft_p50_ms": percentile(ttft, 0.5),
                "ttft_p90_ms": percentile(ttft, 0.9),
                "tpot_p50_ms": percentile(
                    [(r["t_tokens"][-1] - r["t_tokens"][0]) * 1e3
                     / (len(r["tokens"]) - 1) for r in ok], 0.5),
                "tokens_per_round": tpr,
                "occupancy": tpr / (slots * seg),
                "rounds_per_s": rounds / (args.seconds + drain),
                "drain_s": drain,
                "ttft_second_half_over_first":
                    (sum(ttft[half:]) / max(len(ttft) - half, 1))
                    / (sum(ttft[:half]) / max(half, 1)),
                "compiles": srv.compiles_since_mark()}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    _, cell, config, mix = load_cell(args.workload)
    serve, scale = serve_fragment(config, args.rehearse)
    srv = Server(args.workload + "-sweep", serve,
                 stage_weights(config, serve, args.rehearse), args.rehearse)
    try:
        device = srv.wait_healthy(1100.0)["device"]
        if device["platform"] != ("cpu" if args.rehearse else "tpu"):
            raise SystemExit(f"the server found {device}")
        print(json.dumps({"device": device}), flush=True)
        asyncio.run(sweep(args, config, mix, serve, srv, scale))
    finally:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
