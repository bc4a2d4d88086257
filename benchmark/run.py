#!/usr/bin/env python3
"""One run of one benchmark cell; the last line of stdout is its result.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``benchmark/configs/<config>.json``) under a traffic mix
(``benchmark/traffic/<mix>.json``).  The run stages the configuration's
weights if this checkout has none yet, starts ``tpuserve serve`` as the one
owner of the chip, warms every program the window can use, measures for
``--seconds``, stops the server and compares what it served with the plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1``
is a run of its own that captures a device trace in mid-window and reports
the per-layer metrics (``benchmark/layer_metrics/<metric>.json``, each read
by ``benchmark/readers/<reader>.py``).  What belongs to a model is in its
family's module (``benchmark/families``); this file names none.  It reads
``gen_slots``, ``segment_tokens``, ``max_new_tokens`` and ``arch.vocab_size``
of ``serve.extra``: they are the slot scheduler's contract
(``serving/generation.py``) with every model it serves, not one family's.

This process drives the load over HTTP and never imports JAX while the
server holds the chip.  ``--rehearse`` runs the same path on the CPU at the
configuration's tiny ``rehearse`` widths; its last line names platform
``cpu`` and so cannot pass for a chip run.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import traffic  # noqa: E402
from benchmark.client import percentile, stream_request  # noqa: E402
from benchmark.server import Server, stage_weights  # noqa: E402

HERE = ROOT / "benchmark"
BENCHMARK_FILE = ROOT / "BENCHMARK.json"


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_cell(name: str, benchmark_file: Path = BENCHMARK_FILE
              ) -> tuple[dict, dict, dict, dict]:
    bench = json.loads(benchmark_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in {benchmark_file}; it has "
                         f"{sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    path = ROOT / files[cell["config"]]
    config = {**json.loads(path.read_text()), "file": str(path)}
    return bench, cell, config, traffic.load_mix(cell["traffic"])


def metrics_of(bench: dict, cell: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def serve_fragment(config: dict, rehearse: bool) -> tuple[dict, float]:
    """The configuration's serve fragment as this run boots it, and the scale
    of its traffic: the file's own, or the tiny ``rehearse`` widths."""
    serve = json.loads(json.dumps(config["serve"]))
    if not rehearse:
        return serve, 1.0
    reh = config["rehearse"]
    serve["seq_buckets"] = reh["seq_buckets"]
    serve["extra"].update(reh["extra"])
    return serve, float(reh["scale"])


def warm_plan(mix: dict, serve: dict, scale: float) -> tuple[list, list]:
    """The prompt buckets the mix's lengths fall into, and the admission
    batch sizes (powers of two, as the scheduler pads them) a window of this
    mix can form: up to ``admit_max``, or up to every slot."""
    slots = int(serve["extra"]["gen_slots"])
    buckets = sorted({traffic.bucket_for(n, serve["seq_buckets"])
                      for n in traffic.lengths(mix["prompt_tokens"], 512,
                                               scale)})
    admit_max = slots if mix["admit_max"] == "slots" \
        else min(int(mix["admit_max"]), slots)
    return buckets, [1 << i for i in range((admit_max - 1).bit_length() + 1)]


# -- warm-up -------------------------------------------------------------------

def warm_rounds(buckets: list[int], sizes: list[int],
                slots: int) -> list[list[tuple[int, int]]]:
    """Pack (bucket, batch) admissions into rounds: within a round every
    bucket appears once and the batches leave a slot for the blocker; a batch
    of all the slots gets a round of its own."""
    rounds: list[list[tuple[int, int]]] = []
    for size in sizes:
        if size >= slots:
            rounds += [[(b, size)] for b in buckets]
            continue
        cur: list[tuple[int, int]] = []
        for b in buckets:
            if sum(s for _, s in cur) + size > slots - 1:
                rounds.append(cur)
                cur = []
            cur.append((b, size))
        if cur:
            rounds.append(cur)
    return rounds


async def gen_counters(session, srv: Server, model: str) -> dict:
    async with session.get(srv.url + "/metrics") as resp:
        return (await resp.json())["generation"][model]


async def warm_up(session, srv: Server, model: str, gen_url: str,
                  buckets: list[int], sizes: list[int], slots: int, seg: int,
                  max_new: int, vocab: int) -> int:
    """Run every (prompt bucket, admission batch) prefill program, the insert
    and the segment program once, so that each is compiled or restored from
    the cache before the window.  A batch forms when its requests are all
    pending at one admission: they are sent while a blocker's segment runs.
    Returns how many rounds did not group as planned after three tries."""
    rng = np.random.default_rng(0)
    clock = time.perf_counter

    def req(bucket: int, new: int):
        return stream_request(session, gen_url,
                              traffic.token_ids(rng, bucket, vocab),
                              min(new, max_new), due=clock(), clock=clock)

    missed = 0
    for rnd in warm_rounds(buckets, sizes, slots):
        full = rnd[0][1] >= slots
        want = len(rnd) + (0 if rnd == [(rnd[0][0], 1)] else 1)
        for attempt in range(3):
            before = (await gen_counters(session, srv, model))[
                "prefill_dispatches"]
            if want == len(rnd):  # a single request: nothing to group
                recs = [await req(rnd[0][0], seg)]
            else:
                # The blocker leaves after one segment where the batch needs
                # every slot, and stays for three where it does not.
                blocker = asyncio.ensure_future(
                    req(buckets[0], seg if full else 3 * seg))
                await asyncio.sleep(0.03 / (attempt + 1))
                recs = await asyncio.gather(
                    blocker, *(req(b, seg) for b, n in rnd for _ in range(n)))
            bad = [r["error"] for r in recs if r["error"]]
            if bad:
                raise SystemExit(f"warm-up request failed: {bad[0]}")
            got = (await gen_counters(session, srv, model))[
                "prefill_dispatches"] - before
            if got == want:
                break
        else:
            missed += 1
            say(f"warm-up: round {rnd} made {got} prefill dispatches, "
                f"planned {want}")
    return missed


# -- the measured window ---------------------------------------------------------

async def measure(args, config: dict, mix: dict, srv: Server, scale: float,
                  serve: dict) -> dict:
    import aiohttp

    model = serve["model"]
    extra = serve["extra"]
    slots, seg = int(extra["gen_slots"]), int(extra["segment_tokens"])
    vocab = int(extra["arch"]["vocab_size"])
    gen_url = f"{srv.url}/v1/models/{model}:generate"
    generator = importlib.import_module(
        f"benchmark.generators.{mix['generator']}")
    planned = generator.plan(mix, args.seconds, args.seed, vocab, scale, slots)
    buckets, sizes = warm_plan(mix, serve, scale)
    out: dict = {"split": {}}
    timeout = aiohttp.ClientTimeout(total=None, sock_read=300)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout,
                                     connector=conn) as session:
        t = time.monotonic()
        out["warm_missed"] = await warm_up(
            session, srv, model, gen_url, buckets, sizes, slots, seg,
            int(extra["max_new_tokens"]), vocab)
        out["split"]["warm_up_requests_s"] = time.monotonic() - t

        # The reference's sequences, each sent twice, alone: greedy decoding
        # has to repeat, and the tokens are compared after the server stops.
        t = time.monotonic()
        rng = np.random.default_rng(config["weights"]["seed"] + 1)
        out["reference_runs"] = []
        for n in config["reference_prompts"]:
            n = max(2, round(n * scale))
            ids = traffic.token_ids(rng, n, vocab)
            new = min(16, int(extra["max_new_tokens"]))
            a = await stream_request(session, gen_url, ids, new,
                                     due=time.perf_counter(),
                                     clock=time.perf_counter)
            b = await stream_request(session, gen_url, ids, new,
                                     due=time.perf_counter(),
                                     clock=time.perf_counter)
            out["reference_runs"].append(
                {"ids": ids, "tokens": a["tokens"], "again": b["tokens"],
                 "done": a["done"], "done_again": b["done"],
                 "error": a["error"] or b["error"]})
        out["split"]["reference_requests_s"] = time.monotonic() - t

        srv.mark()  # compiles are counted from here
        before = await gen_counters(session, srv, model)
        async with session.get(srv.url + "/admin/perf") as resp:
            perf_before = await resp.json()

        capture_s = min(traffic.profile_seconds(mix), args.seconds)

        async def profile():  # the traced slice, in the middle of the window
            await asyncio.sleep((args.seconds - capture_s) / 2)
            async with session.post(
                    srv.url + "/admin/profile",
                    json={"seconds": capture_s, "top": 1}) as resp:
                if resp.status != 200:
                    raise SystemExit(f"/admin/profile -> {resp.status}: "
                                     f"{(await resp.text())[:300]}")
                return await resp.json()

        gc.collect()
        gc.freeze()
        gc.disable()
        out["setup_s"] = time.monotonic() - T_START
        prof = asyncio.ensure_future(profile()) if args.trace else None
        t_wall0 = time.perf_counter()
        records = await generator.drive(session, gen_url, planned,
                                        args.seconds, time.perf_counter)
        out["drain_s"] = time.perf_counter() - t_wall0 - args.seconds
        gc.enable()
        out["profile"] = await prof if prof else None
        after = await gen_counters(session, srv, model)
        async with session.get(srv.url + "/admin/perf") as resp:
            perf_after = await resp.json()
    out.update(records=records, gen_before=before, gen_after=after,
               perf_before=perf_before, perf_after=perf_after,
               compiles_in_window=srv.compiles_since_mark())
    return out


# -- reduction to metrics ----------------------------------------------------------

def end_to_end(name: str, run: dict, seconds: float) -> float:
    """The cell's end-to-end metrics, over all the requests of the window.
    A request that failed counts as slower than any that answered."""
    recs = [r for r in run["records"] if r["in_window"] or r["error"]]
    if name == "setup_s":
        return run["setup_s"]
    if name == "req_per_s":
        return sum(1 for r in recs if not r["error"]) / seconds
    ttft_q = re.fullmatch(r"ttft_p(\d\d)_ms", name)
    if ttft_q:
        ttft = [(r["t_tokens"][0] - r["due"]) * 1000.0
                if not r["error"] else math.inf for r in recs]
        return percentile(ttft, int(ttft_q.group(1)) / 100)
    if name == "tpot_p50_ms":
        tpot = [(r["t_tokens"][-1] - r["t_tokens"][0]) * 1000.0
                / (len(r["tokens"]) - 1)
                for r in recs if not r["error"] and len(r["tokens"]) > 1]
        return percentile(tpot, 0.5)
    raise SystemExit(f"no rule for the end-to-end metric {name!r}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny widths; the last line says platform cpu")
    ap.add_argument("--benchmark-file", type=Path, default=BENCHMARK_FILE,
                    help="another list of cells than the repo's own")
    args = ap.parse_args()
    if not (ROOT / "pytorch_zappa_serverless_tpu" / "cli.py").is_file():
        raise SystemExit("the program (pytorch_zappa_serverless_tpu) is not "
                         "in this directory: nothing to measure")
    bench, cell, config, mix = load_cell(args.workload, args.benchmark_file)
    serve, scale = serve_fragment(config, args.rehearse)
    split: dict[str, float] = {}

    t = time.monotonic()
    ckpt = stage_weights(config, serve, args.rehearse)
    split["stage_weights_s"] = time.monotonic() - t
    srv = Server(args.workload, serve, ckpt, args.rehearse)
    split["start_to_spawn_s"] = srv.t_spawn - T_START \
        - split["stage_weights_s"]
    try:
        health = srv.wait_healthy(1100.0)
        device = health["device"]
        want = "cpu" if args.rehearse else "tpu"
        if device["platform"] != want or device["count"] < cell["chips"]:
            raise SystemExit(f"the server found {device}; this cell needs "
                             f"{cell['chips']} {want} device(s)")
        split.update(srv.boot_split())
        run = asyncio.run(measure(args, config, mix, srv, scale, serve))
        split.update(run.pop("split"))
    finally:
        t = time.monotonic()
        memory = srv.stop()
        split["server_stop_s"] = time.monotonic() - t

    os.environ["JAX_PLATFORMS"] = "cpu"  # the chip is free, and stays so
    from benchmark.refcheck import check_reference

    t = time.monotonic()
    ref = check_reference(config, serve, ckpt, run["reference_runs"])
    split["reference_check_s"] = time.monotonic() - t

    recs = run["records"]
    errors = [r["error"] for r in recs if r["error"]]
    attempted = sum(1 for r in recs if r["in_window"] or r["error"])
    correct = (not errors and ref["ok"] and run["compiles_in_window"] == 0)
    for e in sorted(set(errors))[:5]:
        say(f"request error: {e}")
    say(f"reference: {ref['note']}")
    say(f"compiles inside the window: {run['compiles_in_window']}; warm-up "
        f"rounds that did not group as planned: {run['warm_missed']}")
    late = [(r["sent"] - r["due"]) * 1000.0 for r in recs if r["sent"]]
    say(f"generator: {len(recs)} requests, sent late p50 "
        f"{percentile(late, 0.5):.3f} ms p90 {percentile(late, 0.9):.3f} ms "
        f"max {max(late):.3f} ms; drained {run['drain_s']:.2f} s after the "
        f"window")
    ttft = sorted((r["t_tokens"][0] - r["due"]) * 1000.0 for r in recs
                  if not r["error"] and r["in_window"])
    say("ttft ms: " + ", ".join(f"p{int(q * 100)} {percentile(ttft, q):.3f}"
                                for q in (0.5, 0.75, 0.9, 0.95)))
    say("set-up split (s): " + ", ".join(f"{k[:-2]} {v:.2f}"
                                         for k, v in split.items()))
    say(f"compile cache {srv.cache_dir}: " + srv.cache_note())

    if len(errors) > 0.1 * max(attempted, 1):
        raise SystemExit(f"{len(errors)} of {attempted} requests failed: no "
                         f"result")
    metrics: dict[str, dict] = {}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": memory["memory_peak_bytes"]}
    line: dict = {"correct": bool(correct), "attempted": attempted,
                  "failed": len(errors)}
    if args.trace:
        from benchmark.trace_reduce import reduce_trace

        t = time.monotonic()
        trace = reduce_trace(run["profile"]["dir"], config["programs"])
        shutil.rmtree(run["profile"]["dir"], ignore_errors=True)  # tens of MB
        say(f"trace reduced in {time.monotonic() - t:.1f} s: "
            + json.dumps({kind: [p["runs"], round(p["seconds"], 6)]
                          for kind, p in trace["programs"].items()}))
        if trace["busy_s"] <= 0 and not args.rehearse:
            raise SystemExit("the trace holds no device operation")
        ctx = {"run": run, "trace": trace, "config": config, "serve": serve,
               "split": split, "seconds": args.seconds, "device": dev,
               "peaks": json.loads((HERE / "peaks.json").read_text())}
        for m in metrics_of(bench, cell["name"], "per_layer"):
            spec = json.loads(
                (HERE / "layer_metrics" / f"{m['name']}.json").read_text())
            reader = importlib.import_module(
                f"benchmark.readers.{spec['reader']}")
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    else:
        for m in metrics_of(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {
                "value": end_to_end(m["name"], run, args.seconds),
                "unit": m["unit"]}
    line.update(metrics=metrics, device=dev)
    # What was compared, beside its limit, where a record of a run that is
    # not correct keeps it: the end of standard error.
    print(f"[bench] correct {bool(correct)}: request errors {len(errors)} "
          f"(limit 0), compiles inside the window "
          f"{run['compiles_in_window']} (limit 0); reference: {ref['note']}",
          file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
