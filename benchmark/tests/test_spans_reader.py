"""``readers/spans.py`` on a context made by hand: every kind, with its
sources there and with them absent (an older program, or no device plane)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark.readers import spans

ROOT = Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmark" / "layer_metrics"
PHASES = ("round.idle", "round.admit_host", "round.lane_wait",
          "prefill.launch", "prefill.fetch", "insert.launch",
          "segment.launch", "segment.fetch", "round.wakeup",
          "round.distribute")


def record(i, **stats):
    return {"error": None, "due": 0.0, "t_tokens": [0.2 + 0.001 * i],
            "stats": {"rounds_to_first_token": 3, **stats}}


def phases(scale):
    return {p: {"sum_ms": scale * (i + 1.0), "count": int(scale)}
            for i, p in enumerate(PHASES)}


def stages(scale):
    return {"gpt2xl": {"serialize": {"sum": 1.0 * scale, "count": 8},
                       "respond": {"sum": 3.0 * scale, "count": 8}}}


@pytest.fixture
def ctx():
    legs = [dict(ingest_ms=1.0 + i, round_wait_ms=40.0 - i, slot_wait_ms=0.0
                 if i < 9 else 90.0, prefill_ms=30.0, first_emit_ms=89.0,
                 egress_ms=0.5) for i in range(10)]
    return {
        "seconds": 10.0, "serve": {"model": "gpt2xl"},
        "run": {
            "drain_s": 0.5,
            "records": [record(i, **leg) for i, leg in enumerate(legs)]
            + [{"error": "HTTP 500", "stats": {}}],
            "gen_before": {"segment_rounds": 10, "host_phases": phases(10.0)},
            "gen_after": {"segment_rounds": 110, "host_phases": phases(110.0)},
            "perf_before": {"ingest": stages(1.0)},
            "perf_after": {"ingest": stages(11.0)},
            "profile": {
                "idle": {"window_ms": 3000.0, "busy_ms": 2700.0,
                         "idle_ms": 300.0, "unattributed_ms": 15.0,
                         "by_phase": {"round.wakeup": 200.0,
                                      "in_program": 85.0},
                         "gaps": [{"before": "segment", "after": "segment",
                                   "ms": 215.0, "count": 30,
                                   "phases": {"round.wakeup": 200.0,
                                              "unattributed": 15.0}}],
                         "clock": {"segments": 30, "ok": 30,
                                   "lag_ms": {"min": 0.1, "max": 0.4}}}}}}


def spec(name):
    return json.loads((METRICS / f"{name}.json").read_text())["args"]


@pytest.mark.parametrize("name,want", [
    ("http_self_p50_ms", 5.5),            # ingest 5 + egress 0.5, the 5th
    ("http_self_p50_ms.xl", 5.5),
    ("round_wait_p50_ms", 35.0),
    ("round_wait_p50_ms.xl", 35.0),
    ("slot_wait_p90_ms", 0.0),            # nearest rank: the 9th of 10
    ("slot_wait_p90_ms.xl", 0.0),
    ("first_emit_p50_ms", 89.0),
    ("first_emit_p50_ms.xl", 89.0),
    ("sse_ms_per_round", (10.0 + 30.0) / 100),
    ("host_turnaround_ms", (9 + 10 + 2 + 3) * 100.0 / 100),
    ("segment_launch_ms", 7.0),           # 700 ms over 100 launches
    ("idle_attributed_pct", 95.0),
    ("idle_attributed_pct.bulk", 95.0),
])
def test_each_metric_reads_its_source(ctx, name, want):
    assert spans.read(ctx, **spec(name)) == pytest.approx(want)


def test_the_table_is_said_once(ctx, capsys):
    spans.read(ctx, **spec("segment_launch_ms"))
    spans.read(ctx, **spec("idle_attributed_pct"))
    out = capsys.readouterr().out.splitlines()
    assert [line.split(":")[0] for line in out] == [
        "[bench] host phases (ms/round)", "[bench] idle by phase",
        "[bench] ttft tiling (ms, medians)"]
    assert "segment.launch 7.000" in out[0] and "100 rounds" in out[0]
    assert "round.wakeup 200.0" in out[1] and "segment-segment" in out[1]
    # Client TTFT 200-209 ms against 160.5-259.5 ms of stamps.
    assert "10 requests" in out[2] and "first_emit 89.000" in out[2]


@pytest.mark.parametrize("name", sorted(
    p.stem for p in METRICS.glob("*.json")
    if json.loads(p.read_text())["reader"] == "spans"))
def test_an_older_program_reads_as_nothing(ctx, name, capsys):
    """No stamps in ``stats``, no ``host_phases``, no ``idle``: ``None`` (or,
    for the one metric that needs no program change, still a number)."""
    run = ctx["run"]
    for r in run["records"]:
        r["stats"] = {"rounds_to_first_token": 3} if not r["error"] else {}
    del run["gen_before"]["host_phases"], run["gen_after"]["host_phases"]
    run["profile"] = {"dir": "/x", "ops": []}
    got = spans.read(ctx, **spec(name))
    assert got == (pytest.approx(0.4) if name == "sse_ms_per_round" else None)
    run["profile"] = None  # and --rehearse posts no profile it could read
    run["perf_after"]["ingest"] = {}
    assert spans.read(ctx, **spec(name)) is None
    assert capsys.readouterr().out == ""


def test_every_spans_metric_is_in_the_benchmark():
    bench = {m["name"]: m
             for m in json.loads((ROOT / "BENCHMARK.json").read_text())[
                 "per_layer"]}
    mine = [json.loads(p.read_text()) for p in METRICS.glob("*.json")
            if json.loads(p.read_text())["reader"] == "spans"]
    assert len(mine) == 13
    for m in mine:
        entry = bench[m["name"]]
        assert {k: entry[k] for k in ("unit", "better", "source", "layer",
                                      "moves")} \
            == {k: m[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}


def test_rehearsal_reports_the_request_and_counter_metrics():
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "gpt2xl-chat",
         "--seed", "1", "--seconds", "6", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    assert {"http_self_p50_ms.xl", "round_wait_p50_ms.xl",
            "slot_wait_p90_ms.xl", "first_emit_p50_ms.xl",
            "sse_ms_per_round", "host_turnaround_ms",
            "segment_launch_ms"} <= set(line["metrics"])
    # No device plane on the CPU: what the trace would give is left out.
    assert not {"pool_copy_slice_pct", "idle_attributed_pct"} \
        & set(line["metrics"])
    assert "[bench] host phases (ms/round)" in proc.stdout
    assert "[bench] ttft tiling" in proc.stdout
