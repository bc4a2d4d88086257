"""``readers/programs.py`` on the blocks a run scrapes round its window
(recorded from a rehearsal of ``gpt2xl-doc-bulk``): every kind, with its
source there and with it absent (the parent of PR 42, whose server keeps no
ledger), and the eleven metrics' files against ``BENCHMARK.json``."""

import json
from pathlib import Path

import pytest

from benchmark.readers import programs

ROOT = Path(__file__).resolve().parents[2]
METRICS = ROOT / "benchmark" / "layer_metrics"
NAMES = {"warmup_trace_s": 0.969272, "warmup_lower_s": 0.970664,
         "warmup_cache_read_s": 0.418329, "warmup_backend_s": 0.028072,
         "warmup_first_run_s": 0.261853, "warmup_ledger_pct": None,
         "boot_import_s": 3.892, "boot_backend_s": 0.025,
         "first_uses_in_window": 2, "first_uses_in_window.xl": 2,
         "first_uses_in_window.bulk": 2}
PROGRAMS = {"first_uses": 13, "trace_s": 0.969272, "lower_s": 0.970664,
            "cache_read_s": 0.418329, "backend_hit_s": 0.028072,
            "backend_miss_s": 0.0, "launch_s": 2.513,
            "first_run_s": 0.261853}
# The same block after a window in which a burst formed a batch that the
# warm-up had not: one prefill and one insert_from, both of cause ``shape``.
AFTER = {**PROGRAMS, "first_uses": 15, "backend_miss_s": 31.5,
         "launch_s": 34.9}
BOOT = {"import_s": 3.892, "backend_s": 0.025, "engine_s": 0.131,
        "http_s": 0.064}


def make(programs_block=PROGRAMS, boot=BOOT, warm=2.8077, ref=0.33,
         after=AFTER):
    gen, gen_after = {"segment_rounds": 7}, {"segment_rounds": 90}
    if programs_block is not None:
        gen["programs"], gen_after["programs"] = programs_block, after
    perf = {"loop_lag": {}}
    if boot is not None:
        perf["boot"] = boot
    return {"run": {"gen_before": gen, "gen_after": gen_after,
                    "perf_before": perf},
            "split": {"warm_up_requests_s": warm, "reference_requests_s": ref}}


def spec(name):
    return json.loads((METRICS / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(NAMES))
def test_each_metric_reads_its_source_and_is_declared(name):
    s = spec(name)
    assert s["reader"] == "programs"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    if name.startswith("first_uses_in_window"):
        # The inside twin of compiles_in_window*: the same split of cells.
        twin = next(m for m in bench["per_layer"] if m["name"]
                    == name.replace("first_uses", "compiles"))
        assert {k: v for k, v in entry.items() if k != "name"} \
            == {k: v for k, v in twin.items() if k != "name"}
    else:
        assert s["moves"] == "setup_s"
        assert "workloads" not in entry  # every cell reports setup_s
    assert {k: s[k] for k in ("unit", "better", "source", "layer", "moves")} \
        == {k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")}
    got = programs.read(make(), **s["args"])
    if NAMES[name] is None:
        assert got == pytest.approx(100 * (2.513 + 0.261853) / 3.1377)
        assert 0 < got <= 100
    else:
        assert got == pytest.approx(NAMES[name])


@pytest.mark.parametrize("name", sorted(NAMES))
def test_a_server_with_no_ledger_gives_none(name):
    """The parent's side of a traced run: no ``programs`` block, no
    ``boot``; the reader returns None and does not raise."""
    ctx = make(programs_block=None, boot=None)
    assert programs.read(ctx, **spec(name)["args"]) is None


def test_backend_sums_hits_and_misses():
    cold = {**PROGRAMS, "backend_hit_s": 0.25, "backend_miss_s": 391.5}
    assert programs.read(make(cold), kind="backend_s") == 391.75


def test_ledger_share_over_100_is_reported_as_read():
    """More first-use seconds than the stopwatch that holds them is a ledger
    that counts twice: the line shows it.  None only where there is no
    stopwatch to divide by (or no ledger: the test above)."""
    over = programs.read(make(warm=2.0, ref=0.3), kind="ledger_pct")
    assert over == pytest.approx(100.0 * (2.513 + 0.261853) / 2.3)
    assert over > 100
    assert programs.read(make(warm=0.0, ref=0.0), kind="ledger_pct") is None
    assert programs.read(make(boot={}), kind="boot_import_s") is None


def test_first_uses_in_window_is_the_ledgers_growth_over_the_window():
    assert programs.read(make(), kind="first_uses_in_window") == 2
    assert programs.read(make(after=PROGRAMS),
                         kind="first_uses_in_window") == 0
