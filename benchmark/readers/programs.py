"""Per-layer metrics from the server's ledger of program first uses
(``/metrics`` ``generation[model]`` ``programs``, cumulative since boot).
Scraped as the window opens it is set-up from inside (everything before the
window is set-up), beside the boot's own stamps (``/admin/perf`` ``boot``);
its growth over the window is the programs first used on the serving path.
A server that keeps neither gives None, and the line leaves the metric out."""

from __future__ import annotations


def read(ctx, kind: str):
    run = ctx["run"]
    if kind.startswith("boot_"):
        return (run["perf_before"].get("boot") or {}).get(kind[5:])
    programs = run["gen_before"].get("programs")
    if not programs:
        return None
    if kind == "first_uses_in_window":
        return run["gen_after"]["programs"]["first_uses"] \
            - programs["first_uses"]
    if kind == "backend_s":  # key hashing on a warm run, XLA's on a cold one
        return programs["backend_hit_s"] + programs["backend_miss_s"]
    if kind == "ledger_pct":
        # The first uses' launches and first runs are intervals of the one
        # dispatch thread inside the harness's stopwatch round its warm-up
        # and reference requests.  A share past 100 is a ledger that counts
        # twice (a nested scope, two threads, a first use still open at the
        # scrape): reported as read, so that the fault shows on the line.
        split = ctx["split"]
        stopwatch = (split.get("warm_up_requests_s", 0.0)
                     + split.get("reference_requests_s", 0.0))
        inside = programs["launch_s"] + programs["first_run_s"]
        if stopwatch <= 0:
            return None
        return 100.0 * inside / stopwatch
    return programs.get(kind)
