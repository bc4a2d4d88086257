"""From a profiler capture (``*.xplane.pb``) to device times.

Read with ``jax.profiler.ProfileData`` and nothing else.  A device plane is
one that has an ``XLA Ops`` line (the operations that ran, one after the
other) beside an ``XLA Modules`` line (one event per program run).  Busy time
is the union of the operation intervals; the window is from the first device
event to the last; both are averaged over the device planes.

Programs are told apart as ``POST /admin/profile`` tells them: the program
annotates each launch (``tpuserve.<kind>.launch``, stat ``programs``), one
chip runs programs in the order they were launched, and each launch names
the next runs that began after it.  A run that no launch in the capture
claims (it was launched before the capture began) falls to the rules of the
configuration's file (``programs``): the module's name and, where two
programs share a name (both are lambdas to ``jax.jit``), whether an
operation of a given family runs inside the module's interval.  Those rules
alone took the run that the capture's end cuts for a prefill, because its
``while`` never closes; its launch says what it is.  It counts with the part
of it the capture holds.

    python3 benchmark/trace_reduce.py <capture dir>     # what a capture holds
"""

from __future__ import annotations

import bisect
import collections
import re
import sys
from pathlib import Path

ENVELOPES = {"while", "conditional", "call"}  # they span their body's ops
LAUNCH = re.compile(r"tpuserve\.(\w+)\.launch")
# A run may seem to begin this long before its launch did: the profiler sets
# the device plane's clock against the host's to within about a millisecond.
EARLY_NS = 2_000_000


def family(op_name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``."""
    return re.sub(r"[.\d]+$", "", op_name.split(" = ")[0].lstrip("%"))


def module_name(event_name: str) -> str:
    """``jit__lambda_(1234)`` -> ``jit__lambda_``."""
    return event_name.split("(")[0]


def union_ns(intervals: list[tuple[int, int]]) -> int:
    total, end = 0, -1
    for s, e in sorted(intervals):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def classify(name: str, start: int, stop: int, rules: dict,
             op_starts: dict[str, list[int]]) -> str:
    for kind, rule in rules.items():
        if not name.startswith(rule["module"]):
            continue
        probe = rule.get("with_op") or rule.get("without_op")
        if probe is None:
            return kind
        starts = op_starts.get(probe, [])
        i = bisect.bisect_left(starts, start)
        inside = i < len(starts) and starts[i] < stop
        if inside == ("with_op" in rule):
            return kind
    return name


def launched(mods: list[tuple[int, int, str]],
             launches: list[tuple[int, str, int]]) -> list[str | None]:
    """The kind each run of ``mods`` (sorted by start) was launched as: every
    launch ``(start, kind, programs)``, in order, takes the next ``programs``
    runs that began after it; ``None`` where no launch claims the run."""
    kinds: list[str | None] = [None] * len(mods)
    j = 0
    for l_start, kind, programs in sorted(launches):
        while j < len(mods) and mods[j][0] < l_start - EARLY_NS:
            j += 1
        kinds[j:j + programs] = [kind] * len(kinds[j:j + programs])
        j = min(j + programs, len(mods))
    return kinds


def read_planes(capture_dir):
    """``([(plane name, lines by name)] of the device planes, the launches
    the host planes hold as (start_ns, kind, programs))``."""
    from jax.profiler import ProfileData

    device, launches = [], []
    for pb in sorted(Path(capture_dir).rglob("*.xplane.pb")):
        for plane in ProfileData.from_file(str(pb)).planes:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                device.append((plane.name, lines))
                continue
            for line in plane.lines:
                for ev in line.events:
                    named = LAUNCH.fullmatch(ev.name)
                    if named:
                        launches.append(
                            (int(ev.start_ns), named.group(1),
                             int(dict(ev.stats).get("programs", 1))))
    return device, launches


def reduce_trace(capture_dir, rules: dict) -> dict:
    busy, window = [], []
    ops: collections.Counter = collections.Counter()
    gaps: collections.Counter = collections.Counter()
    programs: dict[str, dict] = {}
    device, launches = read_planes(capture_dir)
    for _, lines in device:
        intervals, op_starts = [], collections.defaultdict(list)
        timed = []  # (start, duration, family) of what is no envelope
        for ev in lines["XLA Ops"].events:
            start, dur = int(ev.start_ns), int(ev.duration_ns)
            intervals.append((start, start + dur))
            fam = family(ev.name)
            op_starts[fam].append(start)
            if fam not in ENVELOPES:
                ops[fam] += dur
                timed.append((start, dur, fam))
        timed.sort()
        for starts in op_starts.values():
            starts.sort()
        if not intervals:
            continue
        busy.append(union_ns(intervals))
        mods = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                       module_name(ev.name))
                      for ev in lines["XLA Modules"].events) \
            if "XLA Modules" in lines else []
        first = min(s for s, _ in intervals)
        last = max(e for _, e in intervals)
        if mods:
            first, last = min(first, mods[0][0]), max(last, mods[-1][1])
        window.append(last - first)
        prev = None
        k = 0
        for (start, stop, name), kind in zip(mods, launched(mods, launches)):
            kind = kind or classify(name, start, stop, rules, op_starts)
            p = programs.setdefault(kind, {"runs": 0, "seconds": 0.0,
                                           "ops": collections.Counter()})
            p["runs"] += 1
            p["seconds"] += (stop - start) / 1e9
            while k < len(timed) and timed[k][0] < stop:
                if timed[k][0] >= start:  # an operation of this run
                    p["ops"][timed[k][2]] += timed[k][1] / 1e9
                k += 1
            if prev is not None and start > prev[0]:
                gaps[f"{prev[1]}-{kind}"] += start - prev[0]
            prev = (stop, kind) if prev is None or stop > prev[0] else prev
    n = max(len(busy), 1)
    for p in programs.values():
        p["ops"] = dict(p["ops"].most_common())
    return {
        "chips": len(busy),
        "busy_s": sum(busy) / n / 1e9,
        "window_s": sum(window) / n / 1e9,
        "programs": programs,
        "device_ops": [[k, v / n / 1e9] for k, v in ops.most_common()],
        "idle_gaps": [[k, v / n / 1e9] for k, v in gaps.most_common()],
    }


def main() -> int:
    device, launches = read_planes(sys.argv[1])
    print(f"{len(launches)} launches: "
          f"{collections.Counter(kind for _, kind, _ in launches)}")
    for name, lines in device:
        print(f"plane {name}")
        for lname, line in lines.items():
            events = list(line.events)
            names = collections.Counter(
                module_name(e.name) if lname == "XLA Modules"
                else family(e.name) for e in events)
            print(f"  line {lname!r}: {len(events)} events; "
                  f"{names.most_common(12)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
