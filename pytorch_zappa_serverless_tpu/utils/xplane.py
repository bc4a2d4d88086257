"""Xplane (profiler capture) op-time aggregation — THE single classifier.

``POST /admin/profile`` and ``tools/trace_ops.py`` both read device op
times from ``.xplane.pb`` captures; the classification rules (which plane,
which line, what counts as overlapped-async vs synchronous compute) are
metric-load-bearing and must not drift between the two — a divergent copy
once double-booked an SD-1.5 step at 862 ms against a 444 ms wall (async
in-flight windows overlap compute; summing them with it is wrong).

Rules:
- TPU planes: the ``XLA Ops`` line is synchronous compute; ``Async XLA
  Ops`` holds in-flight windows (DMA/prefetch) -> overlap bucket.
- Non-TPU ``/device:`` planes (GPU streams etc.): no such line naming —
  every op-shaped event on any line counts, with the name-based
  ``*-start/done`` async filter as the only overlap test.
- Module/step envelope events (``jit_*``, no `` = ``) are skipped.
- Control-flow ENVELOPES (``while``/``conditional``/``call``) span their
  body ops on the same line: they go to their own bucket, NOT compute
  (an SD-1.5 20-step denoise double-counted to 861 ms/iter against a
  430 ms wall before this).  Consequence: ``device_compute_ms`` is a
  lower bound for loop-heavy programs — the envelope-minus-body gap
  (per-iteration sequencing) is not attributed.

:func:`attribute_idle` reads the same capture for ``POST /admin/profile``
with the device rules of ``benchmark/trace_reduce.py``, so that the program's
idle share and the benchmark's agree: busy is the union of the ``XLA Ops``
intervals (envelopes included), a program run is an ``XLA Modules`` event,
the window runs from the first device event to the last.  On top of them it
reads the host plane's ``tpuserve.*`` annotations (serving/tracing.py
``RoundTimeline``): they name each run and say what the host was doing in
every gap between runs.  And it books the device time of every run to the
named parts of the model (models/decoder.py ``PARTS``): the scope an
operation was traced in is in the capture, as the ``tf_op`` stat of the
operation's event metadata, beside the ``program_id`` of the program it
belongs to.  ``jax.profiler.ProfileData`` shows an event's own stats and not
its metadata's, so :func:`_scopes` reads those two out of the file's
protobuf wire format itself, the event metadata alone (a few thousand
entries: the million events are behind one length each and are skipped).
"""

from __future__ import annotations

import bisect
import collections
import functools
import heapq
import operator
import re
from pathlib import Path

_ASYNC_NAME = re.compile(r"(copy|slice|async)[-_]?(start|done)")
_ENVELOPE = {"while", "conditional", "call"}  # see module docstring rules


def _family(op_name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``."""
    return re.sub(r"[.\d]+$", "", op_name.split(" = ")[0].lstrip("%"))


def op_time_breakdown(trace_dir, capture=None):
    """Aggregate a capture into (compute_ns, counts, overlap_ns,
    envelope_ns) Counters keyed by op family (HLO instruction name sans
    %/trailing indices).  ``capture`` is what :func:`read_capture` made of
    the same directory: a capture with TPU planes is then not read again."""
    from jax.profiler import ProfileData

    compute: collections.Counter = collections.Counter()
    counts: collections.Counter = collections.Counter()
    overlap: collections.Counter = collections.Counter()
    envelope: collections.Counter = collections.Counter()
    if capture is not None and capture[0]:
        for plane in capture[0]:
            for fam, ns in plane["async"]:
                overlap[fam] += ns
            for start, end, fam, is_op, _ in plane["ops"]:
                if not is_op:
                    continue
                if _ASYNC_NAME.search(fam):
                    overlap[fam] += end - start
                elif fam in _ENVELOPE:
                    envelope[fam] += end - start
                else:
                    compute[fam] += end - start
                    counts[fam] += 1
        return compute, counts, overlap, envelope
    for pb in sorted(Path(trace_dir).rglob("*.xplane.pb")):
        for plane in ProfileData.from_file(str(pb)).planes:
            is_tpu = "TPU" in plane.name
            if not is_tpu and "/device:" not in plane.name:
                continue
            for line in plane.lines:
                if is_tpu and line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                line_is_async = is_tpu and line.name == "Async XLA Ops"
                for ev in line.events:
                    name = ev.name
                    if name.startswith("jit_") or " = " not in name:
                        continue
                    fam = _family(name)
                    if line_is_async or _ASYNC_NAME.search(fam):
                        overlap[fam] += ev.duration_ns
                        continue
                    if fam in _ENVELOPE:
                        envelope[fam] += ev.duration_ns
                        continue
                    compute[fam] += ev.duration_ns
                    counts[fam] += 1
    return compute, counts, overlap, envelope


# -- host and device together (POST /admin/profile) ----------------------------

_ANNOTATION = "tpuserve."
# Phases that run on the dispatch thread; the ``round.*`` ones run on the
# event loop (``round.lane_wait`` ends on the dispatch thread, and no event
# loop phase of the same scheduler covers its time).
_DISPATCH = ("prefill.", "segment.")
IN_PROGRAM = "in_program"  # idle between the operations of one program run


def _varint(buf: bytes, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _fields(buf: bytes, at: int, end: int):
    """The fields of the protobuf message ``buf[at:end]``, ``(number,
    value)`` each: a varint's integer, the ``(start, end)`` of a
    length-delimited field's bytes, None for a fixed-width one."""
    while at < end:
        key, at = _varint(buf, at)
        kind = key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = (at, at + size), at + size
        elif kind in (1, 5):
            value, at = None, at + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _entry(buf: bytes, span) -> tuple:
    """The ``(start, end)`` of the message a map field's entry holds."""
    return next(v for f, v in _fields(buf, *span) if f == 2)


@functools.lru_cache(maxsize=None)  # a capture has a few hundred scopes
def _part(scope: str) -> str | None:
    """The part an operation traced under ``scope`` (its ``op_name``, a
    path) belongs to: the innermost component that models/decoder.py's
    ``part`` made.  A primitive or a function of a part's name
    (``jit(norm)``, ``norm``) carries no mark and is none."""
    from ..models.decoder import PART_MARK, PARTS

    for piece in reversed(scope.split("/")):
        if piece.startswith(PART_MARK) and piece[len(PART_MARK):] in PARTS:
            return piece[len(PART_MARK):]
    return None


_INSTRUCTION = re.compile(r"%([\w.\-]+)")  # a name in an instruction's text
_READERS_DEEP = 4  # a prefetch, its wait, a layout change, then the reader


def _by_reader(program: dict[str, str | None]) -> dict[str, str]:
    """The part of every operation of one program that has none of its own
    and whose result an operation with one reads: the compiler's copies,
    prefetches and their waits carry no scope, and their time is the time
    of what they fetch for.  ``program`` is ``{instruction text: part}``;
    a text is ``%name = shape opcode(shape %operand, ...)``.  The nearest
    reader decides, the first in the program's order among equals; an
    operation nothing named reads within ``_READERS_DEEP`` steps stays in
    no part."""
    names = [_INSTRUCTION.findall(text) for text in program]  # its own first
    part_of = {own[0]: part for own, part in zip(names, program.values())}
    bare = {own[0]: text for own, (text, part) in zip(names, program.items())
            if not part}
    readers: dict[str, list[str]] = {}  # of the operations with no part
    for reader, *read in names:
        for operand in read:
            if operand in bare:
                readers.setdefault(operand, []).append(reader)
    found = {}
    for fetched, text in bare.items():
        front = [fetched]
        for _ in range(_READERS_DEEP):
            front = [r for n in front for r in readers.get(n, ())]
            named = next((part_of[r] for r in front if part_of.get(r)), None)
            if named or not front:
                break
        if named:
            found[text] = named
    return found


def _scopes(buf: bytes) -> dict[tuple[int, str], str]:
    """``{(program id, event name): part}`` of a capture file's bytes, for
    every operation whose scope names one: the ``tf_op`` and ``program_id``
    stats of the planes' event metadata (tsl ``xplane.proto``: an XSpace's
    planes are field 1; a plane's lines 3, event metadata 4, stat metadata
    5; an event metadata's name 2, stats 5; a stat's metadata id 1, its
    value 3, 4 (integers), 5 (string) or 7 (a stat metadata's name)).  The
    key holds the program: ``%fusion.12`` is other work in a prefill than
    in a segment.  An operation traced in no part takes its reader's
    (:func:`_by_reader`)."""
    found: dict[tuple[int, str], str] = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        events, stat_names = [], {}
        for f, span in _fields(buf, *plane):
            if f == 4:
                events.append(_entry(buf, span))
            elif f == 5:
                at = dict(_fields(buf, *_entry(buf, span)))
                if 1 in at and 2 in at:
                    stat_names[at[1]] = buf[slice(*at[2])].decode()
        ids = {name: i for i, name in stat_names.items()}
        if "tf_op" not in ids or "program_id" not in ids:
            continue
        wanted = (ids["tf_op"], ids["program_id"])
        programs: dict[int, dict[str, str | None]] = {}
        for span in events:
            name = program = scope = None
            for f, v in _fields(buf, *span):
                if f == 2:
                    name = buf[slice(*v)].decode()
                elif f == 5 and buf[v[0]] == 0x08:  # a stat: its id first
                    which, at = _varint(buf, v[0] + 1)
                    if which not in wanted:  # a dozen stats an event, two read
                        continue
                    stat = dict(_fields(buf, at, v[1]))
                    if which == ids["program_id"]:
                        program = stat.get(3, stat.get(4))
                    else:
                        scope = (buf[slice(*stat[5])].decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if program is None or not name or not name.startswith("%"):
                continue
            programs.setdefault(program & 0xFFFFFFFFFFFFFFFF, {})[name] = \
                _part(scope) if scope else None
        for program, texts in programs.items():
            for text, part in {**texts, **_by_reader(texts)}.items():
                if part:
                    found[program, text] = part
    return found


def _module_run(ev) -> tuple[int, int, str, int | None]:
    """``(start_ns, end_ns, module name, program id)`` of an ``XLA Modules``
    event, whose name is ``jit_name(program id)``."""
    module, _, program = ev.name.partition("(")
    program = program.rstrip(")")
    start = int(ev.start_ns)
    return (start, start + int(ev.duration_ns), module,
            int(program) if program.isdigit() else None)


def read_capture(trace_dir):
    """One pass over a capture -> (device planes, host annotations).

    A device plane (one that has an ``XLA Ops`` line) becomes ``{"ops":
    [(start_ns, end_ns, family, is_op, part)] sorted by start, "async":
    [(family, ns)] of its ``Async XLA Ops`` line, "mods": [(start_ns,
    end_ns, module name)] sorted}``; ``is_op`` is false for the module and
    step envelopes on the line (``jit_*``, no `` = ``), which count as busy
    time and not as operations, and ``part`` is the named part of the model
    the operation was traced in (models/decoder.py ``PARTS``; None: in
    none), looked up by the program whose run holds the operation and the
    operation's name (:func:`_scopes`).  Host annotations are ``(start_ns,
    end_ns, phase, programs)`` sorted by start.  A capture holds a million
    device events with a few hundred names: each event is touched once,
    here, for both reductions of ``POST /admin/profile``."""
    from jax.profiler import ProfileData

    device, host = [], []
    for pb in sorted(Path(trace_dir).rglob("*.xplane.pb")):
        data = pb.read_bytes()
        scopes = _scopes(data)
        # {program: {event name: (family, is_op, part)}}
        known: dict[int | None, dict[str, tuple]] = {}

        def family(name: str, program=None) -> tuple[str, bool, str | None]:
            return (_family(name),
                    not name.startswith("jit_") and " = " in name,
                    scopes.get((program, name)))

        for plane in ProfileData.from_serialized_xspace(data).planes:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                runs = sorted(map(_module_run, lines["XLA Modules"].events)) \
                    if "XLA Modules" in lines else []
                begins = [r[0] for r in runs]
                ops = []
                begin = end = -1  # of the run that holds the event
                for ev in lines["XLA Ops"].events:
                    start = int(ev.start_ns)
                    if not begin <= start < end:  # another run's: its program
                        k = bisect.bisect_right(begins, start) - 1
                        begin, end, _, program = runs[k] if k >= 0 \
                            and start < runs[k][1] else (-1, -1, None, None)
                        seen = known.setdefault(program, {})
                    name = ev.name
                    got = seen.get(name)
                    if got is None:
                        got = seen[name] = family(name, program)
                    ops.append((start, start + int(ev.duration_ns), *got))
                ops.sort(key=operator.itemgetter(0, 1))
                overlapped = []
                if "Async XLA Ops" in lines:
                    for ev in lines["Async XLA Ops"].events:
                        fam, is_op, _ = family(ev.name)
                        if is_op:
                            overlapped.append((fam, int(ev.duration_ns)))
                device.append({"ops": ops, "async": overlapped,
                               "mods": [r[:3] for r in runs]})
                continue
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if not name.startswith(_ANNOTATION):
                        continue
                    programs = 1
                    if name.endswith(".launch"):
                        programs = int(dict(ev.stats).get("programs", 1))
                    start = int(ev.start_ns)
                    host.append((start, start + int(ev.duration_ns),
                                 name[len(_ANNOTATION):], programs))
    host.sort()
    return device, host


def _phase_steps(host) -> tuple[list[int], list[str | None]]:
    """The one phase that holds each instant, as a step function: where
    several annotations cover it, a dispatch-thread phase beats an event-loop
    one, and among equals the one that started last (the innermost)."""
    bounds = sorted({t for s, e, _, _ in host for t in (s, e)})
    steps: list[str | None] = []
    heap: list[tuple] = []
    i = 0
    for t in bounds:
        while i < len(host) and host[i][0] <= t:
            s, e, phase, _ = host[i]
            heapq.heappush(heap, (not phase.startswith(_DISPATCH), -s, e,
                                  phase))
            i += 1
        while heap and heap[0][2] <= t:
            heapq.heappop(heap)
        steps.append(heap[0][3] if heap else None)
    return bounds, steps


def _book(into: dict, bounds, steps, a: int, b: int) -> None:
    """Add the interval [a, b) to ``into`` by the phase that holds it."""
    i = bisect.bisect_right(bounds, a) - 1  # -1: before the first annotation
    while a < b:
        phase = steps[i] if i >= 0 else None
        end = min(bounds[i + 1], b) if i + 1 < len(bounds) else b
        into[phase] = into.get(phase, 0) + end - a
        a = end
        i += 1


# How long before its launch began a device run may seem to have started: the
# profiler sets the device plane's clock against the host's to within about a
# millisecond (on the v5e the device plane read 0.3-0.9 ms early).
_EARLY_NS = 2_000_000


def join_runs(mods: list[tuple[int, int, str]], host) -> list[dict]:
    """Name each device program run by the ``*.launch`` annotation that
    launched it; ``mods`` is ``(start, end, module name)`` sorted by start.

    One chip runs programs in the order they were dispatched, and a run
    cannot begin before its launch did: each launch, in order, takes the next
    ``programs`` runs that began after it (give or take the clocks).  A run
    that began before the next launch with nobody to claim it (launched
    before the capture began, or by code that carries no annotation) keeps
    its module's name.  A fetched launch's run also records when the
    ``<kind>.fetch`` that followed returned."""
    runs = [{"start": s, "end": e, "module": m, "kind": m, "launch_start": None,
             "fetch_end": None} for s, e, m in mods]
    fetches: dict[str, list] = {}
    for h in host:
        if h[2].endswith(".fetch"):
            fetches.setdefault(h[2].split(".")[0], []).append(h)
    j = 0
    for l_start, _, phase, programs in host:
        if not phase.endswith(".launch"):
            continue
        kind = phase.split(".")[0]
        while j < len(runs) and runs[j]["start"] < l_start - _EARLY_NS:
            j += 1
        rows = fetches.get(kind, [])
        k = bisect.bisect_left(rows, (l_start,))
        fetch_end = rows[k][1] if k < len(rows) else None
        for r in runs[j:j + programs]:
            r.update(kind=kind, launch_start=l_start, fetch_end=fetch_end)
        j = min(j + programs, len(runs))
    return runs


def _clock_check(runs: list[dict]) -> tuple[int, dict]:
    """How the two planes' clocks stand, from the join: the least the device
    plane is early by (a run cannot begin before its launch did), and, with
    that put right, how long after each segment's run its ``segment.fetch``
    returned.  That has to be after, and shortly: it is what guards the join
    and the attribution against the clocks drifting apart unnoticed."""
    early = max((r["launch_start"] - r["start"] for r in runs
                 if r["launch_start"] is not None), default=0)
    early = max(early, 0)
    lags = [(r["fetch_end"] - r["end"] - early) / 1e6 for r in runs
            if r["kind"] == "segment" and r["fetch_end"] is not None]
    return early, {
        "segments": len(lags),
        "ok": sum(1 for lag in lags if 0.0 < lag < 5.0),
        "lag_ms": {"min": round(min(lags), 3), "max": round(max(lags), 3)}
        if lags else None,
        "device_early_ms": round(early / 1e6, 3)}


def attribute_idle(trace_dir, capture=None) -> dict:
    """``{"idle": ..., "programs": ...}`` of a capture: every idle gap between
    device runs booked to the host phase that covers it, and every device run
    named from inside the program.  Times are means over the device planes.
    A capture without a device plane gives both empty.  ``capture`` is what
    :func:`read_capture` made of ``trace_dir``, where the caller has it."""
    device, host = capture if capture is not None else read_capture(trace_dir)
    if not device:
        return {"idle": {}, "programs": {}}
    bounds, steps = _phase_steps(host)
    n = len(device)
    window = busy = between = 0
    by_phase: dict = {}
    gaps: dict[tuple[str, str], dict] = {}
    programs: dict[str, dict] = {}
    clock = None
    for plane in device:
        ops, mods = plane["ops"], plane["mods"]
        if not ops:
            continue
        first = min(ops[0][0], mods[0][0] if mods else ops[0][0])
        last = max(max(op[1] for op in ops),
                   max((e for _, e, _ in mods), default=0))
        window += last - first
        end = -1
        for s, e, *_ in ops:  # the union of the operation intervals
            if s > end:
                busy += e - s
                end = e
            elif e > end:
                busy += e - end
                end = e
        runs = join_runs(mods, host)
        early, checked = _clock_check(runs)
        clock = clock or checked
        k = 0
        prev = None
        for r in runs:
            p = programs.setdefault(r["kind"], {"runs": 0, "device_ns": 0,
                                                "ops": {}})
            p["runs"] += 1
            p["device_ns"] += r["end"] - r["start"]
            booked = p["ops"]  # {(part, family): ns}
            while k < len(ops) and ops[k][0] < r["end"]:
                s, e, fam, _, part = ops[k]
                if s >= r["start"] and fam not in _ENVELOPE:
                    booked[part, fam] = booked.get((part, fam), 0) + e - s
                k += 1
            if prev is not None and r["start"] > prev[0]:
                gap = gaps.setdefault((prev[1], r["kind"]),
                                      {"count": 0, "ns": 0, "phases": {}})
                gap["count"] += 1
                gap["ns"] += r["start"] - prev[0]
                between += r["start"] - prev[0]
                _book(gap["phases"], bounds, steps, prev[0] + early,
                      r["start"] + early)
            if prev is None or r["end"] > prev[0]:
                prev = (r["end"], r["kind"])
    for gap in gaps.values():
        for phase, ns in gap["phases"].items():
            by_phase[phase] = by_phase.get(phase, 0) + ns
    idle = window - busy
    by_phase[IN_PROGRAM] = max(idle - between, 0)
    unattributed = by_phase.pop(None, 0)

    def ms(ns) -> float:
        return round(ns / n / 1e6, 3)

    def phases_ms(d: dict) -> dict:
        return {(k or "unattributed"): ms(v)
                for k, v in sorted(d.items(), key=lambda kv: -kv[1])}

    def largest(fams: dict) -> dict:
        return dict(list(phases_ms(fams).items())[:3])

    def ops_ms(booked: dict) -> dict:
        """A kind's operations by family, and the same operations by the
        part of the model each was traced in, each part with its three
        largest families; with no part, ``unnamed_ms`` and ``unnamed_ops``."""
        by_fam: dict = {}
        by_part: dict = {}
        for (part, fam), ns in booked.items():
            by_fam[fam] = by_fam.get(fam, 0) + ns
            by_part.setdefault(part, {})[fam] = ns
        named = {part: fams for part, fams in by_part.items() if part}
        return {
            "ops": phases_ms(by_fam),
            "parts": phases_ms({part: sum(fams.values())
                                for part, fams in named.items()}),
            "unnamed_ms": ms(sum(by_part.get(None, {}).values())),
            "unnamed_ops": largest(by_part.get(None, {})),
            "part_ops": {part: largest(fams) for part, fams in sorted(
                named.items(), key=lambda kv: -sum(kv[1].values()))}}

    longest = sorted(gaps.items(), key=lambda kv: -kv[1]["ns"])[:10]
    return {
        "idle": {
            "window_ms": ms(window), "busy_ms": ms(busy), "idle_ms": ms(idle),
            "by_phase": phases_ms(by_phase),
            "unattributed_ms": ms(unattributed),
            "gaps": [{"before": before, "after": after, "ms": ms(g["ns"]),
                      "count": g["count"] // n,
                      "phases": phases_ms(g["phases"])}
                     for (before, after), g in longest],
            "clock": clock,
        },
        "programs": {
            kind: {"runs": p["runs"] // n, "device_ms": ms(p["device_ns"]),
                   **ops_ms(p["ops"])}
            for kind, p in programs.items()},
    }
