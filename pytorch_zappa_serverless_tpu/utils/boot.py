"""Where the seconds between a process's start and its first healthy answer
go: stamps taken by the entry point (``cli.py``) and the server as boot
passes each point, read back as four intervals that tile spawn to healthy.

``import_s`` runs from the process's own start (``/proc/self/stat``: the
interpreter's start-up is inside it) to the serving package imported and
the configuration read; ``backend_s`` from there to the first device
enumeration done (the TPU client); ``engine_s`` to the engine built
(``build_engine``'s ``cold_start_seconds`` lies inside it and is not
redefined) and ``http_s`` to the listening socket bound.  All on
``time.perf_counter``, the clock of every other span.
"""

from __future__ import annotations

import os
import time

POINTS = ("import", "backend", "engine", "http")
_stamps: dict[str, float] = {}


def _process_start() -> float:
    """``perf_counter`` at which this process began, from its start time in
    ``/proc/self/stat`` (clock ticks since boot, so to 10 ms); where that
    cannot be read, the import of this module, which leaves the
    interpreter's own start out."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return now - (time.clock_gettime(time.CLOCK_BOOTTIME)
                      - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return now


_START = _process_start()  # read once: a scrape pays no file read


def stamp(point: str) -> None:
    """Boot has passed ``point``; the first stamp of a point stands."""
    _stamps.setdefault(point, time.perf_counter())


def split() -> dict[str, float]:
    """``{import_s, backend_s, engine_s, http_s}`` for the points passed so
    far, each from the point before it (an entry point that stamps none,
    such as a test's in-process server, gives ``{}``)."""
    out, last = {}, _START
    for point in POINTS:
        if point not in _stamps:
            break
        out[f"{point}_s"] = round(_stamps[point] - last, 3)
        last = _stamps[point]
    return out
