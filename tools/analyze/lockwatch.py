"""Runtime lock-order sanitizer — the dynamic half of ``lockorder``.

The static analyzer proves the *declared* nesting graph is acyclic; this
module records what actually happens.  Under ``TPUSERVE_LOCKWATCH=1`` the
``threading.Lock``/``RLock``/``Condition`` constructors are wrapped with a
site-filtered factory: a lock created at a source line the static analyzer
knows about (``lockorder.lock_table()`` — the repo's own serving/engine
locks) comes back instrumented; every other creation (stdlib, jax, aiohttp)
gets the real primitive with zero overhead.  Instrumented locks maintain a
per-thread held stack and record every (held -> acquired) pair:

- an **inversion** (B acquired under A after A was acquired under B) is
  recorded as a violation the moment it happens;
- ``violations_against(static_edges)`` additionally cross-checks the
  observed pairs against the static graph — an observed order the static
  graph forbids (a path exists the other way) means the analyzer's model
  and reality disagree, which is itself a finding.

Wiring: the package honors the env knob at import (see
``pytorch_zappa_serverless_tpu/__init__``), the test conftest turns it on
for the tier-1 suite, and ``tools/crashtest.py`` sets it for its
subprocesses so chaos runs double as sanitizer runs.  With
``TPUSERVE_LOCKWATCH_OUT=<path>`` the process dumps a JSON report at exit
(the crashtest reads it back and fails on violations).

asyncio locks are NOT instrumented: they are held across awaits, so a
per-thread stack would lie about them — they belong to the static half
only (docs/ANALYSIS.md).
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import sys
import threading
import time as time_mod
from pathlib import Path

log = logging.getLogger("tools.analyze.lockwatch")

ENV_KNOB = "TPUSERVE_LOCKWATCH"
ENV_OUT = "TPUSERVE_LOCKWATCH_OUT"

_state_lock = threading.Lock()   # guards the observed/violation tables
_held = threading.local()        # per-thread stack of watched-lock names
_observed: dict[tuple[str, str], int] = {}
_violations: list[dict] = []
_enabled = False
_real: dict[str, object] = {}
_sites: dict[tuple[str, int], str] = {}
_root: Path | None = None


def _stack() -> list[str]:
    st = getattr(_held, "stack", None)
    if st is None:
        st = _held.stack = []
    return st


class _WatchedLock:
    """Duck-typed lock wrapper: context manager + acquire/release/locked.

    Works as ``threading.Condition``'s underlying lock too (Condition falls
    back to plain acquire/release when ``_release_save`` & co. are absent),
    so ``wait()``'s release/re-acquire keeps the held stack truthful.
    """

    __slots__ = ("_lock", "name")

    def __init__(self, real, name: str):
        self._lock = real
        self.name = name

    def _note_acquired(self):
        st = _stack()
        if st:
            holder = st[-1]
            if holder != self.name:
                edge = (holder, self.name)
                with _state_lock:
                    first = edge not in _observed
                    _observed[edge] = _observed.get(edge, 0) + 1
                    if first and (self.name, holder) in _observed:
                        _violations.append({
                            "kind": "inversion",
                            "edge": list(edge),
                            "reverse": [self.name, holder],
                        })
                        log.error(
                            "lockwatch: order inversion — %s acquired under "
                            "%s, but the reverse order was also observed",
                            self.name, holder)
        st.append(self.name)

    def acquire(self, blocking: bool = True, timeout: float = -1):
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            self._note_acquired()
        return ok

    def release(self):
        st = _stack()
        # Out-of-order releases are legal (rare, but threading allows
        # them): drop the newest matching entry.
        for i in range(len(st) - 1, -1, -1):
            if st[i] == self.name:
                del st[i]
                break
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self):
        return self._lock.locked()

    # -- Condition protocol --------------------------------------------------
    # threading.Condition picks these up when present; delegating to the
    # real RLock keeps ownership semantics exact (the acquire(False) probe
    # fallback mis-answers for reentrant locks).  wait()'s release window
    # leaves our stack entry in place — the waiting thread is blocked the
    # whole time, so it cannot acquire anything else meanwhile.
    def _release_save(self):
        inner = getattr(self._lock, "_release_save", None)
        return inner() if inner is not None else self._lock.release()

    def _acquire_restore(self, state):
        inner = getattr(self._lock, "_acquire_restore", None)
        if inner is not None:
            inner(state)
        else:
            self._lock.acquire()

    def _is_owned(self):
        inner = getattr(self._lock, "_is_owned", None)
        if inner is not None:
            return inner()
        if self._lock.acquire(False):
            self._lock.release()
            return False
        return True


def _creation_site(depth: int = 2) -> tuple[str, int] | None:
    try:
        frame = sys._getframe(depth)
    except ValueError:
        return None
    fname, line = frame.f_code.co_filename, frame.f_lineno
    if _root is None:
        return None
    try:
        rel = Path(fname).resolve().relative_to(_root).as_posix()
    except ValueError:
        return None
    return (rel, line)


def _make_factory(kind: str):
    real_ctor = _real[kind]

    def factory(*args, **kwargs):
        site = _creation_site()
        name = _sites.get(site) if site is not None else None
        if name is None:
            return real_ctor(*args, **kwargs)
        if kind == "Condition" and not args and "lock" not in kwargs:
            # A Condition IS a lock + waiters: watch its underlying RLock
            # so entering the cv and cv.wait()'s release/re-acquire both
            # maintain the held stack.
            return _real["Condition"](_WatchedLock(_real["RLock"](), name))
        if kind == "Condition":
            return real_ctor(*args, **kwargs)
        return _WatchedLock(real_ctor(*args, **kwargs), name)

    return factory


def enable(root: Path | None = None) -> bool:
    """Install the site-filtered lock factories (idempotent).

    Returns True when enabled.  Scans the repo's static lock table first;
    in an installed deployment without the tools tree this raises ImportError
    upstream and the caller leaves the sanitizer off.
    """
    global _enabled, _root
    if _enabled:
        return True
    from . import REPO_ROOT
    from . import lockorder

    _root = (root or REPO_ROOT).resolve()
    _sites.update(lockorder.lock_table(_root))
    for kind in ("Lock", "RLock", "Condition"):
        _real[kind] = getattr(threading, kind)
    for kind in ("Lock", "RLock", "Condition"):
        setattr(threading, kind, _make_factory(kind))
    _enabled = True
    return True


def disable():
    """Restore the real constructors (already-created watched locks keep
    recording — that is harmless and keeps their semantics stable)."""
    global _enabled
    if not _enabled:
        return
    for kind, ctor in _real.items():
        setattr(threading, kind, ctor)
    _enabled = False


def enabled() -> bool:
    return _enabled


def reset():
    """Clear observed edges + violations (test isolation)."""
    with _state_lock:
        _observed.clear()
        _violations.clear()


def report() -> dict:
    with _state_lock:
        return {
            "enabled": _enabled,
            "edges": [{"from": a, "to": b, "count": n}
                      for (a, b), n in sorted(_observed.items())],
            "violations": [dict(v) for v in _violations],
        }


def violations_against(static_edges: set[tuple[str, str]]) -> list[str]:
    """Observed orders the static graph forbids, plus runtime inversions.

    An observed edge (A, B) is a violation when the static graph contains a
    path B ->* A — the code exercised an order whose reverse the analyzer
    proved to be the declared discipline.  Observed edges the static graph
    simply doesn't know are NOT violations (the static model is one call
    level deep; the runtime sees through every indirection) — they are the
    cross-check's discovery channel, surfaced by the tier-1 test via
    ``report()`` when they invert.
    """
    adj: dict[str, set[str]] = {}
    for a, b in static_edges:
        adj.setdefault(a, set()).add(b)

    def reaches(start: str, goal: str) -> bool:
        seen, frontier = {start}, [start]
        while frontier:
            node = frontier.pop()
            for nxt in adj.get(node, ()):
                if nxt == goal:
                    return True
                if nxt not in seen:
                    seen.add(nxt)
                    frontier.append(nxt)
        return False

    with _state_lock:
        observed = list(_observed)
        out = [f"runtime inversion: {v['edge'][0]} -> {v['edge'][1]} and "
               f"{v['reverse'][0]} -> {v['reverse'][1]} both observed"
               for v in _violations]
    for a, b in observed:
        if reaches(b, a):
            out.append(f"observed {a} -> {b} but the static graph orders "
                       f"{b} ->* {a}")
    return out


_static_cache: set[tuple[str, str]] | None = None


def _static() -> set[tuple[str, str]]:
    global _static_cache
    if _static_cache is None:
        from . import lockorder

        _static_cache = (set(lockorder.static_edges(_root))
                         if _root is not None else set())
    return _static_cache


def _dump(path: str):
    try:
        data = report()
        data["static_violations"] = violations_against(_static())
        tmp = Path(path).with_suffix(".tmp")
        tmp.write_text(json.dumps(data, indent=1) + "\n")
        os.replace(tmp, path)
    except Exception:  # the dump must never break the watched process
        log.exception("lockwatch: report dump failed")


def _dump_loop(path: str, interval_s: float):
    while True:
        time_mod.sleep(interval_s)
        _dump(path)


def enable_from_env() -> bool:
    """The single wiring point: honor TPUSERVE_LOCKWATCH / _OUT.

    With an OUT path the report is rewritten every second from a daemon
    thread (atomic replace) in addition to the atexit dump — chaos
    harnesses SIGKILL their subjects, and a kill must not erase the
    evidence the run existed to collect.
    """
    if os.environ.get(ENV_KNOB, "") in ("", "0"):
        return False
    enable()
    out = os.environ.get(ENV_OUT)
    if out:
        atexit.register(_dump, out)
        threading.Thread(target=_dump_loop, args=(out, 1.0),
                         name="lockwatch-dump", daemon=True).start()
    return True
