"""The scope open on a thread, for the compile listeners.

A scheduler's launch phase (``serving/tracing._Phase``) stores itself here
as it begins and None as it ends; ``engine/cache.py``'s listeners book a
compile to the scope open on the compiling thread.  Stdlib only: the
acceptor workers and the fleet router import ``serving/tracing.py`` and must
stay clear of jax and the engine.
"""

from __future__ import annotations

import threading


class _Thread(threading.local):
    """What one thread's listeners share: the scope open on it, how deep
    inside timed stages the thread is, and the compile request in flight
    (``[the cache's answer, seconds of its read]``, open only while an
    outermost backend stage is)."""
    scope = None
    depth = 0
    request = None


on_thread = _Thread()
