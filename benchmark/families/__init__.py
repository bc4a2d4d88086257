"""One module a model family: everything the harness has to know of a model.

A configuration's file names its family (``"family": "gpt2"``).  The harness
imports ``benchmark.families.<family>`` (a name with a dot in it is imported
as it stands, so a family may live beside its tests) and calls nothing but
the four functions below.  ``run.py``, ``server.py``, ``stage_weights.py``,
``refcheck.py`` and the readers name no model; a later PR brings a family as
new files (this module, its plain reference, its configuration) and edits
none that is there.

``config`` is the configuration's file as loaded; ``serve`` is its ``serve``
fragment as this run boots it (at ``--rehearse`` with the tiny widths laid
over it).  ``serve["extra"]["arch"]`` belongs to the family: it may hold
lists, floats and strings.

``init_tree(seed, config, serve) -> dict``
    The seeded weights, as a nested dict of numpy arrays, from the program's
    own initialiser and in the layout the server boots (``checkpoint:``).
    Called in a CPU child (``stage_weights.py``), once a checkout.

``check(config, serve, checkpoint, runs) -> {"ok", "worst", "note"}``
    The family's plain reference judges what was served.  ``runs`` is one
    record a reference prompt: ``ids``, ``tokens``, ``again`` (the same
    prompt's second answer), and ``done``/``done_again``, the stream's last
    event whole, so that a check may read whatever the program put there.
    ``refcheck.py`` has already seen that no request failed and that
    ``tokens == again``; it offers ``walk`` (position by position against a
    function that gives logits).  ``worst`` is the number compared and
    ``note`` says it beside its limit.

``decode_step_bytes(config, serve, streams, window_s) -> float``
    The bytes an average decode step of the window has to move.  ``streams``
    has one ``(seconds spent decoding, prompt length, tokens made)`` a
    finished request and ``window_s`` is the time they are averaged over, so
    a family whose cache is not linear in positions (a window layer holds
    ``min(position, window)``) can charge each stream what it held.
    Bound: bandwidth.

``prefill_flops(config, serve, prompt_len) -> float``
    The operations to prefill one prompt.  Bound: compute.

Not a family's: ``gen_slots``, ``segment_tokens``, ``max_new_tokens`` and
``arch.vocab_size`` of ``serve.extra`` are the slot scheduler's contract
(``serving/generation.py``), which ``run.py`` reads for every family.
"""

from __future__ import annotations

import importlib


def load(config: dict):
    """The family module of a configuration."""
    name = config["family"]
    return importlib.import_module(
        name if "." in name else f"benchmark.families.{name}")
