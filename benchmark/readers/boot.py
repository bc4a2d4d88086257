"""Per-layer metrics of set-up, from the run's own split of ``setup_s``."""

from __future__ import annotations


def read(ctx, part: str):
    return ctx["split"].get(part)
