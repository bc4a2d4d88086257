"""Block-paged KV primitives: gather a virtual cache, scatter token writes.

The paged generation lane (serving/generation.PagedGenerationScheduler;
docs/GENERATION.md) stores KV as ``[num_blocks, block_size, D]`` pages plus a
per-sequence block table ``[S, max_blocks]`` — vLLM's layout, matching the
jax Pallas paged-attention reference shapes (``k_pages [heads, pages,
page_size, head_dim]`` with a ``page_indices`` lookup).  These two
primitives are the whole device-side contract:

- :func:`gather_kv` materializes the **virtual cache** — the contiguous
  ``[S, max_blocks * block_size, D]`` view a sequence's table describes.
  Virtual position ``j`` holds exactly what absolute position ``j``'s write
  stored, so attention over the gathered view is value-identical to
  attention over the slot pool's contiguous rows (the bit-parity property
  tests/test_generation_v2.py pins).  Positions beyond a sequence's writes
  read whatever is in its trailing (or trash) blocks; the caller's
  ``kpos <= wpos`` mask turns those scores into exact softmax zeros (the
  repo's finite ``-1e9`` mask convention: ``exp(-1e9 - max)`` underflows to
  0.0 in fp32).
- :func:`scatter_kv` routes per-token writes through the table:
  position ``p`` lands in page ``table[p // block_size]`` at offset
  ``p % block_size``.  Rows whose table is all ``TRASH_BLOCK`` (retired pool
  rows, padding rows of a batched prefill chunk) write harmlessly into the
  shared trash page.

XLA lowers both to dynamic-gather/scatter HLOs; the gather reads the same
bytes per step a contiguous cache read would, so the paged lane's step cost
should match the slot pool's (no cell runs it: unmeasured).  On TPU the Pallas
upgrade path is the official ``pltpu`` paged-attention kernel (one async DMA
per page, double-buffered — accelerator guide §9-11): these functions are
the semantics it would replace, kept jnp-level so the CPU backend runs the
identical program tier-1.
"""

from __future__ import annotations

import jax.numpy as jnp


def gather_kv(pages: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """pages [NB, BS, D], tables [S, MB] i32 → virtual cache [S, MB*BS, D]."""
    v = pages[tables]  # [S, MB, BS, D]
    S, MB, BS, D = v.shape
    return v.reshape(S, MB * BS, D)


def paged_index(tables: jnp.ndarray, positions: jnp.ndarray,
                block_size: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(page index, within-page offset) for absolute ``positions`` [S, T]
    under ``tables`` [S, MB] — the block math every paged write shares:
    position ``p`` lives in page ``table[p // block_size]`` at offset
    ``p % block_size``."""
    return (jnp.take_along_axis(tables, positions // block_size, axis=1),
            positions % block_size)


def scatter_kv(pages: jnp.ndarray, tables: jnp.ndarray,
               positions: jnp.ndarray, values: jnp.ndarray,
               block_size: int) -> jnp.ndarray:
    """Write ``values`` [S, T, D] at absolute ``positions`` [S, T] through
    ``tables`` [S, MB]; returns the updated pages [NB, BS, D].

    Callers clip positions into ``[0, MB*BS)`` first (the schedulers'
    ``min(pos, VT-1)``).  Distinct sequences own distinct pages so write
    targets never collide; only trash-routed rows can land on the same slot,
    and nothing reads those.
    """
    bidx, off = paged_index(tables, positions, block_size)
    return pages.at[bidx, off].set(values)
