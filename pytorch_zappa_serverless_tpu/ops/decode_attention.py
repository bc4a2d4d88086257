"""Pallas decode attention over the slot pool — one query a slot, live blocks only.

The slot pool is ``[L, S, T, D]`` with the heads side by side in ``D``.  The
decode step's attention for layer ``l`` needs ``pool[l]`` and nothing else,
and of it only the positions each generating slot has written (``wpos[s]``,
a fifth of ``T`` at the benchmark's chat traffic, where most slots generate
nothing).  The kernel's iteration space is therefore the step's list of live
``(slot, block)`` pairs (:func:`work_list`), and nothing else is visited: a
slot with ``wpos < 0`` is *dead* — it is read nowhere and its output row is
zeros — and a block past a slot's last written position costs no grid step,
no DMA and no arithmetic.  The list is built once a step from ``wpos`` and
shared by every layer's call through scalar prefetch.

What a slot reads is a *span* of its row, ``[first, wpos]``: a cache whose
rows are the positions reads from row 0 (``first`` left out), one that keeps
its oldest positions elsewhere (models/evabyte.py: summaries below a ring of
exact rows) says where its span starts, and the blocks before it are no more
visited than those after it.

The blocks come straight out of the 4-D pool through the BlockSpec index
maps (the layer a prefetched scalar, slot and block read from the list), so
nothing ``[S, T, D]``-sized is sliced, copied or transposed, and one traced
call serves every layer: the layer's index is data (models/decoder.py's
trunk traces its layer once a program).  The grid is
one-dimensional and its bound is the list's count, a value of the step: a
call costs a constant plus a term in live blocks.  (A single grid step that
walks the list with its own double-buffered copies measured the same on the
v5e at ``D`` 1280, and Mosaic refuses its slice of the pool at ``D`` 1600,
which is not a multiple of the 128 lanes: PERF.md section 6, PR 30.)

Math, as the ``jax.numpy`` form's (:func:`attend`, whose docstring has the
``[H, D]`` block that keeps the heads in place), with the softmax online
over blocks: the sum over positions is reordered, nothing is left out.
Running max, denominator and the ``[H, D]`` accumulator live in VMEM scratch
across a slot's blocks (flash_attention.py's pattern): reset at its block 0,
written out at its last.

Grouped queries (``heads`` queries over a pool of fewer K/V heads, so the
pool is narrower than the queries) take the same kernel, the same list and
the same blocks of K and V, each read once for all the queries: only the
query block differs.  It is built outside the kernel as ``[heads, D]`` with
head ``h``'s query in the columns of its K/V head ``h // group`` and zeros
elsewhere, and row ``h`` of the ``[heads, D]`` result carries head ``h``'s
output in those same columns, picked out afterwards.  ``D`` is the pool's
width wherever a block is sized.

A pool whose rows are one leaf (latent attention: a position's row is
scored whole and its first ``values`` columns are what is summed) has a
kernel of its own over **one pool operand** (:func:`latent_attention`,
under its own name in a capture): the same work list, the same blocks, the
same online softmax, but the values are sliced out of the block of rows the
kernel already holds, so a block is fetched once, and the kernel copies its
blocks itself, ``_LATENT_BUFFERS - 1`` grid steps ahead of the step that
reads them (:func:`_latent_kernel` says why).  Its ``jax.numpy`` form and
its entry are :func:`attend_latent`.

A model calls :func:`attend` (with :func:`step_work` once a step, and
:func:`read_block` for what it reports), which takes the kernel or the
``jax.numpy`` form by what it can observe (:func:`_kernel_block`).
``interpret=True`` runs the kernel itself on the CPU for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -1e9  # as the jnp form's: exp(_MASKED - max) is exactly 0

# Bytes a block of K (or of V) aims at.  From the kernel alone on the v5e
# (chip_smoke.py; PERF.md section 6, PR 30): a copy of half a MiB hides the
# grid step that fetches the next one, so a full pool costs what blocks
# twice as long cost, and a slot's last block wastes half as much.
_SLAB_BYTES = 512 << 10
# What K's and V's blocks, two buffers each, may take of the 16 MiB of VMEM
# a kernel is given by default.
_VMEM_BYTES = 8 << 20
# Blocks of rows the latent kernel keeps in VMEM: the one a grid step reads
# and the copies in flight behind it.  From the kernel alone on the v5e
# (chip_smoke.py *joyai*; PERF.md section 6, PR 56): a copy of one block is
# done 0.45 us + its bytes after it is started, longer than the grid step it
# is started in, so with two buffers (the pipeline's own) a step waits for
# the mean of that and its body; started two steps ahead it is there.
_LATENT_BUFFERS = 3


def _slab(d: int, dtype) -> tuple[int, int]:
    """The dtype's sublane tile (8 rows of 32 bits: 16 for bfloat16) and the
    positions in whole tiles that ``_SLAB_BYTES`` hold at width ``d``."""
    itemsize = jnp.dtype(dtype).itemsize
    tile = 8 * 4 // itemsize
    return tile, _SLAB_BYTES // (d * itemsize) // tile * tile


def block_rows(d: int, dtype) -> int:
    """Positions a block of K aims at, at width ``d`` (a tile at least): a
    pool whose length is a multiple of it is read in blocks that long."""
    return max(_slab(d, dtype))


def pick_block_t(total: int, d: int, dtype) -> int:
    """Positions a block holds, from what the pool shows: the largest
    multiple of the dtype's sublane tile that divides ``total`` and keeps
    ``[block, d]`` within ``_SLAB_BYTES``; ``total`` itself when no such
    multiple divides it (one block a slot, which the caller weighs with
    :func:`fits_vmem`)."""
    tile, want = _slab(d, dtype)
    for cand in range(min(want, total) // tile * tile, 0, -tile):
        if total % cand == 0:
            return cand
    return total


def fits_vmem(block_t: int, d: int, dtype) -> bool:
    """Whether the pipeline's four blocks of ``block_t`` positions fit."""
    return 4 * block_t * d * jnp.dtype(dtype).itemsize <= _VMEM_BYTES


def _first_row(first, wpos):
    """``first`` [S] as int32 rows inside ``[0, wpos]`` (None: row 0)."""
    if first is None:
        return jnp.zeros_like(wpos)
    return jnp.clip(first.astype(jnp.int32), 0, jnp.maximum(wpos, 0))


def work_list(wpos, total: int, block_t: int, first=None):
    """The live ``(slot, block)`` pairs of a step, compacted in slot order.

    wpos [S] int32, the last position each slot may read, negative for a
    dead slot, and ``first`` [S] the first (None: 0) → ``(slot [W], block
    [W], count)`` int32 with ``W = S * total / block_t``; entries from
    ``count`` on are padding the kernel never visits.  Built once a step,
    outside the layer loop."""
    S = wpos.shape[0]
    per_slot = total // block_t
    wpos = jnp.minimum(wpos.astype(jnp.int32), total - 1)
    lead = _first_row(first, wpos) // block_t                       # [S]
    blocks = jnp.where(wpos >= 0, wpos // block_t + 1 - lead, 0)
    ends = jnp.cumsum(blocks)
    i = jnp.arange(S * per_slot, dtype=jnp.int32)
    slot = jnp.minimum((ends[None, :] <= i[:, None]).sum(1), S - 1)
    block = jnp.clip(i - (ends - blocks)[slot] + lead[slot], 0,
                     per_slot - 1)
    return (slot.astype(jnp.int32), block.astype(jnp.int32),
            ends[-1].astype(jnp.int32))


def _kernel(layer_ref, slot_ref, block_ref, wpos_ref, first_ref, q_ref, k_ref,
            v_ref, o_ref, m_ref, l_ref, acc_ref, *, block_t: int,
            head_dim: int, grouped: bool = False):
    i = pl.program_id(0)
    b = block_ref[i]
    last = wpos_ref[slot_ref[i]]
    first = first_ref[slot_ref[i]]
    rows, D = acc_ref.shape

    def own():
        """Row h owns head h's columns; rows past the last head own none."""
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, D), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, D), 1)
        return (col >= row * head_dim) & (col < (row + 1) * head_dim)

    @pl.when(b == first // block_t)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Every block visited is live.  The select runs in float32: the mask
    # comes from int32 iotas, whose (8, 128) tiling Mosaic will not relayout
    # to bfloat16's (16, 128).
    k = k_ref[...]
    if grouped:
        qh = q_ref[...]        # [rows, D], each head in its K/V head's columns
    else:
        qh = jnp.where(own(), q_ref[...].astype(jnp.float32),  # [1, D] -> rows
                       0.0).astype(k.dtype)
    scores = jax.lax.dot_general(
        qh, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [rows, bt]
    kpos = b * block_t + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where((kpos >= first) & (kpos <= last), scores, _MASKED)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
    v = v_ref[...]
    acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(b == last // block_t)
    def _():
        out = acc_ref[...] / l_ref[...]
        if grouped:
            # Row h whole: the caller picks its K/V head's columns out.
            o_ref[...] = out.astype(o_ref.dtype)
        else:
            # Each head keeps its own columns: one non-zero term a column.
            o_ref[...] = jnp.where(own(), out, 0.0).sum(
                axis=0, keepdims=True).astype(o_ref.dtype)


def _step_scalars(layer, wpos, first, work, T: int, bt: int):
    """What either kernel prefetches: ``(layer [1], slot, block, count, wpos,
    first)`` int32, the work list built here where the caller brings none."""
    if T % bt:
        raise ValueError(f"block_t {bt} does not divide the pool's {T} "
                         "positions")
    wpos = wpos.astype(jnp.int32)
    slot, block, count = (work_list(wpos, T, bt, first) if work is None
                          else work)
    first = _first_row(first, wpos)
    return (jnp.asarray(layer, jnp.int32).reshape(1), slot, block, count,
            wpos, first)


@functools.partial(jax.jit, static_argnames=("heads", "block_t", "interpret"))
def decode_attention(q, cache_k, cache_v, wpos, work=None, first=None, *,
                     layer, heads: int, block_t: int | None = None,
                     interpret: bool = False):
    """q [S, heads * dh] (already scaled), cache_k / cache_v [L, S, T, D],
    ``layer`` which of the pool to read (an int32 scalar, traced or not: it
    is data, prefetched beside the work list), wpos [S] int32 the last
    position each slot may read (``wpos < T``; negative: the slot is dead,
    read nowhere, its row zeros), ``first`` [S] the first (None: 0) → [S,
    heads * dh].  ``D`` is ``heads * dh``, or narrower where the queries are
    grouped over ``D / dh`` K/V heads.  ``work`` is :func:`work_list` of the
    same ``wpos``, ``first`` and block length, from a caller that builds it
    once for many layers."""
    S, Dq = q.shape
    T, D = cache_k.shape[2:]
    dh = Dq // heads
    grouped = D != Dq
    bt = block_t or pick_block_t(T, D, cache_k.dtype)
    layer, slot, block, count, wpos, first = _step_scalars(
        layer, wpos, first, work, T, bt)
    rows = -(-heads // 16) * 16  # the bf16 sublane tile
    if grouped:
        # Head h's query in the columns of K/V head h // group, [rows, D] a
        # slot (the rows past the last head zeros).
        kv = D // dh
        owner = jnp.arange(heads) * kv // heads      # head h's K/V head
        q = jnp.where((owner[:, None] == jnp.arange(kv))[None, :, :, None],
                      q.reshape(S, heads, 1, dh), 0).reshape(S, heads, D)
        q = jnp.pad(q, ((0, 0), (0, rows - heads), (0, 0)))
    else:
        q = q[:, None, :]
    kv_spec = pl.BlockSpec(
        (None, None, bt, D),
        lambda i, layer, slot, block, wpos, first:
        (layer[0], slot[i], block[i], 0))
    row_spec = pl.BlockSpec(
        (None, q.shape[1], D),
        lambda i, layer, slot, block, wpos, first: (slot[i], 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block_t=bt, head_dim=dh, grouped=grouped),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(count,),  # a dynamic bound: the live blocks and no more
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_attention",
    )(layer, slot, block, wpos, first, q, cache_k, cache_v)
    # No grid step visits a dead slot, so nothing wrote its row.
    live = (wpos >= 0)[:, None]
    if not grouped:
        return jnp.where(live, out[:, 0, :], 0)
    # Row h's own columns: those of its K/V head.
    out = jnp.take_along_axis(out[:, :heads].reshape(S, heads, kv, dh),
                              owner[None, :, None, None], axis=2)
    return jnp.where(live, out.reshape(S, Dq), 0)


def _latent_kernel(layer_ref, slot_ref, block_ref, wpos_ref, first_ref, q_ref,
                   pool_ref, o_ref, m_ref, l_ref, acc_ref, rows_ref, sem_ref, *,
                   block_t: int, values: int):
    """A grid step of :func:`latent_attention`: block ``i`` of the work list,
    the keys its rows and the values their first ``values`` columns.

    The mathematics is :func:`_kernel`'s grouped form (scores, the block's
    max, ``exp``, the sums, ``probs @ values``, float32 throughout but the
    two products' operands), laid out for what a grid step waits on
    (PERF.md section 6, PR 56):

    *Who copies the blocks.*  ``pool_ref`` is the whole leaf where it lies
    in HBM, and ``rows_ref`` ``[buffers, block_t, D]`` holds the blocks in
    flight: step ``i`` starts the copy of block ``i + buffers - 1`` into the
    buffer that block ``i - 1`` has left, waits for its own and reads it.
    (The pipeline's own two buffers start a block's copy in the step before
    the one that reads it, and that copy outlasts the step:
    ``_LATENT_BUFFERS``.  Every copy started is waited for by the step that
    reads it, so none outlives the call.)

    *The running max and sum a lane tile wide.*  ``m_ref`` and ``l_ref`` are
    ``[rows, 128]`` (``[rows, block_t]`` where no whole tiles make up a
    block): the max the same in every column, the sum a partial sum a
    column, added up across columns once, where the span ends.  A ``[rows,
    1]`` max has to be spread over the lanes again before ``exp`` can take
    it, in the middle of the one chain a step is, and the sum's cross-lane
    add a step is spared too: a float32 sum reordered, nothing left out."""
    i = pl.program_id(0)
    count = pl.num_programs(0)
    buffers = rows_ref.shape[0]
    lanes = m_ref.shape[1]

    def copy(j):
        at = j % buffers
        row = pl.multiple_of(block_ref[j] * block_t, block_t)
        return pltpu.make_async_copy(
            pool_ref.at[layer_ref[0], slot_ref[j], pl.ds(row, block_t)],
            rows_ref.at[at], sem_ref.at[at])

    @pl.when(i == 0)
    def _():
        for j in range(buffers - 1):
            pl.when(j < count)(lambda j=j: copy(j).start())

    @pl.when(i + buffers - 1 < count)
    def _():
        copy(i + buffers - 1).start()

    copy(i).wait()
    at = i % buffers
    b = block_ref[i]
    last = wpos_ref[slot_ref[i]]
    first = first_ref[slot_ref[i]]

    @pl.when(b == first // block_t)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                  # [rows, D], a head a row
    scores = jax.lax.dot_general(
        q, rows_ref[at], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [rows, bt]
    # Every block visited is live; the select in float32, as in ``_kernel``.
    kpos = b * block_t + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where((kpos >= first) & (kpos <= last), scores, _MASKED)
    tiles = [scores[:, k:k + lanes] for k in range(0, block_t, lanes)]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, functools.reduce(jnp.maximum, tiles).max(
        axis=-1, keepdims=True))                              # [rows, lanes]
    alpha = jnp.exp(m_prev - m_new)
    probs = [jnp.exp(tile - m_new) for tile in tiles]
    l_ref[...] = alpha * l_ref[...] + functools.reduce(jnp.add, probs)
    p = probs[0] if len(probs) == 1 else jnp.concatenate(probs, axis=1)
    # ``alpha`` over the accumulator's columns: its lane tile side by side
    # where whole tiles make them up (one column of it spread over the lanes
    # costs 0.02 us a step on the v5e), else that column.
    scale = (jnp.concatenate([alpha] * (values // lanes), axis=1)
             if values % lanes == 0 else alpha[:, :1])
    acc_ref[...] = scale * acc_ref[...] + jnp.dot(
        p.astype(q.dtype), rows_ref[at, :, :values],
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(b == last // block_t)
    def _():
        # Row h whole: a head's ``values`` columns.
        o_ref[...] = (acc_ref[...] / l_ref[...].sum(
            axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "values", "block_t",
                                             "buffers", "interpret"))
def latent_attention(q, pool, wpos, work=None, first=None, *, layer,
                     heads: int, values: int, block_t: int | None = None,
                     buffers: int = _LATENT_BUFFERS, interpret: bool = False):
    """q [S, heads * D] (already scaled, each head's query over the row's
    own ``D`` columns), pool [L, S, T, D] the one leaf, ``layer``, ``wpos``,
    ``first`` and ``work`` as :func:`decode_attention` takes them → [S,
    heads * values]: each head's probabilities over its slot's span against
    the rows' first ``values`` columns.  The ``[heads, D]`` query block of a
    slot meets each block of rows once, fetched once, by the kernel's own
    copies into ``buffers`` blocks of VMEM (two is the order of the
    pipeline's own copies: chip_smoke.py times it for the record)."""
    S = q.shape[0]
    T, D = pool.shape[2:]
    bt = block_t or pick_block_t(T, D, pool.dtype)
    layer, slot, block, count, wpos, first = _step_scalars(
        layer, wpos, first, work, T, bt)
    rows = -(-heads // 16) * 16  # the bf16 sublane tile
    # Columns of the running max and sum: a lane tile where whole tiles make
    # up a block of scores, else the block.
    lanes = 128 if bt % 128 == 0 else bt
    q = jnp.pad(q.reshape(S, heads, D), ((0, 0), (0, rows - heads), (0, 0)))

    def at_slot(width):
        return pl.BlockSpec(
            (None, rows, width),
            lambda i, layer, slot, block, wpos, first: (slot[i], 0, 0))

    out = pl.pallas_call(
        functools.partial(_latent_kernel, block_t=bt, values=values),
        out_shape=jax.ShapeDtypeStruct((S, rows, values), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(count,),  # a dynamic bound: the live blocks and no more
            in_specs=[at_slot(D), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=at_slot(values),
            scratch_shapes=[pltpu.VMEM((rows, lanes), jnp.float32),
                            pltpu.VMEM((rows, lanes), jnp.float32),
                            pltpu.VMEM((rows, values), jnp.float32),
                            pltpu.VMEM((buffers, bt, D), pool.dtype),
                            pltpu.SemaphoreType.DMA((buffers,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_attention",
    )(layer, slot, block, wpos, first, q, pool)
    # No grid step visits a dead slot, so nothing wrote its row.
    return jnp.where((wpos >= 0)[:, None],
                     out[:, :heads].reshape(S, heads * values), 0)


def _kernel_block(Tq, total, d, dtype):
    """The block length at which the kernel serves a call, or None where the
    ``jax.numpy`` form of :func:`attend` does: the CPU, several queries a
    slot, a process that addresses several devices (a mesh: a Mosaic kernel
    is not partitioned automatically, and the partitioner splits the einsums
    over ``D`` as it did the heads), and a pool length that only a block too
    large for the kernel's VMEM divides."""
    if (Tq != 1 or jax.default_backend() != "tpu"
            or jax.device_count() != 1):
        return None
    bt = pick_block_t(total, d, dtype)
    return bt if fits_vmem(bt, d, dtype) else None


def read_block(total, d, dtype):
    """Positions :func:`attend` reads of a live slot's row at a time: the
    kernel's block length, or the whole row (the ``jax.numpy`` form)."""
    return _kernel_block(1, total, d, dtype) or total


def step_work(last, total, d, dtype, first=None):
    """The step's :func:`work_list` for the kernel, built once from ``last``
    (and ``first``) [S] and shared by every layer's :func:`attend` over
    ``total`` positions of width ``d``; None where the ``jax.numpy`` form
    runs, which needs none."""
    bt = _kernel_block(1, total, d, dtype)
    return None if bt is None else work_list(last, total, bt, first)


def _in_span(T: int, wpos, first):
    """[S, Tq, T] bool: the rows ``[first, wpos]`` of each query's slot
    (``first`` None: from row 0)."""
    keep = jnp.arange(T)[None, None, :] <= wpos[:, :, None]
    if first is not None:
        keep &= jnp.arange(T)[None, None, :] >= first[:, :, None]
    return keep


def attend(q, cache_k, cache_v, layer, wpos, heads, work=None, first=None):
    """Decode attention over one layer of the pool, read where it lies.

    q [S, Tq, D] (a slot's one query, or the K+1 of a speculative verify),
    cache_k / cache_v [L, S, T, D] the whole pool in its own layout (``D``
    minor, no head split) and ``layer`` which of it to read (an int, or a
    traced int32 scalar), wpos [S, Tq]
    the last position each query may read → [S, Tq, D].  A negative
    ``wpos`` marks a *dead* query (a finished or empty slot): it reads
    nothing and its output row is zeros, whatever its row of the pool
    holds.  ``first`` [S, Tq] is the first position each query reads
    (None: 0).  ``work`` is :func:`step_work` of ``wpos[:, 0]`` (and
    ``first[:, 0]``).

    Head-split attention makes ``(slot, head)`` batch dimensions, and a pool
    whose heads lie side by side in ``D`` then has to be sliced out and
    moved to a heads-major layout, K and V, every layer of every step: that
    copy was three quarters of the decode step on the chip (PERF.md section
    6, PR 26).  Here ``slot`` is the only batch dimension and the
    contraction runs over all of ``D``: head ``h``'s query sits in its own
    columns of an ``[H, D]`` block with zeros elsewhere, so row ``h`` of
    ``q_heads @ K^T`` is head ``h``'s scores and row ``h`` of ``probs @ V``
    carries head ``h``'s output in those same columns.  ``H`` times the
    multiply-adds of the head-split form, on a step bound by the bytes of
    the pool.  Scores and softmax in float32, probabilities and values in
    ``q``'s dtype; a position beyond ``wpos`` weighs exactly zero whatever
    the row holds there.

    The ``jax.numpy`` form below reads all ``T`` positions, and is what the
    tests compare the kernel with.

    A pool narrower than ``q`` holds fewer K/V heads than there are query
    heads (``heads // kv_heads`` queries share each): the kernel takes them
    as it takes the others, by the pool's width, and where it does not run
    they are read in a ``jax.numpy`` form of their own
    (:func:`_attend_grouped`).
    """
    S, Tq, D = q.shape
    dh = D // heads
    q = q * dh ** -0.5
    bt = _kernel_block(Tq, *cache_k.shape[2:], cache_k.dtype)
    if bt is not None:
        return decode_attention(q[:, 0], cache_k, cache_v, wpos[:, 0], work,
                                None if first is None else first[:, 0],
                                layer=layer, heads=heads,
                                block_t=bt)[:, None]
    if cache_k.shape[-1] != D:
        return _attend_grouped(q, cache_k[layer], cache_v[layer], wpos,
                               first, heads)
    cache_k, cache_v = cache_k[layer], cache_v[layer]
    T = cache_k.shape[1]
    own = (jnp.arange(D) // dh)[None, :] == jnp.arange(heads)[:, None]
    qh = jnp.where(own, q[:, :, None, :], 0)                   # [S,Tq,H,D]
    scores = jnp.einsum("smd,std->smt", qh.reshape(S, Tq * heads, D),
                        cache_k, preferred_element_type=jnp.float32)
    scores = jnp.where(_in_span(T, wpos, first)[:, :, None, :],
                       scores.reshape(S, Tq, heads, T), -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("smt,std->smd", probs.reshape(S, Tq * heads, T),
                     cache_v, preferred_element_type=jnp.float32)
    # Each head keeps its own columns: one non-zero term a column, so exact.
    out = jnp.where(own, out.reshape(S, Tq, heads, D), 0).sum(2)
    return jnp.where((wpos >= 0)[:, :, None], out, 0).astype(q.dtype)


def _attend_grouped(q, k, v, wpos, first, heads):
    """q [S, Tq, heads * dh] (scaled) over one layer's k, v [S, T, kv_heads *
    dh]: the pool is ``heads // kv_heads`` times narrower than the queries,
    so splitting it by head moves little, and each K/V head is scored
    against its group of queries."""
    S, Tq, D = q.shape
    T, dh = k.shape[1], D // heads
    kv = k.shape[-1] // dh
    qg = q.reshape(S, Tq, kv, heads // kv, dh)
    scores = jnp.einsum("sqhgd,sthd->sqhgt", qg, k.reshape(S, T, kv, dh),
                        preferred_element_type=jnp.float32)
    scores = jnp.where(_in_span(T, wpos, first)[:, :, None, None, :], scores,
                       -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("sqhgt,sthd->sqhgd", probs, v.reshape(S, T, kv, dh))
    return jnp.where((wpos >= 0)[:, :, None], out.reshape(S, Tq, D),
                     0).astype(q.dtype)


def attend_latent(q, pool, layer, wpos, heads, values, work=None, first=None):
    """Decode attention over one layer of a pool whose rows are one leaf.

    q [S, Tq, heads * D] (scaled by the family, each head's query over the
    row's own ``D`` columns: a latent family folds its key up-projection
    into it), pool [L, S, T, D], ``layer``, ``wpos`` [S, Tq], ``first`` and
    ``work`` as :func:`attend` takes them → [S, Tq, heads * values]: every
    head scores each row of its span whole and sums the rows' first
    ``values`` columns.  A dead query (``wpos < 0``) reads nothing and its
    row is zeros.  The kernel (:func:`latent_attention`) where
    :func:`_kernel_block` gives a block, else the ``jax.numpy`` form below
    over all ``T`` positions, which is what the tests compare the kernel
    with."""
    S, Tq, _ = q.shape
    T, D = pool.shape[2:]
    bt = _kernel_block(Tq, T, D, pool.dtype)
    if bt is not None:
        return latent_attention(q[:, 0], pool, wpos[:, 0], work,
                                None if first is None else first[:, 0],
                                layer=layer, heads=heads, values=values,
                                block_t=bt)[:, None]
    rows = pool[layer]
    scores = jnp.einsum("sqhd,std->sqht", q.reshape(S, Tq, heads, D), rows,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(_in_span(T, wpos, first)[:, :, None, :], scores, -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("sqht,stv->sqhv", probs, rows[..., :values])
    return jnp.where((wpos >= 0)[:, :, None],
                     out.reshape(S, Tq, heads * values), 0).astype(q.dtype)
