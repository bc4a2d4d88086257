"""Routed experts: the router, and a grouped matmul over the experts held here.

A layer of routed experts sends each row to ``top_k`` of ``E`` experts.  A
chip that shares the layer by expert parallelism holds ``held`` of them,
``[offset, offset + held)``: the router keeps its ``E`` outputs and its
``top_k`` (:func:`route`), and the chip computes its own experts' part of the
result for the rows routed to them (:func:`experts`).  What the absent
experts would add is left out; nothing here stands in for the other chips.

Shapes are static: ``N`` rows make ``N x top_k`` assignment rows, sorted by
expert, with the assignments that fall outside the held range in a last group
of their own that is never multiplied.  The sizes of the groups are data.
:func:`expert_matmul` multiplies each group's rows by its expert's matrix:
on one TPU device a Pallas kernel whose iteration space is the list of
``(group, row tile)`` pairs that hold a row (:func:`work_list`), so an expert
that no row reaches costs no grid step and no read of its weights; off the
chip, under a mesh and in the tests, ``jax.lax.ragged_dot``, chosen by what
the process can observe, as ops/decode_attention.attend chooses its form.
The kernel takes an expert's matrix whole in ``K`` and in blocks of ``N``
sized by their bytes: a decode step is bound by the experts' bytes (a handful
of rows an expert), and each matrix reached is read once.  An expert is
``W2 relu(W1 u)^2`` (``relu2``) or gated, ``W2 (silu(W1 u) * (W3 u))``: the
gated form hands the kernel ``W1`` and ``W3`` together (``up=``), a block of
each a grid step, and the product is taken in float32 before it is rounded.

A prefill puts hundreds of rows on each expert and is bound by the
arithmetic, not the bytes, so :func:`plan` keeps the two regimes apart by the
call's static shape, as ops/int8_matmul.plan does.  Under ``_TILES_FROM``
rows a group (``stream``) the call is the one above.  From there on
(``tiles``) a tile holds one group's rows: nothing is multiplied once for
each group in a tile, and nothing is masked.  :func:`experts` lays the rows
out for it (:func:`lay_out`): the gather that sorts them begins each group on
a multiple of ``_ROW_ALIGN`` rows, a row tile of the first call begins where
its group does (its block's offset counted in rows, not in tiles), the call
writes each group on a multiple of the tile, where the second call and the
un-sort read it; rows between groups are never computed.  The grid walks the
tiles in order (:func:`tile_list`), and the kernel fetches an expert's block
itself, into one of two buffers, when the *previous* group's first tile
begins: a whole group's arithmetic lies beside the fetch, where the
pipeline's one step of look-ahead left half of it bare.  The blocks are as
wide as ``_TILES_BLOCK_BYTES`` allows, so a row tile is read once a block of
``N`` (all of ``N`` at the widths served), and the kernel asks for the VMEM
they take (``Plan.vmem``: two buffers a matrix, the row tile and the output
twice, the float32 products, 4 MiB besides; 16 MiB is what a kernel gets
unasked, a v5e core has 128).

The ``tiles`` regime's un-sort is a gather and one pass
(:func:`expert_combine`, a kernel bound by its bytes): a row's ``top_k``
rows are read where the gather left them, in bfloat16, weighed and summed in
float32 in VMEM, and ``[N, K]`` float32 is all that is written.  The cast of
every assignment row to float32 that ``einsum`` makes of the same sum, the
largest array a prefill wrote, does not exist; a weight of 0 selects 0
whatever lies in its row.

``interpret=True`` runs the kernel itself on the CPU for the tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes a block of an expert's matrix aims at: two buffers of it, the row
# tile's and the output's fit the 16 MiB of VMEM a kernel is given.
_BLOCK_BYTES = 4 << 20
# Rows a tile holds at most: the MXU's own 128.  On the chip tiles of 256
# and 512 rows a step cost the same 10.3 us a 128 rows as tiles of 128 (the
# product is Mosaic's, at 0.7-0.8 of the peak whatever its rows) and waste
# half a tile a group more (PERF.md section 6, PR 49).
_MAX_TILE = 128
# The ``tiles`` regime: from this many rows a group, and a block of an
# expert's matrices up to this many bytes (two of them wait in VMEM).
_TILES_FROM = 128
_TILES_BLOCK_BYTES = 13 << 20
_ROW_ALIGN = 16  # rows: a bfloat16 tile of HBM, where a block may begin
# Bytes a block of the gathered rows aims at in the un-sort's pass
# (:func:`expert_combine`): two buffers of it, the output's two and the
# float32 sum fit the 16 MiB a kernel is given unasked.
_COMBINE_BLOCK_BYTES = 3 << 20


def route(x, gate, bias, top_k: int, scale: float, offset: int, held: int,
          scoring: str = "sigmoid"):
    """The router, in float32: x [N, D] (the rows at full width), ``gate``
    [D, E], ``bias`` [E] → ``(weights [N, top_k] float32, group [N, top_k]
    int32)``.  ``s = sigmoid(x @ gate)``; the ``top_k`` largest of ``s +
    bias`` are chosen; their weights are ``s`` there, divided by their sum,
    times ``scale``.  With ``scoring="softmax"`` ``s`` is the softmax over
    all ``E`` and there is no bias in the choice (``bias`` None).  ``group``
    is the chosen expert less ``offset`` where it is held here, and ``held``
    (the group that is not multiplied) elsewhere."""
    logits = jnp.dot(x.astype(jnp.float32), gate.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(s, top_k)
    else:
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = w / w.sum(-1, keepdims=True) * scale
    local = chosen - offset
    here = (local >= 0) & (local < held)
    return w, jnp.where(here, local, held).astype(jnp.int32)


def group_sizes(group, held: int):
    """Rows on each held expert, [held] int32, from :func:`route`'s groups."""
    return jnp.bincount(group.reshape(-1), length=held + 1)[:held].astype(
        jnp.int32)


# What :func:`counters` counts, ``(name, what)`` each, as a family declares
# them (models/decoder.Family.counters).
COUNTERS = (("expert_assignments_held",
             "Rows routed to the experts held here"),
            ("experts_touched", "Held experts that at least one row "
             "reached, a layer a step"),
            ("expert_load_max", "The most rows on one held expert, "
             "a layer a step"))


def counters(sizes):
    """What a layer's routing did here, int32 [3]: rows routed to held
    experts, held experts with at least one row, the most rows on one."""
    return jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()]).astype(
        jnp.int32)


def work_list(sizes, rows: int, tile: int):
    """The ``(group, row tile)`` pairs that hold a row, in order: ``sizes``
    [G] rows a group (sorted by group, so group ``g`` is rows ``[offs[g],
    offs[g + 1])``), ``rows`` a multiple of ``tile`` → ``(offs [G + 1],
    group [W], tile [W], count)`` int32 with ``W = rows / tile + G``;
    entries from ``count`` on are padding the kernel never visits.  An
    empty group has no pair."""
    G = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]).astype(
        jnp.int32)
    first = offs[:-1] // tile
    spans = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(spans)
    i = jnp.arange(rows // tile + G, dtype=jnp.int32)
    g = jnp.minimum((upto[None, :] <= i[:, None]).sum(1), G - 1)
    t = jnp.clip(first[g] + i - (upto - spans)[g], 0, rows // tile - 1)
    return offs, g.astype(jnp.int32), t.astype(jnp.int32), upto[-1].astype(
        jnp.int32)


def _kernel(offs_ref, group_ref, tile_ref, x_ref, *refs, tile: int,
            relu2: bool):
    *w_refs, o_ref = refs
    i = pl.program_id(1)
    g = group_ref[i]
    acc = _product(x_ref, w_refs, relu2)
    row = tile_ref[i] * tile + jax.lax.broadcasted_iota(jnp.int32, acc.shape,
                                                        0)
    mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
    # A tile is visited once for every group that has a row in it, one after
    # the other, and each visit leaves the other groups' rows as they are.
    o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)


def pick_block_n(k: int, n: int, itemsize: int,
                 within: int = _BLOCK_BYTES) -> int:
    """Columns a block of an expert's [k, n] matrix holds: the most, in
    whole lanes of 128 that divide ``n``, within ``within`` bytes; all of
    ``n`` where it is no multiple of 128 (a tiny width of the tests)."""
    if n % 128:
        return n
    fits = [c for c in range(128, n + 1, 128)
            if n % c == 0 and k * c * itemsize <= within]
    return max(fits, default=128)


def pick_tile(rows: int, groups: int) -> int:
    """Rows a tile holds: about a group's share of the rows, a power of two
    between the bfloat16 sublane tile and ``_MAX_TILE``.  A decode step's
    groups hold a row or two and a small tile wastes least; a prefill's
    hold hundreds."""
    want = max(rows // max(groups, 1), 1)
    return min(_MAX_TILE, max(16, 1 << (want - 1).bit_length()))


class Plan(NamedTuple):
    """What a call's static shape chose (:func:`plan`)."""
    regime: str   # "stream" (bound by the experts' bytes) or "tiles"
    tile: int     # rows a tile
    block_n: int  # columns a block of an expert's matrix
    grid: str     # the order the grid walks in
    vmem: int | None  # bytes of VMEM the kernel asks for; None: the default


def plan(M: int, K: int, N: int, G: int, mats: int = 1,
         itemsize: int = 2) -> Plan:
    """The kernel's plan for x [M, K] over w [G, K, N] (``mats`` matrices an
    expert: 2 gated), from the call's static shape alone: ``M // G`` rows a
    group is all a trace knows of the sizes."""
    tm = pick_tile(M, G)
    if M // max(G, 1) < _TILES_FROM:
        return Plan("stream", tm, pick_block_n(K, N, itemsize * mats),
                    "blocks of N, then (group, tile) pairs", None)
    tn, vmem = _tiles_blocks(K, N, mats, itemsize, tm)
    return Plan("tiles", tm, tn, "blocks of N, then whole tiles a group, "
                "the next group's block fetched a group ahead", vmem)


def _tiles_blocks(K: int, N: int, mats: int, itemsize: int, tile: int):
    """``(columns a block, bytes of VMEM)`` of the ``tiles`` regime: two
    buffers a matrix, the row tile and the output twice (the pipeline's),
    the float32 products, and 4 MiB for the compiler's own."""
    tn = pick_block_n(K, N, itemsize * mats, _TILES_BLOCK_BYTES)
    return tn, ((2 * mats * K * tn + 2 * tile * K + 2 * tile * tn) * itemsize
                + (mats + 1) * tile * tn * 4 + (4 << 20))


def plan_summary(rows: int, top_k: int, K: int, F: int, held: int,
                 gated: bool, itemsize: int = 2) -> dict:
    """What :func:`experts` runs for ``rows`` rows routed ``top_k`` ways over
    ``held`` experts of [K, F] (and [F, K] back), for a log line: the regime,
    the row tile, the blocks' columns of both calls, the grid's order, the
    most VMEM a call asks for (None: the default), and the un-sort's form:
    ``combine`` (:func:`expert_combine`, the ``tiles`` regime's) or
    ``einsum``."""
    up = plan(rows * top_k, K, F, held, 2 if gated else 1, itemsize)
    down = plan(rows * top_k, F, K, held, 1, itemsize)
    return {"regime": up.regime, "tile": up.tile,
            "blocks": [up.block_n, down.block_n], "grid": up.grid,
            "vmem": max(up.vmem or 0, down.vmem or 0) or None,
            "unsort": "combine" if up.regime == "tiles" else "einsum"}


def laid_rows(rows: int, groups: int, tile: int, align: int | None = None):
    """Static rows that hold ``rows`` rows of ``groups`` groups, each group
    begun on a multiple of ``align`` (``tile`` unless given), whatever the
    sizes; with an ``align`` under ``tile``, a tile more at the end that no
    group holds, so that a group's last tile can be read whole."""
    align = align or tile
    return (rows + groups * (align - 1)) // align * align \
        + (tile if align != tile else 0)


_NOWHERE = jnp.iinfo(jnp.int32).max  # an index no row lies at


def lay_out(sizes, rows: int, align: int):
    """Where the ``tiles`` regime keeps ``rows`` rows sorted by group, each
    group's first row on a multiple of ``align``: ``sizes`` [G] → at [rows]
    int32, sorted row ``j`` lies at row ``at[j]`` (of :func:`laid_rows`);
    a row past ``sizes.sum()`` belongs to no group and lies ``_NOWHERE``.
    (A sum over a row's one group, not ``shift[group]``: the compiler
    writes a gather from a small table as hundreds of instructions.)"""
    spans, ends = -(-sizes // align), jnp.cumsum(sizes)
    shift = (jnp.cumsum(spans) - spans) * align - (ends - sizes)
    j = jnp.arange(rows, dtype=jnp.int32)
    mine = (((ends - sizes)[None, :] <= j[:, None])
            & (j[:, None] < ends[None, :]))
    at = j + jnp.where(mine, shift[None, :], 0).sum(1)
    return jnp.where(j < ends[-1], at, _NOWHERE).astype(jnp.int32)


def tile_list(sizes, tiles: int, tile: int, align: int | None = None):
    """The ``tiles`` regime's work: ``(group [tiles], first [tiles], upto
    [G], rank [G], count)`` int32.  Tile ``i < count`` holds rows of
    ``group[i]`` alone: rows ``[first[i], first[i] + tile)`` of rows laid
    out on multiples of ``align`` (:func:`lay_out`), of which the group's
    own come first.  Group ``g``'s tiles end before tile ``upto[g]``, and
    ``rank[g]`` groups before it hold a row."""
    align = align or tile
    spans, steps = -(-sizes // tile), -(-sizes // align)
    upto = jnp.cumsum(spans)
    begin = (jnp.cumsum(steps) - steps) * align - (upto - spans) * tile
    i = jnp.arange(tiles, dtype=jnp.int32)
    mine = (((upto - spans)[None, :] <= i[:, None])
            & (i[:, None] < upto[None, :]))
    group = jnp.where(mine, jnp.arange(sizes.shape[0])[None, :], 0).sum(1)
    first = i * tile + jnp.where(mine, begin[None, :], 0).sum(1)
    held = sizes > 0
    return tuple(a.astype(jnp.int32) for a in (
        group, first, upto, jnp.cumsum(held) - held, upto[-1]))


def _product(x_ref, w_refs, relu2: bool):
    """``x @ w`` in float32, squared where positive (``relu2``) or, with two
    matrices, ``silu(x @ gate) * (x @ up)``; each argument a ref or a view
    of one."""
    acc = jnp.dot(x_ref[...], w_refs[0][...],
                  preferred_element_type=jnp.float32)
    if relu2:
        acc = jnp.square(jnp.maximum(acc, 0.0))
    if len(w_refs) == 2:
        acc = jax.nn.silu(acc) * jnp.dot(x_ref[...], w_refs[1][...],
                                         preferred_element_type=jnp.float32)
    return acc


def _tiles_kernel(group_ref, first_ref, upto_ref, rank_ref, x_ref, *refs,
                  mats: int, tn: int, relu2: bool):
    """A tile of one group's rows times a block of its expert's matrices,
    which wait in one of two buffers: the first tile of a group starts the
    fetch of the next group's block (the next block of ``N``'s first group
    after the last) and then waits for its own, started a group ago."""
    w_hbm, o_ref = refs[:mats], refs[mats]
    bufs, sem = refs[mats + 1:2 * mats + 1], refs[2 * mats + 1]
    n, i = pl.program_id(0), pl.program_id(1)
    g, count = group_ref[i], pl.num_programs(1)
    last = upto_ref[g] == count              # no group after this one
    # (block of N, group) pairs before this one: its buffer is their parity.
    visit = n * (rank_ref[group_ref[count - 1]] + 1) + rank_ref[g]
    slot = visit % 2

    def fetch(g, n, slot):
        return [pltpu.make_async_copy(
            w.at[g, :, pl.ds(pl.multiple_of(n * tn, tn), tn)], buf.at[slot],
            sem.at[slot, m]) for m, (w, buf) in enumerate(zip(w_hbm, bufs))]

    @pl.when((i == 0) | (group_ref[jnp.maximum(i - 1, 0)] != g))
    def _():
        @pl.when(visit == 0)
        def _():
            for copy in fetch(g, n, slot):
                copy.start()

        then = n + last.astype(jnp.int32)

        @pl.when(then < pl.num_programs(0))
        def _():
            following = group_ref[jnp.where(last, 0, upto_ref[g])]
            for copy in fetch(following, then, 1 - slot):
                copy.start()

        for copy in fetch(g, n, slot):
            copy.wait()

    o_ref[...] = _product(x_ref, [buf.at[slot] for buf in bufs],
                          relu2).astype(o_ref.dtype)


def _tiles_call(x, mats, sizes, relu2: bool, tile: int, align: int,
                interpret: bool):
    """The ``tiles`` regime: x [.., K] as :func:`lay_out` places the rows on
    multiples of ``align`` → [tiles x tile, N] with them on multiples of
    ``tile``; rows no group holds are not computed."""
    K, (G, _, N) = x.shape[1], mats[0].shape
    tn, vmem = _tiles_blocks(K, N, len(mats), mats[0].dtype.itemsize, tile)
    # Tiles enough for the groups of any sizes that x's rows can hold.
    tiles = x.shape[0] // tile if align == tile else laid_rows(
        x.shape[0] - tile, G, tile) // tile
    group, first, upto, rank, count = tile_list(
        sizes.astype(jnp.int32), tiles, tile, align)
    # A row tile begins where its group's rows do, a multiple of ``align``
    # rows into x and not of the tile: its offset is counted in rows.
    x_spec = pl.BlockSpec(
        (pl.Element(tile), pl.Element(K)),
        lambda n, i, group, first, *_: (pl.multiple_of(first[i], align), 0))
    return pl.pallas_call(
        functools.partial(_tiles_kernel, mats=len(mats), tn=tn, relu2=relu2),
        out_shape=jax.ShapeDtypeStruct((tiles * tile, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, count),
            in_specs=[x_spec, *[pl.BlockSpec(memory_space=pl.ANY)] * len(mats)],
            out_specs=pl.BlockSpec((tile, tn), lambda n, i, *_: (i, n)),
            scratch_shapes=[
                *[pltpu.VMEM((2, K, tn), mats[0].dtype)] * len(mats),
                pltpu.SemaphoreType.DMA((2, len(mats)))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="expert_matmul",
    )(group, first, upto, rank, x, *mats)


@functools.partial(jax.jit, static_argnames=("relu2", "tile", "interpret",
                                             "laid_out"))
def expert_matmul_kernel(x, w, sizes, up=None, *, relu2: bool = False,
                         tile: int | None = None, interpret: bool = False,
                         laid_out: int = 0):
    """x [M, K] sorted by group, w [G, K, N], sizes [G] → [M, N] in ``x``'s
    dtype; rows past ``sizes.sum()`` hold nothing meaningful.  With ``up``
    [G, K, N] the gated form, ``silu(x @ w) * (x @ up)``: both of an
    expert's blocks in one grid step, each half the bytes.  ``laid_out``:
    the ``tiles`` regime, x as :func:`lay_out` places the rows on multiples
    of ``laid_out``, and the result with them on multiples of ``tile``."""
    M, K = x.shape
    G, _, N = w.shape
    mats = (w,) if up is None else (w, up)
    if laid_out:
        return _tiles_call(x, mats, sizes, relu2, tile, laid_out, interpret)
    tm = tile or pick_tile(M, G)
    rows = -(-M // tm) * tm
    if rows != M:
        x = jnp.pad(x, ((0, rows - M), (0, 0)))
    tn = pick_block_n(K, N, w.dtype.itemsize * len(mats))
    offs, group, tiles, count = work_list(sizes.astype(jnp.int32), rows, tm)
    w_spec = pl.BlockSpec((None, K, tn), lambda n, i, offs, g, t: (g[i], 0, n))
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tm, relu2=relu2),
        out_shape=jax.ShapeDtypeStruct((rows, N), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # The inner bound is a value of the call: the pairs that hold a
            # row and no more.
            grid=(N // tn, count),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, i, offs, g, t: (t[i], 0)),
                *[w_spec] * len(mats)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, i, offs, g, t: (t[i], n))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="expert_matmul",
    )(offs, group, tiles, x, *mats)
    return out[:M]


def _combine_kernel(w_ref, y_ref, o_ref):
    """A block of rows' weighted sum over their ``top_k`` gathered rows, in
    float32.  A weight of 0 gives 0 whatever lies in its row: an assignment
    held elsewhere reads a row that is not its own, and with no row held
    anywhere one that was never written."""
    acc = None
    for k in range(y_ref.shape[0]):
        w = w_ref[:, k:k + 1]
        term = jnp.where(w != 0, w * y_ref[k].astype(jnp.float32), 0.0)
        acc = term if acc is None else acc + term
    o_ref[...] = acc


def pick_combine_rows(top_k: int, K: int, itemsize: int) -> int:
    """Rows of the result a block of :func:`expert_combine` holds: a power
    of two from the bfloat16 sublane tile to 256 whose ``top_k`` gathered
    rows each stay within ``_COMBINE_BLOCK_BYTES``."""
    fits = max(_COMBINE_BLOCK_BYTES // (top_k * K * itemsize), 16)
    return min(256, 1 << (fits.bit_length() - 1))


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_combine(y, weights, *, interpret: bool = False):
    """The un-sort's one pass: y [top_k, N, K], a row's ``top_k`` gathered
    rows as they lie, ``weights`` [N, top_k] float32 → ``sum_k weights[n, k]
    y[k, n]`` [N, K] float32, each row of ``y`` read once and turned to
    float32 where it is multiplied; nothing of ``top_k x N x K`` float32
    elements is written.  Bound by its bytes."""
    top_k, N, K = y.shape
    tn = pick_combine_rows(top_k, K, y.dtype.itemsize)
    return pl.pallas_call(
        _combine_kernel,
        out_shape=jax.ShapeDtypeStruct((N, K), jnp.float32),
        grid=(pl.cdiv(N, tn),),
        in_specs=[pl.BlockSpec((tn, top_k), lambda i: (i, 0)),
                  pl.BlockSpec((top_k, tn, K), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((tn, K), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="expert_combine",
    )(weights, y)


def _use_kernel() -> bool:
    """One TPU device: a Mosaic kernel is not partitioned automatically, so
    a process that addresses several devices (a mesh) and the CPU take
    ``jax.lax.ragged_dot``."""
    return jax.default_backend() == "tpu" and jax.device_count() == 1


def expert_matmul(x, w, sizes, relu2: bool = False, up=None):
    """Each group's rows of x [M, K] (sorted by group) times its expert's
    matrix w [G, K, N] → [M, N]; ``relu2`` squares the positive part of the
    float32 product before it is rounded to ``x``'s dtype, and ``up`` [G, K,
    N] makes it the gate of ``silu(x @ w) * (x @ up)``, float32 until the
    product is rounded.  Rows past ``sizes.sum()`` belong to no group here:
    the caller masks them.  The rows lie one after the other, so on the
    chip this is the ``stream`` regime's call whatever their number; rows
    laid out for the ``tiles`` regime are :func:`experts`' to make."""
    if _use_kernel():
        return expert_matmul_kernel(x, w, sizes, up, relu2=relu2)

    def dot(m):
        return jax.lax.ragged_dot(x, m, sizes.astype(jnp.int32),
                                  preferred_element_type=jnp.float32)

    out = dot(w)
    if relu2:
        out = jnp.square(jnp.maximum(out, 0.0))
    if up is not None:
        out = jax.nn.silu(out) * dot(up)
    return out.astype(x.dtype)


def part(name: str):
    """models/decoder.py's scope of a named part of the model's step (what a
    capture's device time is booked to), imported where it is used: the
    models import this module."""
    from ..models.decoder import part as scope

    return scope(name)


def experts(u, w1, w2, weights, group, w3=None):
    """The held experts' part of the layer: u [N, K] the rows in the width
    the experts read, w1 [held, K, F] and w2 [held, F, K] the experts'
    matrices, ``weights`` and ``group`` [N, top_k] from :func:`route` →
    ``(out [N, K] float32, sizes [held])``: ``sum_e weight_e W2_e relu(W1_e
    u)^2`` over the assignments that fall on a held expert, or, with w3
    [held, K, F], the gated expert ``W2_e (silu(W1_e u) * (W3_e u))``."""
    N, top_k = group.shape
    held = w1.shape[0]
    with part("experts.sort"):
        flat = group.reshape(-1)
        order = jnp.argsort(flat, stable=True)   # assignment rows by group
        sizes = group_sizes(group, held)
    chosen = plan(N * top_k, *w1.shape[1:], held, 1 if w3 is None else 2,
                  w1.dtype.itemsize)
    if _use_kernel() and chosen.regime == "tiles":
        return _experts_laid_out(u, w1, w2, w3, weights, order, sizes,
                                 chosen.tile), sizes
    with part("experts.sort"):
        rows = u[order // top_k]                 # [N * top_k, K]
    with part("experts.matmul"):
        y = expert_matmul(
            expert_matmul(rows, w1, sizes, relu2=w3 is None, up=w3), w2,
            sizes)
    with part("experts.unsort"):
        y = jnp.where((jnp.arange(N * top_k) < sizes.sum())[:, None], y, 0)
        back = jnp.zeros_like(order).at[order].set(jnp.arange(N * top_k))
        y = y[back].reshape(N, top_k, -1).astype(jnp.float32)
        return jnp.einsum("nkd,nk->nd", y, weights), sizes


def sorted_places(order, sizes, top_k: int, tile: int):
    """The two gathers of the ``tiles`` regime, as indices: ``order`` [A]
    the assignment rows by group, ``sizes`` [held] → ``(src, back)``.  Row
    ``j`` of the first call's input is row ``src[j]`` of ``u``, each group
    begun on a multiple of ``_ROW_ALIGN`` rows (what a block's offset into
    an array in HBM must be); assignment ``k`` of row ``n`` lies at row
    ``back[n, k]`` of the second call's result, each group on a multiple of
    the tile, or ``_NOWHERE`` where its expert is not held."""
    A = order.shape[0]
    src = jnp.zeros((laid_rows(A, sizes.shape[0], tile, _ROW_ALIGN),),
                    order.dtype).at[lay_out(sizes, A, _ROW_ALIGN)].set(
                        order // top_k, mode="drop")
    back = jnp.zeros_like(order).at[order].set(lay_out(sizes, A, tile))
    return src, back.reshape(-1, top_k)


def _experts_laid_out(u, w1, w2, w3, weights, order, sizes, tile: int):
    """:func:`experts`' sum in the ``tiles`` regime.  The gather that sorts
    the rows lays them out for the first call, which writes each group on a
    multiple of the tile, where the second reads them
    (:func:`sorted_places`), and the un-sort is a gather and one pass: it
    reads those places back, a row's first assignments before its second
    ones (``[top_k, N, K]`` is the gathered rows as they lie; ``[N, top_k,
    K]`` would be a copy of them all), and :func:`expert_combine` weighs and
    sums them as it reads them, at weight 0 where the expert is not held."""
    N, top_k = weights.shape
    with part("experts.sort"):
        src, back = sorted_places(order, sizes, top_k, tile)
        rows = u[src]
    with part("experts.matmul"):
        y = expert_matmul_kernel(rows, w1, sizes, w3, relu2=w3 is None,
                                 tile=tile, laid_out=_ROW_ALIGN)
        y = expert_matmul_kernel(y, w2, sizes, tile=tile, laid_out=tile)
    with part("experts.unsort"):
        here = back != _NOWHERE
        # An assignment held elsewhere reads row 0 at weight 0: a row of the
        # first group that has one, or, with no row held anywhere, a row
        # that nothing wrote.
        y = y[jnp.where(here, back, 0).T.reshape(-1)].reshape(top_k, N, -1)
        return expert_combine(y, jnp.where(here, weights, 0))
