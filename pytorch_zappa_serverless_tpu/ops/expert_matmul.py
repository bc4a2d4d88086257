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

``interpret=True`` runs the kernel itself on the CPU for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Bytes a block of an expert's matrix aims at: two buffers of it, the row
# tile's and the output's fit the 16 MiB of VMEM a kernel is given.
_BLOCK_BYTES = 4 << 20
_MAX_TILE = 128  # rows a tile holds at most


def route(x, gate, bias, top_k: int, scale: float, offset: int, held: int):
    """The router, in float32: x [N, D] (the rows at full width), ``gate``
    [D, E], ``bias`` [E] → ``(weights [N, top_k] float32, group [N, top_k]
    int32)``.  ``s = sigmoid(x @ gate)``; the ``top_k`` largest of ``s +
    bias`` are chosen; their weights are ``s`` there, divided by their sum,
    times ``scale``.  ``group`` is the chosen expert less ``offset`` where
    it is held here, and ``held`` (the group that is not multiplied)
    elsewhere."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               gate.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
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
    acc = jnp.dot(x_ref[...], w_refs[0][...],
                  preferred_element_type=jnp.float32)
    if relu2:
        acc = jnp.square(jnp.maximum(acc, 0.0))
    if len(w_refs) == 2:  # gated: silu(x @ gate) * (x @ up)
        acc = jax.nn.silu(acc) * jnp.dot(x_ref[...], w_refs[1][...],
                                         preferred_element_type=jnp.float32)
    row = tile_ref[i] * tile + jax.lax.broadcasted_iota(jnp.int32, acc.shape,
                                                        0)
    mine = (row >= offs_ref[g]) & (row < offs_ref[g + 1])
    # A tile is visited once for every group that has a row in it, one after
    # the other, and each visit leaves the other groups' rows as they are.
    o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)


def pick_block_n(k: int, n: int, itemsize: int) -> int:
    """Columns a block of an expert's [k, n] matrix holds: the most, in
    whole lanes of 128 that divide ``n``, within ``_BLOCK_BYTES``; all of
    ``n`` where it is no multiple of 128 (a tiny width of the tests)."""
    if n % 128:
        return n
    fits = [c for c in range(128, n + 1, 128)
            if n % c == 0 and k * c * itemsize <= _BLOCK_BYTES]
    return max(fits, default=128)


def pick_tile(rows: int, groups: int) -> int:
    """Rows a tile holds: about a group's share of the rows, a power of two
    between the bfloat16 sublane tile and ``_MAX_TILE``.  A decode step's
    groups hold a row or two and a small tile wastes least; a prefill's
    hold hundreds."""
    want = max(rows // max(groups, 1), 1)
    return min(_MAX_TILE, max(16, 1 << (want - 1).bit_length()))


@functools.partial(jax.jit, static_argnames=("relu2", "tile", "interpret"))
def expert_matmul_kernel(x, w, sizes, up=None, *, relu2: bool = False,
                         tile: int | None = None, interpret: bool = False):
    """x [M, K] sorted by group, w [G, K, N], sizes [G] → [M, N] in ``x``'s
    dtype; rows past ``sizes.sum()`` hold nothing meaningful.  With ``up``
    [G, K, N] the gated form, ``silu(x @ w) * (x @ up)``: both of an
    expert's blocks in one grid step, each half the bytes."""
    M, K = x.shape
    G, _, N = w.shape
    tm = tile or pick_tile(M, G)
    rows = -(-M // tm) * tm
    if rows != M:
        x = jnp.pad(x, ((0, rows - M), (0, 0)))
    mats = (w,) if up is None else (w, up)
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
    the caller masks them."""
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


def experts(u, w1, w2, weights, group, w3=None):
    """The held experts' part of the layer: u [N, K] the rows in the width
    the experts read, w1 [held, K, F] and w2 [held, F, K] the experts'
    matrices, ``weights`` and ``group`` [N, top_k] from :func:`route` →
    ``(out [N, K] float32, sizes [held])``: ``sum_e weight_e W2_e relu(W1_e
    u)^2`` over the assignments that fall on a held expert, or, with w3
    [held, K, F], the gated expert ``W2_e (silu(W1_e u) * (W3_e u))``."""
    N, top_k = group.shape
    held = w1.shape[0]
    flat = group.reshape(-1)
    order = jnp.argsort(flat, stable=True)       # assignment rows by group
    sizes = group_sizes(group, held)
    rows = u[order // top_k]                     # [N * top_k, K]
    y = expert_matmul(expert_matmul(rows, w1, sizes, relu2=w3 is None, up=w3),
                      w2, sizes)
    y = jnp.where((jnp.arange(N * top_k) < sizes.sum())[:, None], y, 0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(N * top_k))
    y = y[back].reshape(N, top_k, -1).astype(jnp.float32)
    return jnp.einsum("nkd,nk->nd", y, weights), sizes
