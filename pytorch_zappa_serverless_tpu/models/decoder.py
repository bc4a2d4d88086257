"""What every token-in, token-out decoder shares, each thing once.

A family (models/gpt2.py is one) brings a block — how a token becomes a row,
one layer, the final norm, the head — as a :class:`Family`, and gets back
everything between the scheduler and the block: the cache seam
(:class:`SlotPool`, :class:`PagedPool`), the one point at which the programs
differ in *where* the cache is; the trunk over its layers; the segment's
scan (models/whisper.py runs its own layers under it); the programs
serving/generation.py jits; and the servable with the
``meta["continuous"]`` contract of both schedulers.

TPU-first structure, one jitted program per (batch, prompt-bucket):

- **Prefill + scan split**: the whole prompt runs in ONE batched forward —
  large MXU matmuls filling the KV cache for every position at once — and
  only the ``max_new`` generated tokens pay the sequential ``lax.scan``.
  A P-token prompt costs one forward, not P scan steps.
- **Ragged prompts inside a bucket**: per-row ``length`` rides as an input;
  attention masks key positions ``>= len_i`` during prefill, the first
  generated token reads its logits from position ``len_i - 1``, and step t
  writes its KV at per-row position ``len_i + t`` (a batched scatter), so
  rows of different lengths share one compiled program with zero recompiles.
"""

from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..engine.cache import layer_traced
from ..ops import decode_attention
from ..ops.flash_attention import masked_attention as _attn
from ..ops.flash_attention import prompt_attend, prompt_form
from ..ops.paged_attention import gather_kv, paged_index
from ..ops.sampling import (DRAFT_SEED_SALT, apply_repetition_penalty,
                            choose)


# The named parts of a model's step, one vocabulary for every family: what a
# profiler capture's device time is booked to (utils/xplane.py reads the
# scope back out of each operation's ``op_name``; docs/OBSERVABILITY.md says
# what each part holds).  A dot makes a sub-part.
PARTS = ("embed", "norm", "qkv", "attend", "cache_write", "attend_out", "mlp",
         "shared", "route", "experts.sort", "experts.matmul",
         "experts.unsort", "ssm", "conv", "summary", "head", "sample")
# What a part's scope begins with, so that no primitive's or function's name
# in an ``op_name`` path (``jit(norm)``, ``conv_general_dilated``) can be
# taken for a part.
PART_MARK = "part."


def part(name: str):
    """The scope of one of :data:`PARTS`: what is traced inside it is that
    part's work (the innermost one, where they nest).  Metadata on the
    traced operations and nothing else: the lowered text, the compile
    cache's key and the compiled program are the same with and without (a
    scope's name is in none of them; the source *lines* of the frames above
    a Mosaic kernel are in its payload, whoever edits them)."""
    if name not in PARTS:
        raise ValueError(f"{name!r} is no part of a model's step: {PARTS}")
    return jax.named_scope(PART_MARK + name)


class Rows:
    """How the ``T`` rows of a slot hold its positions, and what a query
    reads of them.  This one is a row a position: position ``p`` lies at row
    ``p`` and a query there reads rows ``[0, p]``.  A family whose cache is
    not that (models/evabyte.py keeps a ring of exact rows and a summary row
    a chunk before it) brings its own; every method takes ``T`` so that one
    object serves every pool length, and ``row`` / ``span`` are written in
    operators alone, so that the scheduler counts with them in numpy what
    the programs compute with them traced.  What a query reads is always one
    contiguous span of rows: ops/decode_attention.py takes a first and a
    last row and nothing more.

    A row is a K and a V, a leaf each, unless ``values`` says that it is one
    leaf whose first ``values`` columns are what a query sums (latent
    attention: the row is scored whole).  The layer then hands ``attend``
    its queries and that one row; a prefill's ``prompt`` writes the rows and
    attends however the family does, and a decode step turns the queries to
    the row's columns (:meth:`absorb`), attends over the leaf and turns the
    result back (:meth:`expand`)."""

    values: int | None = None

    def count(self, total: int) -> int:
        """Rows a slot needs for ``total`` positions."""
        return total

    def positions(self, T: int) -> int:
        """Positions ``T`` rows hold."""
        return T

    def row(self, pos, T: int):
        """The row position ``pos`` is written to."""
        return pos

    def span(self, pos, T: int):
        """``(first, last)`` rows a query at ``pos`` reads."""
        return pos * 0, pos

    def summaries(self, pos, T: int):
        """How many rows of that span stand for more than one position."""
        return pos * 0

    def windows(self, n: int) -> int:
        """Passes the prompt attention makes over a prompt of ``n``."""
        return 1

    def prefill_batch(self, bucket: int) -> int | None:
        """Prompts of ``bucket`` one prefill dispatch may hold (None: as
        many as are admitted together)."""
        return None

    def settle(self, p, k, v, layer, slots, pos):
        """After position ``pos`` [S] of ``layer`` (its index, an int32
        scalar the trunk hands on traced) was written into ``k``, ``v``
        [L, S, T, D]: whatever else the rows keep of it (``p`` is the
        layer's parameters).  Nothing here."""
        return k, v

    def prompt(self, heads: int, lengths, P: int, put):
        """The prompt attention of a prefill over ``P`` positions of which
        row b holds ``lengths[b]``: ``attend(p, cache, i, q, k, v) ->
        (cache, out [B, P, D])`` leaves in ``cache`` (the pool's leaves, K
        and V first) the rows of layer ``i`` (an index as data: a traced
        int32 scalar) that a decode step will read, and returns the
        attention output (``p``: the layer's parameters).  ``put(leaf, i,
        values [B, n, D], row=0)`` is the only write there is
        (:func:`slot_put`): prompt b's ``n`` rows from ``row`` on, wherever
        the pool keeps prompt b.  Rows it is not handed keep what they held,
        and nothing may read those before a decode step has written them.
        Here causal and ragged in one softmax over ``[P, P]`` scores
        (ops/flash_attention.prompt_attend keeps them on the chip where it
        can), and the rows are the prompt's own K and V."""
        def attend(p, cache, i, q, k, v):
            return ((put(cache[0], i, k), put(cache[1], i, v)) + cache[2:],
                    prompt_attend(q, k, v, lengths, heads))

        return attend

    def prompt_form(self, batch: int, heads: int, P: int, head_dim: int) -> str:
        """The form :meth:`prompt`'s attention takes for ``batch`` prompts
        of ``P`` positions: what the scheduler logs and counts by."""
        return prompt_form(batch, heads, P, head_dim)

    def absorb(self, p, q):
        """A decode step's queries [S, Tq, .] as the rows are scored
        against them (``p``: the layer's parameters).  Here as they come."""
        return q

    def expand(self, p, out):
        """What a decode step's attention gave, [S, Tq, .], as the layer
        takes it.  Here as it comes."""
        return out


ROWS = Rows()  # a row a position


class Kind(NamedTuple):
    """One kind of K/V layer, of a family whose layers do not all keep their
    rows alike (models/mellum.py: a ring of a window's rows in three layers
    of four, a row a position in the fourth): its ``name`` (what the
    family's layer is told and the scheduler counts by), how many ``layers``
    hold it, and its ``rows``.  A kind has a K and a V leaf of its own
    (:func:`cache_leaves`), its own span and its own work list a step."""
    name: str
    layers: int
    rows: Rows


@dataclass(frozen=True)
class Family:
    """A family's block, and the numbers the programs read of it.

    ``embed(params, tokens, dtype)`` → a row a token; ``positions(params,
    dtype)`` → the learned position table added to them, or None.
    ``layer(layer_params, x, attend, pos, lora=None, lora_idx=None)`` → one
    block over x [B, Tq, D] whose rows stand at the absolute positions
    ``pos`` ([B, Tq] or [Tq] int32: what a rotary family turns its queries
    and keys by; the same in prefill and decode); it calls
    ``attend(q, k, v)`` once with its fresh projections ([B, Tq, width]) and
    gets the attention output back — the single point where the phases
    differ, which the programs fill in.  ``norm(params, x)`` is the final
    norm, ``head(params, x [N, D])`` the float32 logits.  ``pre_tree(p)`` /
    ``dec_tree(p, rows)`` pick the weights of a prefill or of a program of
    ``rows`` decode rows, for a family that holds more than one tree.
    ``rows`` says how a slot's cache rows hold its positions (:class:`Rows`:
    a row a position unless the family brings its own).

    What a slot keeps (:func:`cache_leaves`): K and V rows ``width`` wide
    (or, where ``rows.values`` is set, one leaf of rows ``width`` wide) in
    ``kv_layers`` of the layers (None: in all), and whatever ``state``
    declares after them, ``(layers that hold it, shape a slot, dtype)`` a
    leaf.  ``cache_index(i)`` is where layer ``i`` finds its own part of
    those leaves (the trunk hands it on as data); ``kv_heads`` says that the
    ``heads`` queries share fewer K/V heads (``width`` is then the K/V
    heads' and the query block is wider; None: a K/V head a query head).  A
    family with ``state`` or ``counters`` has its layer called with two more
    keywords, ``state=`` and ``count=`` (:func:`_trunk`); ``counters`` says,
    ``(name, what it counts)`` each, the int32 counts a decode step sums
    (:func:`segment_scan`).  ``expert_plan(rows)`` is what the family's
    routed experts run for ``rows`` rows of a program
    (ops/expert_matmul.plan_summary), for the lane's boot log.

    A family whose K/V layers keep their rows in more than one way declares
    them as ``kinds`` (:class:`Kind` each; none: one kind, ``kv_layers``
    layers of ``rows``).  The pool then holds a K and a V leaf a kind, in
    that order and before the state; ``cache_index(i)`` is ``(kind, index)``
    with ``kind`` an index into ``kinds``; the layer is called with one more
    keyword, ``kind=`` (the kind's name), and traced once a kind; ``rows``
    stays what the scheduler asks about a prompt (``prefill_batch``,
    ``windows``).  Only the slot lane serves such a family.
    """
    embed: Callable
    positions: Callable | None
    layer: Callable
    norm: Callable
    head: Callable
    layers: int
    width: int  # of a cache row: a layer's K (and V) with heads side by side
    heads: int
    eos_id: int
    max_positions: int
    vocab_size: int
    pre_tree: Callable = lambda p: p
    dec_tree: Callable = lambda p, rows: p
    rows: Rows = ROWS
    kv_layers: int | None = None
    kv_heads: int | None = None
    state: tuple = ()
    cache_index: Callable = lambda i: i
    counters: tuple = ()
    expert_plan: Callable | None = None
    kinds: tuple = ()


def cache_leaves(fam: Family, slots: int, T: int, dtype) -> tuple:
    """``(shape, dtype)`` of every leaf of a cache of ``slots`` slots of
    ``T`` rows, the slot axis second: K, V (a pair a kind, each with the
    rows its kind needs for ``T`` positions; one leaf where a row is one:
    :func:`row_leaves`), then the family's state."""
    n = row_leaves(fam)
    if fam.kinds:
        kv = tuple(((k.layers, slots, k.rows.count(T), fam.width), dtype)
                   for k in fam.kinds for _ in range(n))
    else:
        kv = (((fam.kv_layers or fam.layers, slots, T, fam.width), dtype),) * n
    return kv + tuple(((n, slots, *shape), dt) for n, shape, dt in fam.state)


def row_leaves(fam: Family) -> int:
    """Leaves that hold a kind's rows: K and V, or the one leaf of a family
    whose ``rows.values`` says a row is scored and summed out of itself."""
    return 1 if fam.rows.values else 2


# ---------------------------------------------------------------------------
# The cache seam
# ---------------------------------------------------------------------------

class SlotPool(NamedTuple):
    """A row a slot: ``k``, ``v`` [L, S, T, D] (``v`` None: the rows are one
    leaf, ``k``, whose first ``rows.values`` columns are the values).
    ``slots`` is ``arange(S)``,
    made where the pool is (:func:`slot_pool`), outside any scan, and
    ``rows`` how the ``T`` rows hold a slot's positions.  One query a slot:
    nothing feeds it several.  ``layer`` is an index as data wherever a
    method takes one (the trunk hands it on as a traced int32 scalar); so
    it is for the page table below."""
    k: jax.Array
    v: jax.Array
    slots: jax.Array
    rows: Rows

    @property
    def positions(self) -> int:
        return self.rows.positions(self.k.shape[2])

    def span(self, pos):
        """``(first, last)`` rows a query at ``pos`` [S] reads."""
        return self.rows.span(pos, self.k.shape[2])

    def write(self, layer, pos, k, v, p=None):
        """This layer's ``k``, ``v`` [S, 1, D] for position ``pos`` [S]
        (``p``: the layer's parameters, for what the rows keep beside)."""
        row = self.rows.row(pos, self.k.shape[2])
        with part("cache_write"):
            ck = self.k.at[layer, self.slots, row].set(k[:, 0])
            if self.v is None:  # one leaf: the row is all there is
                return self._replace(k=ck)
            cv = self.v.at[layer, self.slots, row].set(v[:, 0])
        ck, cv = self.rows.settle(p, ck, cv, layer, self.slots, pos)
        return self._replace(k=ck, v=cv)

    def attend(self, layer, q, span, heads, work=None):
        """Over this layer where it lies, each slot over its ``span`` of
        rows ``(first, last)`` [S] (``last`` negative: dead, reads
        nothing)."""
        first, last = span
        if self.v is None:
            return decode_attention.attend_latent(
                q, self.k, layer, last[:, None], heads, self.rows.values,
                work, first[:, None])
        return decode_attention.attend(q, self.k, self.v, layer,
                                       last[:, None], heads, work,
                                       first[:, None])


def slot_pool(k, v, rows: Rows = ROWS) -> SlotPool:
    return SlotPool(k, v, jnp.arange(k.shape[1]), rows)


def _leaves(pool) -> tuple:
    """A pool's own leaves: K and V, or the one."""
    return (pool.k,) if pool.v is None else (pool.k, pool.v)


def slot_pools(fam: Family, cache):
    """``(pool, state)`` of the slot lane's ``cache`` (its leaves): the one
    :class:`SlotPool`, or for a family of several kinds a list of them, a
    kind each in ``fam.kinds``' order, and the leaves after the rows'."""
    n = row_leaves(fam)
    slots = jnp.arange(cache[0].shape[1])

    def pool(mine, rows):
        return SlotPool(mine[0], mine[1] if n == 2 else None, slots, rows)

    if not fam.kinds:
        return pool(cache[:n], fam.rows), tuple(cache[n:])
    return ([pool(cache[n * j:n * j + n], k.rows)
             for j, k in enumerate(fam.kinds)],
            tuple(cache[n * len(fam.kinds):]))


def slot_put(slots):
    """The write of a prefill into the pool where it lies: ``put(leaf,
    layer, values, row=0)`` puts ``values[b]`` (rows ``[n, D]`` of K or V,
    or a slot's whole state) at ``leaf[layer, slots[b], row:]`` for each of
    the ``B`` prompts, and nothing else of the leaf is touched.  The slot
    axis is the leaves' second (:func:`cache_leaves`), and this is the one
    place a prefill knows it: a family's ``Rows.prompt`` is handed ``put``
    and never sees a slot.  ``B`` updates in place, in order (two prompts
    given one slot: the later one stays; a batch's padding is given its
    first prompt's slot and writes that prompt's values again)."""
    def put(leaf, layer, values, row=0):
        at = (jnp.int32(row),) + (jnp.int32(0),) * (leaf.ndim - 3)
        with part("cache_write"):
            for b in range(values.shape[0]):
                leaf = jax.lax.dynamic_update_slice(
                    leaf, values[b][None, None].astype(leaf.dtype),
                    (layer, slots[b]) + at)
        return leaf

    return put


def zero_cache(fam: Family, slots: int, total: int, dtype) -> tuple:
    """A pool of ``slots`` slots for ``total`` positions, every leaf zeros:
    what the fixed-batch path prefills into."""
    return tuple(jnp.zeros(shape, dt) for shape, dt in cache_leaves(
        fam, slots, fam.rows.count(total), dtype))


class PagedPool(NamedTuple):
    """Fixed-size pages ``k``, ``v`` [L, NB, BS, D] and a block table a row
    [S, MB] (docs/GENERATION.md): writes route through the table, attention
    runs over the gathered *virtual* cache — value-identical to the slot
    pool at the positions a row has written, masked exact-zero beyond them,
    so the bit-parity story of the slot pool carries over.  Finished and
    empty rows carry an all-trash table row (serving/kvcache.py), so their
    frozen-position writes land in the shared trash page."""
    k: jax.Array
    v: jax.Array
    table: jax.Array
    block_size: int

    @property
    def positions(self) -> int:
        return self.table.shape[1] * self.block_size

    def span(self, pos):
        """A page table holds a row a position: ``[0, pos]``."""
        return jnp.zeros_like(pos), pos

    def write(self, layer, wpos, k, v, p=None):
        """This layer's ``k``, ``v`` [S, Tq, D] at ``wpos`` [S, Tq] (or [S]
        for one query a slot), absolute and clipped to the virtual range."""
        def put(pages, values):
            bidx, off = paged_index(
                self.table, wpos if wpos.ndim == 2 else wpos[:, None],
                self.block_size)
            return pages.at[layer, bidx, off].set(values)

        with part("cache_write"):
            ck = put(self.k, k)
            return self._replace(k=ck, v=put(self.v, v))

    def _virtual(self, pages, layer):
        return gather_kv(pages[layer], self.table)

    def view(self, layer):
        """This layer's virtual cache, K and V [S, MB*BS, D]."""
        return self._virtual(self.k, layer), self._virtual(self.v, layer)

    def attend(self, layer, q, span, heads, work=None):
        """Over this layer's virtual cache, each query as far as the last
        row of its ``span``, [S, Tq] (or [S])."""
        wpos = span[1]
        return decode_attention.attend(
            q, self._virtual(self.k, layer)[None],
            self._virtual(self.v, layer)[None], 0,
            wpos if wpos.ndim == 2 else wpos[:, None], heads, work)


# ---------------------------------------------------------------------------
# The trunk and the segment's scan
# ---------------------------------------------------------------------------

def _embed(fam: Family, params, tokens, pos, dtype, clamp=True):
    """``tokens`` at ``pos`` as rows.  A learned position is clamped to its
    table (defensive: the servable's guard keeps every stream inside it)."""
    with part("embed"):
        x = fam.embed(params, tokens, dtype)
        if fam.positions is None:
            return x
        table = fam.positions(params, dtype)
        return x + table[jnp.minimum(pos, fam.max_positions - 1) if clamp
                         else pos]


def _trunk(fam: Family, params, x, pos, cache, attend, adapter_idx=None,
           lengths=None, put=None):
    """Every layer of the family over ``x`` at the positions ``pos``
    (embedded by the program, which builds its masks after it), then the
    final norm → ``(x, cache, counts)``.
    ``attend(p, cache, i, q, k, v) -> (cache, out)`` stores layer ``i``'s K/V
    however the program caches and returns the attention output; ``p`` is
    the layer's parameters, ``cache`` the tuple of leaves (K, V, then the
    family's state: :func:`cache_leaves`) and ``i`` the layer's index into
    them (``fam.cache_index``).  For a family of several kinds ``attend`` is
    a tuple, a kind each, and each is handed its own kind's K and V alone.
    ``adapter_idx`` [B] routes each row through its tenant's LoRA slot of
    ``params["__adapters__"]`` (docs/ADAPTERS.md; 0 = base passthrough).

    A family that declares state gets it as it gets ``attend``, from the
    program: its layer is called with ``state=``, and ``state(update)``
    runs ``update(mine, lengths) -> (mine, out)`` over the layer's own part
    of every leaf after K and V (the slots' own ``leaf[i]`` in a decode
    step, where ``lengths`` is None; zeros a prompt in a prefill, whatever
    the prompt's slot held, where ``lengths`` [B] say how much of each
    prompt is real) and writes what comes back in its place (a prefill
    through ``put``, :func:`slot_put`, as it writes its rows).  ``count=``
    takes the layer's int32 counts (``fam.counters``); their sum over the
    layers is ``counts`` (None where nothing counted).

    The layer is one jitted function of ``(parameters, x, cache, index,
    lora)`` and every layer calls that same object, so a program traces the
    family's block once and lowers it once, as one private function with a
    ``call`` a layer.  XLA inlines the calls before it assigns layouts, so
    the executable holds the unrolled program's matmuls, kernels and cache
    writes; it simplifies the one body first, with the layer's weights as
    that function's parameters, and may fuse the small operations round
    them otherwise than it did a layer at a time (a reshape straight after
    a matmul it folds into a copy of the weight: models/evabyte.py keeps
    one apart, tests/test_aot_tpu_compile.py watches for it).  One trace
    holds while nothing but those arguments differs between layers: the
    index is data (``jnp.int32``), what the body closes over (``pos``,
    ``attend``'s masks and spans, ``adapter_idx``) is the same object at
    every layer, and every ``layer{i}`` has one tree structure and one
    dtype a leaf.  A layer that differs is traced again (never wrongly
    served), and the ledger's ``layer_traces`` says so: a family of three
    kinds of layer (models/nemotron_h.py) is three traces."""
    stacks = None if adapter_idx is None else params.get("__adapters__")
    hooked = bool(fam.state or fam.counters)
    per_kind = row_leaves(fam)
    n_kv = per_kind * max(len(fam.kinds), 1)  # the leaves before the state
    if put is None:  # a decode step: the slots' own state, set in place
        def held(leaf, i):
            return leaf[i]

        def keep(leaf, i, mine):
            return leaf.at[i].set(mine)
    else:  # a prefill: from zeros, into the prompts' slots
        def held(leaf, i):
            return jnp.zeros(x.shape[:1] + leaf.shape[2:], leaf.dtype)

        keep = put

    @functools.partial(jax.jit, static_argnames="kind")
    def layer(p, x, cache, i, lora, kind=None):
        layer_traced()
        counts = None

        def layer_attend(q, k, v=None):  # ``v`` None: the row is one leaf
            nonlocal cache
            if kind is None:
                cache, out = attend(p, cache, i, q, k, v)
            else:  # this kind's K and V, by this kind's ``attend``
                at, upto = per_kind * kind, per_kind * (kind + 1)
                mine, out = attend[kind](p, cache[at:upto], i, q, k, v)
                cache = cache[:at] + tuple(mine) + cache[upto:]
            return out

        def layer_state(update):
            nonlocal cache
            mine, out = update(tuple(held(leaf, i) for leaf in cache[n_kv:]),
                               lengths)
            cache = cache[:n_kv] + tuple(
                keep(leaf, i, m) for leaf, m in zip(cache[n_kv:], mine))
            return out

        def layer_count(c):
            nonlocal counts
            counts = c

        hooks = {"state": layer_state, "count": layer_count} if hooked else {}
        if kind is not None:
            hooks["kind"] = fam.kinds[kind].name
        x = fam.layer(p, x, layer_attend, pos, lora=lora,
                      lora_idx=adapter_idx, **hooks)
        return x, cache, counts

    counts = None
    for i in range(fam.layers):
        at, kind = fam.cache_index(i), {}
        if fam.kinds:
            at, kind = at[1], {"kind": at[0]}
        x, cache, c = layer(
            params[f"layer{i}"], x, cache, jnp.int32(at),
            None if stacks is None else stacks.get(f"layer{i}"), **kind)
        if c is not None:
            counts = c if counts is None else counts + c
    with part("head"):  # the final norm is the head's
        return fam.norm(params, x), cache, counts


def _write_then_attend(fam, pool, wpos, span, work=None):
    """The decode programs' ``attend`` over ``pool``'s layout: this layer's
    K/V in for position ``wpos``, then each query over its ``span`` of the
    layer's rows.  Where a row is one leaf, the queries are turned to its
    columns before and the result turned back after, by the family's own
    ``rows`` (the seam names no matrix)."""
    n = len(_leaves(pool))

    def attend(p, cache, i, q, k, v):
        here = pool._replace(**dict(zip("kv", cache[:n]))).write(
            i, wpos, k, v, p)
        out = here.attend(i, fam.rows.absorb(p, q), span, fam.heads, work)
        return _leaves(here) + cache[n:], fam.rows.expand(p, out)

    return attend


def _decode_logits(fam, params, pool, cache, tok, wpos, span, work, dtype,
                   adapter_idx=None):
    """One token a slot through the trunk, over ``pool``'s layout holding
    ``cache`` (its leaves) → (logits [S, V], cache, counts)."""
    x = _embed(fam, params, tok, wpos, dtype)[:, None, :]
    if isinstance(pool, list):  # a pool, a span and a work list a kind
        attend = tuple(_write_then_attend(fam, p, wpos, s, w)
                       for p, s, w in zip(pool, span, work))
    else:
        attend = _write_then_attend(fam, pool, wpos, span, work)
    x, cache, counts = _trunk(fam, params, x, wpos[:, None], cache, attend,
                              adapter_idx)
    with part("head"):
        return fam.head(params, x[:, 0]), cache, counts


def segment_scan(step, cache, tok, pos, t, finished, seg: int, eos_id: int,
                 seen=None, counters: int = 0):
    """``seg`` steps of ``step`` over every slot, under the emit and finish
    rules every streaming decoder here shares.

    Per-slot carried state (all [S]): ``tok`` the next token to feed, ``pos``
    its cache write position (= prompt_len + steps_generated), ``t`` the
    sampling-step counter (keeps fold_in(seed, t) aligned with the batched
    path), ``finished`` pins retired/empty slots.  Step t emits the token
    decided *before* it, so a lone request's stream equals the fixed-batch
    output bit-for-bit; a row pins to EOS after its first EOS, and its
    ``pos`` freezes so it only overwrites its own dead cache row.
    ``step(cache, tok, pos, t, finished, seen) -> (cache, nxt, seen)`` is the
    model: it feeds ``tok`` at ``pos`` and decides the next token (drawing
    with ``t + 1``); ``cache`` is whatever pytree it threads, ``seen`` the
    fixed-batch lane's seen-token mask (None elsewhere).  A model that
    counts returns a fourth thing, ``counters`` int32 counts of the step,
    which are summed over the segment.  Returns ``(emits [S, seg], *cache's
    leaves, tok, pos, t, finished)`` and, where ``counters``, those sums
    [counters] last: the scheduler's segment contract.
    """
    def body(carry, _):
        cache, tok, pos, t, finished, seen, tally = carry
        cache, nxt, seen, *counts = step(cache, tok, pos, t, finished, seen)
        with part("sample"):  # what the step decided, packed for the host
            if counters:
                tally = tally + counts[0]
            emit = jnp.where(finished, eos_id, tok)
            fin = finished | (tok == eos_id)
            tok_next = jnp.where(fin, eos_id, nxt)
            pos_next = jnp.where(fin, pos, pos + 1)
        return (cache, tok_next, pos_next, t + 1, fin, seen, tally), emit

    tally = jnp.zeros((counters,), jnp.int32) if counters else None
    carry, emits = jax.lax.scan(
        body, (cache, tok, pos, t, finished, seen, tally), None, length=seg)
    with part("sample"):
        emits = jnp.transpose(emits, (1, 0))
    return (emits, *jax.tree.leaves(carry[0]),
            *carry[1:5], *([carry[6]] if counters else []))


# ---------------------------------------------------------------------------
# The programs
# ---------------------------------------------------------------------------

def prefill(fam: Family, params: dict, tokens: jax.Array, lengths: jax.Array,
            cache: tuple, slots: jax.Array, dtype=jnp.bfloat16,
            adapter_idx=None):
    """Whole-prompt forward into the slot pool, returns last-token logits.

    tokens [B, P] int32 (zero-padded), lengths [B] int32, ``cache`` the
    pool's leaves (:func:`cache_leaves`: K and V [L, S, T, D], ``T`` rows
    a slot as the family's ``rows`` lay positions out, then whatever state
    the family declares) and ``slots`` [B] int32 the slot each prompt was
    given.  Returns (logits [B, V] at position length-1, *the pool's
    leaves): each layer attends its own fresh K/V (``Rows.prompt``), starts
    its state from zeros, and writes what a decode step will read straight
    to ``leaf[layer, slots[b]]`` (:func:`slot_put`).  No cache of the batch
    is made and nothing is copied afterwards.  What a slot held in the rows
    a prefill does not write stays there, and no decode step reads a row it
    or the prefill has not written.
    """
    B, P = tokens.shape
    pos = jnp.arange(P)
    x = _embed(fam, params, tokens, pos, dtype, clamp=False)
    put = slot_put(slots)
    if fam.kinds:
        prompt = tuple(k.rows.prompt(fam.heads, lengths, P, put)
                       for k in fam.kinds)
    else:
        prompt = fam.rows.prompt(fam.heads, lengths, P, put)

    x, cache, _ = _trunk(fam, params, x, pos, tuple(cache), prompt,
                         adapter_idx, lengths, put)
    with part("head"):
        last = jnp.take_along_axis(x, (lengths - 1)[:, None, None],
                                   axis=1)[:, 0]
        return (fam.head(params, last),) + cache


def _penalized(logits, seen, repetition_penalty, on):
    """Runtime-gated like the top-k/top-p sort (ops/sampling.choose): the
    knob is a jit input, so default penalty-1.0 traffic must not pay the
    [B, V] selects — lax.cond runs only the taken branch."""
    return jax.lax.cond(on, lambda args: apply_repetition_penalty(*args),
                        lambda args: args[0],
                        (logits, seen, repetition_penalty))


def prefill_start(fam: Family, params: dict, tokens: jax.Array,
                  lengths: jax.Array, temperature: jax.Array,
                  seeds: jax.Array, cache: tuple, slots: jax.Array,
                  dtype=jnp.bfloat16, top_k=None, top_p=None,
                  repetition_penalty=None, presence=None, adapter_idx=None):
    """Admission program: prefill a batch of requests into the slots they
    were given and pick each one's first token.

    The same prefill as :func:`generate` (so the token chain is
    bit-identical to the fixed-batch path), over the scheduler's pool, which
    it donates as it does to a segment.  Returns (first_tok [B], *the pool's
    leaves), K and V [L, S, T, D] first.
    """
    logits, *cache = prefill(fam, params, tokens, lengths, cache, slots,
                             dtype, adapter_idx=adapter_idx)
    with part("sample"):
        if repetition_penalty is not None:
            logits = _penalized(logits, presence, repetition_penalty,
                                jnp.any(repetition_penalty != 1.0))
        first = choose(logits, temperature, seeds,
                       jnp.zeros(tokens.shape[:1], jnp.int32), top_k, top_p)
    return (first, *cache)


def decode_segment(fam: Family, params: dict, pool, tok: jax.Array,
                   pos: jax.Array, step: jax.Array, finished: jax.Array,
                   temperature: jax.Array, seeds: jax.Array, seg: int,
                   dtype=jnp.bfloat16, top_k=None, top_p=None,
                   repetition_penalty=None, presence=None, adapter_idx=None,
                   state=()):
    """Advance every slot of ``pool`` by ``seg`` tokens — the
    continuous-batching program, over either pool.

    The fixed-batch :func:`generate` runs all ``max_new`` steps in one
    program: nothing surfaces until the scan ends, finished rows burn full
    compute, and nobody can join.  Here the same per-step math runs in short
    segments over a pool: between segments the host streams the emitted
    tokens, retires finished slots, and prefills queued requests into the
    free rows — so shapes stay static (one compiled program, reused forever)
    while membership is dynamic.  Finished and empty slots still compute
    (the price of static shapes), and attention counts them *dead*: it reads
    each layer of the pool where it lies, as far as each live slot has
    written, from one list of live blocks a step.  ``state`` is the pool's
    leaves after K and V (a slot's state needs no span: a finished slot's
    goes on changing, nothing reads it, and the prefill that re-uses the
    slot starts from zeros and overwrites it whole).  Returns (emits
    [S, seg], *the cache's leaves, tok, pos, step, finished) and the
    family's counts, as :func:`segment_scan`.
    """
    pools = pool if isinstance(pool, list) else [pool]
    total = min(p.positions for p in pools)
    # Repetition penalty (fixed-batch lane only, which is the slot pool —
    # the streaming lane would need a [S, V] presence buffer donated across
    # segments; declined there, loudly, in serving/server.py): the presence
    # mask rides the scan carry, gaining each fed token before its logits
    # are penalized, so history = prompt + generated-so-far exactly like
    # HF's processor.  The in-carry scatter touches S elements of a donated
    # buffer — noise.
    if repetition_penalty is not None:
        rep_on = jnp.any(repetition_penalty != 1.0)

    def one(cache, tok, pos, t, finished, seen):
        wpos = jnp.minimum(pos, total - 1)
        # A finished slot's token is pinned to EOS whatever it attends to:
        # it is dead to attention, which reads nothing of its row.
        spans, works = [], []
        for each in pools:  # a span and a list of live blocks a kind
            with part("attend"):  # the step's share of it, once for all layers
                first, last = each.span(wpos)
                last = jnp.where(finished, -1, last)
                spans.append((first, last))
                works.append(decode_attention.step_work(
                    last, each.k.shape[2], fam.width, each.k.dtype, first))
        if not isinstance(pool, list):
            spans, works = spans[0], works[0]
        logits, cache, counts = _decode_logits(
            fam, params, pool, cache, tok, wpos, spans, works, dtype,
            adapter_idx)
        with part("sample"):
            if seen is not None:
                seen = seen.at[pools[0].slots, tok].set(True)
                logits = _penalized(logits, seen, repetition_penalty, rep_on)
            nxt = choose(logits, temperature, seeds, t + 1, top_k, top_p)
        return (cache, nxt, seen, *([counts] if fam.counters else []))

    return segment_scan(one, (*(leaf for each in pools
                                for leaf in _leaves(each)), *state),
                        tok, pos, step,
                        finished, seg, fam.eos_id, presence,
                        len(fam.counters))


def generate(fam: Family, params: dict, tokens: jax.Array,
             lengths: jax.Array, temperature: jax.Array, seeds: jax.Array,
             max_new: int, dtype=jnp.bfloat16,
             top_k: jax.Array | None = None,
             top_p: jax.Array | None = None,
             repetition_penalty: jax.Array | None = None,
             adapter_idx: jax.Array | None = None) -> jax.Array:
    """Prefill + scan generation (greedy or sampled per row).  Returns
    [B, max_new] int32, EOS-padded after the first EOS.

    One :func:`prefill_start` into a pool of zeros made here, a slot a row
    of the batch, + a single ``max_new``-length :func:`decode_segment` — the
    fixed-batch path IS the continuous-batching program at seg=max_new, so
    batched and streaming serving share one per-step decoder body and
    cannot drift apart.  ``params`` is the tree as served: the family picks
    what each half runs with.
    """
    B, P = tokens.shape
    presence = None
    if repetition_penalty is not None:
        # Seen-token mask from the prompt (HF semantics: the penalty's
        # history is prompt + generated-so-far); pad positions excluded.
        valid = jnp.arange(P)[None, :] < lengths[:, None]
        presence = jnp.zeros((B, fam.vocab_size), bool).at[
            jnp.arange(B)[:, None], tokens].max(valid)
    first, *cache = prefill_start(
        fam, fam.pre_tree(params), tokens, lengths, temperature, seeds,
        zero_cache(fam, B, P + max_new, dtype), jnp.arange(B), dtype,
        top_k=top_k, top_p=top_p, repetition_penalty=repetition_penalty,
        presence=presence, adapter_idx=adapter_idx)
    step, finished = jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool)
    pool, state = slot_pools(fam, cache)
    emits, *_ = decode_segment(
        fam, fam.dec_tree(params, B), pool, first, lengths, step, finished,
        temperature, seeds, max_new, dtype, top_k=top_k, top_p=top_p,
        repetition_penalty=repetition_penalty, presence=presence,
        adapter_idx=adapter_idx, state=state)
    return emits


def prefill_chunk(fam: Family, params: dict, tokens: jax.Array,
                  start: jax.Array, lengths: jax.Array, pool,
                  temperature: jax.Array, seeds: jax.Array, top_k: jax.Array,
                  top_p: jax.Array, dtype=jnp.bfloat16, adapter_idx=None):
    """One bounded-cost prefill chunk over the paged pool.

    ``tokens`` [G, C] is the chunk's token slice (zero-padded in the final
    chunk), ``start`` [G] its absolute offset, ``lengths`` [G] the FULL
    prompt length.  Queries at absolute positions ``start+i`` attend every
    key ``j <= start+i`` with ``j < length`` — previous chunks' keys come
    back out of the pool, so chaining chunks reproduces the monolithic
    :func:`prefill` attention pattern exactly (tests/test_generation_v2.py
    pins the logits).  The prefix KV cache (serving/prefixcache.py,
    docs/PREFIX.md) rides this same contract for free: a warm admission's
    first chunk simply starts at the cached offset, and positions below it
    resolve through the table to FROZEN shared pages — bit-identical to the
    keys a cold prefill would have written, so reuse needs no program of
    its own.  Returns ``(first_tok [G], cache_k, cache_v)``; ``first_tok``
    is only meaningful for rows whose final chunk this is (the last-position
    gather clips into the chunk), which is how one compiled program serves
    every chunk index.
    """
    G, C = tokens.shape
    VT = pool.positions
    pos = start[:, None] + jnp.arange(C)[None, :]                   # [G, C]
    wpos = jnp.minimum(pos, VT - 1)
    x = _embed(fam, params, tokens, pos, dtype)
    kpos = jnp.arange(VT)
    keep = ((kpos[None, None, :] <= pos[:, :, None])
            & (kpos[None, None, :] < lengths[:, None, None]))
    mask_bias = jnp.where(keep, 0.0, -1e9).astype(jnp.float32)[:, None]

    def attend(p, cache, i, q, k, v):
        here = pool._replace(k=cache[0], v=cache[1]).write(i, wpos, k, v)
        return (here.k, here.v), _attn(q, *here.view(i), mask_bias, fam.heads)

    x, cache, _ = _trunk(fam, params, x, pos, (pool.k, pool.v), attend,
                         adapter_idx)
    with part("head"):
        idx = jnp.clip(lengths - 1 - start, 0, C - 1)
        last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
        logits = fam.head(params, last)
    with part("sample"):
        first = choose(logits, temperature, seeds,
                       jnp.zeros((G,), jnp.int32), top_k, top_p)
    return (first,) + cache


def propose(fam: Family, params: dict, pool, prev: jax.Array, tok: jax.Array,
            pos: jax.Array, step: jax.Array, finished: jax.Array,
            temperature: jax.Array, seeds: jax.Array, k: int,
            dtype=jnp.bfloat16, top_k=None, top_p=None):
    """Draft half of a speculative tick: ``k`` cheap decode steps proposing
    the next ``k`` tokens per row, feeding each proposal back in.

    Runs against the DRAFT rung's params and its own paged cache (same block
    tables as the target — same positions).  The scan runs ``k + 1`` steps:
    step 0 **backfills** ``prev`` (the chain token at ``pos - 1``) — after a
    fully-accepted tick the draft never fed its last proposal, leaving a KV
    hole at ``pos - 1`` that quietly degrades the next tick's acceptance;
    re-feeding ``prev`` recomputes that position's KV (bit-identical when no
    hole exists, so the backfill is idempotent).  Step 0's output is
    discarded and step 1 force-feeds the already-decided ``tok``.  Returns
    ``(proposals [S, k], draft_logits fp32 [S, k, V], cache_k, cache_v)``;
    the raw logits stay on device for the verifier's rejection sampling
    (ops/sampling.speculative_verify).  Sampled rows draw with a salted
    seed chain (DRAFT_SEED_SALT) so proposals are independent of the plain
    lane's and the verifier's draws.
    """
    S = tok.shape[0]
    VT = pool.positions
    draft_seeds = jnp.bitwise_xor(seeds, jnp.int32(DRAFT_SEED_SALT))

    def sstep(carry, _):
        cache_k, cache_v, cur, pos, t, first = carry
        wpos = jnp.minimum(pos, VT - 1)
        logits, (cache_k, cache_v), _ = _decode_logits(
            fam, params, pool, (cache_k, cache_v), cur, wpos,
            pool.span(wpos), None, dtype)
        with part("sample"):
            nxt = choose(logits, temperature, draft_seeds, t + 1, top_k,
                         top_p)
        # Backfill step feeds the pending token next; proposal steps feed
        # the model's own choice.
        prop = jnp.where(finished, fam.eos_id, jnp.where(first, tok, nxt))
        pos_next = jnp.where(finished, pos, pos + 1)
        return ((cache_k, cache_v, prop, pos_next, jnp.where(first, t, t + 1),
                 jnp.zeros_like(first)), (prop, logits))

    init = (pool.k, pool.v, prev, jnp.maximum(pos - 1, 0), step,
            jnp.ones((S,), bool))
    carry, (props, logits) = jax.lax.scan(sstep, init, None, length=k + 1)
    # Drop the backfill step's output: props[0] is the forced pending tok,
    # logits[0] the distribution it was (already) decided from.
    return (jnp.transpose(props[1:], (1, 0)),
            jnp.transpose(logits[1:], (1, 0, 2)), carry[0], carry[1])


def verify(fam: Family, params: dict, pool, toks: jax.Array, pos: jax.Array,
           dtype=jnp.bfloat16):
    """Target half of a speculative tick: ONE batched forward over the
    pending token + K proposals per row.

    ``toks`` [S, K+1] feeds at absolute positions ``pos..pos+K``: K/V for
    every fed token are scattered into the pool first, then each query
    attends it under ``kpos <= qpos`` — the same
    write-then-read-own-position pattern as the decode step, so the target
    logits at query ``i`` are exactly what ``K+1`` sequential decode steps
    would have produced (the greedy ON==OFF parity contract).  Positions
    past the acceptance point hold rejected-token K/V; the next tick's
    writes overwrite them before any mask admits a read.  Returns
    ``(logits fp32 [S, K+1, V], cache_k, cache_v)``.
    """
    S, K1 = toks.shape
    p = pos[:, None] + jnp.arange(K1)[None, :]
    wp = jnp.minimum(p, pool.positions - 1)
    x = _embed(fam, params, toks, wp, dtype)
    x, cache, _ = _trunk(fam, params, x, wp, (pool.k, pool.v),
                         _write_then_attend(fam, pool, wp, pool.span(wp)))
    with part("head"):
        logits = fam.head(params, x.reshape(S * K1, -1)).reshape(S, K1, -1)
    return (logits,) + cache


# ---------------------------------------------------------------------------
# The servable
# ---------------------------------------------------------------------------

def _fallback_tokenize(text: str, vocab_size: int) -> list[int]:
    """Offline stub (same role as BERT's): whitespace words hashed into the
    vocab; real deployments point extra.tokenizer at a tokenizer.json."""
    return [int.from_bytes(hashlib.sha256(w.encode()).digest()[:4], "big")
            % max(vocab_size - 1, 1) for w in text.split()]


# The sampling knobs every admission carries: name, dtype on the device, and
# "off" (whose Python type is the type of a request's value).
KNOBS = (("temperature", np.float32, 0.0), ("seed", np.int32, 0),
         ("top_k", np.int32, 0), ("top_p", np.float32, 1.0))


def knob_spec(b: int) -> dict:
    return {k: jax.ShapeDtypeStruct((b,), dt) for k, dt, _ in KNOBS}


def knob_batch(sample) -> dict:
    """A sample's knobs as batch-1 arrays (one it lacks is off)."""
    return {k: np.asarray([sample.get(k, off)], dt) for k, dt, off in KNOBS}


def make_servable(name: str, cfg_model, fam: Family, params: dict, *,
                  adapter_dims: dict | None = None, tp_rules=None):
    """The servable of a token-in, token-out family: ``params`` is its host
    tree as it will be served (quantized, routed: the family's business),
    ``adapter_dims`` the ``(in, out)`` of every dense a LoRA may target."""
    from ..engine.servable import Servable
    from .vision_common import resolve_dtype

    dtype = resolve_dtype(cfg_model.dtype)
    max_new = int(cfg_model.extra.get("max_new_tokens", 32))
    max_seq = max(cfg_model.seq_buckets)
    if max_seq + max_new > fam.max_positions:
        # Build-time guard: without it, decode positions past a learned
        # position table would silently clamp to its last row (_embed's
        # jnp.minimum is defensive, not a semantics).
        raise ValueError(
            f"{name}: max(seq_buckets) + max_new_tokens = {max_seq} + "
            f"{max_new} exceeds the model's max_positions "
            f"({fam.max_positions}); shrink seq_buckets or max_new_tokens")

    paged_ok = type(fam.rows) is Rows and not fam.state and not fam.kinds
    if not paged_ok and getattr(cfg_model, "kv_cache", "slot") == "paged":
        # A page table holds a row a position and the chunked prefill reads
        # it back as one: a family whose rows are laid out otherwise (or in
        # more than one way), or that keeps state which is no row, has no
        # paged lane, and says so here rather than serve something else.
        raise ValueError(
            f"{name}: kv_cache='paged' cannot serve this family: its cache "
            f"is not a K and a V row a position "
            f"({type(fam.rows).__name__}, {row_leaves(fam)} leaves a row, "
            f"{len(fam.kinds) or 1} kinds of K/V layer, "
            f"{len(fam.state)} leaves of state); use kv_cache='slot'")

    adapters_on = int(getattr(cfg_model, "adapter_slots", 0)) > 0
    if adapters_on:
        # Multi-tenant LoRA slot pool (docs/ADAPTERS.md): fixed-shape zero
        # stacks baked into the param tree — attach/detach replace leaves
        # (same shapes, zero recompiles), slot 0 is the reserved base
        # passthrough, and every request row gathers its own slot
        # (ops/lora.py).  serving/adapters.AdapterManager owns the slots.
        from ..ops.lora import zero_stacks

        adapter_dims = adapter_dims or {}  # a family that names none has none
        targets = tuple(cfg_model.adapter_targets) or ("q", "v")
        unknown = [t for t in targets if t not in adapter_dims]
        if unknown:
            raise ValueError(f"{name}: unknown adapter_targets {unknown}; "
                             f"supported: {sorted(adapter_dims)}")
        dims = {t: adapter_dims[t] for t in targets}
        slots = int(cfg_model.adapter_slots) + 1  # + reserved slot 0
        rank = max(int(cfg_model.adapter_rank), 1)
        params["__adapters__"] = {
            f"layer{i}": zero_stacks(slots, rank, dims)
            for i in range(fam.layers)}
    params = jax.device_put(params)  # ONE batched tree transfer: per-leaf
    # jnp.asarray serializes a host round-trip per buffer.

    tokenizer = None
    tok_path = cfg_model.extra.get("tokenizer")
    if tok_path:
        from tokenizers import Tokenizer

        tokenizer = Tokenizer.from_file(str(tok_path))

    default_temperature = float(cfg_model.extra.get("temperature", 0.0))

    # Over-length policy (extra.overlength): generation defaults to "error"
    # (a clean 400 — silently dropping context changes what gets generated);
    # "truncate" keeps the TAIL (ids[-max_seq:], the HF left-truncation
    # convention for causal LM: the continuation conditions on the most
    # recent context, not the oldest).
    overlength = str(cfg_model.extra.get("overlength", "error"))
    if overlength not in ("truncate", "error"):
        raise ValueError(f"{name}: extra.overlength must be 'truncate' or "
                         f"'error', got {overlength!r}")

    def _fit(ids: list[int]) -> list[int]:
        if len(ids) > max_seq:
            if overlength == "error":
                raise ValueError(
                    f"prompt is {len(ids)} tokens but the longest configured "
                    f"seq bucket is {max_seq}; send a shorter prompt or set "
                    f"extra.overlength='truncate' to keep the last {max_seq}")
            ids = ids[-max_seq:]
        return ids

    def apply_fn(p, inputs):
        # The batch is static per bucket: each compiled program bakes in
        # its regime's weight tree (no runtime branch).
        return {"tokens": generate(
            fam, p, inputs["input_ids"], inputs["length"],
            inputs["temperature"], inputs["seed"], max_new, dtype,
            top_k=inputs["top_k"], top_p=inputs["top_p"],
            repetition_penalty=inputs["repetition_penalty"],
            adapter_idx=inputs.get("adapter_idx"))}

    def input_spec(bucket):
        b, s = bucket
        spec = {"input_ids": jax.ShapeDtypeStruct((b, s), jnp.int32),
                "length": jax.ShapeDtypeStruct((b,), jnp.int32),
                **knob_spec(b),
                "repetition_penalty": jax.ShapeDtypeStruct((b,),
                                                           jnp.float32)}
        if adapters_on:
            # Per-row adapter slot index (docs/ADAPTERS.md): pad rows
            # collate to 0 — the reserved base-passthrough slot.
            spec["adapter_idx"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        return spec

    def preprocess(payload):
        given = payload if isinstance(payload, dict) else {}
        if "input_ids" in given:
            ids = [int(i) for i in given["input_ids"]]
        else:
            text = payload["text"] if isinstance(payload, dict) else str(
                payload.decode() if isinstance(payload, bytes) else payload)
            ids = (tokenizer.encode(text).ids if tokenizer is not None
                   else _fallback_tokenize(text, fam.vocab_size))
        arr = np.asarray(_fit(ids or [fam.eos_id]), np.int32)
        # Knobs are off unless the request sets them.
        sample = {"input_ids": arr, "length": np.int32(arr.shape[0]),
                  **{k: dt(given.get(k, default_temperature
                                     if k == "temperature" else off))
                     for k, dt, off in KNOBS},
                  "repetition_penalty": np.float32(
                      given.get("repetition_penalty", 1.0))}
        if adapters_on:
            # Slot 0 = base passthrough; the server overwrites this with
            # the resolved tenant's slot after the attach gate.
            sample["adapter_idx"] = np.int32(0)
        return sample

    def postprocess(out, i):
        toks = [int(t) for t in out["tokens"][i]]
        if fam.eos_id in toks:
            toks = toks[: toks.index(fam.eos_id)]
        result = {"tokens": toks}
        if tokenizer is not None:
            result["text"] = tokenizer.decode(toks)
        return result

    def collate_lengths(samples, bucket, spec):
        from ..engine.compiled import default_collate

        batch = default_collate(samples, bucket, spec)
        # Padded rows must have length>=1: position len-1 gathers row 0's
        # garbage otherwise fine, but keep the index in range.
        batch["length"] = np.maximum(batch["length"], 1)
        return batch

    # Continuous-batching contract (serving/generation.py): slot-pool decode
    # in `segment_tokens`-step jitted segments with admission by a prefill
    # that writes into the pool.  gen_slots bounds concurrent generations;
    # the cache pool is a tuple of leaves, K and V [L, slots, T, D] first.
    # Admission is model-shaped (whisper admits AUDIO), so the scheduler
    # drives it through the generic trio: ``admit_len_of`` (sample ->
    # bucket-size request),
    # ``collate_admit`` (sample + bucket -> batch-1 payload dict; must carry
    # "length" [1] and may carry "temperature"/"seed" [1] for the slot
    # state), ``admit_spec`` (bucket -> payload ShapeDtypeStructs, used by
    # multi-host followers to join the broadcast), and ``prefill`` takes the
    # pool's leaves, the slots [B] and the payload dict.
    gen_slots = int(cfg_model.extra.get("gen_slots", 4))
    segment_tokens = int(cfg_model.extra.get("segment_tokens", 8))
    total = max_seq + max_new
    T = fam.rows.count(total)  # rows a slot holds for ``total`` positions
    head_dim = fam.width // (fam.kv_heads or fam.heads)

    def kind_meta(k: Kind) -> dict:
        rows = k.rows.count(total)
        return {"name": k.name, "layers": k.layers, "rows": k.rows,
                "count": rows,
                "read_block": decode_attention.read_block(rows, fam.width,
                                                          dtype)}

    def collate_admit(sample, bucket):
        ids = np.asarray(sample["input_ids"], np.int32)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : ids.shape[0]] = ids
        return {"input_ids": toks,
                "length": np.asarray([max(ids.shape[0], 1)], np.int32),
                **knob_batch(sample)}

    def admit_spec(bucket):
        return {"input_ids": jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                "length": jax.ShapeDtypeStruct((1,), jnp.int32),
                **knob_spec(1)}

    def _segment(p, cache, tok, pos, st, fin, temp, seeds, topk, topp):
        pool, state = slot_pools(fam, cache)
        return decode_segment(fam, fam.dec_tree(p, gen_slots), pool, tok, pos,
                              st, fin, temp, seeds, segment_tokens, dtype,
                              top_k=topk, top_p=topp, state=state)

    continuous = {
        "slots": gen_slots,
        "segment_tokens": segment_tokens,
        "total": total,
        "eos_id": fam.eos_id,
        "max_new": max_new,
        "prompt_buckets": tuple(sorted(int(s) for s in cfg_model.seq_buckets)),
        "admit_len_of": lambda s: int(np.asarray(s["input_ids"]).shape[0]),
        "collate_admit": collate_admit,
        "admit_spec": admit_spec,
        # The pool's leaves, ``(shape, dtype)`` each, the slot axis second:
        # K, V, then the family's state.
        "cache_leaves": cache_leaves(fam, gen_slots, T, dtype),
        "counters": dict(fam.counters),  # name -> what it counts
        "expert_plan": fam.expert_plan,  # rows of a program -> its plan
        "cache_dtype": dtype,  # of the paged lane's pages
        # Rows decode attention reads a live slot's row in, by the pool's
        # width (grouped queries share it).
        "read_block": decode_attention.read_block(T, fam.width, dtype),
        # What the scheduler counts with, in numpy (spans, summaries, the
        # passes of a prompt's attention, prompts a prefill dispatch).
        "rows": fam.rows,
        # A family of several kinds of K/V layer: each kind's name, layers,
        # ``Rows``, rows a slot and read block, in the order of the pool's
        # K/V pairs (none: the one kind the four entries above describe).
        "kinds": tuple(kind_meta(k) for k in fam.kinds),
        # The form the prompt attention of a (batch, bucket) prefill takes
        # (of several kinds: each kind's, in their order).
        "prompt_form": lambda batch, bucket: "+".join(
            rows.prompt_form(batch, fam.heads, bucket, head_dim)
            for rows in ([k.rows for k in fam.kinds] or [fam.rows])),
        # Routed lane: admission prefills run on the prefill tree, the
        # slot-pool segment routes on the POOL size (the decode-row count of
        # its program) — consistent with the fixed-batch path at the same
        # row count, so the bit-identical fixed<->continuous parity property
        # survives routing.
        "prefill": (lambda p, cache, slots, payload:
                    prefill_start(fam, fam.pre_tree(p), payload["input_ids"],
                                  payload["length"], payload["temperature"],
                                  payload["seed"], cache, slots, dtype,
                                  top_k=payload["top_k"],
                                  top_p=payload["top_p"])),
        "segment": _segment,
        "detokenize": ((lambda toks: tokenizer.decode(toks))
                       if tokenizer is not None else None),
    }

    # Block-paged contract (serving/generation.PagedGenerationScheduler;
    # docs/GENERATION.md): pure fns parameterized by the pool layout, jitted
    # + donated by the scheduler's factory.  Weight-tree routing mirrors the
    # slot pool's: chunked prefill runs on the prefill tree, decode/propose/
    # verify route on the pool size — verify uses the SAME tree as the plain
    # segment so speculation-ON greedy output is byte-identical to
    # speculation-OFF.
    def _make_paged(block_size: int, spec_k: int):
        bs, K = int(block_size), int(spec_k)

        def dec(p):
            return fam.dec_tree(p, gen_slots)

        return {
            # prefill_chunk/segment take a trailing per-row adapter slot
            # index (docs/ADAPTERS.md): the paged scheduler carries it per
            # stream, so tenants co-decode in one program.  The draft rung
            # never sees adapters — the scheduler falls back to plain
            # decode while any adapter stream is active.
            "prefill_chunk": (
                lambda p, toks, start, length, ck, cv, table, temp, seed,
                topk, topp, aidx:
                prefill_chunk(fam, fam.pre_tree(p), toks, start, length,
                              PagedPool(ck, cv, table, bs), temp, seed, topk,
                              topp, dtype,
                              adapter_idx=aidx if adapters_on else None)),
            "segment": (
                lambda p, ck, cv, table, tok, pos, st, fin, temp, seeds,
                topk, topp, aidx:
                decode_segment(fam, dec(p), PagedPool(ck, cv, table, bs), tok,
                               pos, st, fin, temp, seeds, segment_tokens,
                               dtype, top_k=topk, top_p=topp,
                               adapter_idx=aidx if adapters_on else None)),
            "propose": (
                lambda p, ck, cv, table, prev, tok, pos, st, fin, temp,
                seeds, topk, topp:
                propose(fam, dec(p), PagedPool(ck, cv, table, bs), prev, tok,
                        pos, st, fin, temp, seeds, K, dtype, top_k=topk,
                        top_p=topp)),
            "verify": (
                lambda p, ck, cv, table, toks, pos, fin:
                verify(fam, dec(p), PagedPool(ck, cv, table, bs), toks, pos,
                       dtype)),
        }

    continuous["paged"] = None if not paged_ok else {
        "make": _make_paged,
        "cache_shape": (lambda num_blocks, block_size:
                        (fam.layers, num_blocks, block_size, fam.width)),
        # Host-side admission adapters: the scheduler is model-agnostic and
        # builds its own chunk payloads from raw prompt ids + knobs.
        "prompt_ids": (lambda s:
                       np.asarray(s["input_ids"], np.int32).reshape(-1)),
        "knobs": (lambda s: tuple(type(off)(s.get(k, off))
                                  for k, _, off in KNOBS)),
        # Per-stream adapter slot (docs/ADAPTERS.md): 0 = base passthrough;
        # eviction continuations ({**s, ...} in extend_sample) preserve it.
        "adapter_idx": (lambda s: int(np.asarray(
            s.get("adapter_idx", 0)))),
        # Eviction continuation (docs/GENERATION.md "Exhaustion policy"):
        # prompt + tokens-emitted-so-far becomes the re-admission prompt.
        "extend_sample": (lambda s, toks: {
            **s, "input_ids": np.concatenate(
                [np.asarray(s["input_ids"], np.int32).reshape(-1),
                 np.asarray(toks, np.int32)]),
            "length": np.int32(
                np.asarray(s["input_ids"]).reshape(-1).shape[0] + len(toks))}),
    }

    meta = {"seq_len_of": lambda s: int(s["input_ids"].shape[0]),
            "max_new_tokens": max_new, "collate": collate_lengths,
            "continuous": continuous,
            "tp_rules": tp_rules}
    if adapters_on:
        # Pool layout the AdapterManager builds host stacks against
        # (serving/adapters.py): slot count INCLUDES the reserved slot 0.
        meta["adapters"] = {"slots": slots, "rank": rank,
                            "targets": tuple(cfg_model.adapter_targets),
                            "dims": dims, "layers": fam.layers}
    return Servable(
        name=name, apply_fn=apply_fn, params=params, input_spec=input_spec,
        preprocess=preprocess, postprocess=postprocess,
        bucket_axes=("batch", "seq"), meta=meta)
