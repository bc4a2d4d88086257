"""models/lfm2.py through models/decoder.py's seam, at tiny widths.

The family (gated short convolutions beside grouped-query attention, a dense
feed-forward in the two leading layers and 8 gated experts behind a sigmoid
router in the rest) against the plain reference (benchmark/reference/lfm2.py):
logits of the prefill and of decode steps through the pool and the
convolution's state; a decode step's convolution against the prompt pass; the
decode kernel with grouped queries against the ``jax.numpy`` form; the
prompt's flash form against the form that writes its scores; the gated grouped
matmul in both its forms; the share against the uncut layer; the trunk's
trace count; the paged lane's refusal.
"""

import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fresh_pool

from benchmark.reference import lfm2 as reference
from pytorch_zappa_serverless_tpu.config import ModelConfig
from pytorch_zappa_serverless_tpu.engine.cache import CompileClock
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import lfm2 as M
from pytorch_zappa_serverless_tpu.models.nemotron_h import GroupedRows
from pytorch_zappa_serverless_tpu.ops import decode_attention as DA
from pytorch_zappa_serverless_tpu.ops import expert_matmul as E
from pytorch_zappa_serverless_tpu.serving.generation import build_gen_kernels

# Two dense layers, then one period of the pattern.
ARCH = {"vocab_size": 96, "hidden_size": 64,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv"],
        "dense_layers": 2, "dense_width": 96, "heads": 8, "kv_heads": 2,
        "head_dim": 8, "experts_published": 8, "experts_held": 8, "top_k": 2,
        "expert_width": 48, "rope_theta": 100.0, "max_positions": 512,
        "init_std": 0.1, "eos_id": 96}
CFG = M.config_from_arch(ARCH)
KEYS = {k: getattr(CFG, k) for k in (
    "dense_layers", "conv_kernel", "heads", "kv_heads", "head_dim", "top_k",
    "routed_scale", "expert_offset", "rope_theta", "norm_eps")}
KEYS["layer_types"] = list(CFG.layer_types)
EXTRA = {"max_new_tokens": 16, "gen_slots": 3, "segment_tokens": 4,
         "arch": ARCH}
# float32 at ``highest`` against float32 at ``highest``: two orders of
# summation, and the router's 1e-6 the program leaves out (5e-7 of a
# weight).  Logits here spread over about 1.2; a bfloat16 product or
# bfloat16 scores move them by 1e-2, int8 weights by more (the last test
# of this section).
TOL = 2e-4


@pytest.fixture(scope="module")
def tree():
    tree = M.init_lfm2_params(0, CFG)
    # A bias that moves the choice, as a staged tree's does.
    for i in range(CFG.dense_layers, len(CFG.layer_types)):
        tree[f"layer{i}"]["expert_bias"] = np.random.default_rng(
            [7, i]).normal(0, 0.05, CFG.experts_published).astype(np.float32)
    return tree


@pytest.fixture(scope="module")
def servable(tree):
    from pytorch_zappa_serverless_tpu.models.vision_common import (
        resolve_dtype)

    mc = ModelConfig(name="lfm2", dtype="float32", batch_buckets=(1,),
                     seq_buckets=(8, 16), extra=EXTRA)
    return D.make_servable("lfm2", mc, M.family(CFG, resolve_dtype("float32")),
                           jax.tree.map(np.asarray, tree))


def _reference(tree, ids, control=None):
    return reference.forward(tree, ids, KEYS, control)


# -- (a) the programs against the reference's full forward pass ----------------

# Prompts of one prefill batch (bucket 16), the slot each goes to, and the
# request that had the slot before it (None: a fresh pool).
PROGRAM_CASES = {
    "ragged prompts in one batch": ([16, 5, 11], [0, 1, 2], None),
    "a prompt shorter than the convolution": ([1, 16], [2, 0], None),
    "a slot re-used after another request": ([9, 14], [1, 0], [13, 6]),
}


def _admit(kernels, params, cache, prompts, slots):
    """One batched prefill (padded to a power of two with copies of its
    first prompt, which are given that prompt's slot, as the scheduler pads)
    into ``slots`` of the pool → ``(cache, first tokens)``."""
    prompts = list(prompts) + [prompts[0]] * (
        (1 << (len(prompts) - 1).bit_length()) - len(prompts))
    slots = list(slots) + [slots[0]] * (len(prompts) - len(slots))
    B = len(prompts)
    toks = np.zeros((B, 16), np.int32)
    for j, ids in enumerate(prompts):
        toks[j, :len(ids)] = ids
    payload = {"input_ids": toks,
               "length": np.asarray([len(p) for p in prompts], np.int32),
               "temperature": np.zeros(B, np.float32),
               "seed": np.zeros(B, np.int32), "top_k": np.zeros(B, np.int32),
               "top_p": np.ones(B, np.float32)}
    first, *cache = kernels["prefill"](params, tuple(cache),
                                       np.asarray(slots, np.int32), payload)
    cache = tuple(cache)
    return cache, np.asarray(first)


@pytest.mark.parametrize("case", list(PROGRAM_CASES))
def test_prefill_and_segment_give_the_reference_s_logits(
        case, tree, servable):
    """``prefill_start`` into the pool and ``decode_segment`` as the
    scheduler jits them; then, because a segment returns tokens, the same
    step (``_decode_logits``) over the same pool for the logits of every
    position a segment decoded."""
    lengths, slots, earlier = PROGRAM_CASES[case]
    meta = servable.meta["continuous"]
    kernels = build_gen_kernels(types.SimpleNamespace(servable=servable))
    params = servable.params
    fam = M.family(CFG, jnp.float32)
    rng = np.random.default_rng(5)
    S, seg = meta["slots"], meta["segment_tokens"]
    zf, zi = np.zeros(S, np.float32), np.zeros(S, np.int32)

    def segment(cache, tok, pos, fin):
        packed, *cache = kernels["segment"](params, cache, tok, pos, zi, fin,
                                            zf, zi, zi, zf + 1)
        packed = np.asarray(packed)
        assert packed.shape == (S, seg + 4 + 3)  # emits, carries, counters
        return tuple(cache), packed

    with jax.default_matmul_precision("highest"):
        cache = kernels["alloc_cache"]()
        if earlier:
            before = [[int(t) for t in rng.integers(0, 96, n)]
                      for n in earlier]
            cache, first = _admit(kernels, params, cache, before, slots)
            tok, pos, fin = zi.copy(), zi.copy(), np.ones(S, bool)
            tok[slots], pos[slots], fin[slots] = first[:2], earlier, False
            cache, _ = segment(cache, tok, pos, fin)
        prompts = [[int(t) for t in rng.integers(0, 96, n)] for n in lengths]
        cache, first = _admit(kernels, params, cache, prompts, slots)
        tok, pos, fin = zi.copy(), zi.copy(), np.ones(S, bool)
        tok[slots], pos[slots] = first[:len(slots)], lengths
        fin[slots] = False
        kept = cache  # the segment below donates its own copy
        cache, packed = segment(tuple(jnp.array(leaf) for leaf in cache),
                                tok, pos, fin)
        emits = packed[:, :seg]
        # The same steps once more for their logits, a token at a time.
        pool = D.slot_pool(*kept[:2], fam.rows)
        step_cache, logits = kept, []
        for t in range(seg):
            wpos = jnp.asarray(pos + t)
            fed = jnp.asarray(emits[:, t])
            lg, step_cache, _ = D._decode_logits(
                fam, params, pool, step_cache, fed, wpos, pool.span(wpos),
                None, jnp.float32)
            logits.append(np.asarray(lg))
    for j, (ids, slot) in enumerate(zip(prompts, slots)):
        served = emits[slot].tolist()
        assert served[0] == first[j]
        ref = _reference(tree, ids + served)
        assert first[j] == ref[len(ids) - 1].argmax()
        for t in range(seg):
            assert np.abs(logits[t][slot] - ref[len(ids) + t]).max() < TOL
        # The segment's own choices are the reference's greedy tokens.
        assert served[1:] == ref[len(ids):len(ids) + seg - 1].argmax(
            -1).tolist()
    for leaf, (shape, _) in zip(cache, meta["cache_leaves"]):
        assert leaf.shape == shape


def test_prefill_logits_are_the_reference_s_and_a_lower_precision_is_not(
        tree):
    """The tolerance holds the sound path and fails the nearest precisions
    below it: the reference through int8, and the program in bfloat16 (whose
    products and scores are bfloat16)."""
    fam = M.family(CFG, jnp.float32)
    params = jax.tree.map(jnp.asarray, tree)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 96, (3, 24)).astype(np.int32)
    lens = np.asarray([24, 7, 17], np.int32)
    with jax.default_matmul_precision("highest"):
        logits, *cache = fresh_pool.prefill(fam, params, jnp.asarray(toks),
                                            jnp.asarray(lens), 40, jnp.float32)
        half, *_ = fresh_pool.prefill(
            M.family(CFG, jnp.bfloat16),
            jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2
                         else a, params),
            jnp.asarray(toks), jnp.asarray(lens), 40, jnp.bfloat16)
    # K and V of the one attention layer, the tails of the five convolutions.
    assert [c.shape for c in cache] == [
        (1, 3, 40, 16), (1, 3, 40, 16), (5, 3, 2, 64)]
    for b in range(3):
        ids = toks[b, :lens[b]].tolist()
        ref = _reference(tree, ids)[-1]
        assert np.abs(np.asarray(logits[b]) - ref).max() < TOL
        assert np.abs(_reference(tree, ids, "int8")[-1] - ref).max() > 10 * TOL
        assert np.abs(np.asarray(half[b]) - ref).max() > 10 * TOL


# -- (b) the convolution's state -----------------------------------------------

def test_decode_continues_from_a_prefill_s_state(tree):
    """The tails and the K/V rows after a prefill of n tokens and k decode
    steps are those of a prefill of n + k tokens: a decode step's
    convolution is the prompt pass's at the same position."""
    fam = M.family(CFG, jnp.float32)
    params = jax.tree.map(jnp.asarray, tree)
    ids = np.random.default_rng(3).integers(0, 96, (1, 16)).astype(np.int32)
    n, k = 10, 6
    one = jnp.asarray([n], jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, *short = fresh_pool.prefill(fam, params, jnp.asarray(ids), one,
                                       24, jnp.float32)
        full_logits, *full = fresh_pool.prefill(
            fam, params, jnp.asarray(ids), one + k, 24, jnp.float32)
        pool = D.slot_pool(*short[:2], fam.rows)
        cache = tuple(short)
        for t in range(k):
            wpos = one + t
            logits, cache, _ = D._decode_logits(
                fam, params, pool, cache, jnp.asarray(ids[:, n + t]), wpos,
                pool.span(wpos), None, jnp.float32)
    assert np.abs(np.asarray(logits) - np.asarray(full_logits)).max() < 1e-4
    assert np.abs(np.asarray(cache[2]) - np.asarray(full[2])).max() < 1e-5
    assert np.abs(np.asarray(cache[2])).max() > 1e-3
    for got, want in zip(cache[:2], full[:2]):
        assert np.abs(np.asarray(got)[:, :, :n + k]
                      - np.asarray(want)[:, :, :n + k]).max() < 1e-4


def test_a_one_token_prompt_keeps_a_zero_row_before_it(tree):
    fam = M.family(CFG, jnp.float32)
    params = jax.tree.map(jnp.asarray, tree)
    ids = jnp.asarray([[5, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)
    _, _, _, tail = fresh_pool.prefill(
        fam, params, ids, jnp.asarray([1], jnp.int32), 12, jnp.float32)
    tail = np.asarray(tail)
    assert not tail[:, 0, 0].any() and tail[:, 0, 1].any()


# -- (c) the decode kernel with grouped queries ---------------------------------

# heads, K/V heads, head size, rows a slot, rows a block.
GROUPS = {"4 queries a head of 64": (32, 8, 64, 256, 64),
          "16 queries a head of 128": (32, 2, 128, 128, 32),
          "a tiny group, 10 heads (padded to 16 rows)": (10, 5, 8, 64, 16)}
# The last row each of 5 slots reads (negative: dead) and the first.
SPANS = {"ragged, one dead": ([-1, 255, 5, 17, 0], None),
         "all dead": ([-1, -1, -1, -1, -1], None),
         "spans with a start": ([63, -1, 40, 17, 9], [0, 0, 33, 16, 9])}


@pytest.mark.parametrize("spans", list(SPANS))
@pytest.mark.parametrize("group", list(GROUPS))
def test_decode_kernel_with_grouped_queries_is_the_jnp_form(group, spans):
    heads, kv, dh, T, bt = GROUPS[group]
    last, first = SPANS[spans]
    rng = np.random.default_rng(0)
    S, L = 5, 3
    last = jnp.minimum(jnp.asarray(last, jnp.int32), T - 1)
    first = None if first is None else jnp.asarray(first, jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, 1, heads * dh)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((L, S, T, kv * dh)), jnp.float32)
            for _ in range(2))
    # Rows no live span holds reach nothing, whatever they hold (finite: a
    # masked row of a visited block weighs an exact zero, and 0 x NaN is NaN
    # in either form).
    held = ((jnp.arange(T)[None] <= last[:, None])
            & (jnp.arange(T)[None] >= (0 if first is None
                                       else first[:, None])))
    want = DA._attend_grouped(
        q * dh ** -0.5, k[1], v[1], last[:, None],
        None if first is None else first[:, None], heads)
    k, v = (jnp.where(held[None, :, :, None], a, 1e3) for a in (k, v))
    got = DA.decode_attention(q[:, 0] * dh ** -0.5, k, v, last, None, first,
                              layer=1, heads=heads, block_t=bt,
                              interpret=True)
    assert got.shape == (S, heads * dh)
    assert np.abs(np.asarray(got) - np.asarray(want[:, 0])).max() < 2e-6
    assert not np.asarray(got)[np.asarray(last) < 0].any()


def test_a_block_is_sized_by_the_pool_s_width_not_the_queries():
    # 8,576 positions of 8 K/V heads of 64 in bfloat16: blocks of 512 rows
    # (half a MiB of K), 17 a slot, and one list of live blocks a step.
    rows = M.family(M.PUBLISHED).rows
    T = rows.count(8192 + 384)
    assert T == 8704 and DA.pick_block_t(T, 512, jnp.bfloat16) == 512
    assert DA.fits_vmem(512, 512, jnp.bfloat16)
    assert rows.count(40) == 40  # a pool shorter than a block is one block
    last = jnp.asarray([600, -1, 8703], jnp.int32)
    slot, block, count = DA.work_list(last, T, 512)
    assert int(count) == 2 + 0 + 17
    assert slot[:19].tolist() == [0, 0] + [2] * 17


# -- (d) the prompt's attention --------------------------------------------------

@pytest.mark.parametrize("P, short", [(1100, 531), (200, 77)])
def test_flash_prompt_form_is_the_form_that_writes_its_scores(
        monkeypatch, P, short):
    """Two ragged prompts of 1,100 positions, past ``flash_attention``'s
    blocks of 1,024 queries and 1,024 keys, and of 200, inside one block:
    the form one chip takes at every bucket (steered, the backend being the
    CPU, where the kernel is interpreted) against ``GroupedRows.prompt``,
    and K and V written into the pool once."""
    heads, kv, dh, B = 8, 2, 8, 2
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((B, P, heads * dh)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, P, kv * dh)), jnp.float32)
            for _ in range(2))
    lengths = jnp.asarray([P, short], jnp.int32)
    cache = tuple(jnp.zeros((2, B, P + 8, kv * dh)) for _ in range(2))
    put = D.slot_put(jnp.arange(B))
    want_cache, want = GroupedRows(kv).prompt(heads, lengths, P, put)(
        None, cache, jnp.int32(1), q, k, v)
    rows = M.GroupedFlashRows(kv)
    assert rows.prompt_form(B, heads, P, dh) == "grouped"  # the CPU's
    monkeypatch.setattr(M.GroupedFlashRows, "prompt_form",
                        lambda self, *shape: "flash")
    got_cache, got = rows.prompt(heads, lengths, P, put)(
        None, cache, jnp.int32(1), q, k, v)
    for b, n in enumerate([P, short]):  # rows past a length mean nothing
        assert np.abs(np.asarray(got[b, :n]) - np.asarray(want[b, :n])
                      ).max() < 2e-5
    for a, b in zip(got_cache, want_cache):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- (e) the gated grouped matmul -------------------------------------------------

# Rows on each of 6 experts.
LAYOUTS = {"2 rows an expert, all held": [2, 2, 2, 2, 2, 2],
           "2 rows an expert, one empty": [2, 3, 0, 2, 2, 3],
           "70 rows an expert": [70, 70, 70, 70, 70, 70],
           "70 rows an expert, one empty": [70, 84, 0, 70, 70, 56],
           # What a router makes of a prefill, which sizes drawn evenly lack.
           "groups that straddle tiles": [100, 90, 200, 130, 60, 140],
           "one smaller than a tile": [128, 7, 256, 129, 127, 1],
           "one empty, one of several tiles": [0, 700, 0, 31, 128, 5],
           "three quarters of the rows unheld": [40, 0, 150, 10, 3, 17]}
# Rows past the groups' own: a last group that is held elsewhere.
UNHELD = {"three quarters of the rows unheld": 660}


def _wanted(x, w, up, sizes, relu2):
    out = jax.lax.ragged_dot(x, w, sizes)
    if relu2:
        out = jnp.square(jnp.maximum(out, 0))
    return out if up is None else jax.nn.silu(out) * jax.lax.ragged_dot(
        x, up, sizes)


@pytest.mark.parametrize("form", ["kernel, interpreted", "ragged_dot",
                                  "tiles, interpreted"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_gated_expert_matmul_is_ragged_dot_s(layout, form):
    rng = np.random.default_rng(0)
    sizes = np.asarray(LAYOUTS[layout], np.int32)
    held = int(sizes.sum())
    G, K, N, M_ = 6, 32, 48, held + UNHELD.get(layout, 0)
    x = jnp.asarray(rng.standard_normal((M_, K)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
                for _ in range(2))
    with jax.default_matmul_precision("highest"):
        want = _wanted(x, gate, up, jnp.asarray(sizes), False)
        if form == "ragged_dot":
            got = [E.expert_matmul(x, gate, jnp.asarray(sizes), up=up)]
        elif form == "tiles, interpreted":
            got = [_through_tiles(x, gate, up, sizes, False, tile)
                   for tile in TILES]
        else:
            got = [E.expert_matmul_kernel(x, gate, jnp.asarray(sizes), up,
                                          tile=tile, interpret=True)
                   for tile in (8, 16)]
    for out in got:
        assert out.shape == (M_, N)
        assert np.abs(np.asarray(out) - np.asarray(want))[:held].max() < 1e-3


# Every row tile the ``tiles`` regime's plan can pick, and a small one.
TILES = sorted({16} | {E.plan(rows * 64, 2048, 1536, 64, 2).tile
                       for rows in range(128, 1025, 32)})


def _through_tiles(x, w, up, sizes, relu2, tile, align=E._ROW_ALIGN):
    """``x`` (sorted by group) with each group begun on a multiple of
    ``align`` rows, through the kernel of the ``tiles`` regime, which
    writes each on a multiple of ``tile``, and read back row for row."""
    sizes, rows = jnp.asarray(sizes), x.shape[0]
    align = min(align, tile)
    laid = jnp.zeros((E.laid_rows(rows, len(sizes), tile, align),
                      x.shape[1]), x.dtype).at[
        E.lay_out(sizes, rows, align)].set(x, mode="drop")
    out = E.expert_matmul_kernel(laid, w, sizes, up, relu2=relu2, tile=tile,
                                 interpret=True, laid_out=align)
    at = E.lay_out(sizes, rows, tile)
    # No tile of the result holds two groups' rows.
    owner = np.repeat(np.arange(len(sizes)), np.asarray(sizes))
    tile_of = np.asarray(at)[:len(owner)] // tile
    assert all(len(set(owner[tile_of == t])) == 1 for t in set(tile_of))
    return jnp.take(out, at, axis=0, mode="fill", fill_value=0)


@pytest.mark.parametrize("kind", ["plain", "relu2"])
@pytest.mark.parametrize("layout", list(LAYOUTS)[4:])
def test_tiles_regime_is_ragged_dot_in_every_form(layout, kind):
    """The plain and the squared call of the ``tiles`` regime (the gated
    one is above), with blocks of ``N`` narrower than the matrix, so that
    the kernel's own fetch walks groups and blocks both."""
    relu2 = kind == "relu2"
    rng = np.random.default_rng(1)
    sizes = np.asarray(LAYOUTS[layout], np.int32)
    held = int(sizes.sum())
    G, K, N, M_ = 6, 32, 256, held + UNHELD.get(layout, 0)
    x = jnp.asarray(rng.standard_normal((M_, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((G, K, N)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _wanted(x, w, None, jnp.asarray(sizes), relu2)
        for tile in TILES:
            # As the first call reads its rows, and as the second does.
            for align in (E._ROW_ALIGN, tile):
                got = _through_tiles(x, w, None, sizes, relu2, tile, align)
                assert np.abs(np.asarray(got) - np.asarray(want)
                              )[:held].max() < 1e-3


def test_tiles_regime_walks_blocks_of_n(monkeypatch):
    """Blocks of 128 of 256 columns: every (block, group) pair waits for a
    fetch of its own, the last one starts none."""
    monkeypatch.setattr(E, "_TILES_BLOCK_BYTES", 32 * 128 * 4)
    assert E._tiles_blocks(32, 256, 1, 4, 16)[0] == 128
    rng = np.random.default_rng(2)
    sizes = np.asarray([40, 0, 17, 1, 0, 90], np.int32)
    x = jnp.asarray(rng.standard_normal((int(sizes.sum()), 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((6, 32, 256)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _wanted(x, w, None, jnp.asarray(sizes), False)
        got = _through_tiles(x, w, None, sizes, False, 16)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-3


def test_a_tile_list_names_whole_tiles_of_one_group():
    sizes = jnp.asarray([3, 0, 17, 16, 0, 1], jnp.int32)
    # 37 rows held, 3 held elsewhere; groups on multiples of 8, the tile.
    assert E.laid_rows(40, 6, 8) == (40 + 6 * 7) // 8 * 8
    assert E.lay_out(sizes, 40, 8).tolist() == (
        [0, 1, 2] + list(range(8, 25)) + list(range(32, 48)) + [48]
        + [E._NOWHERE] * 3)
    group, first, upto, rank, count = E.tile_list(sizes, 10, 8)
    # 1 + 3 + 2 + 1 tiles, none for an empty group: the least there can be.
    assert int(count) == 7 == int(-(-np.asarray(sizes) // 8).sum())
    assert group.tolist()[:7] == [0, 2, 2, 2, 3, 3, 5]
    assert first.tolist()[:7] == [0, 8, 16, 24, 32, 40, 48]
    # Where the kernel finds the group to fetch next, and its buffer.
    assert upto.tolist() == [1, 1, 4, 6, 6, 7]
    assert rank.tolist() == [0, 1, 1, 2, 3, 3]
    # Groups on multiples of 4 rows, tiles of 8 all the same: a tile begins
    # where its group does, and a tile more at the end is there to be read.
    assert E.laid_rows(40, 6, 8, 4) == (40 + 6 * 3) // 4 * 4 + 8
    assert E.lay_out(sizes, 40, 4).tolist()[:21] == (
        [0, 1, 2] + list(range(4, 21)) + [24])
    assert E.tile_list(sizes, 10, 8, 4)[1].tolist()[:7] == [
        0, 4, 12, 20, 24, 32, 40]


# The plan, pinned: LFM2's four buckets (4 assignments a token over 64
# experts), its segment (32 slots), Nemotron-H's prefill dispatch (8 x 512
# tokens, 22 a token, 128 held) and its segment.
@pytest.mark.parametrize("rows, shape, regime, tile, block_n, vmem", [
    (2048 * 4, (2048, 1536, 64, 2), "tiles", 128, 1536, 32 << 20),
    (4096 * 4, (2048, 1536, 64, 2), "tiles", 128, 1536, 32 << 20),
    (6144 * 4, (2048, 1536, 64, 2), "tiles", 128, 1536, 32 << 20),
    (8192 * 4, (2048, 1536, 64, 2), "tiles", 128, 1536, 32 << 20),
    (8192 * 4, (1536, 2048, 64, 1), "tiles", 128, 2048, 20709376),
    (32 * 4, (2048, 1536, 64, 2), "stream", 16, 512, None),
    (32 * 4, (1536, 2048, 64, 1), "stream", 16, 1024, None),
    (8 * 512 * 22, (1024, 2688, 128, 1), "tiles", 128, 2688, 19857408),
    (8 * 512 * 22, (2688, 1024, 128, 1), "tiles", 128, 1024, 18153472),
    (32 * 22, (1024, 2688, 128, 1), "stream", 16, 896, None),
    (32 * 22, (2688, 1024, 128, 1), "stream", 16, 512, None)])
def test_the_plan_by_shape(rows, shape, regime, tile, block_n, vmem):
    K, N, G, mats = shape
    chosen = E.plan(rows, K, N, G, mats)
    assert chosen[:3] == (regime, tile, block_n) and chosen.vmem == vmem
    # Both calls of an expert share the rows' layout: one tile.
    assert E.plan(rows, N, K, G, 1).tile == tile


@pytest.mark.parametrize("kind", ["gated", "relu2"])
@pytest.mark.parametrize("tokens, regime", [(40, "stream"), (330, "tiles")])
@pytest.mark.parametrize("unheld", [0.0, 0.75, 1.0])
def test_experts_is_the_same_sum_in_both_regimes(monkeypatch, kind, tokens,
                                                 regime, unheld):
    """:func:`experts` end to end through the kernels (interpreted; the
    process steered to them) against the ``ragged_dot`` path: the sort, the
    rows laid out on tile boundaries, the un-sort, and nothing of an
    assignment whose expert is held elsewhere (or of any, where none of a
    program's rows falls on a held expert)."""
    import functools

    rng = np.random.default_rng(4)
    top_k, held, K, F = 2, 4, 32, 48
    u = jnp.asarray(rng.standard_normal((tokens, K)), jnp.float32)
    w1, w3 = (jnp.asarray(rng.standard_normal((held, K, F)) * 0.2,
                          jnp.float32) for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((held, F, K)) * 0.2, jnp.float32)
    w3 = w3 if kind == "gated" else None
    group = rng.integers(0, held, (tokens, top_k))
    group[rng.random((tokens, top_k)) < unheld] = held
    group[:, 0][group[:, 0] == 1] = 2   # an expert with few rows
    group, weights = jnp.asarray(group, jnp.int32), jnp.asarray(
        rng.random((tokens, top_k)), jnp.float32)
    assert E.plan(tokens * top_k, K, F, held).regime == regime
    with jax.default_matmul_precision("highest"):
        want, sizes = E.experts(u, w1, w2, weights, group, w3=w3)
        monkeypatch.setattr(E, "_use_kernel", lambda: True)
        monkeypatch.setattr(E, "expert_matmul_kernel", functools.partial(
            E.expert_matmul_kernel, interpret=True))
        monkeypatch.setattr(E, "expert_combine", functools.partial(
            E.expert_combine, interpret=True))
        got, sizes_ = E.experts(u, w1, w2, weights, group, w3=w3)
    assert sizes.tolist() == sizes_.tolist()
    assert (np.abs(np.asarray(want)).max() > 0.1) == (unheld < 1)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4


def test_gated_blocks_hold_half_the_columns():
    # Two matrices a grid step: each block half of what one may take.
    assert E.pick_block_n(2048, 1536, 2) == 768
    assert E.pick_block_n(2048, 1536, 4) == 512


# -- (f) the share ----------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Every offset's part of the routed sum is the uncut reference's expert
    layer (no shared expert: nothing is counted twice); and the program's
    layer is the reference's for each share."""
    p = M._init_layer(3, np.random.default_rng(9), CFG, np.float32)
    p["expert_bias"] = np.random.default_rng(8).normal(
        0, 0.05, 8).astype(np.float32)
    x = np.random.default_rng(4).standard_normal((7, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference.experts(
            jax.tree.map(jnp.asarray, p), jnp.asarray(x), KEYS))
        parts = []
        for offset in (0, 2, 4, 6):
            mine = {**p, **{m: p[m][offset:offset + 2]
                            for m in ("w1", "w3", "w2")}}
            mine = jax.tree.map(jnp.asarray, mine)
            parts.append(np.asarray(reference.experts(
                mine, jnp.asarray(x), dict(KEYS, expert_offset=offset))))
            cfg = dataclasses.replace(CFG, experts_held=2,
                                      expert_offset=offset)
            got = M._experts(cfg, mine, jnp.asarray(x)[None],
                             lambda c: None)[0]
            assert np.abs(np.asarray(got) - parts[-1]).max() < TOL
    assert np.abs(sum(parts) - uncut).max() < TOL
    assert min(np.abs(part).max() for part in parts) > 0.01


def test_route_is_the_published_router_but_for_its_1e_6():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((6, 64)).astype(np.float32)
    p = {"router": rng.standard_normal((64, 8)).astype(np.float32) * 0.2,
         "expert_bias": rng.standard_normal(8).astype(np.float32)}
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(reference.routing(
            jax.tree.map(jnp.asarray, p), jnp.asarray(x),
            {"top_k": 2, "routed_scale": 1.0}))
        w, group = E.route(jnp.asarray(x), p["router"], p["expert_bias"], 2,
                           1.0, 0, 8)
    w, group = np.asarray(w), np.asarray(group)
    for n in range(6):
        assert sorted(group[n]) == np.flatnonzero(dense[n]).tolist()
        # The reference divides by the sum + 1e-6, the program by the sum.
        assert np.allclose(w[n], dense[n, group[n]], rtol=2e-6, atol=0)
    assert np.allclose(w.sum(-1), 1.0, atol=1e-6)


# -- (g) one trace a kind of layer, no paged lane --------------------------------

def test_layer_traces_is_three_for_the_segment_and_the_prefill(servable):
    meta = servable.meta["continuous"]
    S = meta["slots"]
    cache = tuple(jnp.zeros(shape, dt) for shape, dt in meta["cache_leaves"])
    zf, zi = jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)
    clock = CompileClock()
    with clock.open("m", "segment", {}, seen=set()):
        jax.jit(meta["segment"])(servable.params, cache, zi, zi + 3, zi,
                                 zi != 0, zf, zi, zi, zf + 1)
    # Convolution and dense, attention and experts, convolution and experts.
    assert clock.snapshot()[-1]["layer_traces"] == 3  # of 6 layers
    payload = {k: jnp.zeros(v.shape, v.dtype)
               for k, v in meta["admit_spec"](8).items()}
    with clock.open("m", "prefill", {"batch": 1, "bucket": 8}, seen=set()):
        jax.jit(meta["prefill"])(servable.params, cache, zi[:1],
                                 {**payload, "length": jnp.ones(1, jnp.int32)})
    assert clock.snapshot()[-1]["layer_traces"] == 3


def test_the_servable_declares_its_pool_and_one_prompt_a_dispatch(servable):
    meta = servable.meta["continuous"]
    assert [shape for shape, _ in meta["cache_leaves"]] == [
        (1, 3, 32, 16), (1, 3, 32, 16), (5, 3, 2, 64)]
    assert list(meta["counters"]) == [
        "expert_assignments_held", "experts_touched", "expert_load_max"]
    assert meta["rows"].prefill_batch(16) == 1
    assert meta["prompt_form"](1, 16) == "grouped"
    assert meta["read_block"] == 32  # off the chip: whole rows
    # What the lane's boot log lists: the experts' plan for a program's rows.
    assert meta["expert_plan"](16) == E.plan_summary(
        16, CFG.top_k, CFG.hidden_size, CFG.expert_width, CFG.experts_held,
        True, 4)
    assert meta["expert_plan"](16)["regime"] == "stream"
    assert meta["expert_plan"](16)["unsort"] == "einsum"


def test_paged_lane_is_refused_at_build(servable):
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

    assert servable.meta["continuous"]["paged"] is None
    with pytest.raises(ValueError, match="kv_cache='paged' cannot serve "
                                         "this family"):
        get_model_builder("lfm2")(ModelConfig(
            name="lfm2", dtype="float32", batch_buckets=(1,),
            seq_buckets=(8, 16), kv_cache="paged", extra=EXTRA))
    with pytest.raises(ValueError, match="not among the 8 published"):
        M.config_from_arch(dict(ARCH, experts_held=4, expert_offset=6))
    with pytest.raises(ValueError, match="operators of unknown kind"):
        M.family(M.config_from_arch(dict(ARCH, layer_types=["conv", "ssm"])))
