"""models/mellum.py through models/decoder.py's seam, at tiny widths.

The family (window layers that keep a ring of rows beside full layers that
keep a row a position, YaRN on the full layers alone, 8 gated experts behind
a softmax router in every layer) against the plain reference
(benchmark/reference/mellum.py): logits of the prefill and of decode steps
through both leaf pairs and wrapped rings; the ring after a prefill; a work
list a kind against a mask; the decode kernel at a group of 8 of 128; the
band form of the prompt attention; YaRN's numbers; the softmax router; the
share against the uncut layer; the trunk's trace count; the scheduler's
counts a kind; the paged lane's refusal.
"""

import asyncio
import dataclasses
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fresh_pool

from benchmark.reference import mellum as reference
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.cache import CompileClock
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import mellum as M
from pytorch_zappa_serverless_tpu.ops import decode_attention as DA
from pytorch_zappa_serverless_tpu.ops import expert_matmul as E
from pytorch_zappa_serverless_tpu.ops import flash_attention as FA
from pytorch_zappa_serverless_tpu.serving.generation import build_gen_kernels

pytest_plugins = "aiohttp.pytest_plugin"  # runs the coroutine tests

W = 8  # the window
# One period of the pattern.
ARCH = {"vocab_size": 96, "hidden_size": 64,
        "layer_types": [M.WINDOW, M.WINDOW, M.WINDOW, M.FULL],
        "heads": 8, "kv_heads": 2, "head_dim": 16, "sliding_window": W,
        "experts_published": 8, "experts_held": 8, "top_k": 2,
        "expert_width": 48, "rope_theta": 100.0, "yarn_factor": 4.0,
        "yarn_original_positions": 16, "max_positions": 512,
        "init_std": 0.1, "eos_id": 96}
CFG = M.config_from_arch(ARCH)
KEYS = {k: getattr(CFG, k) for k in (
    "heads", "kv_heads", "head_dim", "sliding_window", "top_k",
    "expert_offset", "rope_theta", "yarn_factor", "yarn_original_positions",
    "yarn_beta_fast", "yarn_beta_slow", "yarn_attention_factor", "norm_eps")}
KEYS["layer_types"] = list(CFG.layer_types)
EXTRA = {"max_new_tokens": 16, "gen_slots": 3, "segment_tokens": 8,
         "arch": ARCH}
# float32 at ``highest`` against float32 at ``highest``: two orders of
# summation (the ring's rows are summed in another order than positions).
# Logits here spread over about 1; a bfloat16 product or bfloat16 scores
# move them by 1e-2, int8 weights by more, a window read as full, plain
# frequencies on the full layer or a missing ``attention_factor`` by 1e-2
# and more (the second test of this section holds each to ten times this).
TOL = 2e-4


@pytest.fixture(scope="module")
def tree():
    return M.init_mellum_params(0, CFG)


@pytest.fixture(scope="module")
def servable(tree):
    from pytorch_zappa_serverless_tpu.models.vision_common import (
        resolve_dtype)

    mc = ModelConfig(name="mellum", dtype="float32", batch_buckets=(1,),
                     seq_buckets=(8, 24), extra=EXTRA)
    return D.make_servable("mellum", mc,
                           M.family(CFG, resolve_dtype("float32")),
                           jax.tree.map(np.asarray, tree))


def _reference(tree, ids, control=None, **changed):
    return reference.forward(tree, ids, dict(KEYS, **changed), control)


# -- (a) the programs against the reference's full forward pass ----------------

# Prompts of one prefill batch (bucket 24), the slot each goes to, and the
# request that had the slot before it (None: a fresh pool).  The window is 8
# and a segment 8 steps, so every ring wraps inside the segment.
PROGRAM_CASES = {
    "shorter and longer than the window": ([24, 5, 13], [0, 1, 2], None),
    "one token, and the window to the row": ([1, 8], [2, 0], None),
    "a slot re-used after another request": ([9, 19], [1, 0], [23, 6]),
}


def _admit(kernels, params, cache, prompts, slots):
    """One batched prefill (padded to a power of two with copies of its
    first prompt, which are given that prompt's slot, as the scheduler pads)
    into ``slots`` of the pool → ``(cache, first tokens)``."""
    prompts = list(prompts) + [prompts[0]] * (
        (1 << (len(prompts) - 1).bit_length()) - len(prompts))
    slots = list(slots) + [slots[0]] * (len(prompts) - len(slots))
    B = len(prompts)
    toks = np.zeros((B, 24), np.int32)
    for j, ids in enumerate(prompts):
        toks[j, :len(ids)] = ids
    payload = {"input_ids": toks,
               "length": np.asarray([len(p) for p in prompts], np.int32),
               "temperature": np.zeros(B, np.float32),
               "seed": np.zeros(B, np.int32), "top_k": np.zeros(B, np.int32),
               "top_p": np.ones(B, np.float32)}
    first, *cache = kernels["prefill"](params, tuple(cache),
                                       np.asarray(slots, np.int32), payload)
    return tuple(cache), np.asarray(first)


def _step_logits(fam, params, cache, tok, wpos):
    """One decode step over the pool's leaves, a span a kind."""
    pool, _ = D.slot_pools(fam, cache)
    return D._decode_logits(fam, params, pool, cache, tok, wpos,
                            [p.span(wpos) for p in pool], [None] * len(pool),
                            jnp.float32)


@pytest.mark.parametrize("case", list(PROGRAM_CASES))
def test_prefill_and_segment_give_the_reference_s_logits(
        case, tree, servable):
    """``prefill_start`` into the pool and ``decode_segment`` as the
    scheduler jits them, through both leaf pairs; then, because a segment
    returns tokens, the same step (``_decode_logits``) over the same pool
    for the logits of every position a segment decoded."""
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
        step_cache, logits = kept, []
        for t in range(seg):
            lg, step_cache, _ = _step_logits(
                fam, params, step_cache, jnp.asarray(emits[:, t]),
                jnp.asarray(pos + t))
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


def test_prefill_logits_are_the_reference_s_and_each_control_is_not(tree):
    """The tolerance holds the sound path and fails each thing this family
    brought, left out: the nearest precisions below (the reference through
    int8, the program in bfloat16), a window layer read as a full one, the
    full layer turned as a window layer, and ``attention_factor`` alone."""
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
    # K and V of the one full layer, then of the three window layers' rings.
    assert [c.shape for c in cache] == [
        (1, 3, 40, 32), (1, 3, 40, 32), (3, 3, W, 32), (3, 3, W, 32)]
    for b in range(3):
        ids = toks[b, :lens[b]].tolist()
        ref = _reference(tree, ids)[-1]
        assert np.abs(np.asarray(logits[b]) - ref).max() < TOL
        assert np.abs(np.asarray(half[b]) - ref).max() > 10 * TOL
        controls = {
            "int8": _reference(tree, ids, "int8"),
            "no_yarn": _reference(tree, ids, "no_yarn"),
            "attention_factor": _reference(tree, ids,
                                           yarn_attention_factor=1.0)}
        if lens[b] > W:  # inside the window the two masks are one
            controls["window_as_full"] = _reference(tree, ids,
                                                    "window_as_full")
        for name, other in controls.items():
            assert np.abs(other[-1] - ref).max() > 10 * TOL, name


# -- (b) the ring ----------------------------------------------------------------

def test_decode_continues_from_a_prefill_s_rings(tree):
    """The rings and the rows after a prefill of n tokens and k decode steps
    are those of a prefill of n + k tokens: the prefill leaves each of a
    prompt's last positions where a decode step would have written it."""
    fam = M.family(CFG, jnp.float32)
    params = jax.tree.map(jnp.asarray, tree)
    ids = np.random.default_rng(3).integers(0, 96, (1, 24)).astype(np.int32)
    for n, k in ((5, 6), (13, 9), (8, 16)):
        one = jnp.asarray([n], jnp.int32)
        with jax.default_matmul_precision("highest"):
            _, *cache = fresh_pool.prefill(fam, params, jnp.asarray(ids), one,
                                           32, jnp.float32)
            want_logits, *want = fresh_pool.prefill(
                fam, params, jnp.asarray(ids), one + k, 32, jnp.float32)
            cache = tuple(cache)
            for t in range(k):
                logits, cache, _ = _step_logits(
                    fam, params, cache, jnp.asarray(ids[:, n + t]), one + t)
        assert np.abs(np.asarray(logits) - np.asarray(want_logits)).max() \
            < 1e-4
        for got, full in zip(cache[:2], want[:2]):
            assert np.abs(np.asarray(got)[:, :, :n + k]
                          - np.asarray(full)[:, :, :n + k]).max() < 1e-4
        live = min(n + k, W)  # the ring's rows that hold a position
        for got, ring in zip(cache[2:], want[2:]):
            assert ring.shape[2] == W
            assert np.abs(np.asarray(got)[:, :, :live]
                          - np.asarray(ring)[:, :, :live]).max() < 1e-4


def test_a_prefill_leaves_the_prompt_s_last_window_at_p_mod_w():
    """``RingRows.kept`` on rows that say their position: row ``r`` of the
    ring holds the last real position ``p`` with ``p mod W == r``."""
    rows = M.RingRows(2, W)
    P = 24
    k = jnp.broadcast_to(jnp.arange(P, dtype=jnp.float32)[None, :, None],
                         (4, P, 3))
    lengths = jnp.asarray([24, 13, 8, 5], jnp.int32)
    ring = np.asarray(jax.jit(lambda k, n: rows.kept(k, n, P))(k, lengths))
    assert ring.shape == (4, W, 3)
    for b, n in enumerate([24, 13, 8, 5]):
        for r in range(min(n, W)):
            want = n - 1 - (n - 1 - r) % W
            assert (ring[b, r] == want).all(), (n, r)
    assert rows.count(40) == W and rows.count(5) == 5
    at = np.asarray([0, 7, 8, 30])
    assert rows.row(at, W).tolist() == [0, 7, 0, 6]
    first, last = rows.span(at, W)
    assert first.tolist() == [0, 0, 0, 0] and last.tolist() == [0, 7, 7, 7]


# -- (c) a work list a kind, and the kernel at a group of 8 of 128 ---------------

# The position each of 5 slots stands at (negative: dead).
POSITIONS = {"ragged, one dead": [-1, 63, 5, 17, 0],
             "all dead": [-1, -1, -1, -1, -1],
             "past the window": [40, -1, 16, 15, 63]}
KINDS = {"ring": M.RingRows(4, 16), "full": M.FullRows(4)}


@pytest.mark.parametrize("positions", list(POSITIONS))
@pytest.mark.parametrize("kind", list(KINDS))
def test_the_kernel_over_a_kind_s_work_list_reads_its_span(kind, positions):
    """``decode_attention`` under ``interpret=True`` at 32 queries over 4
    K/V heads of 128, over the list of live blocks a kind's spans make,
    against a mask in ``jax.numpy`` over all the rows."""
    rows, at = KINDS[kind], np.asarray(POSITIONS[positions])
    heads, kv, dh, S = 32, 4, 128, 5
    T, bt = rows.count(64), 8
    first, last = rows.span(np.maximum(at, 0), T)
    last = np.where(at < 0, -1, last).astype(np.int32)
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((S, heads * dh)), jnp.float32)
    ck, cv = (jnp.asarray(rng.standard_normal((2, S, T, kv * dh)),
                          jnp.float32) for _ in range(2))
    slot, block, count = DA.work_list(jnp.asarray(last), T, bt,
                                      jnp.asarray(first))
    # The list is the live blocks of the live slots, in order, and no more.
    want = [(s, b) for s in range(S) if last[s] >= 0
            for b in range(first[s] // bt, last[s] // bt + 1)]
    assert int(count) == len(want)
    assert list(zip(np.asarray(slot)[:len(want)].tolist(),
                    np.asarray(block)[:len(want)].tolist())) == want
    got = DA.decode_attention(q * dh ** -0.5, ck, cv, jnp.asarray(last),
                              (slot, block, count), jnp.asarray(first),
                              layer=1, heads=heads, block_t=bt,
                              interpret=True)
    # The mask: rows [first, last] of the slot's own row of layer 1.
    qg = np.asarray(q).reshape(S, kv, heads // kv, dh) * dh ** -0.5
    k, v = (np.asarray(a)[1].reshape(S, T, kv, dh) for a in (ck, cv))
    scores = np.einsum("shgd,sthd->shgt", qg, k)
    keep = ((np.arange(T)[None] >= first[:, None])
            & (np.arange(T)[None] <= last[:, None]))
    scores = np.where(keep[:, None, None], scores, -np.inf)
    live = last >= 0
    probs = np.zeros_like(scores)
    probs[live] = np.asarray(jax.nn.softmax(jnp.asarray(scores[live]), -1))
    want_out = np.einsum("shgt,sthd->shgd", probs, v).reshape(S, heads * dh)
    assert np.abs(np.asarray(got) - want_out).max() < 2e-5
    assert not np.asarray(got)[~live].any()


# -- (d) the band form of the prompt attention ----------------------------------

def _band_bias(P, window):
    at = np.arange(P)
    behind = at[:, None] - at[None, :]
    return jnp.asarray(np.where((behind >= 0) & (behind < window), 0.0,
                                -1e9)[None, None], jnp.float32)


@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256)])
def test_flash_attention_with_a_band_is_the_masked_form(blocks):
    """``flash_attention(window=)`` with K and V a group narrower than the
    queries, at a ``P`` of several blocks, against ``masked_attention`` with
    the band's mask over K and V repeated."""
    B, P, H, KV, dh, window = 2, 640, 4, 2, 16, 200
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, P, H, dh)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((B, P, KV, dh)), jnp.float32)
            for _ in range(2))
    got = FA.flash_attention(q, k, v, causal=True, window=window,
                             block_q=blocks[0], block_k=blocks[1])
    want = FA.masked_attention(
        q.reshape(B, P, H * dh),
        *(jnp.repeat(a, H // KV, axis=2).reshape(B, P, H * dh)
          for a in (k, v)), _band_bias(P, window), H)
    assert np.abs(np.asarray(got).reshape(B, P, H * dh)
                  - np.asarray(want)).max() < 2e-5
    with pytest.raises(ValueError, match="a band"):
        FA.flash_attention(q, k, v, window=window)


def test_blocks_outside_the_band_are_not_visited():
    """The grid's innermost axis is the widest band in blocks, not the
    prompt's; and a block of keys wholly outside a query block's band may
    hold anything (NaN here): nothing of it reaches the output."""
    # The published window at the blocks the kernel takes on the chip: the
    # diagonal's block and the one before it, at every prompt length.
    for P in (4096, 16384):
        assert FA.band_blocks(P // 1024, 1024, 1024, 1024) == 2
    assert FA.band_blocks(5, 128, 128, 200) == 3
    assert FA.band_blocks(5, 128, 128, 1) == 1
    B, P, H, dh, window, blk = 1, 640, 2, 16, 130, 128
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((B, P, H, dh)), jnp.float32)
               for _ in range(3))
    sound = FA.flash_attention(q, k, v, causal=True, window=window,
                               block_q=blk, block_k=blk)
    # Queries of block 4 (512-639) see keys from 383 on: blocks 0 and 1 lie
    # wholly outside its band and outside that of block 3's queries too
    # (from 255 on) but for block 1's last row; poison block 0 alone, and
    # the queries of blocks 2-4 must not change.
    poisoned = [a.at[:, :blk].set(jnp.nan) for a in (k, v)]
    got = FA.flash_attention(q, *poisoned, causal=True, window=window,
                             block_q=blk, block_k=blk)
    assert np.isfinite(np.asarray(got)[:, 2 * blk + window:]).all()
    assert np.array_equal(np.asarray(got)[:, 2 * blk + window:],
                          np.asarray(sound)[:, 2 * blk + window:])


def test_the_prompt_s_two_forms_are_one_attention(tree):
    """The ``jax.numpy`` form a CPU prefill takes against the kernel form a
    chip takes (run here under ``interpret``), a kind each."""
    heads, kv, dh, P = CFG.heads, CFG.kv_heads, CFG.head_dim, 24
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.standard_normal((2, P, heads * dh)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, P, kv * dh)), jnp.float32)
            for _ in range(2))
    lengths = jnp.asarray([24, 11], jnp.int32)
    for rows in (M.FullRows(kv), M.RingRows(kv, W)):
        put = lambda leaf, i, values, row=0: values  # noqa: E731
        _, got = rows.prompt(heads, lengths, P, put)(None, (None, None), 0,
                                                     q, k, v)
        want = FA.flash_attention(
            q.reshape(2, P, heads, dh), k.reshape(2, P, kv, dh),
            v.reshape(2, P, kv, dh), causal=True, window=rows.window,
            interpret=True)
        for b, n in enumerate([24, 11]):  # the real queries
            assert np.abs(np.asarray(got)[b, :n] - np.asarray(want).reshape(
                2, P, heads * dh)[b, :n]).max() < 2e-5


# -- (e) the two rotations ---------------------------------------------------------

def test_yarn_frequencies_are_the_formula_s_at_the_published_parameters():
    cfg = M.PUBLISHED
    assert M.yarn_bounds(cfg) == (18, 35)
    # c(32) = 18.08 and c(1) = 34.98, by hand.
    c = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (  # noqa: E731
        2 * math.log(500000))
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    full, window = M.inv_freq(cfg, M.FULL), M.inv_freq(cfg, M.WINDOW)
    assert full.shape == window.shape == (64,)
    assert full[0] == window[0] == 1.0                # below ``low``: kept
    assert np.allclose(full[:19], window[:19], rtol=1e-6)
    assert np.allclose(full[35:], window[35:] / 16, rtol=1e-6)
    assert np.isclose(full[63], 500000 ** (-126 / 128) / 16, rtol=1e-6)
    assert np.isclose(full[63], 1.5346e-07, rtol=1e-3)
    i = 27  # on the ramp: 9/17 of the way
    plain = 500000 ** (-2 * i / 128)
    assert np.isclose(full[i], plain / 16 * (9 / 17) + plain * (8 / 17),
                      rtol=1e-6)
    assert cfg.yarn_attention_factor == 1.2772588722239782
    # The reference's own, written apart, agree.
    keys = {"head_dim": 128, "rope_theta": 500000.0, "yarn_factor": 16.0,
            "yarn_original_positions": 8192, "yarn_beta_fast": 32.0,
            "yarn_beta_slow": 1.0}
    assert reference.yarn_bounds(keys) == (18, 35)
    assert np.allclose(reference.frequencies(keys, True), full, rtol=1e-6)
    assert np.allclose(reference.frequencies(keys, False), window, rtol=1e-6)


# -- (f) the router and the share ---------------------------------------------------

def test_softmax_route_is_softmax_then_top_k():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((6, 64)).astype(np.float32)
    gate = rng.standard_normal((64, 8)).astype(np.float32) * 0.2
    with jax.default_matmul_precision("highest"):
        s = jax.nn.softmax(jnp.asarray(x) @ jnp.asarray(gate), axis=-1)
        top, chosen = jax.lax.top_k(s, 2)
        w, group = E.route(jnp.asarray(x), gate, None, 2, 1.0, 0, 8,
                           scoring="softmax")
        dense = np.asarray(reference.routing({"router": jnp.asarray(gate)},
                                             jnp.asarray(x), {"top_k": 2}))
    assert np.array_equal(np.asarray(group), np.asarray(chosen))
    assert np.allclose(np.asarray(w),
                       np.asarray(top / top.sum(-1, keepdims=True)),
                       rtol=1e-6, atol=0)
    assert np.allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    for n in range(6):
        assert np.allclose(dense[n, np.asarray(group)[n]], np.asarray(w)[n],
                           rtol=1e-6)
    # A share holds experts [2, 6): the others' rows go to the last group.
    _, local = E.route(jnp.asarray(x), gate, None, 2, 1.0, 2, 4,
                       scoring="softmax")
    chosen = np.asarray(chosen)
    assert np.array_equal(np.asarray(local), np.where(
        (chosen >= 2) & (chosen < 6), chosen - 2, 4))


def test_four_shares_of_two_experts_add_up_to_the_layer(tree):
    """The share test: the layer's experts cut into four chips' parts, each
    holding 2 of 8 behind the whole router, sum to the uncut layer."""
    layer = tree["layer1"]
    x = np.random.default_rng(6).standard_normal((12, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = jax.tree.map(jnp.asarray, layer)
        uncut = np.asarray(reference.experts(whole, jnp.asarray(x), KEYS))
        served = np.asarray(M._experts(CFG, whole, jnp.asarray(x)[None],
                                       lambda c: None)[0])
        assert np.abs(served - uncut).max() < TOL
        parts = []
        for offset in range(0, 8, 2):
            mine = dict(whole, **{m: whole[m][offset:offset + 2]
                                  for m in ("w1", "w3", "w2")})
            parts.append(np.asarray(reference.experts(
                mine, jnp.asarray(x), dict(KEYS, expert_offset=offset))))
            cfg = dataclasses.replace(CFG, experts_held=2,
                                      expert_offset=offset)
            got = M._experts(cfg, mine, jnp.asarray(x)[None],
                             lambda c: None)[0]
            assert np.abs(np.asarray(got) - parts[-1]).max() < TOL
    assert np.abs(sum(parts) - uncut).max() < TOL
    assert min(np.abs(part).max() for part in parts) > 0.01


# -- (g) one trace a kind of layer, the pool, no paged lane ------------------------

def test_layer_traces_is_two_for_the_segment_and_the_prefill(servable):
    meta = servable.meta["continuous"]
    S = meta["slots"]
    cache = tuple(jnp.zeros(shape, dt) for shape, dt in meta["cache_leaves"])
    zf, zi = jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)
    clock = CompileClock()
    with clock.open("m", "segment", {}, seen=set()):
        jax.jit(meta["segment"])(servable.params, cache, zi, zi + 3, zi,
                                 zi != 0, zf, zi, zi, zf + 1)
    assert clock.snapshot()[-1]["layer_traces"] == 2  # of 4 layers
    payload = {k: jnp.zeros(v.shape, v.dtype)
               for k, v in meta["admit_spec"](8).items()}
    with clock.open("m", "prefill", {"batch": 1, "bucket": 8}, seen=set()):
        jax.jit(meta["prefill"])(servable.params, cache, zi[:1],
                                 {**payload, "length": jnp.ones(1, jnp.int32)})
    assert clock.snapshot()[-1]["layer_traces"] == 2


def test_the_servable_declares_four_leaves_and_a_kind_s_numbers(servable):
    meta = servable.meta["continuous"]
    # 24 + 16 positions: the full layer's rows, then the three rings'.
    assert [shape for shape, _ in meta["cache_leaves"]] == [
        (1, 3, 40, 32), (1, 3, 40, 32), (3, 3, W, 32), (3, 3, W, 32)]
    assert [(k["name"], k["layers"], k["count"], k["read_block"])
            for k in meta["kinds"]] == [
        (M.FULL, 1, 40, 40), (M.WINDOW, 3, W, W)]  # off the chip: whole rows
    assert list(meta["counters"]) == [
        "expert_assignments_held", "experts_touched", "expert_load_max"]
    assert meta["rows"].prefill_batch(24) == 1
    assert meta["prompt_form"](1, 24) == "grouped+grouped_band"
    assert meta["expert_plan"](16)["regime"] == "stream"
    fam = M.family(CFG)
    assert [fam.cache_index(i) for i in range(4)] == [
        (1, 0), (1, 1), (1, 2), (0, 0)]
    assert M.family(M.PUBLISHED).kinds[0].rows.count(16384 + 768) == 17408


def test_paged_lane_is_refused_at_build(servable):
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

    assert servable.meta["continuous"]["paged"] is None
    with pytest.raises(ValueError, match="kv_cache='paged' cannot serve "
                                         "this family"):
        get_model_builder("mellum")(ModelConfig(
            name="mellum", dtype="float32", batch_buckets=(1,),
            seq_buckets=(8, 24), kv_cache="paged", extra=EXTRA))
    with pytest.raises(ValueError, match="not among the 8 published"):
        M.config_from_arch(dict(ARCH, experts_held=4, expert_offset=6))
    with pytest.raises(ValueError, match="layers of unknown kind"):
        M.family(M.config_from_arch(dict(ARCH, layer_types=[M.FULL, "ssm"])))


# -- (h) the scheduler -----------------------------------------------------------------

@pytest.fixture()
def engine(tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    eng = build_engine(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(name="mel", builder="mellum", dtype="float32",
                            batch_buckets=(1,), seq_buckets=(8, 24),
                            coalesce_ms=1.0, extra=EXTRA)]))
    yield eng
    eng.shutdown()


async def test_served_streams_are_the_reference_s_greedy_and_rows_are_counted(
        engine, tree):
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    cm = engine.model("mel")
    rng = np.random.default_rng(2)
    prompts = [[int(t) for t in rng.integers(0, 96, n)] for n in (20, 5, 11)]
    sched = GenerationScheduler(cm, engine.runner, cm.cfg).start()
    try:
        reqs = [sched.submit(cm.servable.preprocess({"input_ids": ids}))
                for ids in prompts]
        served = [await asyncio.wait_for(r.done, 120) for r in reqs]
        snap = sched.gen_snapshot()
    finally:
        await sched.stop()
    for ids, toks in zip(prompts, served):
        assert len(toks) == 16
        ref = _reference(tree, ids + toks[:-1])[len(ids) - 1:]
        # Each served token within the tolerance of the reference's best.
        assert max(float(r.max() - r[t]) for r, t in zip(ref, toks)) < TOL
        sample = cm.servable.preprocess({"input_ids": ids})
        assert cm.run_batch([sample])[0][0]["tokens"] == toks  # fixed batch
    assert snap["prefill_dispatches"] == 3  # one prompt a dispatch
    assert snap["prefill_buckets"] == {"8": 1, "24": 2}
    rounds = snap["segment_rounds"]
    by_kind = snap["span_rows_by_kind"]
    assert set(by_kind) == {M.FULL, M.WINDOW}
    assert by_kind[M.FULL]["count"] == by_kind[M.WINDOW]["count"] == rounds
    # A ring holds the window at most, a full layer every position.
    assert 0 < by_kind[M.WINDOW]["sum"] < by_kind[M.FULL]["sum"]
    assert snap["span_rows"]["sum"] == sum(v["sum"] for v in by_kind.values())
    assert snap["live_positions"]["sum"] == 2 * by_kind[M.FULL]["sum"]
    assert snap["window_rolls"] == 0  # a ring's span starts at its row 0
    # Of the pool's rows, a kind's layers weighing its own: one full layer
    # of 40 rows a slot and three rings of 8.
    assert snap["kv_live_share"]["sum"] == pytest.approx(
        (by_kind[M.FULL]["sum"] + 3 * by_kind[M.WINDOW]["sum"])
        / (3 * (40 + 3 * W)), rel=1e-5)
    assert snap["kv_live_share"]["sum"] <= snap["kv_read_share"]["sum"] \
        <= rounds
