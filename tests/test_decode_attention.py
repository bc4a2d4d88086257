"""Decode attention that reads the slot pool where it lies.

``ops/decode_attention``'s ``attend`` (the ``jax.numpy`` form the CPU and the
several-queries-a-slot callers run) and its ``decode_attention`` (the Pallas
kernel of the same contraction, here through its interpret mode) against a
float32 head-split reference: at the published widths of the benchmark's two
configurations (25 heads of 64 at d 1600, 20 of 64 at d 1280; ``T`` and ``L``
cut, not the widths), slots at different lengths (0, ``total - 1``, the clamp
at ``total``), rows that hold garbage beyond their last written position,
bfloat16 and float32 pools — the attention's output, and the logits of a
whole decode step built on it.  A slot with ``wpos < 0`` is dead: both forms
read nothing of its row and return zeros for it, and the kernel visits the
live blocks of its work list and no other.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import gpt2 as G
from pytorch_zappa_serverless_tpu.ops import decode_attention as DA
from pytorch_zappa_serverless_tpu.ops.decode_attention import (
    decode_attention, fits_vmem, pick_block_t, work_list)

WIDTHS = [(1600, 25), (1280, 20)]
# And EvaByte's row (32 heads of 128), whose slots read a span with a start.
WIDTHS_SPAN = WIDTHS + [(4096, 32)]
T, L = 48, 2
BT = 16  # the block length of every kernel case here
# pos per slot: nothing but its own row, mid-block, a block edge, the last
# row, and one past the pool (the segment clamps it to the last row).
POS = [0, 5, 16, T - 1, T]


def _pool(rng, S, D, dtype, garbage_beyond=None):
    """A pool whose slots hold values up to their own length and zeros (or
    ``garbage_beyond``) past it."""
    wpos = np.minimum(np.asarray(POS[:S]), T - 1)
    out = []
    for _ in range(2):
        a = rng.standard_normal((L, S, T, D)).astype(np.float32)
        dead = np.arange(T)[None, :] > wpos[:, None]            # [S, T]
        a[:, dead] = 0.0 if garbage_beyond is None else garbage_beyond
        out.append(jnp.asarray(a, dtype))
    return out[0], out[1], jnp.asarray(wpos, jnp.int32)


def _reference(q, k, v, wpos, heads, first=None):
    """Head-split attention in float32, highest precision: q [S, Tq, D],
    k / v [S, T, D] one layer, wpos [S, Tq] (and ``first`` [S, Tq], the
    first row read) → [S, Tq, D]."""
    S, Tq, D = q.shape
    dh = D // heads
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    qh = q.reshape(S, Tq, heads, dh) * dh ** -0.5
    kh, vh = (a.reshape(S, -1, heads, dh) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh, precision="highest")
    keep = jnp.arange(k.shape[1])[None, None, :] <= wpos[:, :, None]
    if first is not None:
        keep &= jnp.arange(k.shape[1])[None, None, :] >= first[:, :, None]
    s = jnp.where(keep[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vh,
                      precision="highest").reshape(S, Tq, D)


def _run(impl, q, ck, cv, layer, wpos, heads, first=None):
    """The attention under test: [S, 1, D] in, [S, 1, D] out."""
    if impl == "jnp":
        return DA.attend(q, ck, cv, layer, wpos[:, None], heads,
                         first=None if first is None else first[:, None])
    dh = q.shape[-1] // heads
    return decode_attention((q * dh ** -0.5)[:, 0], ck, cv, wpos, None,
                            first, layer=layer, heads=heads, block_t=BT,
                            interpret=True)[:, None]


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("impl", ["jnp", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("D,heads", WIDTHS, ids=["xl", "large"])
def test_matches_float32_reference(D, heads, dtype, impl):
    rng = np.random.default_rng(D)
    S = len(POS)
    ck, cv, wpos = _pool(rng, S, D, dtype)
    q = jnp.asarray(rng.standard_normal((S, 1, D)), dtype)
    for layer in range(L):
        got = _run(impl, q, ck, cv, layer, wpos, heads)
        want = _reference(q, ck[layer], cv[layer], wpos[:, None], heads)
        assert got.shape == (S, 1, D) and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), atol=_tol(dtype),
                                   rtol=_tol(dtype))
    # The slot at position 0 reads its one row: the output is that row of V.
    np.testing.assert_allclose(np.asarray(got[0, 0], np.float32),
                               np.asarray(cv[L - 1, 0, 0], np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("impl", ["jnp", "kernel"])
def test_one_trace_reads_whichever_layer_it_is_handed(impl):
    """The layer is data: one jitted call, traced once, reaches every layer
    of a 3-layer pool (the kernel through a prefetched scalar its index maps
    read), and each answer is that layer's."""
    D, heads, S = 1280, 20, len(POS)
    rng = np.random.default_rng(9)
    two, more = (_pool(rng, S, D, jnp.float32) for _ in range(2))
    ck, cv = (jnp.concatenate([a, b[:1]]) for a, b in zip(two[:2], more[:2]))
    wpos = two[2]
    q = jnp.asarray(rng.standard_normal((S, 1, D)), jnp.float32)
    traces = []

    @jax.jit
    def run(layer):
        traces.append(layer)
        return _run(impl, q, ck, cv, layer, wpos, heads)

    for layer in range(3):
        want = _reference(q, ck[layer], cv[layer], wpos[:, None], heads)
        np.testing.assert_allclose(np.asarray(run(jnp.int32(layer))),
                                   np.asarray(want), atol=2e-5, rtol=2e-5)
    assert len(traces) == 1 and ck.shape[0] == 3


@pytest.mark.parametrize("impl", ["jnp", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("D,heads", WIDTHS, ids=["xl", "large"])
def test_garbage_beyond_wpos_changes_nothing(D, heads, dtype, impl):
    """What a row holds past its slot's last written position (an earlier
    request's keys, here 3e4 everywhere) weighs exactly zero."""
    S = len(POS)
    clean = _pool(np.random.default_rng(7), S, D, dtype)
    dirty = _pool(np.random.default_rng(7), S, D, dtype, garbage_beyond=3e4)
    q = jnp.asarray(np.random.default_rng(8).standard_normal((S, 1, D)),
                    dtype)
    a = _run(impl, q, clean[0], clean[1], 1, clean[2], heads)
    b = _run(impl, q, dirty[0], dirty[1], 1, dirty[2], heads)
    assert np.array_equal(np.asarray(a, np.float32),
                          np.asarray(b, np.float32))


@pytest.mark.parametrize("D,heads", WIDTHS, ids=["xl", "large"])
def test_several_queries_a_slot_equal_one_at_a_time(D, heads):
    """The speculative verify's K+1 queries a slot, each with its own last
    position, give what K+1 single-query calls give."""
    rng = np.random.default_rng(3)
    S, Tq = 3, 4
    ck, cv, _ = _pool(rng, S, D, jnp.float32)
    q = jnp.asarray(rng.standard_normal((S, Tq, D)), jnp.float32)
    wp = jnp.asarray([[0, 1, 2, 3], [20, 21, 22, 23],
                      [T - 4, T - 3, T - 2, T - 1]], jnp.int32)
    many = DA.attend(q, ck, cv, 0, wp, heads)
    for j in range(Tq):
        one = DA.attend(q[:, j:j + 1], ck, cv, 0, wp[:, j:j + 1], heads)
        np.testing.assert_allclose(np.asarray(many[:, j]),
                                   np.asarray(one[:, 0]), atol=1e-5,
                                   rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(many), np.asarray(_reference(q, ck[0], cv[0], wp, heads)),
        atol=2e-5, rtol=2e-5)


# wpos per slot, -1 a dead one: nobody live, one live, everybody at the last
# row, slots that end on a block's last row and on the next block's first,
# a slot of one position, and dead slots between live ones.
RAGGED = {
    "all-dead": [-1, -1, -1, -1],
    "one-live": [-1, -1, 21, -1],
    "all-full": [T - 1, T - 1, T - 1, T - 1],
    "block-edge": [BT - 1, BT, 2 * BT - 1, 2 * BT],
    "one-position": [0, -1, 0, 40],
    "dead-between": [-1, 33, -1, 7],
    # Spans with a start (``FIRST``): starting and ending inside blocks, a
    # row alone in mid-block and on a block's edges, and dead slots between
    # two spans (a dead slot's start is whatever its position says).
    "span-inside-blocks": [40, 30, 47, 33],
    "span-one-row": [7, 16, 31, 47],
    "span-dead-between": [-1, 29, -1, 47],
}
FIRST = {
    "span-inside-blocks": [5, 20, 0, 17],
    "span-one-row": [7, 16, 31, 47],
    "span-dead-between": [12, 10, 0, 33],
}


def _first(pattern):
    return (jnp.asarray(FIRST[pattern], jnp.int32) if pattern in FIRST
            else None)


def _ragged_pool(rng, wpos, D, dtype, dead_rows=0.0):
    """Random K and V over ``len(wpos)`` slots; a dead slot's rows hold
    ``dead_rows`` everywhere."""
    dead = np.asarray(wpos) < 0
    out = []
    for _ in range(2):
        a = rng.standard_normal((L, len(wpos), T, D)).astype(np.float32)
        a[:, dead] = dead_rows
        out.append(jnp.asarray(a, dtype))
    return out


@pytest.mark.parametrize("impl", ["jnp", "kernel"])
@pytest.mark.parametrize("pattern", list(RAGGED))
@pytest.mark.parametrize("D,heads", WIDTHS_SPAN, ids=["xl", "large", "eva"])
def test_ragged_pool_live_rows_match_and_dead_rows_are_zero(D, heads,
                                                            pattern, impl):
    rng = np.random.default_rng(D + len(pattern))
    wpos, first = jnp.asarray(RAGGED[pattern], jnp.int32), _first(pattern)
    ck, cv = _ragged_pool(rng, RAGGED[pattern], D, jnp.float32)
    q = jnp.asarray(rng.standard_normal((len(wpos), 1, D)), jnp.float32)
    got = np.asarray(_run(impl, q, ck, cv, 1, wpos, heads, first))
    want = np.asarray(_reference(
        q, ck[1], cv[1], jnp.maximum(wpos, 0)[:, None], heads,
        None if first is None else jnp.minimum(first, jnp.maximum(
            wpos, 0))[:, None]))
    live = np.asarray(wpos) >= 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
    assert not got[~live].any()


@pytest.mark.parametrize("impl", ["jnp", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("D,heads", WIDTHS, ids=["xl", "large"])
def test_dead_slot_is_zeros_and_its_nan_reaches_nobody(D, heads, dtype, impl):
    """NaN everywhere in a dead slot's rows of the pool: its own output is
    zeros (finite), every other slot's is bit for bit what a clean pool
    gives."""
    wpos = jnp.asarray(RAGGED["dead-between"], jnp.int32)
    clean = _ragged_pool(np.random.default_rng(9), RAGGED["dead-between"], D,
                         dtype)
    dirty = _ragged_pool(np.random.default_rng(9), RAGGED["dead-between"], D,
                         dtype, dead_rows=np.nan)
    q = jnp.asarray(np.random.default_rng(10).standard_normal(
        (len(wpos), 1, D)), dtype)
    a = np.asarray(_run(impl, q, *clean, 0, wpos, heads), np.float32)
    b = np.asarray(_run(impl, q, *dirty, 0, wpos, heads), np.float32)
    assert np.array_equal(a, b)
    assert not b[[0, 2]].any() and b[[1, 3]].any()


@pytest.mark.parametrize("pattern", list(RAGGED))
def test_work_list_holds_the_live_blocks_in_slot_order(pattern):
    wpos = RAGGED[pattern]
    slot, block, count = work_list(jnp.asarray(wpos, jnp.int32), T, BT,
                                   _first(pattern))
    lead = FIRST.get(pattern, [0] * len(wpos))
    want = [(s, b) for s, w in enumerate(wpos)
            for b in range(lead[s] // BT, w // BT + 1) if w >= 0]
    assert int(count) == len(want)
    assert slot.shape == block.shape == (len(wpos) * T // BT,)
    got = list(zip(slot.tolist(), block.tolist()))
    assert got[:len(want)] == want
    # What lies past the count is padding, and still a valid index.
    assert all(0 <= s < len(wpos) and 0 <= b < T // BT for s, b in got)


def _step_logits(params, ck, cv, tok, pos, cfg, dtype, attention):
    """One decode step as ``decode_segment`` runs it, returning the logits;
    ``attention(q, ck, cv, layer, wpos)`` is the part under test."""
    S, total = tok.shape[0], ck.shape[2]
    rows = jnp.arange(S)
    wpos = jnp.minimum(pos, total - 1)
    x = (params["wte"].astype(dtype)[tok]
         + params["wpe"].astype(dtype)[wpos])[:, None, :]
    for i in range(cfg.layers):
        def attend(q, k, v, i=i):
            nonlocal ck, cv
            ck = ck.at[i, rows, wpos].set(k[:, 0])
            cv = cv.at[i, rows, wpos].set(v[:, 0])
            return attention(q, ck, cv, i, wpos)

        x = G._layer(params[f"layer{i}"], x, cfg, attend)
    return G._logits(params, G._ln(params["ln_f"], x, cfg.ln_eps)[:, 0])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("D,heads", WIDTHS, ids=["xl", "large"])
def test_decode_step_logits_match_float32_reference(D, heads, dtype):
    """A whole decode step at the published widths (one layer, a small
    vocabulary): logits on the pool-layout attention against logits on the
    float32 head-split reference, slots at 0, mid, ``total - 1`` and the
    clamp at ``total``."""
    cfg = G.GPT2Config(vocab_size=384, d_model=D, layers=1, heads=heads,
                       ffn_dim=4 * D, max_positions=T, eos_id=383)
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(1, cfg))
    rng = np.random.default_rng(11)
    S = len(POS)
    ck, cv, _ = _pool(rng, S, D, dtype)
    ck, cv = ck[:1] * 0.3, cv[:1] * 0.3
    tok = jnp.asarray(rng.integers(0, 383, S), jnp.int32)
    pos = jnp.asarray(POS, jnp.int32)
    got = _step_logits(
        params, ck, cv, tok, pos, cfg, dtype,
        lambda q, k, v, i, w: DA.attend(q, k, v, i, w[:, None], heads))
    want = _step_logits(
        params, ck.astype(jnp.float32), cv.astype(jnp.float32), tok, pos,
        cfg, jnp.float32,
        lambda q, k, v, i, w: _reference(q, k[i], v[i], w[:, None], heads))
    assert got.dtype == jnp.float32 and got.shape == (S, 384)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)


def test_segment_finished_slot_touches_only_its_own_row():
    """A finished slot keeps computing (static shapes) with its position
    frozen: over a segment it rewrites one row of its own slot and nothing
    of any other's, and the live slots' tokens do not depend on it."""
    cfg = G.GPT2Config(vocab_size=96, d_model=32, layers=2, heads=2,
                       ffn_dim=64, max_positions=T, eos_id=95)
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(2, cfg))
    rng = np.random.default_rng(5)
    S = 3
    ck = jnp.asarray(rng.standard_normal((2, S, T, 32)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((2, S, T, 32)), jnp.float32)
    tok = jnp.asarray([3, 4, 5], jnp.int32)
    pos = jnp.asarray([6, 9, T], jnp.int32)  # the last is past the pool
    zeros = jnp.zeros((S,), jnp.int32)

    def run(finished, ck, cv):
        return D.decode_segment(G.family(cfg), params, D.slot_pool(ck, cv),
                                tok, pos, zeros, jnp.asarray(finished),
                                jnp.zeros((S,)), zeros, 4, jnp.float32)

    emits, k2, _, _, pos2, _, fin2 = run([False, True, False], ck, cv)
    assert pos2.tolist() == [10, 9, T + 4] and fin2.tolist()[1] is True
    assert emits[1].tolist() == [95] * 4  # pinned to EOS
    changed = np.argwhere(np.any(np.asarray(k2 != ck), axis=(0, 3)))
    assert {(s, p) for s, p in changed.tolist()} == (
        {(0, p) for p in range(6, 10)} | {(1, 9)} | {(2, T - 1)})
    # Slot 1's row of the pool replaced by garbage: slots 0 and 2 emit the same.
    dirty_k = ck.at[:, 1].set(3e4)
    dirty_v = cv.at[:, 1].set(-3e4)
    emits_dirty = run([False, True, False], dirty_k, dirty_v)[0]
    assert emits_dirty[0].tolist() == emits[0].tolist()
    assert emits_dirty[2].tolist() == emits[2].tolist()


def _through_kernel(monkeypatch):
    """Make ``attend`` take the kernel here, interpreted, as it does on one
    TPU chip: the choice is by backend, so the test steers it."""
    monkeypatch.setattr(
        DA, "_kernel_block",
        lambda Tq, total, d, dtype: BT if Tq == 1 else None)
    monkeypatch.setattr(DA, "decode_attention", functools.partial(
        decode_attention, interpret=True))


@pytest.mark.parametrize("paged", [False, True], ids=["slots", "paged"])
def test_segment_with_finished_slots_same_tokens_by_kernel_and_jnp(
        monkeypatch, paged):
    """A segment over a pool with finished and empty slots, and one that
    finishes inside the segment, emits the same tokens through the kernel
    (one work list a step) and through the ``jax.numpy`` form."""
    cfg = G.GPT2Config(vocab_size=96, d_model=32, layers=2, heads=2,
                       ffn_dim=64, max_positions=T, eos_id=95)
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(2, cfg))
    rng = np.random.default_rng(6)
    S = 4
    ck = jnp.asarray(rng.standard_normal((2, S, T, 32)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((2, S, T, 32)), jnp.float32)
    tok = jnp.asarray([3, 95, 5, 95], jnp.int32)  # slot 3 finishes at once
    pos = jnp.asarray([6, 9, 2 * BT - 2, 30], jnp.int32)
    zeros = jnp.zeros((S,), jnp.int32)
    finished = jnp.asarray([False, True, False, False])

    def run():
        if paged:  # one page a slot, so the view is the pool
            pool = D.PagedPool(ck, cv,
                               jnp.arange(S, dtype=jnp.int32)[:, None], T)
        else:
            pool = D.slot_pool(ck, cv)
        return D.decode_segment(G.family(cfg), params, pool, tok, pos, zeros,
                                finished, jnp.zeros((S,)), zeros, 6,
                                jnp.float32)

    plain = run()
    _through_kernel(monkeypatch)
    kernel = run()
    assert kernel[0].tolist() == plain[0].tolist()
    assert plain[0][1].tolist() == [95] * 6 and plain[0][3, 1:].tolist() == [95] * 5
    assert kernel[6].tolist() == plain[6].tolist() == [False, True, False, True]
    np.testing.assert_allclose(np.asarray(kernel[1]), np.asarray(plain[1]),
                               atol=1e-4, rtol=1e-4)


def test_segment_builds_one_work_list_a_step(monkeypatch):
    """The list of live blocks is built once a step and shared by every
    layer's kernel call: one ``work_list`` while the scan's body is traced,
    and the kernel inside the one traced layer that is called ``layers``
    times with its index as data."""
    _through_kernel(monkeypatch)
    built = []
    monkeypatch.setattr(DA, "work_list",
                        lambda *a: built.append(a[1:3]) or work_list(*a))
    cfg = G.GPT2Config(vocab_size=96, d_model=32, layers=3, heads=2,
                       ffn_dim=64, max_positions=T, eos_id=95)
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(2, cfg))
    S = 2
    pool = jnp.zeros((3, S, T, 32), jnp.float32)
    zeros = jnp.zeros((S,), jnp.int32)
    text = str(jax.make_jaxpr(
        lambda ck, cv: D.decode_segment(
            G.family(cfg), params, D.slot_pool(ck, cv), zeros, zeros + 4,
            zeros, zeros > 0, jnp.zeros((S,)), zeros, 4,
            jnp.float32))(pool, pool))
    assert built == [(T, BT)]
    assert "pallas_call[" in text and text.count("name=layer") == cfg.layers


@pytest.mark.parametrize("total,d,dtype,block,fits", [
    (960, 1600, jnp.bfloat16, 160, True), (960, 1280, jnp.bfloat16, 192, True),
    (96, 768, jnp.bfloat16, 96, True), (100, 1600, jnp.bfloat16, 100, True),
    (1024, 1600, jnp.float32, 64, True), (48, 128, jnp.float32, 48, True),
    (960, 65536, jnp.bfloat16, 960, False),
    (4100, 1600, jnp.bfloat16, 4100, False)])
def test_pick_block_t_divides_the_pool(total, d, dtype, block, fits):
    assert pick_block_t(total, d, dtype) == block
    assert total % block == 0
    assert fits_vmem(block, d, dtype) is fits


def test_kernel_rejects_a_block_that_does_not_divide_the_pool():
    z = jnp.zeros((1, 2, 48, 128), jnp.float32)
    with pytest.raises(ValueError, match="does not divide"):
        decode_attention(jnp.zeros((2, 128)), z, z, jnp.zeros((2,), jnp.int32),
                         layer=0, heads=2, block_t=32, interpret=True)
