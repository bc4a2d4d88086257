"""models/joyai.py through models/decoder.py's seam, at tiny widths.

The family (latent attention whose slot keeps one leaf of ``[c, k_rope]``
rows, a dense feed-forward in the leading layer and 4 of 16 gated experts
with a shared expert behind a sigmoid router in the rest) against the plain
reference (benchmark/reference/joyai.py, the non-absorbed form): logits of the
prefill and of decode steps through the pool; the absorbed decode step against
the expanded attention; the kernel over one pool operand against the
``jax.numpy`` form; ``flash_attention`` with values narrower than keys; the
router at scale 2.5; eight shares against the uncut layer; the rotation; the
one leaf the servable declares and the paged lane's refusal; the trunk's trace
count; the lane's served streams and counters; the three controls.
"""

import asyncio
import dataclasses
import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fresh_pool

from benchmark.reference import joyai as reference
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.cache import CompileClock
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import joyai as M
from pytorch_zappa_serverless_tpu.ops import decode_attention as DA
from pytorch_zappa_serverless_tpu.ops import expert_matmul as E
from pytorch_zappa_serverless_tpu.ops.flash_attention import flash_attention
from pytorch_zappa_serverless_tpu.serving.generation import build_gen_kernels

pytest_plugins = "aiohttp.pytest_plugin"  # runs the coroutine tests

# One dense layer, then two expert layers; this chip holds experts [4, 8) of
# 16, the second of four shares.
ARCH = {"vocab_size": 96, "hidden_size": 64, "layers": 3, "heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "nope_dim": 16, "rope_dim": 8,
        "v_dim": 16, "dense_layers": 1, "dense_width": 96,
        "experts_published": 16, "experts_held": 4, "expert_offset": 4,
        "top_k": 4, "expert_width": 48, "rope_theta": 100.0,
        "max_positions": 512, "init_std": 0.1, "eos_id": 96}
CFG = M.config_from_arch(ARCH)
KEYS = {k: getattr(CFG, k) for k in (
    "layers", "dense_layers", "heads", "kv_lora_rank", "nope_dim", "rope_dim",
    "top_k", "routed_scale", "expert_offset", "rope_theta", "norm_eps")}
EXTRA = {"max_new_tokens": 16, "gen_slots": 3, "segment_tokens": 4,
         "arch": ARCH}
ROW = CFG.row_stored  # 128: 32 of latent, 8 of the shared rotated key, zeros
# float32 at ``highest`` against float32 at ``highest``: two orders of
# summation (the absorbed step sums over the latent where the reference sums
# over a head), and the router's 1e-20 the program leaves out.  Logits here
# spread over about 2.5; a bfloat16 product moves them by 1e-2, each control
# by more (the last tests).
TOL = 2e-4


@pytest.fixture(scope="module")
def tree():
    tree = M.init_joyai_params(0, CFG)
    # A bias that moves the choice, as a staged tree's does.
    for i in range(CFG.dense_layers, CFG.layers):
        tree[f"layer{i}"]["expert_bias"] = np.random.default_rng(
            [7, i]).normal(0, 0.05, CFG.experts_published).astype(np.float32)
    return tree


@pytest.fixture(scope="module")
def servable(tree):
    from pytorch_zappa_serverless_tpu.models.vision_common import (
        resolve_dtype)

    mc = ModelConfig(name="joyai", dtype="float32", batch_buckets=(1,),
                     seq_buckets=(8, 24), extra=EXTRA)
    return D.make_servable("joyai", mc,
                           M.family(CFG, resolve_dtype("float32")),
                           jax.tree.map(np.asarray, tree))


def _reference(tree, ids, control=None):
    return reference.forward(tree, ids, KEYS, control)


# -- (a) the programs against the reference's full forward pass ----------------

# Prompts, one a prefill dispatch (bucket 24), the slot each goes to, and the
# request that had the slot before it (None: a fresh pool).
PROGRAM_CASES = {
    "three prompts, three slots": ([24, 5, 11], [0, 1, 2], None),
    "a one-token prompt": ([1, 24], [2, 0], None),
    "a slot re-used after a longer request": ([9, 14], [1, 0], [23, 16]),
}


def _admit(kernels, params, cache, prompts, slots):
    """A prefill a prompt (``LatentRows.prefill_batch`` is 1) into ``slots``
    of the pool → ``(cache, first tokens)``."""
    first = []
    for ids, slot in zip(prompts, slots):
        toks = np.zeros((1, 24), np.int32)
        toks[0, :len(ids)] = ids
        payload = {"input_ids": toks,
                   "length": np.asarray([len(ids)], np.int32),
                   "temperature": np.zeros(1, np.float32),
                   "seed": np.zeros(1, np.int32),
                   "top_k": np.zeros(1, np.int32),
                   "top_p": np.ones(1, np.float32)}
        tok, *cache = kernels["prefill"](params, tuple(cache),
                                         np.asarray([slot], np.int32), payload)
        first.append(int(tok[0]))
    return tuple(cache), np.asarray(first)


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
        assert len(cache) == 1  # the one leaf
        if earlier:
            before = [[int(t) for t in rng.integers(0, 96, n)]
                      for n in earlier]
            cache, first = _admit(kernels, params, cache, before, slots)
            tok, pos, fin = zi.copy(), zi.copy(), np.ones(S, bool)
            tok[slots], pos[slots], fin[slots] = first, earlier, False
            cache, _ = segment(cache, tok, pos, fin)
        prompts = [[int(t) for t in rng.integers(0, 96, n)] for n in lengths]
        cache, first = _admit(kernels, params, cache, prompts, slots)
        tok, pos, fin = zi.copy(), zi.copy(), np.ones(S, bool)
        tok[slots], pos[slots], fin[slots] = first, lengths, False
        kept = cache  # the segment below donates its own copy
        cache, packed = segment(tuple(jnp.array(leaf) for leaf in cache),
                                tok, pos, fin)
        emits, counts = packed[:, :seg], packed[0, seg + 4:]
        # The same steps once more for their logits, a token at a time.
        pool, _ = D.slot_pools(fam, kept)
        step_cache, logits = kept, []
        for t in range(seg):
            wpos = jnp.asarray(pos + t)
            lg, step_cache, _ = D._decode_logits(
                fam, params, pool, step_cache, jnp.asarray(emits[:, t]), wpos,
                pool.span(wpos), None, jnp.float32)
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
    assert [leaf.shape for leaf in cache] == [(3, 3, 40, ROW)]
    # Two expert layers, four steps, every slot's row routed (the finished
    # slots' too): at most 4 experts a row here, at least one reached a step.
    assert 0 < counts[0] <= 2 * seg * S * 4 and 2 * seg <= counts[1]


def test_decode_continues_from_a_prefill_s_rows(tree):
    """The rows after a prefill of n tokens and k decode steps are those of
    a prefill of n + k tokens: a decode step writes what the prompt pass
    writes, normed and turned."""
    fam = M.family(CFG, jnp.float32)
    params = jax.tree.map(jnp.asarray, tree)
    ids = np.random.default_rng(3).integers(0, 96, (1, 16)).astype(np.int32)
    n, k = 10, 6
    one = jnp.asarray([n], jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, short = fresh_pool.prefill(fam, params, jnp.asarray(ids), one, 24,
                                      jnp.float32)
        full_logits, full = fresh_pool.prefill(
            fam, params, jnp.asarray(ids), one + k, 24, jnp.float32)
        pool, _ = D.slot_pools(fam, (short,))
        cache = (short,)
        for t in range(k):
            wpos = one + t
            logits, cache, _ = D._decode_logits(
                fam, params, pool, cache, jnp.asarray(ids[:, n + t]), wpos,
                pool.span(wpos), None, jnp.float32)
    assert np.abs(np.asarray(logits) - np.asarray(full_logits)).max() < 1e-4
    got, want = np.asarray(cache[0]), np.asarray(full)
    assert np.abs(got[:, :, :n + k] - want[:, :, :n + k]).max() < 1e-4
    # The row's 40 values, then zeros up to whole lane tiles.
    assert np.abs(got[:, :, :n + k, :CFG.row_width]).max() > 1e-3
    assert not got[..., CFG.row_width:].any()
    assert not got[:, :, n + k:].any()  # nothing past the last position


# -- (b) the absorbed step ----------------------------------------------------------

def test_the_absorbed_step_is_the_expanded_attention(tree):
    """``absorb``, the rows scored whole and their first columns summed,
    ``expand``: the reference's attention, which expands K and V a head for
    every position, at the last position of a sequence."""
    p = jax.tree.map(jnp.asarray, tree["layer1"])
    rows = M.LatentRows(CFG)
    n = 13
    x = jnp.asarray(np.random.default_rng(4).standard_normal((n, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference.attention(p, x, KEYS)                      # [n, D]
        q, row = M._latent(CFG, p, x[None], jnp.arange(n))          # [1, n, .]
        pool = row[None]                      # one layer, one slot: [1,1,n,R]
        got = rows.expand(p, DA.attend_latent(
            rows.absorb(p, q[:, -1:]), pool, 0,
            jnp.asarray([[n - 1]]), CFG.heads, rows.values))
        got = got[0] @ p["o"]
    assert np.abs(np.asarray(got[0]) - np.asarray(want[-1])).max() < 2e-6
    assert rows.absorb(p, q[:, -1:]).shape == (1, 1, CFG.heads * ROW)


# -- (c) the kernel over one pool operand ---------------------------------------------

# heads, row width, value columns, rows a slot, rows a block.
SHAPES = {"32 queries over 640, values 512": (32, 640, 512, 256, 64),
          "4 queries over 40, values 32 (padded to 16 rows)": (4, 40, 32, 64,
                                                              16),
          "blocks of 48 rows, which no 32 values make up": (4, 40, 32, 192,
                                                            48)}
# The last row each of 5 slots reads (negative: dead) and the first, by the
# rows a slot holds and the rows a block.  The kernel copies its blocks itself,
# some grid steps ahead, so the spans end and start on every side of a block's
# edge, leave dead slots between live ones, and make lists shorter than the
# blocks in flight.
SPANS = {
    "ragged, one dead": lambda T, bt: ([-1, 255, 5, 17, 0], None),
    "all dead": lambda T, bt: ([-1, -1, -1, -1, -1], None),
    "spans with a start": lambda T, bt: ([63, -1, 40, 17, 9],
                                         [0, 0, 33, 16, 9]),
    "ends inside the first block, on its edge and a row past it":
        lambda T, bt: ([bt // 2, bt - 1, bt, bt - 2, bt + 1], None),
    "a span of one row": lambda T, bt: ([0, -1, -1, -1, -1], None),
    "a span of one row in a later block":
        lambda T, bt: ([-1, -1, 2 * bt + 3, -1, -1], [0, 0, 2 * bt + 3, 0, 0]),
    "a first inside a later block":
        lambda T, bt: ([T - 1, 3 * bt, 2 * bt + 1, T - 2, bt],
                       [2 * bt + 1, bt + bt // 2, 2 * bt, 3 * bt + 1, bt]),
    "dead slots between live ones":
        lambda T, bt: ([T - 1, -1, bt, -1, 2 * bt - 1], None),
    "two blocks in all": lambda T, bt: ([-1, -1, -1, bt + 1, -1], None),
    "every row of every slot": lambda T, bt: ([T - 1] * 5, None),
}


def _latent_case(shape, spans, seed=0):
    """(q, pool with the rows no span holds spoiled, last, first, want)."""
    heads, width, values, T, bt = SHAPES[shape]
    last, first = SPANS[spans](T, bt)
    rng = np.random.default_rng(seed)
    S, L = 5, 3
    last = jnp.minimum(jnp.asarray(last, jnp.int32), T - 1)
    first = None if first is None else jnp.asarray(first, jnp.int32)
    q = jnp.asarray(rng.standard_normal((S, 1, heads * width)) * width ** -0.5,
                    jnp.float32)
    pool = jnp.asarray(rng.standard_normal((L, S, T, width)), jnp.float32)
    held = ((jnp.arange(T)[None] <= last[:, None])
            & (jnp.arange(T)[None] >= (0 if first is None
                                       else first[:, None])))
    want = DA.attend_latent(q, pool, 1, last[:, None], heads, values, None,
                            None if first is None else first[:, None])
    # Rows no live span holds reach nothing, whatever they hold (finite: a
    # masked row of a visited block weighs an exact zero).
    pool = jnp.where(held[None, :, :, None], pool, 1e3)
    return q, pool, last, first, want


@pytest.mark.parametrize("spans", list(SPANS))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_latent_kernel_reads_its_span_and_is_the_jnp_form(shape, spans):
    heads, width, values, T, bt = SHAPES[shape]
    q, pool, last, first, want = _latent_case(shape, spans)
    got = DA.latent_attention(q[:, 0], pool, last, None, first, layer=1,
                              heads=heads, values=values, block_t=bt,
                              interpret=True)
    assert got.shape == (5, heads * values)
    assert np.abs(np.asarray(got) - np.asarray(want[:, 0])).max() < 2e-6
    assert not np.asarray(got)[np.asarray(last) < 0].any()
    if spans != "all dead":
        assert np.abs(np.asarray(got)).max() > 0.01


@pytest.mark.parametrize("buffers", [1, 2, 4, 7])
@pytest.mark.parametrize("spans", ["ragged, one dead",
                                   "a first inside a later block",
                                   "two blocks in all"])
def test_the_latent_kernel_reads_the_same_whatever_blocks_it_keeps_in_flight(
        spans, buffers):
    """One buffer copies and reads in turn, two is the order of the
    pipeline's own copies, seven is more than the blocks of most lists here."""
    shape = "4 queries over 40, values 32 (padded to 16 rows)"
    heads, width, values, T, bt = SHAPES[shape]
    q, pool, last, first, want = _latent_case(shape, spans, seed=1)
    got = DA.latent_attention(q[:, 0], pool, last, None, first, layer=1,
                              heads=heads, values=values, block_t=bt,
                              buffers=buffers, interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(want[:, 0])).max() < 2e-6
    assert not np.asarray(got)[np.asarray(last) < 0].any()


def test_the_latent_kernel_refuses_a_block_that_does_not_divide_the_rows():
    shape = "4 queries over 40, values 32 (padded to 16 rows)"
    heads, width, values, T, bt = SHAPES[shape]
    q, pool, last, first, _ = _latent_case(shape, "ragged, one dead")
    with pytest.raises(ValueError, match="block_t 48 does not divide"):
        DA.latent_attention(q[:, 0], pool, last, None, first, layer=1,
                            heads=heads, values=values, block_t=48,
                            interpret=True)


def test_the_latent_kernel_s_call_has_one_pool_operand():
    """The grid's bound, five prefetched scalars, the query block and the
    leaf, once: the values come out of the block of rows the kernel already
    holds."""
    S, T, heads, width, values = 4, 128, 32, 640, 512
    jaxpr = jax.make_jaxpr(lambda q, pool, last: DA.latent_attention(
        q, pool, last, layer=2, heads=heads, values=values, block_t=64,
        interpret=True))(
            jax.ShapeDtypeStruct((S, heads * width), jnp.bfloat16),
            jax.ShapeDtypeStruct((3, S, T, width), jnp.bfloat16),
            jax.ShapeDtypeStruct((S,), jnp.int32))

    def calls(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    assert call.params["name"] == "latent_attention"
    shapes = [v.aval.shape for v in call.invars]
    assert shapes.count((3, S, T, width)) == 1 and len(shapes) == 8
    assert [v.aval.shape for v in call.outvars] == [(S, 32, values)]
    # 9,216 rows of 640 in bfloat16: blocks of 384, 24 a slot.
    assert DA.pick_block_t(9216, 640, jnp.bfloat16) == 384
    assert DA.fits_vmem(384, 640, jnp.bfloat16)


# -- (d) the prompt's attention ----------------------------------------------------------

@pytest.mark.parametrize("P, blocks", [(300, (128, 128)), (200, (256, 128))])
def test_flash_attention_with_values_narrower_than_keys_is_the_masked_form(
        P, blocks):
    """Keys of 24, values of 16 a head: the kernel (interpreted) against a
    ``jax.numpy`` attention over ``[heads, P, P]`` scores."""
    B, H, D_, Dv = 2, 4, 24, 16
    rng = np.random.default_rng(2)
    q, k = (jnp.asarray(rng.standard_normal((B, P, H, D_)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((B, P, H, Dv)), jnp.float32)
    got = flash_attention(q, k, v, causal=True, block_q=blocks[0],
                          block_k=blocks[1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D_ ** -0.5
    s = jnp.where(jnp.arange(P)[:, None] >= jnp.arange(P)[None], s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    assert got.shape == (B, P, H, Dv)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5


# sha256 of ``flash_attention``'s traced text with values as wide as keys, the
# parent's (PR 54, commit 3ef190a) letter for letter: the value width reaches
# no program that does not ask for it (LFM2's and Mellum 2's prefills).
FLASH_TEXT = {
    "grouped causal": ((1, 2048, 8, 64), (1, 2048, 2, 64), {"causal": True},
                       "66416124485a76d65d7efb271e0a71a4943b7d28f6f6c6814c7e0"
                       "caf0f2ffa58"),
    "band": ((1, 2048, 8, 128), (1, 2048, 1, 128),
             {"causal": True, "window": 1024},
             "2f007ff3425d58704e5c6750131aa416f9ae06d1f8514818ab0ebe5f7aa5b9"
             "ce"),
    "masked": ((2, 1500, 4, 64), (2, 1500, 4, 64), {},
               "8ca4b249c8cb89caf2f888a7c7275930fed3f6d1fe2b40d19348116c7a6ec"
               "393"),
}


@pytest.mark.parametrize("case", list(FLASH_TEXT))
def test_flash_attention_with_values_as_wide_traces_to_the_parent_s_text(
        case):
    q, kv, kw, pinned = FLASH_TEXT[case]
    sd = jax.ShapeDtypeStruct
    text = str(jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, **kw))(
            sd(q, jnp.bfloat16), sd(kv, jnp.bfloat16), sd(kv, jnp.bfloat16)))
    assert hashlib.sha256(text.encode()).hexdigest() == pinned, (
        f"flash_attention traces to other text than the parent's at {case} "
        f"(jax {jax.__version__}, pinned under 0.9.0)")


def test_the_prompt_s_two_forms_are_one_attention(tree, monkeypatch):
    """``flash_mla`` (steered, the backend being the CPU, where the kernel
    is interpreted) against ``mla``, and the rows written into the pool
    once, as they came."""
    p = jax.tree.map(jnp.asarray, tree["layer2"])
    rows = M.LatentRows(CFG)
    B, P = 2, 200
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((B, P, 64)), jnp.float32)
    lengths = jnp.asarray([P, 77], jnp.int32)
    q, row = M._latent(CFG, p, x, jnp.arange(P))
    cache = (jnp.zeros((3, B, P + 8, ROW)),)
    put = D.slot_put(jnp.arange(B))
    assert rows.prompt_form(B, CFG.heads, P, None) == "mla"  # the CPU's
    want_cache, want = rows.prompt(CFG.heads, lengths, P, put)(
        p, cache, jnp.int32(1), q, row)
    monkeypatch.setattr(M.LatentRows, "prompt_form",
                        lambda self, *shape: "flash_mla")
    got_cache, got = rows.prompt(CFG.heads, lengths, P, put)(
        p, cache, jnp.int32(1), q, row)
    assert got.shape == (B, P, CFG.heads * CFG.v_dim)
    for b, n in enumerate((P, 77)):  # rows past a length mean nothing
        assert np.abs(np.asarray(got[b, :n]) - np.asarray(want[b, :n])).max() \
            < 2e-5
    assert len(got_cache) == 1
    assert np.array_equal(np.asarray(got_cache[0]), np.asarray(want_cache[0]))
    assert np.array_equal(np.asarray(got_cache[0][1, :, :P]), np.asarray(row))
    assert not np.asarray(got_cache[0][0]).any()


# -- (e) the router, the share, the rotation -----------------------------------------

def test_route_at_scale_2_5_is_the_reference_s_choice_and_weights(tree):
    p = jax.tree.map(jnp.asarray, tree["layer1"])
    x = jnp.asarray(np.random.default_rng(9).standard_normal((20, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.routing(p, x, KEYS))          # [20, 16]
        weights, group = E.route(x, p["router"], p["expert_bias"], CFG.top_k,
                                 CFG.routed_scale, 0, 16)
    weights, group = np.asarray(weights), np.asarray(group)
    assert (np.sort(group, -1) == np.sort(
        np.argsort(-want, -1)[:, :CFG.top_k], -1)).all()
    assert np.abs(np.take_along_axis(want, group, -1) - weights).max() < 1e-6
    assert np.abs(weights.sum(-1) - 2.5).max() < 1e-5
    # The bias moves the choice and not the weights.
    plain, _ = E.route(x, p["router"], 0 * p["expert_bias"], CFG.top_k,
                       CFG.routed_scale, 0, 16)
    assert not np.allclose(np.sort(np.asarray(plain), -1),
                           np.sort(weights, -1))


def test_eight_shares_add_up_to_the_uncut_layer_with_one_shared_expert(tree):
    """The layer's 16 experts cut into eight chips' parts, each holding 2
    behind the whole router with the shared expert whole: the parts' routed
    sums and the shared expert **once** are the uncut layer."""
    rng = np.random.default_rng([6, 1])
    layer = dict(tree["layer1"])
    layer.update({m: (rng.standard_normal((16,) + layer[m].shape[1:]) * 0.1
                      ).astype(np.float32) for m in ("w1", "w3", "w2")})
    x = rng.standard_normal((12, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        whole = jax.tree.map(jnp.asarray, layer)
        xs = jnp.asarray(x)
        uncut = np.asarray(reference.experts(
            whole, xs, dict(KEYS, expert_offset=0)))
        shared = np.asarray(reference._gated(
            xs, whole["shared_w1"], whole["shared_w3"], whole["shared_w2"]))
        parts = []
        for offset in range(0, 16, 2):
            mine = dict(whole, **{m: whole[m][offset:offset + 2]
                                  for m in ("w1", "w3", "w2")})
            parts.append(np.asarray(reference.experts(
                mine, xs, dict(KEYS, expert_offset=offset))))
            cfg = dataclasses.replace(CFG, experts_held=2,
                                      expert_offset=offset)
            got = M._experts(cfg, mine, xs[None], lambda c: None)[0]
            assert np.abs(np.asarray(got) - parts[-1]).max() < TOL
    assert np.abs(sum(part - shared for part in parts) + shared
                  - uncut).max() < TOL
    assert min(np.abs(part - shared).max() for part in parts) > 0.01
    assert np.abs(shared).max() > 0.01


def test_the_rotation_pairs_adjacent_columns_at_the_published_theta():
    """Position ``p`` turns columns ``(2i, 2i + 1)`` by ``p x
    32,000,000^(-2i/64)``; position 0 is the identity."""
    assert M.PUBLISHED.rope_theta == 32_000_000 and M.PUBLISHED.rope_dim == 64
    rng = np.random.default_rng(10)
    x = rng.standard_normal((1, 3, 2, 64)).astype(np.float32)
    pos = np.asarray([0, 5, 4096])
    got = np.asarray(M._turned(jnp.asarray(x), jnp.asarray(pos), 32e6))
    assert np.abs(got[0, 0] - x[0, 0]).max() < 1e-6
    for i in (0, 7, 31):
        ang = pos.astype(np.float64) * 32e6 ** (-2 * i / 64)
        a, b = x[0, :, :, 2 * i], x[0, :, :, 2 * i + 1]
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        # float32 angles: 4,096 radians carry an error of 2e-4.
        assert np.abs(got[0, :, :, 2 * i] - (a * cos - b * sin)).max() < 2e-3
        assert np.abs(got[0, :, :, 2 * i + 1]
                      - (b * cos + a * sin)).max() < 2e-3
    # The program's rotation is the reference's.
    ref = np.asarray(reference._turned(jnp.asarray(x[0, :, :, :8]), 100.0))
    mine = np.asarray(M._turned(jnp.asarray(x[:, :, :, :8]),
                                jnp.arange(3), 100.0))[0]
    assert np.abs(ref - mine).max() < 1e-6


# -- (f) one leaf, no paged lane, two traces --------------------------------------------

def test_the_servable_declares_one_leaf_of_1280_bytes_a_row(servable):
    meta = servable.meta["continuous"]
    assert [shape for shape, _ in meta["cache_leaves"]] == [(3, 3, 40, ROW)]
    assert meta["kinds"] == () and meta["rows"].values == 32
    assert list(meta["counters"]) == [
        "expert_assignments_held", "experts_touched", "expert_load_max"]
    assert meta["rows"].prefill_batch(24) == 1
    assert meta["prompt_form"](1, 24) == "mla"
    assert meta["expert_plan"](16)["regime"] == "stream"
    # The benchmark's configuration: 10 layers, 64 slots of 9,216 rows.
    cfg = M.config_from_arch({"layers": 10, "experts_held": 32})
    fam = M.family(cfg)
    assert (cfg.row_width, fam.width, fam.rows.values, fam.heads,
            fam.kv_heads) == (576, 640, 512, 32, 1)
    (shape, dtype), = D.cache_leaves(fam, 64, fam.rows.count(8192 + 1024),
                                     jnp.bfloat16)
    # 576 values a row (1,152 B), stored in five whole lane tiles.
    assert shape == (10, 64, 9216, 640)
    assert shape[-1] * jnp.dtype(dtype).itemsize == 1280
    assert D.row_leaves(fam) == 1 and D.row_leaves(M.family(CFG)) == 1


def test_paged_lane_is_refused_at_build(servable):
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

    assert servable.meta["continuous"]["paged"] is None
    with pytest.raises(ValueError, match="kv_cache='paged' cannot serve "
                                         "this family.*1 leaves a row"):
        get_model_builder("joyai")(ModelConfig(
            name="joyai", dtype="float32", batch_buckets=(1,),
            seq_buckets=(8, 24), kv_cache="paged", extra=EXTRA))
    with pytest.raises(ValueError, match="not among the 16 published"):
        M.config_from_arch(dict(ARCH, experts_held=4, expert_offset=14))


def test_layer_traces_is_two_at_most(servable):
    """The dense layer's tree and the expert layers'."""
    meta = servable.meta["continuous"]
    S = meta["slots"]
    cache = tuple(jnp.zeros(shape, dt) for shape, dt in meta["cache_leaves"])
    zf, zi = jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)
    clock = CompileClock()
    with clock.open("m", "segment", {}, seen=set()):
        jax.jit(meta["segment"])(servable.params, cache, zi, zi + 3, zi,
                                 zi != 0, zf, zi, zi, zf + 1)
    assert clock.snapshot()[-1]["layer_traces"] == 2  # of 3 layers
    payload = {k: jnp.zeros(v.shape, v.dtype)
               for k, v in meta["admit_spec"](8).items()}
    with clock.open("m", "prefill", {"batch": 1, "bucket": 8}, seen=set()):
        jax.jit(meta["prefill"])(servable.params, cache, zi[:1],
                                 {**payload, "length": jnp.ones(1, jnp.int32)})
    assert clock.snapshot()[-1]["layer_traces"] == 2


# -- (g) the scheduler ---------------------------------------------------------------------

@pytest.fixture()
def engine(tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    eng = build_engine(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(name="joy", builder="joyai", dtype="float32",
                            batch_buckets=(1,), seq_buckets=(8, 24),
                            coalesce_ms=1.0, extra=EXTRA)]))
    yield eng
    eng.shutdown()


async def test_served_streams_are_the_reference_s_greedy_and_counters_count(
        engine):
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    cm = engine.model("joy")
    tree = M.init_joyai_params(0, CFG)  # the builder's own seeded tree
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
    # A row is a position: what the live spans hold is what was written.
    assert snap["span_rows"]["count"] == rounds
    assert snap["span_rows"]["sum"] == snap["live_positions"]["sum"] > 0
    assert 0 < snap["kv_live_share"]["sum"] <= snap["kv_read_share"]["sum"] \
        <= rounds
    held, touched, most = (snap[name] for name, _ in E.COUNTERS)
    assert held["count"] == touched["count"] == most["count"] == rounds
    # Two expert layers a step, four steps a round, over the 4 held experts.
    assert 0 < touched["sum"] <= held["sum"]
    assert touched["sum"] // 4 <= most["sum"] <= held["sum"]
    assert set(snap["step_counters"]) == {name for name, _ in E.COUNTERS}


# -- (h) the three controls ------------------------------------------------------------------

@pytest.mark.parametrize("control", reference.CONTROLS)
def test_each_control_is_caught(tree, control):
    """The tolerance holds the sound path (above) and fails the reference
    with one thing changed: weights through int8, the rope part of the score
    left out, the latent kept without its norm."""
    fam = M.family(CFG, jnp.float32)
    params = jax.tree.map(jnp.asarray, tree)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 96, (1, 24)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, _ = fresh_pool.prefill(fam, params, jnp.asarray(toks),
                                       jnp.asarray([24], jnp.int32), 40,
                                       jnp.float32)
    ids = toks[0].tolist()
    ref = _reference(tree, ids)[-1]
    assert np.abs(np.asarray(logits[0]) - ref).max() < TOL
    assert np.abs(_reference(tree, ids, control)[-1] - ref).max() > 10 * TOL
