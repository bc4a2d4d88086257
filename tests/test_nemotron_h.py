"""models/nemotron_h.py through models/decoder.py's seam, at tiny widths.

The family (three kinds of layer, Mamba-2 state beside a grouped-query K/V
pool, 4 of 16 experts held behind the full router) against the plain
reference (benchmark/reference/nemotron_h.py): logits of the prefill and of
decode steps through the pool; the chunked scan against the recurrence; the
share against the uncut layer; the grouped matmul in both its forms; the
counters; the trunk's trace count; the paged lane's refusal.
"""

import asyncio
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fresh_pool

from benchmark.reference import nemotron_h as reference
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.cache import CompileClock
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import nemotron_h as M
from pytorch_zappa_serverless_tpu.ops import expert_matmul as E
from pytorch_zappa_serverless_tpu.serving.generation import build_gen_kernels

ARCH = {"vocab_size": 96, "vocab_published": 384, "hidden_size": 64,
        "pattern": "MEM*EME", "heads": 4, "kv_heads": 2, "head_dim": 16,
        "mamba_heads": 8, "mamba_head_dim": 8, "ssm_state": 16,
        "n_groups": 2, "chunk_size": 8, "experts_published": 16,
        "experts_held": 4, "expert_offset": 4, "top_k": 3, "latent_size": 32,
        "expert_width": 48, "shared_width": 80, "max_positions": 512,
        "init_std": 0.1, "eos_id": 96}
CFG = M.config_from_arch(ARCH)
KEYS = {k: getattr(CFG, k) for k in (
    "pattern", "mamba_heads", "mamba_head_dim", "ssm_state", "n_groups",
    "conv_kernel", "heads", "kv_heads", "head_dim", "top_k", "routed_scale",
    "expert_offset", "norm_eps")}
EXTRA = {"max_new_tokens": 16, "gen_slots": 3, "segment_tokens": 4,
         "arch": ARCH}
TOL = 2e-4  # float32 at ``highest``: two orders of summation

pytest_plugins = "aiohttp.pytest_plugin"  # runs the async test


@pytest.fixture(scope="module")
def tree():
    return M.init_nemotron_params(0, CFG)


@pytest.fixture(scope="module")
def servable():
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

    return get_model_builder("nemotron_h")(ModelConfig(
        name="nemotron_h", dtype="float32", batch_buckets=(1,),
        seq_buckets=(8, 16), extra=EXTRA))


def _reference(tree, ids):
    return reference.forward(tree, ids, KEYS)


# -- (a) the programs against the reference's full forward pass ----------------

# Prompts of one prefill batch (bucket 16, chunks of 8), the slot each goes
# to, and the request that had the slot before it (None: a fresh pool).
PROGRAM_CASES = {
    "ragged prompts in one batch": ([16, 5, 11], [0, 1, 2], None),
    "a prompt shorter than a chunk": ([3, 16], [2, 0], None),
    "a slot re-used after another request": ([9, 14], [1, 0], [13, 6]),
}


def _admit(kernels, meta, params, cache, prompts, slots):
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
            cache, first = _admit(kernels, meta, params, cache, before, slots)
            tok, pos, fin = zi.copy(), zi.copy(), np.ones(S, bool)
            tok[slots], pos[slots], fin[slots] = first[:2], earlier, False
            cache, _ = segment(cache, tok, pos, fin)
        prompts = [[int(t) for t in rng.integers(0, 96, n)] for n in lengths]
        cache, first = _admit(kernels, meta, params, cache, prompts, slots)
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


def test_prefill_logits_are_the_reference_s(tree):
    fam = M.family(CFG, jnp.float32)
    params = jax.tree.map(jnp.asarray, tree)
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 96, (3, 24)).astype(np.int32)
    lens = np.asarray([24, 7, 17], np.int32)
    with jax.default_matmul_precision("highest"):
        logits, *cache = fresh_pool.prefill(fam, params, jnp.asarray(toks),
                                            jnp.asarray(lens), 40, jnp.float32)
    assert [c.shape for c in cache] == [
        (1, 3, 40, 32), (1, 3, 40, 32), (3, 3, 8, 8, 16), (3, 3, 3, 128)]
    for b in range(3):
        ref = _reference(tree, toks[b, :lens[b]].tolist())
        assert np.abs(np.asarray(logits[b]) - ref[-1]).max() < TOL


# -- (b) the chunked scan ----------------------------------------------------

def _recurrence(x, dt, A, B, C, h):
    ys = []
    for t in range(x.shape[1]):
        h = (np.exp(dt[:, t] * A)[..., None, None] * h
             + (dt[:, t, ..., None] * x[:, t])[..., None]
             * B[:, t, :, None, None, :])
        ys.append((h * C[:, t, :, None, None, :]).sum(-1))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("L,chunk", [(8, 8), (24, 8), (32, 16)])
def test_chunked_scan_is_the_sequential_recurrence(L, chunk):
    rng = np.random.default_rng(L)
    b, G, Hg, P, N = 2, 2, 3, 4, 5
    x = rng.standard_normal((b, L, G, Hg, P)).astype(np.float32)
    dt = rng.uniform(0.001, 0.5, (b, L, G, Hg)).astype(np.float32)
    dt[1, L - 5:] = 0.0   # padding: neither decays nor feeds the state
    A = -rng.uniform(1, 16, (G, Hg)).astype(np.float32)
    B = rng.standard_normal((b, L, G, N)).astype(np.float32)
    C = rng.standard_normal((b, L, G, N)).astype(np.float32)
    h0 = rng.standard_normal((b, G, Hg, P, N)).astype(np.float32)
    want_y, want_h = _recurrence(x, dt, A, B, C, h0)
    y, h = M.ssd(*(jnp.asarray(a) for a in (x, dt, A, B, C)), chunk,
                 jnp.asarray(h0))
    assert np.abs(np.asarray(y) - want_y).max() < 1e-4
    assert np.abs(np.asarray(h) - want_h).max() < 1e-4
    # The padded row's state is its state after its last real position.
    _, stop = _recurrence(x[1:, :L - 5], dt[1:, :L - 5], A, B[1:, :L - 5],
                          C[1:, :L - 5], h0[1:])
    assert np.abs(np.asarray(h)[1:] - stop).max() < 1e-4


def test_decode_continues_from_a_prefill_s_state(tree):
    """The state and the tail after a prefill of n tokens and k decode
    steps are those of a prefill of n + k tokens."""
    fam = M.family(CFG, jnp.float32)
    params = jax.tree.map(jnp.asarray, tree)
    ids = np.random.default_rng(3).integers(0, 96, (1, 16)).astype(np.int32)
    n, k = 10, 6
    one = jnp.asarray([n], jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, *short = fresh_pool.prefill(fam, params, jnp.asarray(ids), one,
                                       24, jnp.float32)
        _, *full = fresh_pool.prefill(fam, params, jnp.asarray(ids), one + k,
                                      24, jnp.float32)
        pool = D.slot_pool(*short[:2], fam.rows)
        cache = tuple(short)
        for t in range(k):
            wpos = one + t
            _, cache, _ = D._decode_logits(
                fam, params, pool, cache, jnp.asarray(ids[:, n + t]), wpos,
                pool.span(wpos), None, jnp.float32)
    for got, want in zip(cache[2:], full[2:]):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-4
    for got, want in zip(cache[:2], full[:2]):
        assert np.abs(np.asarray(got)[:, :, :n + k]
                      - np.asarray(want)[:, :, :n + k]).max() < 1e-4


# -- (c) the share -----------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Every offset's part of the routed sum (through ``W_up``), with the
    shared expert counted once, is the uncut reference's expert layer; and
    the program's layer is the reference's for each share."""
    whole = dataclasses.replace(CFG, experts_held=16, expert_offset=0)
    p = M._init_layer("E", np.random.default_rng(9), whole, np.float32)
    x = np.random.default_rng(4).standard_normal((7, 64)).astype(np.float32)
    keys = dict(KEYS, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(reference.experts(p, jnp.asarray(x), keys))
        shared = uncut - np.asarray(reference.experts(
            p, jnp.asarray(x), keys, shared=False))
        parts = []
        for offset in (0, 4, 8, 12):
            mine = dict(p, w1=p["w1"][offset:offset + 4],
                        w2=p["w2"][offset:offset + 4])
            here = dict(keys, expert_offset=offset)
            parts.append(np.asarray(reference.experts(
                mine, jnp.asarray(x), here, shared=False)))
            cfg = dataclasses.replace(CFG, expert_offset=offset)
            got = M._experts(cfg, jax.tree.map(jnp.asarray, mine),
                             jnp.asarray(x)[None], lambda c: None)[0]
            assert np.abs(np.asarray(got) - parts[-1] - shared).max() < TOL
    assert np.abs(sum(parts) + shared - uncut).max() < TOL
    assert max(np.abs(part).max() for part in parts) > 0.01


# -- (d) the grouped matmul ---------------------------------------------------

LAYOUTS = {
    "a step's": [3, 0, 5, 1, 0, 7],
    "every row on one expert": [0, 0, 40, 0, 0, 0],
    "no row held": [0, 0, 0, 0, 0, 0],
    "a row an expert": [1, 1, 1, 1, 1, 1],
    "first and last": [20, 0, 0, 0, 0, 17],
    "a prefill's, every row held": [9, 8, 7, 10, 6, 8],
}


@pytest.mark.parametrize("form", ["kernel, interpreted", "ragged_dot"])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_expert_matmul_is_the_dense_masked_sum(layout, form):
    rng = np.random.default_rng(0)
    sizes = np.asarray(LAYOUTS[layout], np.int32)
    G, K, N, M_ = 6, 32, 48, 48
    x = rng.standard_normal((M_, K)).astype(np.float32)
    w = rng.standard_normal((G, K, N)).astype(np.float32)
    owner = np.repeat(np.arange(G), sizes)
    want = np.stack([x[r] @ w[g] for r, g in enumerate(owner)]) \
        if len(owner) else np.zeros((0, N), np.float32)
    args = (jnp.asarray(x), jnp.asarray(w), jnp.asarray(sizes))
    for relu2 in (False, True):
        if form == "ragged_dot":
            got = [E.expert_matmul(*args, relu2=relu2)]
        else:
            got = [E.expert_matmul_kernel(*args, relu2=relu2, tile=tile,
                                          interpret=True)
                   for tile in (8, 16)]
        ref = np.square(np.maximum(want, 0)) if relu2 else want
        for out in got:
            assert out.shape == (M_, N)
            if len(owner):
                assert np.abs(np.asarray(out)[:len(owner)] - ref).max() < 1e-3


def test_an_empty_expert_costs_no_grid_step():
    sizes = jnp.asarray([3, 0, 5, 1, 0, 7], jnp.int32)
    offs, group, tile, count = E.work_list(sizes, 16, 8)
    assert offs.tolist() == [0, 3, 3, 8, 9, 9, 16]
    # Rows 0-2, 3-7 | 8, 9-15: a pair a group that holds a row, none for the
    # empty ones.
    assert int(count) == 4
    assert group[:4].tolist() == [0, 2, 3, 5]
    assert tile[:4].tolist() == [0, 0, 1, 1]
    assert int(E.work_list(jnp.zeros((6,), jnp.int32), 16, 8)[3]) == 0


# -- (e) the counters ----------------------------------------------------------

def test_counters_count_a_routing_fixed_by_hand():
    # 5 rows, 3 choices each; 4 held experts, 4 stands for "not held here".
    group = np.asarray([[0, 4, 2], [4, 4, 2], [2, 1, 4], [4, 4, 4],
                        [2, 0, 1]], np.int32)
    sizes = E.group_sizes(jnp.asarray(group), 4)
    assert sizes.tolist() == [2, 2, 4, 0]
    held = group[group < 4]
    assert E.counters(sizes).tolist() == [
        len(held), len(np.unique(held)), np.bincount(held).max()]


def test_route_is_the_published_router():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((6, 64)).astype(np.float32)
    p = {"router": rng.standard_normal((64, 16)).astype(np.float32) * 0.2,
         "router_bias": rng.standard_normal(16).astype(np.float32)}
    with jax.default_matmul_precision("highest"):
        dense = np.asarray(reference.routing(
            jax.tree.map(jnp.asarray, p), jnp.asarray(x),
            {"top_k": 3, "routed_scale": 5.0}))
        w, group = E.route(jnp.asarray(x), p["router"], p["router_bias"], 3,
                           5.0, 4, 4)
    w, group = np.asarray(w), np.asarray(group)
    assert np.allclose(w.sum(-1), 5.0, atol=1e-5)
    for n in range(6):
        chosen = np.flatnonzero(dense[n])
        held = sorted(e - 4 for e in chosen if 4 <= e < 8)
        assert sorted(g for g in group[n] if g < 4) == held
        assert (group[n] == 4).sum() == 3 - len(held)
        for g, weight in zip(group[n], w[n]):
            if g < 4:
                assert weight == pytest.approx(dense[n, g + 4], rel=1e-5)


async def test_the_scheduler_carries_the_counters_to_metrics(tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    eng = build_engine(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(name="nemo", builder="nemotron_h",
                            dtype="float32", batch_buckets=(1,),
                            seq_buckets=(8, 16), extra=EXTRA)]))
    try:
        cm = eng.model("nemo")
        sched = GenerationScheduler(cm, eng.runner, cm.cfg).start()
        try:
            reqs = [sched.submit(cm.servable.preprocess(
                {"input_ids": list(range(1, n))}), max_new=8)
                for n in (6, 13)]
            served = [await asyncio.wait_for(r.done, 120) for r in reqs]
            snap = sched.gen_snapshot()
        finally:
            await sched.stop()
    finally:
        eng.shutdown()
    assert [len(t) for t in served] == [8, 8]
    rounds = snap["segment_rounds"]
    steps = rounds * EXTRA["segment_tokens"]
    held, touched, most = (snap[k] for k in (
        "expert_assignments_held", "experts_touched", "expert_load_max"))
    assert held["count"] == touched["count"] == most["count"] == rounds > 0
    assert list(snap["step_counters"]) == [
        "expert_assignments_held", "experts_touched", "expert_load_max"]
    layers, slots = ARCH["pattern"].count("E"), EXTRA["gen_slots"]
    # Every slot's row is routed, a finished slot's too: static shapes.
    assert 0 < held["sum"] <= slots * ARCH["top_k"] * layers * steps
    assert 0 < touched["sum"] <= ARCH["experts_held"] * layers * steps
    assert touched["sum"] <= held["sum"] <= most["sum"] * ARCH["experts_held"]
    assert most["sum"] <= held["sum"]


# -- (f) one trace a kind of layer ------------------------------------------------

def test_layer_traces_is_three_for_the_segment_and_the_prefill(servable):
    meta = servable.meta["continuous"]
    S = meta["slots"]
    cache = tuple(jnp.zeros(shape, dt) for shape, dt in meta["cache_leaves"])
    zf, zi = jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)
    clock = CompileClock()
    with clock.open("m", "segment", {}, seen=set()):
        jax.jit(meta["segment"])(servable.params, cache, zi, zi + 3, zi,
                                 zi != 0, zf, zi, zi, zf + 1)
    assert clock.snapshot()[-1]["layer_traces"] == 3  # of 7 layers
    payload = {k: jnp.zeros(v.shape, v.dtype)
               for k, v in meta["admit_spec"](8).items()}
    with clock.open("m", "prefill", {"batch": 1, "bucket": 8}, seen=set()):
        jax.jit(meta["prefill"])(servable.params, cache, zi[:1],
                                 {**payload, "length": jnp.ones(1, jnp.int32)})
    assert clock.snapshot()[-1]["layer_traces"] == 3


# -- (h) no paged lane --------------------------------------------------------

def test_paged_lane_is_refused_at_build(servable):
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

    assert servable.meta["continuous"]["paged"] is None
    with pytest.raises(ValueError, match="kv_cache='paged' cannot serve "
                                         "this family"):
        get_model_builder("nemotron_h")(ModelConfig(
            name="nemotron_h", dtype="float32", batch_buckets=(1,),
            seq_buckets=(8, 16), kv_cache="paged", extra=EXTRA))


def test_buckets_are_whole_chunks_and_the_share_is_inside_the_published():
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

    with pytest.raises(ValueError, match="whole chunks of 8"):
        get_model_builder("nemotron_h")(ModelConfig(
            name="nemotron_h", dtype="float32", batch_buckets=(1,),
            seq_buckets=(12,), extra=EXTRA))
    with pytest.raises(ValueError, match="not among the 16 published"):
        M.config_from_arch(dict(ARCH, expert_offset=14))
