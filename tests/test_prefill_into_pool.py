"""A slot-lane prefill writes into the pool where its prompts lie (ISSUE 50).

The pool is never cleared between occupants: a prefill overwrites the rows
and the state a decode step will read and leaves the rest of the slot as the
last occupant left it.  So, for every family the slot lane serves:

1. a slot that held a longer sequence (a rolled ring, summaries, state,
   where the family has them), or garbage, serves a shorter prompt the
   tokens a pool of zeros serves it, bit for bit;
2. a batch padded to a power of two writes the slots of its real requests
   and no other.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig
from pytorch_zappa_serverless_tpu.serving.generation import build_gen_kernels
from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder
from test_decoder_seam import (_EVA_ARCH, _GPT2_ARCH, _NEMOTRON_ARCH,
                               _W8A16_ARCH)
from test_lfm2 import ARCH as _LFM2_ARCH

_SLOT = {"gen_slots": 5, "segment_tokens": 4}
# builder, dtype, prompt bucket, extra, the earlier occupant's prompt length
# and segments, the later prompt's length.  EvaByte's first occupant crosses
# position 64: its ring has rolled twice and 16 summaries are written; the
# later prompt ends inside its first window, a chunk half full.
FAMILIES = {
    "gpt2": ("gpt2", "bfloat16", 16, {
        **_SLOT, "max_new_tokens": 12, "arch": _GPT2_ARCH,
        "params_dtype": "bfloat16"}, 15, 3, 6),
    "w8a16": ("gpt2", "bfloat16", 16, {
        **_SLOT, "max_new_tokens": 12, "arch": _W8A16_ARCH,
        "params_dtype": "int8", "quantize_min_size": 1024}, 16, 3, 5),
    "evabyte": ("evabyte", "float32", 64, {
        **_SLOT, "max_new_tokens": 16, "arch": _EVA_ARCH}, 59, 4, 22),
    "nemotron_h": ("nemotron_h", "float32", 16, {
        **_SLOT, "max_new_tokens": 12, "arch": _NEMOTRON_ARCH}, 16, 3, 7),
    "lfm2": ("lfm2", "float32", 16, {
        **_SLOT, "max_new_tokens": 12, "arch": _LFM2_ARCH}, 14, 3, 1),
}


@pytest.fixture(scope="module")
def lanes():
    """``name -> (servable, its jitted programs)``, each built once."""
    made = {}

    def of(name):
        if name not in made:
            builder, dtype, bucket, extra, *_ = FAMILIES[name]
            sv = get_model_builder(builder)(ModelConfig(
                name=builder, dtype=dtype, batch_buckets=(1,),
                seq_buckets=(bucket,), extra=extra))
            made[name] = (sv, build_gen_kernels(
                types.SimpleNamespace(servable=sv)))
        return made[name]

    return of


def _garbage(meta, seed):
    """A pool of finite values that mean nothing, in every leaf."""
    g = np.random.default_rng(seed)
    return tuple(jnp.asarray(g.standard_normal(shape) * 3.0, dt)
                 for shape, dt in meta["cache_leaves"])


def _payload(sv, bucket, prompts):
    meta = sv.meta["continuous"]
    rows = [meta["collate_admit"](sv.preprocess({"input_ids": p}), bucket)
            for p in prompts]
    return {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}


def _serve(sv, kernels, cache, prompt, slot, segments):
    """One prompt prefilled into ``slot`` and decoded alone, every other
    slot finished → ``(cache, its tokens)``."""
    meta = sv.meta["continuous"]
    S, seg = meta["slots"], meta["segment_tokens"]
    payload = _payload(sv, meta["prompt_buckets"][0], [prompt])
    first, *cache = kernels["prefill"](sv.params, tuple(cache),
                                       np.asarray([slot], np.int32), payload)
    zi, zf = np.zeros(S, np.int32), np.zeros(S, np.float32)
    tok, pos, st, fin = zi.copy(), zi.copy(), zi.copy(), np.ones(S, bool)
    tok[slot], pos[slot], fin[slot] = int(np.asarray(first)[0]), \
        len(prompt), False
    emits = []
    for _ in range(segments):
        packed, *cache = kernels["segment"](sv.params, tuple(cache), tok,
                                            pos, st, fin, zf, zi, zi, zf + 1)
        packed = np.asarray(packed)
        emits += packed[slot, :seg].tolist()
        tok, pos, st = (packed[:, seg + k].copy() for k in range(3))
        fin = packed[:, seg + 3] != 0
    return tuple(cache), emits


@pytest.mark.parametrize("before", ["a longer sequence", "garbage"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_re_used_slot_serves_what_a_fresh_pool_serves(lanes, family,
                                                        before):
    sv, kernels = lanes(family)
    meta = sv.meta["continuous"]
    *_, long_n, long_segments, short_n = FAMILIES[family]
    vocab = meta["eos_id"] - 1
    g = np.random.default_rng(3)
    earlier = [int(t) for t in g.integers(1, vocab, long_n)]
    prompt = [int(t) for t in g.integers(1, vocab, short_n)]
    slot = 3
    _, want = _serve(sv, kernels, kernels["alloc_cache"](), prompt, slot, 2)
    if before == "garbage":
        cache = _garbage(meta, 11)
    else:
        cache, held = _serve(sv, kernels, kernels["alloc_cache"](), earlier,
                             slot, long_segments)
        assert len(earlier) + len(held) > len(prompt) + len(want)
    used = [np.asarray(leaf[:, slot]) for leaf in cache]
    assert all(np.isfinite(a.astype(np.float32)).all() and a.any()
               for a in used)
    _, got = _serve(sv, kernels, cache, prompt, slot, 2)
    assert got == want and len(got) == 2 * meta["segment_tokens"]


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_padded_batch_writes_its_own_slots_and_no_other(lanes, family):
    """Three prompts padded to four (the fourth a copy of the first, given
    the first's slot, as ``_admit_batch_sync`` pads) into slots 2, 0 and 3
    of a pool of five: slots 1 and 4 keep every bit of every leaf, and the
    first tokens are those of each prompt prefilled alone."""
    sv, kernels = lanes(family)
    meta = sv.meta["continuous"]
    bucket = meta["prompt_buckets"][0]
    g = np.random.default_rng(5)
    prompts = [[int(t) for t in g.integers(1, meta["eos_id"] - 1, n)]
               for n in (bucket, 3, bucket // 2)]
    slots = [2, 0, 3]
    pool = _garbage(meta, 13)
    held = [np.asarray(leaf) for leaf in pool]
    first, *pool = kernels["prefill"](
        sv.params, pool, np.asarray(slots + slots[:1], np.int32),
        _payload(sv, bucket, prompts + prompts[:1]))
    first = np.asarray(first)
    assert first[3] == first[0]
    for leaf, was in zip(pool, held, strict=True):
        now = np.asarray(leaf)
        for s in (1, 4):
            assert np.array_equal(now[:, s], was[:, s])
        for s in slots:
            assert not np.array_equal(now[:, s], was[:, s])
    for j, (prompt, slot) in enumerate(zip(prompts, slots)):
        alone, *_ = kernels["prefill"](
            sv.params, kernels["alloc_cache"](),
            np.asarray([slot], np.int32), _payload(sv, bucket, [prompt]))
        assert int(np.asarray(alone)[0]) == int(first[j])
