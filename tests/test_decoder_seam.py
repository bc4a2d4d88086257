"""models/decoder.py serves a family it has never seen, and its scan alone.

The toy family below exists in this file only: RMS norm, no biases, a gated
MLP, no learned positions.  It reaches the fixed-batch lane and both
generation schedulers through ``decoder.make_servable`` with no line changed
in the package, and is held to a plain float32 loop that keeps no cache.
Then ``segment_scan`` with a stub for a model: the emit and finish rules
every streaming decoder here shares.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fresh_pool

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.utils import registry

pytest_plugins = "aiohttp.pytest_plugin"  # runs the coroutine tests

VOCAB, WIDTH, HEADS, LAYERS, FFN, EOS = 96, 32, 2, 2, 48, 95


def _rms(scale, x):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + 1e-6)
            * scale).astype(x.dtype)


def _toy_layer(p, x, attend, pos, lora=None, lora_idx=None):
    h = _rms(p["n1"], x)
    x = x + attend(h @ p["q"], h @ p["k"], h @ p["v"]) @ p["o"]
    h = _rms(p["n2"], x)
    return x + (jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]


TOY = D.Family(
    embed=lambda params, tokens, dtype: params["emb"].astype(dtype)[tokens],
    positions=None, layer=_toy_layer,
    norm=lambda params, x: _rms(params["norm"], x),
    head=lambda params, x: (x @ params["head"]).astype(jnp.float32),
    layers=LAYERS, width=WIDTH, heads=HEADS, eos_id=EOS, max_positions=64,
    vocab_size=VOCAB)


def _toy_params(seed=0):
    g = np.random.default_rng(seed)

    def w(*shape):
        return (g.standard_normal(shape) * 0.3).astype(np.float32)

    ones = np.ones((WIDTH,), np.float32)
    params = {"emb": w(VOCAB, WIDTH), "norm": ones, "head": w(WIDTH, VOCAB)}
    for i in range(LAYERS):
        params[f"layer{i}"] = {
            "n1": ones, "n2": ones, "q": w(WIDTH, WIDTH), "k": w(WIDTH, WIDTH),
            "v": w(WIDTH, WIDTH), "o": w(WIDTH, WIDTH),
            "gate": w(WIDTH, FFN), "up": w(WIDTH, FFN), "down": w(FFN, WIDTH)}
    return params


def _reference_logits(params, ids):
    """The toy model over a whole sequence, float32, no cache: logits at
    every position [len(ids), VOCAB]."""
    n, dh = len(ids), WIDTH // HEADS
    x = jnp.asarray(params["emb"])[jnp.asarray(ids)]
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    for i in range(LAYERS):
        p = params[f"layer{i}"]
        h = _rms(p["n1"], x)
        q, k, v = (jnp.reshape(h @ p[m], (n, HEADS, dh)) for m in "qkv")
        s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * dh ** -0.5
        a = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        x = x + jnp.einsum("hqk,khd->qhd", a, v,
                           precision="highest").reshape(n, WIDTH) @ p["o"]
        h = _rms(p["n2"], x)
        x = x + (jax.nn.silu(h @ p["gate"]) * (h @ p["up"])) @ p["down"]
    return _rms(params["norm"], x) @ params["head"]


def _reference_greedy(params, ids, max_new):
    ids, out = list(ids), []
    for _ in range(max_new):
        tok = int(jnp.argmax(_reference_logits(params, ids)[-1]))
        if tok == EOS:
            break
        out.append(tok)
        ids.append(tok)
    return out


@pytest.fixture()
def engine(tmp_path, monkeypatch):
    """The toy family on both lanes of one engine: ``toy`` (slot scheduler)
    and ``toy_paged``, registered for this test only."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    monkeypatch.setitem(
        registry._REGISTRY, "toy_decoder",
        lambda mc: D.make_servable(mc.name, mc, TOY, _toy_params()))
    monkeypatch.setitem(registry._LATENCY_CLASS, "toy_decoder", "latency")
    kw = dict(builder="toy_decoder", dtype="float32", batch_buckets=(1, 2),
              seq_buckets=(16,), coalesce_ms=1.0,
              extra={"max_new_tokens": 12, "gen_slots": 2,
                     "segment_tokens": 3})
    eng = build_engine(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(name="toy", **kw),
                ModelConfig(name="toy_paged", kv_cache="paged",
                            kv_block_size=4, **kw)]))
    yield eng
    eng.shutdown()


def _schedulers(engine):
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler, PagedGenerationScheduler)

    slot, paged = engine.model("toy"), engine.model("toy_paged")
    return (GenerationScheduler(slot, engine.runner, slot.cfg),
            PagedGenerationScheduler(paged, engine.runner, paged.cfg))


async def _stream(sched, sample):
    sched.start()
    try:
        return await asyncio.wait_for(sched.submit(sample).done, 60)
    finally:
        await sched.stop()


async def test_toy_slot_stream_equals_fixed_batch_and_reference(engine):
    cm = engine.model("toy")
    for ids in ([5, 6, 7], [9, 10, 11, 12, 13], [3]):
        sample = cm.servable.preprocess({"input_ids": ids})
        fixed = cm.run_batch([sample])[0][0]["tokens"]
        assert fixed == _reference_greedy(_toy_params(), ids, 12), ids
        assert await _stream(_schedulers(engine)[0], sample) == fixed, ids


async def test_toy_paged_stream_equals_slot_stream(engine):
    cm = engine.model("toy")
    for ids in ([5, 6, 7], list(range(20, 31))):  # 11 ids: three pages
        sample = cm.servable.preprocess({"input_ids": ids})
        slot, paged = _schedulers(engine)
        got = await _stream(paged, sample)
        assert got == await _stream(slot, sample) and got, ids


async def test_toy_sampled_stream_equals_fixed_batch(engine):
    cm = engine.model("toy")
    sample = cm.servable.preprocess(
        {"input_ids": [5, 6, 7], "temperature": 1.3, "seed": 11, "top_k": 5,
         "top_p": 0.9})
    fixed = cm.run_batch([sample])[0][0]["tokens"]
    greedy = cm.run_batch([cm.servable.preprocess(
        {"input_ids": [5, 6, 7]})])[0][0]["tokens"]
    assert fixed and fixed != greedy
    for sched in _schedulers(engine):
        assert await _stream(sched, sample) == fixed


def _pages(P, extra, BS):
    """An empty paged pool with one row's table over pages 1..MB."""
    MB = -(-(P + extra) // BS)
    ck = jnp.zeros((LAYERS, MB + 2, BS, WIDTH), jnp.float32)
    table = jnp.asarray(np.arange(1, MB + 1, dtype=np.int32)[None])
    return D.PagedPool(ck, jnp.zeros_like(ck), table, BS)


def test_toy_chunked_prefill_equals_monolithic_logits():
    params = jax.tree.map(jnp.asarray, _toy_params())
    P, BS, C = 13, 4, 4
    ids = np.random.default_rng(1).integers(1, 90, (P,)).astype(np.int32)
    lens, z1 = jnp.asarray([P], jnp.int32), jnp.zeros((1,), jnp.float32)
    s1 = jnp.zeros((1,), jnp.int32)
    logits, ck_ref, _ = fresh_pool.prefill(
        TOY, params, jnp.asarray(ids[None]), lens, P + 3, jnp.float32)
    want = _reference_logits(_toy_params(), ids)[-1]
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    pool = _pages(P, 3, BS)
    for start in range(0, P, C):
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :len(ids[start:start + C])] = ids[start:start + C]
        first, ck, cv = D.prefill_chunk(
            TOY, params, jnp.asarray(chunk), jnp.asarray([start], jnp.int32),
            lens, pool, z1, s1, s1, z1 + 1, jnp.float32)
        pool = pool._replace(k=ck, v=cv)
    assert int(first[0]) == int(jnp.argmax(logits[0]))
    virt = np.asarray(pool.view(0)[0])[0, :P]
    np.testing.assert_array_equal(virt, np.asarray(ck_ref[0, 0, :P]))


def test_toy_verify_equals_sequential_decode_steps():
    """K+1 queries in one forward read what K+1 one-query steps read."""
    params = jax.tree.map(jnp.asarray, _toy_params())
    ids = np.random.default_rng(2).integers(1, 90, (9,)).astype(np.int32)
    P, K1, BS = 5, 4, 4
    pool = _pages(P, K1, BS)
    z1, s1 = jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32)
    chunk = np.zeros((1, 8), np.int32)
    chunk[0, :P] = ids[:P]
    _, ck, cv = D.prefill_chunk(TOY, params, jnp.asarray(chunk), s1,
                                jnp.asarray([P], jnp.int32), pool, z1, s1,
                                s1, z1 + 1, jnp.float32)
    pool = pool._replace(k=ck, v=cv)
    pos = jnp.asarray([P], jnp.int32)
    many, *_ = D.verify(TOY, params, pool, jnp.asarray(ids[None, P:]), pos,
                        jnp.float32)
    for j in range(K1):
        one, ck, cv = D.verify(TOY, params, pool,
                               jnp.asarray(ids[None, P + j:P + j + 1]),
                               pos + j, jnp.float32)
        pool = pool._replace(k=ck, v=cv)
        np.testing.assert_allclose(np.asarray(many[0, j]),
                                   np.asarray(one[0, 0]), atol=1e-5,
                                   rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(many[0]),
        np.asarray(_reference_logits(_toy_params(), ids)[P:]), atol=2e-4,
        rtol=2e-4)


# tok, finished at the start; the stub model answers tok + 1 (and 95 = EOS
# after 12), so what the scan emits, pins and freezes can be written down.
SCAN_CASES = {
    "live": ([3, 7], [False, False],
             [[3, 4, 5, 6], [7, 8, 9, 10]], [14, 24], [False, False]),
    "finished_at_start": ([3, 7], [False, True],
                          [[3, 4, 5, 6], [95, 95, 95, 95]], [14, 20],
                          [False, True]),
    "reaches_eos": ([11, 3], [False, False],
                    [[11, 12, 95, 95], [3, 4, 5, 6]], [12, 24],
                    [True, False]),
}


@pytest.mark.parametrize("case", list(SCAN_CASES))
def test_segment_scan_emits_pins_and_freezes(case):
    tok, fin, emits, pos_after, fin_after = SCAN_CASES[case]
    fed = []

    def stub(cache, tok, pos, t, finished, seen):
        fed.append((tok, pos, t))
        return cache + 1, jnp.where(tok >= 12, EOS, tok + 1), seen

    out = D.segment_scan(stub, jnp.zeros((), jnp.int32), jnp.asarray(tok),
                         jnp.asarray([10, 20]), jnp.asarray([5, 5]),
                         jnp.asarray(fin), 4, EOS)
    got_emits, cache, tok2, pos2, t2, fin2 = out
    assert got_emits.tolist() == emits       # the token decided before
    assert pos2.tolist() == pos_after        # a finished row's pos freezes
    assert fin2.tolist() == fin_after
    assert t2.tolist() == [9, 9] and int(cache) == 4  # four steps, all rows
    # A finished row feeds EOS from then on, whatever the model answered.
    assert [int(x) for x, f in zip(tok2, fin_after) if f] == [
        EOS] * sum(fin_after)
    assert len(fed) == 1  # traced once: one body, ``seg`` steps of it


# -- the cache as a tuple of leaves: the families that had two keep their text ----

_GPT2_ARCH = {"vocab_size": 96, "d_model": 32, "layers": 3, "heads": 2,
              "ffn_dim": 64, "max_positions": 64, "eos_id": 95}
_W8A16_ARCH = {"vocab_size": 512, "d_model": 128, "layers": 2, "heads": 2,
               "ffn_dim": 256, "max_positions": 64, "eos_id": 511}
_EVA_ARCH = {"vocab_size": 48, "hidden_size": 32, "layers": 3, "heads": 2,
             "intermediate_size": 48, "max_positions": 256, "window_size": 32,
             "chunk_size": 4, "num_pred_heads": 2, "rope_theta": 100.0,
             "init_std": 0.3, "eos_id": 48}
_NEMOTRON_ARCH = {
    "vocab_size": 96, "vocab_published": 384, "hidden_size": 64,
    "pattern": "MEM*EME", "heads": 4, "kv_heads": 2, "head_dim": 16,
    "mamba_heads": 8, "mamba_head_dim": 8, "ssm_state": 16, "n_groups": 2,
    "chunk_size": 8, "experts_published": 16, "experts_held": 4,
    "expert_offset": 4, "top_k": 3, "latent_size": 32, "expert_width": 48,
    "shared_width": 80, "max_positions": 512, "init_std": 0.1, "eos_id": 96}
_LFM2_ARCH = {
    "vocab_size": 96, "hidden_size": 64,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "dense_layers": 2, "dense_width": 96, "heads": 8, "kv_heads": 2,
    "head_dim": 8, "experts_published": 8, "experts_held": 8, "top_k": 2,
    "expert_width": 48, "rope_theta": 100.0, "max_positions": 512,
    "init_std": 0.1, "eos_id": 96}
_MELLUM_ARCH = {
    "vocab_size": 96, "hidden_size": 64,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "heads": 8, "kv_heads": 2, "head_dim": 8, "sliding_window": 8,
    "experts_published": 8, "experts_held": 8, "top_k": 2,
    "expert_width": 48, "rope_theta": 100.0, "yarn_original_positions": 16,
    "max_positions": 512, "init_std": 0.1, "eos_id": 96}
_JOYAI_ARCH = {
    "vocab_size": 96, "hidden_size": 64, "layers": 3, "heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "nope_dim": 16, "rope_dim": 8,
    "v_dim": 16, "dense_layers": 1, "dense_width": 96,
    "experts_published": 16, "experts_held": 4, "expert_offset": 4,
    "top_k": 4, "expert_width": 48, "rope_theta": 100.0,
    "max_positions": 512, "init_std": 0.1, "eos_id": 96}
_SLOT = {"max_new_tokens": 8, "gen_slots": 3, "segment_tokens": 4}
LOWERED = {
    "gpt2": ("gpt2", "bfloat16", (16,), {
        **_SLOT, "arch": _GPT2_ARCH, "params_dtype": "bfloat16"}),
    "w8a16": ("gpt2", "bfloat16", (16,), {
        **_SLOT, "arch": _W8A16_ARCH, "params_dtype": "int8",
        "quantize_min_size": 1024}),
    "evabyte": ("evabyte", "float32", (64,), {
        **_SLOT, "max_new_tokens": 16, "arch": _EVA_ARCH}),
    "nemotron": ("nemotron_h", "bfloat16", (16,), {
        **_SLOT, "arch": _NEMOTRON_ARCH}),
    "lfm2": ("lfm2", "bfloat16", (16,), {**_SLOT, "arch": _LFM2_ARCH}),
    "mellum": ("mellum", "bfloat16", (16,), {**_SLOT, "arch": _MELLUM_ARCH}),
    "joyai": ("joyai", "bfloat16", (16,), {**_SLOT, "arch": _JOYAI_ARCH}),
}
# sha256 of ``jit(...).lower(...).as_text()`` (no source locations in it) of
# the slot lane's programs for the CPU, with the JAX this repository is
# installed with (0.9.0): a batch-2 prefill of the one bucket and the
# segment.  The segments are as PR 43 (commit 877073a) lowered them: they
# proved, for the PR that widened the cache (45), that the widening reached
# none of these programs, and for the PR that made the prefill write into
# the pool (50), which re-pinned the prefills and took the ``insert_from``
# cases away with the program, that it left the segment alone.  A later PR
# that changes one of them on purpose, or a new JAX, re-pins: the failing
# assertion prints the digest to put here.
PR43_TEXT = {
    "gpt2-prefill": "d064dd8bc4cbe57c1846c3e58f568f12d23351b284060ab8560459ddd5c65443",
    "gpt2-segment": "10d533186dec952c3a85af5ea6c15b7a9823b631f115cb90bde581500960262d",
    "w8a16-prefill": "83d1231ce022f565667befa7f30ecc3ac81598c349323f6e5fae41c1251da75b",
    "w8a16-segment": "9bf805388e93f7aeb0522d545d2d4cf0ac9dfdb36063b75ba8ec4e5f074a791e",
    "evabyte-prefill": "ceb0c1ff0aa233c33d0c162ed80694eb826a46b0fdcd32488898860a97f950f3",
    "evabyte-segment": "3bfe5dd77b49871f1811c4874f48af3bbc6abbed36914d17bfe25a37d7742175",
}


@pytest.fixture(scope="module")
def lowered_programs():
    """``{family: {program: lowered text}}``, each family built once."""
    import types

    from pytorch_zappa_serverless_tpu.serving.generation import (
        build_gen_kernels)
    from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

    made = {}

    def of(name):
        if name in made:
            return made[name]
        builder, dtype, buckets, extra = LOWERED[name]
        sv = get_model_builder(builder)(ModelConfig(
            name=builder, dtype=dtype, batch_buckets=(2,),
            seq_buckets=buckets, extra=extra))
        k = build_gen_kernels(types.SimpleNamespace(servable=sv))
        meta = sv.meta["continuous"]
        S = meta["slots"]
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), sv.params)
        payload = {key: jax.ShapeDtypeStruct((2,) + v.shape[1:], v.dtype)
                   for key, v in meta["admit_spec"](buckets[0]).items()}
        cache = tuple(jax.ShapeDtypeStruct(shape, dt)
                      for shape, dt in meta["cache_leaves"])
        assert name in ("nemotron", "lfm2", "mellum", "joyai") or (
            len(cache) == 2 and not meta["counters"])

        def per_slot(dt):
            return jax.ShapeDtypeStruct((S,), dt)

        made[name] = {
            "prefill": k["prefill"].lower(
                params, cache, jax.ShapeDtypeStruct((2,), jnp.int32),
                payload),
            "segment": k["segment"].lower(
                params, cache, *(per_slot(dt) for dt in (
                    jnp.int32, jnp.int32, jnp.int32, jnp.bool_, jnp.float32,
                    jnp.int32, jnp.int32, jnp.float32)))}
        return made[name]

    return of


@pytest.mark.parametrize("case", list(PR43_TEXT))
def test_two_leaf_families_lower_to_the_text_they_had(case, lowered_programs):
    """The cache became a tuple of declared leaves, the trunk hands a layer
    its index through ``fam.cache_index`` and may hand it state and a
    counter: for GPT-2 (bfloat16 and W8A16) and EvaByte, whose cache is K and
    V and nothing else, none of that reaches the lowered module."""
    import hashlib

    family, program = case.split("-")
    text = lowered_programs(family)[program].as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == PR43_TEXT[case], (
        f"{case} lowers to other text than is pinned (jax {jax.__version__}, "
        f"pinned under 0.9.0; {len(text.splitlines())} lines).  If this PR "
        f"meant to change that program, or JAX moved, pin {digest!r} in "
        f"PR43_TEXT; if not, the seam leaked into a family it should not "
        f"touch")


# Nemotron-H's programs for the CPU (the segment as PR 46, commit 385add5,
# lowered it; the prefill as PR 50 made it, into the pool), pinned by the PR
# that gave ops/decode_attention.py grouped queries in
# its kernel and ops/expert_matmul.py a gated form (48): off the chip the
# grouped ``jax.numpy`` form and the ``relu2`` path of ``ragged_dot`` are
# what they were.  On the chip its attention layer takes the kernel now, which
# no CPU lowering shows (PERF.md section 6, PR 48 has the chip's readings).
PR46_NEMOTRON_TEXT = {
    "nemotron-prefill": "22b0d4aedf479043e526b79475cf7c324249f33a549c275933944f6016b8c240",
    "nemotron-segment": "e5eb8a1f461000c1bcb465db0d1385e2ea55ed080a1b99cd18c33a01cd54be02",
}


@pytest.mark.parametrize("case", list(PR46_NEMOTRON_TEXT))
def test_nemotron_lowers_to_the_text_it_had_before_the_grouped_kernel(
        case, lowered_programs):
    import hashlib

    family, program = case.split("-")
    text = lowered_programs(family)[program].as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == PR46_NEMOTRON_TEXT[case], (
        f"{case} lowers to other text than is pinned (jax {jax.__version__}, "
        f"pinned under 0.9.0).  If this PR meant to change that program, or "
        f"JAX moved, pin {digest!r} in PR46_NEMOTRON_TEXT")


# LFM2's and Mellum 2's programs for the CPU as the parent of PR 55 (commit
# 3ef190a) lowered them, pinned by the PR that let a row be one leaf
# (``Rows.values``, ``absorb``, ``expand``) and gave ``flash_attention`` a
# value width: a family that declares none of it gets the text it had.  And
# JoyAI-LLM-Flash's own, as that PR made them: the one leaf donated to both
# programs.
PR55_TEXT = {
    "lfm2-prefill":
        "df6d87eef00f1f8ede148678360d84c90a489c9b42655294797be364299a2fe0",
    "lfm2-segment":
        "112417fc10d896c95914f6c564f236a474424f166abae89e74d98c6a8160e5ad",
    "mellum-prefill":
        "b53a3a02a1ac6ce058a6bbda64c1d3614f7015dab8b6feffc287769065de4e03",
    "mellum-segment":
        "0eb1a7579f45992a36470051693147776e211a4a3d7a1a64f8c18265b143bc5f",
    "joyai-prefill":
        "111699fd6e7ef6eb3c36e73d5ca6633d007cec986fe8510688c650c9a60e19ac",
    "joyai-segment":
        "6466b9714bdb97a5cba01b64897e9d0992ddc0121586afc66f4e540c0031f9a2",
}


@pytest.mark.parametrize("case", list(PR55_TEXT))
def test_families_lower_to_the_text_they_had_before_the_one_leaf_row(
        case, lowered_programs):
    import hashlib

    family, program = case.split("-")
    text = lowered_programs(family)[program].as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == PR55_TEXT[case], (
        f"{case} lowers to other text than is pinned (jax {jax.__version__}, "
        f"pinned under 0.9.0).  If this PR meant to change that program, or "
        f"JAX moved, pin {digest!r} in PR55_TEXT")


# -- the named parts of a step (ISSUE 57) ----------------------------------------

def _scoped_ops(text: str):
    """Every matmul, convolution and custom call of a module lowered with
    ``debug_info``, as ``(operation, local path, function)``, and ``{function:
    [(calling function, the call's path)]}``: an operation inside a private
    function (the trunk's one ``layer``, a jitted kernel wrapper) carries
    the path below its function, and the rest of it is its call site's."""
    import re

    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"\(', text, re.M))
    ops, calls, fn = [], {}, None
    for line in text.splitlines():
        began = re.match(r"\s*func\.func \w+ @([\w.]+)\(", line)
        if began:
            fn = began.group(1)
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        path = names.get(at.group(1), "") if at else ""
        called = re.search(r"\bcall @([\w.]+)\(", line)
        if called:
            calls.setdefault(called.group(1), []).append((fn, path))
        op = re.search(r"(stablehlo\.(?:dot_general|convolution|custom_call)"
                       r"|\w+\.ragged_dot)\b", line)
        if op:
            ops.append((op.group(1), path, fn))
    return ops, calls, set(names.values())


@pytest.mark.parametrize("case", [f"{family}-{program}" for family in LOWERED
                                  for program in ("prefill", "segment")])
def test_every_matmul_lies_in_a_named_part(case, lowered_programs):
    """One vocabulary in every family and in GPT-2 (``decoder.PARTS``): what
    a capture's device time is booked to (utils/xplane.py).  Every matmul,
    convolution and kernel call of both programs is traced inside a part,
    its own scope's or that of every site its function is called from, and
    the scopes the families had under their own names are gone."""
    import re

    from pytorch_zappa_serverless_tpu.utils.xplane import _part

    family, program = case.split("-")
    text = lowered_programs(family)[program].as_text(debug_info=True)
    ops, calls, paths = _scoped_ops(text)
    assert len(ops) >= 4, case

    def outer(fn, seen=()):
        """The parts ``fn``'s call sites lie in (None: one lies in none)."""
        if fn == "main" or fn in seen or fn not in calls:
            return {None}
        return {p for caller, path in calls[fn]
                for p in ([_part(path)] if _part(path)
                          else outer(caller, seen + (fn,)))}

    bare = [(op, path, fn) for op, path, fn in ops
            if not _part(path) and None in outer(fn)]
    assert not bare, f"{case}: in no part of {D.PARTS}: {bare[:5]}"
    parts = {_part(path) for _, path, _ in ops} - {None}
    assert parts <= set(D.PARTS) and {"qkv", "attend_out", "head"} <= parts
    old = [p for p in paths for piece in p.split("/")
           if re.match(r"(eva|nemotron|lfm2|mellum|joyai)_", piece)]
    assert not old, f"{case}: family-named scopes are left: {old[:5]}"


def test_part_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="no part"):
        D.part("mellum_attend_window")
    with D.part("experts.unsort"):
        pass


def test_joyai_declares_one_leaf_and_the_seam_follows_it():
    """The first family whose row is one leaf: ``cache_leaves`` gives one
    where it gives a K and a V, ``slot_pools`` a pool with no V,
    ``SlotPool.write`` writes the one row, and the leaves after it are the
    state's as ever."""
    from pytorch_zappa_serverless_tpu.models import joyai

    cfg = joyai.config_from_arch({"layers": 10, "experts_held": 32})
    fam = joyai.family(cfg)
    assert (fam.layers, fam.width, fam.rows.values, fam.heads) == (
        10, 640, 512, 32)
    assert not fam.kinds and not fam.state and D.row_leaves(fam) == 1
    assert D.row_leaves(TOY) == 2 and D.ROWS.values is None
    assert D.cache_leaves(fam, 64, 9216, jnp.bfloat16) == (
        ((10, 64, 9216, 640), jnp.bfloat16),)
    assert [z.shape for z in D.zero_cache(fam, 2, 24, jnp.float32)] == [
        (10, 2, 24, 640)]
    pool, state = D.slot_pools(fam, (jnp.zeros((2, 3, 8, 640)),))
    assert pool.v is None and state == () and D._leaves(pool) == (pool.k,)
    wrote = pool.write(jnp.int32(1), jnp.asarray([0, 5, 7]),
                       jnp.ones((3, 1, 640)), None)
    assert wrote.v is None and float(wrote.k.sum()) == 3 * 640
    assert np.asarray(wrote.k[1, 1, 5] == 1).all()
    # A family of two leaves a row: the pair, as ever.
    both, _ = D.slot_pools(TOY, (jnp.zeros((2, 3, 8, 4)),) * 2)
    assert both.v is not None and len(D._leaves(both)) == 2
    # The two methods a decode step calls are the identity unless a family
    # brings its own.
    q = jnp.ones((1, 1, 4))
    assert D.ROWS.absorb(None, q) is q and D.ROWS.expand(None, q) is q


def test_lfm2_declares_its_leaves_and_its_counters():
    """The third family with more than K and V: two attention layers' rows
    the K/V heads wide, and the last two rows of ``u`` of each of the eight
    convolutions, through the same ``cache_leaves`` and ``state=``."""
    from pytorch_zappa_serverless_tpu.models import lfm2
    from pytorch_zappa_serverless_tpu.ops import expert_matmul

    cfg = lfm2.config_from_arch(
        {"layer_types": lfm2.PUBLISHED.layer_types[:10]})
    fam = lfm2.family(cfg)
    assert (fam.layers, fam.kv_layers, fam.kv_heads, fam.heads, fam.width) \
        == (10, 2, 8, 32, 512)
    assert fam.state == ((8, (2, 2048), jnp.bfloat16),)
    assert fam.positions is None and fam.counters == expert_matmul.COUNTERS
    assert [fam.cache_index(i) for i in range(10)] == [
        0, 1, 0, 2, 3, 4, 1, 5, 6, 7]
    T = fam.rows.count(8192 + 384)
    assert D.cache_leaves(fam, 32, T, jnp.bfloat16) == (
        ((2, 32, 8704, 512), jnp.bfloat16), ((2, 32, 8704, 512), jnp.bfloat16),
        ((8, 32, 2, 2048), jnp.bfloat16))


def test_mellum_declares_two_kinds_and_four_leaves():
    """The first family whose K/V layers keep their rows in two ways: a K
    and a V leaf a kind, the full layers' first, each with its own rows a
    slot, and a layer finds its pair by ``(kind, index)``."""
    from pytorch_zappa_serverless_tpu.models import mellum
    from pytorch_zappa_serverless_tpu.ops import expert_matmul

    cfg = mellum.config_from_arch(
        {"layer_types": mellum.PUBLISHED.layer_types[:8]})
    fam = mellum.family(cfg)
    assert (fam.layers, fam.kv_heads, fam.heads, fam.width) == (8, 4, 32, 512)
    assert [(k.name, k.layers, type(k.rows).__name__) for k in fam.kinds] == [
        ("full_attention", 2, "FullRows"),
        ("sliding_attention", 6, "RingRows")]
    assert fam.rows is fam.kinds[0].rows and not fam.state
    assert fam.positions is None and fam.counters == expert_matmul.COUNTERS
    assert [fam.cache_index(i) for i in range(8)] == [
        (1, 0), (1, 1), (1, 2), (0, 0), (1, 3), (1, 4), (1, 5), (0, 1)]
    full = ((2, 32, 17408, 512), jnp.bfloat16)
    ring = ((6, 32, 1024, 512), jnp.bfloat16)
    # Whether it is handed the positions or the full kind's rows.
    for T in (16384 + 768, fam.rows.count(16384 + 768)):
        assert D.cache_leaves(fam, 32, T, jnp.bfloat16) == (
            full, full, ring, ring)
    # A family that declares no kinds has the one pair it always had.
    assert D.cache_leaves(TOY, 2, 28, jnp.float32) == (
        ((LAYERS, 2, 28, WIDTH), jnp.float32),) * 2
    pool, state = D.slot_pools(fam, tuple(
        jnp.zeros((n, 2, t, 8)) for n, t in ((2, 40), (2, 40), (6, 8),
                                             (6, 8))))
    assert [p.k.shape for p in pool] == [(2, 2, 40, 8), (6, 2, 8, 8)]
    assert state == () and pool[1].positions > pool[0].positions == 40
