"""EvaByte through models/decoder.py's seam, against its plain reference.

Tiny size (window 32, chunk 4, 3 layers, 2 heads of 16), seeded random
weights with ``mu`` and ``phi`` drawn at unit scale so that the pooling is
far from uniform.  The program's own prefill and segment programs, through
the pool, are held to ``benchmark/reference/evabyte.py``'s full forward in
the logits at every position they decide a token from; then the same model
on ``GenerationScheduler`` end to end, and what the scheduler counts.

Tolerance ``TOL`` = 2e-4 on logits of size about 1: both sides compute in
float32 and differ in the order of their sums (the program's softmax is over
``[summaries, window]`` blocks and its decode step reads the pool), which
moved the logits by at most 3e-6 here; each of the three controls (a part of
the mathematics left out of the reference) moves them by more than 1e-2.
"""

import asyncio
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fresh_pool

from benchmark.reference import evabyte as reference
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import evabyte as E
from pytorch_zappa_serverless_tpu.ops import flash_attention as F

pytest_plugins = "aiohttp.pytest_plugin"  # runs the coroutine tests

W, C = 32, 4
ARCH = dict(vocab_size=48, hidden_size=32, layers=3, heads=2,
            intermediate_size=48, max_positions=256, window_size=W,
            chunk_size=C, num_pred_heads=2, rope_theta=100.0, init_std=0.3,
            eos_id=48)  # outside the vocabulary: no stream ends early
CFG = E.EvaByteConfig(**ARCH)
PUBLISHED_KEYS = dict(num_attention_heads=2, window_size=W, chunk_size=C,
                      rope_theta=100.0, rms_norm_eps=1e-5, vocab_size=48)
TOL = 2e-4
_INIT = E.init_evabyte_params  # the served test lays its own over the name


def _params(seed=0):
    return _INIT(seed, CFG, pool_scale=1.0)


def _family(align=8):
    return E.family(CFG, E.TwoTier(W, C, CFG.heads, align, block_q=16))


def _forced_run(monkeypatch, lengths, bucket, segments, seg):
    """The program's prefill and ``segments`` segments over a ragged batch,
    with the choice of every token taken away from it: ``choose`` hands back
    a token drawn beforehand and reports the logits it was given.  Returns
    (the sequences as fed, logits [S, steps, V] by sampling step)."""
    fam, params = _family(), jax.tree.map(jnp.asarray, _params())
    S, steps = len(lengths), segments * seg + 1
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, 47, (S, bucket)).astype(np.int32)
    forced = jnp.asarray(rng.integers(0, 47, (S, steps)).astype(np.int32))
    seen = {}

    def spy(logits, temperature, seeds, t, top_k=None, top_p=None):
        jax.debug.callback(
            lambda lg, tt: seen.update({int(tt[0]): np.asarray(lg)}),
            logits, t)
        return forced[jnp.arange(S), t]

    monkeypatch.setattr(D, "choose", spy)
    lens = jnp.asarray(lengths, jnp.int32)
    z, zi = jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)
    total = bucket + segments * seg
    tok, ck, cv = D.prefill_start(
        fam, params, jnp.asarray(prompts), lens, z, zi,
        D.zero_cache(fam, S, total, jnp.float32), jnp.arange(S), jnp.float32)
    assert ck.shape == (CFG.layers, S, fam.rows.count(total), 32)
    pos, step, fin = lens, zi, jnp.zeros((S,), bool)
    for _ in range(segments):
        _, ck, cv, tok, pos, step, fin = D.decode_segment(
            fam, params, D.slot_pool(ck, cv, fam.rows), tok, pos, step, fin,
            z, zi, seg, jnp.float32)
    jax.effects_barrier()
    logits = np.stack([seen[t] for t in range(steps)], axis=1)
    fed = [list(prompts[s, :lengths[s]]) + list(np.asarray(forced[s]))
           for s in range(S)]
    return fed, logits


# Row 0 crosses position 32 at step 5 of its first segment and 64 at step 5
# of its fifth; row 1 ends exactly at the bucket's end, which is a window's
# end too, so its first decoded position opens window 2; row 2 crosses 64
# on a segment's first step.
LENGTHS, BUCKET, SEGMENTS, SEG = [27, 64, 40], 64, 5, 8


@pytest.fixture(scope="module")
def forced():
    with pytest.MonkeyPatch.context() as mp:
        run = _forced_run(mp, LENGTHS, BUCKET, SEGMENTS, SEG)
    return run


def _reference_logits(fed, control=None):
    tree = _params()
    return [reference.forward(tree, ids, PUBLISHED_KEYS, control=control)
            for ids in fed]


def _worst(fed, logits, control=None):
    worst = 0.0
    for s, ref in enumerate(_reference_logits(fed, control)):
        lo = LENGTHS[s] - 1
        want = ref[lo:lo + logits.shape[1]]
        worst = max(worst, float(np.max(np.abs(logits[s] - want))))
    return worst


def test_prefill_then_decode_logits_equal_the_reference_everywhere(forced):
    fed, logits = forced
    assert logits.shape == (3, SEGMENTS * SEG + 1, 48)
    assert _worst(fed, logits) < TOL


@pytest.mark.parametrize("control", ["no_summaries", "own_window_chunks",
                                     "uniform_pooling"])
def test_a_reference_with_part_left_out_fails_the_tolerance(forced, control):
    fed, logits = forced
    assert _worst(fed, logits, control) > 50 * TOL


def test_first_window_is_plain_causal_attention():
    """Inside the first window nothing is summarized: the three controls and
    the reference agree, so what tells them apart above is the summaries."""
    ids = list(np.random.default_rng(1).integers(0, 47, W))
    tree = _params()
    want = reference.forward(tree, ids, PUBLISHED_KEYS)
    for control in ("no_summaries", "uniform_pooling"):
        np.testing.assert_allclose(
            reference.forward(tree, ids, PUBLISHED_KEYS, control=control),
            want, atol=1e-6)


@pytest.mark.parametrize("total,T", [(13056, 2880), (12288 + 64, 2880),
                                     (2048, 2176)])
def test_rows_of_the_published_layout(total, T):
    rows = E.TwoTier(2048, 16, 32, 64)
    assert rows.count(total) == T and T % 64 == 0
    assert rows.positions(T) >= total
    R = T - 2048
    # Position 5,000: window 2, 905 exact rows, 256 summaries, one span.
    first, last = rows.span(np.asarray([5000, 0, 2047, 2048]), T)
    assert (last - first + 1).tolist() == [905 + 256, 1, 2048, 129]
    assert first.tolist() == [R - 256, R, R, R - 128]
    assert rows.summaries(np.asarray([5000, 2047, 13055]), T).tolist() == [
        256, 0, 768]
    assert rows.row(np.asarray([5000, 2048]), T).tolist() == [R + 904, R]
    assert rows.windows(4096) == 2 and rows.windows(4097) == 3
    assert rows.prefill_batch(4096) == 1 and rows.prefill_batch(512) == 4


# ---------------------------------------------------------------------------
# The prompt attention's two forms
# ---------------------------------------------------------------------------

def _force_kernel(monkeypatch):
    """``TwoTier.prompt`` takes the kernel, which runs under the
    interpreter (two heads of 16 in one lane tile that hangs over the
    rows' 32 lanes)."""
    monkeypatch.setattr(E.TwoTier, "prompt_form", lambda *a: "kernel")
    monkeypatch.setattr(F, "prompt_attention", functools.partial(
        F.prompt_attention, interpret=True))


def _prefill(tokens, lengths, total):
    fam, params = _family(), jax.tree.map(jnp.asarray, _params())
    return jax.jit(lambda p, t, n: fresh_pool.prefill(
        fam, p, t, n, total, jnp.float32))(
        params, jnp.asarray(tokens), jnp.asarray(lengths, jnp.int32))


# One, two and three windows of 32, the last of them ragged: a row that
# ends inside its first window beside one that fills the bucket, a window
# of which one position is real, a prompt one short of a window's end.
@pytest.mark.parametrize("P,lengths", [
    (32, [32, 9]), (64, [64, 33, 20]), (64, [47]), (96, [96, 65, 31]),
    (96, [70, 95, 64])], ids=str)
def test_prompt_with_the_kernel_forced_matches_the_windows_form(
        monkeypatch, P, lengths):
    tokens = np.random.default_rng(3).integers(
        0, 47, (len(lengths), P)).astype(np.int32)
    want = _prefill(tokens, lengths, P + 16)
    _force_kernel(monkeypatch)
    got = _prefill(tokens, lengths, P + 16)
    np.testing.assert_allclose(got[0], want[0], atol=TOL, rtol=TOL)
    R = want[1].shape[2] - W
    for b, n in enumerate(lengths):
        # The rows a decode step reads: the summary of every chunk the
        # prompt completed (chunk j at row R - 1 - j) and the ring as far
        # as the prompt's last window got.
        rows = slice(R - n // C, R + n % W)
        for mine, theirs in zip(got[1:], want[1:]):
            np.testing.assert_allclose(mine[:, b, rows], theirs[:, b, rows],
                                       atol=TOL, rtol=TOL)
    assert all(np.isfinite(np.asarray(a)).all() for a in got)


@pytest.mark.parametrize("P,lengths", [(64, [60, 27]), (96, [90, 64])],
                         ids=str)
def test_greedy_bytes_equal_with_the_kernel_forced(monkeypatch, P, lengths):
    """A segment of 8 that crosses a window's end in every row (60 -> 64,
    27 -> 32, 90 -> 96; 64 opens a window with its first byte)."""
    fam, params = _family(), jax.tree.map(jnp.asarray, _params())
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, 47, (len(lengths), P)).astype(np.int32))
    z = jnp.zeros((len(lengths),), jnp.float32)

    def greedy():
        return np.asarray(jax.jit(lambda p, t, n: D.generate(
            fam, p, t, n, z, z.astype(jnp.int32), 8, jnp.float32))(
                params, tokens, jnp.asarray(lengths, jnp.int32)))

    want = greedy()
    _force_kernel(monkeypatch)
    np.testing.assert_array_equal(greedy(), want)


def test_prompt_form_is_windows_off_the_chip_and_on_a_mesh(monkeypatch):
    published = E.TwoTier(2048, 16, 32, 64)
    buckets = (4096, 6144, 8192, 12288)  # benchmark/configs/evabyte-16l.json
    assert {published.prompt_form(1, 32, P, 128) for P in buckets} == {
        "windows"}                       # the backend here is the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert {published.prompt_form(1, 32, P, 128) for P in buckets} == {
        "windows"}
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert [published.prompt_form(1, 32, P, 128) for P in buckets] == [
        "kernel"] * 4
    # The rule is ops/flash_attention.prompt_form's: a block of float32
    # scores under its line, heads that fill no lane tiles or a window
    # longer than the kernel holds keep the scan.
    assert _family().rows.prompt_form(4, 2, 96, 16) == "windows"
    assert published.prompt_form(1, 32, 4096, 80) == "windows"
    assert E.TwoTier(4096, 16, 32, 64).prompt_form(1, 32, 8192, 128) \
        == "windows"
    assert E.TwoTier(2048, 16, 32, 64, block_q=256).prompt_form(
        1, 32, 4096, 128) == "windows"   # 72 MiB a block


def test_linear_rows_are_a_row_a_position():
    pos = np.asarray([0, 7, 959])
    assert D.ROWS.count(960) == 960 and D.ROWS.positions(960) == 960
    first, last = D.ROWS.span(pos, 960)
    assert first.tolist() == [0, 0, 0] and last.tolist() == pos.tolist()
    assert D.ROWS.summaries(pos, 960).tolist() == [0, 0, 0]
    assert D.ROWS.windows(768) == 1 and D.ROWS.prefill_batch(768) is None


def test_converter_reads_the_published_names():
    tree = _params()
    sd = {"model.embed_tokens.weight": tree["embed"],
          "model.norm.weight": tree["norm"], "lm_head.weight": tree["head"].T}
    back = {v: k for k, v in E._LAYER_NAMES.items()}
    for i in range(CFG.layers):
        for leaf, w in tree[f"layer{i}"].items():
            if leaf in ("mu", "phi"):
                w = w.reshape(1, CFG.heads, 1, 1, -1)
            elif w.ndim == 2:
                w = w.T
            sd[f"model.layers.{i}.{back[leaf]}"] = w
        sd[f"model.layers.{i}.self_attn.rotary_emb.inv_freq"] = np.ones(4)
    got = E.convert_evabyte(sd)
    jax.tree.map(np.testing.assert_array_equal, got, tree)
    cfg = E.config_from_params(got)
    assert (cfg.layers, cfg.hidden_size, cfg.num_pred_heads) == (3, 32, 2)
    with pytest.raises(KeyError, match="unrecognized evabyte key"):
        E.convert_evabyte({"model.layers.0.self_attn.nope": np.ones(2)})


# ---------------------------------------------------------------------------
# Served: the registered builder on the slot scheduler
# ---------------------------------------------------------------------------

EXTRA = {"max_new_tokens": 24, "gen_slots": 3, "segment_tokens": 8,
         "arch": ARCH}


@pytest.fixture()
def engine(tmp_path, monkeypatch):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    # The builder's seeded weights with the pooling vectors at unit scale.
    monkeypatch.setattr(E, "init_evabyte_params",
                        lambda seed, cfg: _INIT(seed, cfg, pool_scale=1.0))
    eng = build_engine(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(name="eva", builder="evabyte", dtype="float32",
                            batch_buckets=(1,), seq_buckets=(16, 48),
                            coalesce_ms=1.0, extra=EXTRA)]))
    yield eng
    eng.shutdown()


def _scheduler(engine):
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    cm = engine.model("eva")
    return GenerationScheduler(cm, engine.runner, cm.cfg)


def _deficit(ids, toks):
    """How far each served token lies under the reference's best."""
    ref = reference.forward(_params(), ids + toks[:-1], PUBLISHED_KEYS)
    rows = ref[len(ids) - 1:]
    return max(float(np.max(r) - r[t]) for r, t in zip(rows, toks))


async def test_served_stream_is_the_reference_s_greedy_across_windows(engine):
    cm = engine.model("eva")
    meta = cm.servable.meta["continuous"]
    # 32 exact rows + 72 / 4 summaries = 50, in whole blocks of 32 (a block
    # of the decode kernel at this width is longer than a window).
    assert [shape for shape, _ in meta["cache_leaves"]] == [(3, 3, 64, 32)] * 2
    assert meta["paged"] is None
    rng = np.random.default_rng(2)
    # 30: crosses position 32 inside its first segment; 44: prefills two
    # windows and decodes into the third; 5: never leaves the first window.
    prompts = [[int(t) for t in rng.integers(0, 47, n)] for n in (30, 44, 5)]
    sched = _scheduler(engine).start()
    try:
        reqs = [sched.submit(cm.servable.preprocess({"input_ids": ids}))
                for ids in prompts]
        served = [await asyncio.wait_for(r.done, 120) for r in reqs]
        snap = sched.gen_snapshot()
    finally:
        await sched.stop()
    for ids, toks in zip(prompts, served):
        assert len(toks) == 24 and _deficit(ids, toks) < TOL
        sample = cm.servable.preprocess({"input_ids": ids})
        assert cm.run_batch([sample])[0][0]["tokens"] == toks  # fixed batch
    # The two prompts of the 48 bucket arrived together and a dispatch holds
    # one (a window's worth of positions): three prefills for three requests.
    assert snap["prefill_dispatches"] == 3
    assert [r.prefill_windows for r in reqs] == [1, 2, 1]
    # What the scheduler counted: rows held against positions written, the
    # summaries among them, and the windows that completed while decoding
    # (30 -> 32, 44 -> 64; the third stream stays inside its first).
    rounds = snap["span_rows"]["count"]
    assert rounds == snap["segment_rounds"] > 0
    assert snap["window_rolls"] == 2
    assert 0 < snap["summary_rows"]["sum"] < snap["span_rows"]["sum"] \
        < snap["live_positions"]["sum"]
    live, read = snap["kv_live_share"], snap["kv_read_share"]
    assert live["sum"] == pytest.approx(
        snap["span_rows"]["sum"] / (3 * 64), rel=1e-5)
    # On the CPU the ``jax.numpy`` form reads whole rows: one block a slot.
    assert meta["read_block"] == 64
    assert live["sum"] < read["sum"] <= rounds


def test_paged_lane_fails_at_build_and_says_why(tmp_path):
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine

    with pytest.raises(ValueError, match="kv_cache='paged' cannot serve "
                                         "this family"):
        build_engine(ServeConfig(
            compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
            models=[ModelConfig(name="eva", builder="evabyte",
                                dtype="float32", batch_buckets=(1,),
                                seq_buckets=(16,), kv_cache="paged",
                                kv_block_size=4, extra=EXTRA)]))


def test_gpt2_family_is_handed_positions_and_keeps_linear_rows():
    from pytorch_zappa_serverless_tpu.models import gpt2 as G

    cfg = G.GPT2Config(vocab_size=96, d_model=32, layers=1, heads=2,
                       ffn_dim=64, max_positions=16, eos_id=95)
    fam = G.family(cfg)
    assert fam.rows is D.ROWS
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(0, cfg))
    x = jnp.ones((1, 3, 32))
    a = fam.layer(params["layer0"], x, lambda q, k, v: v, jnp.arange(3))
    b = fam.layer(params["layer0"], x, lambda q, k, v: v, jnp.arange(3) + 5)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
