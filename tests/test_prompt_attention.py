"""A prefill's prompt attention: the kernel form against the ``jax.numpy``
form, the prefill with the kernel forced, and the picker's rule.

The kernel (ops/flash_attention.prompt_attention) runs here under the
interpreter at small sizes: an odd head count (five heads of 64, so the last
lane tile hangs over the rows' end), prompts that are no multiple of the
block, ragged lengths down to 1.  What the chip's compiler makes of it at the
real widths is tests/test_aot_tpu_compile.py's; what it costs, chip_smoke.py's.
"""

import asyncio
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import chip_smoke
import fresh_pool
from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu.engine.loader import build_engine
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import gpt2 as G
from pytorch_zappa_serverless_tpu.ops import flash_attention as F

pytest_plugins = "aiohttp.pytest_plugin"

HEADS, HEAD_DIM = 5, 64


def _qkv(rng, B, P, dtype, width=HEADS * HEAD_DIM):
    return (jnp.asarray(rng.standard_normal((B, P, width)), dtype)
            for _ in range(3))


# A window's worth of summaries in these tests: a prefix holds two windows'.
PER = 8


def _prefix(rng, B, counts, dtype, width=HEADS * HEAD_DIM, poison=None):
    """``(pk, pv, counts)`` for a batch of ``B``: one prefix of ``2 * PER``
    rows a row of the batch, or one that all rows share where the counts
    come as a tuple (a prompt's windows); None where there are no counts.
    ``poison`` fills what lies past a row's count (of a shared prefix:
    past the largest)."""
    if counts is None:
        return None
    G = 1 if isinstance(counts, tuple) else B
    pk, pv = (np.asarray(rng.standard_normal((G, 2 * PER, width)), np.float32)
              for _ in range(2))
    if poison is not None:
        for g in range(G):
            past = max(counts) if G == 1 else counts[g]
            pk[g, past:], pv[g, past:] = poison, poison
    return (jnp.asarray(pk, dtype), jnp.asarray(pv, dtype),
            jnp.asarray(counts, jnp.int32))


def _reference(q, k, v, n, heads, prefix=None):
    """A float32 attention over ``concat(prefix[:count], keys)``: the
    ``jax.numpy`` form under the causal, ragged mask, the prefix's counted
    rows open to every query and the others taken out."""
    B, P, _ = q.shape
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    bias = F.prompt_mask(n, P)
    if prefix is not None:
        pk, pv, counts = prefix
        G, J, _ = pk.shape
        kept = jnp.arange(J)[None, :] < counts[:, None]            # [B, J]

        def rows(a):  # each row of the batch its own copy, cleaned
            a = jnp.repeat(a.astype(jnp.float32), B // G, axis=0)
            return jnp.where(kept[:, :, None], a, 0.0)

        k = jnp.concatenate([rows(pk), k], axis=1)
        v = jnp.concatenate([rows(pv), v], axis=1)
        bias = jnp.concatenate(
            [jnp.broadcast_to(jnp.where(kept, 0.0, -1e9)[:, None, None, :],
                              (B, 1, P, J)), bias], axis=-1)
    return np.asarray(F.masked_attention(q, k, v, bias, heads), np.float32)


@pytest.mark.parametrize("B,P,lengths,block,dtype,tol,counts", [
    (1, 96, [96], 64, jnp.bfloat16, 3e-2, None),
    (1, 160, [1], 64, jnp.bfloat16, 3e-2, None),
    (3, 96, [1, 96, 51], 64, jnp.bfloat16, 3e-2, None),
    (3, 160, [160, 7, 129], 64, jnp.bfloat16, 3e-2, None),
    (3, 160, [64, 128, 65], 32, jnp.float32, 2e-5, None),
    (3, 96, [96, 33, 1], None, jnp.float32, 2e-5, None),  # the kernel's block
    # With a prefix: no row of it, a window's worth, two windows' worth; a
    # prefix a row, and one that a prompt's three windows share.
    (3, 96, [1, 96, 51], 64, jnp.bfloat16, 3e-2, [0, PER, 2 * PER]),
    (3, 160, [160, 7, 129], 64, jnp.bfloat16, 3e-2, (0, PER, 2 * PER)),
    (3, 160, [64, 128, 65], 32, jnp.float32, 2e-5, [2 * PER, 3, PER]),
    (3, 96, [96, 96, 33], None, jnp.float32, 2e-5, (0, PER, 2 * PER)),
    (1, 160, [1], 64, jnp.float32, 2e-5, [PER]),
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_kernel_matches_the_masked_form_on_every_real_row(
        rng, B, P, lengths, block, dtype, tol, counts):
    q, k, v = _qkv(rng, B, P, dtype)
    n = jnp.asarray(lengths, jnp.int32)
    prefix = _prefix(rng, B, counts, dtype)
    got = np.asarray(F.prompt_attention(q, k, v, n, heads=HEADS, block=block,
                                        prefix=prefix, interpret=True),
                     np.float32)
    want = (_reference(q, k, v, n, HEADS, prefix) if prefix else np.asarray(
        F.masked_attention(q, k, v, F.prompt_mask(n, P), HEADS), np.float32))
    assert got.shape == want.shape and np.isfinite(got).all()
    for b, length in enumerate(lengths):
        np.testing.assert_allclose(got[b, :length], want[b, :length],
                                   atol=tol, rtol=tol)


@pytest.mark.parametrize("block", [16, 32, 160])
def test_kernel_at_other_blocks(rng, block):
    q, k, v = _qkv(rng, 2, 160, jnp.float32)
    n = jnp.asarray([160, 45], jnp.int32)
    got = np.asarray(F.prompt_attention(q, k, v, n, heads=HEADS, block=block,
                                        interpret=True))
    want = np.asarray(F.masked_attention(q, k, v, F.prompt_mask(n, 160),
                                         HEADS))
    for b, length in enumerate((160, 45)):
        np.testing.assert_allclose(got[b, :length], want[b, :length],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads,head_dim,counts", [
    (3, 128, None), (8, 32, None), (2, 64, None),
    (3, 128, [PER, 2 * PER]), (3, 128, (0, 2 * PER)), (2, 64, [2 * PER, 5]),
    (8, 32, (PER, 0))], ids=str)
def test_kernel_head_sizes_that_fill_lane_tiles(rng, heads, head_dim, counts):
    q, k, v = _qkv(rng, 2, 64, jnp.float32, heads * head_dim)
    n = jnp.asarray([64, 20], jnp.int32)
    prefix = _prefix(rng, 2, counts, jnp.float32, heads * head_dim)
    got = np.asarray(F.prompt_attention(q, k, v, n, heads=heads, block=32,
                                        prefix=prefix, interpret=True))
    want = _reference(q, k, v, n, heads, prefix)
    for b, length in enumerate((64, 20)):
        np.testing.assert_allclose(got[b, :length], want[b, :length],
                                   atol=2e-5, rtol=2e-5)


def test_kernel_refuses_heads_that_fill_no_lane_tile():
    x = jnp.zeros((1, 32, 2 * 80))
    with pytest.raises(ValueError, match="lane tiles"):
        F.prompt_attention(x, x, x, jnp.asarray([32]), heads=2, interpret=True)


@pytest.mark.parametrize("counts", [None, [2 * PER]], ids=str)
def test_a_q_block_past_the_length_comes_out_zeros(rng, counts):
    q, k, v = _qkv(rng, 1, 128, jnp.float32)
    got = np.asarray(F.prompt_attention(
        q, k, v, jnp.asarray([40]), heads=HEADS, block=64,
        prefix=_prefix(rng, 1, counts, jnp.float32), interpret=True))
    assert not got[0, 64:].any() and got[0, :64].any()


@pytest.mark.parametrize("heads,head_dim", [(2, 128), (HEADS, HEAD_DIM)],
                         ids=str)
@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf],
                         ids=["nan", "inf", "-inf"])
def test_what_lies_past_a_prefix_s_count_reaches_no_output(rng, poison, heads,
                                                           head_dim):
    """A prefix's rows past a row's count hold NaN or Inf, in keys and
    values alike (in EvaByte's prefill they are the summaries of windows
    that come later): every output is what clean rows give, all finite.
    The counts: none of the prefix, a window's worth, both windows'."""
    width = heads * head_dim
    q, k, v = _qkv(rng, 3, 64, jnp.float32, width)
    n = jnp.asarray([64, 20, 33], jnp.int32)
    counts = [0, PER, 2 * PER - 1]
    dirty = _prefix(rng, 3, counts, jnp.float32, width, poison=poison)
    got = np.asarray(F.prompt_attention(q, k, v, n, heads=heads, block=32,
                                        prefix=dirty, interpret=True))
    assert np.isfinite(got).all()
    want = _reference(q, k, v, n, heads, dirty)
    for b, length in enumerate((64, 20, 33)):
        np.testing.assert_allclose(got[b, :length], want[b, :length],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("poison", [np.nan, np.inf, -np.inf],
                         ids=["nan", "inf", "-inf"])
def test_what_lies_past_the_rows_end_reaches_no_output(rng, poison):
    """Five heads of 64 are two and a half lane tiles.  The kernel's body
    over rows stored three tiles wide, the last half tile NaN or Inf in Q,
    K and V alike (on the chip it is whatever lies behind the rows): the
    320 real lanes come out as they do from clean rows, all finite."""
    B, P, block, width, stored = 2, 64, 32, HEADS * HEAD_DIM, 384
    q, k, v = _qkv(rng, B, P, jnp.float32)
    n = jnp.asarray([64, 20], jnp.int32)
    spec = pl.BlockSpec((None, P, 128), lambda b, t, lens: (b, 0, t))
    got = pl.pallas_call(
        functools.partial(F._prompt_kernel, sm_scale=HEAD_DIM ** -0.5,
                          block=block, head_dim=HEAD_DIM, width=width),
        out_shape=jax.ShapeDtypeStruct((B, P, stored), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, stored // 128),
            in_specs=[spec, spec, spec], out_specs=spec,
            scratch_shapes=[pltpu.VMEM((P, 128), jnp.float32)]),
        interpret=True,
    )(n, *(jnp.pad(a, ((0, 0), (0, 0), (0, stored - width)),
                   constant_values=poison) for a in (q, k, v)))
    got = np.asarray(got)[:, :, :width]
    want = np.asarray(F.prompt_attention(q, k, v, n, heads=HEADS,
                                         block=block, interpret=True))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


# -- the prefill with the kernel forced -------------------------------------------

CFG = dataclasses.replace(G.SMALL, vocab_size=300, d_model=HEADS * HEAD_DIM,
                          layers=2, heads=HEADS, ffn_dim=256,
                          max_positions=256, eos_id=299)


def _force_kernel(monkeypatch):
    """The picker takes the kernel, which runs under the interpreter."""
    monkeypatch.setattr(F, "prompt_form", lambda *shape: "kernel")
    monkeypatch.setattr(F, "prompt_attention", functools.partial(
        F.prompt_attention, interpret=True))


def _prefill(tokens, lengths, total):
    fam = G.family(CFG)
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(5, CFG))
    return jax.jit(lambda p, t, n: fresh_pool.prefill(
        fam, p, t, n, total, jnp.float32))(
        params, jnp.asarray(tokens), jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("P,lengths", [(96, [96, 1, 40]), (160, [23])])
def test_prefill_with_the_kernel_forced_matches_the_masked_form(
        rng, monkeypatch, P, lengths):
    tokens = rng.integers(0, 299, (len(lengths), P)).astype(np.int32)
    want = _prefill(tokens, lengths, P + 8)
    _force_kernel(monkeypatch)
    got = _prefill(tokens, lengths, P + 8)
    np.testing.assert_allclose(got[0], want[0], atol=2e-4, rtol=2e-4)
    for b, n in enumerate(lengths):  # the rows a decode step will read
        for ck_got, ck_want in zip(got[1:], want[1:]):
            np.testing.assert_allclose(ck_got[:, b, :n], ck_want[:, b, :n],
                                       atol=2e-4, rtol=2e-4)
    assert all(np.isfinite(np.asarray(a)).all() for a in got)


def test_prefill_on_the_cpu_lowers_to_the_masked_form(monkeypatch):
    """Off the chip a prefill is the arithmetic it was before the kernel:
    the program as built is, letter for letter, the one whose attention is
    ``masked_attention`` under ``prompt_mask``, float32 scores ``[B, H, P,
    P]`` and all, and it holds no kernel."""
    fam = G.family(CFG)
    params = jax.eval_shape(lambda: jax.tree.map(
        jnp.asarray, G.init_gpt2_params(5, CFG)))
    shapes = (params, jax.ShapeDtypeStruct((2, 96), jnp.int32),
              jax.ShapeDtypeStruct((2,), jnp.int32))

    def text():
        return jax.jit(lambda p, t, n: fresh_pool.prefill(
            fam, p, t, n, 104, jnp.bfloat16)).lower(*shapes).as_text()

    built = text()
    assert f"2x{HEADS}x96x96xf32" in built and "custom_call" not in built
    monkeypatch.setattr(
        D, "prompt_attend", lambda q, k, v, lengths, heads: F.masked_attention(
            q, k, v, F.prompt_mask(lengths, q.shape[1]), heads))
    assert text() == built


def _greedy(tokens, lengths, max_new):
    fam = G.family(CFG)
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(5, CFG))
    B = len(lengths)
    return np.asarray(jax.jit(
        lambda p, t, n: D.generate(fam, p, t, n, jnp.zeros((B,), jnp.float32),
                                   jnp.zeros((B,), jnp.int32), max_new,
                                   jnp.float32))(
        params, jnp.asarray(tokens), jnp.asarray(lengths, jnp.int32)))


def test_greedy_tokens_equal_with_the_kernel_forced(rng, monkeypatch):
    tokens = rng.integers(0, 299, (2, 96)).astype(np.int32)
    want = _greedy(tokens, [96, 17], 6)
    _force_kernel(monkeypatch)
    np.testing.assert_array_equal(_greedy(tokens, [96, 17], 6), want)


# -- the picker --------------------------------------------------------------------

def test_picker_takes_the_masked_form_off_the_chip(rng, monkeypatch):
    """Here the backend is the CPU: the kernel must not be reached."""
    monkeypatch.setattr(F, "prompt_attention", None)  # a call would raise
    q, k, v = _qkv(rng, 2, 64, jnp.float32)
    n = jnp.asarray([64, 9], jnp.int32)
    assert F.prompt_form(8, 25, 768, 64) == "einsum"
    got = F.prompt_attend(q, k, v, n, HEADS)
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(F.masked_attention(q, k, v, F.prompt_mask(n, 64), HEADS)))


def test_picker_takes_the_masked_form_on_a_mesh(monkeypatch):
    """A process that addresses several TPU devices keeps the einsums, which
    the partitioner splits; one device takes the kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert F.prompt_form(4, 25, 768, 64) == "einsum"
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert F.prompt_form(4, 25, 768, 64) == "kernel"
    assert F.prompt_form(4, 25, 768, 80) == "einsum"  # no whole lane tiles


# What the chip's table (PERF.md section 6, PR 40) says of each of the
# benchmark's prefill shapes: the kernel from 100 MiB of float32 scores on.
KERNEL_SHAPES = {(8, 512, 25), (2, 768, 25), (4, 768, 25), (8, 768, 25),
                 (4, 512, 25), (8, 512, 20), (16, 512, 20), (8, 768, 20),
                 (16, 768, 20)}


@pytest.mark.parametrize("batch,bucket,heads", chip_smoke.PROMPT_SHAPES,
                         ids=lambda v: str(v))
def test_shape_rule_at_the_benchmarks_prefill_shapes(monkeypatch, batch,
                                                     bucket, heads):
    """On one device of a TPU the rule reads the shapes alone."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    want = "kernel" if (batch, bucket, heads) in KERNEL_SHAPES else "einsum"
    assert F.prompt_form(batch, heads, bucket, 64) == want
    assert D.ROWS.prompt_form(batch, heads, bucket, 64) == want


def test_shape_rule_keeps_long_prompts_on_the_masked_form(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert F.prompt_form(1, 25, 2048, 64) == "kernel"
    assert F.prompt_form(1, 25, 4096, 64) == "einsum"


# -- the counter -------------------------------------------------------------------

_ARCH = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 64,
         "vocab_size": 300, "max_positions": 64}


async def test_counter_rises_only_for_a_dispatch_whose_form_is_the_kernel(
        aiohttp_client, tmp_path, monkeypatch):
    """``prefill_kernel_dispatches`` counts, beside ``prefill_dispatches``,
    the dispatches whose (padded batch, bucket) the model's ``prompt_form``
    calls the kernel's: here it is made to say so of two prompts and more,
    so a lone request does not count and a pair does.  The counter is in
    ``/metrics``, in the Prometheus text, and each ``prefill.launch`` phase
    of ``GET /admin/trace`` names its form."""
    from pytorch_zappa_serverless_tpu.serving.server import Server

    said = []
    monkeypatch.setattr(
        D, "prompt_form", lambda batch, heads, P, head_dim: said.append(
            (batch, heads, P, head_dim)) or (
                "kernel" if batch >= 2 else "einsum"))
    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"),
        models=[ModelConfig(
            name="gpt2", batch_buckets=(1, 2), seq_buckets=(8,),
            dtype="float32", coalesce_ms=1.0,
            extra={"max_new_tokens": 8, "gen_slots": 2, "segment_tokens": 4,
                   "arch": _ARCH})])
    engine = build_engine(cfg)

    async def counters(client):
        snap = (await (await client.get("/metrics")).json())
        return snap["generation"]["gpt2"]

    try:
        server = Server(cfg, engine=engine)
        client = await aiohttp_client(server.app)
        sched = server.schedulers["gpt2"]
        cm = engine.model("gpt2")

        async def generate(n):  # submitted together: one admission
            reqs = [sched.submit(cm.servable.preprocess(
                {"input_ids": [3 + i, 5, 7]}), max_new=4) for i in range(n)]
            await asyncio.wait_for(
                asyncio.gather(*[r.done for r in reqs]), 120)

        assert (await counters(client))["prefill_kernel_dispatches"] == 0
        del said[:]  # the boot log asked
        await generate(1)
        alone = await counters(client)
        assert (alone["prefill_dispatches"],
                alone["prefill_kernel_dispatches"]) == (1, 0)
        await generate(2)
        pair = await counters(client)
        assert (pair["prefill_dispatches"],
                pair["prefill_kernel_dispatches"]) == (2, 1)
        # One question a dispatch, of the shapes it runs at.
        assert said == [(1, 2, 8, 16), (2, 2, 8, 16)]
        prom = await (await client.get(
            "/metrics", params={"format": "prometheus"})).text()
        assert 'tpuserve_prefill_kernel_dispatches_total{model="gpt2"} 1' \
            in prom
        assert 'tpuserve_prefill_dispatches_total{model="gpt2"} 2' in prom
        r = await client.get("/admin/trace?rounds=256&model=gpt2")
        launches = [ph for rnd in (await r.json())["rounds"]["gpt2"]
                    for ph in rnd["phases"] if ph["phase"] == "prefill.launch"]
        assert [(ph["batch"], ph["form"]) for ph in launches] \
            == [(1, "einsum"), (2, "kernel")]
    finally:
        engine.shutdown()


async def test_a_model_that_names_no_form_counts_no_kernel_dispatch(tmp_path):
    """Whisper's lane hands the scheduler no ``prompt_form``: its prefills
    are its own, and the boot log and the phases say so."""
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    engine = build_engine(ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"), warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", batch_buckets=(1,), seq_buckets=(8,),
            dtype="float32",
            extra={"max_new_tokens": 8, "gen_slots": 2, "segment_tokens": 4,
                   "arch": _ARCH})]))
    try:
        cm = engine.model("gpt2")
        del cm.servable.meta["continuous"]["prompt_form"]
        sched = GenerationScheduler(cm, engine.runner, cm.cfg).start()
        try:
            assert sched._prompt_forms() == {"8": {"1": "own", "2": "own"}}
            req = sched.submit(cm.servable.preprocess({"input_ids": [3, 4]}),
                               max_new=2)
            await asyncio.wait_for(req.done, 120)
            snap = sched.gen_snapshot()
            assert (snap["prefill_dispatches"],
                    snap["prefill_kernel_dispatches"]) == (1, 0)
        finally:
            await sched.stop()
    finally:
        engine.shutdown()
