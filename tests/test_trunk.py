"""models/decoder.py's trunk: one layer traced and lowered once a program.

``_trunk`` hands every layer to one jitted body with the layer's index as
data, so a program's Python runs the family's block once and its lowered
module holds one private function with a ``call`` a layer.  Held here to the
plain Python loop it replaced (:func:`loop_trunk`, kept as the reference:
``tests/test_aot_tpu_compile.py`` compiles both for the described v5e too),
bit for bit, on every pool and family the programs serve; and to the ledger's
``layer_traces``, which says whether the mechanism held.

Tiny sizes: GPT-2 in bfloat16 (3 layers, 2 heads of 16), the W8A16 lane as
its builder quantizes it (2 layers of 128, the Mosaic kernel interpreted),
EvaByte (3 layers, window 32, chunk 4), GPT-2 with two tenants' LoRA
stacks, and JoyAI-LLM-Flash (3 layers, a pool of one leaf, counters).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fresh_pool

from pytorch_zappa_serverless_tpu.config import ModelConfig
from pytorch_zappa_serverless_tpu.engine.cache import CompileClock
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import evabyte as E
from pytorch_zappa_serverless_tpu.models import gpt2 as G
from pytorch_zappa_serverless_tpu.models import joyai as J
from pytorch_zappa_serverless_tpu.ops import lora as L
from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder


def loop_trunk(fam, params, x, pos, cache, attend, adapter_idx=None,
               lengths=None, put=None):
    """The trunk as it was before the shared body: a Python loop that calls
    the family's block afresh a layer, with the layer's index a Python int
    (for the families whose cache is rows, a K and a V or one leaf, and
    nothing else; a family that counts is handed ``count=``)."""
    stacks = None if adapter_idx is None else params.get("__adapters__")
    counts = None

    def count(c):
        nonlocal counts
        counts = c if counts is None else counts + c

    hooks = {"state": None, "count": count} if fam.counters else {}
    for i in range(fam.layers):
        p = params[f"layer{i}"]

        def layer_attend(q, k, v=None, i=i, p=p):
            nonlocal cache
            cache, out = attend(p, cache, i, q, k, v)
            return out

        x = fam.layer(p, x, layer_attend, pos,
                      lora=None if stacks is None else stacks.get(f"layer{i}"),
                      lora_idx=adapter_idx, **hooks)
    return fam.norm(params, x), cache, counts


# -- the families -------------------------------------------------------------

GPT2 = G.GPT2Config(vocab_size=96, d_model=32, layers=3, heads=2, ffn_dim=64,
                    max_positions=64, eos_id=95)
W8A16_ARCH = {"vocab_size": 512, "d_model": 128, "layers": 2, "heads": 2,
              "ffn_dim": 256, "max_positions": 64, "eos_id": 511}
EVA = E.EvaByteConfig(vocab_size=48, hidden_size=32, layers=3, heads=2,
                      intermediate_size=48, max_positions=256, window_size=32,
                      chunk_size=4, num_pred_heads=2, rope_theta=100.0,
                      init_std=0.3, eos_id=48)
LORA_DIMS = {"q": (32, 32), "v": (32, 32), "fc1": (32, 64)}


def _gpt2(dtype=jnp.bfloat16):
    tree = jax.tree.map(lambda a: jnp.asarray(a, dtype),
                        G.init_gpt2_params(3, GPT2))
    return G.family(GPT2), tree, dtype


def _w8a16():
    sv = get_model_builder("gpt2")(ModelConfig(
        name="gpt2", dtype="bfloat16", seq_buckets=(16,), batch_buckets=(2,),
        extra={"max_new_tokens": 8, "arch": W8A16_ARCH,
               "params_dtype": "int8", "quantize_min_size": 1024}))
    assert sv.params["layer0"]["qkv"]["kernel_q"].dtype == jnp.int8
    return G.family(G.GPT2Config(**W8A16_ARCH)), sv.params, jnp.bfloat16


def _evabyte():
    rows = E.TwoTier(EVA.window_size, EVA.chunk_size, EVA.heads, 8,
                     block_q=16)
    tree = jax.tree.map(jnp.asarray,
                        E.init_evabyte_params(0, EVA, pool_scale=1.0))
    return E.family(EVA, rows), tree, jnp.float32


def _lora():
    fam, tree, dtype = _gpt2(jnp.float32)
    stacks = {f"layer{i}": L.zero_stacks(3, 4, LORA_DIMS)
              for i in range(GPT2.layers)}
    g = np.random.default_rng(11)
    for layer in stacks.values():
        for node in layer.values():
            for leaf in node.values():
                leaf[1:] = g.standard_normal(leaf[1:].shape) * 0.2
    return fam, {**tree, "__adapters__": jax.tree.map(jnp.asarray, stacks)}, \
        dtype


def _joyai():
    cfg = J.config_from_arch({
        "vocab_size": 96, "hidden_size": 64, "layers": 3, "heads": 4,
        "q_lora_rank": 48, "kv_lora_rank": 32, "nope_dim": 16, "rope_dim": 8,
        "v_dim": 16, "dense_layers": 1, "dense_width": 96,
        "experts_published": 16, "experts_held": 4, "expert_offset": 4,
        "top_k": 4, "expert_width": 48, "rope_theta": 100.0,
        "max_positions": 512, "init_std": 0.1, "eos_id": 96})
    tree = jax.tree.map(jnp.asarray, J.init_joyai_params(0, cfg))
    return J.family(cfg, jnp.float32), tree, jnp.float32


FAMILIES = {"gpt2": _gpt2, "w8a16": _w8a16, "evabyte": _evabyte,
            "lora": _lora, "joyai": _joyai}


@pytest.fixture(scope="module")
def built():
    """Each family's ``(fam, params, dtype)``, built once a module."""
    made = {}
    return lambda name: made.setdefault(name, FAMILIES[name]())


# -- the programs --------------------------------------------------------------

S, P, NEW, BS = 3, 40, 8, 4  # slots, prompt bucket, positions after it, page


def _program(name, fam, dtype, adapters):
    """``(fn, args)``: program ``name`` of models/decoder.py as a function of
    the parameter tree and arrays alone, and seeded arguments after the
    tree.  Prompts of 40 cross EvaByte's window of 32; the third slot of a
    segment is finished, the second one position short of its pool's end."""
    g = np.random.default_rng(7)
    total = P + NEW
    aidx = jnp.asarray([0, 1, 2], jnp.int32) if adapters else None
    toks = jnp.asarray(g.integers(0, fam.vocab_size - 1, (S, P)), jnp.int32)
    lens = jnp.asarray([P, 33, 9], jnp.int32)
    zf, zi = jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.int32)
    fin = jnp.asarray([False, False, True])

    def pool(*shape):
        return jnp.asarray(g.standard_normal(shape), dtype)

    if name == "prefill":
        return (lambda p, t, n: fresh_pool.prefill(fam, p, t, n, total,
                                                   dtype, aidx),
                (toks, lens))
    if name == "segment":
        leaves = D.cache_leaves(fam, S, fam.rows.count(total), dtype)
        pos = jnp.asarray([P, total - 2, 9], jnp.int32)

        def segment(p, *rest):  # the pool's leaves (a K and a V, or one)
            mine, state = D.slot_pools(fam, rest[:-2])
            return D.decode_segment(
                fam, p, mine, *rest[-2:], zi, fin, zf, zi, 3, dtype,
                adapter_idx=aidx, state=state)[:3]

        return segment, (*(pool(*shape) for shape, _ in leaves), toks[:, 0],
                         pos)
    # The paged programs: MB pages a slot of a pool of 2 + S * MB.
    MB = total // BS
    shape = (fam.layers, 2 + S * MB, BS, fam.width)
    table = jnp.asarray(2 + np.arange(S * MB).reshape(S, MB), jnp.int32)
    pages = (pool(*shape), pool(*shape), table)
    if name == "prefill_chunk":
        start = jnp.asarray([8, 0, 4], jnp.int32)
        return (lambda p, ck, cv, tb, t, st, n: D.prefill_chunk(
            fam, p, t, st, n, D.PagedPool(ck, cv, tb, BS), zf, zi, zi,
            zf + 1.0, dtype, adapter_idx=aidx),
            (*pages, toks[:, :8], start, lens))
    pos = jnp.asarray([P, 33, 9], jnp.int32)
    if name == "paged_segment":
        return (lambda p, ck, cv, tb, tok, pos: D.decode_segment(
            fam, p, D.PagedPool(ck, cv, tb, BS), tok, pos, zi, fin, zf, zi,
            3, dtype, adapter_idx=aidx)[:3], (*pages, toks[:, 0], pos))
    if name == "verify":
        return (lambda p, ck, cv, tb, t, pos: D.verify(
            fam, p, D.PagedPool(ck, cv, tb, BS), t, pos, dtype),
            (*pages, toks[:, :4], pos))
    assert name == "propose"
    return (lambda p, ck, cv, tb, prev, tok, pos: D.propose(
        fam, p, D.PagedPool(ck, cv, tb, BS), prev, tok, pos, zi, fin, zf, zi,
        3, dtype), (*pages, toks[:, 1], toks[:, 0], pos))


# -- (a) traced once, lowered once ----------------------------------------------

@pytest.mark.parametrize("family,program", [
    ("gpt2", "prefill"), ("gpt2", "segment"), ("gpt2", "prefill_chunk"),
    ("gpt2", "verify"), ("w8a16", "prefill"), ("w8a16", "segment"),
    ("w8a16", "prefill_chunk"), ("w8a16", "verify"), ("evabyte", "prefill"),
    ("evabyte", "segment")])
def test_a_program_traces_its_layer_once_and_lowers_it_once(built, family,
                                                            program):
    fam, params, dtype = built(family)
    runs = []
    counted = dataclasses.replace(
        fam, layer=lambda *a, **kw: runs.append(1) or fam.layer(*a, **kw))
    fn, args = _program(program, counted, dtype, False)
    clock = CompileClock()
    with clock.open(family, program, {}, seen=set()) as use:
        text = jax.jit(fn).lower(params, *args).as_text()
    assert len(runs) == 1 and use.entry["layer_traces"] == 1
    calls = re.findall(r"call @(layer\w*)\(", text)
    assert len(calls) == fam.layers and len(set(calls)) == 1, calls
    assert len(re.findall(rf"func\.func private @{calls[0]}\(", text)) == 1


# -- (b) bit for bit the loop's -------------------------------------------------

@pytest.mark.parametrize("family,program", [
    ("gpt2", "prefill"), ("gpt2", "segment"), ("gpt2", "prefill_chunk"),
    ("gpt2", "paged_segment"), ("gpt2", "verify"), ("gpt2", "propose"),
    ("w8a16", "prefill"), ("w8a16", "segment"), ("evabyte", "prefill"),
    ("evabyte", "segment"), ("lora", "prefill"), ("lora", "segment"),
    ("lora", "prefill_chunk"), ("lora", "paged_segment"),
    ("joyai", "prefill"), ("joyai", "segment")])
def test_the_shared_body_is_the_loop_bit_for_bit(built, monkeypatch, family,
                                                 program):
    """Everything a program returns (logits or tokens, then both cache
    arrays, or the one leaf and what follows it) with the shared body
    against the loop over the same inputs: on the slot pool, the paged pool,
    with adapter indices, on ``TwoTier`` rows, on rows of one leaf."""
    fam, params, dtype = built(family)
    fn, args = _program(program, fam, dtype, family == "lora")
    shared = jax.jit(fn)(params, *args)
    monkeypatch.setattr(D, "_trunk", loop_trunk)
    loop = jax.jit(fn)(params, *args)
    assert len(shared) >= 3 - (family == "joyai")  # one leaf, not two
    for got, want in zip(shared, loop, strict=True):
        assert got.dtype == want.dtype
        assert np.array_equal(np.asarray(got), np.asarray(want),
                              equal_nan=True)
    assert np.isfinite(np.asarray(shared[0], np.float32)).all()


# -- (c) the ledger says whether it held ------------------------------------------

def _traced(fam, params, dtype):
    fn, args = _program("prefill", fam, dtype, False)
    clock = CompileClock()
    with clock.open("m", "prefill", {"batch": S, "bucket": P},
                    seen=set()):
        out = jax.jit(fn)(params, *args)
    return clock.snapshot()[-1], out


def test_first_use_entry_counts_a_trace_a_trunk(built):
    fam, params, dtype = built("gpt2")
    entry, _ = _traced(fam, params, dtype)
    assert entry["layer_traces"] == 1 and entry["program"] == "prefill"
    clock = CompileClock()
    with clock.open("m", "predict", {}, seen=set()) as use:
        assert use.entry["layer_traces"] == 0  # no trunk traced yet
        zf, zi = jnp.zeros((2,), jnp.float32), jnp.zeros((2,), jnp.int32)
        jax.jit(lambda p: D.generate(
            fam, p, jnp.ones((2, 8), jnp.int32), zi + 8, zf, zi, 4,
            dtype))(params)
    # The fixed-batch program holds a prefill's trunk and a segment's.
    assert use.entry["layer_traces"] == 2


def test_a_layer_unlike_its_neighbours_is_traced_again_and_counted(
        built, monkeypatch):
    """Every layer of this tree has a dtype of its own, so no call can reuse
    another's trace: the ledger reads ``layers``, not 1, and what is served
    is still the loop's."""
    fam, params, _ = built("gpt2")
    kinds = (jnp.float32, jnp.bfloat16, jnp.float16)
    mixed = {**params, **{
        f"layer{i}": jax.tree.map(lambda a, kind=kind: a.astype(kind),
                                  params[f"layer{i}"])
        for i, kind in enumerate(kinds)}}
    entry, shared = _traced(fam, mixed, jnp.float32)
    assert entry["layer_traces"] == fam.layers == 3
    monkeypatch.setattr(D, "_trunk", loop_trunk)
    _, loop = _traced(fam, mixed, jnp.float32)
    for got, want in zip(shared, loop, strict=True):
        assert np.array_equal(np.asarray(got), np.asarray(want))
