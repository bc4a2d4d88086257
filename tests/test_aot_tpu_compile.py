"""Chip-less TPU compiles of the Pallas kernels at real widths.

Interpret mode on the CPU cannot see what Mosaic refuses: a slice not
aligned to the tiling, more fast memory than a kernel may use, a shape the
kernel cannot partition.  The TPU compiler is installed here and compiles
for a chip that is *described* and not attached (the ``on-chip-measurement``
guide, section 2), so each case below lowers one kernel with
``interpret=False`` for one device of a ``v5e:2x2`` topology and asserts a
``tpu_custom_call`` came out.  A compile that passes is not a chip run:
``chip_smoke.py`` is where results are checked on the device.

The file name sorts early on purpose, so a suite cut by its clock still
reaches it.  The persistent compile cache is switched off around the cases:
a TPU executable written here cannot be read back without a chip.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

import chip_smoke
from pytorch_zappa_serverless_tpu.models import decoder, evabyte, gpt2
from pytorch_zappa_serverless_tpu.ops import (
    decode_attention as decode_attention_module)
from pytorch_zappa_serverless_tpu.ops.decode_attention import (
    decode_attention, pick_block_t, work_list)
from pytorch_zappa_serverless_tpu.ops import (
    flash_attention as flash_attention_module)
from pytorch_zappa_serverless_tpu.ops.flash_attention import (
    PROMPT_KERNEL_MAX_POSITIONS, flash_attention, prompt_attention)
from pytorch_zappa_serverless_tpu.ops.int8_matmul import (
    int8_matmul, padded_columns)


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip, with the compile cache off."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no compiler in this environment
        pytest.skip(f"v5e:2x2 topology cannot be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    """Lower ``fn`` over ``(shape, dtype)`` arguments placed on the described
    chip; returns the compiled program's text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# GPT-2 small: decode qkv, the 50257-vocab lm head, fc2 (K=3072), and a
# prefill-sized M on fc1.  GPT-2 large at 16 slots: the decode step's five
# shapes (the head as ``pad_weights`` stores it, its logits float32), whose
# blocks hold all of K, and a prefill's rows on qkv, which walk it.
@pytest.mark.parametrize("m,k,n,out_dtype", [
    (8, 768, 2304, None), (8, 768, 50257, None), (8, 3072, 768, None),
    (128, 768, 3072, None),
    (16, 1280, 3840, None), (16, 1280, 1280, None), (16, 1280, 5120, None),
    (16, 5120, 1280, None),
    (16, 1280, padded_columns(1280, 50257), jnp.float32),
    (8, 768, padded_columns(768, 50257), jnp.float32),
    (2048, 1280, 3840, None)],
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_int8_matmul_compiles_for_v5e(one_chip, m, k, n, out_dtype):
    text = _compile(
        lambda x, w, s: int8_matmul(x, w, s, out_dtype=out_dtype,
                                    interpret=False), one_chip,
        ((m, k), jnp.bfloat16), ((k, n), jnp.int8), ((n,), jnp.float32))
    assert "tpu_custom_call" in text and "int8_matmul" in text


# SD-1.5 UNet self-attention at 512x512 (4096 tokens, CFG batch 2 and the b4
# job batch 8), its deeper levels (1024 x d80, 256 x d160), cross-attention
# over the 77-token prompt, and a causal GPT-2-shaped block.
@pytest.mark.parametrize("b,tq,tk,h,d,causal", [
    (2, 4096, 4096, 8, 64, False),
    (8, 4096, 4096, 8, 64, False),
    (2, 1024, 1024, 8, 80, False),
    (2, 256, 256, 8, 160, False),
    (2, 4096, 77, 8, 64, False),
    (8, 128, 128, 12, 64, True),
], ids=lambda v: str(v))
def test_flash_attention_compiles_for_v5e(one_chip, b, tq, tk, h, d, causal):
    text = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        interpret=False), one_chip,
        ((b, tq, h, d), jnp.bfloat16), ((b, tk, h, d), jnp.bfloat16),
        ((b, tk, h, d), jnp.bfloat16))
    assert "tpu_custom_call" in text


# A prefill's prompt attention over rows [B, P, H x 64] where they lie: the
# benchmark's admission batches (XL's 25 heads are 12.5 lane tiles, so the
# last block hangs over the rows' end), a bucket of one block, and the
# longest prompt the picker hands the kernel.
@pytest.mark.parametrize("b,p,h", [
    (4, 768, 25), (8, 512, 25), (1, 512, 25), (16, 256, 20), (16, 768, 20),
    (8, 512, 20), (1, PROMPT_KERNEL_MAX_POSITIONS, 25),
], ids=lambda v: str(v))
def test_prompt_attention_compiles_for_v5e(one_chip, b, p, h):
    text = _compile(
        lambda q, k, v, n: prompt_attention(q, k, v, n, heads=h), one_chip,
        *[((b, p, h * 64), jnp.bfloat16)] * 3, ((b,), jnp.int32))
    assert "tpu_custom_call" in text


def test_prefill_with_the_prompt_kernel_holds_no_score_array(one_chip,
                                                             monkeypatch):
    """A two-layer prefill at GPT-2 XL's widths, four prompts of 768: with
    the kernel (chosen by backend, and the backend here is the CPU, so the
    test steers the choice) no float32 ``[B, H, P, P]`` or ``[B, 1, P, P]``
    array is left in the program; with the other form both are there."""
    cfg = gpt2.GPT2Config(d_model=1600, layers=2, heads=25, ffn_dim=6400)
    prefill, args = chip_smoke.prefill_program(cfg, 4, 768, 8, 960, one_chip)
    scores, mask = "4x25x768x768xf32", "4x1x768x768xf32"
    text = prefill.lower(*args).as_text()
    assert scores in text and mask in text
    monkeypatch.setattr(flash_attention_module, "prompt_form",
                        lambda *shape: "kernel")
    prefill, args = chip_smoke.prefill_program(cfg, 4, 768, 8, 960, one_chip)
    lowered = prefill.lower(*args)
    text = lowered.as_text()
    assert scores not in text and mask not in text and "768x768" not in text
    compiled = lowered.compile().as_text()
    assert compiled.count("tpu_custom_call") == cfg.layers
    # The kernel reads the projections where the matmuls left them: nothing
    # is transposed, and q, k and v are not copied on the way in.
    moves = [line for _, line in chip_smoke.pool_sized_moves(
        compiled, 4 * 768 * 1600) if "4,768,1600" in line]
    assert len(moves) <= cfg.layers and not any(
        "transpose" in line for line in moves), moves


# Decode attention over the slot pool [L, S, T, D], a middle layer: the
# benchmark's two configurations (XL's d 1600 is 12.5 lane tiles; int8-large
# pools 16 slots), and chip_smoke's GPT-2 small pool of 96 positions.  The
# grid's one bound is the count of the work list built from a traced ``wpos``.
# ``eva``: EvaByte's pool (16 layers, 8 slots of 2,880 rows, 32 heads of 128),
# whose slots read a span with a start.
DECODE_POOLS = {"xl": (48, 8, 960, 1600, 25), "large": (36, 16, 960, 1280, 20),
                "small": (12, 8, 96, 768, 12),
                "eva": (16, 8, 2880, 4096, 32)}


@pytest.mark.parametrize("pool", list(DECODE_POOLS))
def test_decode_attention_compiles_for_v5e(one_chip, pool):
    layers, slots, total, d, heads = DECODE_POOLS[pool]
    bt = pick_block_t(total, d, jnp.bfloat16)
    assert pool != "eva" or bt == 64
    text = _compile(
        lambda q, ck, cv, wpos, first: decode_attention(
            q, ck, cv, wpos, work_list(wpos, total, bt, first), first,
            layer=layers // 2, heads=heads, block_t=bt),
        one_chip,
        ((slots, d), jnp.bfloat16), ((layers, slots, total, d), jnp.bfloat16),
        ((layers, slots, total, d), jnp.bfloat16), ((slots,), jnp.int32),
        ((slots,), jnp.int32))
    assert "tpu_custom_call" in text
    # The pool is the kernel's operand as it lies: nothing as large as one
    # layer of it is sliced or copied on the way in.
    assert not chip_smoke.pool_sized_moves(text, slots * total * d)


def test_decode_attention_takes_its_layer_as_data_on_v5e(one_chip):
    """The layer is an argument of the compiled program (a prefetched scalar
    the index maps read), so one compile reaches every layer of a 3-layer
    pool; still the kernel, and still nothing pool-sized moved."""
    layers, slots, total, d, heads = 3, 8, 960, 1600, 25
    bt = pick_block_t(total, d, jnp.bfloat16)
    text = _compile(
        lambda q, ck, cv, wpos, layer: decode_attention(
            q, ck, cv, wpos, layer=layer, heads=heads, block_t=bt),
        one_chip,
        ((slots, d), jnp.bfloat16), ((layers, slots, total, d), jnp.bfloat16),
        ((layers, slots, total, d), jnp.bfloat16), ((slots,), jnp.int32),
        ((), jnp.int32))
    assert "tpu_custom_call" in text
    assert not chip_smoke.pool_sized_moves(text, slots * total * d)


def _kinds(hlo_text: str) -> dict:
    """Operations of an optimised HLO module outside fused computations,
    counted by kind; a fusion by the name the compiler gave it."""
    import collections
    import re

    fused = set(re.findall(r"\bcalls=%?([\w.\-]+)", hlo_text))
    head = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
    inst = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\s([\w\-]+)\(")
    counts, comp = collections.Counter(), ""
    for line in hlo_text.splitlines():
        m = head.match(line)
        if m:
            comp = m.group(1)
            continue
        m = inst.match(line)
        if m and comp not in fused:
            name, op = m.groups()
            counts[re.sub(r"[.\d]+$", "", name) if op == "fusion"
                   else op] += 1
    return dict(counts)


# What carries a step's bytes and arithmetic, by opcode over the whole module
# (inside fused computations too, where a matmul is a ``convolution``): the
# matmuls, the Mosaic kernels, the cache writes in place, the scan, the
# sampler's sort.
_HEAVY = ("convolution", "custom-call", "dynamic-update-slice", "scatter",
          "while", "conditional", "sort")


def _heavy(hlo_text: str) -> dict:
    return {op: hlo_text.count(f" {op}(") for op in _HEAVY}


@pytest.mark.parametrize("program", ["prefill", "segment"])
def test_shared_layer_compiles_to_the_loop_s_program_on_v5e(
        one_chip, monkeypatch, program):
    """Four layers at GPT-2 XL's widths, with the kernels a chip would take
    (the pickers ask the backend, which is the CPU here, so the test steers
    them): the trunk that calls one traced layer and the Python loop it
    replaced (tests/test_trunk.py keeps it) compile to one program.  XLA
    inlines the calls and folds the layer's index, so no ``call`` is left
    and the prefill's operations are the loop's kind for kind.  The segment
    is held to that in what carries its bytes (``_HEAVY``), in what it moves
    and in its temporaries: XLA simplifies a function called from several
    sites before it inlines it, and there it merges the mean that ``_ln``
    and ``jnp.var`` each compute of one row (three operations of ``[8]``
    floats fewer a layer norm) and fuses the small operations round the
    matmuls otherwise (PERF.md section 6, PR 43)."""
    from test_trunk import loop_trunk

    cfg = gpt2.GPT2Config(d_model=1600, layers=4, heads=25, ffn_dim=6400)
    monkeypatch.setattr(
        decode_attention_module, "_kernel_block",
        lambda Tq, total, d, dtype: (pick_block_t(total, d, dtype)
                                     if Tq == 1 else None))
    monkeypatch.setattr(flash_attention_module, "prompt_form",
                        lambda *shape: "kernel")

    def compiled(calls):
        if program == "prefill":
            fn, args = chip_smoke.prefill_program(cfg, 4, 768, 8, 960,
                                                  one_chip)
        else:
            fn, args = chip_smoke.segment_program(cfg, 8, 960, one_chip)
        lowered = fn.lower(*args)
        assert lowered.as_text().count("call @layer(") == calls
        done = lowered.compile()
        return done.as_text(), done.memory_analysis().temp_size_in_bytes

    shared, shared_temp = compiled(cfg.layers)
    monkeypatch.setattr(decoder, "_trunk", loop_trunk)
    loop, loop_temp = compiled(0)
    assert " call(" not in shared  # inlined: nothing is left a call
    assert shared.count("tpu_custom_call") == loop.count("tpu_custom_call") \
        >= cfg.layers
    assert _heavy(shared) == _heavy(loop)
    if program == "prefill":
        assert _kinds(shared) == _kinds(loop)
    else:
        assert sum(_kinds(shared).values()) <= sum(_kinds(loop).values())
        # Nothing as large as a layer of the pool, or as a weight, is moved
        # that the loop's program does not move.
        for elements in (8 * 960 * 1600, 1600 * 1600):
            assert len(chip_smoke.pool_sized_moves(shared, elements)) \
                <= len(chip_smoke.pool_sized_moves(loop, elements))
    assert abs(shared_temp - loop_temp) <= 0.01 * loop_temp


def _evabyte_shapes(cfg, sd):
    """EvaByte's parameter tree as shapes (``sd(*shape, dtype=)``)."""
    D, F = cfg.hidden_size, cfg.intermediate_size

    def vec():
        return sd(D, dtype=jnp.float32)

    params = {"embed": sd(cfg.vocab_size, D), "norm": vec(),
              "head": sd(D, cfg.vocab_size * cfg.num_pred_heads)}
    for i in range(cfg.layers):
        params[f"layer{i}"] = {
            "n1": vec(), "n2": vec(), "mu": vec(), "phi": vec(),
            "q": sd(D, D), "k": sd(D, D), "v": sd(D, D), "o": sd(D, D),
            "gate": sd(D, F), "up": sd(D, F), "down": sd(F, D)}
    return params


def test_evabyte_segment_copies_no_weight_on_v5e(one_chip, monkeypatch):
    """Two layers of EvaByte at the published widths, the 8-slot segment:
    the trunk hands the block its weights as the arguments of a function
    called twice, which XLA simplifies before it inlines it, and a reshape
    folded into a weight there comes out as a copy of the weight a launch
    (33.5 MB each for ``q`` and ``k``: 136 MB of temporaries at two layers
    before ``models/evabyte.py`` kept the projections whole).  The program's
    temporaries stay under one such matrix."""
    cfg = evabyte.EvaByteConfig(layers=2, eos_id=320)
    D, slots = cfg.hidden_size, 8
    monkeypatch.setattr(
        decode_attention_module, "_kernel_block",
        lambda Tq, total, d, dtype: (pick_block_t(total, d, dtype)
                                     if Tq == 1 else None))

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _evabyte_shapes(cfg, sd)
    fam = evabyte.family(cfg, evabyte.TwoTier(
        cfg.window_size, cfg.chunk_size, cfg.heads, 64))
    pool = sd(cfg.layers, slots, fam.rows.count(12288 + 768), D)
    i32, f32 = sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.float32)
    segment = jax.jit(
        lambda p, ck, cv, tok, pos, st, fin, temp, seeds, topk, topp:
        decoder.decode_segment(fam, p, decoder.slot_pool(ck, cv, fam.rows),
                               tok, pos, st, fin, temp, seeds, 8,
                               jnp.bfloat16, top_k=topk, top_p=topp),
        donate_argnums=(1, 2))
    compiled = segment.lower(params, pool, pool, i32, i32, i32,
                             sd(slots, dtype=jnp.bool_), f32, i32, i32,
                             f32).compile()
    assert compiled.as_text().count("tpu_custom_call") == cfg.layers
    assert compiled.memory_analysis().temp_size_in_bytes < D * D * 2


def _evabyte_prefill(fam, params, sd, slots, P, total):
    """EvaByte's prefill of one prompt of ``P`` positions into a pool of
    ``slots`` slots for ``total``, which it donates, lowered from shapes."""
    pool = sd(fam.layers, slots, fam.rows.count(total), fam.width)
    return jax.jit(
        lambda p, ck, cv, at, tokens, lengths: decoder.prefill(
            fam, p, tokens, lengths, (ck, cv), at, jnp.bfloat16),
        donate_argnums=(1, 2)).lower(
            params, pool, pool, sd(1, dtype=jnp.int32),
            sd(1, P, dtype=jnp.int32), sd(1, dtype=jnp.int32))


@pytest.mark.parametrize("family", ["xl", "evabyte"])
def test_prefill_writes_into_the_pool_and_moves_none_of_it(
        one_chip, monkeypatch, family):
    """The admission prefill at GPT-2 XL's widths (four prompts of 768 into
    8 slots of 960 rows) and at EvaByte's (one prompt of 12,288 positions
    into 8 slots of 2,880 rows: the ring, the summaries), four and two
    layers, with the kernels a chip takes: the pool goes in and comes out in
    one buffer (every byte of K and V is aliased), and the program makes no
    cache of zeros (no ``pad``, no ``broadcast``) and copies, slices or
    transposes nothing as large as a slot's rows of one layer with the
    pool's row count in its shape (``chip_smoke.prefill_pool_moves``)."""
    monkeypatch.setattr(flash_attention_module, "prompt_form",
                        lambda *shape: "kernel")
    if family == "xl":
        cfg = gpt2.GPT2Config(d_model=1600, layers=4, heads=25, ffn_dim=6400)
        slots, rows, width = 8, 960, cfg.d_model
        prefill, args = chip_smoke.prefill_program(cfg, 4, 768, slots, rows,
                                                   one_chip)
        done = prefill.lower(*args).compile()
    else:
        cfg = evabyte.EvaByteConfig(layers=2, eos_id=320)

        def sd(*shape, dtype=jnp.bfloat16):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        fam = evabyte.family(cfg, evabyte.TwoTier(
            cfg.window_size, cfg.chunk_size, cfg.heads, 64))
        slots, total, width = 8, 12288 + 768, cfg.hidden_size
        rows = fam.rows.count(total)
        assert rows == 2880
        done = _evabyte_prefill(fam, _evabyte_shapes(cfg, sd), sd, slots,
                                12288, total).compile()
    text = done.as_text()
    assert text.count("tpu_custom_call") == cfg.layers
    pool_bytes = 2 * cfg.layers * slots * rows * width * 2
    assert done.memory_analysis().alias_size_in_bytes >= pool_bytes
    assert chip_smoke.prefill_pool_moves(text, rows, width) == []


@pytest.mark.parametrize("options", ["defaults", "no_remat"])
def test_evabyte_prefill_recomputes_nothing_with_the_options_it_is_given(
        one_chip, monkeypatch, options):
    """The benchmark's sixteen layers of EvaByte (6.5 GB of weights as
    shapes), one prompt of 6,144 positions into the 8-slot pool (6 GB,
    donated).  With the compiler's defaults the rematerialisation pass
    recomputes dozens to hundreds of instructions of it, a layer's V
    projection among them, as if the pool were held twice (with 4 slots it recomputes
    nothing; the chip read the prefill 8% slower than the parent's, PERF.md
    section 6, PR 50); with what ``build_gen_kernels`` compiles a prefill
    with on a TPU it recomputes nothing and its temporaries are no larger.
    Should ``defaults`` fail, this libtpu no longer does it and the option
    can go (``serving/generation._prefill_compiler_options``)."""
    from pytorch_zappa_serverless_tpu.serving.generation import _NO_REMAT

    monkeypatch.setattr(flash_attention_module, "prompt_form",
                        lambda *shape: "kernel")
    cfg = evabyte.EvaByteConfig(layers=16, eos_id=320)

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fam = evabyte.family(cfg, evabyte.TwoTier(
        cfg.window_size, cfg.chunk_size, cfg.heads, 64))
    done = _evabyte_prefill(fam, _evabyte_shapes(cfg, sd), sd, 8, 6144,
                            12288 + 768).compile(
        compiler_options=_NO_REMAT if options == "no_remat" else {})
    recomputed = done.as_text().count(".remat = ")
    assert done.memory_analysis().temp_size_in_bytes < 0.6e9
    if options == "no_remat":
        assert recomputed == 0
    else:
        assert recomputed > 0


def test_evabyte_prefill_with_the_prompt_kernel_holds_no_score_block(
        one_chip, monkeypatch):
    """Two layers of EvaByte at the published widths, one prompt of 12,288
    positions (six windows, 768 summaries), in both forms of its prompt
    attention.  Form ``windows`` (what the picker says here, the backend
    being the CPU) writes a block's float32 scores ``[1, 32, 512, 2048 +
    768]``; with the kernel (the test steers the picker) the program calls
    ``prompt_attention`` once a layer and no float32 array with the 32
    heads and a block's 512 queries in its shape is as large as ``[32,
    512, 2048]``.  Its temporaries: the rotation leaves ``q`` and ``k`` a
    head at a time as ``[128, P]`` (the compiler's choice, which the scan
    read as it lay) and the kernel reads rows, so each is laid out anew on
    the way in and this two-layer program holds one ``[P, 4096]`` bfloat16
    array more at its peak than the other form's (1,041.6 against 940.5
    MB; the 16-layer program the cell runs 929.9 against 926.8: PERF.md
    section 6, PR 46).  Not more than that one array."""
    import re

    cfg = evabyte.EvaByteConfig(layers=2, eos_id=320)
    P, total = 12288, 12288 + 768

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _evabyte_shapes(cfg, sd)
    rows = evabyte.TwoTier(cfg.window_size, cfg.chunk_size, cfg.heads, 64)
    fam = evabyte.family(cfg, rows)

    def compiled():
        done = _evabyte_prefill(fam, params, sd, 8, P, total).compile()
        return done.as_text(), done.memory_analysis().temp_size_in_bytes

    def score_blocks(text):
        """float32 arrays of a block's scores or larger: 32 heads and 512
        queries among their dimensions."""
        found = set()
        for dims in re.findall(r"f32\[([\d,]+)\]", text):
            shape = [int(d) for d in dims.split(",")]
            if {32, 512} <= set(shape) \
                    and np.prod(shape) >= 32 * 512 * 2048:
                found.add(tuple(shape))
        return found

    assert rows.prompt_form(1, cfg.heads, P, 128) == "windows"
    windows, windows_temp = compiled()
    assert "tpu_custom_call" not in windows
    assert (1, 32, 512, 2048 + 768) in score_blocks(windows)
    monkeypatch.setattr(flash_attention_module, "prompt_form",
                        lambda *shape: "kernel")
    assert rows.prompt_form(1, cfg.heads, P, 128) == "kernel"
    kernel, kernel_temp = compiled()
    assert kernel.count("tpu_custom_call") == cfg.layers
    assert "prompt_attention" in kernel
    assert not score_blocks(kernel)
    assert kernel_temp <= windows_temp + 1.01 * P * cfg.hidden_size * 2


def test_decode_segment_compiles_for_v5e_with_one_work_list_a_step(
        one_chip, monkeypatch):
    """The whole 8-token segment program at the ``small`` shape: every
    layer's kernel inside the scan, fed by the step's one work list.  The
    kernel is chosen by backend, and the backend here is the CPU, so the
    test steers the choice."""
    layers, slots, total, d, heads = DECODE_POOLS["small"]
    monkeypatch.setattr(
        decode_attention_module, "_kernel_block",
        lambda Tq, total, d, dtype: (pick_block_t(total, d, dtype)
                                     if Tq == 1 else None))
    cfg = gpt2.GPT2Config(d_model=d, layers=layers, heads=heads,
                          ffn_dim=4 * d)
    built = []
    monkeypatch.setattr(
        decode_attention_module, "work_list",
        lambda *a: built.append(a[1:3]) or work_list(*a))
    segment, args = chip_smoke.segment_program(cfg, slots, total, one_chip)
    text = segment.lower(*args).compile().as_text()
    assert built == [(total, pick_block_t(total, d, jnp.bfloat16))]
    assert text.count("tpu_custom_call") >= layers


# Decode attention with grouped queries: the pool is the K/V heads' width and
# the block is sized by it.  LFM2's (32 queries over 8 heads of 64, 32 slots
# of 8,704 rows) and Nemotron-H's (32 over 2 heads of 128, 1,024 rows).
GROUPED_POOLS = {"lfm2": (2, 32, 8704, 8, 64, 32, 512),
                 "nemotron": (1, 32, 1024, 2, 128, 32, 1024)}


@pytest.mark.parametrize("pool", list(GROUPED_POOLS))
def test_grouped_decode_attention_compiles_for_v5e(one_chip, pool):
    layers, slots, total, kv, dh, heads, block = GROUPED_POOLS[pool]
    d = kv * dh
    bt = pick_block_t(total, d, jnp.bfloat16)
    assert bt == block
    text = _compile(
        lambda q, ck, cv, wpos, first: decode_attention(
            q, ck, cv, wpos, work_list(wpos, total, bt, first), first,
            layer=layers - 1, heads=heads, block_t=bt),
        one_chip,
        ((slots, heads * dh), jnp.bfloat16),
        ((layers, slots, total, d), jnp.bfloat16),
        ((layers, slots, total, d), jnp.bfloat16), ((slots,), jnp.int32),
        ((slots,), jnp.int32))
    assert "tpu_custom_call" in text and "decode_attention" in text
    assert not chip_smoke.pool_sized_moves(text, slots * total * d)


# ``experts`` at the rows a program hands it, by the kernel's own plan:
# LFM2's gated experts at a decode step's 32 rows and at its four buckets (4
# assignments a token), and Nemotron-H's prefill dispatch (8 x 512 tokens,
# 22 a token, ``relu2``, a quarter held): the sort, the rows laid out, both
# calls, the un-sort; the ``tiles`` regime inside the VMEM it asks for,
# which the compiler refuses a kernel that passes.
EXPERT_CALLS = {
    "lfm2 segment": (32, 4, 2048, 1536, 64, True),
    "lfm2 2048": (2048, 4, 2048, 1536, 64, True),
    "lfm2 4096": (4096, 4, 2048, 1536, 64, True),
    "lfm2 6144": (6144, 4, 2048, 1536, 64, True),
    "lfm2 8192": (8192, 4, 2048, 1536, 64, True),
    "nemotron-h prefill": (4096, 22, 1024, 2688, 128, False),
}


@pytest.mark.parametrize("call", list(EXPERT_CALLS))
def test_gated_expert_matmul_compiles_for_v5e(one_chip, monkeypatch, call):
    from pytorch_zappa_serverless_tpu.ops import expert_matmul as E

    monkeypatch.setattr(E, "_use_kernel", lambda: True)
    tokens, top_k, K, F, G, gated = EXPERT_CALLS[call]
    chosen = E.plan(tokens * top_k, K, F, G, 2 if gated else 1)
    assert chosen.regime == ("tiles" if tokens > 32 else "stream")
    assert (chosen.vmem or 0) <= 64 << 20   # of a v5e core's 128 MiB

    def layer(u, w1, w3, w2, weights, group):
        return E.experts(u, w1, w2, weights, group, w3=w3 if gated else None)

    text = _compile(layer, one_chip, ((tokens, K), jnp.bfloat16),
                    *[((G, K, F), jnp.bfloat16)] * 2,
                    ((G, F, K), jnp.bfloat16),
                    ((tokens, top_k), jnp.float32),
                    ((tokens, top_k), jnp.int32))
    assert text.count("tpu_custom_call") >= 2 and "expert_matmul" in text
    # The un-sort's pass runs where the plan says ``tiles`` and nowhere else.
    assert ("expert_combine" in text) == (chosen.regime == "tiles")
    if chosen.regime == "tiles":
        assert not _float32_arrays(text, tokens * top_k, K)


def _float32_arrays(text: str, rows: int, width: int) -> list:
    """The float32 shapes of ``rows x width`` elements, ``width`` last, in a
    compiled program's text: what the un-sort wrote before it weighed and
    summed the routed rows in the pass that reads them (``[A, K]`` or
    ``[top_k, N, K]`` float32, a cast of every assignment row)."""
    import re

    found = {tuple(int(d) for d in dims.split(","))
             for dims in re.findall(r"f32\[([\d,]+)\]", text)}
    return sorted(shape for shape in found
                  if shape[-1] == width and np.prod(shape) == rows * width)


@pytest.mark.parametrize("family, shape", [
    ("lfm2", (4, 8192, 2048)), ("mellum", (8, 16384, 2304)),
    ("nemotron-h", (22, 4096, 1024))])
def test_expert_combine_compiles_for_v5e(one_chip, family, shape):
    """The un-sort's pass at the three families' ``top_k`` and widths, at a
    prefill's rows, inside the VMEM a kernel is given unasked."""
    from pytorch_zappa_serverless_tpu.ops.expert_matmul import expert_combine

    top_k, N, K = shape
    text = _compile(expert_combine, one_chip, (shape, jnp.bfloat16),
                    ((N, top_k), jnp.float32))
    assert "expert_combine" in text and text.count("tpu_custom_call") == 1
    assert not _float32_arrays(text, top_k * N, K)


# sha256 of a decode step's calls as they lower for the described chip, the
# parent's (PR 48) letter for letter: the text outside the kernels' bodies,
# and each body as MLIR printed without source locations (which any edit
# above the kernel moves).  A plan for the prefill regime leaves them be.
EXPERT_DECODE_TEXT = {
    "lfm2":
        "82f56701e5b561a7c11ae305a446047b7273c05ea2445ae071742287b137e02d",
    "nemotron-h":
        "6cde6a61f6837a5f623759bb6ba4cd153b85e4771d7c4176f2c0985957a35016",
}


def _text_without_locations(text: str) -> str:
    import base64
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    body = re.compile(r'(\\22body\\22: ?\\22)([A-Za-z0-9+/=]+)(\\22)')
    ctx = jax_mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        bodies = [ir.Module.parse(base64.b64decode(m.group(2))).operation
                  .get_asm(enable_debug_info=False)
                  for m in body.finditer(text)]
    assert bodies
    return body.sub(r"\1X\3", text) + "\n".join(bodies)


@pytest.mark.parametrize("family", list(EXPERT_DECODE_TEXT))
def test_a_decode_steps_expert_calls_lower_to_the_parents_text(
        one_chip, monkeypatch, family):
    import hashlib

    from pytorch_zappa_serverless_tpu.ops import expert_matmul as E

    monkeypatch.setattr(E, "_use_kernel", lambda: True)
    slots, top_k, K, F, G, gated = {
        "lfm2": (32, 4, 2048, 1536, 64, True),
        "nemotron-h": (32, 22, 1024, 2688, 128, False)}[family]
    assert E.plan(slots * top_k, K, F, G, 1 + gated).regime == "stream"

    def step(u, w1, w3, w2, weights, group):
        return E.experts(u, w1, w2, weights, group, w3=w3 if gated else None)

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in (
                ((slots, K), jnp.bfloat16), ((G, K, F), jnp.bfloat16),
                ((G, K, F), jnp.bfloat16), ((G, F, K), jnp.bfloat16),
                ((slots, top_k), jnp.float32), ((slots, top_k), jnp.int32))]
    text = _text_without_locations(jax.jit(step).lower(*args).as_text())
    assert text.count("expert_matmul") >= 2
    assert hashlib.sha256(text.encode()).hexdigest() \
        == EXPERT_DECODE_TEXT[family]


def _lfm2_shapes(cfg, sd):
    """LFM2's parameter tree as shapes (``sd(*shape, dtype=)``)."""
    D = cfg.hidden_size

    def vec(n=D):
        return sd(n, dtype=jnp.float32)

    params = {"embed": sd(cfg.vocab_size, D), "norm": vec()}
    for i, kind in enumerate(cfg.layer_types):
        p = {"operator_norm": vec(), "ffn_norm": vec()}
        if kind == "conv":
            p.update(in_proj=sd(D, 3 * D), out_proj=sd(D, D),
                     conv_w=sd(cfg.conv_kernel, D, dtype=jnp.float32))
        else:
            q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
            p.update(q=sd(D, q), k=sd(D, kv), v=sd(D, kv), o=sd(q, D),
                     q_norm=vec(cfg.head_dim), k_norm=vec(cfg.head_dim))
        if i < cfg.dense_layers:
            F = cfg.dense_width
            p.update(w1=sd(D, F), w3=sd(D, F), w2=sd(F, D))
        else:
            E, F = cfg.experts_held, cfg.expert_width
            p.update(router=sd(D, cfg.experts_published),
                     expert_bias=vec(cfg.experts_published),
                     w1=sd(E, D, F), w3=sd(E, D, F), w2=sd(E, F, D))
        params[f"layer{i}"] = p
    return params


@pytest.mark.parametrize("program", ["segment", "prefill"])
def test_lfm2_programs_compile_for_v5e_and_hold_no_score_array(
        one_chip, monkeypatch, program):
    """The benchmark's ten layers of LFM2 at the published widths, with the
    kernels a chip takes (the pickers ask the backend, which is the CPU
    here, so the test steers them).  The 32-slot segment over 8,704 rows:
    ``decode_attention`` once an attention layer and ``expert_matmul`` twice
    an expert layer, and temporaries under 0.3 GB (the query blocks, the
    routed rows; 11.7 GB of weights and pool are arguments).  The prefill of
    one prompt of 8,192 positions: the flash form, so no float32 array with
    the 32 heads and two dimensions of 8,192 (``[1, 32, P, P]`` is 8.6 GB),
    and temporaries under 1.5 GB: what is left of 16 GB beside 11.7."""
    import functools
    import re

    from pytorch_zappa_serverless_tpu.models import lfm2
    from pytorch_zappa_serverless_tpu.ops import (
        expert_matmul as expert_matmul_module)

    cfg = lfm2.config_from_arch({"layer_types": lfm2.PUBLISHED.layer_types[:10],
                                 "eos_id": 65536})
    slots, P, total = 32, 8192, 8192 + 384
    monkeypatch.setattr(
        decode_attention_module, "_kernel_block",
        lambda Tq, total, d, dtype: (pick_block_t(total, d, dtype)
                                     if Tq == 1 else None))
    monkeypatch.setattr(expert_matmul_module, "_use_kernel", lambda: True)
    monkeypatch.setattr(lfm2.GroupedFlashRows, "prompt_form",
                        lambda self, *shape: "flash")
    monkeypatch.setattr(lfm2, "flash_attention", functools.partial(
        flash_attention, interpret=False))

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _lfm2_shapes(cfg, sd)
    fam = lfm2.family(cfg)
    T = fam.rows.count(total)
    leaves = [sd(*shape, dtype=dt) for shape, dt in decoder.cache_leaves(
        fam, slots, T, jnp.bfloat16)]
    assert [leaf.shape for leaf in leaves] == [
        (2, 32, 8704, 512), (2, 32, 8704, 512), (8, 32, 2, 2048)]
    if program == "segment":
        i32, f32 = sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.float32)
        done = jax.jit(
            lambda p, ck, cv, tail, tok, pos, st, fin, temp, seeds, topk,
            topp: decoder.decode_segment(
                fam, p, decoder.slot_pool(ck, cv, fam.rows), tok, pos, st,
                fin, temp, seeds, 8, jnp.bfloat16, top_k=topk, top_p=topp,
                state=(tail,)),
            donate_argnums=(1, 2, 3)).lower(
                params, *leaves, i32, i32, i32, sd(slots, dtype=jnp.bool_),
                f32, i32, i32, f32).compile()
        text = done.as_text()
        assert text.count("decode_attention") >= 2
        assert text.count("tpu_custom_call") == 2 + 2 * 8
        assert done.memory_analysis().temp_size_in_bytes < 0.3e9
        return
    done = jax.jit(
        lambda p, ck, cv, tail, at, tokens, lengths: decoder.prefill(
            fam, p, tokens, lengths, (ck, cv, tail), at, jnp.bfloat16),
        donate_argnums=(1, 2, 3)).lower(
            params, *leaves, sd(1, dtype=jnp.int32),
            sd(1, P, dtype=jnp.int32), sd(1, dtype=jnp.int32)).compile()
    text = done.as_text()
    # ``flash_attention`` an attention layer; ``expert_matmul`` twice and
    # ``expert_combine`` once an expert layer.
    assert text.count("tpu_custom_call") == 2 + 3 * 8
    assert text.count("expert_combine") >= 8
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        shape = [int(d) for d in dims.split(",")]
        assert not (32 in shape and shape.count(P) >= 2), shape
    # No cast of the gathered rows: no float32 array of ``[A, K]``.
    assert not _float32_arrays(text, P * cfg.top_k, cfg.hidden_size)
    assert done.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("p,window", [(4096, 1024), (16384, 1024),
                                      (16384, None)])
def test_flash_attention_with_a_group_and_a_band_compiles_for_v5e(
        one_chip, p, window):
    """Mellum 2's prompt attention: 32 queries over 4 K/V heads of 128 read
    through the tile map (K and V are not repeated), with the band of a
    window layer and without."""
    text = _compile(
        functools.partial(flash_attention, causal=True, window=window,
                          interpret=False),
        one_chip, ((1, p, 32, 128), jnp.bfloat16),
        ((1, p, 4, 128), jnp.bfloat16), ((1, p, 4, 128), jnp.bfloat16))
    assert "flash_attention" in text and text.count("tpu_custom_call") == 1


def _mellum_shapes(cfg, sd):
    """Mellum 2's parameter tree as shapes (``sd(*shape, dtype=)``)."""
    D = cfg.hidden_size

    def vec(n=D):
        return sd(n, dtype=jnp.float32)

    q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    E, F = cfg.experts_held, cfg.expert_width
    params = {"embed": sd(cfg.vocab_size, D), "head": sd(D, cfg.vocab_size),
              "norm": vec()}
    for i in range(len(cfg.layer_types)):
        params[f"layer{i}"] = {
            "input_norm": vec(), "post_attention_norm": vec(),
            "q": sd(D, q), "k": sd(D, kv), "v": sd(D, kv), "o": sd(q, D),
            "q_norm": vec(cfg.head_dim), "k_norm": vec(cfg.head_dim),
            "router": sd(D, cfg.experts_published),
            "w1": sd(E, D, F), "w3": sd(E, D, F), "w2": sd(E, F, D)}
    return params


@pytest.mark.parametrize("program", ["segment", "prefill", "prefill 8192"])
def test_mellum_programs_compile_for_v5e_and_hold_no_score_array(
        one_chip, monkeypatch, program):
    """The benchmark's eight layers of Mellum 2 at the published widths,
    with the kernels a chip takes (the pickers ask the backend, which is the
    CPU here, so the test steers them).  The 32-slot segment over both leaf
    pairs (17,408 rows a full layer, a ring of 1,024 a window layer):
    ``decode_attention`` once a layer, each kind over its own work list, and
    ``expert_matmul`` twice a layer; temporaries under 0.3 GB (10.3 GB of
    weights and pool are arguments).  The prefill of one prompt of 16,384
    positions: ``flash_attention`` once a layer, so no float32 array with
    the 32 heads and two dimensions of 16,384 (``[1, 32, P, P]`` is 34 GB),
    and temporaries under 3.5 GB: what is left of 16 GB beside 10.3 and the
    runtime's own.  Either prefill: ``expert_combine`` once a layer behind
    the two ``expert_matmul`` calls, so no float32 array of ``A x K``
    elements (a cast of every assignment row, 4.8 GB written over the eight
    layers at 8,192 tokens until PR 54); at 8,192 the temporaries are under
    0.8 GB (1.015 with the cast)."""
    import re

    from pytorch_zappa_serverless_tpu.models import mellum
    from pytorch_zappa_serverless_tpu.ops import (
        expert_matmul as expert_matmul_module)

    cfg = mellum.config_from_arch(
        {"layer_types": mellum.PUBLISHED.layer_types[:8], "eos_id": 98304})
    slots, total = 32, 16384 + 768
    P = 8192 if program.endswith("8192") else 16384
    monkeypatch.setattr(
        decode_attention_module, "_kernel_block",
        lambda Tq, total, d, dtype: (pick_block_t(total, d, dtype)
                                     if Tq == 1 else None))
    monkeypatch.setattr(expert_matmul_module, "_use_kernel", lambda: True)
    monkeypatch.setattr(mellum, "_on_chip", lambda: True)
    monkeypatch.setattr(mellum, "flash_attention", functools.partial(
        flash_attention, interpret=False))

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _mellum_shapes(cfg, sd)
    fam = mellum.family(cfg)
    leaves = [sd(*shape, dtype=dt) for shape, dt in decoder.cache_leaves(
        fam, slots, fam.rows.count(total), jnp.bfloat16)]
    assert [leaf.shape for leaf in leaves] == [
        (2, 32, 17408, 512)] * 2 + [(6, 32, 1024, 512)] * 2
    if program == "segment":
        i32, f32 = sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.float32)

        def segment(p, *rest):
            pool, state = decoder.slot_pools(fam, rest[:4])
            tok, pos, st, fin, temp, seeds, topk, topp = rest[4:]
            return decoder.decode_segment(
                fam, p, pool, tok, pos, st, fin, temp, seeds, 8,
                jnp.bfloat16, top_k=topk, top_p=topp, state=state)

        done = jax.jit(segment, donate_argnums=(1, 2, 3, 4)).lower(
            params, *leaves, i32, i32, i32, sd(slots, dtype=jnp.bool_),
            f32, i32, i32, f32).compile()
        text = done.as_text()
        assert text.count("decode_attention") >= 2
        assert text.count("tpu_custom_call") == 3 * 8
        assert done.memory_analysis().temp_size_in_bytes < 0.3e9
        return
    done = jax.jit(
        lambda p, fk, fv, rk, rv, at, tokens, lengths: decoder.prefill(
            fam, p, tokens, lengths, (fk, fv, rk, rv), at, jnp.bfloat16),
        donate_argnums=(1, 2, 3, 4)).lower(
            params, *leaves, sd(1, dtype=jnp.int32),
            sd(1, P, dtype=jnp.int32), sd(1, dtype=jnp.int32)).compile()
    text = done.as_text()
    assert text.count("tpu_custom_call") == 4 * 8
    assert text.count("expert_combine") >= 8
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        shape = [int(d) for d in dims.split(",")]
        assert not (32 in shape and shape.count(P) >= 2), shape
    assert not _float32_arrays(text, P * cfg.top_k, cfg.hidden_size)
    temp = done.memory_analysis().temp_size_in_bytes
    print(f"mellum {P:,} prefill temporaries: {temp / 1e9:.2f} GB")
    assert temp < (0.8e9 if P == 8192 else 3.5e9)


@pytest.mark.parametrize("p", [2048, 8192])
def test_flash_attention_with_values_narrower_than_keys_compiles_for_v5e(
        one_chip, p):
    """JoyAI-LLM-Flash's prompt attention: 32 heads, keys of 192 (padded to
    256 lanes), values of 128: the output is 128 wide."""
    args = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
            for shape in ((1, p, 32, 192), (1, p, 32, 192), (1, p, 32, 128))]
    done = jax.jit(functools.partial(flash_attention, causal=True,
                                     interpret=False)).lower(*args).compile()
    text = done.as_text()
    assert "flash_attention" in text and text.count("tpu_custom_call") == 1
    assert f"bf16[1,32,{p},128]" in text  # what the kernel writes


def test_latent_attention_compiles_for_v5e_with_one_pool_operand(one_chip):
    """The benchmark's leaf, [10, 64, 9216, 640] bfloat16 (576 values a row
    in five whole lane tiles), read in blocks of 384 rows: one custom call,
    whose operands hold the leaf once (where it lies: the kernel copies its
    blocks itself).  The kernel has a body of its own: ``decode_attention``'s
    takes no ``values``, and the other families' calls cannot reach it."""
    import inspect
    import re

    from pytorch_zappa_serverless_tpu.ops.decode_attention import (
        latent_attention)

    for call in (decode_attention_module.decode_attention,
                 decode_attention_module._kernel):
        assert "values" not in inspect.signature(call).parameters
    assert "_kernel" not in (
        decode_attention_module._latent_kernel.__code__.co_names)

    S, T, D, values = 64, 9216, 640, 512
    bt = pick_block_t(T, D, jnp.bfloat16)
    assert bt == 384

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    lowered = jax.jit(lambda q, pool, last, layer: latent_attention(
        q, pool, last, layer=layer, heads=32, values=values, block_t=bt)
    ).lower(sd(S, 32 * D), sd(10, S, T, D), sd(S, dtype=jnp.int32),
            sd(dtype=jnp.int32))
    (call,) = [line for line in lowered.as_text().splitlines()
               if "tpu_custom_call" in line]
    assert len(re.findall(r"tensor<10x64x9216x640xbf16>",
                          call.rsplit(" : ", 1)[1].split(" -> ")[0])) == 1
    text = lowered.compile().as_text()
    assert "latent_attention" in text and text.count("tpu_custom_call") == 1


def _joyai_shapes(cfg, sd):
    """JoyAI-LLM-Flash's parameter tree as shapes (``sd(*shape, dtype=)``)."""
    D, H = cfg.hidden_size, cfg.heads

    def vec(n=D):
        return sd(n, dtype=jnp.float32)

    params = {"embed": sd(cfg.vocab_size, D), "head": sd(D, cfg.vocab_size),
              "norm": vec()}
    for i in range(cfg.layers):
        p = {"input_norm": vec(), "post_attention_norm": vec(),
             "q_down": sd(D, cfg.q_lora_rank), "q_norm": vec(cfg.q_lora_rank),
             "q_up": sd(cfg.q_lora_rank, H * cfg.qk_dim),
             "kv_down": sd(D, cfg.row_width),
             "kv_norm": vec(cfg.kv_lora_rank),
             "k_up": sd(cfg.kv_lora_rank, H * cfg.nope_dim),
             "v_up": sd(cfg.kv_lora_rank, H * cfg.v_dim),
             "o": sd(H * cfg.v_dim, D)}
        if i < cfg.dense_layers:
            F = cfg.dense_width
            p.update(w1=sd(D, F), w3=sd(D, F), w2=sd(F, D))
        else:
            E, F = cfg.experts_held, cfg.expert_width
            p.update(router=sd(D, cfg.experts_published),
                     expert_bias=vec(cfg.experts_published),
                     w1=sd(E, D, F), w3=sd(E, D, F), w2=sd(E, F, D),
                     shared_w1=sd(D, F), shared_w3=sd(D, F),
                     shared_w2=sd(F, D))
        params[f"layer{i}"] = p
    return params


@pytest.mark.parametrize("program", ["segment", "prefill 8192"])
def test_joyai_programs_compile_for_v5e_and_move_none_of_the_pool(
        one_chip, monkeypatch, program):
    """The benchmark's ten layers of JoyAI-LLM-Flash at the published widths
    with 32 of 256 experts, with the kernels a chip takes (the pickers ask
    the backend, which is the CPU here, so the test steers them).  The
    64-slot segment over the one leaf (9,216 rows of 640): ``latent_attention``
    once a layer and ``expert_matmul`` twice an expert layer, no other
    kernel; the leaf goes in and comes out in one buffer, and
    the temporaries are under 0.4 GB (12.1 GB of weights and pool are
    arguments).  The prefill of one prompt of 8,192 positions:
    ``flash_attention`` once a layer, so no float32 array with the 32 heads
    and two dimensions of 8,192, the pool aliased, nothing as large as a
    slot's rows of a layer moved, and the temporaries under 1.5 GB."""
    import re

    import chip_smoke
    from pytorch_zappa_serverless_tpu.models import joyai
    from pytorch_zappa_serverless_tpu.ops import (
        expert_matmul as expert_matmul_module)

    cfg = joyai.config_from_arch(
        {"layers": 10, "experts_held": 32, "eos_id": 129280})
    slots, total, P = 64, 8192 + 1024, 8192
    monkeypatch.setattr(
        decode_attention_module, "_kernel_block",
        lambda Tq, total, d, dtype: (pick_block_t(total, d, dtype)
                                     if Tq == 1 else None))
    monkeypatch.setattr(expert_matmul_module, "_use_kernel", lambda: True)
    monkeypatch.setattr(joyai.LatentRows, "prompt_form",
                        lambda self, *shape: "flash_mla")
    monkeypatch.setattr(joyai, "flash_attention", functools.partial(
        flash_attention, interpret=False))

    def sd(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = _joyai_shapes(cfg, sd)
    fam = joyai.family(cfg)
    (shape, dt), = decoder.cache_leaves(fam, slots, fam.rows.count(total),
                                        jnp.bfloat16)
    assert shape == (10, 64, 9216, 640)
    leaf = sd(*shape, dtype=dt)
    pool_bytes = int(np.prod(shape)) * 2
    if program == "segment":
        i32, f32 = sd(slots, dtype=jnp.int32), sd(slots, dtype=jnp.float32)

        def segment(p, leaf, tok, pos, st, fin, temp, seeds, topk, topp):
            pool, state = decoder.slot_pools(fam, (leaf,))
            return decoder.decode_segment(
                fam, p, pool, tok, pos, st, fin, temp, seeds, 8,
                jnp.bfloat16, top_k=topk, top_p=topp, state=state)

        done = jax.jit(segment, donate_argnums=(1,)).lower(
            params, leaf, i32, i32, i32, sd(slots, dtype=jnp.bool_),
            f32, i32, i32, f32).compile()
        text = done.as_text()
        # A kernel a layer for the rows, two an expert layer, and no other.
        assert text.count("latent_attention") >= 10
        assert text.count("tpu_custom_call") == 10 + 2 * 9
        mem = done.memory_analysis()
        assert mem.alias_size_in_bytes >= pool_bytes
        print(f"joyai segment temporaries: {mem.temp_size_in_bytes / 1e9:.3f}"
              f" GB, arguments {mem.argument_size_in_bytes / 1e9:.2f} GB")
        assert mem.temp_size_in_bytes < 0.4e9
        return
    done = jax.jit(
        lambda p, leaf, at, tokens, lengths: decoder.prefill(
            fam, p, tokens, lengths, (leaf,), at, jnp.bfloat16),
        donate_argnums=(1,)).lower(
            params, leaf, sd(1, dtype=jnp.int32), sd(1, P, dtype=jnp.int32),
            sd(1, dtype=jnp.int32)).compile()
    text = done.as_text()
    assert text.count("flash_attention") >= 10
    assert text.count("expert_combine") >= 9
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        dims = [int(d) for d in dims.split(",")]
        assert not (32 in dims and dims.count(P) >= 2), dims
    mem = done.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    assert chip_smoke.prefill_pool_moves(text, 9216, 640) == []
    print(f"joyai {P:,} prefill temporaries: "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
    assert mem.temp_size_in_bytes < 1.5e9
