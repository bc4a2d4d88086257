"""GPT-2 causal text generation — the generative-text lane of the zoo.

Beyond the reference's model surface (SURVEY §2a serves one CNN): text
generation is the workload modern serving frameworks are judged on, and it
stresses exactly the engine features the zoo already exercises — (batch, seq)
buckets, padding masks, static-shape autoregressive decode.

TPU-first structure, one jitted program per (batch, prompt-bucket):

- **Prefill + scan split** (shared design with models/whisper.py's
  decoder): the whole prompt runs in ONE batched forward —
  large MXU matmuls filling the KV cache for every position at once — and
  only the ``max_new`` generated tokens pay the sequential ``lax.scan``.
  A P-token prompt costs one forward, not P scan steps.
- **Ragged prompts inside a bucket**: per-row ``length`` rides as an input;
  attention masks key positions ``>= len_i`` during prefill, the first
  generated token reads its logits from position ``len_i - 1``, and step t
  writes its KV at per-row position ``len_i + t`` (a batched scatter —
  ``cache.at[:, arange(B), pos].set``), so rows of different lengths share
  one compiled program with zero recompiles.
- Static KV cache [L, B, P + max_new, D]; EOS semantics as in whisper:
  a ``finished`` flag pins output to EOS after the first EOS.
- bf16 matmuls / fp32 LayerNorm + softmax + logits; weights tied (lm head =
  wte) like GPT-2.

Weight import from HF ``gpt2``-family torch checkpoints
(``engine/weights.convert_gpt2`` — torch Conv1D stores [in, out] so kernels
map without transpose; the fused c_attn is split into q/k/v so the Megatron
TP rules shard whole heads).  Config is checkpoint-driven
(``config_from_params``): gpt2-medium/large serve with no code edits.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    d_model: int = 768
    layers: int = 12
    heads: int = 12
    ffn_dim: int = 3072
    max_positions: int = 1024
    eos_id: int = 50256
    ln_eps: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads


SMALL = GPT2Config()


def config_from_params(params: dict) -> GPT2Config:
    """Derive GPT2Config from a converted tree's shapes.

    Head count leaves no trace in fused-projection shapes; every published
    GPT-2 size fixes head_dim=64 (small 768/12 … xl 1600/25), so ``heads =
    d_model // 64`` with the usual ``extra.arch`` escape hatch.
    """
    vocab, d_model = (int(x) for x in np.asarray(params["wte"]).shape)
    return GPT2Config(
        vocab_size=vocab,
        d_model=d_model,
        layers=sum(1 for k in params if k.startswith("layer")),
        heads=max(d_model // 64, 1),
        ffn_dim=int(np.asarray(params["layer0"]["fc1"]["kernel"]).shape[1]),
        max_positions=int(np.asarray(params["wpe"]).shape[0]),
    )


# ---------------------------------------------------------------------------
# Core math (pure functions over the param dict; GPT-2 uses tanh-approx GELU)
# ---------------------------------------------------------------------------

def _ln(p, x, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


def _dense(p, x):
    """Plain or W8A16 projection, keyed on the param node.

    The int8 lane (extra.params_dtype: "int8") rewrites layer kernels to
    ``kernel_q`` + ``scale`` at build time; the Pallas kernel keeps dequant
    in VMEM so decode's weight traffic is the int8 bytes only
    (ops/int8_matmul.py module docstring).
    """
    from ..ops.int8_matmul import dense_maybe_int8

    return dense_maybe_int8(p, x)


def _split_heads(x, heads):
    B, T, D = x.shape
    return x.reshape(B, T, heads, D // heads)


def _attn(q, k, v, mask_bias, heads):
    """Prefill attention, heads split out: q [B,Tq,D], k/v [B,Tk,D],
    mask_bias [B,1,Tq,Tk] → [B,Tq,D]."""
    q, k, v = (_split_heads(a, heads) for a in (q, k, v))
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k).astype(jnp.float32)
    probs = jax.nn.softmax(scores + mask_bias, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    B, Tq = out.shape[:2]
    return out.reshape(B, Tq, -1)


def _decode_kernel_block(Tq, total, d, dtype):
    """The block length at which ops/decode_attention.py serves this call,
    or None where the ``jax.numpy`` form of :func:`_attn_decode` does: the
    CPU, several queries a slot, a process that addresses several devices (a
    mesh: a Mosaic kernel is not partitioned automatically, and the
    partitioner splits the einsums over ``D`` as it did the heads), and a
    pool length that only a block too large for the kernel's VMEM divides."""
    if (Tq != 1 or jax.default_backend() != "tpu"
            or jax.device_count() != 1):
        return None
    from ..ops.decode_attention import fits_vmem, pick_block_t

    bt = pick_block_t(total, d, dtype)
    return bt if fits_vmem(bt, d, dtype) else None


def _decode_work(last, total, d, dtype):
    """The step's list of live ``(slot, block)`` pairs for the kernel
    (ops/decode_attention.work_list), built once from ``last`` [S] and
    shared by every layer's :func:`_attn_decode` over ``total`` positions
    of width ``d``; None where the ``jax.numpy`` form runs, which needs
    none."""
    bt = _decode_kernel_block(1, total, d, dtype)
    if bt is None:
        return None
    from ..ops.decode_attention import work_list

    return work_list(last, total, bt)


def _attn_decode(q, cache_k, cache_v, layer, wpos, heads, work=None):
    """Decode attention over one layer of the pool, read where it lies.

    q [S, Tq, D] (a slot's one query, or the K+1 of a speculative verify),
    cache_k / cache_v [L, S, T, D] the whole pool in its own layout (``D``
    minor, no head split) and ``layer`` which of it to read, wpos [S, Tq]
    the last position each query may read → [S, Tq, D].  A negative
    ``wpos`` marks a *dead* query (a finished or empty slot): it reads
    nothing and its output row is zeros, whatever its row of the pool
    holds.  ``work`` is :func:`_decode_work` of ``wpos[:, 0]``.

    :func:`_attn` makes ``(slot, head)`` batch dimensions, and a pool whose
    heads lie side by side in ``D`` then has to be sliced out and moved to a
    heads-major layout, K and V, every layer of every step: that copy was
    three quarters of the decode step on the chip (PERF.md section 6, PR
    26).  Here ``slot`` is the only batch dimension and the contraction
    runs over all of ``D``: head ``h``'s query sits in its own 64 columns of
    an ``[H, D]`` block with zeros elsewhere, so row ``h`` of
    ``q_heads @ K^T`` is head ``h``'s scores and row ``h`` of ``probs @ V``
    carries head ``h``'s output in those same columns.  ``H`` times the
    multiply-adds of the head-split form, on a step bound by the bytes of
    the pool.  Scores and softmax in float32, probabilities and values in
    ``q``'s dtype; a position beyond ``wpos`` weighs exactly zero whatever
    the row holds there.

    On one TPU chip one query a slot goes to the Pallas kernel of the same
    contraction (ops/decode_attention.py), which visits the live blocks of
    the live slots and nothing else; everything else
    (:func:`_decode_kernel_block`) runs the ``jax.numpy`` form below, which
    reads all ``T`` positions.
    """
    S, Tq, D = q.shape
    dh = D // heads
    q = q * dh ** -0.5
    bt = _decode_kernel_block(Tq, cache_k.shape[2], D, cache_k.dtype)
    if bt is not None:
        from ..ops.decode_attention import decode_attention

        return decode_attention(q[:, 0], cache_k, cache_v, wpos[:, 0], work,
                                layer=layer, heads=heads,
                                block_t=bt)[:, None]
    cache_k, cache_v = cache_k[layer], cache_v[layer]
    T = cache_k.shape[1]
    own = (jnp.arange(D) // dh)[None, :] == jnp.arange(heads)[:, None]
    qh = jnp.where(own, q[:, :, None, :], 0)                   # [S,Tq,H,D]
    scores = jnp.einsum("smd,std->smt", qh.reshape(S, Tq * heads, D),
                        cache_k, preferred_element_type=jnp.float32)
    keep = jnp.arange(T)[None, None, :] <= wpos[:, :, None]     # [S,Tq,T]
    scores = jnp.where(keep[:, :, None, :],
                       scores.reshape(S, Tq, heads, T), -1e9)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("smt,std->smd", probs.reshape(S, Tq * heads, T),
                     cache_v, preferred_element_type=jnp.float32)
    # Each head keeps its own columns: one non-zero term a column, so exact.
    out = jnp.where(own, out.reshape(S, Tq, heads, D), 0).sum(2)
    return jnp.where((wpos >= 0)[:, :, None], out, 0).astype(q.dtype)


def _layer(p, x, cfg, attend, lora=None, lora_idx=None):
    """One transformer block: pre-LN attn + MLP, shared by prefill and decode.

    ``attend(q, k, v)`` receives this block's fresh query/key/value
    projections ([B, Tq, D], all from the same ``ln1`` activations), stores
    K/V however the caller caches, and returns the attention output
    [B, Tq, D] — the single point where the two phases differ: prefill
    runs :func:`_attn` over the prompt's own K/V, decode runs
    :func:`_attn_decode` over its layer of the running cache.

    ``lora``/``lora_idx`` (docs/ADAPTERS.md): this layer's stacked
    multi-tenant adapter factors and the per-row slot indices; each dense
    output gains its row's low-rank delta (ops/lora.py) — rows at slot 0
    select the BASE output unchanged, byte-identical passthrough.  The
    fused int8 ``qkv`` path never carries adapters (guarded at build).
    """
    def ad(name, y, inp):
        if lora is None or name not in lora:
            return y
        from ..ops.lora import lora_apply

        return lora_apply(y, inp, lora[name], lora_idx)

    h = _ln(p["ln1"], x, cfg.ln_eps)
    if "qkv" in p:
        # Fused projection (int8 lane): one [D, 3D] matmul instead of three —
        # 2 fewer kernel launches per layer per decode step, and the W8A16
        # Pallas kernel amortizes its grid setup over 3x the weight block.
        q_, k_, v_ = jnp.split(_dense(p["qkv"], h), 3, axis=-1)
    else:
        k_, v_ = ad("k", _dense(p["k"], h), h), ad("v", _dense(p["v"], h), h)
        q_ = ad("q", _dense(p["q"], h), h)
    ao = attend(q_, k_, v_)
    x = x + ad("out", _dense(p["out"], ao), ao)
    h = _ln(p["ln2"], x, cfg.ln_eps)
    h2 = jax.nn.gelu(ad("fc1", _dense(p["fc1"], h), h), approximate=True)
    return x + ad("fc2", _dense(p["fc2"], h2), h2)


def _logits(params, x):
    """Tied projection: lm head = wte (fp32 for a stable argmax/softmax).

    Int8 lane: a quantized TRANSPOSED copy (``lm_q`` [D, V] + per-vocab-row
    ``lm_scale``) replaces the wte read — at 50257x768 the lm head is a third
    of GPT-2 small's per-step weight bytes.  Output stays fp32 (the kernel
    writes its fp32 accumulator out directly).
    """
    if "lm_q" in params:
        from ..ops.int8_matmul import int8_matmul

        # lm_q is PRE-PADDED to the kernel's block alignment at build
        # (ops/int8_matmul.pad_weights) — the call-time pads are zero-width
        # and elided; the pad columns produce exactly-zero logits, sliced
        # off here so a fake vocab id can never win an argmax.
        vocab = params["wte"].shape[0]
        return int8_matmul(x.astype(jnp.bfloat16), params["lm_q"],
                           params["lm_scale"],
                           out_dtype=jnp.float32)[:, :vocab]
    # MXU-native dtypes + fp32 accumulator instead of casting the table up.
    # Bit-identical (bf16 values are exact in f32; products accumulate in
    # f32 either way).  Standalone the up-cast costs 1.4x (0.149 vs
    # 0.103 ms on the v5e at [8,768]x[50257,768]); inside the full generate
    # program XLA fuses the convert and the end-to-end step is unchanged —
    # this form just stops relying on that fusion.
    w = params["wte"]
    return jax.lax.dot_general(x.astype(w.dtype), w,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _lora_of(params: dict, layer: int, adapter_idx):
    """This layer's stacked adapter node, or None (docs/ADAPTERS.md)."""
    if adapter_idx is None:
        return None
    stacks = params.get("__adapters__")
    if stacks is None:
        return None
    return stacks.get(f"layer{layer}")


def prefill(params: dict, tokens: jax.Array, lengths: jax.Array,
            total: int, cfg: GPT2Config, dtype=jnp.bfloat16,
            adapter_idx=None):
    """Whole-prompt forward: fills the KV cache, returns last-token logits.

    tokens [B, P] int32 (zero-padded), lengths [B] int32, ``total`` the cache
    size (P + max_new).  Returns (logits [B, V] at position length-1,
    cache_k, cache_v [L, B, total, D]).  ``adapter_idx`` [B] routes each
    row through its tenant's LoRA slot (0 = base passthrough).
    """
    B, P = tokens.shape
    pos = jnp.arange(P)
    x = (params["wte"].astype(dtype)[tokens]
         + params["wpe"].astype(dtype)[pos][None])
    # Causal AND ragged: query i attends keys j<=i that are real (j < len).
    causal = pos[None, :, None] >= pos[None, None, :]          # [1,P,P]
    real = pos[None, None, :] < lengths[:, None, None]          # [B,1,P]
    mask_bias = jnp.where(causal & real, 0.0, -1e9).astype(jnp.float32)[:, None]
    cache_k = jnp.zeros((cfg.layers, B, total, cfg.d_model), dtype)
    cache_v = jnp.zeros((cfg.layers, B, total, cfg.d_model), dtype)
    for i in range(cfg.layers):
        def attend(q, k, v, i=i):
            nonlocal cache_k, cache_v
            cache_k = cache_k.at[i, :, :P].set(k)
            cache_v = cache_v.at[i, :, :P].set(v)
            return _attn(q, k, v, mask_bias, cfg.heads)

        x = _layer(params[f"layer{i}"], x, cfg, attend,
                   lora=_lora_of(params, i, adapter_idx),
                   lora_idx=adapter_idx)
    x = _ln(params["ln_f"], x, cfg.ln_eps)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    return _logits(params, last), cache_k, cache_v


def _choose(logits, temperature, seeds, t, top_k=None, top_p=None):
    """Next token per row — ops/sampling.choose (temperature + top-k/top-p,
    all [B]-shaped jit inputs; fold_in(key(seed), per-row step) keys keep
    the batched and continuous paths bit-identical)."""
    from ..ops.sampling import choose

    return choose(logits, temperature, seeds, t, top_k, top_p)


def generate(params: dict, tokens: jax.Array, lengths: jax.Array,
             temperature: jax.Array, seeds: jax.Array, max_new: int,
             cfg: GPT2Config, dtype=jnp.bfloat16,
             decode_params: dict | None = None,
             top_k: jax.Array | None = None,
             top_p: jax.Array | None = None,
             repetition_penalty: jax.Array | None = None,
             adapter_idx: jax.Array | None = None) -> jax.Array:
    """Prefill + scan generation (greedy or sampled per row).  Returns
    [B, max_new] int32, EOS-padded after the first EOS.

    One :func:`prefill_start` + a single ``max_new``-length
    :func:`decode_segment` — the fixed-batch path IS the continuous-batching
    kernel at seg=max_new, so batched and streaming serving share one
    per-step decoder body and cannot drift apart.

    ``decode_params`` lets the regime-routed lane (params_dtype "auto")
    prefill with one weight tree and decode with another: prefill is
    MXU-bound (M = B·P rows, where int8 loses — the BERT s128 measurement)
    while decode is weight-bandwidth-bound (M = B rows, where int8 wins
    below the crossover batch).
    """
    B, P = tokens.shape
    presence = None
    if repetition_penalty is not None:
        # Seen-token mask from the prompt (HF semantics: the penalty's
        # history is prompt + generated-so-far); pad positions excluded.
        valid = jnp.arange(P)[None, :] < lengths[:, None]
        presence = jnp.zeros((B, cfg.vocab_size), bool).at[
            jnp.arange(B)[:, None], tokens].max(valid)
    first, cache_k, cache_v = prefill_start(
        params, tokens, lengths, temperature, seeds, P + max_new, cfg, dtype,
        top_k=top_k, top_p=top_p, repetition_penalty=repetition_penalty,
        presence=presence, adapter_idx=adapter_idx)
    emits, *_ = decode_segment(
        params if decode_params is None else decode_params,
        cache_k, cache_v, first, lengths, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B,), bool), temperature, seeds, max_new, cfg, dtype,
        top_k=top_k, top_p=top_p, repetition_penalty=repetition_penalty,
        presence=presence, adapter_idx=adapter_idx)
    return emits


def generate_greedy(params: dict, tokens: jax.Array, lengths: jax.Array,
                    max_new: int, cfg: GPT2Config, dtype=jnp.bfloat16) -> jax.Array:
    """Greedy-only convenience wrapper over :func:`generate`."""
    B = tokens.shape[0]
    return generate(params, tokens, lengths, jnp.zeros((B,), jnp.float32),
                    jnp.zeros((B,), jnp.int32), max_new, cfg, dtype)


# ---------------------------------------------------------------------------
# Continuous batching kernels (serving/generation.py drives these)
# ---------------------------------------------------------------------------

def prefill_start(params: dict, tokens: jax.Array, lengths: jax.Array,
                  temperature: jax.Array, seeds: jax.Array, total: int,
                  cfg: GPT2Config, dtype=jnp.bfloat16, top_k=None,
                  top_p=None, repetition_penalty=None, presence=None,
                  adapter_idx=None):
    """Admission kernel: prefill one request and pick its first token.

    Same prefill as :func:`generate` (so the token chain is bit-identical to
    the fixed-batch path), returned raw so the scheduler can insert the
    cache rows into its slot pool.  Returns (first_tok [B], cache_k,
    cache_v [L, B, total, D]).
    """
    logits, cache_k, cache_v = prefill(params, tokens, lengths, total, cfg,
                                       dtype, adapter_idx=adapter_idx)
    if repetition_penalty is not None:
        from ..ops.sampling import apply_repetition_penalty

        # Runtime-gated like the top-k/top-p sort (ops/sampling.choose):
        # the knob is a jit input, so default penalty-1.0 traffic must not
        # pay the [B, V] selects — lax.cond runs only the taken branch.
        logits = jax.lax.cond(
            jnp.any(repetition_penalty != 1.0),
            lambda args: apply_repetition_penalty(*args),
            lambda args: args[0], (logits, presence, repetition_penalty))
    first = _choose(logits, temperature, seeds,
                    jnp.zeros(tokens.shape[:1], jnp.int32), top_k, top_p)
    return first, cache_k, cache_v


def decode_segment(params: dict, cache_k: jax.Array, cache_v: jax.Array,
                   tok: jax.Array, pos: jax.Array, step: jax.Array,
                   finished: jax.Array, temperature: jax.Array,
                   seeds: jax.Array, seg: int, cfg: GPT2Config,
                   dtype=jnp.bfloat16, top_k=None, top_p=None,
                   repetition_penalty=None, presence=None, adapter_idx=None):
    """Advance every slot by ``seg`` tokens — the continuous-batching kernel.

    The fixed-batch :func:`generate` runs all ``max_new`` steps in one
    program: nothing surfaces until the scan ends, finished rows burn full
    compute, and nobody can join.  Here the same per-step math runs in short
    segments over a SLOT POOL: between segments the host streams the emitted
    tokens, retires finished slots, and prefills queued requests into the
    free rows — so shapes stay static (one compiled program, reused forever)
    while membership is dynamic.

    Per-slot carried state (all [S]): ``tok`` the next token to feed, ``pos``
    its cache write position (= prompt_len + steps_generated), ``step`` the
    sampling-step counter (keeps fold_in(seed, t) aligned with the batched
    path), ``finished`` pins retired/empty slots — they still compute (the
    price of static shapes) but their ``pos`` freezes so they only overwrite
    their own dead cache row, and attention counts them *dead*: they read
    nothing and attend to zeros.  Attention reads each layer of the pool
    where it lies, as far as each live slot has written
    (:func:`_attn_decode`), from one list of live blocks a step.

    Returns (emits [S, seg], cache_k, cache_v, tok, pos, step, finished).
    Step t emits the token decided before it, exactly like :func:`generate`,
    so a lone request's stream equals the fixed-batch output bit-for-bit.
    """
    S = tok.shape[0]
    total = cache_k.shape[2]
    rows = jnp.arange(S)
    # Repetition penalty (fixed-batch lane only — the streaming lane's
    # slot pool would need a [S, V] presence buffer donated across
    # segments; declined there, loudly, in serving/server.py): the
    # presence mask rides the scan carry, gaining each fed token before
    # its logits are penalized, so history = prompt + generated-so-far
    # exactly like HF's processor.  The per-step [S, V] selects are
    # lax.cond-gated on "any row's penalty != 1.0" so default traffic
    # keeps its pre-penalty step cost (the in-carry scatter that remains
    # touches S elements of a donated buffer — noise).
    use_rep = repetition_penalty is not None
    if use_rep:
        rep_on = jnp.any(repetition_penalty != 1.0)

    def sstep(carry, _):
        if use_rep:
            cache_k, cache_v, tok, pos, t, finished, pres = carry
        else:
            cache_k, cache_v, tok, pos, t, finished = carry
            pres = None
        wpos = jnp.minimum(pos, total - 1)
        # A finished slot's token is pinned to EOS whatever it attends to:
        # it is dead to attention, which reads nothing of its row.
        last = jnp.where(finished, -1, wpos)
        work = _decode_work(last, total, cfg.d_model, cache_k.dtype)
        x = (params["wte"].astype(dtype)[tok]
             + params["wpe"].astype(dtype)[jnp.minimum(wpos, cfg.max_positions - 1)]
             )[:, None, :]
        for i in range(cfg.layers):
            def attend(q, k, v, i=i):
                nonlocal cache_k, cache_v
                cache_k = cache_k.at[i, rows, wpos].set(k[:, 0])
                cache_v = cache_v.at[i, rows, wpos].set(v[:, 0])
                return _attn_decode(q, cache_k, cache_v, i, last[:, None],
                                    cfg.heads, work)

            x = _layer(params[f"layer{i}"], x, cfg, attend,
                       lora=_lora_of(params, i, adapter_idx),
                       lora_idx=adapter_idx)
        x = _ln(params["ln_f"], x, cfg.ln_eps)
        logits = _logits(params, x[:, 0])
        if use_rep:
            from ..ops.sampling import apply_repetition_penalty

            pres = pres.at[rows, tok].set(True)
            logits = jax.lax.cond(
                rep_on, lambda args: apply_repetition_penalty(*args),
                lambda args: args[0], (logits, pres, repetition_penalty))
        nxt = _choose(logits, temperature, seeds, t + 1, top_k, top_p)
        emit = jnp.where(finished, cfg.eos_id, tok)
        fin = finished | (tok == cfg.eos_id)
        tok_next = jnp.where(fin, cfg.eos_id, nxt)
        pos_next = jnp.where(fin, pos, pos + 1)
        out = (cache_k, cache_v, tok_next, pos_next, t + 1, fin)
        return (out + (pres,) if use_rep else out), emit

    init = (cache_k, cache_v, tok, pos, step, finished)
    if use_rep:
        init = init + (presence,)
    carry, emits = jax.lax.scan(sstep, init, None, length=seg)
    cache_k, cache_v, tok, pos, step, finished = carry[:6]
    return (jnp.transpose(emits, (1, 0)), cache_k, cache_v, tok, pos, step,
            finished)


# ---------------------------------------------------------------------------
# Block-paged kernels (serving/generation.PagedGenerationScheduler drives
# these; docs/GENERATION.md).  The cache is a pool of fixed-size pages
# [L, num_blocks, block_size, D] + a per-row block table [S, max_blocks]:
# writes route through the table (ops/paged_attention.paged_index), attention
# runs over the gathered VIRTUAL cache (gather_kv) — value-identical to the
# contiguous slot pool at the positions a row has written, masked exact-zero
# beyond them, so the whole bit-parity story of the contiguous kernels
# carries over.
# ---------------------------------------------------------------------------

def _paged_write(cache, layer, table, wpos, values, block_size):
    """Scatter ``values`` through the block table into one layer's pages.

    cache [L, NB, BS, D]; table [S, MB]; wpos [S, T] absolute (pre-clipped
    to the virtual range); values [S, T, D].
    """
    from ..ops.paged_attention import paged_index

    bidx, off = paged_index(table, wpos, block_size)
    return cache.at[layer, bidx, off].set(values)


def _paged_view(cache, layer, table):
    """One layer's virtual cache [S, MB*BS, D], gathered through the table."""
    from ..ops.paged_attention import gather_kv

    return gather_kv(cache[layer], table)


def prefill_chunk_paged(params: dict, tokens: jax.Array, start: jax.Array,
                        lengths: jax.Array, cache_k: jax.Array,
                        cache_v: jax.Array, table: jax.Array,
                        temperature: jax.Array, seeds: jax.Array,
                        top_k: jax.Array, top_p: jax.Array,
                        block_size: int, cfg: GPT2Config, dtype=jnp.bfloat16,
                        adapter_idx=None):
    """One bounded-cost prefill chunk over the paged pool.

    ``tokens`` [G, C] is the chunk's token slice (zero-padded in the final
    chunk), ``start`` [G] its absolute offset, ``lengths`` [G] the FULL
    prompt length.  Queries at absolute positions ``start+i`` attend every
    key ``j <= start+i`` with ``j < length`` — previous chunks' keys come
    back out of the paged cache, so chaining chunks reproduces the
    monolithic :func:`prefill` attention pattern exactly
    (tests/test_generation_v2.py pins the logits).  The prefix KV cache
    (serving/prefixcache.py, docs/PREFIX.md) rides this same contract for
    free: a warm admission's first chunk simply starts at the cached
    offset, and positions below it resolve through the table to FROZEN
    shared pages — bit-identical to the keys a cold prefill would have
    written, so no kernel change is needed for reuse.  Returns
    ``(first_tok [G], cache_k, cache_v)``; ``first_tok`` is only meaningful
    for rows whose final chunk this is (the last-position gather clips into
    the chunk), which is how one compiled program serves every chunk index.
    """
    G, C = tokens.shape
    VT = table.shape[1] * block_size
    pos = start[:, None] + jnp.arange(C)[None, :]                   # [G, C]
    wpos = jnp.minimum(pos, VT - 1)
    x = (params["wte"].astype(dtype)[tokens]
         + params["wpe"].astype(dtype)[jnp.minimum(pos,
                                                   cfg.max_positions - 1)])
    kpos = jnp.arange(VT)
    keep = ((kpos[None, None, :] <= pos[:, :, None])
            & (kpos[None, None, :] < lengths[:, None, None]))
    mask_bias = jnp.where(keep, 0.0, -1e9).astype(jnp.float32)[:, None]
    for i in range(cfg.layers):
        def attend(q, k, v, i=i):
            nonlocal cache_k, cache_v
            cache_k = _paged_write(cache_k, i, table, wpos, k, block_size)
            cache_v = _paged_write(cache_v, i, table, wpos, v, block_size)
            return _attn(q, _paged_view(cache_k, i, table),
                         _paged_view(cache_v, i, table), mask_bias,
                         cfg.heads)

        x = _layer(params[f"layer{i}"], x, cfg, attend,
                   lora=_lora_of(params, i, adapter_idx),
                   lora_idx=adapter_idx)
    x = _ln(params["ln_f"], x, cfg.ln_eps)
    idx = jnp.clip(lengths - 1 - start, 0, C - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    first = _choose(_logits(params, last), temperature, seeds,
                    jnp.zeros((G,), jnp.int32), top_k, top_p)
    return first, cache_k, cache_v


def decode_segment_paged(params: dict, cache_k: jax.Array, cache_v: jax.Array,
                         table: jax.Array, tok: jax.Array, pos: jax.Array,
                         step: jax.Array, finished: jax.Array,
                         temperature: jax.Array, seeds: jax.Array, seg: int,
                         cfg: GPT2Config, block_size: int,
                         dtype=jnp.bfloat16, top_k=None, top_p=None,
                         adapter_idx=None):
    """:func:`decode_segment` over the paged pool — same per-step math, same
    emit/finish semantics, writes and reads routed through ``table``.
    Finished/empty rows carry an all-trash table row (serving/kvcache.py),
    so their frozen-position writes land in the shared trash page."""
    S = tok.shape[0]
    VT = table.shape[1] * block_size

    def sstep(carry, _):
        cache_k, cache_v, tok, pos, t, finished = carry
        wpos = jnp.minimum(pos, VT - 1)
        last = jnp.where(finished, -1, wpos)  # dead, as decode_segment's
        work = _decode_work(last, VT, cfg.d_model, cache_k.dtype)
        x = (params["wte"].astype(dtype)[tok]
             + params["wpe"].astype(dtype)[
                 jnp.minimum(wpos, cfg.max_positions - 1)])[:, None, :]
        for i in range(cfg.layers):
            def attend(q, k, v, i=i):
                nonlocal cache_k, cache_v
                cache_k = _paged_write(cache_k, i, table, wpos[:, None],
                                       k, block_size)
                cache_v = _paged_write(cache_v, i, table, wpos[:, None],
                                       v, block_size)
                return _attn_decode(q, _paged_view(cache_k, i, table)[None],
                                    _paged_view(cache_v, i, table)[None],
                                    0, last[:, None], cfg.heads, work)

            x = _layer(params[f"layer{i}"], x, cfg, attend,
                       lora=_lora_of(params, i, adapter_idx),
                       lora_idx=adapter_idx)
        x = _ln(params["ln_f"], x, cfg.ln_eps)
        logits = _logits(params, x[:, 0])
        nxt = _choose(logits, temperature, seeds, t + 1, top_k, top_p)
        emit = jnp.where(finished, cfg.eos_id, tok)
        fin = finished | (tok == cfg.eos_id)
        tok_next = jnp.where(fin, cfg.eos_id, nxt)
        pos_next = jnp.where(fin, pos, pos + 1)
        return (cache_k, cache_v, tok_next, pos_next, t + 1, fin), emit

    init = (cache_k, cache_v, tok, pos, step, finished)
    carry, emits = jax.lax.scan(sstep, init, None, length=seg)
    cache_k, cache_v, tok, pos, step, finished = carry
    return (jnp.transpose(emits, (1, 0)), cache_k, cache_v, tok, pos, step,
            finished)


def propose_paged(params: dict, cache_k: jax.Array, cache_v: jax.Array,
                  table: jax.Array, prev: jax.Array, tok: jax.Array,
                  pos: jax.Array, step: jax.Array, finished: jax.Array,
                  temperature: jax.Array, seeds: jax.Array, k: int,
                  cfg: GPT2Config, block_size: int, dtype=jnp.bfloat16,
                  top_k=None, top_p=None):
    """Draft half of a speculative tick: ``k`` cheap decode steps proposing
    the next ``k`` tokens per row, feeding each proposal back in.

    Runs against the DRAFT rung's params and its own paged cache (same block
    tables as the target — same positions).  The scan runs ``k + 1`` steps:
    step 0 **backfills** ``prev`` (the chain token at ``pos - 1``) — after a
    fully-accepted tick the draft never fed its last proposal, leaving a KV
    hole at ``pos - 1`` that quietly degrades the next tick's acceptance;
    re-feeding ``prev`` recomputes that position's KV (bit-identical when no
    hole exists, so the backfill is idempotent).  Step 0's output is
    discarded and step 1 force-feeds the already-decided ``tok``.  Returns
    ``(proposals [S, k], draft_logits fp32 [S, k, V], cache_k, cache_v)``;
    the raw logits stay on device for the verifier's rejection sampling
    (ops/sampling.speculative_verify).  Sampled rows draw with a salted
    seed chain (DRAFT_SEED_SALT) so proposals are independent of the plain
    lane's and the verifier's draws.
    """
    from ..ops.sampling import DRAFT_SEED_SALT

    S = tok.shape[0]
    VT = table.shape[1] * block_size
    draft_seeds = jnp.bitwise_xor(seeds, jnp.int32(DRAFT_SEED_SALT))

    def sstep(carry, _):
        cache_k, cache_v, cur, pos, t, first = carry
        wpos = jnp.minimum(pos, VT - 1)
        x = (params["wte"].astype(dtype)[cur]
             + params["wpe"].astype(dtype)[
                 jnp.minimum(wpos, cfg.max_positions - 1)])[:, None, :]
        for i in range(cfg.layers):
            def attend(q, k_, v_, i=i):
                nonlocal cache_k, cache_v
                cache_k = _paged_write(cache_k, i, table, wpos[:, None],
                                       k_, block_size)
                cache_v = _paged_write(cache_v, i, table, wpos[:, None],
                                       v_, block_size)
                return _attn_decode(q, _paged_view(cache_k, i, table)[None],
                                    _paged_view(cache_v, i, table)[None],
                                    0, wpos[:, None], cfg.heads)

            x = _layer(params[f"layer{i}"], x, cfg, attend)
        x = _ln(params["ln_f"], x, cfg.ln_eps)
        logits = _logits(params, x[:, 0])
        nxt = _choose(logits, temperature, draft_seeds, t + 1, top_k, top_p)
        # Backfill step feeds the pending token next; proposal steps feed
        # the model's own choice.
        prop = jnp.where(finished, cfg.eos_id, jnp.where(first, tok, nxt))
        pos_next = jnp.where(finished, pos, pos + 1)
        return ((cache_k, cache_v, prop, pos_next,
                 jnp.where(first, t, t + 1), jnp.zeros_like(first)),
                (prop, logits))

    init = (cache_k, cache_v, prev, jnp.maximum(pos - 1, 0), step,
            jnp.ones((S,), bool))
    carry, (props, logits) = jax.lax.scan(sstep, init, None, length=k + 1)
    cache_k, cache_v = carry[0], carry[1]
    # Drop the backfill step's output: props[0] is the forced pending tok,
    # logits[0] the distribution it was (already) decided from.
    return (jnp.transpose(props[1:], (1, 0)),
            jnp.transpose(logits[1:], (1, 0, 2)), cache_k, cache_v)


def verify_paged(params: dict, cache_k: jax.Array, cache_v: jax.Array,
                 table: jax.Array, toks: jax.Array, pos: jax.Array,
                 finished: jax.Array, cfg: GPT2Config, block_size: int,
                 dtype=jnp.bfloat16):
    """Target half of a speculative tick: ONE batched forward over the
    pending token + K proposals per row.

    ``toks`` [S, K+1] feeds at absolute positions ``pos..pos+K``: K/V for
    every fed token are scattered into the paged cache first, then each
    query attends the gathered virtual cache under ``kpos <= qpos`` — the
    same write-then-read-own-position pattern as the decode step, so the
    target logits at query ``i`` are exactly what ``K+1`` sequential decode
    steps would have produced (the greedy ON==OFF parity contract).
    Positions past the acceptance point hold rejected-token K/V; the next
    tick's writes overwrite them before any mask admits a read.  Returns
    ``(logits fp32 [S, K+1, V], cache_k, cache_v)``.
    """
    S, K1 = toks.shape
    VT = table.shape[1] * block_size
    p = pos[:, None] + jnp.arange(K1)[None, :]
    wp = jnp.minimum(p, VT - 1)
    x = (params["wte"].astype(dtype)[toks]
         + params["wpe"].astype(dtype)[jnp.minimum(wp,
                                                   cfg.max_positions - 1)])
    for i in range(cfg.layers):
        def attend(q, k, v, i=i):
            nonlocal cache_k, cache_v
            cache_k = _paged_write(cache_k, i, table, wp, k, block_size)
            cache_v = _paged_write(cache_v, i, table, wp, v, block_size)
            return _attn_decode(q, _paged_view(cache_k, i, table)[None],
                                _paged_view(cache_v, i, table)[None], 0, wp,
                                cfg.heads)

        x = _layer(params[f"layer{i}"], x, cfg, attend)
    x = _ln(params["ln_f"], x, cfg.ln_eps)
    D = x.shape[-1]
    logits = _logits(params, x.reshape(S * K1, D)).reshape(S, K1, -1)
    return logits, cache_k, cache_v


# ---------------------------------------------------------------------------
# Random init (offline dev mode)
# ---------------------------------------------------------------------------

def init_gpt2_params(seed: int = 0, cfg: GPT2Config = SMALL) -> dict:
    g = np.random.default_rng(seed)

    def dense(i, o):
        return {"kernel": (g.standard_normal((i, o)) * 0.02).astype(np.float32),
                "bias": np.zeros((o,), np.float32)}

    def ln(d):
        return {"scale": np.ones((d,), np.float32), "bias": np.zeros((d,), np.float32)}

    D, F = cfg.d_model, cfg.ffn_dim
    params = {
        "wte": (g.standard_normal((cfg.vocab_size, D)) * 0.02).astype(np.float32),
        "wpe": (g.standard_normal((cfg.max_positions, D)) * 0.01).astype(np.float32),
        "ln_f": ln(D),
    }
    for i in range(cfg.layers):
        params[f"layer{i}"] = {
            "ln1": ln(D), "q": dense(D, D), "k": dense(D, D), "v": dense(D, D),
            "out": dense(D, D), "ln2": ln(D), "fc1": dense(D, F), "fc2": dense(F, D),
        }
    return params


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def _fallback_tokenize(text: str, vocab_size: int) -> list[int]:
    """Offline stub (same role as BERT's): whitespace words hashed into the
    vocab; real deployments point extra.tokenizer at a gpt2 tokenizer.json."""
    import hashlib

    return [int.from_bytes(hashlib.sha256(w.encode()).digest()[:4], "big")
            % max(vocab_size - 1, 1) for w in text.split()]


def make_gpt2_servable(name: str, cfg_model):
    from ..engine import weights as W
    from ..engine.servable import Servable
    from ..parallel.mesh import GPT2_TP_RULES
    from .vision_common import resolve_dtype

    dtype = resolve_dtype(cfg_model.dtype)
    max_new = int(cfg_model.extra.get("max_new_tokens", 32))
    arch = {k: int(v) for k, v in dict(cfg_model.extra.get("arch", {})).items()}
    max_seq = max(cfg_model.seq_buckets)

    if cfg_model.checkpoint:
        params = W.import_params(cfg_model.checkpoint, W.convert_gpt2)
        cfg = dataclasses.replace(config_from_params(params), **arch)
    else:
        cfg = dataclasses.replace(SMALL, **arch) if arch else SMALL
        if cfg.vocab_size <= cfg.eos_id and "eos_id" not in arch:
            cfg = dataclasses.replace(cfg, eos_id=cfg.vocab_size - 1)
        params = init_gpt2_params(0, cfg)
    if max_seq + max_new > cfg.max_positions:
        # Build-time guard: without it, decode positions past the wpe table
        # would silently clamp to the last position embedding (generate()'s
        # jnp.minimum is defensive, not a semantics).
        raise ValueError(
            f"{name}: max(seq_buckets) + max_new_tokens = {max_seq} + "
            f"{max_new} exceeds the model's max_positions "
            f"({cfg.max_positions}); shrink seq_buckets or max_new_tokens")
    params_dtype = str(cfg_model.extra.get("params_dtype", ""))
    routed = params_dtype == "auto"
    # Regime crossover (README "int8 decode regime table"): the round-5
    # dedicated device-trace sweep shows int8 DECODE winning at every
    # measured pool size (1.84x at 8 rows, 1.63x at 16, 1.13x at 32,
    # 1.08x at 64) — the earlier "bf16 wins at x4" datum was the whole
    # generate call, i.e. the int8 PREFILL loss this routed lane already
    # removes.  64 is the measured bracket's end (still winning); beyond
    # it the margin is heading to parity, so the bf16 fallback remains.
    crossover = int(cfg_model.extra.get("int8_crossover_batch", 64))

    def _quantize(tree):
        """fp32 host tree -> W8A16 tree (int8 layer kernels + per-channel
        scales, quantized+padded lm head, bf16 at rest otherwise).

        The tied lm head gets its own quantized TRANSPOSED copy while
        wte/wpe stay bf16 for the (few-row) embedding gathers.  q/k/v fuse
        into one [D, 3D] projection BEFORE quantizing (order [q|k|v],
        matching _layer's jnp.split).  Single-device only (the engine
        rejects int8/auto + mesh), so the Megatron per-head TP layout
        question never arises for the fused node.
        """
        from ..ops.int8_matmul import (pad_weights, quantize_per_channel,
                                       quantize_tree)
        from .vision_common import cast_params_at_rest

        for i in range(cfg.layers):
            lp = tree[f"layer{i}"]
            lp["qkv"] = {
                "kernel": np.concatenate(
                    [np.asarray(lp[n]["kernel"], np.float32) for n in "qkv"],
                    axis=1),
                "bias": np.concatenate(
                    [np.asarray(lp[n]["bias"], np.float32) for n in "qkv"]),
            }
            del lp["q"], lp["k"], lp["v"]
        tree = quantize_tree(tree, min_size=int(
            cfg_model.extra.get("quantize_min_size", 1 << 16)))
        lm_q, lm_scale = quantize_per_channel(
            np.asarray(tree["wte"]).T.copy(), axis=0)
        tree["lm_q"], tree["lm_scale"] = pad_weights(lm_q, lm_scale)
        return cast_params_at_rest(tree, jnp.bfloat16)

    adapters_on = int(getattr(cfg_model, "adapter_slots", 0)) > 0
    if adapters_on and (params_dtype in ("int8", "auto")):
        # The fused int8 qkv projection has no per-projection seam to add a
        # delta at, and the dual-tree routed lane would need the stacks in
        # BOTH trees; refuse at boot rather than silently drop tenants.
        raise ValueError(
            f"{name}: adapter_slots cannot combine with params_dtype="
            f"{params_dtype!r}; serve adapters on the bf16 lane")
    if params_dtype == "int8":
        params = _quantize(params)
    elif routed:
        # Regime-routed lane (VERDICT r4 next #3): hold BOTH weight trees
        # and pick per compiled program — prefill always bf16 (MXU-bound),
        # decode int8 at <= crossover rows, bf16 above.  The big bf16
        # embedding/LN leaves are SHARED into the int8 tree (placed arrays,
        # so device_put cannot duplicate them in HBM); the marginal cost of
        # "auto" over "int8" is the bf16 layer kernels, ~85 MB for small.
        from .vision_common import cast_params_at_rest

        def _copy_tree(t):
            return {k: _copy_tree(v) if isinstance(v, dict) else v
                    for k, v in t.items()}

        bf16 = jax.device_put(cast_params_at_rest(params, jnp.bfloat16))
        q = _quantize(_copy_tree(params))
        q["wte"], q["wpe"], q["ln_f"] = bf16["wte"], bf16["wpe"], bf16["ln_f"]
        for i in range(cfg.layers):
            q[f"layer{i}"]["ln1"] = bf16[f"layer{i}"]["ln1"]
            q[f"layer{i}"]["ln2"] = bf16[f"layer{i}"]["ln2"]
        params = {"bf16": bf16, "int8": q}
    if adapters_on:
        # Multi-tenant LoRA slot pool (docs/ADAPTERS.md): fixed-shape zero
        # stacks baked into the param tree — attach/detach replace leaves
        # (same shapes, zero recompiles), slot 0 is the reserved base
        # passthrough, and every request row gathers its own slot
        # (ops/lora.py).  serving/adapters.AdapterManager owns the slots.
        from ..ops.lora import zero_stacks

        D, F = cfg.d_model, cfg.ffn_dim
        all_dims = {"q": (D, D), "k": (D, D), "v": (D, D), "out": (D, D),
                    "fc1": (D, F), "fc2": (F, D)}
        targets = tuple(cfg_model.adapter_targets) or ("q", "v")
        unknown = [t for t in targets if t not in all_dims]
        if unknown:
            raise ValueError(f"{name}: unknown adapter_targets {unknown}; "
                             f"supported: {sorted(all_dims)}")
        dims = {t: all_dims[t] for t in targets}
        slots = int(cfg_model.adapter_slots) + 1  # + reserved slot 0
        rank = max(int(cfg_model.adapter_rank), 1)
        params["__adapters__"] = {
            f"layer{i}": zero_stacks(slots, rank, dims)
            for i in range(cfg.layers)}
    params = jax.device_put(params)  # ONE batched tree transfer: per-leaf
    # jnp.asarray serializes a host round-trip per buffer.

    def _pre_tree(p):
        """Prefill weights: bf16 on the routed lane (M = B·P rows feed the
        MXU, where the BERT s128 measurement shows int8 losing)."""
        return p["bf16"] if routed else p

    def _dec_tree(p, rows: int):
        """Decode weights for a program with ``rows`` decode rows."""
        if not routed:
            return p
        return p["int8"] if rows <= crossover else p["bf16"]

    tokenizer = None
    tok_path = cfg_model.extra.get("tokenizer")
    if tok_path:
        from tokenizers import Tokenizer

        tokenizer = Tokenizer.from_file(str(tok_path))

    default_temperature = float(cfg_model.extra.get("temperature", 0.0))

    # Over-length policy (extra.overlength): generation defaults to "error"
    # (a clean 400 — silently dropping context changes what gets generated);
    # "truncate" keeps the TAIL (ids[-max_seq:], the HF left-truncation
    # convention for causal LM: the continuation conditions on the most
    # recent context, not the oldest).
    overlength = str(cfg_model.extra.get("overlength", "error"))
    if overlength not in ("truncate", "error"):
        raise ValueError(f"{name}: extra.overlength must be 'truncate' or "
                         f"'error', got {overlength!r}")

    def _fit(ids: list[int]) -> list[int]:
        if len(ids) > max_seq:
            if overlength == "error":
                raise ValueError(
                    f"prompt is {len(ids)} tokens but the longest configured "
                    f"seq bucket is {max_seq}; send a shorter prompt or set "
                    f"extra.overlength='truncate' to keep the last {max_seq}")
            ids = ids[-max_seq:]
        return ids

    def apply_fn(p, inputs):
        B = inputs["input_ids"].shape[0]  # static per bucket: each compiled
        # program bakes in its regime's weight tree (no runtime branch).
        return {"tokens": generate(_pre_tree(p), inputs["input_ids"],
                                   inputs["length"], inputs["temperature"],
                                   inputs["seed"], max_new, cfg, dtype,
                                   decode_params=_dec_tree(p, B),
                                   top_k=inputs["top_k"],
                                   top_p=inputs["top_p"],
                                   repetition_penalty=inputs[
                                       "repetition_penalty"],
                                   adapter_idx=inputs.get("adapter_idx"))}

    def input_spec(bucket):
        b, s = bucket
        spec = {"input_ids": jax.ShapeDtypeStruct((b, s), jnp.int32),
                "length": jax.ShapeDtypeStruct((b,), jnp.int32),
                "temperature": jax.ShapeDtypeStruct((b,), jnp.float32),
                "seed": jax.ShapeDtypeStruct((b,), jnp.int32),
                "top_k": jax.ShapeDtypeStruct((b,), jnp.int32),
                "top_p": jax.ShapeDtypeStruct((b,), jnp.float32),
                "repetition_penalty": jax.ShapeDtypeStruct((b,),
                                                           jnp.float32)}
        if adapters_on:
            # Per-row adapter slot index (docs/ADAPTERS.md): pad rows
            # collate to 0 — the reserved base-passthrough slot.
            spec["adapter_idx"] = jax.ShapeDtypeStruct((b,), jnp.int32)
        return spec

    def preprocess(payload):
        temperature, seed = default_temperature, 0
        top_k, top_p, rep = 0, 1.0, 1.0  # off unless the request sets them
        if isinstance(payload, dict):
            temperature = float(payload.get("temperature", temperature))
            seed = int(payload.get("seed", seed))
            top_k = int(payload.get("top_k", top_k))
            top_p = float(payload.get("top_p", top_p))
            rep = float(payload.get("repetition_penalty", rep))
        if isinstance(payload, dict) and "input_ids" in payload:
            ids = [int(i) for i in payload["input_ids"]]
        else:
            text = payload["text"] if isinstance(payload, dict) else str(
                payload.decode() if isinstance(payload, bytes) else payload)
            ids = (tokenizer.encode(text).ids if tokenizer is not None
                   else _fallback_tokenize(text, cfg.vocab_size))
        ids = _fit(ids or [cfg.eos_id])
        arr = np.asarray(ids, np.int32)
        sample = {"input_ids": arr, "length": np.int32(arr.shape[0]),
                  "temperature": np.float32(temperature),
                  "seed": np.int32(seed),
                  "top_k": np.int32(top_k), "top_p": np.float32(top_p),
                  "repetition_penalty": np.float32(rep)}
        if adapters_on:
            # Slot 0 = base passthrough; the server overwrites this with
            # the resolved tenant's slot after the attach gate.
            sample["adapter_idx"] = np.int32(0)
        return sample

    def postprocess(out, i):
        toks = [int(t) for t in out["tokens"][i]]
        if cfg.eos_id in toks:
            toks = toks[: toks.index(cfg.eos_id)]
        result = {"tokens": toks}
        if tokenizer is not None:
            result["text"] = tokenizer.decode(toks)
        return result

    def collate_lengths(samples, bucket, spec):
        from ..engine.compiled import default_collate

        batch = default_collate(samples, bucket, spec)
        # Padded rows must have length>=1: position len-1 gathers row 0's
        # garbage otherwise fine, but keep the index in range.
        batch["length"] = np.maximum(batch["length"], 1)
        return batch

    # Continuous-batching contract (serving/generation.py): slot-pool decode
    # in `segment_tokens`-step jitted segments with per-request admission via
    # prefill + insert.  gen_slots bounds concurrent generations; the cache
    # pool is [L, slots, max_seq+max_new, D].  Admission is model-shaped
    # (whisper admits AUDIO), so the scheduler drives it through the generic
    # trio: ``admit_len_of`` (sample -> bucket-size request),
    # ``collate_admit`` (sample + bucket -> batch-1 payload dict; must carry
    # "length" [1] and may carry "temperature"/"seed" [1] for the slot
    # state), ``admit_spec`` (bucket -> payload ShapeDtypeStructs, used by
    # multi-host followers to join the broadcast), and ``prefill`` takes the
    # payload dict.
    gen_slots = int(cfg_model.extra.get("gen_slots", 4))
    segment_tokens = int(cfg_model.extra.get("segment_tokens", 8))
    total = max_seq + max_new

    def collate_admit(sample, bucket):
        ids = np.asarray(sample["input_ids"], np.int32)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, : ids.shape[0]] = ids
        return {
            "input_ids": toks,
            "length": np.asarray([max(ids.shape[0], 1)], np.int32),
            "temperature": np.asarray([sample.get("temperature", 0.0)],
                                      np.float32),
            "seed": np.asarray([sample.get("seed", 0)], np.int32),
            "top_k": np.asarray([sample.get("top_k", 0)], np.int32),
            "top_p": np.asarray([sample.get("top_p", 1.0)], np.float32),
        }

    def admit_spec(bucket):
        return {
            "input_ids": jax.ShapeDtypeStruct((1, bucket), jnp.int32),
            "length": jax.ShapeDtypeStruct((1,), jnp.int32),
            "temperature": jax.ShapeDtypeStruct((1,), jnp.float32),
            "seed": jax.ShapeDtypeStruct((1,), jnp.int32),
            "top_k": jax.ShapeDtypeStruct((1,), jnp.int32),
            "top_p": jax.ShapeDtypeStruct((1,), jnp.float32),
        }

    continuous = {
        "slots": gen_slots,
        "segment_tokens": segment_tokens,
        "total": total,
        "eos_id": cfg.eos_id,
        "max_new": max_new,
        "prompt_buckets": tuple(sorted(int(s) for s in cfg_model.seq_buckets)),
        "admit_len_of": lambda s: int(np.asarray(s["input_ids"]).shape[0]),
        "collate_admit": collate_admit,
        "admit_spec": admit_spec,
        "cache_shape": (cfg.layers, gen_slots, total, cfg.d_model),
        "cache_dtype": dtype,
        # Positions decode attention reads a live slot's row in: the
        # kernel's block length, or the whole row (the ``jax.numpy`` form).
        "read_block": (_decode_kernel_block(1, total, cfg.d_model, dtype)
                       or total),
        # Routed lane: admission prefills run bf16, the slot-pool segment
        # routes on the POOL size (the decode-row count of its program) —
        # consistent with the fixed-batch path at the same row count, so the
        # bit-identical fixed<->continuous parity property survives routing.
        "prefill": (lambda p, payload:
                    prefill_start(_pre_tree(p), payload["input_ids"],
                                  payload["length"], payload["temperature"],
                                  payload["seed"], total, cfg, dtype,
                                  top_k=payload["top_k"],
                                  top_p=payload["top_p"])),
        "segment": (lambda p, ck, cv, tok, pos, st, fin, temp, seeds,
                    topk, topp:
                    decode_segment(_dec_tree(p, gen_slots), ck, cv, tok, pos,
                                   st, fin, temp, seeds, segment_tokens, cfg,
                                   dtype, top_k=topk, top_p=topp)),
        "detokenize": ((lambda toks: tokenizer.decode(toks))
                       if tokenizer is not None else None),
    }

    # Block-paged contract (serving/generation.PagedGenerationScheduler;
    # docs/GENERATION.md): pure kernel fns parameterized by the pool layout,
    # jitted + donated by the scheduler's factory.  Weight-tree routing
    # mirrors the slot pool's: chunked prefill runs bf16 (MXU-bound rows),
    # decode/propose/verify route on the pool size — verify uses the SAME
    # tree as the plain segment so speculation-ON greedy output is
    # byte-identical to speculation-OFF.
    def _make_paged(block_size: int, spec_k: int):
        bs, K = int(block_size), int(spec_k)
        return {
            # prefill_chunk/segment take a trailing per-row adapter slot
            # index (docs/ADAPTERS.md): the paged scheduler carries it per
            # stream, so tenants co-decode in one program.  The draft rung
            # never sees adapters — the scheduler falls back to plain
            # decode while any adapter stream is active.
            "prefill_chunk": (
                lambda p, toks, start, length, ck, cv, table, temp, seed,
                topk, topp, aidx:
                prefill_chunk_paged(_pre_tree(p), toks, start, length, ck,
                                    cv, table, temp, seed, topk, topp, bs,
                                    cfg, dtype,
                                    adapter_idx=aidx if adapters_on
                                    else None)),
            "segment": (
                lambda p, ck, cv, table, tok, pos, st, fin, temp, seeds,
                topk, topp, aidx:
                decode_segment_paged(_dec_tree(p, gen_slots), ck, cv, table,
                                     tok, pos, st, fin, temp, seeds,
                                     segment_tokens, cfg, bs, dtype,
                                     top_k=topk, top_p=topp,
                                     adapter_idx=aidx if adapters_on
                                     else None)),
            "propose": (
                lambda p, ck, cv, table, prev, tok, pos, st, fin, temp,
                seeds, topk, topp:
                propose_paged(_dec_tree(p, gen_slots), ck, cv, table, prev,
                              tok, pos, st, fin, temp, seeds, K, cfg, bs,
                              dtype, top_k=topk, top_p=topp)),
            "verify": (
                lambda p, ck, cv, table, toks, pos, fin:
                verify_paged(_dec_tree(p, gen_slots), ck, cv, table, toks,
                             pos, fin, cfg, bs, dtype)),
        }

    continuous["paged"] = {
        "make": _make_paged,
        "cache_shape": (lambda num_blocks, block_size:
                        (cfg.layers, num_blocks, block_size, cfg.d_model)),
        # Host-side admission adapters: the scheduler is model-agnostic and
        # builds its own chunk payloads from raw prompt ids + knobs.
        "prompt_ids": (lambda s:
                       np.asarray(s["input_ids"], np.int32).reshape(-1)),
        "knobs": (lambda s: (float(s.get("temperature", 0.0)),
                             int(s.get("seed", 0)),
                             int(s.get("top_k", 0)),
                             float(s.get("top_p", 1.0)))),
        # Per-stream adapter slot (docs/ADAPTERS.md): 0 = base passthrough;
        # eviction continuations ({**s, ...} in extend_sample) preserve it.
        "adapter_idx": (lambda s: int(np.asarray(
            s.get("adapter_idx", 0)))),
        # Eviction continuation (docs/GENERATION.md "Exhaustion policy"):
        # prompt + tokens-emitted-so-far becomes the re-admission prompt.
        "extend_sample": (lambda s, toks: {
            **s, "input_ids": np.concatenate(
                [np.asarray(s["input_ids"], np.int32).reshape(-1),
                 np.asarray(toks, np.int32)]),
            "length": np.int32(
                np.asarray(s["input_ids"]).reshape(-1).shape[0] + len(toks))}),
    }

    meta = {"seq_len_of": lambda s: int(s["input_ids"].shape[0]),
            "max_new_tokens": max_new, "collate": collate_lengths,
            "continuous": continuous,
            "tp_rules": GPT2_TP_RULES}
    if adapters_on:
        # Pool layout the AdapterManager builds host stacks against
        # (serving/adapters.py): slot count INCLUDES the reserved slot 0.
        meta["adapters"] = {"slots": int(cfg_model.adapter_slots) + 1,
                            "rank": max(int(cfg_model.adapter_rank), 1),
                            "targets": tuple(cfg_model.adapter_targets),
                            "dims": dims, "layers": cfg.layers}
    return Servable(
        name=name, apply_fn=apply_fn, params=params, input_spec=input_spec,
        preprocess=preprocess, postprocess=postprocess,
        bucket_axes=("batch", "seq"), meta=meta)


from ..utils.registry import register_model  # noqa: E402


@register_model("gpt2", latency_class="latency")
def build_gpt2(cfg):
    return make_gpt2_servable("gpt2", cfg)
