"""Whisper-tiny ASR for TPU serving (BASELINE config #4).

Encoder-decoder speech model with autoregressive greedy decode — the first
genuinely hard XLA problem in the zoo (SURVEY §7 hard part 2): generation
must run under static shapes with no per-token recompile.  Design:

- **One jitted program per request bucket**: log-mel [B,80,3000] → conv stem →
  4 pre-LN encoder layers → cross-K/V precompute → **prompt prefill in one
  batched forward** (same structure as models/decoder.py) → ``lax.scan`` over
  only the ``max_new`` generated tokens with a **fixed-size KV cache**
  indexed by the step counter.  No Python in the loop, no dynamic shapes,
  one compile, and the prompt never pays sequential steps.
- Early stopping is semantic, not structural: a ``finished`` flag per sequence
  pins the output to EOT after the first EOT (XLA cannot shrink the scan, so
  the tail steps are masked compute — the price of static shapes).
- Pure param-dict functions (not linen): the scan carries the cache pytree
  explicitly, which keeps the cache layout ([L, B, T, H, Dh]) and the
  step math readable and exactly controllable.
- bf16 matmuls / fp32 LayerNorm+softmax, like the rest of the zoo.

Weight import from HF ``openai/whisper-*`` torch checkpoints
(``engine/weights.convert_whisper``); parity in
``tests/test_whisper_parity.py`` uses teacher-forced stepwise logits (robust
to argmax ties on random weights).

Host side: ``ops/logmel.py`` computes features; long audio chunks into 30 s
windows app-side (the Whisper-idiomatic long-context answer, SURVEY §5).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .decoder import (KNOBS, ROWS, knob_batch, knob_spec, segment_scan,
                      slot_put)


@dataclass(frozen=True)
class WhisperConfig:
    vocab_size: int = 51865
    d_model: int = 384
    encoder_layers: int = 4
    decoder_layers: int = 4
    heads: int = 6
    ffn_dim: int = 1536
    n_mels: int = 80
    source_positions: int = 1500  # 30 s / (10 ms hop * 2x conv stride)
    target_positions: int = 448
    sot_id: int = 50258  # <|startoftranscript|>
    eot_id: int = 50257  # <|endoftext|>

    @property
    def head_dim(self) -> int:
        return self.d_model // self.heads


TINY = WhisperConfig()


def config_from_params(params: dict) -> WhisperConfig:
    """Derive a WhisperConfig from a converted checkpoint's param shapes.

    Serving whisper-base/small/medium needs no code edits: every architecture
    hyperparameter is recoverable from the tree — except the head count,
    which leaves no trace in fused-projection shapes.  All published Whisper
    sizes fix head_dim=64 (tiny 384/6 … large 1280/20), so ``heads =
    d_model // 64``; exotic head counts can override via ``extra.arch``.
    Token ids follow the vocab: 51865+ is the multilingual vocab (EOT 50257),
    51864 the English-only one (EOT 50256); SOT is always EOT+1.
    """
    enc, dec = params["encoder"], params["decoder"]
    conv1 = np.asarray(enc["conv1"]["kernel"])  # [3, n_mels, D]
    n_mels, d_model = int(conv1.shape[1]), int(conv1.shape[2])
    vocab = int(np.asarray(dec["embed_tokens"]).shape[0])
    eot = 50257 if vocab >= 51865 else 50256
    return WhisperConfig(
        vocab_size=vocab,
        d_model=d_model,
        encoder_layers=sum(1 for k in enc if k.startswith("layer")),
        decoder_layers=sum(1 for k in dec if k.startswith("layer")),
        heads=max(d_model // 64, 1),
        ffn_dim=int(np.asarray(enc["layer0"]["fc1"]["kernel"]).shape[1]),
        n_mels=n_mels,
        source_positions=int(np.asarray(enc["pos_embed"]).shape[0]),
        target_positions=int(np.asarray(dec["pos_embed"]).shape[0]),
        sot_id=eot + 1,
        eot_id=eot,
    )


# ---------------------------------------------------------------------------
# Core math (all pure; params are nested dicts from engine/weights.py)
# ---------------------------------------------------------------------------

def _ln(p, x, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


def _dense(p, x):
    """Plain or W8A16 projection, keyed on the param node (gpt2's pattern).

    The int8 lane (extra.params_dtype: "int8") rewrites the DECODER's
    per-step projection kernels to ``kernel_q`` + ``scale`` at build; the
    encoder, conv stem and cross-K/V projections keep plain kernels (their
    matmuls run at M=1500 source positions — the MXU-fed regime where the
    BERT measurement shows int8 losing), so this dispatch leaves them on
    the XLA path untouched.
    """
    from ..ops.int8_matmul import dense_maybe_int8

    return dense_maybe_int8(p, x)


def _logits_tied(dec: dict, x: jax.Array) -> jax.Array:
    """Tied lm-head projection: x [B, D] → logits [B, V] fp32.

    Int8 lane: a quantized TRANSPOSED copy (``lm_q`` [D, Vpad] +
    ``lm_scale``) replaces the embed_tokens read — at whisper-tiny the
    51865x384 head is ~70% of the decoder's per-step weight bytes, the
    single biggest int8 lever in this model.  Pad columns produce exactly-
    zero logits and are sliced off (gpt2 ``_logits``'s scheme).
    """
    if "lm_q" in dec:
        from ..ops.int8_matmul import int8_matmul

        vocab = dec["embed_tokens"].shape[0]
        return int8_matmul(x.astype(jnp.bfloat16), dec["lm_q"],
                           dec["lm_scale"],
                           out_dtype=jnp.float32)[:, :vocab]
    return x.astype(jnp.float32) @ dec["embed_tokens"].astype(jnp.float32).T


def _attn(q, k, v, heads, mask_bias=None):
    """q [B,Tq,D], k/v [B,Tk,D] (already projected) → [B,Tq,D]."""
    B, Tq, D = q.shape
    Tk = k.shape[1]
    hd = D // heads
    q = q.reshape(B, Tq, heads, hd)
    k = k.reshape(B, Tk, heads, hd)
    v = v.reshape(B, Tk, heads, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    if mask_bias is not None:
        scores = scores + mask_bias
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, Tq, D)


def _self_attn_block(p, x, heads, scale, mask_bias=None):
    h = _ln(p["self_ln"], x)
    q = _dense(p["q"], h) * scale
    k = _dense(p["k"], h)
    v = _dense(p["v"], h)
    return x + _dense(p["out"], _attn(q, k, v, heads, mask_bias))


def _ffn_block(p, x):
    h = _ln(p["ffn_ln"], x)
    h = jax.nn.gelu(_dense(p["fc1"], h), approximate=False)
    return x + _dense(p["fc2"], h)


def encode(params: dict, mel: jax.Array, cfg: WhisperConfig = TINY,
           dtype=jnp.bfloat16) -> jax.Array:
    """mel [B, n_mels, 3000] → encoder states [B, 1500, D]."""
    enc = params["encoder"]
    x = jnp.transpose(mel, (0, 2, 1)).astype(dtype)  # NWC
    x = jax.lax.conv_general_dilated(
        x, enc["conv1"]["kernel"].astype(dtype), window_strides=(1,),
        padding=[(1, 1)], dimension_numbers=("NWC", "WIO", "NWC"))
    x = jax.nn.gelu(x + enc["conv1"]["bias"].astype(dtype), approximate=False)
    x = jax.lax.conv_general_dilated(
        x, enc["conv2"]["kernel"].astype(dtype), window_strides=(2,),
        padding=[(1, 1)], dimension_numbers=("NWC", "WIO", "NWC"))
    x = jax.nn.gelu(x + enc["conv2"]["bias"].astype(dtype), approximate=False)
    x = x + enc["pos_embed"].astype(dtype)[None]
    scale = cfg.head_dim ** -0.5
    for i in range(cfg.encoder_layers):
        p = enc[f"layer{i}"]
        x = _self_attn_block(p, x, cfg.heads, scale)
        x = _ffn_block(p, x)
    return _ln(enc["final_ln"], x).astype(dtype)


def _cross_kv(params: dict, enc_out: jax.Array, cfg: WhisperConfig):
    """Precompute per-layer cross-attention K/V once per request."""
    dec = params["decoder"]
    return [( _dense(dec[f"layer{i}"]["ck"], enc_out),
              _dense(dec[f"layer{i}"]["cv"], enc_out))
            for i in range(cfg.decoder_layers)]


def _decoder_step(params, cfg, dtype, cross, tok, pos, cache_k, cache_v, kpos_mask):
    """One decoder position. tok [B] int32; cache [L,B,T,H*D].

    Returns (logits [B,V], new caches). kpos_mask [T] fp32 bias over cache keys.
    """
    dec = params["decoder"]
    B = tok.shape[0]
    scale = cfg.head_dim ** -0.5
    x = (dec["embed_tokens"].astype(dtype)[tok]
         + dec["pos_embed"].astype(dtype)[pos])[:, None, :]  # [B,1,D]
    for i in range(cfg.decoder_layers):
        p = dec[f"layer{i}"]
        # self-attn against the running cache
        h = _ln(p["self_ln"], x)
        q = _dense(p["q"], h) * scale
        k_new = _dense(p["k"], h)[:, 0]  # [B,D]
        v_new = _dense(p["v"], h)[:, 0]
        cache_k = cache_k.at[i, :, pos].set(k_new)
        cache_v = cache_v.at[i, :, pos].set(v_new)
        attn = _attn(q, cache_k[i], cache_v[i], cfg.heads,
                     mask_bias=kpos_mask[None, None, None, :])
        x = x + _dense(p["out"], attn)
        # cross-attn
        h = _ln(p["cross_ln"], x)
        cq = _dense(p["cq"], h) * scale
        ck, cv = cross[i]
        x = x + _dense(p["cout"], _attn(cq, ck, cv, cfg.heads))
        x = _ffn_block(p, x)
    x = _ln(dec["final_ln"], x)
    return _logits_tied(dec, x[:, 0]), cache_k, cache_v


def prefill_decoder(params: dict, cross, prompt: jax.Array, total: int,
                    cfg: WhisperConfig = TINY, dtype=jnp.bfloat16):
    """Whole task-prompt forward (the gpt2-style prefill, back-ported).

    The P prompt tokens cost ONE batched forward — large MXU matmuls filling
    ``cache[:, :, :P]`` for every position at once — instead of P sequential
    scan steps (the r2 "scan-everything" decode).  The prompt is uniform
    across rows (Whisper's fixed task prompt), so only a causal mask is
    needed, no raggedness.  Returns (last-position logits [B, V],
    cache_k, cache_v [L, B, total, D]).
    """
    dec = params["decoder"]
    B, P = prompt.shape
    scale = cfg.head_dim ** -0.5
    pos = jnp.arange(P)
    x = (dec["embed_tokens"].astype(dtype)[prompt]
         + dec["pos_embed"].astype(dtype)[pos][None])
    mask = jnp.where(pos[:, None] >= pos[None, :], 0.0,
                     -1e9).astype(jnp.float32)[None, None]  # [1,1,P,P] causal
    L = cfg.decoder_layers
    cache_k = jnp.zeros((L, B, total, cfg.d_model), dtype)
    cache_v = jnp.zeros((L, B, total, cfg.d_model), dtype)
    for i in range(L):
        p = dec[f"layer{i}"]
        h = _ln(p["self_ln"], x)
        q = _dense(p["q"], h) * scale
        k = _dense(p["k"], h)
        v = _dense(p["v"], h)
        cache_k = cache_k.at[i, :, :P].set(k)
        cache_v = cache_v.at[i, :, :P].set(v)
        x = x + _dense(p["out"], _attn(q, k, v, cfg.heads, mask))
        h = _ln(p["cross_ln"], x)
        cq = _dense(p["cq"], h) * scale
        ck, cv = cross[i]
        x = x + _dense(p["cout"], _attn(cq, ck, cv, cfg.heads))
        x = _ffn_block(p, x)
    x = _ln(dec["final_ln"], x)
    return _logits_tied(dec, x[:, -1]), cache_k, cache_v


def decode_greedy(params: dict, enc_out: jax.Array, prompt: jax.Array,
                  max_new: int, cfg: WhisperConfig = TINY,
                  dtype=jnp.bfloat16) -> jax.Array:
    """Prefill + scan greedy generation with a static KV cache.

    prompt [B, P] int32 (static P) costs one batched forward; only the
    ``max_new`` generated tokens pay sequential scan steps.  Returns tokens
    [B, max_new] int32, EOT-padded after the first EOT — bit-identical to the
    r2 scan-everything decode (same argmax chain), just cheaper.
    """
    B, P = prompt.shape
    total = P + max_new
    cross = _cross_kv(params, enc_out, cfg)
    logits, cache_k, cache_v = prefill_decoder(params, cross, prompt, total,
                                               cfg, dtype)
    first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    kpos = jnp.arange(total)

    def step(carry, t):
        cache_k, cache_v, tok, finished = carry
        mask = jnp.where(kpos <= P + t, 0.0, -1e9).astype(jnp.float32)
        logits, cache_k, cache_v = _decoder_step(
            params, cfg, dtype, cross, tok, P + t, cache_k, cache_v, mask)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # Step t emits the token decided before it (first from prefill); a
        # row pins to EOT after its first EOT.
        emit = jnp.where(finished, cfg.eot_id, tok)
        finished = finished | (tok == cfg.eot_id)
        return (cache_k, cache_v, nxt, finished), emit

    init = (cache_k, cache_v, first, jnp.zeros((B,), bool))
    _, emitted = jax.lax.scan(step, init, jnp.arange(max_new))
    return jnp.transpose(emitted, (1, 0))


def prefill_continuous(params: dict, mel: jax.Array, prompt_ids: tuple,
                       total_self: int, cfg: WhisperConfig = TINY,
                       dtype=jnp.bfloat16, temperature: jax.Array | None = None,
                       seeds: jax.Array | None = None,
                       top_k: jax.Array | None = None,
                       top_p: jax.Array | None = None):
    """Admission kernel for the continuous-batching lane: audio → first token
    + packed cache rows.

    Whisper's per-request conditioning is the ENCODER OUTPUT, not a prompt —
    so admission runs the whole encoder + cross-K/V precompute + task-prompt
    prefill in one program, and the result is packed as
    ``[L, B, source_positions + total_self, D]``: cross-attention K/V in the
    first ``source_positions`` time slots, the self-attention cache after.
    Packing (rather than a second cache pytree) keeps the scheduler's
    prefill/segment plumbing exactly as gpt2 uses it — the cache stays one
    opaque (k, v) pair per model, and the servable's ``prefill`` writes
    these rows into the slots of the pool (models/decoder.py ``slot_put``).
    """
    enc = encode(params, mel, cfg, dtype)
    prompt = jnp.tile(jnp.asarray(prompt_ids, jnp.int32)[None],
                      (mel.shape[0], 1))
    cross = _cross_kv(params, enc, cfg)
    logits, sk, sv = prefill_decoder(params, cross, prompt, total_self, cfg,
                                     dtype)
    if temperature is None:
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        # Sampled admission, same contract as gpt2's prefill_start: the
        # FIRST token draws with the request's knobs at step 0 (without
        # this, every sampled stream opened with the greedy token).
        from ..ops.sampling import choose

        B = mel.shape[0]
        first = choose(logits, temperature,
                       jnp.zeros((B,), jnp.int32) if seeds is None else seeds,
                       jnp.zeros((B,), jnp.int32), top_k, top_p)
    cross_k = jnp.stack([c[0] for c in cross]).astype(dtype)  # [L,B,CL,D]
    cross_v = jnp.stack([c[1] for c in cross]).astype(dtype)
    return (first, jnp.concatenate([cross_k, sk], axis=2),
            jnp.concatenate([cross_v, sv], axis=2))


def decode_segment(params: dict, cache_k: jax.Array, cache_v: jax.Array,
                   tok: jax.Array, pos: jax.Array, step: jax.Array,
                   finished: jax.Array, seg: int,
                   cfg: WhisperConfig = TINY, dtype=jnp.bfloat16,
                   temperature: jax.Array | None = None,
                   seeds: jax.Array | None = None,
                   top_k: jax.Array | None = None,
                   top_p: jax.Array | None = None):
    """Advance every slot by ``seg`` tokens — whisper's continuous-batching
    program: its own layers under models/decoder.py's ``segment_scan``, which
    holds the emit and finish rules (docstring there).

    ``cache_k``/``cache_v`` are the packed pools from
    :func:`prefill_continuous` ([L, S, CL + total_self, D]); ``pos`` [S] is
    each row's next SELF-cache write position (prompt_len + generated so
    far).  Per-step math is identical to :func:`decode_greedy`'s scan body —
    same masks, same fp32 logits, same argmax chain — so a lone slot's
    stream is token-identical to the fixed-batch path.  Sampling knobs
    (``temperature``/``seeds``/``top_k``/``top_p``, all [S] jit inputs;
    None or temperature 0 = greedy, the transcription default) ride per
    slot through ops/sampling.choose, same contract as gpt2.
    """
    from ..ops.sampling import choose

    dec = params["decoder"]
    S = tok.shape[0]
    CL = cfg.source_positions
    total_self = cache_k.shape[2] - CL
    kpos = jnp.arange(total_self)
    rows = jnp.arange(S)
    scale = cfg.head_dim ** -0.5

    def one(cache, tok, pos, t, fin, seen):
        cache_k, cache_v = cache
        wpos = jnp.minimum(pos, total_self - 1)
        x = (dec["embed_tokens"].astype(dtype)[tok]
             + dec["pos_embed"].astype(dtype)[
                 jnp.minimum(wpos, cfg.target_positions - 1)])[:, None, :]
        mask_bias = jnp.where(kpos[None, :] <= wpos[:, None], 0.0,
                              -1e9).astype(jnp.float32)[:, None, None, :]
        for i in range(cfg.decoder_layers):
            p = dec[f"layer{i}"]
            h = _ln(p["self_ln"], x)
            q = _dense(p["q"], h) * scale
            k_new = _dense(p["k"], h)[:, 0]
            v_new = _dense(p["v"], h)[:, 0]
            cache_k = cache_k.at[i, rows, CL + wpos].set(k_new)
            cache_v = cache_v.at[i, rows, CL + wpos].set(v_new)
            attn = _attn(q, cache_k[i, :, CL:], cache_v[i, :, CL:],
                         cfg.heads, mask_bias)
            x = x + _dense(p["out"], attn)
            h = _ln(p["cross_ln"], x)
            cq = _dense(p["cq"], h) * scale
            x = x + _dense(p["cout"], _attn(cq, cache_k[i, :, :CL],
                                            cache_v[i, :, :CL], cfg.heads))
            x = _ffn_block(p, x)
        x = _ln(dec["final_ln"], x)
        logits = _logits_tied(dec, x[:, 0])
        if temperature is None:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            nxt = choose(logits, temperature,
                         jnp.zeros((S,), jnp.int32) if seeds is None
                         else seeds, t + 1, top_k, top_p)
        return (cache_k, cache_v), nxt, seen

    return segment_scan(one, (cache_k, cache_v), tok, pos, step, finished,
                        seg, cfg.eot_id)


def decode_forced(params: dict, enc_out: jax.Array, tokens: jax.Array,
                  cfg: WhisperConfig = TINY, dtype=jnp.bfloat16) -> jax.Array:
    """Teacher-forced stepwise logits [B, T, V] for scoring/parity tests."""
    B, T = tokens.shape
    L = cfg.decoder_layers
    cross = _cross_kv(params, enc_out, cfg)
    cache_k = jnp.zeros((L, B, T, cfg.d_model), dtype)
    cache_v = jnp.zeros((L, B, T, cfg.d_model), dtype)
    kpos = jnp.arange(T)

    def step(carry, t):
        cache_k, cache_v = carry
        mask = jnp.where(kpos <= t, 0.0, -1e9).astype(jnp.float32)
        logits, cache_k, cache_v = _decoder_step(
            params, cfg, dtype, cross, tokens[:, t], t, cache_k, cache_v, mask)
        return (cache_k, cache_v), logits

    _, logits = jax.lax.scan(step, (cache_k, cache_v), jnp.arange(T))
    return jnp.transpose(logits, (1, 0, 2))


# ---------------------------------------------------------------------------
# Random init (offline dev mode: real architecture, synthesized weights)
# ---------------------------------------------------------------------------

def _sinusoids(length: int, channels: int) -> np.ndarray:
    """Whisper's fixed encoder positional embedding."""
    log_timescale = np.log(10000) / (channels // 2 - 1)
    inv = np.exp(-log_timescale * np.arange(channels // 2))
    scaled = np.arange(length)[:, None] * inv[None, :]
    return np.concatenate([np.sin(scaled), np.cos(scaled)], axis=1).astype(np.float32)


def init_whisper_params(seed: int = 0, cfg: WhisperConfig = TINY) -> dict:
    g = np.random.default_rng(seed)

    def dense(i, o, bias=True):
        p = {"kernel": (g.standard_normal((i, o)) * 0.02).astype(np.float32)}
        if bias:
            p["bias"] = np.zeros((o,), np.float32)
        return p

    def ln(d):
        return {"scale": np.ones((d,), np.float32), "bias": np.zeros((d,), np.float32)}

    D, F = cfg.d_model, cfg.ffn_dim

    def enc_layer():
        return {"self_ln": ln(D), "q": dense(D, D), "k": dense(D, D, bias=False),
                "v": dense(D, D), "out": dense(D, D),
                "ffn_ln": ln(D), "fc1": dense(D, F), "fc2": dense(F, D)}

    def dec_layer():
        return {**enc_layer(),
                "cross_ln": ln(D), "cq": dense(D, D), "ck": dense(D, D, bias=False),
                "cv": dense(D, D), "cout": dense(D, D)}

    encoder = {
        "conv1": {"kernel": (g.standard_normal((3, cfg.n_mels, D)) * 0.02).astype(np.float32),
                  "bias": np.zeros((D,), np.float32)},
        "conv2": {"kernel": (g.standard_normal((3, D, D)) * 0.02).astype(np.float32),
                  "bias": np.zeros((D,), np.float32)},
        "pos_embed": _sinusoids(cfg.source_positions, D),
        "final_ln": ln(D),
    }
    for i in range(cfg.encoder_layers):
        encoder[f"layer{i}"] = enc_layer()
    decoder = {
        "embed_tokens": (g.standard_normal((cfg.vocab_size, D)) * 0.02).astype(np.float32),
        "pos_embed": (g.standard_normal((cfg.target_positions, D)) * 0.02).astype(np.float32),
        "final_ln": ln(D),
    }
    for i in range(cfg.decoder_layers):
        decoder[f"layer{i}"] = dec_layer()
    return {"encoder": encoder, "decoder": decoder}


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def _decode_audio_payload(payload) -> np.ndarray:
    """WAV bytes or JSON {"array": [...]} → float32 mono 16 kHz waveform.

    Any WAV sample rate is accepted: non-16 kHz audio goes through the
    anti-aliased windowed-sinc resampler (ops/audio.py — native C++ with a
    numpy fallback).  A JSON {"array": ..., "rate": N} resamples too;
    without "rate" the array is assumed 16 kHz.
    """
    from ..ops.audio import TARGET_RATE, resample

    if isinstance(payload, dict) and "array" in payload:
        x = np.asarray(payload["array"], dtype=np.float32)
        return resample(x, int(payload.get("rate", TARGET_RATE)))
    import io
    import wave

    with wave.open(io.BytesIO(payload)) as w:
        raw = w.readframes(w.getnframes())
        width = w.getsampwidth()
        dt = {1: np.uint8, 2: np.int16, 4: np.int32}[width]
        x = np.frombuffer(raw, dtype=dt).astype(np.float32)
        if width == 1:
            x = (x - 128.0) / 128.0
        else:
            x = x / float(2 ** (8 * width - 1))
        if w.getnchannels() > 1:
            x = x.reshape(-1, w.getnchannels()).mean(-1)
        return resample(x, w.getframerate())


def make_whisper_servable(name: str, cfg_model) -> Any:
    from ..engine.servable import Servable
    from ..engine import weights as W
    from ..ops.logmel import N_FRAMES, chunk_waveform, log_mel_spectrogram
    from .vision_common import resolve_dtype

    dtype = resolve_dtype(cfg_model.dtype)
    max_new = int(cfg_model.extra.get("max_new_tokens", 64))
    # extra.arch overrides architecture fields (tiny test variants; the
    # heads escape hatch for non-64 head_dim checkpoints).
    arch = {k: int(v) for k, v in dict(cfg_model.extra.get("arch", {})).items()}

    if cfg_model.checkpoint:
        # Config is checkpoint-driven: whisper-base/small/... serve without
        # code edits (shapes → WhisperConfig).
        params = W.import_params(cfg_model.checkpoint, W.convert_whisper)
        cfg = dataclasses.replace(config_from_params(params), **arch)
    else:
        cfg = dataclasses.replace(TINY, **arch) if arch else TINY
    if cfg.vocab_size <= cfg.eot_id and "eot_id" not in arch:
        # Shrunk-vocab variant (tiny test archs, staged tiny checkpoints):
        # pin the control ids into range or decode gathers out-of-bounds.
        cfg = dataclasses.replace(cfg, eot_id=cfg.vocab_size - 2,
                                  sot_id=cfg.vocab_size - 1)
    if not cfg_model.checkpoint:
        params = init_whisper_params(0, cfg)
    if str(cfg_model.extra.get("params_dtype", "")) == "int8":
        # W8A16 lane (VERDICT r4 next #4): quantize ONLY the decoder's
        # per-step projections (q/k/v/out/cq/cout/fc1/fc2) + a transposed
        # lm-head copy; the encoder, conv stem and cross-K/V projections
        # stay bf16 — they run once per request at M=1500 source positions,
        # the MXU-fed regime where int8 measured losing (README regime
        # table).  Decode is the bandwidth-bound phase this lane exists for
        # (3.7% MFU, decode-shaped matmuls).
        from ..ops.int8_matmul import (pad_weights, quantize_per_channel,
                                       quantize_tree)
        from .vision_common import cast_params_at_rest

        min_size = int(cfg_model.extra.get("quantize_min_size", 1 << 16))
        dec = params["decoder"]
        for i in range(cfg.decoder_layers):
            lp = dec[f"layer{i}"]
            for n in ("q", "k", "v", "out", "cq", "cout", "fc1", "fc2"):
                lp[n] = quantize_tree(lp[n], min_size=min_size)
        lm_q, lm_scale = quantize_per_channel(
            np.asarray(dec["embed_tokens"]).T.copy(), axis=0)
        dec["lm_q"], dec["lm_scale"] = pad_weights(lm_q, lm_scale)
        params = cast_params_at_rest(params, jnp.bfloat16)
    params = jax.device_put(params)  # ONE batched tree transfer: per-leaf
    # jnp.asarray serializes a host round-trip per buffer.

    # sot, en, transcribe, notimestamps — the multilingual-vocab task prompt;
    # English-only and test vocabs fall back to a bare SOT.
    default_prompt = ((cfg.sot_id, 50259, 50359, 50363)
                      if cfg.vocab_size >= 51865 else (cfg.sot_id,))
    prompt_ids = tuple(cfg_model.extra.get("prompt_ids", default_prompt))

    def apply_fn(p, inputs):
        enc = encode(p, inputs["mel"], cfg, dtype)
        prompt = jnp.tile(jnp.asarray(prompt_ids, jnp.int32)[None],
                          (inputs["mel"].shape[0], 1))
        return {"tokens": decode_greedy(p, enc, prompt, max_new, cfg, dtype)}

    def input_spec(bucket):
        return {"mel": jax.ShapeDtypeStruct((bucket[0], cfg.n_mels, N_FRAMES),
                                            jnp.float32)}

    def preprocess(payload):
        """One request → one sample, or a LIST of samples for long audio.

        Long audio chunks into 30 s windows app-side (SURVEY §5
        "Long-context"): each window becomes its own batcher sample, so
        windows of one request co-batch with each other AND with other
        requests; the server merges per-window results via ``merge_results``.
        """
        audio = _decode_audio_payload(payload)
        windows = chunk_waveform(audio)
        # Sampling knobs (JSON-array payloads only; the :generate lane) ride
        # into the sample so the continuous scheduler's admission sees them;
        # the fixed-batch :predict lane stays greedy (decode_greedy).
        knobs = {}
        if isinstance(payload, dict):
            for key, _, off in KNOBS:
                if key in payload:
                    knobs[key] = type(off)(payload[key])
        samples = [{"mel": log_mel_spectrogram(w), **knobs} for w in windows]
        return samples[0] if len(samples) == 1 else samples

    def postprocess(out, i):
        toks = [int(t) for t in out["tokens"][i]]
        if cfg.eot_id in toks:
            toks = toks[: toks.index(cfg.eot_id)]
        return {"tokens": toks}

    def merge_results(results):
        """Per-window results (in request order) → one transcript."""
        return {"tokens": [t for r in results for t in r["tokens"]],
                "chunks": len(results)}

    # Continuous-batching lane (POST :generate): same scheduler contract as
    # gpt2 — VERDICT r3 called whisper "the test that the abstraction is
    # real".  Admission carries the log-mel window (the model-shaped payload
    # the generic admit trio exists for); one 30 s window per stream (long
    # audio belongs to the chunk-and-merge :predict lane).
    gen_slots = int(cfg_model.extra.get("gen_slots", 4))
    segment_tokens = int(cfg_model.extra.get("segment_tokens", 8))
    P = len(prompt_ids)
    total_self = P + max_new
    CL = cfg.source_positions

    def collate_admit(sample, bucket):
        return {"mel": np.asarray(sample["mel"], np.float32)[None],
                "length": np.asarray([P], np.int32), **knob_batch(sample)}

    def admit_spec(bucket):
        return {"mel": jax.ShapeDtypeStruct((1, cfg.n_mels, N_FRAMES),
                                            jnp.float32),
                "length": jax.ShapeDtypeStruct((1,), jnp.int32),
                **knob_spec(1)}

    def admit(p, cache, slots, payload):
        """The scheduler's admission program: the packed rows of every
        request, whole, over the slot of the pool it was given."""
        first, *rows = prefill_continuous(
            p, payload["mel"], prompt_ids, total_self, cfg, dtype,
            temperature=payload["temperature"], seeds=payload["seed"],
            top_k=payload["top_k"], top_p=payload["top_p"])
        put = slot_put(slots)
        for i in range(cfg.decoder_layers):
            cache = tuple(put(leaf, jnp.int32(i), packed[i])
                          for leaf, packed in zip(cache, rows))
        return (first, *cache)

    continuous = {
        "slots": gen_slots,
        "segment_tokens": segment_tokens,
        "total": total_self,
        "rows": ROWS,  # self-attention: a row a position
        "eos_id": cfg.eot_id,
        "max_new": max_new,
        # One admission bucket: every request is one fixed-size mel window.
        "prompt_buckets": (1,),
        "admit_len_of": lambda s: 1,
        "collate_admit": collate_admit,
        "admit_spec": admit_spec,
        # The pool's leaves, K and V: cross rows, then self rows.
        "cache_leaves": (((cfg.decoder_layers, gen_slots, CL + total_self,
                           cfg.d_model), dtype),) * 2,
        "prefill": admit,
        "segment": (lambda p, cache, tok, pos, st, fin, temp, seeds,
                    topk, topp:
                    decode_segment(p, *cache, tok, pos, st, fin,
                                   segment_tokens, cfg, dtype,
                                   temperature=temp, seeds=seeds,
                                   top_k=topk, top_p=topp)),
        "detokenize": None,
    }

    from ..parallel.mesh import WHISPER_TP_RULES

    return Servable(name=name, apply_fn=apply_fn, params=params,
                    input_spec=input_spec, preprocess=preprocess,
                    postprocess=postprocess, bucket_axes=("batch",),
                    meta={"max_new_tokens": max_new,
                          "merge_results": merge_results,
                          "continuous": continuous,
                          # The fixed-batch lane is decode_greedy — sampling
                          # knobs only work on :generate; the server 400s
                          # them on :predict instead of silently returning
                          # greedy output (ADVICE r5).
                          "predict_ignores_sampling": (
                              "temperature", "seed", "top_k", "top_p"),
                          "tp_rules": WHISPER_TP_RULES})


from ..utils.registry import register_model  # noqa: E402


@register_model("whisper_tiny", latency_class="latency")
def build_whisper_tiny(cfg):
    return make_whisper_servable("whisper_tiny", cfg)
