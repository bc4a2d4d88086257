"""Whisper W8A16 int8 lane (extra.params_dtype: "int8") — VERDICT r4 #4.

Quantization scope is the point under test: ONLY the decoder's per-step
projections (q/k/v/out/cq/cout/fc1/fc2) and a transposed lm-head copy
quantize; the encoder, conv stem and cross-K/V projections (M=1500,
MXU-fed) must keep plain kernels.  Correctness mirrors
tests/test_gpt2_int8.py: the int8 servable's decode logits are compared
against an XLA reference running on the DEQUANTIZED weights (same
quantization error both sides, so drift is the kernel's).
"""

import dataclasses

import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig
from pytorch_zappa_serverless_tpu import models as _zoo  # noqa: F401
from pytorch_zappa_serverless_tpu.models import whisper as W
from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

TINY_ARCH = {"d_model": 128, "encoder_layers": 2, "decoder_layers": 2,
             "heads": 2, "ffn_dim": 256, "vocab_size": 512,
             "source_positions": 1500, "target_positions": 96}


def _tiny_cfg():
    cfg = dataclasses.replace(W.TINY, **TINY_ARCH)
    return dataclasses.replace(cfg, eot_id=cfg.vocab_size - 2,
                               sot_id=cfg.vocab_size - 1)


def _build(**extra):
    cfg = ModelConfig(name="whisper_tiny", dtype="bfloat16",
                      batch_buckets=(1,),
                      extra={"max_new_tokens": 6, "arch": TINY_ARCH,
                             "quantize_min_size": 1024, **extra})
    return get_model_builder("whisper_tiny")(cfg)


@pytest.fixture(scope="module")
def sv_q():
    return _build(params_dtype="int8")


def test_quantization_scope(sv_q):
    """Decoder per-step kernels quantize; encoder and cross-K/V do not."""
    dec = sv_q.params["decoder"]
    enc = sv_q.params["encoder"]
    l0 = dec["layer0"]
    for n in ("q", "k", "v", "out", "cq", "cout", "fc1", "fc2"):
        assert l0[n]["kernel_q"].dtype == np.int8, n
        assert "kernel" not in l0[n]
    # Cross-K/V (admission-time, M=1500) and the whole encoder stay plain.
    assert "kernel" in l0["ck"] and "kernel_q" not in l0["ck"]
    assert "kernel" in l0["cv"]
    assert "kernel" in enc["layer0"]["q"]
    # Tied head: transposed quantized copy + pad; embed stays float for the
    # gathers.
    assert dec["lm_q"].dtype == np.int8
    assert dec["lm_q"].shape[0] == dec["embed_tokens"].shape[1]
    assert dec["embed_tokens"].dtype != np.int8


def _dequant_params(params):
    """XLA-reference params: same values the int8 kernel computes with."""
    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if k == "kernel_q":
                out["kernel"] = (np.asarray(v, np.float32)
                                 * np.asarray(node["scale"])[None, :])
            elif k == "scale" and "kernel_q" in node:
                continue
            elif k in ("lm_q", "lm_scale"):
                continue  # reference ties the head back to bf16 embed
            elif isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = v
        return out

    return walk(params)


def test_int8_decode_matches_dequantized_reference(sv_q):
    import jax.numpy as jnp

    cfg = _tiny_cfg()
    rng = np.random.default_rng(0)
    mel = jnp.asarray(rng.standard_normal((1, 80, 3000)).astype(np.float32))
    enc = W.encode(sv_q.params, mel, cfg, jnp.bfloat16)
    prompt = jnp.asarray([[cfg.sot_id]], jnp.int32)
    got = np.asarray(W.decode_greedy(sv_q.params, enc, prompt, 6, cfg,
                                     jnp.bfloat16))
    ref_params = _dequant_params(
        {k: v for k, v in sv_q.params.items()})
    ref = np.asarray(W.decode_greedy(ref_params, enc, prompt, 6, cfg,
                                     jnp.bfloat16))
    # Same quantized values both sides -> the greedy chains must agree
    # except where the int8 head's quantization flips a near-tie (the
    # reference uses the unquantized head); require first-token agreement
    # via logits instead: compare the prefill logits directly.
    cross = W._cross_kv(sv_q.params, enc, cfg)
    lq, _, _ = W.prefill_decoder(sv_q.params, cross, prompt, 7, cfg,
                                 jnp.bfloat16)
    lr, _, _ = W.prefill_decoder(ref_params, cross, prompt, 7, cfg,
                                 jnp.bfloat16)
    lq, lr = np.asarray(lq), np.asarray(lr)
    assert np.abs(lq - lr).max() < 0.05 * max(np.abs(lr).max(), 1e-3)
    assert got.shape == ref.shape == (1, 6)


def test_int8_servable_runs_end_to_end(sv_q):
    import jax

    mel = np.random.default_rng(1).standard_normal((1, 80, 3000)).astype(
        np.float32)
    out = jax.jit(sv_q.apply_fn)(sv_q.params, {"mel": mel})
    toks = np.asarray(out["tokens"])
    assert toks.shape == (1, 6) and toks.dtype == np.int32


def test_int8_continuous_segment_runs(sv_q):
    """The packed-pool segment kernel works on the quantized tree (the
    continuous lane routes decode through the same _dense dispatch)."""
    import jax.numpy as jnp

    cont = sv_q.servable_meta_continuous if hasattr(
        sv_q, "servable_meta_continuous") else sv_q.meta["continuous"]
    S = cont["slots"]
    cache = tuple(jnp.zeros(shape, dt) for shape, dt in cont["cache_leaves"])
    emits, *_ = cont["segment"](
        sv_q.params, cache, jnp.zeros((S,), jnp.int32),
        jnp.ones((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.zeros((S,), bool), jnp.zeros((S,), jnp.float32),
        jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
        jnp.ones((S,), jnp.float32))
    assert np.asarray(emits).shape == (S, cont["segment_tokens"])


def test_int8_memory_shrinks():
    import jax

    sv = _build()
    sv_q2 = _build(params_dtype="int8")

    def nbytes(tree):
        return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))

    # Decoder kernels int8 + bf16 everything + the extra int8 head copy vs
    # fp32 at rest.
    assert nbytes(sv_q2.params) < 0.5 * nbytes(sv.params)
