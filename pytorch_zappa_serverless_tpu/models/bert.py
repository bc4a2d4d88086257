"""BERT-base sequence classification for TPU serving (BASELINE config #3).

Own flax encoder (not a wrapper): embeddings (word+position+segment, LN) →
12 post-LN transformer layers (MHA 12x64, FFN 3072, exact-erf GELU) → pooler
(tanh on [CLS]) → classifier.  TPU-first choices:

- bf16 compute / fp32 params; LayerNorm + softmax accumulate in fp32.
- Attention as batched einsums — at seq-len 128 the whole layer is a handful
  of MXU matmuls; XLA fuses mask+softmax+scale.  (Long-context models in this
  zoo would swap in the Pallas flash kernel from ``ops/pallas``; BERT-128's
  scores tensor is tiny, so materializing it is optimal, not a compromise.)
- Static (batch, seq) buckets from the engine; attention mask handles padding,
  so a 37-token request in the 128 bucket returns bit-identical logits to an
  unpadded run.

Weight import: HF ``bert-base-uncased``-family torch checkpoints
(``engine/weights.convert_bert``); parity vs torch in
``tests/test_bert_parity.py``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


class Int8Dense(nn.Module):
    """W8A16 projection for the linen tree: ``kernel_q`` int8 + per-output
    ``scale`` (ops/int8_matmul layout), bias fp32.

    Drop-in for ``nn.Dense`` in the encoder when the int8 lane is on — the
    param NAMES differ (kernel_q/scale vs kernel), which is exactly how the
    servable's build-time quantization pass and the engine's int8 gate
    (engine/compiled.py ``_has_q``) recognize the lane.
    """

    features: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from ..ops.int8_matmul import dense_maybe_int8

        K = x.shape[-1]
        kq = self.param("kernel_q", nn.initializers.zeros_init(),
                        (K, self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones_init(),
                           (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,), jnp.float32)
        # One W8A16 dense implementation repo-wide: the same dispatch gpt2's
        # param-dict path uses (flatten, kernel, bias), so tuning there
        # can't silently diverge from this lane.
        return dense_maybe_int8({"kernel_q": kq, "scale": scale,
                                 "bias": bias}, x.astype(self.dtype))


def _dense_cls(quantized: bool):
    return Int8Dense if quantized else nn.Dense


class BertSelfAttention(nn.Module):
    num_heads: int
    head_dim: int
    dtype: jnp.dtype
    quantized: bool = False

    @nn.compact
    def __call__(self, x, mask_bias):
        d = self.num_heads * self.head_dim
        D = _dense_cls(self.quantized)
        q = D(d, dtype=self.dtype, name="query")(x)
        k = D(d, dtype=self.dtype, name="key")(x)
        v = D(d, dtype=self.dtype, name="value")(x)
        B, S, _ = x.shape
        shape = (B, S, self.num_heads, self.head_dim)
        q, k, v = (t.reshape(shape) for t in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(self.head_dim)
        scores = scores.astype(jnp.float32) + mask_bias  # fp32 softmax
        probs = jax.nn.softmax(scores, axis=-1).astype(self.dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, d)
        return out


class BertLayer(nn.Module):
    num_heads: int
    head_dim: int
    mlp_dim: int
    dtype: jnp.dtype
    ln_eps: float = 1e-12
    quantized: bool = False

    @nn.compact
    def __call__(self, x, mask_bias):
        d = self.num_heads * self.head_dim
        D = _dense_cls(self.quantized)
        attn = BertSelfAttention(self.num_heads, self.head_dim, self.dtype,
                                 self.quantized, name="attention")(x, mask_bias)
        attn = D(d, dtype=self.dtype, name="attention_output")(attn)
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         name="attention_ln")(x + attn)
        x = x.astype(self.dtype)
        h = D(self.mlp_dim, dtype=self.dtype, name="intermediate")(x)
        h = nn.gelu(h, approximate=False)
        h = D(d, dtype=self.dtype, name="output")(h)
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         name="output_ln")(x + h)
        return x.astype(self.dtype)


class BertClassifier(nn.Module):
    vocab_size: int = 30522
    max_position: int = 512
    type_vocab_size: int = 2
    num_layers: int = 12
    num_heads: int = 12
    head_dim: int = 64
    mlp_dim: int = 3072
    num_labels: int = 2
    dtype: jnp.dtype = jnp.bfloat16
    ln_eps: float = 1e-12
    # W8A16 encoder projections (Int8Dense); embeddings, LayerNorms, pooler
    # and classifier stay float — they are a few MB against the encoder's
    # ~85M projection params, and the fp32 head keeps logits exact.
    quantized: bool = False

    @nn.compact
    def __call__(self, input_ids, attention_mask, token_type_ids,
                 return_hidden: bool = False):
        """All inputs int32 [B, S]; returns fp32 logits [B, num_labels]
        (or the last hidden states [B, S, D] when ``return_hidden``)."""
        d = self.num_heads * self.head_dim
        x = (nn.Embed(self.vocab_size, d, dtype=self.dtype, name="word_embeddings")(input_ids)
             + nn.Embed(self.max_position, d, dtype=self.dtype,
                        name="position_embeddings")(jnp.arange(input_ids.shape[1])[None])
             + nn.Embed(self.type_vocab_size, d, dtype=self.dtype,
                        name="token_type_embeddings")(token_type_ids))
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         name="embeddings_ln")(x).astype(self.dtype)
        # [B,S] 1/0 -> additive bias broadcast over heads/query: [B,1,1,S].
        mask_bias = (1.0 - attention_mask[:, None, None, :].astype(jnp.float32)) * -1e9
        for i in range(self.num_layers):
            x = BertLayer(self.num_heads, self.head_dim, self.mlp_dim, self.dtype,
                          self.ln_eps, self.quantized, name=f"layer{i}")(x, mask_bias)
        if return_hidden:
            return x
        pooled = jnp.tanh(nn.Dense(d, dtype=jnp.float32, name="pooler")(
            x[:, 0].astype(jnp.float32)))
        return nn.Dense(self.num_labels, dtype=jnp.float32, name="classifier")(pooled)


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def _fallback_tokenize(text: str, vocab_size: int) -> list[int]:
    """Deterministic offline tokenizer stub: whitespace words hashed into the
    wordpiece id space.  Real deployments set extra.tokenizer to a HF
    tokenizer.json; this keeps the dev profile servable with zero assets.
    Unbounded — the servable's ``_fit`` applies the over-length policy, same
    as the real-tokenizer path."""
    import hashlib

    # Skip the wordpiece special/control band only when the vocab has one:
    # with tiny dev vocabs (arch overrides) the old `1000 + h % (vocab-2000)`
    # went NEGATIVE and produced out-of-range ids — flax Embed fills OOB
    # gathers with NaN, which surfaced as NaN probabilities end-to-end.
    lo = 1000 if vocab_size > 2000 else 103
    span = max(vocab_size - lo, 1)
    ids = [101]  # [CLS]
    for w in text.lower().split():
        h = int(hashlib.md5(w.encode()).hexdigest(), 16)
        ids.append(lo + h % span)
    ids.append(102)  # [SEP]
    return ids


def make_bert_servable(name: str, cfg) -> Any:
    from ..engine.servable import Servable
    from ..engine import weights as W
    from .vision_common import resolve_dtype

    num_labels = int(cfg.extra.get("num_labels", 2))
    labels = cfg.extra.get("labels") or [f"label_{i}" for i in range(num_labels)]
    max_seq = max(cfg.seq_buckets)
    # extra.arch overrides architecture hyperparams (num_layers, num_heads,
    # head_dim, mlp_dim, vocab_size, ...) — tiny variants for tests/dev.
    arch = {k: int(v) for k, v in dict(cfg.extra.get("arch", {})).items()}
    int8 = str(cfg.extra.get("params_dtype", "")) == "int8"
    model = BertClassifier(num_labels=num_labels, dtype=resolve_dtype(cfg.dtype),
                           quantized=int8, **arch)

    if cfg.checkpoint:
        params = W.import_params(cfg.checkpoint, W.convert_bert)
    else:
        # Random-init always goes through the FLOAT model (Int8Dense's init
        # would produce zero kernels); the int8 rewrite below converts.
        float_model = BertClassifier(num_labels=num_labels,
                                     dtype=resolve_dtype(cfg.dtype), **arch)
        dummy = jnp.zeros((1, 8), jnp.int32)
        params = float_model.init(jax.random.key(0), dummy,
                                  jnp.ones((1, 8), jnp.int32), dummy)["params"]
    if int8:
        # W8A16 lane (the same rewrite gpt2's builder does): encoder
        # projection kernels -> int8 + per-channel scale, matching the
        # Int8Dense params; everything outside layer{i}/ stays float.
        import flax

        from ..ops.int8_matmul import quantize_tree

        params = flax.core.unfreeze(params)
        params = {k: (quantize_tree(v, min_size=1)
                      if k.startswith("layer") else v)
                  for k, v in dict(params).items()}
    params = jax.device_put(params)  # ONE batched tree transfer: per-leaf jnp.asarray
    # serializes a host round-trip per buffer.

    tokenizer = None
    tok_path = cfg.extra.get("tokenizer")
    if tok_path:
        from tokenizers import Tokenizer

        tokenizer = Tokenizer.from_file(str(tok_path))

    # extra.embed: serve mean-pooled (mask-aware) L2-normalized sentence
    # embeddings instead of classification — the embeddings-API staple.
    embed_mode = bool(cfg.extra.get("embed", False))

    def apply_fn(p, inputs):
        if embed_mode:
            hidden = model.apply({"params": p}, inputs["input_ids"],
                                 inputs["attention_mask"], inputs["token_type_ids"],
                                 return_hidden=True)
            mask = inputs["attention_mask"].astype(jnp.float32)[:, :, None]
            pooled = (hidden.astype(jnp.float32) * mask).sum(1) / jnp.maximum(
                mask.sum(1), 1.0)
            norm = jnp.sqrt(jnp.maximum((pooled * pooled).sum(-1, keepdims=True), 1e-12))
            return {"embedding": pooled / norm}  # [B, D] unit vectors
        logits = model.apply({"params": p}, inputs["input_ids"],
                             inputs["attention_mask"], inputs["token_type_ids"])
        return {"probs": jax.nn.softmax(logits, axis=-1)}  # [B, num_labels]: one small fetch

    def input_spec(bucket):
        b, s = bucket
        return {k: jax.ShapeDtypeStruct((b, s), jnp.int32)
                for k in ("input_ids", "attention_mask", "token_type_ids")}

    # Over-length policy (extra.overlength): classification defaults to
    # "truncate" (keep the head — [CLS] + leading context carries the label
    # signal); "error" turns an over-bucket input into a clean 400 at
    # preprocess time instead of a bucket_for ValueError → 500 downstream.
    overlength = str(cfg.extra.get("overlength", "truncate"))
    if overlength not in ("truncate", "error"):
        raise ValueError(f"{name}: extra.overlength must be 'truncate' or "
                         f"'error', got {overlength!r}")

    def _fit(ids: list[int]) -> list[int]:
        if len(ids) > max_seq:
            if overlength == "error":
                raise ValueError(
                    f"input is {len(ids)} tokens but the longest configured "
                    f"seq bucket is {max_seq}; send a shorter input or serve "
                    f"with a larger seq bucket")
            ids = ids[:max_seq]
        return ids

    def preprocess(payload):
        if isinstance(payload, dict) and "input_ids" in payload:
            ids = _fit([int(i) for i in payload["input_ids"]])
        else:
            text = payload["text"] if isinstance(payload, dict) else str(payload)
            if tokenizer is not None:
                ids = _fit(tokenizer.encode(text).ids)
            else:
                ids = _fit(_fallback_tokenize(text, model.vocab_size))
        ids = np.asarray(ids, dtype=np.int32)
        return {"input_ids": ids,
                "attention_mask": np.ones_like(ids),
                "token_type_ids": np.zeros_like(ids)}

    def postprocess(out, i):
        if embed_mode:
            return {"embedding": np.asarray(out["embedding"][i], dtype=float).tolist()}
        probs = out["probs"][i]
        order = np.argsort(probs)[::-1]
        return {"scores": [{"label": str(labels[int(j)]), "prob": float(probs[int(j)])}
                           for j in order]}

    from ..parallel.mesh import BERT_TP_RULES

    return Servable(
        name=name, apply_fn=apply_fn, params=params, input_spec=input_spec,
        preprocess=preprocess, postprocess=postprocess,
        bucket_axes=("batch", "seq"),
        meta={"seq_len_of": lambda s: int(s["input_ids"].shape[0]),
              "num_labels": num_labels,
              "tp_rules": BERT_TP_RULES})


from ..utils.registry import register_model  # noqa: E402


@register_model("bert_base", latency_class="latency")
def build_bert_base(cfg):
    return make_bert_servable("bert_base", cfg)


@register_model("bert_embed", latency_class="latency")
def build_bert_embed(cfg):
    """Embeddings lane: same encoder, mean-pooled unit vectors out.

    ``replace`` rather than mutating ``cfg.extra`` in place: the caller's
    ModelConfig may be shared (dump_config/stage output would otherwise grow
    a phantom ``embed: true``)."""
    import dataclasses

    cfg = dataclasses.replace(cfg, extra={**cfg.extra, "embed": True})
    return make_bert_servable("bert_embed", cfg)
