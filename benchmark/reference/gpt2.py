"""Plain GPT-2 forward pass: float32 ``jax.numpy``, no cache, no batching, no
kernels; written from the published description (Radford et al. 2019 and the
``GPT2LMHeadModel`` source the configurations name) and independent of
``models/gpt2.py``.  It reads the staged tree the server boots from.

Departures from the published model, each the configuration's own:
- W8A16 (``params_dtype: int8``): every layer matrix and the output head are
  held as symmetric int8 per output channel; the reference is given the same
  quantized weights, dequantized, and computes in float32.
- Embedding tables are held in bfloat16 by both lanes; the reference reads
  the same rounded values.
"""

from __future__ import annotations

import math

import numpy as np


def load_tree(path) -> dict:
    """The staged ``*.tpu.safetensors`` as a nested dict of arrays, each in
    the type it was staged in."""
    import ml_dtypes  # noqa: F401  (names bfloat16 to numpy)
    from safetensors.numpy import load_file

    tree: dict = {}
    for key, value in load_file(str(path)).items():
        node = tree
        *parents, leaf = key.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = value
    return tree


def f32(w):
    """Exact float32 of a staged array (float32 or bfloat16), on the host's
    own XLA device so that the widening runs on every core."""
    import jax.numpy as jnp

    return jnp.asarray(w).astype(jnp.float32)


def bf16_rounded(w):
    """``w`` as the server holds a bfloat16 matrix, in float32."""
    import jax.numpy as jnp

    return jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)


def dequantized(w):
    """``w`` [in, out] through symmetric int8 per output channel and back."""
    import jax.numpy as jnp

    w = f32(w)
    absmax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def prepare(tree: dict, n_layer: int, quantized: bool) -> dict:
    """The weights as the configuration holds them, in float32."""
    mat = dequantized if quantized else bf16_rounded
    wte = bf16_rounded(tree["wte"])
    out = {"wte": wte, "wpe": bf16_rounded(tree["wpe"]), "ln_f": tree["ln_f"],
           "head": dequantized(f32(tree["wte"]).T) if quantized else wte.T}
    for i in range(n_layer):
        lp = tree[f"layer{i}"]
        out[f"layer{i}"] = {
            k: ({"kernel": mat(v["kernel"]), "bias": v["bias"]}
                if "kernel" in v else v) for k, v in lp.items()}
    return out


def forward(weights: dict, ids, n_layer: int, n_head: int,
            eps: float) -> np.ndarray:
    """Logits [T, vocab] of one sequence, every position attending to itself
    and all before it."""
    import jax
    import jax.numpy as jnp

    def ln(p, x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]

    def dense(p, x):
        return x @ p["kernel"] + p["bias"]

    def gelu_new(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        T = ids.shape[0]
        x = jnp.asarray(weights["wte"])[ids] + jnp.asarray(weights["wpe"])[:T]
        causal = jnp.tril(jnp.ones((T, T), bool))
        for i in range(n_layer):
            lp = weights[f"layer{i}"]
            h = ln(lp["ln1"], x)
            q, k, v = (dense(lp[n], h).reshape(T, n_head, -1)
                       for n in ("q", "k", "v"))
            s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
            s = jnp.where(causal[None], s, -jnp.inf)
            a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
            x = x + dense(lp["out"], a.reshape(T, -1))
            h = ln(lp["ln2"], x)
            x = x + dense(lp["fc2"], gelu_new(dense(lp["fc1"], h)))
        return np.asarray(ln(weights["ln_f"], x) @ weights["head"])
