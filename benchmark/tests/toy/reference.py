"""The toy decoder, plainly: numpy, float32, one sequence at a time, no
cache.  Pre-norm blocks of causal attention and a tanh-GELU feed-forward
over learned positions, the output head tied to the embedding.  It reads the
staged tree and imports nothing of the program."""

from __future__ import annotations

import math

import numpy as np


def load(path) -> dict:
    """The staged file as ``{"a/b/c": float32 array}``."""
    import ml_dtypes  # noqa: F401  (names bfloat16 to numpy)
    from safetensors.numpy import load_file

    return {k: v.astype(np.float32) for k, v in load_file(str(path)).items()}


def logits(w: dict, ids: list[int], layer_types: list[str], heads: int,
           eps: float) -> np.ndarray:
    """``[len(ids), vocab]``."""
    def norm(name, x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / np.sqrt(var + eps) * w[f"{name}/scale"] \
            + w[f"{name}/bias"]

    def dense(name, x):
        return x @ w[f"{name}/kernel"] + w[f"{name}/bias"]

    n = len(ids)
    x = w["wte"][ids] + w["wpe"][:n]
    causal = np.tril(np.ones((n, n), bool))
    for i, kind in enumerate(layer_types):
        if kind != "full_attention":
            raise ValueError(f"the toy has no {kind!r} layer")
        h = norm(f"layer{i}/ln1", x)
        q, k, v = (dense(f"layer{i}/{p}", h).reshape(n, heads, -1)
                   for p in "qkv")
        s = np.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
        s = np.where(causal[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        a = np.einsum("hts,shd->thd", p / p.sum(-1, keepdims=True), v)
        x = x + dense(f"layer{i}/out", a.reshape(n, -1))
        h = dense(f"layer{i}/fc1", norm(f"layer{i}/ln2", x))
        h = 0.5 * h * (1.0 + np.tanh(math.sqrt(2.0 / math.pi)
                                     * (h + 0.044715 * h ** 3)))
        x = x + dense(f"layer{i}/fc2", h)
    return norm("ln_f", x) @ w["wte"].T
