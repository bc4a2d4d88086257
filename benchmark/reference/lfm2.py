"""Plain LFM2 forward pass (``model_type: lfm2_moe``): float32 ``jax.numpy``
at ``highest`` matmul precision, one sequence at a time, no cache, no
kernels, no batching; written from the block's equations (ISSUE 48, from the
published ``config.json`` of LiquidAI/LFM2-24B-A2B) and importing nothing
from the package.  It reads the staged tree the server boots from, and
widens one layer at a time, so that 10.5 GB of bfloat16 never stand as 21 GB
of float32.

Layer ``i``: ``x += op_i(N(x))``, then ``x += ffn_i(N(x))``, ``N(x) = x /
rms(x, eps) * w``; a last ``N`` and the head, the embedding transposed.

- ``conv``: ``[B | C | z] = x W_in`` (three parts of the hidden size); ``u =
  B * z``; ``c_t = sum_j w[j] u_{t - (L - 1) + j}`` a channel, ``L =
  conv_kernel``, zeros before position 0: the convolution as ``L`` shifted
  products; ``y = (C * c) W_out``.  No bias, no activation.
- ``full_attention``: ``heads`` queries over ``kv_heads`` K/V heads of
  ``head_dim``; ``q`` and ``k`` normed a head (``N`` over the head's
  columns), then turned by their positions (``rope_theta``, the two halves
  of a head paired); scores ``q . k / sqrt(head_dim)``, causal, one softmax
  a query (computed a block of queries at a time, so that 3,000 positions
  fit); query head ``h`` reads K/V head ``h // (heads / kv_heads)``.
- The feed-forward of a layer with no router: ``W2(silu(W1 x) * (W3 x))``.
- With one: ``s = sigmoid(x W_r)``; the ``top_k`` largest of ``s +
  expert_bias``; weights ``s`` there over (their sum + 1e-6), times
  ``routed_scale``; expert ``e`` gives ``W2_e(silu(W1_e x) * (W3_e x))``.
  Every expert is computed over the sequence and weighted by what the
  router gave each row (zero where it was not chosen): the sum over the
  rows routed to it, with no sorting.

Departures from the published model, the configuration's own:

- The tree holds ``experts_held`` experts from ``expert_offset`` (all of
  them in the benchmark's configuration): the router keeps its published
  width and its ``top_k``, and what absent experts would add is left out.
- Matrices are held in bfloat16 by the server; the reference reads the same
  rounded values (and widens them exactly), then computes in float32.
- Assumed: the tied head; the norms a head before the rotation and the
  rotation's pairing; the convolution's two gates with no activation.

``control="int8"`` is the same pass in the nearest precision below the
configuration's, which served tokens must fail: every matrix, the experts'
and the embedding (the head) too, through symmetric int8 per output channel
and back.

    PYTHONPATH=. python3 benchmark/reference/lfm2.py <checkpoint> <request.json> <out.npz>

computes logits for the request's sequences in a process of its own, on
whatever device JAX finds there.
"""

from __future__ import annotations

import functools
import json
import sys

import numpy as np

from benchmark.reference.gpt2 import load_tree  # the staged file's reader

QUERY_BLOCK = 512  # queries scored at once


def _norm(w, x, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _int8(w):
    """w [..., in, out] through symmetric int8 per output channel and back."""
    import jax.numpy as jnp

    absmax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def conv(p, x, c: dict):
    """x [n, D] normed → [n, D]."""
    import jax.numpy as jnp

    n, D = x.shape
    L = c["conv_kernel"]
    B, C, z = jnp.split(jnp.dot(x, p["in_proj"]), 3, axis=-1)
    u = jnp.concatenate([jnp.zeros((L - 1, D)), B * z])
    mixed = sum(u[j:j + n] * p["conv_w"][j] for j in range(L))
    return jnp.dot(C * mixed, p["out_proj"])


def _turned(x, theta: float):
    """x [n, heads, dh] turned by positions 0..n-1, halves paired."""
    import jax.numpy as jnp

    n, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, x, c: dict):
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    H, kv, dh = c["heads"], c["kv_heads"], c["head_dim"]
    q = _norm(p["q_norm"], jnp.dot(x, p["q"]).reshape(n, H, dh),
              c["norm_eps"])
    k = _norm(p["k_norm"], jnp.dot(x, p["k"]).reshape(n, kv, dh),
              c["norm_eps"])
    q = _turned(q, c["rope_theta"]).reshape(n, kv, H // kv, dh)
    k = _turned(k, c["rope_theta"])
    v = jnp.dot(x, p["v"]).reshape(n, kv, dh)
    out = []
    for start in range(0, n, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) * dh ** -0.5
        seen = (jnp.arange(n)[None, :]
                <= start + jnp.arange(qb.shape[0])[:, None])
        s = jnp.where(seen, s, -jnp.inf)
        out.append(jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1),
                              v))
    return jnp.dot(jnp.concatenate(out).reshape(n, H * dh), p["o"])


def _gated(x, w1, w3, w2):
    import jax
    import jax.numpy as jnp

    return jnp.dot(jax.nn.silu(jnp.dot(x, w1)) * jnp.dot(x, w3), w2)


def routing(p, x, c: dict):
    """The published router → weights [n, experts_published], zero where an
    expert was not chosen."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.dot(x, p["router"]))
    _, chosen = jax.lax.top_k(s + p["expert_bias"], c["top_k"])
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6) * c["routed_scale"]
    return jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None],
                                chosen].set(w)


def experts(p, x, c: dict, control: str | None = None):
    """x [n, D] normed → [n, D]: the held experts' part of the routed sum
    (``p["w1"]``, ``p["w3"]``, ``p["w2"]`` are experts ``[expert_offset,
    expert_offset + held)``)."""
    import jax
    import jax.numpy as jnp

    held = p["w1"].shape[0]
    mine = routing(p, x, c)[:, c["expert_offset"]:c["expert_offset"] + held]

    def one(acc, e):
        *mats, weight = e  # an expert's matrices, widened as they are met
        mats = [m.astype(jnp.float32) for m in mats]
        if control == "int8":
            mats = [_int8(m) for m in mats]
        return acc + weight[:, None] * _gated(x, *mats), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (p["w1"], p["w3"], p["w2"], mine.T))
    return acc


def widened(node, control: str | None = None):
    """A layer's (or the tree's own) leaves in float32, exactly; the
    experts' [held, in, out] stacks stay as staged and are widened an expert
    at a time, inside the loop over them.  Under ``"int8"`` every
    projection matrix goes through int8 and back (the embedding a row a
    channel: it is the head's ``[in, out]`` transposed)."""
    import jax.numpy as jnp

    def one(name, w):
        if np.ndim(w) == 3:
            return jnp.asarray(w)
        w = jnp.asarray(w).astype(jnp.float32)
        if control != "int8" or w.ndim != 2 or name == "conv_w":
            return w
        return _int8(w.T).T if name == "embed" else _int8(w)

    return {k: one(k, w) for k, w in node.items() if not isinstance(w, dict)}


def scalars_of(config: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in config.items()
                        if k != "layer_types"))


@functools.lru_cache(maxsize=None)
def layer_fn(kind: str, routed: bool, config: tuple,
             control: str | None = None):
    """One layer with operator ``kind`` and a routed or a dense feed-forward,
    as a jitted function of its widened leaves and x [n, D]."""
    import jax

    c = dict(config)

    def layer(p, x):
        h = _norm(p["operator_norm"], x, c["norm_eps"])
        x = x + (conv(p, h, c) if kind == "conv" else attention(p, h, c))
        h = _norm(p["ffn_norm"], x, c["norm_eps"])
        if routed:
            return x + experts(p, h, c, control)
        return x + _gated(h, p["w1"], p["w3"], p["w2"])

    return jax.jit(layer)


def forward(tree: dict, ids, config: dict, control: str | None = None,
            keep: int | None = None):
    """Logits [len(ids), vocab_size] at every position, or at the last
    ``keep`` (3,000 positions of 65,536 float32 logits are 0.8 GB).
    ``config`` holds the keys the equations above name (``layer_types``,
    ``dense_layers``, the widths, the share); the tree holds ``layer{i}``
    for each of ``layer_types``."""
    import jax
    import jax.numpy as jnp

    scalars = scalars_of(config)
    with jax.default_matmul_precision("highest"):
        top = widened(tree, control)
        x = top["embed"][jnp.asarray(ids)]
        for i, kind in enumerate(config["layer_types"]):
            x = layer_fn(kind, i >= config["dense_layers"], scalars, control)(
                widened(tree[f"layer{i}"], control), x)
        x = x if keep is None else x[-keep:]
        return np.asarray(jnp.dot(_norm(top["norm"], x, config["norm_eps"]),
                                  top["embed"].T))


def main(argv: list[str]) -> int:
    """Logits for every sequence of a request file, written as a ``.npz``
    beside a note of the device they were computed on."""
    import jax

    ckpt, request, out = argv
    req = json.loads(open(request).read())
    tree = load_tree(ckpt)
    logits = [forward(tree, ids, req["config"], req.get("control"),
                      req.get("keep"))
              for ids in req["sequences"]]
    np.savez(out, *logits)
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "sequences": len(logits)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
