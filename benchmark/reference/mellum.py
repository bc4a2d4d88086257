"""Plain Mellum 2 forward pass (``model_type: mellum``): float32
``jax.numpy`` at ``highest`` matmul precision, one sequence at a time, no
cache, no ring, no kernels, no batching; written from the block's equations
(ISSUE 52, from the published ``config.json`` of
JetBrains/Mellum2-12B-A2.5B-Instruct) and importing nothing from the
package.  It reads the staged tree the server boots from, and widens one
layer at a time, so that 7.6 GB of bfloat16 never stand as 15 GB of float32.

Layer ``i``: ``x += attn_i(N(x))``, then ``x += moe_i(N(x))``, ``N(x) = x /
rms(x, eps) * w``; a last ``N`` and the head ``[hidden, vocab]`` (untied).

- Attention, both kinds: ``heads`` queries over ``kv_heads`` K/V heads of
  ``head_dim``; ``q`` and ``k`` normed a head (``N`` over the head's
  columns), then turned by their positions (the two halves of a head
  paired); scores ``q . k / sqrt(head_dim)``, one softmax a query (computed a
  block of queries at a time, so that 5,000 positions fit); query head ``h``
  reads K/V head ``h // (heads / kv_heads)``.
- ``sliding_attention``: the mask is ``0 <= p - j < sliding_window``, a full
  ``[P, P]`` mask and nothing cleverer; the rotation is plain, ``inv_i =
  theta^(-2i/head_dim)``.
- ``full_attention``: causal; the rotation is YaRN's: with ``c(r) = head_dim
  ln(original / (2 pi r)) / (2 ln theta)``, ``low = max(floor(c(beta_fast)),
  0)``, ``high = min(ceil(c(beta_slow)), head_dim - 1)``, ``ramp_i =
  clip((i - low) / (high - low), 0, 1)``: ``inv_i = plain_i / factor *
  ramp_i + plain_i * (1 - ramp_i)``; cosine and sine both times
  ``attention_factor``, at every position.
- Experts, every layer: ``s = softmax(x W_r)`` over all of them; the
  ``top_k`` largest; weights ``s`` there over their sum; expert ``e`` gives
  ``W2_e(silu(W1_e x) * (W3_e x))``.  Every expert is computed over the
  sequence and weighted by what the router gave each row (zero where it was
  not chosen): the sum over the rows routed to it, with no sorting.

Departures from the published model, the configuration's own:

- The tree holds ``experts_held`` experts from ``expert_offset`` (all of
  them in the benchmark's configuration): the router keeps its published
  width and its ``top_k``, and what absent experts would add is left out.
- Matrices are held in bfloat16 by the server; the reference reads the same
  rounded values (and widens them exactly), then computes in float32.
- Assumed (the published ``config.json`` carries none of these): the norms a
  head on q and k before the rotation; the rotation's pairing; softmax over
  all the experts before the choice; pre-norm residual order.  No "MTP
  head": the configuration has no key for one.

Controls, each of which served tokens must fail: ``"int8"`` is the same pass
in the nearest precision below the configuration's (every matrix, the
experts', the embedding and the head too, through symmetric int8 per output
channel and back); ``"window_as_full"`` reads the window layers causally
with no band (what a program with no ring and no band would compute);
``"no_yarn"`` turns the full layers as the window layers are turned (plain
frequencies, no ``attention_factor``).

    PYTHONPATH=. python3 benchmark/reference/mellum.py <checkpoint> <request.json> <out.npz>

computes logits for the request's sequences in a process of its own, on
whatever device JAX finds there.
"""

from __future__ import annotations

import functools
import json
import math
import sys

import numpy as np

from benchmark.reference.gpt2 import load_tree  # the staged file's reader
# What the two references share letter for letter: the RMS norm, the int8
# control's rounding, a gated expert, a configuration's scalars as a key.
from benchmark.reference.lfm2 import _gated, _int8, _norm, scalars_of

QUERY_BLOCK = 512  # queries scored at once
WINDOW, FULL = "sliding_attention", "full_attention"


def yarn_bounds(c: dict) -> tuple[int, int]:
    def pair(r):
        return (c["head_dim"] * math.log(c["yarn_original_positions"]
                                         / (2 * math.pi * r))
                / (2 * math.log(c["rope_theta"])))

    return (max(math.floor(pair(c["yarn_beta_fast"])), 0),
            min(math.ceil(pair(c["yarn_beta_slow"])), c["head_dim"] - 1))


def frequencies(c: dict, yarn: bool) -> np.ndarray:
    """``[head_dim / 2]`` float32: plain, or YaRN's blend."""
    dh = c["head_dim"]
    plain = [c["rope_theta"] ** (-2 * i / dh) for i in range(dh // 2)]
    if not yarn:
        return np.asarray(plain, np.float32)
    low, high = yarn_bounds(c)
    ramp = [min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
            for i in range(dh // 2)]
    return np.asarray([f / c["yarn_factor"] * r + f * (1 - r)
                       for f, r in zip(plain, ramp)], np.float32)


def _turned(x, inv, scale: float):
    """x [n, heads, dh] turned by positions 0..n-1, halves paired, cosine
    and sine times ``scale``."""
    import jax.numpy as jnp

    n, _, dh = x.shape
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, x, c: dict, kind: str, control: str | None = None):
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    H, kv, dh = c["heads"], c["kv_heads"], c["head_dim"]
    yarn = kind == FULL and control != "no_yarn"
    inv = frequencies(c, yarn)
    scale = c["yarn_attention_factor"] if yarn else 1.0
    band = kind == WINDOW and control != "window_as_full"
    q = _norm(p["q_norm"], jnp.dot(x, p["q"]).reshape(n, H, dh),
              c["norm_eps"])
    k = _norm(p["k_norm"], jnp.dot(x, p["k"]).reshape(n, kv, dh),
              c["norm_eps"])
    q = _turned(q, inv, scale).reshape(n, kv, H // kv, dh)
    k = _turned(k, inv, scale)
    v = jnp.dot(x, p["v"]).reshape(n, kv, dh)
    out = []
    for start in range(0, n, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) * dh ** -0.5
        # How far each key lies behind each query.
        behind = (start + jnp.arange(qb.shape[0])[:, None]
                  - jnp.arange(n)[None, :])
        seen = behind >= 0
        if band:
            seen &= behind < c["sliding_window"]
        s = jnp.where(seen, s, -jnp.inf)
        out.append(jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1),
                              v))
    return jnp.dot(jnp.concatenate(out).reshape(n, H * dh), p["o"])


def routing(p, x, c: dict):
    """The published router → weights [n, experts_published], zero where an
    expert was not chosen."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(jnp.dot(x, p["router"]), axis=-1)
    w, chosen = jax.lax.top_k(s, c["top_k"])
    w = w / jnp.sum(w, -1, keepdims=True)
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
    channel, as a head's ``[in, out]`` transposed)."""
    import jax.numpy as jnp

    def one(name, w):
        if np.ndim(w) == 3:
            return jnp.asarray(w)
        w = jnp.asarray(w).astype(jnp.float32)
        if control != "int8" or w.ndim != 2:
            return w
        return _int8(w.T).T if name == "embed" else _int8(w)

    return {k: one(k, w) for k, w in node.items() if not isinstance(w, dict)}


@functools.lru_cache(maxsize=None)
def layer_fn(kind: str, config: tuple, control: str | None = None):
    """One layer of ``kind``, as a jitted function of its widened leaves and
    x [n, D]."""
    import jax

    c = dict(config)

    def layer(p, x):
        h = _norm(p["input_norm"], x, c["norm_eps"])
        x = x + attention(p, h, c, kind, control)
        h = _norm(p["post_attention_norm"], x, c["norm_eps"])
        return x + experts(p, h, c, control)

    return jax.jit(layer)


def forward(tree: dict, ids, config: dict, control: str | None = None,
            keep: int | None = None):
    """Logits [len(ids), vocab_size] at every position, or at the last
    ``keep`` (5,000 positions of 98,304 float32 logits are 2 GB).
    ``config`` holds the keys the equations above name (``layer_types``, the
    widths, the window, the two rotations, the share); the tree holds
    ``layer{i}`` for each of ``layer_types``."""
    import jax
    import jax.numpy as jnp

    scalars = scalars_of(config)
    with jax.default_matmul_precision("highest"):
        top = widened(tree, control)
        x = top["embed"][jnp.asarray(ids)]
        for i, kind in enumerate(config["layer_types"]):
            x = layer_fn(kind, scalars, control)(
                widened(tree[f"layer{i}"], control), x)
        x = x if keep is None else x[-keep:]
        return np.asarray(jnp.dot(_norm(top["norm"], x, config["norm_eps"]),
                                  top["head"]))


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
