"""Plain JoyAI-LLM-Flash forward pass (``model_type: joyai_llm_flash``):
float32 ``jax.numpy`` at ``highest`` matmul precision, one sequence at a time,
no cache, no kernels, no batching, **the non-absorbed form only**; written
from the block's equations (ISSUE 55, from the published ``config.json`` of
jdopensource/JoyAI-LLM-Flash, whose key set is DeepSeek-V3's) and importing
nothing from the package.  It reads the staged tree the server boots from,
and widens one layer at a time, so that 4.5 GB of bfloat16 never stand as 9 GB
of float32.

Layer ``i``: ``x += attn(N(x))``, then ``x += mlp_i(N(x))``, ``N(x) = x /
rms(x, eps) * w``; a last ``N`` and the head ``[hidden, vocab]`` (untied).

- Latent attention, every layer: ``c_q = N(x W_DQ)``; ``q = c_q W_UQ``,
  ``heads`` of ``nope_dim + rope_dim``, each ``[q_nope, q_rope]``; ``[c_raw,
  k_raw] = x W_DKV``; ``c = N(c_raw)``; ``k_rope = rot(k_raw)``, one head
  that every query shares; ``q_rope = rot(q_rope)`` a head; ``k_nope_h = c
  W_UK_h`` and ``v_h = c W_UV_h`` **expanded for every position**; scores
  ``(q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(nope_dim + rope_dim)``,
  causal, one softmax a query (computed a block of queries at a time, so
  that 8,192 positions fit); ``out = concat_h(probs v_h) W_O``.  The
  rotation turns adjacent columns ``(2i, 2i + 1)`` by ``pos * theta^(-2i /
  rope_dim)``; ``rope_scaling`` is null, so no further factor.
- The feed-forward of a layer with no router: ``W2(silu(W1 x) * (W3 x))``.
- With one: ``s = sigmoid(x W_r)`` over all published experts; the ``top_k``
  largest of ``s + expert_bias`` (``n_group`` 1, ``topk_group`` 1: no group
  is shut out); weights ``s`` there over (their sum + 1e-20), times
  ``routed_scale``; expert ``e`` gives ``W2_e(silu(W1_e x) * (W3_e x))``.
  Every held expert is computed over the sequence and weighted by what the
  router gave each row (zero where it was not chosen).  The shared expert,
  the same form, is added for every row with weight 1.

Departures from the published model, the configuration's own:

- The tree holds ``experts_held`` experts from ``expert_offset``: the router
  keeps its published width and its ``top_k``, the shared expert is whole,
  and what absent experts would add is left out, here as in the program.
- Matrices are held in bfloat16 by the server; the reference reads the same
  rounded values (and widens them exactly), then computes in float32.
- Assumed: ``W_UKV`` staged as its two halves ``k_up`` and ``v_up``; the
  rotated columns stored adjacent-paired; the prediction module
  (``num_nextn_predict_layers`` 1) not built.

Controls, each the same pass with one thing changed, which served tokens
must fail: ``"int8"`` (the nearest precision below the configuration's:
every matrix, the experts', the embedding and the head too, through
symmetric int8 per output channel and back), ``"no_rope_score"`` (the score
is ``q_nope . k_nope`` alone), ``"raw_latent"`` (``c`` is ``c_raw``: the
latent without its norm).

    PYTHONPATH=. python3 benchmark/reference/joyai.py <checkpoint> <request.json> <out.npz>

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
CONTROLS = ("int8", "no_rope_score", "raw_latent")


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


def _turned(x, theta: float):
    """x [n, heads, d] turned by positions 0..n-1, columns ``(2i, 2i + 1)``
    paired."""
    import jax.numpy as jnp

    n, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(p, x, c: dict, control: str | None = None):
    """x [n, D] normed → [n, D]: K and V a head expanded from the latent for
    every position, nothing absorbed."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    H, dn, dr = c["heads"], c["nope_dim"], c["rope_dim"]
    rank = c["kv_lora_rank"]
    q = jnp.dot(_norm(p["q_norm"], jnp.dot(x, p["q_down"]), c["norm_eps"]),
                p["q_up"]).reshape(n, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _turned(q[..., dn:], c["rope_theta"])
    down = jnp.dot(x, p["kv_down"])
    latent = down[:, :rank] if control == "raw_latent" \
        else _norm(p["kv_norm"], down[:, :rank], c["norm_eps"])
    k_rope = _turned(down[:, None, rank:], c["rope_theta"])[:, 0]    # [n, dr]
    k_nope = jnp.dot(latent, p["k_up"]).reshape(n, H, dn)
    v = jnp.dot(latent, p["v_up"]).reshape(n, H, -1)
    out = []
    for start in range(0, n, QUERY_BLOCK):
        s = jnp.einsum("qhd,khd->hqk", q_nope[start:start + QUERY_BLOCK],
                       k_nope)
        if control != "no_rope_score":
            s = s + jnp.einsum("qhd,kd->hqk",
                               q_rope[start:start + QUERY_BLOCK], k_rope)
        s = s * (dn + dr) ** -0.5
        seen = (jnp.arange(n)[None, :]
                <= start + jnp.arange(s.shape[1])[:, None])
        s = jnp.where(seen, s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.dot(jnp.concatenate(out).reshape(n, -1), p["o"])


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
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * c["routed_scale"]
    return jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None],
                                chosen].set(w)


def experts(p, x, c: dict, control: str | None = None):
    """x [n, D] normed → [n, D]: the held experts' part of the routed sum
    (``p["w1"]``, ``p["w3"]``, ``p["w2"]`` are experts ``[expert_offset,
    expert_offset + held)``) and the shared expert's whole."""
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
    return acc + _gated(x, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def widened(node, control: str | None = None):
    """A layer's (or the tree's own) leaves in float32, exactly; the
    experts' [held, in, out] stacks stay as staged and are widened an expert
    at a time, inside the loop over them.  Under ``"int8"`` every
    projection matrix goes through int8 and back (the embedding a row a
    channel)."""
    import jax.numpy as jnp

    def one(name, w):
        if np.ndim(w) == 3:
            return jnp.asarray(w)
        w = jnp.asarray(w).astype(jnp.float32)
        if control != "int8" or w.ndim != 2:
            return w
        return _int8(w.T).T if name == "embed" else _int8(w)

    return {k: one(k, w) for k, w in node.items() if not isinstance(w, dict)}


def scalars_of(config: dict) -> tuple:
    return tuple(sorted(config.items()))


@functools.lru_cache(maxsize=None)
def layer_fn(routed: bool, config: tuple, control: str | None = None):
    """One layer with a routed or a dense feed-forward, as a jitted function
    of its widened leaves and x [n, D]."""
    import jax

    c = dict(config)

    def layer(p, x):
        h = _norm(p["input_norm"], x, c["norm_eps"])
        x = x + attention(p, h, c, control)
        h = _norm(p["post_attention_norm"], x, c["norm_eps"])
        if routed:
            return x + experts(p, h, c, control)
        return x + _gated(h, p["w1"], p["w3"], p["w2"])

    return jax.jit(layer)


def forward(tree: dict, ids, config: dict, control: str | None = None,
            keep: int | None = None):
    """Logits [len(ids), vocab_size] at every position, or at the last
    ``keep`` (8,192 positions of 129,280 float32 logits are 4.2 GB).
    ``config`` holds the keys the equations above name (``layers``,
    ``dense_layers``, the widths, the share); the tree holds ``layer{i}``
    for each of ``layers``."""
    import jax
    import jax.numpy as jnp

    scalars = scalars_of(config)
    with jax.default_matmul_precision("highest"):
        top = widened(tree, control)
        x = top["embed"][jnp.asarray(ids)]
        for i in range(config["layers"]):
            x = layer_fn(i >= config["dense_layers"], scalars, control)(
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
