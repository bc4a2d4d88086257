"""Plain EvaByte forward pass: float32 ``jax.numpy`` at ``highest`` matmul
precision, the whole sequence at once, no cache, no kernels, no batching;
written from the layer's equations (ISSUE 35, from the published
``config.json`` and ``eva_pt_ref.py``) and importing nothing from the
package.  It reads the staged tree the server boots from.

Per head (``dh`` wide, ``s = dh ** -0.5``, window ``W``, chunk ``c``):

- Block: ``h = x + Attn(N(x))``, ``y = h + W_down(silu(W_gate n) * W_up n)``
  with ``n = N(h)`` and ``N(x) = x / rms(x, eps) * (1 + w)``; no biases.
- ``q_t``, ``k_t`` turned by RoPE at position ``t`` over all of ``dh``;
  ``v_t`` as projected.
- Chunk ``j`` holds positions ``[c j, c j + c)``; its summary is ``kbar_j =
  sum_i softmax_i(mu . k_i) k_i`` and ``vbar_j = sum_i softmax_i(phi . k_i -
  |k_i|^2 / 2) v_i`` over its positions, the rotated keys.
- Query ``t`` in window ``w = t // W`` attends ``(k_i, v_i)`` for ``W w <= i
  <= t`` and ``(kbar_j, vbar_j)`` for ``j < (W / c) w`` in one softmax over
  ``s q_t . k``.
- Logits from prediction head 0: the head's first ``vocab_size`` columns.

Assumed, as the configuration's file lists them: RoPE in the half-rotation
layout; no scale on ``mu . k_i``; none inside ``phi . k_i - |k_i|^2 / 2``.
The three ``controls`` leave part of the mathematics out, each a way the
program could be wrong and stay plausible: a test (and the check's own) sees
that each moves the logits by more than the tolerance.

Departure from the published model, the configuration's own: matrices are
held in bfloat16 by the server; the reference reads the same rounded values
(and widens them exactly), then computes in float32.  ``int8=True`` is the
control in the nearest precision below: every matrix through symmetric int8
per output channel and back.

    PYTHONPATH=. python3 benchmark/reference/evabyte.py <checkpoint> <request.json> <out.npz>

computes logits for the request's sequences in a process of its own, on
whatever device JAX finds there (the family's ``check`` sends the real
widths to the chip once the server has left it).
"""

from __future__ import annotations

import json
import sys

import numpy as np

from benchmark.reference.gpt2 import load_tree  # the staged file's reader

BLOCK_Q = 512  # queries scored at a time, so that the scores fit


def _matrix(w, int8: bool):
    """A staged matrix [in, out] in float32, exactly; or through symmetric
    int8 per output channel and back."""
    import jax.numpy as jnp

    w = jnp.asarray(w).astype(jnp.float32)
    if not int8:
        return w
    absmax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _norm(w, x, eps):
    import jax
    import jax.numpy as jnp

    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + jnp.asarray(w, jnp.float32)))


def _rope(x, theta):
    """x [n, H, dh] at positions 0..n-1, the half-rotation layout."""
    import jax.numpy as jnp

    n, _, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv        # [n, dh/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v, mu, phi, W: int, c: int, control: str | None):
    """q, k (rotated), v [n, H, dh]; mu, phi [H, dh] → [n, H, dh]."""
    import jax
    import jax.numpy as jnp

    n, H, dh = q.shape
    nC = -(-n // c)
    pad = nC * c - n
    kp = jnp.pad(k, ((0, pad), (0, 0), (0, 0))).reshape(nC, c, H, dh)
    vp = jnp.pad(v, ((0, pad), (0, 0), (0, 0))).reshape(nC, c, H, dh)
    real = (jnp.arange(nC * c) < n).reshape(nC, c, 1)
    a = jnp.sum(kp * mu, -1)
    b = jnp.sum(kp * phi, -1) - 0.5 * jnp.sum(kp * kp, -1)
    if control == "uniform_pooling":
        a, b = jnp.zeros_like(a), jnp.zeros_like(b)
    wk = jax.nn.softmax(jnp.where(real, a, -jnp.inf), axis=1)[..., None]
    wv = jax.nn.softmax(jnp.where(real, b, -jnp.inf), axis=1)[..., None]
    kbar, vbar = jnp.sum(wk * kp, 1), jnp.sum(wv * vp, 1)        # [nC, H, dh]

    chunk = jnp.arange(nC)
    keys = jnp.arange(n)
    outs = []
    for lo in range(0, n, BLOCK_Q):
        t = jnp.arange(lo, min(lo + BLOCK_Q, n))
        qb = q[lo:lo + BLOCK_Q] * dh ** -0.5
        w = t // W
        exact = jnp.einsum("qhd,khd->hqk", qb, k, precision="highest")
        see = (keys[None, :] >= (W * w)[:, None]) & (keys[None, :]
                                                     <= t[:, None])
        exact = jnp.where(see[None], exact, -jnp.inf)
        past = jnp.einsum("qhd,jhd->hqj", qb, kbar, precision="highest")
        done = chunk[None, :] < ((W // c) * w)[:, None]
        if control == "no_summaries":
            done = jnp.zeros_like(done)
        elif control == "own_window_chunks":
            # Every finished chunk, those of the query's own window too.
            done = (chunk[None, :] + 1) * c <= t[:, None] + 1
        past = jnp.where(done[None], past, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([past, exact], -1), axis=-1)
        outs.append(
            jnp.einsum("hqj,jhd->qhd", p[..., :nC], vbar, precision="highest")
            + jnp.einsum("hqk,khd->qhd", p[..., nC:], v, precision="highest"))
    return jnp.concatenate(outs, 0)


def forward(tree: dict, ids, config: dict, layers: int | None = None,
            int8: bool = False, control: str | None = None):
    """Logits [len(ids), vocab_size] of prediction head 0 at every position.
    ``config`` holds the published keys (``num_attention_heads``,
    ``window_size``, ``chunk_size``, ``rope_theta``, ``rms_norm_eps``,
    ``vocab_size``); ``layers`` how many of the tree's to run (all)."""
    import jax
    import jax.numpy as jnp

    H = int(config["num_attention_heads"])
    W, c = int(config["window_size"]), int(config["chunk_size"])
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    V = int(config["vocab_size"])
    layers = layers if layers is not None else sum(
        1 for k in tree if k.startswith("layer"))
    n = len(ids)

    @jax.jit
    def layer(p, x):
        D = x.shape[-1]
        dh = D // H
        h = _norm(p["n1"], x, eps)
        q, k, v = (jnp.dot(h, p[m], precision="highest").reshape(n, H, dh)
                   for m in "qkv")
        att = _attention(_rope(q, theta), _rope(k, theta), v,
                         p["mu"].reshape(H, dh), p["phi"].reshape(H, dh),
                         W, c, control)
        x = x + jnp.dot(att.reshape(n, D), p["o"], precision="highest")
        m = _norm(p["n2"], x, eps)
        gate = jax.nn.silu(jnp.dot(m, p["gate"], precision="highest"))
        up = jnp.dot(m, p["up"], precision="highest")
        return x + jnp.dot(gate * up, p["down"], precision="highest")

    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(tree["embed"]).astype(jnp.float32)[jnp.asarray(ids)]
        for i in range(layers):
            raw = tree[f"layer{i}"]
            p = {k: (_matrix(w, int8) if np.ndim(w) == 2
                     else jnp.asarray(w, jnp.float32))
                 for k, w in raw.items()}
            x = layer(p, x)
        x = _norm(tree["norm"], x, eps)
        head = _matrix(np.asarray(tree["head"])[:, :V], int8)
        return np.asarray(jnp.dot(x, head, precision="highest"))


def main(argv: list[str]) -> int:
    """Logits for every sequence of a request file, written as a ``.npz``
    beside a note of the device they were computed on."""
    import jax

    ckpt, request, out = argv
    req = json.loads(open(request).read())
    tree = load_tree(ckpt)
    logits = [forward(tree, ids, req["config"], req.get("layers"),
                      bool(req.get("int8")), req.get("control"))
              for ids in req["sequences"]]
    np.savez(out, *logits)
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "sequences": len(logits)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
