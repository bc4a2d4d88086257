"""Plain Nemotron-H forward pass: float32 ``jax.numpy`` at ``highest`` matmul
precision, one sequence at a time, no cache, no kernels, no batching;
written from the layer's equations (ISSUE 45, from the published
``config.json`` of NVIDIA-Nemotron-3-Super-120B-A12B-BF16, ``model_type:
nemotron_h``) and importing nothing from the package.  It reads the staged
tree the server boots from, and widens one layer at a time, so that 9.3 GB
of bfloat16 never stand as 18.6 GB of float32.

``pattern`` says each layer's mixer; every layer is ``x + mixer(N(x))``,
``N(x) = x / rms(x, eps) * w``; a final ``N`` and an untied head.

- ``M`` (Mamba-2): ``[z | xBC | dt] = x W_in``; ``xBC = silu(conv(xBC) +
  b)``, causal, depthwise, ``conv_kernel`` wide; ``xBC = [x | B | C]`` with
  ``x`` as ``mamba_heads`` heads of ``mamba_head_dim`` and ``B``, ``C`` as
  ``n_groups`` groups of ``ssm_state`` (a group's heads share them); ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``.  The recurrence runs a
  position at a time: ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t =
  h_t C_t + D x_t``.  Then ``N_groups(y * silu(z))`` over ``n_groups`` equal
  parts, and ``W_out``.
- ``*`` (attention): ``heads`` queries over ``kv_heads`` K/V heads of
  ``head_dim``, causal, scale ``head_dim ** -0.5``, one softmax over [n, n];
  no bias, no rotation.
- ``E`` (experts): ``s = sigmoid(x W_g)``; the ``top_k`` largest of ``s +
  bias``; weights ``s`` there over their sum, times ``routed_scale``; ``u =
  x W_down``; expert ``e`` gives ``W2_e relu(W1_e u)^2``; the weighted sum
  goes through ``W_up``; plus the shared expert ``V2 relu(V1 x)^2``.

Departures from the published model, the configuration's own:

- **The share.**  Of ``experts_published`` experts the tree holds
  ``experts_held``, from ``expert_offset``: the router keeps its published
  width and its ``top_k``, and the sum runs over the assignments that fall
  on a held expert (a dense masked sum over the held range, no sorting).
  What the absent experts would add is left out.  ``vocab_size`` is the
  slice of the vocabulary the tree holds.
- Matrices are held in bfloat16 by the server; the reference reads the same
  rounded values (and widens them exactly), then computes in float32.
- Assumed: no rotation in the attention layer; no clamp on ``dt``; the
  latent projections have no bias or activation.  The multi-token-prediction
  module is no layer of the pattern and is not computed.

``control="int8"`` is the same pass in the nearest precision below the
configuration's, which served tokens must fail: every matrix, the experts'
too, through symmetric int8 per output channel and back.

    PYTHONPATH=. python3 benchmark/reference/nemotron_h.py <checkpoint> <request.json> <out.npz>

computes logits for the request's sequences in a process of its own, on
whatever device JAX finds there.
"""

from __future__ import annotations

import functools
import json
import sys

import numpy as np

from benchmark.reference.gpt2 import load_tree  # the staged file's reader


def _norm(w, x, eps, groups: int = 1):
    import jax
    import jax.numpy as jnp

    parts = x.reshape(*x.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, -1, keepdims=True)
                                  + eps)
    return parts.reshape(x.shape) * w


def _int8(w):
    """w [..., in, out] through symmetric int8 per output channel and back."""
    import jax.numpy as jnp

    absmax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0.0))


def mamba(p, x, c: dict):
    """x [n, D] normed → [n, D]."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    H, P, N, G, K = (c["mamba_heads"], c["mamba_head_dim"], c["ssm_state"],
                     c["n_groups"], c["conv_kernel"])
    inner = H * P
    z, xbc, dt = jnp.split(jnp.dot(x, p["in_proj"]),
                           [inner, 2 * inner + 2 * G * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(padded[j:j + n] * p["conv_w"][j] for j in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[:, :inner].reshape(n, H, P)
    # A group's heads share its B and C.
    B = jnp.repeat(xbc[:, inner:inner + G * N].reshape(n, G, N), H // G, 1)
    C = jnp.repeat(xbc[:, inner + G * N:].reshape(n, G, N), H // G, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                       # [n, H]
    A = -jnp.exp(p["A_log"])

    def step(h, at):
        x_t, B_t, C_t, dt_t = at
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return h, jnp.sum(h * C_t[:, None, :], -1) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (xs, B, C, dt))
    y = y.reshape(n, inner) * jax.nn.silu(z)
    return jnp.dot(_norm(p["gnorm"], y, c["norm_eps"], G), p["out_proj"])


def attention(p, x, c: dict):
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    H, kv, dh = c["heads"], c["kv_heads"], c["head_dim"]
    q = jnp.dot(x, p["q"]).reshape(n, kv, H // kv, dh)
    k = jnp.dot(x, p["k"]).reshape(n, kv, dh)
    v = jnp.dot(x, p["v"]).reshape(n, kv, dh)
    s = jnp.einsum("qhgd,khd->hgqk", q, k) * dh ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    a = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v)
    return jnp.dot(a.reshape(n, H * dh), p["o"])


def routing(p, x, c: dict):
    """The published router → weights [n, experts_published], zero where an
    expert was not chosen."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(jnp.dot(x, p["router"]))
    _, chosen = jax.lax.top_k(s + p["router_bias"], c["top_k"])
    w = jnp.take_along_axis(s, chosen, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * c["routed_scale"]
    return jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None],
                                chosen].set(w)


def experts(p, x, c: dict, control: str | None = None, shared: bool = True):
    """x [n, D] normed → [n, D]: the held experts' part of the routed sum
    (``p["w1"]``, ``p["w2"]`` are experts ``[expert_offset, expert_offset +
    held)``), through ``W_up``, and the shared expert."""
    import jax
    import jax.numpy as jnp

    held = p["w1"].shape[0]
    mine = routing(p, x, c)[:, c["expert_offset"]:c["expert_offset"] + held]
    u = jnp.dot(x, p["down"])

    def one(acc, e):
        w1, w2, weight = e  # an expert's matrices, widened as they are met
        w1, w2 = w1.astype(jnp.float32), w2.astype(jnp.float32)
        if control == "int8":
            w1, w2 = _int8(w1), _int8(w2)
        return acc + weight[:, None] * jnp.dot(_relu2(jnp.dot(u, w1)),
                                               w2), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(u), (p["w1"], p["w2"], mine.T))
    y = jnp.dot(acc, p["up"])
    if shared:
        y = y + jnp.dot(_relu2(jnp.dot(x, p["s1"])), p["s2"])
    return y


def widened(node, control: str | None = None):
    """A layer's (or the tree's own) leaves in float32, exactly; the
    experts' [held, in, out] stacks stay as staged and are widened an expert
    at a time, inside the loop over them.  Under ``"int8"`` every
    projection matrix goes through int8 and back."""
    import jax.numpy as jnp

    def one(name, w):
        if np.ndim(w) == 3:
            return jnp.asarray(w)
        w = jnp.asarray(w).astype(jnp.float32)
        return _int8(w) if (control == "int8" and w.ndim == 2
                            and name != "conv_w") else w

    return {k: one(k, w) for k, w in node.items() if not isinstance(w, dict)}


def scalars_of(config: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in config.items() if k != "pattern"))


@functools.lru_cache(maxsize=None)
def layer_fn(kind: str, config: tuple, control: str | None = None):
    """One layer of ``kind`` as a jitted function of its widened leaves and
    x [n, D]."""
    import jax

    c = dict(config)

    def layer(p, x):
        h = _norm(p["norm"], x, c["norm_eps"])
        if kind == "M":
            return x + mamba(p, h, c)
        if kind == "E":
            return x + experts(p, h, c, control)
        return x + attention(p, h, c)

    return jax.jit(layer)


def forward(tree: dict, ids, config: dict, control: str | None = None):
    """Logits [len(ids), vocab_size] at every position.  ``config`` holds
    the keys the equations above name (``pattern``, the widths, the share);
    the tree holds ``layer{i}`` for every character of ``pattern``."""
    import jax
    import jax.numpy as jnp

    scalars = scalars_of(config)
    with jax.default_matmul_precision("highest"):
        top = widened(tree, control)
        x = top["embed"][jnp.asarray(ids)]
        for i, kind in enumerate(config["pattern"]):
            x = layer_fn(kind, scalars, control)(
                widened(tree[f"layer{i}"], control), x)
        return np.asarray(jnp.dot(_norm(top["norm"], x, config["norm_eps"]),
                                  top["head"]))


def main(argv: list[str]) -> int:
    """Logits for every sequence of a request file, written as a ``.npz``
    beside a note of the device they were computed on."""
    import jax

    ckpt, request, out = argv
    req = json.loads(open(request).read())
    tree = load_tree(ckpt)
    logits = [forward(tree, ids, req["config"], req.get("control"))
              for ids in req["sequences"]]
    np.savez(out, *logits)
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "sequences": len(logits)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
