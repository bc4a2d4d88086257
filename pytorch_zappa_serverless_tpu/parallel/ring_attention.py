"""Ring attention — sequence-parallel attention over a device mesh axis.

The reference has no long-context machinery at all (SURVEY §5: max sequence
in its mandate is BERT-128; no CP/SP/ring anywhere).  This module is the
mesh-general long-context capability the TPU framework carries anyway: when a
sequence is too long for one chip's HBM (or one chip's attention FLOPs), the
sequence dimension is sharded over a mesh axis and attention runs as a ring —
each device holds its Q shard resident and streams the K/V shards around the
ring with ``jax.lax.ppermute`` (XLA lowers the rotation to ICI
neighbour-to-neighbour RDMA, so the collective rides the torus, never the
host), combining partial results with the same online-softmax algebra as the
Pallas flash kernel (ops/flash_attention.py) uses within a chip:

    ring step s: device d holds K/V chunk (d - s) mod n
      m_new = max(m, rowmax(S_s));  alpha = exp(m - m_new)
      l     = alpha*l + rowsum(exp(S_s - m_new))
      acc   = alpha*acc + exp(S_s - m_new) @ V_s

After n steps every Q row has seen every K/V chunk exactly once; the rotation
runs at loop *entry* for steps 1..n-1, so only n-1 ICI hops are issued (the
n-th would only rotate buffers nobody reads again).  Memory per device is
O(T/n * T/n) for the score block — the quadratic term divides by n^2.

Causality is handled with *global* positions (shard index × shard length +
local offset), so the result is bit-identical in structure to single-device
causal attention; fully-future chunks still circulate (the ring is a fixed
permutation) but their contribution is masked to -1e9 like every other
implementation in this package.

``ring_attention`` is the ``shard_map`` wrapper (host API, takes a Mesh);
``ring_attention_local`` is the per-device body for callers already inside a
``shard_map``.  Both are exercised on the 8-device CPU mesh in
tests/test_ring_attention.py exactly as the driver's multi-chip dry run does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e9


def ring_attention_local(q, k, v, kv_mask=None, *, axis_name: str,
                         causal: bool = False, sm_scale: float | None = None):
    """Per-device ring attention body; call inside shard_map.

    q [B, Tq_loc, H, D], k/v [B, Tk_loc, H, D] — the local shards of
    sequence-sharded arrays; kv_mask optional [B, Tk_loc] bool (True=attend).
    Returns the local output shard [B, Tq_loc, H, D] in q.dtype.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, tq, H, D = q.shape
    tk = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    q32 = q.astype(jnp.float32)
    qpos = idx * tq + jnp.arange(tq)                       # global query rows
    perm = [(i, (i + 1) % n) for i in range(n)]

    if kv_mask is None:
        kv_mask = jnp.ones((B, tk), bool)

    def attend(s, k_c, v_c, mask_c, m, l, acc):
        chunk = (idx - s) % n                              # whose K/V we hold
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32,
                            k_c.astype(jnp.float32)) * scale
        scores = jnp.where(mask_c[:, None, None, :], scores, _NEG_INF)
        if causal:
            kpos = chunk * tk + jnp.arange(tk)             # global key cols
            scores = jnp.where(qpos[None, None, :, None] >= kpos[None, None, None, :],
                               scores, _NEG_INF)
        m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l = alpha * l + p.sum(-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_c.astype(jnp.float32))
        return m_new, l, acc

    def body(s, carry):
        # Rotate at loop entry: step s consumes the chunk rotated s times, and
        # the final step issues no dead rotation (n-1 ICI hops total).
        k_c, v_c, mask_c, m, l, acc = carry
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        mask_c = jax.lax.ppermute(mask_c, axis_name, perm)
        m, l, acc = attend(s, k_c, v_c, mask_c, m, l, acc)
        return k_c, v_c, mask_c, m, l, acc

    m0 = jnp.full((B, H, tq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, tq, 1), jnp.float32)
    acc0 = jnp.zeros((B, H, tq, D), jnp.float32)
    m, l, acc = attend(0, k, v, kv_mask, m0, l0, acc0)   # home chunk, no hop
    *_, m, l, acc = jax.lax.fori_loop(
        1, n, body, (k, v, kv_mask, m, l, acc))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, axis: str = "seq", kv_mask=None,
                   causal: bool = False, sm_scale: float | None = None):
    """Sequence-parallel attention: shard [B, T, H, D] over ``mesh[axis]``.

    T must divide evenly by the axis size (pad upstream; serving buckets are
    already padded to fixed shapes).  kv_mask optional [B, T].
    """
    T = q.shape[1]
    nshards = mesh.shape[axis]
    if T % nshards != 0:
        raise ValueError(f"seq len {T} not divisible by {axis}={nshards}")
    spec = P(None, axis, None, None)
    local = functools.partial(ring_attention_local, axis_name=axis,
                              causal=causal, sm_scale=sm_scale)
    if kv_mask is None:
        fn = jax.shard_map(lambda q, k, v: local(q, k, v), mesh=mesh,
                           in_specs=(spec, spec, spec), out_specs=spec,
                           check_vma=False)
        return fn(q, k, v)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(spec, spec, spec, P(None, axis)),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v, kv_mask)
