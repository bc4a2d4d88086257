"""Pallas decode attention over the slot pool — one query a slot, read in place.

The slot pool is ``[L, S, T, D]`` with the heads side by side in ``D``.  The
decode step's attention for layer ``l`` needs ``pool[l]`` and nothing else,
and of it only the positions each slot has written (``wpos[s]``, a fifth of
``T`` at the benchmark's chat traffic).  This kernel takes its blocks
straight out of the 4-D pool through the BlockSpec index map (layer static),
so nothing ``[S, T, D]``-sized is sliced, copied or transposed, and stops at
each slot's last written block: a block past it is skipped (``pl.when``) and
its index clamped to the last live one, so the pipeline issues no DMA for it.

Math, as ``models/gpt2._attn_decode``'s ``jax.numpy`` form: head ``h``'s
query sits in its own ``D/H`` columns of an ``[H, D]`` block with zeros
elsewhere, so row ``h`` of ``q_heads @ K^T`` is head ``h``'s scores and row
``h`` of ``probs @ V`` carries its output in those same columns; scores and
softmax in float32 (online over blocks: the sum over positions is
reordered, nothing is left out), probabilities and values in the pool's
dtype, a position beyond ``wpos`` weighs exactly zero.

grid ``(S, T / block_t)``, positions innermost; running max, denominator and
the ``[H, D]`` accumulator live in VMEM scratch across a slot's blocks
(flash_attention.py's pattern).  ``interpret=True`` runs the same kernel on
the CPU for tests/test_decode_attention.py; the serving path's choice of
kernel or ``jax.numpy`` form is by backend, in ``models/gpt2._attn_decode``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASKED = -1e9  # as the jnp form's: exp(_MASKED - max) is exactly 0


def pick_block_t(total: int, want: int = 256) -> int:
    """Largest multiple of 16 (the bf16 sublane tile) ≤ ``want`` that divides
    ``total``; ``total`` itself when none does (one block a slot)."""
    for cand in range(min(want, total) // 16 * 16, 15, -16):
        if total % cand == 0:
            return cand
    return total


def _kernel(wpos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_t: int, head_dim: int):
    s, t = pl.program_id(0), pl.program_id(1)
    last = wpos_ref[s]
    rows, D = acc_ref.shape

    def own():
        """Row h owns head h's columns; rows past the last head own none.
        Built where it is used: a skipped block pays for none of it."""
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, D), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, D), 1)
        return (col >= row * head_dim) & (col < (row + 1) * head_dim)

    @pl.when(t == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t * block_t <= last)
    def _():
        # The select runs in float32: the mask comes from int32 iotas, whose
        # (8, 128) tiling Mosaic will not relayout to bfloat16's (16, 128).
        k = k_ref[...]
        qh = jnp.where(own(), q_ref[...].astype(jnp.float32),  # [1, D] -> rows
                       0.0).astype(k.dtype)
        scores = jax.lax.dot_general(
            qh, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)               # [rows, bt]
        kpos = t * block_t + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(kpos <= last, scores, _MASKED)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
        v = v_ref[...]
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        out = acc_ref[...] / l_ref[...]
        # Each head keeps its own columns: one non-zero term a column.
        o_ref[...] = jnp.where(own(), out, 0.0).sum(
            axis=0, keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("layer", "heads", "block_t",
                                             "interpret"))
def decode_attention(q, cache_k, cache_v, wpos, *, layer: int, heads: int,
                     block_t: int | None = None, interpret: bool = False):
    """q [S, D] (already scaled), cache_k / cache_v [L, S, T, D], wpos [S]
    int32 the last position each slot may read (0 <= wpos < T) → [S, D]."""
    S, D = q.shape
    T = cache_k.shape[2]
    bt = block_t or pick_block_t(T)
    if T % bt:
        raise ValueError(f"block_t {bt} does not divide the pool's {T} "
                         "positions")
    rows = -(-heads // 16) * 16  # the bf16 sublane tile

    def kv_index(s, t, wpos_ref):
        return layer, s, jnp.minimum(t, wpos_ref[s] // bt), 0

    kv_spec = pl.BlockSpec((None, None, bt, D), kv_index)
    q_spec = pl.BlockSpec((None, 1, D), lambda s, t, wpos_ref: (s, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, block_t=bt, head_dim=D // heads),
        out_shape=jax.ShapeDtypeStruct((S, 1, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, T // bt),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, D), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="decode_attention",
    )(wpos.astype(jnp.int32), q[:, None, :], cache_k, cache_v)
    return out[:, 0, :]
