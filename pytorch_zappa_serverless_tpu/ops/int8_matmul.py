"""Pallas W8A16 matmul — the int8 serving lane's kernel.

Why a kernel at all (VERDICT r2 item 6): naive XLA weight-only int8 —
``x @ (w_q.astype(bf16) * scale)`` — loses, because XLA materializes the
dequantized bf16 weight in HBM (measured in round 2: the dequant is hoisted
out of the matmul), so every step pays the int8 READ plus a bf16 WRITE+READ:
*more* bandwidth than serving bf16 weights directly.  Autoregressive decode
is weight-bandwidth-bound (GPT-2 small: ~248 MB of bf16 weights per token at
batch 8 vs a ~0.6 ms step ≈ half the v5e's 819 GB/s), so the only way int8
wins is if the int8 bytes are the ONLY weight bytes that cross HBM.  This
kernel does that: int8 blocks stream HBM→VMEM, convert to bf16 in VMEM
(exact: int8 values are integers ≤ 127, all representable in bf16's 8-bit
mantissa), hit the MXU against the activation block, and the per-output-
channel scale multiplies the fp32 accumulator once at the end — dequant never
touches HBM.

Layout and math:

- ``x [M, K]`` (bf16/f32 activations), ``w_q [K, N]`` int8, ``scale [N]``
  fp32 with ``w ≈ w_q * scale`` per column → ``y [M, N]`` in x.dtype.
  Per-COLUMN scales commute with the K-sum, so dequant after accumulation is
  exact w.r.t. scaled-int8 weights (no approximation beyond quantization).
- blocks come from :func:`plan`, a function of ``(M, K, N)`` and the dtypes
  alone.  The grid is ``(nm, nn, nk)``, K innermost, with an fp32
  accumulator scratch carried across K blocks (flash_attention.py's scratch
  pattern); where a block holds all of K, ``nk`` is 1 and nothing is carried.
- decode calls have tiny M (the slot batch, e.g. 8): M is padded to the
  bf16 sublane tile (16) and the block simply spans all of it.  Their time
  is the weight's walk, so their blocks are sized in bytes (``_BLOCK_BYTES``).
- K/N pad to block multiples with zeros (zero rows/cols contribute zero).

Every time in this file is the kernel alone under the profiler on the v5e as
installed for PR 36 (jax/jaxlib 0.9.0, libtpu 0.0.34; PERF.md section 6 has
the table).  The 295 -> 442 GB/s that docs/PERF_DECODE.md quotes for whole-K
blocks at width 768 are from an earlier installation.

``quantize_per_channel`` is the matching symmetric quantizer (per output
channel, max-abs / 127).  ``interpret=True`` auto-selects off-TPU so the
same code path unit-tests on CPU (tests/test_int8_matmul.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _block(dim: int, want: int, tile: int) -> int:
    """Largest multiple of ``tile`` ≤ ``want`` that divides dim-rounded-to-tile.

    Naive ``min(want, round_up(dim, want))`` pads GPT-2's 768-wide dims up to
    1024 (block 512) — streaming ~33-78% zero weight bytes per step, exactly
    the bandwidth the kernel exists to save.  Preferring a divisor (768 →
    384) keeps the padded array the real size.
    """
    padded = _round_up(dim, tile)
    for cand in range(min(want, padded), tile - 1, -tile):
        if padded % cand == 0:
            return cand
    return tile


# Rows at or under which a call is a decode step's: one tile of rows against
# the whole weight matrix, its time the weight's walk.  Blocks sized in bytes
# beat the ones sized for rows by 1.22-1.48x at 8, 16, 32, 64 and 128 rows
# alike (GPT-2 large's qkv, fc1 and fc2).  Past 128 rows the blocks are the
# ones the prefill lanes have always had, and theirs to retune: their time is
# the MXU's, and PERF.md section 7 has the cell that judges them.
DECODE_ROWS = 128
# Weight bytes a decode block aims at.  A call costs about one block more
# than its bytes (nothing hides the first fetch, and the last product hides
# nothing) and a third of a microsecond a grid step, so it wants few blocks.
# At GPT-2 large's shapes (1.6-6.5 MB) blocks of 0.6-1.6 MB read within 7%
# of each other and the 160-320 KB that row-sized blocks come to at width
# 1280 read 1.3-1.8x behind; under 1 MiB sums lowest over a decode step (1.6
# MB: 2% more, 0.8 MB: the same).  A matrix of 1 MB or less is one block: two
# are at best 5-7% ahead (384 x 1536, 1024 x 1024), at 576 KB and under one
# leads (768 x 768 by 6%, 1024 x 512 by 14%), and three blocks of 48 KB take
# twice what one of 144 KB does (384 x 384).
_BLOCK_BYTES = 1 << 20
# What Mosaic gives a kernel by default, and all this one asks for: a step
# holds two buffers of each block and the weight block's copy in x's dtype
# (:func:`vmem_bytes`), 4 MiB at the largest decode plan with bf16 rows.
_VMEM_BYTES = 16 << 20


def plan(M: int, K: int, N: int, w_dtype=jnp.int8) -> tuple[int, int, int]:
    """Blocks ``(bm, bk, bn)`` for ``x [M, K] @ w [K, N]``: each a divisor
    of its dimension rounded up to the tile (16 rows, 128 lanes), so no
    padding beyond the tile is ever streamed.

    A decode step's rows (``M <= DECODE_ROWS``) take one row tile, all of K
    while a 128-column block of it stays within ``_BLOCK_BYTES`` (the
    largest dividing part of it otherwise), and the widest ``bn`` that
    keeps the weight block within ``_BLOCK_BYTES``: a matrix that fits is
    one block.  More rows take the blocks the prefill lanes have always
    had: up to 256 rows, 1024 of K and 512 of N.
    """
    if M > DECODE_ROWS:
        return _block(M, 256, 16), _block(K, 1024, 128), _block(N, 512, 128)
    itemsize = jnp.dtype(w_dtype).itemsize
    bk = _block(K, _BLOCK_BYTES // (128 * itemsize), 128)
    widest = max(_BLOCK_BYTES // (bk * itemsize) // 128, 1) * 128
    return _round_up(M, 16), bk, _block(N, widest, 128)


def plan_summary(M: int, K: int, N: int, w_dtype=jnp.int8) -> dict:
    """What a call's plan comes to, for a log line or a table: its blocks,
    its grid steps and the bytes of a weight block."""
    bm, bk, bn = plan(M, K, N, w_dtype)
    return {"blocks": [bm, bk, bn],
            "grid_steps": -(-M // bm) * -(-K // bk) * -(-N // bn),
            "block_bytes": bk * bn * jnp.dtype(w_dtype).itemsize}


def vmem_bytes(bm: int, bk: int, bn: int, x_dtype, out_dtype=None) -> int:
    """VMEM a grid step of these blocks holds: two buffers each of the x,
    weight, scale (a sublane tile of 8 rows) and output blocks, the weight
    block's copy in ``x_dtype``, and the fp32 accumulator."""
    x_size = jnp.dtype(x_dtype).itemsize
    out_size = jnp.dtype(out_dtype or x_dtype).itemsize
    return (2 * bm * bk * x_size + 2 * bk * bn + bk * bn * x_size
            + 2 * 8 * bn * 4 + 2 * bm * bn * out_size + bm * bn * 4)


def quantize_per_channel(w, axis: int = 0):
    """Symmetric int8 quantization of ``w`` per OUTPUT channel.

    ``axis`` is the reduction (input) axis of the matmul the weight will be
    used in; scales live on the other (output) axis.  Returns
    (w_q int8 same shape, scale fp32 [N]) with ``w ≈ w_q * scale``.
    """
    w = np.asarray(w, np.float32)
    absmax = np.max(np.abs(w), axis=axis)
    scale = np.where(absmax > 0, absmax / 127.0, 1.0).astype(np.float32)
    w_q = np.clip(np.round(w / np.expand_dims(scale, axis)), -127, 127)
    return w_q.astype(np.int8), scale


def padded_columns(K: int, N: int, w_dtype=jnp.int8) -> int:
    """Columns a ``[K, N]`` weight is stored with: the multiple of 128, at
    most 1% past the first, whose decode plan has the widest block.

    A vocabulary's tile count is whatever it is: 50257 columns are 393 = 3 x
    131 tiles, which divide into blocks of 384 or 128 columns; three tiles
    more (396) divide into blocks of 768, which the v5e walks in 88.9 µs
    where it takes 104.5 (K 1280).
    """
    n_128 = _round_up(N, 128)
    return max(range(n_128, n_128 + n_128 // 100 + 1, 128),
               key=lambda n: (plan(DECODE_ROWS, K, n, w_dtype)[2], -n))


def pad_weights(w_q, scale):
    """Pre-pad quantized weights to :func:`int8_matmul`'s call-time padding.

    Why: the kernel's `jnp.pad` on its weight operand runs INSIDE the jitted
    program — for an oddly-sized N like GPT-2's 50257-row lm head that is a
    ~38 MB int8 copy on EVERY decode step (traced at ~40 µs/step, ~10% of
    the int8 lane).  Padding once at build makes the call-time pads
    zero-width (XLA elides them).  Pad columns carry zero weights and scale
    1.0 → exactly-zero outputs; callers slice ``[..., :N]`` off the result
    (zero logits could win an argmax over all-negative real logits
    otherwise).

    Pads K to the 128 tile and N to :func:`padded_columns`, with no block
    parameters: for ANY multiple of 128 the kernel's padded extent is the
    extent itself (:func:`plan` only returns divisors of it), at every M —
    the pre-pad cannot drift from the kernel.
    """
    w_q = np.asarray(w_q)
    scale = np.asarray(scale, np.float32)
    K, N = w_q.shape
    k_p, n_p = _round_up(K, 128), padded_columns(K, N, w_q.dtype)
    w_pad = np.zeros((k_p, n_p), np.int8)
    w_pad[:K, :N] = w_q
    s_pad = np.ones((n_p,), np.float32)
    s_pad[:N] = scale
    return w_pad, s_pad


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    x = x_ref[:]                                   # (bm, bk) bf16
    w = w_ref[:].astype(x.dtype)                   # int8 -> bf16, in VMEM
    acc_ref[:] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finish():
        o_ref[:] = (acc_ref[:] * s_ref[0][None, :]).astype(o_ref.dtype)


def int8_matmul(x, w_q, scale, *, out_dtype=None,
                interpret: bool | None = None):
    """``x [M, K] @ dequant(w_q [K, N], scale [N]) -> [M, N]``.

    ``out_dtype`` defaults to x.dtype; pass fp32 for logits-style consumers —
    the accumulator is fp32 either way, so a fp32 output is exact.  The
    blocks are :func:`plan`'s.
    """
    M, K = x.shape
    K2, N = w_q.shape
    if K != K2 or scale.shape != (N,):
        raise ValueError(f"shape mismatch: x {x.shape}, w_q {w_q.shape}, "
                         f"scale {scale.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    bm, bk, bn = plan(M, K, N, w_q.dtype)
    m_p, k_p, n_p = _round_up(M, bm), _round_up(K, bk), _round_up(N, bn)

    xp = jnp.pad(x, ((0, m_p - M), (0, k_p - K)))
    wp = jnp.pad(w_q, ((0, k_p - K), (0, n_p - N)))
    sp = jnp.pad(scale, (0, n_p - N)).reshape(1, n_p)

    out = pl.pallas_call(
        _kernel,
        grid=(m_p // bm, n_p // bn, k_p // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda im, in_, ik: (im, ik),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bk, bn), lambda im, in_, ik: (ik, in_),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn), lambda im, in_, ik: (0, in_),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda im, in_, ik: (im, in_),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m_p, n_p), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="int8_matmul",
    )(xp, wp, sp)
    return out[:M, :N]


def dense_maybe_int8(p: dict, x):
    """Drop-in for the models' ``_dense``: dispatches on the param dict.

    Quantized params carry ``kernel_q`` int8 [K, N] + ``scale`` fp32 [N]
    (built by :func:`quantize_tree`); unquantized carry ``kernel``.  Handles
    leading batch/seq dims by flattening to [M, K].
    """
    if "kernel_q" not in p:
        y = x @ p["kernel"].astype(x.dtype)
        return y + p["bias"].astype(x.dtype) if "bias" in p else y
    lead = x.shape[:-1]
    K = x.shape[-1]
    y = int8_matmul(x.reshape(-1, K), p["kernel_q"], p["scale"])
    y = y.reshape(*lead, -1)
    return y + p["bias"].astype(x.dtype) if "bias" in p else y


def quantize_tree(params, min_size: int = 1 << 16):
    """Replace every ``{"kernel": 2-D float}`` node with int8 + scale.

    Walks the nested-dict param tree; kernels smaller than ``min_size``
    elements stay float (their HBM traffic is noise and tiny N hurts tile
    efficiency).  Biases/norms untouched — they ride fp32 as before.
    """

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if (k == "kernel" and hasattr(v, "ndim") and v.ndim == 2
                    and np.asarray(v).dtype.kind == "f"
                    and np.asarray(v).size >= min_size):
                w_q, scale = quantize_per_channel(np.asarray(v), axis=0)
                out["kernel_q"] = jnp.asarray(w_q)
                out["scale"] = jnp.asarray(scale)
            elif isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = v
        return out

    return walk(params)
