"""Pallas flash attention — the framework's hot-op TPU kernel.

The zoo's one genuinely memory-bound attention is SD-1.5's UNet
self-attention at 64x64 latents: 4096 tokens -> a [B,8,4096,4096] fp32 score
tensor (~512 MB at B=8) that a naive einsum materializes in HBM
(models/sd_unet.py).  The reference app has no kernels at all (SURVEY §2a:
pure torch-CPU forward), so this is capability-new: a blocked online-softmax
attention in Pallas that keeps scores in VMEM, streaming K/V blocks past a
resident Q block — O(T) memory instead of O(T^2), and the score/softmax/PV
chain never leaves the chip.

Design (standard TPU flash attention, written for this zoo's shapes):

- grid ``(B, H, num_q_blocks, num_k_blocks)``; the K dimension is the
  innermost, sequentially-iterated axis, so VMEM scratch (running max ``m``,
  denominator ``l``, fp32 accumulator ``acc``) carries across K blocks and is
  re-initialised when ``program_id(3) == 0``.
- scores computed on the MXU in fp32 (``preferred_element_type``); the
  probs @ V matmul runs in the input dtype (bf16 in production) with an fp32
  accumulator — same numerics contract as the einsum path it replaces.
- head dim is zero-padded to the 128-lane width: measured on the v5e chip
  this beats unpadded D=64 blocks (17.9 vs 21.2 ms/iter at the SD shape —
  Mosaic's sub-lane handling costs more than the padded DMA), and the
  512x1024 block default is the sweep winner (1.4x over the XLA einsum,
  25.7 -> 17.9 ms for [2,4096,8,64] bf16).
- padding (to block multiples) is masked in-kernel with ``broadcasted_iota``
  against the *static* true length; an optional per-key validity mask
  (``kv_mask``, [B, Tk]) becomes a streamed additive bias block; ``causal``
  skips fully-masked K blocks via ``pl.when`` predication.
- ``interpret=True`` is auto-selected off-TPU so the same code path is unit
  tested on CPU (tests/test_flash_attention.py) and compiled by Mosaic on
  the chip.

A decoder's prefill has a second kernel, :func:`prompt_attention`: causal,
ragged, over rows ``[B, P, D]`` with the heads side by side, read where the
projections left them.  models/decoder.py reaches it through
:func:`prompt_attend`, which takes it or the ``jax.numpy`` form
(:func:`masked_attention`) by :func:`prompt_form`'s rule; models/evabyte.py
hands it a long prompt's windows as rows of the batch and the summaries of
the windows before each as a prefix of keys.

Degenerate rows (every key masked) produce a uniform distribution over the
masked keys rather than NaN — the -1e9 finite mask convention; no zoo model
issues such rows.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_NEG_INF = -1e9


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def band_first(iq, block_q: int, block_k: int, window: int):
    """The first block of keys a block ``iq`` of queries (traced) reads
    under a band of ``window`` (query ``p`` sees keys ``j`` with ``0 <= p -
    j < window``); the last is the diagonal's."""
    return jnp.maximum(iq * block_q - (window - 1), 0) // block_k


def band_blocks(blocks_q: int, block_q: int, block_k: int, window: int) -> int:
    """Blocks of keys the widest band of any block of queries spans: the
    grid's bound on its innermost axis."""
    return max((iq * block_q + block_q - 1) // block_k + 1
               - max(iq * block_q - (window - 1), 0) // block_k
               for iq in range(blocks_q))


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            sm_scale: float, causal: bool, block_q: int, block_k: int,
            tk_valid: int, tk_padded: int, bias_ref=None,
            window: int | None = None):
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = pl.program_id(2) * block_q
    k_start = ik * block_k
    if window is not None:
        # The axis counts from the band's first block (the index map's
        # rule); a step past the diagonal's block is skipped below.
        k_start = (band_first(pl.program_id(2), block_q, block_k, window)
                   + ik) * block_k

    def _block():
        q = q_ref[0, 0]                                   # (bq, D)
        k = k_ref[0, 0]                                   # (bk, D)
        s = jax.lax.dot_general(                          # (bq, bk) fp32 on MXU
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if bias_ref is not None:
            s = s + bias_ref[0][None, :]
        if tk_padded != tk_valid:                         # static: padding exists
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_start + cols < tk_valid, s, _NEG_INF)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_start + cols <= q_start + rows, s, _NEG_INF)
            if window is not None:
                s = jnp.where(q_start + rows - (k_start + cols) < window, s,
                              _NEG_INF)

        m_prev = m_ref[:, :1]                             # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                   # rescale of old state
        p = jnp.exp(s - m_new)                            # (bq, bk)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # K blocks entirely above the diagonal contribute nothing; skip them.
        @pl.when(k_start < q_start + block_q)
        def _():
            _block()
    else:
        _block()

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = False, kv_mask=None,
                    sm_scale: float | None = None, block_q: int | None = None,
                    block_k: int = 1024, interpret: bool | None = None,
                    window: int | None = None):
    """Blocked online-softmax attention.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D]; kv_mask: optional [B, Tk] bool
    (True = attend).  Returns [B, Tq, H, D] in q.dtype.

    ``k`` and ``v`` may hold fewer heads than ``q``, ``[B, Tk, KV, D]`` with
    ``H`` a multiple of ``KV``: query head ``h`` reads K/V head ``h // (H /
    KV)`` through the tile map, and nothing is repeated in HBM.

    ``v`` may be narrower than ``q`` and ``k``, ``[B, Tk, KV, Dv]`` (latent
    attention's prompt form: keys of 192, values of 128): the ``P V``
    product, the accumulator and the result are ``Dv`` wide, each width
    padded to its own lane tiles (256 and 128, where one width for both
    would make 256 and 256).  The scale is ``D``'s.

    ``window`` (with ``causal``) is a band: query ``p`` sees keys ``j`` with
    ``0 <= p - j < window``.  The grid's innermost axis is then as long as
    the widest band in blocks (:func:`band_blocks`) and counts from the
    band's first block (:func:`band_first`): a block of keys wholly outside
    the band costs no grid step, no DMA and no arithmetic, and the mask
    inside a visited block is exact.  Near the start of the prompt, where
    the band is cut short, the spare steps name the diagonal's block again
    (no new copy) and are skipped.
    """
    B, Tq, H, D = q.shape
    Tk, group, Dv = k.shape[1], H // k.shape[2], v.shape[3]
    if causal and Tq != Tk:
        raise ValueError(f"causal needs Tq == Tk, got {Tq} != {Tk}")
    if window is not None and (not causal or kv_mask is not None):
        raise ValueError("a band (window=) is causal and takes no kv_mask")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    if block_q is None:
        # v5e trace sweep at the SD shape [2,4096,8,64] (tools/sweep_flash.py,
        # device-trace timed): the custom-call runs 1.17 ms at block_q=512 vs
        # 1.02 ms at 1024 — fewer q-block passes over K amortize the scratch
        # init/finish.  1024x1024 blocks stay well inside scoped VMEM at
        # d_p=128 (2048-wide q or 4096-wide k blocks OOM the 16 MB budget).
        block_q = 1024 if Tq >= 1024 else 512
    block_q = min(block_q, _round_up(Tq, _LANES))
    block_k = min(block_k, _round_up(Tk, _LANES))
    tq_p, tk_p = _round_up(Tq, block_q), _round_up(Tk, block_k)
    d_p, dv_p = _round_up(D, _LANES), _round_up(Dv, _LANES)

    def _prep(x, t_pad):  # [B,T,H,D] -> [B,H,T_pad,D_pad]
        x = jnp.transpose(x, (0, 2, 1, 3))
        return jnp.pad(x, ((0, 0), (0, 0), (0, t_pad - x.shape[2]),
                           (0, _round_up(x.shape[3], _LANES) - x.shape[3])))

    qt, kt, vt = _prep(q, tq_p), _prep(k, tk_p), _prep(v, tk_p)
    nq, nk = tq_p // block_q, tk_p // block_k

    if window is None and group == 1:
        keys = lambda b, h, iq, ik: (b, h, ik, 0)  # noqa: E731
    else:
        blocks_k = nk

        def keys(b, h, iq, ik):
            if window is not None:
                last = jnp.minimum((iq * block_q + block_q - 1) // block_k,
                                   blocks_k - 1)
                ik = jnp.minimum(
                    band_first(iq, block_q, block_k, window) + ik, last)
            return (b, h // group, ik, 0)

        if window is not None:
            nk = min(nk, band_blocks(nq, block_q, block_k, window))

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d_p), lambda b, h, iq, ik: (b, h, iq, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k, d_p), keys, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k, dv_p), keys, memory_space=pltpu.VMEM),
    ]
    operands = [qt, kt, vt]
    bias_kw = {}
    if kv_mask is not None:
        bias = jnp.where(kv_mask.astype(bool), 0.0, _NEG_INF).astype(jnp.float32)
        bias = jnp.pad(bias, ((0, 0), (0, tk_p - Tk)))
        in_specs.append(pl.BlockSpec((1, block_k), lambda b, h, iq, ik: (b, ik),
                                     memory_space=pltpu.VMEM))
        operands.append(bias)
        bias_kw = {"bias_ref": True}

    kernel = functools.partial(
        _kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, tk_valid=Tk, tk_padded=tk_p,
        **({} if window is None else {"window": window}))
    if bias_kw:
        # bias ref arrives positionally after v_ref; rebind so the kernel body
        # sees it as bias_ref (scratch refs always trail the operand refs).
        base = kernel

        def kernel(q_ref, k_ref, v_ref, bias, o_ref, m, l, acc):
            base(q_ref, k_ref, v_ref, o_ref, m, l, acc, bias_ref=bias)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, block_q, dv_p),
                               lambda b, h, iq, ik: (b, h, iq, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, H, tq_p, dv_p), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running denom l
            pltpu.VMEM((block_q, dv_p), jnp.float32),     # fp32 accumulator
        ],
        interpret=interpret,
        name="flash_attention",
    )(*operands)
    return jnp.transpose(out[:, :, :Tq, :Dv], (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# The prompt attention of a decoder's prefill
# ---------------------------------------------------------------------------

def _prompt_kernel(len_ref, *refs, sm_scale: float, block: int,
                   head_dim: int, width: int, prefix: bool = False):
    if prefix:
        count_ref, q_ref, k_ref, v_ref, pk_ref, pv_ref, o_ref, *scratch = refs
    else:
        q_ref, k_ref, v_ref, o_ref, *scratch = refs
    b, t = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]
    P, W = q_ref.shape
    per = W // head_dim                    # heads side by side in a lane tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, W), 1)
    # Head h's own lanes of the tile (None: all of them), and as a query
    # sees them.
    on_head = [None if per == 1 else lane // head_dim == h for h in range(per)]
    own = on_head
    if scratch:
        # The last tile hangs over the rows' end (``width % W``).  Whatever
        # lies there must not reach a score (0 x NaN): a query sees its
        # head's lanes without them, and K is cleaned into the scratch.  In
        # V it only reaches output lanes that are not written back.  (The
        # selects run in float32: a mask from int32 iotas has their (8, 128)
        # tiling, not bfloat16's.)
        real = t * W + lane < width
        own = [real if m is None else m & real for m in on_head]

        def clean(raw, into):
            at = jax.lax.broadcasted_iota(jnp.int32, raw.shape, 1)
            into[...] = jnp.where(t * W + at < width,
                                  raw[...].astype(jnp.float32),
                                  0.0).astype(into.dtype)
            return into

        k_ref = clean(k_ref, scratch[0])
        if prefix:
            pk_ref = clean(pk_ref, scratch[1])
    below = (jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
             <= jax.lax.broadcasted_iota(jnp.int32, (block, block), 0))
    if prefix:
        # The prefix's rows that are real, by the row's count.  A row past
        # it is kept out of the scores by the mask and out of the values by
        # a zero (a probability of 0 against a NaN is a NaN).
        J = pk_ref.shape[0]
        count = count_ref[b]
        given = jax.lax.broadcasted_iota(jnp.int32, (block, J), 1) < count
        pv = jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, (J, W), 0) < count,
            pv_ref[...].astype(jnp.float32), 0.0).astype(pv_ref.dtype)

    whole = slice(None)

    def scores(qh, keys, rows):
        return jax.lax.dot_general(qh, keys[rows, :],
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def values(p, of, rows):
        return jnp.dot(p.astype(of.dtype), of[rows, :],
                       preferred_element_type=jnp.float32)

    # A block of queries after the other, each over the keys at or below
    # it: its own block under the diagonal's mask, the blocks before it
    # whole, and the prefix's real rows.  The slices are static, so nothing
    # past the diagonal is read or computed and no running state is carried.
    for i in range(P // block):
        own_rows, before = pl.ds(i * block, block), pl.ds(0, i * block)

        @pl.when(i * block < length)
        def _live():
            q = q_ref[own_rows, :].astype(jnp.float32) * sm_scale
            out = None
            for h in range(per):
                # Head h's query on its own lanes and zeros on the others':
                # the contraction over the whole tile is head h's scores,
                # and ``p @ v`` carries head h's output on those same lanes.
                qh = (q if own[h] is None
                      else jnp.where(own[h], q, 0.0)).astype(k_ref.dtype)
                s = jnp.where(below, scores(qh, k_ref, own_rows), _NEG_INF)
                m = s.max(axis=-1, keepdims=True)
                if i:
                    s_before = scores(qh, k_ref, before)
                    m = jnp.maximum(m, s_before.max(axis=-1, keepdims=True))
                if prefix:
                    s_prefix = jnp.where(given, scores(qh, pk_ref, whole),
                                         _NEG_INF)
                    m = jnp.maximum(m, s_prefix.max(axis=-1, keepdims=True))
                p = jnp.exp(s - m)
                l = p.sum(axis=-1, keepdims=True)
                o = values(p, v_ref, own_rows)
                if i:
                    p = jnp.exp(s_before - m)
                    l = l + p.sum(axis=-1, keepdims=True)
                    o = o + values(p, v_ref, before)
                if prefix:
                    p = jnp.exp(s_prefix - m)
                    l = l + p.sum(axis=-1, keepdims=True)
                    o = o + values(p, pv, whole)
                o = o * (1.0 / l)          # l >= 1: the diagonal is kept
                out = o if h == 0 else jnp.where(on_head[h], o, out)
            o_ref[own_rows, :] = out.astype(o_ref.dtype)

        # No query of the block is real: nothing reads its rows.
        @pl.when(i * block >= length)
        def _dead():
            o_ref[own_rows, :] = jnp.zeros((block, W), o_ref.dtype)


def _tile_width(head_dim: int) -> int | None:
    """Lanes a block of the prompt kernel spans: whole heads in whole lane
    tiles, or None where the head size allows neither."""
    if _LANES % head_dim == 0:
        return _LANES
    return head_dim if head_dim % _LANES == 0 else None


@functools.partial(jax.jit, static_argnames=("heads", "block", "interpret"))
def prompt_attention(q, k, v, lengths, *, heads: int, prefix=None,
                     block: int | None = None, interpret: bool = False):
    """Causal, ragged self-attention of a prompt, the scores never in HBM.

    q, k, v [B, P, D] with the heads side by side in ``D`` (as a decoder's
    projections leave them and as its cache keeps them), lengths [B] int32
    → [B, P, D] in ``q``'s dtype.  Query ``i`` of row ``b`` reads keys
    ``j <= i``, in one softmax.  That is all a real query (``i <
    lengths[b]``) may read, so the causal mask is the ragged one on every
    row that is used; rows ``i >= lengths[b]`` hold finite values that
    mean nothing (zeros where a whole block of queries is past the length,
    which is all ``lengths`` is for: it rides in by scalar prefetch).

    ``prefix`` = ``(pk, pv, counts)`` gives every query of row ``b``
    ``counts[b]`` keys more, unmasked and in the same softmax: the first
    ``counts[b]`` rows of ``pk``, ``pv`` [G, J, D], which ``B / G``
    consecutive rows of the batch share (a long prompt's windows, stacked
    as rows of the batch, each reading the summaries of the windows before
    it: models/evabyte.py).  What lies past a count reaches no output.

    Nothing is transposed or padded on the way in: a grid step holds the
    ``[P, 128]`` lanes of one row of the batch where they lie, two heads of
    64 (one of 128, four of 32), K and V whole, and the prefix's ``[J,
    128]``.  Each head's query is masked to its own lanes, so the 128-deep
    contraction is that head's scores and the MXU does what it would on a
    head padded to its lanes.  Scores in float32 off the MXU, probabilities
    in the inputs' dtype against V with a float32 accumulator.
    """
    B, P, D = q.shape
    hd = D // heads
    W = _tile_width(hd)
    if W is None:
        raise ValueError(f"heads of {hd} fill no whole lane tiles")
    block = block or prompt_block(P)
    Pp = _round_up(P, block)
    if Pp != P:  # zero rows, past every length
        q, k, v = (jnp.pad(a, ((0, 0), (0, Pp - P), (0, 0)))
                   for a in (q, k, v))
    spec = pl.BlockSpec((None, Pp, W), lambda b, t, *_: (b, 0, t))
    scalars, operands, in_specs = [lengths], [q, k, v], [spec, spec, spec]
    scratch = [pltpu.VMEM((Pp, W), k.dtype)] if D % W else []
    if prefix is not None:
        pk, pv, counts = prefix
        G, J, _ = pk.shape
        Jp = _round_up(J, _LANES)  # whole lanes of scores; zeros, uncounted
        if Jp != J:
            pk, pv = (jnp.pad(a, ((0, 0), (0, Jp - J), (0, 0)))
                      for a in (pk, pv))
        shared = pl.BlockSpec((None, Jp, W),
                              lambda b, t, *_: (b // (B // G), 0, t))
        scalars.append(counts)
        operands += [pk, pv]
        in_specs += [shared, shared]
        if D % W:
            scratch.append(pltpu.VMEM((Jp, W), pk.dtype))
    out = pl.pallas_call(
        functools.partial(_prompt_kernel, sm_scale=hd ** -0.5, block=block,
                          head_dim=hd, width=D, prefix=prefix is not None),
        out_shape=jax.ShapeDtypeStruct((B, Pp, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(B, pl.cdiv(D, W)),
            in_specs=in_specs, out_specs=spec, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="prompt_attention",
    )(*(a.astype(jnp.int32) for a in scalars), *operands)
    return out[:, :P]


# Positions a prompt may have for the kernel: K and V lie whole in VMEM a
# lane tile at a time, and a block of queries is unrolled after the other.
PROMPT_KERNEL_MAX_POSITIONS = 2048


def prompt_block(P: int) -> int:
    """Queries a block of the prompt kernel holds.  On the v5e a block costs
    about 0.36 us beside its scores (324 G elements a second): at
    ``[4, 768, 25 x 64]`` 64, 128, 256, 384 and 768 read 374, 233, 181, 189
    and 208 us a layer (PR 39's chip runs; PERF.md section 6, PR 40)."""
    return min(256, _round_up(P, 16))


def masked_attention(q, k, v, mask_bias, heads: int):
    """The ``jax.numpy`` form, scores materialised: q [B,Tq,D], k/v [B,Tk,D]
    with the heads side by side, mask_bias [B,1,Tq,Tk] float32 → [B,Tq,D]."""
    def split(x):
        B, T, D = x.shape
        return x.reshape(B, T, heads, D // heads)

    q, k, v = split(q), split(k), split(v)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k).astype(jnp.float32)
    probs = jax.nn.softmax(scores + mask_bias, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    B, Tq = out.shape[:2]
    return out.reshape(B, Tq, -1)


def prompt_mask(lengths, P: int):
    """Causal and ragged as an additive bias [B, 1, P, P]: query i reads
    keys j <= i that are real (j < length)."""
    pos = jnp.arange(P)
    causal = pos[None, :, None] >= pos[None, None, :]              # [1,P,P]
    real = pos[None, None, :] < lengths[:, None, None]              # [B,1,P]
    return jnp.where(causal & real, 0.0, _NEG_INF).astype(jnp.float32)[:, None]


# Bytes of float32 scores ``[B, H, P, P]`` from which a prompt's attention
# takes the kernel.  From both forms alone on the v5e at the benchmark's 17
# prefill shapes (chip_smoke.py *kernels*; PERF.md section 6, PR 40): while
# its scores stay under about 100 MiB the ``jax.numpy`` form costs a quarter
# of one pass over them and wins or ties (0.42-1.10x at 5 to 80 MiB); from
# 100 MiB on it pays about 2.5 passes and the kernel wins, 1.28x at 100 MiB
# and 2.8-4.6x from 112 MiB on.
PROMPT_KERNEL_MIN_SCORE_BYTES = 96 << 20


def prompt_form(batch: int, heads: int, P: int, head_dim: int,
                scores: int | None = None) -> str:
    """Which form :func:`prompt_attend` takes at these shapes, from what it
    can observe: ``"kernel"`` on one TPU device where the heads fill whole
    lane tiles, the prompt is ``PROMPT_KERNEL_MAX_POSITIONS`` at most and
    the float32 scores the other form would write at once (``scores``
    elements: ``[batch, heads, P, P]`` unless a windowed family says what
    its block holds) are ``PROMPT_KERNEL_MIN_SCORE_BYTES`` at least; else
    ``"einsum"`` (the CPU; a mesh, where a Mosaic kernel is not partitioned
    automatically and the partitioner splits the einsums over the heads;
    small batches of short prompts, whose scores XLA's fusions keep
    cheaply)."""
    if (jax.default_backend() != "tpu" or jax.device_count() != 1
            or _tile_width(head_dim) is None
            or P > PROMPT_KERNEL_MAX_POSITIONS):
        return "einsum"
    if scores is None:
        scores = batch * heads * P * P
    return ("kernel" if scores * 4 >= PROMPT_KERNEL_MIN_SCORE_BYTES
            else "einsum")


def prompt_attend(q, k, v, lengths, heads: int):
    """A prefill's prompt attention: q, k, v [B, P, D] with the heads side
    by side, lengths [B] → [B, P, D], causal and ragged in one softmax, by
    the kernel or the ``jax.numpy`` form as :func:`prompt_form` says."""
    B, P, D = q.shape
    if prompt_form(B, heads, P, D // heads) == "kernel":
        return prompt_attention(q, k, v, lengths, heads=heads)
    return masked_attention(q, k, v, prompt_mask(lengths, P), heads)


# Streaming beats materialised scores once the score tensor stops fitting in
# VMEM alongside everything else; below this the fused-einsum path XLA emits
# is already optimal (BERT-128, CLIP-77, Whisper-1500 cross-attn).
FLASH_MIN_TOKENS = 1024


def attention(q, k, v, heads: int, *, causal: bool = False, kv_mask=None):
    """[B, T, C]-layout multi-head attention with automatic kernel dispatch.

    q [B,Tq,C], k/v [B,Tk,C] already projected; returns [B,Tq,C].  Picks the
    Pallas flash kernel when the score tensor is large enough to be
    memory-bound, else the XLA einsum path.
    """
    B, Tq, C = q.shape
    Tk = k.shape[1]
    if causal and Tq != Tk:
        raise ValueError(f"causal needs Tq == Tk, got {Tq} != {Tk}")
    hd = C // heads
    qh = q.reshape(B, Tq, heads, hd)
    kh = k.reshape(B, Tk, heads, hd)
    vh = v.reshape(B, Tk, heads, hd)
    if min(Tq, Tk) >= FLASH_MIN_TOKENS:
        return flash_attention(qh, kh, vh, causal=causal,
                               kv_mask=kv_mask).reshape(B, Tq, C)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh).astype(jnp.float32) * (hd ** -0.5)
    if kv_mask is not None:
        scores = scores + jnp.where(kv_mask.astype(bool), 0.0,
                                    _NEG_INF)[:, None, None, :]
    if causal:
        t = jnp.arange(Tq)
        scores = jnp.where(t[None, None, :, None] >= t[None, None, None, :],
                           scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(vh.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vh).reshape(B, Tq, C)
