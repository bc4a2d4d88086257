"""Fused Pallas decode-step kernels — the op-count wall, attacked.

Autoregressive decode at serving batch sizes is OP-COUNT-BOUND on TPU, not
FLOP-bound: the round-3 trace showed ~360 tiny XLA ops per GPT-2 token step
(~30 per layer: LN stats, three projections' pieces, scatter, softmax chain,
residual adds), each paying fixed sequencing overhead that dwarfs its math at
[8, 768]-sized operands.  The weights are the only real traffic — ~250 MB of
bf16 per step for GPT-2 small, a ~0.3 ms HBM floor at the v5e's 819 GB/s —
so the path past the wall is to collapse each transformer block into as few
launches as possible and let the weight stream set the pace.

Two kernels per layer (NOT one: attn + MLP weights together are ~14 MB,
which crowds VMEM against the KV cache and the pipelining headroom):

- :func:`fused_attn_step` — LN1 + fused-QKV projection + per-row KV-cache
  write at each row's own position + masked attention over the cache + output
  projection + residual, one ``pallas_call``.  The cache rides through the
  kernel via ``input_output_aliases`` (in-place pool update, no per-step
  cache copy through HBM).
- :func:`fused_mlp_step` — LN2 + fc1 + GELU + fc2 + residual, one
  ``pallas_call``.

The embedding gather, final LN, logits matmul (one big MXU op) and the
sampling logic stay in XLA: they are each single well-shaped ops that XLA
already runs well, and the logits matmul is ~77 MB of weight traffic that the
MXU wants as a plain matmul.

Cache layout is **[T, S, D] per layer** (time-major), NOT the [S, T, D] of
the XLA path: Mosaic requires dynamic store indices on TILED dims (the last
two) to be provably tile-aligned, and each row's write position ``pos[s]``
is arbitrary — time-major puts the dynamic index on the untiled leading dim
while the static slot index lands on the sublane dim (first attempt stored
at [s, ds(p,1), :] and Mosaic rejected it: "cannot statically prove that
index in dimension 1 is a multiple of 8").  The attention mask is computed
ONCE per step in XLA as an additive f32 bias [T, S] and shared by every
layer's kernel — no per-layer integer compare chains.

Shapes (S = slot-pool rows, D = d_model, T = cache length):

- activations ``x [S, D]`` bf16 (fp32 LN/softmax inside, like models/gpt2.py)
- per-layer caches ``cache_k/cache_v [T, S, D]`` bf16
- ``pos [S]`` int32 write positions (ragged continuous batching), as
  scalar-prefetch SMEM
- ``mask_bias [T, S]`` f32: 0 where key position <= pos[s], -1e9 elsewhere

Numerics contract: same math as models/gpt2.py ``_layer`` (fp32 LN + softmax,
bf16 matmuls with fp32 accumulate), but fused accumulation ORDER differs, so
logits agree to bf16 tolerance rather than bit-identically; the parity test
(tests/test_fused_decode.py) asserts stepwise logits closeness and greedy
token-chain equality on the test seeds.

``interpret=True`` auto-selects off-TPU (same convention as
ops/int8_matmul.py) so the kernels unit-test on the CPU harness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ln_f32(x32, scale, bias, eps):
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return (x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _proj(h, w_ref, s_ref, b_ref):
    """fp32-accumulated projection; int8 weights dequantize via the
    per-output-channel scale on the ACCUMULATOR (w ~ w_q * s commutes with
    the K-sum — ops/int8_matmul.py's math), so the int8 bytes are the only
    weight bytes that cross HBM and the VMEM dequant is one row-broadcast
    multiply instead of a materialized bf16 weight copy."""
    acc = jax.lax.dot_general(
        h, w_ref[:].astype(h.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if s_ref is not None:
        acc = acc * s_ref[:][None, :].astype(jnp.float32)
    return acc + b_ref[:].astype(jnp.float32)


def _attn_kernel(pos_ref, x_ref, lns_ref, lnb_ref, wqkv_ref, bqkv_ref,
                 wout_ref, bout_ref, mask_ref, ck_hbm_ref, cv_hbm_ref,
                 xo_ref, ck_out_ref, cv_out_ref,
                 ck_s, cv_s, sems, row_sems, *, heads: int,
                 eps: float):
    _attn_body(pos_ref, x_ref, lns_ref, lnb_ref, wqkv_ref, bqkv_ref, None,
               wout_ref, bout_ref, None, mask_ref, ck_hbm_ref, cv_hbm_ref,
               xo_ref, ck_out_ref, cv_out_ref, ck_s, cv_s, sems, row_sems,
               heads=heads, eps=eps)


def _attn_kernel_int8(pos_ref, x_ref, lns_ref, lnb_ref, wqkv_ref, bqkv_ref,
                      sqkv_ref, wout_ref, bout_ref, sout_ref, mask_ref,
                      ck_hbm_ref, cv_hbm_ref, xo_ref, ck_out_ref, cv_out_ref,
                      ck_s, cv_s, sems, row_sems, *, heads: int, eps: float):
    _attn_body(pos_ref, x_ref, lns_ref, lnb_ref, wqkv_ref, bqkv_ref,
               sqkv_ref, wout_ref, bout_ref, sout_ref, mask_ref, ck_hbm_ref,
               cv_hbm_ref, xo_ref, ck_out_ref, cv_out_ref, ck_s, cv_s, sems,
               row_sems, heads=heads, eps=eps)


def _attn_body(pos_ref, x_ref, lns_ref, lnb_ref, wqkv_ref, bqkv_ref,
               sqkv_ref, wout_ref, bout_ref, sout_ref, mask_ref, ck_hbm_ref,
               cv_hbm_ref, xo_ref, ck_out_ref, cv_out_ref,
               ck_s, cv_s, sems, row_sems, *, heads: int, eps: float):
    S, D = x_ref.shape
    T = ck_s.shape[0]
    hd = D // heads

    # The caches stay in HBM (ANY) and alias their outputs: only the S
    # fresh K/V rows are written back (the first version round-tripped the
    # whole pool through VMEM blocks — 4.8 MB/layer of pure overhead, ~40%
    # of the kernel's floor).  The full-pool read the attention needs is an
    # explicit async DMA, started FIRST so it overlaps the LN+QKV matmul.
    load_k = pltpu.make_async_copy(ck_hbm_ref, ck_s, sems.at[0])
    load_v = pltpu.make_async_copy(cv_hbm_ref, cv_s, sems.at[1])
    load_k.start()
    load_v.start()

    x32 = x_ref[:].astype(jnp.float32)
    h = _ln_f32(x32, lns_ref[:].astype(jnp.float32),
                lnb_ref[:].astype(jnp.float32), eps).astype(x_ref.dtype)
    qkv = _proj(h, wqkv_ref, sqkv_ref, bqkv_ref).astype(x_ref.dtype)
    q = qkv[:, :D]
    k_new = qkv[:, D:2 * D]
    v_new = qkv[:, 2 * D:]

    load_k.wait()
    load_v.wait()
    # Splice each row's fresh K/V at that row's own position — into the
    # VMEM copy (for this step's attention), then DMA each touched TIME
    # SLAB [1, S, D] back to the HBM pool.  Whole slabs, not single rows:
    # a DMA slice of the tiled slot dim must be tile-aligned (Mosaic
    # rejects [.., 1, D] out of [.., S, D]), while a dim-0 slice is free —
    # and the slab's untouched entries rewrite their identical HBM bytes,
    # which is benign (this kernel holds the only live copy of the pool).
    # Unrolled over the (static, small) slot dim so only the time index is
    # dynamic, on the untiled leading dim where Mosaic allows it.
    for s in range(S):
        p = pos_ref[s]
        ck_s[pl.ds(p, 1), s, :] = k_new[s:s + 1, :]
        cv_s[pl.ds(p, 1), s, :] = v_new[s:s + 1, :]
    for s in range(S):
        p = pos_ref[s]
        pltpu.make_async_copy(ck_s.at[pl.ds(p, 1)],
                              ck_out_ref.at[pl.ds(p, 1)],
                              row_sems.at[0, s]).start()
        pltpu.make_async_copy(cv_s.at[pl.ds(p, 1)],
                              cv_out_ref.at[pl.ds(p, 1)],
                              row_sems.at[1, s]).start()

    # Masked attention over the cache, processed TWO HEADS AT A TIME.  Why:
    # Mosaic cannot split the 128-wide lane dim (reshape [.., D] ->
    # [.., H, hd] with hd=64 is an "unsupported shape cast", and 64-offset
    # lane slices are unaligned), so per-head structure is built from
    # 128-lane-aligned head PAIRS plus lane masks — every op below is a
    # broadcast, a where, or a full-lane/T-axis reduction, all of which
    # Mosaic lays out natively.  At decode sizes (S~8, T~96) this is ~1
    # MFLOP of VPU work; the MXU has nothing to chew on here.
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = ck_s[:].astype(jnp.float32)                          # [T, S, D]
    vf = cv_s[:].astype(jnp.float32)
    mask2 = mask_ref[:]                                       # [T, S, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 2 * hd), 2)
    first_head = (lane < hd).astype(jnp.float32)              # [1,1,128]
    pairs = []
    for p_idx in range(heads // 2):
        lo, hi = 2 * hd * p_idx, 2 * hd * (p_idx + 1)         # 128-aligned
        q_pair = jnp.expand_dims(qf[:, lo:hi], 0)             # [1, S, 128]
        prod = q_pair * kf[:, :, lo:hi]                       # [T, S, 128]
        # Segmented score sums via lane masks, kept BROADCAST over the 128
        # lanes: Mosaic rejects the 2-D [T, S] intermediates (sublane
        # reductions with implicit output dims), so the whole softmax runs
        # in the 3-D tiled domain — reductions only over the untiled T axis
        # or full lanes with keepdims, both natively supported.
        s_all = jnp.sum(prod, axis=-1, keepdims=True)         # [T, S, 1]
        s_0 = jnp.sum(prod * first_head, axis=-1, keepdims=True)
        scores = jnp.where(first_head > 0, s_0, s_all - s_0)  # [T, S, 128]
        scores = scores + mask2
        m = jnp.max(scores, axis=0, keepdims=True)            # [1, S, 128]
        e = jnp.exp(scores - m)
        probs = e / jnp.sum(e, axis=0, keepdims=True)         # [T, S, 128]
        pairs.append(jnp.sum(probs * vf[:, :, lo:hi], axis=0))  # [S, 128]
    ctx = jnp.concatenate(pairs, axis=-1).astype(x_ref.dtype)
    y = _proj(ctx, wout_ref, sout_ref, bout_ref)
    xo_ref[:] = (x32 + y).astype(xo_ref.dtype)
    # Slab write-backs must land before the kernel retires (reconstructing
    # the same descriptor is the documented wait idiom).
    for s in range(S):
        p = pos_ref[s]
        pltpu.make_async_copy(ck_s.at[pl.ds(p, 1)],
                              ck_out_ref.at[pl.ds(p, 1)],
                              row_sems.at[0, s]).wait()
        pltpu.make_async_copy(cv_s.at[pl.ds(p, 1)],
                              cv_out_ref.at[pl.ds(p, 1)],
                              row_sems.at[1, s]).wait()


def _mlp_body(x_ref, lns_ref, lnb_ref, w1_ref, b1_ref, s1_ref, w2_ref,
              b2_ref, s2_ref, xo_ref, *, eps: float, approx_gelu: bool):
    x32 = x_ref[:].astype(jnp.float32)
    h = _ln_f32(x32, lns_ref[:].astype(jnp.float32),
                lnb_ref[:].astype(jnp.float32), eps).astype(x_ref.dtype)
    h1 = _proj(h, w1_ref, s1_ref, b1_ref)
    h1 = jax.nn.gelu(h1, approximate=approx_gelu).astype(x_ref.dtype)
    h2 = _proj(h1, w2_ref, s2_ref, b2_ref)
    xo_ref[:] = (x32 + h2).astype(xo_ref.dtype)


def _mlp_kernel(x_ref, lns_ref, lnb_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                xo_ref, *, eps: float, approx_gelu: bool):
    _mlp_body(x_ref, lns_ref, lnb_ref, w1_ref, b1_ref, None, w2_ref, b2_ref,
              None, xo_ref, eps=eps, approx_gelu=approx_gelu)


def _mlp_kernel_int8(x_ref, lns_ref, lnb_ref, w1_ref, b1_ref, s1_ref,
                     w2_ref, b2_ref, s2_ref, xo_ref, *, eps: float,
                     approx_gelu: bool):
    _mlp_body(x_ref, lns_ref, lnb_ref, w1_ref, b1_ref, s1_ref, w2_ref,
              b2_ref, s2_ref, xo_ref, eps=eps, approx_gelu=approx_gelu)


def _interp(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _attn_call(kern, n_vmem_inputs, x, cache_k, cache_v, operands,
               interpret):
    """Shared pallas_call scaffolding for the bf16/int8 attention wrappers:
    identical grid spec, scratch banks, aliasing and output shapes — only
    the kernel and the VMEM-operand count differ, so a fix to e.g. the
    scratch sizing or the wait idiom applies to both lanes."""
    vspec = pl.BlockSpec(memory_space=pltpu.VMEM)
    aspec = pl.BlockSpec(memory_space=pl.ANY)
    T, S, D = cache_k.shape
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[vspec] * n_vmem_inputs + [aspec, aspec],
            out_specs=(vspec, aspec, aspec),
            scratch_shapes=[
                pltpu.VMEM((T, S, D), cache_k.dtype),   # ck_s
                pltpu.VMEM((T, S, D), cache_v.dtype),   # cv_s
                pltpu.SemaphoreType.DMA((2,)),           # pool loads
                pltpu.SemaphoreType.DMA((2, S)),         # slab write-backs
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
            jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
        ),
        # The caches are the last two operands and alias outputs 1/2 (same
        # HBM buffers); only the S fresh rows are DMA'd into them.
        input_output_aliases={n_vmem_inputs + 1: 1, n_vmem_inputs + 2: 2},
        interpret=_interp(interpret),
    )(*operands, cache_k, cache_v)


def _check_head_layout(D: int, heads: int, interpret) -> None:
    """The attention kernels build per-head structure from head-PAIR lane
    slices (Mosaic cannot split the lane dim), so they require an even head
    count — and, when actually compiled for TPU, head_dim == 64 so each
    pair is one 128-aligned lane tile (narrower slices land at unaligned
    lane offsets Mosaic rejects).  Violations otherwise surface as opaque
    dot_general/Mosaic shape errors far from the cause (ADVICE r4).
    Interpret mode (CPU tests) has no lane tiling, so only evenness binds."""
    if heads % 2 != 0:
        raise ValueError(
            f"fused decode attention requires an even head count (the "
            f"kernel iterates head PAIRS in the lane dim); got heads={heads}")
    if D % heads != 0:
        raise ValueError(
            f"fused decode attention: d_model {D} not divisible by "
            f"heads {heads}")
    if not _interp(interpret) and D // heads != 64:
        raise ValueError(
            f"fused decode attention compiled for TPU requires head_dim == "
            f"64 (two heads == one 128-lane tile; Mosaic rejects unaligned "
            f"lane slices); got D={D}, heads={heads} -> "
            f"head_dim={D // heads}")


@functools.partial(jax.jit, static_argnames=("heads", "eps", "interpret"))
def fused_attn_step(x, ln_scale, ln_bias, wqkv, bqkv, wout, bout,
                    cache_k, cache_v, pos, mask_bias, *, heads: int,
                    eps: float = 1e-5, interpret: bool | None = None):
    """One attention block of one decode step, fused.

    x [S, D]; wqkv [D, 3D] (q|k|v column order, matching models/gpt2.py's
    fused projection); cache_k/cache_v [T, S, D] (this layer's pool slice,
    time-major); pos [S] int32 write positions; mask_bias [T, S, 1] f32
    (pre-expanded so the kernel never reshapes across the lane boundary).
    Returns (x_out, cache_k, cache_v) with the caches updated in place
    (aliased buffers).
    """
    _check_head_layout(x.shape[-1], heads, interpret)
    kern = functools.partial(_attn_kernel, heads=heads, eps=eps)
    return _attn_call(kern, 8, x, cache_k, cache_v,
                      (pos, x, ln_scale, ln_bias, wqkv, bqkv, wout, bout,
                       mask_bias), interpret)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "interpret"))
def fused_attn_step_int8(x, ln_scale, ln_bias, wqkv_q, bqkv, sqkv, wout_q,
                         bout, sout, cache_k, cache_v, pos, mask_bias, *,
                         heads: int, eps: float = 1e-5,
                         interpret: bool | None = None):
    """W8A16 variant of :func:`fused_attn_step`: int8 weights + per-output
    scales stream to VMEM and dequantize on the fp32 accumulator — the
    weight bytes crossing HBM halve (the one decode lever PERF_DECODE.md's
    bf16 measurements left on the table)."""
    _check_head_layout(x.shape[-1], heads, interpret)
    kern = functools.partial(_attn_kernel_int8, heads=heads, eps=eps)
    return _attn_call(kern, 10, x, cache_k, cache_v,
                      (pos, x, ln_scale, ln_bias, wqkv_q, bqkv, sqkv,
                       wout_q, bout, sout, mask_bias), interpret)


def _mlp_call(kern, x, operands, interpret):
    """Shared pallas_call scaffolding for the bf16/int8 MLP wrappers."""
    vspec = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        in_specs=[vspec] * len(operands),
        out_specs=vspec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=_interp(interpret),
    )(*operands)


@functools.partial(jax.jit,
                   static_argnames=("eps", "approx_gelu", "interpret"))
def fused_mlp_step(x, ln_scale, ln_bias, w1, b1, w2, b2, *, eps: float = 1e-5,
                   approx_gelu: bool = True, interpret: bool | None = None):
    """One MLP block of one decode step, fused: LN + fc1 + GELU + fc2 +
    residual.  x [S, D]; w1 [D, F]; w2 [F, D]."""
    kern = functools.partial(_mlp_kernel, eps=eps, approx_gelu=approx_gelu)
    return _mlp_call(kern, x, (x, ln_scale, ln_bias, w1, b1, w2, b2),
                     interpret)


@functools.partial(jax.jit,
                   static_argnames=("eps", "approx_gelu", "interpret"))
def fused_mlp_step_int8(x, ln_scale, ln_bias, w1_q, b1, s1, w2_q, b2, s2, *,
                        eps: float = 1e-5, approx_gelu: bool = True,
                        interpret: bool | None = None):
    """W8A16 variant of :func:`fused_mlp_step`."""
    kern = functools.partial(_mlp_kernel_int8, eps=eps,
                             approx_gelu=approx_gelu)
    return _mlp_call(kern, x,
                     (x, ln_scale, ln_bias, w1_q, b1, s1, w2_q, b2, s2),
                     interpret)
