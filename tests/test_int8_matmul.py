"""ops/int8_matmul.py — the W8A16 Pallas kernel, interpret-mode on CPU.

The contract under test: int8_matmul(x, w_q, scale) must equal the plain XLA
reference ``x @ (w_q * scale)`` computed in the SAME dtypes (bf16 operands,
fp32 accumulate) — i.e. the kernel introduces no error beyond quantization
itself, which quantize_per_channel's round-trip test bounds separately.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.ops import int8_matmul as int8_module
from pytorch_zappa_serverless_tpu.ops.int8_matmul import (
    dense_maybe_int8, int8_matmul, padded_columns, plan, plan_summary,
    quantize_per_channel, quantize_tree, vmem_bytes)


def _reference(x, w_q, scale):
    w = (w_q.astype(np.float32) * scale[None, :]).astype(jnp.bfloat16)
    return (x.astype(jnp.bfloat16) @ w).astype(np.float32)


@pytest.mark.parametrize("m,k,n", [
    (8, 768, 768),      # GPT-2 decode qkv shape (M = slot batch)
    (16, 768, 3072),    # fc1
    (8, 3072, 768),     # fc2
    (128, 768, 1024),   # the most rows the decode plan takes
    (3, 100, 50),       # everything ragged / below one tile
    (16, 1280, 3840),   # GPT-2 large's decode step at 16 slots: qkv,
    (16, 1280, 1280),   # out,
    (16, 1280, 5120),   # fc1,
    (16, 5120, 1280),   # fc2
    (16, 1280, 1000),   # a ragged wide N
    (8, 16384, 256),    # decode rows, a K no block holds whole
    (144, 1280, 640),   # prefill rows: K walked in two, the carry
])
def test_matches_reference(m, k, n):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((m, k)).astype(np.float32) * 0.5
    w = rng.standard_normal((k, n)).astype(np.float32) * 0.02
    w_q, scale = quantize_per_channel(w, axis=0)

    got = np.asarray(int8_matmul(jnp.asarray(x, jnp.bfloat16),
                                 jnp.asarray(w_q), jnp.asarray(scale)),
                     np.float32)
    want = np.asarray(_reference(x, w_q, scale))
    # Both sides accumulate in fp32 over bf16 products; differences come only
    # from K-blocked summation order — a few ULP at these magnitudes.
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def _tiles(dim):
    return -(-dim // 128) * 128


def _today(m, k, n):
    """The blocks every call had before the plan: divisors within 256 rows,
    1024 of K and 512 of N."""
    block = int8_module._block
    return block(m, 256, 16), block(k, 1024, 128), block(n, 512, 128)


# GPT-2 large's decode step at 16 slots (qkv, out, fc1, fc2, a ragged N, the
# head), XL's widths at 8, GPT-2 small's four, two of Whisper tiny's.
LARGE_STEP = {(16, 1280, 3840): 36, (16, 1280, 1280): 36, (16, 1280, 5120): 36,
              (16, 5120, 1280): 36, (16, 1280, 50257): 1}
PLAN_SHAPES = [*LARGE_STEP, (16, 1280, 1000), (8, 1600, 6400), (8, 6400, 1600),
               (8, 768, 2304), (8, 768, 768), (8, 768, 3072), (8, 3072, 768),
               (8, 384, 384), (8, 1536, 384)]


@pytest.mark.parametrize("m,k,n", PLAN_SHAPES, ids=str)
def test_plan(m, k, n):
    """The plan alone, no kernel run."""
    k_p, n_p = _tiles(k), padded_columns(k, n)
    bm, bk, bn = plan(m, k_p, n_p)
    # Blocks divide the extents as stored, and storing costs under 1% more
    # than the tiles themselves; stored once, an extent stays as it is.
    assert bm == 16 and k_p % bk == 0 and n_p % bn == 0
    assert _tiles(n) <= n_p <= 1.01 * _tiles(n) and n_p % 128 == 0
    assert padded_columns(k, n_p) == n_p
    assert plan(m, k, n)[1] == bk and _tiles(n) % plan(m, k, n)[2] == 0
    # They fit the VMEM the kernel is given, float32 logits included.
    assert (vmem_bytes(bm, bk, bn, jnp.bfloat16, jnp.float32)
            <= int8_module._VMEM_BYTES)
    assert bk * bn <= int8_module._BLOCK_BYTES
    # K is whole wherever a block of 128 columns of it fits the budget.
    assert bk == k_p or k_p * 128 > int8_module._BLOCK_BYTES
    # A block to fetch while another is multiplied, unless the whole matrix
    # is one block's bytes: then it is one block.
    assert (n_p // bn >= 2) == (k_p * n_p > int8_module._BLOCK_BYTES)
    # Rows past a decode step's keep the blocks they had.
    for rows in (256, 768, 6144):
        assert plan(rows, k, n) == _today(rows, k, n)
        assert plan(rows, k_p, n_p) == _today(rows, k_p, n_p)


def test_plan_of_a_decode_step_of_gpt2_large():
    """Grid steps and weight bytes of one decode step, all 145 calls."""
    steps = bytes_ = steps_before = bytes_before = 0
    for (m, k, n), calls in LARGE_STEP.items():
        n_p = padded_columns(k, n)
        steps += calls * plan_summary(m, k, n_p)["grid_steps"]
        bytes_ += calls * k * n_p
        _, bk, bn = _today(m, k, n)
        steps_before += calls * (k // bk) * (_tiles(n) // bn)
        bytes_before += calls * k * _tiles(n)
    assert steps_before == 2962 and steps < 1000
    assert bytes_ <= 1.01 * bytes_before


def test_quantization_error_bounded():
    rng = np.random.default_rng(1)
    w = rng.standard_normal((768, 768)).astype(np.float32) * 0.02
    w_q, scale = quantize_per_channel(w, axis=0)
    back = w_q.astype(np.float32) * scale[None, :]
    # Symmetric per-channel: max error is scale/2 = absmax/254 per column.
    col_absmax = np.abs(w).max(axis=0)
    assert np.all(np.abs(back - w) <= col_absmax / 254 + 1e-9)


def test_quantize_tree_rewrites_kernels_only():
    params = {
        "wte": np.ones((512, 256), np.float32),  # not under a "kernel" key
        "layer0": {
            "q": {"kernel": np.random.default_rng(2).standard_normal(
                (512, 512)).astype(np.float32), "bias": np.zeros(512, np.float32)},
            "ln1": {"scale": np.ones(512, np.float32),
                    "bias": np.zeros(512, np.float32)},
        },
    }
    q = quantize_tree(params, min_size=1024)
    assert q["layer0"]["q"]["kernel_q"].dtype == jnp.int8
    assert q["layer0"]["q"]["scale"].shape == (512,)
    assert "kernel" not in q["layer0"]["q"]
    assert q["layer0"]["q"]["bias"].dtype == np.float32
    assert q["layer0"]["ln1"]["scale"].dtype == np.float32  # norms untouched
    assert q["wte"].dtype == np.float32                     # embeddings untouched


def test_quantize_tree_respects_min_size():
    params = {"tiny": {"kernel": np.ones((8, 8), np.float32)}}
    q = quantize_tree(params, min_size=1024)
    assert "kernel" in q["tiny"] and "kernel_q" not in q["tiny"]


def test_dense_maybe_int8_dispatch():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2, 5, 256)).astype(np.float32)
    w = rng.standard_normal((256, 128)).astype(np.float32) * 0.05
    b = rng.standard_normal((128,)).astype(np.float32)
    plain = {"kernel": jnp.asarray(w), "bias": jnp.asarray(b)}
    w_q, scale = quantize_per_channel(w, axis=0)
    quant = {"kernel_q": jnp.asarray(w_q), "scale": jnp.asarray(scale),
             "bias": jnp.asarray(b)}

    y_plain = np.asarray(dense_maybe_int8(plain, jnp.asarray(x, jnp.bfloat16)),
                         np.float32)
    y_quant = np.asarray(dense_maybe_int8(quant, jnp.asarray(x, jnp.bfloat16)),
                         np.float32)
    assert y_quant.shape == (2, 5, 128)
    # Quantization error at these magnitudes stays small in relative terms.
    err = np.abs(y_quant - y_plain) / (np.abs(y_plain) + 1e-3)
    assert np.median(err) < 0.05
