"""GPT-2 W8A16 int8 lane (extra.params_dtype: "int8").

Correctness is split into two separable claims, tested separately on a tiny
config (the interpret-mode Pallas kernel makes full-size CPU runs minutes):

1. **Kernel path**: the int8 servable's prefill logits must match an XLA
   reference running on the DEQUANTIZED weights — same quantization error on
   both sides, so any drift is the kernel's.  (On a random-init model the
   50k-vocab logit margins sit near zero, so comparing generated tokens
   against the *unquantized* bf16 model mostly measures argmax ties
   flipping under quantization noise — not a kernel property.)
2. **Quantization error**: bounded per-entry by scale/2
   (tests/test_int8_matmul.py::test_quantization_error_bounded).
"""

import numpy as np
import pytest

import fresh_pool

from pytorch_zappa_serverless_tpu.config import ModelConfig
from pytorch_zappa_serverless_tpu import models as _zoo  # noqa: F401
from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

TINY_ARCH = {"vocab_size": 512, "d_model": 128, "layers": 2, "heads": 2,
             "ffn_dim": 256, "max_positions": 64, "eos_id": 511}


def _build(**extra):
    cfg = ModelConfig(name="gpt2", dtype="bfloat16", seq_buckets=(16,),
                      batch_buckets=(2,),
                      extra={"max_new_tokens": 8, "arch": TINY_ARCH,
                             "quantize_min_size": 1024, **extra})
    return get_model_builder("gpt2")(cfg)


@pytest.fixture(scope="module")
def sv_q():
    return _build(params_dtype="int8")


def _dequant_params(params):
    """XLA-reference params: same values the int8 kernel computes with."""
    def walk(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for k, v in node.items():
            if k == "kernel_q":
                out["kernel"] = (np.asarray(v, np.float32)
                                 * np.asarray(node["scale"])[None, :])
            elif k == "scale" and "kernel_q" in node:
                continue
            elif isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = v
        return out

    ref = walk(params)
    # Reference ties the lm head back to (bf16) wte, dropping the quantized
    # head copy — head quantization error is bounded by the kernel tests.
    ref.pop("lm_q", None)
    ref.pop("lm_scale", None)
    return ref


def test_int8_params_rewritten(sv_q):
    l0 = sv_q.params["layer0"]
    # q/k/v fuse into one [D, 3D] projection before quantization.
    assert "q" not in l0 and "k" not in l0 and "v" not in l0
    assert l0["qkv"]["kernel_q"].dtype == np.int8
    assert l0["qkv"]["kernel_q"].shape == (128, 3 * 128)
    assert "kernel" not in l0["qkv"]
    assert l0["fc1"]["kernel_q"].dtype == np.int8
    assert sv_q.params["lm_q"].dtype == np.int8
    assert sv_q.params["lm_q"].shape[0] == sv_q.params["wte"].shape[1]
    # Embedding tables stay float for the gathers.
    assert sv_q.params["wte"].dtype != np.int8


def test_int8_prefill_matches_dequantized_reference(sv_q):
    from pytorch_zappa_serverless_tpu.models import decoder as D
    from pytorch_zappa_serverless_tpu.models import gpt2 as G

    fam = G.family(G.GPT2Config(**TINY_ARCH))
    rng = np.random.default_rng(0)
    toks = rng.integers(1, 500, (2, 16)).astype(np.int32)
    lens = np.full((2,), 16, np.int32)
    logits_q, ck_q, cv_q = fresh_pool.prefill(fam, sv_q.params, toks, lens,
                                              24)
    ref = _dequant_params({k: np.asarray(v) for k, v in sv_q.params.items()}
                          if not isinstance(sv_q.params, dict) else sv_q.params)
    logits_r, ck_r, cv_r = fresh_pool.prefill(fam, ref, toks, lens, 24)
    lq, lr = np.asarray(logits_q), np.asarray(logits_r)
    # lm head: kernel (int8 head) vs bf16 wte reference — error is head
    # quantization only, small relative to logit scale.
    assert np.abs(lq - lr).max() < 0.05 * max(np.abs(lr).max(), 1e-3)
    assert (lq.argmax(-1) == lr.argmax(-1)).all()
    # KV caches (layer matmuls through the kernel) agree to bf16 tolerance.
    np.testing.assert_allclose(np.asarray(ck_q, np.float32),
                               np.asarray(ck_r, np.float32),
                               rtol=0.05, atol=0.02)


def test_int8_generation_runs_end_to_end(sv_q):
    import jax

    rng = np.random.default_rng(1)
    ids = rng.integers(1, 500, (2, 16)).astype(np.int32)
    inputs = {"input_ids": ids,
              "length": np.full((2,), 16, np.int32),
              "temperature": np.zeros((2,), np.float32),
              "seed": np.zeros((2,), np.int32),
              "top_k": np.zeros((2,), np.int32),
              "top_p": np.ones((2,), np.float32),
              "repetition_penalty": np.ones((2,), np.float32)}
    toks = np.asarray(jax.jit(sv_q.apply_fn)(sv_q.params, inputs)["tokens"])
    assert toks.shape == (2, 8)
    assert toks.dtype == np.int32


def test_int8_rejected_on_mesh():
    """TP rules can't see kernel_q nodes and the Pallas matmul is
    single-device — the engine must refuse at boot, not mis-serve."""
    from pytorch_zappa_serverless_tpu.engine.compiled import CompiledModel
    from pytorch_zappa_serverless_tpu.parallel.mesh import make_mesh

    cfg = ModelConfig(name="gpt2", seq_buckets=(16,), batch_buckets=(2,),
                      extra={"max_new_tokens": 8, "arch": TINY_ARCH,
                             "quantize_min_size": 1024, "params_dtype": "int8"})
    sv = get_model_builder("gpt2")(cfg)
    mesh = make_mesh({"data": 2, "model": 4})
    with pytest.raises(ValueError, match="int8"):
        CompiledModel(sv, cfg, mesh=mesh)


def test_int8_memory_shrinks():
    import jax

    sv = _build()
    sv_q = _build(params_dtype="int8")

    def nbytes(tree):
        return sum(np.asarray(x).nbytes for x in jax.tree.leaves(tree))

    # fp32 at-rest vs int8 kernels + bf16 embeddings + extra int8 lm copy.
    assert nbytes(sv_q.params) < 0.45 * nbytes(sv.params)
