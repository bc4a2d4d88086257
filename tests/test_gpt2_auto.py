"""GPT-2 regime-routed lane (extra.params_dtype: "auto") — VERDICT r4 #3.

One servable holds BOTH weight trees and routes per compiled program:
prefill always bf16 (MXU-bound), decode int8 at batch <= crossover rows,
bf16 above.  The routing is by STATIC batch size at trace time, so every
bucket's executable bakes in one tree and there is no runtime branch.

Tested on the tiny config (interpret-mode Pallas kernel on CPU):
- the dual tree exists and the big bf16 embeddings are shared (no HBM dup);
- below the crossover the routed lane's tokens equal a pure-int8-decode
  reference (bf16 prefill + int8 decode_segment, composed by hand);
- above the crossover they equal the pure-bf16 servable exactly;
- the continuous-batching scheduler on the routed lane still matches the
  fixed-batch path token-for-token (the parity property survives routing);
- params_dtype=auto on a family without the lane, or on a mesh, fails at
  boot.
"""

import asyncio

import numpy as np
import pytest

from pytorch_zappa_serverless_tpu.config import ModelConfig, ServeConfig
from pytorch_zappa_serverless_tpu import models as _zoo  # noqa: F401
from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder

TINY_ARCH = {"vocab_size": 512, "d_model": 128, "layers": 2, "heads": 2,
             "ffn_dim": 256, "max_positions": 64, "eos_id": 511}

pytest_plugins = "aiohttp.pytest_plugin"


def _build(**extra):
    cfg = ModelConfig(name="gpt2", dtype="bfloat16", seq_buckets=(16,),
                      batch_buckets=(1, 4),
                      extra={"max_new_tokens": 8, "arch": TINY_ARCH,
                             "quantize_min_size": 1024, **extra})
    return get_model_builder("gpt2")(cfg)


@pytest.fixture(scope="module")
def sv_auto():
    return _build(params_dtype="auto", int8_crossover_batch=2)


def _inputs(batch, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(1, 500, (batch, 16)).astype(np.int32),
            "length": np.full((batch,), 16, np.int32),
            "temperature": np.zeros((batch,), np.float32),
            "seed": np.zeros((batch,), np.int32),
            "top_k": np.zeros((batch,), np.int32),
            "top_p": np.ones((batch,), np.float32),
            "repetition_penalty": np.ones((batch,), np.float32)}


def test_dual_tree_shape_and_sharing(sv_auto):
    p = sv_auto.params
    assert set(p) == {"bf16", "int8"}
    assert p["int8"]["layer0"]["qkv"]["kernel_q"].dtype == np.int8
    assert "qkv" not in p["bf16"]["layer0"]  # bf16 half keeps split q/k/v
    # The big embedding tables are the SAME placed arrays in both trees.
    assert p["int8"]["wte"] is p["bf16"]["wte"]
    assert p["int8"]["wpe"] is p["bf16"]["wpe"]


def test_small_batch_routes_int8_decode(sv_auto):
    """b1 <= crossover: tokens equal bf16-prefill + int8-decode composed by
    hand, AND poisoning the int8 tree's lm head changes the b1 output —
    a structural proof the b1 program reads the int8 tree (greedy chains
    alone can coincide across lanes on a random-init model, which made a
    tokens-differ assertion vacuous)."""
    import jax
    import jax.numpy as jnp

    from pytorch_zappa_serverless_tpu.models import decoder as D
    from pytorch_zappa_serverless_tpu.models import gpt2 as G

    cfg = G.GPT2Config(**TINY_ARCH)
    inputs = _inputs(1)
    fn = jax.jit(sv_auto.apply_fn)
    got = np.asarray(fn(sv_auto.params, inputs)["tokens"])
    by_hand = G.family(cfg, pre_tree=lambda p: p["bf16"],
                       dec_tree=lambda p, rows: p["int8"])
    want = np.asarray(D.generate(
        by_hand, sv_auto.params, jnp.asarray(inputs["input_ids"]),
        jnp.asarray(inputs["length"]), jnp.asarray(inputs["temperature"]),
        jnp.asarray(inputs["seed"]), 8))
    np.testing.assert_array_equal(got, want)
    # Poison: zero the int8 lm-head scales -> every int8-decoded logit is 0
    # -> argmax 0 from the second token on.  b1 must change.
    poisoned = dict(sv_auto.params)
    poisoned["int8"] = dict(sv_auto.params["int8"])
    poisoned["int8"]["lm_scale"] = jnp.zeros_like(
        sv_auto.params["int8"]["lm_scale"])
    got_pois = np.asarray(fn(poisoned, inputs)["tokens"])
    assert not np.array_equal(got, got_pois)
    assert (got_pois[0, 1:] == 0).all()  # all-zero logits argmax to id 0


def test_large_batch_routes_bf16(sv_auto):
    """b4 > crossover: the routed lane IS the bf16 lane token-for-token,
    and poisoning the int8 tree does NOT touch the b4 program."""
    import jax
    import jax.numpy as jnp

    sv_bf16 = _build()  # params_dtype unset -> plain fp32/bf16-compute lane
    inputs = _inputs(4)
    fn = jax.jit(sv_auto.apply_fn)
    got = np.asarray(fn(sv_auto.params, inputs)["tokens"])
    # The plain servable keeps fp32 at-rest weights in tests (the engine
    # applies the serving-profile bf16 cast); cast here to compare like
    # with like.
    from pytorch_zappa_serverless_tpu.models.vision_common import (
        cast_params_at_rest)

    ref_params = cast_params_at_rest(sv_bf16.params, jnp.bfloat16)
    want = np.asarray(jax.jit(sv_bf16.apply_fn)(ref_params,
                                                inputs)["tokens"])
    np.testing.assert_array_equal(got, want)
    poisoned = dict(sv_auto.params)
    poisoned["int8"] = dict(sv_auto.params["int8"])
    poisoned["int8"]["lm_scale"] = jnp.zeros_like(
        sv_auto.params["int8"]["lm_scale"])
    np.testing.assert_array_equal(
        got, np.asarray(fn(poisoned, inputs)["tokens"]))


async def test_scheduler_parity_survives_routing(tmp_path):
    """Continuous lane on auto: same tokens as the fixed-batch path."""
    from pytorch_zappa_serverless_tpu.engine.loader import build_engine
    from pytorch_zappa_serverless_tpu.serving.generation import (
        GenerationScheduler)

    cfg = ServeConfig(
        compile_cache_dir=str(tmp_path / "xla"),
        warmup_at_boot=False,
        models=[ModelConfig(
            name="gpt2", dtype="bfloat16", batch_buckets=(1,),
            seq_buckets=(16,), coalesce_ms=1.0,
            extra={"max_new_tokens": 8, "arch": TINY_ARCH,
                   "quantize_min_size": 1024, "params_dtype": "auto",
                   "int8_crossover_batch": 2, "gen_slots": 2,
                   "segment_tokens": 3})])
    eng = build_engine(cfg)
    try:
        cm = eng.model("gpt2")
        sched = GenerationScheduler(cm, eng.runner, cm.cfg).start()
        try:
            sample = cm.servable.preprocess(
                {"input_ids": list(range(1, 9))})
            got = await asyncio.wait_for(sched.submit(sample).done, 120)
            want = cm.run_batch([sample])[0][0]["tokens"]
            assert got == want
        finally:
            await sched.stop()
    finally:
        eng.shutdown()


def test_auto_rejected_without_lane_and_on_mesh():
    from pytorch_zappa_serverless_tpu.engine.compiled import CompiledModel
    from pytorch_zappa_serverless_tpu.parallel.mesh import make_mesh

    # A family whose builder ignores params_dtype=auto -> no dual tree.
    cfg = ModelConfig(name="resnet18", batch_buckets=(1,),
                      extra={"image_size": 32, "resize_to": 40,
                             "params_dtype": "auto"})
    sv = get_model_builder("resnet18")(cfg)
    with pytest.raises(ValueError, match="auto"):
        CompiledModel(sv, cfg)

    cfg = ModelConfig(name="gpt2", seq_buckets=(16,), batch_buckets=(2,),
                      extra={"max_new_tokens": 8, "arch": TINY_ARCH,
                             "quantize_min_size": 1024,
                             "params_dtype": "auto"})
    sv = get_model_builder("gpt2")(cfg)
    mesh = make_mesh({"data": 2, "model": 4})
    with pytest.raises(ValueError, match="auto"):
        CompiledModel(sv, cfg, mesh=mesh)
