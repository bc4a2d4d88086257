"""GPT-2 parity vs transformers torch + ragged-prompt decode semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

import fresh_pool

from pytorch_zappa_serverless_tpu.config import ModelConfig
from pytorch_zappa_serverless_tpu.engine.weights import convert_gpt2
from pytorch_zappa_serverless_tpu.models import decoder as D
from pytorch_zappa_serverless_tpu.models import gpt2 as G

TINY_ARCH = {"d_model": 32, "layers": 2, "heads": 2, "ffn_dim": 128,
             "vocab_size": 500, "max_positions": 64}


def _greedy(params, tokens, lengths, max_new, cfg, dtype):
    """Greedy generation: temperature 0 on every row."""
    B = tokens.shape[0]
    return D.generate(G.family(cfg), params, tokens, lengths,
                      jnp.zeros((B,), jnp.float32),
                      jnp.zeros((B,), jnp.int32), max_new, dtype)


def _torch_tiny():
    from transformers import GPT2Config as HFConfig
    from transformers import GPT2LMHeadModel

    torch.manual_seed(0)
    cfg = HFConfig(vocab_size=500, n_positions=64, n_embd=32, n_layer=2,
                   n_head=2)
    return GPT2LMHeadModel(cfg).eval()


def _converted():
    tm = _torch_tiny()
    sd = {k: v.detach().numpy() for k, v in tm.state_dict().items()}
    params = convert_gpt2(sd)
    cfg = G.config_from_params(params)
    assert cfg.vocab_size == 500 and cfg.d_model == 32
    assert cfg.layers == 2 and cfg.ffn_dim == 128 and cfg.max_positions == 64
    import dataclasses

    return tm, jax.tree.map(jnp.asarray, params), dataclasses.replace(cfg, heads=2)


def test_prefill_last_logits_parity_ragged(rng):
    """Ragged prompts in one bucket: our per-row last-position logits match a
    torch forward with the matching right-pad attention mask."""
    tm, params, cfg = _converted()
    P = 8
    lengths = np.array([5, 3], np.int32)
    toks = rng.integers(1, 499, (2, P)).astype(np.int64)
    for b, n in enumerate(lengths):
        toks[b, n:] = 0
    logits, ck, cv = jax.jit(
        lambda p, t, l: fresh_pool.prefill(G.family(cfg), p, t, l, P + 4,
                                           jnp.float32))(
            params, jnp.asarray(toks.astype(np.int32)), jnp.asarray(lengths))
    mask = (np.arange(P)[None] < lengths[:, None]).astype(np.int64)
    with torch.no_grad():
        t_logits = tm(input_ids=torch.from_numpy(toks),
                      attention_mask=torch.from_numpy(mask)).logits.numpy()
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(np.asarray(logits)[b], t_logits[b, n - 1],
                                   atol=2e-3, rtol=1e-3)


def test_greedy_matches_torch_generate(rng):
    """Full generation parity: greedy continuation equals HF generate()."""
    tm, params, cfg = _converted()
    prompt = rng.integers(1, 499, (1, 6)).astype(np.int64)
    max_new = 5
    ours = np.asarray(jax.jit(
        lambda p, t, l: _greedy(p, t, l, max_new, cfg, jnp.float32))(
            params, jnp.asarray(prompt.astype(np.int32)),
            jnp.asarray([6], jnp.int32)))
    with torch.no_grad():
        theirs = tm.generate(torch.from_numpy(prompt), max_new_tokens=max_new,
                             do_sample=False, pad_token_id=0).numpy()
    np.testing.assert_array_equal(ours[0], theirs[0, 6:])


def test_ragged_rows_independent():
    """A row's output must not depend on its co-batched neighbors' lengths."""
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(0, _tiny_cfg()))
    cfg = _tiny_cfg()
    fn = jax.jit(lambda p, t, l: _greedy(p, t, l, 4, cfg, jnp.float32))
    g = np.random.default_rng(2)
    row = g.integers(1, 499, (1, 4)).astype(np.int32)
    solo = np.asarray(fn(params, jnp.asarray(np.pad(row, ((0, 0), (0, 4)))),
                         jnp.asarray([4], jnp.int32)))
    other = g.integers(1, 499, (1, 8)).astype(np.int32)
    both = np.asarray(fn(params,
                         jnp.asarray(np.concatenate(
                             [np.pad(row, ((0, 0), (0, 4))), other])),
                         jnp.asarray([4, 8], jnp.int32)))
    np.testing.assert_array_equal(solo[0], both[0])


def _tiny_cfg():
    import dataclasses

    return dataclasses.replace(G.SMALL, **TINY_ARCH, eos_id=499)


def test_eos_padding_semantics():
    params = jax.tree.map(jnp.asarray, G.init_gpt2_params(3, _tiny_cfg()))
    out = np.asarray(_greedy(
        params, jnp.asarray(np.ones((1, 4), np.int32)),
        jnp.asarray([4], jnp.int32), 8, _tiny_cfg(), jnp.float32))[0]
    seen = False
    for t in out:
        if seen:
            assert int(t) == 499
        if int(t) == 499:
            seen = True


def test_servable_end_to_end():
    servable = G.make_gpt2_servable("gpt2", ModelConfig(
        name="gpt2", dtype="float32", seq_buckets=(16,),
        extra={"max_new_tokens": 4, "arch": TINY_ARCH}))
    sample = servable.preprocess({"text": "hello tpu world"})
    assert sample["input_ids"].shape[0] == 3 and sample["length"] == 3
    spec = servable.input_spec((2, 16))
    collate = servable.meta["collate"]
    batch = collate([sample, servable.preprocess("one two")], (2, 16), spec)
    assert batch["input_ids"].shape == (2, 16)
    np.testing.assert_array_equal(batch["length"], [3, 2])
    out = jax.jit(servable.apply_fn)(servable.params, jax.device_put(batch))
    result = servable.postprocess(jax.tree.map(np.asarray, out), 0)
    assert isinstance(result["tokens"], list) and len(result["tokens"]) <= 4


def test_tp_rules_hit_gpt2():
    from jax.sharding import PartitionSpec as P

    from pytorch_zappa_serverless_tpu.parallel.mesh import make_mesh, shard_params

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    servable = G.make_gpt2_servable("gpt2", ModelConfig(
        name="gpt2", dtype="float32", seq_buckets=(16,),
        extra={"max_new_tokens": 2, "arch": TINY_ARCH}))
    mesh = make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])
    params = shard_params(mesh, servable.params, servable.meta["tp_rules"])
    assert params["layer0"]["q"]["kernel"].sharding.spec == P(None, "model")
    assert params["layer0"]["fc2"]["kernel"].sharding.spec == P("model", None)
    assert params["wte"].sharding.spec == P()


class TestSampling:
    """Per-request temperature/seed sampling: jit inputs, no recompile."""

    def _fn(self):
        params = jax.tree.map(jnp.asarray, G.init_gpt2_params(1, _tiny_cfg()))
        cfg = _tiny_cfg()
        fn = jax.jit(lambda p, t, l, temp, s: D.generate(
            G.family(cfg), p, t, l, temp, s, 6, jnp.float32))
        toks = jnp.asarray(np.random.default_rng(0).integers(
            1, 499, (2, 4)).astype(np.int32))
        lens = jnp.asarray([4, 4], jnp.int32)
        return params, fn, toks, lens

    def test_temp_zero_matches_greedy(self):
        params, fn, toks, lens = self._fn()
        zero = np.asarray(fn(params, toks, lens, jnp.zeros(2), jnp.zeros(2, jnp.int32)))
        greedy = np.asarray(_greedy(
            jax.tree.map(jnp.asarray, G.init_gpt2_params(1, _tiny_cfg())),
            toks, lens, 6, _tiny_cfg(), jnp.float32))
        np.testing.assert_array_equal(zero, greedy)

    def test_deterministic_per_seed_and_varies_across_seeds(self):
        params, fn, toks, lens = self._fn()
        temp = jnp.full((2,), 5.0, jnp.float32)  # hot: random weights need it
        a = np.asarray(fn(params, toks, lens, temp, jnp.asarray([7, 7], jnp.int32)))
        b = np.asarray(fn(params, toks, lens, temp, jnp.asarray([7, 7], jnp.int32)))
        np.testing.assert_array_equal(a, b)
        outs = [np.asarray(fn(params, toks, lens, temp,
                              jnp.asarray([s, s + 1], jnp.int32)))
                for s in range(0, 8, 2)]
        assert any(not np.array_equal(outs[0], o) for o in outs[1:]), \
            "different seeds never changed the sample"

    def test_mixed_greedy_and_sampled_rows(self):
        params, fn, toks, lens = self._fn()
        mixed = np.asarray(fn(params, toks, lens,
                              jnp.asarray([0.0, 5.0], jnp.float32),
                              jnp.asarray([0, 3], jnp.int32)))
        solo_greedy = np.asarray(fn(params, toks, lens, jnp.zeros(2),
                                    jnp.zeros(2, jnp.int32)))
        # Row 0 (temp 0) is bit-identical to the all-greedy run regardless of
        # its sampled neighbor.
        np.testing.assert_array_equal(mixed[0], solo_greedy[0])

    def test_servable_accepts_sampling_knobs(self):
        servable = G.make_gpt2_servable("gpt2", ModelConfig(
            name="gpt2", dtype="float32", seq_buckets=(8,),
            extra={"max_new_tokens": 3, "arch": TINY_ARCH}))
        s = servable.preprocess({"text": "a b", "temperature": 0.8, "seed": 42})
        assert s["temperature"] == np.float32(0.8) and s["seed"] == 42
        s = servable.preprocess("plain text")
        assert s["temperature"] == 0.0 and s["seed"] == 0
