"""GPT-2 causal text generation — the generative-text lane of the zoo.

Beyond the reference's model surface (SURVEY §2a serves one CNN): text
generation is the workload modern serving frameworks are judged on.  This
file is GPT-2 and nothing else: the block (pre-LN attention + tanh-GELU MLP,
learned positions, a head tied to ``wte``; bf16 matmuls / fp32 LayerNorm +
logits), its initializer, its int8 and regime-routed weight trees, its
adapter targets and TP rules.  The cache, the generation programs and the
servable are models/decoder.py's, which gets the block as a :func:`family`.

Weight import from HF ``gpt2``-family torch checkpoints
(``engine/weights.convert_gpt2`` — torch Conv1D stores [in, out] so kernels
map without transpose; the fused c_attn is split into q/k/v so the Megatron
TP rules shard whole heads).  Config is checkpoint-driven
(``config_from_params``): gpt2-medium/large serve with no code edits.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.logging import get_logger, log_event
from .decoder import Family, make_servable, part

log = get_logger("models.gpt2")


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    d_model: int = 768
    layers: int = 12
    heads: int = 12
    ffn_dim: int = 3072
    max_positions: int = 1024
    eos_id: int = 50256
    ln_eps: float = 1e-5


SMALL = GPT2Config()


def config_from_params(params: dict) -> GPT2Config:
    """Derive GPT2Config from a converted tree's shapes.

    Head count leaves no trace in fused-projection shapes; every published
    GPT-2 size fixes head_dim=64 (small 768/12 … xl 1600/25), so ``heads =
    d_model // 64`` with the usual ``extra.arch`` escape hatch.
    """
    vocab, d_model = (int(x) for x in np.asarray(params["wte"]).shape)
    return GPT2Config(
        vocab_size=vocab,
        d_model=d_model,
        layers=sum(1 for k in params if k.startswith("layer")),
        heads=max(d_model // 64, 1),
        ffn_dim=int(np.asarray(params["layer0"]["fc1"]["kernel"]).shape[1]),
        max_positions=int(np.asarray(params["wpe"]).shape[0]),
    )


# ---------------------------------------------------------------------------
# Core math (pure functions over the param dict; GPT-2 uses tanh-approx GELU)
# ---------------------------------------------------------------------------

def _ln(p, x, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


def _dense(p, x):
    """Plain or W8A16 projection, keyed on the param node.

    The int8 lane (extra.params_dtype: "int8") rewrites layer kernels to
    ``kernel_q`` + ``scale`` at build time; the Pallas kernel keeps dequant
    in VMEM so decode's weight traffic is the int8 bytes only
    (ops/int8_matmul.py module docstring).
    """
    from ..ops.int8_matmul import dense_maybe_int8

    return dense_maybe_int8(p, x)


def _layer(p, x, cfg, attend, lora=None, lora_idx=None):
    """One transformer block: pre-LN attn + MLP, shared by prefill and decode.

    ``attend(q, k, v)`` receives this block's fresh query/key/value
    projections ([B, Tq, D], all from the same ``ln1`` activations), stores
    K/V however the caller caches, and returns the attention output
    [B, Tq, D] — the single point where the phases differ, which
    models/decoder.py's programs fill in.

    ``lora``/``lora_idx`` (docs/ADAPTERS.md): this layer's stacked
    multi-tenant adapter factors and the per-row slot indices; each dense
    output gains its row's low-rank delta (ops/lora.py) — rows at slot 0
    select the BASE output unchanged, byte-identical passthrough.  The
    fused int8 ``qkv`` path never carries adapters (guarded at build).
    """
    def ad(name, y, inp):
        if lora is None or name not in lora:
            return y
        from ..ops.lora import lora_apply

        return lora_apply(y, inp, lora[name], lora_idx)

    with part("norm"):
        h = _ln(p["ln1"], x, cfg.ln_eps)
    with part("qkv"):
        if "qkv" in p:
            # Fused projection (int8 lane): one [D, 3D] matmul instead of
            # three — 2 fewer kernel launches per layer per decode step, and
            # the W8A16 Pallas kernel amortizes its grid setup over 3x the
            # weight block.
            q_, k_, v_ = jnp.split(_dense(p["qkv"], h), 3, axis=-1)
        else:
            k_ = ad("k", _dense(p["k"], h), h)
            v_ = ad("v", _dense(p["v"], h), h)
            q_ = ad("q", _dense(p["q"], h), h)
    with part("attend"):
        ao = attend(q_, k_, v_)
    with part("attend_out"):
        x = x + ad("out", _dense(p["out"], ao), ao)
    with part("norm"):
        h = _ln(p["ln2"], x, cfg.ln_eps)
    with part("mlp"):
        h2 = jax.nn.gelu(ad("fc1", _dense(p["fc1"], h), h), approximate=True)
        return x + ad("fc2", _dense(p["fc2"], h2), h2)


def _logits(params, x):
    """Tied projection: lm head = wte (fp32 for a stable argmax/softmax).

    Int8 lane: a quantized TRANSPOSED copy (``lm_q`` [D, V] + per-vocab-row
    ``lm_scale``) replaces the wte read — at 50257x768 the lm head is a third
    of GPT-2 small's per-step weight bytes.  Output stays fp32 (the kernel
    writes its fp32 accumulator out directly).
    """
    if "lm_q" in params:
        from ..ops.int8_matmul import int8_matmul

        # lm_q is PRE-PADDED to the kernel's block alignment at build
        # (ops/int8_matmul.pad_weights) — the call-time pads are zero-width
        # and elided; the pad columns produce exactly-zero logits, sliced
        # off here so a fake vocab id can never win an argmax.
        vocab = params["wte"].shape[0]
        return int8_matmul(x.astype(jnp.bfloat16), params["lm_q"],
                           params["lm_scale"],
                           out_dtype=jnp.float32)[:, :vocab]
    # MXU-native dtypes + fp32 accumulator instead of casting the table up.
    # Bit-identical (bf16 values are exact in f32; products accumulate in
    # f32 either way).  Standalone the up-cast costs 1.4x (0.149 vs
    # 0.103 ms on the v5e at [8,768]x[50257,768]); inside the full generate
    # program XLA fuses the convert and the end-to-end step is unchanged —
    # this form just stops relying on that fusion.
    w = params["wte"]
    return jax.lax.dot_general(x.astype(w.dtype), w,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def family(cfg: GPT2Config, **tree_hooks) -> Family:
    """GPT-2's block as models/decoder.py takes it.  Positions are learned
    (``wpe``); a cache row is ``d_model`` wide, every head its own K/V.
    ``tree_hooks``: the routed lane's ``pre_tree`` / ``dec_tree``."""
    return Family(
        embed=lambda params, tokens, dtype: params["wte"].astype(dtype)[tokens],
        positions=lambda params, dtype: params["wpe"].astype(dtype),
        layer=(lambda p, x, attend, pos, lora=None, lora_idx=None:
               _layer(p, x, cfg, attend, lora, lora_idx)),
        norm=lambda params, x: _ln(params["ln_f"], x, cfg.ln_eps),
        head=_logits, layers=cfg.layers, width=cfg.d_model, heads=cfg.heads,
        eos_id=cfg.eos_id, max_positions=cfg.max_positions,
        vocab_size=cfg.vocab_size, **tree_hooks)


# ---------------------------------------------------------------------------
# Random init (offline dev mode)
# ---------------------------------------------------------------------------

def init_gpt2_params(seed: int = 0, cfg: GPT2Config = SMALL) -> dict:
    g = np.random.default_rng(seed)

    def dense(i, o):
        return {"kernel": (g.standard_normal((i, o)) * 0.02).astype(np.float32),
                "bias": np.zeros((o,), np.float32)}

    def ln(d):
        return {"scale": np.ones((d,), np.float32), "bias": np.zeros((d,), np.float32)}

    D, F = cfg.d_model, cfg.ffn_dim
    params = {
        "wte": (g.standard_normal((cfg.vocab_size, D)) * 0.02).astype(np.float32),
        "wpe": (g.standard_normal((cfg.max_positions, D)) * 0.01).astype(np.float32),
        "ln_f": ln(D),
    }
    for i in range(cfg.layers):
        params[f"layer{i}"] = {
            "ln1": ln(D), "q": dense(D, D), "k": dense(D, D), "v": dense(D, D),
            "out": dense(D, D), "ln2": ln(D), "fc1": dense(D, F), "fc2": dense(F, D),
        }
    return params


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def make_gpt2_servable(name: str, cfg_model):
    from ..engine import weights as W
    from ..parallel.mesh import GPT2_TP_RULES

    arch = {k: int(v) for k, v in dict(cfg_model.extra.get("arch", {})).items()}
    if cfg_model.checkpoint:
        params = W.import_params(cfg_model.checkpoint, W.convert_gpt2)
        cfg = dataclasses.replace(config_from_params(params), **arch)
    else:
        cfg = dataclasses.replace(SMALL, **arch) if arch else SMALL
        if cfg.vocab_size <= cfg.eos_id and "eos_id" not in arch:
            cfg = dataclasses.replace(cfg, eos_id=cfg.vocab_size - 1)
        params = init_gpt2_params(0, cfg)
    params_dtype = str(cfg_model.extra.get("params_dtype", ""))
    # Regime crossover (README "int8 decode regime table"): the round-5
    # dedicated device-trace sweep shows int8 DECODE winning at every
    # measured pool size (1.84x at 8 rows, 1.63x at 16, 1.13x at 32,
    # 1.08x at 64) — the earlier "bf16 wins at x4" datum was the whole
    # generate call, i.e. the int8 PREFILL loss this routed lane already
    # removes.  64 is the measured bracket's end (still winning); beyond
    # it the margin is heading to parity, so the bf16 fallback remains.
    crossover = int(cfg_model.extra.get("int8_crossover_batch", 64))

    def _quantize(tree):
        """fp32 host tree -> W8A16 tree (int8 layer kernels + per-channel
        scales, quantized+padded lm head, bf16 at rest otherwise).

        The tied lm head gets its own quantized TRANSPOSED copy while
        wte/wpe stay bf16 for the (few-row) embedding gathers.  q/k/v fuse
        into one [D, 3D] projection BEFORE quantizing (order [q|k|v],
        matching _layer's jnp.split).  Single-device only (the engine
        rejects int8/auto + mesh), so the Megatron per-head TP layout
        question never arises for the fused node.
        """
        from ..ops.int8_matmul import (DECODE_ROWS, pad_weights, plan_summary,
                                       quantize_per_channel, quantize_tree)
        from .vision_common import cast_params_at_rest

        for i in range(cfg.layers):
            lp = tree[f"layer{i}"]
            lp["qkv"] = {
                "kernel": np.concatenate(
                    [np.asarray(lp[n]["kernel"], np.float32) for n in "qkv"],
                    axis=1),
                "bias": np.concatenate(
                    [np.asarray(lp[n]["bias"], np.float32) for n in "qkv"]),
            }
            del lp["q"], lp["k"], lp["v"]
        tree = quantize_tree(tree, min_size=int(
            cfg_model.extra.get("quantize_min_size", 1 << 16)))
        lm_q, lm_scale = quantize_per_channel(
            np.asarray(tree["wte"]).T.copy(), axis=0)
        tree["lm_q"], tree["lm_scale"] = pad_weights(lm_q, lm_scale)

        # How a decode step's rows walk each matrix of a step: one plan for
        # any number of rows up to ``decode_rows``.
        log_event(log, "int8 lane built", model=name, decode_rows=DECODE_ROWS,
                  decode_plan={
                      "head": plan_summary(DECODE_ROWS, *tree["lm_q"].shape),
                      **{n: plan_summary(DECODE_ROWS, *p["kernel_q"].shape)
                         for n, p in tree["layer0"].items()
                         if "kernel_q" in p}})
        return cast_params_at_rest(tree, jnp.bfloat16)

    if (int(getattr(cfg_model, "adapter_slots", 0)) > 0
            and params_dtype in ("int8", "auto")):
        # The fused int8 qkv projection has no per-projection seam to add a
        # delta at, and the dual-tree routed lane would need the stacks in
        # BOTH trees; refuse at boot rather than silently drop tenants.
        raise ValueError(
            f"{name}: adapter_slots cannot combine with params_dtype="
            f"{params_dtype!r}; serve adapters on the bf16 lane")
    tree_hooks = {}
    if params_dtype == "int8":
        params = _quantize(params)
    elif params_dtype == "auto":
        # Regime-routed lane (VERDICT r4 next #3): hold BOTH weight trees
        # and pick per compiled program — prefill always bf16 (MXU-bound),
        # decode int8 at <= crossover rows, bf16 above.  The big bf16
        # embedding/LN leaves are SHARED into the int8 tree (placed arrays,
        # so device_put cannot duplicate them in HBM); the marginal cost of
        # "auto" over "int8" is the bf16 layer kernels, ~85 MB for small.
        from .vision_common import cast_params_at_rest

        def _copy_tree(t):
            return {k: _copy_tree(v) if isinstance(v, dict) else v
                    for k, v in t.items()}

        bf16 = jax.device_put(cast_params_at_rest(params, jnp.bfloat16))
        q = _quantize(_copy_tree(params))
        q["wte"], q["wpe"], q["ln_f"] = bf16["wte"], bf16["wpe"], bf16["ln_f"]
        for i in range(cfg.layers):
            q[f"layer{i}"]["ln1"] = bf16[f"layer{i}"]["ln1"]
            q[f"layer{i}"]["ln2"] = bf16[f"layer{i}"]["ln2"]
        params = {"bf16": bf16, "int8": q}
        # M = B·P prefill rows feed the MXU, where the BERT s128 measurement
        # shows int8 losing; M = B decode rows are weight-bandwidth-bound.
        tree_hooks = {"pre_tree": lambda p: p["bf16"],
                      "dec_tree": (lambda p, rows: p["int8"]
                                   if rows <= crossover else p["bf16"])}

    D, F = cfg.d_model, cfg.ffn_dim
    return make_servable(
        name, cfg_model, family(cfg, **tree_hooks), params,
        adapter_dims={"q": (D, D), "k": (D, D), "v": (D, D), "out": (D, D),
                      "fc1": (D, F), "fc2": (F, D)},
        tp_rules=GPT2_TP_RULES)


from ..utils.registry import register_model  # noqa: E402


@register_model("gpt2", latency_class="latency")
def build_gpt2(cfg):
    return make_gpt2_servable("gpt2", cfg)
