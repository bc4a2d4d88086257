"""LFM2 (``model_type: lfm2_moe``) — gated short convolutions beside
grouped-query attention, dense and routed feed-forwards.

This file is the language model and nothing else: the two operators, the two
feed-forwards, how a prompt's grouped attention keeps its scores on the chip
(:class:`GroupedFlashRows`), and the initializer.  The trunk, the generation
programs and the servable are models/decoder.py's, which gets the block as a
:func:`family`.  Layer ``i`` is ``x += op_i(N(x)); x += ffn_i(N(x))`` with
``N`` an RMSNorm (float32 inside, a learned weight) and no bias anywhere;
after the last layer one more norm (the published ``embedding_norm``) and
the head, which is the embedding transposed, with float32 logits.

- *Gated short convolution* (``layer_types[i] == "conv"``).  ``[B | C | z] =
  h W_in``, three parts of the hidden size; ``u = B * z``; ``c_t = sum_j
  w[j] * u_{t - (L - 1) + j}`` a channel: depthwise, causal, ``conv_L_cache``
  wide, zeros before position 0, no bias, no activation; ``y = (C * c)
  W_out``.  What a slot keeps a layer is the last ``conv_L_cache - 1`` rows
  of ``u`` (``Family.state``), whatever its length: a decode step reads
  them, convolves and shifts, and a prefill leaves the last *real* rows of
  each prompt.
- *Grouped-query attention* (``"full_attention"``).  ``heads`` queries over
  ``kv_heads`` K/V heads; ``q`` and ``k`` are RMSNorm'd a head over its
  ``head_dim`` columns and then turned by their positions over all of them
  (``rope_theta``, the two halves of a head paired); causal softmax in
  float32; no bias.  A slot keeps the normed, turned ``k`` and ``v``.  A
  decode step reads them through ops/decode_attention.attend (the Mosaic
  kernel on one TPU device: the queries of a group share each block of K
  and V); a prompt through :class:`GroupedFlashRows`.
- *Dense feed-forward* (the ``dense_layers`` leading layers): ``W2(silu(W1
  h) * (W3 h))``.
- *Experts* (every later layer).  ops/expert_matmul.route over the normed
  row in float32: sigmoid scores, the ``top_k`` largest of score plus
  ``expert_bias``, weights normalised and scaled; expert ``e`` is the same
  gated form ``W2_e(silu(W1_e h) * (W3_e h))``; no shared expert.  The chip
  holds experts ``[expert_offset, expert_offset + experts_held)`` of
  ``experts_published`` as models/nemotron_h.py does; the benchmark's
  configuration holds them all.

Departure from the published router: it divides the chosen weights by their
sum plus ``1e-6``; :func:`~..ops.expert_matmul.route` divides by the sum
(5e-7 of a weight, under float32's own rounding of the sum).  Assumed, as
benchmark/configs/lfm2-24b-10l.json lists them: the head tied to the
embedding, the norms a head before the rotation, the rotation's pairing, the
convolution's gates with no activation, the initializer's scales.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import decode_attention, expert_matmul
from ..ops.flash_attention import flash_attention
from .decoder import Family, make_servable, part
from .nemotron_h import GroupedRows

_PERIOD = ("full_attention", "conv", "conv", "conv")


@dataclass(frozen=True)
class LFM2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    # Each layer's operator: "conv" or "full_attention".
    layer_types: tuple = ("conv", "conv") + _PERIOD * 9 + (
        "full_attention", "conv")
    dense_layers: int = 2          # leading layers with a dense feed-forward
    dense_width: int = 11776
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    conv_kernel: int = 3           # the published conv_L_cache
    experts_published: int = 64
    experts_held: int = 64
    expert_offset: int = 0
    top_k: int = 4
    expert_width: int = 1536
    routed_scale: float = 1.0
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_positions: int = 128000
    init_std: float = 0.02
    # Assumed: the tokenizer's file is not in this repository.
    eos_id: int = 7


PUBLISHED = LFM2Config()


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------

def _norm(w, x, eps):
    """``x / rms(x) * w`` over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _normed_and_turned(w, x, pos, cfg: LFM2Config):
    """x [B, Tq, n * head_dim]: each head RMSNorm'd over its own columns
    (``w`` [head_dim]), then turned by ``pos`` ([Tq] or [B, Tq]) with the
    two halves of a head paired, all in float32."""
    B, Tq, D = x.shape
    dh = cfg.head_dim
    xh = x.astype(jnp.float32).reshape(B, Tq, D // dh, dh)
    xh = xh * jax.lax.rsqrt((xh * xh).mean(-1, keepdims=True) + cfg.norm_eps)
    xh = xh * w.astype(jnp.float32)
    inv = cfg.rope_theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv      # [.., Tq, dh/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    a, b = xh[..., : dh // 2], xh[..., dh // 2:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(B, Tq, D).astype(x.dtype)


def _conv(cfg: LFM2Config, p, h, state):
    """h [B, Tq, D] normed → [B, Tq, D].  ``state(update)`` hands the layer's
    tail, ``([B, conv_kernel - 1, D],)``, and the prompts' lengths (None: a
    decode step)."""
    B_, T, D = h.shape
    K = cfg.conv_kernel
    f32 = jnp.float32
    gate_in, gate_out, z = jnp.split(h @ p["in_proj"], 3, axis=-1)
    u = gate_in * z
    w = p["conv_w"].astype(f32)                               # [K, D]

    def update(mine, lengths):
        (tail,) = mine
        if lengths is None:        # one token a slot, after the rows it kept
            window = jnp.concatenate([tail, u.astype(tail.dtype)], axis=1)
            conv = (window.astype(f32) * w).sum(1, keepdims=True)
            return (window[:, 1:],), conv
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0))).astype(f32)
        conv = sum(padded[:, j:j + T] * w[j] for j in range(K))
        # The last K - 1 real rows (zeros before position 0).
        at = lengths[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]
        new_tail = jnp.where(
            (at >= 0)[..., None],
            jnp.take_along_axis(u, jnp.maximum(at, 0)[..., None], 1), 0)
        return (new_tail.astype(tail.dtype),), conv

    c = state(update)
    return (gate_out * c.astype(h.dtype)) @ p["out_proj"]


def _attention(cfg: LFM2Config, p, h, attend, pos):
    with part("qkv"):  # the norms a head and the rotation with them
        q = _normed_and_turned(p["q_norm"], h @ p["q"], pos, cfg)
        k = _normed_and_turned(p["k_norm"], h @ p["k"], pos, cfg)
        v = h @ p["v"]
    with part("attend"):
        a = attend(q, k, v).astype(h.dtype)
    with part("attend_out"):
        return a @ p["o"]


def _gated(h, w1, w3, w2):
    """``(silu(h @ w1) * (h @ w3)) @ w2``, the product in float32."""
    gate = jnp.dot(h, w1, preferred_element_type=jnp.float32)
    up = jnp.dot(h, w3, preferred_element_type=jnp.float32)
    return (jax.nn.silu(gate) * up).astype(h.dtype) @ w2


def _experts(cfg: LFM2Config, p, h, count):
    B_, T, D = h.shape
    rows = h.reshape(B_ * T, D)
    with part("route"):
        weights, group = expert_matmul.route(
            rows, p["router"], p["expert_bias"], cfg.top_k, cfg.routed_scale,
            cfg.expert_offset, cfg.experts_held)
    out, sizes = expert_matmul.experts(rows, p["w1"], p["w2"], weights,
                                       group, w3=p["w3"])
    count(expert_matmul.counters(sizes))
    with part("experts.unsort"):  # the sum, as the residual stream takes it
        return out.astype(h.dtype).reshape(B_, T, D)


def _layer(cfg: LFM2Config, p, x, attend, pos, state, count):
    """One block over x [B, Tq, D]; the layer's parameters say its operator
    and its feed-forward."""
    if x.shape[1] > 1:
        # A prompt pass: this layer's weights are touched when its input is
        # there and no sooner (models/evabyte.py has the reason).
        p, x = jax.lax.optimization_barrier((p, x))
    with part("norm"):
        h = _norm(p["operator_norm"], x, cfg.norm_eps)
    if "in_proj" in p:
        with part("conv"):  # the operator whole: projections, taps, state
            x = x + _conv(cfg, p, h, state)
    else:
        y = _attention(cfg, p, h, attend, pos)
        with part("attend_out"):
            x = x + y
    with part("norm"):
        h = _norm(p["ffn_norm"], x, cfg.norm_eps)
    if "router" in p:
        y = _experts(cfg, p, h, count)
        with part("experts.unsort"):  # the sum's last term
            return x + y
    with part("mlp"):
        return x + _gated(h, p["w1"], p["w3"], p["w2"])


# ---------------------------------------------------------------------------
# The cache rows of the attention layers
# ---------------------------------------------------------------------------

class GroupedFlashRows(GroupedRows):
    """A row a position, ``kv_heads`` K/V heads wide, read by ``heads``
    queries, in whole blocks of the decode kernel (``align`` rows).  A
    prompt's attention keeps no ``[heads, P, P]`` array: on one TPU device
    it is ops/flash_attention.flash_attention (causal, blocked over keys,
    the scores in VMEM) over K and V repeated a group's times, 33 MB at
    8,192 positions beside 8.6 GB of scores; elsewhere (the CPU, a mesh)
    the ``jax.numpy`` form of :class:`GroupedRows`.  One prompt a prefill
    dispatch."""

    def __init__(self, kv_heads: int, align: int = 1):
        super().__init__(kv_heads)
        self.align = align

    def count(self, total: int) -> int:
        """Whole blocks of ``align`` rows, where a slot holds one at least."""
        if total <= self.align:
            return total
        return -(-total // self.align) * self.align

    def prefill_batch(self, bucket: int) -> int:
        return 1

    def prompt_form(self, batch, heads, P, head_dim) -> str:
        if jax.default_backend() == "tpu" and jax.device_count() == 1:
            return "flash"
        return "grouped"

    def prompt(self, heads: int, lengths, P: int, put):
        if self.prompt_form(lengths.shape[0], heads, P, None) == "grouped":
            return super().prompt(heads, lengths, P, put)
        kv = self.kv_heads

        def attend(p, cache, i, q, k, v):
            B = q.shape[0]
            dh = q.shape[-1] // heads
            # Causal alone: a real query reads no key past its own length.
            shared = [jnp.repeat(a.reshape(B, P, kv, dh), heads // kv, axis=2)
                      for a in (k, v)]
            out = flash_attention(q.reshape(B, P, heads, dh), *shared,
                                  causal=True)
            return ((put(cache[0], i, k), put(cache[1], i, v)) + cache[2:],
                    out.reshape(B, P, heads * dh))

        return attend


# ---------------------------------------------------------------------------
# The family, the initializer
# ---------------------------------------------------------------------------

def family(cfg: LFM2Config, dtype=jnp.bfloat16) -> Family:
    """The block as models/decoder.py takes it.  A layer finds its part of
    the cache at the number of layers with its operator before it: the
    attention layers share the K/V leaves, the convolutions the tail."""
    kinds = cfg.layer_types
    unknown = set(kinds) - {"conv", "full_attention"}
    if unknown:
        raise ValueError(f"layer_types has operators of unknown kind "
                         f"{sorted(unknown)}")
    index = [kinds[:i].count(k) for i, k in enumerate(kinds)]
    width = cfg.kv_heads * cfg.head_dim

    def head(params, x):
        w = params["embed"]                       # tied: [V, D]
        return jax.lax.dot_general(x.astype(w.dtype), w,
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    return Family(
        embed=lambda params, tokens, dt: params["embed"][tokens].astype(dt),
        positions=None,
        layer=(lambda p, x, attend, pos, lora=None, lora_idx=None,
               state=None, count=None:
               _layer(cfg, p, x, attend, pos, state, count)),
        norm=lambda params, x: _norm(params["norm"], x, cfg.norm_eps),
        head=head,
        layers=len(kinds), width=width, heads=cfg.heads,
        kv_heads=cfg.kv_heads,
        kv_layers=max(kinds.count("full_attention"), 1),
        state=((max(kinds.count("conv"), 1),
                (cfg.conv_kernel - 1, cfg.hidden_size), dtype),),
        cache_index=index.__getitem__,
        counters=expert_matmul.COUNTERS,
        expert_plan=lambda rows: expert_matmul.plan_summary(
            rows, cfg.top_k, cfg.hidden_size, cfg.expert_width,
            cfg.experts_held, True, jnp.dtype(dtype).itemsize),
        eos_id=cfg.eos_id, max_positions=cfg.max_positions,
        vocab_size=cfg.vocab_size,
        rows=GroupedFlashRows(cfg.kv_heads,
                              decode_attention.block_rows(width, dtype)))


def _init_layer(i: int, g: np.random.Generator, cfg: LFM2Config,
                matrix_dtype) -> dict:
    D, std = cfg.hidden_size, cfg.init_std

    def w(*shape):
        a = g.standard_normal(shape, dtype=np.float32)
        a *= std
        return a.astype(matrix_dtype)

    p = {"operator_norm": np.ones((D,), np.float32),
         "ffn_norm": np.ones((D,), np.float32)}
    if cfg.layer_types[i] == "conv":
        bound = cfg.conv_kernel ** -0.5
        p.update(in_proj=w(D, 3 * D), out_proj=w(D, D),
                 conv_w=g.uniform(-bound, bound, (cfg.conv_kernel, D)).astype(
                     np.float32))
    else:
        q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        p.update(q=w(D, q), k=w(D, kv), v=w(D, kv), o=w(q, D),
                 q_norm=np.ones((cfg.head_dim,), np.float32),
                 k_norm=np.ones((cfg.head_dim,), np.float32))
    if i < cfg.dense_layers:
        F = cfg.dense_width
        p.update(w1=w(D, F), w3=w(D, F), w2=w(F, D))
    else:
        E, F = cfg.experts_held, cfg.expert_width
        p.update(router=w(D, cfg.experts_published),
                 expert_bias=np.zeros((cfg.experts_published,), np.float32),
                 w1=w(E, D, F), w3=w(E, D, F), w2=w(E, F, D))
    return p


def init_lfm2_params(seed: int = 0, cfg: LFM2Config = PUBLISHED,
                     matrix_dtype=np.float32) -> dict:
    """Seeded weights: matrices normal at ``init_std`` (in ``matrix_dtype``:
    10 GB of them are drawn straight into what they are staged as), norm
    weights one, the convolution uniform within ``conv_kernel ** -0.5``, the
    routers' ``expert_bias`` zero (the benchmark's staging balances it).  A
    layer's draws depend on the seed and its index alone, so the layers are
    drawn side by side."""
    n = len(cfg.layer_types)

    def part(i):
        g = np.random.default_rng([seed, i])
        if i < n:
            return f"layer{i}", _init_layer(i, g, cfg, matrix_dtype)
        a = g.standard_normal((cfg.vocab_size, cfg.hidden_size),
                              dtype=np.float32)
        a *= cfg.init_std
        return "embed", a.astype(matrix_dtype)

    with ThreadPoolExecutor(8) as pool:
        params = dict(pool.map(part, range(n + 1)))
    params["norm"] = np.ones((cfg.hidden_size,), np.float32)
    return params


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def config_from_arch(arch: dict) -> LFM2Config:
    """``extra.arch`` over the published sizes; it states the depth
    (``layer_types``) and the share (``experts_held``, ``expert_offset``)."""
    fields = {f.name: f.type for f in dataclasses.fields(LFM2Config)}
    cast = {"int": int, "float": float, "tuple": tuple}
    cfg = dataclasses.replace(PUBLISHED, **{
        k: cast[fields[k]](v) for k, v in dict(arch).items()})
    if not 0 <= cfg.expert_offset <= cfg.experts_published - cfg.experts_held:
        raise ValueError(
            f"experts [{cfg.expert_offset}, {cfg.expert_offset} + "
            f"{cfg.experts_held}) are not among the {cfg.experts_published} "
            "published")
    return cfg


def _no_converter(sd):
    raise NotImplementedError(
        "lfm2 boots from a staged native tree (tpuserve stage); no converter "
        "from the published state dict is in this repository")


def make_lfm2_servable(name: str, cfg_model):
    from ..engine import weights as W
    from .vision_common import resolve_dtype

    cfg = config_from_arch(cfg_model.extra.get("arch", {}))
    params = (W.import_params(cfg_model.checkpoint, _no_converter)
              if cfg_model.checkpoint else init_lfm2_params(0, cfg))
    return make_servable(name, cfg_model,
                         family(cfg, resolve_dtype(cfg_model.dtype)), params)


from ..utils.registry import register_model  # noqa: E402


@register_model("lfm2", latency_class="latency")
def build_lfm2(cfg):
    return make_lfm2_servable("lfm2", cfg)
