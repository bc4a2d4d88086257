"""Mellum 2 (``model_type: mellum``) — window attention beside full
attention, routed experts in every layer.

This file is the language model and nothing else: the block, the two kinds of
cache row its attention layers keep (:class:`RingRows`, :class:`FullRows`),
the two rotations, and the initializer.  The trunk, the generation programs
and the servable are models/decoder.py's, which gets the block as a
:func:`family` of two :class:`~.decoder.Kind` s.  Layer ``i`` is ``x +=
attn_i(N(x)); x += moe_i(N(x))`` with ``N`` an RMSNorm (float32 inside, a
learned weight) and no bias anywhere; after the last layer one more norm and
the head ``[hidden, vocab]`` (untied), with float32 logits.

- *Attention, both kinds.*  ``heads`` queries over ``kv_heads`` K/V heads;
  ``q`` and ``k`` are RMSNorm'd a head over its ``head_dim`` columns and then
  turned by their positions over all of them (the two halves of a head
  paired); scores over ``sqrt(head_dim)``, softmax in float32.  A slot keeps
  the normed, turned ``k`` and ``v``.
- *A window layer* (``layer_types[i] == "sliding_attention"``).  A query at
  ``p`` sees keys ``j`` with ``0 <= p - j < sliding_window``.  Rotation:
  plain, ``inv_i = theta^(-2i/head_dim)``.  What a slot keeps a layer is a
  ring of ``sliding_window`` rows, position ``p`` at row ``p mod window``: a
  decode step writes its row (over position ``p - window``, which no later
  query sees) and reads rows ``[0, min(p, window - 1)]``.  Keys are stored
  turned and a softmax does not care for order, so that span is contiguous
  and exact, and ops/decode_attention.py needs no mask of its own.  A prompt
  is read through a band (ops/flash_attention.flash_attention ``window=``),
  and its last ``window`` positions are put into the ring where they lie.
- *A full layer* (``"full_attention"``).  Causal over all positions, a row a
  position.  Rotation: YaRN (:func:`yarn_inv_freq`), cosine and sine both
  times ``attention_factor`` at every position, short or long.
- *Experts* (every layer).  ops/expert_matmul.route with ``softmax`` scoring
  over the normed row in float32: softmax over all experts, the ``top_k``
  largest, weights normalised; expert ``e`` is ``W2_e(silu(W1_e h) * (W3_e
  h))``; no shared expert, no bias in the choice, no scale.  The chip holds
  experts ``[expert_offset, expert_offset + experts_held)`` of
  ``experts_published``; the benchmark's configuration holds them all.

Assumed, as benchmark/configs/mellum2-12b-8l.json lists them (the published
``config.json`` carries none): the norms a head on q and k before the
rotation, the rotation's pairing, softmax over all experts before the
choice, pre-norm residual order, the initializer's scales, ``eos_id``.  The
"MTP head" the model card names has no key in the configuration: none is
built.
"""

from __future__ import annotations

import dataclasses
import math
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import decode_attention, expert_matmul
from ..ops.flash_attention import flash_attention
from .decoder import Family, Kind, make_servable, part
from .lfm2 import GroupedFlashRows

WINDOW, FULL = "sliding_attention", "full_attention"
_PERIOD = (WINDOW, WINDOW, WINDOW, FULL)


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    layer_types: tuple = _PERIOD * 7
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    experts_published: int = 64
    experts_held: int = 64
    expert_offset: int = 0
    top_k: int = 8
    expert_width: int = 896
    rope_theta: float = 500000.0
    # YaRN, on the full layers alone.
    yarn_factor: float = 16.0
    yarn_original_positions: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    norm_eps: float = 1e-6
    max_positions: int = 131072
    init_std: float = 0.02
    # Assumed: the tokenizer's file is not in this repository.
    eos_id: int = 2


PUBLISHED = MellumConfig()


# ---------------------------------------------------------------------------
# The two rotations
# ---------------------------------------------------------------------------

def yarn_bounds(cfg: MellumConfig) -> tuple[int, int]:
    """``(low, high)``: the pairs of a head below ``low`` keep their
    frequency, those from ``high`` on are slowed by ``yarn_factor``, and the
    ramp runs between.  ``c(r)`` is the pair that turns ``r`` times over the
    original positions."""
    def c(r):
        return (cfg.head_dim * math.log(cfg.yarn_original_positions
                                        / (2 * math.pi * r))
                / (2 * math.log(cfg.rope_theta)))

    return (max(math.floor(c(cfg.yarn_beta_fast)), 0),
            min(math.ceil(c(cfg.yarn_beta_slow)), cfg.head_dim - 1))


def inv_freq(cfg: MellumConfig, kind: str) -> np.ndarray:
    """``[head_dim / 2]`` float32 frequencies of ``kind``'s rotation: plain
    on a window layer, YaRN's blend of plain and slowed on a full one."""
    dh = cfg.head_dim
    plain = cfg.rope_theta ** (-np.arange(0, dh, 2, dtype=np.float64) / dh)
    if kind == WINDOW:
        return plain.astype(np.float32)
    low, high = yarn_bounds(cfg)
    ramp = np.clip((np.arange(dh // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0, 1)
    return (plain / cfg.yarn_factor * ramp
            + plain * (1 - ramp)).astype(np.float32)


def _norm(w, x, eps):
    """``x / rms(x) * w`` over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _normed_and_turned(w, x, pos, cfg: MellumConfig, kind: str):
    """x [B, Tq, n * head_dim]: each head RMSNorm'd over its own columns
    (``w`` [head_dim]), then turned by ``pos`` ([Tq] or [B, Tq]) by
    ``kind``'s rotation with the two halves of a head paired, in float32."""
    B, Tq, D = x.shape
    dh = cfg.head_dim
    xh = x.astype(jnp.float32).reshape(B, Tq, D // dh, dh)
    xh = xh * jax.lax.rsqrt((xh * xh).mean(-1, keepdims=True) + cfg.norm_eps)
    xh = xh * w.astype(jnp.float32)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv_freq(cfg, kind)
    scale = 1.0 if kind == WINDOW else cfg.yarn_attention_factor
    cos = (jnp.cos(ang) * scale)[..., None, :]
    sin = (jnp.sin(ang) * scale)[..., None, :]
    a, b = xh[..., : dh // 2], xh[..., dh // 2:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(B, Tq, D).astype(x.dtype)


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------

def _attention(cfg: MellumConfig, p, h, attend, pos, kind: str):
    with part("qkv"):  # the norms a head and the kind's rotation with them
        q = _normed_and_turned(p["q_norm"], h @ p["q"], pos, cfg, kind)
        k = _normed_and_turned(p["k_norm"], h @ p["k"], pos, cfg, kind)
        v = h @ p["v"]
    with part("attend"):
        a = attend(q, k, v).astype(h.dtype)
    with part("attend_out"):
        return a @ p["o"]


def _experts(cfg: MellumConfig, p, h, count):
    B_, T, D = h.shape
    rows = h.reshape(B_ * T, D)
    with part("route"):
        weights, group = expert_matmul.route(
            rows, p["router"], None, cfg.top_k, 1.0, cfg.expert_offset,
            cfg.experts_held, scoring="softmax")
    out, sizes = expert_matmul.experts(rows, p["w1"], p["w2"], weights,
                                       group, w3=p["w3"])
    count(expert_matmul.counters(sizes))
    with part("experts.unsort"):  # the sum, as the residual stream takes it
        return out.astype(h.dtype).reshape(B_, T, D)


def _layer(cfg: MellumConfig, p, x, attend, pos, count, kind: str):
    """One block over x [B, Tq, D]; ``kind`` says which attention."""
    if x.shape[1] > 1:
        # A prompt pass: this layer's weights are touched when its input is
        # there and no sooner (models/evabyte.py has the reason).
        p, x = jax.lax.optimization_barrier((p, x))
    with part("norm"):
        h = _norm(p["input_norm"], x, cfg.norm_eps)
    y = _attention(cfg, p, h, attend, pos, kind)
    with part("attend_out"):
        x = x + y
    with part("norm"):
        h = _norm(p["post_attention_norm"], x, cfg.norm_eps)
    y = _experts(cfg, p, h, count)
    with part("experts.unsort"):
        return x + y


# ---------------------------------------------------------------------------
# The cache rows of the two kinds of layer
# ---------------------------------------------------------------------------

def _on_chip() -> bool:
    return jax.default_backend() == "tpu" and jax.device_count() == 1


class FullRows(GroupedFlashRows):
    """A row a position, ``kv_heads`` K/V heads wide, read by ``heads``
    queries, in whole blocks of the decode kernel (``align`` rows), one
    prompt a prefill dispatch: models/lfm2.py's rows but for the prompt.  A
    prompt's attention keeps no ``[heads, P, P]`` array: on one TPU device
    it is ops/flash_attention.flash_attention (form ``flash``: causal,
    blocked over keys, the scores in VMEM, each K/V head read by its group
    of queries through the tile map, so nothing is repeated in HBM);
    elsewhere (the CPU, a mesh) a ``jax.numpy`` form over ``[B, heads, P,
    P]`` scores (``grouped``)."""

    window = None  # a band's width, where the rows keep one

    def prompt_form(self, batch, heads, P, head_dim) -> str:
        form = "flash" if _on_chip() else "grouped"
        return form if self.window is None else form + "_band"

    def kept(self, k, lengths, P: int):
        """The rows of K (or V) [B, P, D] a slot keeps of a prompt, from
        row 0 on: here all of them."""
        return k

    def prompt(self, heads: int, lengths, P: int, put):
        kv, W = self.kv_heads, self.window
        kernel = _on_chip()
        if not kernel:
            at = jnp.arange(P)
            keep = (at[None, :] <= at[:, None])[None] & (
                at[None, None, :] < lengths[:, None, None])
            if W is not None:
                keep &= (at[:, None] - at[None, :] < W)[None]

        def attend(p, cache, i, q, k, v):
            B = q.shape[0]
            dh = q.shape[-1] // heads
            kh, vh = k.reshape(B, P, kv, dh), v.reshape(B, P, kv, dh)
            if kernel:
                # Causal alone: a real query reads no key past its length.
                out = flash_attention(q.reshape(B, P, heads, dh), kh, vh,
                                      causal=True, window=W)
            else:
                qg = q.reshape(B, P, kv, heads // kv, dh) * dh ** -0.5
                scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kh,
                                    preferred_element_type=jnp.float32)
                scores = jnp.where(keep[:, None, None], scores, -1e9)
                probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
                out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vh)
            return ((put(cache[0], i, self.kept(k, lengths, P)),
                     put(cache[1], i, self.kept(v, lengths, P))) + cache[2:],
                    out.reshape(B, P, heads * dh))

        return attend


class RingRows(FullRows):
    """A ring of ``window`` rows: position ``p`` lies at row ``p mod
    window``, and a query at ``p`` reads rows ``[0, min(p, window - 1)]``,
    which hold exactly positions ``(p - window, p]``.  A prompt's attention
    is the band form (``flash_band``), and of a prompt of ``n`` positions
    the ring is left the last ``min(n, window)``, each where it lies."""

    def __init__(self, kv_heads: int, window: int):
        super().__init__(kv_heads)
        self.window = window

    def count(self, total: int) -> int:
        return min(total, self.window)

    def positions(self, T: int) -> int:
        """A ring holds the newest of as many positions as come."""
        return 1 << 30

    def row(self, pos, T: int):
        return pos % T

    def span(self, pos, T: int):
        return pos * 0, pos - (pos > T - 1) * (pos - (T - 1))  # min(pos, T-1)

    def kept(self, k, lengths, P: int):
        """Prompt b's last ``window`` real positions, position ``p`` at row
        ``p mod window``: the slice that holds them, rolled by where it
        starts.  A prompt shorter than the window is its own first rows."""
        W = self.window
        if P <= W:
            return k
        start = jnp.clip(lengths - W, 0, P - W)                      # [B]

        def ring(rows, at):
            return jnp.roll(jax.lax.dynamic_slice_in_dim(rows, at, W, 0),
                            at % W, axis=0)

        return jax.vmap(ring)(k, start)


# ---------------------------------------------------------------------------
# The family, the initializer
# ---------------------------------------------------------------------------

def family(cfg: MellumConfig, dtype=jnp.bfloat16) -> Family:
    """The block as models/decoder.py takes it: two kinds of K/V layer, the
    full layers first (``Family.rows`` is theirs: what the scheduler asks
    about a prompt), each layer at the number of its kind before it."""
    types = cfg.layer_types
    unknown = set(types) - {WINDOW, FULL}
    if unknown:
        raise ValueError(f"layer_types has layers of unknown kind "
                         f"{sorted(unknown)}")
    width = cfg.kv_heads * cfg.head_dim
    full = FullRows(cfg.kv_heads, decode_attention.block_rows(width, dtype))
    kinds = tuple(Kind(name, types.count(name), rows) for name, rows in (
        (FULL, full), (WINDOW, RingRows(cfg.kv_heads, cfg.sliding_window)))
        if name in types)
    names = [k.name for k in kinds]
    index = [(names.index(t), types[:i].count(t))
             for i, t in enumerate(types)]

    def head(params, x):
        w = params["head"]                        # untied: [D, V]
        return jax.lax.dot_general(x.astype(w.dtype), w,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    return Family(
        embed=lambda params, tokens, dt: params["embed"][tokens].astype(dt),
        positions=None,
        layer=(lambda p, x, attend, pos, lora=None, lora_idx=None,
               state=None, count=None, kind=None:
               _layer(cfg, p, x, attend, pos, count, kind)),
        norm=lambda params, x: _norm(params["norm"], x, cfg.norm_eps),
        head=head,
        layers=len(types), width=width, heads=cfg.heads,
        kv_heads=cfg.kv_heads, kinds=kinds, cache_index=index.__getitem__,
        counters=expert_matmul.COUNTERS,
        expert_plan=lambda rows: expert_matmul.plan_summary(
            rows, cfg.top_k, cfg.hidden_size, cfg.expert_width,
            cfg.experts_held, True, jnp.dtype(dtype).itemsize),
        eos_id=cfg.eos_id, max_positions=cfg.max_positions,
        vocab_size=cfg.vocab_size, rows=kinds[0].rows)


def _init_layer(g: np.random.Generator, cfg: MellumConfig,
                matrix_dtype) -> dict:
    D, std = cfg.hidden_size, cfg.init_std

    def w(*shape):
        a = g.standard_normal(shape, dtype=np.float32)
        a *= std
        return a.astype(matrix_dtype)

    q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    E, F = cfg.experts_held, cfg.expert_width
    return {"input_norm": np.ones((D,), np.float32),
            "post_attention_norm": np.ones((D,), np.float32),
            "q": w(D, q), "k": w(D, kv), "v": w(D, kv), "o": w(q, D),
            "q_norm": np.ones((cfg.head_dim,), np.float32),
            "k_norm": np.ones((cfg.head_dim,), np.float32),
            "router": w(D, cfg.experts_published),
            "w1": w(E, D, F), "w3": w(E, D, F), "w2": w(E, F, D)}


def init_mellum_params(seed: int = 0, cfg: MellumConfig = PUBLISHED,
                       matrix_dtype=np.float32) -> dict:
    """Seeded weights: matrices normal at ``init_std`` (in ``matrix_dtype``:
    7.6 GB of them are drawn straight into what they are staged as), norm
    weights one.  A part's draws depend on the seed and its index alone, so
    the parts are drawn side by side."""
    n = len(cfg.layer_types)

    def part(i):
        g = np.random.default_rng([seed, i])
        if i < n:
            return f"layer{i}", _init_layer(g, cfg, matrix_dtype)
        shape = ((cfg.vocab_size, cfg.hidden_size) if i == n
                 else (cfg.hidden_size, cfg.vocab_size))
        a = g.standard_normal(shape, dtype=np.float32)
        a *= cfg.init_std
        return ("embed", "head")[i - n], a.astype(matrix_dtype)

    with ThreadPoolExecutor(8) as pool:
        params = dict(pool.map(part, range(n + 2)))
    params["norm"] = np.ones((cfg.hidden_size,), np.float32)
    return params


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def config_from_arch(arch: dict) -> MellumConfig:
    """``extra.arch`` over the published sizes; it states the depth
    (``layer_types``) and the share (``experts_held``, ``expert_offset``)."""
    fields = {f.name: f.type for f in dataclasses.fields(MellumConfig)}
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
        "mellum boots from a staged native tree (tpuserve stage); no "
        "converter from the published state dict is in this repository")


def make_mellum_servable(name: str, cfg_model):
    from ..engine import weights as W
    from .vision_common import resolve_dtype

    cfg = config_from_arch(cfg_model.extra.get("arch", {}))
    params = (W.import_params(cfg_model.checkpoint, _no_converter)
              if cfg_model.checkpoint else init_mellum_params(0, cfg))
    return make_servable(name, cfg_model,
                         family(cfg, resolve_dtype(cfg_model.dtype)), params)


from ..utils.registry import register_model  # noqa: E402


@register_model("mellum", latency_class="latency")
def build_mellum(cfg):
    return make_mellum_servable("mellum", cfg)
