"""JoyAI-LLM-Flash (``model_type: joyai_llm_flash``) — latent attention over
rows of one leaf, gated experts with a shared expert behind a sigmoid router.

This file is the language model and nothing else: the block, what a slot
keeps of a position and how the two phases read it (:class:`LatentRows`), and
the initializer.  The trunk, the generation programs and the servable are
models/decoder.py's, which gets the block as a :func:`family`.  Layer ``i`` is
``x += attn(N(x)); x += mlp_i(N(x))`` with ``N`` an RMSNorm (float32 inside,
a learned weight) and no bias anywhere; after the last layer one more norm
and the head ``[hidden, vocab]`` (untied), with float32 logits.

- *Latent attention*, every layer.  With ``h`` the normed row: ``c_q =
  N(h W_DQ)`` (``q_lora_rank`` wide, its own weight); ``q = c_q W_UQ``,
  ``heads`` of ``nope_dim + rope_dim``, each ``[q_nope, q_rope]``.  ``[c_raw,
  k_raw] = h W_DKV``; ``c = N(c_raw)`` (``kv_lora_rank`` wide, its own
  weight); ``k_rope = rot(k_raw)``, **one head that every query shares**;
  ``q_rope = rot(q_rope)`` a head.  ``k_nope_h = c W_UK_h``, ``v_h = c
  W_UV_h`` a head.  Scores ``(q_nope_h . k_nope_h + q_rope_h . k_rope) /
  sqrt(nope_dim + rope_dim)``, causal, softmax in float32; ``o_h = probs
  v_h``; ``out = concat_h(o_h) W_O``.  The rotation turns adjacent columns
  ``(2i, 2i + 1)`` by ``pos * theta^(-2i / rope_dim)``; no rope scaling.
- *What a slot keeps* a position a layer: ``[c, k_rope]``, normed and
  turned, ``kv_lora_rank + rope_dim`` values in **one leaf** (576 where K
  and V a head would be 10,240), stored in whole lane tiles with zeros
  after them (640: :attr:`JoyAIConfig.row_stored` has the reason).  A
  prefill expands ``k_nope`` and ``v``
  from ``c`` and attends as any model does (:meth:`LatentRows.prompt`); a
  decode step never expands: it folds ``W_UK`` into the query
  (:meth:`LatentRows.absorb`), attends over the rows themselves, whose
  first ``kv_lora_rank`` columns are the values
  (ops/decode_attention.attend_latent), and folds ``W_UV`` into the result
  (:meth:`LatentRows.expand`).  The same sums in another order.
- *The leading* ``dense_layers`` *layers' feed-forward*: ``W2(silu(W1 h) *
  (W3 h))``.
- *Experts* (every later layer).  ops/expert_matmul.route over the normed
  row in float32: sigmoid scores over all ``experts_published``, the
  ``top_k`` largest of score plus ``expert_bias`` (one group: none is shut
  out), weights the scores there over their sum, times ``routed_scale``;
  expert ``e`` is ``W2_e(silu(W1_e h) * (W3_e h))``; one shared expert of
  the same form is added for every row with weight 1.  The chip holds
  experts ``[expert_offset, expert_offset + experts_held)`` as
  models/nemotron_h.py does: the router keeps its width and its ``top_k``,
  the chip adds its own experts' part and the shared expert's whole, and
  what the others would have added is left out.

Assumed, as benchmark/configs/joyai-flash-10l.json lists them: the stored
layout of the rotated columns (the converter's business: none is in this
repository), ``W_UKV`` held as its two halves ``k_up`` and ``v_up``, the
initializer's scales, ``eos_id``.  The prediction module
(``num_nextn_predict_layers`` 1) is not built: it changes no logit of the
layers here.  ``route`` divides the chosen weights by their sum alone (the
published router adds 1e-20 to it).
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import expert_matmul
from ..ops.flash_attention import flash_attention
from .decoder import Family, Rows, make_servable, part


@dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    layers: int = 40
    heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    nope_dim: int = 128            # qk_nope_head_dim
    rope_dim: int = 64             # qk_rope_head_dim
    v_dim: int = 128               # v_head_dim
    dense_layers: int = 1          # leading layers with a dense feed-forward
    dense_width: int = 7168
    experts_published: int = 256
    experts_held: int = 256
    expert_offset: int = 0
    top_k: int = 8
    expert_width: int = 768
    routed_scale: float = 2.5
    rope_theta: float = 32e6
    norm_eps: float = 1e-6
    max_positions: int = 131072
    init_std: float = 0.02
    # Assumed: the tokenizer's file is not in this repository.
    eos_id: int = 1

    @property
    def row_width(self) -> int:
        """What a slot keeps of a position a layer: ``[c, k_rope]``."""
        return self.kv_lora_rank + self.rope_dim

    @property
    def row_stored(self) -> int:
        """Columns the leaf keeps a row in: ``row_width`` in whole lane
        tiles of 128, zeros after the row.  The chip pads a last dimension
        to whole tiles whatever the shape says, and for a leaf of 576
        columns whose 9,216 rows are a multiple of 128 its compiler would
        rather keep the *rows* minor: every segment then copied the pool
        into the layout the kernel reads and back, 7 GB each way
        (tests/test_aot_tpu_compile.py compiles the segment for a described
        v5e; PERF.md section 6, PR 55).  A leaf 640 wide costs the bytes the
        chip stored anyway and is read where it lies."""
        return -(-self.row_width // _LANES) * _LANES

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim


_LANES = 128
PUBLISHED = JoyAIConfig()


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------

def _norm(w, x, eps):
    """``x / rms(x) * w`` over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _turned(x, pos, theta: float):
    """x [B, Tq, n, rope_dim] turned by ``pos`` ([Tq] or [B, Tq]), adjacent
    columns ``(2i, 2i + 1)`` paired, in float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv      # [.., Tq, d/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _latent(cfg: JoyAIConfig, p, h, pos):
    """h [B, Tq, D] normed → the queries a head ``[q_nope, q_rope]`` [B, Tq,
    heads * qk_dim] and the position's row ``[c, k_rope, zeros]`` [B, Tq,
    row_stored], both normed and turned."""
    B, Tq, _ = h.shape
    c_q = _norm(p["q_norm"], h @ p["q_down"], cfg.norm_eps)
    q = (c_q @ p["q_up"]).reshape(B, Tq, cfg.heads, cfg.qk_dim)
    q = jnp.concatenate([
        q[..., :cfg.nope_dim],
        _turned(q[..., cfg.nope_dim:], pos, cfg.rope_theta)], axis=-1)
    down = h @ p["kv_down"]
    c = _norm(p["kv_norm"], down[..., :cfg.kv_lora_rank], cfg.norm_eps)
    k_rope = _turned(down[..., None, cfg.kv_lora_rank:], pos,
                     cfg.rope_theta)[..., 0, :]
    spare = jnp.zeros((B, Tq, cfg.row_stored - cfg.row_width), c.dtype)
    return (q.reshape(B, Tq, cfg.heads * cfg.qk_dim),
            jnp.concatenate([c, k_rope, spare], axis=-1))


def _attention(cfg: JoyAIConfig, p, h, attend, pos):
    with part("qkv"):  # the down-projections, their norms, the rotation
        q, row = _latent(cfg, p, h, pos)
    with part("attend"):
        out = attend(q, row).astype(h.dtype)
    with part("attend_out"):
        return out @ p["o"]


def _gated(h, w1, w3, w2):
    """``(silu(h @ w1) * (h @ w3)) @ w2``, the product in float32."""
    gate = jnp.dot(h, w1, preferred_element_type=jnp.float32)
    up = jnp.dot(h, w3, preferred_element_type=jnp.float32)
    return (jax.nn.silu(gate) * up).astype(h.dtype) @ w2


def _experts(cfg: JoyAIConfig, p, h, count):
    B_, T, D = h.shape
    rows = h.reshape(B_ * T, D)
    with part("route"):
        weights, group = expert_matmul.route(
            rows, p["router"], p["expert_bias"], cfg.top_k, cfg.routed_scale,
            cfg.expert_offset, cfg.experts_held)
    out, sizes = expert_matmul.experts(rows, p["w1"], p["w2"], weights,
                                       group, w3=p["w3"])
    count(expert_matmul.counters(sizes))
    with part("shared"):
        shared = _gated(h, p["shared_w1"], p["shared_w3"], p["shared_w2"])
        return out.astype(h.dtype).reshape(B_, T, D) + shared


def _layer(cfg: JoyAIConfig, p, x, attend, pos, count):
    """One block over x [B, Tq, D]; the layer's parameters say its
    feed-forward."""
    if x.shape[1] > 1:
        # A prompt pass: this layer's weights are touched when its input is
        # there and no sooner (models/evabyte.py has the reason).
        p, x = jax.lax.optimization_barrier((p, x))
    with part("norm"):
        h = _norm(p["input_norm"], x, cfg.norm_eps)
    y = _attention(cfg, p, h, attend, pos)
    with part("attend_out"):
        x = x + y
    with part("norm"):
        h = _norm(p["post_attention_norm"], x, cfg.norm_eps)
    if "router" in p:
        y = _experts(cfg, p, h, count)
        with part("shared"):
            return x + y
    with part("mlp"):
        return x + _gated(h, p["w1"], p["w3"], p["w2"])


# ---------------------------------------------------------------------------
# What a slot keeps, and how the two phases read it
# ---------------------------------------------------------------------------

class LatentRows(Rows):
    """A row a position, one leaf: ``[c, k_rope, zeros]``, whose first
    ``values`` (``kv_lora_rank``) columns a decode step sums.  One prompt a
    prefill dispatch."""

    def __init__(self, cfg: JoyAIConfig):
        self.cfg = cfg
        self.values = cfg.kv_lora_rank

    def prefill_batch(self, bucket: int) -> int:
        return 1

    def prompt_form(self, batch, heads, P, head_dim) -> str:
        if jax.default_backend() == "tpu" and jax.device_count() == 1:
            return "flash_mla"
        return "mla"

    def prompt(self, heads: int, lengths, P: int, put):
        """The non-absorbed form: K and V a head expanded from ``c``, keys
        ``[k_nope_h, k_rope]`` of ``qk_dim`` and values of ``v_dim``.  On
        one TPU device ops/flash_attention.flash_attention (``flash_mla``:
        causal, blocked over keys, the scores in VMEM, the values narrower
        than the keys); elsewhere (the CPU, a mesh) a ``jax.numpy`` form
        over ``[B, heads, P, P]`` scores (``mla``)."""
        cfg = self.cfg
        kernel = self.prompt_form(lengths.shape[0], heads, P, None) \
            == "flash_mla"
        if not kernel:
            at = jnp.arange(P)
            keep = (at[None, :] <= at[:, None])[None] & (
                at[None, None, :] < lengths[:, None, None])

        def attend(p, cache, i, q, row, _=None):
            B = q.shape[0]
            with part("qkv"):  # the up-projections, and the keys built
                c = row[..., :self.values]
                k_rope = row[..., self.values:cfg.row_width]
                k_nope = (c @ p["k_up"]).reshape(B, P, heads, cfg.nope_dim)
                v = (c @ p["v_up"]).reshape(B, P, heads, cfg.v_dim)
                k = jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_rope[:, :, None, :], (B, P, heads, cfg.rope_dim))],
                    axis=-1)
                qh = q.reshape(B, P, heads, cfg.qk_dim)
            if kernel:
                # Causal alone: a real query reads no key past its length.
                out = flash_attention(qh, k, v, causal=True)
            else:
                scores = jnp.einsum("bqhd,bkhd->bhqk", qh * cfg.qk_dim ** -0.5,
                                    k, preferred_element_type=jnp.float32)
                scores = jnp.where(keep[:, None], scores, -1e9)
                probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
                out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
            return ((put(cache[0], i, row),) + cache[1:],
                    out.reshape(B, P, heads * cfg.v_dim))

        return attend

    def absorb(self, p, q):
        """``[q_nope_h W_UK_h^T, q_rope_h, zeros]`` a head over
        ``sqrt(qk_dim)``: head ``h``'s query against the row's own
        columns."""
        cfg = self.cfg
        S, Tq, _ = q.shape
        with part("qkv"):
            qh = q.reshape(S, Tq, cfg.heads, cfg.qk_dim)
            q_lat = jnp.einsum(
                "sqhd,chd->sqhc", qh[..., :cfg.nope_dim],
                p["k_up"].reshape(self.values, cfg.heads, cfg.nope_dim),
                preferred_element_type=jnp.float32).astype(q.dtype)
            spare = jnp.zeros((S, Tq, cfg.heads,
                               cfg.row_stored - cfg.row_width), q.dtype)
            out = jnp.concatenate([q_lat, qh[..., cfg.nope_dim:], spare],
                                  axis=-1)
            return (out * cfg.qk_dim ** -0.5).reshape(
                S, Tq, cfg.heads * cfg.row_stored)

    def expand(self, p, out):
        """``o_lat_h W_UV_h`` a head."""
        cfg = self.cfg
        S, Tq, _ = out.shape
        with part("qkv"):
            return jnp.einsum(
                "sqhc,chd->sqhd", out.reshape(S, Tq, cfg.heads, self.values),
                p["v_up"].reshape(self.values, cfg.heads, cfg.v_dim),
                preferred_element_type=jnp.float32).astype(out.dtype).reshape(
                    S, Tq, cfg.heads * cfg.v_dim)


# ---------------------------------------------------------------------------
# The family, the initializer
# ---------------------------------------------------------------------------

def family(cfg: JoyAIConfig, dtype=jnp.bfloat16) -> Family:
    """The block as models/decoder.py takes it: every layer keeps a row of
    one leaf, ``row_stored`` wide."""
    def head(params, x):
        w = params["head"]                        # untied: [D, V]
        return jax.lax.dot_general(x.astype(w.dtype), w,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    return Family(
        embed=lambda params, tokens, dt: params["embed"][tokens].astype(dt),
        positions=None,
        layer=(lambda p, x, attend, pos, lora=None, lora_idx=None,
               state=None, count=None:
               _layer(cfg, p, x, attend, pos, count)),
        norm=lambda params, x: _norm(params["norm"], x, cfg.norm_eps),
        head=head,
        layers=cfg.layers, width=cfg.row_stored, heads=cfg.heads, kv_heads=1,
        counters=expert_matmul.COUNTERS,
        expert_plan=lambda rows: expert_matmul.plan_summary(
            rows, cfg.top_k, cfg.hidden_size, cfg.expert_width,
            cfg.experts_held, True, jnp.dtype(dtype).itemsize),
        eos_id=cfg.eos_id, max_positions=cfg.max_positions,
        vocab_size=cfg.vocab_size, rows=LatentRows(cfg))


def _init_layer(i: int, g: np.random.Generator, cfg: JoyAIConfig,
                matrix_dtype) -> dict:
    D, std = cfg.hidden_size, cfg.init_std

    def w(*shape):
        a = g.standard_normal(shape, dtype=np.float32)
        a *= std
        return a.astype(matrix_dtype)

    H = cfg.heads
    p = {"input_norm": np.ones((D,), np.float32),
         "post_attention_norm": np.ones((D,), np.float32),
         "q_down": w(D, cfg.q_lora_rank),
         "q_norm": np.ones((cfg.q_lora_rank,), np.float32),
         "q_up": w(cfg.q_lora_rank, H * cfg.qk_dim),
         "kv_down": w(D, cfg.row_width),
         "kv_norm": np.ones((cfg.kv_lora_rank,), np.float32),
         "k_up": w(cfg.kv_lora_rank, H * cfg.nope_dim),
         "v_up": w(cfg.kv_lora_rank, H * cfg.v_dim),
         "o": w(H * cfg.v_dim, D)}
    if i < cfg.dense_layers:
        F = cfg.dense_width
        p.update(w1=w(D, F), w3=w(D, F), w2=w(F, D))
    else:
        E, F = cfg.experts_held, cfg.expert_width
        p.update(router=w(D, cfg.experts_published),
                 expert_bias=np.zeros((cfg.experts_published,), np.float32),
                 w1=w(E, D, F), w3=w(E, D, F), w2=w(E, F, D),
                 shared_w1=w(D, F), shared_w3=w(D, F), shared_w2=w(F, D))
    return p


def init_joyai_params(seed: int = 0, cfg: JoyAIConfig = PUBLISHED,
                      matrix_dtype=np.float32) -> dict:
    """Seeded weights: matrices normal at ``init_std`` (in ``matrix_dtype``:
    4.5 GB of them are drawn straight into what they are staged as), norm
    weights one, the routers' ``expert_bias`` zero (the benchmark stages it
    so).  A part's draws depend on the seed and its index alone, so
    the parts are drawn side by side."""
    n = cfg.layers

    def part(i):
        g = np.random.default_rng([seed, i])
        if i < n:
            return f"layer{i}", _init_layer(i, g, cfg, matrix_dtype)
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

def config_from_arch(arch: dict) -> JoyAIConfig:
    """``extra.arch`` over the published sizes; it states the depth
    (``layers``) and the share (``experts_held``, ``expert_offset``)."""
    fields = {f.name: f.type for f in dataclasses.fields(JoyAIConfig)}
    cast = {"int": int, "float": float}
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
        "joyai boots from a staged native tree (tpuserve stage); no converter "
        "from the published state dict is in this repository")


def make_joyai_servable(name: str, cfg_model):
    from ..engine import weights as W
    from .vision_common import resolve_dtype

    cfg = config_from_arch(cfg_model.extra.get("arch", {}))
    params = (W.import_params(cfg_model.checkpoint, _no_converter)
              if cfg_model.checkpoint else init_joyai_params(0, cfg))
    return make_servable(name, cfg_model,
                         family(cfg, resolve_dtype(cfg_model.dtype)), params)


from ..utils.registry import register_model  # noqa: E402


@register_model("joyai", latency_class="latency")
def build_joyai(cfg):
    return make_joyai_servable("joyai", cfg)
