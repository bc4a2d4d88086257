"""Nemotron-H — a decoder of three kinds of layer: Mamba-2, attention, experts.

This file is the ``nemotron_h`` language model and nothing else: the three
mixers, how the attention layer's cache rows are read by grouped queries
(:class:`GroupedRows`), and the initializer.  The trunk, the generation
programs and the servable are models/decoder.py's, which gets the block as a
:func:`family`.  ``pattern`` says which mixer each layer is (``M``, ``*``,
``E``); every layer is ``x + mixer(RMSNorm(x))`` with the residual in the
model's dtype (``residual_in_fp32: false``), then a final RMSNorm and an
untied head with float32 logits.

- *Mamba-2* (``M``).  ``in_proj`` (no bias) to ``[z | xBC | dt]``; ``xBC =
  silu(conv(xBC))``, a causal depthwise convolution of ``conv_kernel`` with
  bias; ``xBC`` splits into ``x`` (``mamba_heads`` heads of
  ``mamba_head_dim``) and ``B``, ``C`` (``n_groups`` groups of ``ssm_state``,
  the heads of a group share them).  ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head, float32.  The state ``h`` [heads, head_dim, state] is
  float32: ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t +
  D x_t``.  Then ``RMSNorm_groups(y * silu(z))`` over ``n_groups`` groups and
  ``out_proj``.  A prompt runs the recurrence as the chunked scan of the
  Mamba-2 paper at ``chunk_size`` (:func:`ssd`): products inside a chunk as
  matmuls, states carried from chunk to chunk.  A padded position takes ``dt
  = 0``, so it neither decays nor feeds the state, and the convolution's
  tail is the last ``conv_kernel - 1`` *real* rows of each prompt.  A decode
  step is the one-step form.  What a slot keeps is the state and that tail,
  and no row a position.
- *Attention* (``*``).  ``heads`` queries over ``kv_heads`` K/V heads of
  ``head_dim``, no bias, causal, no rotation (``nemotron_h``'s attention
  applies none), in the ``jax.numpy`` forms: :class:`GroupedRows` for a
  prompt, ops/decode_attention.attend's grouped form for a step.  The Pallas
  kernels take one K/V head a query head (ROADMAP Reach A2).
- *Experts* (``E``).  The router reads the full-width row in float32
  (ops/expert_matmul.route: sigmoid scores, the ``top_k`` largest of score
  plus bias, weights normalised and scaled).  The row goes down to
  ``latent_size``; expert ``e`` is ``W2_e relu(W1_e u)^2``, not gated, no
  bias; the weighted sum goes up again; a shared expert on the full width is
  added.  **The share**: this chip holds experts ``[expert_offset,
  expert_offset + experts_held)`` of ``experts_published``; the router keeps
  its published outputs and its ``top_k``, the layer computes the part of
  the sum its own experts give, and what the absent experts would add is
  left out.  Nothing here stands in for the other chips or their exchange.

Not served: the multi-token-prediction module the published model ships
(how it joins the embedding with the hidden state is not in its config); a
step yields one token.  Assumed, as benchmark/configs/nemotron3-super-11l.json
lists them: no clamp on ``dt``; the latent projections have no bias or
activation; the initializer's scales.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import expert_matmul
from .decoder import Family, Rows, make_servable, part

_HIGHEST = jax.lax.Precision.HIGHEST
# Prompts one prefill dispatch may hold: 8 x 512 positions make 90,112
# assignment rows and 1.1 GB of temporaries (compiled for a described v5e).
PREFILL_BATCH = 8


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072       # rows of the embedding and the head here
    vocab_published: int = 131072
    hidden_size: int = 4096
    pattern: str = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEM"
                    "EMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    experts_published: int = 512
    experts_held: int = 512
    expert_offset: int = 0
    top_k: int = 22
    latent_size: int = 1024
    expert_width: int = 2688
    shared_width: int = 5376
    routed_scale: float = 5.0
    norm_eps: float = 1e-5
    max_positions: int = 262144
    init_std: float = 0.02
    # Assumed: the tokenizer's file is not in this repository.
    eos_id: int = 2

    @property
    def inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.n_groups * self.ssm_state


PUBLISHED = NemotronHConfig()


# ---------------------------------------------------------------------------
# The mixers
# ---------------------------------------------------------------------------

def _norm(w, x, eps, groups: int = 1):
    """``x / rms(x) * w`` in float32, the mean over each of ``groups``
    equal parts of the last axis."""
    x32 = x.astype(jnp.float32)
    parts = x32.reshape(*x.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt((parts * parts).mean(-1, keepdims=True)
                                  + eps)
    return (parts.reshape(x.shape) * w.astype(jnp.float32)).astype(x.dtype)


def _relu2(x, w1, w2):
    """``relu(x @ w1)^2 @ w2``, the square in float32."""
    h = jnp.dot(x, w1, preferred_element_type=jnp.float32)
    return jnp.dot(jnp.square(jnp.maximum(h, 0.0)).astype(x.dtype), w2)


def ssd(x, dt, A, B, C, chunk: int, h0):
    """The recurrence over a whole sequence as the chunked scan: x [b, L,
    G, Hg, P] (heads by group), dt [b, L, G, Hg] (zero where the position
    is padding), A [G, Hg], B, C [b, L, G, N], ``h0`` [b, G, Hg, P, N] the
    state before position 0, all float32, ``L`` whole chunks → ``(y [b, L,
    G, Hg, P] without the ``D x`` term, the state after position L - 1)``.
    Inside a chunk the products are matmuls; between chunks the state is
    carried, a chunk at a time."""
    b, L, G, Hg, P = x.shape
    nc, Q = L // chunk, chunk
    x, dt, B, C = (a.reshape(b, nc, Q, *a.shape[2:]) for a in (x, dt, B, C))
    cum = jnp.cumsum(dt * A, axis=2)                      # [b,c,Q,G,Hg] <= 0
    dx = x * dt[..., None]
    # Inside a chunk: position t reads s <= t through exp(cum_t - cum_s).
    cb = jnp.einsum("bctgn,bcsgn->bctsg", C, B, precision=_HIGHEST)
    seen = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(
        seen, cum[:, :, :, None] - cum[:, :, None, :], -jnp.inf))
    y = jnp.einsum("bctsgh,bcsghp->bctghp", cb[..., None] * decay, dx,
                   precision=_HIGHEST)
    # What each chunk adds to the state by its end, and the states carried.
    to_end = jnp.exp(cum[:, :, -1:] - cum)                # [b,c,Q,G,Hg]
    adds = jnp.einsum("bcsghp,bcsgn->bcghpn", dx * to_end[..., None], B,
                      precision=_HIGHEST)
    whole = jnp.exp(cum[:, :, -1])                        # [b,c,G,Hg]
    h, starts = h0, []
    for c in range(nc):
        starts.append(h)
        h = whole[:, c, :, :, None, None] * h + adds[:, c]
    y = y + jnp.einsum("bctgn,bcghpn->bctghp", C, jnp.stack(starts, 1),
                       precision=_HIGHEST) * jnp.exp(cum)[..., None]
    return y.reshape(b, L, G, Hg, P), h


def _mamba(cfg: NemotronHConfig, p, x, state):
    """x [B, Tq, D] normed → [B, Tq, D].  ``state(update)`` hands the
    layer's ``(h [B, heads, head_dim, state] float32, tail [B, conv_kernel -
    1, conv_dim])`` and the prompts' lengths (None: a decode step)."""
    B_, T, _ = x.shape
    H, P, N, G = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state,
                  cfg.n_groups)
    Hg, K = H // G, cfg.conv_kernel
    f32 = jnp.float32
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [cfg.inner, cfg.inner + cfg.conv_dim], -1)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])    # [B, T, H]
    A = -jnp.exp(p["A_log"].astype(f32)).reshape(G, Hg)
    conv_w = p["conv_w"].astype(f32)                       # [K, conv_dim]

    def update(mine, lengths):
        h, tail = mine
        h = h.reshape(B_, G, Hg, P, N)
        if lengths is None:       # one token a slot, after the tail it kept
            window = jnp.concatenate([tail, xbc], axis=1)  # [B, K, conv_dim]
            new_tail = window[:, 1:]
            conv = (window.astype(f32) * conv_w).sum(1, keepdims=True)
        else:
            real = jnp.arange(T)[None, :] < lengths[:, None]
            padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0))).astype(f32)
            conv = sum(padded[:, j:j + T] * conv_w[j] for j in range(K))
            # The last K - 1 real rows (zeros before position 0).
            at = lengths[:, None] - (K - 1) + jnp.arange(K - 1)[None, :]
            new_tail = jnp.where(
                (at >= 0)[..., None],
                jnp.take_along_axis(xbc, jnp.maximum(at, 0)[..., None], 1),
                0).astype(tail.dtype)
        act = jax.nn.silu(conv + p["conv_b"]).astype(x.dtype)
        xs, Bm, Cm = jnp.split(act, [cfg.inner, cfg.inner + G * N], -1)
        xs = xs.astype(f32).reshape(B_, T, G, Hg, P)
        Bm = Bm.astype(f32).reshape(B_, T, G, N)
        Cm = Cm.astype(f32).reshape(B_, T, G, N)
        step = dt.reshape(B_, T, G, Hg)
        if lengths is None:
            d1 = step[:, 0]                                # [B, G, Hg]
            h = (jnp.exp(d1 * A)[..., None, None] * h
                 + (d1[..., None] * xs[:, 0])[..., None]
                 * Bm[:, 0, :, None, None, :])
            y = (h * Cm[:, 0, :, None, None, :]).sum(-1)[:, None]
        else:
            y, h = ssd(xs, jnp.where(real[..., None, None], step, 0.0), A,
                       Bm, Cm, cfg.chunk_size, h)
        y = y + p["D"].astype(f32).reshape(G, Hg)[..., None] * xs
        return (h.reshape(B_, H, P, N), new_tail), y.reshape(B_, T, cfg.inner)

    y = state(update)
    y = (y * jax.nn.silu(z.astype(f32))).astype(x.dtype)
    return _norm(p["gnorm"], y, cfg.norm_eps, G) @ p["out_proj"]


def _attention(p, x, attend):
    with part("qkv"):
        q, k, v = x @ p["q"], x @ p["k"], x @ p["v"]
    with part("attend"):
        a = attend(q, k, v).astype(x.dtype)
    with part("attend_out"):
        return a @ p["o"]


def _experts(cfg: NemotronHConfig, p, x, count):
    B_, T, D = x.shape
    rows = x.reshape(B_ * T, D)
    with part("route"):
        weights, group = expert_matmul.route(
            rows, p["router"], p["router_bias"], cfg.top_k, cfg.routed_scale,
            cfg.expert_offset, cfg.experts_held)
    # Into the experts' latent width and out of it: the experts' matmuls too.
    with part("experts.matmul"):
        u = rows @ p["down"]
    out, sizes = expert_matmul.experts(u, p["w1"], p["w2"], weights, group)
    with part("experts.matmul"):
        y = out.astype(x.dtype) @ p["up"]
    count(expert_matmul.counters(sizes))
    with part("shared"):
        return (y + _relu2(rows, p["s1"], p["s2"])).reshape(B_, T, D)


def _layer(cfg: NemotronHConfig, p, x, attend, state, count):
    """One block over x [B, Tq, D]; the layer's parameters say its kind."""
    if x.shape[1] > 1:
        # A prompt pass: this layer's weights are touched when its input is
        # there and no sooner (models/evabyte.py has the reason).
        p, x = jax.lax.optimization_barrier((p, x))
    with part("norm"):
        h = _norm(p["norm"], x, cfg.norm_eps)
    if "in_proj" in p:
        with part("ssm"):  # the mixer whole: projections, convolution, state
            return x + _mamba(cfg, p, h, state).astype(x.dtype)
    if "router" in p:
        y = _experts(cfg, p, h, count)
        with part("shared"):
            return x + y.astype(x.dtype)
    y = _attention(p, h, attend)
    with part("attend_out"):
        return x + y.astype(x.dtype)


# ---------------------------------------------------------------------------
# The cache rows of the attention layers
# ---------------------------------------------------------------------------

class GroupedRows(Rows):
    """A row a position, ``kv_heads`` K/V heads wide, read by ``heads``
    queries: a prompt's attention here, in ``jax.numpy`` (scores [B, heads,
    P, P] float32, one layer at a time), and at most ``PREFILL_BATCH``
    prompts a prefill dispatch."""

    def __init__(self, kv_heads: int):
        self.kv_heads = kv_heads

    def prefill_batch(self, bucket: int) -> int:
        return PREFILL_BATCH

    def prompt_form(self, batch, heads, P, head_dim) -> str:
        return "grouped"

    def prompt(self, heads: int, lengths, P: int, put):
        kv = self.kv_heads
        keep = ((jnp.arange(P)[None, :] <= jnp.arange(P)[:, None])[None]
                & (jnp.arange(P)[None, None, :] < lengths[:, None, None]))

        def attend(p, cache, i, q, k, v):
            B = q.shape[0]
            dh = q.shape[-1] // heads
            qg = q.reshape(B, P, kv, heads // kv, dh) * dh ** -0.5
            kh, vh = k.reshape(B, P, kv, dh), v.reshape(B, P, kv, dh)
            scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kh,
                                preferred_element_type=jnp.float32)
            scores = jnp.where(keep[:, None, None], scores, -1e9)
            probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
            out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vh)
            return ((put(cache[0], i, k), put(cache[1], i, v)) + cache[2:],
                    out.reshape(B, P, heads * dh))

        return attend


# ---------------------------------------------------------------------------
# The family, the initializer
# ---------------------------------------------------------------------------

def family(cfg: NemotronHConfig, dtype=jnp.bfloat16) -> Family:
    """The block as models/decoder.py takes it.  A layer finds its part of
    the cache at the number of layers of its kind before it: the attention
    layers share the K/V leaves, the Mamba-2 layers the state and the tail,
    and an expert layer keeps nothing."""
    kinds = cfg.pattern
    unknown = set(kinds) - set("M*E")
    if unknown:
        raise ValueError(f"pattern has layers of unknown kind {unknown}")
    index = [kinds[:i].count(k) for i, k in enumerate(kinds)]
    n_ssm = kinds.count("M")

    def head(params, x):
        w = params["head"]
        return jax.lax.dot_general(x.astype(w.dtype), w,
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    return Family(
        embed=lambda params, tokens, dt: params["embed"][tokens].astype(dt),
        positions=None,
        layer=(lambda p, x, attend, pos, lora=None, lora_idx=None,
               state=None, count=None:
               _layer(cfg, p, x, attend, state, count)),
        norm=lambda params, x: _norm(params["norm"], x, cfg.norm_eps),
        head=head,
        layers=len(kinds), width=cfg.kv_heads * cfg.head_dim,
        heads=cfg.heads, kv_heads=cfg.kv_heads,
        kv_layers=max(kinds.count("*"), 1),
        state=((n_ssm, (cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
                jnp.float32),
               (n_ssm, (cfg.conv_kernel - 1, cfg.conv_dim), dtype)),
        cache_index=index.__getitem__,
        # ops/expert_matmul.counters, in its order.
        counters=(("expert_assignments_held",
                   "Rows routed to the experts held here"),
                  ("experts_touched", "Held experts that at least one row "
                   "reached, a layer a step"),
                  ("expert_load_max", "The most rows on one held expert, "
                   "a layer a step")),
        expert_plan=lambda rows: expert_matmul.plan_summary(
            rows, cfg.top_k, cfg.latent_size, cfg.expert_width,
            cfg.experts_held, False, jnp.dtype(dtype).itemsize),
        eos_id=cfg.eos_id, max_positions=cfg.max_positions,
        vocab_size=cfg.vocab_size,
        rows=GroupedRows(cfg.kv_heads))


def _init_layer(kind: str, g: np.random.Generator, cfg: NemotronHConfig,
                matrix_dtype) -> dict:
    D, std = cfg.hidden_size, cfg.init_std

    def w(*shape):
        a = g.standard_normal(shape, dtype=np.float32)
        a *= std
        return a.astype(matrix_dtype)

    p = {"norm": np.ones((D,), np.float32)}
    if kind == "M":
        H, K = cfg.mamba_heads, cfg.conv_kernel
        dt = np.exp(g.uniform(np.log(1e-3), np.log(1e-1), H))
        bound = K ** -0.5
        p.update(
            in_proj=w(D, cfg.inner + cfg.conv_dim + H),
            conv_w=g.uniform(-bound, bound, (K, cfg.conv_dim)).astype(
                np.float32),
            conv_b=g.uniform(-bound, bound, cfg.conv_dim).astype(np.float32),
            dt_bias=(dt + np.log(-np.expm1(-dt))).astype(np.float32),
            A_log=np.log(g.uniform(1.0, 16.0, H)).astype(np.float32),
            D=np.ones((H,), np.float32),
            gnorm=np.ones((cfg.inner,), np.float32),
            out_proj=w(cfg.inner, D))
    elif kind == "E":
        E, L, F = cfg.experts_held, cfg.latent_size, cfg.expert_width
        p.update(
            router=w(D, cfg.experts_published),
            router_bias=np.zeros((cfg.experts_published,), np.float32),
            down=w(D, L), up=w(L, D), w1=w(E, L, F), w2=w(E, F, L),
            s1=w(D, cfg.shared_width), s2=w(cfg.shared_width, D))
    else:
        q, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
        p.update(q=w(D, q), k=w(D, kv), v=w(D, kv), o=w(q, D))
    return p


def init_nemotron_params(seed: int = 0, cfg: NemotronHConfig = PUBLISHED,
                         matrix_dtype=np.float32) -> dict:
    """Seeded weights: matrices normal at ``init_std`` (in ``matrix_dtype``:
    9 GB of them are drawn straight into what they are staged as), norm
    weights one; Mamba-2's ``dt_bias`` the inverse softplus of a step drawn
    log-uniform from [0.001, 0.1], ``A`` uniform from [1, 16], ``D`` one, the
    convolution uniform within ``conv_kernel ** -0.5``; the router's bias
    zero (the benchmark's staging sets it as the published model's load
    balancing would: benchmark/families/nemotron_h.py).  A layer's draws
    depend on the seed and its index alone, so the layers are drawn side by
    side."""
    D = cfg.hidden_size

    def part(i):
        g = np.random.default_rng([seed, i])
        if i < len(cfg.pattern):
            return f"layer{i}", _init_layer(cfg.pattern[i], g, cfg,
                                            matrix_dtype)
        a = g.standard_normal((cfg.vocab_size, D), dtype=np.float32)
        a *= cfg.init_std
        a = a.astype(matrix_dtype)
        return ("embed", a) if i == len(cfg.pattern) else (
            "head", np.ascontiguousarray(a.T))

    with ThreadPoolExecutor(8) as pool:
        params = dict(pool.map(part, range(len(cfg.pattern) + 2)))
    params["norm"] = np.ones((D,), np.float32)
    return params


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def config_from_arch(arch: dict) -> NemotronHConfig:
    """``extra.arch`` over the published sizes; it states the share
    (``experts_held``, ``expert_offset``, ``vocab_size``) and the depth
    (``pattern``)."""
    fields = {f.name: f.type for f in dataclasses.fields(NemotronHConfig)}
    cast = {"int": int, "float": float, "str": str}
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
        "nemotron_h boots from a staged native tree (tpuserve stage); no "
        "converter from the published state dict is in this repository")


def make_nemotron_servable(name: str, cfg_model):
    from ..engine import weights as W
    from .vision_common import resolve_dtype

    cfg = config_from_arch(cfg_model.extra.get("arch", {}))
    params = (W.import_params(cfg_model.checkpoint, _no_converter)
              if cfg_model.checkpoint else init_nemotron_params(0, cfg))
    chunk = cfg.chunk_size
    if any(int(s) % chunk for s in cfg_model.seq_buckets):
        raise ValueError(f"{name}: seq_buckets {list(cfg_model.seq_buckets)} "
                         f"must be whole chunks of {chunk} positions")
    return make_servable(name, cfg_model,
                         family(cfg, resolve_dtype(cfg_model.dtype)), params)


from ..utils.registry import register_model  # noqa: E402


@register_model("nemotron_h", latency_class="latency")
def build_nemotron_h(cfg):
    return make_nemotron_servable("nemotron_h", cfg)
