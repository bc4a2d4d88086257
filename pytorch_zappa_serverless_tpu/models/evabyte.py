"""EvaByte — a byte-level decoder whose attention keeps two tiers of cache.

This file is EvaByte and nothing else: the block (RMS norm with a unit
offset, rotary positions, EVA attention, a gated SiLU MLP, no biases, an
untied head of ``num_pred_heads`` x ``vocab_size`` columns; the residual
stream, the attention logits and the head's logits in float32), how its cache
rows hold its positions (:class:`TwoTier`), its initializer and its converter.
The trunk, the generation programs and the servable are models/decoder.py's,
which gets the block as a :func:`family`.

EVA attention (per head, ``dh`` wide, scale ``dh ** -0.5``): a query at
position ``t`` in window ``w = t // window`` attends, in ONE softmax, the
exact keys and values of its own window's positions ``window * w <= i <= t``
and one *summary* ``(kbar_j, vbar_j)`` for every chunk ``j`` of ``chunk``
positions of every *finished* window (``j < window / chunk * w``); chunks of
the query's own window are never read as summaries.  A chunk's summary pools
its (rotated) keys and its values with the layer's learned per-head vectors
``mu`` and ``phi``::

    kbar_j = sum_i softmax_i(mu . k_i) k_i
    vbar_j = sum_i softmax_i(phi . k_i - |k_i|^2 / 2) v_i

both softmaxes over the chunk's positions, in float32.  Served one byte a
step from prediction head 0 (the first ``vocab_size`` columns of the head);
the other heads are held as published and not read (PERF.md section 7).

Conventions the published ``config.json`` does not fix, *assumed* here and in
benchmark/configs/evabyte-16l.json: rotary positions in the half-rotation
layout over all of ``dh``; no scale on ``mu . k_i``; no further scale inside
``phi . k_i - |k_i|^2 / 2``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import decode_attention, flash_attention
from .decoder import Family, Rows, make_servable, part


@dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320
    hidden_size: int = 4096
    layers: int = 32
    heads: int = 32
    intermediate_size: int = 11008
    max_positions: int = 32768
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    rms_norm_eps: float = 1e-5
    init_std: float = 0.01275
    # Assumed: the tokenizer's file is not in this repository;
    # ``extra.arch.eos_id`` says what a deployment's is.
    eos_id: int = 2


PUBLISHED = EvaByteConfig()


# ---------------------------------------------------------------------------
# The block
# ---------------------------------------------------------------------------

def _norm(w, x, eps, dtype):
    """``x / rms(x) * (1 + w)`` in float32 (``norm_add_unit_offset``)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dtype)


def _rope(x, pos, heads: int, theta: float):
    """x [B, Tq, D] turned by its absolute positions ``pos`` ([Tq] or
    [B, Tq]): each head's ``dh`` columns as two halves (the half-rotation
    layout), frequencies ``theta ** (-2 i / dh)``, in float32."""
    B, Tq, D = x.shape
    dh = D // heads
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.asarray(pos, jnp.float32)[..., None] * inv      # [.., Tq, dh/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xh = x.astype(jnp.float32).reshape(B, Tq, heads, dh)
    a, b = xh[..., : dh // 2], xh[..., dh // 2:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(B, Tq, D).astype(x.dtype)


def _layer(p, x, cfg: EvaByteConfig, attend, pos):
    """One block over x [B, Tq, D] (the float32 residual stream) at the
    positions ``pos``; matmuls in the dtype the weights are held in."""
    dt = p["q"].dtype
    if x.shape[1] > 1:
        # A prompt pass: this layer's weights are touched when its input is
        # there and no sooner.  Left to itself the compiler starts on every
        # layer's weights at once and holds what it made of them: 5.5 GB of
        # temporaries for 16 layers over 12,288 positions by its own
        # analysis, 1.1 GB so (PERF.md section 6, PR 35).
        p, x = jax.lax.optimization_barrier((p, x))
    with part("norm"):
        h = _norm(p["n1"], x, cfg.rms_norm_eps, dt)
    with part("qkv"):
        q, k, v = _qkv(p, h, cfg, pos, x.shape[1] == 1)
    with part("attend"):
        a = attend(q, k, v).astype(dt)
    with part("attend_out"):
        x = x + (a @ p["o"]).astype(jnp.float32)
    with part("norm"):
        n = _norm(p["n2"], x, cfg.rms_norm_eps, dt)
    with part("mlp"):
        y = (jax.nn.silu(n @ p["gate"]) * (n @ p["up"])) @ p["down"]
        return x + y.astype(jnp.float32)


def _qkv(p, h, cfg: EvaByteConfig, pos, decode: bool):
    """The three projections of ``h``, the queries and keys turned."""
    q, k = h @ p["q"], h @ p["k"]
    if decode:
        # A decode step: the projections are whole before they are split
        # into heads.  The trunk calls this block as one function of its
        # weights, and XLA simplifies a function called from several sites
        # before it inlines it: with nothing between the matmul and
        # ``_rope``'s reshape it folds the reshape into a transposed copy of
        # the weight, which after inlining is 32 copies of 33.5 MB a segment
        # launch and 1.1 GB of temporaries (compiled for a described v5e:
        # PERF.md section 6, PR 43).
        q, k = jax.lax.optimization_barrier((q, k))
    return (_rope(q, pos, cfg.heads, cfg.rope_theta),
            _rope(k, pos, cfg.heads, cfg.rope_theta), h @ p["v"])


def summarize(p, k, v, real, heads: int):
    """The summaries of chunks: k, v [..., c, D] a chunk's (rotated) keys and
    its values, ``real`` [..., c] which of its positions are written →
    ``(kbar, vbar)`` [..., D] float32.  A chunk with no real position gives
    a finite row that nothing reads."""
    with part("summary"):
        *lead, c, D = k.shape
        dh = D // heads
        kh = k.astype(jnp.float32).reshape(*lead, c, heads, dh)
        vh = v.astype(jnp.float32).reshape(*lead, c, heads, dh)
        mu = p["mu"].astype(jnp.float32).reshape(heads, dh)
        phi = p["phi"].astype(jnp.float32).reshape(heads, dh)
        keep = real[..., None]
        a = jnp.where(keep, (kh * mu).sum(-1), -1e30)          # [..., c, H]
        b = jnp.where(keep, (kh * phi).sum(-1)
                      - 0.5 * (kh * kh).sum(-1), -1e30)
        wk = jax.nn.softmax(a, axis=-2)[..., None]
        wv = jax.nn.softmax(b, axis=-2)[..., None]
        return ((wk * kh).sum(-3).reshape(*lead, D),
                (wv * vh).sum(-3).reshape(*lead, D))


# ---------------------------------------------------------------------------
# The cache rows
# ---------------------------------------------------------------------------

class TwoTier(Rows):
    """A slot's ``T`` rows as two tiers that meet at row ``R = T -
    window``: a ring of ``window`` exact rows above it (position ``t`` at
    row ``R + t % window``) and the chunks' summaries below it, newest
    lowest (chunk ``j`` at row ``R - 1 - j``).  What a query at ``t`` reads
    is then one span, ``[R - window / chunk * (t // window), R + t %
    window]``: the summaries of the finished windows and the exact rows of
    its own so far.  When a window completes, the span's start moves down by
    ``window / chunk`` rows and its end back to ``R``; nothing is copied.
    The write of position ``t`` also rewrites its chunk's summary from the
    chunk's rows so far, so the summary is final with the chunk's last
    position, long before any query reads it."""

    def __init__(self, window: int, chunk: int, heads: int, align: int,
                 block_q: int = 512):
        if window % chunk:
            raise ValueError(f"window {window} is not whole chunks of "
                             f"{chunk}")
        self.window, self.chunk, self.heads = window, chunk, heads
        self.align = align          # T is a multiple of it: whole blocks
        self.block_q = min(block_q, window)  # queries a prompt pass scores

    def count(self, total: int) -> int:
        rows = self.window + -(-total // self.chunk)
        return -(-rows // self.align) * self.align

    def positions(self, T: int) -> int:
        return (T - self.window) * self.chunk

    def row(self, pos, T: int):
        return T - self.window + pos % self.window

    def summaries(self, pos, T: int):
        return self.window // self.chunk * (pos // self.window)

    def span(self, pos, T: int):
        R = T - self.window
        return R - self.summaries(pos, T), R + pos % self.window

    def windows(self, n: int) -> int:
        return max(1, -(-n // self.window))

    def prefill_batch(self, bucket: int) -> int:
        """One window's worth of positions, or one prompt: a prompt pass
        holds ``[heads, block_q, window + summaries]`` float32 scores and
        the whole prompt's activations a row."""
        return max(1, self.window // bucket)

    def settle(self, p, k, v, layer, slots, pos):
        W, c = self.window, self.chunk
        R = k.shape[2] - W
        idx = (R + pos % W // c * c)[:, None] + jnp.arange(c)      # [S, c]
        kbar, vbar = summarize(
            p, k[layer, slots[:, None], idx], v[layer, slots[:, None], idx],
            jnp.arange(c)[None, :] <= (pos % c)[:, None], self.heads)
        at = R - 1 - pos // c
        return (k.at[layer, slots, at].set(kbar.astype(k.dtype)),
                v.at[layer, slots, at].set(vbar.astype(v.dtype)))

    def prompt_form(self, batch, heads, P, head_dim) -> str:
        """``"kernel"`` under ops/flash_attention.prompt_form's rule, read
        for one window (the longest prompt the kernel is handed) and for
        the float32 scores a block of the ``jax.numpy`` form writes
        (``[batch, heads, block_q, window + summaries]``); else
        ``"windows"``: the CPU, a mesh, a tiny configuration."""
        scores = batch * heads * self.block_q * (
            self.window + self.windows(P) * self.window // self.chunk)
        return ("kernel" if flash_attention.prompt_form(
            batch, heads, self.window, head_dim, scores) == "kernel"
            else "windows")

    def attention(self, form: str, heads: int, q, k, v, kbar, vbar, lengths,
                  real=None):
        """The prompt attention alone in one of its forms: q, k, v
        [B, Pw, D] over whole windows, ``kbar``, ``vbar`` [B, Pw / chunk, D]
        every chunk's summary in their dtype, lengths [B] (``real``
        [B, Pw]: which positions they leave real, where the caller has it)
        → [B, Pw, D].  A query reads its own window's keys at or below it
        and the summaries of the windows before, in one softmax; rows past
        a length hold finite values that mean nothing."""
        with part("attend"):
            if form == "kernel":
                return self._kernel_windows(heads, q, k, v, kbar, vbar,
                                            lengths)
            if real is None:
                real = jnp.arange(q.shape[1])[None, :] < lengths[:, None]
            return self._scan_blocks(heads, q, k, v, kbar, vbar, real)

    def _kernel_windows(self, heads, q, k, v, kbar, vbar, lengths):
        """Form ``kernel``: the prompt's windows as rows of the batch of
        ops/flash_attention.prompt_attention (a reshape: they lie one after
        the other), each causal inside itself over what of it is real, and
        window ``w`` reading the first ``w * window / chunk`` summaries of
        its prompt as the kernel's prefix.  The scores stay in VMEM, and
        nothing past the diagonal or past a length is computed."""
        W, per = self.window, self.window // self.chunk
        B, Pw, D = q.shape
        nW = Pw // W
        w = jnp.arange(nW)
        lens = jnp.clip(lengths[:, None] - w * W, 0, W).reshape(B * nW)
        counts = jnp.broadcast_to(per * w, (B, nW)).reshape(B * nW)
        out = flash_attention.prompt_attention(
            *(a.reshape(B * nW, W, D) for a in (q, k, v)), lens, heads=heads,
            prefix=(kbar, vbar, counts) if nW > 1 else None)
        return out.reshape(B, Pw, D)

    def _scan_blocks(self, heads, q, k, v, kbar, vbar, real):
        """Form ``windows``, in ``jax.numpy``: a ``lax.scan`` over blocks of
        ``block_q`` queries, each block's float32 scores ``[B, heads,
        block_q, window + Pw / chunk]`` written out and read back."""
        W, c, Bq = self.window, self.chunk, self.block_q
        B, Pw, D = q.shape
        dh, per = D // heads, W // c

        def split(a):
            return a.reshape(B, -1, heads, dh)

        sk, sv = split(kbar), split(vbar)

        def block(_, b):
            w = b * Bq // W
            qpos = b * Bq + jnp.arange(Bq)
            kpos = w * W + jnp.arange(W)
            qb = split(jax.lax.dynamic_slice_in_dim(q, b * Bq, Bq, 1))
            kw = jax.lax.dynamic_slice_in_dim(k, w * W, W, 1)
            vw = jax.lax.dynamic_slice_in_dim(v, w * W, W, 1)
            here = jax.lax.dynamic_slice_in_dim(real, w * W, W, 1)
            qb = qb * dh ** -0.5
            exact = jnp.einsum("bqhd,bkhd->bhqk", qb, split(kw),
                               preferred_element_type=jnp.float32)
            keep = ((kpos[None, :] <= qpos[:, None])[None]
                    & here[:, None, :])[:, None]           # [B,1,Bq,W]
            exact = jnp.where(keep, exact, -1e9)
            past = jnp.einsum("bqhd,bjhd->bhqj", qb, sk,
                              preferred_element_type=jnp.float32)
            past = jnp.where(jnp.arange(Pw // c) < per * w, past, -1e9)
            probs = jax.nn.softmax(
                jnp.concatenate([past, exact], axis=-1), axis=-1)
            probs = probs.astype(v.dtype)
            out = (jnp.einsum("bhqj,bjhd->bqhd", probs[..., : Pw // c], sv)
                   + jnp.einsum("bhqk,bkhd->bqhd", probs[..., Pw // c:],
                                split(vw)))
            return None, out.reshape(B, Bq, D)

        _, outs = jax.lax.scan(block, None, jnp.arange(Pw // Bq))
        return jnp.moveaxis(outs, 0, 1).reshape(B, Pw, D)

    def prompt(self, heads: int, lengths, P: int, put):
        """Window by window: the exact causal attention inside a query's
        window joined with the summaries of the windows before it in one
        softmax (:meth:`attention`, in the form :meth:`prompt_form` says);
        then the rows a decode step reads, the summaries of every chunk and
        the ring as the prompt's last window leaves it.  No ``[P, P]``
        array."""
        W, c = self.window, self.chunk
        Pw = -(-P // W) * W        # whole windows; the padding is not real
        nC = -(-P // c)
        real = jnp.arange(Pw)[None, :] < lengths[:, None]          # [B, Pw]

        def attend(p, cache, i, q, k, v):
            B, _, D = q.shape
            pad = ((0, 0), (0, Pw - P), (0, 0))
            q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
            kbar, vbar = summarize(p, k.reshape(B, Pw // c, c, D),
                                   v.reshape(B, Pw // c, c, D),
                                   real.reshape(B, Pw // c, c), heads)
            kbar, vbar = kbar.astype(k.dtype), vbar.astype(v.dtype)
            out = self.attention(
                self.prompt_form(B, heads, P, D // heads), heads, q, k, v,
                kbar, vbar, lengths, real)[:, :P]

            # The rows: chunk j's summary at R - 1 - j, and the ring as the
            # last window each prompt reaches leaves it (rows past the
            # prompt's end hold what no query reads before it is rewritten).
            R = cache[0].shape[2] - W
            start = (lengths - 1) // W * W

            def ring(a):
                return jax.vmap(lambda row, s: jax.lax.dynamic_slice_in_dim(
                    row, s, W, 0))(a, start)

            def keep(rows, summary, exact):
                return put(put(rows, i, summary[:, :nC][:, ::-1], R - nC),
                           i, ring(exact), R)

            # The rows are written before the next layer starts: left to
            # itself the compiler keeps every layer's K and V until the end
            # (2.6 GB of temporaries against 1.1 GB, as above).
            return jax.lax.optimization_barrier(
                ((keep(cache[0], kbar, k), keep(cache[1], vbar, v)), out))

        return attend


# ---------------------------------------------------------------------------
# The family, the initializer, the converter
# ---------------------------------------------------------------------------

def _head(cfg: EvaByteConfig, params, x):
    """Prediction head 0: the first ``vocab_size`` columns, float32 logits."""
    w = params["head"][:, : cfg.vocab_size]
    return jax.lax.dot_general(x.astype(w.dtype), w, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def family(cfg: EvaByteConfig, rows: TwoTier) -> Family:
    """EvaByte's block as models/decoder.py takes it.  No learned positions
    (the layer turns its queries and keys by the positions it is handed); a
    cache row is ``hidden_size`` wide, every head its own K and V."""
    return Family(
        embed=lambda params, tokens, dtype: params["embed"][tokens].astype(
            jnp.float32),
        positions=None,
        layer=(lambda p, x, attend, pos, lora=None, lora_idx=None:
               _layer(p, x, cfg, attend, pos)),
        norm=lambda params, x: _norm(params["norm"], x, cfg.rms_norm_eps,
                                     params["head"].dtype),
        head=lambda params, x: _head(cfg, params, x),
        layers=cfg.layers, width=cfg.hidden_size, heads=cfg.heads,
        eos_id=cfg.eos_id, max_positions=cfg.max_positions,
        vocab_size=cfg.vocab_size, rows=rows)


def init_evabyte_params(seed: int = 0, cfg: EvaByteConfig = PUBLISHED,
                        pool_scale: float | None = None) -> dict:
    """Seeded weights as published: matrices normal at ``init_std``, norm
    offsets zero, ``mu`` and ``phi`` normal clamped to [-1, 1] times
    ``init_std`` (``pool_scale`` says another factor: a test draws them at
    unit scale so that the pooling is far from uniform)."""
    g = np.random.default_rng(seed)
    D, F = cfg.hidden_size, cfg.intermediate_size
    scale = cfg.init_std if pool_scale is None else pool_scale

    def w(*shape):
        return (g.standard_normal(shape) * cfg.init_std).astype(np.float32)

    def pool():
        return (np.clip(g.standard_normal((D,)), -1.0, 1.0)
                * scale).astype(np.float32)

    params = {"embed": w(cfg.vocab_size, D),
              "norm": np.zeros((D,), np.float32),
              "head": w(D, cfg.vocab_size * cfg.num_pred_heads)}
    for i in range(cfg.layers):
        params[f"layer{i}"] = {
            "n1": np.zeros((D,), np.float32),
            "n2": np.zeros((D,), np.float32),
            "q": w(D, D), "k": w(D, D), "v": w(D, D), "o": w(D, D),
            "mu": pool(), "phi": pool(),
            "gate": w(D, F), "up": w(D, F), "down": w(F, D)}
    return params


_LAYER_NAMES = {
    "self_attn.q_proj.weight": "q", "self_attn.k_proj.weight": "k",
    "self_attn.v_proj.weight": "v", "self_attn.o_proj.weight": "o",
    "self_attn.adaptive_mu_k": "mu", "self_attn.adaptive_phi": "phi",
    "mlp.gate_proj.weight": "gate", "mlp.up_proj.weight": "up",
    "mlp.down_proj.weight": "down", "input_layernorm.weight": "n1",
    "post_attention_layernorm.weight": "n2"}


def convert_evabyte(sd) -> dict:
    """The published state dict (torch ``Linear`` stores [out, in]) → this
    file's tree: matrices transposed to [in, out], ``adaptive_mu_k`` and
    ``adaptive_phi`` (one ``dh`` vector a head, whatever singleton axes the
    file keeps) flattened with the heads side by side."""
    params: dict = {}
    for key, w in sd.items():
        w = np.asarray(w)
        name = key.removeprefix("model.")
        if name == "embed_tokens.weight":
            params["embed"] = w
        elif name == "norm.weight":
            params["norm"] = w
        elif name == "lm_head.weight":
            params["head"] = np.ascontiguousarray(w.T)
        elif name.startswith("layers."):
            _, n, rest = name.split(".", 2)
            if rest.endswith("rotary_emb.inv_freq"):
                continue
            if rest not in _LAYER_NAMES:
                raise KeyError(f"unrecognized evabyte key: {key}")
            leaf = _LAYER_NAMES[rest]
            if leaf in ("mu", "phi"):
                w = w.reshape(-1)
            elif w.ndim == 2:
                w = np.ascontiguousarray(w.T)
            params.setdefault(f"layer{n}", {})[leaf] = w
        else:
            raise KeyError(f"unrecognized evabyte key: {key}")
    return params


def config_from_params(params: dict) -> EvaByteConfig:
    """What a converted tree's shapes say; heads are 128 wide as published,
    and what leaves no trace in a shape comes from ``extra.arch``."""
    vocab, D = (int(x) for x in np.asarray(params["embed"]).shape)
    return dataclasses.replace(
        PUBLISHED, vocab_size=vocab, hidden_size=D,
        layers=sum(1 for k in params if k.startswith("layer")),
        heads=max(D // 128, 1),
        intermediate_size=int(np.asarray(
            params["layer0"]["gate"]).shape[1]),
        num_pred_heads=int(np.asarray(params["head"]).shape[1]) // vocab)


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def make_evabyte_servable(name: str, cfg_model):
    from ..engine import weights as W
    from .vision_common import resolve_dtype

    fields = {f.name: f.type for f in dataclasses.fields(EvaByteConfig)}
    arch = {k: (float(v) if fields[k] == "float" else int(v))
            for k, v in dict(cfg_model.extra.get("arch", {})).items()}
    if cfg_model.checkpoint:
        params = W.import_params(cfg_model.checkpoint, convert_evabyte)
        cfg = dataclasses.replace(config_from_params(params), **arch)
    else:
        cfg = dataclasses.replace(PUBLISHED, **arch)
        params = init_evabyte_params(0, cfg)
    # Whole blocks of the decode kernel: the rows a block of K holds at this
    # width, or a window where that is shorter.
    align = min(decode_attention.block_rows(
        cfg.hidden_size, resolve_dtype(cfg_model.dtype)), cfg.window_size)
    rows = TwoTier(cfg.window_size, cfg.chunk_size, cfg.heads, align)
    return make_servable(name, cfg_model, family(cfg, rows), params)


from ..utils.registry import register_model  # noqa: E402


@register_model("evabyte", latency_class="latency")
def build_evabyte(cfg):
    return make_evabyte_servable("evabyte", cfg)
