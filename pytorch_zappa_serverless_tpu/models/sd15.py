"""Stable-Diffusion 1.5 txt2img pipeline (BASELINE config #5).

The latency-tolerant endpoint: prompt → CLIP text states → DDIM denoise loop
over the UNet with classifier-free guidance → VAE decode → PNG.  Served
through the async job queue (``POST /v1/models/sd15:submit`` → poll
``GET /v1/jobs/{id}``), mirroring what the reference would need SQS + a second
Lambda for (SURVEY §2b "Async job endpoint").

TPU-first structure — the whole image is ONE XLA program per (batch, h, w)
bucket:

- **Denoise loop as ``lax.scan`` over timesteps** (SURVEY §7 build step 6):
  scheduler constants (alphas-cumprod gathers per step) are precomputed on
  host for the static ``num_steps`` and scanned as per-step inputs; no Python
  between steps, no per-step dispatch.
- **Classifier-free guidance by batch-doubling**: the UNet runs on
  [uncond; cond] stacked along batch — one MXU-saturating call instead of
  two half-empty ones.
- bf16 compute everywhere; latents and scheduler math in fp32 (accumulated
  error in the 20-step loop is visible in bf16).
- Per-request `guidance_scale` and `seed` ride as *inputs* (a [B] array and
  host-side RNG respectively), so they never trigger recompilation;
  `num_steps`/`height`/`width` are compile-time constants from config.

Scheduler: DDIM (eta=0) with SD's scaled-linear beta schedule
(β ∈ [0.00085, 0.012] in sqrt space, 1000 train steps), "leading" timestep
spacing with steps_offset=1 — numerically checked against an independent
NumPy implementation in ``tests/test_sd15.py``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .clip_text import VIT_L14, CLIPTextConfig, encode_text, init_clip_text_params
from .sd_unet import SD15_UNET, UNetConfig, init_unet_params, unet_apply
from .sd_vae import SD15_VAE, VAEConfig, init_vae_params, vae_decode


@dataclass(frozen=True)
class SD15Config:
    clip: CLIPTextConfig = VIT_L14
    unet: UNetConfig = SD15_UNET
    vae: VAEConfig = SD15_VAE
    # Training-noise schedule (SD-1.5 scheduler/config.json).
    beta_start: float = 0.00085
    beta_end: float = 0.012
    train_steps: int = 1000
    steps_offset: int = 1


FULL = SD15Config()

# Tiny variant for tests/CI: same topology (4 stages, attn placement, GEGLU,
# mid attention), ~1000x fewer FLOPs.
TINY = SD15Config(
    clip=CLIPTextConfig(vocab_size=256, width=32, layers=2, heads=2, mlp_dim=64,
                        max_len=16, bot_id=254, eot_id=255),
    unet=UNetConfig(block_channels=(16, 16, 32, 32), layers_per_block=1,
                    heads=2, context_dim=32, groups=4),
    vae=VAEConfig(up_channels=(32, 32, 16, 16), resnets_per_block=1, groups=4),
)


# ---------------------------------------------------------------------------
# DDIM schedule (host-side constants; the scan consumes per-step rows)
# ---------------------------------------------------------------------------

def ddim_schedule(num_steps: int, cfg: SD15Config = FULL) -> dict[str, np.ndarray]:
    """Per-step DDIM constants for the scan, in descending-time order.

    Returns arrays of shape [num_steps]: ``t`` (timestep fed to the UNet),
    ``sqrt_alpha``/``sqrt_one_minus_alpha`` (at t), and the same at the
    *previous* step the update lands on.
    """
    betas = np.linspace(cfg.beta_start ** 0.5, cfg.beta_end ** 0.5,
                        cfg.train_steps, dtype=np.float64) ** 2
    alphas_cumprod = np.cumprod(1.0 - betas)
    step_ratio = cfg.train_steps // num_steps
    t = (np.arange(num_steps) * step_ratio).round()[::-1].astype(np.int64)
    t = t + cfg.steps_offset
    t = np.clip(t, 0, cfg.train_steps - 1)
    prev_t = t - step_ratio
    # set_alpha_to_one=False in SD: the final step lands on alphas_cumprod[0].
    alpha_prev = np.where(prev_t >= 0, alphas_cumprod[np.clip(prev_t, 0, None)],
                          alphas_cumprod[0])
    alpha_t = alphas_cumprod[t]
    return {
        "t": t.astype(np.float32),
        "sqrt_alpha": np.sqrt(alpha_t).astype(np.float32),
        "sqrt_one_minus_alpha": np.sqrt(1.0 - alpha_t).astype(np.float32),
        "sqrt_alpha_prev": np.sqrt(alpha_prev).astype(np.float32),
        "sqrt_one_minus_alpha_prev": np.sqrt(1.0 - alpha_prev).astype(np.float32),
    }


# ---------------------------------------------------------------------------
# The jitted pipeline
# ---------------------------------------------------------------------------

def encode_condition(params: dict, inputs: dict, cfg: SD15Config = FULL,
                     dtype=jnp.bfloat16):
    """Prompt conditioning: (context [2B, T, D], guidance [B, 1, 1, 1])."""
    # One [2B]-batched encode, uncond rows first: the text tower is weight-
    # bandwidth-bound at these batch sizes (profiled 82% HBM util, 2.8% MFU
    # at b1 — tools/profile_sd15.py), so two b1 calls pay the ~500 MB weight
    # read twice for no reason.
    both_ids = jnp.concatenate([inputs["uncond_ids"], inputs["cond_ids"]], axis=0)
    context = encode_text(params["clip"], both_ids, cfg.clip, dtype)  # [2B, T, D]
    g = inputs["guidance"].astype(jnp.float32)[:, None, None, None]
    return context, g


def denoise(params: dict, latents: jax.Array, context: jax.Array, g: jax.Array,
            rows: dict, cfg: SD15Config = FULL, dtype=jnp.bfloat16) -> jax.Array:
    """Scan the DDIM update over the given schedule rows (any contiguous
    slice — the full 20 steps in the monolithic program, one 4-step chunk on
    the preemptible job path; same body either way, so chunked serving stays
    numerically the monolithic scan run in slices)."""

    def step(latents, row):
        B = latents.shape[0]
        lat2 = jnp.concatenate([latents, latents], axis=0)
        t2 = jnp.full((2 * B,), row["t"], jnp.float32)
        eps2 = unet_apply(params["unet"], lat2, t2, context, cfg.unet, dtype)
        eps_u, eps_c = jnp.split(eps2, 2, axis=0)
        eps = eps_u + g * (eps_c - eps_u)
        # DDIM (eta=0): x0-prediction then deterministic step.
        x0 = (latents - row["sqrt_one_minus_alpha"] * eps) / row["sqrt_alpha"]
        latents = row["sqrt_alpha_prev"] * x0 + row["sqrt_one_minus_alpha_prev"] * eps
        return latents, None

    rows = {k: jnp.asarray(v) for k, v in rows.items()}
    latents, _ = jax.lax.scan(step, latents, rows)
    return latents


def decode_image(params: dict, latents: jax.Array, cfg: SD15Config = FULL,
                 dtype=jnp.bfloat16) -> dict:
    # Diffusion-space latents go to the decoder as-is: vae_decode applies the
    # 1/0.18215 scaling internally (models/sd_vae.py).  Decode per image BY
    # DESIGN: at any B>1 libtpu's conv emitter switches to batch-in-sublanes
    # strategies (EmitAllBatchInSublanes in the HLO) whose per-conv relayouts
    # cost ~30 ms/image of pure bandwidth — b4 traced 47.3 ms/image vs 18.1
    # at b1, and the best batched formulation found (batch-as-spatial 3D
    # conv) still measured 26.2/image.  Root cause + falsification attempts:
    # docs/PERF_SD15.md "Round-5 addendum".
    if latents.shape[0] > 1:
        image = jax.lax.map(
            lambda lat: vae_decode(params["vae"], lat[None], cfg.vae, dtype)[0],
            latents)
    else:
        image = vae_decode(params["vae"], latents, cfg.vae, dtype)
    return {"image": (image * 255.0 + 0.5).astype(jnp.uint8)}


def txt2img(params: dict, inputs: dict, schedule: dict, cfg: SD15Config = FULL,
            dtype=jnp.bfloat16) -> dict:
    """One XLA program: tokens + noise → uint8 image.

    inputs: cond_ids/uncond_ids [B, T] int32, latents [B,h,w,4] fp32 (unit
    normal), guidance [B] fp32.  The preemptible job path runs the same three
    pieces (encode_condition → denoise → decode_image) as separate chunked
    dispatches — see ``make_sd15_servable``.
    """
    context, g = encode_condition(params, inputs, cfg, dtype)
    latents = denoise(params, inputs["latents"].astype(jnp.float32), context,
                      g, schedule, cfg, dtype)
    return decode_image(params, latents, cfg, dtype)


# ---------------------------------------------------------------------------
# Tokenization (offline fallback; real deployments point extra.tokenizer at a
# CLIP tokenizer.json and get true BPE via the `tokenizers` library)
# ---------------------------------------------------------------------------

def _fallback_tokenize(text: str, cfg: CLIPTextConfig) -> list[int]:
    """Deterministic offline stub: whitespace words hashed into the vocab.

    Same role as BERT's fallback (models/bert.py): keeps the dev profile
    servable with zero assets; swap in the real BPE for deployments.
    """
    import hashlib

    ids = []
    for w in text.lower().split():
        h = int.from_bytes(hashlib.sha256(w.encode()).digest()[:4], "big")
        ids.append(h % max(cfg.vocab_size - 3, 1))
    return ids


def make_prompt_ids(text: str, cfg: CLIPTextConfig, tokenizer=None) -> np.ndarray:
    if tokenizer is not None:
        ids = tokenizer.encode(text).ids
        # HF CLIP tokenizer.json post-processors already add BOS/EOS; strip
        # them so the wrap below is applied exactly once either way.
        ids = [i for i in ids if i not in (cfg.bot_id, cfg.eot_id)]
    else:
        ids = _fallback_tokenize(text, cfg)
    ids = [cfg.bot_id] + ids[: cfg.max_len - 2] + [cfg.eot_id]
    ids = ids + [cfg.eot_id] * (cfg.max_len - len(ids))  # CLIP pads with EOT
    return np.asarray(ids, np.int32)


# ---------------------------------------------------------------------------
# Servable
# ---------------------------------------------------------------------------

def init_sd15_params(seed: int = 0, cfg: SD15Config = FULL) -> dict:
    return {"clip": init_clip_text_params(seed, cfg.clip),
            "unet": init_unet_params(seed + 1, cfg.unet),
            "vae": init_vae_params(seed + 2, cfg.vae)}


def _png_b64(arr: np.ndarray) -> str:
    import base64
    import io

    from PIL import Image

    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="PNG")
    return base64.b64encode(buf.getvalue()).decode()


def make_sd15_servable(name: str, cfg_model, cfg: SD15Config | None = None):
    from ..engine import weights as W
    from ..engine.servable import Servable
    from .vision_common import resolve_dtype

    if cfg is None:
        cfg = TINY if cfg_model.extra.get("variant") == "tiny" else FULL
    dtype = resolve_dtype(cfg_model.dtype)
    height = int(cfg_model.extra.get("height", 512))
    width = int(cfg_model.extra.get("width", 512))
    num_steps = int(cfg_model.extra.get("num_steps", 20))
    default_guidance = float(cfg_model.extra.get("guidance_scale", 7.5))
    lh, lw = height // 8, width // 8

    tokenizer = None
    tok_path = cfg_model.extra.get("tokenizer")
    if tok_path:
        from tokenizers import Tokenizer

        tokenizer = Tokenizer.from_file(str(tok_path))

    if cfg_model.checkpoint:
        params = (W.load_native(cfg_model.checkpoint)
                  if W.is_native(cfg_model.checkpoint)
                  else W.convert_sd15(cfg_model.checkpoint))
    else:
        params = init_sd15_params(0, cfg)
    params = jax.device_put(params)  # ONE batched tree transfer: per-leaf jnp.asarray
    # serializes a host round-trip per buffer.
    schedule = ddim_schedule(num_steps, cfg)

    def apply_fn(p, inputs):
        return txt2img(p, inputs, schedule, cfg, dtype)

    # Preemptible chunked contract (docs/QOS.md; engine/runner.run_chunked):
    # split the monolithic program into prepare (CLIP encode) → K denoise
    # chunks of ``chunk_steps`` DDIM steps → finalize (VAE decode), each its
    # own dispatch with the lane released between.  On the v5e the 20-step
    # 512² program occupies the lane ~440 ms uninterruptibly; at 4-step
    # chunks the longest slice is ~90-110 ms (4 × ~22 ms UNet CFG steps, or
    # the ~110 ms encode/decode edges), so a co-resident <30 ms latency
    # request waits at most one chunk.  chunk_steps=0 disables (monolithic).
    chunk_steps = int(cfg_model.extra.get("chunk_steps", 4))
    chunked = None
    if 0 < chunk_steps < num_steps:
        rows_np = {k: np.asarray(v) for k, v in schedule.items()}
        chunk_rows = [{k: v[i: i + chunk_steps] for k, v in rows_np.items()}
                      for i in range(0, num_steps, chunk_steps)]

        def prepare_fn(p, batch):
            context, g = encode_condition(p, batch, cfg, dtype)
            return {"latents": batch["latents"].astype(jnp.float32),
                    "context": context, "g": g}

        def chunk_fn(p, state, rows):
            latents = denoise(p, state["latents"], state["context"],
                              state["g"], rows, cfg, dtype)
            return {**state, "latents": latents}

        def finalize_fn(p, state):
            return decode_image(p, state["latents"], cfg, dtype)

        # All chunks share one compiled program (same [chunk_steps] row
        # shapes); a ragged final chunk compiles one more.  The scan body is
        # the SAME ``denoise`` the monolithic program scans, so chunked
        # output matches the 20-step scan (tier-1 parity test).
        chunked = {"num_chunks": len(chunk_rows),
                   "steps_per_chunk": chunk_steps,
                   "chunk_rows": chunk_rows,
                   "prepare": jax.jit(prepare_fn),
                   "chunk": jax.jit(chunk_fn),
                   "finalize": jax.jit(finalize_fn)}

    def input_spec(bucket):
        B = bucket[0]
        T = cfg.clip.max_len
        return {
            "cond_ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
            "uncond_ids": jax.ShapeDtypeStruct((B, T), jnp.int32),
            "latents": jax.ShapeDtypeStruct((B, lh, lw, 4), jnp.float32),
            "guidance": jax.ShapeDtypeStruct((B,), jnp.float32),
        }

    def preprocess(payload):
        if isinstance(payload, (bytes, str)):
            payload = {"prompt": payload.decode() if isinstance(payload, bytes) else payload}
        if not isinstance(payload, dict) or "prompt" not in payload:
            raise ValueError('expected JSON body {"prompt": ...}')
        seed = int(payload.get("seed", 0))
        latents = np.random.default_rng(seed).standard_normal(
            (lh, lw, 4)).astype(np.float32)
        return {
            "cond_ids": make_prompt_ids(str(payload["prompt"]), cfg.clip, tokenizer),
            "uncond_ids": make_prompt_ids(str(payload.get("negative_prompt", "")),
                                          cfg.clip, tokenizer),
            "latents": latents,
            "guidance": np.float32(payload.get("guidance_scale", default_guidance)),
        }

    def postprocess(out, i):
        # Raw pixels only — PNG+base64 encoding is tens of ms of host work
        # and must NOT run on the device-dispatch thread; the job worker
        # applies ``finalize`` (below) in the event loop's executor.
        return {"pixels": np.asarray(out["image"][i]),
                "height": height, "width": width}

    def finalize(result):
        pixels = result.pop("pixels")
        return {**result, "image_b64": _png_b64(pixels), "format": "png"}

    # On a mesh, the CLIP conditioning tower shards Megatron-style; rules are
    # anchored under the "clip/" subtree so the UNet/VAE attn params (q/k/v
    # names too, but not under layer{i}/) can never match.  UNet/VAE stay
    # replicated until an HBM-spill case demands sharding them.
    from ..parallel.mesh import CLIP_TP_RULES

    sd_rules = [("clip/" + pat, spec) for pat, spec in CLIP_TP_RULES]

    meta = {"num_steps": num_steps, "async_only": True,
            "finalize": finalize, "tp_rules": sd_rules}
    if chunked is not None:
        meta["chunked"] = chunked
    return Servable(name=name, apply_fn=apply_fn, params=params,
                    input_spec=input_spec, preprocess=preprocess,
                    postprocess=postprocess, bucket_axes=("batch",),
                    meta=meta)


from ..utils.registry import register_model  # noqa: E402


@register_model("sd15", latency_class="throughput")
def build_sd15(cfg):
    return make_sd15_servable("sd15", cfg)
