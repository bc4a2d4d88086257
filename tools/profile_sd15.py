"""SD-1.5 component-level on-chip profile (VERDICT r2 item 1).

Decomposes the full txt2img step into CLIP encode, one UNet CFG step (b2),
and VAE decode, each measured by pipelined differencing (``2K`` chained
dispatches less ``K``, over ``K``: the fetch and the first dispatch's
latency cancel), and each annotated with XLA's flops/bytes cost analysis so
the roofline gap per component is visible.

Usage:  python tools/profile_sd15.py [--steps 20]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def pipelined_step_ms(fn, params, inputs, K=20, trials=5):
    import jax

    fetch = lambda out: np.asarray(jax.tree.leaves(out)[0])  # noqa: E731
    fetch(fn(params, inputs))
    dev = jax.device_put(inputs)

    def run(k):
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = fn(params, dev)
        fetch(out)
        return time.perf_counter() - t0

    run(K)
    est = []
    for _ in range(trials):
        t_k, t_2k = run(K), run(2 * K)
        est.append(max((t_2k - t_k) / K * 1000, 0.0))
    return float(np.median(est))


def cost(fn, params, inputs):
    ca = fn.lower(params, inputs).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    return float(ca["flops"]), float(ca.get("bytes accessed", 0.0))


def report(name, ms, fl, by, peak_fl=197e12, peak_bw=819e9):
    s = ms / 1000.0
    entry = {
        "component": name,
        "ms": round(ms, 2),
        "gflops": round(fl / 1e9, 1),
        "mb": round(by / 1e6, 1),
        "tflops": round(fl / s / 1e12, 1) if s else None,
        "mfu_pct": round(100 * fl / s / peak_fl, 1) if s else None,
        "hbm_pct": round(100 * by / s / peak_bw, 1) if s else None,
        "roofline_ms": round(max(fl / peak_fl, by / peak_bw) * 1000, 2),
    }
    print(json.dumps(entry), flush=True)
    return entry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--skip-full", action="store_true")
    ap.add_argument("--fp32-weights", action="store_true",
                    help="profile the fp32-at-rest tree (r2 behavior) instead "
                         "of the serving lane's bfloat16-at-rest")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from pytorch_zappa_serverless_tpu.engine.cache import setup_compile_cache
    from pytorch_zappa_serverless_tpu.models import sd15 as S
    from pytorch_zappa_serverless_tpu.models.clip_text import encode_text
    from pytorch_zappa_serverless_tpu.models.sd_unet import unet_apply
    from pytorch_zappa_serverless_tpu.models.sd_vae import vae_decode

    setup_compile_cache()
    cfg = S.FULL
    from pytorch_zappa_serverless_tpu.models.vision_common import (
        cast_params_at_rest)

    params = S.init_sd15_params(0, cfg)
    if not args.fp32_weights:
        params = cast_params_at_rest(params, jnp.bfloat16)
    params = jax.device_put(jax.tree.map(jnp.asarray, params))
    rng = np.random.default_rng(0)

    # CLIP text encode, b1 (the pipeline runs it twice: cond + uncond)
    ids = rng.integers(0, 49000, (1, 77), np.int32)
    clip_fn = jax.jit(lambda p, x: encode_text(p["clip"], x["ids"], cfg.clip,
                                               jnp.bfloat16))
    ms = pipelined_step_ms(clip_fn, params, {"ids": ids}, K=50)
    fl, by = cost(clip_fn, params, {"ids": ids})
    report("clip_encode_b1", ms, fl, by)

    # One UNet step at CFG batch (2x1), 64x64 latents
    lat2 = rng.standard_normal((2, 64, 64, 4)).astype(np.float32)
    ctx2 = rng.standard_normal((2, 77, 768)).astype(np.float32)
    t2 = np.full((2,), 500.0, np.float32)
    unet_fn = jax.jit(lambda p, x: unet_apply(p["unet"], x["lat"], x["t"],
                                              x["ctx"], cfg.unet, jnp.bfloat16))
    inp = {"lat": lat2, "t": t2, "ctx": ctx2}
    ms_unet = pipelined_step_ms(unet_fn, params, inp, K=20)
    fl_u, by_u = cost(unet_fn, params, inp)
    report("unet_cfg_step_b2", ms_unet, fl_u, by_u)

    # VAE decode, b1, 64x64 -> 512x512
    lat = rng.standard_normal((1, 64, 64, 4)).astype(np.float32)
    vae_fn = jax.jit(lambda p, x: vae_decode(p["vae"], x["lat"], cfg.vae,
                                             jnp.bfloat16))
    ms_vae = pipelined_step_ms(vae_fn, params, {"lat": lat}, K=10)
    fl_v, by_v = cost(vae_fn, params, {"lat": lat})
    report("vae_decode_b1", ms_vae, fl_v, by_v)

    print(json.dumps({
        "sum_ms": round(2 * ms + args.steps * ms_unet + ms_vae, 1),
        "formula": f"2*clip + {args.steps}*unet + vae",
    }), flush=True)

    if not args.skip_full:
        sv_inp = None
        from pytorch_zappa_serverless_tpu.config import ModelConfig
        from pytorch_zappa_serverless_tpu.utils.registry import get_model_builder
        from pytorch_zappa_serverless_tpu import models as _zoo  # noqa: F401

        sv = get_model_builder("sd15")(ModelConfig(
            name="sd15", dtype="bfloat16",
            extra={"num_steps": args.steps, "height": 512, "width": 512}))
        sample = sv.preprocess({"prompt": "a photo of a tpu", "seed": 0})
        sv_inp = {k: np.asarray(v)[None] for k, v in sample.items()}
        full_fn = jax.jit(sv.apply_fn)
        ms_full = pipelined_step_ms(full_fn, sv.params, sv_inp, K=3, trials=3)
        fl_f, by_f = cost(full_fn, sv.params, sv_inp)
        report("full_txt2img", ms_full, fl_f, by_f)


if __name__ == "__main__":
    main()
