"""Micro-bench: fused Pallas decode step vs XLA decode_segment, GPT-2 small.

Produces the numbers in docs/PERF_DECODE.md: wall ms/step by pipelined
differencing (each per-step dispatch pays the host's dispatch cost, unlike
in-scan serving) and the per-op DEVICE compute breakdown from a profiler
capture.  Run on the TPU:

    python tools/bench_fused_decode.py
"""
import sys
from pathlib import Path
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import time
import numpy as np
import jax
import jax.numpy as jnp

from pytorch_zappa_serverless_tpu.models.gpt2 import (
    SMALL, init_gpt2_params, decode_segment)
from pytorch_zappa_serverless_tpu.ops.fused_decode import (
    fused_attn_step, fused_mlp_step, fused_attn_step_int8,
    fused_mlp_step_int8)
from pytorch_zappa_serverless_tpu.ops.int8_matmul import (
    int8_matmul, pad_weights, quantize_per_channel)

cfg = SMALL
S, P, MAX_NEW = 8, 64, 32
T = P + MAX_NEW
D, H, F, L = cfg.d_model, cfg.heads, cfg.ffn_dim, cfg.layers
dtype = jnp.bfloat16

params = init_gpt2_params(0, cfg)
# bf16 at rest + fused qkv (int8-lane style) for the fused path
pf = {}
for k, v in params.items():
    if k.startswith("layer"):
        lp = params[k]
        pf[k] = {
            "ln1": lp["ln1"], "ln2": lp["ln2"],
            "qkv": {"kernel": np.concatenate([lp[n]["kernel"] for n in "qkv"], 1),
                    "bias": np.concatenate([lp[n]["bias"] for n in "qkv"])},
            "out": lp["out"], "fc1": lp["fc1"], "fc2": lp["fc2"],
        }
    else:
        pf[k] = v

def cast(tree):
    def c(x):
        x = jnp.asarray(x)
        if x.dtype.kind in "iub":  # int8 kernels, token ids: keep exactly
            return x
        return x.astype(dtype) if x.ndim >= 2 else x.astype(jnp.float32)
    return jax.tree.map(c, tree)

params_x = jax.device_put(cast(params))
params_f = jax.device_put(cast(pf))

rng = np.random.default_rng(0)
tok = jnp.asarray(rng.integers(1, 50000, (S,)), jnp.int32)
pos = jnp.asarray(rng.integers(P // 2, P, (S,)), jnp.int32)
fin = jnp.zeros((S,), bool)
temp = jnp.zeros((S,), jnp.float32)
seed = jnp.zeros((S,), jnp.int32)
step_ctr = jnp.zeros((S,), jnp.int32)

# --- XLA path: decode_segment seg=1 over [L, S, T, D] caches
ck_x = jnp.asarray(rng.standard_normal((L, S, T, D)) * 0.1, dtype)
cv_x = jnp.asarray(rng.standard_normal((L, S, T, D)) * 0.1, dtype)
seg_fn = jax.jit(lambda p, ck, cv, tok, pos, st, fin, temp, seed:
                 decode_segment(p, ck, cv, tok, pos, st, fin, temp, seed,
                                1, cfg, dtype),
                 donate_argnums=(1, 2))

# --- fused path: per-layer [T, S, D] tuples
cks = tuple(jnp.asarray(rng.standard_normal((T, S, D)) * 0.1, dtype) for _ in range(L))
cvs = tuple(jnp.asarray(rng.standard_normal((T, S, D)) * 0.1, dtype) for _ in range(L))

def fused_step(p, cks, cvs, tok, pos):
    x = (p["wte"].astype(dtype)[tok]
         + p["wpe"].astype(dtype)[jnp.minimum(pos, cfg.max_positions - 1)])
    kpos = jnp.arange(T)
    mask = jnp.where(kpos[:, None, None] <= pos[None, :, None], 0.0,
                     -1e9).astype(jnp.float32)
    new_k, new_v = [], []
    for i in range(L):
        lp = p[f"layer{i}"]
        x, ck, cv = fused_attn_step(
            x, lp["ln1"]["scale"], lp["ln1"]["bias"],
            lp["qkv"]["kernel"], lp["qkv"]["bias"],
            lp["out"]["kernel"], lp["out"]["bias"],
            cks[i], cvs[i], pos, mask, heads=H, eps=cfg.ln_eps)
        new_k.append(ck)
        new_v.append(cv)
        x = fused_mlp_step(x, lp["ln2"]["scale"], lp["ln2"]["bias"],
                           lp["fc1"]["kernel"], lp["fc1"]["bias"],
                           lp["fc2"]["kernel"], lp["fc2"]["bias"],
                           eps=cfg.ln_eps)
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    xn = ((x32 - mu) * jax.lax.rsqrt(var + cfg.ln_eps) * p["ln_f"]["scale"]
          + p["ln_f"]["bias"]).astype(dtype)
    w = p["wte"]
    logits = jax.lax.dot_general(xn.astype(w.dtype), w,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    return nxt, tuple(new_k), tuple(new_v)

fused_fn = jax.jit(fused_step, donate_argnums=(1, 2))

# --- fused INT8 path: same structure, halved weight stream
pq = {"wte": params_f["wte"], "wpe": params_f["wpe"], "ln_f": params_f["ln_f"]}
for i in range(L):
    lp = pf[f"layer{i}"]
    q_qkv, s_qkv = quantize_per_channel(np.asarray(lp["qkv"]["kernel"], np.float32), axis=0)
    q_out, s_out = quantize_per_channel(np.asarray(lp["out"]["kernel"], np.float32), axis=0)
    q_f1, s_f1 = quantize_per_channel(np.asarray(lp["fc1"]["kernel"], np.float32), axis=0)
    q_f2, s_f2 = quantize_per_channel(np.asarray(lp["fc2"]["kernel"], np.float32), axis=0)
    pq[f"layer{i}"] = {
        "ln1": lp["ln1"], "ln2": lp["ln2"],
        "qkv": {"kernel_q": q_qkv, "scale": s_qkv, "bias": lp["qkv"]["bias"]},
        "out": {"kernel_q": q_out, "scale": s_out, "bias": lp["out"]["bias"]},
        "fc1": {"kernel_q": q_f1, "scale": s_f1, "bias": lp["fc1"]["bias"]},
        "fc2": {"kernel_q": q_f2, "scale": s_f2, "bias": lp["fc2"]["bias"]},
    }
lm_q, lm_s = pad_weights(*quantize_per_channel(
    np.asarray(params["wte"], np.float32).T.copy(), axis=0))
pq["lm_q"], pq["lm_scale"] = jnp.asarray(lm_q), jnp.asarray(lm_s)
params_q = jax.device_put(cast(pq))

cks_q = tuple(jnp.asarray(rng.standard_normal((T, S, D)) * 0.1, dtype) for _ in range(L))
cvs_q = tuple(jnp.asarray(rng.standard_normal((T, S, D)) * 0.1, dtype) for _ in range(L))

def fused_step_int8(p, cks, cvs, tok, pos):
    x = (p["wte"].astype(dtype)[tok]
         + p["wpe"].astype(dtype)[jnp.minimum(pos, cfg.max_positions - 1)])
    kpos = jnp.arange(T)
    mask = jnp.where(kpos[:, None, None] <= pos[None, :, None], 0.0,
                     -1e9).astype(jnp.float32)
    new_k, new_v = [], []
    for i in range(L):
        lp = p[f"layer{i}"]
        x, ck, cv = fused_attn_step_int8(
            x, lp["ln1"]["scale"], lp["ln1"]["bias"],
            lp["qkv"]["kernel_q"], lp["qkv"]["bias"], lp["qkv"]["scale"],
            lp["out"]["kernel_q"], lp["out"]["bias"], lp["out"]["scale"],
            cks[i], cvs[i], pos, mask, heads=H, eps=cfg.ln_eps)
        new_k.append(ck)
        new_v.append(cv)
        x = fused_mlp_step_int8(
            x, lp["ln2"]["scale"], lp["ln2"]["bias"],
            lp["fc1"]["kernel_q"], lp["fc1"]["bias"], lp["fc1"]["scale"],
            lp["fc2"]["kernel_q"], lp["fc2"]["bias"], lp["fc2"]["scale"],
            eps=cfg.ln_eps)
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    xn = ((x32 - mu) * jax.lax.rsqrt(var + cfg.ln_eps) * p["ln_f"]["scale"]
          + p["ln_f"]["bias"]).astype(dtype)
    logits = int8_matmul(xn, p["lm_q"], p["lm_scale"],
                         out_dtype=jnp.float32)[:, :cfg.vocab_size]
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    return nxt, tuple(new_k), tuple(new_v)

fused_q_fn = jax.jit(fused_step_int8, donate_argnums=(1, 2))


def bench(run, k):
    t0 = time.perf_counter()
    out = None
    for _ in range(k):
        out = run(out)
    np.asarray(jax.tree.leaves(out)[0])
    return time.perf_counter() - t0


# XLA path — carry caches through via donation
state_x = {"ck": ck_x, "cv": cv_x, "tok": tok}
def run_x(prev):
    global state_x
    emits, ck, cv, tok2, *_ = seg_fn(params_x, state_x["ck"], state_x["cv"],
                                     state_x["tok"], pos, step_ctr, fin, temp, seed)
    state_x = {"ck": ck, "cv": cv, "tok": tok2}
    return emits

state_f = {"ck": cks, "cv": cvs, "tok": tok}
def run_f(prev):
    global state_f
    nxt, ck, cv = fused_fn(params_f, state_f["ck"], state_f["cv"],
                           state_f["tok"], pos)
    state_f = {"ck": ck, "cv": cv, "tok": nxt}
    return nxt

state_q = {"ck": cks_q, "cv": cvs_q, "tok": tok}
def run_q(prev):
    global state_q
    nxt, ck, cv = fused_q_fn(params_q, state_q["ck"], state_q["cv"],
                             state_q["tok"], pos)
    state_q = {"ck": ck, "cv": cv, "tok": nxt}
    return nxt

LANES = (("xla_seg1", run_x), ("fused", run_f), ("fused_int8", run_q))
for name, run in LANES:
    bench(run, 3)  # compile + warm
    K = 60
    t1 = bench(run, K)
    t2 = bench(run, 2 * K)
    print(f"{name}: {(t2 - t1) / K * 1000:.3f} ms/step")

# --- device trace of both paths
import tempfile, shutil
from pathlib import Path
from pytorch_zappa_serverless_tpu.utils.xplane import op_time_breakdown

for name, run in LANES:
    tmp = Path(tempfile.mkdtemp(prefix="fusedtrace-"))
    with jax.profiler.trace(str(tmp)):
        out = None
        for _ in range(20):
            out = run(out)
        np.asarray(jax.tree.leaves(out)[0])
    compute, counts, overlap, envelope = op_time_breakdown(tmp)
    total = sum(compute.values())
    print(f"== {name}: {total / 20 / 1e6:.3f} ms/step device compute")
    for fam, ns in compute.most_common(12):
        print(f"   {ns / 20 / 1e6:8.4f} ms  x{counts[fam]:4d}  {fam[:70]}")
    shutil.rmtree(tmp, ignore_errors=True)
