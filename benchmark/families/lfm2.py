"""LFM2 (``benchmark/families/__init__.py`` has the contract).

``serve.extra.arch`` is the program's ``LFM2Config``: the published widths,
the depth (``layer_types``) and the experts held (all of them here).  The
plain reference is ``benchmark/reference/lfm2.py``; the shape arithmetic is
here, because what a decode step reads is not the weights as stored: of the
experts it reads those its rows reach.  The routers' ``expert_bias`` is
balanced at staging and the served tokens are judged by the rule of
``benchmark/families/nemotron_h.py`` (both imported, not copied).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np

from benchmark.families.nemotron_h import balanced_bias, judge
from benchmark.reference import lfm2 as reference

ROOT = Path(__file__).resolve().parents[2]
# A staged tree this large is sent to a process of its own, on whatever
# device JAX finds there: the chip, once the server has left it.
OWN_PROCESS_BYTES = 1e9
REFERENCE_KEYS = ("layer_types", "dense_layers", "conv_kernel", "heads",
                  "kv_heads", "head_dim", "top_k", "routed_scale",
                  "expert_offset", "rope_theta", "norm_eps")
CALIBRATION_TOKENS = 512


def init_tree(seed: int, config: dict, serve: dict) -> dict:
    import ml_dtypes

    from pytorch_zappa_serverless_tpu.models.lfm2 import (config_from_arch,
                                                          init_lfm2_params)

    # Matrices are drawn straight into what they are staged as.
    dtype = (ml_dtypes.bfloat16 if config["weights"]["dtype"] == "bfloat16"
             else np.float32)
    tree = init_lfm2_params(seed, config_from_arch(serve["extra"]["arch"]),
                            dtype)
    return balance_routers(tree, seed, serve)


def balance_routers(tree: dict, seed: int, serve: dict) -> dict:
    """Each expert layer's ``expert_bias`` balanced over one seeded sequence
    by ``benchmark/families/nemotron_h.balanced_bias`` (whose docstring has
    the rule's sources and why seeded weights need it), layer by layer
    through the plain reference.  A calibration aimed at even loads, not a
    source's routing: real routing waits for real weights (PERF.md section
    7)."""
    import jax
    import jax.numpy as jnp

    keys = published(serve)
    scalars = reference.scalars_of(keys)
    ids = np.random.default_rng([seed, 7]).integers(
        0, serve["extra"]["arch"]["vocab_size"], CALIBRATION_TOKENS)
    with jax.default_matmul_precision("highest"):
        x = reference.widened(tree)["embed"][jnp.asarray(ids)]
        for i, kind in enumerate(keys["layer_types"]):
            p = reference.widened(tree[f"layer{i}"])
            routed = i >= keys["dense_layers"]
            if routed:
                # The row the router reads: after this layer's operator.
                c = dict(scalars)
                h = reference._norm(p["operator_norm"], x, c["norm_eps"])
                mid = x + (reference.conv(p, h, c) if kind == "conv"
                           else reference.attention(p, h, c))
                h = reference._norm(p["ffn_norm"], mid, c["norm_eps"])
                score = jax.nn.sigmoid(jnp.dot(h, p["router"]))
                bias = balanced_bias(np.asarray(score), keys["top_k"])
                tree[f"layer{i}"]["expert_bias"] = bias
                p["expert_bias"] = jnp.asarray(bias)
            x = reference.layer_fn(kind, routed, scalars)(p, x)
    return tree


def published(serve: dict) -> dict:
    """The keys the reference reads, as this run boots them."""
    arch = serve["extra"]["arch"]
    return {k: arch[k] for k in REFERENCE_KEYS}


def reference_logits(serve: dict, checkpoint, sequences: list, keep: int,
                     control: str | None = None) -> list:
    """The reference's logits at the last ``keep`` positions of each
    sequence; the real widths in a process of its own (above), a small tree
    here."""
    keys = published(serve)
    if Path(checkpoint).stat().st_size >= OWN_PROCESS_BYTES:
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["PYTHONPATH"] = str(ROOT)
        with tempfile.TemporaryDirectory() as tmp:
            req, out = Path(tmp) / "request.json", Path(tmp) / "logits.npz"
            req.write_text(json.dumps({"config": keys, "control": control,
                                       "keep": keep, "sequences": sequences}))
            proc = subprocess.run(
                [sys.executable, str(ROOT / "benchmark" / "reference"
                                     / "lfm2.py"), str(checkpoint),
                 str(req), str(out)], cwd=str(ROOT), env=env,
                capture_output=True, text=True)
            if proc.returncode == 0:
                print(f"[bench] reference computed in its own process: "
                      f"{proc.stdout.strip().splitlines()[-1]}", flush=True)
                with np.load(out) as z:
                    return [z[f"arr_{i}"] for i in range(len(sequences))]
            print(f"[bench] the reference's own process failed "
                  f"({proc.returncode}): {proc.stderr[-400:]}; computing "
                  f"here", flush=True)
    tree = reference.load_tree(checkpoint)
    return [reference.forward(tree, ids, keys, control, keep)
            for ids in sequences]


def check(config: dict, serve: dict, checkpoint, runs: list,
          control: str | None = None) -> dict:
    """``nemotron_h.judge`` (the rule for a family with a router: the share
    of served tokens that lie more than ``reference_tolerance`` under the
    reference's best, at most ``reference_far_share``) over the float32
    reference's logits at the served positions.  ``control="int8"`` is the
    reference in the nearest precision below the configuration's, which the
    same served tokens must fail (``chip_smoke.py`` lfm2 judges both on the
    chip)."""
    keep = max(len(r["tokens"]) for r in runs)
    return judge(config, runs, reference_logits(
        serve, checkpoint, [r["ids"] + r["tokens"][:-1] for r in runs], keep,
        control))


# -- shape arithmetic -----------------------------------------------------------

def kinds(serve: dict) -> dict:
    """How many layers hold each operator, and how many a dense (``D``) or a
    routed (``E``) feed-forward."""
    a = serve["extra"]["arch"]
    types = a["layer_types"]
    return {"conv": types.count("conv"),
            "full_attention": types.count("full_attention"),
            "D": min(a["dense_layers"], len(types)),
            "E": max(len(types) - a["dense_layers"], 0)}


def experts_held(serve: dict) -> int:
    return serve["extra"]["arch"]["experts_held"]


def expert_bytes(serve: dict) -> float:
    """One expert's three matrices, bfloat16."""
    a = serve["extra"]["arch"]
    return 3 * a["hidden_size"] * a["expert_width"] * 2


def row_bytes(serve: dict) -> float:
    """A K row and a V row of every attention layer, bfloat16."""
    a = serve["extra"]["arch"]
    return kinds(serve)["full_attention"] * 2 * a["kv_heads"] \
        * a["head_dim"] * 2


def layer_params(serve: dict) -> dict:
    """Matrix weights of each operator and feed-forward (``E``: the router
    alone; ``expert``: one routed expert)."""
    a = serve["extra"]["arch"]
    d = a["hidden_size"]
    q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return {"conv": d * 3 * d + d * d + a["conv_kernel"] * d,
            "full_attention": 2 * d * q + 2 * d * kv,
            "D": 3 * d * a["dense_width"],
            "E": d * a["experts_published"],
            "expert": 3 * d * a["expert_width"]}


def state_bytes(serve: dict) -> float:
    """What every slot's convolutions keep: the last rows of ``u``."""
    a = serve["extra"]["arch"]
    return kinds(serve)["conv"] * serve["extra"]["gen_slots"] \
        * (a["conv_kernel"] - 1) * a["hidden_size"] * 2


def decode_step_bytes(config: dict, serve: dict, streams: list,
                      window_s: float) -> float:
    """Every weight that is no routed expert once (bfloat16; the vectors are
    not counted), the tied head, the convolutions' state read and written
    (every slot's), the K/V rows the live streams hold, and the experts a
    step *reaches*: by the expectation under the window's mean live
    streams, ``held x (1 - (1 - top_k / experts_published) ** live)`` a
    layer, as ``benchmark/families/nemotron_h.py`` counts them.  The
    expectation assumes even routing, which the balanced biases aim at; the
    per-layer metric ``experts_touched_share`` is the check on it.  The
    bytes are assumed, not counted."""
    a = serve["extra"]["arch"]
    n, per = kinds(serve), layer_params(serve)
    live = sum(seconds for seconds, _, _ in streams) / window_s
    reached = experts_held(serve) * (
        1.0 - (1.0 - a["top_k"] / a["experts_published"]) ** live)
    rows = sum(seconds * (prompt_len + tokens / 2)
               for seconds, prompt_len, tokens in streams) / window_s
    plain = sum(n[k] * per[k] for k in ("conv", "full_attention", "D", "E"))
    return (2 * (plain + a["hidden_size"] * a["vocab_size"])
            + n["E"] * reached * expert_bytes(serve)
            + 2 * state_bytes(serve) + rows * row_bytes(serve))


def prefill_flops(config: dict, serve: dict, prompt_len: int) -> float:
    """Two operations a weight a token for what a token passes through (its
    ``top_k`` experts, all held here), the causal attention's scores and
    values, and the head for the one position that is sampled."""
    a = serve["extra"]["arch"]
    n, per = kinds(serve), layer_params(serve)
    share = experts_held(serve) / a["experts_published"]
    weights = (sum(n[k] * per[k] for k in ("conv", "full_attention", "D"))
               + n["E"] * (per["E"] + a["top_k"] * share * per["expert"]))
    attend = n["full_attention"] * 2 * 2 * a["heads"] * a["head_dim"] \
        * prompt_len ** 2 / 2
    return (2 * prompt_len * weights + attend
            + 2 * a["hidden_size"] * a["vocab_size"])
