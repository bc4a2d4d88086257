"""JoyAI-LLM-Flash (``benchmark/families/__init__.py`` has the contract).

``serve.extra.arch`` is the program's ``JoyAIConfig``: the published widths,
the depth (``layers``) and the share of the experts held (32 of 256 here).
The plain reference is ``benchmark/reference/joyai.py``; the shape arithmetic
is here, because what a decode step reads is not the weights as stored: of the
experts it reads those its rows reach, and of the pool one leaf of ``[c,
k_rope]`` rows, ``row_bytes`` a position a layer as stored.  The routers'
``expert_bias`` stays the zeros the initializer draws: balanced over 512
seeded tokens by ``benchmark/families/nemotron_h.balanced_bias`` (as
Nemotron-H's and LFM2's are) this family's routers sent a decode step's rows
to *fewer* experts, not more (13 of the 32 held a layer a step against 23-25
unbalanced: PERF.md section 6, PR 55).  The served tokens are judged by the
rule of ``benchmark/families/nemotron_h.py`` (imported, not copied).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np

from benchmark.families.nemotron_h import judge
from benchmark.reference import joyai as reference

ROOT = Path(__file__).resolve().parents[2]
# A staged tree this large is sent to a process of its own, on whatever
# device JAX finds there: the chip, once the server has left it.
OWN_PROCESS_BYTES = 1e9
REFERENCE_KEYS = ("layers", "dense_layers", "heads", "kv_lora_rank",
                  "nope_dim", "rope_dim", "top_k", "routed_scale",
                  "expert_offset", "rope_theta", "norm_eps")
# Queries and keys a block of the prompt kernel holds
# (ops/flash_attention.flash_attention from 1,024 positions on), and the
# lanes a width is padded to.
PROMPT_BLOCK = 1024
LANES = 128


def init_tree(seed: int, config: dict, serve: dict) -> dict:
    import ml_dtypes

    from pytorch_zappa_serverless_tpu.models.joyai import (config_from_arch,
                                                           init_joyai_params)

    # Matrices are drawn straight into what they are staged as.
    dtype = (ml_dtypes.bfloat16 if config["weights"]["dtype"] == "bfloat16"
             else np.float32)
    return init_joyai_params(seed, config_from_arch(serve["extra"]["arch"]),
                             dtype)


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
                                     / "joyai.py"), str(checkpoint),
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
    reference's logits at the served positions.  ``control`` is one of the
    reference's three (``int8``: the nearest precision below the
    configuration's; ``no_rope_score``: the shared rotated key left out of
    the score; ``raw_latent``: the latent without its norm), each of which
    the same served tokens must fail (``chip_smoke.py`` joyai judges all
    four on the chip)."""
    keep = max(len(r["tokens"]) for r in runs)
    return judge(config, runs, reference_logits(
        serve, checkpoint, [r["ids"] + r["tokens"][:-1] for r in runs], keep,
        control))


# -- shape arithmetic -----------------------------------------------------------

def kinds(serve: dict) -> dict:
    """How many layers keep latent rows (all of them), and how many a dense
    (``D``) or a routed (``E``) feed-forward."""
    a = serve["extra"]["arch"]
    return {"latent": a["layers"],
            "D": min(a["dense_layers"], a["layers"]),
            "E": max(a["layers"] - a["dense_layers"], 0)}


def experts_held(serve: dict) -> int:
    return serve["extra"]["arch"]["experts_held"]


def expert_bytes(serve: dict) -> float:
    """One expert's three matrices, bfloat16."""
    a = serve["extra"]["arch"]
    return 3 * a["hidden_size"] * a["expert_width"] * 2


def row_bytes(serve: dict) -> float:
    """One layer's row of one position as the leaf stores it, bfloat16:
    ``[c, k_rope]`` (576 values at the published widths) in whole lane tiles
    of 128 with zeros after them, 640 columns: what the chip keeps for a
    last dimension of 576 whatever the shape says, and what a block's copy
    moves (models/joyai.py ``JoyAIConfig.row_stored``)."""
    a = serve["extra"]["arch"]
    return -(-(a["kv_lora_rank"] + a["rope_dim"]) // LANES) * LANES * 2


def layer_params(serve: dict) -> dict:
    """Matrix weights of a layer's attention, the dense feed-forward
    (``D``), an expert layer outside its routed experts (``E``: the router
    and the shared expert) and one routed expert."""
    a = serve["extra"]["arch"]
    d, h = a["hidden_size"], a["heads"]
    qk = a["nope_dim"] + a["rope_dim"]
    return {"attention": (d * a["q_lora_rank"] + a["q_lora_rank"] * h * qk
                          + d * (a["kv_lora_rank"] + a["rope_dim"])
                          + a["kv_lora_rank"] * h * (a["nope_dim"]
                                                     + a["v_dim"])
                          + h * a["v_dim"] * d),
            "D": 3 * d * a["dense_width"],
            "E": d * a["experts_published"] + 3 * d * a["expert_width"],
            "expert": 3 * d * a["expert_width"]}


def rows_bytes(serve: dict, streams: list, window_s: float) -> float:
    """The latent rows an average decode step of the window reads: each
    live stream's positions (its prompt and half its answer) in every
    layer."""
    rows = sum(seconds * (prompt_len + tokens / 2)
               for seconds, prompt_len, tokens in streams) / window_s
    return rows * kinds(serve)["latent"] * row_bytes(serve)


def decode_step_bytes(config: dict, serve: dict, streams: list,
                      window_s: float) -> float:
    """Every weight that is no routed expert once (bfloat16; the vectors and
    the embedding's 64 rows are not counted), the untied head, the latent
    rows the live streams hold in every layer, and the experts a step
    *reaches*: by the expectation under the window's mean live streams,
    ``held x (1 - (1 - top_k / experts_published) ** live)`` a layer, as
    ``benchmark/families/nemotron_h.py`` counts them.  The expectation
    assumes even routing, which a seeded router with no bias comes near
    (0.72-0.78 of the held where even routing reaches 0.87); the per-layer metric
    ``experts_touched_share`` is the check on it.  The bytes are assumed,
    not counted."""
    a = serve["extra"]["arch"]
    n, per = kinds(serve), layer_params(serve)
    live = sum(seconds for seconds, _, _ in streams) / window_s
    reached = experts_held(serve) * (
        1.0 - (1.0 - a["top_k"] / a["experts_published"]) ** live)
    plain = (n["latent"] * per["attention"] + n["D"] * per["D"]
             + n["E"] * per["E"])
    return (2 * (plain + a["hidden_size"] * a["vocab_size"])
            + n["E"] * reached * expert_bytes(serve)
            + rows_bytes(serve, streams, window_s))


def attend_flops(serve: dict, prompt_len: int, visited: bool = False) -> float:
    """The prompt attention's operations in every layer, the non-absorbed
    form: scores over keys of ``nope_dim + rope_dim`` and values of
    ``v_dim`` a head, causal.  ``visited``: what the kernel computes for a
    bucket of ``prompt_len`` (whole blocks of ``PROMPT_BLOCK`` queries by as
    many keys, the diagonal's whole although half of it is masked, the keys
    padded to 256 lanes and the values to 128), where the other is what the
    mask and the widths leave."""
    a = serve["extra"]["arch"]
    n = kinds(serve)["latent"]
    qk, v = a["nope_dim"] + a["rope_dim"], a["v_dim"]
    if not visited:
        at = np.arange(prompt_len, dtype=np.float64) + 1
        return n * 2 * a["heads"] * (qk + v) * at.sum()
    blocks = -(-prompt_len // PROMPT_BLOCK)
    padded = -(-qk // LANES) * LANES + -(-v // LANES) * LANES
    return (n * 2 * a["heads"] * padded * PROMPT_BLOCK ** 2
            * blocks * (blocks + 1) / 2)


def prefill_flops(config: dict, serve: dict, prompt_len: int) -> float:
    """Two operations a weight a token for what a token passes through (the
    projections with K and V expanded a head, the dense layer, the router,
    the shared expert, and of its ``top_k`` experts the share held here),
    the causal attention as the mask leaves it, and the head for the one
    position that is sampled."""
    a = serve["extra"]["arch"]
    n, per = kinds(serve), layer_params(serve)
    share = experts_held(serve) / a["experts_published"]
    weights = (n["latent"] * per["attention"] + n["D"] * per["D"]
               + n["E"] * (per["E"] + a["top_k"] * share * per["expert"]))
    return (2 * prompt_len * weights + attend_flops(serve, prompt_len)
            + 2 * a["hidden_size"] * a["vocab_size"])
