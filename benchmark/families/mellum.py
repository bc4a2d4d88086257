"""Mellum 2 (``benchmark/families/__init__.py`` has the contract).

``serve.extra.arch`` is the program's ``MellumConfig``: the published widths,
the depth (``layer_types``) and the experts held (all of them here).  The
plain reference is ``benchmark/reference/mellum.py``; the shape arithmetic is
here, because what a decode step reads is not the weights as stored: of the
experts it reads those its rows reach, and of a window layer's positions the
``sliding_window`` newest.  The router is a softmax with no bias, so nothing
is balanced at staging: routing is what a seeded router gives.  The served
tokens are judged by the rule of ``benchmark/families/nemotron_h.py``
(imported, not copied).
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
from benchmark.reference import mellum as reference

ROOT = Path(__file__).resolve().parents[2]
# A staged tree this large is sent to a process of its own, on whatever
# device JAX finds there: the chip, once the server has left it.
OWN_PROCESS_BYTES = 1e9
REFERENCE_KEYS = ("layer_types", "heads", "kv_heads", "head_dim",
                  "sliding_window", "top_k", "expert_offset", "rope_theta",
                  "yarn_factor", "yarn_original_positions", "yarn_beta_fast",
                  "yarn_beta_slow", "yarn_attention_factor", "norm_eps")
WINDOW, FULL = reference.WINDOW, reference.FULL
# Queries and keys a block of the prompt kernel holds
# (ops/flash_attention.flash_attention from 1,024 positions on).
PROMPT_BLOCK = 1024


def init_tree(seed: int, config: dict, serve: dict) -> dict:
    import ml_dtypes

    from pytorch_zappa_serverless_tpu.models.mellum import (
        config_from_arch, init_mellum_params)

    # Matrices are drawn straight into what they are staged as.
    dtype = (ml_dtypes.bfloat16 if config["weights"]["dtype"] == "bfloat16"
             else np.float32)
    return init_mellum_params(seed, config_from_arch(serve["extra"]["arch"]),
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
                                     / "mellum.py"), str(checkpoint),
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
    configuration's; ``window_as_full``: no band and no ring;
    ``no_yarn``: the full layers turned as the window layers), each of which
    the same served tokens must fail (``chip_smoke.py`` mellum judges all
    four on the chip)."""
    keep = max(len(r["tokens"]) for r in runs)
    return judge(config, runs, reference_logits(
        serve, checkpoint, [r["ids"] + r["tokens"][:-1] for r in runs], keep,
        control))


# -- shape arithmetic -----------------------------------------------------------

def kinds(serve: dict) -> dict:
    """How many layers hold each kind of attention (by the names the
    program's ``span_rows_by_kind`` counts them under), and how many routed
    experts (``E``: all of them)."""
    types = serve["extra"]["arch"]["layer_types"]
    return {WINDOW: types.count(WINDOW), FULL: types.count(FULL),
            "E": len(types)}


def experts_held(serve: dict) -> int:
    return serve["extra"]["arch"]["experts_held"]


def expert_bytes(serve: dict) -> float:
    """One expert's three matrices, bfloat16."""
    a = serve["extra"]["arch"]
    return 3 * a["hidden_size"] * a["expert_width"] * 2


def row_bytes(serve: dict) -> float:
    """A K row and a V row of one attention layer, bfloat16."""
    a = serve["extra"]["arch"]
    return 2 * a["kv_heads"] * a["head_dim"] * 2


def layer_params(serve: dict) -> dict:
    """Matrix weights of a layer's attention, its router (``E``) and one
    routed expert."""
    a = serve["extra"]["arch"]
    d = a["hidden_size"]
    q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return {"attention": 2 * d * q + 2 * d * kv,
            "E": d * a["experts_published"],
            "expert": 3 * d * a["expert_width"]}


def rows_read(serve: dict, position) -> float:
    """K/V rows (of one layer each, summed over the layers) a decode step at
    ``position`` reads: every position so far in a full layer, the window's
    newest in a window layer."""
    n, window = kinds(serve), serve["extra"]["arch"]["sliding_window"]
    return n[FULL] * position + n[WINDOW] * np.minimum(position, window)


def decode_step_bytes(config: dict, serve: dict, streams: list,
                      window_s: float) -> float:
    """Every weight that is no routed expert once (bfloat16; the vectors and
    the embedding's 32 rows are not counted), the untied head, the K/V rows
    the live streams hold in both kinds of layer, and the experts a step
    *reaches*: by the expectation under the window's mean live streams,
    ``held x (1 - (1 - top_k / experts_published) ** live)`` a layer, as
    ``benchmark/families/nemotron_h.py`` counts them.  The expectation
    assumes even routing; the per-layer metric ``experts_touched_share`` is
    the check on it, and ``ring_rows_share`` on the rows.  The bytes are
    assumed, not counted."""
    a = serve["extra"]["arch"]
    n, per = kinds(serve), layer_params(serve)
    live = sum(seconds for seconds, _, _ in streams) / window_s
    reached = experts_held(serve) * (
        1.0 - (1.0 - a["top_k"] / a["experts_published"]) ** live)
    rows = sum(seconds * rows_read(serve, prompt_len + tokens / 2)
               for seconds, prompt_len, tokens in streams) / window_s
    plain = n["E"] * (per["attention"] + per["E"])
    return (2 * (plain + a["hidden_size"] * a["vocab_size"])
            + n["E"] * reached * expert_bytes(serve)
            + rows * row_bytes(serve))


def attend_flops(serve: dict, prompt_len: int, visited: bool = False) -> float:
    """The prompt attention's operations, scores and values: causal in the
    full layers, the band in the window layers.  ``visited``: what the
    kernel computes for a bucket of ``prompt_len`` (whole blocks of
    ``PROMPT_BLOCK`` queries by as many keys, the diagonal's and a band's
    first whole although half of each is masked), where the other is what
    the mask leaves."""
    a = serve["extra"]["arch"]
    n, window = kinds(serve), a["sliding_window"]
    pair = 2 * 2 * a["heads"] * a["head_dim"]  # a query against a key
    if not visited:
        at = np.arange(prompt_len, dtype=np.float64) + 1
        return pair * (n[FULL] * at.sum()
                       + n[WINDOW] * np.minimum(at, window).sum())
    blocks = -(-prompt_len // PROMPT_BLOCK)
    band = sum(min(iq + 1, -(-(window - 1) // PROMPT_BLOCK) + 1)
               for iq in range(blocks))
    return pair * PROMPT_BLOCK ** 2 * (
        n[FULL] * blocks * (blocks + 1) / 2 + n[WINDOW] * band)


def prefill_flops(config: dict, serve: dict, prompt_len: int) -> float:
    """Two operations a weight a token for what a token passes through (its
    ``top_k`` experts, all held here), the attention of both kinds as the
    masks leave it, and the head for the one position that is sampled."""
    a = serve["extra"]["arch"]
    n, per = kinds(serve), layer_params(serve)
    share = experts_held(serve) / a["experts_published"]
    weights = n["E"] * (per["attention"] + per["E"]
                        + a["top_k"] * share * per["expert"])
    return (2 * prompt_len * weights + attend_flops(serve, prompt_len)
            + 2 * a["hidden_size"] * a["vocab_size"])
