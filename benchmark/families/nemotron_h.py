"""Nemotron-H (``benchmark/families/__init__.py`` has the contract).

``serve.extra.arch`` is the program's ``NemotronHConfig``: the published
widths, the depth (``pattern``) and the chip's share (``experts_held`` of
``experts_published`` from ``expert_offset``, ``vocab_size`` of
``vocab_published``).  The plain reference is
``benchmark/reference/nemotron_h.py``; the shape arithmetic is here, because
what a decode step reads is not the weights as stored: of the experts held
it reads those its rows reach.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np

from benchmark.reference import nemotron_h as reference

ROOT = Path(__file__).resolve().parents[2]
# A staged tree this large is sent to a process of its own, on whatever
# device JAX finds there: the chip, once the server has left it (as
# benchmark/families/evabyte.py does, by the file's size).
OWN_PROCESS_BYTES = 1e9
REFERENCE_KEYS = ("pattern", "mamba_heads", "mamba_head_dim", "ssm_state",
                  "n_groups", "conv_kernel", "heads", "kv_heads", "head_dim",
                  "top_k", "routed_scale", "expert_offset", "norm_eps")


def init_tree(seed: int, config: dict, serve: dict) -> dict:
    import ml_dtypes

    from pytorch_zappa_serverless_tpu.models.nemotron_h import (
        config_from_arch, init_nemotron_params)

    # Matrices are drawn straight into what they are staged as.
    dtype = (ml_dtypes.bfloat16 if config["weights"]["dtype"] == "bfloat16"
             else np.float32)
    tree = init_nemotron_params(
        seed, config_from_arch(serve["extra"]["arch"]), dtype)
    return balance_routers(tree, seed, serve)


CALIBRATION_TOKENS = 512


def balanced_bias(score: np.ndarray, top_k: int, rounds: int = 300):
    """The bias [E] under which the rows of ``score`` [n, E] spread their
    ``top_k`` choices evenly over the experts: from minus each expert's mean
    score, then the update that this bias was introduced with (Wang et al.,
    "Auxiliary-Loss-Free Load Balancing Strategy for Mixture-of-Experts",
    arXiv:2408.15664, section 2.2; DeepSeek-V3, arXiv:2412.19437, section
    2.1.2, whose ``e_score_correction_bias`` the published config carries
    by name): ``b_i <- b_i + u sign(mean load - load_i)``, here with the
    step ``u`` shrinking.  The rule is theirs; the calibration (how many
    tokens, how many steps, the step's size) is this benchmark's own."""
    n, E = score.shape
    bias = -score.mean(0)
    for t in range(rounds):
        chosen = np.argpartition(-(score + bias), top_k - 1, axis=1)
        load = np.bincount(chosen[:, :top_k].ravel(), minlength=E)
        bias -= 0.01 * 0.985 ** t * np.sign(load - n * top_k / E)
    return bias.astype(np.float32)


def balance_routers(tree: dict, seed: int, serve: dict) -> dict:
    """Each expert layer's ``router_bias`` (the published
    ``e_score_correction_bias``) balanced over one seeded sequence
    (:func:`balanced_bias`), layer by layer through the plain reference.
    A model trained under that rule has had the bias moved against each
    expert's load all through training; seeded weights have no such
    history, and their scores
    share an offset an expert (the hidden states of different tokens have a
    direction in common), which sends most rows to a few experts: 51 of 128
    held experts reached a layer a step where even routing reaches 97, and
    81 with the mean score alone taken out (my chip runs, PR 45).  Balanced,
    the choice falls to what differs between tokens, as it does in a trained
    model, and a held expert sees the deployment's 1.4 rows a step.  This is
    a calibration aimed at even loads, not a source's routing: a trained
    model's loads are near even and not this even, and real routing waits
    for real weights (PERF.md section 7)."""
    import jax
    import jax.numpy as jnp

    keys = published(serve)
    scalars = reference.scalars_of(keys)
    ids = np.random.default_rng([seed, 7]).integers(
        0, serve["extra"]["arch"]["vocab_size"], CALIBRATION_TOKENS)
    with jax.default_matmul_precision("highest"):
        x = reference.widened(tree)["embed"][jnp.asarray(ids)]
        for i, kind in enumerate(keys["pattern"]):
            p = reference.widened(tree[f"layer{i}"])
            if kind == "E":
                h = reference._norm(p["norm"], x, keys["norm_eps"])
                score = jax.nn.sigmoid(jnp.dot(h, p["router"]))
                bias = balanced_bias(np.asarray(score), keys["top_k"])
                tree[f"layer{i}"]["router_bias"] = bias
                p["router_bias"] = jnp.asarray(bias)
            x = reference.layer_fn(kind, scalars)(p, x)
    return tree


def published(serve: dict) -> dict:
    """The keys the reference reads, as this run boots them."""
    arch = serve["extra"]["arch"]
    return {k: arch[k] for k in REFERENCE_KEYS}


def reference_logits(serve: dict, checkpoint, sequences: list,
                     control: str | None = None) -> list:
    """The reference's logits for each sequence; the real widths in a
    process of its own (above), a small tree here."""
    keys = published(serve)
    if Path(checkpoint).stat().st_size >= OWN_PROCESS_BYTES:
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["PYTHONPATH"] = str(ROOT)
        with tempfile.TemporaryDirectory() as tmp:
            req, out = Path(tmp) / "request.json", Path(tmp) / "logits.npz"
            req.write_text(json.dumps({"config": keys, "control": control,
                                       "sequences": sequences}))
            proc = subprocess.run(
                [sys.executable, str(ROOT / "benchmark" / "reference"
                                     / "nemotron_h.py"), str(checkpoint),
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
    return [reference.forward(tree, ids, keys, control) for ids in sequences]


def judge(config: dict, runs: list, logits: list) -> dict:
    """Served tokens against the reference's ``logits`` (one array a run,
    over ``ids + tokens[:-1]``: its last rows are the served positions),
    position by position as ``refcheck.walk`` takes them: a token is *far*
    when it lies more than
    ``reference_tolerance`` under the reference's best, and at most
    ``reference_far_share`` of the served tokens may be.

    Not the farthest token, as the families without a router are judged: a
    router's choice of ``top_k`` of ``experts_published`` flips on a
    bfloat16 rounding, a flipped expert moves that token's logits by tenths,
    and so the farthest of a few hundred sound tokens lies as far under as
    the farthest under a lower precision (0.41 and 0.40 of 8,192; the
    configuration's ``assumed.reference_tolerance`` has the readings).  How
    many lie far tells the two apart."""
    tol = float(config["reference_tolerance"])
    limit = float(config["reference_far_share"])
    under = np.concatenate([
        np.max(rows, -1) - rows[np.arange(len(r["tokens"])), r["tokens"]]
        for r, lg in zip(runs, logits)
        for rows in [np.asarray(lg)[-len(r["tokens"]):]]])
    far, share = int(np.sum(under > tol)), float(np.mean(under > tol))
    return {"ok": share <= limit, "worst": share,
            "note": f"{int(np.sum(under == 0.0))} of {under.size} served "
                    f"tokens are the float32 reference's best; {far} lie "
                    f"more than {tol} under it in the reference's logits, "
                    f"{share:.4f} of them (limit {limit}); the farthest "
                    f"{float(np.max(under)):.4f}"}


def check(config: dict, serve: dict, checkpoint, runs: list) -> dict:
    """:func:`judge` over the float32 reference's logits.  (The same served
    tokens under ``reference_logits(..., control="int8")``, the reference in
    the nearest precision below the configuration's, must come out not ok:
    ``chip_smoke.py`` nemotron judges both on the chip.)"""
    return judge(config, runs, reference_logits(
        serve, checkpoint, [r["ids"] + r["tokens"][:-1] for r in runs]))


# -- shape arithmetic -----------------------------------------------------------

def kinds(serve: dict) -> dict:
    """How many layers of each kind the pattern holds."""
    pattern = serve["extra"]["arch"]["pattern"]
    return {k: pattern.count(k) for k in "M*E"}


def experts_held(serve: dict) -> int:
    return serve["extra"]["arch"]["experts_held"]


def expert_bytes(serve: dict) -> float:
    """One expert's two matrices, bfloat16."""
    a = serve["extra"]["arch"]
    return 2 * a["latent_size"] * a["expert_width"] * 2


def layer_params(serve: dict) -> dict:
    """Matrix weights a layer of each kind holds, the routed experts apart
    (``expert``: one of them)."""
    a = serve["extra"]["arch"]
    d = a["hidden_size"]
    inner = a["mamba_heads"] * a["mamba_head_dim"]
    conv = inner + 2 * a["n_groups"] * a["ssm_state"]
    q, kv = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return {"M": d * (inner + conv + a["mamba_heads"]) + inner * d,
            "*": 2 * d * q + 2 * d * kv,
            "E": (d * a["experts_published"] + 2 * d * a["latent_size"]
                  + 2 * d * a["shared_width"]),
            "expert": 2 * a["latent_size"] * a["expert_width"]}


def state_bytes(serve: dict) -> float:
    """What every slot's Mamba-2 layers keep: the float32 state and the
    convolution's tail, bfloat16, of each."""
    a = serve["extra"]["arch"]
    inner = a["mamba_heads"] * a["mamba_head_dim"]
    conv = inner + 2 * a["n_groups"] * a["ssm_state"]
    return kinds(serve)["M"] * serve["extra"]["gen_slots"] * (
        inner * a["ssm_state"] * 4 + (a["conv_kernel"] - 1) * conv * 2)


def decode_step_bytes(config: dict, serve: dict, streams: list,
                      window_s: float) -> float:
    """Every weight that is no routed expert once (bfloat16; the vectors
    are not counted), the head, the state leaves read and written (every
    slot's: a finished slot's state is computed too), the K/V rows the live
    streams hold, and the experts a step *reaches*: by the expectation under
    the window's mean live streams, ``held x (1 - (1 - top_k /
    experts_published) ** live)`` a layer.  The expectation assumes even
    routing.  Seeded weights alone do not give it (51 of 128 held experts
    reached where it says 97); with the routers' biases balanced
    (:func:`balance_routers`) the cell reaches 0.70 of the held experts
    against the expectation's 0.755, so these bytes overcount the expert
    stream by about 7% and the step by about 4%.  The per-layer metric
    ``experts_touched_share`` is the check on it; the bytes are assumed, not
    counted (PERF.md section 7)."""
    a = serve["extra"]["arch"]
    n, per = kinds(serve), layer_params(serve)
    live = sum(seconds for seconds, _, _ in streams) / window_s
    reached = experts_held(serve) * (
        1.0 - (1.0 - a["top_k"] / a["experts_published"]) ** live)
    rows = sum(seconds * (prompt_len + tokens / 2)
               for seconds, prompt_len, tokens in streams) / window_s
    kv_row = n["*"] * 2 * a["kv_heads"] * a["head_dim"] * 2
    return (2 * (n["M"] * per["M"] + n["*"] * per["*"] + n["E"] * per["E"]
                 + a["hidden_size"] * a["vocab_size"])
            + n["E"] * reached * expert_bytes(serve)
            + 2 * state_bytes(serve) + rows * kv_row)


def prefill_flops(config: dict, serve: dict, prompt_len: int) -> float:
    """Two operations a weight a token for what a token passes through (of
    its ``top_k`` experts the share held here: 5.5 of 22), the causal
    attention's scores and values, and the head for the one position that
    is sampled."""
    a = serve["extra"]["arch"]
    n, per = kinds(serve), layer_params(serve)
    share = experts_held(serve) / a["experts_published"]
    weights = (n["M"] * per["M"] + n["*"] * per["*"]
               + n["E"] * (per["E"] + a["top_k"] * share * per["expert"]))
    attend = n["*"] * 2 * 2 * a["heads"] * a["head_dim"] * prompt_len ** 2 / 2
    return (2 * prompt_len * weights + attend
            + 2 * a["hidden_size"] * a["vocab_size"])
