"""EvaByte (``benchmark/families/__init__.py`` has the contract).

``serve.extra.arch`` is the program's ``EvaByteConfig``.  The plain reference
is ``benchmark/reference/evabyte.py``; the shape arithmetic is here, because
what a stream holds is not linear in its positions: a query at ``t`` reads
``(t mod window) + 1`` exact rows and ``window / chunk x (t div window)``
summary rows, every row a K and a V of ``hidden_size``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np

from benchmark.refcheck import walk
from benchmark.reference import evabyte as reference

ROOT = Path(__file__).resolve().parents[2]
# A staged tree this large is sent to a process of its own, on whatever
# device JAX finds there: the chip, once the server has left it.  On the
# host's cores the 33 TFLOP of two reference sequences in float32 take
# minutes.  (By the file's size: reading 6.5 GB here to count them would be
# reading them twice.)
OWN_PROCESS_BYTES = 1e9


def init_tree(seed: int, config: dict, serve: dict) -> dict:
    from pytorch_zappa_serverless_tpu.models.evabyte import (
        EvaByteConfig, init_evabyte_params)

    return init_evabyte_params(seed, EvaByteConfig(**serve["extra"]["arch"]))


def published(config: dict, serve: dict) -> dict:
    """The keys the reference reads, as this run boots them (at rehearsal
    the tiny window and chunk laid over the file's)."""
    arch = serve["extra"]["arch"]
    return {"num_attention_heads": arch["heads"],
            "window_size": arch["window_size"],
            "chunk_size": arch["chunk_size"],
            "rope_theta": arch.get("rope_theta", config["rope_theta"]),
            "rms_norm_eps": config["rms_norm_eps"],
            "vocab_size": arch["vocab_size"]}


def reference_logits(config: dict, serve: dict, checkpoint, sequences: list,
                     int8: bool = False) -> list:
    """The reference's logits for each sequence; the real widths in a
    process of its own (above), a small tree here."""
    keys = published(config, serve)
    layers = serve["extra"]["arch"]["layers"]
    if Path(checkpoint).stat().st_size >= OWN_PROCESS_BYTES:
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["PYTHONPATH"] = str(ROOT)
        with tempfile.TemporaryDirectory() as tmp:
            req, out = Path(tmp) / "request.json", Path(tmp) / "logits.npz"
            req.write_text(json.dumps({"config": keys, "layers": layers,
                                       "int8": int8,
                                       "sequences": sequences}))
            proc = subprocess.run(
                [sys.executable, str(ROOT / "benchmark" / "reference"
                                     / "evabyte.py"), str(checkpoint),
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
    return [reference.forward(tree, ids, keys, layers, int8)
            for ids in sequences]


def check(config: dict, serve: dict, checkpoint, runs: list,
          int8: bool = False) -> dict:
    """Every served byte must be the float32 reference's best, or lie within
    ``reference_tolerance`` of it in the reference's own logits: the server
    computes in bfloat16, so where the reference's two best are closer than
    the rounding error either is a right answer.  ``int8`` is the control:
    the reference in the nearest precision below the configuration's, which
    the same served bytes must fail."""
    logits = reference_logits(
        config, serve, checkpoint,
        [r["ids"] + r["tokens"][:-1] for r in runs], int8)
    by_ids = {tuple(r["ids"] + r["tokens"][:-1]): lg
              for r, lg in zip(runs, logits)}
    return walk(lambda ids: by_ids[tuple(ids)], runs,
                float(config["reference_tolerance"]))


# -- shape arithmetic -----------------------------------------------------------

def _sizes(serve: dict) -> tuple[int, int, int, int, int, int]:
    a = serve["extra"]["arch"]
    return (a["hidden_size"], a["intermediate_size"], a["layers"],
            a["vocab_size"], a["window_size"], a["chunk_size"])


def span_rows(serve: dict, t) -> tuple:
    """``(exact, summaries)`` rows a query at position ``t`` reads."""
    *_, W, c = _sizes(serve)
    t = np.asarray(t)
    return t % W + 1, W // c * (t // W)


def weight_bytes(serve: dict) -> float:
    """What a decode step reads of the weights, as stored: every layer's
    matrices in bfloat16 and its four vectors in float32, the final norm,
    and prediction head 0's columns (the other seven heads are held and not
    read; a stream's one embedding row is not counted)."""
    d, f, n, v, *_ = _sizes(serve)
    return n * (2 * (4 * d * d + 3 * d * f) + 4 * 4 * d) + 4 * d + 2 * d * v


def row_bytes(serve: dict) -> float:
    """A K row and a V row of every layer, bfloat16."""
    d, _, n, *_ = _sizes(serve)
    return n * 2 * d * 2


def decode_step_bytes(config: dict, serve: dict, streams: list,
                      window_s: float) -> float:
    """Every weight once, and each stream's span for as long as it decoded:
    the mean, over the positions it made, of the exact and summary rows a
    query there reads."""
    held = 0.0
    for seconds, prompt_len, tokens in streams:
        exact, summaries = span_rows(
            serve, np.arange(prompt_len, prompt_len + max(tokens, 1)))
        held += seconds / window_s * float(np.mean(exact + summaries))
    return weight_bytes(serve) + held * row_bytes(serve)


def attend_flops(serve: dict, prompt_len: int) -> float:
    """The prompt attention alone: scores and weighted values of each
    query's exact and summary rows (two multiply-adds a row a column), and
    the pooling of every position into its chunk's summary (three dot
    products and two weighted sums a position)."""
    d, _, n, *_ = _sizes(serve)
    exact, summaries = span_rows(serve, np.arange(prompt_len))
    return n * (2 * 2 * d * float(np.sum(exact + summaries))
                + 10 * d * prompt_len)


def prefill_flops(config: dict, serve: dict, prompt_len: int) -> float:
    """Two operations a matrix weight a byte, the window and summary
    attention, and head 0 for the one position that is sampled."""
    d, f, n, v, *_ = _sizes(serve)
    return (2 * prompt_len * n * (4 * d * d + 3 * d * f)
            + attend_flops(serve, prompt_len) + 2 * d * v)
