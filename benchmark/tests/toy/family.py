"""A second family, as a later PR would bring one: this module, its plain
reference and its configuration are new files, and no file of the harness
knows of them (``benchmark/families/__init__.py`` has the contract).  The
toy is nobody's model: one block, served through the program's ``gpt2``
builder because that is the decoder the program has."""

from __future__ import annotations

from benchmark.refcheck import walk
from benchmark.tests.toy import reference


def init_tree(seed: int, config: dict, serve: dict) -> dict:
    from pytorch_zappa_serverless_tpu.models.gpt2 import (GPT2Config,
                                                          init_gpt2_params)

    arch = serve["extra"]["arch"]
    assert len(config["layer_types"]) == arch["layers"]
    return init_gpt2_params(seed, GPT2Config(**arch))


def check(config: dict, serve: dict, checkpoint, runs: list) -> dict:
    """The walk every family may use, and one thing of the toy's own: the
    ``done`` event reached the check whole, both times."""
    for r in runs:
        for done in (r["done"], r["done_again"]):
            if done["tokens"] != r["tokens"] or "stats" not in done:
                return {"ok": False, "worst": float("inf"),
                        "note": f"the done event came through as {done}"}
    w = reference.load(checkpoint)
    heads = serve["extra"]["arch"]["heads"]
    return walk(lambda ids: reference.logits(w, ids, config["layer_types"],
                                             heads, config["norm_eps"]),
                runs, float(config["reference_tolerance"]))


def _sizes(serve: dict) -> tuple[int, int, int, int]:
    arch = serve["extra"]["arch"]
    return arch["d_model"], arch["ffn_dim"], arch["layers"], arch["vocab_size"]


def decode_step_bytes(config: dict, serve: dict, streams: list,
                      window_s: float) -> float:
    """bfloat16 matrices once a step; each stream's keys and values for as
    long as it decoded, a full layer keeping every position."""
    d, f, n, v = _sizes(serve)
    weights = 2 * (n * (4 * d * d + 2 * d * f) + v * d)
    held = sum(seconds / window_s * (prompt_len + tokens / 2)
               for seconds, prompt_len, tokens in streams)
    return weights + held * len(config["layer_types"]) * 2 * d * 2


def prefill_flops(config: dict, serve: dict, prompt_len: int) -> float:
    d, f, n, v = _sizes(serve)
    t = prompt_len
    return 2 * t * n * (4 * d * d + 2 * d * f) + 2 * n * t * t * d + 2 * d * v
