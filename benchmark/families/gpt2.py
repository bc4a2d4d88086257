"""GPT-2 (``benchmark/families/__init__.py`` has the contract).

``serve.extra.arch`` is the program's ``GPT2Config``; ``params_dtype: int8``
says the layer matrices and the output head are held as int8 (W8A16).  The
plain reference is ``benchmark/reference/gpt2.py``, the shape arithmetic
``benchmark/roofline/gpt2.py``.
"""

from __future__ import annotations

from benchmark.refcheck import walk
from benchmark.reference import gpt2 as reference
from benchmark.roofline import gpt2 as shapes


def _int8(serve: dict) -> bool:
    return serve["extra"]["params_dtype"] == "int8"


def init_tree(seed: int, config: dict, serve: dict) -> dict:
    from pytorch_zappa_serverless_tpu.models.gpt2 import (GPT2Config,
                                                          init_gpt2_params)

    return init_gpt2_params(seed, GPT2Config(**serve["extra"]["arch"]))


def check(config: dict, serve: dict, checkpoint, runs: list) -> dict:
    """Every served token must be the float32 reference's best, or lie
    within ``reference_tolerance`` of it in the reference's own logits: the
    server computes in bfloat16, so where the reference's two best are
    closer than the rounding error either is a right answer."""
    arch = serve["extra"]["arch"]
    weights = reference.prepare(reference.load_tree(checkpoint),
                                arch["layers"], _int8(serve))
    return walk(
        lambda ids: reference.forward(weights, ids, arch["layers"],
                                      arch["heads"],
                                      float(config["layer_norm_epsilon"])),
        runs, float(config["reference_tolerance"]))


def decode_step_bytes(config: dict, serve: dict, streams: list,
                      window_s: float) -> float:
    """Every weight once, and the keys and values of the live positions:
    each stream holds its prompt and the tokens so far (half of them on
    average) while it decodes, and every layer keeps every position."""
    live = sum(seconds * (prompt_len + tokens / 2)
               for seconds, prompt_len, tokens in streams) / window_s
    return shapes.decode_step_bytes(serve["extra"]["arch"], _int8(serve),
                                    live)


def prefill_flops(config: dict, serve: dict, prompt_len: int) -> float:
    return shapes.prefill_flops(serve["extra"]["arch"], prompt_len)
