"""Bytes and operations a GPT-2 step needs, from shapes alone.

``arch`` is the configuration's ``serve.extra.arch``; ``int8`` says the layer
matrices and the output head are held as int8 (W8A16: the arithmetic is still
bfloat16, so the compute peak is the bf16 one).
"""

from __future__ import annotations


def weight_bytes(arch: dict, int8: bool) -> int:
    """Bytes of weights one decode step reads, as they are stored: the four
    matrices of each layer (qkv 3d², out d², two feed-forward 4d² each), the
    output head (the tied table, vocab x d), and the float32 vectors (biases,
    layer norms, and with int8 one scale per output channel)."""
    d, f, n, v = (arch["d_model"], arch["ffn_dim"], arch["layers"],
                  arch["vocab_size"])
    per = 1 if int8 else 2
    matrices = n * (4 * d * d + 2 * d * f) + v * d
    vectors = n * (3 * d + d + f + d + 4 * d) + 2 * d  # biases + norms
    scales = (n * (3 * d + d + f + d) + v) if int8 else 0
    return matrices * per + 4 * (vectors + scales)


def kv_bytes(arch: dict, tokens: float) -> float:
    """Bytes of keys and values in bfloat16 for ``tokens`` cached positions."""
    return tokens * arch["layers"] * arch["d_model"] * 2 * 2


def decode_step_bytes(arch: dict, int8: bool, live_tokens: float) -> float:
    """What one decode step has to move: every weight once, and the keys and
    values of the positions that are live.  Bound: bandwidth."""
    return weight_bytes(arch, int8) + kv_bytes(arch, live_tokens)


def prefill_flops(arch: dict, prompt_tokens: int) -> float:
    """Operations to prefill one prompt of ``prompt_tokens``: two per
    multiply-add in the layer matrices for every token, causal attention
    (scores and weighted values, each n²d/2 multiply-adds a layer), and the
    output head for the last position only.  Bound: compute."""
    d, f, n, v = (arch["d_model"], arch["ffn_dim"], arch["layers"],
                  arch["vocab_size"])
    t = prompt_tokens
    matrices = 2 * t * n * (4 * d * d + 2 * d * f)
    attention = 2 * n * (t * t * d)  # 2 x (2 x t²d/2)
    return matrices + attention + 2 * d * v
