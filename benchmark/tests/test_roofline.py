"""The roofline functions against sums worked out by hand for GPT-2 XL."""

import json
from pathlib import Path

from benchmark.roofline import gpt2

XL = json.loads((Path(__file__).resolve().parents[1] / "configs"
                 / "gpt2-xl.json").read_text())["serve"]["extra"]["arch"]


def test_xl_decode_step_bytes():
    # 48 layers x (4 x 1600² + 2 x 1600 x 6400) + 50257 x 1600 weights.
    assert gpt2.weight_bytes(XL, int8=False) == 1_554_971_200 * 2 + 4_006_400
    # int8: one byte a weight, and a float32 scale per output channel.
    scales = 48 * (3 * 1600 + 1600 + 6400 + 1600) + 50257
    assert gpt2.weight_bytes(XL, int8=True) \
        == 1_554_971_200 + 4 * (1_001_600 + scales)
    # 1000 live positions: K and V, 48 layers, 1600 wide, two bytes each.
    assert gpt2.kv_bytes(XL, 1000) == 307_200_000
    assert gpt2.decode_step_bytes(XL, False, 1000) == 3_421_148_800


def test_xl_prefill_512_flops():
    matrices = 2 * 512 * 1_474_560_000
    attention = 2 * 48 * 512 * 512 * 1600
    head = 2 * 1600 * 50257
    assert matrices == 1_509_949_440_000 and attention == 40_265_318_400
    assert gpt2.prefill_flops(XL, 512) == matrices + attention + head \
        == 1_550_375_580_800
