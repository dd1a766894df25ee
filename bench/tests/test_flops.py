"""Operation and byte counts against hand arithmetic at smollm-135m's
shapes."""
import json
from pathlib import Path

import pytest

from bench import flops

CONF = json.loads((Path(__file__).parents[1] / "configs" / "smollm-135m.json").read_text())


def test_bgmv_decode_call():
    shapes = [("bf16", (64, 576)), ("bf16", (64, 576)), ("bf16", (576, 576)),
              ("bf16", (576, 128)), ("bf16", (128, 576)), ("f32", (4097, 128)),
              ("s32", (64, 1))]
    fl, nb = flops.bgmv(shapes)
    # 2·64·576·576 + 2·64·576·128 + 2·64·128·576 + 64·128
    assert fl == 42_467_328 + 9_437_184 + 9_437_184 + 8_192
    # x, W, B, A (bf16), the 64 λ rows (f32), seg ids (s32), y (bf16)
    assert nb == 73_728 + 663_552 + 147_456 + 147_456 + 32_768 + 256 + 73_728


def test_matmul_params():
    # per layer q 576², k and v 2·576·192, o 576², mlp 3·576·1536
    per_layer = 331_776 + 221_184 + 331_776 + 2_654_208
    lora = (576 * 128 + 128 * 576) + (576 * 128 + 128 * 192)
    want = 30 * per_layer + 4 * lora + 576 * 49_152
    assert want == 135_462_912
    assert flops.matmul_params(CONF["architecture"], CONF["adapter"]) == want


def test_token_and_prefill_flops():
    arch, ad = CONF["architecture"], CONF["adapter"]
    assert flops.token_flops(arch, ad, 100) == 2 * 135_462_912 + 4 * 100 * 576 * 30
    # a 3-token prompt is three tokens at contexts 1, 2, 3
    three = sum(flops.token_flops(arch, ad, c) for c in (1, 2, 3))
    assert flops.prefill_flops(arch, ad, 0, 3) == pytest.approx(three)
    assert flops.prefill_flops(arch, ad, 2, 3) == pytest.approx(flops.token_flops(arch, ad, 3))
