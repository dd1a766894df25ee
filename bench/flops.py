"""Operations and bytes, counted from shapes.

``bgmv`` is one call of the batched multi-λ QR-LoRA matmul
``y[m] = x[m]·W + ((x[m]·B)·Λ[seg[m]])·A``: what the algorithm needs, not
what a kernel happens to move (each lane's λ row, not the whole table).
``token_flops`` is the model's operations for one token at a given
context length: every matrix product of the published architecture, the
adapter on the adapted layers only, and attention over the context.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
               "f8e4m3fn": 1, "s4": 0.5}


def bgmv(shapes: Sequence[Tuple[str, Tuple[int, ...]]]) -> Tuple[float, float]:
    """(flops, bytes) of one call, from its HLO shapes: result, then x, W,
    B, A, λ table, seg ids."""
    (ydt, (M, N)), (xdt, (_, K)), (wdt, _), (bdt, (_, r)), (adt, _), (ldt, _), (sdt, _) = shapes[:7]
    flops = 2.0 * M * K * N + 2.0 * M * K * r + 2.0 * M * r * N + M * r
    b = DTYPE_BYTES
    nbytes = (b[xdt] * M * K + b[wdt] * K * N + b[bdt] * K * r + b[adt] * r * N
              + b[ldt] * M * r + b[sdt] * M + b[ydt] * M * N)
    return flops, nbytes


def matmul_params(arch: Dict, adapter: Dict) -> float:
    """Weights every token multiplies through (embedding lookup excluded,
    output projection included)."""
    d, ff, V = arch["hidden_size"], arch["intermediate_size"], arch["vocab_size"]
    H, KV = arch["num_attention_heads"], arch["num_key_value_heads"]
    L = arch["num_hidden_layers"]
    dh = d // H
    per_layer = d * H * dh + 2 * d * KV * dh + H * dh * d + 3 * d * ff
    lora = 0
    for proj, n_out in (("wq", H * dh), ("wv", KV * dh)):
        if proj in adapter["targets"]:
            cap = min(adapter["rank_cap"], d, n_out)
            lora += d * cap + cap * n_out
    return L * per_layer + adapter["last_layers"] * lora + d * V


def token_flops(arch: Dict, adapter: Dict, context: int) -> float:
    """Operations for one token that attends over ``context`` positions
    (itself included)."""
    d, H = arch["hidden_size"], arch["num_attention_heads"]
    attn = 4.0 * context * d * arch["num_hidden_layers"]  # QKᵀ and PV, H·dh = d
    return 2.0 * matmul_params(arch, adapter) + attn


def prefill_flops(arch: Dict, adapter: Dict, start: int, end: int) -> float:
    """Operations to prefill positions [start, end) of a prompt (positions
    before ``start`` came from the prefix cache)."""
    n = end - start
    ctx_sum = (end * (end + 1) - start * (start + 1)) / 2.0  # Σ (t+1)
    d, L = arch["hidden_size"], arch["num_hidden_layers"]
    return n * 2.0 * matmul_params(arch, adapter) + 4.0 * ctx_sum * d * L
