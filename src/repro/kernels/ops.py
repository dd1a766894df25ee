"""Jit'd public wrappers around the Pallas kernels.

Handles shape padding to block multiples, batching conventions, backend
selection (``interpret=True`` on CPU so the same code path is testable
everywhere), and a custom VJP for the fused QR-LoRA matmul so it can sit on
the training path (B, A, W are frozen in QR-LoRA — their grads are zero).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import paged_decode_attention_kernel
from repro.kernels.qrlora_bgmv import (
    qrlora_bgmv_fused_sharded,
    qrlora_bgmv_kernel,
    qrlora_bgmv_quant_kernel,
)
from repro.kernels.qrlora_matmul import (
    qrlora_matmul_kernel,
    qrlora_matmul_quant_kernel,
)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, mult, axis):
    s = x.shape[axis]
    pad = (-s) % mult
    if pad == 0:
        return x, s
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), s


def _lane_block(dim: int, cap: int) -> int:
    """Block size for a lane-mapped (last-two) block dimension.  Mosaic
    takes a block dim that is a multiple of 128 or the whole array dim:
    the largest 128-multiple divisor of ``dim`` up to ``cap`` keeps VMEM
    bounded at wide layers, and the whole dim is the fallback (K = 576,
    N = 192 have no such divisor)."""
    for b in range(cap - cap % 128, 0, -128):
        if dim % b == 0:
            return b
    return dim


def _matmul_blocking(x2, N, K):
    """Shared tiling for the qrlora matmul kernels: bm is the whole M up to
    256 rows, else 256 with the rows padded to it; bn/bk per
    :func:`_lane_block` (never padded).  Returns (padded x2, original M,
    bm, bn, bk)."""
    M = x2.shape[0]
    bm = 256 if M % 256 == 0 or M > 256 else M
    x2, M0 = _pad_to(x2, bm, 0)
    return x2, M0, bm, _lane_block(N, 256), _lane_block(K, 512)


# ---------------------------------------------------------------------------
# qrlora_matmul with custom VJP (trains λ and x; W/B/A frozen → zero grads)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def qrlora_matmul(x, W, B, A, lam, scale: float = 1.0):
    return _qrlora_fwd_impl(x, W, B, A, lam, scale)


def _qrlora_fwd_impl(x, W, B, A, lam, scale):
    orig_shape = x.shape
    x2 = x.reshape(-1, x.shape[-1])
    K = x2.shape[1]
    N = W.shape[1]
    x2, M0, bm, bn, bk = _matmul_blocking(x2, N, K)
    y = qrlora_matmul_kernel(
        x2, W, B, A, lam, scale=scale, bm=bm, bn=bn, bk=bk,
        interpret=not _on_tpu(),
    )[:M0]
    return y.reshape(*orig_shape[:-1], N)


def _qrlora_fwd(x, W, B, A, lam, scale):
    return _qrlora_fwd_impl(x, W, B, A, lam, scale), (x, W, B, A, lam)


def _qrlora_bwd(scale, res, g):
    x, W, B, A, lam = res
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    lam32 = lam.astype(jnp.float32)
    gA = g2 @ A.astype(jnp.float32).T  # (M, r)
    dx = g2 @ W.astype(jnp.float32).T + ((gA * lam32) @ B.astype(jnp.float32).T) * scale
    dlam = ((x2 @ B.astype(jnp.float32)) * gA).sum(0) * scale
    return (
        dx.reshape(x.shape).astype(x.dtype),
        jnp.zeros_like(W),
        jnp.zeros_like(B),
        jnp.zeros_like(A),
        dlam.astype(lam.dtype),
    )


qrlora_matmul.defvjp(_qrlora_fwd, _qrlora_bwd)


# ---------------------------------------------------------------------------
# qrlora_bgmv — batched multi-λ adapter matmul (multi-tenant serving path)
# ---------------------------------------------------------------------------


def qrlora_bgmv(x, W, B, A, lam_table, seg, scale: float = 1.0):
    """``y[m] = x[m]·W + ((x[m]·B) * Λ[seg[m]])·A·scale`` via the Pallas kernel.

    ``x (..., K)``; ``seg`` is either per-*sequence* (``(batch,)`` for a
    ``(batch, S, K)`` input — every token of a sequence shares its tenant's
    λ) or per-row (``(M,)`` matching flattened x).  ``lam_table
    (n_slots, r)`` fp32.  Inference-only (no VJP): serving never
    differentiates through the λ gather.
    """
    orig_shape = x.shape
    x2 = x.reshape(-1, x.shape[-1])
    M, K = x2.shape
    N = W.shape[1]
    seg = seg.astype(jnp.int32)
    if x.ndim >= 3 and seg.shape[0] != M:
        # per-sequence ids → per-row ids (tokens inherit the sequence slot)
        seg = jnp.repeat(seg, M // seg.shape[0])
    x2, M0, bm, bn, bk = _matmul_blocking(x2, N, K)
    seg2, _ = _pad_to(seg, bm, 0)  # pad rows land in slot 0 (λ ≡ 0)
    y = qrlora_bgmv_kernel(
        x2, W, B, A, lam_table, seg2[:, None],
        scale=scale, bm=bm, bn=bn, bk=bk, interpret=not _on_tpu(),
    )[:M0]
    return y.reshape(*orig_shape[:-1], N)


# ---------------------------------------------------------------------------
# quantized-base variants (int8 / fp8-e4m3 W with per-output-channel scales)
# ---------------------------------------------------------------------------
#
# On TPU these run the fused dequant-in-epilogue kernels (W streams at 1
# byte/element, the bf16 copy is never materialized in HBM).  Off-TPU they
# run the XLA oracle instead of interpret mode — same policy as
# ``paged_decode_attention``: the oracle shares the kernels' exact
# epilogue expression tree, and interpret mode is the wrong thing to pay
# for on the CPU engine path.


def qrlora_matmul_quant(x, q, w_scale, B, A, lam, scale: float = 1.0):
    """Quantized-base ``y = (x·q)·w_scale + ((x·B)·λ)·A·scale``.

    ``q (K, N)`` int8/fp8-e4m3, ``w_scale (N,)`` fp32.  Inference-only
    (the quantized base sits behind frozen-W serving; training keeps bf16).
    """
    orig_shape = x.shape
    x2 = x.reshape(-1, x.shape[-1])
    K = x2.shape[1]
    N = q.shape[1]
    if not _on_tpu():
        y = ref.qrlora_matmul_quant_ref(x2, q, w_scale, B, A, lam, scale)
        return y.reshape(*orig_shape[:-1], N)
    x2, M0, bm, bn, bk = _matmul_blocking(x2, N, K)
    y = qrlora_matmul_quant_kernel(
        x2, q, w_scale, B, A, lam, scale=scale, bm=bm, bn=bn, bk=bk,
    )[:M0]
    return y.reshape(*orig_shape[:-1], N)


def _seg_rows(seg, x, M):
    seg = seg.astype(jnp.int32)
    if x.ndim >= 3 and seg.shape[0] != M:
        # per-sequence ids → per-row ids (tokens inherit the sequence slot)
        seg = jnp.repeat(seg, M // seg.shape[0])
    return seg


def qrlora_bgmv_quant(x, q, w_scale, B, A, lam_table, seg, scale: float = 1.0):
    """Quantized-base batched multi-λ matmul (see :func:`qrlora_bgmv`)."""
    orig_shape = x.shape
    x2 = x.reshape(-1, x.shape[-1])
    M, K = x2.shape
    N = q.shape[1]
    seg = _seg_rows(seg, x, M)
    if not _on_tpu():
        y = ref.qrlora_bgmv_quant_ref(x2, q, w_scale, B, A, lam_table, seg, scale)
        return y.reshape(*orig_shape[:-1], N)
    x2, M0, bm, bn, bk = _matmul_blocking(x2, N, K)
    seg2, _ = _pad_to(seg, bm, 0)  # pad rows land in slot 0 (λ ≡ 0)
    y = qrlora_bgmv_quant_kernel(
        x2, q, w_scale, B, A, lam_table, seg2[:, None],
        scale=scale, bm=bm, bn=bn, bk=bk,
    )[:M0]
    return y.reshape(*orig_shape[:-1], N)


def qrlora_bgmv_sharded(
    x, W, B, A, lam_table, seg, *, mesh, axis, scale: float = 1.0,
    w_scale=None,
):
    """Sharded-λ BGMV in one dispatch: local λ gather + psum + the rows
    kernel inside a single ``shard_map`` (``qrlora_bgmv_fused_sharded``).
    ``lam_table`` is sharded over ``axis``; everything else replicated.
    ``W`` may be int8/fp8 with ``w_scale`` — the fused kernel dequantizes
    in the epilogue.  Off-TPU this runs the same fused path in interpret
    mode (unit-test surface; the CPU *engine* keeps the two-step XLA path
    in ``adapter_api`` for speed).
    """
    orig_shape = x.shape
    x2 = x.reshape(-1, x.shape[-1])
    M, K = x2.shape
    N = W.shape[1]
    seg = _seg_rows(seg, x, M)
    x2, M0, bm, bn, bk = _matmul_blocking(x2, N, K)
    seg2, _ = _pad_to(seg, bm, 0)  # pad rows land in slot 0 (λ ≡ 0)
    y = qrlora_bgmv_fused_sharded(
        x2, W, B, A, lam_table, seg2,
        mesh=mesh, axis=axis, scale=scale, w_scale=w_scale,
        bm=bm, bn=bn, bk=bk, interpret=not _on_tpu(),
    )[:M0]
    return y.reshape(*orig_shape[:-1], N)


# ---------------------------------------------------------------------------
# attention wrappers — model layout (B, S, H, dh) ↔ kernel layout
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 512, bk: int = 512):
    """q (B,Sq,H,dh); k,v (B,Sk,KV,dh) → (B,Sq,H,dh)."""
    B, Sq, H, dh = q.shape
    interpret = not _on_tpu()
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = int(np.gcd(Sq, bq))
    bkk = int(np.gcd(kt.shape[2], bk))
    o = flash_attention_kernel(
        qt, kt, vt, causal=causal, bq=bq, bk=bkk, interpret=interpret
    )
    return o.transpose(0, 2, 1, 3)


def decode_attention(q, k_cache, v_cache, length, *, bk: int = 512):
    """q (B,1,H,dh) or (B,H,dh); caches (B,S,KV,dh) → same rank as q."""
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    interpret = not _on_tpu()
    S = k_cache.shape[1]
    bk = int(np.gcd(S, bk))
    o = decode_attention_kernel(q, k_cache, v_cache, length, bk=bk, interpret=interpret)
    return o[:, None] if squeeze else o


def paged_decode_attention(q, k_pool, v_pool, block_tbl, lengths):
    """q (B,1,H,dh) or (B,H,dh); pools (n_blocks, bs, KV, dh); block_tbl
    (B, max_blocks) int32; lengths (B,) int32 → same rank as q.

    On TPU this runs the fused multi-block Pallas kernel; off-TPU it runs
    the XLA gather reference instead of the kernel's interpret mode — the
    two are bit-identical (asserted in tests/test_paging.py) and interpret
    mode emulates the double-buffered DMA schedule step by step, which is
    exactly the wrong thing to pay for on a CPU smoke run.  Callers must
    pass ``lengths >= 1`` (the engine always does: the current position is
    valid); the reference's all-masked softmax would NaN on a zero.
    """
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    if _on_tpu():
        o = paged_decode_attention_kernel(q, k_pool, v_pool, block_tbl, lengths)
    else:
        o = ref.paged_decode_attention_ref(q, k_pool, v_pool, block_tbl, lengths)
    return o[:, None] if squeeze else o
