"""Fused QR-LoRA matmul Pallas kernel.

Computes ``y = x·W + ((x·B)·λ)·A·scale`` in a single pass so the adapter
never materializes ΔW (an L×M HBM tensor) and x is read from HBM once.

Blocking (TPU, MXU-aligned 128-multiples):

  grid = (M/bm, N/bn, K/bk)  —  k innermost (arbitrary), m/n parallel.

  * ``acc``  (bm, bn) fp32 VMEM scratch — the W-path accumulator.
  * ``pacc`` (bm, r)  fp32 VMEM scratch — the x·B low-rank projection.
    It only depends on (m, k), so it is accumulated during the FIRST
    n-iteration of each m-row and reused for the remaining n-blocks —
    the low-rank FLOPs are paid once per row-block, not once per tile.

At the last k-block the low-rank term ``(pacc·λ)·A_n`` is added and the
tile is written out.  VMEM working set ≈ bm·bk + bk·bn + bm·bn + bk·r +
r·bn (+ scratch) — defaults (256,256,512, r≤256) ≈ 1.2 MB << 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, b_ref, a_ref, lam_ref, o_ref, acc_ref, pacc_ref, *, scale, nk, nn):
    n, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(n == 0, k == 0))
    def _init_p():
        pacc_ref[...] = jnp.zeros_like(pacc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(n == 0)
    def _lowrank_proj():
        pacc_ref[...] += jnp.dot(
            x_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(k == nk - 1)
    def _emit():
        lam = lam_ref[...].astype(jnp.float32)
        low = jnp.dot(
            pacc_ref[...] * lam[None, :],
            a_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        o_ref[...] = (acc_ref[...] + low * scale).astype(o_ref.dtype)


def _kernel_q(
    x_ref, q_ref, ws_ref, b_ref, a_ref, lam_ref, o_ref, acc_ref, pacc_ref,
    *, scale, nk, nn,
):
    """Quantized-base variant: W streams as int8/fp8 blocks plus a (N,)
    fp32 per-output-channel scale.  The int8/fp8 tile is widened to fp32
    in VMEM (never in HBM) and the dequant multiply lands once per output
    tile in the accumulator epilogue — HBM reads of W drop 2× (bf16→int8)
    while the λ/B/A adapter math is unchanged and full precision."""
    n, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(n == 0, k == 0))
    def _init_p():
        pacc_ref[...] = jnp.zeros_like(pacc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        q_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(n == 0)
    def _lowrank_proj():
        pacc_ref[...] += jnp.dot(
            x_ref[...], b_ref[...], preferred_element_type=jnp.float32
        )

    @pl.when(k == nk - 1)
    def _emit():
        lam = lam_ref[...].astype(jnp.float32)
        low = jnp.dot(
            pacc_ref[...] * lam[None, :],
            a_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        ws = ws_ref[...].astype(jnp.float32)  # (bn,)
        o_ref[...] = (acc_ref[...] * ws[None, :] + low * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "bm", "bn", "bk", "interpret")
)
def qrlora_matmul_quant_kernel(
    x: jax.Array,  # (M, K)
    q: jax.Array,  # (K, N) int8 / fp8-e4m3
    w_scale: jax.Array,  # (N,) fp32 per-output-channel dequant scale
    B: jax.Array,  # (K, r)
    A: jax.Array,  # (r, N)
    lam: jax.Array,  # (r,)
    *,
    scale: float = 1.0,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    N = q.shape[1]
    r = B.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        "caller (ops.qrlora_matmul) pads to block multiples"
    )
    assert w_scale.shape == (N,), "w_scale is per-output-channel (N,)"
    nk, nn = K // bk, N // bn
    grid = (M // bm, nn, nk)
    return pl.pallas_call(
        functools.partial(_kernel_q, scale=scale, nk=nk, nn=nn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),  # q(W)
            pl.BlockSpec((bn,), lambda i, j, k: (j,)),  # w_scale
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),  # B
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),  # A
            pl.BlockSpec((r,), lambda i, j, k: (0,)),  # lam
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(x, q, w_scale, B, A, lam)


@functools.partial(
    jax.jit, static_argnames=("scale", "bm", "bn", "bk", "interpret")
)
def qrlora_matmul_kernel(
    x: jax.Array,  # (M, K)
    W: jax.Array,  # (K, N)
    B: jax.Array,  # (K, r)
    A: jax.Array,  # (r, N)
    lam: jax.Array,  # (r,)
    *,
    scale: float = 1.0,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    N = W.shape[1]
    r = B.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        "caller (ops.qrlora_matmul) pads to block multiples"
    )
    nk, nn = K // bk, N // bn
    grid = (M // bm, nn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, nk=nk, nn=nn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),  # W
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),  # B
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),  # A
            pl.BlockSpec((r,), lambda i, j, k: (0,)),  # lam
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(x, W, B, A, lam)
