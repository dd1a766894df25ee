"""Fused paged decode-attention Pallas kernel: one launch per decode step.

The previous kernel put ``(lane, block)`` on the grid and let the BlockSpec
index map pull one pool block per grid cell — correct, but every block costs
a grid step and the online-softmax state lives in scratch between cells.
This version fuses the whole lane into **one grid cell**: the block table
and per-lane lengths ride as scalar-prefetch operands, the K/V pools stay
in HBM (``memory_space=ANY``), and the kernel walks the lane's table itself,
streaming pool blocks through VMEM with double-buffered async DMA

    k/v pool : (n_blocks, bs, KV, dh)   — stays in HBM
    strip    : (2, bs, KV, dh)          — VMEM landing slots (the DMA window)
    gather   : (max_blocks·bs, KV, dh)  — VMEM-resident gathered lane view

so a decode step is one kernel launch per batch instead of a pool gather
materialized in HBM plus a dense attend.  While strips land, the kernel
accumulates the running row-max online (max is exact, so blockwise
accumulation is bit-identical to a flat reduction); the exponentiation,
normalization and PV contraction run as a single fused epilogue over the
VMEM-resident strip at full table width — the same reduction shapes as
:func:`repro.kernels.ref.paged_decode_attention_ref`, which keeps the
kernel bit-identical to the oracle (asserted in tests, not just allclose).

Blocks past a lane's length still stream (the table is trash/stale there —
pool reads are cheap and keep the DMA pipeline regular) but their scores
are masked before the softmax, so trash and stale table entries cannot
contribute.  Callers bound the *table width* instead: the engine slices the
table to the active-lane block high-water mark (``attend_blocks``), so HBM
traffic tracks the longest live lane, not ``max_len``.

grid = (B,);  VMEM ≈ H·dh (q) + (2 + max_blocks)·bs·KV·dh (strips + gather)
+ H·max_blocks·bs (scores).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30
_LOOKAHEAD = 2  # DMA double-buffering depth (outstanding copies per pool)


def _kernel(
    tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
    k_strip, v_strip, k_gather, v_gather, scores, sem,
    *, scale, bs, max_blocks, rep,
):
    b = pl.program_id(0)
    length = len_ref[b]
    q = q_ref[0]  # (H, dh)
    H, dh = q.shape
    KV = k_strip.shape[2]

    def k_dma(i):
        return pltpu.make_async_copy(
            k_hbm.at[tbl_ref[b, i]], k_strip.at[jax.lax.rem(i, _LOOKAHEAD)],
            sem.at[jax.lax.rem(i, _LOOKAHEAD), 0])

    def v_dma(i):
        return pltpu.make_async_copy(
            v_hbm.at[tbl_ref[b, i]], v_strip.at[jax.lax.rem(i, _LOOKAHEAD)],
            sem.at[jax.lax.rem(i, _LOOKAHEAD), 1])

    k_dma(0).start()
    v_dma(0).start()

    qg = q.reshape(KV, rep, dh).astype(jnp.float32)

    def body(i, m):
        # start the next strip into the other slot (consumed last iteration)
        # while this one finishes — the classic two-slot pipeline
        @pl.when(i + 1 < max_blocks)
        def _prefetch():
            k_dma(i + 1).start()
            v_dma(i + 1).start()

        k_dma(i).wait()
        v_dma(i).wait()
        slot = jax.lax.rem(i, _LOOKAHEAD)
        k = k_strip[slot]  # (bs, KV, dh)
        k_gather[pl.ds(i * bs, bs)] = k
        v_gather[pl.ds(i * bs, bs)] = v_strip[slot]
        # score this strip while the next one is in flight; the running max
        # is exact under any association, so accumulating it online is
        # bit-identical to the oracle's flat reduction
        s = jnp.einsum("gri,kgi->grk", qg, k.astype(jnp.float32))
        s = (s * scale).reshape(H, bs)
        kpos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (H, bs), 1)
        s = jnp.where(kpos < length, s, _NEG)
        scores[:, pl.ds(i * bs, bs)] = s
        return jnp.maximum(m, s.max(axis=1, keepdims=True))

    m0 = jnp.full((H, 1), _NEG, jnp.float32)
    m = jax.lax.fori_loop(0, max_blocks, body, m0)

    # fused epilogue at full table width — reduction shapes match the oracle
    s = scores[...]  # (H, W) fp32, masked
    p = jnp.exp(s - m)
    p = p / p.sum(axis=1, keepdims=True)
    # the PV contraction broadcasts V to H heads first — same operand shapes
    # as the oracle's repeated-head einsum, so the k-axis summation
    # associates identically (the grouped form differs by an ulp at W=512)
    v = jnp.repeat(v_gather[...], rep, axis=1)  # (W, H, dh)
    o = jnp.einsum("hk,khd->hd", p.astype(v.dtype), v)
    # empty lanes (idle slots the engine discards) emit zeros, not NaN
    o_ref[0] = jnp.where(length > 0, o, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_kernel(
    q: jax.Array,  # (B, H, dh)
    k_pool: jax.Array,  # (n_blocks, bs, KV, dh)
    v_pool: jax.Array,
    block_tbl: jax.Array,  # (B, max_blocks) int32 pool indices
    lengths: jax.Array,  # (B,) int32 — valid positions per lane
    *,
    interpret: bool = False,
) -> jax.Array:
    B, H, dh = q.shape
    n_blocks, bs, KV, _ = k_pool.shape
    max_blocks = block_tbl.shape[1]
    rep = H // KV
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tbl, lengths
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, dh), lambda b, tbl, lens: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, dh), lambda b, tbl, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_LOOKAHEAD, bs, KV, dh), k_pool.dtype),
            pltpu.VMEM((_LOOKAHEAD, bs, KV, dh), v_pool.dtype),
            pltpu.VMEM((max_blocks * bs, KV, dh), k_pool.dtype),
            pltpu.VMEM((max_blocks * bs, KV, dh), v_pool.dtype),
            pltpu.VMEM((H, max_blocks * bs), jnp.float32),
            pltpu.SemaphoreType.DMA((_LOOKAHEAD, 2)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=dh**-0.5, bs=bs, max_blocks=max_blocks, rep=rep
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tbl.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pool, v_pool)
