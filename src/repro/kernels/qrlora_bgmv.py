"""Batched multi-λ QR-LoRA matmul (BGMV) Pallas kernel.

Multi-tenant serving: every QR-LoRA adapter of a layer shares the same
frozen pivoted-QR factors (B, A) — tenants differ only in the λ vector.
A heterogeneous batch therefore needs ONE extra gather, not per-tenant
weights:

    y[m] = x[m]·W + ((x[m]·B) * Λ[seg[m]]) · A · scale

with ``Λ (n_slots, r)`` the packed per-tenant λ table and ``seg (M,)`` the
per-row adapter-slot ids (slot 0 holds λ≡0, the base-model tenant).

Blocking is identical to ``qrlora_matmul`` (grid (M/bm, N/bn, K/bk), k
innermost, x·B projection accumulated once per row-block).  The per-row λ
gather is expressed as a one-hot (bm, n_slots) × (n_slots, r) matmul at the
emit step — MXU-friendly and free of dynamic-gather lowering restrictions;
the λ table rides whole in VMEM (n_slots·r·4B, ~40 KB at 64 slots × r=160).

VMEM working set ≈ qrlora_matmul + n_slots·r + bm·n_slots — still ≪ 16 MB
at the defaults.

Sharded λ tables: when the serving λ-store shards the slot axis over the
mesh model axis (``serving/lam_store.py``, ``lam_slots`` logical axis),
:func:`lam_gather_sharded` reassembles λ rows from *local* shards under
``shard_map`` — each device holds only ``n_slots / axis_size`` rows, and
the psum of one owned row plus exact zeros is bit-identical to a
replicated ``jnp.take``.

On the TPU path the gather no longer needs its own dispatch:
:func:`qrlora_bgmv_fused_sharded` runs ONE ``shard_map`` whose body does
the tiny local masked gather + (M, r) psum and feeds the reassembled λ
rows straight into :func:`qrlora_bgmv_rows_kernel` — a BGMV variant that
takes per-row λ via BlockSpec instead of the in-kernel one-hot × table
matmul.  (The gather must stay *outside* the Pallas body: summing
per-shard partial λ inside the epilogue would reassociate the float
contraction ``(pacc·λ)·A`` and break bit-identity with the replicated
engine.)

Quantized bases: ``*_quant`` / ``w_scale`` variants stream W as int8 or
fp8-e4m3 blocks plus a (N,) fp32 per-output-channel scale and dequantize
in the accumulator epilogue — see ``core/quantize.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def lam_gather_sharded(
    lam_table: jax.Array,  # (n_slots, r), sharded over axis 0 along `axis`
    seg: jax.Array,  # (B,) int32 global slot ids
    *,
    mesh,
    axis,
) -> jax.Array:
    """λ-row gather that consumes only the *local* shard of the slot table.

    Replicating a ``(n_slots, r)`` λ table on every device caps resident
    tenants at one device's HBM; sharding the slot axis over the mesh model
    axis divides it by the axis size.  Each device maps the global slot ids
    into its own shard (out-of-shard ids masked to exact zeros) and a psum
    reassembles the rows.  Every slot lives on exactly one shard, so the
    sum is one real row plus zeros — **bit-identical** to ``jnp.take`` on
    the replicated table (x + 0.0 is exact), which is what keeps the
    sharded engine's decode bitwise equal to the replicated one.
    """
    from jax.sharding import PartitionSpec as P

    def body(tab, seg_ids):
        n_local = tab.shape[0]
        local = seg_ids - jax.lax.axis_index(axis) * n_local
        ok = (local >= 0) & (local < n_local)
        rows = jnp.take(tab, jnp.clip(local, 0, n_local - 1), axis=0)
        rows = jnp.where(ok[:, None], rows, jnp.zeros_like(rows))
        return jax.lax.psum(rows, axis)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False,
    )(lam_table, seg.astype(jnp.int32))


def ba_gather_sharded(
    B: jax.Array,  # (..., K, r), sharded over the rank dim along `axis`
    A: jax.Array,  # (..., r, N), sharded over the rank dim along `axis`
    *,
    mesh,
    axis,
):
    """Reassemble the shared QR factors from rank-dim shards.

    Replicating B/A on every device is fine at rank 160, but a >1-host
    base replicates them per *host* too — sharding the rank dim over the
    mesh model axis (``qr_rank`` logical axis, ``sharding/rules.py``)
    divides their at-rest HBM by the axis size, the same way ``lam_slots``
    divides the λ tables.  ``all_gather(tiled=True)`` concatenates the
    shards back in device order — an exact reconstruction of the
    replicated arrays, no arithmetic — so every downstream contraction is
    **bit-identical** to the replicated engine.  (Contracting-dim GSPMD
    sharding would instead psum *partial float sums* and lose that.)
    """
    from jax.sharding import PartitionSpec as P

    def body(b, a):
        return (
            jax.lax.all_gather(b, axis, axis=b.ndim - 1, tiled=True),
            jax.lax.all_gather(a, axis, axis=a.ndim - 2, tiled=True),
        )

    b_spec = P(*([None] * (B.ndim - 1)), axis)
    a_spec = P(*([None] * (A.ndim - 2)), axis, None)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(b_spec, a_spec),
        out_specs=(P(), P()),
        check_vma=False,
    )(B, A)


def _kernel(
    x_ref, w_ref, b_ref, a_ref, lam_ref, seg_ref, o_ref, acc_ref, pacc_ref,
    *, scale, nk,
):
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
        table = lam_ref[...].astype(jnp.float32)  # (n_slots, r)
        seg = seg_ref[...]  # (bm, 1) int32
        n_slots = table.shape[0]
        slot_iota = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], n_slots), 1)
        onehot = (slot_iota == seg).astype(jnp.float32)  # (bm, n_slots)
        lam_rows = jnp.dot(onehot, table, preferred_element_type=jnp.float32)
        low = jnp.dot(
            pacc_ref[...] * lam_rows,
            a_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        o_ref[...] = (acc_ref[...] + low * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "bm", "bn", "bk", "interpret")
)
def qrlora_bgmv_kernel(
    x: jax.Array,  # (M, K)
    W: jax.Array,  # (K, N)
    B: jax.Array,  # (K, r)
    A: jax.Array,  # (r, N)
    lam_table: jax.Array,  # (n_slots, r)
    seg: jax.Array,  # (M, 1) int32
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
    n_slots = lam_table.shape[0]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        "caller (ops.qrlora_bgmv) pads to block multiples"
    )
    assert seg.shape == (M, 1), "seg must be (M, 1) int32 row slot-ids"
    nk, nn = K // bk, N // bn
    grid = (M // bm, nn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),  # W
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),  # B
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),  # A
            pl.BlockSpec((n_slots, r), lambda i, j, k: (0, 0)),  # Λ table
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),  # seg ids
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
        name="qrlora_bgmv",
    )(x, W, B, A, lam_table, seg)


def _kernel_q(
    x_ref, q_ref, ws_ref, b_ref, a_ref, lam_ref, seg_ref, o_ref,
    acc_ref, pacc_ref, *, scale, nk,
):
    """Quantized-base BGMV: identical to ``_kernel`` except W arrives as
    int8/fp8 blocks widened to fp32 in VMEM (never in HBM) and the (bn,)
    per-output-channel scale multiplies the accumulator in the epilogue."""
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
        table = lam_ref[...].astype(jnp.float32)  # (n_slots, r)
        seg = seg_ref[...]  # (bm, 1) int32
        n_slots = table.shape[0]
        slot_iota = jax.lax.broadcasted_iota(jnp.int32, (seg.shape[0], n_slots), 1)
        onehot = (slot_iota == seg).astype(jnp.float32)  # (bm, n_slots)
        lam_rows = jnp.dot(onehot, table, preferred_element_type=jnp.float32)
        low = jnp.dot(
            pacc_ref[...] * lam_rows,
            a_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        ws = ws_ref[...].astype(jnp.float32)  # (bn,)
        o_ref[...] = (acc_ref[...] * ws[None, :] + low * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "bm", "bn", "bk", "interpret")
)
def qrlora_bgmv_quant_kernel(
    x: jax.Array,  # (M, K)
    q: jax.Array,  # (K, N) int8 / fp8-e4m3
    w_scale: jax.Array,  # (N,) fp32 per-output-channel dequant scale
    B: jax.Array,  # (K, r)
    A: jax.Array,  # (r, N)
    lam_table: jax.Array,  # (n_slots, r)
    seg: jax.Array,  # (M, 1) int32
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
    n_slots = lam_table.shape[0]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        "caller (ops.qrlora_bgmv) pads to block multiples"
    )
    assert seg.shape == (M, 1), "seg must be (M, 1) int32 row slot-ids"
    assert w_scale.shape == (N,), "w_scale is per-output-channel (N,)"
    nk, nn = K // bk, N // bn
    grid = (M // bm, nn, nk)
    return pl.pallas_call(
        functools.partial(_kernel_q, scale=scale, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),  # q(W)
            pl.BlockSpec((bn,), lambda i, j, k: (j,)),  # w_scale
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),  # B
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),  # A
            pl.BlockSpec((n_slots, r), lambda i, j, k: (0, 0)),  # Λ table
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),  # seg ids
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
        name="qrlora_bgmv_quant",
    )(x, q, w_scale, B, A, lam_table, seg)


def _kernel_rows(
    x_ref, w_ref, ws_ref, b_ref, a_ref, rows_ref, o_ref, acc_ref, pacc_ref,
    *, scale, nk, widen,
):
    """BGMV over pre-gathered per-row λ: ``rows_ref`` is the (bm, r) fp32
    λ-row block, so the emit step skips the one-hot × table matmul and the
    whole-table VMEM residency.  This is what the fused sharded path feeds
    after its shard-local gather + psum.  ``widen`` (static) switches the
    base matmul to the int8/fp8 widen-to-fp32 form; ``ws`` is exactly 1.0
    per channel for unquantized W, which keeps the epilogue bit-identical
    to the plain kernel (x·1.0 is exact)."""
    n, k = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(n == 0, k == 0))
    def _init_p():
        pacc_ref[...] = jnp.zeros_like(pacc_ref)

    if widen:
        acc_ref[...] += jnp.dot(
            x_ref[...].astype(jnp.float32),
            w_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
    else:
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
        lam_rows = rows_ref[...].astype(jnp.float32)  # (bm, r)
        low = jnp.dot(
            pacc_ref[...] * lam_rows,
            a_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        ws = ws_ref[...].astype(jnp.float32)  # (bn,)
        o_ref[...] = (acc_ref[...] * ws[None, :] + low * scale).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "bm", "bn", "bk", "interpret")
)
def qrlora_bgmv_rows_kernel(
    x: jax.Array,  # (M, K)
    W: jax.Array,  # (K, N) — bf16/f32, or int8/fp8 when w_scale dequantizes
    w_scale: jax.Array,  # (N,) fp32; all-ones for unquantized W
    B: jax.Array,  # (K, r)
    A: jax.Array,  # (r, N)
    lam_rows: jax.Array,  # (M, r) fp32 pre-gathered per-row λ
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
        "caller pads to block multiples"
    )
    assert lam_rows.shape == (M, r), "lam_rows is (M, r) pre-gathered λ"
    assert w_scale.shape == (N,), "w_scale is per-output-channel (N,)"
    widen = W.dtype not in (x.dtype, jnp.float32)
    nk, nn = K // bk, N // bn
    grid = (M // bm, nn, nk)
    return pl.pallas_call(
        functools.partial(_kernel_rows, scale=scale, nk=nk, widen=widen),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),  # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),  # W / q
            pl.BlockSpec((bn,), lambda i, j, k: (j,)),  # w_scale
            pl.BlockSpec((bk, r), lambda i, j, k: (k, 0)),  # B
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),  # A
            pl.BlockSpec((bm, r), lambda i, j, k: (i, 0)),  # λ rows
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
        name="qrlora_bgmv_rows",
    )(x, W, w_scale, B, A, lam_rows)


def qrlora_bgmv_fused_sharded(
    x: jax.Array,  # (M, K), replicated
    W: jax.Array,  # (K, N) bf16/f32 or int8/fp8 (with w_scale), replicated
    B: jax.Array,  # (K, r), replicated
    A: jax.Array,  # (r, N), replicated
    lam_table: jax.Array,  # (n_slots, r), sharded over axis 0 along `axis`
    seg: jax.Array,  # (M,) int32 global slot ids
    *,
    mesh,
    axis,
    scale: float = 1.0,
    w_scale: jax.Array | None = None,  # (N,) fp32 when W is quantized
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Sharded-λ BGMV in ONE dispatch: shard-local masked gather + (M, r)
    psum + the rows kernel, all inside a single ``shard_map`` body —
    replaces the ``lam_gather_sharded`` dispatch followed by a separate
    matmul dispatch on the TPU path.

    The psum happens *before* the kernel on the tiny (M, r) λ rows, so the
    kernel consumes exactly the rows a replicated ``jnp.take`` would
    produce (one owned row + exact zeros per slot) and the result stays
    **bit-identical** to the replicated engine.  Summing per-shard partial
    λ contributions after the ``(pacc·λ)·A`` contraction instead would
    reassociate the float sum and lose that guarantee.
    """
    from jax.sharding import PartitionSpec as P

    ws = (
        w_scale
        if w_scale is not None
        else jnp.ones((W.shape[1],), jnp.float32)
    )

    def body(x_, W_, ws_, B_, A_, tab, seg_ids):
        n_local = tab.shape[0]
        local = seg_ids - jax.lax.axis_index(axis) * n_local
        ok = (local >= 0) & (local < n_local)
        rows = jnp.take(tab, jnp.clip(local, 0, n_local - 1), axis=0)
        rows = jnp.where(ok[:, None], rows, jnp.zeros_like(rows))
        rows = jax.lax.psum(rows.astype(jnp.float32), axis)
        return qrlora_bgmv_rows_kernel(
            x_, W_, ws_, B_, A_, rows,
            scale=scale, bm=bm, bn=bn, bk=bk, interpret=interpret,
        )

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(x, W, ws, B, A, lam_table, seg.astype(jnp.int32))
