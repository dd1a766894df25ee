"""Decode attention Pallas kernel: one query position vs a KV cache.

Serving hot-spot for the decode_32k / long_500k shapes: each step reads the
whole (S, KV, dh) cache — memory-bound.  The kernel streams KV blocks
through VMEM with online softmax, processing all H query heads of one batch
element per grid cell so the cache is read once for the whole GQA group
(H/KV heads share each KV block).

grid = (B, S/bk);  VMEM ≈ H·dh (q) + 2·bk·KV·dh (kv) + H·bk (scores).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, bk, nk, rep):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0]

    @pl.when(ik * bk < length)
    def _block():
        q = q_ref[0]  # (H, dh)
        k = k_ref[0]  # (bk, KV, dh)
        v = v_ref[0]
        H, dh = q.shape
        KV = k.shape[1]
        # GQA: expand kv → per-query-head scores without repeating in HBM
        qg = q.reshape(KV, rep, dh)
        s = jnp.einsum("gri,kgi->grk", qg.astype(jnp.float32), k.astype(jnp.float32))
        s = (s * scale).reshape(H, bk)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (H, bk), 1)
        s = jnp.where(kpos < length, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)  # (H, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jnp.einsum(
            "grk,kgi->gri",
            p.reshape(KV, rep, bk),
            v.astype(jnp.float32),
        ).reshape(H, dh)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_kernel(
    q: jax.Array,  # (B, H, dh)
    k_cache: jax.Array,  # (B, S, KV, dh)
    v_cache: jax.Array,
    length: jax.Array,  # () int32 — valid cache prefix
    *,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, dh = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    rep = H // KV
    bk = min(bk, S)
    assert S % bk == 0
    grid = (B, S // bk)
    lengths = jnp.full((B,), length, jnp.int32)
    return pl.pallas_call(
        functools.partial(_kernel, scale=dh**-0.5, bk=bk, nk=S // bk, rep=rep),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, ik: (b,)),  # length
            pl.BlockSpec((1, H, dh), lambda b, ik: (b, 0, 0)),
            pl.BlockSpec((1, bk, KV, dh), lambda b, ik: (b, ik, 0, 0)),
            pl.BlockSpec((1, bk, KV, dh), lambda b, ik: (b, ik, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, dh), lambda b, ik: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(lengths, q, k_cache, v_cache)
