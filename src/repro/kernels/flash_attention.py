"""Flash attention (prefill/train) Pallas kernel with GQA and causal skip.

Online-softmax over KV blocks; grid = (B·H, Sq/bq, Sk/bk) with the KV axis
innermost.  GQA is handled in the BlockSpec index maps (query head h reads
kv head h // (H/KV)) — K/V are never materially repeated.  Causal skipping:
KV blocks strictly above the diagonal write nothing and are masked; the
diagonal block applies the triangular mask.

VMEM working set ≈ bq·dh + 2·bk·dh + bq·bk (+ m/l/acc scratch); defaults
(bq=bk=512, dh≤256) ≈ 1.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, bq, bk, nk):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # (bq, dh)
    k = k_ref[0, 0]  # (bk, dh)
    v = v_ref[0, 0]

    def _block():
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq,bk)
        if causal:
            qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    if causal:
        # skip KV blocks strictly above the diagonal
        pl.when(iq * bq + bq - 1 >= ik * bk)(_block)
    else:
        _block()

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention_kernel(
    q: jax.Array,  # (B, H, Sq, dh)
    k: jax.Array,  # (B, KV, Sk, dh)
    v: jax.Array,
    *,
    causal: bool = True,
    bq: int = 512,
    bk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, dh = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    rep = H // KV
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    scale = dh**-0.5
    grid = (B * H, Sq // bq, Sk // bk)
    nk = Sk // bk
    return pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda bh, iq, ik: (bh // H, bh % H, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda bh, iq, ik: (bh // H, (bh % H) // rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda bh, iq, ik: (bh // H, (bh % H) // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda bh, iq, ik: (bh // H, bh % H, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
