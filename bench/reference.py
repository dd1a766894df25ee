"""Plain reference of the served models, and the comparison that decides
``correct``.

The forward pass is the published decoder (Llama / Qwen2 family: RMSNorm,
rotary embeddings on half-split heads, grouped-query attention with
optional q/k/v bias, SwiGLU MLP, tied output embedding) in straightforward
``jax.numpy`` at float32 with every matrix product at ``HIGHEST``
precision: no cache, no kernels, no batching.  QR-LoRA's update enters as
``x·W + ((x·B)·λ)·A`` on the adapted projections.  It imports nothing of
the program and reads only the tree ``weights.py`` made from the seed.

What is compared: for each sampled request, the reference runs once over
its prompt followed by the tokens the program served; at every served
position the gap is ``max(ref logits) − ref logit of the served token``
(0 where the program picked the reference's best).  The cell's number is
the widest such gap.  The control (``quant="fp8"``) computes every matrix
product with float8_e4m3 operands (per output channel for weights, per row
for activations) and reads the gap of the token that it puts first.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def _q8(x, axis):
    """Round to float8_e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., K) @ w (K, N) in float32, or with fp8 operands."""
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (L, heads, dh): rotate the two halves of each head (HF Llama)."""
    L, _, dh = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, lam, tokens, arch: Dict, quant: Optional[str] = None):
    """Logits (L, V) float32 of ``tokens`` (L,) under one tenant's λ
    (``{"wq": (layers, cap), "wv": (layers, cap)}``)."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    H, KV = arch["num_attention_heads"], arch["num_key_value_heads"]
    d = arch["hidden_size"]
    dh = d // H
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    g = params["groups"]
    ad = g["adapters"]["attn"]
    L = tokens.shape[0]
    causal = jnp.arange(L)[None, :] <= jnp.arange(L)[:, None]

    def layer(x, p):
        h = _rms(x, f32(p["ln1"]), eps)
        a = p["attn"]

        def proj(name, bias):
            y = _mm(h, f32(a[name]), quant)
            if name in p["ad"]:
                B, A, lm = p["ad"][name]
                y = y + _mm(_mm(h, f32(B), quant) * lm, f32(A), quant)
            if bias in a:
                y = y + f32(a[bias])
            return y

        q = _rope(proj("wq", "bq").reshape(L, H, dh), theta)
        k = _rope(proj("wk", "bk").reshape(L, KV, dh), theta)
        v = proj("wv", "bv").reshape(L, KV, dh)
        qg = q.reshape(L, KV, H // KV, dh)
        if quant == "fp8":
            qg, k, v = _q8(qg, -1), _q8(k, -1), _q8(v, -1)
        s = jnp.einsum("qgrd,kgd->grqk", qg, k, precision=HI) * dh ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if quant == "fp8":
            pr = _q8(pr, -1)
        o = jnp.einsum("grqk,kgd->qgrd", pr, v, precision=HI).reshape(L, H * dh)
        x = x + _mm(o, f32(a["wo"]), quant)
        h = _rms(x, f32(p["ln2"]), eps)
        m = p["mlp"]
        u = jax.nn.silu(_mm(h, f32(m["w_gate"]), quant)) * _mm(h, f32(m["w_up"]), quant)
        return x + _mm(u, f32(m["w_down"]), quant), None

    attn = {k: v for k, v in g["attn"].items()}
    adp = {n: (ad[n]["B"], ad[n]["A"], lam[n]) for n in ("wq", "wv") if n in ad}
    xs = {"ln1": g["ln1"], "ln2": g["ln2"], "attn": attn, "mlp": g["mlp"],
          "ad": adp}
    x = f32(params["embed"])[tokens]
    x, _ = jax.lax.scan(layer, x, xs)
    x = _rms(x, f32(params["final_norm"]), eps)
    return _mm(x, f32(params["embed"]).T, quant)


@functools.partial(jax.jit, static_argnames=("arch_items",))
def _served_gaps(params, lam, tokens, targets, arch_items):
    logits = forward(params, lam, tokens, dict(arch_items))
    pick = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - pick


@functools.partial(jax.jit, static_argnames=("arch_items", "quant"))
def _control_gaps(params, lam, tokens, arch_items, quant):
    ref = forward(params, lam, tokens, dict(arch_items))
    low = forward(params, lam, tokens, dict(arch_items), quant)
    pick = jnp.take_along_axis(ref, jnp.argmax(low, axis=1)[:, None], axis=1)[:, 0]
    return jnp.max(ref, axis=1) - pick


def _arch_items(arch: Dict):
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "rms_norm_eps", "rope_theta")
    return tuple((k, arch[k]) for k in keys)


def _pack(prompt, served, bucket):
    """Input = prompt + served[:-1], padded to a multiple of ``bucket``;
    row P-1+i predicts served[i]."""
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    L = -(-len(seq) // bucket) * bucket
    tokens = np.zeros((L,), np.int32)
    tokens[: len(seq)] = seq
    targets = np.zeros((L,), np.int32)
    P = len(prompt)
    targets[P - 1: P - 1 + len(served)] = served
    return tokens, targets, slice(P - 1, P - 1 + len(served))


def widest_gap(params, arch: Dict, samples, lam_of, bucket: int = 256,
               quant: Optional[str] = None) -> Dict[str, float]:
    """Widest gap over ``samples`` [(tenant, prompt, served tokens)].  With
    ``quant`` the control's gap instead of the program's."""
    items = _arch_items(arch)
    worst, n = 0.0, 0
    for tenant, prompt, served in samples:
        tokens, targets, rows = _pack(prompt, served, bucket)
        lam = {k: jnp.asarray(v) for k, v in lam_of(tenant).items()}
        if quant is None:
            g = _served_gaps(params, lam, jnp.asarray(tokens), jnp.asarray(targets), items)
        else:
            g = _control_gaps(params, lam, jnp.asarray(tokens), items, quant)
        g = np.asarray(g)[rows]
        worst = max(worst, float(g.max()))
        n += len(served)
    return {"widest_gap": worst, "tokens": n}
