"""Quantized frozen-base weights: per-output-channel symmetric int8 / fp8.

QR-LoRA's premise is that the frozen base W dominates memory and bandwidth
while the adapter is ~601 λ scalars, so W is the natural quantization
target and the adapter is the natural thing to keep exact: the bf16/f32
QR delta ((x·B)·λ)·A rides on top of the dequantized base unchanged, which
is what keeps accuracy controlled (SBoRA / LoRA-Redux make the same
cheap-frozen-base + full-precision-tiny-adapter argument).

Representation
==============

A quantized weight replaces the ``(…, K, N)`` array with a two-leaf dict::

    {"q": int8|fp8 (…, K, N),  "scale": float32 (…, N)}

* **per-output-channel symmetric**: ``scale[…, n] = max_k |W[…, k, n]| / Q``
  with ``Q = 127`` (int8) or ``448`` (fp8-e4m3), so dequantization is a
  single per-column multiply *after* the contraction::

      x · W  ≈  (x · q) * scale          (exact in the scale: the multiply
                                          distributes over the K-sum)

  That is what lets the Pallas kernels dequantize **in the accumulator
  epilogue** — the int8/fp8 blocks stream from HBM, the fp32 accumulator
  is scaled once per output tile, and a bf16 copy of W is never
  materialized (``kernels/qrlora_matmul.py`` / ``qrlora_bgmv.py``).
* **dict-as-pytree**: the dict rides through ``jax.lax.scan`` layer
  stacking, ``_tslice``, donation and sharding exactly like the array it
  replaces — model code never branches on quantization; only
  ``adapter_api.adapted_matmul`` (the single W consumer) dispatches on it.

Error bound (asserted property-based in ``tests/test_quantize.py``): with
round-to-nearest, ``|W - dequant(quantize(W))| <= scale / 2`` per entry for
int8; fp8-e4m3 is bounded by half the ulp at the scaled magnitude (≤ 1/32
relative at Q=448 normals).

End-to-end ε (documented bound, asserted in
``tests/test_quantize.py``): an int8-quantized reduced engine's
float32 decode logits stay within ``INT8_LOGIT_EPS`` of the **unquantized
fp32 oracle** at matched-context positions — per-channel symmetric int8
is ≤ 0.4 % relative weight error, which compounds through the reduced
3-layer stack to well under this bound.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import BASE_DTYPES  # noqa: F401  (re-exported)

Pytree = Any

#: Largest finite magnitude representable per dtype (the symmetric range
#: the per-channel amax maps onto).  int8 uses 127 (not 128): symmetric,
#: so q = -q is always representable and dequant needs no zero-point.
_QMAX = {"int8": 127.0, "fp8": 448.0}

#: Documented end-to-end bound: max |Δlogit| of an int8-base float32
#: engine vs the unquantized fp32 merged-weight oracle at reduced scale,
#: over *matched-context* decode positions (greedy trajectories may
#: legitimately split on near-tie argmaxes once the perturbed logits
#: differ at all; after a split the positions compare different
#: contexts).  Measured worst case is ~5e-2 on the 3-layer reduced
#: smollm; 0.15 leaves ~3x headroom without letting a real numerics
#: regression through.
INT8_LOGIT_EPS = 0.15

#: Modules whose projection weights may be quantized.  xLSTM's ``x_qkv``
#: is consumed via array *slices* (``p["x_qkv"][..., 2d:]``) which a
#: dict-of-leaves cannot serve, so ssm modules stay in the native dtype.
_QUANTIZABLE_MODULES = ("attn", "mlp", "mamba", "xattn", "moe")


def is_quantized(W: Any) -> bool:
    """True when ``W`` is the quantized-weight dict ``{"q", "scale"}``."""
    return isinstance(W, dict) and "q" in W and "scale" in W


def quantize_weight(W: jax.Array, base_dtype: str) -> Dict[str, jax.Array]:
    """Per-output-channel symmetric quantization of a ``(…, K, N)`` weight.

    ``scale`` is computed over the contracting (-2) axis so dequantization
    commutes with the matmul: ``(x·q)*scale == x·(q*scale)`` exactly in
    real arithmetic, and the kernels apply it once per output tile.
    All-zero columns get scale 1 (q is zero there anyway — avoids 0/0).
    """
    if base_dtype not in _QMAX:
        raise ValueError(
            f"base_dtype={base_dtype!r} is not quantized; expected one of "
            f"{tuple(_QMAX)}"
        )
    qmax = _QMAX[base_dtype]
    W32 = W.astype(jnp.float32)
    amax = jnp.max(jnp.abs(W32), axis=-2)  # (…, N)
    scale = jnp.where(amax > 0, amax / qmax, jnp.ones_like(amax))
    scaled = W32 / scale[..., None, :]
    if base_dtype == "int8":
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:
        q = scaled.astype(jnp.float8_e4m3fn)
    return {"q": q, "scale": scale}


def dequantize_weight(qW: Dict[str, jax.Array], dtype=jnp.float32) -> jax.Array:
    """Materialize the full-precision weight (oracles / adapter merge only
    — the serving hot path never calls this)."""
    return (
        qW["q"].astype(jnp.float32) * qW["scale"][..., None, :]
    ).astype(dtype)


def quantization_error_bound(qW: Dict[str, jax.Array]) -> jax.Array:
    """Per-output-channel max-abs-error bound of int8 round-to-nearest:
    half a quantization step.  Broadcastable against the source W."""
    return qW["scale"][..., None, :] * 0.5


def quantized_bytes(qW: Dict[str, jax.Array]) -> int:
    return qW["q"].size * qW["q"].dtype.itemsize + qW["scale"].size * 4


def quantize_base_params(params: Pytree, base_dtype: str) -> Pytree:
    """Quantize every *adapted* base projection of a params tree in place
    (functionally): each ``groups[mod][proj]`` that carries an adapter
    under ``groups["adapters"][mod][proj]`` is replaced by its
    ``{"q", "scale"}`` dict.  λ, B, A, norms, embeddings and the unembed
    stay in the native dtype — the adapter delta and the softmax head are
    tiny next to W and carry the accuracy.

    ``base_dtype="bf16"`` returns ``params`` unchanged, so call sites can
    apply the knob unconditionally.
    """
    if base_dtype == "bf16":
        return params
    if base_dtype not in BASE_DTYPES:
        raise ValueError(
            f"base_dtype={base_dtype!r} must be one of {BASE_DTYPES}"
        )
    groups = dict(params["groups"])
    adapters = groups.get("adapters", {})
    for mod, projs in adapters.items():
        if mod not in groups or mod not in _QUANTIZABLE_MODULES:
            continue
        mod_params = dict(groups[mod])
        for proj in projs:
            W = mod_params.get(proj)
            if W is None or is_quantized(W):
                continue
            mod_params[proj] = quantize_weight(W, base_dtype)
        groups[mod] = mod_params
    return {**params, "groups": groups}


def resident_base_bytes(
    params: Pytree,
) -> Tuple[int, int]:
    """(quantized bytes, bytes the same leaves would cost at bf16) over
    every quantized projection — the README capacity-table datum."""
    qb = fb = 0
    for mod, projs in params["groups"].items():
        if mod == "adapters" or not isinstance(projs, dict):
            continue
        for leaf in projs.values():
            if is_quantized(leaf):
                qb += quantized_bytes(leaf)
                fb += leaf["q"].size * 2
    return qb, fb
