"""Mixture-of-Experts FFN (mixtral / moonshot / jamba).

Token-choice top-k routing with capacity-bounded, sort-based dispatch:

  1. router → top-k experts per token (probs renormalized over the top-k),
  2. stable-sort token copies by expert id, compute each copy's slot inside
     its expert's capacity-C buffer (overflow drops, standard behaviour),
  3. gather → (E, C, d) dense buffers → batched MXU GEMMs (gate/up/down),
  4. scatter-add back to token order, weighted by router probs.

Every step is differentiable (gather/scatter-add); FLOPs are
``E·C·(6·d·f)`` ≈ ``capacity_factor × active-expert FLOPs`` — no dense
all-experts overcompute.

Distribution: the dispatch is *local* to each data shard (no cross-device
token routing) and each expert's hidden dim f is TP-sharded over the
``model`` axis, so the only collective is the usual row-parallel psum of the
(T, d) output.  This requires per-device concrete shapes → the block runs
inside ``shard_map`` when a mesh is active (see DESIGN.md §4; an EP variant
with all-to-all dispatch is evaluated in the §Perf hillclimb).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.sharding.rules import get_mesh, _rules


def init_moe_params(key, cfg: ModelConfig, n: int, dtype) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    import numpy as np

    std = 1.0 / np.sqrt(d)
    return {
        "w_router": (jax.random.normal(ks[0], (n, d, E), jnp.float32) * std).astype(
            jnp.float32
        ),
        "we_gate": (jax.random.normal(ks[1], (n, E, d, f), jnp.float32) * std).astype(dtype),
        "we_up": (jax.random.normal(ks[2], (n, E, d, f), jnp.float32) * std).astype(dtype),
        "we_down": (
            jax.random.normal(ks[3], (n, E, f, d), jnp.float32) * (1.0 / np.sqrt(f))
        ).astype(dtype),
    }


def _local_moe(
    x: jax.Array,  # (T, d) local tokens
    wr: jax.Array,  # (d, E)
    wg: jax.Array,  # (E, d, f_local)
    wu: jax.Array,
    wd: jax.Array,  # (E, f_local, d)
    *,
    k: int,
    capacity: int,
    psum_axis: Optional[str],
) -> Tuple[jax.Array, jax.Array]:
    T, d = x.shape
    E = wr.shape[-1]
    C = capacity
    logits = x.astype(jnp.float32) @ wr  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)  # (T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    flat_e = topi.reshape(-1)  # (T*k,) copy t*k+j belongs to token t
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=E)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[sorted_e]
    valid = pos < C
    slot = jnp.where(valid, sorted_e * C + pos, E * C)  # sentinel slot E*C
    # invert: slot → flat copy id (sentinel rows collect garbage, sliced off)
    inv = (
        jnp.full((E * C + 1,), T * k, jnp.int32)
        .at[slot]
        .set(order.astype(jnp.int32))
    )[: E * C]
    token_of_slot = jnp.where(inv < T * k, inv // k, T)  # T → zero row
    gate_of_slot = jnp.where(
        inv < T * k, topv.reshape(-1)[jnp.minimum(inv, T * k - 1)], 0.0
    )

    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = x_pad[token_of_slot].reshape(E, C, d)
    h = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", gathered, wg, preferred_element_type=jnp.float32)
    ) * jnp.einsum("ecd,edf->ecf", gathered, wu, preferred_element_type=jnp.float32)
    y_part = jnp.einsum(
        "ecf,efd->ecd", h.astype(x.dtype), wd, preferred_element_type=jnp.float32
    ).astype(jnp.float32)
    y = (
        jnp.zeros((T + 1, d), jnp.float32)
        .at[token_of_slot]
        .add(y_part.reshape(E * C, d) * gate_of_slot[:, None])
    )[:T]
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
    # load-balancing aux loss (Switch): E * Σ_e frac_tokens_e · mean_prob_e
    frac = jnp.mean(
        jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(axis=1), axis=0
    ) / k
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    return y.astype(x.dtype), aux


def moe_ffn(p: Dict, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x (B, S, d) → (y (B, S, d), aux loss scalar)."""
    B, S, d = x.shape
    k = cfg.experts_per_token
    E = cfg.n_experts
    mesh = get_mesh()
    if mesh is None or _rules().get("batch") is None:
        # single device, OR replicated-activation (weight-stationary decode /
        # dp_only) mode: run the dispatch in the global view and let GSPMD
        # partition the expert GEMMs against the sharded weights — partial
        # sums on activations instead of per-step expert-weight all-gathers.
        T = B * S
        C = max(8, int(cfg.capacity_factor * T * k / E + 0.999))
        C = min(C, T * k)
        y, aux = _local_moe(
            x.reshape(T, d),
            p["w_router"],
            p["we_gate"],
            p["we_up"],
            p["we_down"],
            k=k,
            capacity=C,
            psum_axis=None,
        )
        return y.reshape(B, S, d), aux

    rules = _rules()
    dp = tuple(rules.get("dp_axes") or ())
    model_ax = rules.get("model_axis")
    fsdp_axes = rules.get("fsdp")
    fsdp_axes = tuple(fsdp_axes) if fsdp_axes else ()
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_shardable = dp and (B % dp_size == 0)
    T_local = (B // dp_size if batch_shardable else B) * S
    C = max(8, int(cfg.capacity_factor * T_local * k / E + 0.999))
    C = min(C, T_local * k)

    x_spec = P(dp, None, None) if batch_shardable else P(None, None, None)
    f_ok = model_ax is not None and (cfg.d_ff % mesh.shape[model_ax] == 0)
    model_spec = "model" if f_ok else None
    # FSDP weights enter the shard_map STILL d-sharded and are all-gathered
    # INSIDE the body: an outside gather gets hoisted/CSE'd out of the layer
    # scan by XLA and materializes every layer's experts at once (observed:
    # +44 GiB on jamba train — see EXPERIMENTS.md §Perf).
    d_ok = fsdp_axes and all(cfg.d_model % mesh.shape[a] == 0 for a in fsdp_axes)
    fsdp_spec = fsdp_axes if d_ok else None
    we_spec = P(None, fsdp_spec, model_spec)
    wd_spec = P(None, model_spec, fsdp_spec)
    psum_axis = model_ax if f_ok else None

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), we_spec, we_spec, wd_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    def run(xl, wr, wg, wu, wd):
        if d_ok:
            wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axes, axis=2, tiled=True)
        Bl, Sl, _ = xl.shape
        y, aux = _local_moe(
            xl.reshape(Bl * Sl, d),
            wr,
            wg,
            wu,
            wd,
            k=k,
            capacity=C,
            psum_axis=psum_axis,
        )
        axes = dp if batch_shardable else ()
        aux_mean = jax.lax.pmean(aux, axes) if axes else aux
        if not batch_shardable and dp:
            aux_mean = jax.lax.pmean(aux_mean, dp)
        if model_ax is not None:
            aux_mean = jax.lax.pmean(aux_mean, model_ax)
        return y.reshape(Bl, Sl, d), aux_mean

    return run(x, p["w_router"], p["we_gate"], p["we_up"], p["we_down"])
