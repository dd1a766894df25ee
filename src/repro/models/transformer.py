"""Decoder LM covering every assigned family.

Layers are organized into *groups* scanned with ``jax.lax.scan`` so HLO size
is O(1) in depth (essential for the 512-device dry-run compiles):

* dense / audio : group = 1 transformer layer
* moe           : group = 1 layer with MoE FFN
* hybrid (jamba): group = ``hybrid_period`` (8) layers — 7 Mamba + 1
                  attention mixer, FFN alternating dense/MoE
* ssm (xlstm)   : group = the block pattern (mLSTM + sLSTM)
* vlm           : group = ``cross_attn_every`` (5) layers — 4 self-attn + 1
                  gated cross-attn against image embeddings

Each group carries its adapter slices under ``groups["adapters"][module]``,
so the PEFT factors ride through the same scan.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.adapter_api import adapted_matmul
from repro.models import attention as attn_lib
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import xlstm as xlstm_lib
from repro.models.layers import rms_norm, stacked_dense_init
from repro.sharding import shard

Pytree = Any


def _tslice(tree: Pytree, i: int) -> Pytree:
    return jax.tree_util.tree_map(lambda t: t[i], tree)


def _adp_for(
    adapters: Optional[Dict], module: str, seg_ids: Optional[jax.Array] = None
) -> Optional[Dict]:
    if not adapters or module not in adapters:
        return None
    # drop rank metadata before handing to adapted_matmul
    out = {
        proj: {k: v for k, v in leaf.items() if k != "ranks"}
        for proj, leaf in adapters[module].items()
    }
    if seg_ids is not None:
        # multi-tenant serving: per-sequence adapter-slot ids ride with each
        # projection's dict; the "lam" leaf is then the packed λ table
        # (n_slots, r) and adapted_matmul takes the BGMV path.
        for proj in out:
            out[proj]["seg"] = seg_ids
    return out


def gated_mlp(p: Dict, x: jax.Array, adp: Optional[Dict] = None) -> jax.Array:
    adp = adp or {}
    g = adapted_matmul(x, p["w_gate"], adp.get("w_gate"))
    u = adapted_matmul(x, p["w_up"], adp.get("w_up"))
    h = jax.nn.silu(g) * u
    h = shard(h, "batch", None, "ff")
    return shard(adapted_matmul(h, p["w_down"], adp.get("w_down")), "batch", None, None)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_decoder_params(key, cfg: ModelConfig, dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    d, V = cfg.d_model, cfg.vocab_size
    ks = iter(jax.random.split(key, 32))
    G = cfg.n_layers // cfg.group_size
    groups: Dict[str, Pytree] = {}

    fam = cfg.family
    if fam in ("dense", "audio", "moe"):
        groups["ln1"] = jnp.ones((G, d), dtype)
        groups["ln2"] = jnp.ones((G, d), dtype)
        groups["attn"] = attn_lib.init_attn_params(next(ks), cfg, G, dtype)
        if cfg.is_moe:
            groups["moe"] = moe_lib.init_moe_params(next(ks), cfg, G, dtype)
        else:
            groups["mlp"] = {
                "w_gate": stacked_dense_init(next(ks), G, d, cfg.d_ff, dtype),
                "w_up": stacked_dense_init(next(ks), G, d, cfg.d_ff, dtype),
                "w_down": stacked_dense_init(
                    next(ks), G, cfg.d_ff, d, dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5
                ),
            }
    elif fam == "hybrid":
        P = cfg.hybrid_period
        n_mamba, n_moe, n_dense = P - 1, P // 2, P - P // 2 - 1
        groups["ln_mixer"] = jnp.ones((G, P, d), dtype)
        groups["ln_ffn"] = jnp.ones((G, P, d), dtype)
        groups["attn"] = attn_lib.init_attn_params(next(ks), cfg, G, dtype)
        mam = mamba_lib.init_mamba_params(next(ks), cfg, G * n_mamba, dtype)
        groups["mamba"] = jax.tree_util.tree_map(
            lambda t: t.reshape(G, n_mamba, *t.shape[1:]), mam
        )
        moe = moe_lib.init_moe_params(next(ks), cfg, G * n_moe, dtype)
        groups["moe"] = jax.tree_util.tree_map(
            lambda t: t.reshape(G, n_moe, *t.shape[1:]), moe
        )
        mlp = {
            "w_gate": stacked_dense_init(next(ks), G * n_dense, d, cfg.d_ff, dtype),
            "w_up": stacked_dense_init(next(ks), G * n_dense, d, cfg.d_ff, dtype),
            "w_down": stacked_dense_init(
                next(ks), G * n_dense, cfg.d_ff, d, dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5
            ),
        }
        groups["mlp"] = jax.tree_util.tree_map(
            lambda t: t.reshape(G, n_dense, *t.shape[1:]), mlp
        )
    elif fam == "ssm":
        pat = cfg.xlstm_pattern
        groups["ln"] = jnp.ones((G, len(pat), d), dtype)
        if "m" in pat:
            groups["mlstm"] = xlstm_lib.init_mlstm_params(next(ks), cfg, G, dtype)
        if "s" in pat:
            groups["slstm"] = xlstm_lib.init_slstm_params(next(ks), cfg, G, dtype)
    elif fam == "vlm":
        P = cfg.cross_attn_every
        n_self = P - 1
        groups["ln1"] = jnp.ones((G, P, d), dtype)
        groups["ln2"] = jnp.ones((G, P, d), dtype)
        att = attn_lib.init_attn_params(next(ks), cfg, G * n_self, dtype)
        groups["attn"] = jax.tree_util.tree_map(
            lambda t: t.reshape(G, n_self, *t.shape[1:]), att
        )
        groups["xattn"] = attn_lib.init_attn_params(next(ks), cfg, G, dtype, cross=True)
        mlp = {
            "w_gate": stacked_dense_init(next(ks), G * P, d, cfg.d_ff, dtype),
            "w_up": stacked_dense_init(next(ks), G * P, d, cfg.d_ff, dtype),
            "w_down": stacked_dense_init(
                next(ks), G * P, cfg.d_ff, d, dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5
            ),
        }
        groups["mlp"] = jax.tree_util.tree_map(lambda t: t.reshape(G, P, *t.shape[1:]), mlp)
    else:
        raise ValueError(f"unknown family {fam}")

    params = {
        "embed": (jax.random.normal(next(ks), (V, d), jnp.float32) * 0.02).astype(dtype),
        "final_norm": jnp.ones((d,), dtype),
        "unembed": (jax.random.normal(next(ks), (d, V), jnp.float32) * (d**-0.5)).astype(dtype),
        "groups": groups,
    }
    if fam == "vlm":
        params["img_proj"] = stacked_dense_init(next(ks), 1, cfg.d_image, d, dtype)[0]
    return params


# ---------------------------------------------------------------------------
# Group bodies — (x, cache_slice) → (x, new_cache_slice, aux)
# ---------------------------------------------------------------------------


def _ckpt(fn, train: bool):
    """Per-position remat inside multi-layer groups: during the group's
    backward only ONE layer's intermediates are live (without this, a
    jamba group holds 7 Mamba layers' recomputed state tensors at once)."""
    if not train:
        return fn
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.nothing_saveable, prevent_cse=False
    )


def _group_body(
    cfg: ModelConfig, p, x, cache_sl, positions, img, decode, train=False, seg_ids=None,
    length=None, attend_blocks=None, n_valid=None,
):
    fam = cfg.family
    adapters = p.get("adapters")
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Pytree] = {}

    if fam in ("dense", "audio", "moe"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        out, nc = attn_lib.attention(
            p["attn"], h, cfg, positions=positions,
            adp=_adp_for(adapters, "attn", seg_ids),
            cache=cache_sl.get("attn") if cache_sl else None,
            attend_blocks=attend_blocks, n_valid=n_valid,
        )
        if nc is not None:
            new_cache["attn"] = nc
        x = x + out
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            y, aux = moe_lib.moe_ffn(p["moe"], h, cfg)
        else:
            y = gated_mlp(p["mlp"], h, _adp_for(adapters, "mlp", seg_ids))
        x = x + y

    elif fam == "hybrid":
        P = cfg.hybrid_period
        mi = di = oi = 0
        nm_state = {"conv": [], "h": []}
        for i in range(P):
            h = rms_norm(x, p["ln_mixer"][i], cfg.norm_eps)
            if i == cfg.hybrid_attn_index:
                out, nc = _ckpt(
                    lambda h: attn_lib.attention(
                        p["attn"], h, cfg, positions=positions,
                        adp=_adp_for(adapters, "attn", seg_ids),
                        cache=cache_sl.get("attn") if cache_sl else None,
                        attend_blocks=attend_blocks,
                    ),
                    train,
                )(h)
                if nc is not None:
                    new_cache["attn"] = nc
            else:
                mp = _tslice(p["mamba"], mi)
                st = _tslice(cache_sl["mamba"], mi) if cache_sl else None
                out, ns = _ckpt(
                    lambda h, mp=mp, st=st: mamba_lib.mamba_mixer(
                        mp, h, cfg, state=st, adp=_adp_for(adapters, "mamba", seg_ids),
                        length=length,
                    ),
                    train,
                )(h)
                if ns is not None:
                    nm_state["conv"].append(ns["conv"])
                    nm_state["h"].append(ns["h"])
                mi += 1
            x = x + out
            h = rms_norm(x, p["ln_ffn"][i], cfg.norm_eps)
            if i % 2 == 1:
                y, a = _ckpt(
                    lambda h, oi=oi: moe_lib.moe_ffn(_tslice(p["moe"], oi), h, cfg),
                    train,
                )(h)
                aux = aux + a
                oi += 1
            else:
                y = _ckpt(
                    lambda h, di=di: gated_mlp(
                        _tslice(p["mlp"], di), h, _adp_for(adapters, "mlp", seg_ids)
                    ),
                    train,
                )(h)
                di += 1
            x = x + y
        if nm_state["conv"]:
            new_cache["mamba"] = {
                "conv": jnp.stack(nm_state["conv"]),
                "h": jnp.stack(nm_state["h"]),
            }

    elif fam == "ssm":
        for j, kind in enumerate(cfg.xlstm_pattern):
            h = rms_norm(x, p["ln"][j], cfg.norm_eps)
            if kind == "m":
                st = cache_sl.get("mlstm") if cache_sl else None
                out, ns = _ckpt(
                    lambda h, st=st: xlstm_lib.mlstm_mixer(
                        p["mlstm"], h, cfg, state=st, adp=_adp_for(adapters, "mlstm", seg_ids),
                        length=length,
                    ),
                    train,
                )(h)
                if ns is not None:
                    new_cache["mlstm"] = ns
            else:
                st = cache_sl.get("slstm") if cache_sl else None
                out, ns = _ckpt(
                    lambda h, st=st: xlstm_lib.slstm_mixer(
                        p["slstm"], h, cfg, state=st, adp=_adp_for(adapters, "slstm", seg_ids),
                        length=length,
                    ),
                    train,
                )(h)
                if ns is not None:
                    new_cache["slstm"] = ns
            x = x + out

    elif fam == "vlm":
        P = cfg.cross_attn_every
        for i in range(P - 1):
            h = rms_norm(x, p["ln1"][i], cfg.norm_eps)
            ap = _adp_for(adapters, "attn")
            ap = jax.tree_util.tree_map(lambda t: t[i], ap) if ap else None
            st = _tslice(cache_sl["attn"], i) if cache_sl else None
            out, nc = _ckpt(
                lambda h, i=i, ap=ap, st=st: attn_lib.attention(
                    _tslice(p["attn"], i), h, cfg, positions=positions, adp=ap, cache=st
                ),
                train,
            )(h)
            if nc is not None:
                new_cache.setdefault("attn", []).append(nc)
            x = x + out
            h = rms_norm(x, p["ln2"][i], cfg.norm_eps)
            x = x + _ckpt(
                lambda h, i=i: gated_mlp(_tslice(p["mlp"], i), h), train
            )(h)
        # gated cross-attention layer
        h = rms_norm(x, p["ln1"][P - 1], cfg.norm_eps)
        out, _ = attn_lib.attention(
            p["xattn"], h, cfg, positions=positions,
            adp=_adp_for(adapters, "xattn"), cross_kv=img,
        )
        x = x + jnp.tanh(p["xattn"]["xa_gate"]).astype(x.dtype) * out
        h = rms_norm(x, p["ln2"][P - 1], cfg.norm_eps)
        x = x + gated_mlp(_tslice(p["mlp"], P - 1), h)
        if "attn" in new_cache:
            new_cache["attn"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_cache["attn"]
            )
    else:
        raise ValueError(fam)

    return x, (new_cache or None), aux


# ---------------------------------------------------------------------------
# Full forward passes
# ---------------------------------------------------------------------------


def _embed_input(params, cfg, tokens, embeds):
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.dtype))
    else:
        x = params["embed"][tokens]
    return shard(x, "batch", None, None)


def _run_groups(
    params, cfg: ModelConfig, x, positions, cache, img, decode, train, seg_ids=None,
    length=None, attend_blocks=None, n_valid=None,
):
    groups = params["groups"]
    G = jax.tree_util.tree_leaves(groups)[0].shape[0]
    # A paged attention cache (a block table is present) is written in place:
    # its stacked K/V pools ride the carry, and each layer writes into and
    # gathers from them at its own index (xs), so no layer's pool is sliced,
    # relaid out or stacked back.  Dense and recurrent caches go through xs.
    pools = None
    if cache is not None and "block_tbl" in cache.get("attn", {}):
        att = dict(cache["attn"])
        pools = (att.pop("k"), att.pop("v"))
        cache = {**cache, "attn": att}

    def body(carry, xs):
        x, aux, pools = carry
        p, cache_sl, layer = xs
        if pools is not None:
            cache_sl = {**cache_sl, "attn": {
                **cache_sl["attn"], "k": pools[0], "v": pools[1], "layer": layer,
            }}
        x, new_c, a = _group_body(
            cfg, p, x, cache_sl, positions, img, decode, train=train and cfg.remat,
            seg_ids=seg_ids, length=length, attend_blocks=attend_blocks,
            n_valid=n_valid,
        )
        if pools is not None:
            att = dict(new_c["attn"])
            pools = (att.pop("k"), att.pop("v"))
            new_c = {**new_c, "attn": att}
        return (x, aux + a, pools), new_c

    f = body
    if train and cfg.remat:
        f = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable, prevent_cse=False
        )

    carry = (x, jnp.zeros((), jnp.float32), pools)
    layers = jnp.arange(G, dtype=jnp.int32)
    if cfg.scan_layers:
        carry, new_cache = jax.lax.scan(f, carry, (groups, cache, layers))
    else:
        ncs = []
        for i in range(G):
            carry, nc = f(carry, (
                _tslice(groups, i), _tslice(cache, i) if cache is not None else None,
                layers[i],
            ))
            ncs.append(nc)
        new_cache = (
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ncs) if ncs[0] is not None else None
        )
    x, aux, pools = carry
    if pools is not None:
        new_cache = {**new_cache, "attn": {
            **new_cache["attn"], "k": pools[0], "v": pools[1],
        }}
    return x, aux, new_cache


def decoder_apply(
    params, cfg: ModelConfig, tokens=None, embeds=None, image_embeds=None, train=True,
    seg_ids=None,
):
    """Full-sequence forward → (logits (B,S,V), aux_loss)."""
    x = _embed_input(params, cfg, tokens, embeds)
    S = x.shape[1]
    positions = jnp.arange(S)
    img = None
    if cfg.family == "vlm":
        img = (image_embeds.astype(x.dtype) @ params["img_proj"]).astype(x.dtype)
    x, aux, _ = _run_groups(
        params, cfg, x, positions, None, img, decode=False, train=train, seg_ids=seg_ids
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", x, params["unembed"], preferred_element_type=jnp.dtype(cfg.logits_dtype)
    )
    return shard(logits, "batch", None, "vocab"), aux


#: Families whose decode cache contains paged-able attention layers.
PAGED_FAMILIES = ("dense", "audio", "moe", "hybrid")


def _recurrent_layer_states(cfg: ModelConfig, batch: int, dtype) -> Dict[str, Pytree]:
    """The recurrent (non-attention) per-layer decode states of a family —
    Mamba ``{conv, h}`` for hybrid, mLSTM/sLSTM states for ssm.  These are
    O(1) per lane (no ``max_len`` axis) and identical in the dense,
    per-lane, and paged cache layouts."""
    G = cfg.n_layers // cfg.group_size
    fam = cfg.family
    layers: Dict[str, Pytree] = {}
    if fam == "hybrid":
        layers["mamba"] = mamba_lib.init_mamba_state(
            cfg, batch, (G, cfg.hybrid_period - 1), dtype
        )
    elif fam == "ssm":
        if "m" in cfg.xlstm_pattern:
            layers["mlstm"] = xlstm_lib.init_mlstm_state(cfg, batch, (G,), dtype)
        if "s" in cfg.xlstm_pattern:
            layers["slstm"] = xlstm_lib.init_slstm_state(cfg, batch, (G,), dtype)
    return layers


def init_decode_state(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16, per_lane: bool = False,
    paged: bool = False, block_size: int = 16, n_blocks: Optional[int] = None,
):
    """Decode cache.  ``per_lane=True`` gives every batch lane its own write
    offset (``idx (…, batch)``) and position (``pos (batch,)``) so lanes can
    hold sequences of different lengths — the continuous-batching layout
    used by ``repro.serving``.  Default keeps the scalar lock-step layout.
    Every family builds a composite per-layer LaneState tree (attention KV
    next to Mamba/xLSTM recurrent state for hybrid/ssm); the lane axis of
    each leaf is declared by :func:`decode_state_lane_axes`, which the
    serving engine uses for lane splice / snapshot / reset
    (``models/lane_state.py``).

    ``paged=True`` (implies per-lane) swaps the dense ``(batch, max_len)``
    KV region for global block pools stored as ``(G, n_blocks,
    block_size·KV·dh)``: one contiguous row per block and layer, a block's
    tokens one after another (``attention.block_view`` gives a row's
    ``(block_size, KV, dh)`` view).  Rows keep the device layout natural:
    tiling a ``(…, KV, dh)`` minor pair would pad it.  Per-lane block
    tables ``(G, batch, max_len/block_size)`` int32 name each lane's
    blocks — block 0 is the reserved trash block (see serving/paging.py).
    HBM then scales with actual resident tokens, not ``batch × max_len``.
    The layer scan carries the stacked pools and writes them in place
    (``_run_groups``), so a program that donates the cache holds one pool.
    Hybrid pages its attention layers while the Mamba layers keep dense
    per-lane recurrent state in the same cache; a pure-ssm family has no
    attention layers to page and rejects ``paged=True``.
    """
    G = cfg.n_layers // cfg.group_size
    fam = cfg.family
    if paged:
        if fam not in PAGED_FAMILIES:
            raise NotImplementedError(
                f"paged KV cache needs attention layers; family {fam!r} "
                "has none to page (its per-lane state is already O(1) — "
                "use per_lane=True)"
            )
        if max_len % block_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of block_size={block_size}"
            )
        per_lane = True
    cache: Dict[str, Pytree] = {
        "pos": jnp.zeros((batch,) if per_lane else (), jnp.int32)
    }
    KV, dh = cfg.n_kv_heads, cfg.d_head

    if paged:
        max_blocks = max_len // block_size
        if n_blocks is None:
            n_blocks = 1 + batch * max_blocks  # worst case + trash block
        cache["layers"] = {
            "attn": {
                "k": jnp.zeros((G, n_blocks, block_size * KV * dh), dtype),
                "v": jnp.zeros((G, n_blocks, block_size * KV * dh), dtype),
                "block_tbl": jnp.zeros((G, batch, max_blocks), jnp.int32),
                "idx": jnp.zeros((G, batch), jnp.int32),
            },
            **_recurrent_layer_states(cfg, batch, dtype),
        }
        return cache

    def kv(n_lead):
        idx_shape = (*n_lead, batch) if per_lane else n_lead
        return {
            "k": jnp.zeros((*n_lead, batch, max_len, KV, dh), dtype),
            "v": jnp.zeros((*n_lead, batch, max_len, KV, dh), dtype),
            "idx": jnp.zeros(idx_shape, jnp.int32),
        }

    layers = _recurrent_layer_states(cfg, batch, dtype)
    if fam in ("dense", "audio", "moe", "hybrid"):
        layers["attn"] = kv((G,))
    elif fam == "vlm":
        layers["attn"] = kv((G, cfg.cross_attn_every - 1))
    cache["layers"] = layers
    return cache


def decode_state_lane_axes(cfg: ModelConfig, paged: bool = False) -> Dict[str, Pytree]:
    """LaneState protocol: a tree with the structure of
    ``init_decode_state(..., per_lane=True, paged=paged)`` whose leaves are
    the axis carrying the lane dimension (``lane_state.NO_LANE`` for global
    leaves such as the paged block pools).  Composed from each state
    implementation's own declaration, exactly mirroring how
    ``init_decode_state`` composes their initializers."""
    fam = cfg.family
    layers: Dict[str, Pytree] = {}
    if fam == "hybrid":
        layers["mamba"] = mamba_lib.state_lane_axes(2)  # (G, period-1, batch, …)
    elif fam == "ssm":
        if "m" in cfg.xlstm_pattern:
            layers["mlstm"] = xlstm_lib.mlstm_state_lane_axes(1)  # (G, batch, …)
        if "s" in cfg.xlstm_pattern:
            layers["slstm"] = xlstm_lib.slstm_state_lane_axes(1)
    if paged:
        if fam not in PAGED_FAMILIES:
            raise NotImplementedError(f"family {fam!r} has no attention layers to page")
        layers["attn"] = attn_lib.paged_kv_lane_axes()
    elif fam in ("dense", "audio", "moe", "hybrid"):
        layers["attn"] = attn_lib.kv_lane_axes(1)  # (G, batch, …)
    elif fam == "vlm":
        layers["attn"] = attn_lib.kv_lane_axes(2)  # (G, P-1, batch, …)
    return {"pos": 0, "layers": layers}


def paged_prefill_view(cfg: ModelConfig, cache, write_ids, read_ids=None):
    """1-lane paged-cache view for block-aligned admission prefill.

    Holds the engine cache's own stacked pools, not a copy: the layer scan
    carries them and ``decoder_prefill`` on this view writes the prompt's
    K/V into them in place (attention.py's ``_paged_prefill``), so a
    prefill program that donates the cache updates the one pool.  The
    single block-table row is ``write_ids`` (ceil(bucket/block_size),) —
    this pass's *write targets* per block, with trash block 0 standing in
    for already-resident shared prefix blocks and bucket padding.
    Recurrent layers (hybrid's Mamba) get a fresh 1-lane state — prefill
    materializes the prompt's recurrent state into it.
    ``commit_paged_prefill`` folds the result back.

    ``read_ids`` (ceil(bucket/block_size),) switches the view to chunked
    prefill: attention gathers its keys back out of the pool through this
    row — the request's own blocks plus any adopted prefix-cache blocks —
    so a chunk sees every earlier chunk's K/V (including blocks whose K/V
    was never recomputed this prefill) under the absolute causal mask."""
    a = cache["layers"]["attn"]
    G = a["idx"].shape[0]
    nb = write_ids.shape[0]
    attn = {
        "k": a["k"],
        "v": a["v"],
        "block_tbl": jnp.broadcast_to(
            write_ids.astype(jnp.int32)[None, None, :], (G, 1, nb)
        ),
        "idx": jnp.zeros((G, 1), jnp.int32),
    }
    if read_ids is not None:
        attn["read_tbl"] = jnp.broadcast_to(
            read_ids.astype(jnp.int32)[None, None, :], (G, 1, read_ids.shape[0])
        )
    return {
        "pos": jnp.zeros((1,), jnp.int32),
        "layers": {
            "attn": attn,
            **_recurrent_layer_states(cfg, 1, a["k"].dtype),
        },
    }


def commit_paged_prefill(cfg: ModelConfig, cache, filled, lane, table_row, length):
    """Adopt a block-aligned prefill into the engine cache: take the updated
    pools from the prefill view, point ``lane``'s block-table row at its
    blocks (``table_row`` (max_blocks,), tail entries → trash block 0), set
    its offsets to the true prompt ``length``, and splice any recurrent
    layer states (hybrid's Mamba) from the 1-lane view into the lane."""
    from repro.models import lane_state

    a, f = cache["layers"]["attn"], filled["layers"]["attn"]
    G, _, mb = a["block_tbl"].shape
    length = jnp.asarray(length, jnp.int32).reshape(1)
    pos = jax.lax.dynamic_update_slice(cache["pos"], length, (lane,))
    tbl = jax.lax.dynamic_update_slice(
        a["block_tbl"],
        jnp.broadcast_to(table_row.astype(jnp.int32)[None, None, :], (G, 1, mb)),
        (0, lane, 0),
    )
    idx = jax.lax.dynamic_update_slice(
        a["idx"], jnp.broadcast_to(length, (G, 1)), (0, lane)
    )
    layers = {"attn": {"k": f["k"], "v": f["v"], "block_tbl": tbl, "idx": idx}}
    axes = decode_state_lane_axes(cfg, paged=True)["layers"]
    for key in cache["layers"]:
        if key == "attn":
            continue
        layers[key] = lane_state.restore_lane(
            cache["layers"][key], axes[key], lane, filled["layers"][key]
        )
    return {"pos": pos, "layers": layers}


def paged_decode_view(cfg: ModelConfig, cache, rows):
    """The lanes ``rows`` (int32 (R,)) of the paged engine cache as an
    R-lane cache for one decode step: each per-lane leaf (block-table
    rows, offsets, positions, recurrent states) gathered along its lane
    axis, a row out of range (padding) reading trash block 0 at offset 0,
    and the engine's own stacked pools held whole, so the step writes them
    in place as it does the full batch.  ``commit_paged_decode`` folds
    the stepped view back."""
    from repro.models import lane_state

    return lane_state.gather_lanes(cache, decode_state_lane_axes(cfg, paged=True), rows)


def commit_paged_decode(cfg: ModelConfig, cache, decoded, rows):
    """Adopt a decode step over ``paged_decode_view(cache, rows)``: the
    stepped pools, and each row's per-lane leaves written back to its lane.
    Padding rows are dropped; lanes outside ``rows`` are untouched."""
    from repro.models import lane_state

    return lane_state.scatter_lanes(
        cache, decode_state_lane_axes(cfg, paged=True), rows, decoded
    )


def decoder_prefill(
    params, cfg: ModelConfig, cache, tokens=None, embeds=None, image_embeds=None,
    seg_ids=None, length=None, start=None,
):
    """Fill the cache with a prompt; returns (last-position logits, cache).

    ``length`` (int32 (B,)) marks the true prompt length when ``tokens`` is
    right-padded to a bucket size (prompt-length bucketing: distinct padded
    lengths — not distinct prompt lengths — trigger prefill compiles).
    Logits are taken at position ``length-1`` per row and the cache
    position/offsets are set to ``length``, so the padded tail is dead
    weight that decode overwrites and masks.  Causality keeps the valid
    prefix's K/V independent of the padding.

    ``start`` (traced int32 scalar) marks ``tokens`` as one chunk of a
    chunked paged prefill beginning at that absolute position: rope and the
    causal mask run at ``start + arange(S)``, and the logits row is
    ``length - 1 - start`` (meaningful only on the final chunk — earlier
    chunks return clamped garbage the engine ignores).  The cache must be a
    ``paged_prefill_view`` carrying a ``read_tbl``.
    """
    x = _embed_input(params, cfg, tokens, embeds)
    S = x.shape[1]
    positions = jnp.arange(S)
    if start is not None:
        positions = positions + jnp.asarray(start, jnp.int32)
    img = None
    if cfg.family == "vlm":
        img = (image_embeds.astype(x.dtype) @ params["img_proj"]).astype(x.dtype)
    len_arr = None if length is None else jnp.asarray(length, jnp.int32)
    x, _, new_layers = _run_groups(
        params, cfg, x, positions, cache["layers"], img, decode=False, train=False,
        seg_ids=seg_ids, length=len_arr,
    )
    if length is None:
        x_last = x[:, -1:]
        new_pos = jnp.full_like(cache["pos"], S)
    else:
        length = jnp.asarray(length, jnp.int32)
        row = length - 1
        if start is not None:
            row = jnp.clip(row - start, 0, S - 1)
        x_last = jnp.take_along_axis(x, row[:, None, None], axis=1)
        new_pos = jnp.broadcast_to(length, cache["pos"].shape)
        if "attn" in new_layers:
            att = dict(new_layers["attn"])
            att["idx"] = jnp.broadcast_to(length, att["idx"].shape)
            new_layers = {**new_layers, "attn": att}
    x = rms_norm(x_last, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", x, params["unembed"], preferred_element_type=jnp.dtype(cfg.logits_dtype)
    )
    return logits[:, 0], {"pos": new_pos, "layers": new_layers}


def decoder_decode(
    params, cfg: ModelConfig, cache, token=None, embeds=None, image_embeds=None,
    seg_ids=None, attend_blocks=None,
):
    """One decode step. token (B,1) int32 (or embeds (B,1,d)).

    ``attend_blocks`` (static) bounds the paged attend to the first
    that-many block-table columns — see ``attention.attention``."""
    x = _embed_input(params, cfg, token, embeds)
    pos = cache["pos"]
    positions = pos[None] if pos.ndim == 0 else pos[:, None]
    img = None
    if cfg.family == "vlm":
        img = (image_embeds.astype(x.dtype) @ params["img_proj"]).astype(x.dtype)
    x, _, new_layers = _run_groups(
        params, cfg, x, positions, cache["layers"], img, decode=True, train=False,
        seg_ids=seg_ids, attend_blocks=attend_blocks,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", x, params["unembed"], preferred_element_type=jnp.dtype(cfg.logits_dtype)
    )
    return logits[:, 0], {"pos": cache["pos"] + 1, "layers": new_layers}


def decoder_verify(
    params, cfg: ModelConfig, cache, tokens=None, seg_ids=None, n_valid=None,
    attend_blocks=None,
):
    """Speculative verify: one forward over ``tokens`` (B, W) — each lane's
    last committed token followed by its drafted continuation — at absolute
    positions ``pos[b] .. pos[b]+W-1``, returning the logits of ALL W rows
    (``(B, W, V)``).

    Row ``s`` attends to every cache position ``<= pos+s`` (the window's
    own earlier rows included, freshly scattered), so its logits are
    exactly what :func:`decoder_decode` would produce after committing the
    window's first ``s`` tokens — greedy acceptance is then plain prefix
    equality against the per-row argmax.  ``n_valid`` (int32 (B,)) caps how
    many rows each lane writes into its cache (0 for idle lanes); offsets
    (``pos``/``idx``) come back UNCHANGED — the serving engine advances
    them by each lane's accepted length in a separate commit, then rolls
    back paged blocks the acceptance never reached.  Attention-only
    families (no recurrent state to rewind); the engine gates speculation
    accordingly.
    """
    x = _embed_input(params, cfg, tokens, None)
    W = x.shape[1]
    positions = cache["pos"][:, None] + jnp.arange(W)[None, :]
    x, _, new_layers = _run_groups(
        params, cfg, x, positions, cache["layers"], None, decode=True, train=False,
        seg_ids=seg_ids, attend_blocks=attend_blocks, n_valid=n_valid,
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum(
        "bsd,dv->bsv", x, params["unembed"], preferred_element_type=jnp.dtype(cfg.logits_dtype)
    )
    return logits, {"pos": cache["pos"], "layers": new_layers}
