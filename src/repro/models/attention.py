"""GQA attention: train/prefill/decode paths, cross-attention, QK-norm.

Three implementations selectable per config (``attn_impl``):

* ``xla``         — plain einsum+softmax (default; used in distributed
                    lowering; chunks over query blocks when S is large so
                    activation memory is O(S·chunk) instead of O(S²)).
* ``pallas``      — the flash-attention Pallas TPU kernel
                    (``repro/kernels/flash_attention.py``), for real TPU runs.

Adapters (QR-LoRA / LoRA / SVD-LoRA) hook the four projections through
:func:`repro.core.adapter_api.adapted_matmul`.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.adapter_api import adapted_matmul
from repro.models.lane_state import NO_LANE
from repro.models.layers import apply_rope, dense_init, rms_norm, stacked_dense_init
from repro.sharding import shard

_CHUNK_THRESHOLD = 8192  # plain scores up to this S, chunked above
_Q_CHUNK = 512


def _decode_shard_names(cfg: ModelConfig):
    """Model-axis placement for decode-attention activations, matching the
    KV-cache rule in launch/specs.py: kv-heads when they divide the model
    axis, else the head dim (always a multiple of 64)."""
    from repro.sharding.rules import get_mesh

    mesh = get_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return ("heads", None)
    m = mesh.shape["model"]
    if cfg.n_kv_heads % m == 0:
        return ("heads", None)
    if cfg.d_head % m == 0:
        return (None, "heads")
    return (None, None)


def init_attn_params(key, cfg: ModelConfig, n: int, dtype, cross: bool = False) -> Dict:
    H, KV, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model
    ks = jax.random.split(key, 8)
    p = {
        "wq": stacked_dense_init(ks[0], n, d, H * dh, dtype),
        "wk": stacked_dense_init(ks[1], n, d, KV * dh, dtype),
        "wv": stacked_dense_init(ks[2], n, d, KV * dh, dtype),
        "wo": stacked_dense_init(ks[3], n, H * dh, d, dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n, H * dh), dtype)
        p["bk"] = jnp.zeros((n, KV * dh), dtype)
        p["bv"] = jnp.zeros((n, KV * dh), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((n, dh), dtype)
        p["k_norm"] = jnp.ones((n, dh), dtype)
    if cross:
        p["xa_gate"] = jnp.zeros((n,), jnp.float32)
    return p


def _project_qkv(p, x, cfg: ModelConfig, adp, kv_input=None):
    """Project to q (B,S,H,dh) and k,v (B,Skv,KV,dh)."""
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    B = x.shape[0]
    kv_x = x if kv_input is None else kv_input
    q = adapted_matmul(x, p["wq"], (adp or {}).get("wq"))
    k = adapted_matmul(kv_x, p["wk"], (adp or {}).get("wk"))
    v = adapted_matmul(kv_x, p["wv"], (adp or {}).get("wv"))
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, -1, H, dh)
    k = k.reshape(B, -1, KV, dh)
    v = v.reshape(B, -1, KV, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, None, None)
    v = shard(v, "batch", None, None, None)
    return q, k, v


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    B, S, KV, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, n_rep, dh)).reshape(
        B, S, KV * n_rep, dh
    )


def _softmax_attend(q, k, v, mask, scale, decode=False, scores_dtype=jnp.float32):
    """GQA attention via grouped einsum — repeated K/V are NEVER
    materialized (a (B,S,H,dh) broadcast of the KV cache is what GSPMD
    replicates wholesale; see DESIGN.md §4 note on GQA).

    q (B,Sq,H,dh); k,v (B,Sk,KV,dh); mask broadcastable to (B,1,1,Sq,Sk).
    """
    B, Sq, H, dh = q.shape
    KV = k.shape[2]
    rep = H // KV
    qg = q.reshape(B, Sq, KV, rep, dh)
    scores = jnp.einsum(
        "bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=scores_dtype
    ) * scale
    if not decode:
        scores = shard(scores, "batch", "heads", None, None, None)
    neg = -1e30 if scores_dtype == jnp.float32 else -6e4  # bf16-representable
    scores = jnp.where(mask, scores, jnp.asarray(neg, scores_dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(scores_dtype)
    out = jnp.einsum(
        "bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


def _attend_chunked(q, k, v, scale, causal: bool, kv_len=None, scores_dtype=jnp.float32):
    """Query-chunked attention — O(S·chunk) score memory."""
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    c = min(_Q_CHUNK, Sq)
    n_chunks = (Sq + c - 1) // c
    pad = n_chunks * c - Sq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = q.reshape(B, n_chunks, c, H, dh).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(Sk)

    def body(carry, qc_i):
        qc, i = qc_i
        qpos = i * c + jnp.arange(c)
        if causal:
            m = kpos[None, :] <= qpos[:, None]
        else:
            m = jnp.ones((c, Sk), bool)
        if kv_len is not None:
            m = m & (kpos[None, :] < kv_len)
        out = _softmax_attend(qc, k, v, m[None, None, None], scale, scores_dtype=scores_dtype)
        return carry, out

    _, outs = jax.lax.scan(
        body, None, (qs, jnp.arange(n_chunks))
    )  # (n_chunks, B, c, H, dh)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * c, H, dh)
    return out[:, :Sq]


def attention(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    positions: jax.Array,
    adp: Optional[Dict] = None,
    causal: bool = True,
    cache: Optional[Dict] = None,
    cross_kv: Optional[jax.Array] = None,
    attend_blocks: Optional[int] = None,
    n_valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """Returns (output (B,S,d), updated cache or None).

    * ``cache=None``                — train / encoder path.
    * ``cache`` with ``S > 1``      — prefill: fills the cache.
    * ``cache`` with ``S == 1``     — decode: reads + appends one position.
    * ``cache`` with ``n_valid``    — speculative verify: S = k+1 window
                                      positions per lane (see below).
    * ``cross_kv``                  — cross-attention (no cache, no rope).

    ``attend_blocks`` (static) bounds the paged decode attend to the first
    that-many block-table columns — the engine passes the active lanes'
    block high-water mark so attend cost tracks live sequence lengths, not
    ``max_len`` (bit-identical: masked tail columns contribute exact zeros).

    ``n_valid`` (int32 (B,)) switches the per-lane cache paths into
    *speculative verify* mode: ``x`` holds each lane's draft window (the
    last committed token followed by its drafted continuation) fed at that
    lane's own absolute positions ``idx[b] .. idx[b]+S-1``, and row ``s``
    of the output attends exactly to what a single-token decode at position
    ``idx[b]+s`` would see.  Rows at or past a lane's ``n_valid`` write
    nothing (scatter-dropped / trash-redirected), and the cache offsets are
    returned UNCHANGED — the serving engine commits each lane's accepted
    advance separately after comparing drafts against the greedy argmax.
    """
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    B, S = x.shape[:2]
    scale = dh**-0.5
    n_rep = H // KV
    is_cross = cross_kv is not None
    sdt = jnp.dtype(cfg.attn_scores_dtype)

    q, k, v = _project_qkv(p, x, cfg, adp, kv_input=cross_kv)
    if not is_cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions if cache is None or S > 1 else positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and not is_cross:
        if n_valid is not None:  # speculative verify: S-token window per lane
            if "block_tbl" in cache:
                return _paged_verify(
                    p, q, k, v, cache, cfg, adp, scale, sdt, n_valid, attend_blocks
                )
            return _dense_verify(p, q, k, v, cache, cfg, adp, scale, sdt, n_valid)
        if "block_tbl" in cache:  # paged KV cache (block pool + table)
            if S != 1:  # block-aligned prefill: scatter straight into pool blocks
                return _paged_prefill(p, q, k, v, cache, cfg, adp, scale, sdt, positions)
            return _paged_decode(
                p, q, k, v, cache, cfg, adp, scale, sdt, attend_blocks
            )
        if S == 1:  # decode
            nm = _decode_shard_names(cfg)
            idx = cache["idx"]
            k = shard(k, "batch", None, *nm)
            v = shard(v, "batch", None, *nm)
            q = shard(q, "batch", None, *nm)
            kpos = jnp.arange(cache["k"].shape[1])
            if idx.ndim:  # per-lane write offsets (continuous batching)
                upd = lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
                    c, u, i, axis=0
                )
                ck = jax.vmap(upd)(cache["k"], k.astype(cache["k"].dtype), idx)
                cv = jax.vmap(upd)(cache["v"], v.astype(cache["v"].dtype), idx)
                mask = (kpos[None, :] <= idx[:, None])[:, None, None, None, :]
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
                mask = (kpos < idx + 1)[None, None, None, None, :]
            new_cache = {"k": ck, "v": cv, "idx": idx + 1}
            out = _softmax_attend(q, ck.astype(q.dtype), cv.astype(q.dtype), mask, scale, decode=True, scores_dtype=sdt)
            o = adapted_matmul(out.reshape(B, S, H * dh), p["wo"], (adp or {}).get("wo"))
            return shard(o, "batch", None, None), new_cache
        else:  # prefill: write k/v into cache then run the train path
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
            new_cache = {"k": ck, "v": cv, "idx": jnp.full_like(cache["idx"], S)}

    Sk = k.shape[1]
    if S > _CHUNK_THRESHOLD:
        out = _attend_chunked(q, k, v, scale, causal and not is_cross, scores_dtype=sdt)
    else:
        if causal and not is_cross:
            mask = (jnp.arange(Sk)[None, :] <= jnp.arange(S)[:, None])[None, None, None]
        else:
            mask = jnp.ones((1, 1, 1, S, Sk), bool)
        out = _softmax_attend(q, k, v, mask, scale, scores_dtype=sdt)
    o = adapted_matmul(out.reshape(B, S, H * dh), p["wo"], (adp or {}).get("wo"))
    return shard(o, "batch", None, None), new_cache


def block_view(rows: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Paged-pool rows ``(…, block_size·KV·dh)`` → their ``(…, block_size,
    KV, dh)`` view: one block's tokens, token-major, each ``(KV, dh)``."""
    KV, dh = cfg.n_kv_heads, cfg.d_head
    return rows.reshape(*rows.shape[:-1], rows.shape[-1] // (KV * dh), KV, dh)


def block_rows(view):
    """Inverse of ``block_view``: ``(…, block_size, KV, dh)`` → the pool's
    rows ``(…, block_size·KV·dh)``."""
    bs, KV, dh = view.shape[-3:]
    return view.reshape(*view.shape[:-3], bs * KV * dh)


def _write_rows(pool, layer, blk, rows):
    """Write whole block rows into the stacked pool ``(G, n_blocks,
    block_size·KV·dh)`` in place: ``rows[t]`` → ``pool[layer, blk[t]]``.  A
    window that spans the row lowers to one native in-place scatter on the
    carried pool; a window narrower than the row (one token) would be
    expanded into a loop with one update per token.  Targets repeat only
    at trash block 0 (idle lanes, padding), where any of them may win."""
    at = jnp.stack([jnp.broadcast_to(layer, blk.shape), blk], axis=1).astype(jnp.int32)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(0, 1),
        scatter_dims_to_operand_dims=(0, 1),
    )
    return jax.lax.scatter(
        pool, at, rows.astype(pool.dtype), dnums,
        mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


def _write_tokens(pool, layer, blk, off, vals, cfg: ModelConfig):
    """Write one token per lane, ``vals[b]`` (KV, dh) at offset ``off[b]``
    of block ``blk[b]``: read each lane's row, put the token in, write the
    rows back whole (``_write_rows``).  The lanes' real target blocks must
    be distinct (each decoding lane owns its write block)."""
    cur = block_view(pool[layer, blk], cfg)  # (B, bs, KV, dh)
    here = (jnp.arange(cur.shape[1])[None, :] == off[:, None])[:, :, None, None]
    rows = jnp.where(here, vals[:, None].astype(pool.dtype), cur)
    return _write_rows(pool, layer, blk, block_rows(rows))


def _paged_prefill(p, q, k, v, cache, cfg: ModelConfig, adp, scale, sdt, positions):
    """Block-aligned prefill against a paged cache.

    ``cache`` is a prompt-shaped view (``transformer.paged_prefill_view``)
    as the layer scan hands it over: ``k``/``v`` are the engine's whole
    stacked pools ``(G, n_blocks, block_size·KV·dh)``, ``layer`` this
    layer's index into them, and ``block_tbl`` names this pass's *write
    targets* per block — freshly allocated private blocks, or trash block 0
    standing in for already-resident shared prefix blocks and for bucket
    padding.  ``positions`` are the *absolute* sequence positions of this
    pass's rows: ``arange(S)`` for a whole-prompt prefill, or ``start +
    arange(chunk)`` for one chunk of a chunked prefill.  Position ``t`` of
    lane ``b`` is written in place to the ``(KV, dh)`` segment ``t % bs`` of
    row ``pool[layer, tbl[b, t // bs]]``.  The pass starts on a block
    boundary (the engine's chunks are block multiples from block-aligned
    starts), so it writes whole block rows, one native in-place scatter
    per pool; a bucket that ends mid-block fills the rest of its last block
    with padding no position reads.

    Without ``read_tbl`` in the view, attention is the plain causal pass
    over the (bucketed) prompt — bit-identical to the dense prefill path.
    With it (chunked prefill), the keys are *gathered back from the pool*
    as ``pool[layer, read_tbl]`` (full prompt-bucket width) after the
    write, so a chunk attends to every earlier chunk's K/V — including
    prefix-cache blocks whose K/V was never recomputed — under the absolute
    causal mask ``kpos <= positions``.  The write-then-gather round-trip
    returns the chunk's own K/V bit-identically (pool dtype == compute
    dtype) and the gather width equals the monolithic bucket, so the
    softmax reduces over identical score vectors and chunked prefill is
    bit-identical to monolithic prefill, row for row.
    """
    B, S, H, dh = q.shape
    KV = cfg.n_kv_heads
    kp, vp, layer = cache["k"], cache["v"], cache["layer"]
    bs = kp.shape[-1] // (KV * dh)
    tbl = cache["block_tbl"]

    pos = positions.astype(jnp.int32)
    nb = -(-S // bs)  # the pass's blocks, written as whole rows
    blk = jnp.take_along_axis(
        tbl, jnp.broadcast_to(pos[::bs] // bs, (B, nb)), axis=1
    ).reshape(-1)
    pad = ((0, 0), (0, nb * bs - S), (0, 0), (0, 0))  # past the bucket
    rows = lambda x: block_rows(jnp.pad(x, pad).reshape(B * nb, bs, KV, dh))  # noqa: E731
    kp = _write_rows(kp, layer, blk, rows(k))
    vp = _write_rows(vp, layer, blk, rows(v))
    new_cache = {
        "k": kp,
        "v": vp,
        "block_tbl": tbl,
        "idx": jnp.full_like(cache["idx"], S),  # true length overrides in decoder_prefill
    }

    read_tbl = cache.get("read_tbl")
    if read_tbl is not None:  # chunked prefill: attend through the pool
        new_cache["read_tbl"] = read_tbl
        W = read_tbl.shape[1] * bs
        kg = block_view(kp[layer, read_tbl], cfg).reshape(B, W, KV, dh)
        vg = block_view(vp[layer, read_tbl], cfg).reshape(B, W, KV, dh)
        mask = (jnp.arange(W)[None, :] <= pos[:, None])[None, None, None]
        out = _softmax_attend(
            q, kg.astype(q.dtype), vg.astype(q.dtype), mask, scale, scores_dtype=sdt
        )
    elif S > _CHUNK_THRESHOLD:
        out = _attend_chunked(q, k, v, scale, causal=True, scores_dtype=sdt)
    else:
        mask = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None, None, None]
        out = _softmax_attend(q, k, v, mask, scale, scores_dtype=sdt)
    o = adapted_matmul(out.reshape(B, S, H * dh), p["wo"], (adp or {}).get("wo"))
    return shard(o, "batch", None, None), new_cache


def _paged_decode(p, q, k, v, cache, cfg: ModelConfig, adp, scale, sdt,
                  attend_blocks: Optional[int] = None):
    """One decode step against a paged KV cache.

    ``cache``, as the layer scan hands it over: ``k``/``v`` the whole
    stacked pools ``(G, n_blocks, block_size·KV·dh)`` (one row per block),
    ``layer`` this layer's index into them, ``block_tbl`` (B, max_blocks)
    int32, ``idx`` (B,) per-lane lengths.  Lane ``b``'s token ``t`` is the
    ``(KV, dh)`` segment ``t % bs`` of row ``pool[layer, block_tbl[b, t //
    bs]]``; idle lanes point at trash block 0 (never allocated) so the
    shared write needs no per-lane branching.  The write reads each lane's
    current row, puts the token in and scatters the rows back into the
    carried (donated) pools in place; the XLA attend gathers ``pool[layer,
    tbl]``: no layer's pool is sliced, relaid out or copied.  (The Pallas
    kernel, ``attn_impl="pallas"``, takes this layer's pool as a slice.)

    ``attend_blocks`` (static, from the engine's active-lane high-water
    mark) truncates the *attend* to the table's first columns so the
    gather/kernel cost is O(longest live lane), not O(max_len).  Writes
    still go through the full table.  Lanes whose ``idx`` exceeds the bound
    (idle lanes carrying stale offsets) get garbage outputs the engine
    discards; live lanes are bit-identical because a masked softmax column
    contributes exactly zero at any width.
    """
    B = q.shape[0]
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    kp, vp, layer = cache["k"], cache["v"], cache["layer"]
    bs = kp.shape[-1] // (KV * dh)
    tbl, idx = cache["block_tbl"], cache["idx"]
    max_blocks = tbl.shape[1]
    nm = _decode_shard_names(cfg)
    q = shard(q, "batch", None, *nm)
    k = shard(k, "batch", None, *nm)
    v = shard(v, "batch", None, *nm)

    # -- write: this step's k/v into each lane's current block, in place ----
    blk = jnp.take_along_axis(
        tbl, jnp.clip(idx // bs, 0, max_blocks - 1)[:, None], axis=1
    )[:, 0]
    kp = _write_tokens(kp, layer, blk, idx % bs, k[:, 0], cfg)
    vp = _write_tokens(vp, layer, blk, idx % bs, v[:, 0], cfg)
    new_cache = {"k": kp, "v": vp, "block_tbl": tbl, "idx": idx + 1}

    # -- attend through the block table -------------------------------------
    lengths = idx + 1  # current position is valid
    a_blocks = max_blocks
    if attend_blocks is not None and attend_blocks < max_blocks:
        a_blocks = max(attend_blocks, 1)
        tbl = tbl[:, :a_blocks]
        lengths = jnp.minimum(lengths, a_blocks * bs)
    if cfg.attn_impl == "pallas":
        from repro.kernels import ops as kernel_ops

        out = kernel_ops.paged_decode_attention(
            q, block_view(kp[layer], cfg), block_view(vp[layer], cfg), tbl, lengths
        )
    else:
        kg = block_view(kp[layer, tbl], cfg).reshape(B, a_blocks * bs, KV, dh)
        vg = block_view(vp[layer, tbl], cfg).reshape(B, a_blocks * bs, KV, dh)
        kpos = jnp.arange(a_blocks * bs)
        mask = (kpos[None, :] < lengths[:, None])[:, None, None, None, :]
        out = _softmax_attend(
            q, kg.astype(q.dtype), vg.astype(q.dtype), mask, scale, decode=True,
            scores_dtype=sdt,
        )
    o = adapted_matmul(out.reshape(B, 1, H * dh), p["wo"], (adp or {}).get("wo"))
    return shard(o, "batch", None, None), new_cache


def _dense_verify(p, q, k, v, cache, cfg: ModelConfig, adp, scale, sdt, n_valid):
    """Speculative verify against a dense per-lane cache: W window rows per
    lane at positions ``idx[b] .. idx[b]+W-1``.

    Writes use a flat scatter whose index is forced out of range for rows
    ``s >= n_valid[b]`` (``mode="drop"``), so idle lanes and lanes near
    their generation budget write nothing.  Row ``s``'s mask is
    ``kpos <= idx+s`` — exactly the single-token decode mask at that
    position, so row ``s`` is what decode would compute after committing
    the window's first ``s`` tokens; rejected rows leave stale K/V that
    stays masked until a later window overwrites it.  ``idx`` is returned
    UNCHANGED — the engine advances it by the accepted length.
    """
    B, W = q.shape[:2]
    H, dh = cfg.n_heads, cfg.d_head
    idx = cache["idx"]
    L = cache["k"].shape[1]
    nm = _decode_shard_names(cfg)
    q = shard(q, "batch", None, *nm)
    k = shard(k, "batch", None, *nm)
    v = shard(v, "batch", None, *nm)
    pos = idx[:, None] + jnp.arange(W)[None, :]  # (B, W)
    valid = jnp.arange(W)[None, :] < n_valid[:, None]
    flat = jnp.where(valid, jnp.arange(B)[:, None] * L + pos, B * L)
    ck = cache["k"].reshape(B * L, *cache["k"].shape[2:])
    cv = cache["v"].reshape(B * L, *cache["v"].shape[2:])
    ck = ck.at[flat.reshape(-1)].set(
        k.reshape(B * W, *k.shape[2:]).astype(ck.dtype), mode="drop"
    ).reshape(cache["k"].shape)
    cv = cv.at[flat.reshape(-1)].set(
        v.reshape(B * W, *v.shape[2:]).astype(cv.dtype), mode="drop"
    ).reshape(cache["v"].shape)
    new_cache = {"k": ck, "v": cv, "idx": idx}
    kpos = jnp.arange(L)
    mask = (kpos[None, None, :] <= pos[:, :, None])[:, None, None]  # (B,1,1,W,L)
    out = _softmax_attend(
        q, ck.astype(q.dtype), cv.astype(q.dtype), mask, scale, decode=True,
        scores_dtype=sdt,
    )
    o = adapted_matmul(out.reshape(B, W, H * dh), p["wo"], (adp or {}).get("wo"))
    return shard(o, "batch", None, None), new_cache


def _paged_verify(p, q, k, v, cache, cfg: ModelConfig, adp, scale, sdt, n_valid,
                  attend_blocks: Optional[int] = None):
    """Speculative verify against the paged pool: W window rows per lane
    written in place through its block table at ``idx .. idx+W-1`` (the
    stacked pools, ``layer`` and the per-token write as in
    ``_paged_decode``, one window row after another).

    Rows ``s >= n_valid[b]`` have their block forced to trash block 0 — a
    lane whose window exceeds its owned blocks (or an idle lane) scribbles
    only on trash, never forking or touching a shared block.  The attend
    always takes the XLA gather path (the Pallas paged kernel is
    single-query); ``attend_blocks`` truncation and the per-row mask
    ``kpos <= min(idx+s, width-1)`` reproduce ``_paged_decode``'s reduction
    exactly, so live rows are bit-identical to the single-token decode at
    the same position.  ``idx`` is returned UNCHANGED — the engine commits
    accepted advances and decref/trash-repoints past-the-end blocks.
    """
    B, W = q.shape[:2]
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    kp, vp, layer = cache["k"], cache["v"], cache["layer"]
    bs = kp.shape[-1] // (KV * dh)
    tbl, idx = cache["block_tbl"], cache["idx"]
    max_blocks = tbl.shape[1]
    nm = _decode_shard_names(cfg)
    q = shard(q, "batch", None, *nm)
    k = shard(k, "batch", None, *nm)
    v = shard(v, "batch", None, *nm)

    pos = idx[:, None] + jnp.arange(W)[None, :]  # (B, W)
    valid = jnp.arange(W)[None, :] < n_valid[:, None]
    blk = jnp.take_along_axis(tbl, jnp.clip(pos // bs, 0, max_blocks - 1), axis=1)
    blk = jnp.where(valid, blk, 0)  # invalid rows → trash block
    for s in range(W):  # in window order: rows s and s+1 may share a block
        kp = _write_tokens(kp, layer, blk[:, s], pos[:, s] % bs, k[:, s], cfg)
        vp = _write_tokens(vp, layer, blk[:, s], pos[:, s] % bs, v[:, s], cfg)
    new_cache = {"k": kp, "v": vp, "block_tbl": tbl, "idx": idx}

    a_blocks = max_blocks
    if attend_blocks is not None and attend_blocks < max_blocks:
        a_blocks = max(attend_blocks, 1)
        tbl = tbl[:, :a_blocks]
    kg = block_view(kp[layer, tbl], cfg).reshape(B, a_blocks * bs, KV, dh)
    vg = block_view(vp[layer, tbl], cfg).reshape(B, a_blocks * bs, KV, dh)
    kpos = jnp.arange(a_blocks * bs)
    mask = (
        kpos[None, None, :] <= jnp.minimum(pos, a_blocks * bs - 1)[:, :, None]
    )[:, None, None]  # (B,1,1,W,a_blocks*bs)
    out = _softmax_attend(
        q, kg.astype(q.dtype), vg.astype(q.dtype), mask, scale, decode=True,
        scores_dtype=sdt,
    )
    o = adapted_matmul(out.reshape(B, W, H * dh), p["wo"], (adp or {}).get("wo"))
    return shard(o, "batch", None, None), new_cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int, n_attn_layers: int, dtype):
    """Stacked KV cache pytree for the decoder's attention layers."""
    KV, dh = cfg.n_kv_heads, cfg.d_head
    return {
        "k": jnp.zeros((n_attn_layers, batch, max_len, KV, dh), dtype),
        "v": jnp.zeros((n_attn_layers, batch, max_len, KV, dh), dtype),
        "idx": jnp.zeros((n_attn_layers,), jnp.int32),
    }


# ---------------------------------------------------------------------------
# LaneState protocol (models/lane_state.py): which axis carries the lane dim
# ---------------------------------------------------------------------------


def kv_lane_axes(lead_ndim: int):
    """Lane-axes tree for a dense per-lane KV cache built with
    ``lead_ndim`` stacked leading axes (``transformer.init_decode_state``'s
    ``kv(n_lead)``): k/v are ``(*lead, batch, max_len, KV, dh)`` and idx is
    ``(*lead, batch)`` — the lane axis follows the lead axes."""
    return {"k": lead_ndim, "v": lead_ndim, "idx": lead_ndim}


def paged_kv_lane_axes():
    """Lane-axes tree for the paged KV cache: the k/v block pools
    ``(G, n_blocks, block_size·KV·dh)`` are global (lanes address them
    through their block-table rows), so only ``block_tbl`` ``(G, batch,
    max_blocks)`` and ``idx`` ``(G, batch)`` carry a lane dimension."""
    return {"k": NO_LANE, "v": NO_LANE, "block_tbl": 1, "idx": 1}
