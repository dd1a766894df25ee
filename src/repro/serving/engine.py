"""Multi-tenant serving engine: one decode loop, many adapters.

Glues the pieces together:

* :class:`~repro.serving.lam_store.LamStore` — the hierarchical λ-store:
  packed λ slot tables (hot tier, O(one λ row) donated slot writes)
  installed into a parameter *view* (weights and QR factors shared), plus
  an optional host **cold tier** (``cold_slots=N``): evicted tenants spill
  their λ rows to host arrays and admission **promotes them on demand** —
  a queued request whose tenant is cold defers (exactly like a full block
  pool defers admission) until a hot slot can be freed, so tenant capacity
  is bounded by host RAM, not HBM.  With ``shard_lam=True`` the slot axis
  of every λ table is sharded over a 1-D ``"model"`` mesh spanning the
  local devices (``lam_slots`` logical axis in ``sharding/rules.py``), and
  the λ-row gather consumes local shards only
  (``kernels.qrlora_bgmv.lam_gather_sharded``) — bit-identical to the
  replicated gather, with per-device table HBM divided by the mesh size.
* :class:`~repro.serving.scheduler.ContinuousBatchScheduler` — FIFO queue
  over fixed decode lanes.
* the batched multi-λ adapter matmul — per-lane ``seg_ids`` flow through
  ``Model.prefill`` / ``Model.decode_step`` into
  ``adapter_api.adapted_matmul`` (XLA ``take`` gather or the
  ``qrlora_bgmv`` Pallas kernel).
* per-lane decode-state management through the **LaneState protocol**
  (``repro.models.lane_state``): the cache is ``per_lane=True`` (each lane
  has its own write offset and position), so lanes hold sequences of
  different tenants, lengths, and ages.  The engine never branches on the
  model family — admission splices a 1-lane prefill into its lane
  (``restore_lane``), retirement resets the lane to its init value
  (``reset_lane``), and preemption snapshots it (``extract_lane``), all
  driven by the family's lane-axes tree (``Model.lane_axes``).  That is
  what lets attention (dense/paged KV), hybrid jamba (paged KV **and**
  dense Mamba ``{conv, h}`` rows in the same ``step()``), and ssm xlstm
  (mLSTM/sLSTM states, no KV at all) share one decode loop.
* ``paged=True`` swaps the dense ``(lanes, max_len)`` KV region for a
  global block pool + per-lane block tables (``serving/paging.py``).
  Admission allocates only the *prompt's* ``ceil(P/block_size)`` blocks and
  prefills them **block-aligned** — the prompt's K/V scatters straight into
  pool blocks (``models/attention._paged_prefill``), no dense lane-1
  intermediate.  Decode **grows lazily**: a lane gets its next block only
  when its write position crosses a block boundary; when the pool is
  exhausted, unreferenced prefix-cache blocks are scavenged first, then the
  *youngest* lane is preempted back to the front of the queue (its blocks
  freed, its output re-derived deterministically on re-admission), so the
  oldest lane can always finish — decode never deadlocks.
* ``share_prefix=True`` adds **copy-on-write prefix sharing**: a hash-chain
  cache maps (tenant-family λ digest, prefill bucket, full-block token
  prefix) → pool block, so requests repeating a prompt prefix *reuse* the
  resident K/V blocks (refcount++) instead of writing new copies — N lanes
  on one prompt hold ~1× the prefix plus N private tails.  Prefill writes
  into shared blocks are redirected to the trash block; a lane about to
  *decode* into a shared block forks a private copy first (CoW).  The
  partial tail block of a prompt is always private and never cached.

Admission prefill pads prompts to power-of-two buckets (true length rides
along and masks the tail), so 10 mixed-length prompts cost ≤ log2(max_len)
prefill compilations instead of one per distinct length.  The prefix cache
keys on the bucket too: two prefills only share K/V when they ran the same
compiled program, which keeps shared-prefix decode bit-identical to the
unshared engine.

``quantum=N`` adds **time-slice fairness** for dense-layout engines: a
lane that has decoded N tokens while others queue is snapshot-preempted
(LaneState ``extract_lane`` — O(1) per lane for recurrent families) to the
back of the queue and later *restored* instead of re-prefilled, so long
generations round-robin with waiting requests at zero recompute.

``speculate_k=K`` adds **speculative decoding** for attention-only
families: the QR-LoRA structure makes the drafter free — slot 0's zero-λ
base tenant shares every weight and KV block with its targets, so drafting
is just the same forward with the per-token BGMV *skipped* (or, with
``draft_lam_rank=r``, with all but the top-r λ coefficients zeroed).  Each
step drafts K greedy tokens per lane in one dispatch (through a throwaway
cache copy — JAX's functional updates make draft rollback structural),
verifies every lane's (K+1)-token window in one batched multi-position
forward under the full multi-λ view, and accepts each lane's longest
matching prefix.  Greedy decode is bit-deterministic, so acceptance is
exact prefix equality — output is token-identical to the plain engine, at
up to K+1 tokens per host round-trip.  Rejected positions roll back as
pure bookkeeping: dense offsets simply don't advance past the acceptance
(stale rows stay masked until overwritten), and paged lanes decref their
unreached pre-grown window blocks back to the pool (growth never CoW-forks
beyond the write block, so rollback never has to undo a fork).

``prefill_chunk=N`` (chunked prefill, paged layouts) keeps admission off
the decode critical path: a long prompt is split into N-token chunks
processed one (budgeted) chunk per engine step, interleaved with resident
lanes' decode.  Each chunk scatters its K/V into the lane's pool blocks and
attends back *through the pool* (``read_tbl``) under the absolute causal
mask, so the result is bit-identical to the monolithic prefill; prefix-
cache-hit blocks are skipped entirely (their K/V is already resident —
today that saves the FLOPs, not just the memory).  The lane stays dark —
table row trash, offsets zero — until the final chunk commits, so
interleaved decode steps never observe a half-filled prompt.

Engine construction takes an :class:`~repro.serving.config.EngineConfig`
(``MultiTenantEngine(cfg, EngineConfig.serving(), params=p)``); the
pre-config keyword surface (``paged=``, ``share_prefix=``, …) still works
through a once-warning deprecation shim.

The engine is greedy-decode and host-driven: ``step()`` = admit + prefill
chunks + grow + one decode step; ``run()`` loops until queue and lanes
drain, ``stream()`` yields per-token :class:`TokenEvent`\\ s as they decode.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from contextlib import nullcontext
from typing import Any, Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import adapter_api
from repro.core.quantize import quantize_base_params
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.obs import Telemetry
from repro.models.lane_state import extract_lane, restore_lane
from repro.serving.config import EngineConfig
from repro.serving.lam_store import LamStore, extract_lambda
from repro.serving.paging import BlockAllocator, PoolExhausted, PrefixCache
from repro.serving.scheduler import ContinuousBatchScheduler, Request
from repro.sharding.rules import axis_rules, param_sharding_rules

Pytree = Any

_MIN_PREFILL_BUCKET = 8

#: Families whose prompt forward pass is position-local outside attention
#: (token-table embedding, no recurrent mixer), so prefill can run in
#: block-aligned chunks that attend back through the pool.  Hybrid's Mamba
#: scan carries state across the whole prompt — it prefills monolithically.
_CHUNKABLE_FAMILIES = ("dense", "audio", "moe")

# -- deprecation shim --------------------------------------------------------
# Every repro.serving DeprecationWarning message carries this prefix so the
# pytest filter in pyproject.toml can promote exactly the repo's own
# deprecations to errors (shim tests opt back out by the same prefix).
_DEPRECATION = "repro.serving deprecation: "
_warned: set = set()


def _warn_once(topic: str, msg: str) -> None:
    """One DeprecationWarning per process per topic, so a sweep over a
    legacy call site warns once instead of once per construction."""
    if topic in _warned:
        return
    _warned.add(topic)
    warnings.warn(_DEPRECATION + msg, DeprecationWarning, stacklevel=3)


def _reset_deprecation_warnings() -> None:
    """Re-arm the once-per-process deprecation warnings (tests)."""
    _warned.clear()


@dataclasses.dataclass(frozen=True)
class TokenEvent:
    """One decoded token, surfaced as it happens (``MultiTenantEngine.stream``)."""

    uid: int
    tenant: str
    lane: int
    token: int
    index: int  # position of this token in the request's generation
    done: bool  # True on the request's final token (retirement)


def _bucket_len(n: int, max_len: int, floor: int = _MIN_PREFILL_BUCKET) -> int:
    """Smallest power-of-two ≥ n (floor ``floor``), clamped to max_len —
    the padded prompt length admission prefill compiles for.  Paged engines
    raise the floor to ``block_size``: every bucket is then block-aligned
    (matching the write-id geometry chunked prefill needs) and the
    sub-block buckets collapse into one compilation."""
    b = floor
    while b < n:
        b *= 2
    return min(b, max_len)


class MultiTenantEngine:
    def __init__(
        self,
        cfg: ModelConfig,
        config: Optional[EngineConfig] = None,
        *,
        params: Optional[Pytree] = None,
        device: Optional[jax.Device] = None,
        **legacy,
    ):
        """``device`` commits the engine's params, λ tables and KV cache to
        that one device (a one-chip replica, ``serving/replica.py``); its
        steps then run there.  ``None`` leaves placement to JAX's default
        device, or to the mesh under ``shard_lam``/``shard_ba``."""
        if legacy:
            if config is not None:
                raise TypeError(
                    "pass either an EngineConfig or legacy keywords, not both"
                )
            _warn_once(
                "engine-kwargs",
                "MultiTenantEngine(cfg, n_lanes=..., paged=..., ...) keyword "
                "construction is deprecated; pass an EngineConfig "
                "(repro.serving.config), e.g. "
                "MultiTenantEngine(cfg, EngineConfig.serving(), params=p)",
            )
            config = EngineConfig.from_legacy_kwargs(**legacy)
        elif config is None:
            config = EngineConfig()
        if cfg.is_encoder or cfg.family == "vlm":
            raise NotImplementedError(
                f"continuous batching needs a token decode path (family "
                f"{cfg.family!r}: vlm lanes would need per-lane image "
                "embeds, encoders don't decode)"
            )
        if cfg.adapter.mode != "qr_lora":
            raise ValueError("multi-λ serving is defined for qr_lora adapters")
        layout = config.resolved_layout(cfg.family)  # raises: paged + no attn
        paged = layout == "paged"
        if not paged:
            # explicit oracle_dense conflicts fail in EngineConfig itself;
            # these catch layout="auto" resolving dense for a family whose
            # config asked for paged-only machinery
            if config.share_prefix:
                raise ValueError(
                    "share_prefix requires a paged layout (blocks to share)"
                )
            if config.watermark:
                raise ValueError(
                    "watermark requires a paged layout (blocks to reserve)"
                )
        n_lanes, n_slots = config.n_lanes, config.n_slots
        max_len, block_size = config.max_len, config.block_size
        n_blocks, share_prefix = config.n_blocks, config.share_prefix
        watermark, quantum = config.watermark, config.quantum
        cold_slots, shard_lam = config.cold_slots, config.shard_lam
        telemetry, seed = config.telemetry, config.seed
        self.cfg = cfg
        self.config = config
        self.layout = layout
        self.model = build_model(cfg)
        self.params = (
            params if params is not None else self.model.init(jax.random.PRNGKey(seed))
        )
        # Quantized frozen base: the engine knob wins over the model config
        # (serving decides the deployment dtype); "bf16" is a no-op, and
        # re-quantizing already-quantized params is one too, so passing a
        # pre-quantized tree is fine.
        self.base_dtype = (
            config.base_dtype if config.base_dtype != "bf16" else cfg.base_dtype
        )
        self.params = quantize_base_params(self.params, self.base_dtype)
        if device is not None and (config.shard_lam or config.shard_ba):
            raise ValueError(
                "device= pins a one-device engine; shard_lam/shard_ba span a mesh"
            )
        self.device = device
        if device is not None:
            # a copy per device: replicas never share one tree across chips
            self.params = jax.device_put(self.params, device)
        # λ-store tiers + sharding: a 1-D "model" mesh over the local
        # devices carries the slot axis of the packed λ tables when
        # shard_lam is on; the minimal rule table maps ONLY the λ-table
        # logical axis — weights/activations stay replicated, so the
        # sharded engine's math is bit-identical to the replicated one.
        self._cold_tier = cold_slots > 0
        # Telemetry rides on the engine from construction: metric handles
        # are no-op stubs when disabled, so every instrumentation site below
        # runs unconditionally and the disabled hot path pays ~zero.
        self.telemetry = Telemetry(enabled=telemetry)
        tel = self.telemetry
        # deferral episodes are deduped per uid (a request waiting N steps
        # is ONE deferral, not N); the sets persist across telemetry modes
        self._deferred_uids: set = set()
        self._deferred_pool_uids: set = set()
        self._defer_cold = tel.defers.labels(cause="cold_promote")
        self._mesh = None
        self._mesh_rules = None
        if shard_lam or config.shard_ba:
            self._mesh = make_mesh((len(jax.devices()),), ("model",))
            self._mesh_rules = {}
            if shard_lam:
                self._mesh_rules["lam_slots"] = "model"
            if config.shard_ba:
                self._mesh_rules["qr_rank"] = "model"
                # physically shard the B/A leaves over their rank dim; every
                # other leaf keeps a replicated placement (the rule table maps
                # only the opted-in logical axes, so param_sharding_rules
                # yields fully-replicated specs for the rest of the tree)
                with self._rules_ctx():
                    self.params = jax.device_put(
                        self.params, param_sharding_rules(self.params)
                    )
        with self._rules_ctx():
            self.lam_store = LamStore.from_params(
                self.params, n_slots=n_slots, cold_slots=cold_slots,
                cold_path=config.cold_path, mesh=self._mesh, device=device,
            )
        # tier pressure can drop a tenant without an explicit evict — its
        # prefix-cache family must be reclaimed just as eagerly
        self.lam_store.on_drop = lambda tenant, dg: self._drop_stale_family(dg)
        self.lam_store.attach_metrics(tel.registry)
        self.scheduler = ContinuousBatchScheduler(n_lanes)
        self.n_lanes, self.max_len = n_lanes, max_len
        self.collect_logits = config.collect_logits
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.paged = paged
        self.quantum = quantum
        self.slice_preemptions = 0  # quantum snapshot-preemptions
        # speculative decoding: families whose decode state is pure KV can
        # rewind a rejected draft (offsets retreat, stale rows stay masked);
        # hybrid's Mamba scan and ssm's recurrent state cannot.
        config.validate_speculation(cfg.family)
        self.speculate_k = config.speculate_k
        self.draft_lam_rank = config.draft_lam_rank
        self.spec_steps = 0  # speculative engine steps executed
        self.drafted_tokens = 0  # draft tokens proposed across all lanes
        self.accepted_drafts = 0  # drafted tokens the verify pass accepted
        self._draft_view_cache = None  # (λ-store version, drafter view)
        self.events: List[TokenEvent] = []  # tokens decoded by the last step()
        # chunked prefill: paged layouts of chunk-safe families only; hybrid
        # (Mamba scan spans the prompt) silently prefills monolithically
        self.prefill_chunk = config.prefill_chunk if paged else None
        self._chunkable = cfg.family in _CHUNKABLE_FAMILIES
        # uid → in-flight chunked-prefill progress (_begin_chunked_prefill)
        self._prefilling: Dict[int, Dict[str, Any]] = {}
        # uid → shipped-prefill payload awaiting admission (inject_prefilled)
        self._imports: Dict[int, Dict[str, Any]] = {}
        # paged buckets are floored at block_size: block-aligned shapes, one
        # compilation for every sub-block prompt (see _bucket_len)
        self._prefill_floor = (
            max(_MIN_PREFILL_BUCKET, block_size) if paged else _MIN_PREFILL_BUCKET
        )
        if paged:
            if max_len % block_size:
                raise ValueError(
                    f"max_len={max_len} must be a multiple of block_size={block_size}"
                )
            self.block_size = block_size
            self.max_blocks = max_len // block_size
            if n_blocks is None:
                n_blocks = 1 + n_lanes * self.max_blocks  # dense-equivalent
            self.allocator = BlockAllocator(
                n_blocks, block_size, metrics=tel.registry
            )
            if not 0 <= watermark < self.allocator.capacity:
                raise ValueError(
                    f"watermark={watermark} must be in [0, capacity={self.allocator.capacity})"
                )
            self.watermark = watermark
            self.prefix_cache = (
                PrefixCache(self.allocator, metrics=tel.registry)
                if share_prefix else None
            )
            self._lane_blocks: Dict[int, List[int]] = {}
            # uid → prefix blocks pinned (incref'd) at gate approval; consumed
            # by _admit_paged in the same admission round
            self._gate_matches: Dict[int, List[int]] = {}
            self._admit_seq = 0
            self.preemptions = 0
            self.cow_forks = 0
            self.cache = self.model.init_decode_state(
                n_lanes, max_len, self.dtype, paged=True,
                block_size=block_size, n_blocks=n_blocks,
            )
        else:
            if watermark:
                raise ValueError("watermark requires paged=True (blocks to reserve)")
            self.prefix_cache = None
            self.cache = self.model.init_decode_state(
                n_lanes, max_len, self.dtype, per_lane=True
            )
        if device is not None:
            self.cache = jax.device_put(self.cache, device)
        self.steps = 0
        self.decoded_tokens = 0
        self.prefill_buckets: set = set()  # padded lengths actually compiled

        model = self.model
        # LaneState protocol: the family's lane-axes tree drives admission
        # splice, retirement reset, and preemption snapshot/restore — the
        # engine itself never branches on the model family.
        axes = model.lane_axes(paged=paged)
        if paged:
            lane0 = model.init_decode_state(
                1, max_len, self.dtype, paged=True, block_size=block_size,
                n_blocks=2,  # pools are NO_LANE leaves — never restored from
            )
        else:
            lane0 = model.init_decode_state(1, max_len, self.dtype, per_lane=True)
        init_snap = extract_lane(lane0, axes, 0)

        def _prefill(view, cache, tokens, seg, length):
            return model.prefill(view, cache, tokens=tokens, seg_ids=seg, length=length)

        def _decode(view, cache, tok, seg, attend_blocks):
            """One decode step.  ``attend_blocks`` (static, paged layouts)
            bounds the fused attend to the active lanes' block high-water
            mark — HBM traffic tracks the longest live lane, not max_len."""
            return model.decode_step(
                view, cache, token=tok, seg_ids=seg, attend_blocks=attend_blocks
            )

        def _restore(big, small, lane):
            """Splice a 1-lane tree (admission prefill or preemption
            snapshot) into ``lane`` without touching neighbors."""
            return restore_lane(big, axes, lane, small)

        def _extract(cache, lane):
            """Snapshot one lane (preemption: O(1) for recurrent state)."""
            return extract_lane(cache, axes, lane)

        def _reset(cache, lane):
            """Return a lane to its freshly-initialized state (retirement /
            paged release: offsets zeroed, block-table rows → trash block,
            recurrent state re-initialized — xLSTM ``m`` back to -1e30)."""
            return restore_lane(cache, axes, lane, init_snap)

        def _prefill_paged(view, cache, tokens, seg, length, lane, write_ids, table_row):
            """Block-aligned admission prefill: run the prompt through a
            1-lane view whose table row is ``write_ids`` (shared prefix
            blocks and padding redirected to trash block 0), then commit the
            updated pools + the lane's real ``table_row`` into the cache."""
            pview = model.paged_prefill_view(cache, write_ids)
            logits, filled = model.prefill(
                view, pview, tokens=tokens, seg_ids=seg, length=length
            )
            return logits, model.commit_paged_prefill(
                cache, filled, lane, table_row, length
            )

        def _prefill_chunk(view, cache, tokens, seg, length, start, write_ids,
                           read_ids):
            """One non-final chunk of a chunked admission prefill: scatter
            this chunk's K/V into its pool blocks (cached prefix blocks and
            bucket overhang → trash) while attending back through
            ``read_ids``, so the chunk sees every earlier chunk's K/V under
            the absolute causal mask at ``start``.  Only the pools change —
            the lane's table row, offsets and position stay dark until the
            final chunk commits."""
            pview = model.paged_prefill_view(cache, write_ids, read_ids)
            _, filled = model.prefill(
                view, pview, tokens=tokens, seg_ids=seg, length=length,
                start=start,
            )
            a, f = cache["layers"]["attn"], filled["layers"]["attn"]
            attn = {**a, "k": f["k"], "v": f["v"]}
            return {"pos": cache["pos"], "layers": {**cache["layers"], "attn": attn}}

        def _prefill_chunk_final(view, cache, tokens, seg, length, start,
                                 write_ids, read_ids, lane, table_row):
            """Final chunk: same pass, then commit the lane (table row in,
            offsets ← true length) and surface the prompt's next-token
            logits (row ``length-1-start`` lands inside this chunk)."""
            pview = model.paged_prefill_view(cache, write_ids, read_ids)
            logits, filled = model.prefill(
                view, pview, tokens=tokens, seg_ids=seg, length=length,
                start=start,
            )
            return logits, model.commit_paged_prefill(
                cache, filled, lane, table_row, length
            )

        def _append_block(cache, lane, slot, block_id):
            """Lazy growth: point table entry ``slot`` of ``lane`` at a
            freshly allocated block."""
            a = cache["layers"]["attn"]
            G = a["block_tbl"].shape[0]
            tbl = jax.lax.dynamic_update_slice(
                a["block_tbl"],
                jnp.broadcast_to(jnp.asarray(block_id, jnp.int32), (G, 1, 1)),
                (0, lane, slot),
            )
            layers = {**cache["layers"], "attn": {**a, "block_tbl": tbl}}
            return {"pos": cache["pos"], "layers": layers}

        def _fork_block(cache, lane, slot, src, dst):
            """Copy-on-write: copy pool block ``src`` → ``dst`` on every
            layer and repoint the lane's table entry at the private copy."""
            a = cache["layers"]["attn"]
            G = a["block_tbl"].shape[0]
            k = a["k"].at[:, dst].set(a["k"][:, src])
            v = a["v"].at[:, dst].set(a["v"][:, src])
            tbl = jax.lax.dynamic_update_slice(
                a["block_tbl"],
                jnp.broadcast_to(jnp.asarray(dst, jnp.int32), (G, 1, 1)),
                (0, lane, slot),
            )
            attn = {"k": k, "v": v, "block_tbl": tbl, "idx": a["idx"]}
            return {"pos": cache["pos"], "layers": {**cache["layers"], "attn": attn}}

        def _import_blocks(cache, ids, kblk, vblk):
            """Adopt shipped K/V pool blocks (cross-replica prefix import /
            disaggregated prefill): scatter whole blocks into the slots
            ``ids``.  The id vector is padded to ``max_blocks`` width so
            every import shares one compilation; padding entries point at
            trash block 0 and carry zero rows — clobbering the trash block
            is the established write-redirect convention."""
            a = cache["layers"]["attn"]
            attn = {
                **a,
                "k": a["k"].at[:, ids].set(kblk.astype(a["k"].dtype)),
                "v": a["v"].at[:, ids].set(vblk.astype(a["v"].dtype)),
            }
            return {"pos": cache["pos"], "layers": {**cache["layers"], "attn": attn}}

        def _adopt_lane(cache, lane, table_row, length):
            """Commit an imported (already-resident) prompt into ``lane``:
            point its table row at the shipped blocks and advance offsets
            to the true length — ``commit_paged_prefill`` minus the pool
            adoption (the K/V rows arrived via ``_import_blocks``)."""
            a = cache["layers"]["attn"]
            G, _, mb = a["block_tbl"].shape
            ln = jnp.asarray(length, jnp.int32)
            pos = jax.lax.dynamic_update_slice(cache["pos"], ln[None], (lane,))
            tbl = jax.lax.dynamic_update_slice(
                a["block_tbl"],
                jnp.broadcast_to(table_row.astype(jnp.int32), (G, 1, mb)),
                (0, lane, 0),
            )
            idx = jax.lax.dynamic_update_slice(
                a["idx"], jnp.broadcast_to(ln, (G, 1)), (0, lane)
            )
            attn = {**a, "block_tbl": tbl, "idx": idx}
            return {"pos": pos, "layers": {**cache["layers"], "attn": attn}}

        spec_k = config.speculate_k

        def _draft(view, cache, tok, seg, attend_blocks):
            """Draft ``spec_k`` greedy tokens per lane in ONE dispatch,
            threading a LOCAL copy of the cache through the unrolled steps.
            JAX is functional, so the engine's cache never sees the draft
            writes — draft "rollback" is structural, not an operation."""
            toks = []
            t = tok
            for _ in range(spec_k):
                logits, cache = model.decode_step(
                    view, cache, token=t, seg_ids=seg,
                    attend_blocks=attend_blocks,
                )
                t = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
                toks.append(t[:, 0])
            return jnp.stack(toks, axis=1)  # (lanes, spec_k)

        def _verify(view, cache, window, seg, n_valid, attend_blocks):
            """Score each lane's (k+1)-token window in one multi-position
            forward under the full multi-λ view.  The returned cache holds
            every window position's K/V but UNCHANGED offsets — the host
            commits each lane's accepted advance separately."""
            logits, cache = model.verify_step(
                view, cache, tokens=window, seg_ids=seg, n_valid=n_valid,
                attend_blocks=attend_blocks,
            )
            return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        def _commit_advance(cache, adv):
            """Advance each lane's KV write offset and position by its
            accepted length.  Window rows past the acceptance stay masked
            (attends read ``kpos <= idx``) until later steps overwrite them
            — that masking IS the dense-layout KV rollback."""
            a = cache["layers"]["attn"]
            attn = {**a, "idx": a["idx"] + adv[None, :]}
            return {
                "pos": cache["pos"] + adv,
                "layers": {**cache["layers"], "attn": attn},
            }

        # model-forward jits trace adapted_matmul, which consults the
        # logical-axis rules for the λ-table sharding — keep the rule
        # context active around every call (the tracing one included)
        self._prefill = self._with_rules(jax.jit(_prefill))
        self._decode_jit = jax.jit(_decode, static_argnums=(4,))
        self._decode = self._with_rules(self._decode_jit)
        self._restore = jax.jit(_restore)
        self._extract = jax.jit(_extract)
        self._reset = jax.jit(_reset)
        self._prefill_paged = self._with_rules(jax.jit(_prefill_paged))
        self._prefill_chunk = self._with_rules(jax.jit(_prefill_chunk))
        self._prefill_chunk_final = self._with_rules(jax.jit(_prefill_chunk_final))
        self._append_block = jax.jit(_append_block)
        self._fork_block = jax.jit(_fork_block)
        self._import_blocks = jax.jit(_import_blocks)
        self._adopt_lane = jax.jit(_adopt_lane)
        if spec_k:
            self._draft = self._with_rules(jax.jit(_draft, static_argnums=(4,)))
            self._verify = self._with_rules(jax.jit(_verify, static_argnums=(5,)))
            self._commit_advance = jax.jit(_commit_advance)

        # engine-level callback metrics: sampled only at snapshot() time,
        # zero hot-path cost.  The jit compile counts hook the same
        # ``_cache_size`` machinery the compile-count tests already use.
        reg = tel.registry
        reg.callback("serve_queue_depth", lambda: len(self.scheduler.queue),
                     help="requests waiting for a decode lane")
        reg.callback("serve_active_lanes",
                     lambda: sum(r is not None for r in self.scheduler.lanes),
                     help="decode lanes currently occupied")
        reg.callback("serve_lane_capacity", lambda: self.n_lanes,
                     help="fixed decode-lane count")
        reg.callback("serve_steps_total", lambda: self.steps, kind="counter",
                     help="engine decode steps executed")
        reg.callback("serve_decoded_tokens_total",
                     lambda: self.decoded_tokens, kind="counter",
                     help="tokens decoded, incl. preemption re-derivation "
                          "(serve_tokens_total is the delivered subset)")
        reg.callback("serve_prefill_buckets",
                     lambda: len(self.prefill_buckets),
                     help="distinct padded prompt lengths prefilled "
                          "(= prefill compilations under bucketing)")
        jits = [("prefill", self._prefill), ("decode", self._decode),
                ("prefill_paged", self._prefill_paged),
                ("prefill_chunk", self._prefill_chunk)]
        if spec_k:
            jits += [("draft", self._draft), ("verify", self._verify)]
        for _n, _f in jits:
            _cs = getattr(_f, "_cache_size", None)
            if callable(_cs):
                reg.callback(f"serve_jit_compiles_{_n}", _cs, kind="counter",
                             help=f"compiled variants of the {_n} step")

    def _rules_ctx(self):
        if self._mesh is None:
            return nullcontext()
        return axis_rules(self._mesh, self._mesh_rules)

    def _with_rules(self, jf):
        if self._mesh is None:
            return jf

        def wrapped(*args):
            with self._rules_ctx():
                return jf(*args)

        wrapped._cache_size = getattr(jf, "_cache_size", None)
        return wrapped

    @property
    def registry(self) -> LamStore:
        """Deprecated alias of :attr:`lam_store` (the PR-1 name)."""
        _warn_once(
            "engine-registry",
            "MultiTenantEngine.registry is deprecated; use .lam_store",
        )
        return self.lam_store

    # -- tenants ------------------------------------------------------------

    def add_tenant(self, tenant: str, lam_tree) -> int:
        """Register/hot-swap a tenant's λ checkpoint; returns its hot slot
        (or ``COLD_SLOT`` when it landed in the host cold tier).  A
        hot-swap that retires the tenant's old λ digest eagerly drops that
        family's prefix-cache entries."""
        old = self.lam_store.digest(tenant) if tenant in self.lam_store else None
        slot = self.lam_store.register(tenant, lam_tree)
        self._drop_stale_family(old)
        return slot

    def add_tenants(self, lams: Dict[str, Any]) -> Dict[str, int]:
        """Batch :meth:`add_tenant`: the whole cohort lands in one donated
        multi-slot table write (``LamStore.register_many``) — the router's
        peer-promotion path registers a tenant catalog on a replica without
        paying one dispatch per tenant."""
        olds = {
            t: self.lam_store.digest(t) if t in self.lam_store else None
            for t in lams
        }
        slots = self.lam_store.register_many(lams)
        for old in olds.values():
            self._drop_stale_family(old)
        return slots

    def remove_tenant(self, tenant: str) -> None:
        """Drop a tenant from both λ-store tiers (no queued/active work may
        reference it) and reclaim its prefix-cache family eagerly."""
        old = self.lam_store.digest(tenant)
        self.lam_store.evict(tenant)
        self._drop_stale_family(old)

    def _drop_stale_family(self, old_digest: Optional[bytes]) -> None:
        """Prefix-cache entries keyed on a λ digest no resident tenant
        carries can never match again — without this they would hold their
        blocks ref'd until cache LRU finally cycles them out."""
        if old_digest is None or self.prefix_cache is None:
            return
        if self.lam_store.digest_refcount(old_digest) == 0:
            self.prefix_cache.drop_family(old_digest)

    def _params_view(self) -> Pytree:
        # LamStore.install() memoizes on (params identity, version) itself
        return self.lam_store.install(self.params)

    def _draft_params_view(self) -> Pytree:
        """Drafter parameter view.  Base drafter (``draft_lam_rank=None``):
        strip the adapters entirely — exactly the λ ≡ 0 slot-0 tenant,
        with the per-token BGMV *skipped* rather than multiplied by zeros.
        Truncated-λ drafter (``draft_lam_rank=r``): keep only each slot
        row's top-r |λ| coefficients — OSoRA's singular-value-coefficient
        reading of the QR basis makes that a principled smaller model.
        Memoized on the λ-store version (slot writes invalidate)."""
        view = self._params_view()
        if self.draft_lam_rank is None:
            return {**view, "groups": {**view["groups"], "adapters": {}}}
        ver = self.lam_store.version
        if self._draft_view_cache is not None and self._draft_view_cache[0] == ver:
            return self._draft_view_cache[1]
        r = self.draft_lam_rank

        def trunc(leaf):
            lam = leaf["lam"]
            if lam.shape[-1] <= r:
                return leaf
            mag = jnp.abs(lam)
            thr = jnp.sort(mag, axis=-1)[..., -r][..., None]
            return {**leaf, "lam": jnp.where(mag >= thr, lam, jnp.zeros_like(lam))}

        adapters = {
            mod: {proj: trunc(leaf) for proj, leaf in projs.items()}
            for mod, projs in view["groups"]["adapters"].items()
        }
        dview = {**view, "groups": {**view["groups"], "adapters": adapters}}
        self._draft_view_cache = (ver, dview)
        return dview

    # -- requests -----------------------------------------------------------

    def submit(self, tenant: str, prompt, max_new_tokens: int) -> Request:
        if tenant not in self.lam_store:
            raise KeyError(f"unknown tenant {tenant!r} — add_tenant() first")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({prompt.size}) + gen({max_new_tokens}) exceeds "
                f"max_len={self.max_len}"
            )
        if self.paged:
            # feasibility only — blocks are acquired lazily, but a request
            # whose worst-case (unshared) footprint exceeds the pool, or
            # whose prompt can't be admitted while keeping the decode-growth
            # watermark free, could never run to completion.
            worst = self.allocator.blocks_for(prompt.size + max_new_tokens)
            at_admit = self.allocator.blocks_for(prompt.size) + self.watermark
            if max(worst, at_admit) > self.allocator.capacity:
                raise ValueError(
                    f"request needs {worst} blocks ({at_admit} at admission "
                    f"with watermark={self.watermark}) but the pool only has "
                    f"{self.allocator.capacity} — it could never be admitted"
                )
        if self._cold_tier:
            # two-level pinning: submission only *protects* (the tenant must
            # stay in the store but may spill to the cold tier while
            # queued); the hot-slot pin is taken at admission, when the
            # request actually occupies a lane.
            self.lam_store.protect(tenant)
        else:
            # pin from submission (not admission): a queued request must keep
            # its tenant's slot resident until it finishes
            self.lam_store.pin(tenant)
        req = self.scheduler.submit(tenant, prompt, max_new_tokens)
        self.telemetry.on_submit(req)
        return req

    def cancel(self, req: Request) -> None:
        """Withdraw a request.  A queued one just leaves the queue; an
        active lane frees its blocks and resets (tokens already delivered
        stay on the request).  The disaggregated prefill replica's
        export-then-cancel handoff (serving/router.py) rides on this."""
        if req.lane >= 0:
            self._prefilling.pop(req.uid, None)
            self._imports.pop(req.uid, None)
            lane = req.lane
            self.scheduler.finish(req)
            self.lam_store.unpin(req.tenant)
            if self._cold_tier:
                self.lam_store.unprotect(req.tenant)
            if self.paged:
                for b in self._lane_blocks.pop(lane):
                    self.allocator.decref(b)
                self.cache = self._reset(self.cache, lane)
            return
        try:
            self.scheduler.queue.remove(req)
        except ValueError:
            return  # already finished (or never submitted here)
        self._imports.pop(req.uid, None)
        if self._cold_tier:
            self.lam_store.unprotect(req.tenant)
        else:
            self.lam_store.unpin(req.tenant)

    # -- multi-replica hooks (serving/replica.py, serving/router.py) --------

    def export_prefix(self, tenant: str, prompt) -> Optional[Dict[str, Any]]:
        """Package the resident prefix-cache blocks for ``(tenant,
        prompt)`` as a host payload a sibling replica can
        :meth:`import_prefix` — full-block K/V only (the partial tail is
        never cached).  ``None`` when nothing is cached here."""
        if self.prefix_cache is None or tenant not in self.lam_store:
            return None
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        blocks = self.prefix_cache.match(
            self._family_key(tenant, prompt.size), prompt
        )
        if not blocks:
            return None
        a = self.cache["layers"]["attn"]
        ids = jnp.asarray(np.asarray(blocks, np.int32))
        return {
            "block_size": self.block_size,
            "n_blocks": len(blocks),
            "tokens": prompt[: len(blocks) * self.block_size].copy(),
            "k": np.asarray(a["k"][:, ids]),
            "v": np.asarray(a["v"][:, ids]),
        }

    def import_prefix(self, tenant: str, prompt, payload) -> int:
        """Adopt a sibling replica's exported prefix blocks into this
        engine's pool + prefix cache (cross-replica prefix sharing).  Only
        the blocks beyond the local match are imported; LRU cache entries
        are evicted to make room.  Returns blocks adopted — 0 means nothing
        new or no room, and the request simply prefills locally (imports
        are an optimization, never a correctness dependency)."""
        if self.prefix_cache is None or payload is None:
            return 0
        if payload["block_size"] != self.block_size:
            raise ValueError("sibling replica's block geometry differs")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        fam = self._family_key(tenant, prompt.size)
        local = self.prefix_cache.match(fam, prompt)
        n = payload["n_blocks"]
        take = n - len(local)
        if take <= 0:
            return 0
        while not self.allocator.can_alloc(take) and len(self.prefix_cache):
            self.prefix_cache.evict_one()
        if not self.allocator.can_alloc(take):
            return 0
        new_ids = self.allocator.alloc(take)
        self._write_imported_blocks(new_ids, payload, skip=len(local))
        self.prefix_cache.insert(
            fam, prompt[: n * self.block_size], local + new_ids
        )
        for b in new_ids:
            self.allocator.decref(b)  # the cache now holds the only ref
        return take

    def _write_imported_blocks(self, new_ids, payload, *, skip: int) -> None:
        """Scatter payload blocks ``skip:`` into the freshly allocated pool
        slots ``new_ids`` via the fixed-width ``_import_blocks`` jit."""
        take = len(new_ids)
        ids = np.zeros((self.max_blocks,), np.int32)
        ids[:take] = new_ids
        G = payload["k"].shape[0]
        kb = np.zeros((G, self.max_blocks) + payload["k"].shape[2:],
                      payload["k"].dtype)
        vb = np.zeros_like(kb)
        kb[:, :take] = payload["k"][:, skip: skip + take]
        vb[:, :take] = payload["v"][:, skip: skip + take]
        self.cache = self._import_blocks(
            self.cache, jnp.asarray(ids), jnp.asarray(kb), jnp.asarray(vb)
        )

    def export_request_state(self, req: Request) -> Dict[str, Any]:
        """Disaggregation payload for an active request whose prefill has
        committed (first token emitted): the prompt's K/V blocks bit-exact,
        the first token's logits row, and the prompt itself — everything a
        decode replica needs to splice the request into a lane without
        recompute (:meth:`inject_prefilled`).  The caller cancel()s the
        request here right after (serving/router.py)."""
        if not self.paged:
            raise ValueError("export_request_state needs a paged layout")
        if req.lane < 0 or not req.tokens or req.uid in self._prefilling:
            raise ValueError("request has no committed prefill to export")
        nb = self.allocator.blocks_for(req.prompt.size)
        blocks = self._lane_blocks[req.lane][:nb]
        a = self.cache["layers"]["attn"]
        ids = jnp.asarray(np.asarray(blocks, np.int32))
        return {
            "block_size": self.block_size,
            "prompt": req.prompt.copy(),
            "n_blocks": len(blocks),
            "k": np.asarray(a["k"][:, ids]),
            "v": np.asarray(a["v"][:, ids]),
            "logits": req.logits[0] if req.logits else None,
            "token": req.tokens[0],
        }

    def inject_prefilled(self, tenant: str, prompt, max_new_tokens: int,
                         payload: Dict[str, Any]) -> Request:
        """Submit a request whose prefill already ran on a prefill replica:
        admission splices the shipped blocks into a lane
        (``_adopt_prefilled``) instead of running the prompt forward — the
        decode half of prefill/decode disaggregation.  Restricted to
        chunkable (pure-KV) families: recurrent prompt state cannot ship
        block-wise."""
        if not self.paged:
            raise ValueError("inject_prefilled needs a paged layout")
        if not self._chunkable:
            raise ValueError(
                f"family {self.cfg.family!r} carries recurrent prompt state "
                "— its prefill cannot be disaggregated"
            )
        if payload["block_size"] != self.block_size:
            raise ValueError("prefill replica's block geometry differs")
        req = self.submit(tenant, prompt, max_new_tokens)
        self._imports[req.uid] = payload
        return req

    def _adopt_prefilled(self, req: Request, payload) -> np.ndarray:
        """Admission splice of a shipped prefill: adopt locally-cached
        prefix blocks where present, scatter the remaining shipped blocks
        into fresh allocations, commit the lane's table row + offsets
        (``_adopt_lane``), and file the full blocks in the prefix cache.
        No prompt forward pass runs; returns the first token's logits row."""
        P, bs = req.prompt.size, self.block_size
        cached = self._gate_matches.pop(req.uid, [])
        new_ids = self.allocator.alloc(self.allocator.blocks_for(P) - len(cached))
        blocks = cached + new_ids
        self._lane_blocks[req.lane] = blocks
        req.admit_seq = self._admit_seq
        self._admit_seq += 1
        self._write_imported_blocks(new_ids, payload, skip=len(cached))
        table_row = np.zeros((self.max_blocks,), np.int32)
        table_row[: len(blocks)] = blocks
        self.cache = self._adopt_lane(
            self.cache, req.lane, jnp.asarray(table_row), np.int32(P)
        )
        if self.prefix_cache is not None:
            self.prefix_cache.insert(self._family(req), req.prompt, blocks)
            self.telemetry.prefix_hits.inc(len(cached))
            self.telemetry.prefix_misses.inc(P // bs - len(cached))
        row = payload["logits"]
        if row is None:
            if self.collect_logits:
                raise ValueError(
                    "decode replica collects logits but the prefill payload "
                    "carries none — run the prefill replica with "
                    "collect_logits=True"
                )
            # token-only handoff: synthesize a one-hot row so _emit's argmax
            # reproduces the prefill replica's token
            row = np.zeros((self.cfg.vocab_size,), np.float32)
            row[payload["token"]] = 1.0
        return row

    # -- paged block accounting ---------------------------------------------

    def _family_key(self, tenant: str, prompt_len: int) -> bytes:
        """Prefix-cache family key: tenant λ digest + prefill bucket.  Two
        prefills may only share K/V blocks when they ran the same adapter
        *and* the same compiled prefill program (same bucket) — that keeps
        shared-prefix output bit-identical to the unshared engine.  The key
        is digest-based, not tenant-name-based, so two replicas serving the
        same λ derive the same key (cross-replica prefix import relies on
        it)."""
        Pb = _bucket_len(prompt_len, self.max_len, self._prefill_floor)
        return self.lam_store.digest(tenant) + Pb.to_bytes(4, "little")

    def _family(self, req: Request) -> bytes:
        return self._family_key(req.tenant, req.prompt.size)

    def _admission_gate(self):
        """Pool gate for ``scheduler.admit``: approving a request *reserves*
        its fresh prompt blocks for this admission round (so one round can't
        hand the same free blocks to two requests) and keeps ``watermark``
        blocks free as decode-growth headroom.  Approval also *pins*
        (increfs) the request's matched prefix blocks immediately — a later
        request's gate may evict cache entries in the same round, and the
        reservation must survive that — stashing them for ``_admit_paged``.
        When the FIFO head starves while the prefix cache hoards
        reclaimable blocks, the cache is evicted LRU-first until the head
        fits or nothing is left."""
        reserved = [0]

        def gate(req: Request) -> bool:
            while True:
                cached: List[int] = []
                if self.prefix_cache is not None:
                    cached = self.prefix_cache.match(self._family(req), req.prompt)
                need = self.allocator.blocks_for(req.prompt.size) - len(cached)
                if self.allocator.n_free - reserved[0] >= need + self.watermark:
                    for b in cached:
                        self.allocator.incref(b)
                    if self.prefix_cache is not None:
                        self.prefix_cache.hits += len(cached)
                        self.prefix_cache.misses += (
                            req.prompt.size // self.block_size - len(cached)
                        )
                    self._gate_matches[req.uid] = cached
                    reserved[0] += need
                    self._deferred_pool_uids.discard(req.uid)
                    return True
                if self.prefix_cache is None or not len(self.prefix_cache):
                    if req.uid not in self._deferred_pool_uids:
                        self._deferred_pool_uids.add(req.uid)
                        self.telemetry.on_defer(req, "pool_full")
                    return False
                self.prefix_cache.evict_one()

        return gate

    def _make_gate(self):
        """Compose the admission gates: promote-on-demand for cold tenants
        (deferring, exactly like pool-full defers, when every hot slot is
        pinned by an active lane) and the paged block-pool gate.  In
        cold-tier mode approval also takes the hot-slot pin the lane holds
        until retirement/preemption."""
        paged_gate = self._admission_gate() if self.paged else None
        if not self._cold_tier:
            return paged_gate
        reg = self.lam_store

        def gate(req: Request) -> bool:
            if not reg.is_hot(req.tenant) and reg.promote(req.tenant) is None:
                if req.uid not in self._deferred_uids:
                    self._deferred_uids.add(req.uid)
                    self.telemetry.on_defer(req, "cold_promote")
                return False
            self._deferred_uids.discard(req.uid)
            reg.pin(req.tenant)
            if paged_gate is not None and not paged_gate(req):
                reg.unpin(req.tenant)
                return False
            return True

        return gate

    def _reclaim_one_block(self, req: Request) -> Optional[int]:
        """One block for ``req``'s decode growth.  Scavenge cache-only
        prefix blocks first; then preempt the youngest lane (possibly
        ``req`` itself, in which case return None).  The oldest lane always
        wins this race, so decode can never deadlock on an exhausted pool."""
        while not self.allocator.can_alloc(1):
            if self.prefix_cache is not None and len(self.prefix_cache):
                self.prefix_cache.evict_one()
                continue
            active = self.scheduler.active()
            if not active:  # unreachable: req is active when growing
                raise PoolExhausted("no active lane to preempt")
            victim = max(active, key=lambda r: r.admit_seq)
            self._preempt(victim)
            if victim is req:
                return None
        return self.allocator.alloc(1)[0]

    def _preempt(self, victim: Request) -> None:
        """Block-pressure preemption: free a lane's blocks, reset the lane,
        and kick its request to the queue front; greedy decode re-derives
        the lost tokens on re-admission."""
        lane = victim.lane
        self.telemetry.on_preempt(victim, "block_pressure")
        # a mid-chunked-prefill victim just abandons its progress: its lane
        # was never committed (table row still trash), its blocks free like
        # any lane's, and re-admission restarts the chunked prefill
        self._prefilling.pop(victim.uid, None)
        for b in self._lane_blocks.pop(lane):
            self.allocator.decref(b)
        self.cache = self._reset(self.cache, lane)
        if self._cold_tier:
            self.lam_store.unpin(victim.tenant)  # re-pinned at re-admission
        self.scheduler.preempt(victim)
        self.preemptions += 1

    def _preempt_quantum(self, req: Request) -> None:
        """Time-slice preemption: snapshot the lane (LaneState extract —
        O(1) per lane for recurrent families) and re-queue at the back;
        re-admission restores the snapshot, no recompute.  The snapshot is
        staged to host memory so a deep queue of time-sliced requests does
        not pin per-waiter device copies of lane state (a dense attention
        lane's snapshot is its whole ``(max_len, KV, dh)`` K/V region);
        restore ships it back in one transfer."""
        self.telemetry.on_preempt(req, "quantum")
        req.snapshot = jax.device_get(self._extract(self.cache, req.lane))
        if self._cold_tier:
            self.lam_store.unpin(req.tenant)  # re-pinned at re-admission
        self.scheduler.preempt(req, to_back=True, keep_progress=True)
        self.slice_preemptions += 1

    def _grow_lanes(self, window: int = 1) -> None:
        """Lazy growth, oldest lane first: give every active lane the blocks
        its next ``window`` decode writes land in, allocating (or CoW-forking
        a shared block) on block-boundary crossings.  Speculative engines
        grow k+1 positions of headroom at once; only the *write-position*
        block is ever forked (acceptance always reaches it, so the plain
        engine forks it too) — a shared block deeper in the window instead
        caps that lane's draft window at the boundary (_spec_window_cap),
        so speculative rollback never has to undo a CoW fork."""
        bs = self.block_size
        for req in sorted(self.scheduler.active(), key=lambda r: r.admit_seq):
            if req.lane < 0:  # preempted by an older lane's growth this pass
                continue
            if req.uid in self._prefilling:  # not decoding yet — no growth
                continue
            write_pos = req.prompt.size + len(req.tokens) - 1
            span = max(min(window, req.max_new_tokens - len(req.tokens)), 1)
            first_blk = write_pos // bs
            last_blk = (write_pos + span - 1) // bs
            blocks = self._lane_blocks[req.lane]
            for blk_idx in range(first_blk, last_blk + 1):
                if blk_idx >= len(blocks):
                    bid = self._reclaim_one_block(req)
                    if bid is None:  # req itself was the preemption victim
                        break
                    blocks.append(bid)
                    self.cache = self._append_block(
                        self.cache, req.lane, blk_idx, bid
                    )
                elif self.allocator.is_shared(blocks[blk_idx]):
                    if blk_idx != first_blk:
                        # shared block deeper in the speculative window:
                        # leave it — the window caps at this boundary
                        break
                    # copy-on-write: never write into a block someone else
                    # reads
                    src = blocks[blk_idx]
                    if self.allocator.can_alloc(1):
                        dst = self.allocator.fork(src)
                    else:
                        dst = self._reclaim_one_block(req)
                        if dst is None:
                            break
                        self.allocator.decref(src)  # lane's ref moves to the copy
                    blocks[blk_idx] = dst
                    self.cache = self._fork_block(
                        self.cache, req.lane, blk_idx, src, dst
                    )
                    self.cow_forks += 1
                    self.telemetry.on_cow_fork(req, src, dst)

    # -- speculative decoding ------------------------------------------------

    def _spec_window_cap(self, req: Request) -> int:
        """Largest verify window (free token + drafts) this lane can take
        this step: bounded by its remaining generation budget and, paged,
        by the blocks it actually owns — growth stops at the first shared
        block past the write block (forking it just to maybe roll it back
        would desync refcounts from the plain engine) and may come up short
        under pool pressure."""
        nv = min(self.speculate_k + 1, req.max_new_tokens - len(req.tokens))
        if not self.paged:
            return max(nv, 1)
        bs = self.block_size
        write_pos = req.prompt.size + len(req.tokens) - 1
        blocks = self._lane_blocks[req.lane]
        limit_blk = len(blocks)
        for i in range(write_pos // bs + 1, len(blocks)):
            if self.allocator.is_shared(blocks[i]):
                limit_blk = i
                break
        return max(1, min(nv, limit_blk * bs - write_pos))

    def _rollback_window_blocks(self, decoding, adv) -> None:
        """Release window blocks past each lane's accepted frontier — the
        pre-grown headroom a short acceptance didn't reach.  Those are
        always fresh private allocations (growth forks only the write
        block, and every step restores the covers-exactly-the-KV block
        invariant), so a decref + trash-repoint restores exact refcount
        parity with the plain engine; no CoW fork is ever undone."""
        bs = self.block_size
        for req in decoding:
            lane = req.lane
            # pre-emit: len(tokens) is still the pre-step count, so this is
            # the lane's post-commit write offset
            idx_new = req.prompt.size + len(req.tokens) - 1 + int(adv[lane])
            keep = (idx_new - 1) // bs + 1  # plain-engine post-step coverage
            blocks = self._lane_blocks[lane]
            while len(blocks) > keep:
                slot = len(blocks) - 1
                self.allocator.decref(blocks.pop())
                self.cache = self._append_block(self.cache, lane, slot, 0)

    def _step_speculative(self, decoding, tok, ab, finished, t) -> None:
        """One speculative decode step: draft k tokens per lane with the
        cheap drafter view (one dispatch, throwaway cache), verify every
        lane's (k+1)-token window under the full multi-λ view (one
        dispatch), accept each lane's longest greedy-matching prefix, then
        commit offsets and roll back paged blocks past the accepted
        frontier.  Greedy decode is bit-deterministic, so prefix equality
        is *exact* acceptance — the emitted tokens and logits rows are
        identical to the plain engine's, delivered up to k+1 at a time."""
        tel = self.telemetry
        on = tel.enabled
        k = self.speculate_k
        seg = jnp.asarray(self.scheduler.batch_composition())
        view = self._params_view()
        dview = self._draft_params_view()
        t_disp = tel.now() if on else 0.0
        drafts = np.asarray(
            self._draft(dview, self.cache, jnp.asarray(tok), seg, ab)
        )  # host sync fences the draft dispatch
        if on:
            tel.on_spec_phase("draft", t_disp, tel.now())
        window = np.zeros((self.n_lanes, k + 1), np.int32)
        window[:, 0] = tok[:, 0]
        window[:, 1:] = drafts
        n_valid = np.zeros((self.n_lanes,), np.int32)
        for req in decoding:
            n_valid[req.lane] = self._spec_window_cap(req)
        t_ver = tel.now() if on else 0.0
        logits, greedy, cache = self._verify(
            view, self.cache, jnp.asarray(window), seg,
            jnp.asarray(n_valid), ab,
        )
        logits_np = np.asarray(logits)  # host sync: the verify really ran
        greedy_np = np.asarray(greedy)
        t_sync = tel.now() if on else 0.0
        if on:
            tel.on_spec_phase("verify", t_ver, t_sync)
        adv = np.zeros((self.n_lanes,), np.int32)
        for req in decoding:
            lane, nv = req.lane, int(n_valid[req.lane])
            a = 1  # window[0] is the lane's own last token — always accepted
            while a < nv and window[lane, a] == greedy_np[lane, a - 1]:
                a += 1
            adv[lane] = a
        self.cache = self._commit_advance(cache, jnp.asarray(adv))
        if self.paged:
            self._rollback_window_blocks(decoding, adv)
        self.steps += 1
        self.spec_steps += 1
        drafted = k * len(decoding)
        accepted = int(adv.sum()) - len(decoding)
        self.drafted_tokens += drafted
        self.accepted_drafts += accepted
        tel.on_speculate(drafted, accepted, drafted - accepted)
        for req in decoding:
            lane, a = req.lane, int(adv[req.lane])
            req.slice_steps += a  # quantum accounting in accepted TOKENS
            for j in range(a):
                self._emit(req, logits_np[lane, j], finished)
            if on:
                tel.on_decode_lane(req, t_disp, t_sync, req.tokens[-1])
        if on:
            tel.phase("emit", tel.now() - t_sync)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verify pass accepted (0.0 before
        any speculative step has run)."""
        if not self.drafted_tokens:
            return 0.0
        return self.accepted_drafts / self.drafted_tokens

    # -- the serving loop ---------------------------------------------------

    def _admit(self, finished: List[Request]) -> None:
        gate = self._make_gate()
        tel = self.telemetry
        for req in self.scheduler.admit(gate):
            tel.on_admit(req, restored=req.snapshot is not None)
            view = self._params_view()  # after gate: promotion bumps version
            req.slot = self.lam_store.lookup(req.tenant)  # pinned since submit
            req.slice_steps = 0
            if req.snapshot is not None:
                # time-sliced re-admission: restore the preemption snapshot
                # into the (possibly different) lane — no prefill, no emit,
                # decode resumes from the last generated token.
                self.cache = self._restore(self.cache, req.snapshot, req.lane)
                req.snapshot = None
                continue
            seg = jnp.full((1,), req.slot, jnp.int32)
            # prompt-length bucketing: pad to a power of two so distinct
            # prompt lengths share prefill compilations; true length masks
            # (incl. the recurrent states: padded scan steps are identities)
            P = req.prompt.size
            Pb = _bucket_len(P, self.max_len, self._prefill_floor)
            padded = np.zeros((Pb,), np.int32)
            padded[:P] = req.prompt
            length = jnp.full((1,), P, jnp.int32)
            t0 = tel.now() if tel.enabled else 0.0
            if self.paged:
                payload = self._imports.pop(req.uid, None)
                if payload is not None:
                    # disaggregated admission: the prompt's K/V was computed
                    # on a prefill replica and shipped — splice it in, no
                    # prompt forward pass (serving/router.py)
                    row = self._adopt_prefilled(req, payload)
                    if tel.enabled:
                        tel.on_prefill(req, t0, tel.now())
                    self._emit(req, row, finished)
                    continue
                self.prefill_buckets.add(Pb)
                if (
                    self.prefill_chunk is not None
                    and self._chunkable
                    and Pb > self.prefill_chunk
                    and (
                        P > 2 * self.prefill_chunk
                        or self._gate_matches.get(req.uid)
                    )
                ):
                    # long prompt: allocate its blocks now, stream its
                    # chunks through the following steps' prefill budget.
                    # Short prompts (≤ 2 chunks of work) auto-disable —
                    # splitting one dispatch into two buys no TBT bound and
                    # pays a second dispatch — UNLESS a cached prefix lets
                    # the chunk path skip resident blocks' compute entirely
                    # (the monolithic path recomputes them into the trash).
                    self._begin_chunked_prefill(req, padded, seg, length, t0)
                    continue
                logits = self._admit_paged(req, view, padded, seg, length)
            else:
                self.prefill_buckets.add(Pb)
                lane_cache = self.model.init_decode_state(
                    1, self.max_len, self.dtype, per_lane=True
                )
                logits, lane_cache = self._prefill(
                    view, lane_cache, jnp.asarray(padded)[None, :], seg, length
                )
                self.cache = self._restore(self.cache, lane_cache, req.lane)
            # materialize before timing: the host sync is part of the
            # prefill cost the lane actually paid
            row = np.asarray(logits[0])
            if tel.enabled:
                tel.on_prefill(req, t0, tel.now())
            self._emit(req, row, finished)

    def _admit_paged(self, req: Request, view, padded, seg, length):
        """Paged admission: adopt the shared-prefix blocks the gate pinned,
        allocate private blocks for the rest of the prompt only (lazy — gen
        blocks come later), and prefill block-aligned."""
        P, bs = req.prompt.size, self.block_size
        cached = self._gate_matches.pop(req.uid, [])
        if self.prefix_cache is not None and len(cached) < P // bs:
            # re-match: an earlier admission in this round may have filed
            # this very prefix (same-round sharing).  Only *extend* the
            # gate-pinned base — extending allocates less than the gate
            # reserved, never more — and only when the fresh chain agrees
            # with the pinned blocks (eviction races can reshuffle entries).
            # A gate match already covering every full block skips the
            # re-walk: there is nothing left to extend.
            fresh = self.prefix_cache.match(self._family(req), req.prompt)
            if len(fresh) > len(cached) and fresh[: len(cached)] == cached:
                for b in fresh[len(cached):]:
                    self.allocator.incref(b)
                self.prefix_cache.hits += len(fresh) - len(cached)
                self.prefix_cache.misses -= len(fresh) - len(cached)
                cached = fresh
        new_ids = self.allocator.alloc(self.allocator.blocks_for(P) - len(cached))
        blocks = cached + new_ids
        self._lane_blocks[req.lane] = blocks
        req.admit_seq = self._admit_seq
        self._admit_seq += 1

        nb = -(-len(padded) // bs)  # bucket table width
        write_ids = np.zeros((nb,), np.int32)  # cached prefix + padding → trash
        write_ids[len(cached): len(blocks)] = new_ids
        table_row = np.zeros((self.max_blocks,), np.int32)
        table_row[: len(blocks)] = blocks
        logits, self.cache = self._prefill_paged(
            view, self.cache, jnp.asarray(padded)[None, :], seg, length,
            req.lane, jnp.asarray(write_ids), jnp.asarray(table_row),
        )
        if self.prefix_cache is not None:
            # file this prompt's full blocks for reuse (the partial tail —
            # still receiving decode writes — is never cached)
            self.prefix_cache.insert(self._family(req), req.prompt, blocks)
            # monotonic telemetry counters tally once, post re-match — the
            # cache's own hit/miss attrs are adjusted incrementally above
            # but net out to the same totals
            self.telemetry.prefix_hits.inc(len(cached))
            self.telemetry.prefix_misses.inc(P // bs - len(cached))
        return logits

    # -- chunked prefill ----------------------------------------------------

    def _begin_chunked_prefill(self, req: Request, padded, seg, length, t0):
        """Paged admission, chunked: adopt/allocate the prompt's blocks
        exactly like :meth:`_admit_paged`, but run no prefill yet — queue
        the prompt for chunk-at-a-time processing interleaved with decode
        steps (:meth:`_run_prefill_chunks`).  The lane stays dark (table row
        trash, offsets zero) until the final chunk commits, so decode steps
        running between chunks neither read nor clobber the half-filled
        prompt; the lane's own interim decode writes land in the trash
        block and its outputs are discarded."""
        P, bs, C = req.prompt.size, self.block_size, self.prefill_chunk
        cached = self._gate_matches.pop(req.uid, [])
        if self.prefix_cache is not None and len(cached) < P // bs:
            # same-round re-match as _admit_paged (extend-only, see there)
            fresh = self.prefix_cache.match(self._family(req), req.prompt)
            if len(fresh) > len(cached) and fresh[: len(cached)] == cached:
                for b in fresh[len(cached):]:
                    self.allocator.incref(b)
                self.prefix_cache.hits += len(fresh) - len(cached)
                self.prefix_cache.misses -= len(fresh) - len(cached)
                cached = fresh
        new_ids = self.allocator.alloc(self.allocator.blocks_for(P) - len(cached))
        blocks = cached + new_ids
        self._lane_blocks[req.lane] = blocks
        req.admit_seq = self._admit_seq
        self._admit_seq += 1

        Pb = len(padded)
        # chunk starts may overhang the bucket (the cache-hit skip is block-
        # aligned, not chunk-aligned); pad the token buffer and write table
        # by one chunk so overhang positions write trash like any padding
        tokens = np.zeros((Pb + C,), np.int32)
        tokens[:P] = req.prompt
        write_ids = np.zeros((-(-(Pb + C) // bs),), np.int32)
        write_ids[len(cached): len(blocks)] = new_ids
        # chunks attend through the lane's own blocks at the monolithic
        # bucket width — cached blocks included, which is what lets prefill
        # skip recomputing their K/V entirely
        read_ids = np.zeros((-(-Pb // bs),), np.int32)
        read_ids[: len(blocks)] = blocks
        table_row = np.zeros((self.max_blocks,), np.int32)
        table_row[: len(blocks)] = blocks
        skip = len(cached) * bs
        if skip >= P:
            # fully cached prompt: every K/V block is resident; one pass
            # over the last C positions just to surface the logits row
            starts = [max(P - C, 0)]
        else:
            starts = list(range(skip, P, C))
        req.prefill_pos = starts[0]
        self._prefilling[req.uid] = {
            "req": req, "seg": seg, "length": length, "t0": t0,
            "tokens": tokens,
            "write_ids": jnp.asarray(write_ids),
            "read_ids": jnp.asarray(read_ids),
            "table_row": jnp.asarray(table_row),
            "starts": starts, "next": 0, "cached": len(cached),
        }

    def _run_prefill_chunks(self, finished: List[Request]) -> None:
        """Advance in-flight chunked prefills, FIFO by admission order,
        spending at most ``prefill_chunk`` prompt tokens per engine step
        (and always at least one chunk, so prefill cannot starve) — the
        budget is what keeps resident lanes' time-between-tokens bounded
        while long prompts stream in."""
        tel = self.telemetry
        C = self.prefill_chunk
        budget = C
        for st in sorted(self._prefilling.values(),
                         key=lambda s: s["req"].admit_seq):
            while budget > 0 and st["next"] < len(st["starts"]):
                budget -= C
                req = st["req"]
                start = st["starts"][st["next"]]
                last = st["next"] + 1 == len(st["starts"])
                view = self._params_view()
                toks = jnp.asarray(st["tokens"][start: start + C])[None, :]
                t0 = tel.now() if tel.enabled else 0.0
                if last:
                    logits, self.cache = self._prefill_chunk_final(
                        view, self.cache, toks, st["seg"], st["length"],
                        np.int32(start), st["write_ids"], st["read_ids"],
                        req.lane, st["table_row"],
                    )
                    row = np.asarray(logits[0])  # host sync: chunk really ran
                else:
                    self.cache = self._prefill_chunk(
                        view, self.cache, toks, st["seg"], st["length"],
                        np.int32(start), st["write_ids"], st["read_ids"],
                    )
                st["next"] += 1
                req.prefill_pos = -1 if last else st["starts"][st["next"]]
                if tel.enabled:
                    # non-final chunk spans measure dispatch cost only: a
                    # forced per-chunk device sync would serialize the very
                    # prefill/decode overlap chunking exists to create (and
                    # showed up as the chunked-on > chunked-off regression
                    # in BENCH_smoke).  The final chunk's logits sync fences
                    # the whole chunk sequence, so total cost stays honest.
                    tel.on_prefill_chunk(req, t0, tel.now(), start, C)
                if last:
                    self._finish_chunked(st, req, row, finished)

    def _finish_chunked(self, st, req: Request, row, finished: List[Request]):
        """Final chunk committed: file the prompt in the prefix cache (the
        blocks only now hold its K/V — monolithic prefill inserts at
        admission, chunked at completion) and emit the first token."""
        del self._prefilling[req.uid]
        tel = self.telemetry
        if self.prefix_cache is not None:
            P, bs = req.prompt.size, self.block_size
            self.prefix_cache.insert(
                self._family(req), req.prompt, self._lane_blocks[req.lane]
            )
            tel.prefix_hits.inc(st["cached"])
            tel.prefix_misses.inc(P // bs - st["cached"])
        if tel.enabled:
            tel.on_prefill(req, st["t0"], tel.now())
        self._emit(req, row, finished)

    def _emit(self, req: Request, logits_row: np.ndarray, finished: List[Request]):
        tok = int(logits_row.argmax())
        req.tokens.append(tok)
        if self.collect_logits:
            req.logits.append(logits_row)
        self.decoded_tokens += 1
        # stream delivery is exactly-once: a block-pressure-preempted request
        # re-derives its cleared tokens bit-identically (greedy decode is
        # deterministic), so indexes already delivered are not re-emitted
        if len(req.tokens) > req.delivered:
            req.delivered = len(req.tokens)
            self.telemetry.on_token(req)
            self.events.append(
                TokenEvent(
                    uid=req.uid, tenant=req.tenant, lane=req.lane, token=tok,
                    index=len(req.tokens) - 1, done=req.done,
                )
            )
        if req.done:
            self.telemetry.on_retire(req)
            lane = req.lane
            self.scheduler.finish(req)
            self.lam_store.unpin(req.tenant)
            if self._cold_tier:
                self.lam_store.unprotect(req.tenant)
            if self.paged:
                for b in self._lane_blocks.pop(lane):
                    self.allocator.decref(b)  # shared blocks survive in-cache
                # reset repoints the lane's table row at the trash block so
                # the freed blocks can be reallocated without the idle lane
                # scribbling into them; dense lanes skip it — admission fully
                # overwrites every per-lane leaf, so a reset would only copy
                # the whole cache per retirement for nothing
                self.cache = self._reset(self.cache, lane)
            finished.append(req)

    def step_begin(self) -> Dict[str, Any]:
        """First half of :meth:`step`: quantum time-slicing, admission,
        chunked-prefill advance, lane growth, and the decode *dispatch* —
        everything up to (but not including) the host sync on the decode
        logits.  Returns a pending handle for :meth:`step_finish`.

        The split exists for multi-replica drivers (serving/router.py):
        dispatching every replica's decode before syncing any lets the
        replicas' device work overlap instead of serializing on each host
        round-trip.  Speculative steps host-sync internally (draft feeds
        verify), so they complete inside ``step_begin`` and return an
        already-finished handle."""
        finished: List[Request] = []
        self.events = []
        tel = self.telemetry
        on = tel.enabled
        t = tel.now() if on else 0.0
        if self.quantum is not None and self.scheduler.queue:
            # preempt only as many over-quantum lanes as waiters that free
            # lanes can't already absorb (counted before preemption re-queues
            # victims), most-overdue first — otherwise every expiry would
            # churn lanes through extract/restore that admission could have
            # filled for free
            need = len(self.scheduler.queue) - len(self.scheduler.free_lanes())
            if need > 0:
                over = [r for r in self.scheduler.active() if r.slice_steps >= self.quantum]
                over.sort(key=lambda r: (-r.slice_steps, r.lane))
                for req in over[:need]:
                    self._preempt_quantum(req)
            if on:
                now = tel.now()
                tel.phase("quantum", now - t)
                t = now
        self._admit(finished)
        if on:
            now = tel.now()
            tel.phase("admit", now - t)
            t = now
        if self._prefilling:
            self._run_prefill_chunks(finished)
            if on:
                now = tel.now()
                tel.phase("prefill_chunk", now - t)
                t = now
        if self.paged:
            self._grow_lanes(self.speculate_k + 1 if self.speculate_k else 1)
            if on:
                now = tel.now()
                tel.phase("grow", now - t)
                t = now
        active = self.scheduler.active()
        # mid-chunked-prefill lanes occupy a lane but have no token to
        # decode yet — they ride the shared step as masked rows (their
        # writes hit the trash block, their logits are discarded)
        decoding = (
            [r for r in active if r.uid not in self._prefilling]
            if self._prefilling
            else active
        )
        if not decoding:
            return {"finished": finished, "decoding": None}
        tok = np.zeros((self.n_lanes, 1), np.int32)
        for req in decoding:
            tok[req.lane, 0] = req.tokens[-1]
        ab = None
        if self.paged:
            # bound the fused attend to the decoding lanes' block high-water
            # mark, bucketed to powers of two so distinct active lengths
            # share decode compilations (≤ log2(max_blocks) variants).  The
            # mark uses each lane's *planned* final length (prompt +
            # generation budget, known at admission) rather than its current
            # length: the bucket is then fixed for the request's lifetime,
            # so lane growth never triggers a mid-request recompile — a few
            # masked attend columns (bit-identical, see _paged_decode) buy
            # a compile-free steady state.
            hw = max(
                -(-(r.prompt.size + r.max_new_tokens) // self.block_size)
                for r in decoding
            )
            ab = 1
            while ab < hw:
                ab *= 2
            ab = min(ab, self.max_blocks)
        if self.speculate_k:
            self._step_speculative(decoding, tok, ab, finished, t)
            return {"finished": finished, "decoding": None}
        seg = jnp.asarray(self.scheduler.batch_composition())
        view = self._params_view()
        t_disp = tel.now() if on else 0.0
        logits, self.cache = self._decode(view, self.cache, jnp.asarray(tok), seg, ab)
        if on:
            now = tel.now()
            tel.phase("dispatch", now - t_disp)
            t = now
        return {
            "finished": finished, "decoding": decoding, "logits": logits,
            "t_disp": t_disp, "t": t,
        }

    def step_finish(self, pending: Dict[str, Any]) -> List[Request]:
        """Second half of :meth:`step`: host-sync the dispatched decode
        logits and emit each decoding lane's token.  Idempotent-free — call
        exactly once per :meth:`step_begin`."""
        finished = pending["finished"]
        decoding = pending["decoding"]
        if decoding is None:
            return finished
        tel = self.telemetry
        on = tel.enabled
        logits_np = np.asarray(pending["logits"])  # host sync: decode ran
        t_sync = 0.0
        if on:
            t_sync = tel.now()
            tel.phase("sync", t_sync - pending["t"])
        self.steps += 1
        t_disp = pending["t_disp"]
        for req in decoding:
            req.slice_steps += 1
            self._emit(req, logits_np[req.lane], finished)
            if on:
                tel.on_decode_lane(req, t_disp, t_sync, req.tokens[-1])
        if on:
            tel.phase("emit", tel.now() - t_sync)
        return finished

    def step(self) -> List[Request]:
        """Time-slice over-quantum lanes (when work queues), admit waiting
        requests, advance chunked prefills under the token budget, grow/
        CoW-fork lanes crossing block boundaries, run one shared decode step
        over the committed lanes (a draft+verify pair when ``speculate_k``
        is set — up to k+1 tokens per lane per step); returns requests that
        finished this step.  Per-token events land in ``self.events``.
        Exactly ``step_finish(step_begin())`` — multi-replica drivers call
        the halves directly to pipeline dispatches across replicas."""
        return self.step_finish(self.step_begin())

    def run(self) -> Dict[int, Request]:
        """Drain the queue; returns uid → finished request."""
        out: Dict[int, Request] = {}
        while self.scheduler.has_work:
            for req in self.step():
                out[req.uid] = req
        return out

    def stream(self) -> Iterator[TokenEvent]:
        """Drain the queue, yielding every token as it decodes — the
        streaming-delivery counterpart of :meth:`run` (same schedule, same
        tokens; ``event.done`` marks a request's final token)."""
        while self.scheduler.has_work:
            self.step()
            yield from self.events

    def lower_decode(self):
        """Lower the plain decode step for the current lanes at the widest
        attend bucket — ``.compile().as_text()`` shows what the device runs
        (e.g. whether the adapter matmuls are the BGMV kernel)."""
        tok = jnp.zeros((self.n_lanes, 1), jnp.int32)
        seg = jnp.asarray(self.scheduler.batch_composition())
        ab = self.max_blocks if self.paged else None
        with self._rules_ctx():
            return self._decode_jit.lower(self._params_view(), self.cache, tok, seg, ab)

    # -- accounting ---------------------------------------------------------

    def kv_cache_bytes(self) -> int:
        """Device bytes held by the decode KV cache (pools/regions + block
        tables + offsets) — the paged-vs-dense benchmark datum."""
        return sum(
            leaf.nbytes for leaf in jax.tree_util.tree_leaves(self.cache)
        )

    def blocks_in_use(self) -> int:
        """Blocks currently out of the free list (lane-held + cache-held)."""
        return self.allocator.n_in_use

    def release_prefix_cache(self) -> int:
        """Drop every prefix-cache entry; returns blocks freed to the pool
        (entries still referenced by active lanes free nothing)."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.clear()

    @property
    def prefill_compilations(self) -> int:
        """Distinct padded prompt lengths prefilled so far — with bucketing
        this is the number of prefill compilations the engine caused."""
        return len(self.prefill_buckets)

    @property
    def deferred_promotions(self) -> int:
        """Admissions deferred on a cold tenant, counted once per deferral
        episode — back-compat alias of
        ``serve_deferrals_total{cause="cold_promote"}`` (reads 0 when
        telemetry is disabled; episode dedup itself always runs)."""
        return int(self._defer_cold.value)

    def metrics(self) -> Dict[str, Any]:
        """JSON-able snapshot of every serving metric (``repro.obs``):
        latency histograms (TTFT / TBT / E2E / queue-wait / step phases),
        request / preemption / deferral / prefix-cache counters, and the
        sampled occupancy callbacks (block pool, λ tiers, queue depth, jit
        compile counts).  ``{}`` when telemetry is disabled."""
        return self.telemetry.snapshot()


# ---------------------------------------------------------------------------
# Per-tenant merged-weight reference (correctness oracle for the engine)
# ---------------------------------------------------------------------------


def merge_tenant_params(params: Pytree, cfg: ModelConfig, lam_tree) -> Pytree:
    """Single-tenant params with λ folded into the weights and adapters
    stripped — the classic one-adapter deployment (launch/serve.py)."""
    scale = adapter_api.adapter_scale(cfg.adapter)
    groups = dict(params["groups"])
    adapters = groups.get("adapters", {})
    for mod, projs in adapters.items():
        mod_params = dict(groups[mod])
        for proj, leaf in projs.items():
            adp = {"B": leaf["B"], "A": leaf["A"], "lam": lam_tree[mod][proj]}
            mod_params[proj] = adapter_api.merge_adapter(
                mod_params[proj], adp, scale
            )
        groups[mod] = mod_params
    groups["adapters"] = {}
    return {**params, "groups": groups}


@functools.lru_cache(maxsize=None)
def _reference_steps(cfg: ModelConfig):
    """(model, jitted prefill, jitted decode step) of the merged-weight
    reference — one compilation per config, not one per call (an eager
    ``lax.scan`` recompiles its layer stack every time it is called)."""
    model = build_model(cfg)
    prefill = jax.jit(lambda p, c, t: model.prefill(p, c, tokens=t))
    decode = jax.jit(lambda p, c, t: model.decode_step(p, c, token=t))
    return model, prefill, decode


def reference_decode(
    cfg: ModelConfig, params: Pytree, lam_tree, prompt, n_tokens: int, max_len: int
):
    """Greedy decode of one prompt through merged weights (no adapters on
    the runtime path); returns (tokens list, logits (n_tokens, V))."""
    model, prefill, decode = _reference_steps(cfg)
    merged = merge_tenant_params(params, cfg, lam_tree)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    cache = model.init_decode_state(1, max_len, dtype)
    logits, cache = prefill(merged, cache, jnp.asarray(prompt)[None, :])
    toks, rows = [int(jnp.argmax(logits[0]))], [np.asarray(logits[0])]
    for _ in range(n_tokens - 1):
        logits, cache = decode(merged, cache, jnp.asarray([[toks[-1]]], jnp.int32))
        toks.append(int(jnp.argmax(logits[0])))
        rows.append(np.asarray(logits[0]))
    return toks, np.stack(rows)


def base_lambda(params: Pytree) -> Dict[str, Dict[str, jax.Array]]:
    """The base model's λ tree (all zeros) — tenant-shaped."""
    return jax.tree_util.tree_map(jnp.zeros_like, extract_lambda(params))
