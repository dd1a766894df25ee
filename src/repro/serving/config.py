"""Typed engine configuration: :class:`EngineConfig`.

The engine constructor grew one keyword per PR — ``paged``, ``share_prefix``,
``watermark``, ``quantum``, ``cold_slots``, ``shard_lam``, ``telemetry``, … —
until call sites read like a flag soup and invalid combinations (quantum on a
paged engine, share_prefix without blocks to share) could only fail deep
inside ``__init__``.  This module collapses the sprawl into one frozen
dataclass that validates on construction, so a config object is proof of a
coherent engine setup before any device memory is touched.

Layouts
=======

``layout`` replaces the old ``paged: bool`` and flips the default:

* ``"paged"``   — block-pool KV cache (the serving layout; the default
  resolution for every family with attention layers to page).
* ``"oracle_dense"`` — the dense per-lane ``(lanes, max_len)`` layout.  It
  survives as the *test oracle* the paged engine is validated against, and
  as the only layout for recurrent-only families (ssm) and time-sliced
  (``quantum``) serving, whose lane snapshots live in dense lane state.
* ``"auto"``    — resolve per model family at engine construction: paged for
  :data:`~repro.models.transformer.PAGED_FAMILIES` (unless ``quantum`` is
  set), oracle-dense otherwise.  This is the default.

Presets
=======

``EngineConfig.serving()`` — the production posture: paged layout, prefix
sharing, one watermark block of decode headroom, and chunked prefill at two
blocks per step.  ``EngineConfig.oracle_dense()`` — the reference posture the
tests compare against.  Both accept field overrides.

Legacy kwargs (``MultiTenantEngine(cfg, paged=True, ...)``) still construct —
:meth:`EngineConfig.from_legacy_kwargs` maps them onto a config (old default
``paged=False`` maps to the oracle layout) behind a once-per-process
``DeprecationWarning`` raised by the engine shim.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.configs.base import BASE_DTYPES
from repro.models.transformer import PAGED_FAMILIES

LAYOUTS = ("auto", "paged", "oracle_dense")

#: Families speculative decoding supports.  Accepting a drafted prefix is a
#: pure KV rewind — offsets advance, rejected positions stay masked until
#: overwritten — which only attention state allows.  Hybrid's Mamba scan
#: state advances irreversibly per token and ssm has no KV cache at all, so
#: neither can roll back a rejected draft.
SPECULATIVE_FAMILIES = ("dense", "audio", "moe")

#: Engine keywords accepted before EngineConfig existed, in their historical
#: order.  ``paged`` maps onto ``layout``; everything else is 1:1.
LEGACY_KWARGS = (
    "n_lanes", "n_slots", "max_len", "collect_logits", "seed", "paged",
    "block_size", "n_blocks", "share_prefix", "watermark", "quantum",
    "cold_slots", "shard_lam", "telemetry",
)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Validated multi-tenant engine configuration (see module docstring)."""

    layout: str = "auto"
    n_lanes: int = 4
    n_slots: int = 8
    max_len: int = 128
    collect_logits: bool = False
    seed: int = 0
    block_size: int = 16
    n_blocks: Optional[int] = None
    share_prefix: bool = False
    watermark: int = 0
    quantum: Optional[int] = None
    cold_slots: int = 0
    #: mmap-backed cold tier: spill λ rows to this file (catalog JSON rides
    #: alongside) instead of host arrays, so the spilled tenant catalog —
    #: rows, LRU order, and prefix-family digests — survives an engine
    #: restart.  Requires ``cold_slots > 0``.
    cold_path: Optional[str] = None
    shard_lam: bool = False
    telemetry: bool = True
    #: Chunked-prefill token budget per engine step (paged layouts only).
    #: Admission splits prompts longer than this into ``prefill_chunk``-token
    #: chunks interleaved with resident lanes' decode steps, bounding
    #: time-between-tokens under long-prompt admission.  ``None`` disables
    #: (monolithic admission prefill).  Must be a multiple of ``block_size``.
    prefill_chunk: Optional[int] = None
    #: Speculative decoding: draft this many tokens per lane per step with
    #: the slot-0 base drafter (λ ≡ 0 — shares every weight and KV block),
    #: verify all lanes' drafts in one batched forward, and accept the
    #: longest matching greedy prefix.  ``0`` disables.  Token-identical to
    #: plain greedy decode by construction; requires a family in
    #: :data:`SPECULATIVE_FAMILIES` (checked at engine construction).
    speculate_k: int = 0
    #: Drafter variant: keep only the top-r |λ| coefficients per tenant slot
    #: instead of dropping the adapter entirely — a principled smaller model
    #: under the paper's QR-basis structure, trading drafter cost for
    #: acceptance rate on strongly-adapted tenants.  ``None`` = λ ≡ 0 base
    #: drafter.  Needs ``speculate_k >= 1``.
    draft_lam_rank: Optional[int] = None
    #: Frozen-base weight dtype: "bf16" leaves the model's native weights
    #: alone; "int8"/"fp8" quantize every adapted base projection
    #: per-output-channel at engine construction (``core/quantize.py``) and
    #: dequantize in the kernel epilogue — λ, B, A stay full precision.
    base_dtype: str = "bf16"
    #: Shard the shared QR factors B/A over their rank dim along the mesh
    #: model axis (the ``qr_rank`` logical axis) — divides their at-rest
    #: HBM by the axis size for >1-host bases; decode stays bit-identical
    #: to replicated (exact all_gather reassembly, see
    #: ``kernels/qrlora_bgmv.ba_gather_sharded``).
    shard_ba: bool = False

    def __post_init__(self):
        if self.layout not in LAYOUTS:
            raise ValueError(
                f"layout={self.layout!r} must be one of {LAYOUTS}"
            )
        for name in ("n_lanes", "n_slots", "max_len", "block_size"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name}={getattr(self, name)} must be >= 1")
        if self.watermark < 0:
            raise ValueError(f"watermark={self.watermark} must be >= 0")
        if self.cold_slots < 0:
            raise ValueError(f"cold_slots={self.cold_slots} must be >= 0")
        if self.cold_path is not None and self.cold_slots <= 0:
            raise ValueError("cold_path requires cold_slots > 0 (a tier to back)")
        if self.quantum is not None:
            if self.quantum < 1:
                raise ValueError(f"quantum={self.quantum} must be >= 1 decode step")
            if self.layout == "paged":
                raise ValueError(
                    "quantum time-slicing snapshots lane state, which a "
                    "paged lane spreads over pool blocks — use the dense "
                    "layout (layout='oracle_dense') for time-sliced serving"
                )
        if self.prefill_chunk is not None:
            if self.layout == "oracle_dense":
                raise ValueError(
                    "prefill_chunk requires a paged layout (chunks scatter "
                    "into pool blocks)"
                )
            if self.prefill_chunk < self.block_size or (
                self.prefill_chunk % self.block_size
            ):
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} must be a positive "
                    f"multiple of block_size={self.block_size}"
                )
        if self.speculate_k < 0:
            raise ValueError(f"speculate_k={self.speculate_k} must be >= 0")
        if self.speculate_k and self.prefill_chunk is not None:
            raise ValueError(
                "speculate_k is incompatible with prefill_chunk: a lane mid "
                "chunked-prefill is dark (its interim decode writes land in "
                "the trash block) and cannot draft or verify a window — run "
                "monolithic admission prefill with speculation"
            )
        if self.draft_lam_rank is not None:
            if self.draft_lam_rank < 1:
                raise ValueError(
                    f"draft_lam_rank={self.draft_lam_rank} must be >= 1"
                )
            if self.speculate_k < 1:
                raise ValueError(
                    "draft_lam_rank configures the speculative drafter — it "
                    "needs speculate_k >= 1"
                )
        if self.base_dtype not in BASE_DTYPES:
            raise ValueError(
                f"base_dtype={self.base_dtype!r} must be one of {BASE_DTYPES}"
            )
        if self.layout == "oracle_dense":
            if self.share_prefix:
                raise ValueError(
                    "share_prefix requires a paged layout (blocks to share)"
                )
            if self.watermark:
                raise ValueError(
                    "watermark requires a paged layout (blocks to reserve)"
                )

    # -- resolution ---------------------------------------------------------

    def resolved_layout(self, family: str) -> str:
        """Concrete layout for ``family``; raises when an explicit
        ``layout="paged"`` names a family with nothing to page."""
        if self.layout == "oracle_dense":
            return "oracle_dense"
        if self.layout == "paged":
            if family not in PAGED_FAMILIES:
                raise ValueError(
                    f"layout='paged' needs attention layers to page; family "
                    f"{family!r} has none — its per-lane state is already "
                    "O(1), run layout='oracle_dense'"
                )
            return "paged"
        if self.quantum is not None or family not in PAGED_FAMILIES:
            return "oracle_dense"
        return "paged"

    def validate_speculation(self, family: str) -> None:
        """Reject ``speculate_k`` for families whose decode state cannot
        rewind a rejected draft (engine construction calls this once the
        model family is known — the config itself is family-agnostic)."""
        if self.speculate_k and family not in SPECULATIVE_FAMILIES:
            raise ValueError(
                f"speculate_k={self.speculate_k} needs a KV-rollback family "
                f"{SPECULATIVE_FAMILIES}; family {family!r} carries "
                "recurrent decode state that cannot rewind rejected draft "
                "positions"
            )

    # -- presets ------------------------------------------------------------

    @classmethod
    def serving(cls, **overrides) -> "EngineConfig":
        """Production posture: paged KV, CoW prefix sharing, one watermark
        block of decode-growth headroom, chunked prefill at two blocks of
        tokens per step."""
        bs = overrides.get("block_size", 16)
        base = dict(
            layout="paged", share_prefix=True, watermark=1,
            prefill_chunk=2 * bs,
        )
        base.update(overrides)
        return cls(**base)

    @classmethod
    def oracle_dense(cls, **overrides) -> "EngineConfig":
        """The dense reference layout the paged engine is validated
        against (and the layout for ssm / time-sliced serving)."""
        base = dict(layout="oracle_dense")
        base.update(overrides)
        return cls(**base)

    # -- legacy bridge ------------------------------------------------------

    @classmethod
    def from_legacy_kwargs(cls, **kwargs) -> "EngineConfig":
        """Map the pre-EngineConfig keyword soup onto a config.  The old
        default ``paged=False`` maps to the oracle layout — legacy call
        sites keep their exact engine."""
        unknown = sorted(set(kwargs) - set(LEGACY_KWARGS))
        if unknown:
            raise TypeError(f"unknown engine kwargs: {unknown}")
        paged = kwargs.pop("paged", False)
        return cls(layout="paged" if paged else "oracle_dense", **kwargs)
