"""Public model API: ``build_model(cfg)`` → :class:`Model`.

A :class:`Model` bundles init / apply / prefill / decode plus adapter
attachment (QR-LoRA & baselines) behind one interface used by the trainer,
the server, the dry-run, and the benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import adapter_api
from repro.models import encoder as enc_lib
from repro.models import transformer as tfm_lib

Pytree = Any

# Projections adaptable per family: module key in groups → weight names.
_ADAPTER_MODULES = {
    "dense": {"attn": ("wq", "wk", "wv", "wo"), "mlp": ("w_gate", "w_up", "w_down")},
    "audio": {"attn": ("wq", "wk", "wv", "wo"), "mlp": ("w_gate", "w_up", "w_down")},
    "moe": {"attn": ("wq", "wk", "wv", "wo")},
    "hybrid": {"attn": ("wq", "wk", "wv", "wo"), "mamba": ("m_in", "m_out")},
    "ssm": {"mlstm": ("x_qkv", "x_up", "x_down"), "slstm": ("x_qkv", "x_up", "x_down")},
    "vlm": {"attn": ("wq", "wk", "wv", "wo"), "xattn": ("wq", "wk", "wv", "wo")},
    "encoder": {"attn": ("wq", "wk", "wv", "wo")},
}

# adapter-config target name → (module, weight) aliases
_TARGET_ALIAS = {
    "mamba_in": ("mamba", "m_in"),
    "mamba_out": ("mamba", "m_out"),
}


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ---- init ------------------------------------------------------------
    def init(self, key, with_adapters: bool = True) -> Pytree:
        if self.cfg.is_encoder:
            params = enc_lib.init_encoder_params(key, self.cfg)
        else:
            params = tfm_lib.init_decoder_params(key, self.cfg)
        if with_adapters and self.cfg.adapter.mode not in ("none", "ft"):
            params = self.attach_adapters(key, params)
        return params

    def _adapter_targets(self) -> Dict[str, Tuple[str, ...]]:
        """module → tuple of weight names selected by cfg.adapter.targets."""
        modules = _ADAPTER_MODULES.get(
            "encoder" if self.cfg.is_encoder else self.cfg.family, {}
        )
        sel: Dict[str, list] = {}
        for t in self.cfg.adapter.targets:
            if t in _TARGET_ALIAS:
                mod, w = _TARGET_ALIAS[t]
                if mod in modules:
                    sel.setdefault(mod, []).append(w)
                continue
            for mod, weights in modules.items():
                if t in weights:
                    sel.setdefault(mod, []).append(t)
        return {m: tuple(ws) for m, ws in sel.items()}

    def attach_adapters(self, key, params: Pytree) -> Pytree:
        """Compute pivoted-QR (or LoRA/SVD) factors from the current weights
        and install them under ``groups["adapters"]``."""
        cfg = self.cfg
        groups = dict(params["groups"])
        adapters: Dict[str, Dict] = {}
        for mod, weights in self._adapter_targets().items():
            if mod not in groups:
                continue
            mod_params = dict(groups[mod])
            stacked, lead_shapes = {}, {}
            for w in weights:
                W = mod_params[w]
                lead = W.shape[:-2]
                stacked[w] = W.reshape(-1, *W.shape[-2:])
                lead_shapes[w] = lead
            sub, new_w = adapter_api.init_adapters(
                jax.random.fold_in(key, hash(mod) % (2**31)), cfg, stacked
            )
            for w in weights:
                if new_w[w] is not stacked[w]:  # svd subtract-init path
                    mod_params[w] = new_w[w].reshape(*lead_shapes[w], *new_w[w].shape[-2:])
                if w in sub:
                    adapters.setdefault(mod, {})[w] = jax.tree_util.tree_map(
                        lambda t, lead=lead_shapes[w]: t.reshape(*lead, *t.shape[1:]),
                        sub[w],
                    )
            groups[mod] = mod_params
        groups["adapters"] = adapters
        return {**params, "groups": groups}

    def dryrun_params(self, dtype=jnp.bfloat16) -> Pytree:
        """ShapeDtypeStruct pytree — exact shapes, no allocation."""
        shapes = jax.eval_shape(lambda k: self.init(k, with_adapters=False), jax.random.PRNGKey(0))
        cfg = self.cfg
        if cfg.adapter.mode in ("none", "ft"):
            return shapes
        groups = dict(shapes["groups"])
        adapters = {}
        for mod, weights in self._adapter_targets().items():
            if mod not in groups:
                continue
            stacked_shapes = {}
            lead = {}
            for w in weights:
                s = groups[mod][w].shape
                lead[w] = s[:-2]
                n = 1
                for x in s[:-2]:
                    n *= x
                stacked_shapes[w] = (n, s[-2], s[-1])
            sub = adapter_api.dryrun_adapters(cfg, stacked_shapes)
            for w, adp in sub.items():
                adapters.setdefault(mod, {})[w] = {
                    k: jax.ShapeDtypeStruct((*lead[w], *v.shape[1:]), v.dtype)
                    for k, v in adp.items()
                }
        groups["adapters"] = adapters
        return {**shapes, "groups": groups}

    # ---- forward ---------------------------------------------------------
    # ``seg_ids`` (int32 (batch,)) selects a per-sequence adapter slot when
    # the params carry a packed multi-tenant λ table (see repro.serving).
    def apply(self, params, tokens=None, embeds=None, image_embeds=None, train=True,
              seg_ids=None):
        if self.cfg.is_encoder:
            return enc_lib.encoder_apply(params, self.cfg, tokens), jnp.zeros((), jnp.float32)
        return tfm_lib.decoder_apply(
            params, self.cfg, tokens=tokens, embeds=embeds,
            image_embeds=image_embeds, train=train, seg_ids=seg_ids,
        )

    def init_decode_state(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                          per_lane: bool = False, paged: bool = False,
                          block_size: int = 16, n_blocks: Optional[int] = None):
        return tfm_lib.init_decode_state(
            self.cfg, batch, max_len, dtype, per_lane=per_lane, paged=paged,
            block_size=block_size, n_blocks=n_blocks,
        )

    def lane_axes(self, paged: bool = False):
        """LaneState protocol: the lane-axis tree of ``init_decode_state``'s
        per-lane cache (see ``repro.models.lane_state``)."""
        return tfm_lib.decode_state_lane_axes(self.cfg, paged=paged)

    def paged_prefill_view(self, cache, write_ids, read_ids=None):
        return tfm_lib.paged_prefill_view(self.cfg, cache, write_ids, read_ids)

    def commit_paged_prefill(self, cache, filled, lane, table_row, length):
        return tfm_lib.commit_paged_prefill(
            self.cfg, cache, filled, lane, table_row, length
        )

    def paged_decode_view(self, cache, rows):
        return tfm_lib.paged_decode_view(self.cfg, cache, rows)

    def commit_paged_decode(self, cache, decoded, rows):
        return tfm_lib.commit_paged_decode(self.cfg, cache, decoded, rows)

    def prefill(self, params, cache, tokens=None, embeds=None, image_embeds=None,
                seg_ids=None, length=None, start=None):
        return tfm_lib.decoder_prefill(
            params, self.cfg, cache, tokens=tokens, embeds=embeds,
            image_embeds=image_embeds, seg_ids=seg_ids, length=length, start=start,
        )

    def decode_step(self, params, cache, token=None, embeds=None, image_embeds=None,
                    seg_ids=None, attend_blocks=None):
        return tfm_lib.decoder_decode(
            params, self.cfg, cache, token=token, embeds=embeds,
            image_embeds=image_embeds, seg_ids=seg_ids, attend_blocks=attend_blocks,
        )

    def verify_step(self, params, cache, tokens=None, seg_ids=None, n_valid=None,
                    attend_blocks=None):
        """Speculative verify: ``tokens`` (B, W) windows at each lane's own
        positions → (logits (B, W, V), cache with offsets UNCHANGED).
        Attention-only families (see ``transformer.decoder_verify``)."""
        return tfm_lib.decoder_verify(
            params, self.cfg, cache, tokens=tokens, seg_ids=seg_ids,
            n_valid=n_valid, attend_blocks=attend_blocks,
        )

    # ---- PEFT helpers ------------------------------------------------------
    def trainable_mask(self, params, extra_trainable=()):
        extra = tuple(extra_trainable)
        if self.cfg.is_encoder and self.cfg.adapter.mode != "ft":
            extra = extra + ("cls_w", "cls_b", "pooler")  # paper trains the task head
        return adapter_api.trainable_mask(params, self.cfg, extra)

    def count_trainable(self, params, include_head: bool = False):
        extra = ("cls_w", "cls_b", "pooler") if include_head else ()
        return adapter_api.count_trainable_params(params, self.cfg, extra)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
