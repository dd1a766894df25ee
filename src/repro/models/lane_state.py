"""LaneState protocol: per-lane decode-state management for every family.

The continuous-batching engine (``repro.serving``) runs one decode step over
a fixed set of *lanes* whose occupants come and go independently.  Each
model family keeps different per-lane state — the attention KV cache (dense
region or paged block table), the Mamba ``{conv, h}`` selective-SSM state,
the mLSTM ``{conv, C, n, m}`` matrix memory, the sLSTM ``{c, n, h, m}``
scalar memory — and a composite (jamba-style hybrid) cache nests several of
them per layer group.  The engine must not care: admission, retirement, and
preemption all reduce to four operations on a *pytree of lanes* (and a
decode step over a few of the lanes to ``gather_lanes`` / ``scatter_lanes``):

* ``init``         — build an ``n_lanes``-wide state
  (``transformer.init_decode_state(..., per_lane=True)``).
* ``reset_lane``   — return one lane to its freshly-initialized value
  without touching neighbors (retirement / paged release).
* ``extract_lane`` — snapshot one lane's slice (preemption: recurrent
  state is O(1) per lane, so a snapshot is cheap and exact).
* ``restore_lane`` — write a 1-lane tree (an admission prefill, or an
  ``extract_lane`` snapshot) into lane ``i`` of the batch state.

The glue that makes this generic is the **lane-axes tree**: a pytree with
the *same structure* as the state whose leaves name the axis carrying the
lane dimension (``NO_LANE`` for global leaves such as the paged KV block
pools, which are indexed through per-lane block tables instead of sliced).
Each state implementation declares its axes next to its ``init_*_state``
(``attention.kv_lane_axes`` / ``attention.paged_kv_lane_axes``,
``mamba.state_lane_axes``, ``xlstm.mlstm_state_lane_axes`` /
``xlstm.slstm_state_lane_axes``);
``transformer.decode_state_lane_axes(cfg, paged=...)`` composes them into
the composite cache's tree exactly as ``init_decode_state`` composes the
states.  The four operations below are then plain ``tree_map``\\ s — no
per-family branching anywhere in the serving layer.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Pytree = Any

#: Lane-axes leaf marking a *global* (not per-lane) state leaf — e.g. the
#: paged KV block pools, shared by all lanes and addressed via block tables.
#: Such leaves are skipped by extract/restore/reset (snapshots carry a
#: zero-size placeholder so tree structures still line up).
NO_LANE = -1


def extract_lane(state: Pytree, axes: Pytree, lane) -> Pytree:
    """Snapshot lane ``lane``: every per-lane leaf sliced to size 1 along
    its lane axis (``NO_LANE`` leaves become 0-size placeholders).  The
    result is exactly what ``restore_lane`` accepts — and what an admission
    prefill produces when run with ``n_lanes=1``."""

    def ex(t, ax):
        if ax == NO_LANE:
            return jnp.zeros((0,), t.dtype)
        return jax.lax.dynamic_slice_in_dim(t, lane, 1, axis=ax)

    return jax.tree_util.tree_map(ex, state, axes)


def restore_lane(state: Pytree, axes: Pytree, lane, snapshot: Pytree) -> Pytree:
    """Write a 1-lane ``snapshot`` into lane ``lane`` of ``state`` without
    touching any other lane; ``NO_LANE`` leaves pass through unchanged."""

    def re(t, ax, s):
        if ax == NO_LANE:
            return t
        return jax.lax.dynamic_update_slice_in_dim(t, s.astype(t.dtype), lane, axis=ax)

    return jax.tree_util.tree_map(re, state, axes, snapshot)


def gather_lanes(state: Pytree, axes: Pytree, rows) -> Pytree:
    """The lanes ``rows`` (int32 (R,)) as an R-lane state, in that order:
    every per-lane leaf gathered along its lane axis, a row out of range
    filled with zeros (for the paged cache: trash block 0 at offset 0).
    ``NO_LANE`` leaves pass through whole.  ``scatter_lanes`` writes the
    R-lane result back."""

    def ga(t, ax):
        if ax == NO_LANE:
            return t
        return jnp.take(t, rows, axis=ax, mode="fill", fill_value=0)

    return jax.tree_util.tree_map(ga, state, axes)


def scatter_lanes(state: Pytree, axes: Pytree, rows, sub: Pytree) -> Pytree:
    """Write the R-lane ``sub`` (a ``gather_lanes(state, axes, rows)`` after
    some step) back into lanes ``rows`` of ``state``; rows out of range are
    dropped, every other lane is untouched, and ``NO_LANE`` leaves are
    taken from ``sub``."""

    def sc(t, ax, s):
        if ax == NO_LANE:
            return s
        at = (slice(None),) * ax + (rows,)
        return t.at[at].set(s.astype(t.dtype), mode="drop")

    return jax.tree_util.tree_map(sc, state, axes, sub)


def reset_lane(state: Pytree, axes: Pytree, lane, init_snapshot: Pytree) -> Pytree:
    """Return lane ``lane`` to its initial value.  ``init_snapshot`` is the
    lane-0 extract of a freshly initialized 1-lane state (NOT zeros: the
    xLSTM stabilizer ``m`` initializes to -1e30)."""
    return restore_lane(state, axes, lane, init_snapshot)
