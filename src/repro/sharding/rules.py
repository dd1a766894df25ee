"""Logical-axis sharding rules (flax-partitioning style, dependency-free).

Model code annotates activations with *logical* axis names::

    x = shard(x, "batch", "seq", None)

and a launcher-installed rule table maps logical names → mesh axes.  With no
mesh installed (unit tests, single-device runs) ``shard`` is the identity, so
model code never branches on distribution.

Baseline rule table (see DESIGN.md §4):

    batch   → ("pod", "data")   # DP across pods and the data axis
    heads   → "model"           # TP: attention heads / flattened head dim
    ff      → "model"           # TP: FFN hidden
    vocab   → "model"           # TP: embedding / logits vocab shard
    kv_seq  → None              # hillclimb: long-context KV sharding
    expert_ff → "model"         # MoE: TP inside each expert
    fsdp    → "data"            # param/optimizer sharding for big archs
    lam_slots → None            # serving: packed λ-table slot axis (the
                                # multi-tenant engine maps it to "model"
                                # under shard_lam=True; see serving/lam_store)
    qr_rank   → None            # serving: rank dim of the shared QR factors
                                # B (..., K, r) / A (..., r, N) — the engine
                                # maps it to "model" under shard_ba=True and
                                # reassembles with an exact all_gather
                                # (kernels/qrlora_bgmv.ba_gather_sharded)
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()


def _rules() -> Dict[str, Any]:
    return getattr(_state, "rules", {})


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def default_rules(mesh: Mesh, *, fsdp: bool = False, dp_only: bool = False, replicate_batch: bool = False) -> Dict[str, Any]:
    axes = mesh.axis_names
    dp: Tuple[str, ...] = tuple(a for a in ("pod", "data") if a in axes)
    model = "model" if "model" in axes else None
    if dp_only:
        # QR-LoRA PEFT lever: everything data-parallel, weights replicated —
        # the frozen base has no gradients to all-reduce, so DP costs only
        # the λ psum (bytes, not gigabytes).
        all_dp = tuple(a for a in axes)
        return {
            "batch": all_dp,
            "heads": None,
            "ff": None,
            "vocab": None,
            "expert_ff": None,
            "kv_seq": None,
            "fsdp": None,
            "lam_slots": None,
            "qr_rank": None,
            "dp_axes": all_dp,
            "model_axis": None,
        }
    return {
        "batch": None if replicate_batch else (dp if dp else None),
        "heads": model,
        "ff": model,
        "vocab": model,
        "expert_ff": model,
        "kv_seq": None,
        "fsdp": (dp if fsdp else None),
        "lam_slots": None,  # λ-table sharding is a serving-side opt-in
        "qr_rank": None,  # B/A rank-dim sharding is a serving-side opt-in
        "dp_axes": dp,  # consumed by shard_map blocks (MoE)
        "model_axis": model,
    }


def set_mesh(mesh: Optional[Mesh], rules: Optional[Dict[str, Any]] = None, **kw):
    _state.mesh = mesh
    _state.rules = (
        {} if mesh is None else (rules if rules is not None else default_rules(mesh, **kw))
    )


@contextmanager
def axis_rules(mesh: Optional[Mesh], rules: Optional[Dict[str, Any]] = None, **kw):
    prev_mesh, prev_rules = get_mesh(), _rules()
    set_mesh(mesh, rules, **kw)
    try:
        yield
    finally:
        _state.mesh = prev_mesh
        _state.rules = prev_rules


def logical_spec(*names) -> P:
    rules = _rules()
    out = []
    for n in names:
        if n is None:
            out.append(None)
        else:
            out.append(rules.get(n, None))
    return P(*out)


def lam_slot_axis() -> Optional[Any]:
    """Mesh axis the packed λ-table *slot* dim is sharded over (the
    ``lam_slots`` logical axis), or None when λ tables are replicated.
    ``adapted_matmul``'s multi-tenant seg path consults this to route the
    λ-row gather through local shards (``kernels.qrlora_bgmv``)."""
    if get_mesh() is None:
        return None
    return _rules().get("lam_slots")


def qr_rank_axis() -> Optional[Any]:
    """Mesh axis the shared QR factors' *rank* dim is sharded over (the
    ``qr_rank`` logical axis), or None when B/A are replicated.
    ``adapted_matmul`` consults this to reassemble the factors with an
    exact all_gather before the contraction
    (``kernels.qrlora_bgmv.ba_gather_sharded``)."""
    if get_mesh() is None:
        return None
    return _rules().get("qr_rank")


def shard(x: jax.Array, *names) -> jax.Array:
    """Attach a sharding constraint by logical axis names (no-op w/o mesh)."""
    mesh = get_mesh()
    if mesh is None:
        return x
    spec = logical_spec(*names)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules (in_shardings for jit / dry-run)
# ---------------------------------------------------------------------------

# Path-suffix → logical axes for each weight kind. Leading "layers"/"groups"
# stacked dim is handled generically (None, or "fsdp" when enabled).
_PARAM_LOGICAL: Dict[str, Tuple] = {
    # token / position embeddings
    "embed": ("vocab", None),
    "pos_embed": (None, None),
    "unembed": ("fsdp", "vocab"),
    # attention (column-parallel qkv, row-parallel o)
    "wq": ("fsdp", "heads"),
    "wk": ("fsdp", "heads"),
    "wv": ("fsdp", "heads"),
    "wo": ("heads", "fsdp"),
    "bq": ("heads",),
    "bk": ("heads",),
    "bv": ("heads",),
    # mlp (column-parallel gate/up, row-parallel down)
    "w_gate": ("fsdp", "ff"),
    "w_up": ("fsdp", "ff"),
    "w_down": ("ff", "fsdp"),
    # MoE experts: (E, d, f) / (E, f, d); router replicated
    "we_gate": (None, "fsdp", "expert_ff"),
    "we_up": (None, "fsdp", "expert_ff"),
    "we_down": (None, "expert_ff", "fsdp"),
    "w_router": (None, None),
    # mamba
    "m_in": ("fsdp", "ff"),
    "m_gate": ("fsdp", "ff"),
    "m_conv": ("ff", None),
    "m_xproj": ("ff", None),
    "m_dt_w": (None, "ff"),
    "m_dt_b": ("ff",),
    "m_A_log": ("ff", None),
    "m_D": ("ff",),
    "m_out": ("ff", "fsdp"),
    # xlstm
    "x_qkv": ("fsdp", "heads"),
    "x_gates": ("fsdp", "heads"),
    "x_rec": (None, "heads", None),
    "x_up": ("fsdp", "ff"),
    "x_down": ("ff", "fsdp"),
    # vlm
    "img_proj": (None, None),
    "xa_gate": (),
    # norms / scalars / head
    "scale": (None,),
    "bias": (None,),
    "cls_w": (None, None),
    "cls_b": (None,),
}

_ADAPTER_LEAVES = ("A", "B", "lam", "ranks")


def _spec_for_path(path: Sequence[str], shape: Tuple[int, ...]) -> P:
    rules = _rules()
    name = path[-1]
    if "adapters" in path:
        # adapter factors replicate by default (small — DESIGN.md §4), but
        # the serving engine can opt B/A onto their rank dim ("qr_rank",
        # shard_ba): B (..., K, r) shards dim -1, A (..., r, N) dim -2.
        ax = rules.get("qr_rank") if name in ("A", "B") else None
        mesh = get_mesh()
        if ax is not None and mesh is not None and len(shape) >= 2:
            rank_dim = len(shape) - 1 if name == "B" else len(shape) - 2
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= mesh.shape[a]
            if shape[rank_dim] % size == 0:
                spec = [None] * len(shape)
                spec[rank_dim] = ax
                return P(*spec)
        return P(*([None] * len(shape)))
    logical = _PARAM_LOGICAL.get(name)
    if logical is None:
        return P(*([None] * len(shape)))
    mapped = [rules.get(ax, None) if ax else None for ax in logical]
    # account for leading stacked-layer dims ((G, ...) or (G, k, ...))
    extra = len(shape) - len(logical)
    mapped = [None] * extra + mapped
    # drop mappings that do not divide the dim (GSPMD pads, but uneven shards
    # on the *contracting* dim of a matmul hurt; prefer replication there)
    out = []
    mesh = get_mesh()
    for dim, ax in zip(shape, mapped):
        if ax is None or mesh is None:
            out.append(ax)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= mesh.shape[a]
        out.append(ax if dim % size == 0 else None)
    return P(*out)


def param_sharding_rules(params_shapes: Any) -> Any:
    """Map a pytree of ShapeDtypeStructs/arrays → pytree of NamedShardings."""
    mesh = get_mesh()
    assert mesh is not None, "param_sharding_rules requires an active mesh"

    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    out = []
    for path, leaf in flat:
        keys = tuple(
            str(getattr(p, "key", getattr(p, "idx", ""))) for p in path
        )
        spec = _spec_for_path(keys, leaf.shape)
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def replica_device_groups(n: int) -> "list":
    """Partition the local devices into ``n`` per-replica groups — the
    placement seam for the multi-replica serving tier (serving/replica.py).

    Replicas are data-parallel copies of the whole engine, so they split
    the device pool along what would be the mesh *data* axis: with ``d``
    devices, replica ``i`` owns devices ``i*d//n : (i+1)*d//n``.  Fewer
    devices than replicas is a CPU-only case (single-device tests): every
    group is then the full device list, the replicas time-share it, and
    the scaling win comes from cache locality rather than parallel
    compute.  On an accelerator it is an error — replicas stacked on one
    chip would hide that the host lacks the chips.
    """
    if n < 1:
        raise ValueError(f"n={n} must be >= 1")
    devices = jax.devices()
    if len(devices) < n:
        if devices[0].platform != "cpu":
            raise ValueError(
                f"{n} replicas need {n} {devices[0].platform} devices; "
                f"this host has {len(devices)}"
            )
        return [list(devices) for _ in range(n)]
    return [
        list(devices[i * len(devices) // n: (i + 1) * len(devices) // n])
        for i in range(n)
    ]
