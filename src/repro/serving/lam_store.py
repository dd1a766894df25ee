"""Sharded hierarchical λ-store for multi-tenant QR-LoRA serving.

Every QR-LoRA adapter of a layer shares the frozen pivoted-QR factors
(B, A) computed from the *base* weights, so a tenant is fully described by
its λ coefficient tree: ``{module: {proj: λ (n_stack, rank_cap)}}`` — the
exact payload of a QR-LoRA checkpoint.  The store pins those trees into a
two-tier hierarchy:

**Hot tier** — packed per-projection device tables in the *install layout*

    Λ[proj] : (*stack_lead, n_slots, rank_cap)  fp32

indexed by *slot id* on the second-to-last axis.  Slot 0 is reserved for
the base model (λ ≡ 0) and is never evicted; the remaining slots are
managed LRU with pin counts so slots referenced by in-flight requests are
not recycled under them.  Because the slot axis already sits where
``install()`` needs it, a register/hot-swap/evict is **one jitted, donated
``dynamic_update_slice`` call** writing one λ row across all tables — no
per-key Python loop, no table re-pack, no O(table) transpose.

**Cold tier** — host-side λ rows (numpy, one dict per tenant) holding up to
``cold_slots`` evicted tenants.  Hot eviction under pressure *spills* the
LRU tenant's rows to the host instead of dropping them; admission promotes
them back into a hot slot on demand.  Tenant capacity is therefore bounded
by host RAM (``bytes_per_tenant`` ≈ a few kB), not by HBM.

**Sharding** — with a ``mesh``, the slot axis of every table is sharded
over the ``"lam_slots"`` logical axis (``sharding/rules.py``; the serving
engine maps it to the mesh model axis).  Each device then holds
``n_slots / axis_size`` λ rows, and the BGMV seg path gathers rows from
local shards only (``kernels.qrlora_bgmv.lam_gather_sharded``) with a psum
reassembling exact rows — bit-identical to the replicated gather.

``install(params)`` produces a parameter view whose adapter ``lam`` leaves
*are* the packed tables (the layer scan strips the lead axes and
``adapted_matmul`` sees the per-layer ``(n_slots, rank_cap)`` table).  The
view is memoized on ``version``: repeated calls return the same object, a
slot write refreshes only the λ leaves, and every other leaf (weights, B,
A) is shared with the input forever.
"""
from __future__ import annotations

import hashlib
import json
import math
import os
from collections import OrderedDict
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

BASE_TENANT = "__base__"

# register() return value for a tenant that landed in the host cold tier
# (hot slots exhausted and pinned); promote() assigns the real slot later.
COLD_SLOT = -1


def _lam_digest(flat: Dict[Tuple[str, str], Any]) -> bytes:
    """Content hash of a λ tree — the tenant-*family* identity.

    Two tenants with bit-identical λ produce bit-identical K/V for the same
    tokens, so they may share prompt-prefix KV blocks (serving/paging.py's
    ``PrefixCache`` keys on this digest).  Tenants whose λ differ anywhere
    get distinct digests and never share."""
    h = hashlib.sha1()
    for key in sorted(flat):
        leaf = np.asarray(flat[key], np.float32)
        h.update(repr((key, leaf.shape)).encode())
        h.update(np.ascontiguousarray(leaf).tobytes())
    return h.digest()


def lam_digest(lam_tree: Dict[str, Dict[str, Any]]) -> bytes:
    """Content hash of a nested ``{module: {proj: λ}}`` tree — identical to
    the digest :meth:`LamStore.register` assigns, computable *without* a
    store.  The multi-replica router (serving/router.py) places requests by
    this digest before the tenant is registered on any replica."""
    return _lam_digest({
        (mod, proj): leaf
        for mod, projs in lam_tree.items()
        for proj, leaf in projs.items()
    })


def extract_lambda(params: Pytree) -> Dict[str, Dict[str, jax.Array]]:
    """Pull the λ coefficient tree out of a parameter pytree."""
    adapters = params["groups"].get("adapters", {})
    return {
        mod: {proj: leaf["lam"] for proj, leaf in projs.items()}
        for mod, projs in adapters.items()
    }


def random_lambda(key, params: Pytree, scale: float = 0.05) -> Dict[str, Dict[str, jax.Array]]:
    """A synthetic tenant: i.i.d. normal λ (stand-in for a fine-tuned one)."""
    lam0 = extract_lambda(params)
    leaves, treedef = jax.tree_util.tree_flatten(lam0)
    keys = jax.random.split(key, len(leaves))
    out = [
        jax.random.normal(k, l.shape, jnp.float32) * scale
        for k, l in zip(keys, leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, out)


def _write_slot_impl(tables, rows, slot):
    """One λ row (the tenant's, across every table) written at ``slot``."""
    out = {}
    for key, tab in tables.items():
        row = rows[key].astype(tab.dtype)[..., None, :]
        idx = (0,) * (tab.ndim - 2) + (slot, 0)
        out[key] = jax.lax.dynamic_update_slice(tab, row, idx)
    return out


def _extract_slot_impl(tables, zero_rows, slot):
    """Read one λ row out of every table, then scrub the slot to zero
    (base-model-safe until overwritten) — the spill path, one call."""
    rows = {key: jnp.take(tab, slot, axis=-2) for key, tab in tables.items()}
    return rows, _write_slot_impl(tables, zero_rows, slot)


def _write_slots_impl(tables, rows, slots):
    """k λ rows written across every table in ONE donated call — the batch
    register/promote path for mass-admission spikes.  ``slots`` (k,) may
    repeat an index only with identical rows (the power-of-two padding
    repeats the last entry, so the duplicate scatter is a no-op)."""
    out = {}
    for key, tab in tables.items():
        out[key] = tab.at[..., slots, :].set(rows[key].astype(tab.dtype))
    return out


def _extract_slots_impl(tables, zero_rows, slots):
    """Read k λ rows out of every table, then scrub the slots to zero —
    the batched spill, one call."""
    rows = {key: jnp.take(tab, slots, axis=-2) for key, tab in tables.items()}
    return rows, _write_slots_impl(tables, zero_rows, slots)


class MmapColdTier:
    """Restart-surviving cold tier: λ rows in an mmap-backed record file.

    Drop-in for the in-memory ``OrderedDict`` cold tier (same mapping
    surface: membership, LRU-ordered iteration coldest-first, ``pop`` /
    ``__setitem__`` / ``move_to_end``).  Every tenant's λ rows flatten into
    one fixed-size fp32 record of the data file; the tenant → record
    catalog (LRU order, per-tenant λ digests) persists as a JSON sidecar
    next to it.  A restarted server passing the same ``cold_path`` to
    :class:`LamStore` reopens both and finds its spilled tenant catalog
    intact — digests included, so prefix-sharing family identity survives
    the restart too."""

    def __init__(
        self,
        path: str,
        lam_shapes: Dict[Tuple[str, str], Tuple[int, ...]],
        capacity: int,
    ):
        self.path = str(path)
        self.catalog_path = self.path + ".json"
        self._keys = sorted(lam_shapes)
        self._shapes = {k: tuple(lam_shapes[k]) for k in self._keys}
        self._sizes = [int(np.prod(self._shapes[k])) for k in self._keys]
        self._row_floats = int(sum(self._sizes))
        self.capacity = int(capacity)
        # tenant → (record index, digest hex); insertion order IS LRU order
        self._index: "OrderedDict[str, Tuple[int, str]]" = OrderedDict()
        self._free: List[int] = []
        if os.path.exists(self.catalog_path):
            self._load_catalog()
        else:
            os.makedirs(os.path.dirname(os.path.abspath(self.path)), exist_ok=True)
            self._free = list(range(self.capacity - 1, -1, -1))
        mode = "r+" if os.path.exists(self.path) else "w+"
        self._mm = np.memmap(
            self.path, np.float32, mode=mode,
            shape=(self.capacity, max(self._row_floats, 1)),
        )

    def _schema(self) -> list:
        return [[list(k), list(self._shapes[k])] for k in self._keys]

    def _load_catalog(self) -> None:
        with open(self.catalog_path) as f:
            cat = json.load(f)
        if cat["schema"] != self._schema():
            raise ValueError(
                f"cold catalog {self.catalog_path} was written for a "
                "different λ schema (other model or adapter config)"
            )
        # the record file's geometry wins; a larger requested capacity
        # grows the file, a smaller one is ignored (records would dangle)
        stored = int(cat["capacity"])
        grown = list(range(self.capacity - 1, stored - 1, -1))
        self.capacity = max(self.capacity, stored)
        self._free = grown + [int(i) for i in cat["free"]]
        for tenant, rec, dg in cat["tenants"]:
            self._index[tenant] = (int(rec), dg)

    def _save(self) -> None:
        cat = {
            "schema": self._schema(),
            "capacity": self.capacity,
            "tenants": [[t, rec, dg] for t, (rec, dg) in self._index.items()],
            "free": self._free,
        }
        tmp = self.catalog_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(cat, f)
        os.replace(tmp, self.catalog_path)  # atomic: never a torn catalog

    def digests(self) -> Dict[str, bytes]:
        """Per-tenant λ digests restored from the catalog (LamStore seeds
        its digest refcounts from this on reopen)."""
        return {t: bytes.fromhex(dg) for t, (_, dg) in self._index.items()}

    # -- the OrderedDict surface LamStore drives ----------------------------

    def __contains__(self, tenant: str) -> bool:
        return tenant in self._index

    def __len__(self) -> int:
        return len(self._index)

    def __iter__(self) -> Iterator[str]:
        return iter(self._index)

    def pop(self, tenant: str, *default):
        if tenant not in self._index:
            if default:
                return default[0]
            raise KeyError(tenant)
        rec, _ = self._index.pop(tenant)
        flat = np.array(self._mm[rec])  # copy out before the record recycles
        self._free.append(rec)
        self._save()
        rows, off = {}, 0
        for key, size in zip(self._keys, self._sizes):
            rows[key] = flat[off: off + size].reshape(self._shapes[key])
            off += size
        return rows

    def __setitem__(self, tenant: str, rows) -> None:
        if tenant in self._index:
            rec, _ = self._index.pop(tenant)
        elif self._free:
            rec = self._free.pop()
        else:
            # unreachable through LamStore (its cold-room accounting runs
            # first) — guard direct misuse
            raise RuntimeError(f"mmap cold tier full (capacity={self.capacity})")
        rows = {k: np.asarray(rows[k], np.float32) for k in self._keys}
        self._mm[rec] = np.concatenate(
            [rows[k].reshape(-1) for k in self._keys]
        ) if self._row_floats else 0.0
        self._mm.flush()
        self._index[tenant] = (rec, _lam_digest(rows).hex())
        self._save()

    def move_to_end(self, tenant: str) -> None:
        self._index.move_to_end(tenant)
        self._save()


class LamStore:
    """Hierarchical λ-pool: hot device slots + host cold tier, LRU/pinning,
    hot-swap, O(one λ row) slot writes, optional mesh-sharded tables.

    Per-tenant state is *only* the λ vectors (~``sum(n_stack·rank_cap)``
    fp32 scalars) — compare S-LoRA-style serving where each adapter is a
    rank-r factor *pair* per projection (``r·(d_in+d_out)`` params).  That
    gap is what makes 10⁴⁺ resident tenants cheap here: the hot tier is a
    few MB of HBM, the cold tier a few MB of host RAM.

    Two pin levels back the serving engine's admission flow:

    * ``pin``/``unpin`` — hot-slot pins: the slot is referenced by an
      *active* decode lane and must not be recycled or spilled.
    * ``protect``/``unprotect`` — residency pins: the tenant belongs to a
      *queued* request and must stay resident somewhere (it may spill to
      the cold tier, but never drops out of the store).
    """

    def __init__(
        self,
        lam_shapes: Dict[Tuple[str, str], Tuple[int, ...]],
        n_slots: int = 8,
        *,
        cold_slots: int = 0,
        cold_path: Optional[str] = None,
        mesh=None,
        device=None,
    ):
        assert n_slots >= 2, "need slot 0 (base) plus at least one tenant slot"
        self._lam_shapes = dict(lam_shapes)
        self.mesh = mesh
        self.device = device  # one-device engines commit their tables here
        self.shard_axis: Optional[str] = None
        if mesh is not None:
            from repro.sharding.rules import logical_spec

            ax = logical_spec("lam_slots")[0]
            if ax is not None:
                self.shard_axis = ax
                size = math.prod(
                    mesh.shape[a] for a in (ax if isinstance(ax, tuple) else (ax,))
                )
                n_slots = -(-n_slots // size) * size  # pad to an even shard
        self.n_slots = n_slots
        self.cold_slots = int(cold_slots)
        # (module, proj) → (*stack_lead, n_slots, cap) fp32, zero-initialized
        # so every unused slot (and slot 0) is the base model.
        self._tables: Dict[Tuple[str, str], jax.Array] = {
            key: self._make_table(shape) for key, shape in self._lam_shapes.items()
        }
        # LRU order: least-recently-used first.  Slot 0 is permanently pinned.
        self._slots: "OrderedDict[str, int]" = OrderedDict({BASE_TENANT: 0})
        self._pins: Dict[str, int] = {BASE_TENANT: 1}
        self._protect: Dict[str, int] = {}
        self._free = list(range(n_slots - 1, 0, -1))
        # cold tier: tenant → {key: np λ row}, LRU order (coldest first).
        # With cold_path the tier is mmap-backed and restart-surviving
        # (MmapColdTier exposes the same mapping surface).
        if cold_path is not None:
            if cold_slots <= 0:
                raise ValueError("cold_path requires cold_slots > 0")
            self._cold: Any = MmapColdTier(cold_path, self._lam_shapes, cold_slots)
            self.cold_slots = self._cold.capacity  # file geometry wins
        else:
            self._cold = OrderedDict()
        self.version = 0  # bumped on any *device table* mutation (view key)
        # tenant → λ content hash (the prefix-sharing family id) + refcounts
        # per digest so the engine can tell when a family went extinct; the
        # base tenant's digest is that of the all-zeros tree, so explicit
        # zero-λ tenants land in the same family.
        self._digests: Dict[str, bytes] = {}
        self._digest_refs: Dict[bytes, int] = {}
        self._digest_add(
            BASE_TENANT,
            _lam_digest({k: np.zeros(s, np.float32) for k, s in self._lam_shapes.items()}),
        )
        if isinstance(self._cold, MmapColdTier):
            # reopened catalog: spilled tenants are already resident — seed
            # their digests so family identity survives the restart
            for tenant, dg in self._cold.digests().items():
                self._digest_add(tenant, dg)
        # per-instance jits: donated tables, one executable per store so the
        # compile/alloc counters below are attributable in tests
        self._write = jax.jit(_write_slot_impl, donate_argnums=(0,))
        self._extract = jax.jit(_extract_slot_impl, donate_argnums=(0,))
        self._write_batch = jax.jit(_write_slots_impl, donate_argnums=(0,))
        self._extract_batch = jax.jit(_extract_slots_impl, donate_argnums=(0,))
        self.slot_writes = 0  # donated device calls (register/spill/evict/promote)
        self.spills = 0  # hot → cold demotions
        self.promotes = 0  # cold → hot promotions
        self.cold_registers = 0  # registers that landed directly in the cold tier
        self.lru_drops = 0  # tenants silently dropped by tier pressure
        # called as on_drop(tenant, digest) whenever LRU pressure drops a
        # tenant from the store entirely (no explicit evict) — the engine
        # uses it to reclaim the tenant's prefix-cache family eagerly
        self.on_drop = None
        # install() memo: (params identity, version) → view
        self._install_params: Optional[Pytree] = None
        self._install_version = -1
        self._install_view: Optional[Pytree] = None

    def _make_table(self, row_shape: Tuple[int, ...]) -> jax.Array:
        full = (*row_shape[:-1], self.n_slots, row_shape[-1])
        tab = jnp.zeros(full, jnp.float32)
        if self.shard_axis is not None:
            from jax.sharding import NamedSharding

            from repro.sharding.rules import logical_spec

            spec = logical_spec(*([None] * (len(row_shape) - 1)), "lam_slots", None)
            tab = jax.device_put(tab, NamedSharding(self.mesh, spec))
        elif self.device is not None:
            tab = jax.device_put(tab, self.device)
        return tab

    # -- construction -------------------------------------------------------

    @classmethod
    def from_params(cls, params: Pytree, n_slots: int = 8, **kw) -> "LamStore":
        lam = extract_lambda(params)
        shapes = {
            (mod, proj): tuple(leaf.shape)
            for mod, projs in lam.items()
            for proj, leaf in projs.items()
        }
        if not shapes:
            raise ValueError("params carry no adapters — nothing to serve")
        return cls(shapes, n_slots=n_slots, **kw)

    # -- bookkeeping --------------------------------------------------------

    def __contains__(self, tenant: str) -> bool:
        return tenant in self._slots or tenant in self._cold

    def __len__(self) -> int:
        return len(self._slots) + len(self._cold)

    @property
    def tenants(self) -> Tuple[str, ...]:
        return tuple(self._slots) + tuple(self._cold)

    @property
    def hot_tenants(self) -> Tuple[str, ...]:
        return tuple(self._slots)

    @property
    def cold_tenants(self) -> Tuple[str, ...]:
        return tuple(self._cold)

    def is_hot(self, tenant: str) -> bool:
        return tenant in self._slots

    def is_cold(self, tenant: str) -> bool:
        return tenant in self._cold

    def lookup(self, tenant: str) -> int:
        """Slot id of a hot tenant (touches LRU recency)."""
        if tenant in self._cold:
            raise KeyError(f"tenant {tenant!r} is in the cold tier — promote() first")
        slot = self._slots[tenant]
        self._slots.move_to_end(tenant)
        return slot

    def pin(self, tenant: str) -> int:
        """Mark a hot tenant's slot as referenced by an active decode lane."""
        slot = self.lookup(tenant)
        self._pins[tenant] = self._pins.get(tenant, 0) + 1
        return slot

    def unpin(self, tenant: str) -> None:
        n = self._pins.get(tenant, 0) - 1
        if n <= 0:
            self._pins.pop(tenant, None)
        else:
            self._pins[tenant] = n

    def protect(self, tenant: str) -> None:
        """Residency pin: the tenant must stay in the store (either tier)
        until unprotected — a queued request depends on it."""
        if tenant not in self:
            raise KeyError(f"unknown tenant {tenant!r}")
        self._protect[tenant] = self._protect.get(tenant, 0) + 1

    def unprotect(self, tenant: str) -> None:
        n = self._protect.get(tenant, 0) - 1
        if n <= 0:
            self._protect.pop(tenant, None)
        else:
            self._protect[tenant] = n

    # -- digest bookkeeping -------------------------------------------------

    def digest(self, tenant: str) -> bytes:
        """λ content hash of a resident tenant (prefix-sharing family id)."""
        return self._digests[tenant]

    def digest_refcount(self, dg: bytes) -> int:
        """Resident tenants (hot or cold) carrying this λ digest — 0 means
        the family is extinct and its prefix-cache entries are garbage."""
        return self._digest_refs.get(dg, 0)

    def _digest_add(self, tenant: str, dg: bytes) -> None:
        old = self._digests.get(tenant)
        if old == dg:
            return
        if old is not None:
            self._digest_drop_ref(old)
        self._digests[tenant] = dg
        self._digest_refs[dg] = self._digest_refs.get(dg, 0) + 1

    def _digest_remove(self, tenant: str) -> None:
        dg = self._digests.pop(tenant, None)
        if dg is not None:
            self._digest_drop_ref(dg)

    def _digest_drop_ref(self, dg: bytes) -> None:
        n = self._digest_refs.get(dg, 0) - 1
        if n <= 0:
            self._digest_refs.pop(dg, None)
        else:
            self._digest_refs[dg] = n

    # -- device slot writes (the O(one λ row) paths) -------------------------

    def _zero_rows(self) -> Dict[Tuple[str, str], np.ndarray]:
        return {k: np.zeros(s, np.float32) for k, s in self._lam_shapes.items()}

    def _write_slot(self, slot: int, rows) -> None:
        """ONE donated jitted call: every table gets its row at ``slot``
        overwritten in place (buffer donation — no table copy, no re-pack)."""
        self._tables = self._write(self._tables, rows, jnp.asarray(slot, jnp.int32))
        self.slot_writes += 1
        self.version += 1

    def _extract_rows(self, slot: int) -> Dict[Tuple[str, str], np.ndarray]:
        """Read slot ``slot``'s λ row from every table and scrub the slot —
        one donated call; returns host fp32 rows (the spill payload)."""
        rows, self._tables = self._extract(
            self._tables, self._zero_rows(), jnp.asarray(slot, jnp.int32)
        )
        self.slot_writes += 1
        self.version += 1
        return {k: np.asarray(v) for k, v in jax.device_get(rows).items()}

    @staticmethod
    def _pad_pow2(slots: List[int]) -> np.ndarray:
        """Slot index vector padded to a power of two by repeating the last
        entry — spike sizes then share a handful of compilations, and the
        duplicate scatter rewrites an identical row (a no-op)."""
        kp = 1
        while kp < len(slots):
            kp *= 2
        return np.asarray(list(slots) + [slots[-1]] * (kp - len(slots)), np.int32)

    def _write_slots(self, slots: List[int], rows_list) -> None:
        """Batched :meth:`_write_slot`: k λ rows land in k slots in ONE
        donated device call (mass-admission spikes, router peer promotion)."""
        idx = self._pad_pow2(slots)
        batch = {}
        for key in self._lam_shapes:
            stack = np.stack(
                [np.asarray(r[key], np.float32) for r in rows_list], axis=-2
            )
            if len(idx) != len(slots):
                pad = np.repeat(stack[..., -1:, :], len(idx) - len(slots), axis=-2)
                stack = np.concatenate([stack, pad], axis=-2)
            batch[key] = stack
        self._tables = self._write_batch(self._tables, batch, jnp.asarray(idx))
        self.slot_writes += 1
        self.version += 1

    def _extract_slots(self, slots: List[int]) -> List[Dict[Tuple[str, str], np.ndarray]]:
        """Batched :meth:`_extract_rows`: k λ rows leave the device (their
        slots scrubbed to zero) in one donated call."""
        idx = self._pad_pow2(slots)
        zeros = {
            key: np.zeros((*s[:-1], len(idx), s[-1]), np.float32)
            for key, s in self._lam_shapes.items()
        }
        rows, self._tables = self._extract_batch(
            self._tables, zeros, jnp.asarray(idx)
        )
        self.slot_writes += 1
        self.version += 1
        host = {k: np.asarray(v) for k, v in jax.device_get(rows).items()}
        return [
            {k: np.ascontiguousarray(host[k][..., i, :]) for k in host}
            for i in range(len(slots))
        ]

    # -- tiering ------------------------------------------------------------

    def _make_cold_room(self) -> bool:
        """Ensure the cold tier can take one more tenant, dropping the
        coldest unprotected entry if full; False when it can't."""
        if self.cold_slots <= 0:
            return False
        if len(self._cold) < self.cold_slots:
            return True
        for tenant in self._cold:  # LRU first
            if self._protect.get(tenant, 0) or self._pins.get(tenant, 0):
                continue
            self._cold.pop(tenant)
            self._dropped(tenant)
            return True
        return False

    def _dropped(self, tenant: str) -> None:
        """Bookkeeping for a tenant LRU pressure pushed out of the store."""
        dg = self._digests.get(tenant)
        self._digest_remove(tenant)
        self.lru_drops += 1
        if self.on_drop is not None:
            self.on_drop(tenant, dg)

    def _spill_to_cold(self, tenant: str) -> int:
        """Demote a hot tenant: λ rows → host, slot scrubbed; returns the
        freed slot (caller reuses it or returns it to the free list)."""
        slot = self._slots.pop(tenant)
        self._cold[tenant] = self._extract_rows(slot)
        self._cold.move_to_end(tenant)
        self.spills += 1
        return slot

    def _try_evict_lru(self) -> Optional[int]:
        """Free one hot slot, least-recently-used first: spill to the cold
        tier when there's room, else drop outright (unprotected tenants
        only).  None when every hot slot is pinned or protected-with-no-
        cold-room — the caller defers or falls back to the cold tier."""
        for tenant in self._slots:
            if tenant == BASE_TENANT or self._pins.get(tenant, 0):
                continue
            if self._make_cold_room():
                return self._spill_to_cold(tenant)
            if not self._protect.get(tenant, 0):
                slot = self._slots.pop(tenant)
                self._dropped(tenant)
                self._write_slot(slot, self._zero_rows())  # base-safe scrub
                return slot
        return None

    def spill(self, tenant: str) -> None:
        """Explicitly demote a hot tenant's λ to the host cold tier."""
        if tenant == BASE_TENANT:
            raise ValueError("slot 0 (base tenant) cannot be spilled")
        if tenant in self._cold:
            return
        if tenant not in self._slots:
            raise KeyError(f"unknown tenant {tenant!r}")
        if self._pins.get(tenant, 0):
            raise RuntimeError(f"tenant {tenant!r} is pinned by an active lane")
        if not self._make_cold_room():
            raise RuntimeError(
                f"cold tier {'full of protected tenants' if self.cold_slots else 'disabled'}"
                f" (cold_slots={self.cold_slots}) — cannot spill {tenant!r}"
            )
        self._free.append(self._spill_to_cold(tenant))

    def promote(self, tenant: str) -> Optional[int]:
        """Host→device promotion of a cold tenant; returns its hot slot, or
        None when no hot slot can be freed (caller defers admission, the
        same way a full block pool defers it)."""
        if tenant in self._slots:
            return self.lookup(tenant)
        # pop before freeing a slot: the promotion itself vacates one cold
        # entry, and the LRU eviction below may need exactly that room to
        # spill its victim (it must never recycle the tenant's own rows)
        rows = self._cold.pop(tenant, None)
        if rows is None:
            raise KeyError(f"unknown tenant {tenant!r}")
        slot = self._free.pop() if self._free else self._try_evict_lru()
        if slot is None:
            self._cold[tenant] = rows  # deferred: back into the cold tier
            return None
        self._write_slot(slot, rows)
        self._slots[tenant] = slot
        self._slots.move_to_end(tenant)
        self.promotes += 1
        return slot

    # -- registration / hot-swap -------------------------------------------

    def _validate(
        self, tenant: str, lam_tree: Dict[str, Dict[str, jax.Array]]
    ) -> Tuple[Dict[Tuple[str, str], np.ndarray], bytes]:
        """Shape-check a λ tree and flatten it to host rows + digest."""
        if tenant == BASE_TENANT:
            raise ValueError("slot 0 (base tenant) is immutable")
        flat = {
            (mod, proj): leaf
            for mod, projs in lam_tree.items()
            for proj, leaf in projs.items()
        }
        if set(flat) != set(self._lam_shapes):
            raise ValueError(
                f"λ tree keys {sorted(flat)} != registry keys {sorted(self._lam_shapes)}"
            )
        for key, leaf in flat.items():
            want = self._lam_shapes[key]
            if tuple(leaf.shape) != want:
                raise ValueError(f"λ[{key}] shape {leaf.shape} != {want}")
        rows = {k: np.asarray(v, np.float32) for k, v in flat.items()}
        return rows, _lam_digest(rows)

    def _exhausted(self) -> RuntimeError:
        return RuntimeError(
            f"λ-pool exhausted: all {self.n_slots} slots pinned by in-flight "
            f"requests and the cold tier is "
            f"{'full' if self.cold_slots else 'disabled'} "
            "(raise n_slots/cold_slots or drain the queue)"
        )

    def register(self, tenant: str, lam_tree: Dict[str, Dict[str, jax.Array]]) -> int:
        """Load (or hot-swap) a tenant's λ; returns its hot slot id, or
        :data:`COLD_SLOT` when it landed in the host cold tier."""
        rows, dg = self._validate(tenant, lam_tree)
        if tenant in self and (
            self._pins.get(tenant, 0) or self._protect.get(tenant, 0)
        ):
            # pins cover active lanes; protects cover queued AND preempted
            # requests (a quantum-preempted lane resumes from its snapshot —
            # swapping λ under it would mix adapters within one generation)
            raise RuntimeError(
                f"tenant {tenant!r} is referenced by in-flight requests — "
                "hot-swapping its λ mid-generation would mix adapters"
            )
        if tenant in self._slots:
            slot = self.lookup(tenant)  # hot-swap in place
            self._write_slot(slot, rows)
            self._digest_add(tenant, dg)
            return slot
        if tenant in self._cold:
            # cold hot-swap: replace the host rows, no device traffic
            self._cold[tenant] = rows
            self._cold.move_to_end(tenant)
            self._digest_add(tenant, dg)
            return COLD_SLOT
        slot = self._free.pop() if self._free else self._try_evict_lru()
        if slot is None:
            if self._make_cold_room():
                self._cold[tenant] = rows
                self._digest_add(tenant, dg)
                self.cold_registers += 1
                return COLD_SLOT
            raise self._exhausted()
        self._write_slot(slot, rows)
        self._slots[tenant] = slot
        self._slots.move_to_end(tenant)
        self._digest_add(tenant, dg)
        return slot

    def register_many(
        self, lam_trees: Dict[str, Dict[str, Dict[str, jax.Array]]]
    ) -> Dict[str, int]:
        """Batch :meth:`register`: every *new* tenant's λ row lands on the
        device in one donated multi-slot write — a mass-admission spike (or
        the router shipping a tenant cohort to a replica) costs one
        dispatch, not one per tenant.  Already-resident tenants take the
        single-tenant hot-swap path (its in-flight guards apply).  Returns
        tenant → hot slot id or :data:`COLD_SLOT`."""
        result: Dict[str, int] = {}
        fresh = []
        for tenant, tree in lam_trees.items():
            if tenant in self:
                result[tenant] = self.register(tenant, tree)
            else:
                fresh.append((tenant, *self._validate(tenant, tree)))
        slots: List[int] = []
        rows_list = []
        for tenant, rows, dg in fresh:
            slot = self._free.pop() if self._free else self._try_evict_lru()
            if slot is None:
                if not self._make_cold_room():
                    if slots:  # land what already got slots first
                        self._write_slots(slots, rows_list)
                        slots = []
                    raise self._exhausted()
                self._cold[tenant] = rows
                self._digest_add(tenant, dg)
                self.cold_registers += 1
                result[tenant] = COLD_SLOT
                continue
            slots.append(slot)
            rows_list.append(rows)
            self._slots[tenant] = slot
            self._slots.move_to_end(tenant)
            self._digest_add(tenant, dg)
            result[tenant] = slot
        if slots:
            self._write_slots(slots, rows_list)
        return result

    def promote_many(self, tenants: Iterable[str]) -> Dict[str, Optional[int]]:
        """Batch :meth:`promote`: every promotable cold tenant's row lands
        hot in one donated multi-slot write.  Per-tenant results mirror
        ``promote()`` — slot id, or None when no hot slot could be freed
        (the tenant stays cold; the caller defers)."""
        result: Dict[str, Optional[int]] = {}
        slots: List[int] = []
        rows_list = []
        for tenant in dict.fromkeys(tenants):
            if tenant in self._slots:
                result[tenant] = self.lookup(tenant)
                continue
            rows = self._cold.pop(tenant, None)
            if rows is None:
                raise KeyError(f"unknown tenant {tenant!r}")
            slot = self._free.pop() if self._free else self._try_evict_lru()
            if slot is None:
                self._cold[tenant] = rows  # deferred: back into the cold tier
                result[tenant] = None
                continue
            slots.append(slot)
            rows_list.append(rows)
            self._slots[tenant] = slot
            self._slots.move_to_end(tenant)
            self.promotes += 1
            result[tenant] = slot
        if slots:
            self._write_slots(slots, rows_list)
        return result

    def spill_many(self, tenants: Iterable[str]) -> None:
        """Batch :meth:`spill`: all victims' λ rows leave the device in one
        extract+scrub call.  Cold-tier room for the whole cohort is checked
        up front, so the batch either fully lands or raises before any slot
        is scrubbed."""
        victims: List[str] = []
        for tenant in dict.fromkeys(tenants):
            if tenant == BASE_TENANT:
                raise ValueError("slot 0 (base tenant) cannot be spilled")
            if tenant in self._cold:
                continue
            if tenant not in self._slots:
                raise KeyError(f"unknown tenant {tenant!r}")
            if self._pins.get(tenant, 0):
                raise RuntimeError(f"tenant {tenant!r} is pinned by an active lane")
            victims.append(tenant)
        if not victims:
            return
        droppable = sum(
            1 for t in self._cold
            if not (self._protect.get(t, 0) or self._pins.get(t, 0))
        )
        if self.cold_slots - len(self._cold) + droppable < len(victims):
            raise RuntimeError(
                f"cold tier cannot absorb {len(victims)} spills "
                f"(cold_slots={self.cold_slots})"
            )
        slots = [self._slots.pop(t) for t in victims]
        for tenant, slot, rows in zip(victims, slots, self._extract_slots(slots)):
            self._make_cold_room()  # cannot fail: room was pre-checked
            self._cold[tenant] = rows
            self._cold.move_to_end(tenant)
            self._free.append(slot)
            self.spills += 1

    def evict(self, tenant: str) -> None:
        """Explicitly drop a tenant from both tiers (must not be pinned or
        residency-protected)."""
        if tenant == BASE_TENANT:
            raise ValueError("slot 0 (base tenant) cannot be evicted")
        if self._pins.get(tenant, 0):
            raise RuntimeError(f"tenant {tenant!r} is pinned by in-flight requests")
        if self._protect.get(tenant, 0):
            raise RuntimeError(f"tenant {tenant!r} is protected by queued requests")
        if tenant in self._cold:
            self._cold.pop(tenant)
            self._digest_remove(tenant)
            return
        slot = self._slots.pop(tenant)
        self._digest_remove(tenant)
        self._write_slot(slot, self._zero_rows())  # base-safe scrub
        self._free.append(slot)

    # -- parameter view -----------------------------------------------------

    @property
    def tables(self) -> Dict[Tuple[str, str], jax.Array]:
        """Slot-major ``(n_slots, *stack_lead, cap)`` view of the packed
        tables (introspection/debugging; the serving path consumes the
        install-layout storage directly, so this transpose never runs on
        the hot path)."""
        return {key: jnp.moveaxis(tab, -2, 0) for key, tab in self._tables.items()}

    def install(self, params: Pytree) -> Pytree:
        """Params view whose adapter λ leaves *are* the packed slot tables.

        Tables live in the install layout ``(*stack_lead, n_slots, cap)``,
        so no moveaxis/re-pack happens here, and the view is memoized on
        ``version``: repeated calls return the same object until a slot
        write, which refreshes only the λ leaf references.  Every other
        leaf (weights, B, A) is shared with the input — installing is
        O(#tables) dict construction, not O(bytes)."""
        if params is self._install_params and self.version == self._install_version:
            return self._install_view
        groups = dict(params["groups"])
        adapters = {
            mod: dict(projs) for mod, projs in groups.get("adapters", {}).items()
        }
        for (mod, proj), table in self._tables.items():
            leaf = dict(adapters[mod][proj])
            leaf["lam"] = table
            adapters[mod][proj] = leaf
        groups["adapters"] = adapters
        view = {**params, "groups": groups}
        self._install_params = params
        self._install_version = self.version
        self._install_view = view
        return view

    # -- accounting ---------------------------------------------------------

    def attach_metrics(self, registry) -> None:
        """Expose tier occupancy and churn through a
        :class:`~repro.obs.metrics.MetricsRegistry`.  Everything is
        callback-sampled from the counters/containers the store already
        maintains — attaching metrics adds zero work to the
        register/promote/evict paths."""
        cb = registry.callback
        cb("lam_hot_slots_in_use", lambda: len(self._slots) - 1,
           help="hot-tier λ slots holding a tenant (base slot 0 excluded)")
        cb("lam_hot_slots_capacity", lambda: self.hot_capacity,
           help="usable hot-tier λ slots")
        cb("lam_cold_tenants", lambda: len(self._cold),
           help="tenants resident in the host cold tier")
        cb("lam_cold_capacity", lambda: self.cold_slots,
           help="host cold-tier capacity (tenants)")
        cb("lam_table_bytes", self.table_bytes,
           help="device bytes of the packed hot-tier λ tables")
        cb("lam_cold_bytes", self.cold_bytes,
           help="host bytes currently held by the cold tier")
        cb("lam_spills_total", lambda: self.spills, kind="counter",
           help="hot → cold λ demotions")
        cb("lam_promotes_total", lambda: self.promotes, kind="counter",
           help="cold → hot λ promotions")
        cb("lam_cold_registers_total", lambda: self.cold_registers,
           kind="counter", help="registers that landed directly in the cold tier")
        cb("lam_lru_drops_total", lambda: self.lru_drops, kind="counter",
           help="tenants dropped from the store by tier pressure")
        cb("lam_slot_writes_total", lambda: self.slot_writes, kind="counter",
           help="donated device slot writes (register/spill/evict/promote)")

    @property
    def hot_capacity(self) -> int:
        """Usable hot slots (excludes the reserved base slot 0)."""
        return self.n_slots - 1

    def bytes_per_tenant(self) -> int:
        """Bytes of per-tenant λ state (one row across all tables) — the
        same figure on device (hot) and host (cold)."""
        return sum(4 * math.prod(shape) for shape in self._lam_shapes.values())

    def table_bytes(self) -> int:
        """Device bytes of the packed hot-tier tables (whole mesh)."""
        return self.bytes_per_tenant() * self.n_slots

    def cold_bytes(self) -> int:
        """Host bytes currently held by the cold tier."""
        return self.bytes_per_tenant() * len(self._cold)


# Back-compat name: PR 1 grew the serving subsystem around AdapterRegistry;
# the hierarchical store supersedes it with the same core surface.
AdapterRegistry = LamStore
