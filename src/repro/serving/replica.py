"""Engine replicas + the transport seam under the multi-replica router.

One :class:`EngineReplica` wraps one :class:`~repro.serving.engine.
MultiTenantEngine` with a replica identity (id, role, device group) and the
load signal the router's spillover policy reads.  On a host with a device
per replica, each replica's engine lives on its own device: the frozen tree
is initialized once and copied to every device, and the replica's KV pool
and λ tables are committed there too.  With fewer devices than replicas
(single-device CPU tests only) the replicas share one tree by reference and
time-share the device.

Transport seam
==============

Replicas exchange two payload kinds (both host ``np.ndarray`` dicts built by
the engine's export hooks):

* **prefix** — full-block K/V for a cached prompt prefix
  (``engine.export_prefix`` → ``engine.import_prefix``), shipped when a
  sibling already prefillled the prompt family this replica is about to.
* **prefill** — a committed prompt's blocks + first-token logits
  (``engine.export_request_state`` → ``engine.inject_prefilled``), the
  prefill→decode disaggregation handoff.

:class:`LocalTransport` moves them by reference (replicas share a process)
but meters every shipment in bytes — the datum a cross-host transport would
pay for real, and the number the smoke bench gates on.  A future RPC
transport implements the same two-method surface against serialized
payloads; nothing above the seam changes.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.serving.config import EngineConfig
from repro.serving.engine import MultiTenantEngine
from repro.sharding.rules import replica_device_groups

#: Replica roles under prefill/decode disaggregation.  ``"both"`` is the
#: symmetric (non-disaggregated) default; a ``"prefill"`` replica only runs
#: prompt prefill (requests are exported after their first committed token),
#: a ``"decode"`` replica only decodes (its prompts arrive pre-filled).
ROLES = ("both", "prefill", "decode")


def payload_nbytes(payload: Optional[Dict[str, Any]]) -> int:
    """Wire size of an export payload: array bytes plus a nominal 8 per
    scalar/None field (what a length-prefixed header would carry)."""
    if payload is None:
        return 0
    total = 0
    for v in payload.values():
        if isinstance(v, np.ndarray):
            total += v.nbytes
        else:
            total += 8
    return total


class Transport:
    """Seam between replicas: ship export payloads, meter the bytes."""

    def ship(self, payload: Dict[str, Any], src: "EngineReplica",
             dst: "EngineReplica", kind: str) -> Dict[str, Any]:
        raise NotImplementedError

    def stats(self) -> Dict[str, Any]:
        raise NotImplementedError


class LocalTransport(Transport):
    """In-process transport: payloads move by reference, the meter runs as
    if they crossed a wire (per-kind shipment and byte counts)."""

    def __init__(self):
        self.shipments: Dict[str, int] = {}
        self.bytes: Dict[str, int] = {}

    def ship(self, payload, src, dst, kind):
        n = payload_nbytes(payload)
        self.shipments[kind] = self.shipments.get(kind, 0) + 1
        self.bytes[kind] = self.bytes.get(kind, 0) + n
        return payload

    def total_bytes(self) -> int:
        return sum(self.bytes.values())

    def stats(self) -> Dict[str, Any]:
        return {
            "shipments": dict(self.shipments),
            "bytes": dict(self.bytes),
            "total_bytes": self.total_bytes(),
        }


class EngineReplica:
    """One engine + its replica identity under the router."""

    def __init__(self, replica_id: int, engine: MultiTenantEngine, *,
                 role: str = "both", devices: Optional[Sequence[Any]] = None):
        if role not in ROLES:
            raise ValueError(f"role={role!r} must be one of {ROLES}")
        self.replica_id = replica_id
        self.engine = engine
        self.role = role
        #: device group of this replica (``sharding.replica_device_groups``);
        #: its engine lives on the first one when groups are disjoint
        self.devices = list(devices) if devices is not None else []
        self.alive = True

    @property
    def name(self) -> str:
        return f"r{self.replica_id}"

    def load(self) -> int:
        """Queued + active requests — the router's spillover signal."""
        sched = self.engine.scheduler
        return len(sched.queue) + len(sched.active())

    def has_work(self) -> bool:
        return self.alive and self.engine.scheduler.has_work

    def __repr__(self) -> str:
        return (
            f"EngineReplica({self.name}, role={self.role!r}, "
            f"load={self.load()}, alive={self.alive})"
        )


def build_replicas(
    cfg,
    config: EngineConfig,
    n: int,
    *,
    roles: Optional[Sequence[str]] = None,
    params=None,
    lams: Optional[Dict[str, Any]] = None,
    config_overrides: Optional[Callable[[int, EngineConfig], EngineConfig]] = None,
) -> List[EngineReplica]:
    """Build ``n`` replicas of one frozen parameter tree.

    Replica 0 initializes (or adopts ``params``); the rest are constructed
    from the same tree — no re-init.  With a device per replica, each
    engine is committed to the first device of its group and holds its own
    copy of the tree; otherwise (single-device CPU) they share it.  With
    ``roles=None`` every replica is ``"both"``; pass explicit roles for a
    disaggregated layout (the router validates the mix).  ``lams``
    pre-registers a tenant catalog on every replica via the batch API —
    benches and the single-replica baseline use it; the router's lazy
    placement-time registration makes it optional.  ``config_overrides``
    lets a caller vary per-replica geometry (e.g. a prefill-only replica
    with fewer lanes).
    """
    if n < 1:
        raise ValueError(f"n={n} must be >= 1")
    if roles is not None and len(roles) != n:
        raise ValueError(f"got {len(roles)} roles for {n} replicas")
    groups = replica_device_groups(n)
    own_device = len({g[0] for g in groups}) == n
    replicas: List[EngineReplica] = []
    for i in range(n):
        rcfg = config if config_overrides is None else config_overrides(i, config)
        eng = MultiTenantEngine(
            cfg, rcfg, params=params, device=groups[i][0] if own_device else None
        )
        if params is None:
            params = eng.params  # replica 0 initialized; siblings share
        replicas.append(EngineReplica(
            i, eng, role="both" if roles is None else roles[i],
            devices=groups[i],
        ))
        if lams:
            eng.add_tenants(lams)
    return replicas
