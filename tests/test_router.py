"""Multi-replica router: deterministic λ-digest placement on the consistent
ring, routed output token-identical to a single engine across layouts,
load spillover with cross-replica prefix import, replica-failure
re-placement, and disaggregated prefill→decode bit-identity."""
import jax
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.serving import (
    EngineConfig,
    EngineReplica,
    MultiTenantEngine,
    Router,
    build_replicas,
    random_lambda,
)


def _paged(**over):
    kw = dict(
        layout="paged", n_lanes=2, n_slots=6, max_len=48, block_size=8,
        share_prefix=True, prefill_chunk=8,
    )
    kw.update(over)
    return EngineConfig(**kw)


# ---------------------------------------------------------------------------
# placement: deterministic, balanced, minimally disruptive on ring change
# ---------------------------------------------------------------------------


def test_placement_deterministic_and_minimally_disruptive():
    """Any front-end computes the same ring (no shared state), every
    replica owns a share of the digest space, and removing a replica moves
    ONLY the digests it owned — the consistent-hashing contract the λ/
    prefix locality story rests on."""
    cfg = get_reduced("smollm-135m")
    eng = MultiTenantEngine(cfg, _paged())  # ring logic reads names + loads

    def mk_router(n):
        return Router([EngineReplica(i, eng) for i in range(n)],
                      telemetry=False)

    rng = np.random.default_rng(0)
    digests = [rng.integers(0, 256, 20, dtype=np.uint8).tobytes()
               for _ in range(256)]
    ra, rb = mk_router(3), mk_router(3)
    owners = [ra.owner_of(d).name for d in digests]
    assert owners == [rb.owner_of(d).name for d in digests], (
        "two routers over the same replica set disagree on placement"
    )
    assert set(owners) == {"r0", "r1", "r2"}, "a replica owns no digests"
    ra.kill_replica(2)
    for d, before in zip(digests, owners):
        after = ra.owner_of(d).name
        if before == "r2":
            assert after in ("r0", "r1")
        else:
            assert after == before, (
                "killing r2 moved a digest r2 never owned — remapping is "
                "not minimal"
            )


# ---------------------------------------------------------------------------
# token identity: routed == single engine, paged and dense layouts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("layout_kw", [
    dict(layout="paged", block_size=8, share_prefix=True, prefill_chunk=8),
    dict(layout="oracle_dense"),
])
def test_routed_tokens_identical_to_single_engine(layout_kw):
    cfg = get_reduced("smollm-135m")
    econf = EngineConfig(n_lanes=2, n_slots=6, max_len=48, **layout_kw)
    replicas = build_replicas(cfg, econf, 2)
    params = replicas[0].engine.params
    router = Router(replicas)
    lams = {
        f"t{i}": random_lambda(jax.random.PRNGKey(i), params, 0.2)
        for i in (1, 2, 3)
    }
    router.add_tenants(lams)
    rng = np.random.default_rng(5)
    jobs = [
        (f"t{1 + i % 3}",
         rng.integers(2, cfg.vocab_size, size=P).astype(np.int32), G)
        for i, (P, G) in enumerate(
            [(17, 4), (9, 3), (24, 5), (12, 4), (20, 3), (8, 2)])
    ]
    routed = [router.submit(t, p, g) for t, p, g in jobs]
    router.run()

    ref_eng = MultiTenantEngine(cfg, econf, params=params)
    ref_eng.add_tenants(lams)
    refs = [ref_eng.submit(t, p, g) for t, p, g in jobs]
    ref_eng.run()
    for r, ref in zip(routed, refs):
        assert r.finished, r
        assert r.tokens == ref.tokens, (
            f"routed {r} diverged from the single-engine reference"
        )


# ---------------------------------------------------------------------------
# spillover + cross-replica prefix import
# ---------------------------------------------------------------------------


def test_spillover_imports_prefix_from_home_replica():
    """A spilled request costs one block-ship, not a re-prefill: the home
    replica's cached prompt prefix is shipped into the spill target before
    submission, and the spilled output still matches the primary's."""
    cfg = get_reduced("smollm-135m")
    econf = _paged(n_lanes=1, n_slots=4)
    replicas = build_replicas(cfg, econf, 2)
    params = replicas[0].engine.params
    router = Router(replicas, spill_threshold=0)  # any load gap spills
    lam = random_lambda(jax.random.PRNGKey(1), params, 0.2)
    router.add_tenant("fam", lam)
    home = router.owner_of(router.digest("fam"))
    sibling = next(r for r in router.replicas if r is not home)
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, cfg.vocab_size, size=24).astype(np.int32)

    first = router.submit("fam", prompt, 3)
    router.run()  # home prefills and caches the 3 full prompt blocks
    assert first.replica is home and first.finished
    r_primary = router.submit("fam", prompt, 3)   # loads equal → primary
    r_spill = router.submit("fam", prompt, 3)     # home 1 deep → spills
    assert r_primary.replica is home
    assert r_spill.replica is sibling
    stats = router.transport.stats()
    assert stats["shipments"].get("prefix", 0) == 1, stats
    assert stats["bytes"]["prefix"] > 0
    assert len(sibling.engine.prefix_cache) >= 3, (
        "spill target did not adopt the shipped prefix blocks"
    )
    router.run()
    assert r_spill.finished and r_spill.tokens == r_primary.tokens
    assert 0.0 < router.placement_hit_rate() < 1.0  # the spill was counted


# ---------------------------------------------------------------------------
# replica failure: orphans re-place on survivors and finish identically
# ---------------------------------------------------------------------------


def test_replica_failure_replaces_and_finishes_identically():
    cfg = get_reduced("smollm-135m")
    econf = _paged()
    replicas = build_replicas(cfg, econf, 3)
    params = replicas[0].engine.params
    router = Router(replicas)
    lams = {
        f"t{i}": random_lambda(jax.random.PRNGKey(i), params, 0.2)
        for i in (1, 2)
    }
    router.add_tenants(lams)
    rng = np.random.default_rng(2)
    prompts = {
        t: rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
        for t in lams
    }
    routed = [router.submit(t, prompts[t], 6) for t in lams for _ in range(2)]
    for _ in range(2):
        router.step()  # mid-flight: nothing can have finished (gen=6)
    victim = routed[0].replica
    orphans = [r for r in routed if r.replica is victim and not r.finished]
    assert orphans, "victim replica carried no work — test setup broke"
    assert router.kill_replica(victim.replica_id) == len(orphans)
    assert router.kill_replica(victim.replica_id) == 0  # idempotent
    router.run()

    ref_eng = MultiTenantEngine(cfg, econf, params=params)
    ref_eng.add_tenants(lams)
    refs = [ref_eng.submit(t, prompts[t], 6) for t in lams for _ in range(2)]
    ref_eng.run()
    for r, ref in zip(routed, refs):
        assert r.finished and r.replica.alive, r
        assert r.tokens == ref.tokens, (
            f"failover changed the output of {r} vs the reference"
        )
    for r in orphans:
        assert r.placements == 2, "orphan was not re-placed exactly once"
    snap = router.registry.snapshot()["router_placements_total"]["series"]
    failovers = sum(
        s["value"] for s in snap if s["labels"]["outcome"] == "failover")
    assert failovers == len(orphans)
    m = router.metrics()
    assert m["replicas"][victim.name]["alive"] is False
    assert all(m["replicas"][r.name]["alive"] for r in router.replicas
               if r is not victim)


# ---------------------------------------------------------------------------
# disaggregation: prefill replica → decode replica, bit-identical, zero
# prompt recompute on the decode side
# ---------------------------------------------------------------------------


def test_disaggregated_handoff_bit_identical_zero_recompute():
    cfg = get_reduced("smollm-135m")
    econf = _paged(n_slots=4, max_len=64, collect_logits=True)
    replicas = build_replicas(cfg, econf, 2)
    params = replicas[0].engine.params
    router = Router(replicas, disaggregate=True)
    assert [r.role for r in router.replicas] == ["prefill", "decode"]
    lam = random_lambda(jax.random.PRNGKey(1), params, 0.2)
    router.add_tenant("fam", lam)
    rng = np.random.default_rng(4)
    prompt = rng.integers(2, cfg.vocab_size, size=24).astype(np.int32)
    routed = [router.submit("fam", prompt, 5) for _ in range(2)]
    router.run()

    eng = MultiTenantEngine(cfg, econf, params=params)
    eng.add_tenant("fam", lam)
    ref = eng.submit("fam", prompt, 5)
    eng.run()
    decode_rep = router.replicas[1]
    for r in routed:
        assert r.finished and r.replica is decode_rep, r
        assert r.placements == 2 and r.phase == "decode"
        assert r.tokens == ref.tokens, (
            f"disaggregated tokens {r.tokens} != monolithic {ref.tokens}"
        )
        # the first emitted logits row is the very row the prefill replica
        # committed — the whole sequence must be bit-identical
        np.testing.assert_array_equal(
            np.stack(r.engine_req.logits), np.stack(ref.logits))
    assert decode_rep.engine.prefill_compilations == 0, (
        "decode replica compiled a prefill bucket — the handoff recomputed "
        "the prompt"
    )
    stats = router.transport.stats()
    assert stats["shipments"].get("prefill", 0) == len(routed), stats
    snap = router.registry.snapshot()["router_placements_total"]["series"]
    handoffs = sum(
        s["value"] for s in snap if s["labels"]["outcome"] == "handoff")
    assert handoffs == len(routed)


# ---------------------------------------------------------------------------
# device placement: one replica per device, never stacked on an accelerator
# ---------------------------------------------------------------------------


def test_engine_device_commits_params_cache_and_lam_tables():
    cfg = get_reduced("smollm-135m")
    dev = jax.devices()[0]
    eng = MultiTenantEngine(cfg, _paged(), device=dev)
    leaves = jax.tree_util.tree_leaves(
        (eng.params, eng.cache, eng.lam_store.tables)
    )
    assert all(leaf.committed and leaf.devices() == {dev} for leaf in leaves)
    with pytest.raises(ValueError, match="mesh"):
        MultiTenantEngine(cfg, EngineConfig(shard_lam=True), device=dev)


class _FakeTpu:
    platform = "tpu"


def test_replica_groups_refuse_stacking_on_an_accelerator(monkeypatch):
    """Fewer chips than replicas would stack replicas on one chip and hide
    that the host lacks the chips; only the CPU time-shares."""
    from repro.sharding import replica_device_groups

    chips = [_FakeTpu()]
    monkeypatch.setattr(jax, "devices", lambda: chips)
    with pytest.raises(ValueError, match="4 replicas need 4 tpu devices"):
        replica_device_groups(4)
    assert replica_device_groups(1) == [chips]


_FOUR_DEVICE_SCRIPT = """
import jax, numpy as np
from repro.configs import get_reduced
from repro.serving import EngineConfig, MultiTenantEngine, Router, build_replicas
from repro.launch.serve_multi import tenant_lambdas, tenant_prompts
devs = jax.devices()
assert len(devs) == 4, devs
cfg = get_reduced("smollm-135m")
econf = EngineConfig(layout="paged", n_lanes=2, n_slots=9, max_len=32, block_size=8)
one = MultiTenantEngine(cfg, econf, device=devs[0])
lams = tenant_lambdas(one.params, 8, 0, 0.3)
one.add_tenants({t: l for t, l in lams.items() if t != "__base__"})
prompts = tenant_prompts(lams, cfg.vocab_size, 9, 0)
want = {r.tenant: r.tokens for r in
        [one.submit(t, p, 6) for t, p in prompts.items()]}
one.run()
want = {t: list(v) for t, v in want.items()}
reps = build_replicas(cfg, econf, 4, params=one.params)
for i, rep in enumerate(reps):
    e = rep.engine
    leaves = jax.tree_util.tree_leaves((e.params, e.cache, e.lam_store.tables))
    assert {d for l in leaves for d in l.devices()} == {devs[i]}, i
router = Router(reps, telemetry=False)
router.add_tenants(lams)
routed = {t: router.submit(t, p, 6) for t, p in prompts.items()}
router.run()
assert {t: r.tokens for t, r in routed.items()} == want
assert sum(r.engine.decoded_tokens > 0 for r in reps) >= 2
print("FOUR_REPLICAS_OK")
"""


def test_four_replicas_each_on_own_device():
    """Four virtual CPU devices: replica i's params, KV pool and λ tables
    sit on device i only, and routed tokens equal one engine's."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICE_SCRIPT], cwd=root,
                       env=env, capture_output=True, text=True, timeout=600)
    assert "FOUR_REPLICAS_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
