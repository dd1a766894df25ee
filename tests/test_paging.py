"""Paged KV cache: block allocator properties (refcounts, CoW fork), the
prefix cache, the paged decode-attention kernel vs its XLA gather oracle,
paged-vs-dense engine equivalence, shared-prefix vs unshared bit-equality,
lazy growth, and pool-exhaustion/preemption behavior."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.configs import get_reduced
from repro.kernels import ref
from repro.kernels.paged_attention import paged_decode_attention_kernel
from repro.serving import (
    BASE_TENANT,
    BlockAllocator,
    EngineConfig,
    MultiTenantEngine,
    PoolExhausted,
    PrefixCache,
    base_lambda,
    random_lambda,
    reference_decode,
)

KS = jax.random.split(jax.random.PRNGKey(7), 8)


# ---------------------------------------------------------------------------
# block allocator
# ---------------------------------------------------------------------------


def test_allocator_basics_and_trash_block():
    al = BlockAllocator(n_blocks=5, block_size=8)
    assert al.capacity == 4 and al.n_free == 4
    assert al.blocks_for(0) == 0
    assert al.blocks_for(1) == 1
    assert al.blocks_for(8) == 1
    assert al.blocks_for(9) == 2
    a = al.alloc(2)
    b = al.alloc(2)
    assert 0 not in a + b, "block 0 is the reserved trash block"
    assert len(set(a + b)) == 4 and al.n_free == 0
    with pytest.raises(PoolExhausted):
        al.alloc(1)
    al.free(a)
    assert al.n_free == 2
    c = al.alloc(2)
    assert set(c) == set(a), "freed blocks are reused"


def test_allocator_double_free_and_trash_free_raise():
    al = BlockAllocator(n_blocks=4, block_size=4)
    ids = al.alloc(1)
    al.free(ids)
    with pytest.raises(ValueError):
        al.free(ids)  # double free
    with pytest.raises(ValueError):
        al.free([0])  # trash block is never allocated
    with pytest.raises(ValueError):
        al.alloc(-1)


@settings(max_examples=40, deadline=None)
@given(n_blocks=st.integers(2, 24), seed=st.integers(0, 10_000))
def test_allocator_random_traffic_conserves_blocks(n_blocks, seed):
    """Property: any interleaving of allocs/frees never double-hands a
    block, never exceeds capacity, and drains back to a full free list."""
    rng = np.random.default_rng(seed)
    al = BlockAllocator(n_blocks=n_blocks, block_size=8)
    live = []
    for _ in range(50):
        if live and rng.random() < 0.4:
            al.free(live.pop(rng.integers(len(live))))
        else:
            n = int(rng.integers(0, max(al.capacity // 2, 1) + 1))
            try:
                ids = al.alloc(n)
            except PoolExhausted:
                assert n > al.n_free
                continue
            assert len(ids) == n and 0 not in ids
            live.append(ids)
        flat = [b for ids in live for b in ids]
        assert len(flat) == len(set(flat)), "block handed out twice"
        assert len(flat) + al.n_free == al.capacity, "blocks leaked"
    for ids in live:
        al.free(ids)
    assert al.n_free == al.capacity


def test_allocator_refcounts_and_fork():
    al = BlockAllocator(n_blocks=5, block_size=8)
    [b] = al.alloc(1)
    assert al.ref_count(b) == 1 and not al.is_shared(b)
    al.incref(b)
    assert al.ref_count(b) == 2 and al.is_shared(b)
    assert not al.decref(b), "shared block must survive one decref"
    assert al.ref_count(b) == 1
    with pytest.raises(ValueError):
        al.fork(b)  # fork of an unshared block is a bug
    al.incref(b)
    new = al.fork(b)  # transfers one owner's ref to a private copy
    assert new != b and al.ref_count(new) == 1 and al.ref_count(b) == 1
    with pytest.raises(ValueError):
        al.incref(0)  # trash block never shared
    with pytest.raises(ValueError):
        al.incref(new + 1 if new + 1 < al.n_blocks else 1)  # free block
    al.free([b, new])
    assert al.n_free == al.capacity


@settings(max_examples=40, deadline=None)
@given(n_blocks=st.integers(2, 24), seed=st.integers(0, 10_000))
def test_allocator_refcount_traffic_conserves_blocks(n_blocks, seed):
    """Property: any interleaving of alloc/incref/decref/fork keeps every
    live block uniquely owned, never hands out block 0, and drains back to
    a full free list once every reference is dropped."""
    rng = np.random.default_rng(seed)
    al = BlockAllocator(n_blocks=n_blocks, block_size=8)
    refs = {}  # block → expected refcount
    for _ in range(80):
        p = rng.random()
        if refs and p < 0.25:
            b = list(refs)[rng.integers(len(refs))]
            refs[b] += 1
            al.incref(b)
        elif refs and p < 0.5:
            b = list(refs)[rng.integers(len(refs))]
            freed = al.decref(b)
            refs[b] -= 1
            assert freed == (refs[b] == 0)
            if not refs[b]:
                del refs[b]
        elif refs and p < 0.6:
            shared = [b for b, n in refs.items() if n > 1]
            if shared:
                b = shared[rng.integers(len(shared))]
                try:
                    new = al.fork(b)
                except PoolExhausted:
                    assert al.n_free == 0
                    continue
                refs[b] -= 1
                refs[new] = 1
        else:
            n = int(rng.integers(0, max(al.capacity // 2, 1) + 1))
            try:
                ids = al.alloc(n)
            except PoolExhausted:
                assert n > al.n_free
                continue
            assert 0 not in ids and len(set(ids)) == n
            for b in ids:
                assert b not in refs, "block handed out twice"
                refs[b] = 1
        for b, n in refs.items():
            assert al.ref_count(b) == n
        assert len(refs) + al.n_free == al.capacity, "blocks leaked"
    for b, n in list(refs.items()):
        for _ in range(n):
            al.decref(b)
    assert al.n_free == al.capacity


def test_prefix_cache_match_insert_evict():
    al = BlockAllocator(n_blocks=9, block_size=4)
    pc = PrefixCache(al)
    fam = b"family-0"
    toks = np.arange(2, 12, dtype=np.int32)  # 10 tokens → 2 full blocks
    ids = al.alloc(3)  # 2 full + 1 tail
    assert pc.match(fam, toks) == []
    pc.insert(fam, toks, ids)
    assert len(pc) == 2, "only full blocks are cached, never the tail"
    assert al.ref_count(ids[0]) == al.ref_count(ids[1]) == 2  # cache-owned
    assert al.ref_count(ids[2]) == 1
    assert pc.match(fam, toks) == ids[:2]
    # longest-chain semantics: a prompt sharing only the first block
    other = toks.copy()
    other[5] = 99
    assert pc.match(fam, other) == ids[:1]
    # family isolation: a different λ digest shares nothing
    assert pc.match(b"family-1", toks) == []
    # retire the lane; cache keeps the full blocks alive
    al.free(ids)
    assert al.n_free == al.capacity - 2
    assert pc.match(fam, toks) == ids[:2]
    # eviction LRU-first returns blocks to the pool
    assert pc.evict_one() and pc.evict_one()
    assert len(pc) == 0 and al.n_free == al.capacity
    assert pc.match(fam, toks) == []


# ---------------------------------------------------------------------------
# paged decode-attention kernel vs oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_gather_ref(dtype):
    B, H, KV, dh = 3, 8, 2, 64
    n_blocks, bs, mb = 11, 16, 4
    q = (jax.random.normal(KS[0], (B, H, dh)) * 0.5).astype(dtype)
    kp = (jax.random.normal(KS[1], (n_blocks, bs, KV, dh)) * 0.5).astype(dtype)
    vp = (jax.random.normal(KS[2], (n_blocks, bs, KV, dh)) * 0.5).astype(dtype)
    tbl = jax.random.randint(KS[3], (B, mb), 0, n_blocks)
    lens = jnp.asarray([1, 37, 64], jnp.int32)
    o = paged_decode_attention_kernel(q, kp, vp, tbl, lens, interpret=True)
    r = ref.paged_decode_attention_ref(q, kp, vp, tbl, lens)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(r, np.float32), **tol
    )


def test_paged_ref_matches_dense_ref_on_identity_layout():
    """With an identity block table the paged oracle must reproduce the
    dense decode oracle exactly (same positions, same masking)."""
    B, H, KV, dh = 2, 4, 2, 32
    bs, mb = 8, 4
    n_blocks = mb  # blocks 0..mb-1 laid out contiguously
    kp = jax.random.normal(KS[4], (n_blocks, bs, KV, dh), jnp.float32)
    vp = jax.random.normal(KS[5], (n_blocks, bs, KV, dh), jnp.float32)
    q = jax.random.normal(KS[6], (B, H, dh), jnp.float32)
    tbl = jnp.tile(jnp.arange(mb)[None], (B, 1))
    dense_k = jnp.tile(kp.reshape(1, mb * bs, KV, dh), (B, 1, 1, 1))
    dense_v = jnp.tile(vp.reshape(1, mb * bs, KV, dh), (B, 1, 1, 1))
    for length in (1, 13, mb * bs):
        o_paged = ref.paged_decode_attention_ref(
            q, kp, vp, tbl, jnp.full((B,), length, jnp.int32)
        )
        o_dense = ref.decode_attention_ref(q, dense_k, dense_v, length)
        np.testing.assert_allclose(
            np.asarray(o_paged), np.asarray(o_dense), atol=1e-6, rtol=1e-6
        )


def test_paged_kernel_ignores_trash_and_stale_blocks():
    """Entries past ``length`` (padding → trash block 0, stale ids) must not
    leak into the output: poisoning them leaves the result unchanged."""
    B, H, KV, dh = 1, 4, 1, 32
    n_blocks, bs, mb = 6, 8, 3
    q = jax.random.normal(KS[0], (B, H, dh), jnp.float32)
    kp = jax.random.normal(KS[1], (n_blocks, bs, KV, dh), jnp.float32)
    vp = jax.random.normal(KS[2], (n_blocks, bs, KV, dh), jnp.float32)
    tbl = jnp.asarray([[2, 4, 0]], jnp.int32)  # last entry = trash
    lens = jnp.asarray([11], jnp.int32)  # only blocks 0..1 + 3 positions
    base = paged_decode_attention_kernel(q, kp, vp, tbl, lens, interpret=True)
    kp_p = kp.at[0].set(1e4).at[4, 5:].set(-1e4)  # poison trash + masked tail
    vp_p = vp.at[0].set(1e4).at[4, 5:].set(-1e4)
    poisoned = paged_decode_attention_kernel(q, kp_p, vp_p, tbl, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(base), np.asarray(poisoned), atol=1e-6)


# ---------------------------------------------------------------------------
# engine: paged vs dense end-to-end
# ---------------------------------------------------------------------------


def _run_engine(cfg, paged, specs, rng_seed=3, **kw):
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged" if paged else "oracle_dense", n_lanes=2, n_slots=4,
            max_len=48, collect_logits=True, block_size=8, **kw,
        ),
    )
    lams = {BASE_TENANT: base_lambda(eng.params)}
    for i in (1, 2):
        t = f"t{i}"
        lams[t] = random_lambda(jax.random.PRNGKey(i), eng.params, scale=0.3)
        eng.add_tenant(t, lams[t])
    rng = np.random.default_rng(rng_seed)
    reqs = {}
    for t, P, G in specs:
        # P: a prompt length, or the prompt itself (shared prefixes)
        prompt = (
            P if isinstance(P, np.ndarray)
            else rng.integers(2, cfg.vocab_size, size=P).astype(np.int32)
        )
        r = eng.submit(t, prompt, G)
        reqs[r.uid] = (t, prompt, G)
    done = eng.run()
    return eng, reqs, lams, done


SPECS = [(BASE_TENANT, 6, 4), ("t1", 9, 5), ("t2", 7, 3), ("t1", 13, 4)]
_PRE = np.random.default_rng(17).integers(2, 256, size=16).astype(np.int32)
# Every way the paged pool is written: monolithic prefill (P ≤ 16), chunked
# prefill (P > 2 chunks), a prefix-cache hit (t1's 2-block prefix again,
# chunked past the cached blocks), decode appends across block boundaries,
# and lane resets at retirement, two lanes at a time.
MIXED_SPECS = [
    ("t1", np.concatenate([_PRE, np.arange(3, 7, dtype=np.int32)]), 6),
    (BASE_TENANT, 35, 6),
    ("t2", 9, 4),
    ("t1", np.concatenate([_PRE, np.arange(9, 14, dtype=np.int32)]), 5),
    (BASE_TENANT, 13, 5),
]


def test_engine_paged_matches_dense_tokens_and_logits():
    """Mixed tenants × mixed prompt lengths × lane reuse: the paged engine
    must be token- and logit-identical to the dense per-lane engine."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    _, dense_reqs, _, dense_done = _run_engine(cfg, paged=False, specs=SPECS)
    paged_eng, paged_reqs, _, paged_done = _run_engine(cfg, paged=True, specs=SPECS)
    assert dense_done.keys() == paged_done.keys() == dense_reqs.keys()
    for uid in dense_done:
        rd, rp = dense_done[uid], paged_done[uid]
        assert rd.tokens == rp.tokens, f"uid={uid}"
        np.testing.assert_array_equal(np.stack(rd.logits), np.stack(rp.logits))
    # pool fully drained back to the free list
    assert paged_eng.allocator.n_free == paged_eng.allocator.capacity

    # the pool written in place by every path at once (donated steps, carried
    # pools): chunked and monolithic prefill, a prefix hit, appends, resets
    _, _, _, dense_done = _run_engine(cfg, paged=False, specs=MIXED_SPECS)
    eng, _, _, paged_done = _run_engine(
        cfg, paged=True, specs=MIXED_SPECS, share_prefix=True, prefill_chunk=16
    )
    assert dense_done.keys() == paged_done.keys()
    for uid in dense_done:
        assert dense_done[uid].tokens == paged_done[uid].tokens, f"uid={uid}"
    snap = eng.metrics()
    assert snap["serve_prefill_chunk_ms"]["series"][0]["count"] >= 3
    assert eng.prefix_cache.hits == 2
    assert eng.prefill_compilations >= 2  # monolithic buckets ran too
    eng.release_prefix_cache()
    assert eng.allocator.n_free == eng.allocator.capacity


def _record_caches(eng, names, arg=1):
    """Wrap the engine programs ``names`` so each call's ``cache`` argument
    (positional ``arg``) is kept: (program, cache) in call order."""
    seen = []
    for name in names:
        def call(*args, _f=getattr(eng, name), _name=name):
            seen.append((_name, args[arg]))
            return _f(*args)
        setattr(eng, name, call)
    return seen


def _deleted(cache):
    """The cache was donated: every leaf's buffer is gone."""
    return all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(cache))


def test_engine_model_steps_donate_the_cache():
    """Decode and paged prefill (whole-prompt and chunked) donate the cache
    they are given, so one pool is live: the tree each of them was handed
    is deleted once the step is done.  A speculative step's draft and
    verify do not donate (draft rollback is the untouched input)."""
    cfg = get_reduced("smollm-135m")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=2, n_slots=2, max_len=48, block_size=8,
            prefill_chunk=8,
        ),
    )
    donating = ("_decode", "_prefill_paged", "_prefill_chunk", "_prefill_chunk_final")
    seen = _record_caches(eng, donating)
    eng.submit(BASE_TENANT, np.arange(2, 22, dtype=np.int32), 6)  # chunked
    eng.submit(BASE_TENANT, np.arange(2, 7, dtype=np.int32), 6)  # whole
    while eng.scheduler.has_work:
        n = len(seen)
        eng.step()
        assert all(_deleted(c) for _, c in seen[n:])
        assert not _deleted(eng.cache)
    assert {name for name, _ in seen} == set(donating)

    spec = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=2, n_slots=2, max_len=48, block_size=8,
            speculate_k=2,
        ),
    )
    seen = _record_caches(spec, ("_draft", "_verify"))
    spec.submit(BASE_TENANT, np.arange(2, 7, dtype=np.int32), 8)
    spec.run()
    assert {name for name, _ in seen} == {"_draft", "_verify"}
    assert not any(_deleted(c) for _, c in seen)


def test_engine_kv_pool_programs_donate_the_cache():
    """The KV pool programs donate the engine cache too, so no whole-pool
    copy is ever made and one pool is live: after each call the tree it
    was handed is deleted — block-table growth, lane resets, copy-on-write
    forks, the disaggregated-prefill import and adoption, the dense
    layout's lane restore, and the speculative offset commit."""
    cfg = get_reduced("smollm-135m")
    pool_programs = (
        "_append_block", "_reset", "_fork_block", "_import_blocks", "_adopt_lane",
    )

    def paged(**kw):
        return MultiTenantEngine(
            cfg,
            EngineConfig(
                layout="paged", n_lanes=2, n_slots=2, max_len=48, block_size=8, **kw,
            ),
        )

    prefill, decode = paged(), paged()
    seen = _record_caches(decode, pool_programs, arg=0)
    first = prefill.submit(BASE_TENANT, np.arange(2, 14, dtype=np.int32), 6)
    prefill.step()  # prefill only: the first token is committed
    payload = prefill.export_request_state(first)
    moved = decode.inject_prefilled(BASE_TENANT, first.prompt, 6, payload)
    own = decode.submit(BASE_TENANT, np.arange(2, 14, dtype=np.int32), 6)
    decode.step()  # both admitted; ``own``'s tail block (8..11) is private
    tail = decode._lane_blocks[own.lane][-1]
    decode.allocator.incref(tail)  # a second owner: the next write forks it
    done = decode.run()
    assert moved.uid in done and own.uid in done
    assert {name for name, _ in seen} == set(pool_programs)
    assert all(_deleted(c) for _, c in seen)
    decode.allocator.decref(tail)

    dense = MultiTenantEngine(
        cfg, EngineConfig(layout="oracle_dense", n_lanes=2, n_slots=2, max_len=48)
    )
    seen = _record_caches(dense, ("_restore",), arg=0)
    dense.submit(BASE_TENANT, np.arange(2, 9, dtype=np.int32), 3)
    dense.run()
    assert [name for name, _ in seen] == ["_restore"]
    assert _deleted(seen[0][1])

    spec = paged(speculate_k=2)
    seen = _record_caches(spec, ("_commit_advance",), arg=0)
    spec.submit(BASE_TENANT, np.arange(2, 7, dtype=np.int32), 8)
    spec.run()
    assert seen and all(_deleted(c) for _, c in seen)


def test_engine_paged_matches_merged_weight_reference():
    """The serve_multi correctness oracle (per-tenant λ merged into the
    weights, single-lane decode) holds under paged=True."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng, reqs, lams, done = _run_engine(cfg, paged=True, specs=SPECS[:3])
    for uid, (t, prompt, G) in reqs.items():
        req = done[uid]
        ref_toks, ref_logits = reference_decode(
            cfg, eng.params, lams[t], prompt, G, 48
        )
        assert req.tokens == ref_toks, f"uid={uid} tenant={t}"
        np.testing.assert_allclose(
            np.stack(req.logits), ref_logits, atol=1e-4, rtol=1e-4
        )


def test_engine_pool_exhaustion_defers_then_completes():
    """With a pool that holds one request at a time, admission defers the
    second request (strict FIFO) until retirement frees blocks."""
    cfg = get_reduced("smollm-135m")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=2, n_slots=3, max_len=32, block_size=8,
            n_blocks=1 + 2,  # 2 usable blocks
        ),
    )
    eng.submit(BASE_TENANT, np.arange(2, 10, dtype=np.int32), 8)  # 2 blocks
    eng.submit(BASE_TENANT, np.arange(2, 12, dtype=np.int32), 6)  # 2 blocks
    eng.step()
    # one lane busy, the other free but starved of blocks
    busy = [r is not None for r in eng.scheduler.lanes]
    assert busy.count(True) == 1 and len(eng.scheduler.queue) == 1
    assert eng.allocator.n_free == 0
    done = eng.run()
    assert sorted(len(r.tokens) for r in done.values()) == [6, 8]
    assert eng.allocator.n_free == eng.allocator.capacity


def test_engine_rejects_never_admittable_request():
    cfg = get_reduced("smollm-135m")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=1, n_slots=2, max_len=32, block_size=8,
            n_blocks=1 + 2,
        ),
    )
    with pytest.raises(ValueError):  # 24 tokens → 3 blocks > capacity 2
        eng.submit(BASE_TENANT, np.arange(2, 18, dtype=np.int32), 8)


def test_engine_paged_memory_below_dense_for_short_traffic():
    """The point of paging: pool sized to traffic beats lanes×max_len."""
    cfg = get_reduced("smollm-135m")
    dense = MultiTenantEngine(
        cfg, EngineConfig.oracle_dense(n_lanes=4, n_slots=2, max_len=256)
    )
    paged = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=4, n_slots=2, max_len=256, block_size=16,
            n_blocks=1 + 4 * 2,  # 4 lanes × 2 blocks (≤32-token requests)
        ),
    )
    assert paged.kv_cache_bytes() < dense.kv_cache_bytes()


# ---------------------------------------------------------------------------
# copy-on-write prefix sharing + lazy growth + preemption
# ---------------------------------------------------------------------------


def _run_prefix_engine(cfg, share_prefix, specs, *, lanes=2, n_blocks=None, seed=11):
    """Engine run where tenants t1/t1b share one λ checkpoint (a tenant
    *family*) and t2 is distinct; ``specs`` entries are (tenant, prompt)."""
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=lanes, n_slots=6, max_len=48,
            collect_logits=True, block_size=8, n_blocks=n_blocks,
            share_prefix=share_prefix,
        ),
    )
    fam_lam = random_lambda(jax.random.PRNGKey(1), eng.params, scale=0.3)
    eng.add_tenant("t1", fam_lam)
    eng.add_tenant("t1b", fam_lam)  # same λ bytes → same prefix family
    eng.add_tenant("t2", random_lambda(jax.random.PRNGKey(2), eng.params, scale=0.3))
    reqs = {}
    for tenant, prompt in specs:
        r = eng.submit(tenant, prompt, 4)
        reqs[r.uid] = (tenant, prompt)
    done = eng.run()
    return eng, reqs, done


def test_engine_shared_prefix_bit_identical_to_unshared():
    """Mixed tenants × shared/unshared prompts: prefix sharing must change
    block accounting only — tokens and logits stay bit-identical to the
    unshared paged engine."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    rng = np.random.default_rng(5)
    pre = rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)  # 2 full blocks
    tails = [rng.integers(2, cfg.vocab_size, size=4).astype(np.int32) for _ in range(4)]
    specs = [
        ("t1", np.concatenate([pre, tails[0]])),   # seeds the t1-family prefix
        ("t1", np.concatenate([pre, tails[1]])),   # same tenant, same prefix
        ("t1b", np.concatenate([pre, tails[2]])),  # same family, other tenant
        ("t2", np.concatenate([pre, tails[3]])),   # different λ — must NOT share
        ("t2", rng.integers(2, cfg.vocab_size, size=9).astype(np.int32)),
    ]
    _, _, base_done = _run_prefix_engine(cfg, share_prefix=False, specs=specs)
    eng, _, shared_done = _run_prefix_engine(cfg, share_prefix=True, specs=specs)
    assert base_done.keys() == shared_done.keys()
    for uid in base_done:
        assert base_done[uid].tokens == shared_done[uid].tokens, f"uid={uid}"
        np.testing.assert_array_equal(
            np.stack(base_done[uid].logits), np.stack(shared_done[uid].logits)
        )
    # the t1-family prefix (2 blocks) was reused twice; t2 shared nothing
    assert eng.prefix_cache.hits == 4
    # lanes drained; only cache-held prefix blocks remain out of the pool
    assert eng.allocator.n_in_use == eng.prefix_cache.cached_blocks
    eng.release_prefix_cache()
    assert eng.allocator.n_free == eng.allocator.capacity


def test_engine_shared_prefix_matches_merged_weight_reference():
    """Sharing must also preserve the external oracle: per-tenant merged
    weights, single-lane decode."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    rng = np.random.default_rng(9)
    pre = rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)
    specs = [("t1", np.concatenate([pre, rng.integers(2, cfg.vocab_size, size=3).astype(np.int32)]))
             for _ in range(3)]
    eng, reqs, done = _run_prefix_engine(cfg, share_prefix=True, specs=specs)
    assert eng.prefix_cache.hits > 0
    lam = {"t1": None}
    # rebuild the family λ the same way _run_prefix_engine did
    lam["t1"] = random_lambda(jax.random.PRNGKey(1), eng.params, scale=0.3)
    for uid, (tenant, prompt) in reqs.items():
        ref_toks, ref_logits = reference_decode(
            cfg, eng.params, lam[tenant], prompt, 4, 48
        )
        assert done[uid].tokens == ref_toks
        np.testing.assert_allclose(
            np.stack(done[uid].logits), ref_logits, atol=1e-4, rtol=1e-4
        )


def test_engine_shared_prefix_footprint_is_one_prefix_plus_tails():
    """The HBM point of the feature: N lanes on one prompt hold ~1× the
    prefix plus N private growth tails, not N× everything."""
    cfg = get_reduced("smollm-135m")
    lanes, bs, P, gen = 4, 8, 32, 4
    rng = np.random.default_rng(3)
    prompt = rng.integers(2, cfg.vocab_size, size=P).astype(np.int32)
    peaks = {}
    for share in (False, True):
        eng = MultiTenantEngine(
            cfg,
            EngineConfig(
                layout="paged", n_lanes=lanes, n_slots=6, max_len=64,
                block_size=bs, share_prefix=share,
            ),
        )
        fam = random_lambda(jax.random.PRNGKey(1), eng.params, scale=0.2)
        for i in range(lanes):
            eng.add_tenant(f"fam{i}", fam)  # one family, many tenants
            eng.submit(f"fam{i}", prompt, gen)
        eng.run()
        peaks[share] = eng.allocator.peak_in_use
    prefix_blocks = P // bs
    # decode writes land past the (fully cached) prompt → one growth block per lane
    assert peaks[True] == prefix_blocks + lanes
    assert peaks[False] == lanes * (prefix_blocks + 1)


def test_engine_gate_pins_matches_against_same_round_eviction():
    """Regression: request A's gate approval matches cached prefix blocks
    (need 0), then request B's gate evicts the cache in the *same* round.
    A's reservation must survive (the gate pins matched blocks at
    approval), so admission defers B instead of crashing with
    PoolExhausted escaping run()."""
    cfg = get_reduced("smollm-135m")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=2, n_slots=2, max_len=32, block_size=8,
            n_blocks=1 + 4, share_prefix=True,
        ),
    )
    rng = np.random.default_rng(2)
    shared = rng.integers(2, cfg.vocab_size, size=16).astype(np.int32)  # 2 blocks
    other = rng.integers(2, cfg.vocab_size, size=24).astype(np.int32)  # 3 blocks
    # seed the cache, drain, leaving 2 cache-only blocks out of 4
    eng.submit(BASE_TENANT, shared, 2)
    eng.run()
    assert eng.prefix_cache.cached_blocks == 2 and eng.allocator.n_free == 2
    # A: full match (need 0).  B: needs 3 — its gate evicts A's chain.
    a = eng.submit(BASE_TENANT, shared, 4)
    b = eng.submit(BASE_TENANT, other, 4)
    done = eng.run()  # must not raise
    assert len(done[a.uid].tokens) == 4 and len(done[b.uid].tokens) == 4
    eng.release_prefix_cache()
    assert eng.allocator.n_free == eng.allocator.capacity


def test_engine_lazy_growth_allocates_prompt_only():
    """Admission takes ceil(P/bs) blocks — not prompt+gen — and decode adds
    blocks one boundary at a time."""
    cfg = get_reduced("smollm-135m")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(layout="paged", n_lanes=1, n_slots=2, max_len=64, block_size=8),
    )
    eng.submit(BASE_TENANT, np.arange(2, 14, dtype=np.int32), 24)  # P=12
    eng.step()  # prefill + first decode: write pos 12 sits in the tail block
    assert eng.allocator.n_in_use == 2  # ceil(12/8), nothing reserved for gen
    while len(eng.scheduler.active()[0].tokens) < 5:
        eng.step()  # write positions 13..15 stay inside block 1
        assert eng.allocator.n_in_use == 2
    eng.step()  # write position 16 crosses into block 2
    assert eng.allocator.n_in_use == 3
    eng.run()
    assert eng.allocator.n_free == eng.allocator.capacity


def test_engine_preemption_frees_youngest_and_recovers():
    """Two lanes racing for the last block: the youngest is preempted back
    to the queue (blocks freed), the oldest finishes, the victim re-runs
    deterministically — outputs match an uncontended pool bit-for-bit."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")

    def run(n_blocks):
        eng = MultiTenantEngine(
            cfg,
            EngineConfig(
                layout="paged", n_lanes=2, n_slots=2, max_len=32,
                collect_logits=True, block_size=8, n_blocks=n_blocks,
            ),
        )
        a = eng.submit(BASE_TENANT, np.arange(2, 10, dtype=np.int32), 16)
        b = eng.submit(BASE_TENANT, np.arange(12, 20, dtype=np.int32), 16)
        done = eng.run()
        assert eng.allocator.n_free == eng.allocator.capacity
        return eng, done[a.uid], done[b.uid]

    eng_big, a_big, b_big = run(n_blocks=1 + 8)  # uncontended
    assert eng_big.preemptions == 0
    # 5 usable blocks: both requests need 3; they collide crossing pos 16
    eng, a, b = run(n_blocks=1 + 5)
    assert eng.preemptions >= 1
    assert b.preemptions >= 1 and a.preemptions == 0, "victim is the youngest"
    for got, want in ((a, a_big), (b, b_big)):
        assert got.tokens == want.tokens
        np.testing.assert_array_equal(np.stack(got.logits), np.stack(want.logits))


def test_engine_cow_fork_on_shared_write_block():
    """A lane about to decode into a block another owner holds must fork a
    private copy first — and keep producing the same tokens."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")

    def run(tamper):
        eng = MultiTenantEngine(
            cfg,
            EngineConfig(
                layout="paged", n_lanes=1, n_slots=2, max_len=32,
                collect_logits=True, block_size=8,
            ),
        )
        req = eng.submit(BASE_TENANT, np.arange(2, 14, dtype=np.int32), 6)  # P=12
        eng.step()  # admit; tail block (positions 8..11) is private
        tail = eng._lane_blocks[req.lane][-1]
        if tamper:
            eng.allocator.incref(tail)  # simulate another owner of the tail
        done = eng.run()
        return eng, req, tail, done[req.uid]

    _, _, _, clean = run(tamper=False)
    eng, req, tail, forked = run(tamper=True)
    assert eng.cow_forks == 1
    assert eng.allocator.ref_count(tail) == 1, "lane's ref moved to the copy"
    assert forked.tokens == clean.tokens
    np.testing.assert_array_equal(np.stack(forked.logits), np.stack(clean.logits))
    eng.allocator.decref(tail)
    assert eng.allocator.n_free == eng.allocator.capacity


@settings(max_examples=10, deadline=None)
@given(k=st.integers(1, 4), n_blocks=st.integers(5, 9), seed=st.integers(0, 50))
def test_engine_speculative_rollback_conserves_blocks(k, n_blocks, seed):
    """Property: whatever (draft depth, pool size, traffic) throws at the
    speculative engine — window growth, rejected-draft rollback, preemption
    under pressure — its tokens match the plain paged engine and the block
    pool drains back to a full free list (refcounts exact, nothing leaked
    to the trash table or double-freed)."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    rng = np.random.default_rng(seed)
    specs = [
        (int(rng.integers(3, 12)), int(rng.integers(4, 9)))
        for _ in range(int(rng.integers(2, 5)))
    ]

    def run(speculate_k):
        eng = MultiTenantEngine(
            cfg,
            EngineConfig(
                layout="paged", n_lanes=2, n_slots=2, max_len=24,
                block_size=8, n_blocks=n_blocks, speculate_k=speculate_k,
            ),
        )
        subs = [
            eng.submit(BASE_TENANT, rng2.integers(2, cfg.vocab_size, size=P).astype(np.int32), G)
            for rng2 in [np.random.default_rng(seed + 1)]
            for P, G in specs
        ]
        done = eng.run()
        assert eng.allocator.n_free == eng.allocator.capacity, "blocks leaked"
        return [done[r.uid].tokens for r in subs]

    assert run(k) == run(0)


# ---------------------------------------------------------------------------
# prompt-length bucketing
# ---------------------------------------------------------------------------


def test_prefill_bucketing_bounds_compilations():
    """10 requests at 10 distinct prompt lengths must share ≤4 prefill
    compilations (power-of-two buckets), not compile one prefill each."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(
        cfg, EngineConfig.oracle_dense(n_lanes=2, n_slots=2, max_len=64)
    )
    rng = np.random.default_rng(0)
    lengths = [3, 5, 6, 9, 11, 14, 17, 21, 26, 31]  # 10 distinct lengths
    for P in lengths:
        eng.submit(BASE_TENANT, rng.integers(2, cfg.vocab_size, size=P), 2)
    done = eng.run()
    assert len(done) == len(lengths)
    assert eng.prefill_compilations <= 4, eng.prefill_buckets
    # the jit cache agrees with the host-side bucket accounting
    cache_size = getattr(eng._prefill, "_cache_size", None)
    if cache_size is not None:
        assert cache_size() <= 4


def test_prefill_bucketing_preserves_logits():
    """Bucketed (padded+masked) prefill returns the same next-token logits
    as the unpadded merged-weight reference decode."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig.oracle_dense(n_lanes=1, n_slots=2, max_len=32, collect_logits=True),
    )
    prompt = np.arange(2, 13, dtype=np.int32)  # length 11 → bucket 16
    eng.submit(BASE_TENANT, prompt, 3)
    done = eng.run()
    req = next(iter(done.values()))
    ref_toks, ref_logits = reference_decode(
        cfg, eng.params, base_lambda(eng.params), prompt, 3, 32
    )
    assert req.tokens == ref_toks
    np.testing.assert_allclose(np.stack(req.logits), ref_logits, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# fused multi-block kernel: bit-identity sweep + zero-length lanes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "mb,lens",
    [
        (1, [7]),                  # single block, ragged tail
        (2, [16, 9]),              # exact block boundary + mid-block
        (4, [1, 37, 64]),          # one position / ragged / full table
        (8, [111, 64, 3, 57]),     # deep table, mixed raggedness
    ],
    ids=["1blk", "2blk", "4blk", "8blk"],
)
def test_fused_paged_kernel_bit_identical_to_ref(mb, lens):
    """The fused multi-block kernel (scalar-prefetched block-table walk,
    online softmax) must be *bit-identical* to the XLA gather oracle —
    it is the decode path of every paged engine."""
    B = len(lens)
    H, KV, dh, bs = 8, 2, 64, 16
    n_blocks = 1 + B * mb
    q = jax.random.normal(KS[0], (B, H, dh), jnp.float32) * 0.5
    kp = jax.random.normal(KS[1], (n_blocks, bs, KV, dh), jnp.float32) * 0.5
    vp = jax.random.normal(KS[2], (n_blocks, bs, KV, dh), jnp.float32) * 0.5
    tbl = jax.random.randint(KS[3], (B, mb), 1, n_blocks)
    lengths = jnp.asarray(lens, jnp.int32)
    o = paged_decode_attention_kernel(q, kp, vp, tbl, lengths, interpret=True)
    r = ref.paged_decode_attention_ref(q, kp, vp, tbl, lengths)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


def test_fused_paged_kernel_zero_length_lane_emits_zeros():
    """Idle lanes (length 0, all-trash tables) must produce finite output —
    exactly zeros — where the gather oracle softmaxes over nothing (NaN)."""
    B, H, KV, dh, bs, mb = 3, 4, 2, 32, 8, 2
    q = jax.random.normal(KS[4], (B, H, dh), jnp.float32)
    kp = jax.random.normal(KS[5], (5, bs, KV, dh), jnp.float32)
    vp = jax.random.normal(KS[6], (5, bs, KV, dh), jnp.float32)
    tbl = jnp.asarray([[1, 2], [0, 0], [3, 0]], jnp.int32)
    lens = jnp.asarray([11, 0, 5], jnp.int32)
    o = np.asarray(paged_decode_attention_kernel(q, kp, vp, tbl, lens, interpret=True))
    r = np.asarray(ref.paged_decode_attention_ref(q, kp, vp, tbl, lens))
    np.testing.assert_array_equal(o[1], 0.0)
    np.testing.assert_array_equal(o[0], r[0])
    np.testing.assert_array_equal(o[2], r[2])


# ---------------------------------------------------------------------------
# chunked prefill: bit-equality, preemption, prefix-skip
# ---------------------------------------------------------------------------


def _run_chunked(cfg, specs, *, prefill_chunk, rng_seed=3, **kw):
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=2, n_slots=4, max_len=128, block_size=16,
            collect_logits=True, prefill_chunk=prefill_chunk, **kw,
        ),
    )
    lams = {BASE_TENANT: base_lambda(eng.params)}
    lams["t1"] = random_lambda(jax.random.PRNGKey(1), eng.params, scale=0.3)
    eng.add_tenant("t1", lams["t1"])
    rng = np.random.default_rng(rng_seed)
    reqs = {}
    for t, P, G in specs:
        prompt = rng.integers(2, cfg.vocab_size, size=P).astype(np.int32)
        r = eng.submit(t, prompt, G)
        reqs[r.uid] = (t, prompt, G)
    done = eng.run()
    return eng, reqs, done


CHUNK_SPECS = [(BASE_TENANT, 37, 6), ("t1", 50, 5), ("t1", 9, 4), (BASE_TENANT, 60, 3)]


def test_chunked_prefill_bit_identical_to_monolithic():
    """Splitting admission prefill into block-aligned chunks interleaved
    with resident decode steps is a scheduling change only: every request's
    tokens AND logits must match the monolithic-prefill engine bitwise."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    _, mono_reqs, mono_done = _run_chunked(cfg, CHUNK_SPECS, prefill_chunk=None)
    eng, chunk_reqs, chunk_done = _run_chunked(cfg, CHUNK_SPECS, prefill_chunk=16)
    assert mono_done.keys() == chunk_done.keys()
    for uid in mono_done:
        assert mono_done[uid].tokens == chunk_done[uid].tokens, f"uid={uid}"
        np.testing.assert_array_equal(
            np.stack(mono_done[uid].logits), np.stack(chunk_done[uid].logits)
        )
    assert eng.allocator.n_free == eng.allocator.capacity
    # the chunk machinery actually ran, and telemetry saw it
    snap = eng.metrics()
    assert snap["serve_prefill_chunk_ms"]["series"][0]["count"] >= 2
    phases = {s["labels"]["phase"] for s in snap["serve_step_phase_ms"]["series"]}
    assert "prefill_chunk" in phases
    spans = {
        e["name"]
        for e in eng.telemetry.tracer.to_chrome()["traceEvents"]
        if e["ph"] == "X"
    }
    assert "prefill_chunk" in spans


def test_chunked_prefill_mid_chunk_preemption_recovers():
    """Block pressure while a lane is still mid-prefill must preempt it
    cleanly (chunk progress discarded, blocks freed) and re-derive its
    output exactly once re-admitted."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")

    def run(n_blocks):
        eng = MultiTenantEngine(
            cfg,
            EngineConfig(
                layout="paged", n_lanes=2, n_slots=2, max_len=64, block_size=8,
                collect_logits=True, prefill_chunk=8, n_blocks=n_blocks,
            ),
        )
        a = eng.submit(BASE_TENANT, np.arange(2, 17, dtype=np.int32), 6)  # P=15
        b = eng.submit(BASE_TENANT, np.arange(20, 52, dtype=np.int32), 4)  # P=32
        done = eng.run()
        assert eng.allocator.n_free == eng.allocator.capacity
        return eng, done[a.uid], done[b.uid]

    _, a_big, b_big = run(n_blocks=1 + 12)  # uncontended
    # 6 usable blocks: a (2) + b (4) fit, but a's growth at position 16
    # lands while b is still chunking its 32-token prompt → b preempted
    eng, a, b = run(n_blocks=1 + 6)
    assert eng.preemptions >= 1 and b.preemptions >= 1 and a.preemptions == 0
    names = b.trace.names()
    assert names.index("preempt") < names.index("prefill"), (
        "victim was not mid-prefill when preempted"
    )
    for got, want in ((a, a_big), (b, b_big)):
        assert got.tokens == want.tokens
        np.testing.assert_array_equal(np.stack(got.logits), np.stack(want.logits))


def test_chunked_prefill_skips_cached_prefix_blocks():
    """A chunked prefill over a prefix-cache hit must not recompute the
    cached blocks: chunk starts skip them (or collapse to one logits-only
    pass when the whole prompt is cached) with bit-identical outputs."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    rng = np.random.default_rng(5)
    pre = rng.integers(2, cfg.vocab_size, size=32).astype(np.int32)  # 2 blocks
    tail = rng.integers(2, cfg.vocab_size, size=5).astype(np.int32)

    def run(prefill_chunk):
        eng = MultiTenantEngine(
            cfg,
            EngineConfig(
                layout="paged", n_lanes=1, n_slots=2, max_len=64, block_size=16,
                collect_logits=True, share_prefix=True,
                prefill_chunk=prefill_chunk,
            ),
        )
        subs = []
        subs.append(eng.submit(BASE_TENANT, pre, 4))  # seeds the prefix cache
        eng.run()
        subs.append(eng.submit(BASE_TENANT, pre, 4))  # fully cached prompt
        eng.run()
        subs.append(eng.submit(BASE_TENANT, np.concatenate([pre, tail]), 4))
        eng.run()  # cached prefix + uncached ragged tail
        return eng, subs

    eng_m, mono = run(prefill_chunk=None)
    eng_c, chunked = run(prefill_chunk=16)
    assert eng_c.prefix_cache.hits == eng_m.prefix_cache.hits > 0
    for rm, rc in zip(mono, chunked):
        assert rm.tokens == rc.tokens
        np.testing.assert_array_equal(np.stack(rm.logits), np.stack(rc.logits))


# ---------------------------------------------------------------------------
# compact decode: a paged step runs only its decoding lanes
# ---------------------------------------------------------------------------

_LIVE_PRE = np.random.default_rng(23).integers(2, 256, size=16).astype(np.int32)


def _live_phases(vocab):
    """Submissions and the steps run after each: 1, then 3, then 9 lanes
    decode at once on 16 lanes (lane buckets 8, 8 and 16).  A prompt that
    files a 2-block prefix and is prefilled in chunks, block-boundary
    appends, a 35-token prompt chunked while 3 lanes decode beside it, a
    prefix hit, retirements, and six short prompts on reused lanes."""
    rng = np.random.default_rng(29)
    p = lambda n: rng.integers(2, vocab, size=n).astype(np.int32)  # noqa: E731
    return [
        ([("t1", np.concatenate([_LIVE_PRE, p(3)]), 14)], 5),
        ([(BASE_TENANT, p(35), 6), ("t2", p(9), 3), (BASE_TENANT, p(6), 6),
          ("t1", np.concatenate([_LIVE_PRE, p(5)]), 5)], 5),
        ([(t, p(n), g) for t, n, g in (
            ("t2", 5, 4), (BASE_TENANT, 12, 5), ("t1", 7, 3), ("t2", 13, 6),
            (BASE_TENANT, 6, 4), ("t1", 10, 5))], 0),
    ]


def _run_live_phases(cfg, layout, watch=None, **kw):
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout=layout, n_lanes=16, n_slots=4, max_len=64, block_size=8,
            collect_logits=True, **kw,
        ),
    )
    lams = {BASE_TENANT: base_lambda(eng.params)}
    for i in (1, 2):
        lams[f"t{i}"] = random_lambda(jax.random.PRNGKey(i), eng.params, scale=0.3)
        eng.add_tenant(f"t{i}", lams[f"t{i}"])
    if watch is not None:
        eng._decode = watch(eng, eng._decode)
    reqs = []
    for subs, steps in _live_phases(cfg.vocab_size):
        reqs += [(eng.submit(t, prompt, g), t, prompt) for t, prompt, g in subs]
        for _ in range(steps):
            eng.step()
    eng.run()
    return eng, reqs, lams


def _lanes_of(cache, lanes):
    """Positions, block-table rows and offsets of ``lanes``, on the host."""
    a = cache["layers"]["attn"]
    return (np.asarray(cache["pos"])[lanes], np.asarray(a["block_tbl"])[:, lanes],
            np.asarray(a["idx"])[:, lanes])


def test_engine_compact_decode_matches_dense_and_leaves_other_lanes(monkeypatch):
    """A paged step over 1, 3 and 9 of 16 lanes runs a lane bucket of 8, 8
    and 16 rows.  Every request's tokens equal the dense per-lane engine's;
    its logits equal, bit for bit, those of the same paged engine stepping
    all 16 lanes, and the merged-weight reference's at 1e-4.  A compact
    step leaves every lane outside its rows — free, or mid-way through a
    chunked prefill — bitwise as it was."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    seen = []  # (decoding lanes, rows run, mid-chunk lanes left out)

    def watch(eng, decode):
        def step(view, cache, tok, seg, rows, ab):
            lanes = [r.lane for r in eng.scheduler.active()
                     if r.uid not in eng._prefilling]
            if rows is None:
                seen.append((len(lanes), tok.shape[0], 0))
                return decode(view, cache, tok, seg, rows, ab)
            out = [i for i in range(eng.n_lanes) if i not in lanes]
            chunking = {r.lane for r in eng.scheduler.active()} - set(lanes)
            before = _lanes_of(cache, out)  # read before the cache is donated
            logits, new = decode(view, cache, tok, seg, rows, ab)
            for b, c in zip(before, _lanes_of(new, out)):
                np.testing.assert_array_equal(b, c)
            assert np.asarray(rows)[: len(lanes)].tolist() == lanes
            assert (np.asarray(rows)[len(lanes):] == eng.n_lanes).all()
            seen.append((len(lanes), tok.shape[0], len(chunking)))
            return logits, new
        return step

    eng, reqs, lams = _run_live_phases(
        cfg, "paged", watch, share_prefix=True, prefill_chunk=8
    )
    assert {(1, 8), (3, 8), (9, 16)} <= {(n, rows) for n, rows, _ in seen}
    assert any(rows < 16 and chunking for _, rows, chunking in seen)
    assert eng.prefix_cache.hits == 2
    assert eng.metrics()["serve_decode_rows_total"]["series"][0]["value"] == sum(
        rows for _, rows, _ in seen
    )
    eng.release_prefix_cache()
    assert eng.allocator.n_free == eng.allocator.capacity

    _, dense, _ = _run_live_phases(cfg, "oracle_dense")
    from repro.serving import engine as engine_mod

    monkeypatch.setattr(engine_mod, "_MIN_DECODE_ROWS", 16)  # every step full
    _, full, _ = _run_live_phases(cfg, "paged", share_prefix=True, prefill_chunk=8)
    for (r, t, prompt), (d, _, _), (f, _, _) in zip(reqs, dense, full):
        assert r.tokens == d.tokens == f.tokens, f"uid={r.uid}"
        np.testing.assert_array_equal(np.stack(r.logits), np.stack(f.logits))
        ref_toks, ref_logits = reference_decode(
            cfg, eng.params, lams[t], prompt, len(r.tokens), 64
        )
        assert r.tokens == ref_toks, f"uid={r.uid}"
        np.testing.assert_allclose(
            np.stack(r.logits), ref_logits, atol=1e-4, rtol=1e-4
        )
