"""Multi-tenant serving subsystem: λ-store eviction/hot-swap, scheduler
admission & batch composition, the batched multi-λ kernel vs the XLA take
reference, and the engine vs per-tenant merged-weight decodes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.kernels import ops, ref
from repro.serving import (
    BASE_TENANT,
    ContinuousBatchScheduler,
    EngineConfig,
    LamStore,
    MultiTenantEngine,
    base_lambda,
    random_lambda,
    reference_decode,
)

KS = jax.random.split(jax.random.PRNGKey(0), 8)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

SHAPES = {("attn", "wq"): (3, 8), ("mlp", "w_up"): (3, 8)}


def _lam_tree(value):
    out = {}
    for (mod, proj), shape in SHAPES.items():
        out.setdefault(mod, {})[proj] = jnp.full(shape, value, jnp.float32)
    return out


def test_registry_slot0_and_allocation():
    reg = LamStore(SHAPES, n_slots=4)
    assert BASE_TENANT in reg and reg.lookup(BASE_TENANT) == 0
    s1 = reg.register("a", _lam_tree(1.0))
    s2 = reg.register("b", _lam_tree(2.0))
    assert {s1, s2}.isdisjoint({0}) and s1 != s2
    # table rows hold the right λ; unused slots stay zero (base-safe)
    tab = np.asarray(reg.tables[("attn", "wq")])
    assert np.all(tab[0] == 0.0) and np.all(tab[s1] == 1.0) and np.all(tab[s2] == 2.0)
    free = ({1, 2, 3} - {s1, s2}).pop()
    assert np.all(tab[free] == 0.0)


def test_registry_lru_eviction_and_pinning():
    reg = LamStore(SHAPES, n_slots=3)  # slots 1,2 usable
    sa = reg.register("a", _lam_tree(1.0))
    sb = reg.register("b", _lam_tree(2.0))
    reg.lookup("a")  # touch: b is now LRU
    sc = reg.register("c", _lam_tree(3.0))
    assert "b" not in reg and sc == sb  # b evicted, its slot reused
    assert np.all(np.asarray(reg.tables[("attn", "wq")])[sc] == 3.0)
    # pinned tenants survive eviction pressure
    reg.pin("a")
    sd = reg.register("d", _lam_tree(4.0))  # evicts c (only unpinned)
    assert "c" not in reg and "a" in reg and sd == sc
    reg.pin("d")
    with pytest.raises(RuntimeError):
        reg.register("e", _lam_tree(5.0))  # everything pinned
    reg.unpin("a")
    assert reg.register("e", _lam_tree(5.0)) == sa


def test_registry_hot_swap_and_install():
    reg = LamStore(SHAPES, n_slots=3)
    s = reg.register("a", _lam_tree(1.0))
    v0 = reg.version
    assert reg.register("a", _lam_tree(9.0)) == s  # hot-swap, same slot
    assert reg.version > v0
    assert np.all(np.asarray(reg.tables[("attn", "wq")])[s] == 9.0)
    # install produces (lead, n_slots, cap) λ leaves sharing B/A with input
    B = jnp.ones((3, 4, 8))
    params = {"groups": {"adapters": {
        "attn": {"wq": {"B": B, "A": B, "lam": jnp.zeros((3, 8)), "ranks": jnp.ones((3,), jnp.int32)}},
        "mlp": {"w_up": {"B": B, "A": B, "lam": jnp.zeros((3, 8)), "ranks": jnp.ones((3,), jnp.int32)}},
    }}}
    view = reg.install(params)
    leaf = view["groups"]["adapters"]["attn"]["wq"]
    assert leaf["lam"].shape == (3, 3, 8)  # (n_stack, n_slots, cap)
    assert leaf["B"] is B  # factors shared, not copied
    np.testing.assert_array_equal(np.asarray(leaf["lam"][:, s]), 9.0)


def test_registry_hot_swap_pinned_raises():
    reg = LamStore(SHAPES, n_slots=3)
    s = reg.register("a", _lam_tree(1.0))
    reg.pin("a")
    with pytest.raises(RuntimeError):  # would mix adapters mid-generation
        reg.register("a", _lam_tree(2.0))
    assert np.all(np.asarray(reg.tables[("attn", "wq")])[s] == 1.0)
    reg.unpin("a")
    assert reg.register("a", _lam_tree(2.0)) == s


def test_registry_base_slot_immutable():
    reg = LamStore(SHAPES, n_slots=2)
    with pytest.raises(ValueError):
        reg.register(BASE_TENANT, _lam_tree(1.0))
    with pytest.raises(ValueError):
        reg.evict(BASE_TENANT)


def test_registry_explicit_evict_scrubs_slot():
    reg = LamStore(SHAPES, n_slots=3)
    s = reg.register("a", _lam_tree(7.0))
    reg.evict("a")
    assert "a" not in reg
    assert np.all(np.asarray(reg.tables[("attn", "wq")])[s] == 0.0)
    assert reg.register("b", _lam_tree(1.0)) == s  # slot back on free list


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------


def test_scheduler_admission_and_composition():
    sch = ContinuousBatchScheduler(n_lanes=2)
    r0 = sch.submit("a", np.arange(4), 3)
    r1 = sch.submit("b", np.arange(5), 2)
    r2 = sch.submit("c", np.arange(6), 1)
    admitted = sch.admit()
    assert [r.uid for r in admitted] == [r0.uid, r1.uid]  # FIFO
    assert {r.lane for r in admitted} == {0, 1}
    assert sch.admit() == []  # lanes full; r2 waits
    r0.slot, r1.slot = 3, 1
    np.testing.assert_array_equal(sch.batch_composition(), [3, 1])
    # finishing a lane admits the next queued request into that lane
    r0.tokens.extend([0, 0, 0])
    sch.finish(r0)
    assert sch.batch_composition()[0] == 0  # idle lane → base slot
    nxt = sch.admit()
    assert [r.uid for r in nxt] == [r2.uid] and nxt[0].lane == 0
    assert sch.has_work
    sch.finish(r1)
    sch.finish(r2)
    assert not sch.has_work


# ---------------------------------------------------------------------------
# qrlora_bgmv kernel (interpret mode) vs XLA take reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "M,K,N,r,n_slots", [(64, 128, 96, 16, 4), (33, 48, 80, 8, 5), (8, 256, 128, 4, 2)]
)
def test_qrlora_bgmv_matches_ref(M, K, N, r, n_slots, dtype):
    x = (jax.random.normal(KS[0], (M, K)) * 0.3).astype(dtype)
    W = (jax.random.normal(KS[1], (K, N)) * 0.1).astype(dtype)
    B = (jax.random.normal(KS[2], (K, r)) * 0.1).astype(dtype)
    A = (jax.random.normal(KS[3], (r, N)) * 0.1).astype(dtype)
    tab = jax.random.normal(KS[4], (n_slots, r), jnp.float32).at[0].set(0.0)
    seg = jax.random.randint(KS[5], (M,), 0, n_slots)
    y = ops.qrlora_bgmv(x, W, B, A, tab, seg, 0.7)
    yr = ref.qrlora_bgmv_ref(x, W, B, A, tab, seg, 0.7)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32), **tol)
    # slot-0 (base-model) rows are exactly the plain matmul
    base_rows = np.asarray(seg) == 0
    if base_rows.any():
        np.testing.assert_allclose(
            np.asarray(y, np.float32)[base_rows],
            np.asarray(x @ W, np.float32)[base_rows],
            **tol,
        )


def test_qrlora_bgmv_per_sequence_ids():
    Bb, S, K, N, r = 4, 6, 48, 32, 8
    x = jax.random.normal(KS[0], (Bb, S, K)) * 0.3
    W = jax.random.normal(KS[1], (K, N)) * 0.1
    B = jax.random.normal(KS[2], (K, r)) * 0.1
    A = jax.random.normal(KS[3], (r, N)) * 0.1
    tab = jax.random.normal(KS[4], (3, r), jnp.float32).at[0].set(0.0)
    seq = jnp.asarray([0, 2, 1, 2])
    y = ops.qrlora_bgmv(x, W, B, A, tab, seq)
    yr = ref.qrlora_bgmv_ref(
        x.reshape(-1, K), W, B, A, tab, jnp.repeat(seq, S)
    ).reshape(Bb, S, N)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# engine end-to-end: mixed batch vs merged-weight per-tenant decodes
# ---------------------------------------------------------------------------


def test_engine_mixed_batch_matches_merged_reference():
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig.oracle_dense(n_lanes=2, n_slots=4, max_len=40, collect_logits=True),
    )
    rng = np.random.default_rng(3)
    lams = {BASE_TENANT: base_lambda(eng.params)}
    for i in (1, 2):
        t = f"t{i}"
        lams[t] = random_lambda(jax.random.PRNGKey(i), eng.params, scale=0.3)
        eng.add_tenant(t, lams[t])

    # 4 requests over 2 lanes: lanes are reused mid-stream (continuous
    # batching) with heterogeneous prompt and generation lengths
    specs = [(BASE_TENANT, 6, 4), ("t1", 9, 5), ("t2", 7, 3), ("t1", 5, 4)]
    reqs = {}
    for t, P, G in specs:
        prompt = rng.integers(2, cfg.vocab_size, size=P).astype(np.int32)
        r = eng.submit(t, prompt, G)
        reqs[r.uid] = (t, prompt, G)

    done = eng.run()
    assert len(done) == len(specs)
    for uid, req in done.items():
        t, prompt, G = reqs[uid]
        ref_toks, ref_logits = reference_decode(cfg, eng.params, lams[t], prompt, G, 40)
        assert req.tokens == ref_toks, f"uid={uid} tenant={t}"
        np.testing.assert_allclose(
            np.stack(req.logits), ref_logits, atol=1e-4, rtol=1e-4
        )


def test_engine_queued_tenant_survives_registration_pressure():
    """submit() pins its tenant, so registering new tenants while the
    request is still queued must evict someone else (or refuse)."""
    cfg = get_reduced("smollm-135m")
    eng = MultiTenantEngine(
        cfg, EngineConfig(n_lanes=1, n_slots=3, max_len=24, block_size=8)
    )  # 2 usable slots; auto layout → paged
    eng.add_tenant("t1", random_lambda(jax.random.PRNGKey(1), eng.params, 0.1))
    eng.submit("t1", np.arange(2, 6), 2)  # queued, pins t1
    eng.add_tenant("t2", random_lambda(jax.random.PRNGKey(2), eng.params, 0.1))
    eng.add_tenant("t3", random_lambda(jax.random.PRNGKey(3), eng.params, 0.1))
    assert "t1" in eng.lam_store and "t2" not in eng.lam_store  # t2 was LRU
    done = eng.run()
    assert len(done) == 1 and len(next(iter(done.values())).tokens) == 2


def test_engine_rejects_unknown_tenant_and_overflow():
    cfg = get_reduced("smollm-135m")
    eng = MultiTenantEngine(cfg, EngineConfig(n_lanes=1, n_slots=2, max_len=16))
    with pytest.raises(KeyError):
        eng.submit("ghost", np.arange(4), 4)
    with pytest.raises(ValueError):
        eng.submit(BASE_TENANT, np.arange(10), 10)  # 20 > max_len


# ---------------------------------------------------------------------------
# LaneState families: xlstm-only and jamba hybrid batches through the same
# engine, verified against per-request merged-weight single-stream oracles
# ---------------------------------------------------------------------------


def _run_family_engine(arch, specs, **config_kw):
    cfg = get_reduced(arch).replace(dtype="float32")
    config_kw.setdefault("layout", "oracle_dense")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            n_lanes=2, n_slots=4, max_len=48, collect_logits=True, **config_kw
        ),
    )
    lams = {BASE_TENANT: base_lambda(eng.params)}
    for i in (1, 2):
        t = f"t{i}"
        lams[t] = random_lambda(jax.random.PRNGKey(i), eng.params, scale=0.3)
        eng.add_tenant(t, lams[t])
    rng = np.random.default_rng(3)
    reqs = {}
    for t, P, G in specs:
        prompt = rng.integers(2, cfg.vocab_size, size=P).astype(np.int32)
        r = eng.submit(t, prompt, G)
        reqs[r.uid] = (t, prompt, G)
    done = eng.run()
    assert done.keys() == reqs.keys()
    return cfg, eng, lams, reqs, done


# mixed prompt lengths across buckets (8 and 16) with mid-stream lane reuse
FAMILY_SPECS = [(BASE_TENANT, 6, 4), ("t1", 9, 5), ("t2", 7, 3), ("t1", 13, 4)]


@pytest.mark.parametrize(
    "arch,kw",
    [
        ("xlstm_125m", {}),                                    # ssm: no KV at all
        ("jamba_1_5_large_398b", {}),                          # hybrid, dense lanes
        ("jamba_1_5_large_398b", dict(layout="paged", block_size=8)),  # hybrid, paged
    ],
    ids=["xlstm", "hybrid-dense", "hybrid-paged"],
)
def test_engine_recurrent_families_match_merged_reference(arch, kw):
    """The acceptance bar of the LaneState refactor: xlstm and jamba
    tenants admit, decode, and retire in the shared batch with outputs
    identical to merged-weight single-stream references — including the
    hybrid's paged attention KV riding next to dense Mamba state."""
    cfg, eng, lams, reqs, done = _run_family_engine(arch, FAMILY_SPECS, **kw)
    for uid, req in done.items():
        t, prompt, G = reqs[uid]
        ref_toks, ref_logits = reference_decode(cfg, eng.params, lams[t], prompt, G, 48)
        assert req.tokens == ref_toks, f"uid={uid} tenant={t}"
        np.testing.assert_allclose(
            np.stack(req.logits), ref_logits, atol=1e-4, rtol=1e-4
        )
    if kw.get("layout") == "paged":
        assert eng.allocator.n_free == eng.allocator.capacity, "blocks leaked"


def test_engine_hybrid_paged_bit_identical_to_dense():
    """Paging the hybrid's attention layers is a layout change only: tokens
    and logits must match the dense hybrid engine bit-for-bit."""
    _, _, _, dense_reqs, dense_done = _run_family_engine(
        "jamba_1_5_large_398b", FAMILY_SPECS
    )
    _, eng, _, paged_reqs, paged_done = _run_family_engine(
        "jamba_1_5_large_398b", FAMILY_SPECS, layout="paged", block_size=8
    )
    for uid in dense_done:
        assert dense_done[uid].tokens == paged_done[uid].tokens, f"uid={uid}"
        np.testing.assert_array_equal(
            np.stack(dense_done[uid].logits), np.stack(paged_done[uid].logits)
        )


def test_engine_hybrid_paged_preemption_recovers():
    """Pool pressure on a hybrid engine preempts the youngest lane (blocks
    freed, Mamba lane state reset) and re-derives its output exactly."""
    cfg = get_reduced("jamba_1_5_large_398b").replace(dtype="float32")

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
    eng, a, b = run(n_blocks=1 + 5)  # collide crossing position 16
    assert eng.preemptions >= 1 and b.preemptions >= 1 and a.preemptions == 0
    for got, want in ((a, a_big), (b, b_big)):
        assert got.tokens == want.tokens
        np.testing.assert_array_equal(np.stack(got.logits), np.stack(want.logits))


def test_engine_family_gates():
    with pytest.raises(NotImplementedError):  # vlm: per-lane image embeds
        MultiTenantEngine(
            get_reduced("llama_3_2_vision_11b"), EngineConfig(n_lanes=1, n_slots=2)
        )
    with pytest.raises(ValueError, match="has none"):  # ssm has no KV to page
        MultiTenantEngine(
            get_reduced("xlstm_125m"),
            EngineConfig(layout="paged", n_lanes=1, n_slots=2),
        )
    with pytest.raises(ValueError, match="dense layout"):  # quantum needs dense
        EngineConfig(layout="paged", n_lanes=1, n_slots=2, quantum=2)


# ---------------------------------------------------------------------------
# quantum time-slicing: snapshot preemption → exact restore (recurrent lane)
# ---------------------------------------------------------------------------


def test_engine_quantum_round_robin_is_bit_identical():
    """A recurrent (xlstm) lane preempted by the time-slice snapshots its
    LaneState and restores it on re-admission: every request's tokens and
    logits match the un-sliced engine bit-for-bit (extract/restore
    round-trip determinism — the O(1)-state preemption path)."""
    cfg = get_reduced("xlstm_125m").replace(dtype="float32")

    def run(quantum):
        eng = MultiTenantEngine(
            cfg,
            EngineConfig(
                n_lanes=1, n_slots=3, max_len=48, collect_logits=True,
                quantum=quantum,
            ),
        )
        eng.add_tenant("t1", random_lambda(jax.random.PRNGKey(1), eng.params, 0.3))
        rng = np.random.default_rng(0)
        subs = [
            eng.submit(BASE_TENANT, rng.integers(2, cfg.vocab_size, size=7).astype(np.int32), 9),
            eng.submit("t1", rng.integers(2, cfg.vocab_size, size=5).astype(np.int32), 9),
        ]
        eng.run()
        return eng, subs

    eng_plain, plain = run(quantum=None)
    eng_q, sliced = run(quantum=3)
    assert eng_q.slice_preemptions >= 2, "quantum never fired"
    assert eng_plain.slice_preemptions == 0
    for rp, rq in zip(plain, sliced):
        assert rq.preemptions >= 1
        assert rp.tokens == rq.tokens
        np.testing.assert_array_equal(np.stack(rp.logits), np.stack(rq.logits))


# ---------------------------------------------------------------------------
# streaming token events
# ---------------------------------------------------------------------------


def test_engine_stream_yields_every_token_in_decode_order():
    cfg = get_reduced("smollm-135m").replace(dtype="float32")

    def build():
        eng = MultiTenantEngine(cfg, EngineConfig(n_lanes=2, n_slots=3, max_len=32))
        eng.add_tenant("t1", random_lambda(jax.random.PRNGKey(1), eng.params, 0.2))
        rng = np.random.default_rng(7)
        subs = []
        for t, P, G in [(BASE_TENANT, 5, 4), ("t1", 8, 3), ("t1", 4, 5)]:
            subs.append(eng.submit(t, rng.integers(2, cfg.vocab_size, size=P).astype(np.int32), G))
        return eng, subs

    eng_run, subs_run = build()
    eng_stream, subs_stream = build()
    events = list(eng_stream.stream())
    # stream == run, token for token
    eng_run.run()
    per_uid = {}
    for ev in events:
        assert ev.index == len(per_uid.setdefault(ev.uid, [])), "events out of order"
        per_uid[ev.uid].append(ev.token)
        assert ev.tenant == subs_stream[ev.uid].tenant
    for r_run, r_stream in zip(subs_run, subs_stream):
        assert per_uid[r_stream.uid] == r_run.tokens
    # exactly one terminal event per request, carrying its final token
    finals = [ev for ev in events if ev.done]
    assert sorted(ev.uid for ev in finals) == sorted(r.uid for r in subs_stream)
    for ev in finals:
        assert ev.token == subs_stream[ev.uid].tokens[-1]
    # and events arrive before retirement would have reported them: the
    # first event lands on the very first step, not after any drain
    assert events[0].index == 0


def test_engine_stream_is_exactly_once_under_preemption():
    """A block-pressure-preempted request re-derives its cleared tokens;
    stream() must not deliver the already-surfaced indexes twice."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=2, n_slots=2, max_len=32, block_size=8,
            n_blocks=1 + 5,  # two 3-block requests collide crossing position 16
        ),
    )
    a = eng.submit(BASE_TENANT, np.arange(2, 10, dtype=np.int32), 16)
    b = eng.submit(BASE_TENANT, np.arange(12, 20, dtype=np.int32), 16)
    events = list(eng.stream())
    assert eng.preemptions >= 1 and b.preemptions >= 1
    per_uid = {}
    for ev in events:
        assert ev.index == len(per_uid.setdefault(ev.uid, [])), (
            f"uid={ev.uid} duplicated or skipped index {ev.index}"
        )
        per_uid[ev.uid].append(ev.token)
    assert per_uid[a.uid] == a.tokens and per_uid[b.uid] == b.tokens


def test_engine_quantum_preempts_at_most_one_lane_per_waiter():
    """One waiting request must not churn the whole batch: only the most
    overdue lane is snapshot-preempted, the rest keep decoding."""
    cfg = get_reduced("xlstm_125m").replace(dtype="float32")
    eng = MultiTenantEngine(
        cfg, EngineConfig(n_lanes=2, n_slots=2, max_len=32, quantum=2)
    )
    rng = np.random.default_rng(1)
    for _ in range(3):  # 2 lanes + 1 waiter
        eng.submit(BASE_TENANT, rng.integers(2, cfg.vocab_size, size=5).astype(np.int32), 8)
    # run until the first quantum expiry fires
    while eng.slice_preemptions == 0 and eng.scheduler.has_work:
        before_active = len(eng.scheduler.active())
        eng.step()
    assert eng.slice_preemptions == 1, "both lanes churned for one waiter"
    assert before_active == 2
    eng.run()


# ---------------------------------------------------------------------------
# serve_multi's verification rule against the merged-weight reference
# ---------------------------------------------------------------------------


def _ref_rows(margins):
    """Reference logits whose top-2 margin at position i is margins[i]
    (token 1 leads token 0)."""
    rows = np.zeros((len(margins), 4), np.float32)
    rows[:, 1] = 1.0
    rows[:, 0] = 1.0 - np.asarray(margins, np.float32)
    return rows


@pytest.mark.parametrize("case,ok", [
    ("identical", True),
    ("logit_gap_over_tol", False),
    ("split_at_near_tie", True),
    ("split_at_margin_over_tol", False),
    ("short_generation", False),
])
def test_compare_to_reference_rule(case, ok):
    """Logits within tol through the first split; a split only where the
    reference's top-2 margin is under tol; a short output never passes."""
    from repro.launch.serve_multi import compare_to_reference

    tol = 0.25
    ref = _ref_rows([1.0, 0.3, 0.2, 1.0])
    ref_toks = [1, 1, 1, 1]
    logits, toks = ref.copy(), list(ref_toks)
    if case == "logit_gap_over_tol":
        logits[3, 2] += 0.3
    elif case == "split_at_near_tie":
        toks[2:] = [0, 3]  # margin 0.2 < tol; position 3 is not compared
        logits[3] = 9.0
    elif case == "split_at_margin_over_tol":
        toks[1] = 0  # margin 0.3: within 2·tol, but not a near-tie
    elif case == "short_generation":
        toks, logits = toks[:3], logits[:3]
    got, err, n, margin = compare_to_reference(toks, logits, ref_toks, ref, tol)
    assert got is ok
    if case == "split_at_near_tie":
        assert n == 3 and margin == pytest.approx(0.2) and err == 0.0
