"""Quantized frozen base (core/quantize.py + the fused dequant kernels).

Four contracts:

* quantize→dequantize round-trip error is bounded per entry by half a
  quantization step (property-based over value scales),
* the fused dequant-in-epilogue Pallas kernels are **bit-identical** to the
  jitted XLA oracles in interpret mode at single-k-block shapes (and within
  fp32 tolerance with a split contracting dim),
* an int8-base engine's float32 decode logits stay within the documented
  ``INT8_LOGIT_EPS`` of the unquantized fp32 merged-weight oracle,
* rank-dim-sharded B/A (``shard_ba``) decodes bit-identically to the
  replicated engine on a forced 2-device CPU mesh (subprocess, same rig as
  the sharded-λ test in ``test_lam_store.py``).

The oracles must be compared **jitted**: an eager-dispatched ref rounds
some fp32 intermediates differently from the compiled expression the
interpret-mode kernel lowers to (~1-ulp), while ``jax.jit(ref)`` and the
kernel compile to the same tree (the ``optimization_barrier`` in the quant
refs pins the epilogue's multiply-then-add ordering — see ``kernels/ref.py``).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_reduced
from repro.core.quantize import (
    INT8_LOGIT_EPS,
    dequantize_weight,
    is_quantized,
    quantization_error_bound,
    quantize_base_params,
    quantize_weight,
    quantized_bytes,
    resident_base_bytes,
)
from repro.kernels import ref
from repro.kernels.qrlora_bgmv import (
    ba_gather_sharded,
    qrlora_bgmv_fused_sharded,
    qrlora_bgmv_quant_kernel,
    qrlora_bgmv_rows_kernel,
)
from repro.kernels.qrlora_matmul import qrlora_matmul_quant_kernel
from repro.serving import EngineConfig, MultiTenantEngine
from repro.serving.engine import reference_decode
from repro.serving.lam_store import random_lambda

KEY = jax.random.PRNGKey(0)
KS = jax.random.split(KEY, 8)

QUANT_DTYPES = ["int8", "fp8"]

# single k-block: K == bk, so the kernel's whole contraction happens in one
# fp32 accumulation — the same expression tree as the jitted oracle
M, K, N, R = 8, 256, 128, 16
BLK = dict(bm=8, bn=128, bk=256)


def _operands(k=KS, r=R, n_slots=4):
    x = jax.random.normal(k[0], (M, K), jnp.float32) * 0.3
    W = jax.random.normal(k[1], (K, N), jnp.float32) * 0.05
    B = jax.random.normal(k[2], (K, r), jnp.float32) * 0.05
    A = jax.random.normal(k[3], (r, N), jnp.float32) * 0.05
    lam = jax.random.normal(k[4], (r,), jnp.float32)
    tab = jax.random.normal(k[5], (n_slots, r), jnp.float32)
    tab = tab.at[0].set(0.0)  # slot 0 is the base tenant
    seg = jax.random.randint(k[6], (M,), 0, n_slots)
    return x, W, B, A, lam, tab, seg


# ---------------------------------------------------------------------------
# round-trip error bound
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 50), log_mag=st.floats(-3.0, 3.0))
@settings(max_examples=25, deadline=None)
def test_int8_round_trip_error_bounded(seed, log_mag):
    """|W − dequant(quantize(W))| ≤ scale/2 per entry: round-to-nearest on
    a symmetric per-output-channel grid never misses by more than half a
    step, independent of the weight magnitude."""
    W = jax.random.normal(
        jax.random.PRNGKey(seed), (32, 24), jnp.float32
    ) * (10.0 ** log_mag)
    qW = quantize_weight(W, "int8")
    assert qW["q"].dtype == jnp.int8 and qW["scale"].shape == (24,)
    err = jnp.abs(W - dequantize_weight(qW))
    bound = jnp.broadcast_to(quantization_error_bound(qW), W.shape)
    assert bool(jnp.all(err <= bound + 1e-12)), float(jnp.max(err - bound))


@pytest.mark.parametrize("log_mag", [-3.0, 0.0, 3.0])
def test_fp8_round_trip_error_bounded(log_mag):
    """fp8-e4m3 round-trip: ≤ 1/16 relative per entry (half the e4m3 ulp at
    3 mantissa bits, for normals after per-channel scaling to |q| ≤ 448),
    at any weight magnitude (the per-channel scale absorbs it)."""
    W = jax.random.normal(KS[7], (64, 48), jnp.float32) * (10.0 ** log_mag)
    qW = quantize_weight(W, "fp8")
    assert qW["q"].dtype == jnp.float8_e4m3fn and qW["scale"].shape == (48,)
    deq = dequantize_weight(qW)
    rel = jnp.abs(W - deq) / jnp.maximum(jnp.abs(W), 1e-6)
    # subnormal-region entries (tiny vs the channel amax) can exceed the
    # relative bound but are absolutely tiny; bound those by scale instead
    absolute_ok = jnp.abs(W - deq) <= qW["scale"][None, :]
    assert bool(jnp.all((rel <= 1.0 / 16 + 1e-6) | absolute_ok))


def test_quantize_weight_edge_cases():
    # all-zero column: scale falls back to 1, q is exactly zero
    W = jnp.zeros((8, 4), jnp.float32).at[:, 1].set(jnp.linspace(-2, 2, 8))
    qW = quantize_weight(W, "int8")
    assert float(qW["scale"][0]) == 1.0
    np.testing.assert_array_equal(np.asarray(qW["q"][:, 0]), 0)
    # amax entries map to exactly ±127 (symmetric — no zero-point)
    assert int(jnp.max(jnp.abs(qW["q"][:, 1]))) == 127
    assert is_quantized(qW) and not is_quantized(W)
    with pytest.raises(ValueError, match="not quantized"):
        quantize_weight(W, "bf16")
    # stacked-layer leading dims quantize per (layer, channel)
    Ws = jax.random.normal(KEY, (3, 8, 4), jnp.float32)
    qs = quantize_weight(Ws, "int8")
    assert qs["q"].shape == (3, 8, 4) and qs["scale"].shape == (3, 4)
    assert quantized_bytes(qs) == 3 * 8 * 4 * 1 + 3 * 4 * 4


# ---------------------------------------------------------------------------
# kernel vs jitted oracle: bit-identity in interpret mode
# ---------------------------------------------------------------------------


def _quantize_for(base_dtype, W):
    qW = quantize_weight(W, base_dtype)
    return qW["q"], qW["scale"]


@pytest.mark.parametrize("base_dtype", QUANT_DTYPES)
@pytest.mark.parametrize("scale", [1.0, 0.5])
def test_quant_matmul_kernel_bit_identical_to_jitted_oracle(base_dtype, scale):
    x, W, B, A, lam, _, _ = _operands()
    q, ws = _quantize_for(base_dtype, W)
    got = qrlora_matmul_quant_kernel(
        x, q, ws, B, A, lam, scale=scale, interpret=True, **BLK
    )
    want = jax.jit(ref.qrlora_matmul_quant_ref, static_argnames="scale")(
        x, q, ws, B, A, lam, scale=scale
    )
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want),
        err_msg=f"{base_dtype} fused matmul not bitwise vs jitted oracle",
    )


@pytest.mark.parametrize("base_dtype", QUANT_DTYPES)
def test_quant_bgmv_kernel_bit_identical_to_jitted_oracle(base_dtype):
    x, W, B, A, _, tab, seg = _operands()
    q, ws = _quantize_for(base_dtype, W)
    got = qrlora_bgmv_quant_kernel(
        x, q, ws, B, A, tab, seg[:, None], interpret=True, **BLK
    )
    want = jax.jit(ref.qrlora_bgmv_quant_ref)(x, q, ws, B, A, tab, seg)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want),
        err_msg=f"{base_dtype} fused BGMV not bitwise vs jitted oracle",
    )


def test_rows_kernel_unquantized_bit_identical_to_bgmv_oracle():
    """The pre-gathered-λ kernel with all-ones w_scale (the fused sharded
    path's bf16/f32 mode) is the plain BGMV: ×1.0 is exact."""
    x, W, B, A, _, tab, seg = _operands()
    rows = jnp.take(tab, seg, axis=0)
    ones = jnp.ones((N,), jnp.float32)
    got = qrlora_bgmv_rows_kernel(x, W, ones, B, A, rows, interpret=True, **BLK)
    want = jax.jit(ref.qrlora_bgmv_ref)(x, W, B, A, tab, seg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("base_dtype", ["bf16"] + QUANT_DTYPES)
def test_fused_sharded_bgmv_matches_oracle_on_1dev_mesh(base_dtype):
    """One shard_map dispatch (local gather + psum + rows kernel) against
    the two-step oracle.  A 1-device mesh makes the gather the identity,
    so this isolates the kernel fusion; the 2-device case rides in the
    subprocess test below."""
    from jax.sharding import Mesh

    x, W, B, A, _, tab, seg = _operands()
    if base_dtype == "bf16":
        q, ws = W, None
        want = jax.jit(ref.qrlora_bgmv_ref)(x, W, B, A, tab, seg)
    else:
        q, ws = _quantize_for(base_dtype, W)
        want = jax.jit(ref.qrlora_bgmv_quant_ref)(x, q, ws, B, A, tab, seg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    got = qrlora_bgmv_fused_sharded(
        x, q, B, A, tab, seg, mesh=mesh, axis="model", w_scale=ws,
        interpret=True, **BLK,
    )
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(want),
        err_msg=f"fused sharded BGMV ({base_dtype}) not bitwise vs oracle",
    )


def test_quant_matmul_kernel_multi_k_block_close():
    """With the contracting dim split over k-blocks the kernel's staged
    fp32 accumulation reassociates the sum — tolerance, not bit-identity."""
    x, W, B, A, lam, _, _ = _operands()
    q, ws = _quantize_for("int8", W)
    got = qrlora_matmul_quant_kernel(
        x, q, ws, B, A, lam, interpret=True, bm=8, bn=128, bk=64
    )
    want = ref.qrlora_matmul_quant_ref(x, q, ws, B, A, lam)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


def test_ba_gather_sharded_1dev_is_identity():
    from jax.sharding import Mesh

    _, _, B, A, _, _, _ = _operands()
    mesh = Mesh(np.array(jax.devices()[:1]), ("model",))
    B_, A_ = ba_gather_sharded(B, A, mesh=mesh, axis="model")
    np.testing.assert_array_equal(np.asarray(B_), np.asarray(B))
    np.testing.assert_array_equal(np.asarray(A_), np.asarray(A))


# ---------------------------------------------------------------------------
# params-tree quantization
# ---------------------------------------------------------------------------


def test_quantize_base_params_targets_only_adapted_projections():
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(cfg, EngineConfig(n_lanes=1, n_slots=2, max_len=16))
    qp = quantize_base_params(eng.params, "int8")
    attn = qp["groups"]["attn"]
    targets = set(cfg.adapter.targets)
    for proj in ("wq", "wk", "wv", "wo"):
        if proj in attn:
            assert is_quantized(attn[proj]) == (proj in targets), proj
    # untouched structure: adapters, norms, embed stay plain arrays
    assert not is_quantized(qp["groups"]["adapters"]["attn"]["wq"]["B"])
    assert isinstance(qp["embed"], jax.Array)
    # idempotent (the engine applies the knob unconditionally)
    qp2 = quantize_base_params(qp, "int8")
    assert qp2["groups"]["attn"]["wq"]["q"] is qp["groups"]["attn"]["wq"]["q"]
    # bf16 knob is the identity
    assert quantize_base_params(eng.params, "bf16") is eng.params
    qb, fb = resident_base_bytes(qp)
    assert 0 < qb < fb, (qb, fb)


# ---------------------------------------------------------------------------
# end-to-end ε: int8 engine vs the unquantized fp32 oracle
# ---------------------------------------------------------------------------


def test_int8_engine_logits_within_documented_eps():
    """Acceptance: the int8-base float32 engine decodes the same tokens as
    the quantized merged-weight reference, and its logits stay within
    ``INT8_LOGIT_EPS`` of the **unquantized** fp32 oracle at every
    matched-context position — the documented end-to-end quantization ε.

    ε is only meaningful while both sides consumed the same tokens: the
    reduced config's weights are random, so greedy argmax sits on
    near-ties that a 1e-2 logit perturbation can legitimately flip, after
    which the trajectories compare different contexts.  Position 0 (the
    shared prompt) is always comparable; later positions while the token
    prefixes agree."""
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    # one pristine params tree for both sides — the oracle must see the
    # very weights the int8 engine quantized, not a same-shape re-init
    src = MultiTenantEngine(cfg, EngineConfig(n_lanes=1, n_slots=2, max_len=32))
    pristine = src.params
    eng = MultiTenantEngine(
        cfg,
        EngineConfig(
            n_lanes=2, n_slots=4, max_len=32, collect_logits=True,
            base_dtype="int8",
        ),
        params=pristine,
    )
    assert eng.base_dtype == "int8"
    assert is_quantized(eng.params["groups"]["attn"]["wq"])
    lam = random_lambda(jax.random.PRNGKey(1), eng.params, 0.3)
    eng.add_tenant("t1", lam)
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, cfg.vocab_size, size=9).astype(np.int32)
    gen = 5
    req = eng.submit("t1", prompt, gen)
    eng.run()

    got = np.stack(req.logits)
    toks_fp32, fp32_logits = reference_decode(cfg, pristine, lam, prompt, gen, 32)
    lcp = 0
    while lcp < gen and req.tokens[lcp] == toks_fp32[lcp]:
        lcp += 1
    n_cmp = min(lcp + 1, gen)  # position i's context is tokens[:i]
    eps = float(np.max(np.abs(got[:n_cmp] - fp32_logits[:n_cmp])))
    assert eps < INT8_LOGIT_EPS, (
        f"int8 engine drifted {eps:.4f} from the fp32 oracle over the "
        f"{n_cmp} matched-context positions (documented bound "
        f"{INT8_LOGIT_EPS})"
    )
    # tokens match the *quantized* merged reference exactly (serve_multi
    # --verify path): quantization error is shared, decode path is not
    toks_q, q_logits = reference_decode(cfg, eng.params, lam, prompt, gen, 32)
    assert req.tokens == toks_q, (req.tokens, toks_q)
    assert float(np.max(np.abs(got - q_logits))) < 0.05


# ---------------------------------------------------------------------------
# sharded B/A: bit-identical to replicated on a 2-device CPU mesh
# ---------------------------------------------------------------------------

_SHARD_BA_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, numpy as np
    from repro.configs import get_reduced
    from repro.serving import BASE_TENANT, EngineConfig, MultiTenantEngine, random_lambda

    cfg = get_reduced("smollm-135m").replace(dtype="float32")

    def run(**kw):
        eng = MultiTenantEngine(cfg, EngineConfig(n_lanes=2, n_slots=4, max_len=32,
                                                  collect_logits=True, **kw))
        for i in (1, 2):
            eng.add_tenant(f"t{i}", random_lambda(jax.random.PRNGKey(i), eng.params, 0.3))
        rng = np.random.default_rng(3)
        subs = []
        for t, P, G in [(BASE_TENANT, 6, 4), ("t1", 9, 5), ("t2", 7, 3)]:
            subs.append(eng.submit(t, rng.integers(2, cfg.vocab_size, size=P).astype(np.int32), G))
        eng.run()
        return eng, subs

    eng_r, subs_r = run()
    eng_s, subs_s = run(shard_ba=True)
    B = eng_s.params["groups"]["adapters"]["attn"]["wq"]["B"]
    A = eng_s.params["groups"]["adapters"]["attn"]["wq"]["A"]
    assert len(jax.devices()) == 2, jax.devices()
    for arr, dim in ((B, B.ndim - 1), (A, A.ndim - 2)):
        shards = arr.addressable_shards
        assert len(shards) == 2 and shards[0].data.shape[dim] == arr.shape[dim] // 2, (
            "QR factor not sharded over the rank dim: "
            f"{[s.data.shape for s in shards]} vs global {arr.shape}")
    for rr, rs in zip(subs_r, subs_s):
        assert rr.tokens == rs.tokens, (rr.tokens, rs.tokens)
        assert np.array_equal(np.stack(rr.logits), np.stack(rs.logits)), (
            "shard_ba decode logits not bit-identical to replicated")
    # combined with sharded lam tables: still bitwise
    eng_b, subs_b = run(shard_ba=True, shard_lam=True)
    for rr, rb in zip(subs_r, subs_b):
        assert rr.tokens == rb.tokens and np.array_equal(
            np.stack(rr.logits), np.stack(rb.logits))
    print("SHARDED_BA_BIT_IDENTICAL_OK")
    """
)


def test_sharded_ba_decode_bit_identical_2dev():
    """Acceptance: on a 2-device CPU mesh, the engine with rank-dim-sharded
    QR factors (``shard_ba``, each device holding r/2 columns of B and rows
    of A) decodes bit-identically to the replicated engine — the tiled
    all_gather is an exact reconstruction, not an approximation.  Also
    covers shard_ba+shard_lam together.  Subprocess because the
    device-count flag must be set before jax initializes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run(
        [sys.executable, "-c", _SHARD_BA_SCRIPT],
        capture_output=True,
        text=True,
        timeout=900,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "SHARDED_BA_BIT_IDENTICAL_OK" in r.stdout, (
        r.stdout[-3000:] + r.stderr[-3000:]
    )
