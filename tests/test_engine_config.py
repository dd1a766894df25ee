"""EngineConfig: construction-time validation, per-family layout
resolution, presets, the legacy-kwarg bridge, and the engine's
once-per-process deprecation shim (kwargs construction, ``.registry``)."""
import warnings

import pytest

from repro.configs import get_reduced
from repro.serving import EngineConfig, MultiTenantEngine
from repro.serving.engine import _reset_deprecation_warnings


# ---------------------------------------------------------------------------
# validation
# ---------------------------------------------------------------------------


def test_config_defaults_are_auto_layout():
    cfg = EngineConfig()
    assert cfg.layout == "auto" and cfg.prefill_chunk is None
    # auto resolves paged for attention families, dense for recurrent
    assert cfg.resolved_layout("dense") == "paged"
    assert cfg.resolved_layout("moe") == "paged"
    assert cfg.resolved_layout("ssm") == "oracle_dense"


@pytest.mark.parametrize(
    "kw",
    [
        dict(layout="dense"),  # not a layout name
        dict(n_lanes=0),
        dict(n_slots=0),
        dict(max_len=0),
        dict(block_size=0),
        dict(watermark=-1),
        dict(cold_slots=-1),
        dict(quantum=0),
        dict(layout="paged", quantum=2),  # snapshots need dense lanes
        dict(layout="oracle_dense", prefill_chunk=16),  # chunks need blocks
        dict(layout="paged", prefill_chunk=24),  # not a block multiple
        dict(layout="paged", prefill_chunk=8),  # below one block
        dict(layout="oracle_dense", share_prefix=True),
        dict(layout="oracle_dense", watermark=1),
        dict(speculate_k=-1),
        dict(draft_lam_rank=4),  # a drafter needs speculate_k >= 1
        dict(speculate_k=2, draft_lam_rank=0),
        dict(layout="paged", speculate_k=2, prefill_chunk=16),  # verify vs chunk
        dict(base_dtype="int4"),  # not a base dtype
        dict(base_dtype="float16"),
    ],
    ids=lambda kw: ",".join(f"{k}={v}" for k, v in kw.items()),
)
def test_config_rejects_incoherent_combinations(kw):
    with pytest.raises(ValueError):
        EngineConfig(**kw)


def test_config_base_dtype_validation():
    # every quantized-base knob constructs (the installed jax has fp8-e4m3)
    assert EngineConfig(base_dtype="int8").base_dtype == "int8"
    assert EngineConfig(base_dtype="bf16").base_dtype == "bf16"
    assert EngineConfig(base_dtype="fp8").base_dtype == "fp8"


def test_config_layout_resolution_gates_and_quantum():
    with pytest.raises(ValueError, match="has none"):
        EngineConfig(layout="paged").resolved_layout("ssm")
    # quantum only bends auto (to dense); explicit dense is untouched
    assert EngineConfig(quantum=2).resolved_layout("dense") == "oracle_dense"
    assert EngineConfig.oracle_dense(quantum=2).resolved_layout("dense") == (
        "oracle_dense"
    )


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------


def test_serving_preset_is_the_production_posture():
    cfg = EngineConfig.serving()
    assert cfg.layout == "paged" and cfg.share_prefix and cfg.watermark == 1
    assert cfg.prefill_chunk == 2 * cfg.block_size
    # the chunk budget tracks a block_size override unless pinned explicitly
    assert EngineConfig.serving(block_size=8).prefill_chunk == 16
    assert EngineConfig.serving(prefill_chunk=64).prefill_chunk == 64


def test_oracle_dense_preset_accepts_overrides():
    cfg = EngineConfig.oracle_dense(n_lanes=2, quantum=3)
    assert cfg.layout == "oracle_dense" and cfg.quantum == 3
    assert not cfg.share_prefix and cfg.prefill_chunk is None


# ---------------------------------------------------------------------------
# legacy bridge
# ---------------------------------------------------------------------------


def test_from_legacy_kwargs_round_trip():
    # the old default paged=False maps onto the oracle layout
    assert EngineConfig.from_legacy_kwargs() == EngineConfig.oracle_dense()
    got = EngineConfig.from_legacy_kwargs(
        n_lanes=2, n_slots=3, max_len=32, paged=True, block_size=8,
        share_prefix=True, watermark=1,
    )
    want = EngineConfig(
        layout="paged", n_lanes=2, n_slots=3, max_len=32, block_size=8,
        share_prefix=True, watermark=1,
    )
    assert got == want
    with pytest.raises(TypeError, match="unknown engine kwargs"):
        EngineConfig.from_legacy_kwargs(paged=True, blocksize=8)


def test_engine_legacy_kwargs_warn_once_and_match_config_engine():
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    _reset_deprecation_warnings()
    with pytest.warns(DeprecationWarning, match="repro.serving deprecation"):
        legacy = MultiTenantEngine(cfg, n_lanes=1, n_slots=2, max_len=16)
    # once per process: the second legacy construction is silent
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        MultiTenantEngine(cfg, n_lanes=1, n_slots=2, max_len=16)
    assert not caught
    # the shim builds the very config a migrated call site would pass
    assert legacy.config == EngineConfig.oracle_dense(
        n_lanes=1, n_slots=2, max_len=16
    )
    assert legacy.layout == "oracle_dense" and not legacy.paged


def test_engine_rejects_config_plus_legacy_kwargs():
    cfg = get_reduced("smollm-135m")
    with pytest.raises(TypeError, match="not both"):
        MultiTenantEngine(cfg, EngineConfig(), n_lanes=2)


def test_engine_registry_property_is_deprecated_alias():
    cfg = get_reduced("smollm-135m").replace(dtype="float32")
    eng = MultiTenantEngine(cfg, EngineConfig(n_lanes=1, n_slots=2, max_len=16))
    _reset_deprecation_warnings()
    with pytest.warns(DeprecationWarning, match="lam_store"):
        reg = eng.registry
    assert reg is eng.lam_store
