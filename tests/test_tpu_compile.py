"""Compile the served path's adapter kernels for a described TPU v5e.

Interpret mode (every other kernel test) never checks Mosaic's tiling
rules, so a block shape the chip refuses passes there.  These tests lower
and compile the BGMV kernels through the ``ops`` wrappers — and so through
their block-size rule — for one chip of a described ``v5e:2x2`` topology,
at smollm-135m's adapted projection shapes (d_model 576; wq 576→576, wv
576→192 with 3 KV heads of 64; QR rank cap 128).  Nothing runs on a device.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

K = 576  # smollm-135m d_model
RANK = 128  # smollm-135m QR rank cap
N_SLOTS = 17  # base tenant + 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_compile(one_chip, monkeypatch):
    """Compile ``fn`` for one described v5e chip with the kernels' TPU
    branch taken (this process's backend is the CPU, so ``ops`` would pick
    interpret mode).  The persistent cache is off: a TPU executable written
    here could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)


SHAPES = [
    pytest.param(M, N, id=f"{'decode' if M == 8 else 'prefill'}-M{M}-{proj}")
    for M in (8, 512)  # decode lanes; a prefill of 512 prompt tokens
    for proj, N in (("wq", 576), ("wv", 192))
]


@pytest.mark.parametrize("M,N", SHAPES)
def test_bgmv_bf16_compiles_for_v5e(tpu_compile, M, N):
    compiled = tpu_compile(
        lambda x, W, B, A, tab, seg: ops.qrlora_bgmv(x, W, B, A, tab, seg),
        ((M, K), jnp.bfloat16), ((K, N), jnp.bfloat16),
        ((K, RANK), jnp.bfloat16), ((RANK, N), jnp.bfloat16),
        ((N_SLOTS, RANK), jnp.float32), ((M,), jnp.int32),
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "qrlora_bgmv" in text


@pytest.mark.parametrize("M,N", SHAPES)
def test_bgmv_int8_compiles_for_v5e(tpu_compile, M, N):
    compiled = tpu_compile(
        lambda x, q, ws, B, A, tab, seg: ops.qrlora_bgmv_quant(
            x, q, ws, B, A, tab, seg
        ),
        ((M, K), jnp.bfloat16), ((K, N), jnp.int8), ((N,), jnp.float32),
        ((K, RANK), jnp.bfloat16), ((RANK, N), jnp.bfloat16),
        ((N_SLOTS, RANK), jnp.float32), ((M,), jnp.int32),
    )
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "qrlora_bgmv_quant" in text


@pytest.mark.parametrize("M,N", SHAPES)
def test_qrlora_matmul_compiles_for_v5e(tpu_compile, M, N):
    """The single-tenant fused matmul shares the block-size rule."""
    compiled = tpu_compile(
        lambda x, W, B, A, lam: ops.qrlora_matmul(x, W, B, A, lam),
        ((M, K), jnp.bfloat16), ((K, N), jnp.bfloat16),
        ((K, RANK), jnp.bfloat16), ((RANK, N), jnp.bfloat16),
        ((RANK,), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "dim,cap,want",
    [(576, 512, 576), (576, 256, 576), (192, 256, 192), (1536, 256, 256),
     (1536, 512, 512), (8192, 512, 512), (384, 256, 128), (48, 256, 48)],
)
def test_lane_block_rule(dim, cap, want):
    """Largest 128-multiple divisor up to the cap, else the whole dim."""
    assert ops._lane_block(dim, cap) == want
