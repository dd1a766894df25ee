"""Compile the served path's adapter kernels for a described TPU v5e.

Interpret mode (every other kernel test) never checks Mosaic's tiling
rules, so a block shape the chip refuses passes there.  These tests lower
and compile the BGMV kernels through the ``ops`` wrappers — and so through
their block-size rule — for one chip of a described ``v5e:2x2`` topology,
at smollm-135m's adapted projection shapes (d_model 576; wq 576→576, wv
576→192 with 3 KV heads of 64; QR rank cap 128).  It also compiles the
serving engine's own decode, chunked-prefill and KV pool programs at
smollm-135m widths and checks that they keep the paged KV pool in place.
Nothing runs on a device.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.model_zoo import build_model
from repro.serving import EngineConfig, MultiTenantEngine

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
def tpu_branch(monkeypatch):
    """The kernels' TPU branch taken (this process's backend is the CPU, so
    ``ops`` would pick interpret mode, and the model step the XLA ``take``
    in place of the BGMV kernel), and the persistent cache off: a TPU
    executable written here could not be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def tpu_compile(one_chip, tpu_branch):
    """Compile ``fn`` for one described v5e chip."""

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
        return jax.jit(fn).lower(*args).compile()

    return compile_


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


# ---------------------------------------------------------------------------
# the serving engine's model-step programs write the paged pool in place
# ---------------------------------------------------------------------------

LANES = 64  # the benchmark cell's decode lanes
# Two pool sizes: whatever a program holds beyond the pool it aliases must
# not grow with the pool.
POOL_BLOCKS = (4_096, 8_192)
CHUNK = 256  # the cell's prefill chunk; a 1,024-token prompt's bucket
PROGRAMS = {  # case → (engine program, decode attend bucket, decode rows)
    "serve_decode": ("_decode_jit", 8, LANES),
    "serve_decode_attend128": ("_decode_jit", 128, LANES),  # 2,048 positions a lane
    # the lane bucket of a step with at most 8 of the 64 lanes decoding
    "serve_decode_rows8": ("_decode_jit", 8, 8),
    "serve_decode_rows8_attend128": ("_decode_jit", 128, 8),
    "serve_prefill_chunk": ("_prefill_chunk", None, None),
    "serve_append_block": ("_append_block", None, None),
    "serve_reset_lane": ("_reset", None, None),
}


@pytest.fixture(scope="module")
def smollm_engine():
    """A paged engine at smollm-135m widths with a tiny pool: its jits are
    what the chip runs; the test widens the pool in the shapes it lowers
    with.  Zero weights: only shapes matter to the compiler."""
    cfg = get_config("smollm-135m")
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), build_model(cfg).dryrun_params()
    )
    return MultiTenantEngine(
        cfg,
        EngineConfig(
            layout="paged", n_lanes=LANES, n_slots=9, max_len=2048,
            block_size=16, n_blocks=8, prefill_chunk=CHUNK,
        ),
        params=params,
    )


def _program_args(eng, case, n_blocks, one_chip):
    """The engine program of ``case`` and its argument shapes, with the
    pool widened to ``n_blocks``; the cache is the argument it donates."""
    spec = lambda t, shape=None: jax.ShapeDtypeStruct(  # noqa: E731
        t.shape if shape is None else shape, t.dtype, sharding=one_chip
    )
    cache = jax.tree_util.tree_map(spec, eng.cache)
    attn = cache["layers"]["attn"]
    for pool in ("k", "v"):
        t = attn[pool]  # (G, n_blocks, …)
        attn[pool] = spec(t, (t.shape[0], n_blocks, *t.shape[2:]))
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip
    )
    name, attend, rows = PROGRAMS[case]
    fn = getattr(eng, name)
    if name == "_append_block":
        return fn, cache, (cache, i32(), i32(), i32())
    if name == "_reset":
        return fn, cache, (cache, i32())
    view = jax.tree_util.tree_map(spec, eng._params_view())
    if name == "_decode_jit":
        lanes = None if rows == LANES else i32(rows)
        return fn, cache, (view, cache, i32(rows, 1), i32(rows), lanes, attend)
    bs = eng.block_size
    bucket = 4 * CHUNK
    return fn, cache, (
        view, cache, i32(1, CHUNK), i32(1), i32(1), i32(),
        i32(-(-(bucket + CHUNK) // bs)), i32(bucket // bs),
    )


def _nbytes(tree):
    return sum(
        int(np.prod(t.shape)) * t.dtype.itemsize for t in jax.tree_util.tree_leaves(tree)
    )


@pytest.fixture(scope="module")
def compiled_program(smollm_engine, one_chip):
    """A case's program compiled for the described v5e at a pool size, with
    the cache's shapes; each compiled once."""
    seen = {}

    def compiled(case, n_blocks):
        if (case, n_blocks) not in seen:
            fn, cache, args = _program_args(smollm_engine, case, n_blocks, one_chip)
            seen[case, n_blocks] = fn.lower(*args).compile(), cache
        return seen[case, n_blocks]

    return compiled


@pytest.mark.parametrize("case", list(PROGRAMS))
def test_serve_program_writes_kv_pool_in_place_for_v5e(
    compiled_program, tpu_branch, case
):
    """The engine's programs keep one donated pool: compiled for a
    described v5e, each aliases both pools to its output, and neither its
    temporaries nor what it returns beside the cache grow with the pool —
    no per-layer slice, relayout copy or stacked copy-back of the pool, in
    the model step (the layer scan carries the pools; a step over a few
    lanes gathers their table rows, not the pool) or in the KV pool
    programs (they pass the pools through)."""
    beside = []
    for n_blocks in POOL_BLOCKS:
        compiled, cache = compiled_program(case, n_blocks)
        memory = compiled.memory_analysis()
        pools = _nbytes([cache["layers"]["attn"][p] for p in ("k", "v")])
        assert memory.alias_size_in_bytes >= pools, n_blocks
        if PROGRAMS[case][0] == "_decode_jit":  # the adapters stay on the kernel
            assert "qrlora_bgmv" in compiled.as_text()
        beside.append((
            memory.temp_size_in_bytes, memory.output_size_in_bytes - _nbytes(cache),
        ))
    assert beside[0] == beside[1], beside


def test_serve_decode_temporaries_scale_with_its_rows_for_v5e(
    compiled_program, tpu_branch
):
    """Widening the attend from 8 to 128 blocks adds at most a quarter as
    many temporaries to a decode step over 8 rows as to one over all 64
    lanes: the attend's gather and its relayout are built for the rows the
    step runs.  (Every decode step also holds a fixed 62.9 MB temporary,
    a relaid-out copy of the embedding table, whose space its other
    temporaries may reuse: so the growth is compared, not the totals.)"""
    def growth(lo, hi):
        def temp(case):
            compiled, _ = compiled_program(case, POOL_BLOCKS[0])
            return compiled.memory_analysis().temp_size_in_bytes

        return temp(hi) - temp(lo)

    few = growth("serve_decode_rows8", "serve_decode_rows8_attend128")
    every = growth("serve_decode", "serve_decode_attend128")
    assert 4 * few <= every, (few, every)
